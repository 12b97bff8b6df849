"""A decoded split carries its fixed-width columns in the device layout
(sql/sources.py _attach_prepared) and ``upload.build`` ships them as they
are (columnar/batch.py _build_host_columns). The reference in every case
is today's build of the same frame: ``_pandas_to_numpy`` +
``DeviceColumn.build_host_buffers``, byte for byte. Whatever has no
prepared form — nulls, booleans, strings, a cut split, a frame that never
saw a decode worker — takes that build itself."""

import copy

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.orc as paorc
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, PreparedColumns, Schema, _build_host_columns,
    _pandas_to_numpy, bucket_capacity,
)
from spark_rapids_tpu.columnar.column import DeviceColumn, shared_validity
from spark_rapids_tpu.columnar.dtype import from_arrow
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.scan_pipeline import _nbytes
from spark_rapids_tpu.sql.sources import (
    _arrow_decode, _attach_dict_hints, _attach_prepared,
)

pytestmark = pytest.mark.smoke

_FIXED = "scan.upload.fixedColumns"
_SHIPPED = "scan.upload.shippedColumns"
_INT64_MIN = np.iinfo(np.int64).min


def _counts():
    return (REGISTRY.value(_FIXED), REGISTRY.value(_SHIPPED))


def _decoded(table: pa.Table) -> pd.DataFrame:
    """A frame as the pipelined reader's decode worker hands it on."""
    df = _arrow_decode(table, True)
    return _attach_prepared(_attach_dict_hints(df, table), table)


def _schema(table: pa.Table) -> Schema:
    return Schema(table.column_names,
                  [from_arrow(f.type) for f in table.schema])


def _build(df, schema, prepared):
    n = len(df)
    *bufs, counts = _build_host_columns(
        df, schema, n, bucket_capacity(n), True, {}, False, 0, prepared)
    return (*bufs, counts["shipped"])


def _todays_build(df, schema):
    """What the parent built: every column through pandas and
    ``build_host_buffers``, from a frame that carries nothing."""
    plain = df.copy()
    plain.attrs.clear()
    cap = bucket_capacity(len(plain))
    return [DeviceColumn.build_host_buffers(
        *_pandas_to_numpy(plain.iloc[:, i], dt), dt, cap)
        for i, dt in enumerate(schema.dtypes)]


def _same_bytes(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _values(np_dtype, n):
    rng = np.random.default_rng(n)
    if np.dtype(np_dtype).kind == "f":
        return (rng.random(n) * 2000 - 1000).astype(np_dtype)
    return rng.integers(-1000, 1000, n).astype(np_dtype)


_NUMERIC = [np.int32, np.int64, np.float32, np.float64]


# --------------------------------------------------------------------------
# the worker's buffers against today's build
# --------------------------------------------------------------------------

@pytest.mark.parametrize("np_dtype", _NUMERIC, ids=lambda d: d.__name__)
def test_full_batch_ships_the_decoders_own_memory(np_dtype):
    table = pa.table({"x": pa.array(_values(np_dtype, 1024))})
    df = _decoded(table)
    schema = _schema(table)
    before = _counts()
    bufs, *_rest, shipped = _build(df, schema, df.attrs["srt_prepared"])
    assert shipped == 1
    assert _counts() == (before[0] + 1, before[1] + 1)
    data, validity = bufs[0]
    # no copy anywhere: the frame's column, Arrow's buffer and what jax
    # is handed are one piece of memory, and nobody can write into it
    assert np.shares_memory(data, df["x"].to_numpy())
    assert not data.flags.writeable and not validity.flags.writeable
    assert validity is shared_validity(1024, 1024)
    _same_bytes(bufs[0], _todays_build(df, schema)[0])


@pytest.mark.parametrize("np_dtype", _NUMERIC, ids=lambda d: d.__name__)
def test_partial_row_group_is_padded_with_the_null_fill(np_dtype):
    vals = _values(np_dtype, 700)
    table = pa.table({"x": pa.array(vals)})
    df = _decoded(table)
    schema = _schema(table)
    bufs, *_rest, shipped = _build(df, schema, df.attrs["srt_prepared"])
    data, validity = bufs[0]
    assert shipped == 1 and data.shape == (1024,)
    assert (data[:700] == vals).all() and (data[700:] == 0).all()
    assert validity[:700].all() and not validity[700:].any()
    _same_bytes(bufs[0], _todays_build(df, schema)[0])


_TEMPORAL = {
    "s": pa.timestamp("s"), "ms": pa.timestamp("ms"),
    "us": pa.timestamp("us"), "ns": pa.timestamp("ns"),
    "date32": pa.date32(),
}


@pytest.mark.parametrize("rows", [1024, 700], ids=["full", "partial"])
@pytest.mark.parametrize("kind", list(_TEMPORAL))
def test_temporal_columns_give_todays_micros(kind, rows):
    """Any unit, before and after 1970, nanoseconds that do not divide:
    int64 micros (int32 days for a date) as the pandas path yields."""
    raw = np.random.default_rng(rows).integers(-40_000, 40_000, rows)
    raw[:4] = [-1, -999, -1001, 0]
    typ = _TEMPORAL[kind]
    arr = pa.array(raw.astype(np.int32 if kind == "date32" else np.int64),
                   type=pa.int32() if kind == "date32" else pa.int64())
    table = pa.table({"t": arr.cast(typ)})
    df = _decoded(table)
    schema = _schema(table)
    bufs, *_rest, shipped = _build(df, schema, df.attrs["srt_prepared"])
    assert shipped == 1
    _same_bytes(bufs[0], _todays_build(df, schema)[0])
    if kind == "us" and rows == 1024:  # already the device's: no copy
        arrow = table.column("t").chunk(0).buffers()[1]
        assert np.shares_memory(bufs[0][0],
                                np.frombuffer(arrow, dtype=np.int64))


def _two_chunks():
    return pa.chunked_array([pa.array([1, 2, 3], pa.int64()),
                             pa.array([4, 5], pa.int64())])


_UNPREPARED = {
    "int-with-null": lambda: pa.array([1, None, 3, 4, 5], pa.int64()),
    "float-with-null": lambda: pa.array([1.5, None, 3.0, 4.0, 5.0]),
    "timestamp-with-null": lambda: pa.array([1, None, 3, 4, 5],
                                            pa.timestamp("ms")),
    "bool": lambda: pa.array([True, False, True, True, False]),
    "string": lambda: pa.array(["a", "b", "a", "c", "b"]),
    "zoned-timestamp": lambda: pa.array([1, 2, 3, 4, 5],
                                        pa.timestamp("ms", tz="UTC")),
    "nat-sentinel": lambda: pa.array([1, _INT64_MIN, 3, 4, 5],
                                     pa.timestamp("ms")),
    "two-chunks": _two_chunks,
}


@pytest.mark.parametrize("kind", list(_UNPREPARED))
def test_what_has_no_prepared_form_takes_the_present_path(kind):
    """Beside a prepared neighbour, so the frame does carry the attr."""
    table = pa.table({"c": _UNPREPARED[kind](),
                      "ok": pa.array(np.arange(5, dtype=np.int32))})
    df = _decoded(table)
    prepared = df.attrs["srt_prepared"]
    assert set(prepared) == {"ok"}
    if kind in ("int-with-null", "float-with-null"):
        assert str(df["c"].dtype) in ("Int64", "Float64")  # extension
    schema = _schema(table)
    before = _counts()
    bufs, *_rest, shipped = _build(df, schema, prepared)
    fixed = 1 if kind == "string" else 2
    assert shipped == 1
    assert _counts() == (before[0] + fixed, before[1] + 1)
    want = _todays_build(df, schema)
    if kind == "string":  # hinted: (validity, codes) alone, PR 27's path
        assert bufs[0][0] is None
    else:
        _same_bytes(bufs[0], want[0])
    _same_bytes(bufs[1], want[1])


def test_stale_or_mistyped_prepared_columns_are_dropped():
    table = pa.table({"t": pa.array(np.arange(16), pa.timestamp("ms")),
                      "x": pa.array(np.arange(16, dtype=np.int64))})
    df = _decoded(table)
    schema = _schema(table)
    want = _todays_build(df, schema)
    # the frame was cut since the worker made them
    stale = copy.copy(df.attrs["srt_prepared"])
    stale.rows = 15
    bufs, *_rest, shipped = _build(df, schema, stale)
    assert shipped == 0
    _same_bytes(bufs[0], want[0])
    # the scan's schema reads the column as another type than the file's
    other = Schema(["t", "x"], [schema.dtypes[1], schema.dtypes[1]])
    bufs, *_rest, shipped = _build(df, other, df.attrs["srt_prepared"])
    assert shipped == 1  # x alone
    for g, w in zip(bufs, _todays_build(df, other)):
        _same_bytes(g, w)


def test_a_frame_nobody_vouched_for_builds_as_today():
    """``from_pandas`` reads no attr: createDataFrame, a CPU-to-TPU
    transition or a re-used scan frame ships nothing unless the scan's
    upload hands the prepared columns over."""
    table = pa.table({"x": pa.array(_values(np.float64, 700))})
    df = _decoded(table)
    assert "srt_prepared" in df.attrs
    before = _counts()
    plain = DeviceBatch.from_pandas(df, dict_numerics=False)
    assert _counts() == (before[0] + 1, before[1])
    taken = DeviceBatch.from_pandas(df, dict_numerics=False,
                                    prepared=df.attrs["srt_prepared"])
    assert _counts() == (before[0] + 2, before[1] + 1)
    for a, b in zip(plain.columns[0].tree_flatten()[0],
                    taken.columns[0].tree_flatten()[0]):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    pd.testing.assert_frame_equal(plain.to_pandas(), taken.to_pandas())


@pytest.mark.parametrize("rows", [1024, 700], ids=["full", "partial"])
def test_an_upload_writes_into_nothing_it_was_handed(rows):
    table = pa.table({
        "x": pa.array(_values(np.float64, rows)),
        "k": pa.array(_values(np.int32, rows)),
        "t": pa.array(_values(np.int64, rows), pa.timestamp("ms"))})
    df = _decoded(table)
    prepared = df.attrs["srt_prepared"]
    frame_before = df.copy(deep=True)
    bytes_before = {k: (v[1].tobytes(), v[2].tobytes())
                    for k, v in prepared.items()}
    for _ in range(2):  # the same buffers may be shipped again
        b = DeviceBatch.from_pandas(df, dict_numerics=False,
                                    prepared=prepared)
        pd.testing.assert_frame_equal(b.to_pandas(), frame_before,
                                      check_dtype=False)
    pd.testing.assert_frame_equal(df, frame_before)
    assert bytes_before == {k: (v[1].tobytes(), v[2].tobytes())
                            for k, v in prepared.items()}


def test_prepared_columns_ride_the_frame_by_reference():
    """pandas deep-copies and compares ``attrs`` at every derivation: the
    buffers must not be copied at each column access, and two frames'
    attrs must compare without an error."""
    a = _decoded(pa.table({"x": pa.array(_values(np.float64, 700))}))
    b = _decoded(pa.table({"x": pa.array(_values(np.float64, 700))}))
    prepared = a.attrs["srt_prepared"]
    assert isinstance(prepared, PreparedColumns) and prepared.rows == 700
    assert a["x"].attrs["srt_prepared"] is prepared
    assert a.iloc[:10].attrs["srt_prepared"] is prepared
    assert copy.deepcopy(prepared) is prepared
    assert prepared != b.attrs["srt_prepared"]
    assert len(pd.concat([a, b])) == 1400
    # the prefetch queue's budget sees what the buffers hold of their own
    assert prepared.nbytes == 1024 * 8
    assert _nbytes(a) == int(a.memory_usage(deep=False).sum()) + 1024 * 8
    full = _decoded(pa.table({"x": pa.array(_values(np.float64, 1024))}))
    assert full.attrs["srt_prepared"].nbytes == 0


# --------------------------------------------------------------------------
# end to end
# --------------------------------------------------------------------------

def _lineitem(rows: int) -> pd.DataFrame:
    rng = np.random.default_rng(7)
    return pd.DataFrame({
        "l_quantity": rng.integers(1, 51, rows).astype(np.float64),
        "l_extendedprice": rng.random(rows) * 1000,
        "l_discount": rng.integers(0, 11, rows) / 100.0,
        "l_shipdate": pd.to_datetime(
            rng.integers(-400, 10_000, rows), unit="D").astype(
                "datetime64[ms]"),
    })


def _q6(table):
    c = F.col
    return table.filter(
        (c("l_shipdate") >= pd.Timestamp("1994-01-01"))
        & (c("l_shipdate") < pd.Timestamp("1995-01-01"))
        & (c("l_discount") >= 0.05) & (c("l_discount") <= 0.07)
        & (c("l_quantity") < 24)
    ).agg(F.sum(c("l_extendedprice") * c("l_discount")).alias("revenue"))


def _q6_pandas(df: pd.DataFrame) -> float:
    m = df[(df.l_shipdate >= "1994-01-01") & (df.l_shipdate < "1995-01-01")
           & (df.l_discount >= 0.05) & (df.l_discount <= 0.07)
           & (df.l_quantity < 24)]
    return float((m.l_extendedprice * m.l_discount).sum())


@pytest.fixture
def tpu_session(session):
    session.set_conf("spark.rapids.sql.test.enabled", True)
    return session


def test_q6_over_a_full_and_a_partial_row_group_equals_pandas_twice(
        tpu_session, tmp_path):
    df = _lineitem(1024 + 700)
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path,
                   row_group_size=1024)
    assert pq.ParquetFile(path).num_row_groups == 2
    query = _q6(tpu_session.read.parquet(path))
    want = _q6_pandas(df)
    for _ in range(2):  # the second collect ships the same shared arrays
        before = _counts()
        got = query.collect()
        assert got["revenue"][0] == pytest.approx(want, rel=1e-9)
        # four columns a row group, both batches, all shipped
        assert _counts() == (before[0] + 8, before[1] + 8)


def test_a_split_cut_by_scan_chunk_drops_the_buffers_and_answers_the_same(
        tpu_session, tmp_path):
    df = _lineitem(1024 + 700)
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path,
                   row_group_size=1024)
    tpu_session.set_conf("spark.rapids.sql.batchSizeRows", 512)
    before = _counts()
    got = _q6(tpu_session.read.parquet(path)).collect()
    assert got["revenue"][0] == pytest.approx(_q6_pandas(df), rel=1e-9)
    fixed, shipped = _counts()
    assert fixed - before[0] == 4 * 4 and shipped == before[1]


@pytest.mark.parametrize("fmt", ["parquet", "orc"])
def test_a_scan_of_either_format_ships_its_fixed_columns(
        tpu_session, tmp_path, fmt):
    df = pd.DataFrame({
        "k": np.arange(700, dtype=np.int64),
        "v": np.linspace(-5.0, 5.0, 700),
        "t": pd.to_datetime(np.arange(-350, 350), unit="D"),
        "s": ["a", "b"] * 350})
    path = str(tmp_path / f"t.{fmt}")
    table = pa.Table.from_pandas(df, preserve_index=False)
    if fmt == "parquet":
        pq.write_table(table, path)
        scan = tpu_session.read.parquet(path)
    else:
        paorc.write_table(table, path)
        scan = tpu_session.read.orc(path)
    before = _counts()
    got = scan.collect().sort_values("k").reset_index(drop=True)
    assert _counts() == (before[0] + 3, before[1] + 3)
    assert got["k"].tolist() == df["k"].tolist()
    assert got["v"].tolist() == df["v"].tolist()
    assert got["s"].tolist() == df["s"].tolist()
    assert (got["t"].to_numpy().astype("datetime64[us]")
            == df["t"].to_numpy().astype("datetime64[us]")).all()


def test_the_legacy_reader_prepares_nothing(tpu_session, tmp_path):
    df = _lineitem(700)
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path)
    tpu_session.set_conf("spark.rapids.sql.scan.prefetchDepth", 0)
    before = _counts()
    got = _q6(tpu_session.read.parquet(path)).collect()
    assert got["revenue"][0] == pytest.approx(_q6_pandas(df), rel=1e-9)
    assert _counts() == (before[0] + 4, before[1])
