"""A Parquet scan declares its integer columns' bounds from the file
footers when it is planned (ParquetSource.declared_int_bounds,
TpuScanExec._declare_stats), so a dense-key aggregate engages on a plan's
FIRST execution; sources that cannot declare (no statistics, in-memory
frames) keep the second-execution gate of tests/test_dense_group.py."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.obs.metrics import REGISTRY
from tests.querytest import assert_frames_equal, with_tpu_session

N = 6000


def _frame(rng, lo=1000, hi=9000, n=N):
    return pd.DataFrame({
        "okey": pd.Series(rng.integers(lo, hi, n)).astype("Int64")
                  .mask(pd.Series(rng.random(n) < 0.03)),
        "skey": pd.Series(rng.integers(0, 40, n), dtype="int32"),
        "qty": rng.uniform(1.0, 50.0, n),
    })


def _write(path, df, **kw):
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), str(path),
                   row_group_size=1500, **kw)
    return str(path)


def _q(o):
    from spark_rapids_tpu.sql import functions as F
    return o.group_by("okey").agg(
        F.sum("qty").alias("sq"), F.count("*").alias("n"),
        F.max("qty").alias("mx"))


def _expected(df):
    g = df.groupby("okey", dropna=False)["qty"]
    return pd.DataFrame({"sq": g.sum(), "n": g.size(), "mx": g.max()}) \
        .reset_index()


def _dense_plans():
    return (REGISTRY.value("agg.dense.plans", source="declared"),
            REGISTRY.value("agg.dense.plans", source="seen"))


def _collect(sdf_fn):
    """(answer, dense plans by declared bounds, by the seen gate)."""
    d0, s0 = _dense_plans()
    out = with_tpu_session(lambda s: sdf_fn())
    d1, s1 = _dense_plans()
    return out, d1 - d0, s1 - s0


@pytest.mark.smoke
def test_parquet_key_goes_dense_on_first_collect(session, rng, tmp_path):
    df = _frame(rng)
    o = session.read.parquet(_write(tmp_path / "o.parquet", df))
    cols0 = REGISTRY.value("scan.stats.declaredColumns")
    reruns0 = session.capacity_spec_reruns
    first, declared, seen = _collect(lambda: _q(o))
    assert declared >= 1 and seen == 0
    # okey alone: the scan is pruned to what the query reads
    assert REGISTRY.value("scan.stats.declaredColumns") - cols0 == 1
    assert_frames_equal(first, _expected(df), ignore_order=True, approx=True)
    second, declared, seen = _collect(lambda: _q(o))
    assert declared >= 1 and seen == 0
    assert_frames_equal(second, first, ignore_order=True, approx=True)
    assert session.capacity_spec_reruns == reruns0, \
        "footer bounds are exact: no re-execution"


@pytest.mark.parametrize("source", ["no_statistics", "in_memory"])
def test_undeclared_sources_wait_for_a_second_execution(
        session, rng, tmp_path, source):
    df = _frame(rng)
    if source == "no_statistics":
        o = session.read.parquet(_write(tmp_path / "o.parquet", df,
                                        write_statistics=False))
    else:
        o = session.create_dataframe(df, 2)
    first, declared, seen = _collect(lambda: _q(o))
    assert (declared, seen) == (0, 0), "no bounds before the first upload"
    assert_frames_equal(first, _expected(df), ignore_order=True, approx=True)
    second, declared, seen = _collect(lambda: _q(o))
    assert declared == 0 and seen >= 1
    assert_frames_equal(second, first, ignore_order=True, approx=True)


def test_bounds_are_the_union_over_surviving_row_groups(session, tmp_path):
    from spark_rapids_tpu.sql.sources import ParquetSource
    # four row groups of 1500 rows; okey rises with the row, day with it
    df = pd.DataFrame({"okey": np.arange(N, dtype="int64") + 5000,
                       "day": (np.arange(N) // 1500).astype("int32"),
                       "qty": np.ones(N)})
    path = _write(tmp_path / "o.parquet", df)
    src = ParquetSource([path])
    assert src.declared_int_bounds() == {
        "okey": (5000, 5000 + N - 1), "day": (0, 3)}
    assert src.declared_int_bounds([("day", ">=", 2)]) == {
        "okey": (8000, 5000 + N - 1), "day": (2, 3)}
    assert src.with_columns(["okey", "qty"]).declared_int_bounds() == {
        "okey": (5000, 5000 + N - 1)}
    # nothing survives: declared, with nothing to bound
    assert src.declared_int_bounds([("day", ">", 9)]) == {
        "okey": None, "day": None}
    # through the engine: the pushed filter narrows what the scan declares
    from spark_rapids_tpu.sql import functions as F
    o = session.read.parquet(path)
    session.column_stats.pop("okey", None)
    out, declared, _ = _collect(
        lambda: _q(o.filter(F.col("day") >= 2)))
    assert declared >= 1
    assert session.column_stats["okey"] == (8000, 5000 + N - 1)
    assert_frames_equal(out, _expected(df[df.day >= 2]),
                        ignore_order=True, approx=True)


def test_all_null_row_group_declares_nothing_and_breaks_nothing(
        session, rng, tmp_path):
    from spark_rapids_tpu.sql.sources import ParquetSource
    df = _frame(rng)
    df.loc[1500:2999, "okey"] = pd.NA      # the second row group whole
    path = _write(tmp_path / "o.parquet", df)
    rest = df["okey"].dropna()
    assert ParquetSource([path]).declared_int_bounds()["okey"] == (
        int(rest.min()), int(rest.max()))
    o = session.read.parquet(path)
    out, declared, seen = _collect(lambda: _q(o))
    assert declared >= 1 and seen == 0
    assert_frames_equal(out, _expected(df), ignore_order=True, approx=True)
    # a column of nothing but nulls: declared (no pass over it), no bounds
    df2 = df.assign(okey=pd.Series([pd.NA] * N, dtype="Int64"))
    path2 = _write(tmp_path / "n.parquet", df2)
    assert ParquetSource([path2]).declared_int_bounds()["okey"] is None
    session.column_stats.pop("okey", None)
    out2, declared, seen = _collect(lambda: _q(session.read.parquet(path2)))
    assert (declared, seen) == (0, 0)
    assert "okey" not in session.column_stats
    assert_frames_equal(out2, _expected(df2), ignore_order=True, approx=True)


def test_file_rewritten_after_planning_reexecutes_exactly(
        session, rng, tmp_path, monkeypatch):
    """The footers promise [1000, 9000); the file the scan then reads was
    rewritten with keys up to 40000: the device's ok flag goes false, the
    deferred verification re-executes without dense grouping."""
    from spark_rapids_tpu.sql.sources import ParquetSource
    path = _write(tmp_path / "o.parquet", _frame(rng))
    wide = _frame(rng, lo=1000, hi=40000)
    o = session.read.parquet(path)
    session.column_stats.pop("okey", None)
    declare = ParquetSource.declared_int_bounds

    def declare_then_rewrite(self, filters=None):
        got = declare(self, filters)
        _write(path, wide)
        return got
    monkeypatch.setattr(ParquetSource, "declared_int_bounds",
                        declare_then_rewrite)
    reruns0 = session.capacity_spec_reruns
    out, declared, _ = _collect(lambda: _q(o))
    assert declared >= 1
    assert session.capacity_spec_reruns == reruns0 + 1
    assert_frames_equal(out, _expected(wide), ignore_order=True, approx=True)


def test_upload_makes_no_pass_over_a_declared_column(
        session, rng, tmp_path, monkeypatch):
    df = _frame(rng)
    path = _write(tmp_path / "o.parquet", df)
    nostats = _write(tmp_path / "p.parquet", df, write_statistics=False)
    from spark_rapids_tpu.sql import functions as F
    measured = []
    real = pd.Series.count

    def counting(self, *a, **kw):
        measured.append(self.name)
        return real(self, *a, **kw)

    def q(o):
        return o.group_by("okey", "skey").agg(F.sum("qty").alias("sq"))
    monkeypatch.setattr(pd.Series, "count", counting)
    with_tpu_session(lambda s: q(session.read.parquet(path)))
    assert not {"okey", "skey"} & set(measured), measured
    with_tpu_session(lambda s: q(session.read.parquet(nostats)))
    assert {"okey", "skey"} <= set(measured), \
        "a file without statistics is still measured batch by batch"
