"""Dictionary-encoded string columns upload codes-only (columnar/batch.py
_build_host_columns): a string column that arrives with the decode
worker's hint (sql/sources.py _arrow_dict_hint: Arrow's own dictionary
encode, no Python string a row) and that the scan's dictionary registry
accepts is built as (validity, codes) alone, and where the batch's values
are the registry's the worker's buffers go out as they are. Everything
else — no hint, a hint of the wrong length, a NUL byte, a closed or
outgrown dictionary, too many values — takes the packed or slab path with
the buffers it always had."""

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import dtype as dtmod
from spark_rapids_tpu.columnar.batch import DeviceBatch, bucket_capacity
from spark_rapids_tpu.columnar.column import (
    DICT_MAX_CARD, dict_factorize_hint, host_dict_encode_hinted,
)
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.sources import _arrow_decode, _attach_dict_hints

pytestmark = pytest.mark.smoke

_STRINGS = "scan.upload.stringColumns"
_CODES_ONLY = "scan.upload.codesOnlyColumns"
_CODES_SHIPPED = "scan.upload.codesShippedColumns"
_ARROW_HINTS = "scan.hint.arrowColumns"


def _counts():
    return (REGISTRY.value(_STRINGS), REGISTRY.value(_CODES_ONLY))


def _hint_outcomes():
    return {o: REGISTRY.counter(_ARROW_HINTS, outcome=o).value
            for o in ("hinted", "card", "nul")}


def _decoded(columns: dict) -> pd.DataFrame:
    """A frame as the pipelined reader's decode worker hands it on: Arrow
    table -> pandas, hints attached. A value that is not a list is an
    Arrow array or chunked array already."""
    table = pa.table({k: pa.array(v) if isinstance(v, list) else v
                      for k, v in columns.items()})
    return _attach_dict_hints(_arrow_decode(table, True), table)


def _leaf_shapes(col):
    return [(str(leaf.dtype), leaf.shape)
            for leaf in jax.tree_util.tree_leaves(col)]


def _upload(df, **kw):
    kw.setdefault("dict_numerics", False)  # as a file scan uploads
    return DeviceBatch.from_pandas(df, **kw)


_KEYS = ["A", None, "", "R", "A", "N", "", None, "R", "A"]


# --------------------------------------------------------------------------
# the codes-only build
# --------------------------------------------------------------------------

@pytest.mark.parametrize("blocked", [0, 64], ids=["packed", "slab"])
def test_hinted_column_uploads_codes_only_and_round_trips(blocked):
    df = _decoded({"k": _KEYS, "v": list(range(len(_KEYS)))})
    assert set(df.attrs["srt_dict_fact"]) == {"k"}
    before = _counts()
    state = {}
    b = _upload(df, dict_state=state, blocked_chars=blocked)
    col = b.column("k")
    assert col.is_lazy and not col.has_slab
    assert col.dict_values == ("", "A", "N", "R")
    assert state[0] == col.dict_values
    # (validity, codes) are the only leaves: no chars, offsets, prefix8
    assert _leaf_shapes(col) == [("bool", (16,)), ("int32", (16,))]
    codes = np.asarray(col.dict_codes)
    want = [1, 4, 0, 3, 1, 2, 0, 4, 3, 1]  # nulls carry the sentinel
    assert codes[:len(_KEYS)].tolist() == want
    assert (codes[len(_KEYS):] == 4).all()
    assert np.asarray(col.validity).tolist() == \
        [k is not None for k in _KEYS] + [False] * 6
    assert _counts() == (before[0] + 1, before[1] + 1)
    out = b.to_pandas()
    assert out["k"].isna().tolist() == [k is None for k in _KEYS]
    assert [None if pd.isna(x) else x for x in out["k"]] == _KEYS
    assert out["v"].tolist() == list(range(len(_KEYS)))


def test_codes_only_equals_the_unhinted_build():
    """The hinted column carries the codes, validity and dictionary the
    unhinted build of the same values attaches beside its chars, and the
    chars and prefix8 it rebuilds on the device are the ones that build
    uploads."""
    df = _decoded({"k": _KEYS})
    lazy = _upload(df).column("k")
    plain = df.copy()
    plain.attrs.clear()
    full = _upload(plain).column("k")
    assert lazy.is_lazy and not full.is_lazy
    assert full.dict_values == lazy.dict_values
    np.testing.assert_array_equal(np.asarray(full.dict_codes),
                                  np.asarray(lazy.dict_codes))
    np.testing.assert_array_equal(np.asarray(full.validity),
                                  np.asarray(lazy.validity))
    np.testing.assert_array_equal(np.asarray(full.prefix8),
                                  np.asarray(lazy.prefix8))
    np.testing.assert_array_equal(np.asarray(full.offsets),
                                  np.asarray(lazy.offsets))
    used = int(np.asarray(full.offsets)[-1])
    np.testing.assert_array_equal(np.asarray(full.data)[:used],
                                  np.asarray(lazy.data)[:used])


@pytest.mark.parametrize("missing", [None, np.nan, pd.NA],
                         ids=["None", "nan", "NA"])
def test_validity_read_off_the_hint_matches_isna(missing):
    """The codes-only build takes validity from the hint's NA sentinel;
    the unhinted path asks ``isna``. Both call the same rows missing."""
    s = pd.Series(["x", missing, "y", "x", missing], dtype=object)
    codes, _u = dict_factorize_hint(s.to_numpy(dtype=object), True)
    assert (codes >= 0).tolist() == (~s.isna()).tolist()


# --------------------------------------------------------------------------
# the hint, made by Arrow on the decode worker
# --------------------------------------------------------------------------

def _chunked(*chunks, typ=pa.string()):
    return pa.chunked_array([pa.array(c, typ) for c in chunks], typ)


_HINT_CASES = {
    "no-null": (_chunked(["A", "N", "R", "A", "N", "A", "R", "N"]),
                "hinted"),
    "nulls": (_chunked(["b", None, "a", "b", None, "c"]), "hinted"),
    "empties": (_chunked(["", "x", "", None, "x", ""]), "hinted"),
    "all-null": (_chunked([None, None, None]), "card"),
    "chunks": (_chunked(["b", "a", None], ["c", "a"], [], ["b", "d"]),
               "hinted"),
    "large-string": (_chunked(["q", None, "p", "q"],
                              typ=pa.large_string()), "hinted"),
    "partial-batch": (_chunked([f"k{i % 7}" for i in range(1000)]),
                      "hinted"),
    "sliced": (pa.chunked_array(
        [pa.array(["zz", "a", "b", "a", None]).slice(1)]), "hinted"),
    "over-cap": (_chunked([f"v{i:04d}" for i in range(DICT_MAX_CARD + 44)]),
                 "card"),
    "late-over-cap": (_chunked(
        ["x"] * 4096 + [f"v{i:04d}" for i in range(DICT_MAX_CARD + 1)]),
        "card"),
    "nul-twin": (_chunked(["a", "a\x00", "a", "b", "a\x00", "b"]), "nul"),
}


@pytest.mark.parametrize("case", list(_HINT_CASES))
def test_arrow_hint_equals_the_factorize_hint(case, monkeypatch):
    """The worker's hint for a string column of the scan's Arrow table is
    ``dict_factorize_hint``'s over the same values, codes and uniques, and
    the buffers beside it are what the remap path builds from it; it is
    made without one Python object a row (pandas' factorize and the
    object conversion are patched to raise while the scan decodes)."""
    col, outcome = _HINT_CASES[case]
    want = dict_factorize_hint(
        np.asarray(col.to_pylist(), dtype=object), True)

    def never(*_a, **_k):
        raise AssertionError("a per-row Python object was made")
    before = _hint_outcomes()
    with monkeypatch.context() as m:
        m.setattr(pd, "factorize", never)
        m.setattr(pd, "unique", never)
        m.setattr(pd.Series, "to_numpy", never)
        df = _decoded({"k": col})
    grew = {o: v - before[o] for o, v in _hint_outcomes().items()}
    assert grew == {o: int(o == outcome)
                    for o in ("hinted", "card", "nul")}
    if outcome != "hinted":
        assert "srt_dict_fact" not in df.attrs
        if case == "nul-twin":
            # pandas merges the twins into one unique; Arrow keeps both
            assert len(want[1]) == 2
            enc = pa.compute.dictionary_encode(col.chunk(0))
            assert enc.dictionary.to_pylist() == ["a", "a\x00", "b"]
        else:
            assert want is None
        return
    codes, uniques, ready = df.attrs["srt_dict_fact"]["k"]
    assert codes.dtype == np.int32
    np.testing.assert_array_equal(codes, want[0])
    assert list(uniques) == list(want[1])
    assert all(type(u) is str for u in uniques)
    # the buffers the worker leaves are the ones the remap path builds
    n = len(col)
    cap = bucket_capacity(n)
    vpad, out, vals, as_made = host_dict_encode_hinted(
        (codes, uniques, None), dtmod.STRING, cap, {}, 0)
    assert not as_made and vals == tuple(sorted(uniques))
    assert ready[2] == vals
    assert ready[1].dtype == np.int32 and ready[1].shape == (cap,)
    np.testing.assert_array_equal(ready[1], out)
    np.testing.assert_array_equal(ready[0], vpad)
    assert not ready[0].flags.writeable and not ready[1].flags.writeable
    assert df.attrs["srt_dict_fact"].nbytes >= codes.nbytes + out.nbytes


def test_partition_constant_keeps_the_factorize_hint():
    """A column past the Arrow table's (a hive partition key, one value a
    frame) has no Arrow column to encode: it is hinted through pandas as
    before, carries no worker buffers, and uploads codes-only through the
    remap path."""
    table = pa.table({"k": pa.array(["a", "b", "a"])})
    df = _arrow_decode(table, True)
    df["region"] = pd.Series(["eu"] * 3, dtype=object)
    before = _hint_outcomes()
    df = _attach_dict_hints(df, table)
    assert sum(_hint_outcomes().values()) == sum(before.values()) + 1
    codes, uniques, ready = df.attrs["srt_dict_fact"]["region"]
    assert ready is None and list(uniques) == ["eu"]
    assert df.attrs["srt_dict_fact"]["k"][2] is not None
    shipped = REGISTRY.value(_CODES_SHIPPED)
    b = _upload(df, dict_state={})
    assert b.column("region").is_lazy and b.column("k").is_lazy
    assert REGISTRY.value(_CODES_SHIPPED) == shipped + 1  # k alone
    assert b.to_pandas()["region"].tolist() == ["eu"] * 3


# --------------------------------------------------------------------------
# the NUL gate, on the decode worker: over the dictionary's values
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arr,hinted", [
    (pa.chunked_array([pa.array(["a", "b", None, ""])]), True),
    (pa.chunked_array([pa.array(["a", "a\x00"])]), False),
    (pa.chunked_array([pa.array(["a"]), pa.array(["\x00b"])]), False),
    # a slice that leaves the NUL-bearing row out is clean
    (pa.chunked_array([pa.array(["x\x00", "a", "b"]).slice(1)]), True),
    (pa.chunked_array([pa.array(["a", "x\x00", "b"]).slice(1, 1)]), False),
    (pa.chunked_array([pa.array(["", ""])]), True),
    (pa.chunked_array([pa.array([], pa.string())]), False),  # no rows
    (pa.chunked_array([pa.array(["a\x00"], pa.large_string())]), False),
    (pa.chunked_array([pa.array(["ab"], pa.large_string())]), True),
    # a layout the scan does not read: nothing known, so no hint
    (pa.chunked_array([pa.array(["ab"], pa.string_view())]), False),
], ids=["clean", "nul", "nul-2nd-chunk", "sliced-clean", "sliced-nul",
        "empties", "no-rows", "large-nul", "large-clean", "view"])
def test_arrow_nul_scan(arr, hinted, request):
    before = _hint_outcomes()
    df = _decoded({"z": arr})
    assert ("z" in df.attrs.get("srt_dict_fact", {})) is hinted
    grew = {o: v - before[o] for o, v in _hint_outcomes().items()}
    nul = "nul" in request.node.callspec.id
    assert grew == {"hinted": int(hinted), "card": 0, "nul": int(nul)}


def test_nul_column_gets_no_hint_and_groups_apart(session, tmp_path):
    """pandas 3 factorize merges 'a' with 'a\\x00'; Arrow's encode keeps
    them apart, so the NUL is there to see among the dictionary's values:
    the worker gives the column no hint, and the upload's own gate closes
    its dictionary."""
    vals = ["a", "a\x00", "a", "b", "a\x00", "b"] * 4
    df = _decoded({"z": vals, "k": ["p", "q"] * 12})
    assert set(df.attrs["srt_dict_fact"]) == {"k"}
    before = _counts()
    state = {}
    b = _upload(df, dict_state=state, blocked_chars=64)
    z = b.column("z")
    assert z.dict_values is None and z.has_slab  # slab, as it always was
    assert state[0] is False  # closed for the whole scan
    assert b.column("k").is_lazy
    assert _counts() == (before[0] + 2, before[1] + 1)
    z2 = _upload(df, blocked_chars=0).column("z")
    assert z2.dict_values is None and not z2.is_lazy  # packed
    assert b.to_pandas()["z"].tolist() == vals

    p = str(tmp_path / "nul.parquet")
    pd.DataFrame({"z": vals, "v": np.arange(len(vals), dtype=np.int64)}) \
        .to_parquet(p, row_group_size=8, index=False)
    out = session.read.parquet(p).group_by("z") \
        .agg(F.count("v").alias("n")).collect()
    assert dict(zip(out["z"], out["n"])) == {"a": 8, "a\x00": 8, "b": 8}


# --------------------------------------------------------------------------
# the fallbacks: the buffers the parent built
# --------------------------------------------------------------------------

def _many_values():
    return [f"v{i:04d}" for i in range(DICT_MAX_CARD + 44)]


def _no_hint(df):
    df.attrs.pop("srt_dict_fact", None)
    return df


def _short_hint(df):
    codes, uniques, _ready = df.attrs["srt_dict_fact"]["k"]
    df.attrs["srt_dict_fact"] = {"k": (codes[:-1], uniques, None)}
    return df


@pytest.mark.parametrize("blocked", [0, 64], ids=["packed", "slab"])
@pytest.mark.parametrize("case", ["no-hint", "short-hint", "closed",
                                  "over-cap", "dict-off"])
def test_fallbacks_build_the_unhinted_buffers(case, blocked):
    """Every way out of the codes-only build lands on the build an
    unhinted frame of the same values gets: same leaves, same bytes."""
    keys = _many_values() if case == "over-cap" else _KEYS
    df = _decoded({"k": keys})
    ref = _no_hint(_decoded({"k": keys}))
    state, ref_state = {}, {}
    kw = {}
    if case == "no-hint":
        df = _no_hint(df)
    elif case == "short-hint":
        df = _short_hint(df)
    elif case == "closed":
        state[0] = ref_state[0] = False
    elif case == "over-cap":
        assert "srt_dict_fact" not in df.attrs  # the worker's probe bailed
    elif case == "dict-off":
        kw["dict_encode"] = False
    before = _counts()
    got = _upload(df, dict_state=state, blocked_chars=blocked, **kw)
    want = _upload(ref, dict_state=ref_state, blocked_chars=blocked, **kw)
    assert _counts() == (before[0] + 2, before[1])
    g, w = got.column("k"), want.column("k")
    assert not (g.is_lazy and g.dict_values is not None)
    assert g.dict_values == w.dict_values
    assert jax.tree_util.tree_structure(g) == jax.tree_util.tree_structure(w)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert state == ref_state


def test_unseen_value_in_second_batch_falls_back(session, tmp_path):
    """The registry's dictionary is the first batch's; a later batch that
    falls out of it builds packed or slab for that column from then on,
    and the scan's answer does not change."""
    first = _decoded({"k": ["A", "N", "A", None], "j": ["x", "y", "x", "y"]})
    second = _decoded({"k": ["A", "Z", "N", "A"], "j": ["y", "y", "x", "x"]})
    third = _decoded({"k": ["A", "N", "N", "A"], "j": ["y", "y", "x", "x"]})
    state = {}
    before = _counts()
    b1 = _upload(first, dict_state=state, blocked_chars=64)
    b2 = _upload(second, dict_state=state, blocked_chars=64)
    b3 = _upload(third, dict_state=state, blocked_chars=64)
    assert b1.column("k").is_lazy and b1.column("j").is_lazy
    for b in (b2, b3):
        assert b.column("k").dict_values is None and b.column("k").has_slab
        assert b.column("j").is_lazy
    assert state[0] is False and state[1] == ("x", "y")
    assert _counts() == (before[0] + 6, before[1] + 4)
    assert b2.to_pandas()["k"].tolist() == ["A", "Z", "N", "A"]

    rows = 64
    pdf = pd.DataFrame({
        "k": ["A" if i % 2 else "N" for i in range(rows)],
        "v": np.arange(rows, dtype=np.int64)})
    pdf.loc[rows - 3, "k"] = "Z"  # the last row group alone holds it
    p = str(tmp_path / "late.parquet")
    pdf.to_parquet(p, row_group_size=16, index=False)
    want = pdf.groupby("k")["v"].sum()
    for depth in (0, 2):
        session.set_conf("spark.rapids.sql.scan.prefetchDepth", depth)
        before = _counts()
        out = session.read.parquet(p).group_by("k") \
            .agg(F.sum("v").alias("sv")).collect()
        assert dict(zip(out["k"], out["sv"])) == want.to_dict(), depth
        strings, codes_only = (a - b for a, b in zip(_counts(), before))
        assert strings == 4
        assert codes_only == (3 if depth else 0)


# --------------------------------------------------------------------------
# the ship path: the worker's buffers go out where their values are the
# scan's dictionary, and only there
# --------------------------------------------------------------------------

def _shipped():
    return REGISTRY.value(_CODES_SHIPPED)


def test_worker_buffers_ship_only_under_the_scans_own_dictionary(
        session, tmp_path):
    """Three splits: the first establishes both dictionaries and ships;
    the second holds a subset of ``k``'s values, so its ``k`` is remapped
    against the registry (codes-only, not shipped); the third holds a
    value the registry never saw, so ``k`` leaves the dictionary. ``j``
    holds the same values in every split and ships in all three. Nothing
    is written into a shipped buffer."""
    splits = [
        {"k": ["N", "A", "R", None, "A"], "j": ["y", "x", "x", "y", "x"]},
        {"k": ["R", "A", "A", "R", None], "j": ["x", "y", "y", "x", "x"]},
        {"k": ["A", "Z", "N", "R", "A"], "j": ["y", "y", "x", "x", "y"]},
    ]
    frames = [_decoded(sp) for sp in splits]
    readies = [{nm: h[2] for nm, h in f.attrs["srt_dict_fact"].items()}
               for f in frames]
    copies = [{nm: (r[0].copy(), r[1].copy()) for nm, r in rd.items()}
              for rd in readies]
    state = {}
    grown = []
    batches = []
    for f in frames:
        before = (_counts()[1], _shipped())
        batches.append(_upload(f, dict_state=state, blocked_chars=64))
        grown.append((_counts()[1] - before[0], _shipped() - before[1]))
    # (codes-only, shipped as made) a batch
    assert grown == [(2, 2), (2, 1), (1, 1)]
    assert state[1] == ("x", "y") and state[0] is False
    b1, b2, b3 = batches
    assert b1.column("k").dict_values == b2.column("k").dict_values \
        == ("A", "N", "R")
    # the remapped subset speaks the registry's codes, not its own
    assert np.asarray(b2.column("k").dict_codes)[:5].tolist() \
        == [2, 0, 0, 2, 3]
    assert b3.column("k").dict_values is None and b3.column("k").has_slab
    for sp, b in zip(splits, batches):
        out = b.to_pandas()
        assert [None if pd.isna(x) else x for x in out["k"]] == sp["k"]
        assert out["j"].tolist() == sp["j"]
    # a shipped buffer is the worker's own array, read-only and unwritten
    for rd, cp in zip(readies, copies):
        for nm, r in rd.items():
            assert not r[1].flags.writeable
            np.testing.assert_array_equal(r[0], cp[nm][0])
            np.testing.assert_array_equal(r[1], cp[nm][1])
    # another capacity than the worker's: remapped, never shipped
    before = _shipped()
    wide = _upload(_decoded(splits[0]), capacity=64)
    assert _shipped() == before and wide.column("k").is_lazy
    assert np.asarray(wide.column("k").dict_codes).shape == (64,)

    # the same three splits as row groups of one file, through the scan
    pdf = pd.concat([pd.DataFrame(sp) for sp in splits], ignore_index=True)
    pdf["v"] = np.arange(len(pdf), dtype=np.int64)
    p = str(tmp_path / "three.parquet")
    pdf.to_parquet(p, row_group_size=5, index=False)
    for keys in (["k"], ["j"], ["k", "j"]):
        before = (_counts()[1], _shipped())
        out = session.read.parquet(p).group_by(*keys) \
            .agg(F.sum("v").alias("sv")).collect()
        want = pdf.groupby(keys, dropna=False)["v"].sum()
        got = {tuple(None if pd.isna(x) else x for x in row[:-1]): row[-1]
               for row in out[keys + ["sv"]].itertuples(index=False)}
        assert got == {tuple(None if pd.isna(x) else x
                             for x in (kk if isinstance(kk, tuple)
                                       else (kk,))): sv
                       for kk, sv in want.items()}, keys
        codes_only, shipped = (_counts()[1] - before[0],
                               _shipped() - before[1])
        assert (codes_only, shipped) == {
            "k": (2, 1), "j": (3, 3), "kj": (5, 4)}["".join(keys)], keys


# --------------------------------------------------------------------------
# end to end: a Q1-shaped scan
# --------------------------------------------------------------------------

def _q1_frame(rows=4000):
    rng = np.random.default_rng(11)
    flag = rng.choice(np.array(["A", "N", "R"], dtype=object), rows)
    flag[rng.random(rows) < 0.05] = None
    return pd.DataFrame({
        "l_returnflag": flag,
        "l_linestatus": rng.choice(np.array(["F", "O"], dtype=object), rows),
        "l_quantity": rng.integers(1, 51, rows).astype(np.float64),
        "l_extendedprice": rng.random(rows) * 1000.0,
        "l_discount": rng.integers(0, 11, rows) / 100.0,
    })


@pytest.mark.parametrize("depth", [0, 2])
def test_q1_shaped_scan_equals_pandas(session, tmp_path, depth):
    """Two string group keys, nulls in one, over eight row groups: the
    pipelined reader uploads both keys codes-only in every batch, each as
    the decode worker left it, the legacy reader (no hints) in none, and
    both give pandas' answer."""
    pdf = _q1_frame()
    p = str(tmp_path / "lineitem.parquet")
    pdf.to_parquet(p, row_group_size=500, index=False)
    session.set_conf("spark.rapids.sql.scan.prefetchDepth", depth)
    session.set_conf("spark.rapids.sql.test.enabled", True)
    before, shipped_before = _counts(), _shipped()
    out = (session.read.parquet(p)
           .group_by("l_returnflag", "l_linestatus")
           .agg(F.sum("l_quantity").alias("sum_qty"),
                F.sum(F.col("l_extendedprice")
                      * (F.lit(1.0) - F.col("l_discount")))
                .alias("sum_disc_price"),
                F.avg("l_discount").alias("avg_disc"),
                F.count("l_quantity").alias("n"))
           .collect())
    strings, codes_only = (a - b for a, b in zip(_counts(), before))
    assert strings == 16
    assert codes_only == (16 if depth else 0)
    # every row group holds all of A, N, R and both of F, O, so every
    # batch's values are the scan's dictionary: the first batch of the
    # scan establishes it from the worker's buffers and ships like the rest
    assert _shipped() - shipped_before == codes_only

    ref = pdf.assign(
        disc_price=pdf.l_extendedprice * (1.0 - pdf.l_discount)) \
        .groupby(["l_returnflag", "l_linestatus"], dropna=False) \
        .agg(sum_qty=("l_quantity", "sum"),
             sum_disc_price=("disc_price", "sum"),
             avg_disc=("l_discount", "mean"),
             n=("l_quantity", "count")).reset_index()

    def keyed(df):
        df = df.copy()
        df["l_returnflag"] = [None if pd.isna(x) else x
                              for x in df["l_returnflag"]]
        return df.sort_values(["l_returnflag", "l_linestatus"],
                              na_position="last").reset_index(drop=True)
    out, ref = keyed(out), keyed(ref)
    assert len(out) == len(ref) == 8  # (A, N, R, NULL) x (F, O)
    assert out["l_returnflag"].tolist() == ref["l_returnflag"].tolist()
    assert out["l_linestatus"].tolist() == ref["l_linestatus"].tolist()
    assert out["n"].tolist() == ref["n"].tolist()
    for c in ("sum_qty", "sum_disc_price", "avg_disc"):
        np.testing.assert_allclose(out[c].to_numpy(dtype=float),
                                   ref[c].to_numpy(dtype=float),
                                   rtol=1e-9)
