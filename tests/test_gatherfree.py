"""Gather-free execution (docs/gatherfree.md): dict-coded vs decoded
equality across join/agg/sort/exchange, the exchange-boundary dictionary
merge, blocked char slabs, and the small-query fast path.

Tier-1 tests here are tiny-data and mostly unit-level (no full query
planning) — the 870s budget is nearly spent. The full dict-on tpch +
tpcxbb sweeps ride the slow tier (test_gatherfree_sweep_slow).
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.ops import rowops


def _strs(df: pd.DataFrame, col: str = "s"):
    # pandas 3's str dtype spells a missing value nan and .where(..., None)
    # keeps it nan, so normalize element-wise
    return [None if pd.isna(v) else v for v in df[col].tolist()]


# ---------------------------------------------------------------------------
# value tables: bit-identical images for dictionary columns
# ---------------------------------------------------------------------------

def test_dict_value_tables_match_char_path():
    from spark_rapids_tpu.ops import hashing, sortops
    df = pd.DataFrame({"s": ["a", "bb", "a", None, "ccc", "", "Ünïcode"]})
    bd = DeviceBatch.from_pandas(df)                      # dict-encoded
    bp = DeviceBatch.from_pandas(df, dict_encode=False)   # packed chars
    assert bd.columns[0].dict_values is not None
    n = len(df)
    h1d, h2d = hashing.string_poly_hashes_col(bd.columns[0])
    h1p, h2p = hashing.string_poly_hashes_col(bp.columns[0])
    np.testing.assert_array_equal(np.asarray(h1d)[:n], np.asarray(h1p)[:n])
    np.testing.assert_array_equal(np.asarray(h2d)[:n], np.asarray(h2p)[:n])
    for a, b in zip(sortops._string_prefix_chunks(bd.columns[0]),
                    sortops._string_prefix_chunks(bp.columns[0])):
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n])


def test_dict_hash_values_flag_is_value_identical():
    from spark_rapids_tpu.columnar import dictionary as dm
    from spark_rapids_tpu.ops import hashing
    df = pd.DataFrame({"s": ["x", "y", None, "x"]})
    bd = DeviceBatch.from_pandas(df)
    assert bd.columns[0].dict_values is not None
    h_on = hashing.string_poly_hashes_col(bd.columns[0])
    old = dm._FLAGS["hash_values"]
    try:
        dm._FLAGS["hash_values"] = False
        h_off = hashing.string_poly_hashes_col(bd.columns[0])
    finally:
        dm._FLAGS["hash_values"] = old
    for a, b in zip(h_on, h_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# exchange-boundary dictionary merge (union + remap)
# ---------------------------------------------------------------------------

def test_union_dictionaries_canonical_and_remap():
    from spark_rapids_tpu.columnar.dictionary import union_dictionaries
    vals, remaps = union_dictionaries([("a", "c"), ("b", "c"), ()])
    assert vals == ("a", "b", "c")
    assert remaps[0].tolist() == [0, 2, 3]   # a->0, c->2, NULL->3
    assert remaps[1].tolist() == [1, 2, 3]
    assert remaps[2].tolist() == [3]         # empty dict: only NULL


def test_concat_merges_differing_dictionaries():
    d1 = DeviceBatch.from_pandas(pd.DataFrame({"s": ["a", "c", "a"]}))
    d2 = DeviceBatch.from_pandas(pd.DataFrame({"s": ["b", "c", None]}))
    assert d1.columns[0].dict_values != d2.columns[0].dict_values
    cc = rowops.concat_batches([d1, d2], 16, dict_merge=True)
    assert cc.columns[0].dict_values == ("a", "b", "c")
    assert _strs(cc.to_pandas()) == ["a", "c", "a", "b", "c", None]
    # rollback: merge off decodes at the boundary, identical values
    cc2 = rowops.concat_batches([d1, d2], 16, dict_merge=False)
    assert cc2.columns[0].dict_values is None
    assert _strs(cc2.to_pandas()) == ["a", "c", "a", "b", "c", None]


def test_concat_merge_all_null_part():
    d1 = DeviceBatch.from_pandas(pd.DataFrame({"s": ["a", "b"]}))
    # an all-null column never dictionary-encodes (card 0) — the concat
    # must fall back to decoding, not crash or drop rows
    d2 = DeviceBatch.from_pandas(
        pd.DataFrame({"s": pd.Series([None, None], dtype="object")}))
    assert d2.columns[0].dict_values is None
    cc = rowops.concat_batches([d1, d2], 16, dict_merge=True)
    assert _strs(cc.to_pandas()) == ["a", "b", None, None]


# ---------------------------------------------------------------------------
# blocked char slabs
# ---------------------------------------------------------------------------

def test_slab_roundtrip_and_movement():
    df = pd.DataFrame({
        "s": ["alpha", "", "gamma-ray-long-string", None, "zz", "qqq"],
        "x": np.arange(6)})
    b = DeviceBatch.from_pandas(df, dict_encode=False, blocked_chars=64)
    assert b.columns[0].has_slab
    assert _strs(b.to_pandas()) == _strs(df)
    # filter = gather: the slab moves by rows, packed chars stay lazy
    fb = rowops.filter_batch(b, b.columns[1].data % 2 == 0)
    assert fb.columns[0].has_slab
    assert _strs(fb.to_pandas()) == ["alpha", "gamma-ray-long-string", "zz"]
    # concat of differing strides re-pads
    b2 = DeviceBatch.from_pandas(pd.DataFrame(
        {"s": ["m"], "x": [9]}), dict_encode=False, blocked_chars=64)
    cs = rowops.concat_batches([b, b2], 16)
    assert cs.columns[0].has_slab
    assert _strs(cs.to_pandas()) == _strs(df) + ["m"]


def test_slab_images_match_packed():
    from spark_rapids_tpu.ops import hashing, sortops
    df = pd.DataFrame({"s": ["alpha", "", "sixteen-bytes-xx", None, "Ü"]})
    bs = DeviceBatch.from_pandas(df, dict_encode=False, blocked_chars=64)
    bp = DeviceBatch.from_pandas(df, dict_encode=False, blocked_chars=0)
    assert bs.columns[0].has_slab and not bp.columns[0].has_slab
    for a, b in zip(sortops._string_prefix_chunks(bs.columns[0]),
                    sortops._string_prefix_chunks(bp.columns[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(hashing.string_poly_hashes_col(bs.columns[0]),
                    hashing.string_poly_hashes_col(bp.columns[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(sortops.string_prefix8(bs.columns[0])),
        np.asarray(sortops.string_prefix8(bp.columns[0])))


def test_slab_stride_respects_max():
    long = "x" * 200
    df = pd.DataFrame({"s": [long, "a"]})
    b = DeviceBatch.from_pandas(df, dict_encode=False, blocked_chars=64)
    # longest row exceeds maxStride: stays packed
    assert not b.columns[0].has_slab
    assert _strs(b.to_pandas()) == [long, "a"]


# ---------------------------------------------------------------------------
# wire: codes cross the shuffle, v1 rollback byte-compatible values
# ---------------------------------------------------------------------------

def test_wire_dict_codes_roundtrip_and_rollback():
    from spark_rapids_tpu.columnar import dictionary as dm
    from spark_rapids_tpu.shuffle import wire
    df = pd.DataFrame({"s": ["a", "bb", None, "a"], "x": [1, 2, 3, 4]})
    bd = DeviceBatch.from_pandas(df)
    exp = _strs(df)
    rb = wire.deserialize_batch(wire.serialize_batch(bd))
    assert rb.columns[0].dict_values is not None  # codes-only off the wire
    assert _strs(rb.to_pandas()) == exp
    old = dm._FLAGS["wire"]
    try:
        dm._FLAGS["wire"] = False
        blob = wire.serialize_batch(bd)
        assert blob[4:8] == (1).to_bytes(4, "little")  # legacy v1 frame
        assert _strs(wire.deserialize_batch(blob).to_pandas()) == exp
    finally:
        dm._FLAGS["wire"] = old


# ---------------------------------------------------------------------------
# small-query fast path: byte-identical to the general path
# ---------------------------------------------------------------------------

def test_small_query_fast_path_byte_identical(session):
    from spark_rapids_tpu.sql import functions as F
    fact = pd.DataFrame({
        "k": [0, 1, 2, 0, 1, 2, 0, 3],
        "s": ["a", "b", None, "a", "c", "b", "c", "a"],
        "v": [1.5, 2.0, 3.25, 0.5, 1.0, 2.5, 4.0, 0.25]})
    dim = pd.DataFrame({"k": [0, 1, 2, 3], "name": ["p", "q", "r", "s"]})

    def q(s):
        f = s.create_dataframe(fact, 2)
        d = s.create_dataframe(dim, 1)
        return (f.join(d, on="k").group_by("name")
                .agg(F.sum("v").alias("sv"), F.count("*").alias("c"))
                .order_by("name"))

    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.smallQuery.enabled", False)
    slow = q(session).collect()
    session.set_conf("spark.rapids.sql.smallQuery.enabled", True)
    fast = q(session).collect()  # last_plan below is THIS plan
    pd.testing.assert_frame_equal(fast.reset_index(drop=True),
                                  slow.reset_index(drop=True))
    # the fast path really collapsed the plan: no multi-partition hash
    # exchange survives
    for node in session.last_plan.walk():
        part = getattr(node, "partitioning", None)
        if part and part[0] == "hash":
            assert part[-1] == 1, part


def test_concat_dict_merge_survives_retrace():
    """The cached concat kernel must keep its dict_merge setting on a
    RE-TRACE at a new batch shape (regression: a closure over a local
    later reassigned to the device manager silently flipped it)."""
    from spark_rapids_tpu.exec.tpu import _concat_device
    d1 = DeviceBatch.from_pandas(pd.DataFrame({"s": ["a", "c"]}))
    d2 = DeviceBatch.from_pandas(pd.DataFrame({"s": ["b", "c"]}))
    out1 = _concat_device([d1, d2], d1.schema, 2.0)
    assert out1.columns[0].dict_values == ("a", "b", "c")
    d3 = DeviceBatch.from_pandas(
        pd.DataFrame({"s": ["a", "c"] * 6}))
    d4 = DeviceBatch.from_pandas(
        pd.DataFrame({"s": ["b", "c", "b"] * 4}))
    out2 = _concat_device([d3, d4], d3.schema, 2.0)
    assert out2.columns[0].dict_values == ("a", "b", "c")


def test_small_query_keeps_semaphore_for_expanding_plans(session):
    from spark_rapids_tpu.sql.planner import Planner
    from spark_rapids_tpu.sql import plan as lp
    from spark_rapids_tpu.sql.sources import InMemorySource
    conf = session.conf.copy().set("spark.rapids.sql.enabled", True)
    df = pd.DataFrame({"a": [1, 2]})
    scan = lambda: lp.LogicalScan(InMemorySource(df, 1))  # noqa: E731
    p = Planner(conf)
    p.note_input_size(scan())
    assert p.small_query and not p.small_query_keep_sem
    p2 = Planner(conf)
    p2.note_input_size(lp.LogicalJoin(scan(), scan(), "inner",
                                      ["a"], ["a"]))
    assert p2.small_query and p2.small_query_keep_sem


def test_small_query_disengages_on_explicit_partitions(session):
    from spark_rapids_tpu.sql.planner import Planner
    from spark_rapids_tpu.sql import plan as lp
    from spark_rapids_tpu.sql.sources import InMemorySource
    df = pd.DataFrame({"a": [1, 2, 3]})
    logical = lp.LogicalScan(InMemorySource(df, 2))
    p = Planner(session.conf.copy().set("spark.rapids.sql.enabled", True))
    p.note_input_size(logical)
    assert p.small_query
    conf2 = session.conf.copy().set("spark.rapids.sql.enabled", True) \
        .set("spark.rapids.sql.shuffle.partitions", 4)
    p2 = Planner(conf2)
    p2.note_input_size(logical)
    assert not p2.small_query


# ---------------------------------------------------------------------------
# slow tier: dict-on oracle sweeps over real query shapes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gatherfree_sweep_slow(session):
    """Dict + blocked-chars ON vs OFF over join/agg/sort/exchange query
    shapes at a real (if small) scale, both verified against the CPU
    oracle — the tiny-data tier-1 pins above cannot catch capacity-bucket
    or multi-batch effects."""
    from spark_rapids_tpu.sql import functions as F
    rng = np.random.default_rng(5)
    n = 20000
    fact = pd.DataFrame({
        "k": rng.integers(0, 40, n).astype(np.int64),
        "cat": pd.Series(rng.choice(
            ["Books", "Games", "Tools", None, "Música"], n)),
        "tag": pd.Series(["t%04d" % i
                          for i in rng.integers(0, 8000, n)]),
        "v": rng.random(n)})
    dim = pd.DataFrame({"k": np.arange(40, dtype=np.int64),
                        "name": ["n%02d" % (i % 23) for i in range(40)]})

    def queries(s):
        f = s.create_dataframe(fact, 3)
        d = s.create_dataframe(dim, 1)
        yield (f.join(d, on="k").filter(F.col("cat") != "Games")
               .group_by("name").agg(F.sum("v").alias("sv"),
                                     F.count("*").alias("c")))
        yield f.group_by("tag").agg(F.sum("v").alias("sv"))
        yield f.order_by("cat", "tag").select("cat", "tag").limit(300)
        yield (f.group_by("cat").agg(F.max("tag").alias("mx"),
                                     F.min("tag").alias("mn")))

    def run_all():
        outs = []
        for q in queries(session):
            df = q.collect()
            outs.append(df.sort_values(list(df.columns))
                        .reset_index(drop=True))
        return outs

    session.set_conf("spark.rapids.sql.enabled", False)
    oracle = run_all()
    session.set_conf("spark.rapids.sql.enabled", True)
    for dict_on in (True, False):
        session.set_conf("spark.rapids.sql.dict.enabled", dict_on)
        got = run_all()
        for g, o in zip(got, oracle):
            pd.testing.assert_frame_equal(g, o, check_dtype=False,
                                          rtol=1e-9)
    session.set_conf("spark.rapids.sql.dict.enabled", True)
