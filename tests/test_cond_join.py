"""Equi-joins that carry a residual condition (Spark's hash join with a
condition; TPC-H Q21's EXISTS / NOT EXISTS with ``l2.l_suppkey <>
l1.l_suppkey``): the split of a condition into keys and residual, the plan,
and the device operator (``cjoin``) against the CPU operator and a
brute-force pandas evaluation of every key-equal pair, in both its forms:
the pieces (every pair) and the extent form (a semi or anti join decided
from each key's least and largest build value)."""

import operator

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.exec import tpujoin
from spark_rapids_tpu.exec.tpujoin import TpuShuffledHashJoinExec
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.ops import joins as join_ops
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql import plan as lp
from spark_rapids_tpu.sql.planner import Planner
from tests.querytest import assert_frames_equal, assert_tpu_and_cpu_equal

NO_BROADCAST = {"spark.rapids.sql.autoBroadcastJoinThreshold": "-1"}
# a sort-probe key range: wider than the dense table's cap (2^27)
WIDE = 1 << 40


def _sides(rng, kname, n=300, nb=260, keys=40, scale=1):
    """A stream (left) and a build (right) with duplicate keys, NULL keys,
    NULLs in the residual's columns and a string column that rides."""
    left = pd.DataFrame({
        "k": (pd.Series(rng.integers(0, keys, n)) * scale).astype("Int64")
        .mask(pd.Series(rng.random(n) < 0.06)),
        "a": pd.Series(rng.integers(0, 4, n)).astype("Int64")
        .mask(pd.Series(rng.random(n) < 0.1)),
        "name": pd.Series([f"supp_{rng.integers(0, 90)}" for _ in range(n)]),
        "v": np.round(rng.uniform(0, 3, n)),
    })
    right = pd.DataFrame({
        kname: (pd.Series(rng.integers(0, keys + 8, nb)) * scale)
        .astype("Int64").mask(pd.Series(rng.random(nb) < 0.06)),
        "b": pd.Series(rng.integers(0, 4, nb)).astype("Int64")
        .mask(pd.Series(rng.random(nb) < 0.1)),
        "bname": pd.Series([f"supp_{rng.integers(0, 90)}"
                            for _ in range(nb)]),
        "bv": np.round(rng.uniform(0, 3, nb)),
    })
    return left, right


def _brute(left, right, kname, how, rcol_l, rcol_r, holds=operator.ne):
    """Every key-equal pair, its residual ``holds(l.rcol_l, r.rcol_r)``
    under SQL NULL semantics (NULL is not a pass), then the join type."""
    passed = set()
    pairs = []
    for i, (k, x) in enumerate(zip(left["k"], left[rcol_l])):
        if pd.isna(k):
            continue
        for j, (k2, y) in enumerate(zip(right[kname], right[rcol_r])):
            if pd.isna(k2) or k != k2 or pd.isna(x) or pd.isna(y):
                continue
            if holds(x, y):
                passed.add(i)
                pairs.append((i, j))
    if how == "leftsemi":
        return left.iloc[sorted(passed)].reset_index(drop=True)
    if how == "leftanti":
        keep = [i for i in range(len(left)) if i not in passed]
        return left.iloc[keep].reset_index(drop=True)
    li = [i for i, _ in pairs]
    ri = [j for _, j in pairs]
    return pd.concat([left.iloc[li].reset_index(drop=True),
                      right.iloc[ri].reset_index(drop=True)], axis=1)


def _watch_probe(monkeypatch):
    """The dense plans the joins chose, recorded as they were made."""
    chosen = []
    orig = TpuShuffledHashJoinExec._dense_plan

    def spy(self, ctx, schema):
        got = orig(self, ctx, schema)
        chosen.append(bool(got))
        return got
    monkeypatch.setattr(TpuShuffledHashJoinExec, "_dense_plan", spy)
    return chosen


def _cut(monkeypatch, piece):
    """Pieces of ``piece`` pairs (None: the engine's own)."""
    if piece is not None:
        monkeypatch.setattr(join_ops, "COND_PIECE_PAIRS", piece)


def _on_pieces(how, rl, rr):
    """``rl <> rr`` as a residual that a semi or anti join evaluates in
    pieces: a conjunction, which the extent form declines, that passes the
    same pairs (a NULL ``rr`` fails both)."""
    ne = F.col(rl) != F.col(rr)
    return ne if how == "inner" else ne & F.col(rr).isNotNull()


@pytest.mark.parametrize("how", ["inner", "leftsemi", "leftanti"])
@pytest.mark.parametrize("conf", [None, NO_BROADCAST],
                         ids=["broadcast", "shuffled"])
@pytest.mark.parametrize("probe", ["dense", "sort"])
@pytest.mark.parametrize("piece", [None, 64, 8, 3],
                         ids=["chosen", "pieces-of-64", "pieces-of-8",
                              "pieces-of-3"])
def test_residual_join_matches_cpu_and_every_pair(session, rng, monkeypatch,
                                                  how, conf, probe, piece):
    _cut(monkeypatch, piece)
    kname = f"k2_{probe}"
    left, right = _sides(rng, kname, scale=1 if probe == "dense" else WIDE)
    chosen = _watch_probe(monkeypatch)
    pieces = REGISTRY.counter("join.cond.pieces")
    pairs = REGISTRY.counter("join.cond.pairs")
    extent = REGISTRY.counter("join.cond.extentRows")
    before = (pieces.value, pairs.value, extent.value)

    def q(s):
        return s.create_dataframe(left, 2).join(
            s.create_dataframe(right, 2),
            on=(F.col("k") == F.col(kname)) & _on_pieces(how, "a", "b"),
            how=how)
    got = assert_tpu_and_cpu_equal(q, conf=conf, ignore_order=True)
    assert_frames_equal(got, _brute(left, right, kname, how, "a", "b"),
                        ignore_order=True)
    assert chosen and all(c == (probe == "dense") for c in chosen)
    key_equal = sum(int((right[kname] == k).sum())
                    for k in left["k"].dropna())
    assert pairs.value - before[1] == key_equal
    if piece in (8, 3):
        # rows of up to a dozen key-equal pairs over pieces of 8: most
        # pieces start or end inside a row's pairs
        assert pieces.value - before[0] >= 20
    else:
        assert pieces.value > before[0]
    assert extent.value == before[2]
    if how == "leftanti":
        # a row whose every pair reads NULL in the residual is kept
        assert got["a"].isna().any()


@pytest.mark.parametrize("zeros", [0.0, 0.5, 0.95],
                         ids=["every-row-pairs", "half-without",
                              "runs-without"])
@pytest.mark.parametrize("cap", [4, 16])
def test_slot_rows_window_and_whole_batch(rng, zeros, cap):
    """A piece's slot -> stream row map equals a binary search of every
    slot, both where the window of ``cap`` rows holds the piece's row ends
    and where rows without pairs crowd it (the pass over every row)."""
    import jax.numpy as jnp
    counts = rng.integers(1, 6, 400) * (rng.random(400) >= zeros)
    incl = np.cumsum(counts).astype(np.int32)
    total = int(incl[-1])
    for base in range(0, total, cap):
        got = np.asarray(join_ops._slot_rows(jnp.asarray(incl),
                                             jnp.int32(base), cap))
        slots = np.arange(base, min(base + cap, total))
        want = np.searchsorted(incl, slots, side="right")
        assert (got[:len(slots)] == want).all(), base


@pytest.mark.parametrize("how", ["inner", "leftsemi", "leftanti"])
@pytest.mark.parametrize("case", ["empty-build", "no-match",
                                  "string-residual", "wide-residual",
                                  "float-residual"])
def test_residual_join_edges(session, rng, monkeypatch, how, case):
    _cut(monkeypatch, 16)
    left, right = _sides(rng, "k2e")
    rl, rr = "a", "b"
    if case == "empty-build":
        right = right.iloc[:0]
    elif case == "no-match":
        right = right.assign(k2e=right["k2e"] + 1000)
    elif case == "string-residual":
        rl, rr = "name", "bname"
    elif case == "wide-residual":
        # values spanning more than 2^32: two words and a validity bit
        left = left.assign(a=left["a"] * WIDE - 7)
        right = right.assign(b=right["b"] * WIDE - 7)
    else:
        rl, rr = "v", "bv"

    extent = REGISTRY.counter("join.cond.extentRows")
    before = extent.value

    def q(s):
        return s.create_dataframe(left, 2).join(
            s.create_dataframe(right, 1), left_on=["k"], right_on=["k2e"],
            condition=_on_pieces(how, rl, rr), how=how)
    got = assert_tpu_and_cpu_equal(q, ignore_order=True)
    assert_frames_equal(got, _brute(left, right, "k2e", how, rl, rr),
                        ignore_order=True)
    assert extent.value == before


def _extent_sides(rng, kname, scale=1):
    """``_sides`` and two keys more: one whose build values are all NULL,
    one whose build values are one value that some of its stream rows
    equal."""
    left, right = _sides(rng, kname, scale=scale)
    null_key, one_key = 60 * scale, 61 * scale

    def ints(*xs):
        return pd.array(xs, dtype="Int64")
    left = pd.concat([left, pd.DataFrame({
        "k": ints(null_key, null_key, one_key, one_key, one_key),
        "a": ints(1, None, 2, 1, 3), "name": "supp_0", "v": 1.0})],
        ignore_index=True)
    right = pd.concat([right, pd.DataFrame({
        kname: ints(null_key, null_key, one_key, one_key),
        "b": ints(None, None, 2, 2), "bname": "supp_1", "bv": 1.0})],
        ignore_index=True)
    return left, right


def _residual(op, build_left):
    """(the residual over stream column ``a`` and build column ``b``, the
    same comparison of a stream value x and a build value y)."""
    if build_left:
        return op(F.col("b"), F.col("a")), lambda x, y: op(y, x)
    return op(F.col("a"), F.col("b")), op


def _extent_counters():
    return [REGISTRY.counter(f"join.cond.{n}")
            for n in ("extentRows", "pieces", "pairs")]


_COMPARISONS = [(op, build_left)
                for op in (operator.ne, operator.lt, operator.le, operator.gt,
                           operator.ge)
                for build_left in (True, False)]


@pytest.mark.parametrize("how", ["leftsemi", "leftanti"])
@pytest.mark.parametrize("path", ["dense-broadcast", "sort-shuffled"])
@pytest.mark.parametrize(
    "op,build_left", _COMPARISONS,
    ids=[f"{'b' if bl else 's'}-{op.__name__}-{'s' if bl else 'b'}"
         for op, bl in _COMPARISONS])
def test_extent_form_matches_cpu_and_every_pair(session, rng, monkeypatch,
                                                op, build_left, path, how):
    """Each comparison, with the build column on either side, decided from
    each key's extremes: the CPU operator's rows and the brute force's,
    NULLs on both sides, a key with only NULL build values and a run of one
    value equal to some stream values; the pairs counted as the pieces
    count them, and no piece run."""
    probe, placing = path.split("-")
    kname = f"k3_{probe}"
    left, right = _extent_sides(rng, kname,
                                scale=1 if probe == "dense" else WIDE)
    chosen = _watch_probe(monkeypatch)
    cond, holds = _residual(op, build_left)
    counters = _extent_counters()
    before = [c.value for c in counters]

    def q(s):
        return s.create_dataframe(left, 2).join(
            s.create_dataframe(right, 2),
            on=(F.col("k") == F.col(kname)) & cond, how=how)
    got = assert_tpu_and_cpu_equal(
        q, conf=NO_BROADCAST if placing == "shuffled" else None)
    assert_frames_equal(got, _brute(left, right, kname, how, "a", "b",
                                    holds), ignore_order=True)
    assert chosen and all(c == (probe == "dense") for c in chosen)
    extent, pieces, pairs = (c.value - b for c, b in zip(counters, before))
    assert extent > 0 and pieces == 0
    assert pairs == sum(int((right[kname] == k).sum())
                        for k in left["k"].dropna())


@pytest.mark.parametrize("how", ["leftsemi", "leftanti"])
@pytest.mark.parametrize("conf", [None, NO_BROADCAST],
                         ids=["broadcast", "shuffled"])
@pytest.mark.parametrize("case", ["wide-ne", "wide-ge", "empty-build",
                                  "no-match", "bounds-miss"])
def test_extent_form_edges(session, rng, monkeypatch, how, conf, case):
    """Values spanning more than 2^32 (two words), an empty build, no
    key-equal pair, and advisory bounds that miss a build key, so the dense
    probe falls back to the sort probe and its runs."""
    kname = "k3e"
    left, right = _extent_sides(rng, kname)
    op, build_left = {"wide-ge": (operator.ge, True),
                      "no-match": (operator.le, False),
                      "bounds-miss": (operator.lt, True)}.get(
                          case, (operator.ne, False))
    if case.startswith("wide"):
        left = left.assign(a=left["a"] * WIDE - 7)
        right = right.assign(b=right["b"] * WIDE - 7)
    elif case == "empty-build":
        right = right.iloc[:0]
    elif case == "no-match":
        right = right.assign(k3e=right["k3e"] + 1000)
    planned = []
    if case == "bounds-miss":
        orig = TpuShuffledHashJoinExec._dense_plan

        def missing(self, ctx, schema):
            got = orig(self, ctx, schema)
            planned.append(got)
            return got and (got[0] + 3, got[1])
        monkeypatch.setattr(TpuShuffledHashJoinExec, "_dense_plan", missing)
    cond, holds = _residual(op, build_left)
    counters = _extent_counters()
    before = [c.value for c in counters]

    def q(s):
        return s.create_dataframe(left, 2).join(
            s.create_dataframe(right, 1), left_on=["k"], right_on=[kname],
            condition=cond, how=how)
    got = assert_tpu_and_cpu_equal(q, conf=conf, ignore_order=True)
    assert_frames_equal(got, _brute(left, right, kname, how, "a", "b",
                                    holds), ignore_order=True)
    extent, pieces, _pairs = (c.value - b for c, b in zip(counters, before))
    assert extent > 0 and pieces == 0
    if case == "bounds-miss":
        assert planned and all(planned)


@pytest.mark.parametrize("how,residual", [
    ("leftsemi", "conjunction"), ("leftanti", "expression"),
    ("leftsemi", "float"), ("leftanti", "string"),
    ("inner", "ne"), ("inner", "lt")])
def test_declined_residuals_keep_the_pieces(session, rng, monkeypatch, how,
                                            residual):
    """A semi or anti join whose residual is not one comparison of two
    integer columns, and every inner join, take the pieces' programs
    (``layout``, ``prep``, then ``piece`` or ``pairs``) and no extent."""
    asked = []
    orig = tpujoin.cached_jit

    def spy(sig, *a, **kw):
        asked.append(sig)
        return orig(sig, *a, **kw)
    monkeypatch.setattr(tpujoin, "cached_jit", spy)
    left, right = _sides(rng, "k3d")
    a, b = F.col("a"), F.col("b")
    cond = {"conjunction": (a != b) & (b >= 1), "expression": a + 1 != b,
            "float": F.col("v") != F.col("bv"),
            "string": F.col("name") < F.col("bname"), "ne": a != b,
            "lt": b < a}[residual]
    counters = _extent_counters()
    before = [c.value for c in counters]

    def q(s):
        return s.create_dataframe(left, 2).join(
            s.create_dataframe(right, 1), left_on=["k"], right_on=["k3d"],
            condition=cond, how=how)
    assert_tpu_and_cpu_equal(q, ignore_order=True)
    forms = {sig.rsplit("|", 1)[1] for sig in asked
             if sig.startswith("cjoin|")}
    assert forms == {"layout", "prep",
                     "pairs" if how == "inner" else "piece"}
    extent, pieces, _pairs = (c.value - b for c, b in zip(counters, before))
    assert extent == 0 and pieces > 0


def test_condition_splits_into_keys_and_residual(session, rng):
    left, right = _sides(rng, "k2p", n=20, nb=20)
    L = session.create_dataframe(left, 1)
    R = session.create_dataframe(right, 1)
    j = L.join(R, on=(F.col("a") != F.col("b")) & (F.col("k2p") == F.col("k"))
               & (F.col("v") > 1.0), how="leftsemi")._plan
    assert isinstance(j, lp.LogicalJoin)
    assert [e.name for e in j.left_keys] == ["k"]
    assert [e.name for e in j.right_keys] == ["k2p"]
    assert "Neq" in repr(j.condition) and "Gt" in repr(j.condition)
    # no equality across the sides: the nested-loop join, as before
    bnlj = L.join(R, on=F.col("a") < F.col("b"), how="inner")
    plan = Planner(session.conf.copy()).plan(bnlj._plan)
    assert "CpuBroadcastNestedLoopJoinExec" in [
        type(n).__name__ for n in plan.walk()]


def test_join_without_residual_plans_as_before(session, rng):
    left, right = _sides(rng, "k2q", n=20, nb=20)
    L = session.create_dataframe(left, 1)
    R = session.create_dataframe(right, 1)
    conf = session.conf.copy()

    def tree(df):
        plan = Planner(conf).plan(df._plan)
        return [(n.describe(), n.fingerprint_extra()) for n in plan.walk()]
    by_keys = L.join(R, left_on=["k"], right_on=["k2q"], how="leftsemi")
    by_cond = L.join(R, on=F.col("k") == F.col("k2q"), how="leftsemi")
    assert by_keys._plan.condition is None and by_cond._plan.condition is None
    assert tree(by_keys) == tree(by_cond)
    joins = [n for n in Planner(conf).plan(by_cond._plan).walk()
             if type(n).__name__.endswith("JoinExec")]
    assert joins and all(n.condition is None for n in joins)


@pytest.mark.parametrize("how", ["left", "right", "full"])
def test_outer_residual_join_stays_on_cpu_with_a_reason(session, rng, how):
    left, right = _sides(rng, "k2o", n=60, nb=50)

    def q(s):
        return s.create_dataframe(left, 2).join(
            s.create_dataframe(right, 1), left_on=["k"], right_on=["k2o"],
            condition=F.col("a") != F.col("b"), how=how)
    text = q(session).explain()
    assert f"a {how} join with a residual condition is not supported" \
        in text
    allowed = ["CpuJoinExec", "CpuBroadcastHashJoinExec",
               "CpuShuffleExchangeExec", "CpuBroadcastExchangeExec",
               "CpuScanExec", "CpuProjectExec"]
    got = assert_tpu_and_cpu_equal(q, ignore_order=True,
                                   allow_non_tpu=allowed)
    # the CPU operator's outer rows: every preserved row with no passing
    # pair appears once, null-extended
    inner = _brute(left, right, "k2o", "inner", "a", "b")
    extra = 0
    if how in ("left", "full"):
        extra += len(left) - len(_brute(left, right, "k2o", "leftsemi",
                                        "a", "b"))
    if how in ("right", "full"):
        hit = set()
        for j, (k2, y) in enumerate(zip(right["k2o"], right["b"])):
            m = left[(left["k"] == k2).fillna(False)
                     & (left["a"] != y).fillna(False)]
            if not pd.isna(k2) and not pd.isna(y) and len(m):
                hit.add(j)
        extra += len(right) - len(hit)
    assert len(got) == len(inner) + extra


def _bench_module(name):
    import importlib.util
    import os
    import sys
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if here not in sys.path:
        sys.path.insert(0, here)
    spec = importlib.util.spec_from_file_location(
        f"bench_{name.replace('/', '_')}", os.path.join(here, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_q21_as_the_specification_writes_it(session, tmp_path):
    """The benchmark's Q21 (the two existence subqueries as a leftsemi and
    a leftanti join with the residual ``<>``) on the engine, every
    operator on the TPU, equals its plain pandas reference, on the
    benchmark's own generator and Parquet files at SF 0.01 (lines an
    order a Poisson draw of mean 4; ``s_name`` uploads as a slab), where
    EXISTS and NOT EXISTS each keep and drop late lines; both joins take
    the extent form and no piece runs."""
    import os

    import pyarrow.parquet as pq
    data = _bench_module("data")
    q21 = _bench_module("queries/q21")
    from match import results_match
    root = data.ensure_tables(str(tmp_path), 0.01, 2**31 + 21,
                              list(q21.READS))[0]
    frames = {t: pq.read_table(os.path.join(root, f"{t}.parquet"),
                               columns=cols).to_pandas()
              for t, cols in q21.READS.items()}
    li = frames["lineitem"]
    late = li[li.l_receiptdate > li.l_commitdate]
    suppliers = li.groupby("l_orderkey").l_suppkey.nunique()
    late_suppliers = late.groupby("l_orderkey").l_suppkey.nunique()
    exists = late.l_orderkey.map(suppliers) > 1
    not_exists = late.l_orderkey.map(late_suppliers) == 1
    assert 0 < exists.sum() < len(late)
    assert 0 < (exists & not_exists).sum() < exists.sum()
    want = q21.reference(frames)
    assert len(want) > 0
    session.set_conf("spark.rapids.sql.test.enabled", True)
    pieces = REGISTRY.counter("join.cond.pieces")
    extent = REGISTRY.counter("join.cond.extentRows")
    before = (pieces.value, extent.value)
    tables = {t: session.read.parquet(os.path.join(root, f"{t}.parquet"))
              for t in frames}
    got = q21.build(session, tables).collect()
    assert results_match(got, want), f"{got}\n{want}"
    kinds = [(n.join_type, n.condition is not None)
             for n in session.last_plan.walk()
             if isinstance(n, TpuShuffledHashJoinExec)]
    assert ("leftsemi", True) in kinds and ("leftanti", True) in kinds
    assert pieces.value == before[0] and extent.value > before[1]
