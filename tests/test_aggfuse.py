"""Fused count-distinct (spark.rapids.sql.agg.fuseCountDistinct,
exec/aggfuse.py): the distinct -> regroup -> count chain collapses to one
sorted pass. Differential coverage: string + int keys, null keys, both
spellings (distinct().group_by().count() and count(*) over distinct),
global count-distinct is NOT matched (no keys), conf gate."""

import numpy as np
import pandas as pd
import pytest

from tests.querytest import (
    assert_frames_equal, with_cpu_session, with_tpu_session,
)


def _df(session, rng, n=3000):
    brands = [f"Brand#{i}" for i in range(8)]
    types = [f"TYPE {c}" for c in "ABCD"]
    return session.create_dataframe(pd.DataFrame({
        "brand": pd.Series(rng.choice(brands, n)).mask(
            pd.Series(rng.random(n) < 0.04)),
        "typ": pd.Series(rng.choice(types, n)),
        "size": pd.Series(rng.integers(1, 9, n)).astype("Int64").mask(
            pd.Series(rng.random(n) < 0.03)),
        "supp": pd.Series(rng.integers(0, 120, n)).astype("Int64").mask(
            pd.Series(rng.random(n) < 0.05)),
    }), 2)


@pytest.mark.smoke
def test_fused_count_distinct_matches_oracle(session, rng):
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)

    def q(s):
        return (d.select("brand", "typ", "size", "supp").distinct()
                .group_by("brand", "typ", "size")
                .agg(F.count("*").alias("cnt")))
    cpu = with_cpu_session(q)
    session.capture_plans = True
    tpu = with_tpu_session(q)
    session.capture_plans = False
    assert_frames_equal(tpu, cpu, ignore_order=True)
    plan = session.captured_plans[-1]
    assert any(type(n).__name__ == "TpuCountDistinctExec"
               for n in plan.walk()), "chain did not fuse"


def test_count_distinct_function_spelling(session, rng):
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)

    def q(s):
        return (d.group_by("brand")
                .agg(F.count_distinct(F.col("supp")).alias("nsupp")))
    cpu = with_cpu_session(q)
    tpu = with_tpu_session(q)
    assert_frames_equal(tpu, cpu, ignore_order=True)


def _fused(session):
    return [n for n in session.captured_plans[-1].walk()
            if type(n).__name__ == "TpuCountDistinctExec"]


COUNT_OF_A_KEY = {
    # F.count_distinct's expansion: level 2 is count(__dist)
    "count_distinct": (lambda d, F: d.group_by("brand", "typ").agg(
        F.count_distinct("supp").alias("cnt")), True),
    # the chain by hand with count(K) of the one key G1 adds
    "count_of_the_distinct_key": (lambda d, F: d.select(
        "brand", "supp").distinct().group_by("brand").agg(
        F.count("supp").alias("cnt")), True),
    # G1 adds two keys: count(size) is not a count of distinct tuples
    "count_of_one_of_two_rest_keys": (lambda d, F: d.select(
        "brand", "size", "supp").distinct().group_by("brand").agg(
        F.count("size").alias("cnt")), False),
    # a count of a grouping key
    "count_of_a_group_key": (lambda d, F: d.select(
        "brand", "supp").distinct().group_by("brand").agg(
        F.count("brand").alias("cnt")), False),
    # count(*) beside a key that holds nulls stays the other count
    "count_star": (lambda d, F: d.select(
        "brand", "supp").distinct().group_by("brand").agg(
        F.count("*").alias("cnt")), True),
}


@pytest.mark.parametrize("case", sorted(COUNT_OF_A_KEY))
def test_count_of_a_key_over_the_distinct(session, rng, case):
    """``_match_chain`` takes count(K) of the distinct key (a null K is not
    counted) and refuses a count of any other column."""
    from spark_rapids_tpu.sql import functions as F
    build, fuses = COUNT_OF_A_KEY[case]
    d = _df(session, rng)
    cpu = with_cpu_session(lambda s: build(d, F))
    session.capture_plans = True
    tpu = with_tpu_session(lambda s: build(d, F))
    session.capture_plans = False
    assert_frames_equal(tpu, cpu, ignore_order=True)
    fused = _fused(session)
    assert bool(fused) == fuses, session.captured_plans[-1].tree_string()
    if fused:
        assert fused[0].skip_null == (case != "count_star")


def _typed_keys(rng, n):
    """Group keys of every kind of image the kernel packs or carries."""
    def nulled(series, share=0.06):
        return series.mask(pd.Series(rng.random(n) < share))
    return {
        "dict_string": nulled(pd.Series(rng.choice(["x", "yy", "zzz"], n))),
        # too many values for a dictionary: prefix8 + length + hashes
        "plain_string": nulled(pd.Series(
            [f"name-{v:05d}-{'p' * (v % 23)}"
             for v in rng.integers(0, 700, n)])),
        "int8": nulled(pd.Series(rng.integers(-100, 100, n)).astype("Int8")),
        "int32": pd.Series(rng.integers(-5, 5, n).astype(np.int32))
        * 400_000_000,
        "int64": nulled(pd.Series(rng.integers(-3, 3, n) * (1 << 61))
                        .astype("Int64")),
        "bool": nulled(pd.Series(rng.random(n) < 0.5).astype("boolean")),
        "float64": pd.Series(rng.choice([0.0, 1.5, -2.25, np.inf, 1e300], n)),
        "date": pd.Series(pd.to_datetime("1995-01-01")
                          + pd.to_timedelta(rng.integers(-9, 9, n), "D")),
    }


TYPED_G2 = [("dict_string",), ("plain_string",), ("int8", "int32"),
            ("int64", "bool"), ("float64",), ("date", "dict_string"),
            # more words than one direct sort takes: the chained sorts
            ("int64", "plain_string", "float64", "int32")]


@pytest.mark.parametrize("g2", TYPED_G2, ids="+".join)
@pytest.mark.parametrize("form", ["count_distinct", "distinct_count"])
def test_every_kind_of_group_key_comes_back_as_it_went_in(session, rng, g2,
                                                          form):
    """The kernel decodes a group's keys from its packed sort words (codes,
    integers, booleans, dates) or reads them from a row of the group
    (floats, plain strings): both against the CPU path, nulls included."""
    from spark_rapids_tpu.sql import functions as F
    n = 4000
    keys = _typed_keys(rng, n)
    frame = pd.DataFrame({k: keys[k] for k in g2})
    frame["d"] = pd.Series(rng.integers(-40, 40, n)).astype("Int64").mask(
        pd.Series(rng.random(n) < 0.1))
    d = session.create_dataframe(frame, 3)

    def q(s):
        if form == "count_distinct":
            return d.group_by(*g2).agg(F.count_distinct("d").alias("c"))
        return (d.distinct().group_by(*g2).agg(F.count("*").alias("c")))
    cpu = with_cpu_session(q)
    session.capture_plans = True
    tpu = with_tpu_session(q)
    session.capture_plans = False
    assert _fused(session), session.captured_plans[-1].tree_string()
    assert_frames_equal(tpu, cpu, ignore_order=True)
    assert len(tpu) > 2 and tpu.c.max() >= 1


def test_an_exchange_above_the_fused_count_shrinks_its_output(session, rng):
    """The operator's output keeps its input's capacity with a group a
    row live: like an aggregate's, it is padding the exchange's collapse
    drops before the sort and the fetch."""
    from spark_rapids_tpu.exec.tpu import TpuShuffleExchangeExec
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)

    def q(s):
        return (d.group_by("typ").agg(F.count_distinct("supp").alias("c"))
                .order_by("c", "typ"))
    session.capture_plans = True
    tpu = with_tpu_session(q)
    session.capture_plans = False
    exchanges = [n for n in session.captured_plans[-1].walk()
                 if isinstance(n, TpuShuffleExchangeExec)]
    assert len(exchanges) == 1 and _fused(session)
    assert TpuShuffleExchangeExec._padded_producer(exchanges[0].children[0])
    assert_frames_equal(tpu, with_cpu_session(q))


def test_the_two_counts_differ_by_the_null_key(session, rng):
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)
    pairs = d.select("brand", "supp").distinct().group_by("brand")
    star = with_tpu_session(lambda s: pairs.agg(F.count("*").alias("c")))
    key = with_tpu_session(lambda s: pairs.agg(F.count("supp").alias("c")))
    both = star.merge(key, on="brand")
    assert len(both) == len(star) == len(key)
    assert (both.c_x - both.c_y == 1).all()   # every brand met a null supp


def test_global_count_distinct_not_fused(session, rng):
    """No outer grouping keys: the unfused final aggregate returns ONE
    row (count 0) on empty/fully-dead input via force_single_group; the
    fused kernel would return zero rows. Must not match (ADVICE r4 #1),
    and the empty-input shape must hold."""
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)

    def q(s):
        return d.distinct().group_by().agg(F.count("*").alias("cnt"))
    cpu = with_cpu_session(q)
    session.capture_plans = True
    tpu = with_tpu_session(q)
    session.capture_plans = False
    assert_frames_equal(tpu, cpu, ignore_order=True)
    assert not any(type(n).__name__ == "TpuCountDistinctExec"
                   for n in session.captured_plans[-1].walk()), \
        "global count-distinct must not fuse"

    # empty input: one row, count 0, on both paths
    e = session.create_dataframe(pd.DataFrame({
        "brand": pd.Series([], dtype=object),
        "supp": pd.Series([], dtype="Int64")}), 2)

    def qe(s):
        return e.distinct().group_by().agg(F.count("*").alias("cnt"))
    cpu_e = with_cpu_session(qe)
    tpu_e = with_tpu_session(qe)
    assert len(tpu_e) == 1 and int(tpu_e["cnt"].iloc[0]) == 0
    assert_frames_equal(tpu_e, cpu_e, ignore_order=True)


def test_computed_outer_grouping_not_fused(session, rng):
    """A computed outer grouping expr aliased to an inner output name
    must not fuse to grouping on the raw child column (ADVICE r4 #2)."""
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)

    def q(s):
        return (d.select("size", "supp").distinct()
                .group_by((F.col("size") + 1).alias("size"))
                .agg(F.count("*").alias("cnt")))
    cpu = with_cpu_session(q)
    session.capture_plans = True
    tpu = with_tpu_session(q)
    session.capture_plans = False
    assert_frames_equal(tpu, cpu, ignore_order=True)
    assert not any(type(n).__name__ == "TpuCountDistinctExec"
                   for n in session.captured_plans[-1].walk()), \
        "computed outer grouping must not fuse"


def test_computed_key_alias_collision_groups_and_types(session, rng):
    """group_by((expr).alias(existing_name)): must group on the computed
    values (not the shadowed raw column) and the output schema must carry
    the computed dtype (code-review r5: logical + AggPlan schemas read
    the raw column's dtype through the passthrough shadow)."""
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng, n=500)

    def q(s):
        return (d.group_by(F.length(F.col("brand")).alias("brand"))
                .agg(F.count("*").alias("cnt")))
    cpu = with_cpu_session(q)
    tpu = with_tpu_session(q)
    assert_frames_equal(tpu, cpu, ignore_order=True)
    assert str(tpu["brand"].dtype).lower().startswith("int")


def test_fuse_conf_gate(session, rng):
    from spark_rapids_tpu.sql import functions as F
    d = _df(session, rng)

    def q(s):
        return (d.distinct().group_by("brand", "typ")
                .agg(F.count("*").alias("cnt")))
    conf = {"spark.rapids.sql.agg.fuseCountDistinct": "false"}
    cpu = with_cpu_session(q)
    session.capture_plans = True
    tpu = with_tpu_session(q, conf=conf)
    session.capture_plans = False
    assert_frames_equal(tpu, cpu, ignore_order=True)
    assert not any(type(n).__name__ == "TpuCountDistinctExec"
                   for n in session.captured_plans[-1].walk())


@pytest.mark.parametrize("join_type,padded", [
    ("inner", False), ("left", False), ("leftsemi", True), ("leftanti", True)])
def test_an_operator_declares_its_padded_output(join_type, padded):
    """The exchange reads ``padded_output`` and names no operator: a join
    says it by its type, an aggregate, a limit and the fused count by
    their class, and any other operator inherits False."""
    from spark_rapids_tpu.exec.aggfuse import TpuCountDistinctExec
    from spark_rapids_tpu.exec.base import PhysicalPlan
    from spark_rapids_tpu.exec.tpu import (
        TpuFilterExec,
        TpuHashAggregateExec,
        TpuLocalLimitExec,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.exec.tpujoin import TpuShuffledHashJoinExec
    join = TpuShuffledHashJoinExec.__new__(TpuShuffledHashJoinExec)
    join.join_type = join_type
    join.children = []
    assert join.padded_output is padded
    assert TpuShuffleExchangeExec._padded_producer(join) is padded
    assert TpuCountDistinctExec.padded_output
    assert TpuHashAggregateExec.padded_output
    assert TpuLocalLimitExec.padded_output
    assert not PhysicalPlan.padded_output and not TpuFilterExec.padded_output
