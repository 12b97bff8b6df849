"""Out-of-core (larger-than-HBM) operator execution (exec/outofcore.py).

Tier-1 oracle pins at a TINY artificial working-set budget
(``spark.rapids.tpu.outOfCore.partitionBytes``): a join/agg/sort whose
measured working set exceeds the budget must complete via grace
partitioning + spill (spill events > 0, out-of-core operator counters
advancing) with results identical to the CPU oracle."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F
from querytest import assert_frames_equal, with_cpu_session, \
    with_tpu_session

OOC_CONF = {
    "spark.rapids.tpu.outOfCore.enabled": True,
    "spark.rapids.tpu.outOfCore.partitionBytes": 32 * 1024,
    "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
}


def _spills():
    return (REGISTRY.value("spill.events", direction="device_to_host")
            + REGISTRY.value("spill.events", direction="host_to_disk"))


def _ooc_ops(op):
    return REGISTRY.value("ooc.operators", op=op)


def _left(rng, n=2500):
    # sized to exceed the 32KB budget several times over while staying
    # tier-1-cheap (the budget, not the data, is what forces spilling)
    return pd.DataFrame({
        "k": rng.integers(0, 150, n).astype(np.int64),
        "v": rng.random(n),
        "s": np.array(["s%02d" % i for i in rng.integers(0, 40, n)]),
    })


def test_grace_join_matches_oracle_with_spill(session, rng):
    left = _left(rng)
    right = pd.DataFrame({"k": np.arange(150, dtype=np.int64),
                          "tag": ["t%d" % i for i in range(150)]})

    def q(s):
        return (s.create_dataframe(left, 3)
                .join(s.create_dataframe(right, 2), on="k", how="inner")
                .group_by("tag")
                .agg(F.sum("v").alias("sv"), F.count("*").alias("n")))

    cpu = with_cpu_session(q)
    s0, j0 = _spills(), _ooc_ops("join")
    tpu = with_tpu_session(q, conf=OOC_CONF)
    assert _ooc_ops("join") > j0, "grace join did not engage"
    assert _spills() > s0, "no spill events at a 32KB budget"
    assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)


@pytest.mark.slow  # extra outer-join coverage; the inner-join pin is tier-1
def test_grace_left_outer_join_preserves_unmatched(session, rng):
    # half the left keys have no match: outer preservation must survive
    # the hash partitioning (unmatched rows emit from whichever bucket
    # they land in)
    left = _left(rng)
    right = pd.DataFrame({"k": np.arange(0, 150, 2, dtype=np.int64)})
    right["tag"] = ["t%d" % i for i in range(len(right))]

    def q(s):
        return (s.create_dataframe(left, 2)
                .join(s.create_dataframe(right, 2), on="k", how="left")
                .group_by("s")
                .agg(F.count("*").alias("n"), F.sum("v").alias("sv")))

    cpu = with_cpu_session(q)
    tpu = with_tpu_session(q, conf=OOC_CONF)
    assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)


def test_external_sort_matches_oracle_exactly(session, rng):
    df = _left(rng)

    def q(s):
        return s.create_dataframe(df, 3).order_by("v")

    cpu = with_cpu_session(q)
    s0, o0 = _spills(), _ooc_ops("sort")
    tpu = with_tpu_session(q, conf=OOC_CONF)
    assert _ooc_ops("sort") > o0, "external sort did not engage"
    assert _spills() > s0
    # ORDER matters: the bucketed external sort must emit the exact
    # globally sorted sequence, not just the right multiset
    assert_frames_equal(tpu, cpu, ignore_order=False, approx=True)


def test_spillable_agg_matches_oracle_with_spill(session, rng):
    n = 3000
    df = pd.DataFrame({
        "k": rng.integers(0, 1500, n).astype(np.int64),
        "v": rng.random(n),
        "w": rng.integers(-50, 50, n),
    })

    def q(s):
        return (s.create_dataframe(df, 3).group_by("k")
                .agg(F.sum("v").alias("sv"), F.count("*").alias("n"),
                     F.max("w").alias("mw")))

    cpu = with_cpu_session(q)
    s0, a0 = _spills(), _ooc_ops("aggregate")
    tpu = with_tpu_session(q, conf=OOC_CONF)
    assert _ooc_ops("aggregate") > a0, "spillable agg did not engage"
    assert _spills() > s0
    assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)


def test_outofcore_default_off_leaves_plans_alone(session, rng):
    # acceptance: transport/out-of-core selection defaults OFF —
    # the ooc counters must not move and results stay correct
    df = _left(rng, 2000)

    def q(s):
        return (s.create_dataframe(df, 2).group_by("s")
                .agg(F.sum("v").alias("sv")))

    before = sum(_ooc_ops(op) for op in ("join", "sort", "aggregate"))
    cpu = with_cpu_session(q)
    tpu = with_tpu_session(q)
    assert sum(_ooc_ops(op)
               for op in ("join", "sort", "aggregate")) == before
    assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)


def test_choose_fanout_from_measured_sizes(session):
    from types import SimpleNamespace
    from spark_rapids_tpu.exec import outofcore as ooc
    ctx = SimpleNamespace(conf=session.conf, session=session)
    assert ooc.choose_fanout(ctx, 10 << 20, 1 << 20) == 16
    assert ooc.choose_fanout(ctx, 3 << 20, 1 << 20) == 4
    assert ooc.choose_fanout(ctx, 100, 1 << 20) == 2   # floor
    assert ooc.choose_fanout(ctx, 1 << 40, 1) == 64    # clamp
    session.set_conf("spark.rapids.tpu.outOfCore.fanout", 8)
    try:
        assert ooc.choose_fanout(ctx, 10 << 20, 1 << 20) == 8
    finally:
        session.reset_conf()


def test_level_hash_changes_between_levels(session, rng):
    # grace recursion relies on a different partition assignment per
    # level while equal keys still co-locate at every level
    import jax
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.exec.outofcore import hash_split_kernel
    df = pd.DataFrame({"k": rng.integers(0, 1000, 512).astype(np.int64),
                       "v": rng.random(512)})
    batch = DeviceBatch.from_pandas(df)
    counts = []
    for level in range(3):
        _sorted, c = hash_split_kernel([0], 4, level)(batch)
        counts.append(tuple(int(x) for x in jax.device_get(c)))
        assert sum(counts[-1]) == len(df)
    assert len(set(counts)) > 1, "levels produced identical partitions"
