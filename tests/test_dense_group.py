"""Bounded-int composite grouping keys (spark.rapids.sql.agg.denseKeys,
ops/aggregate.dense_composite): advisory scan stats give each int key a
slot range; the kernel verifies on device, and a stale-stats miss
re-executes the query without dense grouping (deferred speculation
verification) and blocklists the plan. Pins: correctness with stats
present, correctness with DELIBERATELY WRONG (too-narrow) stats, null
keys, and multi-key composites."""

import numpy as np
import pandas as pd
import pytest

from tests.querytest import (
    assert_frames_equal, with_cpu_session, with_tpu_session,
)


def _orders(session, rng, n=6000):
    return session.create_dataframe(pd.DataFrame({
        "okey": pd.Series(rng.integers(1000, 9000, n)).astype("Int64")
                  .mask(pd.Series(rng.random(n) < 0.03)),
        "skey": pd.Series(rng.integers(0, 40, n)).astype("Int64"),
        "qty": rng.uniform(1.0, 50.0, n),
    }), 2)


def _q(o):
    from spark_rapids_tpu.sql import functions as F
    return (o.group_by("okey").agg(
        F.sum("qty").alias("sq"), F.count("*").alias("n"),
        F.max("qty").alias("mx")))


@pytest.mark.smoke
def test_dense_single_key_matches_oracle(session, rng):
    # dense grouping engages from the SECOND execution of a plan (the
    # first records the fingerprint while scan stats fill in): both the
    # generic first run and the dense later runs must match the oracle
    o = _orders(session, rng)
    cpu = with_cpu_session(lambda s: _q(o))
    reruns0 = session.capacity_spec_reruns
    for _ in range(3):
        tpu = with_tpu_session(lambda s: _q(o))
        assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)
    assert session.capacity_spec_reruns == reruns0, \
        "healthy stats must never trigger a re-execution"


def test_dense_multi_key_with_nulls(session, rng):
    from spark_rapids_tpu.sql import functions as F
    o = _orders(session, rng)

    def q(s):
        return (o.group_by("okey", "skey")
                .agg(F.sum("qty").alias("sq"), F.count("*").alias("n")))
    cpu = with_cpu_session(q)
    for _ in range(3):
        tpu = with_tpu_session(q)
        assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)


def test_dense_stale_stats_fall_back_exactly(session, rng):
    """Corrupt the advisory bounds to a range that excludes most keys:
    the deferred verification must catch the dense miss, transparently
    re-execute without dense grouping (still oracle-exact), and
    blocklist the plan so the NEXT run does not re-pay the re-execution."""
    o = _orders(session, rng)
    cpu = with_cpu_session(lambda s: _q(o))
    first = with_tpu_session(lambda s: _q(o))
    assert_frames_equal(first, cpu, ignore_order=True, approx=True)
    # the registry now has real bounds; shift them to a large-but-wrong
    # window so every live key falls outside the advertised range (a
    # tiny range would fall under the low-cardinality floor and
    # legitimately skip dense instead of exercising the miss path)
    touched = []
    for name, (lo, hi) in list(session.column_stats.items()):
        if name == "okey":
            session.column_stats[name] = (hi + 10000, hi + 40000)
            touched.append(name)
    assert touched, "scan stats never recorded the group key"
    reruns0 = session.capacity_spec_reruns
    bl0 = len(session.capacity_spec_blocklist)
    second = with_tpu_session(lambda s: _q(o))
    assert_frames_equal(second, cpu, ignore_order=True, approx=True)
    assert session.capacity_spec_reruns == reruns0 + 1
    assert len(session.capacity_spec_blocklist) > bl0
    third = with_tpu_session(lambda s: _q(o))
    assert_frames_equal(third, cpu, ignore_order=True, approx=True)
    assert session.capacity_spec_reruns == reruns0 + 1, \
        "blocklisted plan must not re-execute again"


def test_dense_conf_gate(session, rng):
    o = _orders(session, rng)
    conf = {"spark.rapids.sql.agg.denseKeys": "false"}
    cpu = with_cpu_session(lambda s: _q(o))
    tpu = with_tpu_session(lambda s: _q(o), conf=conf)
    assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)


def _reference_groups(df, kinds):
    """pandas: the dense reducer's answer for ``kinds`` over column v."""
    out = {}
    for key, g in df.groupby(["a", "b"], dropna=False, sort=False):
        v = g["v"]
        row = {"sum": v.sum(min_count=1), "min": v.min(), "max": v.max(),
               "count_valid": int(v.notna().sum()),
               "first": v.iloc[0], "last": v.iloc[-1],
               "first_valid": v.dropna().iloc[0] if v.notna().any()
               else np.nan,
               "last_valid": v.dropna().iloc[-1] if v.notna().any()
               else np.nan}
        out[tuple(None if pd.isna(k) else int(k) for k in key)] = \
            [row[k] for k in kinds]
    return out


@pytest.mark.parametrize("kinds", [
    ("sum",), ("sum", "count_valid", "min", "max"),
    ("first", "last", "first_valid", "last_valid"),
    ("sum", "min", "max", "count_valid", "first", "last", "first_valid",
     "last_valid")], ids=lambda k: "+".join(k))
def test_dense_reducer_every_kind_against_pandas(rng, kinds):
    """ops/aggregate._dense_payload_reduce on its own: two int keys with
    nulls, a float64 input with nulls, dead rows behind the live ones, a
    run of 300 rows beside runs of one; a few inputs ride the sort and
    many are gathered (rowops.sort_carrying), and both give pandas' groups."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
    from spark_rapids_tpu.ops import aggregate as agg_ops
    n = 3000
    a = pd.Series(rng.integers(100, 160, n)).astype("Int64")
    a[:300] = 100                       # with b below: one long run
    b = pd.Series(rng.integers(-5, 5, n)).astype("Int64")
    b[:300] = 0
    a = a.mask(pd.Series(rng.random(n) < 0.05))
    v = pd.Series(rng.uniform(-100.0, 100.0, n)).astype("Float64").mask(
        pd.Series(rng.random(n) < 0.3))
    df = pd.DataFrame({"a": a, "b": b, "v": v})
    batch = DeviceBatch.from_pandas(df, capacity=4096)
    fdt = batch.schema.dtypes[2]
    idt = batch.schema.dtypes[0]
    reductions = [(k, 2, idt if k == "count_valid" else fdt) for k in kinds]
    schema = Schema(["a", "b"] + list(kinds),
                    [idt, idt] + [dt for _, _, dt in reductions])
    los, sizes = jnp.asarray([100, -5], jnp.int64), (64, 16)
    live = batch.row_mask()
    comp, ok = agg_ops.dense_composite(batch, [0, 1], los, sizes, live)
    out = agg_ops._dense_payload_reduce(
        batch, [0, 1], reductions, schema, live, comp, los, sizes)
    assert bool(ok)
    got = out.to_pandas()
    want = _reference_groups(df, kinds)
    assert len(got) == len(want)
    for row in got.itertuples(index=False):
        key = tuple(None if pd.isna(k) else int(k) for k in row[:2])
        for kind, g, w in zip(kinds, row[2:], want[key]):
            if pd.isna(w):
                assert pd.isna(g), (key, kind, g)
            else:
                assert g == pytest.approx(w, rel=1e-12), (key, kind)


def test_segmented_scan_is_a_run_sum_at_each_runs_last_row(rng):
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.aggregate import _segmented_scan
    lengths = np.array([1, 1, 7, 1, 64, 2, 1, 129, 3])
    ids = np.repeat(np.arange(len(lengths)), lengths)
    x = rng.uniform(-5, 5, len(ids))
    first = np.r_[True, ids[1:] != ids[:-1]]
    start = jax.lax.cummax(jnp.where(jnp.asarray(first),
                                     jnp.arange(len(ids), dtype=jnp.int32),
                                     0))
    got = np.asarray(_segmented_scan(jnp.add, jnp.asarray(x), start,
                                     jnp.asarray(lengths.max(), jnp.int32)))
    ends = np.cumsum(lengths) - 1
    want = np.array([x[ids == g].sum() for g in range(len(lengths))])
    np.testing.assert_allclose(got[ends], want, rtol=1e-12)
    np.testing.assert_allclose(got, np.concatenate(
        [np.cumsum(x[ids == g]) for g in range(len(lengths))]), rtol=1e-12)
