"""Adaptive partial-aggregation skip (the session-level analogue of the
reference's AQE-style statistics): a partial pass that barely reduces is
learned per aggregate signature and skipped from batch 0 on later
executions, with rows projected straight into the partial layout
(ops/aggregate.py aggregate_passthrough). Correctness is mode-invariant:
the final aggregate reduces whatever layout arrives."""

import numpy as np
import pandas as pd

from spark_rapids_tpu.sql import functions as F
from querytest import assert_frames_equal, with_cpu_session


def _hicard(rng, n=40000):
    return pd.DataFrame({
        "k": rng.integers(0, n, n).astype(np.int64),  # ~unique keys
        "v": rng.random(n),
        "w": rng.integers(-100, 100, n),
    })


def test_ratio_cache_learns_and_skips(session, rng):
    pdf = _hicard(rng)

    def q(s):
        return (s.create_dataframe(pdf, 4)
                 .group_by("k")
                 .agg(F.sum("v").alias("sv"), F.count("*").alias("n"),
                      F.min("w").alias("mw")))

    cpu = with_cpu_session(q)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.agg_ratio_cache.clear()
    tpu1 = q(session).collect()
    # the high-cardinality partial pass learned its poor reduction ratio
    assert session.agg_ratio_cache, "ratio never learned"
    assert max(r for r, _uses in session.agg_ratio_cache.values()) > 0.85, \
        session.agg_ratio_cache
    # second execution skips the partial pass from batch 0 (passthrough
    # projection) and still matches
    tpu2 = q(session).collect()
    assert_frames_equal(tpu1, cpu, ignore_order=True, approx=True)
    assert_frames_equal(tpu2, cpu, ignore_order=True, approx=True)


def test_low_cardinality_never_learns_poor(session, rng):
    pdf = pd.DataFrame({
        "k": rng.integers(0, 5, 20000).astype(np.int64),
        "v": rng.random(20000),
    })

    def q(s):
        return (s.create_dataframe(pdf, 4)
                 .group_by("k").agg(F.sum("v").alias("sv")))

    cpu = with_cpu_session(q)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.agg_ratio_cache.clear()
    tpu = q(session).collect()
    assert_frames_equal(tpu, cpu, ignore_order=True, approx=True)
    # bounded-cardinality paths shrink capacity, proving reduction with
    # no sync — nothing poor may be recorded for this signature
    assert all(r <= 0.85 for r, _uses in session.agg_ratio_cache.values()), \
        session.agg_ratio_cache


def test_skip_with_fused_filter_matches(session, rng):
    # the fused pre-filter degrades to a row compaction inside the
    # passthrough; differential across both executions
    pdf = _hicard(rng)

    def q(s):
        return (s.create_dataframe(pdf, 4)
                 .filter(F.col("w") > 0)
                 .group_by("k").agg(F.sum("v").alias("sv")))

    cpu = with_cpu_session(q)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.agg_ratio_cache.clear()
    tpu1 = q(session).collect()
    tpu2 = q(session).collect()
    assert_frames_equal(tpu1, cpu, ignore_order=True, approx=True)
    assert_frames_equal(tpu2, cpu, ignore_order=True, approx=True)


# ---------------------------------------------------------------------------
# Runtime skip (spark.rapids.sql.agg.runtimeSkip, default on): the
# AQE-style replacement for the first-batch-only heuristic — decisions
# come from the measured cumulative reduction rate as batches stream and
# are journaled with that rate.
# ---------------------------------------------------------------------------

def _skip_on_off_equal(session, pdf, q):
    cpu = with_cpu_session(q)
    session.set_conf("spark.rapids.sql.enabled", True)
    for on in (True, False):
        session.set_conf("spark.rapids.sql.agg.runtimeSkip", on)
        session.agg_ratio_cache.clear()
        first = q(session).collect()   # measures / legacy-heuristic run
        second = q(session).collect()  # cached-decision run
        assert_frames_equal(first, cpu, ignore_order=True, approx=True)
        assert_frames_equal(second, cpu, ignore_order=True, approx=True)


def test_runtime_skip_on_off_high_cardinality(session, rng):
    pdf = _hicard(rng, n=12000)
    _skip_on_off_equal(session, pdf, lambda s: (
        s.create_dataframe(pdf, 4).group_by("k")
         .agg(F.sum("v").alias("sv"), F.count("*").alias("n"))))


def test_runtime_skip_on_off_low_cardinality(session, rng):
    pdf = pd.DataFrame({
        "k": rng.integers(0, 4, 6000).astype(np.int64),
        "v": rng.random(6000)})
    _skip_on_off_equal(session, pdf, lambda s: (
        s.create_dataframe(pdf, 4).group_by("k")
         .agg(F.sum("v").alias("sv"), F.max("v").alias("mx"))))


def test_runtime_skip_on_off_all_null_keys(session, rng):
    # every key null: SQL still produces the one null group
    pdf = pd.DataFrame({
        "k": pd.array([None] * 2000, dtype="Int64"),
        "v": rng.random(2000)})
    _skip_on_off_equal(session, pdf, lambda s: (
        s.create_dataframe(pdf, 4).group_by("k")
         .agg(F.sum("v").alias("sv"), F.count("*").alias("n"))))


def test_runtime_skip_on_off_empty_batches(session, rng):
    # more partitions than rows: some batches stream through empty
    pdf = pd.DataFrame({
        "k": np.asarray([1, 2], np.int64),
        "v": np.asarray([0.5, 1.5])})
    _skip_on_off_equal(session, pdf, lambda s: (
        s.create_dataframe(pdf, 4).group_by("k")
         .agg(F.sum("v").alias("sv"))))


def test_skip_decision_journaled_with_measured_rate(session, rng):
    """The aggSkipDecision event is the audit trail: a first execution
    decides from the MEASURED cumulative reduction rate (carried on the
    event), later executions decide from the session cache (source
    'cache')."""
    from spark_rapids_tpu.obs.events import EVENTS
    pdf = _hicard(rng)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.agg.runtimeSkip", True)
    session.agg_ratio_cache.clear()
    # ONE dataframe (the ratio cache is keyed on the data-uid-stamped
    # plan fingerprint — a fresh create_dataframe mints a fresh key)
    df = (session.create_dataframe(pdf, 4).group_by("k")
          .agg(F.sum("v").alias("sv")))
    # the flight ring is bounded: cut by seq, not by index
    seq0 = max((ev.get("seq", 0) for ev in EVENTS.flight_events()),
               default=0)
    df.collect()
    first = [ev for ev in EVENTS.flight_events()
             if ev.get("seq", 0) > seq0
             and ev["kind"] == "aggSkipDecision"]
    assert first, "first execution journaled no decision"
    # the first partition decides from measurement; later partitions of
    # the same execution already see its recorded ratio
    assert first[0]["source"] == "measured"
    for ev in first:
        # ~unique keys: the measured rate is near 1 and above threshold
        assert 0.85 < ev["measuredRatio"] <= 1.0, ev
        assert ev["decision"] == "skip"
        assert 0.0 < ev["threshold"] < 1.0
    assert first[0]["batches"] >= 1
    # the flight ring is bounded: cut by seq, not by index
    seq0 = max((ev.get("seq", 0) for ev in EVENTS.flight_events()),
               default=0)
    df.collect()
    second = [ev for ev in EVENTS.flight_events()
              if ev.get("seq", 0) > seq0
              and ev["kind"] == "aggSkipDecision"]
    assert second and all(ev["source"] == "cache" for ev in second)
    assert all(ev["decision"] == "skip" for ev in second)
