"""One-pass hash aggregation (spark.rapids.sql.agg.hashAggEnabled,
docs/hashagg.md): the slot-table partial pass must be frame-identical to
the default sort+segment spelling and the CPU oracle across key dtypes,
nulls, dict-coded string keys, every reduction kind, and the recursed
VMEM-bound fan-out (agg.hash.maxTableSlots forced tiny)."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.sql import functions as F
from querytest import assert_frames_equal, with_cpu_session


def _click_frame(rng, n=2500):
    pdf = pd.DataFrame({
        "k": rng.integers(0, 700, n).astype(np.int64),
        "k2": rng.integers(-40, 40, n).astype(np.int64),
        "cat": rng.choice(["Books", "Home", "Shoes", "Toys"], n),
        "v": rng.random(n),
        "w": rng.integers(-100, 100, n),
        "flag": rng.random(n) < 0.3,
    })
    pdf.loc[rng.random(n) < 0.1, "k"] = None
    pdf["k"] = pdf["k"].astype("Int64")
    pdf.loc[rng.random(n) < 0.15, "w"] = None
    pdf["w"] = pdf["w"].astype("Int64")
    return pdf


def _all_kinds(df):
    return df.agg(
        F.sum("v").alias("sv"), F.count("*").alias("n"),
        F.count("w").alias("nw"), F.min("w").alias("mn"),
        F.max("w").alias("mx"), F.first("v").alias("fv"),
        F.max("flag").alias("af"))


def _hash_vs_sort_vs_cpu(session, q, extra_conf=None, sort_leg=True):
    # sort_leg=False skips the sort+segment spelling (its CPU equality
    # is already pinned by the cheaper cases) to keep tier-1 in budget
    cpu = with_cpu_session(q)
    session.set_conf("spark.rapids.sql.enabled", True)
    for k, v in (extra_conf or {}).items():
        session.set_conf(k, v)
    if sort_leg:
        session.set_conf("spark.rapids.sql.agg.hashAggEnabled", False)
        sort = q(session).collect()
        assert_frames_equal(sort, cpu, ignore_order=True, approx=True)
    session.set_conf("spark.rapids.sql.agg.hashAggEnabled", True)
    session.agg_ratio_cache.clear()
    hsh = q(session).collect()
    assert_frames_equal(hsh, cpu, ignore_order=True, approx=True)
    return hsh


def test_hash_agg_single_int_key_all_kinds(session, rng):
    pdf = _click_frame(rng)
    _hash_vs_sort_vs_cpu(
        session, lambda s: _all_kinds(
            s.create_dataframe(pdf, 4).group_by("k")))


def test_hash_agg_composite_keys_with_nulls(session, rng):
    pdf = _click_frame(rng)
    _hash_vs_sort_vs_cpu(
        session,
        lambda s: (s.create_dataframe(pdf, 4).group_by("k", "k2")
                    .agg(F.sum("v").alias("sv"),
                         F.count("*").alias("n"))))


def test_hash_agg_dict_string_key(session, rng):
    # dict-coded string keys enter the table as their exact per-batch
    # code image — no 8-byte prefix truncation caveat
    pdf = _click_frame(rng)
    _hash_vs_sort_vs_cpu(
        session,
        lambda s: (s.create_dataframe(pdf, 4).group_by("cat", "k2")
                    .agg(F.sum("v").alias("sv"),
                         F.min("w").alias("mn"))))


def test_hash_agg_forced_fanout_matches(session, rng):
    """agg.hash.maxTableSlots forced below the batch's table size: the
    partial pass recursively hash-partitions the batch into
    disjoint-key slices (exec/outofcore.split_batch_by_hash), runs the
    slot table per slice, and concatenates — journaled as hashAggSplit
    out-of-core events."""
    from spark_rapids_tpu.obs.events import EVENTS
    pdf = _click_frame(rng, n=5000)
    # the flight ring is bounded: cut by seq, not by index
    seq0 = max((ev.get("seq", 0) for ev in EVENTS.flight_events()),
               default=0)
    hsh = _hash_vs_sort_vs_cpu(
        session,
        lambda s: (s.create_dataframe(pdf, 2).group_by("k")
                    .agg(F.sum("v").alias("sv"),
                         F.count("*").alias("n"))),
        extra_conf={"spark.rapids.sql.agg.hash.maxTableSlots": 1024},
        sort_leg=False)
    assert len(hsh) > 0
    splits = [ev for ev in EVENTS.flight_events()
              if ev.get("seq", 0) > seq0 and ev["kind"] == "outOfCore"
              and ev.get("op") == "hashAggSplit"]
    assert splits, "forced fan-out never engaged"


def test_hash_agg_respects_conf_default_off(session, rng):
    # default-safe: without the conf the dispatch never takes the hash
    # branch (aggregate kernels carry no |hash marker)
    from spark_rapids_tpu.utils import kernelcache
    pdf = _click_frame(rng, n=800)
    session.set_conf("spark.rapids.sql.enabled", True)
    before = set(kernelcache.cache_snapshot())
    df = (session.create_dataframe(pdf, 2).group_by("k")
          .agg(F.sum("v").alias("sv")))
    df.collect()
    fresh = set(kernelcache.cache_snapshot()) - before
    assert not [k for k in fresh if k.startswith("aggupd")
                and "|hash" in k]
