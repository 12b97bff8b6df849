"""Scan pipeline tests (sql/scan_pipeline.py): ordering under prefetch,
exception propagation, early-exit cancellation, depth bound, pandas-vs-
direct decode value equality, serial-rollback equivalence."""

import contextlib
import gc
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.scan_pipeline import (
    ScanPrefetcher, build_partitions, decode_pool,
)

pytestmark = pytest.mark.smoke


def _write_parquet(tmp_path, name="t.parquet", rows=600, row_group=50):
    rng = np.random.default_rng(3)
    df = pd.DataFrame({
        "i": np.arange(rows, dtype=np.int64),
        "f": rng.random(rows),
        "b": (np.arange(rows) % 3 == 0),
        "s": [f"str{k % 13}" for k in range(rows)],
        "ni": pd.array([None if k % 7 == 0 else k for k in range(rows)],
                       dtype="Int64"),
    })
    p = tmp_path / name
    df.to_parquet(str(p), row_group_size=row_group, index=False)
    return str(p), df


# --------------------------------------------------------------------------
# ScanPrefetcher unit level
# --------------------------------------------------------------------------

def _tasks(n, decode=None, record=None):
    def mk(i):
        def fn():
            if record is not None:
                record.append(i)
            if decode is not None:
                return decode(i)
            return pd.DataFrame({"v": [i]})
        return fn
    return [(None, mk(i)) for i in range(n)]


def test_prefetcher_order_preserved():
    pf = ScanPrefetcher(_tasks(16), depth=4, pool=decode_pool(3),
                        max_bytes=1 << 30)
    got = [int(pf.get(i)["v"][0]) for i in range(16)]
    assert got == list(range(16))


def test_prefetcher_exception_propagates_at_failing_split():
    def decode(i):
        if i == 3:
            raise ValueError("split 3 is poisoned")
        return pd.DataFrame({"v": [i]})
    pf = ScanPrefetcher(_tasks(16, decode=decode), depth=3,
                        pool=decode_pool(3), max_bytes=1 << 30)
    assert int(pf.get(0)["v"][0]) == 0
    assert int(pf.get(1)["v"][0]) == 1
    assert int(pf.get(2)["v"][0]) == 2
    with pytest.raises(ValueError, match="split 3 is poisoned"):
        pf.get(3)
    # after the first failure the window stops growing: consuming later
    # splits submits only themselves (get(3)'s window reached split 6)
    for i in range(4, 8):
        assert int(pf.get(i)["v"][0]) == i
    assert 8 not in pf._submitted


def test_prefetcher_depth_honored():
    """While the consumer sits on split 0, at most depth splits beyond it
    may start decoding."""
    started = []
    gate = threading.Event()

    def decode(i):
        started.append(i)
        gate.wait(timeout=10)
        return pd.DataFrame({"v": [i]})
    depth = 2
    pf = ScanPrefetcher(_tasks(10, decode=decode), depth=depth,
                        pool=decode_pool(4), max_bytes=1 << 30)
    t = threading.Thread(target=lambda: pf.get(0), daemon=True)
    t.start()
    time.sleep(0.3)  # let the window submit and workers start
    assert max(started, default=0) <= depth
    assert max(pf._submitted) <= depth
    # ...and no fewer: the full window 0..depth must actually be
    # SUBMITTED while the consumer blocks (a prefetcher degraded to
    # serial decode-on-get would still pass every upper-bound and
    # ordering assertion in this file via get()'s inline fallback)
    assert pf._submitted == set(range(depth + 1))
    gate.set()
    t.join(timeout=10)
    assert not t.is_alive()
    # prefetch genuinely ran ahead: splits beyond 0 decoded on the pool
    assert set(started) == set(range(depth + 1))


def test_prefetcher_cancel_leaves_no_work(session):
    """Early consumer exit: unstarted decodes are cancelled, in-flight
    ones drain, no decoded-frame references survive, no device buffers
    leak (LeakTracker clean), and the pool thread count stays bounded."""
    from spark_rapids_tpu.memory.leak import TRACKER
    live_before = TRACKER.live_count
    threads_before = threading.active_count()
    for _ in range(5):
        pf = ScanPrefetcher(_tasks(32), depth=8, pool=decode_pool(3),
                            max_bytes=1 << 30)
        pf.get(0)
        pf.cancel()
        assert pf.drain(timeout=10)
        assert not pf._futures and pf._pending_bytes == 0
        del pf
    gc.collect()
    assert TRACKER.live_count == live_before
    # the shared daemon pool is bounded; repeated early exits must not
    # keep spawning threads
    assert threading.active_count() <= threads_before + 3


# --------------------------------------------------------------------------
# a split's life: submitted, started, decoded, taken
# --------------------------------------------------------------------------

_LIFE = ("scan.prefetch.splits", "scan.prefetch.hits",
         "scan.prefetch.stallTime", "scan.prefetch.queueTime",
         "scan.prefetch.activeTime")


def _life_counts():
    """(splits, hits, stalls, decodes a worker took, activeTime records)."""
    from spark_rapids_tpu.obs.metrics import REGISTRY
    return (REGISTRY.counter(_LIFE[0]).value, REGISTRY.counter(_LIFE[1]).value,
            *(REGISTRY.timer(n).count for n in _LIFE[2:]))


def _life_seconds(name):
    from spark_rapids_tpu.obs.metrics import REGISTRY
    return REGISTRY.timer(name).total_seconds


@contextlib.contextmanager
def _traced():
    """The tracer on for the block; yields a callable that returns the
    events so far. Off and empty afterwards, whatever the block did."""
    from spark_rapids_tpu.obs.trace import TRACER
    TRACER.configure(True)
    TRACER.clear()
    try:
        yield TRACER.events
    finally:
        TRACER.configure(False)
        TRACER.clear()


class _Gated:
    """Hand-made tasks whose decode of split i waits on ``gates[i]``, and
    a ``wait`` for the prefetcher that opens the gate of the split it
    waits for only once the consumer is inside the wait: a closed gate is
    a stall and an open one (after ``drain``) a hit, whatever the clock
    does."""

    def __init__(self, monkeypatch, n, hold_s=0.0):
        from spark_rapids_tpu.sql import scan_pipeline
        self.gates = [threading.Event() for _ in range(n)]
        self.tasks = _tasks(n, decode=self._decode)
        self.waiting_for = None
        real_wait = scan_pipeline.wait

        def wait(futures, timeout=10, return_when=None):
            if return_when is None:     # drain(), not a stall
                return real_wait(futures, timeout=timeout)
            time.sleep(hold_s)
            self.gates[self.waiting_for].set()
            return real_wait(futures, timeout=timeout,
                             return_when=return_when)
        monkeypatch.setattr(scan_pipeline, "wait", wait)

    def _decode(self, i):
        assert self.gates[i].wait(timeout=10)
        return pd.DataFrame({"v": [i]})

    def get(self, pf, i):
        self.waiting_for = i
        return int(pf.get(i)["v"][0])


def _life_scenario(monkeypatch):
    """Six splits, depth 2, four workers: stall, hit, hit, stall, an
    inline decode of a split taken before, stall, stall. Returns the
    growth of (splits, hits, stalls, started, activeTime records)."""
    g = _Gated(monkeypatch, 6)
    pf = ScanPrefetcher(g.tasks, depth=2, pool=decode_pool(4),
                        max_bytes=1 << 30)
    before = _life_counts()
    assert g.get(pf, 0) == 0            # nothing decoded yet: a stall
    g.gates[1].set()
    g.gates[2].set()
    assert pf.drain(timeout=10)         # 1 and 2 are decoded
    assert g.get(pf, 1) == 1            # a hit (and submits 3)
    assert g.get(pf, 2) == 2            # a hit (and submits 4)
    assert g.get(pf, 3) == 3            # its gate is closed: a stall
    assert g.get(pf, 1) == 1            # taken before: decoded inline
    assert g.get(pf, 4) == 4            # a stall
    assert g.get(pf, 5) == 5            # a stall
    return tuple(b - a for a, b in zip(before, _life_counts()))


def test_hits_stalls_and_inline_decodes_add_up_to_the_splits(monkeypatch):
    first = _life_scenario(monkeypatch)
    splits, hits, stalls, started, active = first
    inline = 1
    assert (splits, hits, stalls) == (7, 2, 4)
    assert hits + stalls + inline == splits
    assert started == 6 and active == splits
    # the counts repeat exactly
    assert _life_scenario(monkeypatch) == first


def test_stall_and_decode_spans_say_what_the_wait_met(monkeypatch):
    with _traced() as so_far:
        _life_scenario(monkeypatch)
        events = so_far()
    stalls = {e["args"]["split"]: e["args"] for e in events
              if e["name"] == "scan.prefetch.stall"}
    assert sorted(stalls) == [0, 3, 4, 5]
    # split 0 waited with the whole window 0..2 undecoded; 3 with 3 and 4
    # (5 is submitted by get(3)'s window too); the last with itself alone
    assert [stalls[i]["inflight"] for i in (0, 3, 4, 5)] == [3, 3, 2, 1]
    for a in stalls.values():
        assert a["submitted_ago_s"] >= 0 and a["running"] in (True, False)
    decodes = [e["args"] for e in events if e["name"] == "scan.decode"]
    assert sorted(a["split"] for a in decodes) == list(range(6))
    assert all(a["queued_s"] >= 0 for a in decodes)
    takes = [e["args"] for e in events if e["name"] == "scan.host.take"]
    # two pieces a split that had a future, one for the inline decode
    assert len(takes) == 2 * 6 + 1
    assert sum(a.get("submitted", 0) for a in takes) == 6
    assert sorted(a["split"] for a in takes if a.get("hit")) == [1, 2]


@pytest.mark.parametrize("workers, at_least, at_most", [
    # a window no wider than the pool: no split waits for a worker
    (4, 0.0, 1e-3),
    # one worker under a window of three: splits 1 and 2 queue behind
    # split 0's decode, which is held for 0.2 s
    (1, 0.2, None)], ids=["window-fits-pool", "one-worker"])
def test_queue_time_says_when_the_pool_is_the_limit(monkeypatch, workers,
                                                    at_least, at_most):
    g = _Gated(monkeypatch, 3, hold_s=0.2)
    g.gates[1].set()
    g.gates[2].set()
    pf = ScanPrefetcher(g.tasks, depth=2, pool=decode_pool(workers),
                        max_bytes=1 << 30)
    before = _life_seconds("scan.prefetch.queueTime")
    with _traced() as so_far:
        assert [g.get(pf, i) for i in range(3)] == [0, 1, 2]
        queued = sorted(e["args"]["queued_s"] for e in so_far()
                        if e["name"] == "scan.decode")
    grown = _life_seconds("scan.prefetch.queueTime") - before
    assert grown == pytest.approx(sum(queued), abs=1e-4)
    assert grown >= at_least
    if at_most is not None:
        assert queued[1] < at_most      # the median of the three


def test_active_time_runs_from_first_to_last_get_and_ends_on_cancel():
    def active():
        return _life_seconds("scan.prefetch.activeTime")
    pf = ScanPrefetcher(_tasks(8), depth=2, pool=decode_pool(3),
                        max_bytes=1 << 30)
    before = active()
    t0 = time.perf_counter()
    pf.get(0)
    time.sleep(0.05)                    # the consumer's own work counts
    pf.get(1)
    wall = time.perf_counter() - t0
    assert 0.05 <= active() - before <= wall
    time.sleep(0.05)
    pf.cancel()                         # ends it, the pause included
    ended = active() - before
    assert ended >= 0.1
    time.sleep(0.02)
    pf.cancel()
    assert pf.drain(timeout=10)
    assert active() - before == ended   # not active after the cancel


# --------------------------------------------------------------------------
# build_partitions (the source-facing surface)
# --------------------------------------------------------------------------

class _Ctx:
    """Minimal ExecContext stand-in for build_partitions."""

    def __init__(self, conf):
        self.conf = conf


def _conf(depth):
    from spark_rapids_tpu.config.conf import TpuConf
    return TpuConf({"spark.rapids.sql.scan.prefetchDepth": depth})


def test_build_partitions_serial_matches_pipelined():
    for depth in (0, 3):
        parts = build_partitions(_Ctx(_conf(depth)), _tasks(7))
        got = [int(df["v"][0]) for p in parts for df in p()]
        assert got == list(range(7))


def test_input_file_context_cleared_on_error_and_abandon():
    from spark_rapids_tpu.exec import taskctx

    def decode(i):
        if i == 1:
            raise RuntimeError("decode boom")
        return pd.DataFrame({"v": [i]})
    for depth in (0, 2):
        tasks = [(f"/data/f{i}", (lambda i=i: decode(i)))
                 for i in range(3)]
        parts = build_partitions(_Ctx(_conf(depth)), tasks)
        # normal consumption publishes the split's file around the yield
        it = parts[0]()
        next(it)
        assert taskctx.input_file() == "/data/f0"
        it.close()  # abandoned: the file context must not leak
        assert taskctx.input_file() == ""
        # a failing decode must also leave no stale file context
        with pytest.raises(RuntimeError, match="decode boom"):
            list(parts[1]())
        assert taskctx.input_file() == ""


def test_early_exit_cancels_pending_decodes():
    started = []
    slow = threading.Event()

    def decode(i):
        started.append(i)
        if i > 0:
            slow.wait(timeout=5)
        return pd.DataFrame({"v": [i]})
    tasks = _tasks(24, decode=decode)
    parts = build_partitions(_Ctx(_conf(4)), tasks)
    it = parts[0]()
    next(it)
    it.close()  # GeneratorExit -> prefetcher.cancel()
    slow.set()
    time.sleep(0.3)
    # cancellation keeps the tail of the scan from ever decoding
    assert len(started) < len(tasks)


# --------------------------------------------------------------------------
# end-to-end over file sources
# --------------------------------------------------------------------------

def test_parquet_order_and_values_all_depths(session, tmp_path):
    p, df = _write_parquet(tmp_path)
    outs = {}
    for depth in (0, 1, 4):
        session.set_conf("spark.rapids.sql.scan.prefetchDepth", depth)
        outs[depth] = session.read.parquet(p).collect()
    for depth, out in outs.items():
        assert out["i"].tolist() == df["i"].tolist(), \
            f"row order broken at depth {depth}"
        assert out["s"].tolist() == df["s"].tolist()
        assert out["ni"].isna().tolist() == df["ni"].isna().tolist()


def test_direct_decode_value_equality(session, tmp_path):
    """pandas-vs-direct decode equality across dtypes: nullable ints,
    strings, bools, floats, hive partition keys."""
    d = tmp_path / "hive"
    rng = np.random.default_rng(5)
    for key in (1, 2):
        sub = d / f"k={key}"
        sub.mkdir(parents=True)
        pd.DataFrame({
            "i": np.arange(100, dtype=np.int64) * key,
            "f32": rng.random(100).astype(np.float32),
            "bo": (np.arange(100) % 2 == 0),
            "s": [None if j % 9 == 0 else f"v{j}" for j in range(100)],
            "ni": pd.array([None if j % 5 == 0 else j for j in range(100)],
                           dtype="Int32"),
        }).to_parquet(str(sub / "part.parquet"), row_group_size=25,
                      index=False)
    res = {}
    for direct in (True, False):
        session.set_conf("spark.rapids.sql.scan.directDecode", direct)
        res[direct] = session.read.parquet(str(d)).collect()
    a, b = res[True], res[False]
    assert list(a.columns) == list(b.columns)
    for c in a.columns:
        av, bv = a[c], b[c]
        assert av.isna().tolist() == bv.isna().tolist(), c
        ok = ~av.isna()
        if av.dtype.kind == "f" or str(av.dtype).startswith("Float"):
            np.testing.assert_allclose(
                av[ok].to_numpy(dtype=float), bv[ok].to_numpy(dtype=float))
        else:
            assert av[ok].astype(str).tolist() == \
                bv[ok].astype(str).tolist(), c


def test_csv_and_orc_pipelined_match_serial(session, tmp_path):
    pdf = pd.DataFrame({"x": np.arange(40, dtype=np.int64),
                        "y": np.arange(40) * 0.5})
    for i in range(3):
        pdf.iloc[i * 10:(i + 1) * 10].to_csv(
            str(tmp_path / f"c{i}.csv"), index=False)
    import pyarrow as pa
    import pyarrow.orc as paorc
    paorc.write_table(pa.Table.from_pandas(pdf, preserve_index=False),
                      str(tmp_path / "o.orc"))
    for reader, arg in (("csv", str(tmp_path)),
                        ("orc", str(tmp_path / "o.orc"))):
        outs = {}
        for depth in (0, 3):
            session.set_conf("spark.rapids.sql.scan.prefetchDepth", depth)
            outs[depth] = getattr(session.read, reader)(arg) \
                .order_by("x").collect()
        assert outs[0]["x"].tolist() == outs[3]["x"].tolist()
        np.testing.assert_allclose(outs[0]["y"].to_numpy(dtype=float),
                                   outs[3]["y"].to_numpy(dtype=float))


def test_failing_split_propagates_through_query(session, tmp_path):
    p, _df = _write_parquet(tmp_path, rows=200, row_group=50)
    import os
    # truncate the file AFTER footer parse captured the split plan: decode
    # of some row group must now fail, and the error must reach collect()
    src = session.read.parquet(p)
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 3)
    session.set_conf("spark.rapids.sql.scan.prefetchDepth", 3)
    with pytest.raises(Exception):
        src.collect()
    from spark_rapids_tpu.exec import taskctx
    assert taskctx.input_file() == ""


def test_prefetch_metrics_and_trace_overlap(session, tmp_path):
    """Decode spans (pool threads) overlap exec spans (task thread) in
    the exported Chrome trace, and stall/queue metrics reach the profile
    report."""
    p, _df = _write_parquet(tmp_path, rows=4000, row_group=200)
    trace = tmp_path / "scan.trace.json"
    session.set_conf("spark.rapids.sql.scan.prefetchDepth", 4)
    session.set_conf("spark.rapids.tpu.trace.path", str(trace))
    try:
        df = session.read.parquet(p)
        df.filter(df["i"] >= 0).agg(F.sum("f").alias("sf")).collect()
    finally:
        session.set_conf("spark.rapids.tpu.trace.path", "")
    report = session.profile_report()
    assert "scan.prefetch" in report, report
    import json
    doc = json.loads(trace.read_text())
    evs = doc["traceEvents"]
    decode = [e for e in evs if e["name"] == "scan.decode"]
    exec_spans = [e for e in evs
                  if e["name"] not in ("scan.decode", "scan.prefetch.stall")
                  and e.get("ph") == "X"]
    assert decode, "no decode spans traced"
    main_tid = exec_spans[0]["tid"]
    assert any(e["tid"] != main_tid for e in decode), \
        "decode never left the task thread"

    def overlaps(a, b):
        return (a["ts"] < b["ts"] + b["dur"]
                and b["ts"] < a["ts"] + a["dur"])
    pairs = [(d, x) for d in decode for x in exec_spans
             if d["tid"] != x["tid"] and overlaps(d, x)]
    assert pairs, "no decode span overlapped an exec span"


def test_rg_stats_keyed_by_mtime(session, tmp_path):
    """Rewriting a file invalidates its cached row-group stats: pruning
    must see the NEW statistics."""
    import os
    from spark_rapids_tpu.sql.sources import ParquetSource
    p = tmp_path / "m.parquet"
    pd.DataFrame({"v": np.arange(100, dtype=np.int64)}).to_parquet(
        str(p), index=False)
    src = ParquetSource([str(p)])
    keep, pruned = src.prune_splits([("v", ">", 1000)])
    assert pruned == 1 and not keep
    # rewrite with values that DO match; bump mtime past fs granularity
    pd.DataFrame({"v": np.arange(2000, 2100, dtype=np.int64)}).to_parquet(
        str(p), index=False)
    os.utime(str(p), (time.time() + 5, time.time() + 5))
    keep, pruned = src.prune_splits([("v", ">", 1000)])
    assert len(keep) == 1 and pruned == 0


def test_compile_cache_counters_registered(session):
    """obs/compilecache.py listeners feed the process registry; the
    profile report carries a compileCache section after compiles."""
    from spark_rapids_tpu.obs import compilecache
    assert compilecache.install()  # idempotent; session already installed
    df = session.create_dataframe(
        pd.DataFrame({"z": np.arange(64, dtype=np.int64)}), 2)
    df.agg(F.sum((F.col("z") * 31 + 7) % 11).alias("s")).collect()
    prof = session.profile_json()
    assert prof is not None and "compileCache" in prof["summary"]
