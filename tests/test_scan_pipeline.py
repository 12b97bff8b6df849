"""Scan pipeline tests (sql/scan_pipeline.py): ordering under prefetch,
exception propagation, early-exit cancellation, the window's bounds and
who refills it, pandas-vs-direct decode value equality, serial-rollback
equivalence."""

import contextlib
import gc
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as wait_futures

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


def _pf(tasks, depth, threads, max_bytes=1 << 30):
    """A prefetcher over the shared pool at that width, as
    build_partitions makes one."""
    return ScanPrefetcher(tasks, depth=depth, threads=threads,
                          max_bytes=max_bytes)


def _settled(pf, timeout=10.0):
    """Wait until no decode of ``pf`` is in flight. A finishing worker
    submits the next split under the lock that counts it, so a count of
    zero under that lock means nothing runs and nothing is about to."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        pf.drain(timeout=timeout)
        with pf._lock:
            if pf._inflight == 0:
                return True
        time.sleep(0.002)
    return False


def _worker_submits():
    from spark_rapids_tpu.obs.metrics import REGISTRY
    return REGISTRY.counter("scan.prefetch.workerSubmits").value


def test_prefetcher_order_preserved():
    pf = _pf(_tasks(16), depth=4, threads=3)
    got = [int(pf.get(i)["v"][0]) for i in range(16)]
    assert got == list(range(16))


def test_prefetcher_exception_propagates_at_failing_split():
    def decode(i):
        if i == 3:
            raise ValueError("split 3 is poisoned")
        return pd.DataFrame({"v": [i]})
    depth = threads = 3
    pf = _pf(_tasks(16, decode=decode), depth=depth, threads=threads)
    assert int(pf.get(0)["v"][0]) == 0
    assert int(pf.get(1)["v"][0]) == 1
    assert int(pf.get(2)["v"][0]) == 2
    with pytest.raises(ValueError, match="split 3 is poisoned"):
        pf.get(3)
    assert _settled(pf)
    # the window ran at most threads + depth untaken splits ahead of the
    # last take (split 2) before the worker that met the error ended it
    ahead = set(pf._submitted)
    assert max(ahead) <= 2 + threads + depth
    # after the first failure the window stops growing: consuming later
    # splits submits only themselves
    for i in range(4, 12):
        assert int(pf.get(i)["v"][0]) == i
    assert _settled(pf)
    assert pf._submitted - ahead <= set(range(4, 12))
    assert 12 not in pf._submitted


class _Sitting:
    """Gated decodes under a consumer that asks for split 0 on a thread
    of its own and then asks for nothing more: what is submitted, by
    whom, is the window's alone."""

    def __init__(self, n, depth, threads, max_bytes=1 << 30, poison=()):
        self.gates = [threading.Event() for _ in range(n)]
        self.started = []
        self.got = []
        self.pf = _pf(_tasks(n, decode=self._decode), depth, threads,
                      max_bytes)
        self.poison = poison
        self.consumer = threading.Thread(
            target=lambda: self.got.append(self.pf.get(0)), daemon=True)
        self.consumer.start()
        time.sleep(0.3)  # let the window submit and workers start

    def _decode(self, i):
        self.started.append(i)
        assert self.gates[i].wait(timeout=10)
        if i in self.poison:
            raise ValueError(f"split {i} is poisoned")
        return pd.DataFrame({"v": [i]})

    def open(self, *splits):
        for i in splits:
            self.gates[i].set()

    def join(self):
        self.consumer.join(timeout=10)
        assert not self.consumer.is_alive()


def test_prefetcher_depth_honored():
    """The window's two bounds, in splits. While nothing is decoded,
    exactly ``threads`` splits are submitted and started, none beyond;
    under a consumer that sits on split 0 the workers go on submitting as
    they finish, and stop at ``threads + depth`` untaken."""
    depth, threads = 2, 4
    s = _Sitting(16, depth, threads)
    # no fewer: a prefetcher degraded to serial decode-on-get would pass
    # every upper bound and ordering assertion in this file through
    # get()'s inline fallback
    assert s.pf._submitted == set(range(threads))
    assert set(s.started) == set(range(threads))
    before = _worker_submits()
    s.open(0)
    s.join()                            # split 0 is taken, and sat on
    assert s.pf._submitted == set(range(threads + 1))
    s.open(*range(1, 16))
    assert _settled(s.pf)
    # threads + depth wait decoded behind split 0, every one of them past
    # the first window submitted by a worker: the consumer never came back
    assert s.pf._submitted == set(range(1 + threads + depth))
    assert len(s.pf._futures) == threads + depth
    assert _worker_submits() - before == depth + 1
    assert sorted(s.started) == list(range(1 + threads + depth))


def test_prefetcher_window_stops_at_the_byte_budget():
    """``max_bytes`` of one split: a decoded split that waits stops the
    window, whoever asks, though both bounds in splits have room."""
    from spark_rapids_tpu.obs.metrics import REGISTRY
    from spark_rapids_tpu.sql.scan_pipeline import _nbytes
    stalls = REGISTRY.counter("scan.prefetch.budgetStalls")
    depth, threads = 2, 4
    s = _Sitting(16, depth, threads,
                 max_bytes=_nbytes(pd.DataFrame({"v": [0]})))
    assert s.pf._submitted == set(range(threads))
    before = stalls.value
    s.open(0)
    s.join()
    # split 0's worker met a full budget (split 0 itself, not yet taken);
    # the take freed it and the consumer submitted one split
    assert s.pf._submitted == set(range(threads + 1))
    assert stalls.value - before == 1
    s.open(*range(1, 16))
    assert _settled(s.pf)
    # 1..4 are decoded and wait: four untaken of six, nothing in flight,
    # and the budget let none of their workers submit
    assert s.pf._submitted == set(range(threads + 1))
    assert stalls.value - before == 1 + threads
    # a take frees budget: once the four that wait are taken the window
    # is refilled to the pool's width, and stops again at the first of
    # them to be decoded
    assert [int(s.pf.get(i)["v"][0]) for i in range(1, threads + 1)] == \
        list(range(1, threads + 1))
    assert _settled(s.pf)
    assert s.pf._submitted == set(range(2 * threads + 1))
    s.pf.cancel()


def test_worker_tops_up_while_the_consumer_sleeps():
    """A slot is refilled where it frees: with the consumer asleep on
    split 0 the workers submit, and the decodes in flight never pass the
    pool's width (sampled inside every decode)."""
    depth, threads, n = 2, 3, 48
    samples = []

    def decode(i):
        with pf._lock:
            samples.append(pf._inflight)
        time.sleep(0.002)
        return pd.DataFrame({"v": [i]})
    pf = _pf(_tasks(n, decode=decode), depth, threads)
    before = _worker_submits()
    assert int(pf.get(0)["v"][0]) == 0
    time.sleep(0.2)                     # asleep: no get() submits anything
    assert _settled(pf)
    assert _worker_submits() - before >= 1
    assert pf._submitted == set(range(1 + threads + depth))
    assert [int(pf.get(i)["v"][0]) for i in range(1, n)] == \
        list(range(1, n))
    assert len(samples) == n and max(samples) == threads
    assert pf._inflight == 0 and pf._pending_bytes == 0


@pytest.mark.parametrize("how", ["error", "cancel"])
def test_nothing_submitted_after_error_or_cancel(how):
    """Decodes that finish after the first error, or after a cancel(),
    refill nothing."""
    depth, threads = 2, 4
    s = _Sitting(16, depth, threads, poison=(1,))
    window = set(range(threads))
    assert s.pf._submitted == window
    before = _worker_submits()
    if how == "error":
        s.open(1)                       # split 1's worker meets the error
        assert wait_futures([s.pf._futures[1]], timeout=10)[0]
    else:
        s.pf.cancel()
    s.open(2, 3, 0, *range(4, 16))      # the others finish after it
    s.join()
    assert _settled(s.pf)
    assert s.pf._submitted == window and _worker_submits() == before
    assert sorted(s.started) == sorted(window)
    if how == "error":
        assert int(s.got[0]["v"][0]) == 0
        with pytest.raises(ValueError, match="split 1 is poisoned"):
            s.pf.get(1)
        # a split the consumer asks for still decodes, alone
        assert [int(s.pf.get(i)["v"][0]) for i in (2, 3, 4)] == [2, 3, 4]
        assert s.pf._submitted == window | {4}
    else:
        assert s.got == [None]          # build_partitions decodes it inline
        assert not s.pf._futures and s.pf._pending_bytes == 0


def test_an_error_in_a_split_passed_over_does_not_end_prefetch():
    """A split the consumer passed over while it decoded is dropped when
    it ends; its error surfaces nowhere, so it must not stop the window."""
    depth, threads, n = 2, 2, 12
    s = _Sitting(n, depth, threads, poison=(1,))
    s.open(0, *range(2, n))
    s.join()
    assert int(s.pf.get(2)["v"][0]) == 2    # split 1 still decodes
    assert 1 in s.pf._skip
    before = len(s.pf._submitted)
    s.open(1)                               # it fails, for nobody
    assert _settled(s.pf)
    assert not s.pf._failed
    assert [int(s.pf.get(i)["v"][0]) for i in range(3, n)] == \
        list(range(3, n))
    assert _settled(s.pf)
    # the window went on ahead of the consumer to the scan's end
    assert before < n and s.pf._submitted == set(range(n))
    assert sorted(s.started) == list(range(n))  # each decoded once
    assert s.pf._inflight == 0 and s.pf._pending_bytes == 0
    assert not s.pf._skip


@pytest.mark.parametrize("n, threads, depth", [
    (64, 4, 2),     # decode times shuffled: completions out of order
    (24, 1, 2),     # the pool of one worker
    (3, 8, 2),      # more threads than the scan has splits
], ids=["shuffled-64", "one-worker", "pool-wider-than-scan"])
def test_order_and_one_decode_a_split(n, threads, depth):
    rng = np.random.default_rng(11)
    pause = rng.permutation(n) % 7 * 0.0015
    decoded, samples = [], []

    def decode(i):
        with pf._lock:
            samples.append(pf._inflight)
        time.sleep(pause[i])
        decoded.append(i)
        return pd.DataFrame({"v": [i]})
    pf = _pf(_tasks(n, decode=decode), depth, threads)
    assert [int(pf.get(i)["v"][0]) for i in range(n)] == list(range(n))
    assert sorted(decoded) == list(range(n))
    if n > threads > 1:
        assert decoded != sorted(decoded)   # the test is not vacuous
    assert max(samples) == min(threads, n)
    assert pf._submitted == set(range(n)) and not pf._futures
    assert pf._inflight == 0 and pf._pending_bytes == 0


def test_window_under_more_workers_than_cores():
    """Stress: the consumer and sixteen workers all submit under one
    lock, the interpreter switching threads as often as it can. A lost
    update would decode a split twice, or none, or leave the counts off
    zero."""
    n, threads, depth = 600, 16, 3
    decoded, samples = [], []

    def decode(i):
        with pf._lock:
            samples.append(pf._inflight)
        decoded.append(i)
        return pd.DataFrame({"v": [i]})
    pf = _pf(_tasks(n, decode=decode), depth, threads)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        t0 = time.monotonic()
        got = [int(pf.get(i)["v"][0]) for i in range(n)]
        assert time.monotonic() - t0 < 60
    finally:
        sys.setswitchinterval(interval)
    assert got == list(range(n))
    assert sorted(decoded) == list(range(n))
    assert 1 <= min(samples) and max(samples) <= threads
    assert pf._inflight == 0 and pf._pending_bytes == 0
    assert not pf._futures and not pf._charged and not pf._skip


def test_a_displaced_pool_ends_prefetch_and_loses_no_frame():
    """decode_pool() shuts a pool down when a session asks for another
    width. A worker that finishes on such a pool cannot refill the
    window; its own frame is still the consumer's, and the refusal is
    raised where the consumer asks for a split that was never submitted."""
    gate = threading.Event()

    def decode(i):
        assert gate.wait(timeout=10)
        return pd.DataFrame({"v": [i]})
    pool = ThreadPoolExecutor(max_workers=2)
    pf = ScanPrefetcher(_tasks(6, decode=decode), depth=2, threads=2,
                        pool=pool, max_bytes=1 << 30)
    got = []
    t = threading.Thread(target=lambda: got.append(pf.get(0)), daemon=True)
    t.start()
    time.sleep(0.2)
    assert pf._submitted == {0, 1}
    pool.shutdown(wait=False)
    gate.set()
    t.join(timeout=10)
    assert not t.is_alive() and int(got[0]["v"][0]) == 0
    assert int(pf.get(1)["v"][0]) == 1
    with pytest.raises(RuntimeError, match="after shutdown"):
        pf.get(2)


def test_prefetcher_cancel_leaves_no_work(session):
    """Early consumer exit: unstarted decodes are cancelled, in-flight
    ones drain, no decoded-frame references survive, no device buffers
    leak (LeakTracker clean), and the pool thread count stays bounded."""
    from spark_rapids_tpu.memory.leak import TRACKER
    live_before = TRACKER.live_count
    threads_before = threading.active_count()
    for _ in range(5):
        pf = _pf(_tasks(32), depth=8, threads=3)
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
    growth of (splits, hits, stalls, started, activeTime records) and of
    the splits the workers submitted."""
    g = _Gated(monkeypatch, 6)
    pf = _pf(g.tasks, depth=2, threads=4)
    before, by_workers = _life_counts(), _worker_submits()
    assert g.get(pf, 0) == 0            # nothing decoded yet: a stall
    g.gates[1].set()
    g.gates[2].set()
    # 1 and 2 are decoded (3 and 4 are not: their gates are closed)
    assert not wait_futures([pf._futures[1], pf._futures[2]],
                            timeout=10).not_done
    assert g.get(pf, 1) == 1            # a hit
    assert g.get(pf, 2) == 2            # a hit
    assert g.get(pf, 3) == 3            # its gate is closed: a stall
    assert g.get(pf, 1) == 1            # taken before: decoded inline
    assert g.get(pf, 4) == 4            # a stall
    assert g.get(pf, 5) == 5            # a stall
    return (tuple(b - a for a, b in zip(before, _life_counts())),
            _worker_submits() - by_workers)


def test_hits_stalls_and_inline_decodes_add_up_to_the_splits(monkeypatch):
    first = _life_scenario(monkeypatch)
    (splits, hits, stalls, started, active), by_workers = first
    inline = 1
    assert (splits, hits, stalls) == (7, 2, 4)
    assert hits + stalls + inline == splits
    assert started == 6 and active == splits
    # get(0) submitted the pool's width; split 0's worker submitted 4 and
    # the first of 1 and 2 to finish submitted 5
    assert by_workers == 2
    # the counts repeat exactly
    assert _life_scenario(monkeypatch) == first


def test_stall_and_decode_spans_say_what_the_wait_met(monkeypatch):
    with _traced() as so_far:
        _counts, by_workers = _life_scenario(monkeypatch)
        events = so_far()
    stalls = {e["args"]["split"]: e["args"] for e in events
              if e["name"] == "scan.prefetch.stall"}
    assert sorted(stalls) == [0, 3, 4, 5]
    # split 0 waited with the pool's width undecoded (0..3); by split 3
    # every split is submitted, so 3 waited with 3..5, 4 with two and the
    # last with itself alone
    assert [stalls[i]["inflight"] for i in (0, 3, 4, 5)] == [4, 3, 2, 1]
    for a in stalls.values():
        assert a["submitted_ago_s"] >= 0 and a["running"] in (True, False)
    decodes = [e["args"] for e in events if e["name"] == "scan.decode"]
    assert sorted(a["split"] for a in decodes) == list(range(6))
    assert all(a["queued_s"] >= 0 for a in decodes)
    takes = [e["args"] for e in events if e["name"] == "scan.host.take"]
    # two pieces a split that had a future, one for the inline decode
    assert len(takes) == 2 * 6 + 1
    # every split is submitted once: by a take's piece or by a worker
    assert sum(a.get("submitted", 0) for a in takes) + by_workers == 6
    assert sorted(a["split"] for a in takes if a.get("hit")) == [1, 2]


@pytest.mark.parametrize("workers, at_least, at_most", [
    # the window is the pool's width: no split waits for a worker (a
    # handoff to an idle thread: a tenth of the other case's hold is room
    # for a loaded machine, where a woken thread waits for the interpreter)
    (3, 0.0, 0.02),
    # a pool narrower than the prefetcher was told (as when another scan
    # holds its other workers): splits 1 and 2 queue behind split 0's
    # decode, which is held for 0.2 s
    (1, 0.2, None)], ids=["window-fits-pool", "pool-narrower-than-told"])
def test_queue_time_says_when_the_pool_is_the_limit(monkeypatch, workers,
                                                    at_least, at_most):
    g = _Gated(monkeypatch, 3, hold_s=0.2)
    g.gates[1].set()
    g.gates[2].set()
    pool = decode_pool(workers)
    # every thread of the pool is started: a handoff, not a thread's birth
    wait_futures([pool.submit(time.sleep, 0.01) for _ in range(workers)])
    pf = ScanPrefetcher(g.tasks, depth=2, threads=3, pool=pool,
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
    pf = _pf(_tasks(8), depth=2, threads=3)
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
