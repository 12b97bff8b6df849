"""Asynchronous scan pipeline: split prefetch on a shared decode thread
pool, kept as full as the pool is wide.

The reference closes its scan gap with a multithreaded, coalescing Parquet
reader that overlaps host decode with device transfer (GpuParquetScan's
MULTITHREADED/COALESCING reader modes, GpuMultiFileReader.scala); the
analogue here overlaps the three serial stages of a file scan —

    host decode (pyarrow, GIL-released)  ->  host->device upload
                                         ->  device compute

— by decoding splits ahead of the consuming task on a shared daemon pool,
while the upload side double-buffers (exec/transitions.py): batch i+1's
``device_put`` is dispatched while batch i computes.

The window (``ScanPrefetcher``): splits are submitted in order, and one
scan keeps as many decodes in flight (submitted, not yet decoded) as the
pool has threads. A slot is refilled where it frees: by the worker that
finishes a decode, by the consumer when it takes a frame (a take frees
budget) and when it asks for one. Two bounds in splits: at most
``threads`` undecoded, and at most ``threads + prefetchDepth`` submitted
and not yet taken, so ``spark.rapids.sql.scan.prefetchDepth`` is how many
decoded splits may wait ahead of the consumer beyond those decoding. One
bound in bytes (Backpressure, below).

Contract (tests/test_scan_pipeline.py):

  * partition order is preserved exactly — split i's frames are yielded by
    partition i, in decode order;
  * the first decode exception propagates to the consumer of the failing
    split, and no further splits are submitted after a failure (the
    worker that saw it marks the scan failed; a split the consumer asks
    for still decodes, and the error of a split it passed over ends
    nothing);
  * abandoning a partition generator early (CollectLimit, errors) cancels
    every not-yet-started decode and drops decoded-frame references, so the
    pipeline holds no buffers after GC; a finishing worker submits nothing
    for a cancelled scan;
  * ``prefetchDepth=0`` selects the LEGACY reader end to end (the
    reference keeps its PERFILE reader as a separate code path the same
    way): synchronous full arrow->pandas decode on the consuming thread
    in strict pull order, no hints, no direct decode — pre-pipeline
    behavior exactly (the safe rollback path).

Backpressure: decoded-but-unconsumed frames are host memory; every
submission but that of the split the consumer is asking for stalls once
their estimated bytes reach ``spark.rapids.sql.scan.prefetchMaxBytes``
(clamped to the host spill budget) or while the device manager is over its
HBM spill budget — prefetch can never race the spill framework for memory
it is trying to free. A split is charged when it is decoded, not when it
is submitted, so the worst case of one scan is ``prefetchMaxBytes`` plus
the ``threads`` splits that were decoding when the budget filled (and the
frame the consumer holds). The device side needs no extra gate: uploads
happen on the consuming task thread, which already holds a TpuSemaphore
permit, and every uploaded batch is metered against the HBM budget
(memory/device.py meter_batch).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from typing import Callable, List, Optional, Tuple

from spark_rapids_tpu.obs.events import EVENTS
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.obs.progress import PROGRESS
from spark_rapids_tpu.obs.trace import TRACER

# one decode task per split: () -> pd.DataFrame
DecodeFn = Callable[[], "pd.DataFrame"]  # noqa: F821
# (input_file path or None for non-file sources, decode)
ScanTask = Tuple[Optional[str], DecodeFn]

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_SIZE = 0
_POOL_LOCK = threading.Lock()

# observability handles, resolved once (the pipeline hot path is one
# future.result() per split; metrics must not add registry lookups)
_STALL_TIME = REGISTRY.timer("scan.prefetch.stallTime")
_DECODE_TIME = REGISTRY.timer("scan.prefetch.decodeTime")
# a split's life: pool.submit -> a worker enters _decode (queueTime) ->
# decoded (decodeTime) -> taken by get(), which either found it done
# (hits) or waited (stallTime). By Little's law (queueTime + decodeTime)
# / activeTime is the mean number of splits submitted and not yet decoded
# while a scan was active: to be read against the pool's width, which is
# what the window keeps in flight.
_QUEUE_TIME = REGISTRY.timer("scan.prefetch.queueTime")
_ACTIVE_TIME = REGISTRY.timer("scan.prefetch.activeTime")
_SPLITS = REGISTRY.counter("scan.prefetch.splits")
_HITS = REGISTRY.counter("scan.prefetch.hits")
_BYTES = REGISTRY.counter("scan.prefetch.bytesDecoded")
_BUDGET_STALLS = REGISTRY.counter("scan.prefetch.budgetStalls")
# splits submitted by a worker that had just finished a decode, not by the
# consumer's thread: the window is refilled where a slot frees
_WORKER_SUBMITS = REGISTRY.counter("scan.prefetch.workerSubmits")


def _nbytes(obj) -> int:
    """Host bytes a decoded split retains in the prefetch queue: pandas
    frames by column memory_usage (and the worker's buffers beside them,
    sources._attach_prepared and _attach_dict_hints), deviceDecode
    RawRowGroups (and anything else plan-shaped) by their ``nbytes``."""
    if obj is None:
        return 0
    mu = getattr(obj, "memory_usage", None)
    if mu is not None:
        # plus what the worker's device-layout buffers hold of their own
        # (padded and converted columns, dictionary codes; a full batch's
        # fixed-width columns share the frame's)
        return int(mu(deep=False).sum()) + sum(
            obj.attrs[k].nbytes for k in ("srt_prepared", "srt_dict_fact")
            if k in obj.attrs)
    return int(getattr(obj, "nbytes", 0) or 0)


def decode_pool(threads: int) -> ThreadPoolExecutor:
    """Shared daemon decode pool. One per process; rebuilt (old pool left
    to drain) if a session reconfigures the thread count."""
    global _POOL, _POOL_SIZE
    with _POOL_LOCK:
        if _POOL is None or _POOL_SIZE != threads:
            if _POOL is not None:
                # idle executor workers never exit on their own; release
                # the displaced pool's threads once in-flight decodes
                # drain (repeated reconfiguration must not leak threads)
                _POOL.shutdown(wait=False)
            _POOL = ThreadPoolExecutor(
                max_workers=threads, thread_name_prefix="srt-scan-decode")
            _POOL_SIZE = threads
        return _POOL


def _conf_int(conf, key: str, default: int) -> int:
    try:
        return int(conf.get(key, default))
    except (TypeError, ValueError):
        return default


def pipeline_config(conf):
    """(prefetch_depth, decode_threads, max_bytes) from a TpuConf."""
    import os
    depth = _conf_int(conf, "spark.rapids.sql.scan.prefetchDepth", 2)
    threads = _conf_int(conf, "spark.rapids.sql.scan.decodeThreads", 0)
    if threads <= 0:
        # the workers carry decode + the dictionary factorize hints, so
        # even a 2-core box wants 2 (the consuming thread's residual work
        # is upload memcpys and compute dispatch, largely GIL-released)
        threads = min(4, max(2, (os.cpu_count() or 2) - 1))
    max_bytes = _conf_int(conf, "spark.rapids.sql.scan.prefetchMaxBytes",
                          256 << 20)
    # decoded frames that overflow host memory would fight the spill
    # framework for the same RAM; clamp to the host spill budget
    spill = _conf_int(conf, "spark.rapids.memory.host.spillStorageSize",
                      1 << 30)
    return depth, threads, min(max_bytes, spill)


class ScanPrefetcher:
    """Order-preserving prefetch over one scan's splits, as many decodes
    in flight as the pool has threads.

    Splits are submitted in order while fewer than ``threads`` are
    undecoded and fewer than ``threads + depth`` are submitted and not
    yet taken (and the byte budget allows): by ``get(i)`` on its way in
    and again once it has taken split i's frame, and by every worker that
    finishes a decode. ``get(i)`` blocks on split i's future and hands
    the frame over — the prefetcher drops its own reference so consumed
    frames are GC-eligible the moment the consumer releases them.
    """

    def __init__(self, tasks: List[ScanTask], depth: int, threads: int,
                 max_bytes: int, pool: Optional[ThreadPoolExecutor] = None):
        self._tasks = tasks
        self._depth = max(1, depth)
        self._threads = max(1, threads)
        # the shared pool at that width: the width has one source (tests
        # pass a pool of their own to stand for one that other scans hold)
        self._pool = pool or decode_pool(self._threads)
        self._max_bytes = max(1, max_bytes)
        self._lock = threading.Lock()
        self._futures: dict = {}          # split index -> Future, untaken
        # split index -> [submitted at, a worker has taken it], beside its
        # future: the stall span reads it, _decode writes it
        self._life: dict = {}
        self._submitted: set = set()
        self._next = 0                    # from here on, unsubmitted
        self._cancelled = False
        self._failed = False
        self._pending_bytes = 0           # decoded, not yet consumed
        self._charged: dict = {}          # ... by split, as _decode charged
        self._inflight = 0                # submitted, not yet decoded
        self._skip: set = set()           # submitted splits never consumed
        # scan.prefetch.activeTime is recorded up to here (None: not
        # active — before the first get(), after cancel())
        self._active_since: Optional[float] = None
        # journal sampling state: the event log records rare facts, not
        # per-split streams — budget stalls emit on the entering
        # transition only, decode stalls emit the first _EVENT_CAP per
        # scan (exact aggregates live in the REGISTRY timers/counters)
        self._budget_stalled = False
        self._stall_events = 0
        # built on the query's thread: the pool threads' scan.decode*
        # spans carry the id of the query they decode for
        self._query = TRACER.current_query() if TRACER.enabled else None

    _EVENT_CAP = 16

    # -- worker side --------------------------------------------------------
    def _decode(self, i: int, life: list):
        queued_s = time.perf_counter() - life[0]
        path, fn = self._tasks[i]
        try:
            with self._lock:
                if self._cancelled:
                    return None
            life[1] = True
            _QUEUE_TIME.record(queued_s)
            if TRACER.enabled:
                TRACER.bind_query(self._query)
            with _DECODE_TIME.time():
                with TRACER.span("scan.decode", split=i,
                                 file=path or "<memory>",
                                 queued_s=round(queued_s, 6)):
                    df = fn()
            nbytes = _nbytes(df)
            attrs = getattr(df, "attrs", None)
            if attrs is not None:
                # carried to the consumer, whose scan.host.release span
                # names the free of this many bytes (exec/transitions.py)
                attrs["srt_nbytes"] = nbytes
            with self._lock:
                if self._cancelled or i in self._skip:
                    # raced a cancel (or a skip of a never-consumed
                    # split) mid-decode: drop the frame so the abandoned
                    # work retains no buffers or budget
                    self._skip.discard(i)
                    return None
                self._pending_bytes += nbytes
                self._charged[i] = nbytes
            _BYTES.add(nbytes)
            if PROGRESS.enabled:  # live scan progress (/api/query/<id>)
                PROGRESS.scan_split(nbytes)
            return df
        except BaseException:
            # the first error ends prefetch here, on the worker: decodes
            # that finish after it must not refill the window. Not so for
            # a split the consumer passed over: its error is nobody's
            with self._lock:
                if i not in self._skip:
                    self._failed = True
            raise
        finally:
            with self._lock:
                self._inflight -= 1
                try:
                    _WORKER_SUBMITS.add(self._top_up_locked())
                except RuntimeError:
                    # the pool was displaced by a session with another
                    # decodeThreads and takes no new work: prefetch ends,
                    # and this decode's frame is not lost to it. A split
                    # the consumer still has to ask for raises there.
                    self._failed = True

    # -- consumer side ------------------------------------------------------
    def _over_budget_locked(self) -> bool:
        if self._pending_bytes >= self._max_bytes:
            return True
        # device spill pressure: while the HBM budget is exceeded the
        # spill handlers are freeing memory — do not pile more host
        # frames (whose uploads would immediately re-pressure it)
        from spark_rapids_tpu.memory.device import TpuDeviceManager
        dm = TpuDeviceManager.current()
        return dm is not None and dm.allocated > dm.hbm_budget

    def _submit_locked(self, j: int) -> None:
        life = [time.perf_counter(), False]
        # the worker's first line takes the lock this thread holds, so
        # the bookkeeping below is in place before _decode reads it
        self._futures[j] = self._pool.submit(self._decode, j, life)
        self._life[j] = life
        self._submitted.add(j)
        self._inflight += 1

    def _top_up_locked(self) -> int:
        """Submit the next unsubmitted splits, in order, while fewer than
        ``threads`` are undecoded, fewer than ``threads + depth`` are
        submitted and untaken, the budget allows and the scan is neither
        cancelled nor failed; returns how many that was. Called wherever
        a slot frees: a decode ends (the worker), a frame is taken or
        asked for (the consumer)."""
        n = 0
        while (not (self._cancelled or self._failed)
               and self._next < len(self._tasks)
               and self._inflight < self._threads
               and len(self._futures) < self._threads + self._depth):
            if self._over_budget_locked():
                _BUDGET_STALLS.add(1)
                if not self._budget_stalled:
                    # backpressure fact, on the ENTERING transition only
                    # (sustained pressure re-trips per attempt): prefetch
                    # submission stopped here, the pipeline runs at
                    # consumer speed until the budget drains
                    self._budget_stalled = True
                    EVENTS.emit("scanBudgetStall", split=self._next)
                break
            # a submission the budget let through: the next budget trip
            # is a NEW stall episode and journals again
            self._budget_stalled = False
            self._submit_locked(self._next)
            self._next += 1
            n += 1
        return n

    def get(self, i: int):
        """Decoded frame of split ``i`` (blocking). Re-raises the split's
        decode exception (the worker that met it has marked the pipeline
        failed, so no later splits are submitted after the first error)."""
        t_in = time.perf_counter()
        # scan.host.take: get() less the wait itself, two pieces a split
        with TRACER.span("scan.host.take", split=i) as sp:
            with self._lock:
                if self._active_since is None:
                    self._active_since = t_in
                # earlier splits submitted but never consumed (device-
                # scan-cache replay bypasses their partitions entirely):
                # reclaim their budget, or their frames would pin
                # _pending_bytes for the scan's lifetime and starve the
                # window. A genuinely out-of-order consumer just
                # re-decodes inline (fut-is-None path below) —
                # correctness over overlap for that rare case.
                for j in [k for k in self._futures if k < i]:
                    f = self._futures.pop(j)
                    self._life.pop(j, None)
                    if f.cancel():
                        self._inflight -= 1
                    elif f.done():
                        self._pending_bytes -= self._charged.pop(j, 0)
                    else:
                        # running: drop its result on finish. The done
                        # callback reclaims the budget if the decode
                        # raced past its own skip check before the marker
                        # landed.
                        self._skip.add(j)
                        f.add_done_callback(
                            lambda fr, j=j: self._reclaim_skipped(j))
                submitted = 0
                if i not in self._submitted:
                    # whatever the budget says, and when cancelled or
                    # failed too: the requested split itself must decode
                    self._submit_locked(i)
                    submitted = 1
                # splits the consumer passed over are never submitted
                self._next = max(self._next, i + 1)
                # taken out before the top-up: the split asked for is not
                # one of the untaken that bound the window
                fut = self._futures.pop(i, None)
                life = self._life.pop(i, None)
                submitted += self._top_up_locked()
                inflight = self._inflight
            if sp is not None:
                sp.set(submitted=submitted)
        _SPLITS.add(1)
        try:
            if fut is None:
                # split consumed before (a concurrently re-driven
                # partition, e.g. a racing device-scan-cache filler):
                # decode inline — correctness over overlap for the rare
                # second consumer
                return self._tasks[i][1]()
            hit = fut.done()
            if hit:
                _HITS.add(1)
            else:
                self._stall(i, fut, life, inflight)
            with TRACER.span("scan.host.take", split=i, hit=hit) as sp:
                df = fut.result()
                with self._lock:
                    self._pending_bytes -= self._charged.pop(i, 0)
                    # a take is what frees budget
                    submitted = self._top_up_locked()
                if sp is not None:
                    sp.set(submitted=submitted)
                return df
        finally:
            self._settle_active()

    def _stall(self, i: int, fut, life: list, inflight: int) -> None:
        """Split ``i`` is not decoded yet: wait for it. The span says what
        the wait met: ``inflight`` decodes submitted and not done (this
        one among them), how long ago this one was submitted, and whether
        a worker had taken it (``running``) or it still sat in the pool's
        queue."""
        t0 = time.perf_counter()
        if PROGRESS.enabled:  # live stall state, cleared below
            PROGRESS.scan_stalled(True)
        from spark_rapids_tpu.obs.syncledger import sync_scope
        with TRACER.span("scan.prefetch.stall", split=i, inflight=inflight,
                         submitted_ago_s=round(t0 - life[0], 6),
                         running=life[1]), \
                sync_scope("scan.stall", detail=f"split={i}"):
            wait([fut], return_when=FIRST_COMPLETED)
        if PROGRESS.enabled:
            PROGRESS.scan_stalled(False)
        stall_s = time.perf_counter() - t0
        _STALL_TIME.record(stall_s)
        with self._lock:
            self._stall_events += 1
            sample = self._stall_events <= self._EVENT_CAP
        if sample:
            # bounded sample per scan: a thousand-split scan must not
            # flood the journal/flight ring (scan.prefetch.stallTime
            # carries the exact aggregate)
            EVENTS.emit("scanStall", split=i, stall_s=round(stall_s, 6))

    def _settle_active(self, end: bool = False) -> None:
        """Record scan.prefetch.activeTime up to now: every get() does on
        its way out, so the timer's total runs from the entry of the first
        get() to the return of the last; ``end`` (cancel()) stops it."""
        now = time.perf_counter()
        with self._lock:
            since = self._active_since
            if since is None:
                return
            self._active_since = None if end else now
        _ACTIVE_TIME.record(now - since)

    def _reclaim_skipped(self, j: int) -> None:
        """Done-callback for a skipped-while-running decode: if _decode
        raced past its skip check (frame returned, bytes accounted),
        reclaim the budget here — otherwise the orphaned bytes would pin
        _pending_bytes for the scan's lifetime."""
        with self._lock:
            if self._cancelled or j not in self._skip:
                return  # _decode saw the marker (or cancel reset budget)
            self._skip.discard(j)
            self._pending_bytes -= self._charged.pop(j, 0)

    def cancel(self) -> None:
        """Early consumer exit: cancel unstarted decodes, drop every
        retained frame reference. Running decodes finish (pyarrow reads
        are not interruptible) but their results are discarded."""
        with self._lock:
            self._cancelled = True
            futures = list(self._futures.values())
            self._futures.clear()
            self._life.clear()
            self._charged.clear()
            self._pending_bytes = 0
        n = sum(1 for f in futures if f.cancel())
        if n:
            with self._lock:
                # cancelled-before-start futures never run _decode's
                # accounting; settle the in-flight count for them here
                self._inflight -= n
        self._settle_active(end=True)

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait for in-flight decodes to finish (tests; bounded)."""
        with self._lock:
            futures = list(self._futures.values())
        done, not_done = wait(futures, timeout=timeout)
        return not not_done


def build_partitions(ctx, tasks: List[ScanTask]) -> List["Partition"]:  # noqa: F821
    """Partition list over one scan's splits, honoring
    ``spark.rapids.sql.scan.prefetchDepth``.

    Each partition publishes its split's input file to the task context
    around the yield (try/finally — the file context must not leak across
    tasks when the consumer abandons the generator or decode raises) and,
    with a positive depth, pulls its frame from a shared ScanPrefetcher.
    """
    from spark_rapids_tpu.exec import taskctx

    depth, threads, max_bytes = pipeline_config(ctx.conf)

    if depth <= 0:
        # serial rollback path: decode on the consuming thread at pull
        # time, nothing shared, no pool — the pre-pipeline behavior
        def make_serial(path: Optional[str], fn: DecodeFn) -> "Partition":  # noqa: F821
            def run():
                if path is not None:
                    taskctx.set_input_file(path)
                try:
                    yield fn()
                finally:
                    if path is not None:
                        taskctx.clear_input_file()
            return run
        return [make_serial(p, fn) for p, fn in tasks]

    prefetcher = ScanPrefetcher(tasks, depth, threads, max_bytes)

    def make(i: int, path: Optional[str]) -> "Partition":  # noqa: F821
        def run():
            df = prefetcher.get(i)
            if df is None:  # cancelled scan re-consumed: decode inline
                df = tasks[i][1]()
            if path is not None:
                taskctx.set_input_file(path)
            try:
                yield df
            except BaseException:
                # abandoned mid-yield (GeneratorExit) or a downstream
                # error thrown into the generator: stop feeding the pool
                prefetcher.cancel()
                raise
            finally:
                if path is not None:
                    taskctx.clear_input_file()
        return run
    return [make(i, p) for i, (p, _fn) in enumerate(tasks)]
