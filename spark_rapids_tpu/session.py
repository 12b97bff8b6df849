"""Session and DataFrame front-end.

Plays the role of SparkSession + the plugin bootstrap: building a session
installs the TPU override rules exactly the way
``spark.plugins=com.nvidia.spark.SQLPlugin`` installs ColumnarOverrideRules
(reference: Plugin.scala:36-54, SQLPlugin.scala:28-31). `explain` and the
`spark.rapids.*` conf surface match the reference's user API (L7).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Union

import pandas as pd

from spark_rapids_tpu.config.conf import TpuConf
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.sql import plan as lp
from spark_rapids_tpu.sql.functions import Column, SortOrder, _c, _expr, col as col_fn
from spark_rapids_tpu.sql.planner import Planner
from spark_rapids_tpu.sql.sources import CsvSource, InMemorySource, ParquetSource


class OrderedSet:
    """Insertion-ordered set (dict-backed) so size sweeps can evict
    oldest-first — an arbitrary ``set.pop()`` could drop a hot entry or,
    worse, re-enable a blocklisted speculation key."""

    def __init__(self):
        self._d: dict = {}

    def add(self, k) -> None:
        self._d[k] = True

    def __contains__(self, k) -> bool:
        return k in self._d

    def __len__(self) -> int:
        return len(self._d)

    def pop_oldest(self) -> None:
        del self._d[next(iter(self._d))]


class LruDict(dict):
    """dict whose reads move the key to the end, so the size sweep's
    oldest-first eviction approximates LRU instead of FIFO (a stable hot
    query set inserted early must outlive churned dead keys)."""

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def __getitem__(self, k):
        v = super().__getitem__(k)
        if next(reversed(self)) != k:
            super().__delitem__(k)
            super().__setitem__(k, v)
        return v


class TpuSparkSession:
    _active: Optional["TpuSparkSession"] = None
    _lock = threading.Lock()

    def __init__(self, conf: TpuConf):
        self.conf = conf
        self._base_settings = dict(conf._settings)
        from spark_rapids_tpu.memory.device import TpuDeviceManager
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        from spark_rapids_tpu.memory.spill import (
            BufferCatalog, MemoryEventHandler,
        )
        self.device_manager = TpuDeviceManager.get(conf)
        self.semaphore = TpuSemaphore.get(conf.concurrent_tpu_tasks)
        # persistent-compile-cache hit/miss counters (obs/compilecache.py):
        # registered once per process so first-run warmup attribution is
        # first-class in profile reports
        from spark_rapids_tpu.obs import compilecache
        compilecache.install()
        # cross-process compile manifest + AOT pre-warm from history:
        # configured at session START so the pre-warm pass overlaps
        # everything the first query does
        compilecache.SHARED.configure_from_conf(conf)
        from spark_rapids_tpu.serving import prewarm as _prewarm
        _prewarm.maybe_start_from_conf(conf)
        # spillable-buffer runtime wired into execution: cached scan
        # batches register here and over-budget allocations spill them
        # device->host->disk (reference: GpuShuffleEnv.initStorage,
        # GpuShuffleEnv.scala:51-72 + DeviceMemoryEventHandler.scala:65-89)
        self.buffer_catalog = BufferCatalog(
            conf.host_spill_storage_size,
            device_manager=self.device_manager)
        self.memory_event_handler = MemoryEventHandler(
            self.buffer_catalog.device_store)
        self.device_manager.register_oom_handler(self.memory_event_handler)
        # test hook: captured executed physical plans
        # (reference: ExecutionPlanCaptureCallback, Plugin.scala:144-233)
        self.captured_plans: List = []
        self.capture_plans = False
        # device-resident scan batches (spark.rapids.sql.cacheDeviceScans)
        self.device_scan_cache: dict = {}
        # encoded-page cache for the deviceDecode scan path
        # (spark.rapids.sql.scan.pageCache.*): hot tables re-decode from
        # cached encoded pages instead of re-reading + re-uploading
        from spark_rapids_tpu.memory.spill import EncodedPageCache
        self.page_cache = EncodedPageCache(
            int(conf.get("spark.rapids.sql.scan.pageCache.maxBytes",
                         256 << 20) or 0),
            int(conf.get("spark.rapids.sql.scan.pageCache.deviceMaxBytes",
                         64 << 20) or 0)) \
            if conf.get_bool("spark.rapids.sql.scan.pageCache.enabled",
                             True) else None
        # device mesh for distributed execution (None = single-device);
        # when set, TpuShuffleExchangeExec exchanges over it with an ICI
        # all_to_all instead of collapsing locally (parallel/distributed.py)
        self.mesh = None
        # accelerated shuffle manager (spark.rapids.shuffle.transport.
        # enabled): lazily built; shares the session catalog so shuffle
        # buffers are spillable (RapidsShuffleInternalManager.scala:74-178)
        self._shuffle_env = None
        self._shuffle_id_counter = 0
        self._active_shuffles: List[int] = []
        # catalog ids of per-query transient spillables (exchange buckets,
        # broadcast tables): consumed entries remove themselves; leftovers
        # (short-circuited limits, errors) release at query end
        self._transient_bids: set = set()
        # adaptive statistics: aggregate signature -> last observed
        # partial-pass reduction ratio (groups/rows); known-poor reducers
        # skip their partial pass from batch 0 on later executions
        self.agg_ratio_cache: LruDict = LruDict()
        # adaptive capacity speculation (spark.rapids.sql.adaptiveCapacity
        # .enabled): structural-plan-fingerprint -> last observed join
        # expansion sizes; later executions skip the per-join capacity
        # sync and verify in one deferred fetch (exec/tpujoin.py,
        # _verify_speculation). capacity_spec_reruns counts verification
        # misses (each one transparently re-executed without speculation).
        self.capacity_cache: LruDict = LruDict()
        self.capacity_spec_reruns = 0
        self.capacity_spec_hits = 0
        # speculation keys that failed verification and must not retry
        # ("nocache|" prefix: dense grouping keys — chronically-stale
        # stats would otherwise re-execute every run). Insertion-ordered
        # set (dict keys) so the size sweep evicts oldest-first — an
        # arbitrary set.pop() could re-enable a known-bad speculation.
        self.capacity_spec_blocklist: OrderedSet = OrderedSet()
        # plan fingerprints that have executed once: over bounds measured
        # batch by batch, dense grouping only engages from the second
        # execution (first-run scan stats cannot cover the upload yet —
        # they record as batches stream). Over Parquet footers' bounds it
        # engages on the first (ExecContext.declared_stats)
        self.dense_plans_seen: OrderedSet = OrderedSet()
        # scan-derived integer column bounds: column name -> (min, max),
        # unioned across every scanned batch carrying that name. ADVISORY
        # (the role of the reference's cuDF column min/max the join build
        # reads): the dense-key join fast path sizes its direct-index
        # table from these and VERIFIES them on device, falling back to
        # the exact sort probe on mismatch — correctness never depends on
        # this registry (exec/tpujoin.py).
        self.column_stats: dict = {}
        # rename provenance: alias -> {source column names} recorded by
        # rename-only projections, so stats resolve through `.alias(...)`
        self.column_aliases: dict = {}
        # observability state of the last executed query (obs/)
        self.last_query_metrics: dict = {}
        self.last_node_times: dict = {}
        self.last_plan = None
        self.last_profile = None
        # adaptive-execution record of the last AQE query: stage count,
        # rule decisions, final plan tree (sql/adaptive/executor.py)
        self.last_aqe: Optional[dict] = None
        # tenant/job-group tag (set_job_group): flows into every event,
        # the tenant.* metric labels, and live progress records — the
        # per-tenant accounting substrate the serving layer reads.
        # Thread-scoped: the scheduler's workers each run a different
        # tenant's job concurrently (_set_thread_job_group); a plain
        # set_job_group also updates the session-wide default so the
        # single-threaded API keeps its exact pre-serving behavior.
        self._job_group_default: tuple = (None, "")
        self._job_group_tls = threading.local()
        # serving-layer state: cross-query plan/result caches and the
        # AQE exchange-reuse cache (serving/caches.py), created lazily on
        # first use so sessions that never serve pay nothing
        self._serving_caches = None
        self._serving_lock = threading.Lock()
        # per-executing-thread ExecContext scope: register/release of
        # per-query resources (transient spillables, shuffle ids) routes
        # to the OWNING query's context so concurrent queries cannot
        # free each other's buffers
        self._exec_scope = threading.local()
        self._shuffle_lock = threading.Lock()
        # SIGUSR1 -> flight-recorder + thread-stack + progress dump into
        # the event log (obs/monitor.py; main-thread sessions only)
        if conf.get_bool("spark.rapids.tpu.ui.signalDiagnostics", True):
            from spark_rapids_tpu.obs.monitor import (
                install_signal_diagnostics,
            )
            install_signal_diagnostics()

    def clear_device_cache(self) -> None:
        for _source, parts in self.device_scan_cache.values():
            for entries in parts.values():
                for _fname, bid in entries:
                    self.buffer_catalog.remove(bid)
        self.device_scan_cache.clear()

    def _make_transport(self, executor_id: str):
        kind = self.conf.get("spark.rapids.shuffle.transport.class",
                             "inprocess")
        if kind == "socket":
            from spark_rapids_tpu.shuffle.socket_transport import (
                SocketTransport,
            )
            return SocketTransport(executor_id)
        if kind == "inprocess":
            from spark_rapids_tpu.shuffle.transport import InProcessTransport
            return InProcessTransport(executor_id)
        # SPI: dotted path "module:Class" taking (executor_id)
        import importlib
        mod, _, cls = kind.partition(":")
        return getattr(importlib.import_module(mod), cls)(executor_id)

    @property
    def shuffle_envs(self):
        """The executor pool for the accelerated shuffle manager. With
        spark.rapids.shuffle.executors > 1, map tasks stripe across the
        pool and cross-executor fetches ride the configured transport
        (socket = real TCP loopback) through serializer -> server ->
        client -> received catalog — the reference's multi-executor UCX
        flow (RapidsShuffleInternalManager.scala:74-362) in one process."""
        if self._shuffle_env is None:
            from spark_rapids_tpu.shuffle.manager import ShuffleEnv
            bsize = int(self.conf.get(
                "spark.rapids.shuffle.bounceBuffers.size", 4 << 20))
            bcount = int(self.conf.get(
                "spark.rapids.shuffle.bounceBuffers.count", 16))
            nexec = int(self.conf.get("spark.rapids.shuffle.executors", 1))
            self._shuffle_env = [
                ShuffleEnv(f"local-exec-{i}",
                           self._make_transport(f"local-exec-{i}"),
                           bounce_buffer_size=bsize,
                           bounce_buffer_count=bcount,
                           buffer_catalog=self.buffer_catalog)
                for i in range(max(1, nexec))]
        return self._shuffle_env

    @property
    def shuffle_env(self):
        return self.shuffle_envs[0]

    def _current_ctx(self):
        """The ExecContext of the query executing on THIS thread (set by
        ``_execute``); None outside a query. Per-query resource tracking
        (transients, shuffle ids) routes here so concurrent queries each
        release exactly their own."""
        return getattr(self._exec_scope, "ctx", None)

    def next_shuffle_id(self) -> int:
        with self._shuffle_lock:
            self._shuffle_id_counter += 1
            sid = self._shuffle_id_counter
            self._active_shuffles.append(sid)
        ctx = self._current_ctx()
        if ctx is not None:
            ctx.active_shuffles.append(sid)
        return sid

    def release_active_shuffles(self, ctx=None) -> None:
        """Unregister every shuffle a query registered (the reference's
        unregisterShuffle path). With a context, only that query's
        shuffles; without one (session.stop), everything outstanding."""
        if ctx is None:
            ctx = self._current_ctx()
        if self._shuffle_env is None:
            if ctx is not None:
                ctx.active_shuffles.clear()
            return
        with self._shuffle_lock:
            if ctx is not None:
                sids, ctx.active_shuffles = list(ctx.active_shuffles), []
                self._active_shuffles = [
                    s for s in self._active_shuffles if s not in set(sids)]
            else:
                sids, self._active_shuffles = self._active_shuffles, []
        for env in self._shuffle_env:
            for sid in sids:
                env.shuffle_catalog.remove_shuffle(sid)

    def register_transient(self, bid: int) -> int:
        ctx = self._current_ctx()
        if ctx is not None:
            ctx.transient_bids.add(bid)
        else:
            self._transient_bids.add(bid)
        return bid

    def add_transient_batch(self, batch, priority: int) -> int:
        """Register a per-query spillable in the catalog AND the transient
        set in one step — the pairing is load-bearing (an add_batch alone
        would pin the buffer in the catalog past query end)."""
        return self.register_transient(
            self.buffer_catalog.add_batch(batch, priority))

    def consume_transient(self, bid: int) -> None:
        ctx = self._current_ctx()
        if ctx is not None:
            ctx.transient_bids.discard(bid)
        self._transient_bids.discard(bid)
        self.buffer_catalog.remove(bid)

    def release_transient_buffers(self, ctx=None) -> None:
        """Free per-query spillables a short-circuited (or failed) query
        never consumed. With a context, only that query's; the session-
        level set (registrations outside any query) drains too when no
        other query is executing them."""
        if ctx is None:
            ctx = self._current_ctx()
        if ctx is not None:
            bids, ctx.transient_bids = set(ctx.transient_bids), set()
        else:
            bids, self._transient_bids = set(self._transient_bids), set()
        for bid in bids:
            self.buffer_catalog.remove(bid)

    def set_mesh(self, n_devices: Optional[int]) -> None:
        """Configure an n-device data-parallel mesh for distributed
        exchanges (the session-level analogue of enabling the reference's
        RapidsShuffleManager, GpuShuffleEnv.scala:27-136). ``None`` returns
        to single-device execution."""
        if n_devices is None:
            self.mesh = None
            return
        from spark_rapids_tpu.parallel.distributed import data_parallel_mesh
        self.mesh = data_parallel_mesh(n_devices)

    # --- builder -----------------------------------------------------------
    class Builder:
        def __init__(self):
            self._conf: Dict[str, object] = {}
            self._name = "spark-rapids-tpu"

        def app_name(self, name: str) -> "TpuSparkSession.Builder":
            self._name = name
            return self

        def config(self, key: str, value) -> "TpuSparkSession.Builder":
            self._conf[key] = value
            return self

        def get_or_create(self) -> "TpuSparkSession":
            with TpuSparkSession._lock:
                if TpuSparkSession._active is None:
                    TpuSparkSession._active = TpuSparkSession(
                        TpuConf(self._conf))
                else:
                    for k, v in self._conf.items():
                        TpuSparkSession._active.conf.set(k, v)
                return TpuSparkSession._active

    @staticmethod
    def builder() -> "TpuSparkSession.Builder":
        return TpuSparkSession.Builder()

    @staticmethod
    def active() -> "TpuSparkSession":
        s = TpuSparkSession._active
        if s is None:
            s = TpuSparkSession.builder().get_or_create()
        return s

    def stop(self) -> None:
        """Tear the session down (SparkSession.stop parity): release
        cached/spilled buffers, detach the memory event handler from the
        process-wide device manager (a later session registers its own),
        and clear the singleton."""
        self.clear_device_cache()
        self.clear_serving_caches()
        from spark_rapids_tpu.serving import prewarm as _prewarm
        _prewarm.cancel_active()
        self.release_active_shuffles()
        if self._shuffle_env is not None:
            for env in self._shuffle_env:
                env.close()
            self._shuffle_env = None
        self.device_manager.unregister_oom_handler(self.memory_event_handler)
        self.buffer_catalog.close()
        with TpuSparkSession._lock:
            if TpuSparkSession._active is self:
                TpuSparkSession._active = None

    # --- tenancy -----------------------------------------------------------
    def set_job_group(self, tenant, description: str = "") -> None:
        """Tag subsequent queries with a tenant/job-group id (the
        SparkContext.setJobGroup analogue). The tag flows into every
        event the journal records for those queries, the ``tenant.*``
        counters in the process-wide metrics registry (rendered live at
        ``/metrics`` and aggregated at ``/api/tenants``), and the live
        query-progress records. ``set_job_group(None)`` clears it."""
        group = (str(tenant) if tenant else None,
                 str(description or ""))
        self._job_group_default = group
        self._job_group_tls.value = group

    def clear_job_group(self) -> None:
        self.set_job_group(None)

    def _set_thread_job_group(self, tenant, description: str = "") -> None:
        """Tag THIS THREAD's queries only (the serving workers' form:
        each worker runs a different tenant's job concurrently, and a
        session-wide tag would cross-attribute them)."""
        self._job_group_tls.value = (str(tenant) if tenant else None,
                                     str(description or ""))

    @property
    def _job_group(self) -> tuple:
        return getattr(self._job_group_tls, "value",
                       self._job_group_default)

    # --- serving ------------------------------------------------------------
    def _serving(self):
        """The session's serving-cache bundle (serving/caches.py), or
        None when every serving cache is disabled — the legacy planning
        path then runs with zero extra work per query."""
        conf = self.conf
        from spark_rapids_tpu.serving import caches as sc
        if not (conf.get_bool(sc.PLAN_CACHE_ENABLED, True)
                or conf.get_bool(sc.RESULT_CACHE_ENABLED, False)):
            return None
        return self._serving_bundle()

    def _serving_bundle(self):
        if self._serving_caches is None:
            with self._serving_lock:
                if self._serving_caches is None:
                    from spark_rapids_tpu.serving.caches import (
                        ServingCaches,
                    )
                    self._serving_caches = ServingCaches()
        return self._serving_caches

    def serving_scheduler(self, **kwargs):
        """Build an admission scheduler over this session
        (serving/scheduler.py): submit/status/cancel with per-tenant
        weighted-fair lanes, bounded-queue load-shed, per-query
        deadlines and tenant HBM quotas. The caller owns its lifecycle
        (``close()``)."""
        from spark_rapids_tpu.serving.scheduler import QueryScheduler
        return QueryScheduler(self, **kwargs)

    def clear_serving_caches(self) -> None:
        if self._serving_caches is not None:
            self._serving_caches.clear()

    @staticmethod
    def _count_rows(outs) -> int:
        try:
            return sum(len(df) for df in outs) if outs else 0
        except TypeError:
            return 0

    def _note_tenant(self, tenant, status: str, wall_s: float,
                     rows: int = 0) -> None:
        """Per-tenant accounting, once per query end (success or
        failure): the counters /api/tenants aggregates and a Prometheus
        scrape sees as srt_tenant_* series."""
        from spark_rapids_tpu.obs.metrics import REGISTRY
        t = tenant or "default"
        REGISTRY.counter("tenant.queries", tenant=t, status=status).add(1)
        REGISTRY.counter("tenant.wallSeconds", tenant=t).add(
            round(wall_s, 6))
        if rows:
            REGISTRY.counter("tenant.rowsReturned", tenant=t).add(rows)

    # --- conf --------------------------------------------------------------
    def set_conf(self, key: str, value) -> None:
        self.conf.set(key, value)

    def get_conf(self, key: str, default=None):
        return self.conf.get(key, default)

    def reset_conf(self) -> None:
        self.conf._settings = dict(self._base_settings)

    # --- data --------------------------------------------------------------
    def create_dataframe(self, df: pd.DataFrame,
                         num_partitions: int = 1) -> "DataFrame":
        return DataFrame(self, lp.LogicalScan(InMemorySource(df,
                                                             num_partitions)))

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 2) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(self, lp.LogicalRange(start, end, step,
                                               num_partitions))

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    # --- execution ---------------------------------------------------------
    def _execute(self, logical: lp.LogicalPlan):
        """logical -> CPU physical -> TPU overrides -> run; returns
        (final physical plan, list of output pandas DataFrames)."""
        import time

        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import metrics as obs_metrics
        from spark_rapids_tpu.obs.trace import TRACER

        conf = self.conf
        # per-query tracer window: configure from conf, then drop the
        # events of queries that have ended, so an exported file holds
        # this query (a speculation re-run is part of the same query and
        # keeps its spans) and whatever still runs on other threads
        trace_path = str(conf.get("spark.rapids.tpu.trace.path", "") or "")
        trace_on = (conf.get_bool("spark.rapids.tpu.trace.enabled", False)
                    or bool(trace_path))
        TRACER.configure(trace_on, conf.get_bool(
            "spark.rapids.tpu.trace.jaxAnnotations", False))
        if trace_on:
            TRACER.begin_query()
        with TRACER.span("query.begin"):
            ctx = ExecContext(conf, self)
            # gather-free execution flags (docs/gatherfree.md): per-value hash
            # tables, exchange-boundary dictionary merge, codes-on-the-wire
            from spark_rapids_tpu.columnar import dictionary as _dictionary
            _dictionary.configure_from_conf(conf)
            # reset NOW, not on the success path: a failed query must not
            # leave the previous query's profile/metrics masquerading as "the
            # last executed query" in a post-mortem
            self.last_query_metrics = {}
            self.last_node_times = {}
            self.last_plan = None
            self.last_profile = None
            self.last_aqe = None
            # process-wide registry snapshot: the profile reports this query's
            # DELTA of spill/fetch/compile activity
            global_before = (obs_metrics.REGISTRY.values()
                             if ctx.metrics_enabled else None)
            # truncation counters snapshot: the profile's observability
            # section reports this query's DELTA, not the process totals.
            # The 5th element is the compile-ledger seq watermark: the
            # profile's ``compiles`` section covers entries recorded after
            # it; the 6th is the sync-ledger watermark feeding the profile's
            # ``syncs`` section + occupancy estimate
            from spark_rapids_tpu.obs.compileledger import LEDGER as _LEDGER
            from spark_rapids_tpu.obs.syncledger import SYNC_LEDGER as _SYNCS
            obs_before = (TRACER.dropped, obs_events.EVENTS.dropped,
                          obs_events.EVENTS.rotations,
                          obs_events.EVENTS.rotate_failures,
                          _LEDGER.seq, _SYNCS.seq) \
                if ctx.metrics_enabled else None
            t_query0 = time.perf_counter()
            # durable event journal (obs/events.py): the query window opens
            # HERE so planning failures are on record too; the failure path
            # below dumps the always-on flight recorder into the log
            obs_events.EVENTS.configure_from_conf(conf)
            # compile ledger (obs/compileledger.py): per-cause attribution of
            # every backend compile this query triggers
            from spark_rapids_tpu.obs.compileledger import LEDGER
            LEDGER.configure_from_conf(conf)
            # host-sync ledger (obs/syncledger.py): per-site attribution of
            # every device<->host blocking point, plus the opt-in transfer-
            # guard coverage audit (spark.rapids.tpu.debug.transferGuard)
            from spark_rapids_tpu.obs import syncledger as _syncledger
            _SYNCS.configure_from_conf(conf)
            _guard_mode = str(conf.get(
                "spark.rapids.tpu.debug.transferGuard", "off") or "off")
            _syncledger.set_guard_mode(
                _guard_mode if _guard_mode in ("log", "disallow") else None)
            # zero-warm-up layer: coarse secondary-dimension shape buckets
            # (one compile serves a dimension range), the cross-process
            # shared compile cache (one compile per CLUSTER) and the AOT
            # pre-warm pass (history compiles before traffic). All three
            # default off/empty = byte-identical engine behavior.
            from spark_rapids_tpu.obs import compilecache as _compilecache
            from spark_rapids_tpu.serving import prewarm as _prewarm
            from spark_rapids_tpu.utils import kernelcache as _kernelcache
            _kernelcache.configure_shape_buckets_from_conf(conf)
            _compilecache.SHARED.configure_from_conf(conf)
            _prewarm.maybe_start_from_conf(conf)
            # live monitoring service (obs/monitor.py): starts/stops the
            # embedded HTTP server on conf change and keeps the progress
            # tracker's single hot-path flag in lockstep. Off (the default)
            # this is two conf reads and ctx.progress stays None.
            from spark_rapids_tpu.obs import monitor as obs_monitor
            from spark_rapids_tpu.obs.progress import PROGRESS
            obs_monitor.maybe_serve(conf)
            tenant, job_desc = self._job_group
            qid = obs_events.EVENTS.query_start(
                tenant=tenant,
                confFingerprint=obs_events.conf_fingerprint(conf._settings))
            if trace_on:
                # every span of this thread carries the journal's id from
                # here on (query.begin too: a span is stamped as it closes)
                TRACER.set_query(qid)
            qp = None
            if PROGRESS.enabled:
                qp = PROGRESS.begin(qid, tenant=tenant, description=job_desc)
                ctx.progress = qp
            # per-thread execution scope: register/release of per-query
            # resources (transients, shuffle ids) resolves to THIS context
            # while the query runs on this thread
            self._exec_scope.ctx = ctx
        try:
            # transfer-guard audit: untracked device->host transfers
            # outside any sync_scope are logged (or raise) while the
            # query body runs; sync scopes re-enter "allow"
            with _syncledger.guard_context(_guard_mode):
                plan, outs, ctx = self._plan_and_run(
                    logical, ctx, conf, obs_metrics, global_before,
                    t_query0, obs_before)
        except BaseException as e:
            wall_s = round(time.perf_counter() - t_query0, 6)
            err = f"{type(e).__name__}: {e}"[:300]
            # cooperative cancellation / deadline: a first-class terminal
            # state, not a failure — the dedicated journal event carries
            # the flight-recorder tail + compile-ledger tail so a killed
            # query still leaves its last moments on record
            from spark_rapids_tpu.serving.cancellation import (
                QueryCancelled, QueryTimeout,
            )
            if isinstance(e, QueryTimeout):
                status, kind = "timeout", "queryTimeout"
            elif isinstance(e, QueryCancelled):
                status, kind = "cancelled", "queryCancelled"
            else:
                status, kind = "failed", None
            if kind is not None:
                extra = {}
                if status == "timeout" and ctx.cancel is not None:
                    extra["deadlineSeconds"] = ctx.cancel.deadline_s
                obs_events.EVENTS.emit(
                    kind, reason=err, wall_s=wall_s,
                    events=obs_events.EVENTS.flight_events(),
                    compiles=_LEDGER.tail(), syncs=_SYNCS.tail(),
                    **extra)
            obs_events.EVENTS.query_end(
                status=status, flight_dump=kind is None, error=err,
                wall_s=wall_s)
            self._note_tenant(tenant, status, wall_s)
            if qp is not None:
                PROGRESS.finish(qp, status, error=err)
            raise
        finally:
            self._exec_scope.ctx = None
            _syncledger.set_guard_mode(None)
            if trace_on:
                TRACER.end_query(qid)
        with TRACER.span("query.finish"):
            wall_s = round(time.perf_counter() - t_query0, 6)
            rows_out = self._count_rows(outs)
            obs_events.EVENTS.query_end(
                status="success", wall_s=wall_s, rowsReturned=rows_out,
                **self._coverage_fields(plan, ctx))
            self._note_tenant(tenant, "success", wall_s, rows_out)
            if qp is not None:
                PROGRESS.finish(qp, "success")
            self._sweep_adaptive_caches()
        if trace_on and trace_path:
            TRACER.export_chrome(trace_path)
        return plan, outs

    def _plan_and_run(self, logical, ctx, conf, obs_metrics, global_before,
                      t_query0, obs_before=None):
        """The planning + execution body of ``_execute``, factored out so
        the event journal's failure path wraps it in one place. Returns
        (plan, outputs, final ExecContext) — a speculation re-run swaps
        in a fresh context, and the coverage event reads the one that
        actually executed."""
        import time

        from spark_rapids_tpu.obs.trace import TRACER

        with TRACER.span("plan.logical"):
            # record rename provenance (alias -> source names) from the
            # LOGICAL plan — physical projections can fuse away, but the
            # logical tree always carries `.alias(...)` / USING-join renames.
            # Advisory input to the dense-key join's stats resolution; bounds
            # are device-verified there, so staleness only loosens them.
            self._note_rename_aliases(logical)
            # column pruning (narrowing projects above filters / semi-anti
            # build sides), then projection pushdown: mark file scans with
            # the query's referenced column subset before planning
            # (sql/pushdown.py)
            from spark_rapids_tpu.sql.pushdown import (
                annotate_scan_pruning, prune_filter_columns,
            )
            logical = prune_filter_columns(logical)
            annotate_scan_pruning(logical)
            planner = Planner(conf)
            # tiny-query overhead-floor fast path: single-partition planning
            # + semaphore/shrink-sync/bookkeeping elision (docs/gatherfree.md);
            # mesh execution keeps the general plan (data is born distributed)
            if getattr(self, "mesh", None) is None:
                planner.note_input_size(logical)
            ctx.small_query = planner.small_query
            ctx.small_query_keep_sem = planner.small_query_keep_sem
            if isinstance(logical, lp.LogicalLimit):
                # root-position limit plans as one CollectLimit operator
                cpu_plan = planner.plan_collect_limit(logical)
            else:
                cpu_plan = planner.plan(logical)
        # adaptive query execution (sql/adaptive/): cut the plan into
        # stages at hash-exchange boundaries, materialize map sides,
        # re-optimize the remainder from the observed sizes. Off (the
        # default) — and on a mesh, and for stage-less plans — the
        # legacy single-shot path below runs byte-identically.
        if (conf.get_bool("spark.rapids.sql.adaptive.enabled", False)
                and getattr(self, "mesh", None) is None):
            from spark_rapids_tpu.sql.adaptive.executor import (
                has_adaptive_stages,
            )
            if has_adaptive_stages(cpu_plan):
                return self._run_adaptive(cpu_plan, ctx, conf,
                                          obs_metrics, global_before,
                                          t_query0, obs_before)
        with TRACER.span("plan.rewrite") as sp:
            plan, outs, cache_key, plan_cache_hit = self._rewrite_plan(
                cpu_plan, logical, conf, ctx)
            if sp is not None:
                sp.set(plan_cache_hit=plan_cache_hit)
        if outs is not None:  # result-cache hit: nothing to execute
            self._finish_query(plan, ctx, conf, obs_metrics,
                               global_before, t_query0, obs_before)
            return plan, outs, ctx
        if ctx.speculate and any(
                type(n).__name__ in ("TpuWriteExec", "CpuWriteExec")
                for n in plan.walk()):
            # writes commit files DURING the drain; a speculation miss
            # detected after it would have committed truncated output and
            # the re-execution would collide with the committed path.
            # Capacity syncs stay exact under write commands.
            ctx.speculate = False
        try:
            with TRACER.span("Query", speculative=bool(ctx.speculate)):
                outs = self._drain(plan, ctx, conf)
            if ctx.spec_pending and not self._verify_speculation(ctx):
                # a speculated capacity did not cover its actual size:
                # the speculative output may be truncated. Re-execute the
                # same physical plan without speculation (the cache
                # entries that missed were dropped above, so the next
                # execution re-learns them with the exact sync).
                self.capacity_spec_reruns += 1
                # ratios learned from a misspeculated run may be garbage
                # (a dense-group miss collapses group counts)
                for sig in ctx.ratio_writes:
                    self.agg_ratio_cache.pop(sig, None)
                self.release_active_shuffles(ctx)
                self.release_transient_buffers(ctx)
                prev_progress = ctx.progress
                small = ctx.small_query
                keep_sem = ctx.small_query_keep_sem
                ctx = ExecContext(conf, self, speculate=False)
                ctx.progress = prev_progress  # same query, same record
                ctx.small_query = small
                ctx.small_query_keep_sem = keep_sem
                # re-point this thread's execution scope at the fresh
                # context so the re-run's registrations release with IT
                self._exec_scope.ctx = ctx
                with TRACER.span("Query", speculative=False,
                                 rerun=True):
                    outs = self._drain(plan, ctx, conf)
        finally:
            self.release_active_shuffles(ctx)
            self.release_transient_buffers(ctx)
        if cache_key is not None:
            # opt-in result cache: remember (plan, outputs) for identical
            # dashboard-style re-submissions (deterministic reads only)
            self._serving().result_cache.maybe_put(
                cache_key, cpu_plan, plan, outs, conf, self._job_group[0])
        self._finish_query(plan, ctx, conf, obs_metrics, global_before,
                           t_query0, obs_before)
        return plan, outs, ctx

    def _rewrite_plan(self, cpu_plan, logical, conf, ctx):
        """The ``plan.rewrite`` span of ``_plan_and_run``: serving-cache
        look-ups, the tag+convert rewrite onto TPU operators and the
        plan's journal events. Returns (plan, the result cache's outputs
        or None, serving cache key, whether the plan cache hit)."""
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.sql.overrides import (
            TpuOverrides, TransitionOverrides, assert_is_on_tpu,
        )

        # cross-query serving caches (serving/caches.py), keyed by
        # (plan digest, conf fingerprint, source data versions):
        #   * result cache (opt-in): identical dashboard-style query ->
        #     answer straight from the cached host frames, zero execution;
        #   * plan cache (on by default): repeat submission skips the
        #     tag+convert rewrite entirely — zero re-planning, and the
        #     identical operator signatures keep every kernel-cache key
        #     warm (timed_compiles stays 0).
        caches = self._serving()
        cache_key = caches.key_for(cpu_plan, conf, logical) \
            if caches is not None else None
        tenant = self._job_group[0]
        if cache_key is not None:
            hit = caches.result_cache.get(cache_key, conf, tenant)
            if hit is not None:
                plan, outs = hit
                obs_events.EVENTS.emit(
                    "resultCacheHit", planDigest=cache_key[0],
                    rows=self._count_rows(outs))
                if self.capture_plans:
                    self.captured_plans.append(plan)
                return plan, outs, cache_key, False
        plan = caches.plan_cache.get(cache_key, conf, tenant) \
            if cache_key is not None else None
        plan_cache_hit = plan is not None
        overrides = None
        if plan_cache_hit:
            pass  # tag+convert skipped: the rewrite was cached
        elif conf.sql_enabled:
            overrides = TpuOverrides(conf)
            plan = overrides.apply(cpu_plan)
            plan = TransitionOverrides(conf).apply(plan)
            if (getattr(self, "mesh", None) is None and conf.get_bool(
                    "spark.rapids.sql.agg.fuseCountDistinct", True)):
                from spark_rapids_tpu.exec.aggfuse import (
                    fuse_count_distinct,
                )
                plan = fuse_count_distinct(plan)
            if conf.get_bool("spark.rapids.sql.reuseSubtrees.enabled",
                             True):
                from spark_rapids_tpu.exec.reuse import (
                    reuse_common_subtrees,
                )
                plan = reuse_common_subtrees(plan)
        else:
            plan = cpu_plan
        if conf.test_enabled and not plan_cache_hit:
            assert_is_on_tpu(plan, conf)
        if cache_key is not None and not plan_cache_hit:
            caches.plan_cache.put(cache_key, plan, conf)
        if self.capture_plans:
            self.captured_plans.append(plan)
        # durable plan facts: structural digest + operator coverage + the
        # tree itself (tools/history_server.py renders plan pages from
        # the log alone), and one cpuFallback event per tagged-off
        # operator with the tag pass's will-not-work reasons (the
        # explain-why-not record the qualification tool ranks by impact)
        if plan_cache_hit:
            obs_events.EVENTS.emit(
                "planCacheHit", planDigest=obs_events.plan_digest(plan))
        obs_events.EVENTS.emit(
            "queryPlan", planDigest=obs_events.plan_digest(plan),
            planCacheHit=plan_cache_hit,
            planTree=plan.tree_string()[:20000],
            **self._coverage_fields(plan))
        if ctx.progress is not None:
            ctx.progress.set_plan(plan)
        if overrides is not None:
            for meta in overrides.fallback_metas():
                obs_events.EVENTS.emit(
                    "cpuFallback", op=meta.plan.name,
                    describe=meta.plan.describe()[:200],
                    reasons=list(meta.reasons))
        return plan, None, cache_key, plan_cache_hit

    def _run_adaptive(self, cpu_plan, ctx, conf, obs_metrics,
                      global_before, t_query0, obs_before):
        """Adaptive branch of ``_plan_and_run``: the executor owns
        per-stage conversion + materialization + re-planning; this wraps
        it with the same event/metrics/profile bookkeeping as the legacy
        path. Capacity speculation is off — AQE's stage barriers are the
        syncs speculation avoids, and a speculative re-execution would
        invalidate the statistics its own re-planning consumed."""
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs.trace import TRACER
        from spark_rapids_tpu.sql.adaptive.executor import AdaptiveExecutor

        ctx.speculate = False
        adaptive = AdaptiveExecutor(self, conf, ctx)
        # static-shape digest FIRST, so a query that dies mid-stage still
        # leaves a plan record next to its flight-recorder dump (the
        # legacy path emits queryPlan before the drain); no coverage
        # census — the plan is unconverted at this point
        obs_events.EVENTS.emit(
            "queryPlan", planDigest=obs_events.plan_digest(cpu_plan),
            adaptive=True, phase="static")
        if ctx.progress is not None:
            # the static shape now; the executor re-sets the tree as
            # runtime re-planning evolves it and reports stage progress
            ctx.progress.set_plan(cpu_plan)
        try:
            with TRACER.span("Query", adaptive=True):
                plan, outs = adaptive.execute(cpu_plan)
        finally:
            self.release_active_shuffles()
            self.release_transient_buffers()
        if self.capture_plans:
            self.captured_plans.append(plan)
        # the digest is of the runtime-re-planned FINAL plan: it differs
        # from the static shape exactly when an AQE rule fired
        obs_events.EVENTS.emit(
            "queryPlan", planDigest=obs_events.plan_digest(plan),
            planTree=plan.tree_string()[:20000],
            adaptive=True, phase="final", aqeStages=len(adaptive.stages),
            aqeDecisions=len(adaptive.decisions),
            **self._coverage_fields(plan))
        if ctx.progress is not None:
            ctx.progress.set_plan(plan)
        self._finish_query(plan, ctx, conf, obs_metrics, global_before,
                           t_query0, obs_before)
        return plan, outs, ctx

    def _finish_query(self, plan, ctx, conf, obs_metrics, global_before,
                      t_query0, obs_before):
        """Shared post-run bookkeeping of both execution paths:
        per-operator SQL metrics of the last executed query (the
        reference surfaces these in the Spark UI, GpuExec.scala:61-67),
        the memory runtime's counters and the profile report. The trace
        export waits for ``_execute``'s last span."""
        import time

        from spark_rapids_tpu.obs.trace import TRACER
        with TRACER.span("query.finish"):
            if ctx.metrics_enabled:
                cat = self.buffer_catalog
                mem = {
                    "allocatedBytes": self.device_manager.allocated,
                    "spillCount": self.memory_event_handler.spill_count,
                    "deviceStoreBytes": cat.device_store.total_size,
                    "hostStoreBytes": cat.host_store.total_size,
                    "diskStoreBytes": cat.disk_store.total_size,
                }
                for k, v in mem.items():
                    ctx.registry.gauge(k, op="memory").set(v)
                # per-tier resident bytes into the process-wide registry
                cat.publish_metrics()
            self.last_query_metrics = ctx.metrics
            self.last_node_times = ctx.node_times  # profiler (syncEachOp)
            self.last_plan = plan
            self.last_profile = None
            if ctx.metrics_enabled:
                from spark_rapids_tpu.obs.profile import build_profile
                delta = obs_metrics.registry_delta(
                    global_before, obs_metrics.REGISTRY.values())
                self.last_profile = build_profile(
                    plan, ctx, delta,
                    wall_s=time.perf_counter() - t_query0,
                    obs_before=obs_before)

    # --- observability ------------------------------------------------------
    def _coverage_fields(self, plan, ctx=None) -> dict:
        """TPU-vs-CPU operator census of a converted plan (transitions
        excluded — they are the boundary, not a side), plus — given the
        executed context — observed per-CPU-operator inclusive seconds,
        the qualification tool's estimated fallback time impact."""
        tpu = cpu = 0
        cpu_time: dict = {}
        for node in plan.walk():
            if node.name in ("HostToDeviceExec", "DeviceToHostExec"):
                continue
            if node.columnar_output or getattr(node, "columnar_input",
                                               False):
                tpu += 1
                continue
            cpu += 1
            if ctx is not None:
                st = ctx.node_stats.get(id(node))
                if st is not None:
                    d = node.describe()[:200]
                    cpu_time[d] = round(
                        cpu_time.get(d, 0.0) + st["time"], 6)
        total = tpu + cpu
        out = {"tpuOps": tpu, "cpuOps": cpu,
               "coveragePct": round(100.0 * tpu / total, 2)
               if total else 100.0}
        if cpu_time:
            out["cpuOpTime"] = cpu_time
        return out

    def dump_flight_recorder(self) -> List[dict]:
        """Snapshot the always-on flight recorder (obs/events.py): the
        last N events — and spans, while tracing is on — regardless of
        whether the event log is enabled. Also writes the snapshot into
        the journal as a ``flightRecorder`` event when it is."""
        from spark_rapids_tpu.obs.events import EVENTS
        # one snapshot serves both the journal and the caller — a second
        # flight_events() here could diverge under concurrent emitters
        return EVENTS.dump_flight(reason="manual")["events"]

    def profile_report(self) -> str:
        """Human-readable profile of the last executed query: plan tree
        annotated with inclusive/exclusive time, rows, batches, plus the
        query's spill/fetch/compile-cache activity (obs/profile.py).
        Empty string when no profiled query has run (metrics disabled)."""
        return "" if self.last_profile is None else \
            self.last_profile.render()

    def profile_json(self) -> Optional[dict]:
        """Machine shape of the last query's profile (None when no
        profiled query has run). Consumed by tools/trace_summary.py."""
        return None if self.last_profile is None else \
            self.last_profile.to_json()

    # adaptive-state size cap: fingerprints embed per-upload data uids,
    # so a workload that keeps creating DataFrames mints fresh keys every
    # query and the dicts would grow for the session's lifetime
    # (ADVICE r4 #4). The LruDict caches touch keys on read, so
    # oldest-first half-eviction approximates LRU; the ordered sets evict
    # oldest-first (never arbitrary — a random blocklist eviction would
    # re-enable a known-bad speculation).
    ADAPTIVE_CACHE_CAP = 4096

    def _sweep_adaptive_caches(self) -> None:
        cap = self.ADAPTIVE_CACHE_CAP
        for d in (self.capacity_cache, self.agg_ratio_cache,
                  self.column_stats, self.column_aliases):
            if len(d) > cap:
                for k in list(d.keys())[:len(d) - cap // 2]:
                    del d[k]
        for s in (self.capacity_spec_blocklist, self.dense_plans_seen):
            if len(s) > cap:
                while len(s) > cap // 2:
                    s.pop_oldest()

    def _verify_speculation(self, ctx) -> bool:
        """ONE deferred fetch validating every capacity the query
        speculated (exec/tpujoin.py). A covered speculation is EXACT —
        capacities only pad — so success means the speculative output
        stands; any shortfall (or a dense-probe ok-flag gone false) drops
        the offending cache entry and returns False, and _execute
        re-runs the plan without speculation. Surviving entries are
        refreshed with the actual sizes so the cache follows data drift
        while it stays inside the buckets."""
        import jax
        flat = []
        for _key, totals_d, _caps, oks_d, _exact in ctx.spec_pending:
            flat.extend(totals_d)
            flat.extend(oks_d)
        if flat:
            from spark_rapids_tpu.obs.syncledger import sync_scope
            with sync_scope("speculation.verify",
                            detail=f"arrays={len(flat)}"):
                fetched = jax.device_get(flat)
        else:
            fetched = []
        pos = 0
        all_good = True
        for key, totals_d, caps, oks_d, exact in ctx.spec_pending:
            sizes = fetched[pos:pos + len(totals_d)]
            pos += len(totals_d)
            oks = fetched[pos:pos + len(oks_d)]
            pos += len(oks_d)
            good = all(bool(o) for o in oks)
            if good and exact is not None:
                # exchange-shrink speculation: the cached row counts were
                # used as EXACT host metadata (batch._host_rows), so any
                # drift — not just overflow — invalidates
                good = all(int(a) == int(e) for a, e in zip(sizes, exact))
            elif good:
                # join-expansion speculation: capacities only pad, so the
                # entry stands while the actual sizes stay covered.
                # Verify the CONSUMED prefix (a short-circuiting parent —
                # CollectLimit — may abandon the emission loop early;
                # batches never expanded cannot have truncated anything)
                for cap, sz in zip(caps, sizes):
                    sz = [int(x) for x in sz]
                    if cap is None:  # speculated-empty batch
                        if sz[0] != 0:
                            good = False
                            break
                        continue
                    out_cap, s_caps, b_caps = cap
                    cchars = list(s_caps) + list(b_caps)
                    if (sz[0] > out_cap or len(sz) - 1 != len(cchars)
                            or any(c > cc
                                   for c, cc in zip(sz[1:], cchars))):
                        good = False
                        break
            if good:
                ent = self.capacity_cache.get(key)
                if (exact is None and ent is not None
                        and len(sizes) == ent.get("n")):
                    ent["sizes"] = [[int(x) for x in s] for s in sizes]
            else:
                self.capacity_cache.pop(key, None)
                if key.startswith("nocache|"):
                    self.capacity_spec_blocklist.add(key)
                all_good = False
        return all_good

    def _note_rename_aliases(self, logical) -> None:
        from spark_rapids_tpu.sql.exprs.core import Alias, Col
        amap = self.column_aliases
        stack = [logical]
        while stack:
            node = stack.pop()
            stack.extend(node.children)
            if isinstance(node, lp.LogicalProject):
                for out_name, e in node.exprs:
                    while isinstance(e, Alias):
                        e = e.children[0]
                    if isinstance(e, Col) and e.name != out_name:
                        amap.setdefault(out_name, set()).add(e.name)

    def _drain(self, plan, ctx, conf) -> List[pd.DataFrame]:
        from spark_rapids_tpu.obs.trace import TRACER
        outs: List[pd.DataFrame] = []
        # the recursive partition planning (split planning, Parquet
        # footers, prefetcher start) closes before the first pull
        with TRACER.span("plan.partitions") as sp:
            parts = plan.executed_partitions(ctx)
            if sp is not None:
                sp.set(partitions=len(parts))
        if plan.columnar_output:
            # drain every partition's device batches first, then convert
            # with to_pandas_many: TWO device->host round trips for the
            # whole result set instead of two per output partition
            from spark_rapids_tpu.columnar.batch import DeviceBatch
            batches: List[DeviceBatch] = []
            for part in parts:
                try:
                    batches.extend(part())
                finally:
                    if self.semaphore is not None:
                        self.semaphore.release()
            # result fetch under a "Collect" scope: the fused-fetch
            # pack/slice kernels it compiles attribute to "Collect" in
            # the ledger, and the device->host seconds land as a
            # Collect/fetchTime SQL metric. Deliberately NOT charged to
            # the root node's breakdown (node_id=None): the fetch runs
            # AFTER the root's pull window, and folding it in would
            # break the device+transfer+dispatch == exclusive invariant
            # of the per-operator rows (obs/profile.py)
            import time as _time

            from spark_rapids_tpu.obs import compileledger
            from spark_rapids_tpu.obs.syncledger import sync_scope
            with compileledger.op_context("Collect", None, None):
                _t0 = _time.perf_counter()
                with sync_scope("collect.fetch",
                                detail=f"batches={len(batches)}"):
                    outs = DeviceBatch.to_pandas_many(
                        batches, fused_fetch_bytes=int(conf.get(
                            "spark.rapids.sql.collect.fusedFetchBytes",
                            4 << 20)))
                if ctx.metrics_enabled:
                    ctx.metric_add("Collect", "fetchTime",
                                   _time.perf_counter() - _t0)
        else:
            for part in parts:
                for df in part():
                    outs.append(df)
        return outs


class DataFrameWriter:
    """df.write.mode("overwrite").parquet(path) — the DataFrameWriter
    surface over LogicalWrite (reference: GpuDataWritingCommandExec path)."""

    def __init__(self, df: "DataFrame"):
        self._df = df
        self._mode = "error"
        self._partition_cols: List[str] = []

    def mode(self, m: str) -> "DataFrameWriter":
        m = {"errorifexists": "error"}.get(m, m)
        assert m in ("error", "overwrite"), m
        self._mode = m
        return self

    def partition_by(self, *cols: str) -> "DataFrameWriter":
        """Hive-style dynamic partitioning: one key=value directory level
        per column (reference: GpuInsertIntoHadoopFsRelationCommand's
        dynamic-partition write path)."""
        missing = [c for c in cols if c not in self._df.schema.names]
        if missing:
            raise ValueError(f"partition_by columns not in schema: {missing}")
        self._partition_cols = list(cols)
        return self

    partitionBy = partition_by

    def _run(self, path: str, fmt: str) -> None:
        plan = lp.LogicalWrite(self._df._plan, path, fmt, self._mode,
                               self._partition_cols)
        self._df.session._execute(plan)

    def parquet(self, path: str) -> None:
        self._run(path, "parquet")

    def csv(self, path: str) -> None:
        self._run(path, "csv")

    def orc(self, path: str) -> None:
        self._run(path, "orc")


class DataFrameReader:
    def __init__(self, session: TpuSparkSession):
        self.session = session
        self._schema: Optional[Schema] = None
        self._options: Dict[str, str] = {}

    def schema(self, schema: Schema) -> "DataFrameReader":
        self._schema = schema
        return self

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = value
        return self

    def parquet(self, *paths: str) -> "DataFrame":
        return DataFrame(self.session,
                         lp.LogicalScan(ParquetSource(list(paths))))

    def csv(self, *paths: str) -> "DataFrame":
        header = str(self._options.get("header", "true")).lower() == "true"
        return DataFrame(self.session,
                         lp.LogicalScan(CsvSource(list(paths),
                                                  schema=self._schema,
                                                  header=header)))

    def orc(self, *paths: str) -> "DataFrame":
        from spark_rapids_tpu.sql.sources import OrcSource
        return DataFrame(self.session,
                         lp.LogicalScan(OrcSource(list(paths))))


class GroupedData:
    def __init__(self, df: "DataFrame", grouping_cols: Sequence):
        self.df = df
        self.grouping = grouping_cols

    def agg(self, *agg_cols: Column) -> "DataFrame":
        from spark_rapids_tpu.sql.exprs.core import Alias, Col
        schema = self.df._plan.schema()
        child = self.df._plan
        grouping = []
        computed = []   # non-column keys get pre-projected (Spark's shape)
        for i, g in enumerate(self.grouping):
            e = _c(g)
            name = e.sql_name(schema)
            base = e.children[0] if isinstance(e, Alias) else e
            if not isinstance(base, Col):
                # a computed key aliased to an EXISTING column name would
                # collide with its passthrough twin in the pre-projection
                # and name-binding would silently group on the raw column;
                # project under an internal name, output the user's alias
                iname = f"__grp{i}" if name in schema.names else name
                computed.append((iname, e))
                e = Col(iname)
            grouping.append((name, e))
        if computed:
            passthrough = [(n, col_fn(n).expr) for n in schema.names]
            child = lp.LogicalProject(child, passthrough + computed)
        result_exprs = []
        for c in agg_cols:
            e = _expr(c)
            result_exprs.append((e.sql_name(schema), e))
        from spark_rapids_tpu.sql.exprs.aggregates import find_aggregates
        if any(getattr(fn, "is_distinct", False)
               for _, e in result_exprs for fn in find_aggregates(e)):
            return self._agg_with_distinct(child, grouping, schema,
                                           result_exprs)
        # key results reference the aggregate's OUTPUT names (finalize
        # resolves Col against grouping names), not the pre-projection's
        # internal names
        results = [(n, Col(n)) for n, _ in grouping] + result_exprs
        return DataFrame(self.df.session,
                         lp.LogicalAggregate(child, grouping, results))

    def _agg_with_distinct(self, child, grouping, schema, result_exprs):
        """count(DISTINCT d) rewrite: aggregate twice.

        Level 1 groups by keys+d, reducing every non-distinct aggregate to
        its update intermediates; level 2 groups by the keys, merging the
        intermediates and counting the now-unique d values. Same plan shape
        Spark produces for a single distinct column set (the reference
        falls back to CPU for the multi-distinct cases it can't split this
        way, aggregate.scala:40-225)."""
        from spark_rapids_tpu.sql.exprs import aggregates as am
        from spark_rapids_tpu.sql.exprs.core import Col
        fns, seen = [], set()
        for _, e in result_exprs:
            for fn in am.find_aggregates(e):
                if id(fn) not in seen:
                    seen.add(id(fn))
                    fns.append(fn)
        dist = [fn for fn in fns if getattr(fn, "is_distinct", False)]
        dist_names = {fn.children[0].sql_name(schema) for fn in dist}
        if len(dist_names) > 1:
            raise NotImplementedError(
                "multiple DISTINCT aggregate column sets in one aggregation "
                f"are not supported: {sorted(dist_names)}")
        # the grouping machinery keys columns by name: materialize d as
        # __dist so both aggregation levels can refer to it uniformly
        names = child.schema().names
        child = lp.LogicalProject(
            child, [(n, col_fn(n).expr) for n in names]
            + [("__dist", dist[0].children[0])])
        l1_grouping = list(grouping) + [("__dist", Col("__dist"))]

        # reduction kind -> aggregate constructor, shared by the level-1
        # (update) and level-2 (merge) tables; count_valid only appears on
        # the update side (its merge kind is 'sum')
        kind_ctor = {
            "sum": am.Sum, "min": am.Min, "max": am.Max, "any": am.Max,
            "first": lambda e: am.First(e, False),
            "first_valid": lambda e: am.First(e, True),
            "last": lambda e: am.Last(e, False),
            "last_valid": lambda e: am.Last(e, True),
        }

        def level1_fn(kind, child_expr):
            if kind == "count_valid":
                return am.Count(child_expr)
            return kind_ctor[kind](child_expr)

        def merge_fn(kind, ref):
            return kind_ctor[kind](ref)

        l1_results = [(n, Col(n)) for n, _ in l1_grouping]
        fn_level2 = {}
        pi = 0
        for fn in fns:
            if getattr(fn, "is_distinct", False):
                # d is unique per level-2 group now; counting its non-NULL
                # occurrences is exactly count(DISTINCT d)
                fn_level2[id(fn)] = am.Count(Col("__dist"))
                continue
            refs = []
            for (ukind, cidx), mkind in zip(fn.update_ops(), fn.merge_ops()):
                pname = f"__p{pi}"
                pi += 1
                l1_results.append((pname, level1_fn(ukind, fn.children[cidx])))
                refs.append(merge_fn(mkind, Col(pname)))
            fn_level2[id(fn)] = fn.finalize(refs, schema)
        level1 = lp.LogicalAggregate(child, l1_grouping, l1_results)

        def rewrite(e):
            if isinstance(e, am.AggregateFunction):
                return fn_level2[id(e)]
            return e.map_children(rewrite)

        l2_grouping = [(n, col_fn(n).expr) for n, _ in grouping]
        l2_results = list(l2_grouping) + [(n, rewrite(e))
                                          for n, e in result_exprs]
        return DataFrame(self.df.session,
                         lp.LogicalAggregate(level1, l2_grouping, l2_results))

    def count(self) -> "DataFrame":
        from spark_rapids_tpu.sql import functions as F
        return self.agg(F.count("*").alias("count"))


class RollupData:
    """rollup/cube grouping: an Expand producing one projection per
    grouping set (null-ed out keys + a grouping id), then a regular
    aggregate over keys+gid (Spark's Expand+Aggregate lowering)."""

    def __init__(self, df: "DataFrame", grouping_cols: Sequence,
                 kind: str):
        self.df = df
        self.grouping = grouping_cols
        self.kind = kind  # 'rollup' | 'cube'

    def _grouping_sets(self, nkeys: int):
        if self.kind == "rollup":
            return [list(range(k)) for k in range(nkeys, -1, -1)]
        import itertools
        sets = []
        for r in range(nkeys, -1, -1):
            sets.extend(list(c) for c in
                        itertools.combinations(range(nkeys), r))
        return sets

    def agg(self, *agg_cols: Column) -> "DataFrame":
        from spark_rapids_tpu.sql.exprs.core import Literal
        schema = self.df._plan.schema()
        keys = [(_c(g).sql_name(schema), _c(g)) for g in self.grouping]
        key_dtypes = [e.dtype(schema) for _, e in keys]
        key_names = {n for n, _ in keys}
        # non-key child columns pass through; key columns are re-emitted
        # per grouping set (nulled when rolled up) to avoid name collisions
        base = [(n, col_fn(n).expr) for n in schema.names
                if n not in key_names]
        projections = []
        for gid, kept in enumerate(self._grouping_sets(len(keys))):
            proj = list(base)
            for j, (name, e) in enumerate(keys):
                if j in kept:
                    proj.append((name, e))
                else:
                    proj.append((name, Literal(None, key_dtypes[j])))
            proj.append(("_gid", Literal(gid)))
            projections.append(proj)
        expand = lp.LogicalExpand(self.df._plan, projections)
        grouping = [(n, col_fn(n).expr) for n, _ in keys]
        grouping.append(("_gid", col_fn("_gid").expr))
        results = [(n, col_fn(n).expr) for n, _ in keys]
        for c in agg_cols:
            e = _expr(c)
            results.append((e.sql_name(schema), e))
        return DataFrame(self.df.session,
                         lp.LogicalAggregate(expand, grouping, results))


class DataFrame:
    def __init__(self, session: TpuSparkSession, plan: lp.LogicalPlan):
        self.session = session
        self._plan = plan

    # --- schema ------------------------------------------------------------
    @property
    def schema(self) -> Schema:
        return self._plan.schema()

    @property
    def columns(self) -> List[str]:
        return list(self.schema.names)

    def __getitem__(self, name: str) -> Column:
        return col_fn(name)

    # --- transformations ---------------------------------------------------
    def select(self, *cols) -> "DataFrame":
        from spark_rapids_tpu.sql.exprs.core import Col
        from spark_rapids_tpu.sql.window import WindowExpression
        schema = self.schema
        exprs = []
        for c in cols:
            e = _c(c)
            exprs.append((e.sql_name(schema), e))
        # window expressions in a projection: append the windowed columns
        # first (Spark's WindowExec shape), then project over them
        win_items = []

        def extract(e):
            if isinstance(e, WindowExpression):
                name = f"__w{len(win_items)}"
                win_items.append((name, e))
                return Col(name)
            return e.map_children(extract)

        exprs = [(n, extract(e)) for n, e in exprs]
        child = self._plan
        if win_items:
            child = lp.LogicalWindow(child, win_items)
        return DataFrame(self.session, lp.LogicalProject(child, exprs))

    def with_column(self, name: str, c: Column) -> "DataFrame":
        from spark_rapids_tpu.sql.window import WindowExpression
        from spark_rapids_tpu.sql.exprs.generators import ExplodeSplit
        e = _expr(c)
        if isinstance(e, ExplodeSplit):
            if name in self.schema.names:
                raise ValueError(f"generated column {name!r} would shadow "
                                 "an existing column")
            if e.with_pos and "pos" in self.schema.names:
                raise ValueError("posexplode's 'pos' column would shadow an "
                                 "existing column; rename it first")
            return DataFrame(self.session, lp.LogicalGenerate(
                self._plan, e.split.children[0], e.split.delim, name,
                e.with_pos))
        if isinstance(e, WindowExpression):
            # window columns append to the child (Spark's WindowExec shape)
            out = DataFrame(self.session,
                            lp.LogicalWindow(self._plan, [(name, e)]))
            if name in self.schema.names:
                raise ValueError(f"window column {name!r} would shadow an "
                                 "existing column")
            return out
        schema = self.schema
        exprs = [(n, col_fn(n).expr) for n in schema.names if n != name]
        exprs.append((name, e))
        return DataFrame(self.session, lp.LogicalProject(self._plan, exprs))

    withColumn = with_column

    def filter(self, condition: Column) -> "DataFrame":
        return DataFrame(self.session,
                         lp.LogicalFilter(self._plan, _expr(condition)))

    where = filter

    def group_by(self, *cols) -> GroupedData:
        return GroupedData(self, cols)

    groupBy = group_by

    def rollup(self, *cols) -> "RollupData":
        return RollupData(self, cols, "rollup")

    def cube(self, *cols) -> "RollupData":
        return RollupData(self, cols, "cube")

    def agg(self, *agg_cols: Column) -> "DataFrame":
        return GroupedData(self, []).agg(*agg_cols)

    def order_by(self, *cols) -> "DataFrame":
        orders = []
        for c in cols:
            if isinstance(c, SortOrder):
                orders.append(c)
            elif isinstance(c, str):
                orders.append(SortOrder(col_fn(c).expr))
            else:
                orders.append(SortOrder(_expr(c)))
        return DataFrame(self.session, lp.LogicalSort(self._plan, orders))

    orderBy = order_by
    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, lp.LogicalLimit(self._plan, n))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session,
                         lp.LogicalUnion([self._plan, other._plan]))

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             left_on=None, right_on=None) -> "DataFrame":
        """Equi-join. ``on`` names columns present on both sides;
        ``left_on``/``right_on`` pair differently-named keys positionally
        (the TPC-H shape: l_orderkey = o_orderkey)."""
        how = {"outer": "full", "full_outer": "full", "left_outer": "left",
               "right_outer": "right", "semi": "leftsemi",
               "anti": "leftanti"}.get(how, how)

        def keyify(spec):
            if isinstance(spec, str):
                spec = [spec]
            return [col_fn(c).expr if isinstance(c, str) else _expr(c)
                    for c in spec]
        if left_on is not None or right_on is not None:
            assert left_on is not None and right_on is not None
            lkeys = keyify(left_on)
            rkeys = keyify(right_on)
            assert len(lkeys) == len(rkeys), "left_on/right_on length mismatch"
        elif on is None:
            lkeys, rkeys = [], []
            how = "cross"
        elif isinstance(on, Column):
            # arbitrary boolean condition -> nested-loop join (reference:
            # GpuBroadcastNestedLoopJoinExec, disabled on device by default)
            return DataFrame(self.session,
                             lp.LogicalJoin(self._plan, other._plan, how,
                                            [], [], condition=_expr(on)))
        elif isinstance(on, (str, list, tuple)):
            # Spark USING-column semantics: one output column per key name
            names = [on] if isinstance(on, str) else list(on)
            if how in ("leftsemi", "leftanti"):
                lkeys, rkeys = keyify(names), keyify(names)
            else:
                return self._join_using(other, names, how)
        else:
            raise TypeError("join on must be a column name, list of names, "
                            "or a boolean Column condition")
        return DataFrame(self.session,
                         lp.LogicalJoin(self._plan, other._plan, how,
                                        lkeys, rkeys))

    def _join_using(self, other: "DataFrame", names, how: str) -> "DataFrame":
        """join(on=[k]) merges each key into ONE output column: rename the
        right side's keys, join positionally, then re-emit a single key
        column (the left value, the right for right joins, coalesce for
        full — matching Spark's USING resolution)."""
        from spark_rapids_tpu.sql.exprs.conditional import Coalesce
        shared = (set(self.schema.names) & set(other.schema.names)) \
            - set(names)
        if shared:
            raise ValueError(
                "join(on=...) with non-key columns present on both sides is "
                f"ambiguous: {sorted(shared)}; alias or drop them first")
        rmap = {n: f"__rk_{n}" for n in names}
        right = other.select(*[
            col_fn(n).alias(rmap[n]) if n in rmap else col_fn(n)
            for n in other.schema.names])
        joined = DataFrame(self.session, lp.LogicalJoin(
            self._plan, right._plan, how,
            [col_fn(n).expr for n in names],
            [col_fn(rmap[n]).expr for n in names]))
        out = []
        for n in names:
            if how == "right":
                out.append(col_fn(rmap[n]).alias(n))
            elif how == "full":
                out.append(Column(Coalesce([col_fn(n).expr,
                                            col_fn(rmap[n]).expr])).alias(n))
            else:
                out.append(col_fn(n))
        out += [col_fn(n) for n in self.schema.names if n not in names]
        out += [col_fn(n) for n in other.schema.names if n not in names]
        return joined.select(*out)

    def drop(self, *names: str) -> "DataFrame":
        dropped = set(names)
        return self.select(*[n for n in self.schema.names
                             if n not in dropped])

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        return self.select(*[
            col_fn(n).alias(new) if n == old else col_fn(n)
            for n in self.schema.names])

    withColumnRenamed = with_column_renamed

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)

    def distinct(self) -> "DataFrame":
        """Deduplicate rows (planned as a group-by over every column)."""
        exprs = [(n, col_fn(n).expr) for n in self.schema.names]
        return DataFrame(self.session,
                         lp.LogicalAggregate(self._plan, exprs, [
                             (n, col_fn(n).expr) for n in self.schema.names]))

    def repartition(self, n: int) -> "DataFrame":
        return DataFrame(self.session, lp.LogicalRepartition(self._plan, n))

    def coalesce(self, n: int) -> "DataFrame":
        return DataFrame(self.session, lp.LogicalCoalesce(self._plan, n))

    # --- actions -----------------------------------------------------------
    def collect(self) -> pd.DataFrame:
        _, outs = self.session._execute(self._plan)
        # null-mask-preserving concat: partition frames can mix masked
        # and plain dtypes across partitions (exec/cpu.py)
        from spark_rapids_tpu.exec.cpu import concat_host_frames
        from spark_rapids_tpu.obs.trace import TRACER
        with TRACER.span("collect.concat") as sp:
            out = concat_host_frames(outs, self.schema)
            if sp is not None:
                sp.set(rows=len(out))
        return out

    toPandas = collect

    def count_rows(self) -> int:
        return int(len(self.collect()))

    def explain(self, mode: str = "ALL") -> str:
        """Print the physical plan with TPU tag annotations (the reference's
        hallmark spark.rapids.sql.explain feature)."""
        from spark_rapids_tpu.sql.overrides import TpuOverrides, TransitionOverrides
        conf = self.session.conf.copy()
        cpu_plan = Planner(conf).plan(self._plan)
        overrides = TpuOverrides(conf)
        overrides.apply(cpu_plan)
        text = overrides.explain_text()
        print(text)
        return text
