"""Physical plan base classes.

Execution model: a physical operator produces a list of *partitions*, each a
zero-arg callable returning an iterator of batches (the Spark
``RDD.mapPartitions`` shape the reference's operators use, e.g.
aggregate.scala:259-286). Two payload kinds flow through a mixed plan:

  * CPU operators:   pandas DataFrames          (the fallback path)
  * TPU operators:   columnar DeviceBatch       (the accelerated path)

Explicit transition operators convert between them
(exec/transitions.py — the analogue of GpuRowToColumnarExec /
GpuColumnarToRowExec / HostColumnarToGpu).
"""

from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence

from spark_rapids_tpu.columnar.batch import Schema

Partition = Callable[[], Iterator]  # yields pd.DataFrame or DeviceBatch


def group_contiguous(parts: Sequence[Partition],
                     n: int) -> List[List[Partition]]:
    """Contiguous partition grouping for CoalesceExec (like Spark's
    DefaultPartitionCoalescer), shared by the CPU and TPU operators."""
    n = min(max(1, int(n)), max(len(parts), 1))
    per = -(-len(parts) // n) if parts else 0
    groups: List[List[Partition]] = [[] for _ in range(n)]
    for i, p in enumerate(parts):
        groups[min(i // max(per, 1), n - 1)].append(p)
    return groups


class PhysicalPlan:
    """Base physical operator."""

    # True if this operator's output is device columnar (TPU path)
    columnar_output = False
    # True if this operator's batches systematically carry far more
    # capacity than rows, so an exchange above it counts and shrinks them
    # (TpuShuffleExchangeExec._padded_producer)
    padded_output = False

    def __init__(self, children: Sequence["PhysicalPlan"] = ()):  # noqa: D401
        self.children: List[PhysicalPlan] = list(children)

    @property
    def name(self) -> str:
        return type(self).__name__

    def output_schema(self) -> Schema:
        raise NotImplementedError

    def partitions(self, ctx: "ExecContext") -> List[Partition]:
        raise NotImplementedError

    def executed_partitions(self, ctx: "ExecContext") -> List[Partition]:
        """``partitions`` wrapped with per-operator SQL metrics and tracer
        spans (reference: GpuMetricNames per-exec Spark metrics,
        GpuExec.scala:24-41, + NvtxWithMetrics.scala:17-44). Consumers call
        this; operators implement ``partitions``. With metrics AND tracing
        disabled the partitions pass through untouched — no timers on the
        hot path."""
        parts = self.partitions(ctx)
        from spark_rapids_tpu.obs import compileledger
        from spark_rapids_tpu.obs.trace import TRACER
        prog = ctx.progress  # live monitoring (obs/progress.py)
        cancel = ctx.cancel  # cooperative cancellation (serving/)
        if not ctx.metrics_enabled and not TRACER.enabled \
                and prog is None and not compileledger.LEDGER.enabled \
                and cancel is None:
            return parts
        import time
        # tiny-query lite bookkeeping
        # (spark.rapids.sql.smallQuery.liteBookkeeping): one record per
        # operator per partition instead of per-batch timers + ledger
        # scopes + tracer spans — a pure fixed-cost removal for queries
        # whose wall time is Python dispatch. Anything that genuinely
        # needs batch granularity (tracing, profile sync, live progress,
        # cancellation scopes) forces the full wrapper back on.
        if (ctx.small_query and ctx.small_query_lite
                and not TRACER.enabled and prog is None
                and cancel is None and not ctx.profile_sync):
            record_lite = ctx.metrics_enabled
            lite_op = self.describe()
            lite_id = id(self)
            members = getattr(self, "member_ops", None)

            def lite_wrap(part: Partition) -> Partition:
                def run():
                    t0 = time.perf_counter()
                    rows = 0
                    it = part()
                    while True:
                        # ledger scope around the pull only (a thread-
                        # local set/unset): compile attribution — and a
                        # fused stage's member pipeline — survive, while
                        # the per-batch timers, tracer spans and
                        # progress heartbeats are elided
                        prev_op = compileledger.push_op(
                            lite_op, lite_id, ctx, members)
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                        finally:
                            compileledger.pop_op(prev_op)
                        r = getattr(batch, "_host_rows", None)
                        if r is None and not hasattr(batch, "num_rows"):
                            r = len(batch)
                        rows += r or 0
                        yield batch
                    if record_lite:
                        ctx.record_op(lite_op, lite_id,
                                      time.perf_counter() - t0, rows)
                return run
            return [lite_wrap(p) for p in parts]
        op = self.describe()
        record = ctx.metrics_enabled
        node_id = id(self)
        # profile mode: force a device sync after every operator's batch
        # so totalTime is ATTRIBUTABLE per kernel — without it dispatch is
        # async and all queued compute lands on whichever operator first
        # syncs (the first device_get carries ~85% of wall time). The
        # sync is a fetch of the num_rows device scalar, which completes
        # only once the batch's producing kernels have.
        sync_each = ctx.profile_sync

        def _force_sync(batch):
            nr = getattr(batch, "num_rows", None)
            if nr is not None:
                import jax

                from spark_rapids_tpu.obs.syncledger import sync_scope
                with sync_scope("profile.syncEachOp", nbytes=4):
                    jax.device_get(nr)

        def wrap(part: Partition, pidx: int) -> Partition:
            def run():
                it = part()
                while True:
                    if cancel is not None:
                        # batch-pull boundary: a cancelled or past-
                        # deadline query raises here instead of being
                        # killed mid-kernel, so the session's normal
                        # failure path releases its buffers/shuffles
                        cancel.check()
                    t0 = time.perf_counter()
                    with TRACER.span(self.name, op=op,
                                     partition=pidx) as sp:
                        # operator scope: a backend compile fired by a
                        # kernel call inside this pull attributes to
                        # THIS operator (obs/compileledger.py), and
                        # transfer sites report their seconds against it.
                        # Fused stages publish their member pipeline too.
                        prev_op = compileledger.push_op(
                            op, node_id, ctx,
                            getattr(self, "member_ops", None))
                        try:
                            batch = next(it)
                        except StopIteration:
                            return
                        finally:
                            compileledger.pop_op(prev_op)
                        rows = (batch._host_rows
                                if hasattr(batch, "_host_rows")
                                else len(batch))
                        if sp is not None:
                            sp.set(batch_rows=rows)
                    if sync_each:
                        t1 = time.perf_counter()
                        _force_sync(batch)
                        t2 = time.perf_counter()
                        # pull vs sync split: the pull is python dispatch
                        # (+ children + transfers), the sync is the
                        # device draining THIS operator's queued kernels
                        # (children already synced before yielding) —
                        # the profile's device/transfer/dispatch rows
                        compileledger.note_breakdown(
                            ctx, node_id, pull_s=t1 - t0, sync_s=t2 - t1)
                        # per-node-identity inclusive time: the profiler
                        # subtracts children to get exclusive per-kernel
                        # attribution (describe() keys merge same-shaped
                        # operators, which hides where time goes)
                        with ctx._stats_lock:
                            ctx.node_times[node_id] = ctx.node_times.get(
                                node_id, 0.0) + (time.perf_counter() - t0)
                    if record:
                        ctx.record_op(op, node_id,
                                      time.perf_counter() - t0, rows)
                    if prog is not None:
                        # per-batch heartbeat: per-operator rows/batches/
                        # time so far, served live at /api/query/<id>
                        prog.op_batch(node_id, op, rows,
                                      time.perf_counter() - t0)
                    yield batch
            return run
        return [wrap(p, i) for i, p in enumerate(parts)]

    def map_children(self, fn) -> "PhysicalPlan":
        import copy
        new = copy.copy(self)
        new.children = [fn(c) for c in self.children]
        return new

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + f"{self.describe()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.name

    def fingerprint_extra(self) -> str:
        """Extra identity beyond ``describe()`` for the structural plan
        fingerprint (plan_fingerprint): scans add their data identity,
        projects their expression signatures. Collisions are safe — every
        consumer of the fingerprint (the adaptive capacity cache) device-
        verifies what it speculates — they only cost cache churn."""
        return ""

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def plan_fingerprint(node: "PhysicalPlan") -> str:
    """Structural identity of a plan subtree, stable across executions of
    the same query over the same data (plan objects are rebuilt per
    execution; this string is not). Keys the session's adaptive capacity
    cache (reference analogue: AQE's per-stage runtime statistics reuse,
    which also keys on the canonicalized plan subtree)."""
    import hashlib
    parts: List[str] = []

    def rec(n: "PhysicalPlan") -> None:
        parts.append(n.describe())
        parts.append(n.fingerprint_extra())
        parts.append("(")
        for c in n.children:
            rec(c)
        parts.append(")")
    rec(node)
    return hashlib.md5("|".join(parts).encode()).hexdigest()


class ExecContext:
    """Per-query execution context: conf, session services, metrics."""

    def __init__(self, conf, session=None, speculate: bool = True):
        from spark_rapids_tpu.obs.metrics import MetricsRegistry
        self.conf = conf
        self.session = session
        # per-query metrics registry: per-op counters carry an op= label
        # and render back into the legacy {op: {metric: value}} dict via
        # the ``metrics`` property (session.last_query_metrics shape).
        # Thread-safe — the shuffle server and partition executor threads
        # accumulate concurrently.
        self.registry = MetricsRegistry()
        self.metrics_enabled = conf.get_bool(
            "spark.rapids.sql.metrics.enabled", True)
        # per-plan-node (identity-keyed) inclusive time/rows/batches for
        # the profile report (obs/profile.py)
        import threading
        self.node_stats: dict = {}
        self._stats_lock = threading.Lock()
        # per-operator sync for kernel attribution (tools/profile_query.py)
        self.profile_sync = conf.get_bool(
            "spark.rapids.sql.profile.syncEachOp", False)
        self.node_times: dict = {}
        # per-plan-node wall-time components (obs/compileledger.py
        # note_breakdown): pull_s/sync_s under profile_sync, transfer_s
        # from the host<->device transfer sites — the profile report
        # renders these as device/transfer/dispatch rows (obs/profile.py)
        self.node_breakdown: dict = {}
        # adaptive capacity speculation (spark.rapids.sql.adaptiveCapacity.
        # enabled): operators that speculated a device->host size fetch
        # from the session cache append (key, totals_device, caps_used,
        # ok_flags_device) here; the session verifies the whole list in
        # ONE fetch at query end and re-executes without speculation on
        # any miss (session._execute). ``speculate=False`` is that exact
        # re-execution.
        self.speculate = (
            speculate and session is not None
            and conf.get_bool("spark.rapids.sql.adaptiveCapacity.enabled",
                              True))
        self.spec_pending: list = []
        # integer columns whose bounds the scans of THIS execution read
        # from their files' footers while the plan was laid out
        # (TpuScanExec._declare_stats): an aggregate whose keys all
        # resolve to these plans dense on a first execution
        self.declared_stats: set = set()
        # adaptive-ratio cache entries written during this execution:
        # a speculative run that later fails verification learned its
        # ratios from possibly-garbage group counts — the session clears
        # exactly these before re-executing (session._execute)
        self.ratio_writes: list = []
        # per-query materialization state of deduped shared subtrees
        # (exec/reuse.TpuReuseSubtreeExec) — context-scoped so a fresh
        # context (speculation re-execution) re-runs the subtree
        self.reuse_state: dict = {}
        # live QueryProgress record (obs/progress.py), set by the session
        # only when the monitoring UI is enabled; None (the default)
        # keeps every heartbeat site a single is-None check
        self.progress = None
        # cooperative cancellation scope (serving/cancellation.py): the
        # scheduler installs it thread-locally before running a job;
        # executed_partitions checks it at every batch-pull boundary.
        # None (the default) keeps the hot path untouched.
        from spark_rapids_tpu.serving.cancellation import current_scope
        self.cancel = current_scope()
        # tiny-query overhead-floor fast path (sql/planner.py
        # note_input_size): the session sets this after planning when the
        # measured input is a single resident batch under the threshold.
        # Exchanges skip their shrink sync, uploads skip the semaphore,
        # and executed_partitions swaps the per-batch-pull bookkeeping
        # for one per-partition record (liteBookkeeping).
        self.small_query = False
        # expanding plans (joins/explode) keep the admission semaphore
        # even under the fast path — leaf row counts do not bound THEIR
        # working set (sql/planner.note_input_size)
        self.small_query_keep_sem = False
        self.small_query_lite = conf.get_bool(
            "spark.rapids.sql.smallQuery.liteBookkeeping", True)
        # per-QUERY resource tracking (shuffle ids registered, transient
        # spillable buffer ids): concurrent queries must each release
        # exactly their own at query end — a shared session-level list
        # would free a neighbor's live buffers (session.py routes its
        # register/release calls through the executing query's context)
        self.active_shuffles: list = []
        self.transient_bids: set = set()

    def metric_add(self, op: str, name: str, value):
        self.registry.counter(name, op=op).add(value)

    def record_op(self, op: str, node_id: int, seconds: float, rows):
        """One executed batch of one operator: per-op SQL metrics plus the
        per-node-identity stats the profile report attributes time with."""
        self.metric_add(op, "totalTime", seconds)
        self.metric_add(op, "numOutputBatches", 1)
        if rows is not None:
            self.metric_add(op, "numOutputRows", rows)
        with self._stats_lock:
            st = self.node_stats.get(node_id)
            if st is None:
                st = self.node_stats[node_id] = {
                    "time": 0.0, "rows": 0, "batches": 0}
            st["time"] += seconds
            st["batches"] += 1
            if rows is not None:
                st["rows"] += rows

    def op_metrics(self) -> dict:
        """Legacy nested-dict render of the registry: {op: {metric:
        value}} (the session.last_query_metrics shape)."""
        out: dict = {}
        for m in self.registry.metrics():
            op = m.labels.get("op")
            if op is not None:
                out.setdefault(op, {})[m.name] = m.value
        return out

    @property
    def metrics(self) -> dict:
        return self.op_metrics()
