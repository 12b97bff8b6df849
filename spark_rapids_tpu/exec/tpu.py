"""TPU columnar physical operators (the Gpu*Exec equivalents, L4).

Each operator's per-batch work is a single ``jax.jit``-compiled function
(cached per capacity bucket via pytree static aux data), so XLA fuses the
whole expression tree — and for aggregation the whole
hash/sort/segment-reduce pipeline — into one device executable. This is the
TPU-first improvement over the reference's one-cuDF-kernel-per-expression
dispatch (GpuExpressions.scala:98-149).
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema, bucket_capacity
from spark_rapids_tpu.columnar.column import (
    DICT_MAX_CARD_SMALL, DICT_SMALL_TABLE_ROWS, _char_bucket,
)
from spark_rapids_tpu.exec.aggutil import AggPlan
from spark_rapids_tpu.exec.base import ExecContext, Partition, PhysicalPlan
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.obs.trace import TRACER
from spark_rapids_tpu.ops import aggregate as agg_ops
from spark_rapids_tpu.ops import rowops, sortops
from spark_rapids_tpu.ops.groupby import row_hashes
from spark_rapids_tpu.utils.kernelcache import cached_jit, expr_signature
from spark_rapids_tpu.sql.exprs.core import Expression
from spark_rapids_tpu.sql.exprs.evalbridge import (
    eval_projection, make_context, to_device_column,
)
from spark_rapids_tpu.sql.exprs.stringexprs import counting_pattern_predicates
from spark_rapids_tpu.sql.functions import SortOrder


class TpuExec(PhysicalPlan):
    columnar_output = True

    def output_schema(self) -> Schema:
        raise NotImplementedError


def _concat_device(batches: List[DeviceBatch], schema: Schema,
                   growth: float, keep_masks=None,
                   coarse: bool = False) -> DeviceBatch:
    """Concatenate device batches (GpuCoalesceBatches / ConcatAndConsumeAll,
    GpuCoalesceBatches.scala:38-165). ``keep_masks``: per-batch keep
    vectors of a claimed Filter's mask form, ``None`` for a batch the
    filter already compacted (see _fused_filter_source). ``coarse``:
    pad the output capacity up the shape-bucket ladder
    (utils/kernelcache.bucket_dim) — used for SECONDARY-dimension
    materializations (join build tables, broadcast tables, fused
    count-distinct inputs) so one downstream compile serves a capacity
    range; identity while spark.rapids.tpu.compile.shapeBuckets is off."""
    if len(batches) == 1 and keep_masks is None:
        if coarse:
            from spark_rapids_tpu.utils.kernelcache import bucket_dim
            if bucket_dim(batches[0].capacity) == batches[0].capacity:
                return batches[0]
            # single-batch build tables still re-pad to the coarse
            # bucket: the point is a STABLE downstream capacity
        else:
            return batches[0]
    if not batches:
        return DeviceBatch.empty(schema)
    # mesh execution commits batches to their shard device; a concat that
    # spans shards (single-partition exchange, broadcast materialization)
    # must colocate first or the jit below rejects the device mix
    # colocation check via validity — NEVER .data: a lazy (codes-only)
    # string column would materialize its chars eagerly right here,
    # measured as 6 spurious device round trips per q1 run
    devs = {b.columns[0].validity.device for b in batches if b.columns}
    if len(devs) > 1:
        target = batches[0].columns[0].validity.device
        batches = [jax.device_put(b, target) for b in batches]
        if keep_masks is not None:
            keep_masks = [jax.device_put(k, target) for k in keep_masks]
    total_cap = sum(b.capacity for b in batches)
    out_cap = bucket_capacity(total_cap, growth)
    if coarse:
        from spark_rapids_tpu.utils.kernelcache import bucket_dim
        out_cap = bucket_dim(out_cap)
    # one generic jitted concat kernel; jax re-specializes per pytree shape.
    # char capacity 0 = per-column sum computed inside concat_batches.
    # dict-merge (union+remap at the boundary) changes the OUTPUT
    # representation for mixed-dictionary inputs, so the flag is part of
    # the kernel-cache signature — flipping
    # spark.rapids.sql.dict.mergeOnExchange mid-process cannot serve a
    # stale trace.
    from spark_rapids_tpu.columnar.dictionary import merge_exchange_enabled
    # NB: bind the flag as a default arg, not a closure — this frame
    # reuses the name ``dm`` below for the device manager, and a closure
    # over a reassigned local would silently flip the merge behavior on
    # every re-trace of the cached kernel
    dmerge = merge_exchange_enabled()
    if keep_masks is None:
        kernel = cached_jit(f"concat|dm{int(dmerge)}", lambda: jax.jit(
            lambda bs, oc, cc, _dm=dmerge: rowops.concat_batches(
                bs, oc, cc, dict_merge=_dm), static_argnums=(1, 2)))
        out = kernel(batches, out_cap, 0)
    else:
        kernel = cached_jit(f"concatmask|dm{int(dmerge)}", lambda: jax.jit(
            lambda bs, ks, oc, cc, _dm=dmerge: rowops.concat_batches(
                bs, oc, cc, keep_masks=ks, dict_merge=_dm),
            static_argnums=(2, 3)))
        out = kernel(batches, list(keep_masks), out_cap, 0)
    from spark_rapids_tpu.memory.device import TpuDeviceManager
    dm = TpuDeviceManager.current()
    if dm is not None:
        dm.meter_batch(out)
    return out


_COLLAPSE_BYTES = REGISTRY.counter("exchange.collapse.bytes")
_COLLAPSE_BATCHES = REGISTRY.counter("exchange.collapse.batches")
_COLLAPSE_COMPACTED = REGISTRY.counter("exchange.collapse.compactedBatches")
# the batches collapses emitted: 1 a collapse within its bound, one a
# piece where the bound cut it (_collapse_bound_bytes)
_COLLAPSE_PIECES = REGISTRY.counter("exchange.collapse.pieces")
_MERGE_ROWS = REGISTRY.counter("agg.merge.inputRows")
_MERGE_BYTES = REGISTRY.counter("agg.merge.inputBytes")
_PASSTHROUGH_ROWS = REGISTRY.counter("agg.partial.passthroughRows")
_DECLARED_COLUMNS = REGISTRY.counter("scan.stats.declaredColumns")


def _counting_rows(counter, kernel, byte_counter=None, row_bytes=0):
    """``kernel`` with the rows of its first argument added to ``counter``
    at every call, by what the host knows of the batch without a sync
    (``num_rows_hint``: the row count where it has been fetched, else the
    capacity); ``byte_counter`` takes those rows at ``row_bytes`` each."""
    def run(batch, *rest):
        rows = batch.num_rows_hint()
        counter.add(rows)
        if byte_counter is not None:
            byte_counter.add(rows * row_bytes)
        return kernel(batch, *rest)
    return run


def _row_bytes(schema: Schema) -> int:
    """Bytes a row of ``schema`` occupies at the least: each column's value
    at its width (a string as one 4-byte code) and a validity byte."""
    return sum((4 if dt.is_string else jnp.dtype(dt.np_dtype).itemsize) + 1
               for dt in schema.dtypes)


def _collapse_bound_bytes() -> int:
    """The most one batch of a local exchange collapse may hold, in bytes
    at ``_row_bytes`` a slot of its output capacity: a quarter of the
    metered HBM budget. A batch at the bound lives beside the batches it is
    concatenated from (as many bytes again) and, under a join, beside the
    build, the probe's sort over build and stream, and the probe's
    (counts, starts, perm) of 12 bytes a stream slot; a quarter leaves
    room for all of them. On a v5e (15.2 GB budget, 3.8 GB bound) that
    lets every collapse of a 2^26-slot batch of at most 56 bytes a row
    through whole (lineitem's four columns in Q5 at SF10 are 36), and cuts
    Q5's 180M lineitem rows at SF30 into 2^26-slot pieces."""
    from spark_rapids_tpu.memory.device import TpuDeviceManager
    dm = TpuDeviceManager.current()
    return dm.hbm_budget // 4 if dm is not None else 1 << 62


def _collapse_concat(batches: List[DeviceBatch], schema: Schema,
                     growth: float, keep_masks=None,
                     compacted: int = 0, pieces: int = 1) -> DeviceBatch:
    """One concat of a local exchange collapse, counted by what the host
    knows without a sync: ``exchange.collapse.batches`` input batches,
    ``exchange.collapse.bytes`` of device storage at their capacity
    (padding included, rows a mask will drop included) and one
    ``exchange.collapse.pieces``. The span ``exchange.collapse`` covers the
    concat's dispatch alone (``compacted``: how many of its batches a
    claimed filter had already compacted, _Drain; ``pieces``: the batches
    the collapse has emitted with this one, 1 where its bound did not cut
    it); the drain of the children above it belongs to the operator
    spans."""
    nbytes = sum(b.device_memory_size() for b in batches)
    _COLLAPSE_BATCHES.add(len(batches))
    _COLLAPSE_BYTES.add(nbytes)
    _COLLAPSE_PIECES.add(1)
    with TRACER.span("exchange.collapse", batches=len(batches),
                     bytes=nbytes, compacted=compacted, pieces=pieces,
                     bound_bytes=_collapse_bound_bytes()):
        return _concat_device(batches, schema, growth, keep_masks)


def _fused_filter_source(node: PhysicalPlan, ctx: ExecContext):
    """(source node, claimed filter) for the exchange/broadcast collapse: a
    deterministic TpuFilterExec directly below is claimed by the collapse
    and run a batch at a time as the child's batches arrive
    (_drain_claimed) — the exchange-side sibling of
    fuse_filter_into_aggregate (exec/fusion.py). ``claimed(batch)`` returns
    ``(batch, mask)`` in the filter's output schema:

      * where the selected columns are ``rowops.sort_compactable`` (at most
        four, fixed-width or dictionary strings — every filtered collapse
        of the benchmark), the filter's own ``filter|...`` kernel: the
        batch comes back prefix-compact with a device-side row count, no
        sync, and ``mask`` is None, so a collapse of such batches is the
        unmasked concat's block copies;
      * else the ``filtermask|...`` kernel's keep vector beside the
        zero-copy selection, and the concat compacts every part with one
        permutation and one gather a dtype group (``concatmask``) instead
        of per-batch per-column compaction gathers.

    The form is chosen from the batch's columns alone, a batch at a time.
    Returns (node, None) when nothing is claimed. NB the whole-stage cutter
    mirrors this claim (exec/stagecompiler/cutter._parent_claims_filter)
    and leaves the claimed filter out of fused pipelines — changes to the
    conditions here must be reflected there."""
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    if isinstance(node, TpuCoalesceBatchesExec):
        # the collapse concat coalesces everything anyway — a TargetSize
        # re-batching between the filter and the exchange is a no-op on
        # this path, and looking through it is what lets the filter be
        # claimed (the planner inserts Coalesce above every filter)
        node = node.children[0]
    if (isinstance(node, TpuFilterExec) and not node._impure
            and ctx.conf.get_bool(
                "spark.rapids.sql.exchange.fuseFilter", True)):
        cond = node.condition
        out_sel = node.out_sel
        compact_kernel = node._kernel
        sig = "filtermask|" + expr_signature(cond)

        def build():
            def mask(batch: DeviceBatch):
                ectx = make_context(batch)
                pred = to_device_column(ectx, cond.eval_device(ectx))
                return pred.data & pred.validity & batch.row_mask()
            return jax.jit(mask)
        mask_kernel = counting_pattern_predicates([cond])(
            cached_jit(sig, build))

        def claimed(batch: DeviceBatch):
            view = _select_view(batch, out_sel)
            if rowops.sort_compactable(view.columns):
                return compact_kernel(batch), None
            return view, mask_kernel(batch)
        return node.children[0], claimed
    return node, None


class _Drain:
    """A collapse's child partitions, pulled in order a group of batches
    at a time (``take``). A claimed filter (_fused_filter_source) runs on
    every batch as it arrives, so its program is queued on the device
    while the host decodes the next split."""

    def __init__(self, parts: Sequence[Partition], claimed):
        self.claimed = claimed
        self._source = (claimed(b) if claimed is not None else (b, None)
                        for p in parts for b in p())
        # the (batch, mask) that would have carried the last group past
        # its bound: the first of the next
        self._held = None
        self.exhausted = False

    def take(self, full=None):
        """(batches, keep masks, compacted) of the next group: every batch
        left, or, where ``full(batches, batch)`` says ``batch`` would carry
        the group past its bound, the batches before it (one at the
        least). ``compacted`` counts the batches that came back compacted
        (``exchange.collapse.compactedBatches``); where that is all of
        them, or nothing is claimed, the masks are None and the concat is
        the unmasked one."""
        batches, masks = [], []
        # the serial section before the collapse's consumer can start: it
        # holds the children's pulls, as the operator span does
        with TRACER.span("exchange.drain",
                         claimed=self.claimed is not None) as sp:
            while True:
                got = (self._held if self._held is not None
                       else next(self._source, None))
                self._held = None
                if got is None:
                    self.exhausted = True
                    break
                if batches and full is not None and full(batches, got[0]):
                    self._held = got
                    break
                batches.append(got[0])
                masks.append(got[1])
            compacted = (sum(m is None for m in masks)
                         if self.claimed is not None else 0)
            if sp is not None:
                sp.set(batches=len(batches), compacted=compacted)
        _COLLAPSE_COMPACTED.add(compacted)
        if self.claimed is None or compacted == len(masks):
            masks = None
        return batches, masks, compacted


def _drain_claimed(parts: Sequence[Partition], claimed):
    """(batches, keep masks, compacted) of every batch of a collapse's
    child partitions, in order (_Drain.take)."""
    return _Drain(parts, claimed).take()


def _select_view(batch: DeviceBatch, out_sel) -> DeviceBatch:
    """Zero-copy column selection (no device op)."""
    if out_sel is None:
        return batch
    names, idx = out_sel
    return DeviceBatch(
        Schema(list(names), [batch.schema.dtypes[i] for i in idx]),
        [batch.columns[i] for i in idx], batch.num_rows)


def _split_by_pid(batch: DeviceBatch, pid: jnp.ndarray, n: int):
    """Sort rows by partition id (dead rows to the back) and count per-pid
    rows — the contiguous-split analogue (GpuPartitioning.scala:41-75)."""
    pid = jnp.where(batch.row_mask(), pid, n)
    perm = jnp.argsort(pid, stable=True).astype(jnp.int32)
    sorted_batch = rowops.gather_batch(batch, perm, batch.num_rows)
    counts = jnp.zeros((n,), jnp.int32).at[
        jnp.clip(pid, 0, n - 1)].add(jnp.where(pid < n, 1, 0))
    return sorted_batch, counts


class TpuProjectExec(TpuExec):
    """reference: GpuProjectExec (basicPhysicalOperators.scala:65)."""

    def __init__(self, child: PhysicalPlan,
                 exprs: Sequence[Tuple[str, Expression]]):
        super().__init__([child])
        self.exprs = list(exprs)
        names = [n for n, _ in self.exprs]
        bound = [e for _, e in self.exprs]
        from spark_rapids_tpu.sql.exprs.nondet import has_nondeterministic
        self._impure = any(has_nondeterministic(e) for e in bound)
        from spark_rapids_tpu.sql.exprs.core import Alias, BoundRef

        def as_ref(e):
            """The BoundRef behind (possibly aliased) e, else None."""
            while isinstance(e, Alias):
                e = e.children[0]
            return e if isinstance(e, BoundRef) else None

        self._pure_selection = (not self._impure and all(
            as_ref(e) is not None for e in bound))
        if self._pure_selection:
            # selection/rename-only projection: re-arrange the COLUMN
            # OBJECTS, no device work at all. A jitted identity kernel
            # would copy every buffer (jit outputs are fresh buffers
            # unless donated) — measured 0.39s PER narrowing project on a
            # 2M-row join chain (q7 carries three of them).
            sel = (tuple(names), tuple(as_ref(e).index for e in bound))
            self._kernel = lambda batch: _select_view(batch, sel)
        elif self._impure:
            # nondeterministic exprs read task-local state (partition id,
            # row offset, input file) that must be current at call time, so
            # the projection is traced eagerly per batch instead of through
            # the process-wide kernel cache (the reference similarly special
            # cases these, GpuTransitionOverrides.scala:110-123).
            self._kernel = counting_pattern_predicates(bound)(
                lambda batch: eval_projection(batch, bound, names))
        elif any(as_ref(e) is not None for e in bound):
            # mixed projection: jit computes ONLY the derived outputs;
            # bare-reference outputs pass their column objects through
            # untouched (the jitted identity would copy their buffers)
            comp = [(n, e) for n, e in self.exprs if as_ref(e) is None]
            sig = "projectmix|" + "|".join(
                f"{n}={expr_signature(e)}" for n, e in comp)
            ckern = counting_pattern_predicates(bound)(
                cached_jit(sig, lambda: jax.jit(
                    lambda batch: eval_projection(
                        batch, [e for _n, e in comp],
                        [n for n, _e in comp]))))

            def mixed_kernel(batch: DeviceBatch) -> DeviceBatch:
                computed = ckern(batch)
                out_cols = []
                ci = 0
                for _n, e in self.exprs:
                    ref = as_ref(e)
                    if ref is not None:
                        out_cols.append(batch.columns[ref.index])
                    else:
                        out_cols.append(computed.columns[ci])
                        ci += 1
                return DeviceBatch(
                    Schema(names, [c.dtype for c in out_cols]),
                    out_cols, batch.num_rows)
            self._kernel = mixed_kernel
        else:
            sig = "project|" + "|".join(
                f"{n}={expr_signature(e)}" for n, e in self.exprs)
            self._kernel = counting_pattern_predicates(bound)(
                cached_jit(sig, lambda: jax.jit(
                    lambda batch: eval_projection(batch, bound, names))))

    def output_schema(self) -> Schema:
        cs = self.children[0].output_schema()
        return Schema([n for n, _ in self.exprs],
                      [e.dtype(cs) for _, e in self.exprs])

    def describe(self) -> str:
        return f"TpuProjectExec([{', '.join(n for n, _ in self.exprs)}])"

    def fingerprint_extra(self) -> str:
        from spark_rapids_tpu.utils.kernelcache import expr_signature
        return ";".join(expr_signature(e) for _, e in self.exprs)

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        from spark_rapids_tpu.exec import taskctx
        child_parts = self.children[0].executed_partitions(ctx)

        def make(index: int, part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                seen = 0
                for batch in part():
                    if self._impure:
                        taskctx.set_partition(index)
                        taskctx.set_row_base(seen)
                        seen += batch.num_rows_host()
                    yield self._kernel(batch)
            return run
        return [make(i, p) for i, p in enumerate(child_parts)]


class TpuFilterExec(TpuExec):
    """reference: GpuFilterExec (basicPhysicalOperators.scala:126).

    ``out_sel``: optional (names, indices) output selection fused from a
    pure-column Project above (exec/fusion.py fuse_selection_into_filter):
    the predicate evaluates over the FULL input, but the row compaction
    gathers ONLY the selected columns — predicate-only columns (string
    slabs especially) are never moved."""

    def __init__(self, child: PhysicalPlan, condition: Expression,
                 out_sel=None):
        super().__init__([child])
        self.condition = condition
        self.out_sel = out_sel

        def kernel(batch: DeviceBatch) -> DeviceBatch:
            ctx = make_context(batch)
            pred = to_device_column(ctx, condition.eval_device(ctx))
            keep = pred.data & pred.validity
            return rowops.filter_batch(_select_view(batch, out_sel), keep)
        # the un-jitted closure: whole-stage fusion traces it INSIDE the
        # fused program (exec/stagecompiler/fusedexec.member_fn), so the
        # fused and standalone spellings can never diverge
        self._raw_kernel = kernel
        from spark_rapids_tpu.sql.exprs.nondet import has_nondeterministic
        self._impure = has_nondeterministic(condition)
        if self._impure:
            # see TpuProjectExec: task-local state must be read at call time
            self._kernel = counting_pattern_predicates([condition])(kernel)
        else:
            # names participate in the cache key: the closure bakes the
            # output Schema, so an aliased selection must not hit a
            # same-ordinal kernel compiled under different names
            sel_sig = ("" if out_sel is None
                       else f"|sel={tuple(out_sel[1])}"
                            f":{','.join(out_sel[0])}")
            sig = "filter|" + expr_signature(condition) + sel_sig
            self._kernel = counting_pattern_predicates([condition])(
                cached_jit(sig, lambda: jax.jit(kernel)))

    def output_schema(self) -> Schema:
        cs = self.children[0].output_schema()
        if self.out_sel is None:
            return cs
        names, idx = self.out_sel
        return Schema(list(names), [cs.dtypes[i] for i in idx])

    def describe(self) -> str:
        sel = ("" if self.out_sel is None
               else f", sel={list(self.out_sel[0])}")
        return f"TpuFilterExec({self.condition!r}{sel})"

    def fingerprint_extra(self) -> str:
        # expr repr prints only class name + children for many nodes
        # (startswith('a') vs startswith('b') collide); the signature
        # serializes every instance attribute
        from spark_rapids_tpu.utils.kernelcache import expr_signature
        return expr_signature(self.condition)

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        from spark_rapids_tpu.exec import taskctx
        child_parts = self.children[0].executed_partitions(ctx)

        def make(index: int, part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                seen = 0
                for batch in part():
                    if self._impure:
                        taskctx.set_partition(index)
                        taskctx.set_row_base(seen)
                        seen += batch.num_rows_host()
                    yield self._kernel(batch)
            return run
        return [make(i, p) for i, p in enumerate(child_parts)]


class TpuHashAggregateExec(TpuExec):
    """reference: GpuHashAggregateExec (aggregate.scala:227-509). Streaming
    per-batch update, then concat + merge of the (small) partial results —
    the reference's exact loop shape, each step one fused XLA program."""

    padded_output = True

    def __init__(self, child: PhysicalPlan, plan: AggPlan, mode: str,
                 pre_mask: Optional[Expression] = None):
        super().__init__([child])
        self.plan = plan
        self.mode = mode
        # fused pre-filter predicate (exec/fusion.py): evaluated inside the
        # update kernel, replacing a standalone Filter's compaction gathers
        self.pre_mask = pre_mask
        p = self.plan
        if mode == "partial":
            key_exprs = [e for _, e in p.grouping]
            reductions = []
            for ops in p.update_plan:
                for kind, input_idx, idt in ops:
                    reductions.append((kind, input_idx, idt))
            mask_sig = ("|mask=" + expr_signature(pre_mask)
                        if pre_mask is not None else "")
            # every program over the child's batches counts the pattern
            # predicates of the fused mask, the keys and the inputs
            counted = counting_pattern_predicates(
                ([pre_mask] if pre_mask is not None else [])
                + key_exprs + list(p.update_inputs))
            self._kernel = counted(cached_jit(
                "aggupd|" + p.signature + mask_sig,
                lambda: jax.jit(lambda b: agg_ops.aggregate_update(
                    b, key_exprs, p.update_inputs, reductions,
                    p.partial_schema, mask_expr=pre_mask))))
            # bounded-int composite grouping key variant (advisory scan
            # stats resolved at partitions() time; the ONLY compiled
            # grouping path — a miss re-executes via the deferred
            # speculation verification, ops/aggregate.dense_composite)
            self._dense_update = lambda sizes: counted(cached_jit(
                f"aggupd|{p.signature}{mask_sig}|dense{sizes}",
                lambda: jax.jit(lambda b, los: agg_ops.aggregate_update(
                    b, key_exprs, p.update_inputs, reductions,
                    p.partial_schema, mask_expr=pre_mask,
                    dense=(los, sizes)))))
            # one-pass hash-aggregation variant (spark.rapids.sql.agg.
            # hashAggEnabled): same program, the slot-table branch armed
            # with its slot budget — _hash_payload_reduce declines at
            # TRACE time where inapplicable, so this kernel is safe for
            # any batch
            self._hash_update = lambda mt: counted(cached_jit(
                f"aggupd|{p.signature}{mask_sig}|hash{mt}",
                lambda: jax.jit(lambda b: agg_ops.aggregate_update(
                    b, key_exprs, p.update_inputs, reductions,
                    p.partial_schema, mask_expr=pre_mask, hash_table=mt))))
            # adaptive low-reduction skip: rows projected straight into the
            # partial layout (spark.rapids.sql.agg.skipAggPassReductionRatio)
            self._passthrough_kernel = _counting_rows(
                _PASSTHROUGH_ROWS, counted(cached_jit(
                    "aggpass|" + p.signature + mask_sig,
                    lambda: jax.jit(lambda b: agg_ops.aggregate_passthrough(
                        b, key_exprs, p.update_inputs, reductions,
                        p.partial_schema, mask_expr=pre_mask)))))
            # merging partials within the partition uses merge kinds
            self._merge_kernel = self._make_merge_kernel()
        else:
            self._merge_kernel = self._make_merge_kernel()
            final_exprs = p.finalize_exprs()
            names = [n for n, _ in final_exprs]
            bound = [e for _, e in final_exprs]
            self._final_kernel = cached_jit(
                "aggfin|" + p.signature,
                lambda: jax.jit(lambda b: eval_projection(b, bound, names)))

    def _make_merge_kernel(self):
        p = self.plan
        reductions = []
        for merged in p.merge_plan:
            for kind, col, idt in merged:
                reductions.append((kind, col, idt))
        # every aggmrg program counts its input into agg.merge.inputRows
        # and, at the partial layout's least width, agg.merge.inputBytes
        def counted(kernel):
            return _counting_rows(_MERGE_ROWS, kernel, _MERGE_BYTES,
                                  _row_bytes(p.partial_schema))
        self._dense_merge = lambda sizes: counted(cached_jit(
            f"aggmrg|{p.signature}|dense{sizes}",
            lambda: jax.jit(lambda b, los: agg_ops.aggregate_merge(
                b, p.num_keys, reductions, p.partial_schema,
                dense=(los, sizes)))))
        self._hash_merge = lambda mt: counted(cached_jit(
            f"aggmrg|{p.signature}|hash{mt}",
            lambda: jax.jit(lambda b: agg_ops.aggregate_merge(
                b, p.num_keys, reductions, p.partial_schema,
                hash_table=mt))))
        return counted(cached_jit(
            "aggmrg|" + p.signature,
            lambda: jax.jit(lambda b: agg_ops.aggregate_merge(
                b, p.num_keys, reductions, p.partial_schema))))

    def _dense_group_plan(self, ctx: ExecContext):
        """(los list, sizes tuple, spec_key) for the bounded-int composite
        grouping key, or None (non-int keys, unresolvable stats, >62
        bits, speculation off, a first execution over bounds no scan
        declared, or blocklisted after a verification miss).
        The dense program is the ONLY compiled grouping path; the
        device-computed ok flag joins the deferred speculation
        verification and a miss re-executes without dense (and
        blocklists this plan so chronically-stale stats do not re-run
        every execution)."""
        if (ctx.session is None or not getattr(ctx, "speculate", False)
                or not ctx.conf.get_bool(
                    "spark.rapids.sql.agg.denseKeys", True)):
            return None
        p = self.plan
        if p.num_keys == 0:
            return None
        from spark_rapids_tpu.exec.statsutil import dense_group_plan
        from spark_rapids_tpu.sql.exprs.core import BoundRef
        key_names, key_dts = [], []
        if self.mode == "partial":
            cs = p.child_schema
            for name, e in p.grouping:
                if not isinstance(e, BoundRef):
                    return None
                names = {name}
                if 0 <= e.index < len(cs.names):
                    names.add(cs.names[e.index])
                key_names.append(names)
                key_dts.append(cs.dtypes[e.index])
        else:
            ps = p.partial_schema
            for j in range(p.num_keys):
                key_names.append({ps.names[j]})
                key_dts.append(ps.dtypes[j])
        from spark_rapids_tpu.exec.base import plan_fingerprint
        from spark_rapids_tpu.exec.statsutil import stats_names
        fp = plan_fingerprint(self)
        # dense engages on a FIRST execution only where every key resolves
        # to columns whose bounds a scan declared while this plan was laid
        # out (Parquet footers: they cover exactly the splits the scans
        # below will read). Otherwise only for a plan the session has
        # EXECUTED before: on a first execution the measured stats may not
        # cover this upload yet (they record as batches stream, after
        # planning), and a guaranteed-stale speculation would re-execute
        # the query
        def declared(names) -> bool:
            found = stats_names(ctx.session, names)
            return bool(found) and found <= ctx.declared_stats
        source = "declared" if all(map(declared, key_names)) else "seen"
        if source == "seen":
            seen = ctx.session.dense_plans_seen
            if fp not in seen:
                seen.add(fp)
                return None
        got = dense_group_plan(ctx.session, key_names, key_dts)
        if got is None:
            return None
        skey = f"nocache|densegroup|{fp}|{got[1]}"
        if skey in ctx.session.capacity_spec_blocklist:
            return None
        REGISTRY.counter("agg.dense.plans", source=source).add(1)
        return got[0], got[1], skey

    def output_schema(self) -> Schema:
        return (self.plan.partial_schema if self.mode == "partial"
                else self.plan.output_schema)

    def describe(self) -> str:
        keys = ", ".join(n for n, _ in self.plan.grouping)
        fused = (f", fused_filter={self.pre_mask!r}"
                 if self.pre_mask is not None else "")
        return f"TpuHashAggregateExec(mode={self.mode}, keys=[{keys}]{fused})"

    def fingerprint_extra(self) -> str:
        extra = ""
        if self.pre_mask is not None:
            from spark_rapids_tpu.utils.kernelcache import expr_signature
            extra = "|mask:" + expr_signature(self.pre_mask)
        return self.plan.signature + extra

    # batches sampled before an undecided signature commits to the
    # update path: bounds the row-count syncs a first execution pays
    _SKIP_SAMPLE_BATCHES = 3

    def _runtime_partial(self, ctx, it, first, update_kernel, merge_kernel,
                         cache, sig, adaptive, prior, skip_ratio, growth):
        """Runtime partial-aggregation skip (spark.rapids.sql.agg.
        runtimeSkip): the partial pass measures output_groups/input_rows
        as batches stream and flips to passthrough MID-STREAM once the
        cumulative ratio exceeds the threshold — already-updated partials
        flush as-is (the final aggregate reduces any mix of grouped and
        passthrough layouts). Decisions are journaled (aggSkipDecision,
        carrying the measured rate) and recorded in the session ratio
        cache either way, so later executions decide from batch 0 with
        zero syncs; capacity-shrunk outputs prove strong reduction
        without any sync and are never recorded (the bounded-cardinality
        paths, matching the legacy heuristic)."""
        from spark_rapids_tpu.obs.events import EVENTS
        partials = []
        # a recorded good ratio short-circuits measurement entirely
        decided = "update" if (not adaptive or prior is not None) else None
        in_rows = out_rows = sampled = 0
        b = first
        while b is not None:
            if decided == "skip":
                yield self._passthrough_kernel(b)
                b = next(it, None)
                continue
            p = update_kernel(b)
            partials.append(p)
            if decided is None:
                if p.capacity < b.capacity:
                    decided = "update"
                else:
                    from spark_rapids_tpu.obs.syncledger import sync_scope
                    with sync_scope("agg.runtimeSkip",
                                    detail=f"batch={sampled}"):
                        out_rows += p.num_rows_host()
                    in_rows += b.num_rows_hint()
                    sampled += 1
                    measured = out_rows / max(in_rows, 1)
                    if measured > skip_ratio:
                        decided = "skip"
                        cache[sig] = [measured, 0]
                        ctx.ratio_writes.append(sig)
                        EVENTS.emit("aggSkipDecision", decision="skip",
                                    source="measured",
                                    measuredRatio=float(measured),
                                    batches=sampled, threshold=skip_ratio)
                        for pp in partials:
                            yield pp
                        partials = []
                    elif sampled >= self._SKIP_SAMPLE_BATCHES:
                        decided = "update"
                        cache[sig] = [measured, 0]
                        ctx.ratio_writes.append(sig)
                        EVENTS.emit("aggSkipDecision", decision="update",
                                    source="measured",
                                    measuredRatio=float(measured),
                                    batches=sampled, threshold=skip_ratio)
            b = next(it, None)
        if decided is None and sampled > 0:
            # stream ended while still sampling (short partitions): the
            # cumulative measurement is the signature's decision —
            # recorded so later executions decide from batch 0 with no
            # syncs (the legacy heuristic's single-batch learning)
            measured = out_rows / max(in_rows, 1)
            cache[sig] = [measured, 0]
            ctx.ratio_writes.append(sig)
            EVENTS.emit("aggSkipDecision", decision="update",
                        source="measured", measuredRatio=float(measured),
                        batches=sampled, threshold=skip_ratio)
        if len(partials) == 1:
            yield partials[0]
        elif partials:
            merged = _concat_device(partials, self.plan.partial_schema,
                                    growth)
            yield merge_kernel(merged)

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        growth = ctx.conf.capacity_growth

        from spark_rapids_tpu.config.conf import (
            AGG_HASH_ENABLED, AGG_HASH_MAX_SLOTS, AGG_RUNTIME_SKIP,
            AGG_SKIP_RATIO,
        )
        skip_ratio = float(ctx.conf.get(AGG_SKIP_RATIO.key))
        runtime_skip = ctx.conf.get_bool(AGG_RUNTIME_SKIP.key, True)
        hash_on = ctx.conf.get_bool(AGG_HASH_ENABLED.key, False)
        max_slots = int(ctx.conf.get(AGG_HASH_MAX_SLOTS.key))

        dense = self._dense_group_plan(ctx)
        # dense keys outrank the hash table (exact composite key, fewer
        # sort operands); hash engages exactly where dense cannot
        use_hash = hash_on and self.plan.num_keys > 0 and dense is None
        if dense is not None:
            los_arr = jnp.asarray(dense[0], jnp.int64)
            sizes, skey = dense[1], dense[2]

            def _register(ok) -> None:
                from spark_rapids_tpu.exec.tpujoin import _start_host_copies
                _start_host_copies([ok])
                ctx.spec_pending.append((skey, [], [], [ok], None))

            dmerge = self._dense_merge(sizes)

            def merge_kernel(b):
                out, ok = dmerge(b, los_arr)
                _register(ok)
                return out
            if self.mode == "partial":
                dupd = self._dense_update(sizes)

                def update_kernel(b):
                    out, ok = dupd(b, los_arr)
                    _register(ok)
                    return out
            else:
                update_kernel = None
        elif use_hash:
            merge_kernel = self._hash_merge(max_slots)
            update_kernel = (self._hash_update(max_slots)
                             if self.mode == "partial" else None)
        else:
            merge_kernel = self._merge_kernel
            update_kernel = self._kernel if self.mode == "partial" else None

        # VMEM-bound recursed bucketing: a batch whose slot table would
        # exceed maxTableSlots splits by key hash into in-budget slices
        # (disjoint key sets), each aggregates in-VMEM, and the slices'
        # partial outputs concatenate back into ONE valid partial batch
        # (no cross-slice merge needed — no key spans two slices). Only
        # column-reference grouping keys can drive the input-batch
        # partitioner; expression keys keep the in-trace sorted fallback.
        hash_split_idx = None
        if use_hash and self.mode == "partial":
            from spark_rapids_tpu.sql.exprs.core import BoundRef
            if all(isinstance(e, BoundRef) for _, e in self.plan.grouping):
                hash_split_idx = [e.index for _, e in self.plan.grouping]

        if hash_split_idx is not None and update_kernel is not None:
            from spark_rapids_tpu.exec import outofcore as ooc
            from spark_rapids_tpu.ops import tablekernels as tk
            base_update = update_kernel

            def _bucketed_update(b, level=0):
                if (level >= 3
                        or tk.hash_table_size(b.capacity) <= max_slots):
                    return base_update(b)
                need = -(-tk.hash_table_size(b.capacity) // max_slots)
                n = 2
                while n < 2 * need and n < 64:
                    n <<= 1
                parts = [_bucketed_update(s, level + 1)
                         for s in ooc.split_batch_by_hash(
                             ctx, hash_split_idx, b, n, level, growth)]
                if not parts:
                    return base_update(b)
                return _concat_device(parts, self.plan.partial_schema,
                                      growth)
            update_kernel = _bucketed_update

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                # out-of-core: a grouped aggregate whose input exceeds
                # the budget hash-partitions its partial-layout batches
                # onto the spill store and merges bucket by bucket
                # (disjoint key sets; exec/outofcore.py)
                from spark_rapids_tpu.exec import outofcore as ooc
                src = part
                if ooc.enabled_for(ctx) and self.plan.num_keys > 0:
                    # streaming probe: never materializes past the
                    # budget — on engagement the unconsumed tail flows
                    # straight into the grace driver's staging pass
                    prefix, rest, engaged = ooc.split_stream_on_budget(
                        ctx, iter(part()))
                    if engaged:
                        import itertools
                        yield from ooc.grace_aggregate(
                            ctx, self, itertools.chain(prefix, rest),
                            growth)
                        return
                    src = lambda ob=prefix: iter(ob)  # noqa: E731
                if self.mode == "partial":
                    it = iter(src())
                    first = next(it, None)
                    if first is None:
                        yield self._kernel(DeviceBatch.empty(
                            self.children[0].output_schema()))
                        return
                    # adaptive statistics (Spark-AQE-style): the session
                    # remembers each aggregate's observed reduction
                    # ratio; a known-poor reducer skips its partial pass
                    # from batch 0 — including single-batch partitions,
                    # where the ratio is otherwise only learnable AFTER
                    # paying the full pass. Keyed on the PLAN FINGERPRINT
                    # (data-uid-stamped, exec/base.py): a different data
                    # source mints a different key, so entries never need
                    # a use-count expiry — the old structural-signature
                    # key's periodic expiry flipped the skip decision in
                    # steady state, changing batch shapes downstream and
                    # forcing a retrace in the bench's timed window.
                    cache = getattr(ctx.session, "agg_ratio_cache", None) \
                        if ctx.session else None
                    from spark_rapids_tpu.exec.base import plan_fingerprint
                    sig = plan_fingerprint(self) + "|ratio"
                    adaptive = (skip_ratio < 1.0 and cache is not None
                                and self.plan.num_keys > 0)
                    prior = None
                    if adaptive and sig in cache:
                        ratio_known, uses = cache[sig]
                        prior = ratio_known
                        if ratio_known > skip_ratio:
                            cache[sig][1] = uses + 1
                            if runtime_skip:
                                from spark_rapids_tpu.obs.events import (
                                    EVENTS,
                                )
                                EVENTS.emit(
                                    "aggSkipDecision", decision="skip",
                                    source="cache",
                                    measuredRatio=float(ratio_known),
                                    threshold=skip_ratio)
                            yield self._passthrough_kernel(first)
                            for b in it:
                                yield self._passthrough_kernel(b)
                            return
                    if runtime_skip:
                        # AQE-style runtime decision from measured
                        # per-batch reduction rates (spark.rapids.sql.
                        # agg.runtimeSkip); false restores the legacy
                        # first-batch-only heuristic below
                        yield from self._runtime_partial(
                            ctx, it, first, update_kernel, merge_kernel,
                            cache, sig, adaptive, prior, skip_ratio,
                            growth)
                        return
                    p0 = update_kernel(first)
                    second = next(it, None)
                    # learn the ratio (one row-count sync, first execution
                    # of a signature only) whenever the partial kept its
                    # input capacity — the bounded-cardinality paths
                    # shrink it, proving heavy reduction without a round
                    # trip
                    ratio = None
                    if (adaptive and sig not in cache
                            and p0.capacity >= first.capacity):
                        ratio = (p0.num_rows_host()
                                 / max(first.num_rows_hint(), 1))
                        cache[sig] = [ratio, 0]
                        ctx.ratio_writes.append(sig)
                    if second is None:
                        yield p0
                        return
                    # adaptive skip: the first batch's pass barely reduced
                    # -> project the remaining batches straight into the
                    # partial layout and let the final aggregate reduce
                    # once; on a single chip the exchange is a local
                    # concat, so a low-reduction partial pass is pure cost
                    if ratio is not None and ratio > skip_ratio:
                        yield p0
                        while second is not None:
                            yield self._passthrough_kernel(second)
                            second = next(it, None)
                        return
                    partials = [p0, update_kernel(second)]
                    partials.extend(update_kernel(b) for b in it)
                    merged = _concat_device(partials, self.plan.partial_schema,
                                            growth)
                    yield merge_kernel(merged)
                    return
                batches = list(src())
                merged_in = _concat_device(batches, self.plan.partial_schema,
                                           growth)
                merged = merge_kernel(merged_in)
                yield self._final_kernel(merged)
            return run
        return [make(p) for p in child_parts]


class TpuSortExec(TpuExec):
    """reference: GpuSortExec (GpuSortExec.scala:50-253) — RequireSingleBatch
    global sort: concat partition batches, one fused device sort."""

    def __init__(self, child: PhysicalPlan, orders: Sequence[SortOrder]):
        super().__init__([child])
        self.orders = list(orders)

        def kernel(batch: DeviceBatch) -> DeviceBatch:
            work, key_idx = self._key_batch(batch)
            sorted_b = sortops.sort_batch(
                work, key_idx,
                [o.ascending for o in self.orders],
                [o.nulls_first for o in self.orders])
            # drop appended key columns
            ncols = len(batch.schema.names)
            return DeviceBatch(batch.schema, sorted_b.columns[:ncols],
                               sorted_b.num_rows)
        sig = "sort|" + "|".join(
            f"{expr_signature(o.expr)}:{o.ascending}:{o.nulls_first}"
            for o in self.orders)
        self._kernel = cached_jit(sig, lambda: jax.jit(kernel))

    def _key_batch(self, batch: DeviceBatch):
        """Append evaluated sort-key expressions as extra columns."""
        ctx = make_context(batch)
        cols = list(batch.columns)
        names = list(batch.schema.names)
        dts = list(batch.schema.dtypes)
        key_idx = []
        for i, o in enumerate(self.orders):
            c = to_device_column(ctx, o.expr.eval_device(ctx))
            cols.append(c)
            names.append(f"_sk{i}")
            dts.append(c.dtype)
            key_idx.append(len(cols) - 1)
        return DeviceBatch(Schema(names, dts), cols, batch.num_rows), key_idx

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def describe(self) -> str:
        return f"TpuSortExec({self.orders})"

    def fingerprint_extra(self) -> str:
        from spark_rapids_tpu.utils.kernelcache import expr_signature
        return ";".join(
            f"{expr_signature(o.expr)}|a{int(o.ascending)}"
            f"|n{int(o.nulls_first)}" for o in self.orders)

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        growth = ctx.conf.capacity_growth
        schema = self.output_schema()

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                # out-of-core: a working set past the budget range-
                # partitions onto the spill store and sorts bucket by
                # bucket (external merge sort, exec/outofcore.py). The
                # probe streams — the input is never fully materialized
                # past the budget.
                from spark_rapids_tpu.exec import outofcore as ooc
                if ooc.enabled_for(ctx):
                    prefix, rest, engaged = ooc.split_stream_on_budget(
                        ctx, iter(part()))
                    if engaged:
                        import itertools
                        yield from ooc.external_sort(
                            ctx, self, itertools.chain(prefix, rest),
                            schema, growth)
                        return
                    batches = prefix
                else:
                    batches = list(part())
                merged = _concat_device(batches, schema, growth)
                yield self._kernel(merged)
            return run
        return [make(p) for p in child_parts]


def _colocated(scalar, batch: DeviceBatch):
    """``scalar`` on ``batch``'s device. Mesh execution commits each
    partition's batches to its shard device, so a device scalar carried
    from one partition's kernel into the next partition's (the limit's
    running count) must follow the batch or jit rejects the device mix."""
    if isinstance(scalar, jax.Array) and batch.columns:
        dev = batch.columns[0].validity.device
        if scalar.device != dev:
            return jax.device_put(scalar, dev)
    return scalar


class TpuLocalLimitExec(TpuExec):
    """reference: GpuLocalLimitExec / GpuGlobalLimitExec (limit.scala).

    ``remaining`` stays a device scalar threaded through one fused
    slice-and-decrement kernel per batch — the per-batch row-count readback
    the round-1 version paid (a full device->host round trip each) is gone.
    Later batches past the limit yield empty slices instead of breaking
    the loop: the extra enqueues are cheaper than one sync."""

    padded_output = True

    def __init__(self, child: PhysicalPlan, limit: int):
        super().__init__([child])
        self.limit = limit

        def step(b, remaining):
            out = rowops.slice_batch(b, jnp.asarray(0, jnp.int32), remaining)
            return out, remaining - out.num_rows
        self._kernel = cached_jit("limitstep", lambda: jax.jit(step))

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                import numpy as np
                remaining = np.asarray(self.limit, np.int32)
                # early-exit check every 8 batches: one round trip per 8
                # upstream batches at most, instead of either one per batch
                # (round 1) or none at all (which would drain an unbounded
                # upstream under LIMIT k)
                for i, batch in enumerate(part()):
                    if (i + 1) % 8 == 0 and int(remaining) <= 0:
                        break
                    out, remaining = self._kernel(
                        batch, _colocated(remaining, batch))
                    yield out
            return run
        return [make(p) for p in child_parts]


class TpuGlobalLimitExec(TpuLocalLimitExec):
    pass


class TpuCollectLimitExec(TpuLocalLimitExec):
    """Root-position limit (reference: GpuCollectLimitExec,
    GpuOverrides.scala:1641-1643): one output partition draining children
    in order with the device-scalar remaining count."""

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)

        def run() -> Iterator[DeviceBatch]:
            import numpy as np
            remaining = np.asarray(self.limit, np.int32)
            i = 0
            for part in child_parts:
                for batch in part():
                    if (i + 1) % 8 == 0 and int(remaining) <= 0:
                        return
                    i += 1
                    out, remaining = self._kernel(
                        batch, _colocated(remaining, batch))
                    yield out
        return [run]


class TpuCoalescePartitionsExec(TpuExec):
    """Narrow partition merge (Spark CoalesceExec; reference rule
    GpuOverrides.scala:1611-1615): group child partitions contiguously,
    no device work at all."""

    def __init__(self, child: PhysicalPlan, n: int):
        super().__init__([child])
        self.n = max(1, int(n))

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def describe(self) -> str:
        return f"TpuCoalescePartitionsExec({self.n})"

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        from spark_rapids_tpu.exec.base import group_contiguous
        child_parts = self.children[0].executed_partitions(ctx)
        groups = group_contiguous(child_parts, self.n)
        schema = self.output_schema()

        def make(group: List[Partition]) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                got = False
                for p in group:
                    for b in p():
                        got = True
                        yield b
                if not got:
                    yield DeviceBatch.empty(schema)
            return run
        return [make(g) for g in groups]


class TpuUnionExec(TpuExec):
    """reference: GpuUnionExec."""

    def __init__(self, children: Sequence[PhysicalPlan]):
        super().__init__(children)

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        out: List[Partition] = []
        for c in self.children:
            out.extend(c.executed_partitions(ctx))
        return out


class TpuRangeExec(TpuExec):
    """reference: GpuRangeExec — generates the sequence directly on device."""

    def __init__(self, start: int, end: int, step: int, num_partitions: int,
                 name: str = "id"):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.num_partitions = num_partitions
        self.col_name = name

    def output_schema(self) -> Schema:
        from spark_rapids_tpu.columnar import dtypes
        return Schema([self.col_name], [dtypes.INT64])

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self.num_partitions) if total else 0
        growth = ctx.conf.capacity_growth
        schema = self.output_schema()

        @functools.partial(jax.jit, static_argnums=(2,))
        def srt_range(lo, n, capacity):  # the program's name on the device
            from spark_rapids_tpu.columnar.column import DeviceColumn
            from spark_rapids_tpu.columnar import dtypes
            idx = jnp.arange(capacity, dtype=jnp.int64)
            data = self.start + (lo + idx) * self.step
            validity = idx < n
            col = DeviceColumn(dtypes.INT64, data, validity)
            return DeviceBatch(schema, [col], n.astype(jnp.int32))

        def make(i: int) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                lo = i * per
                hi = min(total, (i + 1) * per)
                n = max(hi - lo, 0)
                cap = bucket_capacity(max(per, 1), growth)
                yield srt_range(jnp.asarray(lo, jnp.int64),
                                jnp.asarray(n, jnp.int32), cap)
            return run
        return [make(i) for i in range(self.num_partitions)]


class TpuExpandExec(TpuExec):
    """reference: GpuExpandExec (GpuExpandExec.scala:202) — one jitted
    projection kernel per set, each input batch replayed through all of
    them."""

    def __init__(self, child: PhysicalPlan, projections):
        super().__init__([child])
        self.projections = [list(p) for p in projections]
        self._kernels = []
        for pi, proj in enumerate(self.projections):
            names = [n for n, _ in proj]
            bound = [e for _, e in proj]
            sig = f"expand{pi}|" + "|".join(
                f"{n}={expr_signature(e)}" for n, e in proj)
            self._kernels.append(cached_jit(sig, lambda bound=bound,
                                            names=names: jax.jit(
                lambda batch: eval_projection(batch, bound, names))))

    def output_schema(self) -> Schema:
        cs = self.children[0].output_schema()
        first = self.projections[0]
        return Schema([n for n, _ in first],
                      [e.dtype(cs) for _, e in first])

    def describe(self) -> str:
        return f"TpuExpandExec({len(self.projections)} sets)"

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                for batch in part():
                    for kern in self._kernels:
                        yield kern(batch)
            return run
        return [make(p) for p in child_parts]


class TpuScanExec(TpuExec):
    """Columnar scan: host-side decode (pyarrow/pandas — the reference also
    parses footers and rebuilds file buffers on the CPU,
    GpuParquetScan.scala:316-373) + device upload per batch."""

    def __init__(self, source, schema: Schema, pushed_filters=None):
        super().__init__()
        self.source = source
        self._schema = schema
        self.pushed_filters = pushed_filters

    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"TpuScanExec({self.source.describe()})"

    def fingerprint_extra(self) -> str:
        # pushed filters are (name, op, value) tuples (sql/pushdown.py
        # extract_pushable_filters), with repr-stable literal values
        pushed = ",".join(repr(f) for f in (self.pushed_filters or ()))
        return (f"{self.source.data_uid()}|{pushed}"
                f"|{','.join(self._schema.names)}")

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        from spark_rapids_tpu.exec.transitions import scan_raw_parts
        cpu_parts = scan_raw_parts(ctx, self.source, self.pushed_filters)
        declared = frozenset()
        if cpu_parts is None:
            # the scan's share of plan.partitions, apart from the
            # recursion over the other operators: laying out the splits
            # (pruning by footer statistics, starting the prefetcher) and
            # declaring the footers' integer bounds
            with TRACER.span("scan.plan.splits") as sp:
                if self.pushed_filters and hasattr(self.source,
                                                   "prune_splits"):
                    cpu_parts = self.source.cpu_partitions(
                        ctx, self.pushed_filters)
                else:
                    cpu_parts = self.source.cpu_partitions(ctx)
                if sp is not None:
                    total = len(getattr(self.source, "splits", cpu_parts))
                    sp.set(files=len(getattr(self.source, "paths", ())),
                           splits=len(cpu_parts),
                           pruned=max(0, total - len(cpu_parts)))
            with TRACER.span("scan.plan.stats") as sp:
                declared = self._declare_stats(ctx)
                if sp is not None:
                    sp.set(columns=len(declared))
        max_rows = ctx.conf.batch_size_rows
        schema = self._schema

        # device-resident scan cache (spark.rapids.sql.cacheDeviceScans):
        # skip the re-upload when the same source is scanned again — the
        # HBM analogue of a cached DataFrame
        from spark_rapids_tpu.exec.transitions import scan_cache_for
        cache = scan_cache_for(ctx, self.source, schema, max_rows,
                               self.pushed_filters)
        # one dictionary registry per scan: every batch of this scan
        # encodes against the first batch's dictionaries, so the
        # aggregation fast path compiles ONE program per scan (a racing
        # concurrent partition at worst costs one extra retrace).
        # Small in-memory tables PRE-SEED the registry from the whole
        # column: a dimension table split across partitions would
        # otherwise disable encoding the moment partition 2 shows a
        # value outside partition 1's dictionary — exactly the natural-
        # key columns (all-distinct) whose codes joins fan out to fact
        # scale.
        dict_state: dict = {}
        src_df = getattr(self.source, "df", None)
        if src_df is not None and 0 < len(src_df) <= DICT_SMALL_TABLE_ROWS:
            for ci, dt in enumerate(schema.dtypes):
                if not dt.is_string:
                    continue
                vals = src_df.iloc[:, ci].dropna().unique()
                if (0 < len(vals) <= DICT_MAX_CARD_SMALL
                        and all(isinstance(v, str) for v in vals)):
                    dict_state[ci] = tuple(sorted(vals))

        # mesh execution: partition i uploads to mesh device i so scan data
        # is born distributed (reference map tasks produce data already
        # spread over executors) — the downstream exchange's device_put is
        # then a no-op placement
        mesh = getattr(ctx.session, "mesh", None) if ctx.session else None
        mesh_devs = list(mesh.devices.flat) if mesh is not None else None

        from spark_rapids_tpu.exec.transitions import (
            scan_dict_numerics, upload_partition,
        )
        dict_numerics = scan_dict_numerics(ctx, self.source)

        def make(i: int, part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                return upload_partition(ctx, part, schema, max_rows,
                                        dict_state, cache, i,
                                        mesh_devs=mesh_devs,
                                        dict_numerics=dict_numerics,
                                        declared_stats=declared)
            return run
        return [make(i, p) for i, p in enumerate(cpu_parts)]

    def _declare_stats(self, ctx: ExecContext) -> frozenset:
        """Union the integer bounds the source reads from its footers
        (ParquetSource.declared_int_bounds) into session.column_stats
        while the plan is laid out, so every aggregate and join above
        plans with the bounds of exactly the splits this scan will read.
        Returns the declared names: the upload does not measure them
        again (transitions.note_scan_stats). Other sources declare
        nothing and keep the batch-by-batch measurement."""
        declare = getattr(self.source, "declared_int_bounds", None)
        if declare is None or ctx.session is None:
            return frozenset()
        from spark_rapids_tpu.exec.statsutil import note_bounds
        bounds = declare(self.pushed_filters)
        for name, b in bounds.items():
            if b is not None:
                note_bounds(ctx.session, name, *b)
        ctx.declared_stats.update(bounds)
        _DECLARED_COLUMNS.add(len(bounds))
        return frozenset(bounds)


class TpuShuffleExchangeExec(TpuExec):
    """reference: GpuShuffleExchangeExec + GpuPartitioning
    (GpuShuffleExchangeExec.scala:60-215, GpuPartitioning.scala:41-75).

    Device-side partitioning: hash rows, sort by partition id (one fused
    kernel — the contiguous-split analogue), then slice per output
    partition. In-process exchange; the distributed path rides the mesh
    transport (shuffle/)."""

    def __init__(self, child: PhysicalPlan, partitioning):
        super().__init__([child])
        self.partitioning = partitioning

        kind = partitioning[0]
        if kind == "roundrobin":
            n = partitioning[-1]

            def rr_kernel(batch: DeviceBatch):
                # row-level round robin like Spark's repartition(n) —
                # every output partition receives an even share of each
                # batch's rows
                pid = (jnp.arange(batch.capacity, dtype=jnp.int32)
                       % jnp.int32(n))
                return _split_by_pid(batch, pid, n)
            self._pkernel = cached_jit(
                f"exchrr|{n}", lambda: jax.jit(rr_kernel))
        elif kind == "hash":
            key_idx = tuple(partitioning[1])
            n = partitioning[2]

            def pkernel(batch: DeviceBatch):
                h1, h2 = row_hashes(batch, key_idx)
                pid = (h1 % jnp.uint64(n)).astype(jnp.int32)
                return _split_by_pid(batch, pid, n)
            self._pkernel = cached_jit(
                f"exchhash|{key_idx}|{n}", lambda: jax.jit(pkernel))
        elif kind == "range":
            key_idx = tuple(partitioning[1])
            asc = tuple(partitioning[2])
            nf = tuple(partitioning[3])
            n = partitioning[4]
            sig = f"exchrange|{key_idx}|{asc}|{nf}|{n}"

            def sample_kernel(batch: DeviceBatch):
                ops = sortops.sort_key_operands(batch, key_idx, asc, nf)
                return jnp.stack([o.astype(jnp.uint64) for o in ops])
            self._sample_kernel = cached_jit(
                sig + "|sample", lambda: jax.jit(sample_kernel))

            def range_pkernel(batch: DeviceBatch, bounds):
                pid = sortops.range_partition_ids(batch, key_idx, asc, nf,
                                                  list(bounds))
                return _split_by_pid(batch, pid, n)
            self._pkernel = cached_jit(
                sig + "|part", lambda: jax.jit(range_pkernel))

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    @staticmethod
    def _padded_producer(node: PhysicalPlan) -> bool:
        """Does the subtree below (up to the next exchange) contain an
        operator whose batches systematically carry far more capacity than
        rows (``padded_output``)? Aggregates always do; limits and
        semi/anti joins compact hard within unchanged capacity. Plain
        filters are deliberately NOT counted: at moderate selectivity the
        shrink's count-fetch sync + gathers measured slower than just
        concatenating (a very selective filter below a join is the
        accepted trade-off)."""
        if node.padded_output:
            return True
        if isinstance(node, TpuShuffleExchangeExec):
            return False  # already shrunk at that boundary
        return any(TpuShuffleExchangeExec._padded_producer(c)
                   for c in node.children)

    def describe(self) -> str:
        return f"TpuShuffleExchangeExec({self.partitioning[0]})"

    def fingerprint_extra(self) -> str:
        return repr(self.partitioning)

    def materialize_stage(self, ctx: ExecContext):
        """AQE query-stage materialization (sql/adaptive/): run the map
        side on device, bring the batches to the host in one fused fetch
        (DeviceBatch.to_pandas_many — two round trips for the whole
        stage), split each map partition with the canonical host hash
        and report per-(map, partition) sizes. AQE is a statistics
        barrier by design: the map output must become host-addressable
        for the runtime to measure and re-partition it — the role the
        reference's shuffle catalog registration plays
        (RapidsCachingWriter -> MapStatus.partition_sizes)."""
        from spark_rapids_tpu.exec.cpu import concat_host_frames
        from spark_rapids_tpu.sql.adaptive import stats as aqestats
        assert self.partitioning[0] == "hash", self.partitioning
        key_idx = list(self.partitioning[1])
        n = self.partitioning[-1]
        schema = self.output_schema()
        sess = ctx.session
        per_map: List[List[DeviceBatch]] = []
        for part in self.children[0].executed_partitions(ctx):
            try:
                per_map.append(list(part()))
            finally:
                if sess is not None and sess.semaphore is not None:
                    sess.semaphore.release()
        flat = [b for bs in per_map for b in bs]
        # stage-barrier fetch under this exchange's operator scope: the
        # fused-fetch slice/pack kernels it compiles attribute HERE, and
        # the device->host seconds land in this node's transfer component
        import time as _time

        from spark_rapids_tpu.obs import compileledger
        from spark_rapids_tpu.obs.syncledger import sync_scope
        with compileledger.op_context(self.describe(), id(self), ctx):
            t0 = _time.perf_counter()
            with sync_scope("aqe.stageFetch",
                            detail=f"batches={len(flat)}"):
                frames = DeviceBatch.to_pandas_many(
                    flat, fused_fetch_bytes=int(ctx.conf.get(
                        "spark.rapids.sql.collect.fusedFetchBytes",
                        4 << 20)))
            compileledger.note_transfer(_time.perf_counter() - t0, "d2h")
        map_outputs = []
        pos = 0
        for bs in per_map:
            dfs = frames[pos:pos + len(bs)]
            pos += len(bs)
            df = concat_host_frames(dfs, schema)
            map_outputs.append(aqestats.split_frame(df, key_idx, n))
        return map_outputs, aqestats.stats_from_map_outputs(map_outputs)

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        schema = self.output_schema()
        growth = ctx.conf.capacity_growth
        kind = self.partitioning[0]

        # per-edge transport selection (shuffle/manager.py
        # ShuffleTransportKind): ICI = in-slice mesh collective, MANAGER =
        # catalog + transport wire (inprocess/socket — the cross-host /
        # DCN path), LOCAL = single-process collapse or bucket
        # materialization. The default mode ('legacy') reproduces the
        # historical inline selection byte-identically.
        from spark_rapids_tpu.shuffle.manager import (
            ShuffleTransportKind, select_transport_kind,
        )
        mesh = getattr(ctx.session, "mesh", None) if ctx.session else None
        n_req = self.partitioning[-1] if kind != "single" else 1
        tkind = select_transport_kind(ctx.conf, ctx.session, kind, n_req)
        manager_on = tkind is ShuffleTransportKind.MANAGER
        # roundrobin is exempt from collapse: it IS the user-visible
        # repartition(n) shape (output partition/file count of a
        # following write)
        collapse = (tkind is ShuffleTransportKind.LOCAL
                    and kind in ("hash", "range")
                    and ctx.conf.get_bool(
                        "spark.rapids.sql.shuffle.localCollapse", True))

        if tkind is ShuffleTransportKind.ICI:
            # distributed exchange: one fused shard_map program whose core
            # is an ICI all_to_all (shuffle/ici.py over
            # parallel/distributed.py), replacing the reference's UCX
            # transfers (RapidsShuffleInternalManager.scala) for EVERY
            # exchange kind (GpuShuffleExchangeExec.scala:60-215): hash
            # (joins/aggregates), range (distributed global sort:
            # per-shard sample -> host bounds -> all_to_all), roundrobin.
            # Each upstream partition stays resident on its own mesh
            # device end-to-end — no single-device funnel — and the
            # backend folds device-side send counts into
            # MapOutputStatistics + skew/journal/ledger surfaces.
            from spark_rapids_tpu.shuffle.ici import IciMeshExchange
            backend = IciMeshExchange(self, mesh, schema, growth)
            return backend.partitions(ctx, child_parts)

        if kind == "single" or collapse:
            from spark_rapids_tpu.exec import outofcore as ooc
            if ooc.enabled_for(ctx):
                # out-of-core mode: the collapse concat IS the whole-
                # dataset funnel array larger-than-HBM execution must
                # avoid — stream the pieces through individually and let
                # the downstream grace operators partition-and-spill them
                def stream_pieces() -> Iterator[DeviceBatch]:
                    got = False
                    for p in child_parts:
                        for b in p():
                            got = True
                            yield b
                    if not got:
                        yield DeviceBatch.empty(schema)
                return [stream_pieces]
            # sync-free collapse: when no aggregate feeds this exchange,
            # the producer batches are NOT systematically over-padded, so
            # the count-fetch sync + per-batch shrink gathers cost more
            # than they save — capacity-based concats (zero round trips)
            # hand the consumer one big batch, keeping joins and
            # aggregates on one wide kernel instead of per-fragment
            # dispatches. Where one batch would pass the bound
            # (_collapse_bound_bytes), the drain is cut into consecutive
            # groups and each is concatenated as the drain reaches its
            # end: the consumer gets pieces within the bound, and the
            # drain never holds more than one group's inputs beside them.
            # A join's stream takes the pieces in rounds (exec/tpujoin.py
            # _rounds); a consumer that needs one batch (a build, an
            # aggregate's merge, a sort) concatenates them again.
            # Aggregate producers keep the shrink (their outputs carry
            # pre-agg padding worth removing before the merge/sort).
            if not self._padded_producer(self.children[0]):
                # a deterministic Filter directly below is claimed and run
                # a batch at a time under the drain (_fused_filter_source)
                src_node, claimed = _fused_filter_source(
                    self.children[0], ctx)
                fused_parts = (src_node.executed_partitions(ctx)
                               if claimed is not None else child_parts)

                def nosync_concat() -> Iterator[DeviceBatch]:
                    drain = _Drain(fused_parts, claimed)
                    bound, row = _collapse_bound_bytes(), _row_bytes(schema)

                    def full(batches, batch):
                        cap = batch.capacity + sum(b.capacity
                                                   for b in batches)
                        return bucket_capacity(cap, growth) * row > bound
                    pieces = 0
                    while not drain.exhausted:
                        batches, masks, compacted = drain.take(full)
                        if not batches:  # the children had no batch
                            yield DeviceBatch.empty(schema)
                            return
                        pieces += 1
                        piece = _collapse_concat(batches, schema, growth,
                                                 masks, compacted, pieces)
                        # the inputs go before the consumer takes the
                        # piece, and the piece before the next group
                        del batches, masks
                        yield piece
                        del piece
                return [nosync_concat]

            def single() -> Iterator[DeviceBatch]:
                import jax as _jax
                batches = _drain_claimed(child_parts, None)[0]
                if not batches:
                    yield DeviceBatch.empty(schema)
                    return
                if getattr(ctx, "small_query", False):
                    # tiny-query fast path: the shrink exists to drop
                    # pre-aggregation padding before heavy downstream
                    # kernels — at single-resident-batch scale the
                    # count-fetch round trip costs more than the padding
                    # it would remove
                    yield _collapse_concat(batches, schema, growth)
                    return
                # capacity shrink: post-aggregate partials carry their
                # pre-aggregate input capacity as padding; ONE batched
                # row-count fetch lets each piece drop to its true bucket
                # so every downstream kernel compiles and runs at the
                # real scale instead of the padded one. Speculation
                # (spark.rapids.sql.adaptiveCapacity.enabled): later
                # executions reuse the remembered counts as host
                # metadata and defer an EXACT-equality check to query
                # end (session._verify_speculation) — the slice kernel
                # clamps liveness by the device-side row count, so a
                # covered speculation emits identical data
                cache = entry = None
                if getattr(ctx, "speculate", False):
                    from spark_rapids_tpu.exec.base import (
                        plan_fingerprint,
                    )
                    from spark_rapids_tpu.exec.reuse import (
                        subtree_deterministic,
                    )
                    if subtree_deterministic(self):
                        skey = plan_fingerprint(self) + "|shrink"
                        cache = ctx.session.capacity_cache
                        entry = cache.get(skey)
                # under speculation the cache entry must key on an
                # execution-invariant batch set: which batches already
                # carry _host_rows differs between run 1 (one-time
                # agg-ratio learning syncs set some) and run 2, so
                # filtering to the unknown ones made entry['n'] mismatch
                # and wasted the first speculation window (ADVICE r4 #5).
                # Counts only speculate once they have proven STABLE
                # across two consecutive runs: adaptive strategy shifts
                # (dense grouping / partial-skip engage from a plan's
                # second execution) legitimately change the counts between
                # run 1 and run 2 under an identical structural
                # fingerprint, and speculating unstable counts forces a
                # full re-execution at verify time.
                need = (list(batches) if cache is not None
                        else [b for b in batches if b._host_rows is None])
                # per-batch stats: row count + each plain (non-dict)
                # string column's live char total — shrinking the char
                # slab alongside the rows stops every downstream string
                # kernel from paying the pre-aggregation char padding.
                # Layout is computed PER BATCH (a scan can close a
                # dictionary mid-stream, so batches of one exchange may
                # disagree on which string columns are plain); the
                # speculation entry keys on the layout so a mismatch can
                # never mis-assign a char total as a row count.
                def batch_stats(b):
                    vals = [b.num_rows]
                    for col in b.columns:
                        if (col.dtype.is_string
                                and col.dict_values is None
                                and not col.has_slab):
                            # slab columns carry a STATIC stride — no
                            # char total to fetch (and reading offsets
                            # here would materialize their packed chars)
                            vals.append(col.offsets[jnp.minimum(
                                b.num_rows.astype(jnp.int32),
                                jnp.int32(col.offsets.shape[0] - 1))])
                    return vals

                if need:
                    per_batch = [batch_stats(b) for b in need]
                    layout = tuple(len(v) for v in per_batch)
                    counts_d = [v for vals in per_batch for v in vals]
                    if (entry is not None
                            and entry.get("layout") == layout
                            and entry.get("stable")):
                        from spark_rapids_tpu.exec.tpujoin import (
                            _start_host_copies,
                        )
                        _start_host_copies(counts_d)
                        ctx.session.capacity_spec_hits += 1
                        ctx.spec_pending.append(
                            (skey, counts_d, [], [], entry["counts"]))
                        stats = entry["counts"]
                    else:
                        from spark_rapids_tpu.obs.syncledger import (
                            sync_scope,
                        )
                        with sync_scope("exchange.shrink",
                                        detail=f"counts={len(counts_d)}"):
                            stats = [int(c)
                                     for c in _jax.device_get(counts_d)]
                        if cache is not None:
                            if (entry is not None
                                    and entry.get("layout") == layout
                                    and entry["counts"] == stats):
                                entry["stable"] = True
                            else:
                                cache[skey] = {"layout": layout,
                                               "counts": stats}
                    pos = 0
                    for b, vals in zip(need, per_batch):
                        b._host_rows = int(stats[pos])
                        b._host_chars = [int(c) for c in
                                         stats[pos + 1:pos + len(vals)]]
                        pos += len(vals)
                shrunk = []
                for b in batches:
                    target = bucket_capacity(max(b._host_rows, 1), growth)
                    # full char_caps tuple: one entry per string column
                    # (0 = keep; dict-backed strings move codes only)
                    ccaps = []
                    hc = list(getattr(b, "_host_chars", []) or [])
                    for col in b.columns:
                        if not col.dtype.is_string:
                            continue
                        if (col.dict_values is None and not col.has_slab
                                and hc):
                            ccaps.append(_char_bucket(max(hc.pop(0), 1)))
                        else:
                            ccaps.append(0)
                    char_shrink = any(
                        cc and col.dtype.is_string
                        and col.dict_values is None and not col.is_lazy
                        and cc < col.data.shape[0]
                        for cc, col in zip(
                            ccaps, [c for c in b.columns
                                    if c.dtype.is_string]))
                    if target < b.capacity or char_shrink:
                        ccaps_t = tuple(ccaps)
                        kern = cached_jit(
                            f"shrink|{target}|{ccaps_t}",
                            lambda t=target, cc=ccaps_t: jax.jit(
                                lambda bb, c: rowops.slice_batch_to(
                                    bb, jnp.asarray(0, jnp.int32), c, t,
                                    cc)))
                        sb = kern(b, jnp.asarray(b._host_rows, jnp.int32))
                        sb._host_rows = b._host_rows
                        shrunk.append(sb)
                    else:
                        shrunk.append(b)
                yield _collapse_concat(shrunk, schema, growth)
            return [single]

        assert kind in ("hash", "range", "roundrobin")
        n = self.partitioning[-1]

        def slice_kernel(b: DeviceBatch, start, count, rows: int):
            # shrink to the bucket of the KNOWN row count: post-aggregate
            # pieces stop inheriting the pre-aggregate capacity, so the
            # downstream merge/sort kernels run at the output's true scale
            out_cap = bucket_capacity(max(rows, 1), growth)
            kern = cached_jit(f"slice|{out_cap}", lambda: jax.jit(
                lambda bb, s, c: rowops.slice_batch_to(bb, s, c, out_cap)))
            return kern(b, start, count)

        # materialization barrier: partition every child batch once,
        # bucket the slices
        state = {"buckets": None}

        def compute_range_bounds(batches: List[DeviceBatch]):
            """Reservoir-style sample of sort-key operand vectors -> n-1
            lexicographic upper bounds (GpuRangePartitioner.scala:42-120)."""
            import jax
            import numpy as np
            # one batched fetch of every batch's (row count, key operands)
            from spark_rapids_tpu.obs.syncledger import sync_scope
            with sync_scope("exchange.rangeBounds",
                            detail=f"batches={len(batches)}"):
                fetched = jax.device_get([(b.num_rows,
                                           self._sample_kernel(b))
                                          for b in batches])
            from spark_rapids_tpu.parallel.distributed import (
                pick_bounds_from_samples,
            )
            samples = []
            k = None
            for batch, (rows, ops) in zip(batches, fetched):
                rows = int(rows)
                batch._host_rows = rows
                ops = np.asarray(ops)  # (k, capacity)
                k = ops.shape[0]
                if rows == 0:
                    continue
                take = min(rows, 128)
                sel = np.linspace(0, rows - 1, take).astype(np.int64)
                samples.append(ops[:, sel])
            if k is None:
                # no batches at all: operand count from an empty probe
                k = np.asarray(self._sample_kernel(
                    DeviceBatch.empty(schema))).shape[0]
            bounds = pick_bounds_from_samples(samples, k, n)
            return tuple(jnp.asarray(b) for b in bounds)

        def split_to_slices(batches, bounds):
            """Split each batch by partition id and yield
            (batch_index, pid, piece) — the shared core of both exchange
            materializations. Bucket counts are fetched in windows: one
            device->host round trip per WINDOW batches (per-batch scalar
            syncs each pay a full round trip; one giant window would pin
            every split output in device memory at once)."""
            import itertools
            import jax
            import numpy as np
            split_iter = ((bi, (self._pkernel(b, bounds) if kind == "range"
                                else self._pkernel(b)))
                          for bi, b in enumerate(batches))
            WINDOW = 16
            windowed = iter(lambda: list(itertools.islice(split_iter,
                                                          WINDOW)), [])
            from spark_rapids_tpu.obs.syncledger import sync_scope
            for window in windowed:
                with sync_scope("exchange.split",
                                detail=f"window={len(window)}"):
                    window_counts = jax.device_get(
                        [c for _, (_s, c) in window])
                for (bi, (sorted_batch, _c)), host_counts in zip(
                        window, window_counts):
                    host_counts = np.asarray(host_counts)
                    offsets = np.concatenate([[0], np.cumsum(host_counts)])
                    for pid in range(n):
                        if host_counts[pid] == 0:
                            continue
                        yield bi, pid, slice_kernel(
                            sorted_batch,
                            jnp.asarray(offsets[pid], jnp.int32),
                            jnp.asarray(host_counts[pid], jnp.int32),
                            int(host_counts[pid]))

        # map-side output registers in the spillable BufferCatalog at the
        # shuffle-output band (spills FIRST under pressure,
        # SpillPriorities.scala:26-50 / RapidsShuffleInternalManager.scala:
        # 92-141 route all shuffle data through the catalog); the reduce
        # side acquires (faulting spilled pieces back) and frees on
        # consumption
        use_catalog = ctx.session is not None

        def materialize():
            if state["buckets"] is not None:
                return state["buckets"]
            from spark_rapids_tpu.memory.spill import SpillPriorities
            buckets: List[List] = [[] for _ in range(n)]
            all_batches = [b for p in child_parts for b in p()]
            bounds = (compute_range_bounds(all_batches)
                      if kind == "range" else None)
            for _bi, pid, piece in split_to_slices(all_batches, bounds):
                if use_catalog:
                    buckets[pid].append(ctx.session.add_transient_batch(
                        piece, SpillPriorities.OUTPUT_FOR_READ))
                else:
                    buckets[pid].append(piece)
            state["buckets"] = buckets
            return buckets

        if manager_on:
            # accelerated shuffle manager path: map-side slices register
            # as spillable shuffle blocks via CachingShuffleWriter; the
            # reduce side reads them back through CachingShuffleReader
            # over the (in-process) transport — the engine-integrated
            # RapidsShuffleInternalManager.scala:74-362 flow
            from spark_rapids_tpu.shuffle.manager import (
                CachingShuffleReader, CachingShuffleWriter,
            )
            mstate = {"statuses": None}

            def materialize_manager():
                if mstate["statuses"] is not None:
                    return mstate["statuses"]
                # map tasks stripe across the executor pool
                # (spark.rapids.shuffle.executors); with >1, reduce-side
                # fetches of other executors' blocks traverse the real
                # transport wire (socket: serializer -> server -> client)
                envs = ctx.session.shuffle_envs
                shuffle_id = ctx.session.next_shuffle_id()
                per_map_batches = [list(p()) for p in child_parts]
                bounds = (compute_range_bounds(
                    [b for bs in per_map_batches for b in bs])
                    if kind == "range" else None)
                statuses = []
                for mi, batches in enumerate(per_map_batches):
                    per_pid: List[List[DeviceBatch]] = [[] for _ in range(n)]
                    for _bi, pid, piece in split_to_slices(batches, bounds):
                        per_pid[pid].append(piece)
                    writer = CachingShuffleWriter(envs[mi % len(envs)],
                                                  shuffle_id, mi)
                    statuses.append(writer.write(per_pid))
                if statuses and ctx.metrics_enabled:
                    # per-shuffle skew from the EXACT device byte sizes
                    # the writer recorded (MapStatus.partition_sizes) —
                    # the satellite observability AQE's stage stats also
                    # report on the host path (obs/shuffleobs.py)
                    from spark_rapids_tpu.obs.shuffleobs import (
                        record_shuffle_skew,
                    )
                    from spark_rapids_tpu.shuffle.manager import (
                        aggregate_map_statistics,
                    )
                    record_shuffle_skew(
                        aggregate_map_statistics(statuses)
                        .bytes_by_partition,
                        source=f"tpu:manager-{shuffle_id}")
                mstate["statuses"] = (shuffle_id, statuses)
                return mstate["statuses"]

            def make_manager(pid: int) -> Partition:
                def run() -> Iterator[DeviceBatch]:
                    from spark_rapids_tpu.shuffle.client import (
                        ShuffleFetchFailedError,
                    )
                    shuffle_id, statuses = materialize_manager()
                    # reduce with bounded PER-PEER retry — the in-process
                    # analogue of mapping transport errors into Spark's
                    # stage-retry path (RapidsShuffleClient.scala:409-418
                    # -> RapidsShuffleFetchFailedException). Each peer
                    # group moves in ONE metadata/transfer round trip
                    # (RapidsCachingReader groups per BlockManagerId) and
                    # a failure re-fetches only that peer's blocks (they
                    # live in the spillable map-side catalog), never data
                    # already fetched. The pieces still concatenate into
                    # ONE wide batch before yielding — deliberate:
                    # downstream joins/aggregates run one wide kernel
                    # instead of per-fragment dispatches (same trade as
                    # the collapse path).
                    max_retries = ctx.conf.get_int(
                        "spark.rapids.shuffle.maxFetchRetries", 3)
                    reader = CachingShuffleReader(ctx.session.shuffle_env)
                    batches = []
                    for peer, group in reader.peer_groups(statuses):
                        attempt = 0
                        while True:
                            try:
                                got = reader.read_group(
                                    shuffle_id, pid, peer, group)
                                break
                            except ShuffleFetchFailedError as e:
                                attempt += 1
                                if attempt > max_retries:
                                    raise
                                from spark_rapids_tpu.obs.metrics import (
                                    REGISTRY,
                                )
                                from spark_rapids_tpu.obs.trace import (
                                    TRACER,
                                )
                                REGISTRY.counter(
                                    "shuffle.fetch.retries").add(1)
                                TRACER.instant(
                                    "shuffle.fetch.retry",
                                    peer=str(peer), attempt=attempt)
                                from spark_rapids_tpu.obs.events import (
                                    EVENTS,
                                )
                                EVENTS.emit("fetchRetry", peer=str(peer),
                                            attempt=attempt,
                                            error=str(e)[:200])
                                from spark_rapids_tpu.obs.progress import (
                                    PROGRESS,
                                )
                                if PROGRESS.enabled:
                                    PROGRESS.shuffle_retry()
                                import logging
                                logging.getLogger(__name__).warning(
                                    "shuffle fetch failed (%s); retrying "
                                    "%d/%d", e, attempt, max_retries)
                        batches.extend(got)
                    if not batches:
                        yield DeviceBatch.empty(schema)
                        return
                    yield _concat_device(batches, schema, growth)
                return run
            return [make_manager(i) for i in range(n)]

        def make(pid: int) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                buckets = materialize()
                if buckets[pid] is None:
                    raise RuntimeError(
                        f"shuffle partition {pid} already consumed "
                        "(freed on use)")
                if not buckets[pid]:
                    yield DeviceBatch.empty(schema)
                    return
                if use_catalog:
                    catalog = ctx.session.buffer_catalog
                    pieces = []
                    for bid in buckets[pid]:
                        pieces.append(catalog.acquire_batch(bid))
                        ctx.session.consume_transient(bid)  # free on use
                    buckets[pid] = None
                else:
                    pieces = buckets[pid]
                yield _concat_device(pieces, schema, growth)
            return run
        return [make(i) for i in range(n)]
