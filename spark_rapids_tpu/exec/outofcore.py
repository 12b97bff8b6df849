"""Out-of-core (larger-than-HBM) operator execution.

The second half of the pod-scale data plane (ROADMAP item 4, PAPER.md
L2): when an operator's measured device working set exceeds the
working-set budget (``spark.rapids.tpu.outOfCore.partitionBytes``), its
input is partitioned into fan-out buckets of spillable slices registered
on the 3-tier store (memory/spill.py) — the device store is
synchronously spilled down to the budget as buckets accumulate — and the
operator processes ONE bucket at a time, faulting its pieces back:

  * **grace hash join** — both sides hash-partitioned on the join keys
    (equal keys co-locate, so per-bucket joins union to the exact
    result); a bucket whose build fragment still exceeds the budget is
    recursed with a different hash, up to
    ``spark.rapids.tpu.outOfCore.maxRecursion`` levels (the reference's
    sub-partitioner, GpuShuffledHashJoinExec's spillable build batches);
  * **external merge sort** — sampled range bounds (the
    GpuRangePartitioner sample), range-partitioned spill buckets, one
    in-HBM sort per bucket, buckets emitted in range order = a globally
    sorted stream;
  * **spillable aggregation** — partial-layout batches hash-partitioned
    on the grouping keys; per-bucket merges (disjoint key sets) union to
    the exact aggregate.

Fan-out is chosen from the same MEASURED batch sizes AQE's statistics
collect (``DeviceBatch.device_memory_size`` — host metadata, no device
sync). Everything here is opt-in (``outOfCore.enabled``, default false)
and value-identical: partitioning only changes the order work is done
in, never what is computed.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, Schema, bucket_capacity,
)
from spark_rapids_tpu.columnar.column import _char_bucket
from spark_rapids_tpu.memory.spill import SpillPriorities
from spark_rapids_tpu.obs.syncledger import sync_scope
from spark_rapids_tpu.ops import rowops, sortops
from spark_rapids_tpu.ops.groupby import row_hashes
from spark_rapids_tpu.utils.kernelcache import bucket_dim, cached_jit

_MAX_FANOUT = 64


# ---------------------------------------------------------------------------
# policy: enablement, budgets, fan-out
# ---------------------------------------------------------------------------

def enabled_for(ctx) -> bool:
    """Out-of-core applies when opted in, a session (and therefore the
    spill catalog) exists, and no device mesh is configured — mesh
    execution distributes the working set instead (composing the two is
    future work; docs/distributed.md)."""
    if ctx.session is None:
        return False
    if getattr(ctx.session, "mesh", None) is not None:
        return False
    return ctx.conf.get_bool("spark.rapids.tpu.outOfCore.enabled", False)


def working_set_budget(ctx) -> int:
    b = int(ctx.conf.get("spark.rapids.tpu.outOfCore.partitionBytes", 0))
    if b > 0:
        return b
    from spark_rapids_tpu.memory.device import TpuDeviceManager
    dm = TpuDeviceManager.current()
    if dm is not None:
        return max(dm.hbm_budget // 2, 1 << 20)
    return 1 << 30


def total_batch_bytes(batches) -> int:
    """Measured device bytes of a batch list (capacity-based host
    metadata — the same sizes the exchange's MapStatus records)."""
    return sum(b.device_memory_size() for b in batches if b is not None)


def choose_fanout(ctx, total_bytes: int, budget: int) -> int:
    """Bucket count from MEASURED sizes: next power of two of
    total/budget, clamped to [2, 64]; ``outOfCore.fanout`` overrides."""
    f = int(ctx.conf.get("spark.rapids.tpu.outOfCore.fanout", 0))
    if f > 0:
        return max(2, min(f, _MAX_FANOUT))
    need = max(2, -(-int(total_bytes) // max(int(budget), 1)))
    n = 2
    while n < need and n < _MAX_FANOUT:
        n <<= 1
    return n


def _max_recursion(ctx) -> int:
    return int(ctx.conf.get("spark.rapids.tpu.outOfCore.maxRecursion", 3))


def split_stream_on_budget(ctx, it, budget: Optional[int] = None):
    """Consume ``it`` until the accumulated measured bytes EXCEED the
    budget. Returns ``(prefix, rest, engaged)``: on engagement ``rest``
    is the still-unconsumed iterator (the input was never fully
    materialized — the point of out-of-core is that it may not fit);
    otherwise the whole input is in ``prefix`` and the caller keeps the
    fast in-HBM path."""
    if budget is None:
        budget = working_set_budget(ctx)
    prefix: List[DeviceBatch] = []
    total = 0
    for b in it:
        prefix.append(b)
        total += b.device_memory_size()
        if total > budget:
            return prefix, it, True
    return prefix, None, False


def _stage_spillable(session, batches, budget: int, on_batch=None):
    """Register every incoming batch as a transient spillable (spilling
    the device store down to the budget as they arrive) WITHOUT holding
    them live — the staging pass that bounds peak residency to roughly
    budget + one batch while the driver still needs a second look (to
    size the fan-out, or to sample sort bounds). ``on_batch`` runs on
    each live batch before it is staged (the sort driver samples its
    range bounds here). Returns (bids, bytes)."""
    store = session.buffer_catalog.device_store
    bids: List[int] = []
    total = 0
    for b in batches:
        if b is None:
            continue
        if on_batch is not None:
            on_batch(b)
        total += b.device_memory_size()
        bids.append(session.add_transient_batch(
            b, SpillPriorities.OUTPUT_FOR_READ))
        del b
        if store.total_size > budget:
            store.synchronous_spill(budget)
    return bids, total


def _drain_staged(session, bids):
    """Yield staged batches one at a time, freeing each registration."""
    catalog = session.buffer_catalog
    for bid in bids:
        b = catalog.acquire_batch(bid)
        session.consume_transient(bid)
        yield b


def _record(ctx, op: str, fanout: int, total_bytes: int, budget: int,
            level: int = 0) -> None:
    from spark_rapids_tpu.obs.events import EVENTS
    from spark_rapids_tpu.obs.metrics import REGISTRY
    REGISTRY.counter("ooc.operators", op=op).add(1)
    REGISTRY.counter("ooc.fanout", op=op).add(fanout)
    EVENTS.emit("outOfCore", op=op, fanout=fanout, bytes=int(total_bytes),
                budgetBytes=int(budget), level=level)


# ---------------------------------------------------------------------------
# spillable fan-out partitions
# ---------------------------------------------------------------------------

class SpilledPartitions:
    """Fan-out buckets of spillable batch slices.

    ``add_batch`` splits one batch by a per-row partition id (device
    kernel), registers each non-empty slice as a transient spillable in
    the session catalog, and pushes the device store down to the budget
    — partition-and-spill. ``consume_bucket`` faults a bucket's pieces
    back (the acquireBuffer tier walk) and frees them."""

    def __init__(self, session, schema: Schema, n: int, growth: float,
                 budget: int):
        self.session = session
        self.schema = schema
        self.n = n
        self.growth = growth
        self.budget = budget
        self.buckets: List[List[int]] = [[] for _ in range(n)]
        self.bytes = [0] * n
        self.rows = [0] * n

    def add_batch(self, batch: DeviceBatch, split_kernel) -> None:
        """``split_kernel(batch) -> (pid-sorted batch, (n,) counts)``."""
        sorted_b, counts = split_kernel(batch)
        with sync_scope("outofcore.partitionCounts", detail="spill"):
            host_counts = np.asarray(jax.device_get(counts))
        offsets = np.concatenate([[0], np.cumsum(host_counts)])
        for p in range(self.n):
            c = int(host_counts[p])
            if c == 0:
                continue
            out_cap = bucket_capacity(c, self.growth)
            kern = cached_jit(f"slice|{out_cap}", lambda oc=out_cap: jax.jit(
                lambda bb, s, cc: rowops.slice_batch_to(bb, s, cc, oc)))
            piece = kern(sorted_b, jnp.asarray(int(offsets[p]), jnp.int32),
                         jnp.asarray(c, jnp.int32))
            self.bytes[p] += piece.device_memory_size()
            self.rows[p] += c
            self.buckets[p].append(self.session.add_transient_batch(
                piece, SpillPriorities.OUTPUT_FOR_READ))
        self.spill_to_budget()

    def spill_to_budget(self) -> None:
        store = self.session.buffer_catalog.device_store
        if store.total_size > self.budget:
            store.synchronous_spill(self.budget)

    def consume_bucket(self, p: int) -> List[DeviceBatch]:
        out = []
        catalog = self.session.buffer_catalog
        for bid in self.buckets[p]:
            out.append(catalog.acquire_batch(bid))
            self.session.consume_transient(bid)
        self.buckets[p] = []
        return out


def split_batch_by_hash(ctx, key_idx, batch: DeviceBatch, n: int,
                        level: int, growth: float) -> List[DeviceBatch]:
    """In-memory hash fan-out of ONE batch into <= n disjoint-key slices
    (equal keys co-locate; empty buckets are dropped). The light sibling
    of SpilledPartitions.add_batch — same partitioner and slice kernels,
    no spill-store registration — used by the hash-aggregation VMEM
    bound (exec/tpu.py): a batch whose slot table would exceed
    spark.rapids.sql.agg.hash.maxTableSlots splits here and aggregates
    per slice, the disjoint key sets making the slices' partial outputs
    union to exactly the whole batch's groups."""
    split = hash_split_kernel(key_idx, n, level)
    sorted_b, counts = split(batch)
    with sync_scope("outofcore.partitionCounts", detail="hashSplit"):
        host_counts = np.asarray(jax.device_get(counts))
    offsets = np.concatenate([[0], np.cumsum(host_counts)])
    out: List[DeviceBatch] = []
    for p in range(n):
        c = int(host_counts[p])
        if c == 0:
            continue
        out_cap = bucket_capacity(c, growth)
        kern = cached_jit(f"slice|{out_cap}", lambda oc=out_cap: jax.jit(
            lambda bb, s, cc: rowops.slice_batch_to(bb, s, cc, oc)))
        out.append(kern(sorted_b, jnp.asarray(int(offsets[p]), jnp.int32),
                        jnp.asarray(c, jnp.int32)))
    _record(ctx, "hashAggSplit", n, batch.device_memory_size(), 0, level)
    return out


# ---------------------------------------------------------------------------
# partition-id kernels
# ---------------------------------------------------------------------------

def _level_hash(batch: DeviceBatch, key_idx, level: int):
    """Per-row 64-bit key hash for grace level ``level``: level 0 uses
    h1, level 1 the independent h2, deeper levels a mix — so a fragment
    that did not split at one level re-partitions differently at the
    next (identical keys still co-locate at every level)."""
    h1, h2 = row_hashes(batch, list(key_idx))
    if level == 0:
        return h1
    if level == 1:
        return h2
    return h1 ^ (h2 + jnp.uint64(0x9E3779B97F4A7C15) * jnp.uint64(level))


def hash_split_kernel(key_idx, n: int, level: int):
    """Jitted (batch) -> (pid-sorted batch, counts) splitting on the key
    hash — the grace join / spillable agg partitioner."""
    from spark_rapids_tpu.exec.tpu import _split_by_pid
    key_idx = tuple(key_idx)
    sig = f"ooc|hsplit|{key_idx}|{n}|{level}"

    def build():
        def split(b: DeviceBatch):
            pid = (_level_hash(b, key_idx, level)
                   % jnp.uint64(n)).astype(jnp.int32)
            return _split_by_pid(b, pid, n)
        return jax.jit(split)
    return cached_jit(sig, build)


# ---------------------------------------------------------------------------
# grace hash join
# ---------------------------------------------------------------------------

def join_applicable(ctx, exec_) -> bool:
    return (enabled_for(ctx) and exec_.join_type != "cross"
            and bool(exec_._bkey) and exec_.condition is None)


def grace_join(ctx, exec_, build_batches, stream_batches, growth: float,
               level: int = 0) -> Iterator[DeviceBatch]:
    """Partition both sides on the join-key hash into spillable buckets,
    then join bucket by bucket; a build fragment still over budget
    recurses with the next hash level. Equal keys co-locate, NULL keys
    land in SOME bucket deterministically (they never match; outer rows
    are preserved wherever they land), so the per-bucket results union
    to exactly the in-HBM join's output.

    Both sides are ITERABLES and are never fully materialized: each
    batch is staged onto the spill store as it arrives (peak residency
    ~ budget + one batch), the fan-out is chosen from the staged
    measured totals, and the staged batches drain back one at a time
    into the fan-out partitioner."""
    session = ctx.session
    budget = working_set_budget(ctx)
    bbids, bbytes = _stage_spillable(session, build_batches, budget)
    sbids, sbytes = _stage_spillable(session, stream_batches, budget)
    n = choose_fanout(ctx, bbytes + sbytes, budget)
    _record(ctx, "join", n, bbytes + sbytes, budget, level)
    si, bi = exec_._sides()
    build_schema = exec_.children[bi].output_schema()
    stream_schema = exec_.children[si].output_schema()
    bsplit = hash_split_kernel(exec_._bkey, n, level)
    ssplit = hash_split_kernel(exec_._skey, n, level)
    bparts = SpilledPartitions(session, build_schema, n, growth, budget)
    sparts = SpilledPartitions(session, stream_schema, n, growth, budget)
    for b in _drain_staged(session, bbids):
        bparts.add_batch(b, bsplit)
    for s in _drain_staged(session, sbids):
        sparts.add_batch(s, ssplit)
    from spark_rapids_tpu.exec.tpu import _concat_device
    emitted = False
    for p in range(n):
        frag_bytes = bparts.bytes[p]
        bpieces = bparts.consume_bucket(p)
        spieces = sparts.consume_bucket(p)
        if not bpieces and not spieces:
            continue
        if (frag_bytes > budget and level + 1 < _max_recursion(ctx)
                and len(bpieces) + len(spieces) > 1):
            for out in grace_join(ctx, exec_, bpieces, spieces, growth,
                                  level + 1):
                emitted = True
                yield out
            continue
        build = _concat_device(bpieces, build_schema, growth, coarse=True) \
            if bpieces else DeviceBatch.empty(build_schema)
        for out in _join_bucket(ctx, exec_, build, spieces):
            emitted = True
            yield out
        bparts.spill_to_budget()
    if not emitted:
        yield DeviceBatch.empty(exec_.output_schema())


def _join_bucket(ctx, exec_, build: DeviceBatch,
                 streams: List[DeviceBatch]) -> Iterator[DeviceBatch]:
    """One bucket's in-HBM join via the exec's cached probe/expand
    kernels — the plain (non-speculating, non-dense) emission loop.

    NB: this is deliberately the SIMPLIFIED twin of
    TpuShuffledHashJoinExec's main emission loop (exec/tpujoin.py run():
    the stream taken in rounds within the collapse bound, one totals fetch
    a round, capacity speculation keyed by the round, dense probe
    selection); here every stream batch is its own round, since a bucket
    fits the budget by construction. Changes to join emission semantics
    there (new join types, size/cap layout of _totals, _expand's
    contract) must be mirrored here — the out-of-core tests diff both
    paths against the oracle, which is the drift tripwire."""
    growth = ctx.conf.capacity_growth
    jt = exec_.join_type
    matched_acc = None
    for stream in streams:
        if jt in ("leftsemi", "leftanti"):
            yield exec_._semi(stream, exec_._probe(build, stream)[0])
            continue
        counts, bstart, bperm = exec_._probe(build, stream)
        with sync_scope("outofcore.spillSizes", detail="joinTotals"):
            sizes = [int(x) for x in jax.device_get(
                exec_._totals(build, stream, counts, bstart, bperm))]
        if jt == "full":
            flags = exec_._match_flags(build, counts, bstart, bperm)
            matched_acc = (flags if matched_acc is None
                           else matched_acc | flags)
        total = sizes[0]
        if total == 0:
            continue
        n_s = sum(1 for d in stream.schema.dtypes if d.is_string)
        s_caps = tuple(_char_bucket(c) for c in sizes[1:1 + n_s])
        b_caps = tuple(_char_bucket(c) for c in sizes[1 + n_s:])
        out_cap = bucket_dim(bucket_capacity(total, growth))
        expanded = exec_._expand(build, stream, counts, bstart, bperm,
                                 out_cap, s_caps, b_caps)
        from spark_rapids_tpu.memory.device import TpuDeviceManager
        dm = TpuDeviceManager.current()
        if dm is not None:
            dm.meter_batch(expanded)
        yield expanded
    if jt == "full":
        if matched_acc is None:
            matched_acc = jnp.zeros((build.capacity,), jnp.bool_)
        si, _bi = exec_._sides()
        stream_schema = exec_.children[si].output_schema()
        tail = exec_._unmatched(build, matched_acc, stream_schema)
        if tail.num_rows_host() > 0:
            yield tail


# ---------------------------------------------------------------------------
# external merge sort
# ---------------------------------------------------------------------------

def external_sort(ctx, exec_, batches, schema: Schema,
                  growth: float) -> Iterator[DeviceBatch]:
    """Sampled range bounds -> range-partitioned spill buckets -> one
    in-HBM sort per bucket, emitted in range order: a globally sorted
    stream whose concatenation is byte-identical to the single-batch
    sort (equal keys share a bucket and the per-batch slice order
    preserves the stable tie order).

    ``batches`` is an ITERABLE, never fully materialized: each batch is
    sampled (the GpuRangePartitioner sample — small host fetch) then
    staged onto the spill store; bounds and fan-out come from the staged
    totals, and the staged batches drain back one at a time into the
    range partitioner."""
    session = ctx.session
    budget = working_set_budget(ctx)
    asc = [o.ascending for o in exec_.orders]
    nf = [o.nulls_first for o in exec_.orders]
    base_sig = "ooc|" + exec_.fingerprint_extra()

    def build_sample():
        def samp(b: DeviceBatch):
            work, key_idx = exec_._key_batch(b)
            ops = sortops.sort_key_operands(work, key_idx, asc, nf)
            return b.num_rows, jnp.stack([o.astype(jnp.uint64)
                                          for o in ops])
        return jax.jit(samp)
    sample_kernel = cached_jit(base_sig + "|sample", build_sample)

    samples = []
    kbox = {"k": None}

    def sample(b: DeviceBatch) -> None:
        with sync_scope("outofcore.sample", detail="sortBounds"):
            rows, ops = jax.device_get(sample_kernel(b))
        rows = int(rows)
        ops = np.asarray(ops)
        kbox["k"] = ops.shape[0]
        if rows > 0:
            take = min(rows, 128)
            sel = np.linspace(0, rows - 1, take).astype(np.int64)
            samples.append(ops[:, sel])

    staged, total = _stage_spillable(session, batches, budget,
                                     on_batch=sample)
    k = kbox["k"]
    n = choose_fanout(ctx, total, budget)
    _record(ctx, "sort", n, total, budget)
    from spark_rapids_tpu.parallel.distributed import (
        pick_bounds_from_samples,
    )
    bounds = tuple(jnp.asarray(b) for b in pick_bounds_from_samples(
        samples, k if k is not None else len(asc), n))

    from spark_rapids_tpu.exec.tpu import _concat_device, _split_by_pid
    sig = base_sig + f"|{n}"

    def build_split():
        def split(b: DeviceBatch, *bnds):
            work, key_idx = exec_._key_batch(b)
            pid = sortops.range_partition_ids(work, key_idx, asc, nf,
                                              list(bnds))
            return _split_by_pid(b, pid, n)
        return jax.jit(split)
    split_kernel = cached_jit(sig + "|split", build_split)

    parts = SpilledPartitions(session, schema, n, growth, budget)
    for b in _drain_staged(session, staged):
        parts.add_batch(b, lambda bb: split_kernel(bb, *bounds))
    emitted = False
    for p in range(n):
        pieces = parts.consume_bucket(p)
        if not pieces:
            continue
        merged = _concat_device(pieces, schema, growth)
        emitted = True
        yield exec_._kernel(merged)
        parts.spill_to_budget()
    if not emitted:
        yield exec_._kernel(DeviceBatch.empty(schema))


# ---------------------------------------------------------------------------
# spillable aggregation
# ---------------------------------------------------------------------------

def grace_aggregate(ctx, exec_, batches,
                    growth: float) -> Iterator[DeviceBatch]:
    """Partial-layout batches hash-partitioned on the grouping keys into
    spillable buckets; each bucket merges (and in final mode finalizes)
    independently — key sets are disjoint across buckets, so the union
    of per-bucket outputs IS the aggregate. ``batches`` is an ITERABLE:
    in partial mode the per-batch update pass runs as each batch arrives
    (streaming, bounded by one batch) and its partial is staged onto the
    spill store; fan-out comes from the staged measured totals."""
    session = ctx.session
    plan = exec_.plan
    budget = working_set_budget(ctx)

    def updated():
        for b in batches:
            if b is None:
                continue
            yield exec_._kernel(b) if exec_.mode == "partial" else b
    staged, total = _stage_spillable(session, updated(), budget)
    n = choose_fanout(ctx, total, budget)
    _record(ctx, "aggregate", n, total, budget)
    pschema = plan.partial_schema
    split = hash_split_kernel(range(plan.num_keys), n, 0)
    parts = SpilledPartitions(session, pschema, n, growth, budget)
    for partial in _drain_staged(session, staged):
        parts.add_batch(partial, split)
    from spark_rapids_tpu.exec.tpu import _concat_device
    emitted = False
    for p in range(n):
        pieces = parts.consume_bucket(p)
        if not pieces:
            continue
        merged = exec_._merge_kernel(
            _concat_device(pieces, pschema, growth))
        emitted = True
        yield (merged if exec_.mode == "partial"
               else exec_._final_kernel(merged))
        parts.spill_to_budget()
    if not emitted:
        if exec_.mode == "partial":
            yield exec_._kernel(DeviceBatch.empty(
                exec_.children[0].output_schema()))
        else:
            merged = exec_._merge_kernel(DeviceBatch.empty(pschema))
            yield exec_._final_kernel(merged)
