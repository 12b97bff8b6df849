"""Device aggregate kernel: one fused XLA program per (update|merge) step.

Combines grouping (ops/groupby.py) with the update/merge reduction plans of
exec/aggutil.py. The returned function is jit-compiled once per capacity
bucket and covers: key-expression evaluation, hashing, sort, segment
reductions, and key gathering — the whole per-batch aggregate step the
reference performs through multiple cuDF calls (aggregate.scala:338-396)
runs as a single XLA executable here.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtype import DType
from spark_rapids_tpu.ops import groupby as gb
from spark_rapids_tpu.sql.exprs.core import Expression
from spark_rapids_tpu.sql.exprs.evalbridge import make_context, to_device_column


def aggregate_update(batch: DeviceBatch,
                     key_exprs: Sequence[Expression],
                     input_exprs: Sequence[Expression],
                     reductions: Sequence[Tuple[str, int, DType]],
                     out_schema: Schema,
                     mask_expr: Expression = None,
                     dense=None, hash_table=None) -> DeviceBatch:
    """Partial aggregation of one batch: group by evaluated keys, reduce
    evaluated inputs. reductions: (kind, input_index, out_dtype).
    ``dense``: optional (los device vector, static sizes tuple) enabling
    the exact bounded-int composite grouping key (dense_composite).
    ``hash_table``: optional max slot count enabling the one-pass hash
    aggregation branch (_hash_payload_reduce).

    ``mask_expr``: optional fused pre-filter predicate evaluated over the
    INPUT batch; failing rows are excluded from every group without the
    row-compaction gather a standalone Filter would pay (one gather per
    column at ~5M rows/s on this TPU — the fusion's whole point; the
    reference instead relies on cuDF's cheap gathers,
    basicPhysicalOperators.scala GpuFilterExec:126)."""
    from spark_rapids_tpu.sql.exprs.core import BoundRef
    ctx = make_context(batch)
    live = None
    if mask_expr is not None:
        pred = to_device_column(ctx, mask_expr.eval_device(ctx))
        live = pred.data & pred.validity & batch.row_mask()
    # plain column-reference keys pass the ORIGINAL DeviceColumn through so
    # upload-computed metadata (prefix8, dict codes) survives the
    # expression bridge
    key_cols = [batch.columns[e.index] if isinstance(e, BoundRef)
                else to_device_column(ctx, e.eval_device(ctx))
                for e in key_exprs]
    input_cols = [to_device_column(ctx, e.eval_device(ctx))
                  for e in input_exprs]
    work_schema = Schema(
        [f"k{i}" for i in range(len(key_cols))]
        + [f"v{i}" for i in range(len(input_cols))],
        [c.dtype for c in key_cols] + [c.dtype for c in input_cols])
    work = DeviceBatch(work_schema, key_cols + input_cols, batch.num_rows)
    return _grouped_reduce(work, list(range(len(key_cols))),
                           [(kind, len(key_cols) + idx, dt)
                            for kind, idx, dt in reductions],
                           out_schema,
                           force_single_group=len(key_cols) == 0,
                           live=live, dense=dense, hash_table=hash_table)


def aggregate_passthrough(batch: DeviceBatch,
                          key_exprs: Sequence[Expression],
                          input_exprs: Sequence[Expression],
                          reductions: Sequence[Tuple[str, int, DType]],
                          out_schema: Schema,
                          mask_expr: Expression = None) -> DeviceBatch:
    """Skipped partial aggregation: project rows straight into the partial
    layout WITHOUT grouping — every row becomes a singleton group
    (count = valid?1:0, sum = value, min/max/first/last = value). Used by
    the adaptive low-reduction skip
    (spark.rapids.sql.agg.skipAggPassReductionRatio): when the partial
    pass barely reduces, the grouping sort is pure overhead on a single
    chip (the exchange is a local concat) — the final aggregate reduces
    once over the projected rows. A fused filter mask degrades to one
    row compaction here (rowops.filter_batch)."""
    from spark_rapids_tpu.ops.rowops import filter_batch
    from spark_rapids_tpu.sql.exprs.core import BoundRef
    ctx = make_context(batch)
    if mask_expr is not None:
        pred = to_device_column(ctx, mask_expr.eval_device(ctx))
        batch = filter_batch(batch, pred.data & pred.validity)
        ctx = make_context(batch)
    key_cols = [batch.columns[e.index] if isinstance(e, BoundRef)
                else to_device_column(ctx, e.eval_device(ctx))
                for e in key_exprs]
    input_cols = [to_device_column(ctx, e.eval_device(ctx))
                  for e in input_exprs]
    out_cols: List[DeviceColumn] = list(key_cols)
    ones = None
    for kind, idx, out_dt in reductions:
        col = input_cols[idx]
        if kind == "count_valid":
            if ones is None:
                ones = jnp.ones((batch.capacity,), jnp.bool_)
            out_cols.append(DeviceColumn(
                out_dt, col.validity.astype(out_dt.np_dtype), ones))
        elif col.dtype.is_string:
            out_cols.append(col)
        elif kind == "any":
            out_cols.append(DeviceColumn(
                out_dt, (col.data & col.validity).astype(out_dt.np_dtype),
                col.validity))
        else:  # sum/min/max/first/last(_valid): the value IS the partial
            data = col.data
            if data.dtype != out_dt.np_dtype:
                data = data.astype(out_dt.np_dtype)
            out_cols.append(DeviceColumn(out_dt, data, col.validity))
    return DeviceBatch(out_schema, out_cols, batch.num_rows)


def aggregate_merge(batch: DeviceBatch, num_keys: int,
                    reductions: Sequence[Tuple[str, int, DType]],
                    out_schema: Schema, dense=None,
                    hash_table=None) -> DeviceBatch:
    """Merge partial outputs: group by leading key columns, reduce
    intermediate columns with merge kinds. reductions: (kind, col_idx, dt)."""
    return _grouped_reduce(batch, list(range(num_keys)), list(reductions),
                           out_schema, force_single_group=num_keys == 0,
                           dense=dense, hash_table=hash_table)


# group-slot width of the fast aggregation branch: segment reductions at
# capacity width cost the TPU seconds per call (scatter cost scales with
# the output width), at 64Ki slots they are ~20x cheaper. Queries whose
# per-batch group count exceeds this fall back to the exact-width branch
# inside the same compiled program (lax.cond).
GROUP_SLOTS = 65536


# cap on the direct dictionary slot table (product of per-key
# cardinalities): bounds the one-hot matmul's minor dimension
DICT_SLOT_MAX = 4096


def _dict_path_info(batch: DeviceBatch, key_idx: List[int]):
    """Static probe: every key column dictionary-encoded at upload and the
    joint slot table small -> (cards, strides, T), else None. All inputs to
    this decision are pytree aux data, so the branch is resolved at trace
    time (no lax.cond)."""
    from spark_rapids_tpu.ops import densered
    if batch.capacity > densered.MAX_EXACT_CAPACITY:
        return None  # the f32-exactness argument caps the batch size
    cards = []
    for ki in key_idx:
        col = batch.columns[ki]
        if col.dict_values is None:
            return None
        cards.append(col.dict_card + 1)  # +1: the NULL code
    T = 1
    for c in cards:
        T *= c
    if T > DICT_SLOT_MAX:
        return None
    strides = []
    acc = 1
    for c in reversed(cards):
        strides.append(acc)
        acc *= c
    return cards, list(reversed(strides)), T


def _grouped_reduce(batch: DeviceBatch, key_idx: List[int],
                    reductions: List[Tuple[str, int, DType]],
                    out_schema: Schema,
                    force_single_group: bool,
                    live=None, dense=None, hash_table=None) -> DeviceBatch:
    def out(res):
        # dense callers always receive (result, ok): paths the dense key
        # does not apply to are trivially ok
        return (res, jnp.asarray(True)) if dense is not None else res
    if not key_idx:
        return out(_single_group_reduce(batch, reductions, out_schema, live))
    has_string_reduction = any(
        batch.columns[ci].dtype.is_string and kind != "count_valid"
        for kind, ci, _dt in reductions)
    if has_string_reduction:
        return out(_sorted_space_reduce(batch, key_idx, reductions,
                                        out_schema, live))
    dict_info = _dict_path_info(batch, key_idx)
    if dict_info is not None:
        return out(_dict_matmul_reduce(batch, key_idx, reductions,
                                       out_schema, dict_info, live))
    if dense is not None:
        # bounded-int keys (advisory scan stats, exec/tpu.py): exact
        # composite grouping key. ONLY the dense program is compiled —
        # the ok flag rides the deferred speculation verification
        # (session._verify_speculation) and a stale-stats miss
        # re-executes the query without dense grouping. A lax.cond
        # fallback would compile BOTH grouping paths into every
        # aggregation (measured to push big multi-agg chains past the
        # bench's per-query deadline).
        los, sizes = dense
        lv = batch.row_mask() if live is None else live
        comp, ok = dense_composite(batch, key_idx, los, sizes, lv)
        return _dense_payload_reduce(batch, key_idx, reductions,
                                     out_schema, lv, comp, los, sizes), ok
    if hash_table is not None:
        # opt-in one-pass hash aggregation (spark.rapids.sql.agg.
        # hashAggEnabled): claims slots and folds accumulators in one
        # walk — no sort, no segment scan. Engages exactly where the
        # dense path cannot (unbounded keys) and the sorted path is
        # today's fallback; declines (None) at TRACE time when a key
        # needs char-level images or the table exceeds the slot budget,
        # falling through to the branches below.
        res = _hash_payload_reduce(batch, key_idx, reductions, out_schema,
                                   live, hash_table)
        if res is not None:
            return out(res)
    # dictionary-encoded keys (bounded cardinality): the sort-free slot
    # attempt usually wins; otherwise (high/unknown cardinality) the
    # payload-sort path — its segment ops see SORTED ids, which XLA lowers
    # ~10x cheaper than the row-space scatters of the old sort branch
    if len(key_idx) <= 32 and not all(
            batch.columns[ki].dict_values is not None for ki in key_idx):
        return out(_sorted_payload_reduce(batch, key_idx, reductions,
                                          out_schema, live))
    return out(_rowspace_reduce(batch, key_idx, reductions, out_schema,
                                live))


def _sorted_payload_reduce(batch: DeviceBatch, key_idx: List[int],
                           reductions: List[Tuple[str, int, DType]],
                           out_schema: Schema, live=None) -> DeviceBatch:
    """High-cardinality keyed aggregation in sorted space.

    Shape (each step chosen for how XLA:TPU compiles, all measured):
      1. group_rows' 4-operand hash sort assigns the sorted order — the
         SAME compiled sort every other grouping path uses (a lax.sort
         gains ~25-150s of COMPILE time per extra operand at >=512k rows
         on this backend, so the wide carry-everything-through-the-sort
         spelling is unusable: 2 keys + 12 payloads measured 301s to
         compile);
      2. every reduction input and the exact key images move to sorted
         space with dtype-grouped PACKED gathers (compile-cheap, ~100ms
         run at 4M);
      3. group boundaries = the hash boundaries REFINED by adjacent-image
         comparison, so two keys are merged only when every exact image
         agrees — at least as strong as the dual-hash grouping this
         replaces (fixed-width keys: image = value, exact; strings:
         prefix8+length+both poly hashes). The refinement can only ever
         SPLIT a hash collision, never merge distinct keys; an
         interleaved collision (probability ~2^-128) splits a group into
         runs rather than corrupting it;
      4. every reduction runs as a segment op over SORTED ids — ~100x
         cheaper than the row-space scatters of the old design (measured
         5.7s -> 0.05s per op at 4M rows / 1.25M groups).

    The reference leans on cuDF's hash aggregation
    (aggregate.scala:338-396) which has no TPU analogue; this is the
    sort-based recipe re-tuned for XLA's scatter and sort lowering."""
    from spark_rapids_tpu.ops import hashing
    from spark_rapids_tpu.ops.rowops import gather_columns
    from spark_rapids_tpu.ops.sortops import string_prefix8, u64_key_image
    from spark_rapids_tpu.ops.tablekernels import compact_permutation

    capacity = batch.capacity
    if live is None:
        live = batch.row_mask()
    pos = jnp.arange(capacity, dtype=jnp.int32)

    info = gb.group_rows(batch, key_idx, compute_rep=False, live=live)
    perm = info.perm

    # exact key images + per-key validity signature, gathered to sorted
    # space alongside the reduction inputs in dtype-grouped packed gathers
    imgs: List[jnp.ndarray] = []
    nullsig = jnp.zeros((capacity,), jnp.uint32)
    for j, ki in enumerate(key_idx):
        col = batch.columns[ki]
        if col.dtype.is_string and col.dict_values is not None:
            # dictionary codes are exact per batch by construction: ONE
            # image, zero char reads (vs prefix+length+two poly hashes)
            per = [col.dict_codes.astype(jnp.uint64)]
        elif col.dtype.is_string:
            # layout-aware: slab columns derive lens/prefix/hashes
            # densely from their words, packed columns scan chars —
            # bit-identical images either way (docs/gatherfree.md)
            lens = col.lens_()
            h1, h2 = hashing.string_poly_hashes_col(col)
            per = [string_prefix8(col), lens.astype(jnp.uint64), h1, h2]
        else:
            per = u64_key_image(col)
        # canonical image for null rows; real values sharing it are told
        # apart by the validity signature
        imgs.extend(jnp.where(col.validity, im, jnp.uint64(0))
                    for im in per)
        nullsig = nullsig | (col.validity.astype(jnp.uint32)
                             << jnp.uint32(j))

    payload_cols: List[int] = []
    payload_pos: dict = {}
    for _kind, ci, _dt in reductions:
        if ci not in payload_pos:
            payload_pos[ci] = len(payload_cols)
            payload_cols.append(ci)
    vectors: List[jnp.ndarray] = list(imgs) + [nullsig]
    for ci in payload_cols:
        col = batch.columns[ci]
        if col.dtype.is_string:
            # only count_valid consumes string inputs here (string
            # min/max take the sorted-space path); validity stands in
            d = col.validity
        else:
            d = col.data
        vectors.extend([d, col.validity])
    from spark_rapids_tpu.ops.rowops import packed_gather_vectors
    gathered = packed_gather_vectors(vectors, perm)
    imgs_s = gathered[:len(imgs)]
    nullsig_s = gathered[len(imgs)]
    payloads_s = gathered[len(imgs) + 1:]

    # refined boundaries: hash boundary OR any exact image disagreement
    # (group_rows' boundary is already masked to live rows; the
    # refinement must be too — dead rows sort last)
    dead_slot = _sorted_dead_mask(info, live)
    differs = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                               nullsig_s[1:] != nullsig_s[:-1]])
    for img_s in imgs_s:
        differs = differs | jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), img_s[1:] != img_s[:-1]])
    boundary = (info.boundary | differs) & ~dead_slot
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    sid = jnp.where(dead_slot, capacity, jnp.clip(gid, 0, capacity - 1))
    num_groups = boundary.sum().astype(jnp.int32)
    group_live = pos < num_groups

    def seg(op, x):
        return op(x, sid, num_segments=capacity + 1,
                  indices_are_sorted=True)[:capacity]

    # key output columns: one packed gather at the groups' first rows
    slot_perm, _n = compact_permutation(boundary)
    rep_row = perm[slot_perm]
    out_cols = gather_columns([batch.columns[ki] for ki in key_idx],
                              rep_row, group_live)

    live_slot = ~dead_slot
    for kind, ci, out_dt in reductions:
        pi = payload_pos[ci] * 2
        data_s, valid_s = payloads_s[pi], payloads_s[pi + 1] != 0
        src_dtype = batch.columns[ci].data.dtype
        if src_dtype == jnp.bool_ and data_s.dtype != jnp.bool_:
            data_s = data_s != 0
        if batch.columns[ci].dtype.is_string:
            # only count_valid reaches here; the payload pair carries
            # validity twice
            data, validity = _seg_reduce_kind(
                "count_valid", valid_s, valid_s & live_slot, live_slot,
                seg, pos, lambda x: x, capacity, capacity, out_dt)
        else:
            data, validity = _seg_reduce_kind(
                kind, data_s, valid_s & live_slot, live_slot, seg, pos,
                lambda x: x, capacity, capacity, out_dt)
        out_cols.append(DeviceColumn(out_dt, data, validity & group_live))
    return DeviceBatch(out_schema, out_cols, num_groups)


def _sorted_dead_mask(info: "gb.GroupInfo", live) -> jnp.ndarray:
    """bool per SORTED slot: the slot holds a dead (padding or
    filtered-out) row. group_rows sorts dead rows last, so the mask is
    one gather-free comparison against the live count."""
    capacity = info.perm.shape[0]
    n_live = jnp.sum(live.astype(jnp.int32))
    return jnp.arange(capacity, dtype=jnp.int32) >= n_live


def _hash_payload_reduce(batch: DeviceBatch, key_idx: List[int],
                         reductions: List[Tuple[str, int, DType]],
                         out_schema: Schema, live, max_slots: int):
    """Hash aggregation over the open-addressing slot table
    (ops/tablekernels.hash_grouped_aggregate): every row probes to its
    key's slot and each reduction is one segment op over the slots — no
    sort. This is the cuDF open-addressing groupby shape
    (aggregate.scala:338-396) the sorted path only approximates.

    Trace-time applicability (returns None -> caller falls through to the
    sorted/row-space branches):
      * every key must have an EXACT one-word image: fixed-width values
        (u64_key_image) or dictionary codes (exact per batch by
        construction). Plain un-dictionaried strings would need
        char-level images — declined.
      * hash_table_size(capacity) must fit ``max_slots``
        (spark.rapids.sql.agg.hash.maxTableSlots — the VMEM-class bound;
        exec/tpu.py buckets oversized batches through the out-of-core
        fan-out before calling in here).

    Null keys form real groups: the null image is a canonical sentinel
    and the per-key validity bits join the key image vector, so a real
    value sharing the sentinel stays a distinct group (the sorted path's
    nullsig spelling)."""
    from spark_rapids_tpu.ops import tablekernels as tk
    from spark_rapids_tpu.ops.rowops import gather_columns
    from spark_rapids_tpu.ops.sortops import u64_key_image

    capacity = batch.capacity
    for ki in key_idx:
        col = batch.columns[ki]
        if col.dtype.is_string and col.dict_values is None:
            return None
    T = tk.hash_table_size(capacity)
    if T > max_slots:
        return None
    if live is None:
        live = batch.row_mask()
    pos = jnp.arange(capacity, dtype=jnp.int32)

    imgs: List[jnp.ndarray] = []
    nullsig = jnp.zeros((capacity,), jnp.uint32)
    for j, ki in enumerate(key_idx):
        col = batch.columns[ki]
        if col.dtype.is_string:
            per = [col.dict_codes.astype(jnp.uint64)]
        else:
            per = u64_key_image(col)
        imgs.extend(jnp.where(col.validity, im, jnp.uint64(0))
                    for im in per)
        nullsig = nullsig | (col.validity.astype(jnp.uint32)
                             << jnp.uint32(j))
    imgs.append(nullsig.astype(jnp.uint64))

    # lower every reduction kind onto the kernel's {sum,min,max} job
    # contract; semantics mirror _seg_reduce_kind exactly (the oracle the
    # tier-1 tests pin this path against)
    jobs = []
    for kind, ci, out_dt in reductions:
        col = batch.columns[ci]
        valid = col.validity & live
        if kind == "count_valid":
            jobs.append(("sum", valid.astype(jnp.int64), live))
        elif kind == "sum":
            jobs.append(("sum",
                         jnp.where(valid, col.data, 0).astype(
                             out_dt.np_dtype), valid))
        elif kind in ("min", "max"):
            v2, _neutral = gb.minmax_operands(col.data, kind)
            jobs.append((kind, v2, valid))
        elif kind in ("first", "last", "first_valid", "last_valid"):
            eligible = valid if kind.endswith("_valid") else live
            jobs.append(("min" if kind.startswith("first") else "max",
                         pos, eligible))
        elif kind == "any":
            jobs.append(("max", (col.data & valid).astype(jnp.int32),
                         live))
        else:
            raise ValueError(f"unknown reduction kind: {kind}")

    counts, rep, accs, nels = tk.hash_grouped_aggregate(imgs, live, jobs, T)

    # compact used slots to the front; n_used <= live rows <= capacity and
    # T >= 2*capacity, so the first ``capacity`` compacted entries hold
    # every used slot — output width stays the input bucket (as the
    # sorted path) and downstream shape bucketing is undisturbed
    used = counts > 0
    slot_perm, n_used = tk.compact_permutation(used)
    sel = slot_perm[:capacity]
    group_live = pos < n_used
    rep_row = jnp.clip(rep, 0, capacity - 1)[sel]
    out_cols = gather_columns([batch.columns[ki] for ki in key_idx],
                              rep_row, group_live)

    for (kind, ci, out_dt), (jkind, _d, _e), acc, nel in zip(
            reductions, jobs, accs, nels):
        a, ne = acc[sel], nel[sel]
        has = ne > 0
        if kind == "count_valid":
            data = jnp.where(has, a, 0).astype(out_dt.np_dtype)
            validity = group_live
        elif kind == "sum":
            data = jnp.where(has, a, 0).astype(out_dt.np_dtype)
            validity = has & group_live
        elif kind in ("min", "max"):
            data = jnp.where(has, a, jnp.zeros((), a.dtype))
            if out_dt.np_dtype == jnp.bool_:
                data = data.astype(jnp.bool_)
            data = data.astype(out_dt.np_dtype)
            validity = has & group_live
        elif kind in ("first", "last", "first_valid", "last_valid"):
            rowsel = jnp.clip(a, 0, capacity - 1)
            data = batch.columns[ci].data[rowsel].astype(out_dt.np_dtype)
            validity = has & batch.columns[ci].validity[rowsel] & group_live
        else:  # any
            data = (jnp.where(has, a, 0) > 0).astype(out_dt.np_dtype)
            validity = group_live
        out_cols.append(DeviceColumn(out_dt, data, validity))
    return DeviceBatch(out_schema, out_cols, n_used.astype(jnp.int32))


def _dict_matmul_reduce(batch: DeviceBatch, key_idx: List[int],
                        reductions: List[Tuple[str, int, DType]],
                        out_schema: Schema, dict_info,
                        live=None) -> DeviceBatch:
    """Direct-addressed aggregation over dictionary codes: slot id is pure
    arithmetic on the host-computed codes (no hashing, no collision or
    agreement checks — codes are exact by construction), every sum/count
    rides ONE one-hot matmul (ops/densered.py), and the group-key output
    columns are HOST CONSTANTS decoded from the static dictionary (zero
    device char reads). Output capacity shrinks to the slot-table bucket,
    so downstream exchange/merge/sort stop paying the input batch's
    padding. This is the cuDF hash-aggregation analogue rebuilt around the
    MXU (reference: aggregate.scala:338-396)."""
    import numpy as np
    from spark_rapids_tpu.columnar.batch import bucket_capacity
    from spark_rapids_tpu.ops import densered
    from spark_rapids_tpu.ops.rowops import gather_column
    from spark_rapids_tpu.ops.tablekernels import compact_permutation

    cards, strides, T = dict_info
    capacity = batch.capacity
    if live is None:
        live = batch.row_mask()
    slot = jnp.zeros((capacity,), jnp.int32)
    for ki, stride in zip(key_idx, strides):
        slot = slot + batch.columns[ki].dict_codes * jnp.int32(stride)
    slot = jnp.where(live, slot, T)  # park dead rows outside the table

    dense_jobs = []
    dense_pos = {}  # reduction index -> dense job index
    for ri, (kind, ci, out_dt) in enumerate(reductions):
        col = batch.columns[ci]
        if kind in densered.DENSE_KINDS and (
                kind == "count_valid"
                or not col.dtype.is_string
                and densered.dense_supported(kind, col.data.dtype)):
            dense_pos[ri] = len(dense_jobs)
            dense_jobs.append((kind, col.validity if kind == "count_valid"
                               else col.data, col.validity,
                               out_dt.np_dtype))
    dense_res, row_count = densered.slot_reduce_dense(slot, live, T,
                                                      dense_jobs)
    used = row_count > 0
    slot_perm, n_used = compact_permutation(used)
    from spark_rapids_tpu.utils.kernelcache import bucket_dim
    out_cap = bucket_dim(bucket_capacity(T))
    pad_n = out_cap - T
    perm_pad = jnp.concatenate(
        [slot_perm, jnp.zeros((pad_n,), jnp.int32)]) if pad_n else slot_perm
    group_live = jnp.arange(out_cap, dtype=jnp.int32) < n_used

    def place(data_t, valid_t):
        """(T,) slot-space result -> (out_cap,) compacted group rows."""
        if pad_n:
            data_t = jnp.concatenate(
                [data_t, jnp.zeros((pad_n,), data_t.dtype)])
            valid_t = jnp.concatenate(
                [valid_t, jnp.zeros((pad_n,), jnp.bool_)])
        return data_t[perm_pad], valid_t[perm_pad] & group_live

    out_cols: List[DeviceColumn] = []
    # key columns: decoded from the static dictionary on the HOST at trace
    # time; only the T-row compaction gather runs on device
    for ki, stride, card1 in zip(key_idx, strides, cards):
        col = batch.columns[ki]
        card = card1 - 1
        code_of_slot = (np.arange(out_cap) // stride) % card1
        code_of_slot[T:] = card
        validity = code_of_slot < card
        if col.dtype.is_string:
            vals = np.array(
                [col.dict_values[c] if c < card else None
                 for c in code_of_slot], dtype=object)
        else:
            fill = col.dict_values[0]
            vals = np.array(
                [col.dict_values[c] if c < card else fill
                 for c in code_of_slot], dtype=col.dtype.np_dtype)
        bufs = DeviceColumn.build_host_buffers(vals, validity, col.dtype,
                                               out_cap)
        const_col = DeviceColumn(
            col.dtype, *(jnp.asarray(b) for b in bufs),
            dict_codes=jnp.asarray(code_of_slot.astype(np.int32)),
            dict_values=col.dict_values)
        out_cols.append(gather_column(const_col, perm_pad, group_live))

    def seg(op, x):
        return op(x, slot, num_segments=T + 1)[:T]

    pos = jnp.arange(capacity, dtype=jnp.int32)
    for ri, (kind, ci, out_dt) in enumerate(reductions):
        if ri in dense_pos:
            data_t, valid_t = dense_res[dense_pos[ri]]
            d, v = place(data_t, valid_t)
            out_cols.append(DeviceColumn(out_dt, d, v))
            continue
        # tail kinds (min/max/first/last/any, dtypes the dense engine
        # declined): T-width segment ops — one indexed pass each, only
        # paid when the query uses them
        col = batch.columns[ci]
        data_t, valid_t = _seg_reduce_kind(
            kind, col.data, col.validity & live, live, seg, pos,
            lambda x: x, capacity, T, out_dt)
        d, v = place(data_t, valid_t)
        out_cols.append(DeviceColumn(out_dt, d, v))
    return DeviceBatch(out_schema, out_cols, n_used.astype(jnp.int32))


def _single_group_reduce(batch: DeviceBatch,
                         reductions: List[Tuple[str, int, DType]],
                         out_schema: Schema, live=None) -> DeviceBatch:
    """Global aggregate: plain masked vector reductions, no sort, no
    segments, no gathers (SQL: global agg of empty input = one row).

    The output batch has MIN_CAPACITY (not the input capacity): a global
    aggregate is exactly one row, and carrying the input's padding forward
    forced every downstream exchange/merge to run at pre-aggregation scale
    (a 4-batch global sum would concat to 4M-capacity for 4 rows)."""
    from spark_rapids_tpu.columnar.batch import MIN_CAPACITY
    capacity = batch.capacity
    out_cap = MIN_CAPACITY
    if live is None:
        live = batch.row_mask()
    pos = jnp.arange(capacity, dtype=jnp.int32)
    out_cols: List[DeviceColumn] = []
    slot0 = jnp.arange(out_cap, dtype=jnp.int32) == 0

    def place(scalar, valid_scalar, out_dt):
        data = jnp.zeros((out_cap,), out_dt.np_dtype).at[0].set(
            scalar.astype(out_dt.np_dtype))
        validity = jnp.zeros((out_cap,), jnp.bool_).at[0].set(valid_scalar)
        return DeviceColumn(out_dt, data, validity)

    for kind, col_idx, out_dt in reductions:
        col = batch.columns[col_idx]
        if col.dtype.is_string:
            if kind == "count_valid":
                cnt = jnp.sum((col.validity & live).astype(jnp.int64))
                out_cols.append(place(cnt, jnp.asarray(True), out_dt))
                continue
            # string min/max/first/last over one group: pick the winning
            # row with the select machinery over a trivial GroupInfo
            from spark_rapids_tpu.ops.rowops import gather_column
            info = _trivial_group_info(batch, live)
            rows, has = gb.segment_select_string(kind, col, info)
            out_cols.append(gather_column(col, rows[:out_cap],
                                          has[:out_cap] & slot0))
            continue
        valid = col.validity & live
        vs = col.data
        any_valid = jnp.any(valid)
        if kind == "count_valid":
            out_cols.append(place(jnp.sum(valid.astype(jnp.int64)),
                                  jnp.asarray(True), out_dt))
        elif kind == "sum":
            x = jnp.where(valid, vs, 0).astype(out_dt.np_dtype)
            out_cols.append(place(jnp.sum(x), any_valid, out_dt))
        elif kind in ("min", "max"):
            vs, neutral = gb.minmax_operands(vs, kind)
            x = jnp.where(valid, vs, neutral)
            red = jnp.min(x) if kind == "min" else jnp.max(x)
            if out_dt.np_dtype == jnp.bool_:
                red = red.astype(jnp.bool_)
            out_cols.append(place(red.astype(out_dt.np_dtype), any_valid,
                                  out_dt))
        elif kind in ("first", "last", "first_valid", "last_valid"):
            eligible = valid if kind.endswith("_valid") else live
            big = capacity + 1
            if kind.startswith("first"):
                sel = jnp.min(jnp.where(eligible, pos, big))
            else:
                sel = jnp.max(jnp.where(eligible, pos, -1))
            picked = (sel >= 0) & (sel < capacity)
            sel_c = jnp.clip(sel, 0, capacity - 1)
            out_cols.append(place(vs[sel_c].astype(out_dt.np_dtype),
                                  picked & valid[sel_c], out_dt))
        elif kind == "any":
            out_cols.append(place(
                jnp.any(vs & valid).astype(out_dt.np_dtype),
                jnp.asarray(True), out_dt))
        else:
            raise ValueError(f"unknown reduction kind: {kind}")
    return DeviceBatch(out_schema, out_cols, jnp.asarray(1, jnp.int32))


def _trivial_group_info(batch: DeviceBatch, live=None) -> "gb.GroupInfo":
    capacity = batch.capacity
    if live is None:
        live = batch.row_mask()
    idx = jnp.arange(capacity, dtype=jnp.int32)
    dead = (~live).astype(jnp.uint8)
    dead_s, perm = jax.lax.sort((dead, idx), num_keys=1, is_stable=True)
    live_s = dead_s == 0
    boundary = jnp.zeros((capacity,), jnp.bool_).at[0].set(live_s[0])
    # dead rows MUST be parked outside group 0 (same convention as
    # group_rows): they can be VALID rows excluded by a fused filter mask,
    # and with gid 0 they would compete in string min/max and win
    # positional first/last (also fixes padding rows nulling a global
    # last(string))
    gid = jnp.where(live_s, 0, capacity - 1)
    return gb.GroupInfo(perm, gid, boundary, jnp.asarray(1, jnp.int32),
                        jnp.zeros((capacity,), jnp.int32))


def _sorted_space_reduce(batch: DeviceBatch, key_idx: List[int],
                         reductions: List[Tuple[str, int, DType]],
                         out_schema: Schema, live=None) -> DeviceBatch:
    """The original sorted-space path (string reductions need the ordered
    slots of segment_select_string)."""
    capacity = batch.capacity
    info = gb.group_rows(batch, key_idx, live=live)
    num_groups = info.num_groups
    out_cols: List[DeviceColumn] = []
    out_cols.extend(gb.gather_keys(batch, key_idx, info))
    group_live = jnp.arange(capacity, dtype=jnp.int32) < num_groups
    for kind, col_idx, out_dt in reductions:
        col = batch.columns[col_idx]
        if col.dtype.is_string:
            if kind == "count_valid":
                data, validity = gb.segment_reduce(
                    kind, col.validity, col.validity, info, out_dt.np_dtype)
                out_cols.append(DeviceColumn(out_dt, data,
                                             validity & group_live))
                continue
            from spark_rapids_tpu.ops.rowops import gather_column
            rows, has = gb.segment_select_string(kind, col, info)
            out_cols.append(gather_column(col, rows, has & group_live))
            continue
        data, validity = gb.segment_reduce(kind, col.data, col.validity,
                                           info, out_dt.np_dtype)
        out_cols.append(DeviceColumn(out_dt, data, validity & group_live))
    return DeviceBatch(out_schema, out_cols, num_groups)


def _seg_reduce_kind(kind: str, vs, valid, live, seg, order_vec, to_row,
                     capacity: int, width: int, out_dt: DType):
    """One non-string reduction kind over a segment closure — the SINGLE
    definition of per-kind null/tie semantics shared by the row-space
    reduce_core (slot and sort branches) and the dictionary tail path, so
    they cannot diverge. ``valid`` must already be masked to live rows;
    ``seg(op, x)`` reduces (capacity,) -> (width,); ``order_vec``/``to_row``
    define first/last ordering and map a selected order value back to an
    original row index. Returns (data (width,), validity (width,)) — the
    caller ANDs its group-liveness mask into validity."""
    has_valid = seg(jax.ops.segment_max, valid.astype(jnp.int32)) > 0
    if kind == "count_valid":
        data = seg(jax.ops.segment_sum, valid.astype(jnp.int64))
        return (data.astype(out_dt.np_dtype),
                jnp.ones((width,), jnp.bool_))
    if kind == "sum":
        x = jnp.where(valid, vs, 0).astype(out_dt.np_dtype)
        return seg(jax.ops.segment_sum, x), has_valid
    if kind in ("min", "max"):
        v2, neutral = gb.minmax_operands(vs, kind)
        x = jnp.where(valid, v2, neutral)
        op = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
        data = seg(op, x)
        if out_dt.np_dtype == jnp.bool_:
            data = data.astype(jnp.bool_)
        return data.astype(out_dt.np_dtype), has_valid
    if kind in ("first", "last", "first_valid", "last_valid"):
        eligible = valid if kind.endswith("_valid") else live
        big = capacity + 1
        if kind.startswith("first"):
            sel = seg(jax.ops.segment_min,
                      jnp.where(eligible, order_vec, big))
        else:
            sel = seg(jax.ops.segment_max,
                      jnp.where(eligible, order_vec, -1))
        picked = (sel >= 0) & (sel < capacity)
        rowsel = to_row(jnp.clip(sel, 0, capacity - 1))
        data = vs[rowsel].astype(out_dt.np_dtype)
        return data, picked & valid[rowsel]
    if kind == "any":
        data = seg(jax.ops.segment_max, (vs & valid).astype(jnp.int32)) > 0
        return data.astype(out_dt.np_dtype), jnp.ones((width,), jnp.bool_)
    raise ValueError(f"unknown reduction kind: {kind}")


# slot count of the sort-free hash-table branch (the cuDF hash-aggregation
# analogue): row key-images scatter into this many slots; exact per-key
# image equality over each used slot proves the slot is a true group
SLOT_TABLE = 8192


def _slot_hash_attempt(batch: DeviceBatch, key_idx: List[int], live=None):
    """Sort-free group assignment attempt: map each row's exact 64-bit key
    images to a slot (mixed image % SLOT_TABLE) and verify per-key image
    equality within every used slot. Returns (fast_ok bool scalar, slot id
    per row (dead -> SLOT_TABLE), rep_row per slot, used mask, n_used).

    Exactness: fixed-width key images carry the full value; string images
    carry the first 8 bytes + length and are only trusted when every live
    string is <= 8 bytes (checked). A slot shared by two distinct key
    tuples makes some per-key (min != max) -> fast_ok False and the caller
    takes the sort-based branch — collisions and >SLOT_TABLE-group batches
    degrade, never corrupt."""
    from spark_rapids_tpu.ops.hashing import splitmix64
    capacity = batch.capacity
    if live is None:
        live = batch.row_mask()
    T = min(SLOT_TABLE, capacity)
    # per key column: (key index, [exact equality image vectors]) — every
    # image of a key must agree slot-wide for the slot to be a true group
    key_images = []
    ok_short = jnp.asarray(True)
    for ki in key_idx:
        col = batch.columns[ki]
        if col.dtype.is_string and col.dict_values is not None:
            # dictionary codes are exact per batch by construction: ONE
            # image, zero char reads, and no prefix-length constraint —
            # dict string columns are codes-only integers, so treating
            # them as plain strings here was needlessly conservative
            per_key = [col.dict_codes.astype(jnp.uint64)]
        elif col.dtype.is_string:
            from spark_rapids_tpu.ops.sortops import string_prefix8
            lens = col.lens_()
            # host-computed at upload (gather-propagated, zero char reads),
            # derived densely from the slab words, or one device
            # reconstruction pass
            img = string_prefix8(col)
            # the raw prefix is injective over the bytes, but 0-padding
            # aliases 'a' with 'a\x00' — the length joins the agreement
            # check as its OWN image (XOR-folding it into one 64-bit word
            # would reintroduce probabilistic equality)
            per_key = [img, lens.astype(jnp.uint64)]
            ok_short = ok_short & jnp.all(
                jnp.where(live, lens, 0) <= 8)
        else:
            from spark_rapids_tpu.ops.sortops import u64_key_image
            per_key = [u64_key_image(col)[0]]
        # null keys get a distinct image band (exactness against a real
        # value sharing the sentinel comes from the validity agreement
        # check below)
        per_key = [jnp.where(col.validity, im,
                             jnp.uint64(0x9E3779B97F4A7C15))
                   for im in per_key]
        key_images.append((ki, per_key))
    rid = jnp.asarray(0x243F6A8885A308D3, jnp.uint64)
    for _ki, per_key in key_images:
        for img in per_key:
            rid = splitmix64(rid ^ img)
    slot = jnp.where(live, (rid % jnp.uint64(T)).astype(jnp.int32), T)

    def seg(op, x):
        return op(x, slot, num_segments=T + 1)[:T]

    used_cnt = seg(jax.ops.segment_sum, jnp.ones((capacity,), jnp.int32))
    used = used_cnt > 0
    collide = jnp.asarray(False)
    for ki, per_key in key_images:
        for img in per_key:
            smin = seg(jax.ops.segment_min,
                       jnp.where(live, img, ~jnp.uint64(0)))
            smax = seg(jax.ops.segment_max,
                       jnp.where(live, img, jnp.uint64(0)))
            collide = collide | jnp.any(used & (smin != smax))
        # a real value whose image happens to equal the null sentinel
        # would merge with nulls undetected by the image test alone —
        # require slot-wide validity agreement too
        v = batch.columns[ki].validity.astype(jnp.int32)
        vmin = seg(jax.ops.segment_min, jnp.where(live, v, 2))
        vmax = seg(jax.ops.segment_max, jnp.where(live, v, -1))
        collide = collide | jnp.any(used & (vmin != vmax))
    fast_ok = ok_short & ~collide
    n_used = used.sum().astype(jnp.int32)
    return fast_ok, slot, used, n_used


def _rowspace_reduce(batch: DeviceBatch, key_idx: List[int],
                     reductions: List[Tuple[str, int, DType]],
                     out_schema: Schema, live=None) -> DeviceBatch:
    """Keyed aggregation with NO per-column permutation gathers: one packed
    scatter bridges the hash-sorted group assignment back to row space,
    then every reduction runs directly on the unpermuted columns. When the
    batch's group count fits GROUP_SLOTS (the overwhelmingly common case)
    the segment reductions run at slot width — ~20x cheaper than
    capacity-wide scatters on TPU; the exact capacity-wide branch lives in
    the same program behind a lax.cond."""
    capacity = batch.capacity
    gs = min(capacity, GROUP_SLOTS)
    if live is None:
        live = batch.row_mask()
    pos = jnp.arange(capacity, dtype=jnp.int32)

    def reduce_core(width: int, seg_id, order_vec, to_row, num_groups,
                    slot_perm=None):
        """All outputs at ``width`` segment slots, padded to capacity.
        seg_id: per-row segment (width = parked); order_vec: per-row
        ordering for first/last; to_row: map a selected order value back
        to an original row index; slot_perm: optional slot compaction
        (used hash-table slots to the front)."""
        nseg = width + 1  # parked slot for dead/overflow rows

        def pad(x):
            if width == capacity:
                return x
            return jnp.concatenate(
                [x, jnp.zeros((capacity - width,), x.dtype)])

        def seg(op, x):
            r = op(x, seg_id, num_segments=nseg)[:width]
            return r[slot_perm] if slot_perm is not None else r

        # representative (first) row per group, for key gathering
        big = capacity + 1
        rep_slot = seg(jax.ops.segment_min,
                       jnp.where(live, order_vec, big))
        rep_row = to_row(jnp.clip(rep_slot, 0, capacity - 1))
        group_live = jnp.arange(width, dtype=jnp.int32) < num_groups

        outs = []
        from spark_rapids_tpu.ops.rowops import gather_column
        for ki in key_idx:
            kcol = gather_column(batch.columns[ki], rep_row, group_live)
            if kcol.dtype.is_string and kcol.dict_values is not None:
                # dictionary strings stay codes-only: materializing a
                # char slab here would give the two cond branches
                # DIFFERENT char capacities (width-dependent lazy
                # buckets). 2 leaves (codes, validity), padded with the
                # NULL sentinel; dict presence is trace-static so both
                # branches agree on the layout.
                card = jnp.int32(len(kcol.dict_values))
                codes = kcol.dict_codes
                validity = kcol.validity
                if width != capacity:
                    codes = jnp.concatenate(
                        [codes, jnp.full((capacity - width,), card,
                                         jnp.int32)])
                    validity = pad(validity)
                outs.append(DeviceColumn(kcol.dtype, None, validity,
                                         dict_codes=codes,
                                         dict_values=kcol.dict_values))
                continue
            if kcol.prefix8 is not None or kcol.dict_values is not None:
                # group outputs are tiny; drop the prefix image and the
                # dictionary so the cond's flat-leaf layout stays fixed
                # (3 leaves per string col, 2 per fixed-width)
                kcol = DeviceColumn(kcol.dtype, kcol.data, kcol.validity,
                                    kcol.offsets)
            if width != capacity:
                if kcol.dtype.is_string:
                    last = kcol.offsets[width]
                    off_pad = jnp.full((capacity - width,), 0, jnp.int32) + last
                    kcol = DeviceColumn(
                        kcol.dtype, kcol.data,
                        pad(kcol.validity),
                        jnp.concatenate([kcol.offsets, off_pad]))
                else:
                    kcol = DeviceColumn(kcol.dtype, pad(kcol.data),
                                        pad(kcol.validity))
            outs.append(kcol)

        for kind, col_idx, out_dt in reductions:
            col = batch.columns[col_idx]
            if col.dtype.is_string:  # only count_valid reaches here
                cnt = seg(jax.ops.segment_sum,
                          (col.validity & live).astype(jnp.int64))
                outs.append(DeviceColumn(
                    out_dt, pad(cnt.astype(out_dt.np_dtype)),
                    pad(jnp.ones((width,), jnp.bool_) & group_live)))
                continue
            data, validity = _seg_reduce_kind(
                kind, col.data, col.validity & live, live, seg, order_vec,
                to_row, capacity, width, out_dt)
            outs.append(DeviceColumn(out_dt, pad(data),
                                     pad(validity & group_live)))
        return tuple(jax.tree_util.tree_leaves(outs))

    def slot_branch():
        _fast_ok, slot, used, n_used = _slot_state
        width = min(SLOT_TABLE, capacity)
        from spark_rapids_tpu.ops.tablekernels import compact_permutation
        slot_perm, _cnt = compact_permutation(used)
        leaves = reduce_core(width, slot, pos, lambda x: x, n_used,
                             slot_perm=slot_perm)
        return leaves + (n_used,)

    def sort_branch():
        info = gb.group_rows(batch, key_idx, compute_rep=False,
                              live=live)
        num_groups = info.num_groups
        # one scatter carries (group id, sorted position) per original row
        packed = jnp.zeros((capacity,), jnp.int64).at[info.perm].set(
            info.group_id_sorted.astype(jnp.int64) * (capacity + 1)
            + pos.astype(jnp.int64))
        gid_row = (packed // (capacity + 1)).astype(jnp.int32)
        inv_pos = (packed % (capacity + 1)).astype(jnp.int32)

        def at(width: int):
            sid = jnp.where(live & (gid_row < width),
                            jnp.clip(gid_row, 0, width - 1), width)
            return reduce_core(
                width, sid, inv_pos,
                lambda x: info.perm[jnp.clip(x, 0, capacity - 1)],
                num_groups)
        if gs == capacity:
            return at(capacity) + (num_groups,)
        return jax.lax.cond(
            num_groups <= gs, lambda: at(gs) + (num_groups,),
            lambda: at(capacity) + (num_groups,))

    # sort-free hash-table attempt first (the cuDF hash-agg analogue):
    # exact via per-key image agreement, falls back to the sort path for
    # collisions, long string keys, or > SLOT_TABLE groups. The attempt
    # itself costs ~17 segment passes (~0.8s at 1M rows), so only try it
    # when every key column is dictionary-encoded (bounded cardinality —
    # typically these took the direct dict path already, landing here only
    # when the joint slot table overflowed DICT_SLOT_MAX); high-cardinality
    # keys would fail the attempt anyway and go straight to the sort path.
    attempt_worthwhile = all(
        batch.columns[ki].dict_values is not None for ki in key_idx)
    if attempt_worthwhile:
        _slot_state = _slot_hash_attempt(batch, key_idx, live)
        leaves = jax.lax.cond(_slot_state[0], slot_branch, sort_branch)
    else:
        leaves = sort_branch()
    num_groups = leaves[-1]
    leaves = leaves[:-1]
    # rebuild columns from the flattened leaves (cond needs flat outputs)
    out_cols: List[DeviceColumn] = []
    it = iter(leaves)
    for ki in key_idx:
        col = batch.columns[ki]
        dt = col.dtype
        if dt.is_string and col.dict_values is not None:
            # lazy-column leaf order is (validity, codes) — column.py
            # tree_flatten
            validity, codes = next(it), next(it)
            out_cols.append(DeviceColumn(dt, None, validity,
                                         dict_codes=codes,
                                         dict_values=col.dict_values))
        elif dt.is_string:
            chars, validity, offsets = next(it), next(it), next(it)
            out_cols.append(DeviceColumn(dt, chars, validity, offsets))
        else:
            data, validity = next(it), next(it)
            out_cols.append(DeviceColumn(dt, data, validity))
    for _kind, _ci, out_dt in reductions:
        data, validity = next(it), next(it)
        out_cols.append(DeviceColumn(out_dt, data, validity))
    return DeviceBatch(out_schema, out_cols, num_groups)


def _equality_image(col: DeviceColumn):
    """(fields, decode) of one key column for the fused count-distinct.

    ``fields``: [(uint64 image, bits)] whose joint value is equal exactly
    where two VALID values of the column are: a dictionary code takes the
    bits its cardinality needs, an integer of at most 32 bits its width,
    so several keys share one sort operand (_pack_fields). ``decode``
    rebuilds the column from those fields' values, or is None where the
    image is not invertible (floats; plain strings, whose image is
    prefix8 + length + two polynomial hashes, the grouping contract):
    such a key is gathered from a row of its group instead."""
    from spark_rapids_tpu.ops import hashing
    from spark_rapids_tpu.ops.sortops import string_prefix8, u64_key_image
    dt = col.dtype
    if dt.is_string and col.dict_values is not None:
        card = len(col.dict_values)

        def decode_codes(vals, validity):
            codes = jnp.where(validity, vals[0].astype(jnp.int32), card)
            return DeviceColumn(dt, None, validity, dict_codes=codes,
                                dict_values=col.dict_values)
        return ([(col.dict_codes.astype(jnp.uint64),
                  max(1, (card - 1).bit_length()))], decode_codes)
    if dt.is_string:
        h1, h2 = hashing.string_poly_hashes_col(col)
        return ([(string_prefix8(col), 64),
                 (col.lens_().astype(jnp.uint64), 32), (h1, 64), (h2, 64)],
                None)
    d = col.data
    if d.dtype == jnp.bool_:
        return ([(d.astype(jnp.uint64), 1)],
                lambda vals, validity: DeviceColumn(dt, vals[0] != 0,
                                                    validity))
    if jnp.issubdtype(d.dtype, jnp.floating):
        return [(im, 64) for im in u64_key_image(col)], None
    bits = 8 * d.dtype.itemsize
    img = d.astype(jnp.int64).view(jnp.uint64)
    if bits < 64:
        img = img & jnp.uint64((1 << bits) - 1)

    def decode_int(vals, validity):
        v = vals[0].view(jnp.int64)
        if bits < 64 and jnp.issubdtype(d.dtype, jnp.signedinteger):
            sign = jnp.int64(1 << (bits - 1))
            v = (v ^ sign) - sign
        return DeviceColumn(dt, v.astype(d.dtype), validity)
    return [(img, bits)], decode_int


def _pack_fields(fields):
    """Pack [(uint64 image, bits)] into as few words of at most 64 bits
    as hold them, in order, the first field the most significant of its
    word; a word is the narrowest unsigned type its fields fill (a sort
    compiles and runs by the bits it carries). Returns (words, places):
    places[i] = (word, shift, bits) of field i."""
    groups: List[List[int]] = []
    used = 0
    for i, (_img, bits) in enumerate(fields):
        if groups and used + bits <= 64:
            groups[-1].append(i)
            used += bits
        else:
            groups.append([i])
            used = bits
    words, places = [], [None] * len(fields)
    for w, group in enumerate(groups):
        shift = sum(fields[i][1] for i in group)
        word = None
        for i in group:
            img, bits = fields[i]
            shift -= bits
            part = img << jnp.uint64(shift) if shift else img
            word = part if word is None else word | part
            places[i] = (w, shift, bits)
        used = sum(fields[i][1] for i in group)
        words.append(word.astype(next(
            t for t in (jnp.uint8, jnp.uint16, jnp.uint32, jnp.uint64)
            if used <= 8 * jnp.dtype(t).itemsize)))
    return words, places


def _field(words, place) -> jnp.ndarray:
    w, shift, bits = place
    v = words[w].astype(jnp.uint64)
    v = v >> jnp.uint64(shift) if shift else v
    return v & jnp.uint64((1 << bits) - 1) if bits < 64 else v


def count_distinct_reduce(batch: DeviceBatch, g2_idx: List[int],
                          rest_idx: List[int], skip_null: bool = False):
    """count(distinct <rest keys>) grouped by <g2 keys> in ONE sorted
    pass over the combined G1 = g2+rest tuple — the fused form of the
    distinct -> regroup -> count chain Spark (and this planner) expands
    count(DISTINCT) into (the reference executes that chain as two full
    cuDF aggregations, aggregate.scala:40-225; on this backend each
    aggregation pass costs a hash sort + segment sweep, so fusing the
    two levels halves the dominant cost — q16's shape).

    Two carrying sorts and elementwise passes, no gather or scatter where
    every g2 key's image is invertible (sorts are cheap on this chip,
    gathers dear, PERF.md). Each key is a validity bit and an equality
    image (_equality_image) packed with its neighbours into uint64 words
    (q16: brand and type codes, the int32 size, their validity and the
    dead flag are one word; the int64 supplier key a second; its validity
    a third). Neither sort need be stable (equal words are one tuple,
    group starts have distinct positions), which spares each an operand.
    Sorted by (dead, g2 words, rest words): a G1-distinct tuple
    starts where ANY word differs from the previous row; a G2 group
    starts where a g2 word differs. A second sort, keyed by a group
    start's own position, brings the group starts to the front in order
    with their g2 words and the running count of tuples before them, so a
    group's count is the next group's running count less its own and its
    keys are decoded from the words it carries. Exactness matches the
    grouping paths: fixed-width keys and dictionary codes are exact,
    plain strings prefix8+length+dual-poly-hash (collision ~2^-128, the
    documented grouping contract). Null keys group together: the image
    of a NULL is 0 under a validity bit of 0.

    ``skip_null`` (static) is SQL's count(DISTINCT k): a tuple with a
    NULL among its rest keys is not counted, and a group whose every
    tuple is such reads 0 and stays. Off, it is count(*) over the
    distinct tuples, where NULL is a value like another.

    Returns (keys, counts, num_groups): keys = {g2 column index: that key
    column of every group}, counts = distinct live G1 tuples a group, both
    prefix-compact at the batch's capacity.
    """
    from spark_rapids_tpu.ops.rowops import (
        gather_columns, packed_gather_vectors,
    )
    from spark_rapids_tpu.ops.sortops import (
        MAX_DIRECT_SORT_OPERANDS, lexsort_permutation,
    )
    capacity = batch.capacity
    live = batch.row_mask()
    pos = jnp.arange(capacity, dtype=jnp.int32)

    def key_fields(idx_list, lead):
        """[validity bit, image fields] a key; (fields, what each key's
        decode needs: (validity field, its image fields, decode))."""
        fields, keys = list(lead), []
        for ki in idx_list:
            col = batch.columns[ki]
            imgs, decode = _equality_image(col)
            at = len(fields)
            fields.append((col.validity.astype(jnp.uint64), 1))
            fields.extend((jnp.where(col.validity, im, jnp.uint64(0)), b)
                          for im, b in imgs)
            keys.append((at, range(at + 1, len(fields)), decode))
        return fields, keys

    # dead rows last: the flag is the first word's most significant field
    g2_fields, g2_keys = key_fields(
        g2_idx, [((~live).astype(jnp.uint64), 1)])
    r_fields, r_keys = key_fields(rest_idx, [])
    g2_words, g2_places = _pack_fields(g2_fields)
    r_words, r_places = _pack_fields(r_fields)
    n2 = len(g2_words)
    words = g2_words + r_words
    # a key that cannot be decoded is read from a row of its group
    carried = [ki for ki, (_v, _f, decode) in zip(g2_idx, g2_keys)
               if decode is None]
    if len(words) + bool(carried) <= MAX_DIRECT_SORT_OPERANDS:
        s = jax.lax.sort(tuple(words) + ((pos,) if carried else ()),
                         num_keys=len(words), is_stable=False)
        words_s, perm = list(s[:len(words)]), s[-1]
    else:
        perm = lexsort_permutation(words)
        words_s = packed_gather_vectors(words, perm)

    def differs(vecs, acc):
        for v in vecs:
            acc = acc | jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), v[1:] != v[:-1]])
        return acc

    # dead rows sorted last and live rows were a prefix: ``live`` is the
    # sorted rows' mask too
    d_g2 = differs(words_s[:n2], pos == 0)
    g2_b = d_g2 & live
    g1_b = differs(words_s[n2:], d_g2) & live
    if skip_null:
        for at, _f, _d in r_keys:
            g1_b = g1_b & (_field(words_s[n2:], r_places[at]) != 0)
    g1 = g1_b.astype(jnp.int32)
    before = jnp.cumsum(g1) - g1          # tuples counted before this row
    total = jnp.sum(g1)
    n_groups = jnp.sum(g2_b.astype(jnp.int32))
    # the group starts to the front, in order, with what they carry
    c = jax.lax.sort(
        (jnp.where(g2_b, pos, capacity),) + tuple(words_s[:n2]) + (before,)
        + ((perm,) if carried else ()), num_keys=1, is_stable=False)
    heads, before_c = list(c[1:1 + n2]), c[1 + n2]
    group_live = pos < n_groups
    after = jnp.where(pos == n_groups - 1, total,
                      jnp.concatenate([before_c[1:], before_c[:1]]))
    counts = jnp.where(group_live, after - before_c, 0).astype(jnp.int64)
    keys = {}
    for ki, (at, img_fields, decode) in zip(g2_idx, g2_keys):
        if decode is not None:
            validity = (_field(heads, g2_places[at]) != 0) & group_live
            keys[ki] = decode([_field(heads, g2_places[f])
                               for f in img_fields], validity)
    if carried:
        rep_rows = jnp.where(group_live, c[-1], 0)
        for ki, col in zip(carried, gather_columns(
                [batch.columns[ki] for ki in carried], rep_rows,
                group_live)):
            keys[ki] = col
    return keys, counts, n_groups


def dense_composite(batch: DeviceBatch, key_idx: List[int],
                    los: jnp.ndarray, sizes: Tuple[int, ...], live):
    """Single u64 composite grouping key for bounded-int key tuples:
    slot_i = key_i - lo_i (value) or size_i (NULL), composite = mixed-radix
    over (size_i + 1). Bijective with the key tuple INCLUDING null-ness,
    so adjacent-equality on the composite is an EXACT group boundary — no
    hashes, no image refinement, and the grouping sort drops from 4
    operands (dead, h1, h2, idx) to 2 (composite, idx), the measured
    dominant cost of high-cardinality aggregation (q18/q21 shape).

    ``los``: int64 device vector (k,), advisory scan-stat lower bounds.
    ``sizes``: static per-key slot counts (bucketed pow2 of the stat
    range). Returns (comp u64, ok bool): ok=False when any live valid key
    falls outside its advisory range — the caller defers ok to the
    speculation verification and the query re-executes without dense
    grouping on a miss, so correctness never depends on the stats."""
    capacity = batch.capacity
    comp = jnp.zeros((capacity,), jnp.uint64)
    ok = jnp.asarray(True)
    for j, ki in enumerate(key_idx):
        col = batch.columns[ki]
        off = col.data.astype(jnp.int64) - los[j]
        size = sizes[j]
        in_rng = (off >= 0) & (off < size)
        ok = ok & jnp.all(in_rng | ~col.validity | ~live)
        slot = jnp.where(col.validity, jnp.clip(off, 0, size - 1),
                         size).astype(jnp.uint64)
        comp = comp * jnp.uint64(size + 1) + slot
    return comp, ok


def _segmented_scan(combine, x: jnp.ndarray, start: jnp.ndarray,
                    longest: jnp.ndarray) -> jnp.ndarray:
    """Inclusive scan of ``x`` with ``combine`` inside runs of consecutive
    rows: ``start[i]`` is the first row of row i's run, ``longest`` the
    longest run's length. Each row takes in the row 1, 2, 4... places
    before it while that row is in its run, so a run's last row holds the
    whole run after log2(longest) elementwise passes. This is the segment
    reduction over sorted ids without the scatter, which costs a float64
    or int64 row 75 ns on a v5e against 1 ns here (PERF.md, PR 28)."""
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)

    def step(carry):
        acc, d = carry
        reach = (pos - d) >= start
        return jnp.where(reach, combine(jnp.roll(acc, d), acc), acc), d * 2
    return jax.lax.while_loop(lambda c: c[1] < longest, step,
                              (x, jnp.asarray(1, jnp.int32)))[0]


_SCAN_OF_SEGMENT_OP = ((jax.ops.segment_sum, jnp.add),
                       (jax.ops.segment_max, jnp.maximum),
                       (jax.ops.segment_min, jnp.minimum))


def _dense_keys(batch: DeviceBatch, key_idx: List[int], comp: jnp.ndarray,
                los: jnp.ndarray, sizes: Tuple[int, ...],
                live) -> List[DeviceColumn]:
    """The key columns a composite stands for: dense_composite inverted
    (it is a bijection of the key tuple, null-ness included)."""
    cols: List[DeviceColumn] = []
    for j in range(len(key_idx) - 1, -1, -1):
        col = batch.columns[key_idx[j]]
        radix = jnp.uint64(sizes[j] + 1)
        slot = (comp % radix).astype(jnp.int64)
        comp = comp // radix
        valid = (slot != sizes[j]) & live
        data = jnp.where(valid, slot + los[j], 0).astype(col.data.dtype)
        cols.append(DeviceColumn(col.dtype, data, valid))
    return cols[::-1]


def _dense_payload_reduce(batch: DeviceBatch, key_idx: List[int],
                          reductions: List[Tuple[str, int, DType]],
                          out_schema: Schema, live, comp: jnp.ndarray,
                          los: jnp.ndarray,
                          sizes: Tuple[int, ...]) -> DeviceBatch:
    """Grouped reduction over an exact composite key (dense_composite),
    built from what this backend does cheaply a row — sorts and elementwise
    passes — and none of what it does dearly — gathers and scatters:

      1. one stable sort by the composite carries every reduction input;
      2. a group is a run of equal composites, and every reduction is a
         segmented scan over the runs (_segmented_scan), whose result stands
         in the run's last row. Reduction semantics stay single-sourced
         through _seg_reduce_kind, whose ``seg`` here stays in sorted space;
      3. one stable sort by "not a run's last row" brings the groups to the
         front with their composite and results, and the key columns are
         the composite decoded (_dense_keys)."""
    from spark_rapids_tpu.ops.rowops import sort_carrying
    capacity = batch.capacity
    pos = jnp.arange(capacity, dtype=jnp.int32)
    # dead rows sort last: composite < product(size_i+1) <= 2^62 < MAX
    comp2 = jnp.where(live, comp, ~jnp.uint64(0))

    payload_cols: List[int] = []
    payload_pos: dict = {}
    for _kind, ci, _dt in reductions:
        if ci not in payload_pos:
            payload_pos[ci] = len(payload_cols)
            payload_cols.append(ci)
    vectors: List[jnp.ndarray] = []
    for ci in payload_cols:
        col = batch.columns[ci]
        d = col.validity if col.dtype.is_string else col.data
        vectors.extend([d, col.validity])
    comp_s, payloads_s = sort_carrying(comp2, vectors)

    n_live = jnp.sum(live.astype(jnp.int32))
    live_slot = pos < n_live
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), comp_s[1:] != comp_s[:-1]]) & live_slot
    # a run ends where the next one starts, or the live rows do
    last = jnp.concatenate(
        [first[1:] | ~live_slot[1:], jnp.ones((1,), jnp.bool_)]) & live_slot
    start = jax.lax.cummax(jnp.where(first, pos, 0))
    longest = jnp.max(jnp.where(live_slot, pos - start + 1, 0))
    num_groups = first.sum().astype(jnp.int32)

    def seg(op, x):
        combine = next(c for o, c in _SCAN_OF_SEGMENT_OP if o is op)
        return _segmented_scan(combine, x, start, longest)

    results: List[jnp.ndarray] = []
    for kind, ci, out_dt in reductions:
        pi = payload_pos[ci] * 2
        data_s, valid_s = payloads_s[pi], payloads_s[pi + 1] != 0
        src_dtype = batch.columns[ci].data.dtype
        if src_dtype == jnp.bool_ and data_s.dtype != jnp.bool_:
            data_s = data_s != 0
        if batch.columns[ci].dtype.is_string:
            kind, data_s = "count_valid", valid_s
        data, validity = _seg_reduce_kind(
            kind, data_s, valid_s & live_slot, live_slot, seg, pos,
            lambda x: x, capacity, capacity, out_dt)
        results.extend([data, validity])

    _, grouped = sort_carrying((~last).astype(jnp.uint8),
                               [comp_s] + results)
    group_live = pos < num_groups
    out_cols = _dense_keys(batch, key_idx, grouped[0], los, sizes,
                           group_live)
    for i, (_kind, _ci, out_dt) in enumerate(reductions):
        data, validity = grouped[1 + 2 * i], grouped[2 + 2 * i]
        out_cols.append(DeviceColumn(out_dt, data, validity & group_live))
    return DeviceBatch(out_schema, out_cols, num_groups)
