"""Row-level batch kernels: gather, compaction (filter), concatenation.

These replace cuDF's Table.filter / Table.concatenate / gather calls
(reference call sites: basicPhysicalOperators.scala GpuFilterExec:126,
GpuCoalesceBatches.scala:52). All shape-static: outputs share the input
capacity (or a target bucket) and carry a new num_rows scalar.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn


def sort_carrying(key: jnp.ndarray, vectors: Sequence[jnp.ndarray]):
    """``vectors`` in the stable order of ``key``: (sorted key, [vectors]).
    The vectors ride the sort as operands: a sort moves an operand at about
    1 ns a slot on a v5e where a gather by the permutation costs 10-17 ns,
    and compiles about 10 s longer for every 32 bits of operand (PERF.md,
    PR 28)."""
    as_ops = [v.astype(jnp.uint8) if v.dtype == jnp.bool_ else v
              for v in vectors]
    out = jax.lax.sort((key, *as_ops), num_keys=1, is_stable=True)
    return out[0], [o != 0 if v.dtype == jnp.bool_ else o
                    for o, v in zip(out[1:], vectors)]


def rank_of_iota(sorted_vals: jnp.ndarray, out_len: int) -> jnp.ndarray:
    """``searchsorted(sorted_vals, arange(out_len), side='right')`` as a
    histogram + cumsum: two dense-ish passes instead of a per-element
    binary search (searchsorted at 2^22 costs ~0.8s on this TPU; this
    form ~0.2s). Values below 0 count toward every position, values above
    out_len toward none — exactly searchsorted's clip behavior for an
    iota query vector."""
    hist = jnp.zeros((out_len + 1,), jnp.int32).at[
        jnp.clip(sorted_vals.astype(jnp.int32), 0, out_len)].add(1)
    return jnp.cumsum(hist[:out_len]).astype(jnp.int32)


def packed_gather_vectors(vectors: Sequence[jnp.ndarray],
                          perm: jnp.ndarray) -> List[jnp.ndarray]:
    """Gather many same-length raw vectors by one index vector with
    dtype-grouped STACKED gathers (the gather_columns trick without the
    column wrapper): a (n, k) row gather moves k lane-contiguous elements
    per index — 4-6x cheaper than k separate 1-D gathers on TPU. Bool
    inputs ride as int8 (callers convert back)."""
    groups: dict = {}
    for i, v in enumerate(vectors):
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int8)
        groups.setdefault(str(v.dtype), []).append((i, v))
    out: List[jnp.ndarray] = [None] * len(vectors)
    for _dt, items in groups.items():
        if len(items) == 1:
            i, v = items[0]
            out[i] = v[perm]
        else:
            m = jnp.stack([v for _i, v in items], axis=1)[perm, :]
            for j, (i, _v) in enumerate(items):
                out[i] = m[:, j]
    return out


def gather_columns(cols: Sequence[DeviceColumn], perm: jnp.ndarray,
                   live: jnp.ndarray,
                   char_caps: Sequence[int] = ()) -> List[DeviceColumn]:
    """Gather MANY columns by one index vector with PACKED row gathers.

    A 1-D gather lowers to a scalar-ish loop on TPU (~5M elem/s); gathering
    a stacked (n, k) matrix along rows moves k lane-contiguous elements per
    index and measures ~4-6x faster for typical column counts. So all
    fixed-width payloads sharing a dtype ride ONE stacked gather (data,
    validity, string lengths/starts, prefix images, dictionary codes), and
    only the string char slabs keep their per-column char-space gather.
    ``char_caps``: optional per-STRING-column output char capacities (same
    contract as the old per-column gather)."""
    out_cap = perm.shape[0]
    plans: dict = {}   # dtype key -> list of (array, col_index, field)
    parts: List[dict] = [dict() for _ in cols]

    def add(arr, ci, field):
        # bool matrices hit a pathological gather lowering on TPU
        # (measured ~100x slower than int8); ride as int8 lanes instead
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int8)
        plans.setdefault(str(arr.dtype), []).append((arr, ci, field))

    slabs: dict = {}
    for i, c in enumerate(cols):
        add(c.validity, i, "validity")
        if c.dtype.is_string:
            if c.dict_values is not None:
                # dictionary strings move ONLY their codes; the output is
                # a codes-only (lazy) column — chars rebuild from the
                # static dictionary if a consumer ever reads them. Char
                # space (tens of MB at fact scale) is never touched here.
                add(c.dict_codes, i, "codes")
                continue
            if c.has_slab:
                # blocked chars: the fixed-stride slab moves with ONE 2-D
                # row gather (k lane-contiguous words per index — the
                # stacked-gather form), lens ride the packed int32 group.
                # No char-index gather happens at all; packed chars only
                # materialize if a downstream consumer reads them.
                add(c.lens_(), i, "slens")
                slabs[i] = c._slab64
                continue
            # _ExtentColumn (concat's flat view) carries explicit extents;
            # plain columns derive them from the offsets vector
            lens = getattr(c, "ext_lens", None)
            if lens is None:
                lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int32)
            starts = getattr(c, "ext_starts", None)
            if starts is None:
                starts = c.offsets[:-1].astype(jnp.int32)
            add(lens, i, "lens")
            add(starts, i, "starts")
            if c.prefix8 is not None:
                add(c.prefix8, i, "prefix8")
        else:
            add(c.data, i, "data")
        if c.dict_values is not None:
            add(c.dict_codes, i, "codes")

    for _key, entries in plans.items():
        if len(entries) == 1:
            arr, ci, field = entries[0]
            parts[ci][field] = arr[perm]
            continue
        m = jnp.stack([a for a, _, _ in entries], axis=1)[perm, :]
        for j, (_a, ci, field) in enumerate(entries):
            parts[ci][field] = m[:, j]

    out: List[DeviceColumn] = []
    si = 0
    for i, c in enumerate(cols):
        p = parts[i]
        validity = (p["validity"] != 0) & live
        codes = None
        if c.dict_values is not None:
            codes = jnp.where(live, p["codes"],
                              jnp.asarray(c.dict_card, jnp.int32))
        if not c.dtype.is_string:
            data = p["data"]
            if data.dtype != c.data.dtype:
                # bool payloads rode the packed gather as int8 (see add());
                # restore the column's physical dtype
                data = data.astype(c.data.dtype)
            out.append(DeviceColumn(c.dtype, data, validity,
                                    dict_codes=codes,
                                    dict_values=c.dict_values))
            continue
        occ = char_caps[si] if si < len(char_caps) else 0
        si += 1
        if i in slabs:
            slab_out = slabs[i][perm]
            slab_out = jnp.where(live[:, None], slab_out,
                                 jnp.uint64(0))
            lens_out = jnp.where(live, p["slens"], 0).astype(jnp.int32)
            out.append(DeviceColumn(c.dtype, None, validity,
                                    slab64=slab_out, lens=lens_out))
            continue
        if codes is not None:
            # codes-only output: chars never move (see the add() loop) —
            # the column materializes from its static dictionary only if
            # some consumer actually reads chars
            out.append(DeviceColumn(c.dtype, None, validity,
                                    dict_codes=codes,
                                    dict_values=c.dict_values))
            continue
        nchars = c.data.shape[0]
        new_len = jnp.where(live, p["lens"], 0)
        new_offsets = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(new_len).astype(jnp.int32)])
        out_chars_n = occ if occ > 0 else nchars
        total_new = new_offsets[out_cap]
        k = jnp.arange(out_chars_n, dtype=jnp.int32)
        out_row = jnp.clip(rank_of_iota(new_offsets, out_chars_n) - 1,
                           0, out_cap - 1)
        src_idx = p["starts"][out_row] + (k - new_offsets[out_row])
        gathered = c.data[jnp.clip(src_idx, 0, nchars - 1)]
        new_chars = jnp.where(k < total_new, gathered, 0).astype(jnp.uint8)
        prefix8 = None
        if c.prefix8 is not None:
            prefix8 = jnp.where(live, p["prefix8"], jnp.uint64(0))
        out.append(DeviceColumn(c.dtype, new_chars, validity, new_offsets,
                                prefix8, codes, c.dict_values))
    return out


def gather_column(col: DeviceColumn, perm: jnp.ndarray,
                  live: jnp.ndarray,
                  out_char_capacity: int = 0) -> DeviceColumn:
    """Gather rows of a column by index vector ``perm`` (len = out capacity).
    ``live`` marks which output slots are real rows; dead slots become
    invalid/empty. ``out_char_capacity`` sizes the output char buffer for
    string columns (default: same as the source — callers that *expand*
    rows, like joins, must pass the synced total). Multi-column callers
    should use gather_columns (packed row gathers)."""
    caps = (out_char_capacity,) if col.dtype.is_string else ()
    return gather_columns([col], perm, live, caps)[0]


def _shared_dict(parts: Sequence[DeviceColumn]):
    """The dictionary all ``parts`` share, or None: a concat result keeps
    codes only when every input encodes against the SAME static values."""
    if parts[0].dict_values is None or any(
            p.dict_values != parts[0].dict_values for p in parts):
        return None
    return parts[0].dict_values


# union-dictionary cardinality ceiling for the exchange-boundary merge:
# beyond it the merged dictionary would stop being "small host constant"
# material (it rides jit cache keys as aux data), so the concat decodes
# instead — the same bound the small-table pre-seed uses.
DICT_MERGE_MAX_CARD = 1 << 14


def _concat_dict_info(parts: Sequence[DeviceColumn], dict_merge: bool):
    """(values, effective per-part codes) for a concat keeping codes:
    identical dictionaries pass through; DIFFERENT dictionaries merge by
    union + an O(cardinality) static remap per part (the exchange-
    boundary merge, docs/gatherfree.md) when ``dict_merge`` is on.
    (None, None) -> the caller must decode (legacy char path)."""
    shared = _shared_dict(parts)
    if shared is not None:
        return shared, [p.dict_codes for p in parts]
    if not dict_merge:
        return None, None
    if any(p.dict_values is None or p.dict_codes is None for p in parts):
        return None, None
    from spark_rapids_tpu.columnar.dictionary import (
        union_dictionaries_cached,
    )
    vals, remaps = union_dictionaries_cached(
        [p.dict_values for p in parts])
    if len(vals) > DICT_MERGE_MAX_CARD:
        return None, None
    eff = []
    for p, r in zip(parts, remaps):
        card_p = len(p.dict_values)
        eff.append(jnp.asarray(r)[jnp.clip(p.dict_codes, 0, card_p)])
    return vals, eff


def _concat_slabs(parts: Sequence[DeviceColumn]):
    """Per-part slabs re-padded to the widest word count, or None when
    some part is not slab-backed (the caller then takes the char path,
    which transparently materializes slab parts)."""
    if any(not p.has_slab for p in parts):
        return None
    w_out = max(int(p._slab64.shape[1]) for p in parts)
    out = []
    for p in parts:
        s = p._slab64
        w = int(s.shape[1])
        if w < w_out:
            s = jnp.pad(s, ((0, 0), (0, w_out - w)))
        out.append(s)
    return out


def gather_batch(batch: DeviceBatch, perm: jnp.ndarray,
                 num_rows: jnp.ndarray) -> DeviceBatch:
    out_cap = perm.shape[0]
    live = jnp.arange(out_cap, dtype=jnp.int32) < num_rows
    cols = gather_columns(batch.columns, perm, live)
    return DeviceBatch(batch.schema, cols, num_rows.astype(jnp.int32))


def sort_compactable(columns: Sequence[DeviceColumn]) -> bool:
    """Can ``filter_batch`` compact these columns by one carrying sort? At
    most four columns (a wider batch would compile for minutes:
    sort_carrying), each fixed-width or a dictionary string, which moves as
    ``(validity, dict_codes)`` and comes out codes-only, as a gather leaves
    it. Strings with chars or a slab take the gather. Read from the batch
    alone: ``filter_batch`` and a collapse that has claimed a filter
    (exec/tpu._fused_filter_source) both ask it."""
    return len(columns) <= 4 and all(
        c.dict_values is not None and c.dict_codes is not None
        if c.dtype.is_string else c.dict_values is None
        for c in columns)


def filter_batch(batch: DeviceBatch, keep: jnp.ndarray) -> DeviceBatch:
    """Compact rows where ``keep`` (bool capacity-vector) is True to the
    front, in their order; the output is prefix-compact with a device-side
    ``num_rows``. keep is pre-masked to live rows by the caller or here.
    A ``sort_compactable`` batch rides one stable sort by "dropped" (5 ns a
    slot at 2^26 slots on a v5e, PERF.md PR 28); any other pays
    ``compact_permutation`` and a packed gather a dtype group."""
    keep = keep & batch.row_mask()
    if sort_compactable(batch.columns):
        _, moved = sort_carrying(
            (~keep).astype(jnp.uint8),
            [v for c in batch.columns for v in (
                (c.validity, c.dict_codes) if c.dtype.is_string
                else (c.data, c.validity))])
        new_rows = keep.sum().astype(jnp.int32)
        live = jnp.arange(batch.capacity, dtype=jnp.int32) < new_rows
        cols = []
        for i, c in enumerate(batch.columns):
            a, b = moved[2 * i], moved[2 * i + 1]
            if c.dtype.is_string:
                # codes-only against the same dictionary; dead slots read
                # the NULL code, as gather_columns leaves them
                cols.append(DeviceColumn(
                    c.dtype, None, a & live,
                    dict_codes=jnp.where(
                        live, b, jnp.asarray(c.dict_card, jnp.int32)),
                    dict_values=c.dict_values))
            else:
                cols.append(DeviceColumn(c.dtype, a, b & live))
        return DeviceBatch(batch.schema, cols, new_rows)
    # stable partition via the O(n) prefix-count kernel
    from spark_rapids_tpu.ops.tablekernels import compact_permutation
    perm, new_rows = compact_permutation(keep)
    return gather_batch(batch, perm, new_rows)


def concat_batches(batches: Sequence[DeviceBatch],
                   out_capacity: int,
                   out_char_capacity: int = 0,
                   keep_masks: Optional[Sequence[jnp.ndarray]] = None,
                   dict_merge: bool = True
                   ) -> DeviceBatch:
    """Concatenate batches into one of ``out_capacity`` (device analogue of
    cuDF Table.concatenate under GpuCoalesceBatches).

    TPU shape: part row counts are device scalars (dynamic), so a static
    concatenation is impossible — but the compaction source index is pure
    arithmetic over the per-part bases (P dense passes, no gathers), and
    the payload move is ONE packed gather per dtype group from the
    statically concatenated flat buffers (gather_columns). The previous
    spelling gathered per part per column at out_capacity width and
    measured ~770ms for a 4-part 5-column concat at 4M rows; this one
    runs the same shape in ~1/3 of that.

    ``keep_masks``: optional per-part bool keep vectors, the mask form of
    a Filter a collapse has claimed (exec/tpu._fused_filter_source), for
    parts no carrying sort can compact (strings with chars or a slab, five
    columns or more): kept rows compact to the front in part order via ONE
    O(n) compact_permutation and one gather a dtype group over the whole
    output capacity, about 48 ns a slot on a v5e (PERF.md, PR 36). A part
    the claimed filter already compacted has ``None`` for a mask and keeps
    its live rows. With no masks at all, the form every sort-compactable
    collapse takes, the parts move by block copies (below)."""
    schema = batches[0].schema
    idx = jnp.arange(out_capacity, dtype=jnp.int32)
    if keep_masks is not None:
        from spark_rapids_tpu.ops.tablekernels import compact_permutation
        flat_keep = jnp.concatenate(
            [b.row_mask() if k is None else k & b.row_mask()
             for k, b in zip(keep_masks, batches)])
        perm, total = compact_permutation(flat_keep)
        total = total.astype(jnp.int32)
        flat_n = perm.shape[0]
        if flat_n >= out_capacity:
            src = perm[:out_capacity]
        else:
            src = jnp.concatenate(
                [perm, jnp.zeros((out_capacity - flat_n,), jnp.int32)])
        live_out = idx < total
    else:
        total = batches[0].num_rows
        for b in batches[1:]:
            total = total + b.num_rows
        total = total.astype(jnp.int32)
        live_out = idx < total

        # source flat index per output slot: part p's rows [0, n_p) land
        # at [base_p, base_p + n_p), reading flat slots [static_off_p+rel)
        src = jnp.zeros((out_capacity,), jnp.int32)
        base = jnp.asarray(0, jnp.int32)
        static_off = 0
        for b in batches:
            rel = idx - base
            in_p = (idx >= base) & (rel < b.num_rows)
            src = jnp.where(in_p, jnp.int32(static_off) + rel, src)
            base = base + b.num_rows
            static_off += b.capacity

    # fast path (no keep masks): fixed-width and codes-only columns move
    # with CONTIGUOUS dynamic_update_slice block copies instead of a
    # row gather — batch i's full padded buffer lands at its dynamic
    # base and batch i+1's copy overwrites i's padding (bases advance by
    # LIVE counts): contiguous copies run at memory speed where XLA's
    # 1-D gather lowering does not. Plain string columns (dynamic char
    # extents) stay on the gather path below.
    def _block_copy(arrs, fill=None):
        dt0 = arrs[0].dtype
        out = jnp.zeros((out_capacity,), dt0) if fill is None else \
            jnp.full((out_capacity,), fill, dt0)
        base = jnp.asarray(0, jnp.int32)
        for arr, b in zip(arrs, batches):
            out = jax.lax.dynamic_update_slice(out, arr, (base,))
            base = base + b.num_rows.astype(jnp.int32)
        return out

    def _block_copy2d(arrs):
        # slab rows: same contiguous block-copy trick, one word-matrix
        # per part landing at its dynamic row base
        w = int(arrs[0].shape[1])
        out = jnp.zeros((out_capacity, w), arrs[0].dtype)
        base = jnp.asarray(0, jnp.int32)
        for arr, b in zip(arrs, batches):
            out = jax.lax.dynamic_update_slice(
                out, arr, (base, jnp.asarray(0, jnp.int32)))
            base = base + b.num_rows.astype(jnp.int32)
        return out

    blockable = keep_masks is None and all(
        b.capacity <= out_capacity for b in batches)

    # flat columns: static dense concatenation of every part buffer;
    # string offsets get static per-part char bases (the flat array is
    # NOT a valid offsets vector at part boundaries, but gather_columns
    # only reads per-row starts and lens, and dead rows' lens are masked
    # by ``live``)
    flat_cols: List[DeviceColumn] = []
    char_caps: List[int] = []
    block_out: dict = {}
    for ci, dt in enumerate(schema.dtypes):
        parts = [b.columns[ci] for b in batches]
        shared, eff_codes = _concat_dict_info(parts, dict_merge)
        slab_parts = (_concat_slabs(parts)
                      if dt.is_string and shared is None else None)
        if blockable and dt.is_string and slab_parts is not None:
            # blocked chars: slab rows block-copy exactly like fixed-
            # width payloads — 2-D contiguous copies, no char gather
            validity = _block_copy([p.validity for p in parts]) & live_out
            lens_b = jnp.where(live_out,
                               _block_copy([p.lens_() for p in parts]),
                               0).astype(jnp.int32)
            slab_b = jnp.where(live_out[:, None],
                               _block_copy2d(slab_parts), jnp.uint64(0))
            block_out[ci] = DeviceColumn(dt, None, validity,
                                         slab64=slab_b, lens=lens_b)
            continue
        if blockable and (not dt.is_string or shared is not None):
            validity = _block_copy([p.validity for p in parts]) & live_out
            if dt.is_string:
                card = len(shared)
                codes_b = jnp.where(live_out, _block_copy(
                    eff_codes, fill=jnp.int32(card)), jnp.int32(card))
                block_out[ci] = DeviceColumn(
                    dt, None, validity, dict_codes=codes_b,
                    dict_values=shared)
            else:
                codes_b = None
                if shared is not None:
                    card = len(shared)
                    codes_b = jnp.where(live_out, _block_copy(
                        eff_codes, fill=jnp.int32(card)), jnp.int32(card))
                block_out[ci] = DeviceColumn(
                    dt, _block_copy([p.data for p in parts]), validity,
                    dict_codes=codes_b, dict_values=shared)
            continue
        codes = (jnp.concatenate(eff_codes)
                 if shared is not None else None)
        if dt.is_string and shared is not None:
            # dictionary strings concat as codes only — no char extents,
            # no char slab reads (and lazy inputs stay unmaterialized);
            # differing dictionaries merged by union+remap above
            flat_cols.append(DeviceColumn(
                dt, None, jnp.concatenate([p.validity for p in parts]),
                dict_codes=codes, dict_values=shared))
            char_caps.append(0)
            continue
        if dt.is_string and slab_parts is not None:
            # slab flat view: rows are self-contained (no cross-part
            # offset bases), so the compaction gather moves slab rows
            # directly — including under keep_masks
            flat_cols.append(DeviceColumn(
                dt, None, jnp.concatenate([p.validity for p in parts]),
                slab64=jnp.concatenate(slab_parts),
                lens=jnp.concatenate([p.lens_() for p in parts])))
            char_caps.append(0)
            continue
        if dt.is_string:
            char_base = 0
            starts_parts = []
            for p in parts:
                starts_parts.append(p.offsets[:-1].astype(jnp.int32)
                                    + jnp.int32(char_base))
                char_base += p.data.shape[0]
            # trailing entry only closes the last row's length; boundary
            # rows are dead and masked in the gather
            lens_flat = jnp.concatenate(
                [(p.offsets[1:] - p.offsets[:-1]).astype(jnp.int32)
                 for p in parts])
            starts_flat = jnp.concatenate(starts_parts)
            offsets_flat = jnp.concatenate(
                [starts_flat, jnp.asarray([char_base], jnp.int32)])
            # rebuild a consistent offsets vector from starts+lens is
            # unnecessary: gather_columns derives lens as adjacent
            # differences, which would be wrong at part boundaries — so
            # hand it explicit extents via a shim column whose offsets
            # encode starts and whose boundary rows are masked dead
            chars_flat = jnp.concatenate([p.data for p in parts])
            has_prefix = all(p.prefix8 is not None for p in parts)
            prefix8 = (jnp.concatenate([p.prefix8 for p in parts])
                       if has_prefix else None)
            flat_cols.append(_ExtentColumn(
                dt, chars_flat, jnp.concatenate(
                    [p.validity for p in parts]),
                offsets_flat, prefix8, codes, shared,
                starts=starts_flat, lens=lens_flat))
            char_caps.append(out_char_capacity if out_char_capacity > 0
                             else char_base)
        else:
            flat_cols.append(DeviceColumn(
                dt, jnp.concatenate([p.data for p in parts]),
                jnp.concatenate([p.validity for p in parts]),
                dict_codes=codes, dict_values=shared))
    gathered = (gather_columns(flat_cols, src, live_out, tuple(char_caps))
                if flat_cols else [])
    cols: List[DeviceColumn] = []
    gi = 0
    for ci in range(len(schema.dtypes)):
        if ci in block_out:
            cols.append(block_out[ci])
        else:
            cols.append(gathered[gi])
            gi += 1
    return DeviceBatch(schema, cols, total)


class _ExtentColumn(DeviceColumn):
    """String column whose per-row (start, len) extents are explicit —
    concat's flat view has inter-part gaps no offsets vector can encode.
    Only consumed by gather_columns."""

    def __init__(self, dtype, data, validity, offsets, prefix8, dict_codes,
                 dict_values, starts, lens):
        super().__init__(dtype, data, validity, offsets, prefix8,
                         dict_codes, dict_values)
        self.ext_starts = starts
        self.ext_lens = lens


def slice_batch(batch: DeviceBatch, start: jnp.ndarray,
                count: jnp.ndarray) -> DeviceBatch:
    """Rows [start, start+count) compacted to the front (zero-copy-ish slice,
    the analogue of SlicedGpuColumnVector)."""
    return slice_batch_to(batch, start, count, batch.capacity)


def slice_batch_to(batch: DeviceBatch, start: jnp.ndarray,
                   count: jnp.ndarray, out_capacity: int,
                   char_caps=()) -> DeviceBatch:
    """slice_batch gathering into an ``out_capacity``-row batch. Callers
    that learn row counts on the host (the exchange's bucket split) use
    this to SHRINK capacity, so downstream kernels stop paying for the
    pre-aggregation padding (a 4-group result inheriting a 32k-row input
    bucket would otherwise keep every later sort/agg at 32k).
    ``char_caps``: optional per-STRING-column output char capacities —
    shrinking the char slab too stops downstream string kernels (poly
    hashes, char gathers, the result fetch) from paying the
    pre-aggregation CHAR padding, which dwarfs the row padding for
    string-keyed aggregates."""
    idx = jnp.arange(out_capacity, dtype=jnp.int32)
    perm = jnp.clip(idx + start.astype(jnp.int32), 0, batch.capacity - 1)
    n = jnp.minimum(count.astype(jnp.int32),
                    jnp.maximum(batch.num_rows - start.astype(jnp.int32), 0))
    live = idx < n
    cols = gather_columns(batch.columns, perm, live, char_caps)
    return DeviceBatch(batch.schema, cols, n.astype(jnp.int32))
