"""Device equi-join kernels (reference: cuDF inner/left/.. joins called from
shims/spark300/.../GpuHashJoin.scala:113-244).

TPU-first design: cuDF probes a device hash table (data-dependent memory,
which XLA cannot express). Instead the join runs as sort + sorted search,
everything shape-static:

  1. build the EXACT order-preserving u64 key images of both sides' key
     columns (the same images the sort kernels use, ops/sortops.py) —
     fixed-width types get one image carrying the full value, strings get
     64-byte prefix chunks + length + the two independent 64-bit poly
     hashes as tiebreaks;
  2. one fused ``lax.sort`` over the *union* of both sides' image vectors
     assigns every row a joint dense key id (int32). Equality is exact for
     every fixed-width type (the image IS the value) and for strings up to
     64 bytes; longer strings additionally need prefix+length+both-hash
     agreement (cuDF compares full keys, GpuHashJoin.scala:217-233 — the
     residual gap is documented incompat territory, far beyond the
     reference's own float-order caveats);
  3. sort the build side by key id; probe = two ``searchsorted`` calls per
     stream row giving the match range [bstart, bend);
  4. count-then-expand: match counts are summed on device, one host sync
     picks a bucketed output capacity, and a second jitted kernel
     materializes the (stream_row, build_row) pairs by inverse-searchsorted
     over the count prefix sum.

Null keys never match (SQL semantics): rows with any invalid key column are
parked outside the id space; float keys follow Spark's join-key equality
(-0.0 == 0.0, NaN == NaN) via the image normalization. Output capacity is
the only data-dependent quantity and costs exactly one device->host sync
per stream batch.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.rowops import filter_batch, gather_column


def _key_valid(batch: DeviceBatch, key_idx: Sequence[int]) -> jnp.ndarray:
    v = batch.row_mask()
    for ki in key_idx:
        v = v & batch.columns[ki].validity
    return v


def _union_string_extents(bcol: DeviceColumn, scol: DeviceColumn):
    """(chars, starts, lens) of the build-then-stream row union (row order
    matching the probe's image concatenation) for exact full-length key
    verification. Explicit extents rather than an offsets array: the
    stream chars land after the build side's PHYSICAL (padded) buffer, so
    the union has a gap no offsets layout could express."""
    b_chars = jnp.int32(bcol.data.shape[0])
    chars = jnp.concatenate([bcol.data, scol.data])
    starts = jnp.concatenate([
        bcol.offsets[:-1].astype(jnp.int32),
        scol.offsets[:-1].astype(jnp.int32) + b_chars])
    lens = jnp.concatenate([
        (bcol.offsets[1:] - bcol.offsets[:-1]).astype(jnp.int32),
        (scol.offsets[1:] - scol.offsets[:-1]).astype(jnp.int32)])
    return chars, starts, lens


def join_probe(build: DeviceBatch, stream: DeviceBatch,
               build_keys: Sequence[int], stream_keys: Sequence[int],
               cross: bool = False, exact_long_strings: bool = True):
    """Phase 1. Returns device arrays
    (counts[ns], bstart[ns], bperm[nb], total_inner) where counts[i] is the
    number of build matches of stream row i and bperm maps sorted build
    slots back to build rows."""
    nb, ns = build.capacity, stream.capacity
    if cross:
        n_live = build.num_rows
        counts = jnp.where(stream.row_mask(), n_live, 0).astype(jnp.int32)
        bstart = jnp.zeros((ns,), jnp.int32)
        dead = (~build.row_mask()).astype(jnp.uint8)
        _, bperm = jax.lax.sort(
            (dead, jnp.arange(nb, dtype=jnp.int32)), num_keys=1,
            is_stable=True)
        return counts, bstart, bperm

    # per-key image assembly. String keys where BOTH sides are
    # dict-encoded never touch chars:
    #   - identical dictionaries: the code IS the exact equality image;
    #   - different dictionaries (e.g. the two tables of a join were
    #     scanned separately): the dictionaries are STATIC host tuples,
    #     so a union id map is built at trace time and baked in as
    #     constants — one tiny-table gather per side yields an exact
    #     full-value equality image. This replaces the 11-operand
    #     prefix-chunk+hash image (64 char gathers + 2 poly-hash scans
    #     per side) that dominated string-keyed join profiles.
    import numpy as np
    from spark_rapids_tpu.ops.hashing import string_poly_hashes_col
    from spark_rapids_tpu.ops.sortops import u64_key_image
    b_imgs: List[jnp.ndarray] = []
    s_imgs: List[jnp.ndarray] = []
    plain_str_pairs = []  # string keys that DID take the char-image path
    for bk, sk in zip(build_keys, stream_keys):
        bc, sc = build.columns[bk], stream.columns[sk]
        if (bc.dtype.is_string and bc.dict_values is not None
                and sc.dict_values is not None):
            if bc.dict_values == sc.dict_values:
                b_imgs.append(bc.dict_codes.astype(jnp.uint64))
                s_imgs.append(sc.dict_codes.astype(jnp.uint64))
            else:
                union: dict = {}
                for v in bc.dict_values:
                    union.setdefault(v, len(union))
                for v in sc.dict_values:
                    union.setdefault(v, len(union))
                null_id = len(union)  # codes==card mark NULL/padding
                bmap = jnp.asarray(np.asarray(
                    [union[v] for v in bc.dict_values] + [null_id],
                    np.uint64))
                smap = jnp.asarray(np.asarray(
                    [union[v] for v in sc.dict_values] + [null_id],
                    np.uint64))
                b_imgs.append(bmap[jnp.clip(bc.dict_codes, 0,
                                            len(bc.dict_values))])
                s_imgs.append(smap[jnp.clip(sc.dict_codes, 0,
                                            len(sc.dict_values))])
            continue
        b_imgs.extend(u64_key_image(bc))
        s_imgs.extend(u64_key_image(sc))
        if bc.dtype.is_string:
            # layout-aware hashes (ops/hashing.string_poly_hashes_col):
            # one-side-dict and slab keys stay gather-free — value-table
            # or dense-word hashes, bit-identical to the char scan
            h1, h2 = string_poly_hashes_col(bc)
            b_imgs.extend([h1, h2])
            h1, h2 = string_poly_hashes_col(sc)
            s_imgs.extend([h1, h2])
            plain_str_pairs.append((bc, sc))
    assert len(b_imgs) == len(s_imgs), (len(b_imgs), len(s_imgs))
    bkv = _key_valid(build, build_keys)
    skv = _key_valid(stream, stream_keys)

    # NOTE (measured, do not "optimize" back): a single-sided variant —
    # sort only the build images and u64-searchsorted the stream against
    # them — runs ~3x SLOWER than this union sort on TPU, because u64
    # comparisons are emulated and searchsorted lowers to a per-element
    # binary search. The union sort exists precisely so the searchsorted
    # below runs on dense int32 ids. Wide keys (multi-column / string)
    # take LSD passes inside lexsort_permutation — a direct multi-operand
    # sort gains ~25-150s of COMPILE time per operand at >=512k rows.
    from spark_rapids_tpu.ops.rowops import packed_gather_vectors
    from spark_rapids_tpu.ops.sortops import lexsort_permutation
    imgs = [jnp.concatenate([bi, si]) for bi, si in zip(b_imgs, s_imgs)]
    invalid = (~jnp.concatenate([bkv, skv])).astype(jnp.uint8)
    perm = lexsort_permutation([invalid] + imgs)
    sorted_vecs = packed_gather_vectors([invalid] + imgs, perm)
    inv_s, imgs_s = sorted_vecs[0], sorted_vecs[1:]
    valid_s = inv_s == 0
    # position 0 is always a group start; later positions start a group
    # when any image differs from the previous row's
    differs = jnp.zeros(inv_s.shape, jnp.bool_).at[0].set(True)
    for img_s in imgs_s:
        differs = differs | jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), img_s[1:] != img_s[:-1]])

    # EXACT equality for >64-byte string keys (default): strings agreeing
    # on prefix+length+both hashes are image-ties. Adjacent-pair compares
    # alone are NOT exact (an interleaved tie like A,B,A would split equal
    # keys into different groups and DROP true matches), so the cond-gated
    # repair re-sorts with extended 320-byte prefix images — content-
    # sorting ties so equal keys become adjacent — then splits residual
    # adjacent ties by full-length compare. This matches cuDF's full-key
    # comparison (GpuHashJoin.scala:217-233) except the documented
    # residual: keys sharing a 320-byte prefix AND length AND both 64-bit
    # poly hashes AND interleaving in the tie run. With
    # exact_long_strings=False the dual-hash tiebreak stands (incompat,
    # spark.rapids.sql.join.exactLongStrings).
    str_pairs = plain_str_pairs
    if exact_long_strings and str_pairs:
        prev_valid = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), valid_s[:-1]])
        tie = (~differs) & valid_s & prev_valid
        long_present = jnp.asarray(False)
        for bcol, scol in str_pairs:
            for col, kv in ((bcol, bkv), (scol, skv)):
                # lens_() never materializes a lazy/slab column; slab
                # strides are bounded so slab keys can only trip the
                # repair when the stride genuinely exceeds 64 bytes
                lens = col.lens_()
                long_present = long_present | jnp.any(
                    jnp.where(kv, lens, 0) > 64)
        need = long_present & jnp.any(tie)

        def repair(_):
            from spark_rapids_tpu.ops.strings import compare_extents
            ext_imgs = []
            unions = []
            for bcol, scol in str_pairs:
                chars, starts, lens = _union_string_extents(bcol, scol)
                unions.append((chars, starts, lens))
                nc = chars.shape[0]
                for c in range(8, 40):  # bytes 64..320 as u64 chunks
                    img = jnp.zeros(starts.shape, jnp.uint64)
                    for b in range(8):
                        p = c * 8 + b
                        idxc = jnp.clip(starts + p, 0, nc - 1)
                        byte = jnp.where(p < lens, chars[idxc],
                                         jnp.asarray(0, jnp.uint8))
                        img = (img << jnp.uint64(8)) | byte.astype(jnp.uint64)
                    ext_imgs.append(img)
            ops2 = [invalid] + list(imgs) + list(ext_imgs)
            perm2 = lexsort_permutation(ops2)
            sorted2 = packed_gather_vectors(ops2, perm2)
            inv2, all_s = sorted2[0], sorted2[1:]
            valid2 = inv2 == 0
            d2 = jnp.zeros(inv2.shape, jnp.bool_).at[0].set(True)
            for img_s2 in all_s:
                d2 = d2 | jnp.concatenate(
                    [jnp.zeros((1,), jnp.bool_), img_s2[1:] != img_s2[:-1]])
            # residual ties (identical to 320 bytes): adjacent full-length
            # compare — now content-sorted, equal keys are adjacent
            prev2 = jnp.concatenate([perm2[:1], perm2[:-1]])
            extra = jnp.zeros(d2.shape, jnp.bool_)
            for chars, starts, lens in unions:
                cmp = compare_extents(chars, starts[prev2], lens[prev2],
                                      chars, starts[perm2], lens[perm2])
                extra = extra | (cmp != 0)
            prev_v2 = jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), valid2[:-1]])
            tie2 = (~d2) & valid2 & prev_v2
            return d2 | (tie2 & extra), perm2, valid2

        differs, perm, valid_s = jax.lax.cond(
            need, repair, lambda _: (differs, perm, valid_s), None)
    boundary = differs & valid_s
    pid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    pid = jnp.where(valid_s, pid, -1)
    ids = jnp.zeros((nb + ns,), jnp.int32).at[perm].set(pid)
    bid = ids[:nb]
    sid = ids[nb:]

    big = jnp.asarray(nb + ns + 1, jnp.int32)
    bid_key = jnp.where(bkv, bid, big)
    _bid_s, bperm = jax.lax.sort((bid_key, jnp.arange(nb, dtype=jnp.int32)),
                                 num_keys=1, is_stable=True)
    # per-id (start, count) table over the DENSE id space instead of two
    # searchsorted calls (a binary search per stream row costs ~0.2s per
    # million rows on TPU; the table is one small scatter + cumsum + one
    # packed row gather)
    nid_cap = nb + ns
    cntb = jnp.zeros((nid_cap + 1,), jnp.int32).at[
        jnp.where(bkv, bid, nid_cap)].add(1)[:nid_cap]
    starts = jnp.cumsum(cntb) - cntb  # first bperm slot holding each id
    tbl = jnp.stack([starts, cntb], axis=1)
    sid_c = jnp.clip(jnp.where(skv, sid, 0), 0, nid_cap - 1)
    picked = tbl[sid_c, :]
    bstart = picked[:, 0].astype(jnp.int32)
    counts = jnp.where(skv & (sid >= 0), picked[:, 1], 0).astype(jnp.int32)
    return counts, bstart, bperm


def join_probe_dense(build: DeviceBatch, stream: DeviceBatch,
                     build_key: int, stream_key: int, lo_arr: jnp.ndarray,
                     table_size: int):
    """Dense-key direct-index probe: the sort-free fast path for the
    PK-FK joins that dominate analytic schemas (every TPC-H/TPCxBB equi
    join is on dense contiguous int keys).

    Instead of the union lexsort over nb+ns key images (join_probe), the
    build side scatters a (table_size, 2) [start, count] table indexed by
    ``key - lo`` and every stream row probes with ONE gather. The build
    side still sorts — but only ITSELF, by table offset (one int32
    operand), to give the same (counts, bstart, bperm) contract
    join_expand consumes; the stream side (usually the big fact table) is
    never sorted at all. This replaces cuDF's device hash build+probe
    (GpuHashJoin.scala:113-244) with the shape-static TPU equivalent:
    the "hash table" is the identity map on a bounded key range.

    ``lo_arr``: int64 device scalar, the assumed minimum key.
    ``table_size``: static bucketed range. Returns (counts, bstart,
    bperm, ok) — ``ok`` is False when some VALID build key fell outside
    [lo, lo+table_size): the bounds came from name-keyed scan statistics
    (session.column_stats) which are advisory, so the caller must fall
    back to the exact sort probe when verification fails. Out-of-range
    STREAM keys need no verification: when ok holds, every build key is
    in-table, so an out-of-range stream key matching nothing is correct
    SQL semantics, not data loss."""
    nb, ns = build.capacity, stream.capacity
    bkv = _key_valid(build, [build_key])
    skv = _key_valid(stream, [stream_key])
    lo = lo_arr.astype(jnp.int64)
    boff = build.columns[build_key].data.astype(jnp.int64) - lo
    in_tbl = (boff >= 0) & (boff < table_size)
    ok = jnp.all(in_tbl | ~bkv)
    off_key = jnp.where(in_tbl & bkv, boff,
                        table_size).astype(jnp.int32)
    off_sorted, bperm = jax.lax.sort(
        (off_key, jnp.arange(nb, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    # scatter-add over SORTED offsets (random-index scatters serialize on
    # TPU; the build-side sort just above makes this one cheap)
    cnt = jnp.zeros((table_size + 1,), jnp.int32).at[off_sorted].add(1)[
        :table_size]
    starts = jnp.cumsum(cnt) - cnt
    tbl = jnp.stack([starts, cnt], axis=1)
    soff = stream.columns[stream_key].data.astype(jnp.int64) - lo
    s_in = skv & (soff >= 0) & (soff < table_size)
    sidx = jnp.clip(soff, 0, table_size - 1).astype(jnp.int32)
    picked = tbl[sidx, :]
    bstart = picked[:, 0].astype(jnp.int32)
    counts = jnp.where(s_in, picked[:, 1], 0).astype(jnp.int32)
    return counts, bstart, bperm, ok


def outer_adjusted_counts(stream: DeviceBatch,
                          counts: jnp.ndarray) -> jnp.ndarray:
    """Left-outer: every live stream row emits at least one output row."""
    return jnp.where(stream.row_mask(), jnp.maximum(counts, 1), 0)


def expand_totals(build: DeviceBatch, stream: DeviceBatch,
                  counts: jnp.ndarray, counts_adj: jnp.ndarray,
                  bperm: jnp.ndarray, bstart: jnp.ndarray) -> jnp.ndarray:
    """All host-needed expansion sizes in ONE device array (one sync):
    [total_rows, chars per stream string col..., chars per build string
    col...]. String char totals are exact (each emitted pair copies the
    source strings once); build-side totals ride a prefix sum over the
    sorted build rows. A string column the expand moves without its chars
    (gather_columns: a dictionary column by its codes, a slab column by
    its slab) sizes no char buffer and reads 0: its total would cost two
    gathers over the build and four over the stream for nothing (q16 at
    SF100: 11.1 s of a 36.8 s query for p_brand and p_type, PERF.md)."""
    def str_lens(c):
        return c.lens_().astype(jnp.int64)

    def copies_chars(c):
        return c.dict_values is None and not c.has_slab

    zero = jnp.zeros((), jnp.int64)
    parts = [counts_adj.sum().astype(jnp.int64)]
    for c in stream.columns:
        if c.dtype.is_string:
            parts.append(
                (counts_adj.astype(jnp.int64) * str_lens(c)).sum()
                if copies_chars(c) else zero)
    nb = build.capacity
    for c in build.columns:
        if c.dtype.is_string and not copies_chars(c):
            parts.append(zero)
        elif c.dtype.is_string:
            lens_sorted = str_lens(c)[bperm]
            cl = jnp.concatenate([jnp.zeros((1,), jnp.int64),
                                  jnp.cumsum(lens_sorted)])
            hi = jnp.clip(bstart + counts, 0, nb)
            lo = jnp.clip(bstart, 0, nb)
            parts.append((cl[hi] - cl[lo]).sum())
    return jnp.stack(parts)


def join_expand(build: DeviceBatch, stream: DeviceBatch,
                counts: jnp.ndarray, counts_adj: jnp.ndarray,
                bstart: jnp.ndarray, bperm: jnp.ndarray,
                out_capacity: int, swap_sides: bool,
                stream_char_caps: Tuple[int, ...] = (),
                build_char_caps: Tuple[int, ...] = ()) -> DeviceBatch:
    """Phase 2: materialize pairs into an out_capacity batch.

    counts_adj >= counts drives emission (left-outer rows with no match
    still emit one row with a null build side). ``swap_sides`` puts the
    build side's columns first (right outer join runs with build=left).
    The char-cap tuples (one entry per string column of that side, from
    expand_totals) size expanded string buffers."""
    nb, ns = build.capacity, stream.capacity
    total = counts_adj.sum().astype(jnp.int32)
    incl = jnp.cumsum(counts_adj).astype(jnp.int32)
    excl = incl - counts_adj
    k = jnp.arange(out_capacity, dtype=jnp.int32)
    from spark_rapids_tpu.ops.rowops import rank_of_iota
    srow = jnp.clip(rank_of_iota(incl, out_capacity), 0, ns - 1)
    j = k - excl[srow]
    matched = counts[srow] > 0
    slot = bstart[srow] + jnp.minimum(j, jnp.maximum(counts[srow] - 1, 0))
    brow = bperm[jnp.clip(slot, 0, nb - 1)]
    live = k < total

    def side_cols(batch, perm, live_mask, caps):
        # packed row gathers: every fixed-width payload of the side rides
        # one stacked (n, k) gather (see rowops.gather_columns)
        from spark_rapids_tpu.ops.rowops import gather_columns
        return gather_columns(batch.columns, perm, live_mask, caps)

    stream_cols = side_cols(stream, srow, live, stream_char_caps)
    build_cols = side_cols(build, brow, live & matched, build_char_caps)
    if swap_sides:
        names = list(build.schema.names) + list(stream.schema.names)
        dts = list(build.schema.dtypes) + list(stream.schema.dtypes)
        cols = build_cols + stream_cols
    else:
        names = list(stream.schema.names) + list(build.schema.names)
        dts = list(stream.schema.dtypes) + list(build.schema.dtypes)
        cols = stream_cols + build_cols
    return DeviceBatch(Schema(names, dts), cols, total)


def build_match_flags(build: DeviceBatch, counts: jnp.ndarray,
                      bstart: jnp.ndarray, bperm: jnp.ndarray) -> jnp.ndarray:
    """bool[nb]: build rows matched by any stream row (for full outer).
    Coverage of the sorted-slot ranges via +1/-1 deltas and a prefix sum."""
    nb = build.capacity
    has = counts > 0
    one = jnp.where(has, 1, 0)
    delta = jnp.zeros((nb + 1,), jnp.int32)
    delta = delta.at[jnp.clip(bstart, 0, nb)].add(one)
    delta = delta.at[jnp.clip(bstart + counts, 0, nb)].add(-one)
    covered_slot = jnp.cumsum(delta)[:nb] > 0
    return jnp.zeros((nb,), jnp.bool_).at[bperm].set(covered_slot)


def null_columns(schema: Schema, capacity: int) -> List[DeviceColumn]:
    """All-null columns of the given schema (the missing side of outer-join
    rows)."""
    cols = []
    validity = jnp.zeros((capacity,), jnp.bool_)
    for dt in schema.dtypes:
        if dt.is_string:
            cols.append(DeviceColumn(
                dt, jnp.zeros((16,), jnp.uint8), validity,
                jnp.zeros((capacity + 1,), jnp.int32)))
        else:
            cols.append(DeviceColumn(
                dt, jnp.zeros((capacity,), dt.np_dtype), validity))
    return cols


def unmatched_build_batch(build: DeviceBatch, matched: jnp.ndarray,
                          stream_schema: Schema,
                          swap_sides: bool) -> DeviceBatch:
    """Full-outer tail: build rows no stream row matched, with an all-null
    stream side. Output capacity = build capacity (compacted)."""
    keep = build.row_mask() & ~matched
    compact = filter_batch(build, keep)
    nulls = null_columns(stream_schema, compact.capacity)
    if swap_sides:
        names = list(build.schema.names) + list(stream_schema.names)
        dts_ = list(build.schema.dtypes) + list(stream_schema.dtypes)
        cols = list(compact.columns) + nulls
    else:
        names = list(stream_schema.names) + list(build.schema.names)
        dts_ = list(stream_schema.dtypes) + list(build.schema.dtypes)
        cols = nulls + list(compact.columns)
    return DeviceBatch(Schema(names, dts_), cols, compact.num_rows)


def semi_anti_filter(stream: DeviceBatch, counts: jnp.ndarray,
                     anti: bool) -> DeviceBatch:
    """leftsemi: stream rows with >=1 match; leftanti: live rows with none
    (null-keyed rows count as unmatched — SQL null never equals)."""
    if anti:
        mask = stream.row_mask() & (counts == 0)
    else:
        mask = counts > 0
    return filter_batch(stream, mask)
