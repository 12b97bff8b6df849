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


def _dense_offsets(build: DeviceBatch, build_key: int, bkv, lo, table_size):
    """(each build row's table offset, ``key - lo``, or ``table_size`` for
    a NULL key or one outside the table; whether every valid key is in
    the table). The dense probe's ``bperm`` is the stable order of these
    offsets."""
    boff = build.columns[build_key].data.astype(jnp.int64) - lo
    in_tbl = (boff >= 0) & (boff < table_size)
    ok = jnp.all(in_tbl | ~bkv)
    off_key = jnp.where(in_tbl & bkv, boff,
                        table_size).astype(jnp.int32)
    return off_key, ok


def _packed_table(off_sorted, table_size: int):
    """The (start, count) of every table offset as one (table_size, 2)
    s32 table: 16 bytes a slot while it is built, and a stream row reads
    its pair with one row gather."""
    cnt = jnp.zeros((table_size + 1,), jnp.int32).at[off_sorted].add(1)[
        :table_size]
    starts = jnp.cumsum(cnt) - cnt
    tbl = jnp.stack([starts, cnt], axis=1)

    def lookup(sidx):
        picked = tbl[sidx, :]
        return picked[:, 0].astype(jnp.int32), picked[:, 1]
    return lookup


def _compact_table(off_sorted, table_size: int):
    """The (start, count) of every table offset from one exclusive prefix
    sum ``cum`` of table_size + 1 s32 entries: ``cum[o]`` is the first
    ``bperm`` slot of offset ``o`` and ``cum[o + 1] - cum[o]`` its count,
    so 4 bytes a slot, and 8 while the sum is taken. A stream row reads
    entries ``o`` and ``o + 1`` with one gather of a 2-entry window, which
    costs what two gathers do on a v5e (PERF.md, section 6)."""
    # each offset counted one entry up, so the sum is exclusive; a NULL
    # or out-of-table row (offset table_size) lands past the end, dropped
    cum = jnp.cumsum(jnp.zeros((table_size + 1,), jnp.int32).at[
        off_sorted + 1].add(1, mode="drop"))
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))

    def lookup(sidx):
        # sidx < table_size, so the window [sidx, sidx + 1] is in bounds
        pair = jax.lax.gather(
            cum, sidx[:, None], dnums, slice_sizes=(2,),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return pair[:, 0], pair[:, 1] - pair[:, 0]
    return lookup


def join_probe_dense(build: DeviceBatch, stream: DeviceBatch,
                     build_key: int, stream_key: int, lo_arr: jnp.ndarray,
                     table_size: int, compact: bool = False):
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
    SQL semantics, not data loss. ``compact``: the table is one prefix
    sum, 8 bytes a slot while built (_compact_table), instead of the
    packed [start, count] rows, 16 (_packed_table): a table twice as
    large in the same HBM, at a slower read."""
    nb, ns = build.capacity, stream.capacity
    bkv = _key_valid(build, [build_key])
    skv = _key_valid(stream, [stream_key])
    lo = lo_arr.astype(jnp.int64)
    off_key, ok = _dense_offsets(build, build_key, bkv, lo, table_size)
    off_sorted, bperm = jax.lax.sort(
        (off_key, jnp.arange(nb, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    # scatter-add over SORTED offsets (random-index scatters serialize on
    # TPU; the build-side sort just above makes this one cheap)
    lookup = (_compact_table if compact else _packed_table)(off_sorted,
                                                            table_size)
    soff = stream.columns[stream_key].data.astype(jnp.int64) - lo
    s_in = skv & (soff >= 0) & (soff < table_size)
    sidx = jnp.clip(soff, 0, table_size - 1).astype(jnp.int32)
    bstart, cnt = lookup(sidx)
    counts = jnp.where(s_in, cnt, 0).astype(jnp.int32)
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


# ---------------------------------------------------------------------------
# joins with a residual condition (Spark's hash join with a condition:
# EXISTS / NOT EXISTS with a non-equality, TPC-H Q21)
#
# After the probe, stream row i's key-equal pairs are (i, bperm[bstart_i +
# j]) for j < counts_i. The residual reads few columns, so those are packed
# into 32-bit words (an integer column whose values span less than 2^32 in
# one word, NULL a sentinel) and the build's words are put in key order
# once. The pairs are evaluated pair-major in pieces of at most
# COND_PIECE_PAIRS (join_expand's layout: slot -> stream row by a histogram
# of the row ends and a prefix sum): a semi or anti join adds each pass to
# its stream row's count, an inner join emits the pairs that pass.
#
# A semi or anti join whose residual is one comparison of a build column
# with a stream column (cond_extent) enumerates no pair: some key-equal
# pair passes ``b <op> s`` exactly when the key's valid build values V are
# not empty and min V < s (<, <=), max V > s (>, >=), or V holds a value
# other than s (<>), so a stream row is decided by one gather of its key's
# extremes.
# ---------------------------------------------------------------------------

# pairs one piece evaluates: the pieces bound the HBM a conditioned join
# holds, whatever its pair count
COND_PIECE_PAIRS = 1 << 24
_NULL_WORD = 0xFFFFFFFF
# the extent form's word for a key with two distinct valid values
_MARK_WORD = 0xFFFFFFFE


def _copies_chars(c: DeviceColumn) -> bool:
    return c.dtype.is_string and c.dict_values is None and not c.has_slab


def cond_packable(dt) -> bool:
    """A fixed-width integer-kind value (ints, dates, timestamps, bools)
    rides the residual's words; floats and strings move as columns."""
    if dt.is_string:
        return False
    npdt = jnp.dtype(dt.np_dtype)
    return npdt == jnp.bool_ or (jnp.issubdtype(npdt, jnp.signedinteger)
                                 and npdt.itemsize <= 8)


def _pack(cols, narrow, lows, n: int) -> jnp.ndarray:
    """(n, W) uint32 words of packable columns: a narrowed column is one
    word, value - low or the NULL sentinel; any other is its bits (two
    words at 64 bits) and a shared word of validity bits."""
    words, vbits = [], None
    for i, c in enumerate(cols):
        if narrow[i]:
            words.append(jnp.where(
                c.validity,
                (c.data.astype(jnp.int64) - lows[i]).astype(jnp.uint32),
                jnp.uint32(_NULL_WORD)))
            continue
        d = c.data.astype(jnp.int64 if c.data.dtype.itemsize == 8
                          else jnp.int32)
        w = jax.lax.bitcast_convert_type(d, jnp.uint32)
        words.extend([w[:, 0], w[:, 1]] if w.ndim == 2 else [w])
        bit = c.validity.astype(jnp.uint32) << jnp.uint32(i)
        vbits = bit if vbits is None else vbits | bit
    if vbits is not None:
        words.append(vbits)
    if not words:
        return jnp.zeros((n, 0), jnp.uint32)
    return jnp.stack(words, axis=1)


def _unpack(words, dts, narrow, lows, live) -> List[DeviceColumn]:
    out, k = [], 0
    vword = words[:, -1] if not all(narrow) else None
    for i, dt in enumerate(dts):
        npdt = jnp.dtype(dt.np_dtype)
        if narrow[i]:
            w = words[:, k]
            k += 1
            valid = w != jnp.uint32(_NULL_WORD)
            data = (w.astype(jnp.int64) + lows[i]).astype(npdt)
        else:
            if npdt.itemsize == 8:
                data = jax.lax.bitcast_convert_type(
                    words[:, k:k + 2], jnp.int64).astype(npdt)
                k += 2
            else:
                data = jax.lax.bitcast_convert_type(
                    words[:, k], jnp.int32).astype(npdt)
                k += 1
            valid = ((vword >> jnp.uint32(i)) & 1) == 1
        out.append(DeviceColumn(dt, data, valid & live))
    return out


def cond_side(dts):
    """(packable positions, other positions) of one side's residual
    columns: the others (strings, floats) move as columns."""
    pk = tuple(i for i, d in enumerate(dts) if cond_packable(d))
    return pk, tuple(i for i in range(len(dts)) if i not in pk)


def _assemble(n, pk, packed, other, moved):
    cols = [None] * n
    for i, c in zip(pk, packed):
        cols[i] = c
    for i, c in zip(other, moved):
        cols[i] = c
    return cols


def _passes(cond, names, dts, cols, live, n) -> jnp.ndarray:
    """The residual over pairs laid out as rows: TRUE where it holds (a
    NULL condition is not a pass, as in Spark)."""
    from spark_rapids_tpu.sql.exprs.evalbridge import (
        make_context, to_device_column,
    )
    pairs = DeviceBatch(Schema(list(names), list(dts)), cols, n)
    ctx = make_context(pairs)
    pred = to_device_column(ctx, cond.eval_device(ctx))
    return pred.data.astype(jnp.bool_) & pred.validity & live


def cond_layout(build: DeviceBatch, stream: DeviceBatch, counts, bstart,
                bperm, build_cols: Sequence[int],
                stream_cols: Sequence[int]):
    """What the host needs to plan one stream batch, in one int64 vector:
    [key-equal pairs, then per packable column the residual reads (stream
    side first) its least and largest valid value, then per string column
    its longest value and the chars of every pair]; the build's other (not
    packable) columns the residual reads, in key order; the counts of the
    live rows; and zero passing pairs a stream row."""
    from spark_rapids_tpu.ops.rowops import gather_columns
    live = stream.row_mask()
    c = jnp.where(live, counts, 0)
    sizes = [c.astype(jnp.int64).sum()]
    big = jnp.iinfo(jnp.int64).max
    sides = [[stream.columns[i] for i in stream_cols],
             [build.columns[i] for i in build_cols]]
    for cols in sides:
        for col in cols:
            if cond_packable(col.dtype):
                v = col.data.astype(jnp.int64)
                sizes += [jnp.where(col.validity, v, big).min(),
                          jnp.where(col.validity, v, -big).max()]
    nb = build.capacity
    b_other = gather_columns([x for x in sides[1]
                              if not cond_packable(x.dtype)],
                             bperm, jnp.ones((nb,), jnp.bool_))
    real = c.astype(jnp.int64)
    for col in sides[0]:
        if col.dtype.is_string:
            lens = (col.lens_().astype(jnp.int64) if _copies_chars(col)
                    else jnp.zeros((stream.capacity,), jnp.int64))
            sizes += [lens.max(), (real * lens).sum()]
    for col in b_other:
        if not col.dtype.is_string:
            continue
        lens = (col.lens_().astype(jnp.int64) if _copies_chars(col)
                else jnp.zeros((nb,), jnp.int64))
        cl = jnp.concatenate([jnp.zeros((1,), jnp.int64), jnp.cumsum(lens)])
        hi = jnp.clip(bstart + counts, 0, nb)
        lo = jnp.clip(bstart, 0, nb)
        sizes += [lens.max(), jnp.where(live, cl[hi] - cl[lo], 0).sum()]
    return (jnp.stack([jnp.asarray(x, jnp.int64) for x in sizes]), b_other,
            c, jnp.zeros((stream.capacity,), jnp.int32))


def cond_prep(build: DeviceBatch, stream: DeviceBatch, bperm, counts,
              bstart, lows, s_idx, b_idx, narrow):
    """Pack the residual's columns into words (the build's in key order)
    and lay out every key-equal pair for the pieces. Returns (stream rows,
    build words, incl): the pairs of stream row i take slots [incl_i -
    counts_i, incl_i), and the pair at slot p is bperm slot ``p + off_i``,
    where ``off_i`` is word 0 of the stream row and its packed columns
    follow. ``counts``: cond_layout's, zero past the live rows."""
    ns, nb = stream.capacity, build.capacity
    s_cols = [stream.columns[i] for i in s_idx]
    b_cols = [build.columns[i] for i in b_idx]
    s_pk, _ = cond_side([c.dtype for c in s_cols])
    b_pk, _ = cond_side([c.dtype for c in b_cols])
    ns_pk = len(s_pk)
    s_words = _pack([s_cols[i] for i in s_pk], narrow[:ns_pk],
                    lows[:ns_pk], ns)
    b_words = _pack([b_cols[i] for i in b_pk], narrow[ns_pk:],
                    lows[ns_pk:], nb)[bperm]
    incl = jnp.cumsum(counts).astype(jnp.int32)
    off = (bstart - (incl - counts)).astype(jnp.int32)
    s_rows = jnp.concatenate(
        [jax.lax.bitcast_convert_type(off, jnp.uint32)[:, None], s_words],
        axis=1)
    return s_rows, b_words, incl


def _slot_rows(incl, base, pair_cap: int):
    """Each slot's stream row in the piece [base, base + pair_cap): the
    row of slot ``base`` plus the rows that end at or before the slot, by
    a histogram of the row ends inside the piece and a prefix sum. The
    ends are read from a window of ``pair_cap`` rows, which holds every
    row that ends inside the piece unless rows without pairs crowd it;
    then from every row of the batch."""
    ns = incl.shape[0]
    w = min(pair_cap, ns)
    r0 = jnp.searchsorted(incl, base, side="right").astype(jnp.int32)
    r1 = jnp.searchsorted(incl, base + pair_cap,
                          side="left").astype(jnp.int32)
    at = jnp.clip(r0, 0, ns - w)

    def ends(rows):
        rel = rows - base
        return jnp.zeros((pair_cap,), jnp.int32).at[
            jnp.where((rel > 0) & (rel < pair_cap), rel, pair_cap)].add(
                1, mode="drop")
    hist = jax.lax.cond(
        r1 <= at + w,
        lambda: ends(jax.lax.dynamic_slice(incl, (at,), (w,))),
        lambda: ends(incl))
    return jnp.clip(r0 + jnp.cumsum(hist), 0, ns - 1)


def _piece(s_cols, s_rows, b_words, b_other, incl, base, total, lows,
           cond, names, dts, n_s, narrow, pair_cap, caps):
    """The pairs at slots [base, base + pair_cap): each slot's stream
    row, bperm slot, and pass."""
    from spark_rapids_tpu.ops.rowops import gather_columns
    nb = b_words.shape[0]
    srow = _slot_rows(incl, base, pair_cap)
    p = base + jnp.arange(pair_cap, dtype=jnp.int32)
    real = p < total
    s_pk, s_oth = cond_side(dts[:n_s])
    b_pk, b_oth = cond_side(dts[n_s:])
    ns_pk = len(s_pk)
    g = s_rows[srow]
    slot = p + jax.lax.bitcast_convert_type(g[:, 0], jnp.int32)
    bslot = jnp.clip(slot, 0, nb - 1)
    n_sc = sum(1 for i in s_oth if dts[i].is_string)
    s_side = _assemble(
        n_s, s_pk,
        _unpack(g[:, 1:], [dts[i] for i in s_pk], narrow[:ns_pk],
                lows[:ns_pk], real),
        s_oth, gather_columns([s_cols[i] for i in s_oth], srow, real,
                              caps[:n_sc]))
    b_side = _assemble(
        len(dts) - n_s, b_pk,
        _unpack(b_words[bslot], [dts[n_s + i] for i in b_pk],
                narrow[ns_pk:], lows[ns_pk:], real),
        b_oth, gather_columns(b_other, bslot, real, caps[n_sc:]))
    ok = _passes(cond, names, dts, s_side + b_side, real, pair_cap)
    return srow, bslot, ok, real


def cond_piece_counts(stream: DeviceBatch, s_rows, b_words, b_other, incl,
                      acc, base, total, lows, cond, names, dts, s_idx,
                      narrow, pair_cap, caps):
    """One piece of a semi or anti join: adds each slot that passed to its
    stream row's count (a row's slots may run over a piece's end; each
    piece adds its own share)."""
    s_cols = [stream.columns[i] for i in s_idx]
    srow, _slot, ok, real = _piece(
        s_cols, s_rows, b_words, b_other, incl, base, total, lows,
        cond, names, dts, len(s_idx), narrow, pair_cap, caps)
    ns = incl.shape[0]
    return acc.at[jnp.where(real, srow, ns)].add(ok.astype(jnp.int32),
                                                 mode="drop")


def cond_piece_pairs(build: DeviceBatch, stream: DeviceBatch, bperm,
                     s_rows, b_words, b_other, incl, base, total, lows,
                     cond, names, dts, s_idx, narrow, pair_cap, caps,
                     s_caps, b_caps) -> DeviceBatch:
    """One piece of an inner join: the pairs of the piece that passed, as
    joined rows compacted to the front of ``pair_cap`` slots.
    ``s_caps``/``b_caps`` size the output's string chars (expand_totals'
    whole-batch totals, an upper bound for any piece)."""
    from spark_rapids_tpu.ops.rowops import gather_columns
    s_cols = [stream.columns[i] for i in s_idx]
    srow, bslot, ok, _real = _piece(
        s_cols, s_rows, b_words, b_other, incl, base, total, lows,
        cond, names, dts, len(s_idx), narrow, pair_cap, caps)
    cols = (gather_columns(stream.columns, srow, ok, s_caps)
            + gather_columns(build.columns, bperm[bslot], ok, b_caps))
    out = DeviceBatch(
        Schema(list(stream.schema.names) + list(build.schema.names),
               list(stream.schema.dtypes) + list(build.schema.dtypes)),
        cols, pair_cap)
    return filter_batch(out, ok)


def cond_extent(build: DeviceBatch, stream: DeviceBatch, counts, bstart,
                bperm, low, lo_arr, b_col: int, s_col: int, op: str,
                narrow: bool, build_key: int, table_size: int):
    """The pass (0 or 1) of every stream row of a semi or anti join whose
    residual is ``build[b_col] <op> stream[s_col]`` (``op``: ne, lt, le,
    gt or ge), from the valid build values of its key alone.

    The build's rows of a key hold the slots [bstart, bstart + counts) of
    the probe's order. Sorting the build by (run, value) keeps every run
    in its slots and puts its least valid value first (its largest, for
    > and >=; NULLs last), so one gather at ``bstart`` reads the extreme,
    and for <> whether the run holds two distinct valid values. The run
    of a row is the dense probe's table offset where ``table_size`` is
    given (``lo_arr`` its low key), else the slot order is taken from
    ``bperm`` and the runs from the stream rows' ranges. ``narrow``: the
    build column's valid values span less than 2^32 - 2 and ride as
    ``value - low`` in one word. ``counts``: cond_layout's, zero past the
    live rows."""
    nb = build.capacity
    col = build.columns[b_col]
    v = col.data.astype(jnp.int64)
    desc = op in ("gt", "ge")
    if narrow:
        w = (v - low).astype(jnp.uint32)
        keys = [jnp.where(col.validity,
                          jnp.uint32(_NULL_WORD - 1) - w if desc else w,
                          jnp.uint32(_NULL_WORD))]
    else:
        keys = [(~col.validity).astype(jnp.uint8), ~v if desc else v]
    hit = counts > 0
    if table_size:
        run, _ok = _dense_offsets(build, build_key,
                                  _key_valid(build, [build_key]),
                                  lo_arr.astype(jnp.int64), table_size)
    else:
        # a run's first slot and the slot after its last start a segment;
        # slots of keys no stream row probes fall in segments of their own
        edges = jnp.concatenate([jnp.where(hit, bstart, nb),
                                 jnp.where(hit, bstart + counts, nb)])
        run = jnp.cumsum(jnp.zeros((nb + 1,), jnp.int32).at[edges].set(1)[
            :nb])
        keys = [k[bperm] for k in keys]
    srt = jax.lax.sort((run, *keys), num_keys=1 + len(keys))
    run, key = srt[0], srt[-1]
    valid = key != jnp.uint32(_NULL_WORD) if narrow else srt[1] == 0
    multi = jnp.zeros((nb,), jnp.bool_)
    if op == "ne":
        # two distinct valid values in a run: some adjacent valid pair of
        # it differs (values ascend), read at the run's first slot as the
        # least run at or after it that holds such a pair
        differs = ((run[1:] == run[:-1]) & valid[1:] & (key[1:] != key[:-1]))
        at = jnp.concatenate([jnp.where(differs, run[:-1],
                                        jnp.iinfo(jnp.int32).max),
                              jnp.full((1,), jnp.iinfo(jnp.int32).max,
                                       jnp.int32)])
        multi = jax.lax.cummin(at, reverse=True) == run
    b = jnp.clip(bstart, 0, nb - 1)
    if narrow:
        # one word a slot: the extreme, NULL (no valid value) or the mark
        # (two distinct values), which no valid word reaches (_words_plan)
        got = jnp.where(multi, jnp.uint32(_MARK_WORD), key)[b]
        has = got != jnp.uint32(_NULL_WORD)
        two = got == jnp.uint32(_MARK_WORD)
        w = jnp.uint32(_NULL_WORD - 1) - got if desc else got
        ext = w.astype(jnp.int64) + low
    else:
        flags = valid.astype(jnp.uint32) | (multi.astype(jnp.uint32) << 1)
        got = jnp.concatenate([jax.lax.bitcast_convert_type(key, jnp.uint32),
                               flags[:, None]], axis=1)[b]
        has, two = (got[:, 2] & 1) == 1, (got[:, 2] >> 1) == 1
        k = jax.lax.bitcast_convert_type(got[:, :2], jnp.int64)
        ext = ~k if desc else k
    s = stream.columns[s_col]
    sv = s.data.astype(jnp.int64)
    hold = {"ne": (ext != sv) | two, "lt": ext < sv, "le": ext <= sv,
            "gt": ext > sv, "ge": ext >= sv}[op]
    return (hit & s.validity & has & hold).astype(jnp.int32)
