"""Table-shaped device kernels shared by the operators: stream compaction,
the open-addressing table of the hash aggregate, and the Parquet
page-decode expanders. Every one is plain jnp that XLA compiles; there is
one implementation of each and nothing selects another.

Compaction: every filter/join output pays a stable partition ("kept rows
first, in order" — the cuDF filter/apply_boolean_mask equivalent the
reference leans on, GpuFilterExec in basicPhysicalOperators.scala). The
permutation needs only the two exclusive running counts

    kept_ex[i] = #kept in rows [0, i)      dead_ex[i] = #dead in rows [0, i)

which are two cumsums and one scatter, O(n) where an argsort is
O(n log n).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def dual_prefix_counts(keep: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                   jnp.ndarray]:
    """(kept_ex, dead_ex, kept_total) for a bool vector."""
    keep_i32 = keep.astype(jnp.int32)
    incl = jnp.cumsum(keep_i32)
    kept_ex = incl - keep_i32
    dead = 1 - keep_i32
    dead_ex = jnp.cumsum(dead) - dead
    return kept_ex, dead_ex, incl[-1]


def compact_permutation(keep: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable-partition permutation: kept row indices first (in order),
    then the rest. Returns (perm int32[n], kept_total). O(n), replacing
    the argsort spelling."""
    n = keep.shape[0]
    kept_ex, dead_ex, kept_total = dual_prefix_counts(keep)
    dest = jnp.where(keep, kept_ex, kept_total + dead_ex).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    perm = jnp.zeros((n,), jnp.int32).at[dest].set(idx)
    return perm, kept_total.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Open-addressing hash table (grouped aggregation)
# ---------------------------------------------------------------------------
#
# The engine's joins and grouped aggregations spell "hash table" as
# sort + segment sweeps (ops/joins.py, ops/groupby.py). The hash aggregate
# (spark.rapids.sql.agg.hashAggEnabled, off by default) is the other
# spelling: a power-of-two open-addressing table with linear probing,
# the cuDF hash groupby the reference calls (aggregate.scala:338-396),
# built by vectorized round-based claiming.
#
# Contract: every key column is reduced to an EXACT uint64 equality image
# first (ops/sortops.u64_key_image — fixed-width values carry the full
# value, dictionary codes are exact within a batch), so table equality is
# exact, never probabilistic. Load factor is bounded at <= 1/2 by
# hash_table_size, so linear probing always terminates at an empty slot.

_HASH_SEED = 0x243F6A8885A308D3


def hash_table_size(capacity: int) -> int:
    """Static power-of-two table size at load factor <= 1/2. With shape
    buckets on (spark.rapids.tpu.compile.shapeBuckets) the size pads up
    the coarse ladder so one compiled table program serves a capacity
    range; the load factor only drops."""
    t = 16
    while t < 2 * max(int(capacity), 1):
        t <<= 1
    from spark_rapids_tpu.utils.kernelcache import bucket_dim
    return bucket_dim(t)


def _mix_images(images) -> jnp.ndarray:
    from spark_rapids_tpu.ops.hashing import splitmix64
    h = jnp.asarray(_HASH_SEED, jnp.uint64)
    for img in images:
        h = splitmix64(h ^ img.astype(jnp.uint64))
    return h


def _hash_build(images, valid: jnp.ndarray, table_size: int):
    """Round-based claiming. Each round every still-pending row tries
    slot (h + probe) % T; rows whose slot holds their key join it, rows
    hitting an empty slot race a scatter-min claim (one winner per slot
    per round), losers re-try the same slot next round (the winner's key
    may BE theirs). Terminates because every round either places >= 1
    row or advances every pending row's probe past a full slot.
    Returns (slot[n] int32 (invalid -> T), counts (T,) int32)."""
    T = table_size
    n = valid.shape[0]
    k = len(images)
    h = _mix_images(images)
    rows = jnp.arange(n, dtype=jnp.int32)
    # table arrays carry one spill slot at index T so masked scatters
    # have a harmless destination
    init = {
        "tab": [jnp.zeros((T + 1,), jnp.uint64) for _ in range(k)],
        "occ": jnp.zeros((T + 1,), jnp.bool_),
        "slot": jnp.full((n,), T, jnp.int32),
        "pending": valid,
        "probe": jnp.zeros((n,), jnp.uint64),
    }

    def cond(st):
        return jnp.any(st["pending"])

    def body(st):
        slot = ((h + st["probe"]) % jnp.uint64(T)).astype(jnp.int32)
        occ = st["occ"][slot]
        eq = jnp.ones((n,), jnp.bool_)
        for j in range(k):
            eq = eq & (st["tab"][j][slot] == images[j])
        found = st["pending"] & occ & eq
        empty = st["pending"] & ~occ
        cand = jnp.where(empty, slot, T)
        winner = jnp.full((T + 1,), n, jnp.int32).at[cand].min(rows)
        placed = empty & (winner[jnp.clip(slot, 0, T - 1)] == rows)
        wslot = jnp.where(placed, slot, T)
        tab = [st["tab"][j].at[wslot].set(images[j]) for j in range(k)]
        occ2 = st["occ"].at[wslot].set(True).at[T].set(False)
        done = found | placed
        return {
            "tab": tab,
            "occ": occ2,
            "slot": jnp.where(done, slot, st["slot"]),
            "pending": st["pending"] & ~done,
            # a claim loser re-probes the SAME slot (its key may have
            # just been placed there); only occupied-mismatch advances
            "probe": st["probe"] + jnp.where(
                st["pending"] & ~done & occ, 1, 0).astype(jnp.uint64),
        }

    st = jax.lax.while_loop(cond, body, init)
    slot = st["slot"]
    counts = jnp.zeros((T + 1,), jnp.int32).at[slot].add(
        jnp.where(valid, 1, 0))[:T]
    return slot, counts


# Job contract of hash_grouped_aggregate (normalized by the caller,
# ops/aggregate.py): every engine reduction kind lowers to one of THREE
# accumulator kinds over (data, eligible) pairs —
#   'sum'  acc += data            where eligible
#   'min'  acc  = min(acc, data)  where eligible (first eligible seeds)
#   'max'  acc  = max(acc, data)  where eligible
# count = sum over ones, first/last = min/max over the row-position
# vector, any = max over the 0/1 value. Each job also counts its eligible
# rows (n_eligible), which doubles as the accumulator-validity flag —
# acc is UNDEFINED where n_eligible == 0 (it holds the segment-op
# neutral; callers must mask).


def _minmax_neutral(dtype, kind: str):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if kind == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if kind == "min" else info.min, dtype)


def hash_grouped_aggregate(images, valid: jnp.ndarray, jobs,
                           table_size: int):
    """Grouped aggregation over the open-addressing table: the
    round-claiming build assigns slots, then each job is ONE segment op
    at table width.

    ``images``: exact uint64 key-image columns (nulls already
    sentineled + validity folded in by the caller); ``valid``: live-row
    mask (dead rows never enter the table); ``jobs``: list of
    (kind, data (n,), eligible (n,) bool) with kind in {sum, min, max}
    (see the job contract above).

    Returns slot-space results — (counts (T,) int32 rows per slot,
    rep (T,) int32 first-arrival row per used slot, accs: per-job (T,)
    accumulators, nels: per-job (T,) int32 eligible counts). acc is
    undefined where its nel == 0; the caller compacts used slots into
    group rows (counts > 0) and masks by nel."""
    T = table_size
    n = valid.shape[0]
    slot, counts = _hash_build(images, valid, T)
    rows = jnp.arange(n, dtype=jnp.int32)
    sid = jnp.where(valid, slot, T)
    rep = jnp.clip(
        jax.ops.segment_min(rows, sid, num_segments=T + 1)[:T], 0, n - 1)
    accs, nels = [], []
    for kind, data, elig in jobs:
        el = elig & valid
        nel = jax.ops.segment_sum(el.astype(jnp.int32), sid,
                                  num_segments=T + 1)[:T]
        if kind == "sum":
            x = jnp.where(el, data, jnp.zeros((), data.dtype))
            acc = jax.ops.segment_sum(x, sid, num_segments=T + 1)[:T]
        elif kind == "min":
            x = jnp.where(el, data, _minmax_neutral(data.dtype, "min"))
            acc = jax.ops.segment_min(x, sid, num_segments=T + 1)[:T]
        else:
            x = jnp.where(el, data, _minmax_neutral(data.dtype, "max"))
            acc = jax.ops.segment_max(x, sid, num_segments=T + 1)[:T]
        accs.append(acc)
        nels.append(nel)
    return counts, rep, accs, nels


# ---------------------------------------------------------------------------
# Parquet page-decode expanders (device-resident scan path)
# ---------------------------------------------------------------------------
#
# The raw-page scan mode (sql/parquet_raw.py -> ops/parquet_decode.py,
# spark.rapids.sql.scan.deviceDecode, off by default) uploads encoded page
# bytes as u32 word buffers plus small host-built run tables, and these
# expand them into the engine's device columns. Four families:
#
#   hybrid_expand   RLE/bit-packed hybrid -> int32 stream (definition
#                   levels and dictionary indices): each element finds
#                   its run with searchsorted.
#   delta_unpack    DELTA_BINARY_PACKED -> int64 stream: all deltas
#                   extracted vectorized, then one cumsum.
#   plain_fixed     PLAIN fixed-width word reassembly (i32/i64/f32/f64/
#                   bool) -- pure re-blocking of the uploaded words.
#   slab_pack       PLAIN byte-array -> PR 11 (cap, stride/8) u64 char
#                   slab, identical packing to columnar.column.np_build_slab.
#
# Bit extraction everywhere uses a u64 window over adjacent u32 words
# ((lo | hi<<32) >> (bit & 31)) so no shift ever reaches 32 on a u32 lane;
# bit widths > 32 are rejected host-side (fallback reason deltaWide).

def _u64_window(words_u32, w):
    """words (W,) uint32, w (..) int32 word index -> u64 little-endian
    window starting at word w. Callers guarantee w+1 < W via host-side
    padding; the clip is belt-and-braces for null-row garbage indices."""
    top = words_u32.shape[0] - 1
    wc = jnp.clip(w, 0, top)
    lo = words_u32[wc].astype(jnp.uint64)
    hi = words_u32[jnp.clip(wc + 1, 0, top)].astype(jnp.uint64)
    return lo | (hi << jnp.uint64(32))


def _extract_bits(words_u32, bit, bw_u64):
    """Extract bw-bit little-endian fields at absolute bit positions
    ``bit`` (int64). bw may be a scalar or per-element u64 array, <= 32."""
    bit = jnp.maximum(bit, 0)
    w = (bit >> 5).astype(jnp.int32)
    off = (bit & 31).astype(jnp.uint64)
    window = _u64_window(words_u32, w)
    mask = (jnp.uint64(1) << bw_u64) - jnp.uint64(1)
    return (window >> off) & mask


def hybrid_expand(words, out_start, kind, value, bit_start, bw,
                  n: int) -> jnp.ndarray:
    """Expand an RLE/bit-packed hybrid stream to (n,) int32. ``bw`` is a
    per-run int32 bit-width array (multi-page chunks merge pages with
    differing dictionary index widths into one run table)."""
    k = jnp.arange(n, dtype=jnp.int32)
    r = jnp.searchsorted(out_start, k, side="right").astype(jnp.int32) - 1
    r = jnp.clip(r, 0, kind.shape[0] - 1)
    bit = bit_start[r] + (k - out_start[r]).astype(jnp.int64) * \
        bw[r].astype(jnp.int64)
    bp = _extract_bits(words, bit, bw[r].astype(jnp.uint64)).astype(
        jnp.int32)
    return jnp.where(kind[r] == 1, bp, value[r])


def delta_unpack(words, out_start, bwid, min_delta, bit_start, first,
                 n: int) -> jnp.ndarray:
    """DELTA_BINARY_PACKED stream -> (n,) int64 values."""
    if n <= 1:
        return jnp.full((max(n, 1),), first, jnp.int64)[:n]
    d = jnp.arange(n - 1, dtype=jnp.int32)
    m = jnp.searchsorted(out_start, d, side="right").astype(jnp.int32) - 1
    m = jnp.clip(m, 0, bwid.shape[0] - 1)
    bit = bit_start[m] + (d - out_start[m]).astype(jnp.int64) * \
        bwid[m].astype(jnp.int64)
    raw = _extract_bits(words, bit, bwid[m].astype(jnp.uint64))
    deltas = raw.astype(jnp.int64) + min_delta[m]
    vals = jnp.concatenate([first[:1], deltas])
    return jnp.cumsum(vals)


def plain_fixed(words, kind: str, n: int) -> jnp.ndarray:
    """Reassemble a PLAIN fixed-width value stream from uploaded u32
    words. ``kind`` in {i32, i64, f32, f64, bool}."""
    if kind == "i32":
        return jax.lax.bitcast_convert_type(words, jnp.int32)[:n]
    if kind == "f32":
        return jax.lax.bitcast_convert_type(words, jnp.float32)[:n]
    if kind == "i64":
        lo = words[0::2].astype(jnp.uint64)
        hi = words[1::2].astype(jnp.uint64)
        return (lo | (hi << jnp.uint64(32))).astype(jnp.int64)[:n]
    if kind == "f64":
        lo = words[0::2].astype(jnp.uint64)
        hi = words[1::2].astype(jnp.uint64)
        return jax.lax.bitcast_convert_type(
            lo | (hi << jnp.uint64(32)), jnp.float64)[:n]
    if kind == "bool":
        k = jnp.arange(n, dtype=jnp.int32)
        return ((words[k >> 5] >> (k & 31).astype(jnp.uint32)) & 1) \
            .astype(jnp.bool_)
    raise ValueError(f"plain_fixed kind {kind}")


def slab_pack(chars_u8, starts, lens, cap: int, stride: int) -> jnp.ndarray:
    """Gather PLAIN byte-array values into a (cap, stride/8) u64 char
    slab (np_build_slab packing: byte j of a row at bit 8*(j%8) of word
    j//8, zero past the row's length; rows with len 0 are all-zero).
    ``starts``/``lens`` must be padded to ``cap`` with 0-length rows and
    ``chars_u8`` padded by >= stride bytes so every 8-byte load lands in
    bounds."""
    nwords = stride // 8
    bytepos = (jnp.arange(nwords, dtype=jnp.int32)[None, :, None] * 8
               + jnp.arange(8, dtype=jnp.int32)[None, None, :])
    src = starts[:, None, None] + bytepos.astype(jnp.int64)
    src = jnp.clip(src, 0, max(chars_u8.shape[0] - 1, 0))
    byte = jnp.where(bytepos < lens[:, None, None], chars_u8[src], 0)
    # little-endian pack: byte j lands at bit 8*j, matching np_build_slab
    return jax.lax.bitcast_convert_type(byte, jnp.uint64)
