"""Dense one-hot matmul reductions: every count and sum of an aggregation
in ONE MXU pass.

Why: XLA:TPU lowers 1-D indexed ops (gather/scatter/segment_*) to
element-at-a-time loops far below memory speed, and a q1-shaped
aggregation makes ~17 such passes per batch, while dense elementwise ops
and matmuls run at hardware speed. This module re-expresses per-slot reductions as

    totals[t, k] = sum_n onehot(slot[n] == t) * limbs[n, k]

one ``(T, N) @ (N, K)`` matmul whose operands are built with dense
elementwise ops only. The reference reaches the same goal through cuDF's
hash aggregation (reference: aggregate.scala:338-396 driving
cudf groupBy; the hash table is a GPU-friendly structure, the one-hot
matmul is the MXU-friendly one).

Exactness: all values ride as small non-negative integer "limbs" of at
most LIMB_BITS bits. Products against the 0/1 one-hot are exact in
bfloat16 (integers <= 255), and the MXU accumulates in float32, which is
exact for integers < 2^24; limb width is chosen so that a per-slot limb
total can never reach 2^24 even if every row lands in one slot. Integer
sums are therefore EXACT (mod 2^64, i.e. Spark's wraparound semantics);
float sums ride a per-column fixed-point image with ~2^-40 relative
precision, comparable to this hardware's emulated float64 (~49-bit
mantissa, see ops/floatbits.py).
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# one-hot blocks above this many elements are scan-chunked so the
# materialized (block, T) one-hot stays <= ~128 MB bf16 (measured best
# on v5e at 1M rows x 8192 slots: 128-step scan beats 512-step by ~35%)
_MAX_ONEHOT_ELEMS = 1 << 27

# kinds this engine can evaluate; everything else (min/max/first/last/any,
# string payloads) falls back to T-width segment ops in the caller
DENSE_KINDS = ("sum", "count_valid")


# largest capacity the exactness argument covers: at the minimum limb
# width b=1, per-slot totals stay < 2^24 only while capacity <= 2^23
MAX_EXACT_CAPACITY = 1 << 23


def limb_bits_for(capacity: int) -> int:
    """Largest limb width whose worst-case per-slot total stays f32-exact:
    (2^b - 1) * capacity < 2^24, capped at 8 so limb values stay exact in
    bfloat16 (integers <= 255). Callers must refuse capacities above
    MAX_EXACT_CAPACITY (the engine asserts)."""
    assert capacity <= MAX_EXACT_CAPACITY, capacity
    return max(1, min(8, 24 - max(1, (capacity - 1).bit_length())))


def _onehot_totals(slot: jnp.ndarray, cols: Sequence[jnp.ndarray],
                   T: int) -> jnp.ndarray:
    """totals (T, K) f32 of per-slot sums of ``cols`` (each f32 (N,) holding
    bf16-exact small integers). Rows with slot outside [0, T) contribute
    nothing."""
    n = slot.shape[0]
    K = len(cols)
    V = jnp.stack([c.astype(jnp.bfloat16) for c in cols], axis=1)  # (N, K)
    iota = jnp.arange(T, dtype=slot.dtype)

    def block_tot(s, v):
        oh = (s[:, None] == iota[None, :]).astype(jnp.bfloat16)  # (B, T)
        return jax.lax.dot_general(
            oh, v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (T, K)

    max_block = max(128, _MAX_ONEHOT_ELEMS // max(T, 1))
    if n <= max_block:
        return block_tot(slot, V)
    B = 1 << (max_block.bit_length() - 1)  # power-of-two block
    npad = -(-n // B) * B
    if npad != n:
        # pad to a whole number of blocks; padded rows sit at slot T (the
        # parked id), whose one-hot row is all-zero
        slot = jnp.concatenate(
            [slot, jnp.full((npad - n,), T, slot.dtype)])
        V = jnp.concatenate(
            [V, jnp.zeros((npad - n, K), V.dtype)], axis=0)
    C = npad // B

    def body(acc, xs):
        s, v = xs
        return acc + block_tot(s, v), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((T, K), jnp.float32),
        (slot.reshape(C, B), V.reshape(C, B, K)))
    return acc


def _int_limbs(x: jnp.ndarray, contribute: jnp.ndarray, width: int,
               b: int) -> List[jnp.ndarray]:
    """Biased two's-complement limbs of an integer column. ``width`` is 32
    or 64; the bias 2^(width-1) makes every limb non-negative, and the
    caller subtracts count * bias after the matmul (exact: counts < 2^24).
    Rows with ``contribute`` False emit all-zero limbs (no bias either, so
    no count correction is needed for them)."""
    if width == 64:
        u = x.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    else:
        u = (x.astype(jnp.int64) + jnp.int64(1 << 31)).astype(jnp.uint64)
    nlimbs = -(-width // b)
    mask = jnp.uint64((1 << b) - 1)
    out = []
    for li in range(nlimbs):
        limb = ((u >> jnp.uint64(b * li)) & mask).astype(jnp.float32)
        out.append(jnp.where(contribute, limb, 0.0))
    return out


def _f64_limb_word(tot: jnp.ndarray, lo: int, hi: int, b: int,
                   base: int) -> jnp.ndarray:
    """sum_{li in [lo, hi)} tot[:, li] * 2^(b*li - base) accumulated in
    FLOAT64. Exact: each limb total is an integer <= 2^24
    (limb_bits_for guarantees (2^b - 1) * capacity < 2^24), every scale
    is a power of two, and partial sums stay far below 2^48 — within
    even this hardware's emulated float64 (~49-bit) integer-exact range.

    Why not int64: XLA:TPU's X64-rewriting pass MISCOMPILES the
    previous formulation (f32 matmul totals -> int64 convert -> shifts
    -> subtract, fused after the one-hot dot): the recombined sum
    silently dropped the high limb's contribution in full-graph
    compilations while every piece computed correctly in isolation
    (verified on v5e; returning the totals as a program output or
    constant-folding them "fixed" it). Keeping the recombination in
    pure f64 arithmetic avoids the rewritten-int64 pattern entirely."""
    out = jnp.zeros(tot.shape[:1], jnp.float64)
    for li in range(lo, hi):
        out = out + tot[:, li].astype(jnp.float64) * jnp.float64(
            1 << (b * li - base))
    return out


def _recombine_int(tot: jnp.ndarray, count: jnp.ndarray, width: int,
                   b: int) -> jnp.ndarray:
    """Per-slot integer sum from limb totals, exact mod 2^64 (Spark's
    wraparound overflow semantics for free). tot: (T, nlimbs) f32 exact
    integers; count: (T,) int64. Limb words are accumulated in f64
    (see _f64_limb_word) and assembled into int64 at the end — each
    word is < 2^44 so the f64->int64 converts are exact, and the final
    shifts/adds wrap mod 2^64 exactly like the direct reconstruction."""
    nlimbs = tot.shape[1]
    word_limbs = max(1, 24 // b)  # limbs per f64 word: <= 24 value bits
    words = []
    for lo in range(0, nlimbs, word_limbs):
        hi = min(lo + word_limbs, nlimbs)
        words.append((b * lo,
                      _f64_limb_word(tot, lo, hi, b, b * lo)))
    s = jnp.zeros(tot.shape[:1], jnp.int64)
    for base, w in words:
        s = s + (w.astype(jnp.int64) << jnp.int64(base))
    if width == 32:
        return s - (count << jnp.int64(31))
    return s - (count << jnp.int64(63))


_F_BITS = 43  # fixed-point fraction bits per word of a float sum


def _fixed_word_limbs(xi: jnp.ndarray, finite: jnp.ndarray,
                      b: int) -> List[jnp.ndarray]:
    """Limbs of one biased fixed-point word (|xi| <= 2^43 -> 45-bit
    unsigned after the +2^43 bias)."""
    u = (xi + jnp.int64(1 << _F_BITS)).astype(jnp.uint64)
    nlimbs = -(-(_F_BITS + 2) // b)
    mask = jnp.uint64((1 << b) - 1)
    out = []
    for li in range(nlimbs):
        limb = ((u >> jnp.uint64(b * li)) & mask).astype(jnp.float32)
        out.append(jnp.where(finite, limb, 0.0))
    return out


def _float_fixedpoint(x64: jnp.ndarray, contribute: jnp.ndarray,
                      b: int) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """TWO-word fixed-point image of the FINITE values of a float column:
    a primary word at quantum q = s/2^43 (s a power of ~2 above the batch
    absmax) plus a residual word at quantum q/2^43, i.e. ~86 bits of
    dynamic range below absmax. A single-word image would quantize to the
    BATCH absmax, zeroing the sums of groups whose values are orders of
    magnitude smaller; with the residual word the representation error is
    ~absmax * 2^-86 per element — finer than float64 accumulation itself.
    Design limit: a group whose values sit more than ~86 bits below the
    batch absmax (ratio > ~7e25) still quantizes to zero — beyond any
    realistic column's dynamic range, but not beyond adversarial input.
    Non-finite values are excluded here and handled by the per-slot
    special-value columns in slot_reduce_dense (one stray NaN/inf must not
    poison the scale and corrupt every other group). Returns
    (primary+residual limbs, q) — per-slot sum recovers as
    (sum(xi) + sum(xi2)/2^43) * q."""
    finite = contribute & jnp.isfinite(x64)
    ax = jnp.where(finite, jnp.abs(x64), 0.0)
    absmax = jnp.max(ax)
    # floor(log2) via log2+floor: +/-1 ulp of log error lands in [t-1, t+1],
    # +2 of headroom keeps |x|/s <= 1/2 either way (exactness of s does not
    # matter, only its range); the clamp keeps s finite for values near
    # DBL_MAX (the xi clip below bounds the image in that regime)
    e = jnp.floor(jnp.log2(jnp.maximum(absmax, 1e-300))) + 2.0
    s = jnp.exp2(jnp.clip(e, -1020.0, 1023.0))
    s = jnp.where(absmax > 0, s, 1.0)
    q = s / jnp.float64(1 << _F_BITS)
    lim = jnp.float64(1 << _F_BITS)
    xf = jnp.where(finite, x64, 0.0)
    xi = jnp.clip(jnp.round(xf / q), -lim, lim).astype(jnp.int64)
    r = xf - xi.astype(jnp.float64) * q
    xi2 = jnp.clip(jnp.round(r * lim / q), -lim, lim).astype(jnp.int64)
    return (_fixed_word_limbs(xi, finite, b)
            + _fixed_word_limbs(xi2, finite, b)), q


def _recombine_fixed_word(tot: jnp.ndarray, count: jnp.ndarray,
                          b: int) -> jnp.ndarray:
    """float64 value of one word's per-slot sum(xi) from its limb totals.
    Pure-f64 reconstruction (see _f64_limb_word for why int64 is
    unusable here): the high/low halves each stay below 2^42, the bias
    subtraction happens in the small-magnitude high half, and every
    scale is a power of two — bit-exact."""
    nlimbs = tot.shape[1]
    lo_limbs = -(-24 // b)
    s_lo = _f64_limb_word(tot, 0, min(lo_limbs, nlimbs), b, 0)
    s_hi = _f64_limb_word(tot, lo_limbs, nlimbs, b, b * lo_limbs)
    a = s_hi - count.astype(jnp.float64) * jnp.float64(
        1 << (_F_BITS - b * lo_limbs))
    return a * jnp.float64(1 << (b * lo_limbs)) + s_lo


def _recombine_float(tot: jnp.ndarray, count: jnp.ndarray, q: jnp.ndarray,
                     b: int) -> jnp.ndarray:
    """Per-slot float sum from the two-word limb totals."""
    nlimbs = tot.shape[1] // 2
    w1 = _recombine_fixed_word(tot[:, :nlimbs], count, b)
    w2 = _recombine_fixed_word(tot[:, nlimbs:], count, b)
    return (w1 + w2 / jnp.float64(1 << _F_BITS)) * q


def dense_supported(kind: str, np_dtype) -> bool:
    """Can this (reduction kind, input numpy dtype) ride the matmul?"""
    if kind == "count_valid":
        return True
    if kind != "sum":
        return False
    return (jnp.issubdtype(np_dtype, jnp.integer)
            or jnp.issubdtype(np_dtype, jnp.floating))


def slot_reduce_dense(slot: jnp.ndarray, live: jnp.ndarray, T: int,
                      jobs: Sequence[Tuple[str, jnp.ndarray, jnp.ndarray,
                                           object]]):
    """Evaluate ``jobs`` — (kind, values, validity, out_np_dtype) with kind
    in DENSE_KINDS — per slot in one matmul.

    Returns (results, row_count): results is a list of
    (data (T,), has_valid (T,) bool); row_count (T,) int32 counts LIVE rows
    per slot (the group-existence mask, independent of any job validity).
    """
    capacity = slot.shape[0]
    b = limb_bits_for(capacity)
    cols: List[jnp.ndarray] = [live.astype(jnp.float32)]  # col 0: row count
    recipes = []  # (kind, start, ncols, out_dt, extra)
    for kind, values, validity, out_dt in jobs:
        contribute = validity & live
        start = len(cols)
        if kind == "count_valid":
            cols.append(contribute.astype(jnp.float32))
            recipes.append(("count", start, 1, out_dt, None))
            continue
        assert kind == "sum", kind
        if jnp.issubdtype(values.dtype, jnp.floating):
            x64 = values.astype(jnp.float64)
            limbs, s = _float_fixedpoint(x64, contribute, b)
            cols.append(contribute.astype(jnp.float32))
            # per-slot special-value counts: IEEE sum semantics per GROUP
            # (NaN or mixed-sign inf -> NaN; else the inf's sign wins)
            # without letting one NaN/inf poison the shared scale
            cols.append((contribute & jnp.isnan(x64)).astype(jnp.float32))
            cols.append((contribute & jnp.isposinf(x64)).astype(jnp.float32))
            cols.append((contribute & jnp.isneginf(x64)).astype(jnp.float32))
            cols.extend(limbs)
            recipes.append(("fsum", start, 4 + len(limbs), out_dt, s))
        else:
            width = 64 if values.dtype in (jnp.int64, jnp.uint64) else 32
            limbs = _int_limbs(values, contribute, width, b)
            cols.append(contribute.astype(jnp.float32))
            cols.extend(limbs)
            recipes.append(("isum", start, 1 + len(limbs), out_dt, width))

    totals = _onehot_totals(slot, cols, T)  # (T, K) f32, exact integers
    row_count = totals[:, 0].astype(jnp.int32)
    results = []
    for kind, start, ncols, out_dt, extra in recipes:
        count = totals[:, start].astype(jnp.int64)
        has_valid = count > 0
        if kind == "count":
            results.append((count.astype(out_dt), jnp.ones_like(has_valid)))
        elif kind == "isum":
            tot = totals[:, start + 1:start + ncols]
            data = _recombine_int(tot, count, extra, b)
            results.append((data.astype(out_dt), has_valid))
        else:
            nan_c = totals[:, start + 1].astype(jnp.int64)
            pos_c = totals[:, start + 2].astype(jnp.int64)
            neg_c = totals[:, start + 3].astype(jnp.int64)
            finite_c = count - nan_c - pos_c - neg_c
            tot = totals[:, start + 4:start + ncols]
            data = _recombine_float(tot, finite_c, extra, b)
            is_nan = (nan_c > 0) | ((pos_c > 0) & (neg_c > 0))
            data = jnp.where(
                is_nan, jnp.float64(jnp.nan),
                jnp.where(pos_c > 0, jnp.float64(jnp.inf),
                          jnp.where(neg_c > 0, jnp.float64(-jnp.inf), data)))
            results.append((data.astype(out_dt), has_valid))
    return results, row_count
