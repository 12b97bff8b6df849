"""Arithmetic float64 -> IEEE-754 bits (no 64-bit float bitcast).

XLA:TPU has no 64-bit element types: it rewrites them away, and that
rewrite is not implemented for a bitcast of a float64 operand
(``bitcast_convert_type`` f64 -> u64 fails with "UNIMPLEMENTED: While
rewriting computation to not contain X64 element types ... bitcast-convert",
re-measured on v5e with libtpu 0.0.34; ``f64.view(uint64)``, the 2x uint32
form, ``frexp`` and ``ldexp`` went the same way when this module was
written), while 64-bit integer bitcasts and arithmetic compile fine. Sort key
images (ops/sortops.py) and row hashes (ops/hashing.py) need the exact IEEE
bit pattern of float columns, so this module reconstructs it with exact
floating-point arithmetic only:

  * binary normalization: scale |x| into [1, 2) by a fixed unrolled ladder
    of exact power-of-two multiplies, accumulating the unbiased exponent;
  * mantissa: ``x1 * 2^52`` is then an exact 53-bit integer;
  * zero/inf/NaN patch in as constants. Denormals flush to +0.0 bits: TPU
    float arithmetic is flush-to-zero on read, so their true bits are
    unrecoverable on device — and they already behave as 0.0 in every
    other traced op.

Matches ``np.float64.view(np.uint64)`` bit-for-bit (denormals aside) after
the engine's standard normalizations (-0.0 -> +0.0, NaN -> canonical quiet
NaN), which this function applies itself — so it is also the device twin of
the normalize-then-view sequence in ops/hashing.py's numpy path.

Measured TPU v5e caveat: float64 there is emulated as a double-float32
pair (~49-bit mantissa, float32 exponent range) and even a device_put/
device_get roundtrip is lossy. Bit-exactness with the host is therefore
impossible on hardware for ANY implementation; the contract this module
ships is (a) bit-exact on CPU (the differential-test mesh), (b) on TPU,
strictly monotone w.r.t. device float ordering and equality-consistent
with device float equality (verified empirically across exponent bands),
so sorts, joins and group-bys agree with what the device's own float
semantics say. The ladder steps above 2^128 are unreachable there (their
constants saturate to inf, making the compares trivially false), which is
harmless: no representable value needs them.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_U64 = jnp.uint64

# descending ladder; after processing step k the magnitude lies in
# [2^(1-2k'), 2^k') for the next k' — ten exact steps land in [1, 2)
_EXP_STEPS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)

_CANONICAL_NAN_BITS = np.uint64(0x7FF8) << np.uint64(48)
_INF_BITS = np.uint64(0x7FF) << np.uint64(52)


def f64_bits(f: jnp.ndarray) -> jnp.ndarray:
    """uint64 IEEE bits of a float64 array, with -0.0 normalized to +0.0,
    every NaN mapped to the canonical quiet NaN pattern, and denormals
    flushed to +0.0 bits.

    One code path on every backend, so the CPU differential-test mesh
    exercises exactly what runs on TPU. The denormal flush is not a choice:
    XLA float arithmetic (including the ``== 0.0`` comparison the previous
    normalize-then-view used) reads denormals as zero on both backends, so
    their true bits are unrecoverable in any traced op."""
    return f64_bits_arith(f)


def f64_bits_arith(f: jnp.ndarray) -> jnp.ndarray:
    """The arithmetic reconstruction (no 64-bit float bitcast)."""
    f = f.astype(jnp.float64)
    ax = jnp.abs(f)
    neg = f < 0  # False for -0.0: normalized to +0.0 by construction
    nan = jnp.isnan(f)
    inf = jnp.isinf(ax)
    # denormals bucket with zero: FTZ hardware reads them as 0.0, and a
    # comparison cannot even distinguish them reliably under FTZ
    zero = ax < 2.0 ** -1022
    special = zero | inf | nan

    x1 = jnp.where(special, 1.0, ax)
    e = jnp.zeros(f.shape, jnp.int64)
    for k in _EXP_STEPS:
        big = x1 >= 2.0 ** k
        x1 = jnp.where(big, x1 * 2.0 ** -k, x1)
        e = e + jnp.where(big, k, 0)
        lift = x1 < 2.0 ** (1 - k)
        x1 = jnp.where(lift, x1 * 2.0 ** k, x1)
        e = e - jnp.where(lift, k, 0)
    # value == x1 * 2^e with x1 in [1, 2), e in [-1022, 1023]
    scaled = (x1 * 2.0 ** 52).astype(_U64)  # exact integer in [2^52, 2^53)
    mant = scaled - (_U64(1) << _U64(52))
    biased = jnp.clip(e + 1023, 1, 2046).astype(_U64)
    bits = (biased << _U64(52)) | mant
    bits = jnp.where(zero, _U64(0), bits)
    bits = jnp.where(inf, _U64(_INF_BITS), bits)
    bits = jnp.where(nan, _U64(_CANONICAL_NAN_BITS), bits)
    sign = jnp.where(neg & ~nan & ~zero, _U64(1) << _U64(63), _U64(0))
    return bits | sign


def np_f64_bits(f: np.ndarray) -> np.ndarray:
    """Numpy twin: normalize (-0.0 and denormals -> +0.0, NaN -> canonical)
    then view — the reference result f64_bits must match bit-for-bit."""
    f64 = np.asarray(f, dtype=np.float64).copy()
    f64[np.abs(f64) < 2.0 ** -1022] = 0.0
    f64[np.isnan(f64)] = np.nan
    return f64.view(np.uint64)
