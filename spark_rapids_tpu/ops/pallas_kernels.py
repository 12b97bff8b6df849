"""Pallas TPU kernels for the engine's hot data-movement ops.

First kernel: dual exclusive prefix-count for stream compaction. Every
filter/join output pays a stable partition ("kept rows first, in order" —
the cuDF filter/apply_boolean_mask equivalent the reference leans on,
GpuFilterExec in basicPhysicalOperators.scala). The XLA spelling used to
be a full O(n log n) argsort; the compaction permutation only actually
needs the two exclusive running counts

    kept_ex[i] = #kept in rows [0, i)      dead_ex[i] = #dead in rows [0, i)

and those are one sequential O(n) sweep. The Pallas kernel runs the sweep
block-by-block over the TPU's sequential grid with the carry pair living
in SMEM — one HBM read producing both prefix streams in a single pass.
Mosaic has no cumsum primitive, so the in-block scan is the classic
scan-by-matmul: a (16,128) tile times a 128x128 upper-triangular ones
matrix gives per-row inclusive prefixes on the MXU, and a 16x16 strict
lower-triangular matmul accumulates across rows. Counts <= 2048 are exact
in float32. Off-TPU the jnp twin (two fused cumsums) provides identical
results.

Toggle: SPARK_RAPIDS_TPU_PALLAS (see ``_mode``): unset/0 runs the jnp
twins, =1 the Mosaic-compiled kernels on a TPU backend, =interpret the
kernel bodies in the Pallas interpreter (CPU CI of the kernels).
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_ROWS = 16
_LANES = 128
_BLK = _ROWS * _LANES  # 2048 elements per grid step


def _mode() -> str:
    """Which implementation of each kernel runs.

    'jnp' — the XLA twins; the default on every backend, TPU included.
    Whether any Pallas body should become the TPU default is undecided:
    that takes an A/B against its twin on the chip, per kernel family.
    'pallas' — SPARK_RAPIDS_TPU_PALLAS=1 on a TPU backend: the
    Mosaic-compiled kernels. A family the compiler refuses raises
    ``PallasKernelRefused`` at its first use (``require_kernels``); the
    twin is never substituted for an explicitly requested kernel.
    'interpret' — SPARK_RAPIDS_TPU_PALLAS=interpret: the kernel bodies
    under the Pallas interpreter (CPU CI of the kernels themselves)."""
    env = os.environ.get("SPARK_RAPIDS_TPU_PALLAS", "auto")
    if env in ("0", "off", "jnp", "auto"):
        return "jnp"
    if env == "interpret":
        return "interpret"
    if env in ("1", "on"):
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return "jnp"


def _dual_prefix_jnp(keep_i32: jnp.ndarray):
    incl = jnp.cumsum(keep_i32)
    kept_ex = incl - keep_i32
    dead = 1 - keep_i32
    dead_ex = jnp.cumsum(dead) - dead
    return kept_ex, dead_ex, incl[-1]


def _dual_prefix_kernel(keep_ref, kex_ref, dex_ref, tot_ref, carry):
    import jax.experimental.pallas as pl
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        # explicit int32 zeros: with jax x64 enabled a bare python 0
        # lands as int64 and interpret mode's ref-write discharge rejects
        # the dtype mismatch against the int32 SMEM scratch
        carry[0] = jnp.int32(0)
        carry[1] = jnp.int32(0)

    k = keep_ref[:].astype(jnp.float32)           # (16, 128) of 0/1
    d = 1.0 - k
    # inclusive prefix along lanes: x @ upper-triangular ones (MXU)
    r = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    tri_incl = (r <= c).astype(jnp.float32)       # (128, 128)
    # strict prefix across sublane rows: lower-triangular row-sum matmul
    r2 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _ROWS), 0)
    c2 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _ROWS), 1)
    tri_rows = (r2 > c2).astype(jnp.float32)      # (16, 16)

    def dual_scan(x):
        within = jnp.dot(x, tri_incl, preferred_element_type=jnp.float32)
        rowsum = within[:, _LANES - 1:_LANES]     # (16, 1) per-row totals
        off = jnp.dot(tri_rows, rowsum,
                      preferred_element_type=jnp.float32)  # rows before
        incl = within + off
        ex = (incl - x).astype(jnp.int32)
        total = incl[_ROWS - 1, _LANES - 1].astype(jnp.int32)
        return ex, total

    kex, ktot = dual_scan(k)
    dex, dtot = dual_scan(d)
    kex_ref[:] = kex + carry[0]
    dex_ref[:] = dex + carry[1]
    carry[0] = carry[0] + ktot
    carry[1] = carry[1] + dtot

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        tot_ref[0, 0] = carry[0]


@functools.partial(jax.jit, static_argnums=(1,))
def _dual_prefix_pallas(keep_i32: jnp.ndarray, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = keep_i32.shape[0]
    padded = ((n + _BLK - 1) // _BLK) * _BLK
    buf = jnp.zeros((padded,), jnp.int32).at[:n].set(keep_i32)
    buf = buf.reshape(padded // _LANES, _LANES)
    grid = padded // _BLK
    # traced with x64 OFF: the engine runs jax in 64-bit mode, where the
    # index maps' grid positions and literals come out i64 and Mosaic
    # fails to legalize them ("failed to legalize operation
    # 'func.return'", measured on v5e / libtpu 0.0.34). Everything in
    # this kernel is 32-bit anyway.
    with jax.enable_x64(False):
        kex, dex, tot = pl.pallas_call(
            _dual_prefix_kernel,
            grid=(grid,),
            in_specs=[pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((padded // _LANES, _LANES), jnp.int32),
                jax.ShapeDtypeStruct((padded // _LANES, _LANES), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
            interpret=interpret,
        )(buf)
    return kex.reshape(-1)[:n], dex.reshape(-1)[:n], tot[0, 0]


class PallasKernelRefused(RuntimeError):
    """SPARK_RAPIDS_TPU_PALLAS=1 asked for a kernel family that this
    backend's compiler rejects; carries the compiler's message."""


# family -> None once its probe compiled and ran, else what it raised
_probe_verdicts: Dict[str, Optional[Exception]] = {}


def require_kernels(family: str) -> None:
    """Eager one-shot compile probe of one kernel family (a key of
    ``KERNEL_PROBES``). The caller is usually *inside* a traced
    per-batch kernel, where a pallas_call just traces in and a compile
    failure would surface later, at the outer program's compile, with no
    hint of which kernel caused it — so each family is proven here with
    a small concrete run, and a refusal raises here with the compiler's
    message. Each family gets its own probe because they exercise
    different Mosaic surfaces (matmul scan, 64-bit tables with scalar
    while-loops, scalar-indexed fori_loop walks)."""
    if family not in _probe_verdicts:
        try:
            jax.block_until_ready(KERNEL_PROBES[family]())
        except Exception as e:  # noqa: BLE001 — re-raised below with context
            _probe_verdicts[family] = e
        else:
            _probe_verdicts[family] = None
    err = _probe_verdicts[family]
    if err is not None:
        raise PallasKernelRefused(
            f"pallas {family} kernel does not compile on "
            f"{jax.default_backend()}: {type(err).__name__}: {err}") from err


def _probe_compaction():
    probe = jnp.asarray(np.arange(_BLK) % 3 == 0, jnp.int32)
    return _dual_prefix_pallas(probe, False)


def dual_prefix_counts(keep: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                   jnp.ndarray]:
    """(kept_ex, dead_ex, kept_total) for a bool vector."""
    keep_i32 = keep.astype(jnp.int32)
    mode = _mode()
    if mode == "pallas":
        require_kernels("compaction")
        return _dual_prefix_pallas(keep_i32, False)
    if mode == "interpret":
        return _dual_prefix_pallas(keep_i32, True)
    return _dual_prefix_jnp(keep_i32)


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
# Open-addressing hash-table kernels (join build/probe, grouped-agg)
# ---------------------------------------------------------------------------
#
# The engine's joins and grouped aggregations spell "hash table" as
# sort + segment sweeps (ops/joins.py, ops/groupby.py) because XLA cannot
# express data-dependent memory. Pallas CAN: these kernels are the real
# thing — a power-of-two open-addressing table with linear probing, the
# cuDF hash build/probe the reference calls (GpuHashJoin.scala:113-244)
# re-founded on the TPU's sequential grid.
#
# Contract: every key column is reduced to an EXACT uint64 equality image
# first (ops/sortops.u64_key_image — fixed-width values carry the full
# value, dictionary codes are exact within a batch), so table equality is
# exact, never probabilistic. The build kernel walks rows sequentially
# with the table in scratch, emitting each row's slot and its arrival
# rank within the slot; the probe kernel is read-only and data-parallel
# per stream row. Both run under the same SPARK_RAPIDS_TPU_PALLAS switch
# as the compaction kernel (=interpret covers them in CPU CI); the jnp
# twins implement the identical table algorithm with vectorized
# round-based claiming, so either mode yields the same groups.
#
# Load factor is bounded at <= 1/2 by hash_table_size, so linear probing
# always terminates at an empty slot and the whole-table-in-scratch
# single-step grid is adequate for the batch sizes the interpret/CI path
# sees; an HBM-blocked variant is the TPU-at-scale follow-up.

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


def _hash_build_jnp(images, valid: jnp.ndarray, table_size: int):
    """Vectorized twin of the build kernel: round-based claiming. Each
    round every still-pending row tries slot (h + probe) % T; rows whose
    slot holds their key join it, rows hitting an empty slot race a
    scatter-min claim (one winner per slot per round), losers re-try the
    same slot next round (the winner's key may BE theirs). Terminates
    because every round either places >= 1 row or advances every
    pending row's probe past a full slot."""
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
    table = jnp.stack([t[:T] for t in st["tab"]])
    return slot, None, table, counts


def _hash_probe_jnp(table: jnp.ndarray, counts: jnp.ndarray, images,
                    valid: jnp.ndarray, table_size: int) -> jnp.ndarray:
    T = table_size
    n = valid.shape[0]
    k = table.shape[0]
    h = _mix_images(images)
    init = {
        "slot": jnp.full((n,), T, jnp.int32),
        "pending": valid,
        "probe": jnp.zeros((n,), jnp.uint64),
    }

    def cond(st):
        return jnp.any(st["pending"])

    def body(st):
        slot = ((h + st["probe"]) % jnp.uint64(T)).astype(jnp.int32)
        occ = counts[slot] > 0
        eq = jnp.ones((n,), jnp.bool_)
        for j in range(k):
            eq = eq & (table[j][slot] == images[j])
        found = st["pending"] & occ & eq
        absent = st["pending"] & ~occ  # empty slot ends the probe chain
        return {
            "slot": jnp.where(found, slot, st["slot"]),
            "pending": st["pending"] & ~(found | absent),
            "probe": st["probe"] + jnp.where(
                st["pending"], 1, 0).astype(jnp.uint64),
        }

    return jax.lax.while_loop(cond, body, init)["slot"]


def _hash_build_kernel(k: int, T: int, keys_ref, valid_ref, slot_ref,
                       rank_ref, tab_ref, cnt_ref):
    """Sequential build: rows insert one at a time with the table held in
    the kernel's output refs (single-step grid). Per row: linear-probe to
    the first slot that is empty (claim it, rank 0) or already holds the
    key (rank = member count so far). The sequential walk is what gives
    exact per-row arrival ranks with no sort anywhere."""
    import jax.experimental.pallas as pl
    n = slot_ref.shape[1]
    cnt_ref[...] = jnp.zeros((1, T), jnp.int32)
    tab_ref[...] = jnp.zeros((k, T), jnp.uint64)
    slot_ref[...] = jnp.full((1, n), T, jnp.int32)
    rank_ref[...] = jnp.zeros((1, n), jnp.int32)

    def insert(e, _):
        e = e.astype(jnp.int32)
        v = valid_ref[0, e] != 0
        row_keys = [keys_ref[j, e] for j in range(k)]
        h = jnp.asarray(_HASH_SEED, jnp.uint64)
        from spark_rapids_tpu.ops.hashing import splitmix64
        for kk in row_keys:
            h = splitmix64(h ^ kk)

        def probe_cond(carry):
            _p, _s, code = carry
            return code == 0

        def probe_body(carry):
            p, _s, _code = carry
            s = ((h + p.astype(jnp.uint64)) % jnp.uint64(T)).astype(
                jnp.int32)
            c = cnt_ref[0, s]
            eq = jnp.asarray(True)
            for j in range(k):
                eq = eq & (tab_ref[j, s] == row_keys[j])
            code = jnp.where(c == 0, jnp.int32(1),
                             jnp.where(eq, jnp.int32(2), jnp.int32(0)))
            return p + jnp.int32(1), s, code

        _p, s, code = jax.lax.while_loop(
            probe_cond, probe_body, (jnp.int32(0), jnp.int32(0),
                                     jnp.int32(0)))

        @pl.when(v)
        def _():
            for j in range(k):
                tab_ref[j, s] = row_keys[j]
            rank = cnt_ref[0, s]
            cnt_ref[0, s] = rank + 1
            slot_ref[0, e] = s
            rank_ref[0, e] = rank
        return 0

    jax.lax.fori_loop(0, n, insert, 0)


def _hash_probe_kernel(k: int, T: int, tab_ref, cnt_ref, keys_ref,
                       valid_ref, slot_ref):
    """Read-only probe: per stream row, follow the chain to the row's key
    slot or the first empty slot (absent -> T)."""
    import jax.experimental.pallas as pl
    n = slot_ref.shape[1]
    slot_ref[...] = jnp.full((1, n), T, jnp.int32)

    def probe(e, _):
        e = e.astype(jnp.int32)
        v = valid_ref[0, e] != 0
        row_keys = [keys_ref[j, e] for j in range(k)]
        h = jnp.asarray(_HASH_SEED, jnp.uint64)
        from spark_rapids_tpu.ops.hashing import splitmix64
        for kk in row_keys:
            h = splitmix64(h ^ kk)

        def probe_cond(carry):
            _p, _s, code = carry
            return code == 0

        def probe_body(carry):
            p, _s, _code = carry
            s = ((h + p.astype(jnp.uint64)) % jnp.uint64(T)).astype(
                jnp.int32)
            c = cnt_ref[0, s]
            eq = jnp.asarray(True)
            for j in range(k):
                eq = eq & (tab_ref[j, s] == row_keys[j])
            # 1 = absent (empty slot ends the chain), 2 = found
            code = jnp.where(c == 0, jnp.int32(1),
                             jnp.where(eq, jnp.int32(2), jnp.int32(0)))
            return p + jnp.int32(1), s, code

        _p, s, code = jax.lax.while_loop(
            probe_cond, probe_body, (jnp.int32(0), jnp.int32(0),
                                     jnp.int32(0)))

        @pl.when(v & (code == 2))
        def _():
            slot_ref[0, e] = s
        return 0

    jax.lax.fori_loop(0, n, probe, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _hash_build_pallas(keys: jnp.ndarray, valid: jnp.ndarray,
                       table_size: int, interpret: bool):
    import jax.experimental.pallas as pl
    k, n = keys.shape
    T = table_size
    slot, rank, tab, cnt = pl.pallas_call(
        functools.partial(_hash_build_kernel, k, T),
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((k, T), jnp.uint64),
            jax.ShapeDtypeStruct((1, T), jnp.int32),
        ],
        interpret=interpret,
    )(keys, valid.astype(jnp.int32).reshape(1, n))
    return slot[0], rank[0], tab, cnt[0]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _hash_probe_pallas(tab: jnp.ndarray, cnt: jnp.ndarray,
                       keys: jnp.ndarray, valid: jnp.ndarray,
                       table_size: int, interpret: bool):
    import jax.experimental.pallas as pl
    k, n = keys.shape
    slot = pl.pallas_call(
        functools.partial(_hash_probe_kernel, k, table_size),
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32)],
        interpret=interpret,
    )(tab, cnt.reshape(1, -1), keys,
      valid.astype(jnp.int32).reshape(1, n))[0]
    return slot[0]


# whole-table-in-refs bound for the COMPILED pallas path: a (k, T)
# uint64 table must stay VMEM-resident in the single-step grid, so
# tables past this slot count route to the jnp twin instead (identical
# contract — the decision is static per capacity bucket, made at trace
# time). Interpret mode has no such bound.
_PALLAS_MAX_TABLE = 1 << 17

def _probe_hash_table():
    keys = jnp.asarray(np.arange(32) % 5, jnp.uint64).reshape(1, -1)
    valid = jnp.ones((32,), jnp.bool_)
    _slot, _r, tab, cnt = _hash_build_pallas(keys, valid, 64, False)
    return _hash_probe_pallas(tab, cnt, keys, valid, 64, False)


def hash_kernels_mode() -> str:
    """'pallas' | 'interpret' | 'off' — whether the hash-table kernels
    replace the sort-based join/agg paths. Rides the same
    SPARK_RAPIDS_TPU_PALLAS switch as the compaction kernel: default
    (auto/jnp) keeps the sort paths byte-identical."""
    m = _mode()
    if m == "pallas":
        require_kernels("hash_table")
        return "pallas"
    if m == "interpret":
        return "interpret"
    return "off"


def hash_table_build(images, valid: jnp.ndarray, table_size: int,
                     mode: Optional[str] = None):
    """Build the open-addressing table over exact u64 key images.
    Returns (slot[n] int32 (invalid -> T), rank[n] int32 or None,
    table (k, T) uint64, counts (T,) int32). rank is per-row arrival
    order within its slot (pallas/interpret only — the vectorized twin
    derives placement by a one-operand sort instead)."""
    mode = mode or hash_kernels_mode()
    if mode == "pallas" and table_size > _PALLAS_MAX_TABLE:
        mode = "jnp"  # table would not fit the single-step VMEM grid
    if mode in ("pallas", "interpret"):
        keys = jnp.stack([im.astype(jnp.uint64) for im in images])
        return _hash_build_pallas(keys, valid, table_size,
                                  mode == "interpret")
    return _hash_build_jnp(images, valid, table_size)


def hash_table_probe(table: jnp.ndarray, counts: jnp.ndarray, images,
                     valid: jnp.ndarray, table_size: int,
                     mode: Optional[str] = None) -> jnp.ndarray:
    """Slot of each probe row's key, or table_size when absent/invalid."""
    mode = mode or hash_kernels_mode()
    if mode == "pallas" and table_size > _PALLAS_MAX_TABLE:
        mode = "jnp"  # match hash_table_build's routing
    if mode in ("pallas", "interpret"):
        keys = jnp.stack([im.astype(jnp.uint64) for im in images])
        return _hash_probe_pallas(table, counts, keys, valid, table_size,
                                  mode == "interpret")
    return _hash_probe_jnp(table, counts, images, valid, table_size)


def hash_join_probe(build_images, build_valid: jnp.ndarray,
                    stream_images, stream_valid: jnp.ndarray,
                    table_size: int, mode: Optional[str] = None):
    """Hash-table join probe with the (counts, bstart, bperm) contract of
    ops/joins.join_probe: counts[i] build matches of stream row i,
    bstart[i] the first slot of its match group in bperm, bperm grouping
    build rows by key (dead rows last). Replaces the union lexsort over
    both sides' key images with one table build + O(1) probes; the only
    ordering work left is placing build rows contiguously per group —
    the sequential kernel derives that from arrival ranks, the jnp twin
    from a single int32 sort of the build side only."""
    mode = mode or hash_kernels_mode()
    nb = build_valid.shape[0]
    T = table_size
    slot_b, rank, table, counts_t = hash_table_build(
        build_images, build_valid, T, mode=mode)
    starts = jnp.cumsum(counts_t) - counts_t
    if rank is not None:
        live_total = counts_t.sum().astype(jnp.int32)
        rows = jnp.arange(nb, dtype=jnp.int32)
        dead = ~build_valid
        dead_i = dead.astype(jnp.int32)
        dead_ex = jnp.cumsum(dead_i) - dead_i
        pos = jnp.where(
            build_valid,
            starts[jnp.clip(slot_b, 0, T - 1)] + rank,
            live_total + dead_ex).astype(jnp.int32)
        bperm = jnp.zeros((nb,), jnp.int32).at[pos].set(rows)
    else:
        off_key = jnp.where(build_valid, slot_b, T).astype(jnp.int32)
        _off, bperm = jax.lax.sort(
            (off_key, jnp.arange(nb, dtype=jnp.int32)), num_keys=1,
            is_stable=True)
    slot_s = hash_table_probe(table, counts_t, stream_images,
                              stream_valid, T, mode=mode)
    hit = slot_s < T
    safe = jnp.clip(slot_s, 0, T - 1)
    bstart = jnp.where(hit, starts[safe], 0).astype(jnp.int32)
    counts = jnp.where(hit, counts_t[safe], 0).astype(jnp.int32)
    return counts, bstart, bperm


# ---------------------------------------------------------------------------
# Grouped hash AGGREGATION: slot table with in-kernel accumulators
# ---------------------------------------------------------------------------
#
# hash_join_probe/hash_group_ids only assign groups; every reduction still
# ran as a separate segment sweep downstream. This kernel is the cuDF
# groupby shape the reference actually calls (single-pass open-addressing
# aggregation, PAPER.md L3): each row claims (or joins) its key's slot and
# folds its value into per-slot accumulators IN THE SAME probe — one pass
# over the rows, no sort, no segment scan, no per-reduction re-walk.
#
# Job contract (normalized by the caller, ops/aggregate.py): every engine
# reduction kind lowers to one of THREE accumulator kinds over
# (data, eligible) pairs —
#   'sum'  acc += data            where eligible
#   'min'  acc  = min(acc, data)  where eligible (first eligible seeds)
#   'max'  acc  = max(acc, data)  where eligible
# count = sum over ones, first/last = min/max over the row-position
# vector, any = max over the 0/1 value. Each job also counts its eligible
# rows (n_eligible), which doubles as the accumulator-validity flag —
# acc is UNDEFINED where n_eligible == 0 (the pallas kernel leaves the
# zero init, the jnp twin the segment-op neutral; callers must mask).


def _hash_agg_kernel(k: int, T: int, kinds, keys_ref, valid_ref, *refs):
    """Sequential insert-and-accumulate: rows fold into the table one at
    a time with the table AND every accumulator in the kernel's output
    refs (single-step grid). Per row: linear-probe to its key's slot
    (claiming an empty one), then update each job's accumulator — the
    whole grouped aggregation in one walk."""
    import jax.experimental.pallas as pl
    nj = len(kinds)
    data_refs = refs[:nj]
    elig_refs = refs[nj:2 * nj]
    tab_ref, cnt_ref, rep_ref = refs[2 * nj:2 * nj + 3]
    acc_refs = refs[2 * nj + 3:2 * nj + 3 + nj]
    nel_refs = refs[2 * nj + 3 + nj:]
    n = valid_ref.shape[1]
    cnt_ref[...] = jnp.zeros((1, T), jnp.int32)
    rep_ref[...] = jnp.zeros((1, T), jnp.int32)
    tab_ref[...] = jnp.zeros((k, T), jnp.uint64)
    for j in range(nj):
        acc_refs[j][...] = jnp.zeros((1, T), acc_refs[j].dtype)
        nel_refs[j][...] = jnp.zeros((1, T), jnp.int32)

    def insert(e, _):
        e = e.astype(jnp.int32)
        v = valid_ref[0, e] != 0
        row_keys = [keys_ref[j, e] for j in range(k)]
        h = jnp.asarray(_HASH_SEED, jnp.uint64)
        from spark_rapids_tpu.ops.hashing import splitmix64
        for kk in row_keys:
            h = splitmix64(h ^ kk)

        def probe_cond(carry):
            _p, _s, code = carry
            return code == 0

        def probe_body(carry):
            p, _s, _code = carry
            s = ((h + p.astype(jnp.uint64)) % jnp.uint64(T)).astype(
                jnp.int32)
            c = cnt_ref[0, s]
            eq = jnp.asarray(True)
            for j in range(k):
                eq = eq & (tab_ref[j, s] == row_keys[j])
            code = jnp.where(c == 0, jnp.int32(1),
                             jnp.where(eq, jnp.int32(2), jnp.int32(0)))
            return p + jnp.int32(1), s, code

        _p, s, code = jax.lax.while_loop(
            probe_cond, probe_body, (jnp.int32(0), jnp.int32(0),
                                     jnp.int32(0)))

        @pl.when(v)
        def _():
            for j in range(k):
                tab_ref[j, s] = row_keys[j]
            c = cnt_ref[0, s]
            rep_old = rep_ref[0, s]
            rep_ref[0, s] = jnp.where(c == 0, e, rep_old)
            cnt_ref[0, s] = c + 1
            # accumulator updates are branch-free (where on loaded
            # values, unconditional store) — nesting pl.when is avoided
            for j, kind in enumerate(kinds):
                el = elig_refs[j][0, e] != 0
                d = data_refs[j][0, e]
                a = acc_refs[j][0, s]
                ne = nel_refs[j][0, s]
                if kind == "sum":
                    upd = a + d
                elif kind == "min":
                    upd = jnp.where(ne == 0, d, jnp.minimum(a, d))
                else:  # max
                    upd = jnp.where(ne == 0, d, jnp.maximum(a, d))
                acc_refs[j][0, s] = jnp.where(el, upd, a)
                nel_refs[j][0, s] = ne + jnp.where(el, 1, 0)
        return 0

    jax.lax.fori_loop(0, n, insert, 0)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _hash_agg_pallas(kinds, dtypes, table_size: int, interpret: bool,
                     keys: jnp.ndarray, valid: jnp.ndarray, datas, eligs):
    import jax.experimental.pallas as pl
    k, n = keys.shape
    T = table_size
    nj = len(kinds)
    ins = [keys, valid.astype(jnp.int32).reshape(1, n)]
    ins += [d.reshape(1, n) for d in datas]
    ins += [e.astype(jnp.int32).reshape(1, n) for e in eligs]
    outs = pl.pallas_call(
        functools.partial(_hash_agg_kernel, k, T, kinds),
        out_shape=(
            [jax.ShapeDtypeStruct((k, T), jnp.uint64),
             jax.ShapeDtypeStruct((1, T), jnp.int32),
             jax.ShapeDtypeStruct((1, T), jnp.int32)]
            + [jax.ShapeDtypeStruct((1, T), dt) for dt in dtypes]
            + [jax.ShapeDtypeStruct((1, T), jnp.int32)
               for _ in range(nj)]),
        interpret=interpret,
    )(*ins)
    _tab, cnt, rep = outs[0], outs[1][0], outs[2][0]
    accs = [o[0] for o in outs[3:3 + nj]]
    nels = [o[0] for o in outs[3 + nj:]]
    return cnt, rep, accs, nels


def _hash_agg_jnp(images, valid: jnp.ndarray, jobs, table_size: int):
    """Vectorized twin: the shared round-claiming build assigns slots,
    then each job is ONE segment op at table width. Accumulator values
    on slots with n_eligible == 0 are the segment-op neutrals (the
    kernel leaves zeros there) — both are in the contract's undefined
    band and masked by callers."""
    T = table_size
    n = valid.shape[0]
    slot, _rank, _tab, counts = _hash_build_jnp(images, valid, T)
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


def _minmax_neutral(dtype, kind: str):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if kind == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if kind == "min" else info.min, dtype)


def _probe_hash_aggregate():
    """Covers the dtypes the engine actually accumulates in (int64 sums,
    float64 sums, int32 selections)."""
    keys = jnp.asarray(np.arange(32) % 5, jnp.uint64).reshape(1, -1)
    valid = jnp.ones((32,), jnp.bool_)
    datas = (jnp.arange(32, dtype=jnp.int64),
             jnp.arange(32, dtype=jnp.float64),
             jnp.arange(32, dtype=jnp.int32))
    _cnt, _rep, accs, _nels = _hash_agg_pallas(
        ("sum", "sum", "min"), (jnp.int64, jnp.float64, jnp.int32), 64,
        False, keys, valid, datas, (valid, valid, valid))
    return accs


def hash_grouped_aggregate(images, valid: jnp.ndarray, jobs,
                           table_size: int, mode: Optional[str] = None):
    """One-pass grouped aggregation over the open-addressing table.

    ``images``: exact uint64 key-image columns (nulls already
    sentineled + validity folded in by the caller); ``valid``: live-row
    mask (dead rows never enter the table); ``jobs``: list of
    (kind, data (n,), eligible (n,) bool) with kind in {sum, min, max}
    (see module contract above).

    Returns slot-space results — (counts (T,) int32 rows per slot,
    rep (T,) int32 first-arrival row per used slot, accs: per-job (T,)
    accumulators, nels: per-job (T,) int32 eligible counts). acc is
    undefined where its nel == 0; the caller compacts used slots into
    group rows (counts > 0) and masks by nel."""
    mode = mode or hash_kernels_mode()
    if mode == "pallas" and table_size > _PALLAS_MAX_TABLE:
        mode = "jnp"  # table would not fit the single-step VMEM grid
    if mode == "pallas":
        require_kernels("hash_aggregate")
    if mode in ("pallas", "interpret"):
        keys = jnp.stack([im.astype(jnp.uint64) for im in images])
        kinds = tuple(kind for kind, _d, _e in jobs)
        dts = tuple(jnp.dtype(d.dtype) for _k, d, _e in jobs)
        datas = tuple(d for _k, d, _e in jobs)
        eligs = tuple(e & valid for _k, _d, e in jobs)
        return _hash_agg_pallas(kinds, dts, table_size,
                                mode == "interpret", keys, valid,
                                datas, eligs)
    return _hash_agg_jnp(images, valid, jobs, table_size)


def hash_group_ids(images, valid: jnp.ndarray, table_size: int,
                   mode: Optional[str] = None):
    """Grouped-agg accumulate substrate: dense group id per row from the
    hash table (no sort). Returns (gid[n] int32 (invalid -> -1),
    num_groups int32, rep_rows[n] int32 — rep_rows[g] is the first
    original row of group g for g < num_groups)."""
    mode = mode or hash_kernels_mode()
    n = valid.shape[0]
    T = table_size
    slot, rank, _table, counts_t = hash_table_build(images, valid, T,
                                                    mode=mode)
    used = counts_t > 0
    gid_of_slot = (jnp.cumsum(used.astype(jnp.int32)) - 1).astype(
        jnp.int32)
    safe = jnp.clip(slot, 0, T - 1)
    gid = jnp.where(valid & (slot < T), gid_of_slot[safe], -1)
    num_groups = used.sum().astype(jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    if rank is not None:
        # the kernel's arrival ranks name each group's first row directly
        first = valid & (rank == 0)
        rep_rows = jnp.zeros((n,), jnp.int32).at[
            jnp.where(first, gid, n)].set(rows, mode="drop")
    else:
        first_of_slot = jnp.full((T + 1,), n, jnp.int32).at[
            jnp.where(valid, slot, T)].min(rows)[:T]
        rep_rows = jnp.zeros((n,), jnp.int32).at[
            jnp.where(used, gid_of_slot, n)].set(first_of_slot,
                                                 mode="drop")
    return gid, num_groups, rep_rows


# ---------------------------------------------------------------------------
# Parquet page-decode kernels (device-resident scan path)
# ---------------------------------------------------------------------------
#
# The raw-page scan mode (sql/parquet_raw.py -> ops/parquet_decode.py)
# uploads encoded page bytes as u32 word buffers plus small host-built run
# tables, and these kernels expand them into the engine's device columns.
# Four families:
#
#   hybrid_expand   RLE/bit-packed hybrid -> int32 stream (definition
#                   levels and dictionary indices). The genuinely
#                   sequential part is the run cursor; because every run
#                   covers >= 1 output element the cursor advances at most
#                   one run per element, so the kernel walk is a single
#                   fori_loop with the cursor as carry. The jnp twin finds
#                   each element's run with searchsorted instead.
#   delta_unpack    DELTA_BINARY_PACKED -> int64 stream. Sequential
#                   accumulator carry in the kernel; the twin extracts all
#                   deltas vectorized and takes one cumsum.
#   plain_fixed     PLAIN fixed-width word reassembly (i32/i64/f32/f64/
#                   bool) -- pure re-blocking of the uploaded words.
#   slab_pack       PLAIN byte-array -> PR 11 (cap, stride/8) u64 char
#                   slab, identical packing to columnar.column.np_build_slab.
#
# Bit extraction everywhere uses a u64 window over adjacent u32 words
# ((lo | hi<<32) >> (bit & 31)) so no shift ever reaches 32 on a u32 lane;
# bit widths > 32 are rejected host-side (fallback reason deltaWide).
# Same SPARK_RAPIDS_TPU_PALLAS switch as the other kernels: the jnp twin
# is the default and CI spelling, =interpret runs these kernel bodies on
# CPU, =1 runs them Mosaic-compiled on a TPU (require_kernels).

_BITW_MASK = jnp.uint64(0xFFFFFFFF)


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


def _hybrid_expand_jnp(words, out_start, kind, value, bit_start, bw, n):
    k = jnp.arange(n, dtype=jnp.int32)
    r = jnp.searchsorted(out_start, k, side="right").astype(jnp.int32) - 1
    r = jnp.clip(r, 0, kind.shape[0] - 1)
    bit = bit_start[r] + (k - out_start[r]).astype(jnp.int64) * \
        bw[r].astype(jnp.int64)
    bp = _extract_bits(words, bit, bw[r].astype(jnp.uint64)).astype(
        jnp.int32)
    return jnp.where(kind[r] == 1, bp, value[r])


def _hybrid_expand_kernel(os_ref, kind_ref, val_ref, bs_ref, bw_ref,
                          words_ref, out_ref):
    import jax.experimental.pallas as pl  # noqa: F401 (pattern parity)
    n = out_ref.shape[0]
    top = words_ref.shape[0] - 1

    def body(k, cur):
        # every run covers >= 1 element, so the cursor advances <= 1 here
        cur = jnp.where(os_ref[cur + 1] <= k, cur + 1, cur)
        bw = bw_ref[cur].astype(jnp.uint64)
        bit = bs_ref[cur] + (k - os_ref[cur]).astype(jnp.int64) * \
            bw_ref[cur].astype(jnp.int64)
        bit = jnp.maximum(bit, 0)
        w = jnp.clip((bit >> 5).astype(jnp.int32), 0, top)
        off = (bit & 31).astype(jnp.uint64)
        lo = words_ref[w].astype(jnp.uint64)
        hi = words_ref[jnp.minimum(w + 1, top)].astype(jnp.uint64)
        mask = (jnp.uint64(1) << bw) - jnp.uint64(1)
        bp = (((lo | (hi << jnp.uint64(32))) >> off) & mask).astype(
            jnp.int32)
        out_ref[k] = jnp.where(kind_ref[cur] == 1, bp, val_ref[cur])
        return cur

    jax.lax.fori_loop(0, n, body, jnp.int32(0))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _hybrid_expand_pallas(words, out_start, kind, value, bit_start, bw,
                          n: int, interpret: bool):
    import jax.experimental.pallas as pl
    return pl.pallas_call(
        _hybrid_expand_kernel,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(out_start, kind, value, bit_start, bw, words)


def hybrid_expand(words, out_start, kind, value, bit_start, bw,
                  n: int, mode: Optional[str] = None) -> jnp.ndarray:
    """Expand an RLE/bit-packed hybrid stream to (n,) int32. ``bw`` is a
    per-run int32 bit-width array (multi-page chunks merge pages with
    differing dictionary index widths into one run table)."""
    mode = mode or _mode()
    if mode == "pallas":
        require_kernels("hybrid_expand")
        return _hybrid_expand_pallas(words, out_start, kind, value,
                                     bit_start, bw, n, False)
    if mode == "interpret":
        return _hybrid_expand_pallas(words, out_start, kind, value,
                                     bit_start, bw, n, True)
    return _hybrid_expand_jnp(words, out_start, kind, value, bit_start,
                              bw, n)


def _delta_unpack_jnp(words, out_start, bwid, min_delta, bit_start,
                      first, n):
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


def _delta_unpack_kernel(os_ref, bw_ref, md_ref, bs_ref, words_ref,
                         first_ref, out_ref):
    n = out_ref.shape[0]
    top = words_ref.shape[0] - 1

    def body(k, carry):
        cur, acc = carry
        # miniblocks each hold >= 1 delta -> cursor advances <= 1
        cur = jnp.where((k >= 1) & (os_ref[cur + 1] <= k - 1), cur + 1,
                        cur)
        bw = bw_ref[cur].astype(jnp.uint64)
        bit = bs_ref[cur] + (k - 1 - os_ref[cur]).astype(jnp.int64) * \
            bw_ref[cur].astype(jnp.int64)
        bit = jnp.maximum(bit, 0)
        w = jnp.clip((bit >> 5).astype(jnp.int32), 0, top)
        off = (bit & 31).astype(jnp.uint64)
        lo = words_ref[w].astype(jnp.uint64)
        hi = words_ref[jnp.minimum(w + 1, top)].astype(jnp.uint64)
        mask = (jnp.uint64(1) << bw) - jnp.uint64(1)
        raw = ((lo | (hi << jnp.uint64(32))) >> off) & mask
        delta = raw.astype(jnp.int64) + md_ref[cur]
        acc = jnp.where(k == 0, first_ref[0], acc + delta)
        out_ref[k] = acc
        return cur, acc

    jax.lax.fori_loop(0, n, body, (jnp.int32(0), jnp.int64(0)))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _delta_unpack_pallas(words, out_start, bwid, min_delta, bit_start,
                         first, n: int, interpret: bool):
    import jax.experimental.pallas as pl
    return pl.pallas_call(
        _delta_unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int64),
        interpret=interpret,
    )(out_start, bwid, min_delta, bit_start, words, first)


def delta_unpack(words, out_start, bwid, min_delta, bit_start, first,
                 n: int, mode: Optional[str] = None) -> jnp.ndarray:
    """DELTA_BINARY_PACKED stream -> (n,) int64 values."""
    mode = mode or _mode()
    if mode == "pallas":
        require_kernels("delta_unpack")
        return _delta_unpack_pallas(words, out_start, bwid, min_delta,
                                    bit_start, first, n, False)
    if mode == "interpret":
        return _delta_unpack_pallas(words, out_start, bwid, min_delta,
                                    bit_start, first, n, True)
    return _delta_unpack_jnp(words, out_start, bwid, min_delta,
                             bit_start, first, n)


def _plain_fixed_jnp(words, kind, n):
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


def _plain_fixed_kernel(words_ref, out_ref, *, kind):
    n = out_ref.shape[0]
    w = words_ref[:]
    if kind == "i32":
        out_ref[:] = jax.lax.bitcast_convert_type(w, jnp.int32)[:n]
    elif kind == "f32":
        out_ref[:] = jax.lax.bitcast_convert_type(w, jnp.float32)[:n]
    elif kind == "i64":
        lo = w[0::2].astype(jnp.uint64)
        hi = w[1::2].astype(jnp.uint64)
        out_ref[:] = (lo | (hi << jnp.uint64(32))).astype(jnp.int64)[:n]
    elif kind == "f64":
        lo = w[0::2].astype(jnp.uint64)
        hi = w[1::2].astype(jnp.uint64)
        out_ref[:] = jax.lax.bitcast_convert_type(
            lo | (hi << jnp.uint64(32)), jnp.float64)[:n]
    else:  # bool
        k = jnp.arange(n, dtype=jnp.int32)
        out_ref[:] = ((w[k >> 5] >> (k & 31).astype(jnp.uint32)) & 1) \
            .astype(jnp.bool_)


_PLAIN_DT = {"i32": jnp.int32, "i64": jnp.int64, "f32": jnp.float32,
             "f64": jnp.float64, "bool": jnp.bool_}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _plain_fixed_pallas(words, kind: str, n: int, interpret: bool):
    import jax.experimental.pallas as pl
    return pl.pallas_call(
        functools.partial(_plain_fixed_kernel, kind=kind),
        out_shape=jax.ShapeDtypeStruct((n,), _PLAIN_DT[kind]),
        interpret=interpret,
    )(words)


def plain_fixed(words, kind: str, n: int,
                mode: Optional[str] = None) -> jnp.ndarray:
    """Reassemble a PLAIN fixed-width value stream from uploaded u32
    words. ``kind`` in {i32, i64, f32, f64, bool}. f64 goes through a
    u64 -> f64 bitcast and the TPU has no 64-bit floats to bitcast to
    (ops/floatbits.py) — compiled-pallas mode therefore defers to jnp
    for f64; interpret/jnp are CPU-safe."""
    mode = mode or _mode()
    if mode == "pallas" and kind == "f64":
        mode = "jnp"
    if mode == "pallas":
        require_kernels(f"plain_fixed[{kind}]")
        return _plain_fixed_pallas(words, kind, n, False)
    if mode == "interpret":
        return _plain_fixed_pallas(words, kind, n, True)
    return _plain_fixed_jnp(words, kind, n)


def _slab_pack_jnp(chars_u8, starts, lens, cap: int, stride: int):
    nwords = stride // 8
    bytepos = (jnp.arange(nwords, dtype=jnp.int32)[None, :, None] * 8
               + jnp.arange(8, dtype=jnp.int32)[None, None, :])
    src = starts[:, None, None] + bytepos.astype(jnp.int64)
    src = jnp.clip(src, 0, max(chars_u8.shape[0] - 1, 0))
    byte = jnp.where(bytepos < lens[:, None, None], chars_u8[src], 0)
    # little-endian pack: byte j lands at bit 8*j, matching np_build_slab
    return jax.lax.bitcast_convert_type(byte, jnp.uint64)


def _slab_pack_kernel(chars_ref, starts_ref, lens_ref, out_ref):
    import jax.experimental.pallas as pl
    cap, nwords = out_ref.shape
    shifts = (jnp.arange(8, dtype=jnp.int32) * 8).astype(jnp.uint64)
    offs = jnp.arange(8, dtype=jnp.int32)

    def row(r, _):
        s = starts_ref[r]
        ln = lens_ref[r]

        def word(w, _):
            b = chars_ref[pl.ds(s + w * 8, 8)].astype(jnp.uint64)
            b = jnp.where(w * 8 + offs < ln, b, jnp.uint64(0))
            out_ref[r, w] = (b << shifts).sum()
            return 0

        jax.lax.fori_loop(0, nwords, word, 0)
        return 0

    jax.lax.fori_loop(0, cap, row, 0)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _slab_pack_pallas(chars_u8, starts, lens, cap: int, stride: int,
                      interpret: bool):
    import jax.experimental.pallas as pl
    return pl.pallas_call(
        _slab_pack_kernel,
        out_shape=jax.ShapeDtypeStruct((cap, stride // 8), jnp.uint64),
        interpret=interpret,
    )(chars_u8, starts, lens)


def slab_pack(chars_u8, starts, lens, cap: int, stride: int,
              mode: Optional[str] = None) -> jnp.ndarray:
    """Gather PLAIN byte-array values into a (cap, stride/8) u64 char
    slab (np_build_slab packing: byte j of a row at bit 8*(j%8) of word
    j//8, zero past the row's length; rows with len 0 are all-zero).
    ``starts``/``lens`` must be padded to ``cap`` with 0-length rows and
    ``chars_u8`` padded by >= stride bytes so every 8-byte load lands in
    bounds."""
    mode = mode or _mode()
    if mode == "pallas":
        require_kernels("slab_pack")
        return _slab_pack_pallas(chars_u8, starts, lens, cap, stride,
                                 False)
    if mode == "interpret":
        return _slab_pack_pallas(chars_u8, starts, lens, cap, stride,
                                 True)
    return _slab_pack_jnp(chars_u8, starts, lens, cap, stride)


def _probe_hybrid_expand():
    words = jnp.asarray(np.arange(8, dtype=np.uint32))
    os_ = jnp.asarray(np.array([0, 4, 8], np.int32))
    kind = jnp.asarray(np.array([0, 1], np.uint8))
    val = jnp.asarray(np.array([7, 0], np.int32))
    bs = jnp.asarray(np.array([0, 0], np.int64))
    bw = jnp.asarray(np.array([0, 4], np.int32))
    return _hybrid_expand_pallas(words, os_, kind, val, bs, bw, 8, False)


def _probe_delta_unpack():
    words = jnp.asarray(np.arange(8, dtype=np.uint32))
    os_ = jnp.asarray(np.array([0, np.iinfo(np.int32).max], np.int32))
    bw = jnp.asarray(np.array([4, 0], np.int32))
    md = jnp.asarray(np.array([1, 0], np.int64))
    bs = jnp.asarray(np.array([0, 0], np.int64))
    first = jnp.asarray(np.array([5], np.int64))
    return _delta_unpack_pallas(words, os_, bw, md, bs, first, 8, False)


def _probe_plain_fixed(kind: str):
    words = jnp.asarray(np.arange(16, dtype=np.uint32))
    return _plain_fixed_pallas(words, kind, 8, False)


def _probe_slab_pack():
    chars = jnp.asarray(np.arange(48, dtype=np.uint8))
    starts = jnp.asarray(np.array([0, 5, 0, 0], np.int64))
    lens = jnp.asarray(np.array([5, 11, 0, 0], np.int32))
    return _slab_pack_pallas(chars, starts, lens, 4, 16, False)


# One eager compile probe per kernel family (``require_kernels``): each
# is a tiny concrete run of the family's compiled (non-interpret) entry.
KERNEL_PROBES: Dict[str, Callable] = {
    "compaction": _probe_compaction,
    "hash_table": _probe_hash_table,
    "hash_aggregate": _probe_hash_aggregate,
    "hybrid_expand": _probe_hybrid_expand,
    "delta_unpack": _probe_delta_unpack,
    **{f"plain_fixed[{kind}]": functools.partial(_probe_plain_fixed, kind)
       for kind in ("i32", "f32", "i64", "bool")},
    "slab_pack": _probe_slab_pack,
}
