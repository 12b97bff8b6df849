"""Advisory scan-statistics resolution shared by the dense-key fast
paths (join direct-index probe, bounded-int composite grouping keys).

The session unions each scanned int column's (min, max) into a
name-keyed registry — from a Parquet file's footers when the scan is
planned (TpuScanExec._declare_stats), else batch by batch as the scan
uploads (exec/transitions.note_scan_stats) — and records
rename provenance from the logical plan (session.column_aliases). The
bounds are ADVISORY — every consumer verifies them on device and falls
back to its exact path — so resolution here only needs to be sound
enough to usually hit (the reference's analogue is the cuDF column
min/max the join build reads)."""

from __future__ import annotations

from typing import Optional, Tuple


def note_bounds(session, name: str, lo: int, hi: int) -> None:
    """Union one column's (lo, hi) into the registry."""
    reg = session.column_stats
    prev = reg.get(name)
    if prev is not None:
        lo, hi = min(lo, prev[0]), max(hi, prev[1])
    reg[name] = (lo, hi)


def stats_names(session, names) -> set:
    """The registry entries reachable from any of ``names`` through the
    rename-alias map (walk bounded — alias chains are shallow)."""
    reg = session.column_stats
    amap = session.column_aliases
    names = set(names)
    frontier = set(names)
    for _ in range(8):
        nxt = set()
        for n in frontier:
            nxt |= amap.get(n, set()) - names
        if not nxt:
            break
        names |= nxt
        frontier = nxt
    return {n for n in names if n in reg}


def int_bounds_for_names(session, names) -> Optional[Tuple[int, int]]:
    """Union advisory (lo, hi) over every stats entry reachable from any
    of ``names``. None when nothing resolves."""
    if session is None:
        return None
    reg = session.column_stats
    bounds = [reg[n] for n in stats_names(session, names)]
    if not bounds:
        return None
    return (min(b[0] for b in bounds), max(b[1] for b in bounds))


def _pow2_at_least(n: int) -> int:
    size = 1
    while size < n:
        size <<= 1
    return size


def dense_group_plan(session, key_names, key_dtypes,
                     max_bits: int = 62) -> Optional[Tuple[list, tuple]]:
    """(los, sizes) for a bounded-int composite grouping key
    (ops/aggregate.dense_composite), or None. ``key_names``: per key a
    SET of candidate registry names (output name + source name); dtypes
    must all be fixed-width integers. Sizes bucket to powers of two so
    the kernel-cache key is stable under small data drift."""
    import numpy as np
    los, sizes = [], []
    total = 1
    for names, dt in zip(key_names, key_dtypes):
        npdt = np.dtype(dt.np_dtype)
        if dt.is_string or npdt.kind not in ("i", "u"):
            return None
        b = int_bounds_for_names(session, names)
        if b is None:
            return None
        lo, hi = int(b[0]), int(b[1])
        rng = hi - lo + 1
        if rng <= 0:
            return None
        size = _pow2_at_least(rng)
        total *= size + 1
        if total > (1 << max_bits):
            return None
        los.append(lo)
        sizes.append(size)
    # low-cardinality tuples take the dictionary matmul path anyway
    # (ops/aggregate._dict_path_info, DICT_SLOT_MAX): a dense variant
    # would compile a duplicate program and speculate for nothing
    if total <= 4096:
        return None
    return los, tuple(sizes)
