"""Device string kernels over the (offsets, chars) layout.

The TPU replacement for cuDF's string kernels (reference call sites:
sql/rapids/stringFunctions.scala, 698 LoC). Patterns used:

  * per-row fixed-length literal compare: a (capacity, m) gather where m is
    the *static* literal length — XLA unrolls/fuses it;
  * variable-length column-vs-column equality: double 64-bit polynomial hash
    (ops/hashing.py) + length equality — fixed-width compare;
  * per-char segment ops (row id of each char via searchsorted on offsets)
    for contains/length/case mapping.

Unicode note: kernels are byte-oriented; case mapping is ASCII-only (cuDF is
also ASCII-limited for some ops). Multi-byte-aware variants are tracked as
incompat.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes
from spark_rapids_tpu.ops import hashing
from spark_rapids_tpu.sql.exprs.core import DevCol, DevScalar, DevValue, EvalContext


def lengths_of(col: DevCol) -> jnp.ndarray:
    return (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)


def _validity(ctx: EvalContext, v: DevValue) -> jnp.ndarray:
    if isinstance(v, DevScalar):
        return jnp.full((ctx.capacity,), v.valid, dtype=jnp.bool_)
    return v.validity


def string_equal_literal(ctx: EvalContext, col: DevCol,
                         lit: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """col == literal. Returns (eq bool vec, validity).

    Fast paths from upload metadata (no char reads): a dictionary-encoded
    column compares int32 codes against the literal's host-resolved code;
    a column carrying prefix8 with a <=8-byte literal compares one u64
    image + the length. The char-gather spelling ( _match_at: a
    (capacity, m) indexed gather) only remains for derived columns."""
    pat = lit.encode("utf-8")
    m = len(pat)
    if getattr(col, "dict_values", None) is not None:
        try:
            code = col.dict_values.index(lit)
        except ValueError:
            return jnp.zeros(col.validity.shape, jnp.bool_), col.validity
        return col.dict_codes == jnp.int32(code), col.validity
    lens = lengths_of(col)
    if m == 0:
        return lens == 0, col.validity
    if getattr(col, "prefix8", None) is not None and m <= 8:
        img = int.from_bytes(pat.ljust(8, b"\0"), "big")
        return ((col.prefix8 == jnp.uint64(img)) & (lens == m),
                col.validity)
    eq = _match_at(col, jnp.asarray(col.offsets[:-1]), pat) & (lens == m)
    return eq, col.validity


def _match_at(col: DevCol, starts: jnp.ndarray, pat: bytes) -> jnp.ndarray:
    """For each row, do the chars starting at ``starts[r]`` equal ``pat``?
    (no length checking; out-of-bounds reads are masked)"""
    m = len(pat)
    nchars = col.data.shape[0]
    idx = starts[:, None].astype(jnp.int32) + jnp.arange(m, dtype=jnp.int32)[None, :]
    in_bounds = idx < nchars
    gathered = col.data[jnp.clip(idx, 0, nchars - 1)]
    patv = jnp.asarray(bytearray(pat), dtype=jnp.uint8)
    return jnp.all((gathered == patv[None, :]) & in_bounds, axis=1)


def _row_of_pos(offsets: jnp.ndarray, k: jnp.ndarray,
                capacity: int) -> jnp.ndarray:
    """Row id of every position in ``k`` (which must be arange(n)): the
    last row r with offsets[r] <= k. O(n) sorted scatter + prefix sum —
    the drop-in replacement for the per-position binary search
    (``searchsorted`` lowers to log(capacity) dependent gather rounds per
    element on TPU; this was the dominant cost of every char-space
    kernel at scale)."""
    n_pos = k.shape[0]
    marks = jnp.zeros((n_pos + 1,), jnp.int32).at[
        jnp.clip(offsets[:capacity].astype(jnp.int32), 0, n_pos)].add(1)
    ids = jnp.cumsum(marks[:n_pos]) - 1
    return jnp.clip(ids, 0, capacity - 1).astype(jnp.int32)


def starts_with(ctx: EvalContext, col: DevCol, lit: str):
    pat = lit.encode("utf-8")
    m = len(pat)
    lens = lengths_of(col)
    if m == 0:
        return jnp.ones((ctx.capacity,), dtype=jnp.bool_), col.validity
    if getattr(col, "prefix8", None) is not None and m <= 8:
        # dense u64 image compare on the upload-computed prefix — no char
        # reads (see string_equal_literal)
        want = int.from_bytes(pat, "big")
        shift = jnp.uint64(8 * (8 - m))
        return (((col.prefix8 >> shift) == jnp.uint64(want)) & (lens >= m),
                col.validity)
    eq = _match_at(col, jnp.asarray(col.offsets[:-1]), pat) & (lens >= m)
    return eq, col.validity


def ends_with(ctx: EvalContext, col: DevCol, lit: str):
    pat = lit.encode("utf-8")
    m = len(pat)
    lens = lengths_of(col)
    if m == 0:
        return jnp.ones((ctx.capacity,), dtype=jnp.bool_), col.validity
    starts = jnp.maximum(col.offsets[1:] - m, 0)
    eq = _match_at(col, starts, pat) & (lens >= m)
    return eq, col.validity


def _pos_match(chars: jnp.ndarray, pat: bytes) -> jnp.ndarray:
    """bool[nchars]: position i matches iff chars[i:i+m] == pat (a match may
    run over a row's end: the caller bounds it by the row's extent)."""
    nchars = chars.shape[0]
    pos_match = jnp.ones((nchars,), dtype=jnp.bool_)
    for j, c in enumerate(pat):
        shifted = jnp.roll(chars, -j) if j else chars
        # mask rolled-around tail
        ok = (jnp.arange(nchars) + j) < nchars
        pos_match = pos_match & (shifted == c) & ok
    return pos_match


def contains(ctx: EvalContext, col: DevCol, lit: str):
    pat = lit.encode("utf-8")
    m = len(pat)
    lens = lengths_of(col)
    if m == 0:
        return jnp.ones((ctx.capacity,), dtype=jnp.bool_), col.validity
    chars = col.data
    nchars = chars.shape[0]
    capacity = ctx.capacity
    pos_match = _pos_match(chars, pat)
    # a match at position p counts for row r iff p >= off[r] and
    # p + m <= off[r+1]; per-row ANY is a prefix-sum range query (two
    # tiny gathers per ROW) instead of per-char row ids + segment_max
    i = jnp.arange(nchars, dtype=jnp.int32)
    total = col.offsets[capacity]
    pm = pos_match & (i < total)
    ps = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(pm.astype(jnp.int32))])
    starts_r = col.offsets[:-1].astype(jnp.int32)
    ends_r = col.offsets[1:].astype(jnp.int32)
    hi = jnp.clip(ends_r - (m - 1), starts_r, nchars)
    cnt = ps[hi] - ps[starts_r]
    return (cnt > 0) & (lens >= m), col.validity


def like_segments(ctx: EvalContext, col: DevCol, head: str,
                  middle: Tuple[str, ...], tail: str):
    """LIKE of literal segments separated by ``%``: ``head%m1%m2...%tail``,
    ``head`` and ``tail`` possibly empty (the pattern starts or ends with
    ``%``). ``head`` is anchored to the row's start and ``tail`` to its end;
    each middle segment is matched at its earliest position at or after
    the end of the one before, which leaves the most room for the rest, so
    a row matches iff this greedy walk ends at or before the tail's start.
    A middle segment costs one pass a byte over the chars, a reversed
    running minimum over them (the earliest match at or after every
    position) and one gather a row."""
    starts = col.offsets[:-1].astype(jnp.int32)
    ends = col.offsets[1:].astype(jnp.int32)
    chars = col.data
    nchars = chars.shape[0]
    hb, tb = head.encode("utf-8"), tail.encode("utf-8")
    ok = (ends - starts) >= (len(hb) + len(tb))
    if hb:
        ok = ok & _match_at(col, starts, hb)
    limit = ends - len(tb)
    if tb:
        ok = ok & _match_at(col, jnp.maximum(limit, 0), tb)
    pos = starts + len(hb)
    i = jnp.arange(nchars, dtype=jnp.int32)
    for seg in middle:
        pat = seg.encode("utf-8")
        nxt = jax.lax.cummin(
            jnp.where(_pos_match(chars, pat), i, jnp.int32(nchars)),
            reverse=True)
        at = nxt[jnp.clip(pos, 0, nchars - 1)]
        # ``pos`` of an empty last row is nchars itself: the clip then
        # reads a match before it, which ``at >= pos`` refuses
        ok = ok & (at >= pos) & (at + len(pat) <= limit)
        pos = at + len(pat)
    return ok & (pos <= limit), col.validity


def string_equal(ctx: EvalContext, lv: DevValue, rv: DevValue):
    """General string equality (column/column or column/literal)."""
    if isinstance(rv, DevScalar) and isinstance(lv, DevCol):
        eq, _ = string_equal_literal(ctx, lv, str(rv.value))
        validity = lv.validity & _validity(ctx, rv)
        return eq, validity
    if isinstance(lv, DevScalar) and isinstance(rv, DevCol):
        eq, _ = string_equal_literal(ctx, rv, str(lv.value))
        validity = rv.validity & _validity(ctx, lv)
        return eq, validity
    if isinstance(lv, DevScalar) and isinstance(rv, DevScalar):
        eq = jnp.full((ctx.capacity,), lv.value == rv.value, dtype=jnp.bool_)
        return eq, _validity(ctx, lv) & _validity(ctx, rv)
    # column vs column: double-hash + length equality. With two independent
    # 64-bit hashes a false positive needs a 2^-128 event.
    lh1, lh2 = hashing.string_poly_hashes(lv.offsets, lv.data, lv.validity)
    rh1, rh2 = hashing.string_poly_hashes(rv.offsets, rv.data, rv.validity)
    eq = (lh1 == rh1) & (lh2 == rh2) & (lengths_of(lv) == lengths_of(rv))
    return eq, lv.validity & rv.validity


def string_compare_literal(ctx: EvalContext, col: DevCol,
                           lit: str) -> jnp.ndarray:
    """Exact per-row lexicographic compare of col vs a literal.
    Returns int8 cmp in {-1, 0, 1} (sign of col <=> lit)."""
    pat = lit.encode("utf-8")
    m = len(pat)
    lens = lengths_of(col)
    starts = col.offsets[:-1].astype(jnp.int32)
    nchars = col.data.shape[0]
    # positions 0..m inclusive: position m catches "col longer than lit".
    # encode past-end as 0, real bytes as byte+1 (same order trick as sort).
    js = jnp.arange(m + 1, dtype=jnp.int32)
    idx = jnp.clip(starts[:, None] + js[None, :], 0, nchars - 1)
    a = jnp.where(js[None, :] < lens[:, None],
                  col.data[idx].astype(jnp.int32) + 1, 0)
    bvals = np.zeros(m + 1, dtype=np.int32)
    bvals[:m] = np.frombuffer(pat, dtype=np.uint8).astype(np.int32) + 1
    diff = a - jnp.asarray(bvals)[None, :]
    nz = diff != 0
    first = jnp.argmax(nz, axis=1)
    val = jnp.take_along_axis(diff, first[:, None], axis=1)[:, 0]
    any_nz = jnp.any(nz, axis=1)
    return jnp.where(any_nz, jnp.sign(val), 0).astype(jnp.int8)


def compare_extents(data_a: jnp.ndarray, sa: jnp.ndarray, la: jnp.ndarray,
                    data_b: jnp.ndarray, sb: jnp.ndarray,
                    lb: jnp.ndarray) -> jnp.ndarray:
    """Exact elementwise lexicographic byte-order compare of string extents
    (starts+lengths into char buffers). Returns int8 cmp in {-1, 0, 1}.
    Chunked 8-bytes-at-a-time while_loop: trip count is
    ceil(longest-undecided-extent/8), shapes all static.

    Past-end positions pack as raw 0x00 (full 8-bit lanes, so a real 0xff
    byte cannot overflow into its neighbour); the prefix-of case where all
    compared bytes tie ('a' vs 'a\\x00') is settled by the final length
    tiebreak, which is exact for raw 0-padding."""
    maxlen = jnp.maximum(la, lb)
    na, nb = data_a.shape[0], data_b.shape[0]

    def pack(data, nchars, starts, lens, k):
        img = jnp.zeros(starts.shape, dtype=jnp.uint64)
        base = (k * 8).astype(jnp.int32)
        for b in range(8):
            pos = base + b
            idx = jnp.clip(starts + pos, 0, nchars - 1)
            byte = jnp.where(pos < lens, data[idx].astype(jnp.uint64),
                             jnp.uint64(0))
            img = (img << jnp.uint64(8)) | byte
        return img

    def cond(state):
        k, cmp, done = state
        live_max = jnp.max(jnp.where(done, 0, maxlen))
        return (k * 8) < live_max

    def body(state):
        k, cmp, done = state
        au = pack(data_a, na, sa, la, k)
        bu = pack(data_b, nb, sb, lb, k)
        newly = (~done) & (au != bu)
        cmp = jnp.where(newly,
                        jnp.where(au < bu, jnp.int8(-1), jnp.int8(1)), cmp)
        done = done | (au != bu)
        return k + 1, cmp, done

    n = sa.shape[0]
    init = (jnp.int32(0), jnp.zeros((n,), jnp.int8),
            jnp.zeros((n,), jnp.bool_))
    _, cmp, done = jax.lax.while_loop(cond, body, init)
    # all compared bytes tied: one string is a 0-padded prefix of the other
    lentie = jnp.sign(la - lb).astype(jnp.int8)
    return jnp.where(done, cmp, lentie)


def compare_rows(col: DevCol, rows_a: jnp.ndarray,
                 rows_b: jnp.ndarray) -> jnp.ndarray:
    """Exact compare of row selections a vs b of one string column."""
    lens = lengths_of(col)
    starts = col.offsets[:-1].astype(jnp.int32)
    return compare_extents(col.data, starts[rows_a], lens[rows_a],
                           col.data, starts[rows_b], lens[rows_b])


def string_compare_columns(lv: DevCol, rv: DevCol) -> jnp.ndarray:
    """Exact per-row lexicographic byte-order compare of two string
    columns. Returns int8 cmp in {-1, 0, 1}."""
    return compare_extents(
        lv.data, lv.offsets[:-1].astype(jnp.int32), lengths_of(lv),
        rv.data, rv.offsets[:-1].astype(jnp.int32), lengths_of(rv))


def string_compare(ctx: EvalContext, lv: DevValue,
                   rv: DevValue) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """General string three-way compare (column/column or column/literal).
    Returns (cmp int8 vec, validity). Exact byte order — the device twin of
    cuDF's string comparator (reference: stringFunctions.scala ordering ops)."""
    validity = _validity(ctx, lv) & _validity(ctx, rv)
    if isinstance(rv, DevScalar) and isinstance(lv, DevCol):
        return string_compare_literal(ctx, lv, str(rv.value)), validity
    if isinstance(lv, DevScalar) and isinstance(rv, DevCol):
        cmp = string_compare_literal(ctx, rv, str(lv.value))
        return (-cmp).astype(jnp.int8), validity
    if isinstance(lv, DevScalar) and isinstance(rv, DevScalar):
        a, b = str(lv.value), str(rv.value)
        c = -1 if a < b else (1 if a > b else 0)
        return jnp.full((ctx.capacity,), c, dtype=jnp.int8), validity
    return string_compare_columns(lv, rv), validity


def upper_ascii(col: DevCol) -> DevCol:
    c = col.data
    is_lower = (c >= 97) & (c <= 122)
    return DevCol(col.dtype, jnp.where(is_lower, c - 32, c), col.validity,
                  col.offsets)


def lower_ascii(col: DevCol) -> DevCol:
    c = col.data
    is_upper = (c >= 65) & (c <= 90)
    return DevCol(col.dtype, jnp.where(is_upper, c + 32, c), col.validity,
                  col.offsets)


def substring(ctx: EvalContext, col: DevCol, pos: int, length: int) -> DevCol:
    """Spark substring: 1-based ``pos``; negative counts from the end;
    ``length`` < 0 means to-the-end. Byte-oriented (ASCII-exact)."""
    lens = lengths_of(col)
    if pos > 0:
        start = jnp.minimum(jnp.asarray(pos - 1, jnp.int32), lens)
    elif pos == 0:
        start = jnp.zeros_like(lens)
    else:
        start = jnp.maximum(lens + pos, 0)
    if length < 0:
        new_len = lens - start
    else:
        new_len = jnp.minimum(jnp.asarray(length, jnp.int32), lens - start)
    new_len = jnp.maximum(new_len, 0)
    return _gather_substrings(ctx, col, col.offsets[:-1] + start, new_len)


def _gather_substrings(ctx: EvalContext, col: DevCol, src_start: jnp.ndarray,
                       new_len: jnp.ndarray) -> DevCol:
    """Build a new string column taking new_len[r] bytes from src_start[r]."""
    capacity = ctx.capacity
    nchars = col.data.shape[0]
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(new_len).astype(jnp.int32)])
    total_new = new_offsets[capacity]
    k = jnp.arange(nchars, dtype=jnp.int32)
    out_row = _row_of_pos(new_offsets, k, capacity)
    src_idx = src_start[out_row].astype(jnp.int32) + (k - new_offsets[out_row])
    gathered = col.data[jnp.clip(src_idx, 0, nchars - 1)]
    new_chars = jnp.where(k < total_new, gathered, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, new_chars, col.validity, new_offsets)


def concat_columns(ctx: EvalContext, cols) -> DevCol:
    """concat(s1, s2, ...): NULL if any input is NULL (Spark semantics)."""
    capacity = ctx.capacity
    lens = [lengths_of(c) for c in cols]
    validity = cols[0].validity
    for c in cols[1:]:
        validity = validity & c.validity
    total_len = sum(lens[1:], lens[0])
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(total_len).astype(jnp.int32)])
    out_cap = sum(int(c.data.shape[0]) for c in cols)
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out_row = _row_of_pos(new_offsets, k, capacity)
    # position within the concatenated row
    rel = k - new_offsets[out_row]
    # walk the parts: select source column and index per char
    out = jnp.zeros((out_cap,), dtype=jnp.uint8)
    part_start = jnp.zeros((capacity,), dtype=jnp.int32)
    for c, ln in zip(cols, lens):
        in_part = (rel >= part_start[out_row]) & (rel < part_start[out_row] + ln[out_row])
        src = c.offsets[:-1][out_row].astype(jnp.int32) + (rel - part_start[out_row])
        nc = c.data.shape[0]
        vals = c.data[jnp.clip(src, 0, nc - 1)]
        out = jnp.where(in_part, vals, out)
        part_start = part_start + ln
    total_new = new_offsets[capacity]
    out = jnp.where(k < total_new, out, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, out, validity, new_offsets)


def select_strings(ctx: EvalContext, cond: jnp.ndarray, a: DevCol,
                   b: DevCol, validity: jnp.ndarray) -> DevCol:
    """Row-wise choice between two string columns (the string kernel behind
    if()/coalesce()): rows where ``cond`` take their bytes from ``a``,
    others from ``b``. Same segment-gather shape as concat_columns."""
    capacity = ctx.capacity
    la, lb = lengths_of(a), lengths_of(b)
    lens = jnp.where(cond, la, lb)
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(lens).astype(jnp.int32)])
    total_new = new_offsets[capacity]
    out_cap = int(a.data.shape[0]) + int(b.data.shape[0])
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out_row = _row_of_pos(new_offsets, k, capacity)
    rel = k - new_offsets[out_row]
    src_a = a.offsets[:-1][out_row].astype(jnp.int32) + rel
    src_b = b.offsets[:-1][out_row].astype(jnp.int32) + rel
    va = a.data[jnp.clip(src_a, 0, a.data.shape[0] - 1)]
    vb = b.data[jnp.clip(src_b, 0, b.data.shape[0] - 1)]
    out = jnp.where(cond[out_row], va, vb)
    out = jnp.where(k < total_new, out, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, out, validity, new_offsets)


def _char_row_ids(col: DevCol, capacity: int) -> jnp.ndarray:
    """Row id owning each char slot (clipped into [0, capacity-1])."""
    nchars = col.data.shape[0]
    i = jnp.arange(nchars, dtype=jnp.int32)
    return _row_of_pos(col.offsets, i, capacity)


def trim(ctx: EvalContext, col: DevCol, chars: str = " \t\r\n",
         left: bool = True, right: bool = True) -> DevCol:
    """trim/ltrim/rtrim of a literal char set (Spark default: spaces; the
    wider default whitespace set matches java.lang.String.trim)."""
    capacity = ctx.capacity
    nchars = col.data.shape[0]
    i = jnp.arange(nchars, dtype=jnp.int32)
    row_ids = _char_row_ids(col, capacity)
    is_trim = jnp.zeros((nchars,), jnp.bool_)
    for ch in chars.encode("utf-8"):
        is_trim = is_trim | (col.data == ch)
    total = col.offsets[capacity]
    live = i < total
    # first / last non-trim char position per row (defaults: empty row)
    non_trim = (~is_trim) & live
    big = jnp.int32(2**30)
    # clamp the segment identities (int32 min/max for empty segments) so
    # the arithmetic below cannot wrap around
    first_keep = jnp.minimum(jax.ops.segment_min(
        jnp.where(non_trim, i, big), row_ids, num_segments=capacity), big)
    last_keep = jnp.maximum(jax.ops.segment_max(
        jnp.where(non_trim, i, -1), row_ids, num_segments=capacity), -1)
    starts = col.offsets[:-1].astype(jnp.int32)
    ends = col.offsets[1:].astype(jnp.int32)
    new_start = jnp.where(left, jnp.minimum(first_keep, ends), starts)
    new_end = jnp.where(right, last_keep + 1, ends)
    # all-trim rows: first_keep=big, last_keep=-1 -> empty
    new_len = jnp.maximum(
        jnp.minimum(new_end, ends) - jnp.maximum(new_start, starts), 0)
    src_start = jnp.maximum(new_start, starts)
    return _gather_substrings(ctx, col, src_start, new_len)


def pad(ctx: EvalContext, col: DevCol, n: int, pad_char: str,
        left: bool) -> DevCol:
    """lpad/rpad to exactly ``n`` bytes (Spark truncates longer strings)."""
    capacity = ctx.capacity
    lens = lengths_of(col)
    out_len = jnp.full((capacity,), n, dtype=jnp.int32)
    new_offsets = jnp.arange(capacity + 1, dtype=jnp.int32) * jnp.int32(n)
    out_cap = max(capacity * n, 1)
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out_row = k // jnp.maximum(n, 1)
    out_row = jnp.clip(out_row, 0, capacity - 1)
    p = k - out_row * n                      # position within the row
    padlen = jnp.maximum(n - lens, 0)
    if left:
        from_src = p >= padlen[out_row]
        src_rel = p - padlen[out_row]
    else:
        from_src = p < lens[out_row]
        src_rel = p
    nchars = col.data.shape[0]
    src_idx = col.offsets[:-1][out_row].astype(jnp.int32) + src_rel
    vals = col.data[jnp.clip(src_idx, 0, max(nchars - 1, 0))]
    pad_byte = pad_char.encode("utf-8")[0] if pad_char else ord(" ")
    out = jnp.where(from_src, vals, jnp.uint8(pad_byte))
    total_new = new_offsets[capacity]
    out = jnp.where(k < total_new, out, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, out, col.validity, new_offsets)


def locate(ctx: EvalContext, col: DevCol, lit: str,
           start_pos: int = 1) -> jnp.ndarray:
    """1-based byte position of the first occurrence of ``lit`` at or after
    ``start_pos``; 0 when absent (Spark locate/instr semantics)."""
    pat = lit.encode("utf-8")
    m = len(pat)
    capacity = ctx.capacity
    lens = lengths_of(col)
    if m == 0:
        return jnp.where(lens >= 0, jnp.int32(max(start_pos, 1)), 0)
    chars = col.data
    nchars = chars.shape[0]
    pos_match = _pos_match(chars, pat)
    i = jnp.arange(nchars, dtype=jnp.int32)
    row_ids = _char_row_ids(col, capacity)
    fits = (i + m) <= col.offsets[row_ids + 1]
    rel = i - col.offsets[:-1][row_ids]
    after = rel >= (start_pos - 1)
    total = col.offsets[capacity]
    big = jnp.int32(2**30)
    cand = jnp.where(pos_match & fits & after & (i < total), rel, big)
    first = jax.ops.segment_min(cand, row_ids, num_segments=capacity)
    return jnp.where(first < big, first + 1, 0).astype(jnp.int32)


def replace_literal(ctx: EvalContext, col: DevCol, search: str,
                    replacement: str) -> DevCol:
    """str_replace with literal search/replacement. Non-overlapping
    leftmost-first matches selected with a short lax.scan over char
    positions, then the output is built with an expansion gather."""
    pat = search.encode("utf-8")
    rep = replacement.encode("utf-8")
    m = len(pat)
    capacity = ctx.capacity
    if m == 0:
        return col
    chars = col.data
    nchars = chars.shape[0]
    pos_match = _pos_match(chars, pat)
    i = jnp.arange(nchars, dtype=jnp.int32)
    row_ids = _char_row_ids(col, capacity)
    fits = (i + m) <= col.offsets[row_ids + 1]
    total = col.offsets[capacity]
    candidate = pos_match & fits & (i < total)

    # greedy leftmost non-overlapping selection: scan position by position,
    # carrying (blocked_until, current_row)
    def step(carry, x):
        blocked_until, = carry
        pos, cand, row_start = x
        fresh = pos >= jnp.maximum(blocked_until, row_start)
        take = cand & fresh
        new_blocked = jnp.where(take, pos + m, blocked_until)
        return (new_blocked,), take
    row_start = col.offsets[:-1][row_ids].astype(jnp.int32)
    (_,), selected = jax.lax.scan(
        step, (jnp.int32(-1),), (i, candidate, row_start))

    delta = len(rep) - m
    sel_i = selected.astype(jnp.int32)
    matches_per_row = jax.ops.segment_sum(sel_i, row_ids,
                                          num_segments=capacity)
    lens = lengths_of(col)
    new_len = lens + matches_per_row * delta
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(new_len).astype(jnp.int32)])

    # source-position -> output-position mapping: each selected match makes
    # the following chars shift by delta and its own m chars map into rep
    shift_after = jnp.cumsum(sel_i) * delta        # includes own match
    # a char at position p is inside a match iff a selected start s has
    # s <= p < s+m
    start_marks = jnp.zeros((nchars + 1,), jnp.int32)
    start_marks = start_marks.at[jnp.clip(i, 0, nchars)].add(sel_i)
    end_marks = jnp.zeros((nchars + 1,), jnp.int32)
    end_marks = end_marks.at[jnp.clip(i + m, 0, nchars)].add(sel_i)
    inside = jnp.cumsum(start_marks - end_marks)[:nchars] > 0

    # output chars built by scatter: passthrough chars go to
    # i + shift_before(i) where shift_before counts earlier matches' delta
    shift_before = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        (jnp.cumsum(sel_i) * delta)[:-1].astype(jnp.int32)])
    out_cap = max(int(nchars + (nchars // max(m, 1) + 1) * max(delta, 0)), 1)
    out = jnp.zeros((out_cap,), jnp.uint8)
    pass_dst = i + shift_before
    keep = (~inside) & (i < total)
    out = out.at[jnp.where(keep, jnp.clip(pass_dst, 0, out_cap - 1),
                           out_cap - 1)].max(
        jnp.where(keep, chars, 0).astype(jnp.uint8), mode="drop")
    # replacement bytes for each selected match
    if len(rep):
        repv = jnp.asarray(bytearray(rep), dtype=jnp.uint8)
        match_dst = i + shift_before   # match start maps to same shifted pos
        for j in range(len(rep)):
            dst = jnp.clip(match_dst + j, 0, out_cap - 1)
            out = out.at[jnp.where(selected, dst, out_cap - 1)].max(
                jnp.where(selected, repv[j], 0).astype(jnp.uint8),
                mode="drop")
    total_new = new_offsets[capacity]
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out = jnp.where(k < total_new, out, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, out, col.validity, new_offsets)


def initcap_ascii(col: DevCol) -> DevCol:
    """Uppercase the first letter of each word, lowercase the rest."""
    c = col.data
    nchars = c.shape[0]
    prev = jnp.roll(c, 1).at[0].set(ord(" "))
    # chars at row starts also begin words
    starts_mask = jnp.zeros((nchars,), jnp.bool_)
    nrows = col.offsets.shape[0] - 1
    starts_mask = starts_mask.at[
        jnp.clip(col.offsets[:-1], 0, max(nchars - 1, 0))].set(True)
    word_start = starts_mask | (prev == ord(" "))
    lowered = jnp.where((c >= 65) & (c <= 90), c + 32, c)
    uppered = jnp.where((c >= 97) & (c <= 122), c - 32, c)
    return DevCol(dtypes.STRING,
                  jnp.where(word_start, uppered, lowered).astype(jnp.uint8),
                  col.validity, col.offsets)


# ---------------------------------------------------------------------------
# numeric <-> string casts (reference: GpuCast.scala:240-877 string arms —
# cuDF renders/parses these on device; same here, with static char bounds)

_POW10_TABLE = np.array([10 ** k for k in range(20)], dtype=np.uint64)


def integral_to_string(ctx: EvalContext, data: jnp.ndarray,
                       validity: jnp.ndarray) -> DevCol:
    """Decimal rendering of an integral/bool-free column. Static char
    bound: 20 digits + sign per row."""
    cap = data.shape[0]
    v = data.astype(jnp.int64)
    neg = v < 0
    # magnitude in uint64 (int64 min safe: -(v+1)+1)
    mag = jnp.where(neg, (-(v + 1)).astype(jnp.uint64) + jnp.uint64(1),
                    v.astype(jnp.uint64))
    pow10 = jnp.asarray(_POW10_TABLE)
    ndig = jnp.ones((cap,), jnp.int32)
    for k in range(1, 20):
        ndig = ndig + (mag >= pow10[k]).astype(jnp.int32)
    lens = jnp.where(validity, ndig + neg.astype(jnp.int32), 0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    out_chars = cap * 21
    k = jnp.arange(out_chars, dtype=jnp.int32)
    row = _row_of_pos(offsets, k, cap)
    pos = k - offsets[row]
    negr = neg[row]
    sign_char = (pos == 0) & negr
    j = pos - negr.astype(jnp.int32)
    exp = jnp.clip(ndig[row] - 1 - j, 0, 19)
    digit = ((mag[row] // pow10[exp]) % jnp.uint64(10)).astype(jnp.uint8)
    ch = jnp.where(sign_char, jnp.uint8(ord("-")),
                   jnp.uint8(ord("0")) + digit)
    total = offsets[cap]
    chars = jnp.where(k < total, ch, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, chars, validity, offsets)


def strings_from_choices(ctx: EvalContext, idx: jnp.ndarray,
                         choices, validity: jnp.ndarray) -> DevCol:
    """Per-row selection from a static list of literal strings (bool
    rendering, month names, ...)."""
    cap = idx.shape[0]
    enc = [str(c).encode("utf-8") for c in choices]
    packed = np.frombuffer(b"".join(enc), np.uint8) if any(enc) else \
        np.zeros(1, np.uint8)
    lit_lens = np.array([len(e) for e in enc], np.int32)
    lit_starts = np.concatenate(
        [[0], np.cumsum(lit_lens)[:-1]]).astype(np.int32)
    ll, ls = jnp.asarray(lit_lens), jnp.asarray(lit_starts)
    pk = jnp.asarray(packed)
    sel = jnp.clip(idx.astype(jnp.int32), 0, len(enc) - 1)
    lens = jnp.where(validity, ll[sel], 0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    out_chars = cap * max(1, int(lit_lens.max()) if len(enc) else 1)
    k = jnp.arange(out_chars, dtype=jnp.int32)
    row = _row_of_pos(offsets, k, cap)
    pos = k - offsets[row]
    src = jnp.clip(ls[sel[row]] + pos, 0, pk.shape[0] - 1)
    total = offsets[cap]
    chars = jnp.where(k < total, pk[src], 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, chars, validity, offsets)


def civil_from_days(days: jnp.ndarray):
    """days-since-epoch -> (year, month, day), Hinnant's civil_from_days
    with floor division (correct for pre-1970)."""
    z = days.astype(jnp.int64) + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def date_to_string(ctx: EvalContext, days: jnp.ndarray,
                   validity: jnp.ndarray) -> DevCol:
    """'yyyy-MM-dd' rendering. Years outside 0..9999 cannot be rendered in
    this fixed format, so those rows become NULL rather than silently
    rendering a clamped wrong year (the host oracle renders 5-digit and
    negative years, so a clamp would diverge from it)."""
    cap = days.shape[0]
    y, m, d = civil_from_days(days)
    validity = validity & (y >= 0) & (y <= 9999)
    y = jnp.clip(y, 0, 9999)
    dash = jnp.full((cap,), ord("-"), jnp.int64)
    zero = jnp.uint8(ord("0"))
    comps = [zero + (y // 1000 % 10).astype(jnp.uint8),
             zero + (y // 100 % 10).astype(jnp.uint8),
             zero + (y // 10 % 10).astype(jnp.uint8),
             zero + (y % 10).astype(jnp.uint8),
             dash.astype(jnp.uint8),
             zero + (m // 10 % 10).astype(jnp.uint8),
             zero + (m % 10).astype(jnp.uint8),
             dash.astype(jnp.uint8),
             zero + (d // 10 % 10).astype(jnp.uint8),
             zero + (d % 10).astype(jnp.uint8)]
    table = jnp.stack(comps, axis=1).reshape(-1)  # (cap*10,)
    lens = jnp.where(validity, 10, 0).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(lens)])
    out_chars = cap * 10
    k = jnp.arange(out_chars, dtype=jnp.int32)
    row = _row_of_pos(offsets, k, cap)
    pos = k - offsets[row]
    ch = table[jnp.clip(row * 10 + pos, 0, cap * 10 - 1)]
    total = offsets[cap]
    chars = jnp.where(k < total, ch, 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, chars, validity, offsets)



def _nonws_span(col: DevCol, capacity: int):
    """(first, last) index of each row's non-whitespace span (sentinels:
    first=2^30, last=-1 for all-whitespace rows), plus the char iota and
    row-id map. Whitespace = the explicit ASCII set " \\t\\n\\r\\v\\f",
    mirrored by the host parsers (cast.py strips the same set)."""
    nchars = col.data.shape[0]
    i = jnp.arange(nchars, dtype=jnp.int32)
    row_ids = _char_row_ids(col, capacity)
    total = col.offsets[capacity]
    live = i < total
    data = col.data
    is_ws = ((data == 32) | (data == 9) | (data == 10) | (data == 13)
             | (data == 11) | (data == 12))
    non_ws = (~is_ws) & live
    big = jnp.int32(2 ** 30)
    first = jnp.minimum(jax.ops.segment_min(
        jnp.where(non_ws, i, big), row_ids, num_segments=capacity), big)
    last = jnp.maximum(jax.ops.segment_max(
        jnp.where(non_ws, i, -1), row_ids, num_segments=capacity), -1)
    return first, last, i, row_ids, live


def string_to_integral(ctx: EvalContext, col: DevCol, dst):
    """Parse decimal strings -> (int64 data, validity). Accepted form:
    optional surrounding ASCII whitespace, optional sign, >=1 integer
    digits, optional '.digits*' tail (truncated) — the same rule as the
    host oracle; anything else (incl. exponent forms) and out-of-range
    values become NULL (non-ANSI)."""
    capacity = ctx.capacity
    nchars = col.data.shape[0]
    data = col.data
    big = jnp.int32(2 ** 30)
    first, last, i, row_ids, live = _nonws_span(col, capacity)
    first_ch = data[jnp.clip(first, 0, nchars - 1)]
    neg = first_ch == ord("-")
    has_sign = neg | (first_ch == ord("+"))
    dstart = first + has_sign.astype(jnp.int32)
    # optional fractional tail: integer digits end before the first '.'
    dot = jnp.minimum(jax.ops.segment_min(
        jnp.where(live & (data == ord(".")) & (i >= dstart[row_ids])
                  & (i <= last[row_ids]), i, big),
        row_ids, num_segments=capacity), big)
    has_dot = dot <= last
    int_end = jnp.where(has_dot, dot - 1, last)
    ndig = int_end - dstart + 1
    is_digit = (data >= 48) & (data <= 57)
    # every char in [dstart, last] must be a digit except the single dot
    checked = live & (i >= dstart[row_ids]) & (i <= last[row_ids])
    ok_char = is_digit | ((data == ord(".")) & (i == dot[row_ids]))
    bad_any = jax.ops.segment_max(
        (checked & ~ok_char).astype(jnp.int32), row_ids,
        num_segments=capacity) > 0
    pow10 = jnp.asarray(_POW10_TABLE)
    in_int = checked & is_digit & (i <= int_end[row_ids])
    weight = jnp.clip(int_end[row_ids] - i, 0, 19)
    contrib = jnp.where(in_int,
                        (data - 48).astype(jnp.uint64) * pow10[weight],
                        jnp.uint64(0))
    mag = jax.ops.segment_sum(contrib, row_ids, num_segments=capacity)
    # magnitude bound counts SIGNIFICANT digits — '0000…001' is one digit
    # no matter how many leading zeros (they contribute nothing to mag)
    sig = jnp.minimum(jax.ops.segment_min(
        jnp.where(in_int & (data != ord("0")), i, big), row_ids,
        num_segments=capacity), big)
    nsig = jnp.where(sig <= int_end, int_end - sig + 1, 0)
    ok = (col.validity & (ndig >= 1) & (nsig <= 19) & ~bad_any)
    lim = jnp.uint64(1) << jnp.uint64(63)
    ok = ok & jnp.where(neg, mag <= lim, mag <= lim - jnp.uint64(1))
    val = mag.astype(jnp.int64)
    val = jnp.where(neg, -val, val)
    info = np.iinfo(dst.np_dtype)
    if info.bits < 64:
        ok = ok & (val >= info.min) & (val <= info.max)
    return val, ok


def string_to_date(ctx: EvalContext, col: DevCol):
    """Parse 'yyyy-MM-dd'-prefixed strings -> (days int32, ok). Matches the
    host rule: strip surrounding whitespace, the first 10 chars must be
    \\d{4}-\\d{2}-\\d{2} (trailing text ignored, like np.datetime64 on
    text[:10] after the host regex); the calendar triple is validated by a
    days_from_civil/civil_from_days roundtrip (month lengths, leap years)."""
    from spark_rapids_tpu.sql.exprs.datetimeexprs import (
        civil_from_days, days_from_civil,
    )
    capacity = ctx.capacity
    nchars = col.data.shape[0]
    data = col.data
    first, last, _i, _row_ids, _live = _nonws_span(col, capacity)
    has10 = (last - first + 1) >= 10
    y, m, d, ymd_ok = _parse_ymd_at(data, nchars, first)
    pat_ok = ymd_ok & has10
    days = days_from_civil(jnp, y.astype(jnp.int64), m.astype(jnp.int64),
                           d.astype(jnp.int64))
    ry, rm, rd = civil_from_days(jnp, days)
    roundtrip = (ry == y) & (rm == m) & (rd == d)
    ok = col.validity & pat_ok & roundtrip
    return days.astype(jnp.int32), ok


def _parse_ymd_at(data: jnp.ndarray, nchars: int, first: jnp.ndarray):
    """Parse \\d{4}-\\d{2}-\\d{2} at per-row offsets. Returns
    (y, m, d, pattern_ok)."""
    ps = first[:, None] + jnp.arange(10, dtype=jnp.int32)[None, :]
    ch = data[jnp.clip(ps, 0, nchars - 1)].astype(jnp.int32)
    digit_pos = np.array([0, 1, 2, 3, 5, 6, 8, 9])
    is_digit = (ch >= 48) & (ch <= 57)
    pat_ok = (jnp.all(is_digit[:, digit_pos], axis=1)
              & (ch[:, 4] == ord("-")) & (ch[:, 7] == ord("-")))
    d10 = ch - 48
    y = d10[:, 0] * 1000 + d10[:, 1] * 100 + d10[:, 2] * 10 + d10[:, 3]
    m = d10[:, 5] * 10 + d10[:, 6]
    d = d10[:, 8] * 10 + d10[:, 9]
    return y, m, d, pat_ok


def string_to_unix_ts(ctx: EvalContext, col: DevCol, with_time: bool):
    """Parse 'yyyy-MM-dd' (with_time=False) or 'yyyy-MM-dd HH:mm:ss'
    strings -> (epoch seconds int64, ok). Whitespace-trimmed EXACT-length
    match (the host twin uses strptime, which rejects trailing text);
    calendar triples roundtrip-validated, time fields range-checked."""
    from spark_rapids_tpu.sql.exprs.datetimeexprs import (
        civil_from_days, days_from_civil,
    )
    capacity = ctx.capacity
    nchars = col.data.shape[0]
    data = col.data
    first, last, _i, _row_ids, _live = _nonws_span(col, capacity)
    want = 19 if with_time else 10
    exact = (last - first + 1) == want
    y, m, d, pat_ok = _parse_ymd_at(data, nchars, first)
    days = days_from_civil(jnp, y.astype(jnp.int64), m.astype(jnp.int64),
                           d.astype(jnp.int64))
    ry, rm, rd = civil_from_days(jnp, days)
    # y >= 1: the host oracle's strptime rejects proleptic year 0
    ok = (col.validity & exact & pat_ok & (y >= 1)
          & (ry == y) & (rm == m) & (rd == d))
    secs = days * 86400
    if with_time:
        ts = first[:, None] + jnp.arange(10, 19, dtype=jnp.int32)[None, :]
        tch = data[jnp.clip(ts, 0, nchars - 1)].astype(jnp.int32)
        tdig = (tch >= 48) & (tch <= 57)
        tpat = (jnp.all(tdig[:, np.array([1, 2, 4, 5, 7, 8])], axis=1)
                & (tch[:, 0] == ord(" ")) & (tch[:, 3] == ord(":"))
                & (tch[:, 6] == ord(":")))
        td = tch - 48
        hh = td[:, 1] * 10 + td[:, 2]
        mi = td[:, 4] * 10 + td[:, 5]
        ss = td[:, 7] * 10 + td[:, 8]
        ok = ok & tpat & (hh < 24) & (mi < 60) & (ss < 60)
        secs = secs + hh.astype(jnp.int64) * 3600 \
            + mi.astype(jnp.int64) * 60 + ss.astype(jnp.int64)
    return secs, ok


# --- round-2 kernel additions (VERDICT r1 item 8 expression breadth) -------

def reverse_string(ctx: EvalContext, col: DevCol) -> DevCol:
    """Byte reversal per row (exact for ASCII, like the case maps)."""
    capacity = ctx.capacity
    lens = lengths_of(col)
    nchars = col.data.shape[0]
    k = jnp.arange(nchars, dtype=jnp.int32)
    row = _char_row_ids(col, capacity)
    rel = k - col.offsets[:-1][row].astype(jnp.int32)
    src = (col.offsets[:-1][row].astype(jnp.int32)
           + (lens[row] - 1 - rel))
    total = col.offsets[capacity]
    out = jnp.where(k < total,
                    col.data[jnp.clip(src, 0, nchars - 1)], 0)
    return DevCol(dtypes.STRING, out.astype(jnp.uint8), col.validity,
                  col.offsets)


def repeat_string(ctx: EvalContext, col: DevCol, n: int) -> DevCol:
    """repeat(str, n): n <= 0 -> empty string."""
    capacity = ctx.capacity
    n = max(int(n), 0)
    lens = lengths_of(col)
    new_len = lens * n
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(new_len).astype(jnp.int32)])
    out_cap = max(int(col.data.shape[0]) * n, 16)
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out_row = _row_of_pos(new_offsets, k, capacity)
    rel = k - new_offsets[out_row]
    safe_len = jnp.maximum(lens[out_row], 1)
    src = (col.offsets[:-1][out_row].astype(jnp.int32) + rel % safe_len)
    nchars = col.data.shape[0]
    total = new_offsets[capacity]
    out = jnp.where(k < total,
                    col.data[jnp.clip(src, 0, nchars - 1)], 0)
    return DevCol(dtypes.STRING, out.astype(jnp.uint8), col.validity,
                  new_offsets)


def ascii_first(ctx: EvalContext, col: DevCol) -> DevCol:
    """ascii(str): code of the first byte, 0 for empty."""
    lens = lengths_of(col)
    nchars = col.data.shape[0]
    first = col.data[jnp.clip(col.offsets[:-1].astype(jnp.int32), 0,
                              max(nchars - 1, 0))]
    data = jnp.where(lens > 0, first.astype(jnp.int32), 0)
    return DevCol(dtypes.INT32, data, col.validity)


def chr_from_int(ctx: EvalContext, data: jnp.ndarray,
                 validity: jnp.ndarray) -> DevCol:
    """chr(n): the character with code n % 256 (negative -> empty string),
    UTF-8 encoded — codes 128..255 emit their two-byte encoding so the
    result decodes exactly like the host's chr()."""
    capacity = ctx.capacity
    code = (data.astype(jnp.int64) % 256).astype(jnp.int32)
    neg = data < 0
    two_byte = (code >= 128) & ~neg
    lens = jnp.where(neg | ~validity, 0,
                     jnp.where(two_byte, 2, 1)).astype(jnp.int32)
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(lens).astype(jnp.int32)])
    out_cap = _char_capacity_for(2 * capacity)
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out_row = _row_of_pos(new_offsets, k, capacity)
    rel = k - new_offsets[out_row]
    c = code[out_row]
    first = jnp.where(two_byte[out_row], 0xC0 | (c >> 6), c)
    second = 0x80 | (c & 0x3F)
    total = new_offsets[capacity]
    out = jnp.where(k < total,
                    jnp.where(rel == 0, first, second), 0).astype(jnp.uint8)
    return DevCol(dtypes.STRING, out, validity, new_offsets)


def _char_capacity_for(capacity: int, minimum: int = 16) -> int:
    cap = minimum
    while cap < capacity:
        cap <<= 1
    return cap


def concat_ws_columns(ctx: EvalContext, sep: str, cols) -> DevCol:
    """concat_ws(sep, s1, s2, ...): joins the NON-NULL parts with sep;
    result is never NULL (all-null row -> empty string) — Spark
    semantics."""
    capacity = ctx.capacity
    sep_bytes = np.frombuffer(sep.encode("utf-8"), dtype=np.uint8)
    sep_arr = jnp.asarray(sep_bytes if len(sep_bytes) else
                          np.zeros(1, np.uint8))
    sep_len = len(sep_bytes)
    # parts: for each input column, an optional separator (when a valid
    # part precedes) then the column's bytes (when valid)
    lens = [lengths_of(c) for c in cols]
    part_lens = []
    any_before = jnp.zeros((capacity,), jnp.bool_)
    for c, ln in zip(cols, lens):
        sep_here = jnp.where(any_before & c.validity, sep_len, 0)
        part_lens.append(sep_here.astype(jnp.int32))
        part_lens.append(jnp.where(c.validity, ln, 0).astype(jnp.int32))
        any_before = any_before | c.validity
    total_len = part_lens[0]
    for pl in part_lens[1:]:
        total_len = total_len + pl
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(total_len).astype(jnp.int32)])
    # worst case: every column valid in every row -> one separator per
    # row per gap, plus every input byte
    out_cap = (sum(int(c.data.shape[0]) for c in cols)
               + sep_len * max(len(cols) - 1, 0) * capacity)
    out_cap = _char_capacity_for(max(out_cap, 16), 16)
    k = jnp.arange(out_cap, dtype=jnp.int32)
    out_row = _row_of_pos(new_offsets, k, capacity)
    rel = k - new_offsets[out_row]
    out = jnp.zeros((out_cap,), dtype=jnp.uint8)
    part_start = jnp.zeros((capacity,), dtype=jnp.int32)
    pi = 0
    for c in cols:
        for is_sep in (True, False):
            pl = part_lens[pi]
            pi += 1
            in_part = ((rel >= part_start[out_row])
                       & (rel < part_start[out_row] + pl[out_row]))
            off = rel - part_start[out_row]
            if is_sep:
                vals = sep_arr[jnp.clip(off, 0, max(sep_len - 1, 0))]
            else:
                src = c.offsets[:-1][out_row].astype(jnp.int32) + off
                nc = c.data.shape[0]
                vals = c.data[jnp.clip(src, 0, nc - 1)]
            out = jnp.where(in_part, vals, out)
            part_start = part_start + pl
    total_new = new_offsets[capacity]
    out = jnp.where(k < total_new, out, 0).astype(jnp.uint8)
    validity = jnp.ones((capacity,), jnp.bool_) & ctx.row_mask
    return DevCol(dtypes.STRING, out, validity, new_offsets)


def translate_string(ctx: EvalContext, col: DevCol, matching: str,
                     replace: str) -> DevCol:
    """translate(str, matching, replace): per-byte mapping; matching bytes
    beyond len(replace) are deleted (Spark semantics, ASCII-exact)."""
    capacity = ctx.capacity
    lut = np.arange(256, dtype=np.int16)
    mb = matching.encode("utf-8")
    rb = replace.encode("utf-8")
    for i, ch in enumerate(mb):
        lut[ch] = rb[i] if i < len(rb) else -1  # -1 = delete
    lut_arr = jnp.asarray(lut)
    nchars = col.data.shape[0]
    mapped = lut_arr[col.data.astype(jnp.int32)]
    k = jnp.arange(nchars, dtype=jnp.int32)
    row = _char_row_ids(col, capacity)
    total = col.offsets[capacity]
    live = (k < total) & (mapped >= 0)
    # stable compaction of surviving chars keeps row-major order
    from spark_rapids_tpu.ops.tablekernels import compact_permutation
    perm, _cnt = compact_permutation(live)
    new_chars = jnp.where(jnp.arange(nchars) <
                          jnp.cumsum(live.astype(jnp.int32))[-1],
                          mapped[perm].astype(jnp.uint8), 0)
    import jax
    keep_per_row = jax.ops.segment_sum(
        jnp.where(live, 1, 0), row, num_segments=capacity)
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(keep_per_row).astype(jnp.int32)])
    return DevCol(dtypes.STRING, new_chars.astype(jnp.uint8), col.validity,
                  new_offsets)
