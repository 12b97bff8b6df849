"""Device-resident Parquet decode: raw pages -> DeviceBatch.

The deviceDecode scan mode (spark.rapids.sql.scan.deviceDecode) splits a
row-group's decode across the two sides of the scan pipeline:

  * ``prepare_rowgroup`` runs ON THE DECODE WORKER (sql/scan_pipeline.py
    pool): reads raw column-chunk bytes (sql/parquet_raw.py), splits and
    decompresses pages, and builds per-column DECODE PLANS — small numpy
    run tables plus the encoded streams viewed as u32 word buffers. Host
    work is byte shuffling plus O(#runs) header parsing; no value is
    decoded on the host. Columns the device path cannot take fall back to
    the classic pyarrow host decode per column (journaled as
    ``scanDeviceFallback`` with a reason, ranked by tools/qualification).
  * ``decode_rowgroup`` runs ON THE CONSUMER THREAD: ships every plan's
    buffers in ONE ``jax.device_put`` (plus the fallback columns' classic
    host buffers) and expands them with the ops/tablekernels decode
    family straight into PR 11's native column forms — dictionary
    codes-only, (cap, stride/8) u64 char slabs, dense fixed-width arrays.

Pages are cached encoded (memory/spill.py EncodedPageCache): a warm
re-scan re-decodes from cached pages — device-resident ones skip even the
upload — and performs zero host file reads.

Encoding coverage and the fallback-reason vocabulary live in
docs/scan_device.md.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.obs.events import EVENTS
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import parquet_raw as praw

_DEV_BYTES = REGISTRY.counter("scan.device.bytesDevice")
_HOST_BYTES = REGISTRY.counter("scan.device.bytesHost")
_DEV_COLS = REGISTRY.counter("scan.device.columns")
_FB_COLS = REGISTRY.counter("scan.device.fallbackColumns")
_DEV_SPLITS = REGISTRY.counter("scan.device.splits")
_HOST_READS = REGISTRY.counter("scan.device.hostReads")
_DEC_TIME = REGISTRY.timer("scan.device.decodeTime")
_HOST_DEC_TIME = REGISTRY.timer("scan.device.hostDecodeTime")
_PREP_TIME = REGISTRY.timer("scan.device.prepTime")

# journal dedup: one scanDeviceFallback event per (path, column, reason)
# — a thousand-row-group scan must not flood the flight ring (the
# per-column counters carry the exact aggregates)
_EMITTED: Dict[Tuple[str, str, str], bool] = {}
_EMITTED_CAP = 1024

_FIXED_KINDS = {"INT32": ("i32", 4), "INT64": ("i64", 8),
                "FLOAT": ("f32", 4), "DOUBLE": ("f64", 8)}

_DICT_ENCODINGS = (praw.ENC_PLAIN_DICTIONARY, praw.ENC_RLE_DICTIONARY)


def _note_fallback(path: str, column: str, reason: str, rg: int) -> None:
    _FB_COLS.add(1)
    key = (path, column, reason)
    if key in _EMITTED:
        return
    if len(_EMITTED) >= _EMITTED_CAP:
        _EMITTED.clear()
    _EMITTED[key] = True
    EVENTS.emit("scanDeviceFallback", column=column, reason=reason,
                path=path, rowGroup=rg)


def _words_u8(parts: List[bytes]) -> Tuple[np.ndarray, List[int]]:
    """Concatenate byte streams into one u32 word buffer (8 pad bytes so
    every u64 window load lands in bounds). Returns (words, per-part
    byte offsets)."""
    offs, total = [], 0
    for p in parts:
        offs.append(total)
        total += len(p)
    buf = b"".join(parts) + b"\0" * (((-total) % 4) + 8)
    return np.frombuffer(buf, np.uint32).copy(), offs


def _pad1(arr: np.ndarray, cap: int, fill=0) -> np.ndarray:
    out = np.full(cap, fill, arr.dtype)
    out[:len(arr)] = arr
    return out


def _pad_run_table(tbl: dict) -> dict:
    """Guard row past the real runs: decode runs at output length = the
    CAPACITY bucket, so the cursor / searchsorted must have somewhere
    sane to land for padding rows (values there are masked anyway)."""
    r = len(tbl["kind"])
    big = np.iinfo(np.int32).max
    return {
        "out_start": np.concatenate(
            [tbl["out_start"], np.asarray([big], np.int32)]),
        "kind": _pad1(tbl["kind"], r + 1),
        "value": _pad1(tbl["value"], r + 1),
        "bit_start": _pad1(tbl["bit_start"], r + 1),
        "bw": _pad1(tbl["bw"], r + 1),
    }


def _count_level_ones(levels: bytes, num_values: int) -> int:
    """Non-null count of a max_def=1 page from its def-level hybrid
    stream, O(#runs) + popcount over bit-packed spans (the format
    zero-pads partial groups, so popcount is exact)."""
    pos = 0
    out = 0
    ones = 0
    while out < num_values and pos < len(levels):
        header, pos = praw._uvarint(levels, pos)
        if header & 1:
            groups = header >> 1
            span = levels[pos:pos + groups]
            pos += groups
            take = min(groups * 8, num_values - out)
            ones += int(np.unpackbits(
                np.frombuffer(span, np.uint8)).sum())
            out += take
        else:
            count = header >> 1
            v = levels[pos] if pos < len(levels) else 0
            pos += 1
            take = min(count, num_values - out)
            if v & 1:
                ones += take
            out += take
    return min(ones, num_values)


class _Unsupported(Exception):
    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _split_page(chunk, page) -> Tuple[Optional[bytes], bytes]:
    if chunk.max_def == 0:
        return None, page.payload
    n = int.from_bytes(page.payload[:4], "little")
    return page.payload[4:4 + n], page.payload[4 + n:]


def _plan_levels(chunk) -> Tuple[dict, List[int], List[bytes]]:
    """(levels plan-part, per-page non-null counts, per-page value
    streams)."""
    nns: List[int] = []
    streams: List[bytes] = []
    if chunk.max_def == 0:
        for pg in chunk.pages:
            nns.append(pg.num_values)
            streams.append(pg.payload)
        return {}, nns, streams
    lv_parts: List[bytes] = []
    tables: List[dict] = []
    for pg in chunk.pages:
        lv, rest = _split_page(chunk, pg)
        streams.append(rest)
        nns.append(_count_level_ones(lv, pg.num_values))
        lv_parts.append(lv)
    words, offs = _words_u8(lv_parts)
    for lv, off, pg in zip(lv_parts, offs, chunk.pages):
        tables.append(praw.hybrid_run_table(lv, 1, pg.num_values,
                                            base_bit=off * 8))
    tbl = _pad_run_table(praw.merge_run_tables(tables))
    return {"lv_words": words, **{f"lv_{k}": v for k, v in tbl.items()}}, \
        nns, streams


def _plan_codes(streams: List[bytes], nns: List[int]) -> dict:
    """Dictionary-index streams ([bw byte][hybrid]) -> merged run
    table + word buffer (cd_*)."""
    bodies = [s[1:] for s in streams]
    words, offs = _words_u8(bodies)
    tables = []
    for s, off, nn in zip(streams, offs, nns):
        bw = s[0] if s else 0
        if bw > 32:
            raise _Unsupported("dictWide")
        t = praw.hybrid_run_table(s[1:], bw, nn, base_bit=off * 8)
        tables.append(t)
    tbl = _pad_run_table(praw.merge_run_tables(tables))
    return {"cd_words": words, **{f"cd_{k}": v for k, v in tbl.items()}}


def plan_column(chunk: "praw.RawColumnChunk", dt, arrow_type,
                blocked: int) -> dict:
    """One column chunk -> decode plan: {"kind", "upload": {name: np
    array}, "meta": {...}}. Raises _Unsupported(reason) when the chunk
    must ride the host path."""
    from spark_rapids_tpu.columnar.batch import bucket_capacity

    if chunk.unsupported:
        raise _Unsupported(chunk.unsupported)
    if chunk.max_rep > 0:
        raise _Unsupported("nested")
    if chunk.max_def > 1:
        raise _Unsupported("defLevels")
    if not chunk.pages:
        raise _Unsupported("empty")
    pt = chunk.physical_type
    encs = {pg.encoding for pg in chunk.pages}
    is_dict = bool(encs & set(_DICT_ENCODINGS))
    if is_dict and not encs <= set(_DICT_ENCODINGS):
        # writer overflowed its dictionary mid-chunk and switched the
        # remaining pages to PLAIN — decodable only column-at-a-time on
        # the host
        raise _Unsupported("mixedEncoding")
    if is_dict and chunk.dict_page is None:
        raise _Unsupported("noDictPage")
    if not is_dict and len(encs) > 1:
        raise _Unsupported("mixedEncoding")
    enc = next(iter(encs))
    lv, nns, streams = _plan_levels(chunk)
    nn_total = sum(nns)
    nv_cap = bucket_capacity(max(nn_total, 1))
    meta = {"n": chunk.num_values, "nn": nn_total,
            "max_def": chunk.max_def, "ts": None, "cast": None}
    upload = dict(lv)
    import pyarrow as pa
    if pa.types.is_timestamp(arrow_type):
        meta["ts"] = arrow_type.unit

    if pt == "BOOLEAN":
        if enc != praw.ENC_PLAIN:
            raise _Unsupported(f"enc:{praw.ENCODING_NAMES.get(enc, enc)}")
        # PLAIN booleans ARE a bit-packed stream: spell each page as one
        # bw=1 bit-packed run and ride the hybrid expander
        words, offs = _words_u8(streams)
        tbl = _pad_run_table({
            "out_start": np.concatenate(
                [np.zeros(1, np.int64), np.cumsum(nns)]).astype(np.int32),
            "kind": np.ones(len(nns), np.uint8),
            "value": np.zeros(len(nns), np.int32),
            "bit_start": np.asarray([o * 8 for o in offs], np.int64),
            "bw": np.ones(len(nns), np.int32),
        })
        upload.update({"cd_words": words,
                       **{f"cd_{k}": v for k, v in tbl.items()}})
        meta["kind"] = "bool"
        return {"kind": "bool", "upload": upload, "meta": meta}

    if pt == "BYTE_ARRAY":
        if not dt.is_string:
            raise _Unsupported("binary")
        if is_dict:
            dvals = praw.parse_plain_byte_array(chunk.dict_page.payload,
                                                chunk.dict_page.num_values)
            return _plan_str_dict(upload, meta, streams, nns, dvals,
                                  blocked)
        if enc != praw.ENC_PLAIN:
            raise _Unsupported(f"enc:{praw.ENCODING_NAMES.get(enc, enc)}")
        return _plan_str_plain(upload, meta, streams, nn_total, nv_cap,
                               blocked)

    if pt not in _FIXED_KINDS:
        raise _Unsupported(f"type:{pt}")  # INT96, FLBA
    pkind, isize = _FIXED_KINDS[pt]
    meta["pkind"] = pkind
    if dt.np_dtype is not None and pkind in ("i32", "i64") \
            and dt.np_dtype.itemsize < isize:
        meta["cast"] = dt.np_dtype.str  # int8/int16 stored as INT32

    if is_dict:
        # dictionary page is a PLAIN fixed stream of `card` values:
        # upload it raw, decode it device-side, gather by codes
        card = chunk.dict_page.num_values
        dw, _ = _words_u8([chunk.dict_page.payload])
        if len(chunk.dict_page.payload) < card * isize:
            raise _Unsupported("dictShort")
        upload.update({"dv_words": dw})
        upload.update(_plan_codes(streams, nns))
        meta["card"] = card
        return {"kind": "fixed_dict", "upload": upload, "meta": meta}

    if enc == praw.ENC_DELTA_BINARY_PACKED:
        if pkind not in ("i32", "i64"):
            raise _Unsupported("deltaFloat")
        words, offs = _words_u8(streams)
        pages = []
        for j, (s, off, nn) in enumerate(zip(streams, offs, nns)):
            res = praw.delta_header_table(s, base_bit=off * 8)
            if res is None:
                raise _Unsupported("deltaWide")
            first, _vpm, total, tbl = res
            if total != nn:
                raise _Unsupported("deltaCount")
            guard = {"out_start": np.concatenate(
                [tbl["out_start"],
                 np.asarray([np.iinfo(np.int32).max], np.int32)]),
                "bit_width": _pad1(tbl["bit_width"],
                                   len(tbl["bit_width"]) + 1),
                "min_delta": _pad1(tbl["min_delta"],
                                   len(tbl["min_delta"]) + 1),
                "bit_start": _pad1(tbl["bit_start"],
                                   len(tbl["bit_start"]) + 1)}
            for k, v in guard.items():
                upload[f"d{j}_{k}"] = v
            upload[f"d{j}_first"] = np.asarray([first], np.int64)
            pages.append((j, total))
        upload["dl_words"] = words
        meta["delta_pages"] = pages
        return {"kind": "fixed_delta", "upload": upload, "meta": meta}

    if enc != praw.ENC_PLAIN:
        raise _Unsupported(f"enc:{praw.ENCODING_NAMES.get(enc, enc)}")
    # PLAIN fixed width: the value streams concatenate into one aligned
    # buffer (each page's stream is exactly nn_p * itemsize bytes)
    clipped = [s[:nn * isize] for s, nn in zip(streams, nns)]
    for s, nn in zip(clipped, nns):
        if len(s) != nn * isize:
            raise _Unsupported("levelMismatch")
    words, _ = _words_u8(clipped)
    upload["vals"] = words
    return {"kind": "fixed_plain", "upload": upload, "meta": meta}


def _plan_str_plain(upload: dict, meta: dict, streams: List[bytes],
                    nn_total: int, nv_cap: int, blocked: int) -> dict:
    from spark_rapids_tpu.columnar.column import slab_stride_for
    if blocked <= 0:
        raise _Unsupported("slabOff")
    chars = b"".join(streams)
    starts, lens = praw.plain_byte_array_starts(chars, nn_total)
    max_len = int(lens.max()) if nn_total else 0
    stride = slab_stride_for(max_len, blocked)
    if not stride:
        raise _Unsupported("slabStride")
    pad = np.zeros(((-len(chars)) % 4) + max(stride, 8), np.uint8)
    upload["chars"] = np.concatenate(
        [np.frombuffer(chars, np.uint8), pad])
    upload["st"] = _pad1(starts, nv_cap)
    upload["ln"] = _pad1(lens, nv_cap)
    meta["stride"] = stride
    return {"kind": "str_plain", "upload": upload, "meta": meta}


def _plan_str_dict(upload: dict, meta: dict, streams: List[bytes],
                   nns: List[int], dvals: List[bytes],
                   blocked: int) -> dict:
    """Dictionary string column: codes ride the hybrid expander; the
    page dictionary (canonically sorted, matching host_dict_encode's
    compile-key contract) becomes either the batch dictionary (codes-
    only column) or a host-built char slab the device gathers rows from
    (large-cardinality / NUL-bearing dictionaries)."""
    from spark_rapids_tpu.columnar.column import (
        DICT_MAX_CARD, np_build_slab, slab_stride_for,
    )
    card = len(dvals)
    order = sorted(range(card), key=lambda i: dvals[i])
    remap = np.empty(card + 1, np.int32)
    for rank, i in enumerate(order):
        remap[i] = rank
    remap[card] = card
    svals = [dvals[i] for i in order]
    has_nul = any(b"\0" in v for v in svals)
    try:
        vals_tuple = tuple(v.decode("utf-8") for v in svals)
    except UnicodeDecodeError:
        raise _Unsupported("dictUtf8")
    if sorted(vals_tuple) != list(vals_tuple):
        # bytewise and str sort orders diverge past the BMP; keep the
        # canonical contract by re-sorting in str space
        order2 = sorted(range(card), key=lambda i: vals_tuple[i])
        inv = np.empty(card + 1, np.int32)
        for rank, i in enumerate(order2):
            inv[i] = rank
        inv[card] = card
        remap = inv[remap]
        svals = [svals[i] for i in order2]
        vals_tuple = tuple(vals_tuple[i] for i in order2)
    max_len = max((len(v) for v in svals), default=0)
    stride = slab_stride_for(max_len, blocked) if blocked > 0 else 0
    dict_ok = card <= DICT_MAX_CARD and card > 0 and not has_nul
    if not dict_ok and not stride:
        raise _Unsupported("dictStride")
    if stride:
        dchars = b"".join(svals)
        offs = np.zeros(card + 2, np.int32)
        offs[1:card + 1] = np.cumsum([len(v) for v in svals])
        offs[card + 1] = offs[card]  # zero-length null row at index card
        slab, slens = np_build_slab(
            np.frombuffer(dchars or b"\0", np.uint8), offs, card + 1,
            stride)
        upload["slab"] = slab
        upload["slens"] = slens.astype(np.int32)
        meta["stride"] = stride
    else:
        meta["stride"] = 0
    upload["rm"] = remap
    upload.update(_plan_codes(streams, nns))
    meta["card"] = card
    meta["dict_ok"] = dict_ok
    meta["vals"] = vals_tuple if dict_ok else None
    return {"kind": "str_dict", "upload": upload, "meta": meta}


# ---------------------------------------------------------------------------
# Worker side: RawRowGroup assembly
# ---------------------------------------------------------------------------

class RawRowGroup:
    """Worker-side product of the deviceDecode path: per-column decode
    plans + the host-decoded fallback frame. Flows through the scan
    prefetcher like a DataFrame (``nbytes`` feeds its budget)."""

    is_raw_rowgroup = True

    def __init__(self, path: str, rg: int, pvals: dict, n: int,
                 mtime: Optional[float]):
        self.path = path
        self.rg = rg
        self.pvals = pvals
        self.n = n
        self.mtime = mtime
        self.plans: Dict[str, dict] = {}       # column -> decode plan
        self.cached: Dict[str, bool] = {}      # column -> page-cache hit
        self.fallback: List[Tuple[str, str]] = []
        self.fallback_df = None
        self.stats: Dict[str, Tuple[int, int]] = {}
        self.nbytes = 0

    # generic operator wrappers count split rows through either of these
    @property
    def _host_rows(self) -> int:
        return self.n

    def __len__(self) -> int:
        return self.n


def prepare_rowgroup(path: str, rg: int, pvals: dict, columns: List[str],
                     dtypes_by_name: dict, blocked: int, page_cache=None,
                     direct: bool = True):
    """Build a RawRowGroup on the decode worker. Returns a plain pandas
    DataFrame instead when NO column can ride the device path (the
    consumer then treats the split exactly like a legacy one)."""
    md = praw.file_metadata(path)
    mtime = praw.file_mtime(path)
    rg_meta = md.row_group(rg)
    arrow_schema = md.schema.to_arrow_schema()
    ci_by_name = {rg_meta.column(ci).path_in_schema: ci
                  for ci in range(rg_meta.num_columns)}
    raw = RawRowGroup(path, rg, pvals, int(rg_meta.num_rows), mtime)
    with _PREP_TIME.time():
        for name in columns:
            ci = ci_by_name.get(name)
            if ci is None:
                raw.fallback.append((name, "missing"))
                _note_fallback(path, name, "missing", rg)
                continue
            cache_key = (path, mtime, rg, name)
            hit = page_cache.get(cache_key) if page_cache is not None \
                else None
            if hit is not None:
                raw.plans[name] = hit
                raw.cached[name] = True
                raw.nbytes += hit.get("nbytes", 0)
                continue
            dt = dtypes_by_name[name]
            try:
                chunk = praw.read_column_chunk(path, rg, ci, md=md,
                                               mtime=mtime)
                plan = plan_column(chunk, dt,
                                   arrow_schema.field(name).type, blocked)
            except _Unsupported as e:
                raw.fallback.append((name, e.reason))
                _note_fallback(path, name, e.reason, rg)
                continue
            except Exception:  # noqa: BLE001 — never fail the scan here
                raw.fallback.append((name, "parseError"))
                _note_fallback(path, name, "parseError", rg)
                continue
            plan["nbytes"] = sum(a.nbytes for a in plan["upload"].values())
            raw.plans[name] = plan
            raw.cached[name] = False
            raw.nbytes += plan["nbytes"]
            if page_cache is not None:
                page_cache.put(cache_key, plan, plan["nbytes"])
            # footer min/max seed the advisory stats registry (consumers
            # verify on device before relying on them) — the analogue of
            # note_scan_stats on the pandas path
            if dt.is_integral:
                col = rg_meta.column(ci)
                s = col.statistics
                if s is not None and s.has_min_max \
                        and isinstance(s.min, int) \
                        and isinstance(s.max, int):
                    raw.stats[name] = (int(s.min), int(s.max))
    if raw.fallback:
        fb_cols = [name for name, _ in raw.fallback]
        import pyarrow.parquet as pq

        from spark_rapids_tpu.sql.sources import (
            _arrow_decode, _attach_dict_hints,
        )
        with _HOST_DEC_TIME.time():
            table = pq.ParquetFile(path).read_row_group(rg,
                                                        columns=fb_cols)
            df = _arrow_decode(table, direct)
            df = _attach_dict_hints(df, table)
        _HOST_READS.add(1)
        _HOST_BYTES.add(int(df.memory_usage(deep=False).sum()))
        raw.fallback_df = df
        raw.nbytes += int(df.memory_usage(deep=False).sum())
    if not raw.plans and columns:
        # nothing rides the device path: hand back the classic frame
        return raw.fallback_df if raw.fallback_df is not None else None
    return raw


# ---------------------------------------------------------------------------
# Consumer side: plans -> DeviceBatch
# ---------------------------------------------------------------------------

def _decode_levels(up, meta, cap: int, n: int):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import tablekernels as tk
    row_mask = jnp.arange(cap, dtype=jnp.int32) < n
    if meta["max_def"] == 0 or "lv_words" not in up:
        return row_mask
    levels = tk.hybrid_expand(up["lv_words"], up["lv_out_start"],
                              up["lv_kind"], up["lv_value"],
                              up["lv_bit_start"], up["lv_bw"], cap)
    return (levels == meta["max_def"]) & row_mask


def _value_positions(validity):
    import jax.numpy as jnp
    pos = jnp.cumsum(validity.astype(jnp.int32)) - 1
    return jnp.maximum(pos, 0)


def _gather_rows(vals_v, validity, fill):
    """Value-space stream -> row space: non-null row k takes value
    cumsum(validity)[k]-1, null rows take the canonical fill."""
    import jax.numpy as jnp
    idx = jnp.clip(_value_positions(validity), 0,
                   max(vals_v.shape[0] - 1, 0))
    return jnp.where(validity, vals_v[idx], fill)


def _apply_ts(vals, unit):
    import jax.numpy as jnp
    if unit in (None, "us"):
        return vals
    if unit == "ms":
        return vals * jnp.int64(1000)
    if unit == "s":
        return vals * jnp.int64(1000000)
    return vals // jnp.int64(1000)  # ns


def _decode_codes(up, cap_or_n: int):
    from spark_rapids_tpu.ops import tablekernels as tk
    return tk.hybrid_expand(up["cd_words"], up["cd_out_start"],
                            up["cd_kind"], up["cd_value"],
                            up["cd_bit_start"], up["cd_bw"], cap_or_n)


def _decode_column(name: str, plan: dict, up: dict, dt, cap: int,
                   dict_state: Optional[dict], i: int):
    """One uploaded plan -> DeviceColumn (eager jnp dispatch)."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar import dtype as dtypes
    from spark_rapids_tpu.columnar.batch import bucket_capacity
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.ops import tablekernels as tk
    meta = plan["meta"]
    kind = plan["kind"]
    n = meta["n"]
    validity = _decode_levels(up, meta, cap, n)
    fill = dtypes.null_fill_value(dt)

    if kind == "bool":
        nv = bucket_capacity(max(meta["nn"], 1))
        vals_v = _decode_codes(up, nv) != 0
        out = _gather_rows(vals_v, validity, jnp.bool_(False))
        return DeviceColumn(dt, out, validity)

    if kind == "fixed_plain":
        nv = bucket_capacity(max(meta["nn"], 1))
        vals_v = tk.plain_fixed(up["vals"], meta["pkind"], nv)
        return _finish_fixed(dt, vals_v, validity, meta, fill)

    if kind == "fixed_delta":
        parts = []
        for j, total in meta["delta_pages"]:
            parts.append(tk.delta_unpack(
                up["dl_words"], up[f"d{j}_out_start"],
                up[f"d{j}_bit_width"], up[f"d{j}_min_delta"],
                up[f"d{j}_bit_start"], up[f"d{j}_first"], total))
        vals_v = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if meta["pkind"] == "i32":
            vals_v = vals_v.astype(jnp.int32)
        return _finish_fixed(dt, vals_v, validity, meta, fill)

    if kind == "fixed_dict":
        nv = bucket_capacity(max(meta["nn"], 1))
        codes_v = _decode_codes(up, nv)
        dvals = tk.plain_fixed(up["dv_words"], meta["pkind"],
                               max(meta["card"], 1))
        vals_v = dvals[jnp.clip(codes_v, 0, max(meta["card"] - 1, 0))]
        return _finish_fixed(dt, vals_v, validity, meta, fill)

    if kind == "str_plain":
        nv = up["st"].shape[0]
        slab_v = tk.slab_pack(up["chars"], up["st"], up["ln"],
                              nv, meta["stride"])
        idx = jnp.clip(_value_positions(validity), 0, nv - 1)
        slab = jnp.where(validity[:, None], slab_v[idx], jnp.uint64(0))
        lens = jnp.where(validity, up["ln"][idx], 0).astype(jnp.int32)
        return _widen_slab(DeviceColumn, dt, slab, lens, validity,
                           meta["stride"], dict_state, i)

    # str_dict: canonical codes in row space first
    nv = bucket_capacity(max(meta["nn"], 1))
    codes_v = _decode_codes(up, nv)
    canon_v = up["rm"][jnp.clip(codes_v, 0, meta["card"])]
    card = meta["card"]
    idx = jnp.clip(_value_positions(validity), 0, nv - 1)
    codes_row = jnp.where(validity, canon_v[idx], card).astype(jnp.int32)
    use_dict = meta["dict_ok"]
    if use_dict and dict_state is not None:
        st = dict_state.get(i)
        if st is False:
            use_dict = False
        elif st is None:
            dict_state[i] = meta["vals"]
        elif tuple(st) != meta["vals"]:
            # remap into the established dictionary when this page dict
            # is a subset; otherwise close the column for the scan
            held = {v: k for k, v in enumerate(st)}
            if all(v in held for v in meta["vals"]):
                tbl = np.asarray(
                    [held[v] for v in meta["vals"]] + [len(st)], np.int32)
                codes_row = jnp.asarray(tbl)[
                    jnp.clip(codes_row, 0, card)]
                card = len(st)
                return DeviceColumn(dt, None, validity,
                                    dict_codes=codes_row,
                                    dict_values=tuple(st))
            dict_state[i] = False
            use_dict = False
    if use_dict:
        return DeviceColumn(dt, None, validity, dict_codes=codes_row,
                            dict_values=meta["vals"])
    if meta["stride"]:
        rows = jnp.clip(codes_row, 0, card)  # card = the zero null row
        slab = up["slab"][rows]
        lens = jnp.where(validity, up["slens"][rows], 0).astype(jnp.int32)
        return _widen_slab(DeviceColumn, dt, slab, lens, validity,
                           meta["stride"], dict_state, i)
    # dict_ok guaranteed stride>0 when not dict-eligible; reaching here
    # means the scan closed the dictionary and no slab was built — decode
    # through the dictionary host constants (card is small by dict_ok)
    import jax

    from spark_rapids_tpu.columnar.column import np_build_slab
    svals = [v.encode("utf-8") for v in meta["vals"]]
    offs = np.zeros(card + 2, np.int32)
    offs[1:card + 1] = np.cumsum([len(v) for v in svals])
    offs[card + 1] = offs[card]
    stride = 8
    while stride < max((len(v) for v in svals), default=1):
        stride <<= 1
    slab_h, lens_h = np_build_slab(
        np.frombuffer(b"".join(svals) or b"\0", np.uint8), offs,
        card + 1, stride)
    slab_d, lens_d = jax.device_put((slab_h, lens_h))
    rows = jnp.clip(codes_row, 0, card)
    slab = slab_d[rows]
    lens = jnp.where(validity, lens_d[rows], 0).astype(jnp.int32)
    return _widen_slab(DeviceColumn, dt, slab, lens, validity, stride,
                       dict_state, i)


def _finish_fixed(dt, vals_v, validity, meta, fill):
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import DeviceColumn
    out = _gather_rows(vals_v, validity, fill)
    if meta.get("cast"):
        out = out.astype(np.dtype(meta["cast"]))
    if meta.get("ts"):
        out = _apply_ts(out, meta["ts"])
    if dt.np_dtype is not None and out.dtype != dt.np_dtype:
        out = out.astype(dt.np_dtype)
    out = jnp.where(validity, out,
                    jnp.asarray(fill, out.dtype))  # canonical null fill
    return DeviceColumn(dt, out, validity)


def _widen_slab(DeviceColumn, dt, slab, lens, validity, stride: int,
                dict_state: Optional[dict], i: int):
    """Honor the per-scan widen-only stride registry (the from_pandas
    slab contract): later batches pad to the widest stride seen so a
    scan compiles one program shape per widening, not per batch."""
    import jax.numpy as jnp
    if dict_state is not None:
        prev = int(dict_state.get(("slab", i), 0) or 0)
        if prev > stride:
            pad = (prev - stride) // 8
            slab = jnp.pad(slab, ((0, 0), (0, pad)))
            stride = prev
        if prev >= 0:
            dict_state[("slab", i)] = stride
    return DeviceColumn(dt, None, validity, slab64=slab, lens=lens)


def _pkey_buffers(pvals: dict, pkeys, pkey_dtypes, n: int, cap: int):
    """Partition-value scalar columns as classic host buffers."""
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.sql.sources import _infer_partition_value
    out = []
    for k in pkeys:
        dt = pkey_dtypes[k]
        v = _infer_partition_value(pvals[k]) if k in pvals else None
        if v is None:
            vals = (np.empty(n, object) if dt.is_string
                    else np.zeros(n, dt.np_dtype))
            validity = np.zeros(n, np.bool_)
        elif dt.is_string:
            vals = np.full(n, str(v), object)
            validity = np.ones(n, np.bool_)
        else:
            vals = np.full(n, dt.np_dtype.type(v))
            validity = np.ones(n, np.bool_)
        out.append((k, dt,
                    DeviceColumn.build_host_buffers(vals, validity, dt,
                                                    cap)))
    return out


def _fallback_buffers(df, name: str, dt, cap: int):
    from spark_rapids_tpu.columnar.batch import _pandas_to_numpy
    from spark_rapids_tpu.columnar.column import DeviceColumn
    values, validity = _pandas_to_numpy(df[name], dt)
    return DeviceColumn.build_host_buffers(values, validity, dt, cap)


def _slice_col(col, dt, lo: int, m: int, cap2: int):
    """Static device slice of one decoded column into a chunk batch."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import DeviceColumn
    def cut(a, fill=0):
        if a is None:
            return None
        part = a[lo:lo + m]
        if part.shape[0] == cap2:
            return part
        pad_shape = (cap2 - part.shape[0],) + part.shape[1:]
        return jnp.concatenate(
            [part, jnp.full(pad_shape, fill, part.dtype)])
    validity = cut(col.validity, False)
    if col.dict_values is not None and col._data is None:
        return DeviceColumn(dt, None, validity,
                            dict_codes=cut(col.dict_codes,
                                           len(col.dict_values)),
                            dict_values=col.dict_values)
    if col.has_slab:
        return DeviceColumn(dt, None, validity, slab64=cut(col._slab64),
                            lens=cut(col._lens))
    if dt.is_string:
        # packed strings only arise from fallback columns; re-slice via
        # offsets is host work we avoid — keep whole-chars with shifted
        # offsets (chars stay shared, extents stay correct)
        offs = col.offsets[lo:lo + m + 1]
        base = offs[0]
        offs = jnp.concatenate(
            [offs - base,
             jnp.full((cap2 - m,), offs[-1] - base, offs.dtype)])
        return DeviceColumn(dt, col.data, validity, offsets=offs,
                            prefix8=cut(col.prefix8))
    return DeviceColumn(dt, cut(col.data), validity)


def decode_rowgroup(ctx, raw: RawRowGroup, schema, max_rows: int,
                    dict_state: Optional[dict], part_index: int,
                    device=None):
    """Consumer-side: RawRowGroup -> DeviceBatch(es). One device_put for
    every plan buffer + fallback/pkey host buffers, then eager kernel
    decode; row groups larger than ``max_rows`` yield device-sliced
    chunk batches (no extra host work, no syncs)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import (
        DeviceBatch, bucket_capacity,
    )
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.obs import compileledger
    from spark_rapids_tpu.obs.progress import PROGRESS
    from spark_rapids_tpu.obs.syncledger import sync_scope

    session = ctx.session
    page_cache = getattr(session, "page_cache", None) if session else None
    n = raw.n
    cap = bucket_capacity(max(n, 1))
    if session is not None:
        from spark_rapids_tpu.exec.statsutil import note_bounds
        for name, (lo, hi) in raw.stats.items():
            note_bounds(session, name, lo, hi)

    dt_by_name = dict(zip(schema.names, schema.dtypes))
    fb_names = {name for name, _ in raw.fallback}
    pkeys = [nm for nm in schema.names
             if nm not in raw.plans and nm not in fb_names]

    # assemble the single-upload tree: cached-on-device plans are reused
    # as-is; everything else (plan buffers, fallback columns' classic
    # buffers, partition-value scalars) rides ONE device_put
    tree = {}
    reused = {}
    all_cached = bool(raw.plans)
    for name, plan in raw.plans.items():
        key = (raw.path, raw.mtime, raw.rg, name)
        dev = page_cache.get_device(key) if page_cache is not None \
            else None
        if dev is not None:
            reused[name] = dev
            continue
        if not raw.cached.get(name):
            all_cached = False
        tree[name] = plan["upload"]
    fb_tree = {}
    if raw.fallback_df is not None:
        for name, _reason in raw.fallback:
            if name in raw.fallback_df.columns:
                fb_tree[name] = _fallback_buffers(raw.fallback_df, name,
                                                  dt_by_name[name], cap)
    pk_bufs = _pkey_buffers(raw.pvals, pkeys,
                            {k: dt_by_name[k] for k in pkeys}, n, cap) \
        if pkeys else []

    chunk_ms = [] if n <= max_rows else \
        [min(max_rows, n - lo) for lo in range(0, n, max_rows)]
    t0 = time.perf_counter()
    scope_kind = "scan.pagecache" if (all_cached and not fb_tree) \
        else "scan.upload"
    with sync_scope(scope_kind, detail=f"partition={part_index}") as sc:
        dev_tree, dev_fb, dev_pk, num_rows, dev_ms = jax.device_put(
            (tree, fb_tree, [b for _k, _d, b in pk_bufs],
             np.asarray(n, np.int32),
             [np.asarray(m, np.int32) for m in chunk_ms]), device=device)
        up_bytes = sum(
            a.nbytes for up in tree.values() for a in up.values())
        sc.add_bytes(up_bytes)
    compileledger.note_transfer(time.perf_counter() - t0, "h2d")

    # promote freshly uploaded plan buffers into the cache's device tier
    if page_cache is not None:
        for name, up in dev_tree.items():
            key = (raw.path, raw.mtime, raw.rg, name)
            page_cache.promote(key, up, raw.plans[name].get("nbytes", 0))

    enc_bytes = sum(p.get("nbytes", 0) for p in raw.plans.values())
    _DEV_BYTES.add(enc_bytes)
    _DEV_COLS.add(len(raw.plans))
    _DEV_SPLITS.add(1)
    if PROGRESS.enabled:
        PROGRESS.note("scan", deviceColumns=len(raw.plans),
                      hostColumns=len(raw.fallback),
                      deviceBytes=enc_bytes)

    with _DEC_TIME.time():
        cols = []
        for i, name in enumerate(schema.names):
            dt = dt_by_name[name]
            if name in raw.plans:
                up = reused.get(name) or dev_tree[name]
                cols.append(_decode_column(name, raw.plans[name], up, dt,
                                           cap, dict_state, i))
            elif name in fb_tree:
                bufs = dev_fb[name]
                cols.append(DeviceColumn(dt, *bufs))
            else:
                j = [nm for nm, _d, _b in pk_bufs].index(name)
                cols.append(DeviceColumn(dt, *dev_pk[j]))

    if n <= max_rows:
        batch = DeviceBatch(schema, cols, num_rows)
        batch._host_rows = n
        if PROGRESS.enabled:
            PROGRESS.scan_upload(n)
        yield batch
        return
    for j, lo in enumerate(range(0, n, max_rows)):
        m = chunk_ms[j]
        cap2 = bucket_capacity(m)
        ccols = [_slice_col(c, dt, lo, m, cap2)
                 for c, dt in zip(cols, schema.dtypes)]
        batch = DeviceBatch(schema, ccols, dev_ms[j])
        batch._host_rows = m
        if PROGRESS.enabled:
            PROGRESS.scan_upload(m)
        yield batch
