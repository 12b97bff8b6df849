"""Device-resident columnar batches and the host (pandas) twin.

``DeviceBatch`` is the TPU analogue of a Spark ``ColumnarBatch`` of
``GpuColumnVector``s; ``HostBatch`` is the twin used on the CPU side after a
``DeviceToHost`` transition (reference: RapidsHostColumnVector.java).

Capacity bucketing: batches are padded to a bucketed capacity (default
power-of-two) so that the set of XLA programs compiled for any query is
bounded by O(#operators x log(max batch rows)) rather than one per distinct
row count. This replaces cuDF's fully-dynamic shapes (SURVEY.md section 7
hard-part 1/3).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from spark_rapids_tpu.columnar import dtype as dtypes
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtype import DType
from spark_rapids_tpu.obs.syncledger import sync_scope
from spark_rapids_tpu.obs.trace import TRACER

def _host_nbytes(tree) -> int:
    """Bytes landed by a completed device->host fetch (numpy leaves)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += getattr(leaf, "nbytes", 0) or 0
    return total



MIN_CAPACITY = 8


def _start_host_copies_tree(tree) -> None:
    """Issue async device->host copies for every array leaf before a
    blocking ``jax.device_get``: without copies in flight, a multi-array
    fetch serializes one round trip PER ARRAY; with them the whole tree
    lands in about one round trip plus transfer time. Best-effort — a
    backend without the method just skips."""
    for leaf in jax.tree_util.tree_leaves(tree):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is None:
            continue
        try:
            copy()
        except Exception:  # noqa: BLE001 — prefetch is advisory only
            return


def bucket_capacity(n: int, growth: float = 2.0, minimum: int = MIN_CAPACITY) -> int:
    """Smallest capacity bucket >= n. growth=2.0 -> power-of-two buckets.
    growth <= 1 cannot make progress (it would loop forever)."""
    assert growth > 1.0, f"bucket growth must exceed 1.0, got {growth}"
    cap = minimum
    while cap < n:
        cap = int(np.ceil(cap * growth))
    return cap


class Schema:
    """Ordered (name, dtype) pairs."""

    def __init__(self, names: Sequence[str], dtypes_: Sequence[DType]):
        assert len(names) == len(dtypes_)
        self.names: Tuple[str, ...] = tuple(names)
        self.dtypes: Tuple[DType, ...] = tuple(dtypes_)

    def __len__(self) -> int:
        return len(self.names)

    def __eq__(self, other) -> bool:
        return (isinstance(other, Schema) and self.names == other.names
                and self.dtypes == other.dtypes)

    def __hash__(self) -> int:
        return hash((self.names, self.dtypes))

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}: {d}" for n, d in zip(self.names, self.dtypes))
        return f"Schema({cols})"

    def index_of(self, name: str) -> int:
        return self.names.index(name)

    def dtype_of(self, name: str) -> DType:
        return self.dtypes[self.index_of(name)]

    @staticmethod
    def from_pandas(df: pd.DataFrame) -> "Schema":
        names, dts = [], []
        for i, name in enumerate(df.columns):
            names.append(str(name))
            dts.append(_pandas_col_dtype(df.iloc[:, i]))
        return Schema(names, dts)


class SplitAttrs(dict):
    """What a scan's decode worker computed for its split, riding the
    frame's ``attrs``: the dictionary hints (``srt_dict_fact``) and the
    prepared columns (``srt_prepared``). pandas deep-copies ``attrs``
    whenever a frame or a column is derived from another and compares
    them when frames are concatenated; megabytes of arrays would be
    copied at every column access, and compared with an error. So this
    copies as itself and equals only itself: it rides by reference, and
    nothing writes into it. ``nbytes``: what its buffers hold beyond the
    frame's own memory, for the prefetch queue's budget."""

    nbytes = 0

    def __deepcopy__(self, memo):
        return self

    def __eq__(self, other):
        return self is other

    def __ne__(self, other):
        return self is not other


class PreparedColumns(SplitAttrs):
    """{column name: (dtype, data, validity)}: the fixed-width columns of
    one decoded split of ``rows`` rows, already in the device layout
    (column.prepared_fixed_buffers)."""

    def __init__(self, rows: int):
        super().__init__()
        self.rows = rows


def _build_host_columns(df: pd.DataFrame, schema: "Schema", n: int,
                        cap: int, dict_encode: bool,
                        dict_state: Optional[dict], dict_numerics: bool,
                        blocked_chars: int,
                        prepared: Optional[PreparedColumns] = None):
    """The host half of ``DeviceBatch.from_pandas`` (its ``upload.build``
    span): every column's device-layout buffers, dictionary probe and
    char slab. Returns (host_bufs, dict_metas, slab_metas), one entry a
    column, and the span's counts: ``codes_only`` string columns built
    as (validity, codes) alone, ``codes_shipped`` of them that went out
    as the decode worker's own buffers, ``shipped`` fixed-width columns
    that went out as the decode worker prepared them."""
    from spark_rapids_tpu.columnar.column import (
        host_dict_encode_hinted, host_dict_encode_stateful, np_build_slab,
        slab_stride_for,
    )
    from spark_rapids_tpu.obs.metrics import REGISTRY
    # per-column factorize hints precomputed by the scan pipeline's
    # decode workers (sources._attach_dict_hints), keyed by column
    # name: (codes, uniques, ready buffers or None); only trusted when
    # the frame was not re-chunked since
    hints = getattr(df, "attrs", None)
    hints = hints.get("srt_dict_fact") if hints else None
    # build every column's device-layout buffers host-side, then ship
    # the whole batch in ONE device_put (per-buffer uploads each pay
    # their own dispatch)
    host_bufs = []
    dict_metas = []
    slab_metas = []
    codes_only = codes_shipped = shipped = 0
    if prepared is not None and prepared.rows != n:
        prepared = None  # the frame was cut since, like a stale hint
    # positional iteration: join outputs may carry duplicate column names
    for i, dt in enumerate(schema.dtypes):
        name = str(df.columns[i])
        fact = hints.get(name) if hints else None
        if fact is not None and len(fact[0]) != n:
            fact = None
        encode = dict_encode and (dict_numerics or dt.is_string)
        if fact is not None and encode and dt.is_string:
            # hinted string column (the decode worker encoded it and
            # found no NUL byte in its values): encode first, and where
            # the scan's registry accepts, (validity, codes) are the
            # whole column — no object array, chars, offsets or prefix8
            # is built or shipped, as after any concat or exchange
            # (DeviceColumn's codes-only form); where the worker's values
            # are the registry's, its buffers go out untouched
            enc = host_dict_encode_hinted(fact, dt, cap, dict_state, i)
            if enc is not None:
                vpad, codes, vals, as_made = enc
                host_bufs.append((None, vpad, codes))
                dict_metas.append(vals)
                slab_metas.append(0)
                codes_only += 1
                codes_shipped += as_made
                continue
            # the hint does not encode (and has closed the scan's
            # registry, where there is one): asking again gives the same
            # answer, so build unencoded
            encode = False
        prep = prepared.get(name) if prepared else None
        if prep is not None and prep[0] == dt and len(prep[1]) == cap:
            # the decode worker left this column in the device layout:
            # shipped as it is, nothing allocated, copied or scanned (its
            # buffers may be shared, so nothing here writes into them)
            bufs = prep[1:]
            values, validity = bufs[0][:n], bufs[1][:n]
            shipped += 1
        else:
            values, validity = _pandas_to_numpy(df.iloc[:, i], dt)
            bufs = DeviceColumn.build_host_buffers(values, validity, dt,
                                                   cap)
        # ``dict_numerics=False`` (file-scan uploads): only string
        # columns are dictionary-probed — the numeric probe+encode is
        # an element-wise pass per column per batch on the upload hot
        # path, and integer grouping keys ride the dense-key path
        # (spark.rapids.sql.agg.denseKeys) instead of dictionaries
        enc = host_dict_encode_stateful(
            values, validity, dt, cap, dict_state, i,
            fact=fact[:2] if fact is not None else None) \
            if encode else None
        if enc is not None and dt.is_string:
            # only pay the slab scan when a dictionary was actually
            # built (high-cardinality columns already bailed at the
            # probe): NUL-bearing data must not be dictionary-encoded
            # (see string_host_buffers_have_nul)
            from spark_rapids_tpu.columnar.column import (
                string_host_buffers_have_nul,
            )
            if string_host_buffers_have_nul(bufs, n):
                enc = None
                if dict_state is not None:
                    dict_state[i] = False  # close for the whole scan
        if enc is not None:
            codes, vals = enc
            bufs = bufs + (codes,)
            dict_metas.append(vals)
            slab_metas.append(0)
        else:
            dict_metas.append(None)
            stride = 0
            if blocked_chars > 0 and dt.is_string:
                chars_b, _v, offs_b = bufs[0], bufs[1], bufs[2]
                max_len = int((offs_b[1:n + 1] - offs_b[:n]).max()) \
                    if n else 0
                stride = slab_stride_for(max_len, blocked_chars)
                if stride and dict_state is not None:
                    # per-scan stride registry (the slab twin of the
                    # dictionary registry): LATER batches pad to the
                    # widest stride seen so far. A later batch can
                    # still WIDEN the stride (one new program shape),
                    # but strides are pow2-bucketed so churn is
                    # bounded at log2(maxStride/8) widenings per
                    # column per scan
                    prev = int(dict_state.get(("slab", i), 0) or 0)
                    if prev < 0:
                        stride = 0  # column exceeded maxStride earlier
                    else:
                        stride = max(stride, prev)
                        dict_state[("slab", i)] = stride
                if not stride and dict_state is not None \
                        and dt.is_string and blocked_chars > 0:
                    dict_state[("slab", i)] = -1
                if stride:
                    words, lens = np_build_slab(chars_b, offs_b, cap,
                                                stride)
                    bufs = (words, bufs[1], lens)
            slab_metas.append(stride)
        host_bufs.append(bufs)
    REGISTRY.counter("scan.upload.stringColumns").add(
        sum(dt.is_string for dt in schema.dtypes))
    REGISTRY.counter("scan.upload.codesOnlyColumns").add(codes_only)
    REGISTRY.counter("scan.upload.codesShippedColumns").add(codes_shipped)
    REGISTRY.counter("scan.upload.fixedColumns").add(
        sum(not dt.is_string for dt in schema.dtypes))
    REGISTRY.counter("scan.upload.shippedColumns").add(shipped)
    return host_bufs, dict_metas, slab_metas, {
        "codes_only": codes_only, "codes_shipped": codes_shipped,
        "shipped": shipped}


@jax.tree_util.register_pytree_node_class
class DeviceBatch:
    """Columns + a device scalar row count; static capacity.

    ``num_rows`` is an int32 *device scalar* so it can flow through traced
    code (a filter's output count is data, not shape). ``num_rows_host()``
    syncs it to the host when operator orchestration needs the value.
    """

    def __init__(self, schema: Schema, columns: List[DeviceColumn],
                 num_rows: jnp.ndarray):
        self.schema = schema
        self.columns = columns
        self.num_rows = num_rows
        self._host_rows: Optional[int] = None

    def tree_flatten(self):
        return (self.columns, self.num_rows), self.schema

    @classmethod
    def tree_unflatten(cls, schema, children):
        columns, num_rows = children
        return cls(schema, list(columns), num_rows)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.index_of(name)]

    def num_rows_host(self) -> int:
        if self._host_rows is None:
            # fallback sync site: a named call-site scope (if any) wins
            # via sync_scope reentrancy, so this only attributes scalar
            # count fetches nobody wrapped explicitly
            with sync_scope("batch.rowCount", nbytes=4):
                self._host_rows = int(self.num_rows)
        return self._host_rows

    def num_rows_hint(self) -> int:
        """Row-count upper bound WITHOUT a device sync: the exact count if
        already fetched, else the capacity. A scalar device->host fetch
        blocks on everything queued before it, so control-flow that only
        needs an estimate must use this."""
        return self._host_rows if self._host_rows is not None \
            else self.capacity

    def row_mask(self) -> jnp.ndarray:
        """bool (capacity,): True for live rows (the leading num_rows)."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def device_memory_size(self) -> int:
        """Bytes of device storage held by this batch."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self):
            total += leaf.size * leaf.dtype.itemsize
        return total

    def __repr__(self) -> str:
        return (f"DeviceBatch(rows~{self._host_rows}, capacity={self.capacity}, "
                f"schema={self.schema})")

    # --- conversion --------------------------------------------------------
    @staticmethod
    def from_pandas(df: pd.DataFrame, capacity: Optional[int] = None,
                    schema: Optional[Schema] = None,
                    dict_encode: bool = True,
                    dict_state: Optional[dict] = None,
                    dict_numerics: bool = True,
                    blocked_chars: int = 0,
                    device=None,
                    prepared: Optional[PreparedColumns] = None
                    ) -> "DeviceBatch":
        """Host -> device transition (reference: GpuRowToColumnarExec /
        HostColumnarToGpu, GpuRowToColumnarExec.scala:45-502).

        ``dict_encode``: probe each column for low cardinality and attach a
        host-computed dictionary (codes + static values) — the aggregation
        fast path's direct slot addressing rides it (see
        DeviceColumn.dict_codes). ``dict_state``: a mutable per-scan
        registry making every batch of one scan share one dictionary (see
        host_dict_encode_stateful). ``blocked_chars``: when > 0, string
        columns that did NOT dictionary-encode and whose longest row fits
        the given byte stride upload as fixed-stride char SLABS (+lens)
        instead of packed chars+offsets — row movement then rides 2-D
        lane-contiguous row gathers and packed chars only materialize if
        an operator genuinely reads them (spark.rapids.sql.dict.
        blockedChars). ``prepared``: the columns of ``df`` that the scan's
        decode worker left in the device layout (PreparedColumns), which
        the build ships as they are; the caller vouches that ``df`` is
        the worker's frame, row for row."""
        if schema is None:
            schema = Schema.from_pandas(df)
        n = len(df)
        cap = capacity if capacity is not None else bucket_capacity(n)
        with TRACER.span("upload.build", rows=n,
                         columns=len(schema.dtypes)) as sp:
            host_bufs, dict_metas, slab_metas, counts = \
                _build_host_columns(df, schema, n, cap, dict_encode,
                                    dict_state, dict_numerics,
                                    blocked_chars, prepared)
            nbytes = 0
            if sp is not None:
                nbytes = sum(int(getattr(b, "nbytes", 0))
                             for bufs in host_bufs for b in bufs)
                sp.set(bytes=nbytes, **counts)
        # ``device``: explicit placement for sharded scans (mesh execution
        # uploads partition i to mesh device i so data is born distributed)
        with TRACER.span("upload.put", bytes=nbytes):
            dev = jax.device_put((host_bufs, np.asarray(n, np.int32)),
                                 device=device)
        dev_bufs, num_rows = dev
        cols = []
        for dt, bufs, dvals, stride in zip(schema.dtypes, dev_bufs,
                                           dict_metas, slab_metas):
            if dvals is not None:
                cols.append(DeviceColumn(dt, *bufs[:-1], dict_codes=bufs[-1],
                                         dict_values=dvals))
            elif stride:
                words, vpad, lens = bufs
                cols.append(DeviceColumn(dt, None, vpad, slab64=words,
                                         lens=lens))
            else:
                cols.append(DeviceColumn(dt, *bufs))
        batch = DeviceBatch(schema, cols, num_rows)
        batch._host_rows = n
        return batch

    def to_pandas(self) -> pd.DataFrame:
        """Device -> host transition (reference: GpuColumnarToRowExec).
        All column buffers (and the row count) ride one batched
        ``jax.device_get`` — per-buffer fetches pay a full round trip
        each."""
        return DeviceBatch.to_pandas_many([self])[0]

    @staticmethod
    def to_pandas_many(batches: Sequence["DeviceBatch"],
                       fused_fetch_bytes: int = 4 << 20) -> List[pd.DataFrame]:
        """Convert many batches with at most TWO total device->host round
        trips (row counts, then every batch's buffers) — the whole-query
        output fetch of collect() rides this, so the sync count is
        independent of the partition count. When the padded buffers fit
        under ``fused_fetch_bytes`` the counts and full-capacity buffers
        ride ONE round trip instead (and no per-length device slice
        programs need compiling): for small-result collects the round
        trips, not the bytes, are the cost."""
        import jax
        if not batches:
            return []
        need = [b for b in batches if b._host_rows is None]
        total_padded = sum(b.device_memory_size() for b in batches)
        if total_padded <= fused_fetch_bytes:
            # mesh results live on several devices; one jitted pack
            # cannot span them — the multi-array fused fetch handles that
            devs = set()
            for b in batches:
                devs |= getattr(b.num_rows, "devices", set)() \
                    if callable(getattr(b.num_rows, "devices", None)) \
                    else set()
            if len(devs) <= 1:
                return DeviceBatch._to_pandas_packed(batches)
            if need:
                return DeviceBatch._to_pandas_fused(batches)
        if need:
            with sync_scope("batch.fetch", detail="rowCounts",
                            nbytes=4 * len(need)):
                counts = jax.device_get([b.num_rows for b in need])
            for b, c in zip(need, counts):
                b._host_rows = int(c)
        all_views = [[col.device_views(b._host_rows) for col in b.columns]
                     for b in batches]
        _start_host_copies_tree(all_views)
        with sync_scope("batch.fetch", detail="buffers") as sc:
            host = jax.device_get(all_views)
            sc.add_bytes(_host_nbytes(host))
        out: List[pd.DataFrame] = []
        for b, host_cols in zip(batches, host):
            n = b._host_rows
            series: List[pd.Series] = []
            for dt, col, parts in zip(b.schema.dtypes, b.columns, host_cols):
                values, validity = col.numpy_from_host(parts, n)
                series.append(_numpy_to_pandas(values, validity, dt)
                              .reset_index(drop=True))
            if not series:
                out.append(pd.DataFrame(index=range(n)))
                continue
            # positional construction: join outputs may carry duplicate
            # column names (both sides keep their key column, like Spark)
            df = pd.concat(series, axis=1)
            df.columns = list(b.schema.names)
            out.append(df)
        return out

    @staticmethod
    def _to_pandas_packed(batches: Sequence["DeviceBatch"]) -> List[pd.DataFrame]:
        """ONE device buffer for the whole result set: a jitted kernel
        concatenates every batch's row count + column buffers into a
        single uint8 slab, fetched with a single device_get. Even a
        batched multi-array fetch pays per-ARRAY costs, and a small
        query has ~10-50 output arrays. The
        slab layout is derived host-side from the same static structure
        the kernel packs, then sliced into numpy views."""
        import jax
        from spark_rapids_tpu.utils.kernelcache import cached_jit

        # (static) pack plan: mirrors the kernel's segment order. float64
        # data cannot be packed (no f64 bitcast on this stack — see
        # ops/floatbits.py; arithmetic bit extraction is not value-exact
        # for -0.0/NaN/denormals) so it rides as SIDE arrays in the same
        # fetch; everything else lands in one uint8 slab.
        plan = []  # per batch: list of (field, np_dtype, count)
        sig_parts = []
        for b in batches:
            fields = [("rows", np.dtype(np.int32), 1)]
            for col in b.columns:
                if col.dtype.is_string and col.has_slab:
                    cap = int(col.validity.shape[0])
                    w = int(col._slab64.shape[1])
                    fields.append(("slab", np.dtype(np.uint64), cap * w))
                    fields.append(("lens", np.dtype(np.int32), cap))
                    fields.append(("validity", np.dtype(np.uint8), cap))
                elif col.dtype.is_string and col.is_lazy:
                    cap = int(col.validity.shape[0])
                    fields.append(("codes", np.dtype(np.int32), cap))
                    fields.append(("validity", np.dtype(np.uint8), cap))
                elif col.dtype.is_string:
                    cap = int(col.validity.shape[0])
                    fields.append(("chars", np.dtype(np.uint8),
                                   int(col.data.shape[0])))
                    fields.append(("offsets", np.dtype(np.int32), cap + 1))
                    fields.append(("validity", np.dtype(np.uint8), cap))
                else:
                    cap = int(col.validity.shape[0])
                    dt = np.dtype(col.data.dtype)
                    if dt == np.dtype(np.bool_):
                        dt = np.dtype(np.uint8)
                    if dt == np.dtype(np.float64):
                        fields.append(("side", dt, cap))
                    else:
                        fields.append(("data", dt, cap))
                    fields.append(("validity", np.dtype(np.uint8), cap))
            plan.append(fields)
            sig_parts.append(";".join(f"{f}:{d}:{c}" for f, d, c in fields))
        sig = "packfetch|" + "|".join(sig_parts)

        def build():
            def to_bytes(arr):
                if arr.dtype == jnp.bool_:
                    return arr.astype(jnp.uint8)
                if arr.dtype == jnp.uint8:
                    return arr
                if arr.dtype.itemsize == 8:
                    # 64-bit ints: split into u32 words (the x64-rewrite
                    # pass rejects a direct 64->8 bitcast), then to bytes
                    u = arr.astype(jnp.uint64)
                    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
                    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
                    arr = jnp.stack([lo, hi], axis=-1).reshape(-1)
                return jax.lax.bitcast_convert_type(
                    arr, jnp.uint8).reshape(-1)

            def pack(bs):
                segs = []
                sides = []
                for b in bs:
                    segs.append(to_bytes(
                        b.num_rows.astype(jnp.int32).reshape(1)))
                    for col in b.columns:
                        if col.dtype.is_string and col.has_slab:
                            segs.append(to_bytes(
                                col._slab64.reshape(-1)))
                            segs.append(to_bytes(
                                col._lens.astype(jnp.int32)))
                            segs.append(col.validity.astype(jnp.uint8))
                        elif col.dtype.is_string and col.is_lazy:
                            segs.append(to_bytes(
                                col.dict_codes.astype(jnp.int32)))
                            segs.append(col.validity.astype(jnp.uint8))
                        elif col.dtype.is_string:
                            segs.append(col.data)
                            segs.append(to_bytes(
                                col.offsets.astype(jnp.int32)))
                            segs.append(col.validity.astype(jnp.uint8))
                        else:
                            if col.data.dtype == jnp.float64:
                                sides.append(col.data)
                            else:
                                segs.append(to_bytes(col.data))
                            segs.append(col.validity.astype(jnp.uint8))
                return jnp.concatenate(segs), sides
            return jax.jit(pack)

        slab_d, sides_d = cached_jit(sig, build)(list(batches))
        _start_host_copies_tree((slab_d, sides_d))
        with sync_scope("batch.fetch", detail="packed") as sc:
            slab, sides = jax.device_get((slab_d, sides_d))
            sc.add_bytes(_host_nbytes((slab, sides)))
        slab = np.asarray(slab)
        sides = [np.asarray(sd) for sd in sides]
        side_i = 0

        out: List[pd.DataFrame] = []
        off = 0

        def take(dt: np.dtype, count: int):
            nonlocal off
            nb = dt.itemsize * count
            arr = slab[off:off + nb].view(dt)
            off += nb
            return arr

        for b, fields in zip(batches, plan):
            it = iter(fields)
            _f, dt, c = next(it)
            n = int(take(dt, c)[0])
            b._host_rows = n
            series: List[pd.Series] = []
            for col, cdt in zip(b.columns, b.schema.dtypes):
                if cdt.is_string and col.has_slab:
                    w = int(col._slab64.shape[1])
                    # NB: do not name this ``slab`` — that is the outer
                    # fetched byte buffer take() slices from
                    slab_col = take(*next(it)[1:]).reshape(-1, w)
                    lens = take(*next(it)[1:])
                    validity = take(*next(it)[1:]).astype(bool)
                    trimmed = (validity[:n], lens[:n], slab_col[:n])
                elif cdt.is_string and col.is_lazy:
                    codes = take(*next(it)[1:])
                    validity = take(*next(it)[1:]).astype(bool)
                    trimmed = (validity[:n], codes[:n])
                elif cdt.is_string:
                    chars = take(*next(it)[1:])
                    offsets = take(*next(it)[1:])
                    validity = take(*next(it)[1:]).astype(bool)
                    trimmed = (validity[:n], offsets[:n + 1], chars)
                else:
                    field, fdt, fcount = next(it)
                    if field == "side":
                        data = sides[side_i]
                        side_i += 1
                    else:
                        data = take(fdt, fcount)
                    validity = take(*next(it)[1:]).astype(bool)
                    if cdt.np_dtype == np.bool_:
                        data = data.astype(bool)
                    trimmed = (data[:n], validity[:n])
                values, validity = col.numpy_from_host(trimmed, n)
                series.append(_numpy_to_pandas(values, validity, cdt)
                              .reset_index(drop=True))
            if not series:
                out.append(pd.DataFrame(index=range(n)))
                continue
            df = pd.concat(series, axis=1)
            df.columns = list(b.schema.names)
            out.append(df)
        return out

    @staticmethod
    def _to_pandas_fused(batches: Sequence["DeviceBatch"]) -> List[pd.DataFrame]:
        """One device_get of (num_rows + full-capacity buffers) for every
        batch, trimmed to the fetched row counts host-side."""
        import jax

        def views(c):
            # lazy (codes-only) string columns ship codes+validity and
            # decode through their static dictionary on the host —
            # touching .data here would materialize the worst-case char
            # slab on device and ship it to the host. Slab columns
            # ship words+lens and unpack host-side.
            if c.dtype.is_string and c.has_slab:
                return (c.validity, c._lens, c._slab64)
            if c.dtype.is_string and c.is_lazy:
                return (c.validity, c.dict_codes)
            if c.dtype.is_string:
                return (c.data, c.validity, c.offsets)
            return (c.data, c.validity)

        payload = [(b.num_rows, [views(c) for c in b.columns])
                   for b in batches]
        _start_host_copies_tree(payload)
        with sync_scope("batch.fetch", detail="fused") as sc:
            host = jax.device_get(payload)
            sc.add_bytes(_host_nbytes(host))
        out: List[pd.DataFrame] = []
        for b, (count, host_cols) in zip(batches, host):
            n = int(count)
            b._host_rows = n
            series: List[pd.Series] = []
            for dt, col, parts in zip(b.schema.dtypes, b.columns, host_cols):
                if dt.is_string and col.has_slab:
                    validity, lens, slab = (np.asarray(p) for p in parts)
                    trimmed = (validity[:n], lens[:n], slab[:n])
                elif dt.is_string and col.is_lazy:
                    validity, codes = (np.asarray(p) for p in parts)
                    trimmed = (validity[:n], codes[:n])
                elif dt.is_string:
                    chars, validity, offsets = (np.asarray(p) for p in parts)
                    trimmed = (validity[:n], offsets[:n + 1], chars)
                else:
                    data, validity = (np.asarray(p) for p in parts)
                    trimmed = (data[:n], validity[:n])
                values, validity = col.numpy_from_host(trimmed, n)
                series.append(_numpy_to_pandas(values, validity, dt)
                              .reset_index(drop=True))
            if not series:
                out.append(pd.DataFrame(index=range(n)))
                continue
            df = pd.concat(series, axis=1)
            df.columns = list(b.schema.names)
            out.append(df)
        return out

    @staticmethod
    def empty(schema: Schema, capacity: int = MIN_CAPACITY) -> "DeviceBatch":
        cols = []
        for dt in schema.dtypes:
            cols.append(DeviceColumn.from_numpy(
                np.empty(0, dtype=object if dt.is_string else dt.np_dtype),
                None, dt, capacity))
        return DeviceBatch(schema, cols, jnp.asarray(0, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# pandas <-> numpy(+mask) helpers
# ---------------------------------------------------------------------------

def _pandas_col_dtype(s: pd.Series) -> DType:
    dt = s.dtype
    name = str(dt)
    mapping = {
        "boolean": dtypes.BOOL, "bool": dtypes.BOOL,
        "Int8": dtypes.INT8, "int8": dtypes.INT8,
        "Int16": dtypes.INT16, "int16": dtypes.INT16,
        "Int32": dtypes.INT32, "int32": dtypes.INT32,
        "Int64": dtypes.INT64, "int64": dtypes.INT64,
        "Float32": dtypes.FLOAT32, "float32": dtypes.FLOAT32,
        "Float64": dtypes.FLOAT64, "float64": dtypes.FLOAT64,
    }
    if name in mapping:
        return mapping[name]
    if name.startswith("datetime64"):
        # NOTE: logical dates also land here (host convention: dates ride
        # as datetime64 -> micros); the srt_logical_dtype attrs marker
        # tells date-aware consumers (Cast to string) without changing the
        # micros unpack every datetime consumer assumes
        return dtypes.TIMESTAMP_US
    if name in ("object", "str", "string"):
        return dtypes.STRING
    raise TypeError(f"unsupported pandas dtype: {name}")


def _pandas_to_numpy(s: pd.Series, dt: DType) -> Tuple[np.ndarray, np.ndarray]:
    """Null discipline: numpy-backed numeric/bool columns cannot represent
    missing (float NaN is a *value*, like SQL NaN, not NULL) so they are
    all-valid; nullable extension dtypes (Int64/Float64/boolean) use their
    mask; datetime64 NaT and object-column None are NULL."""
    if (not dt.is_string and isinstance(s.dtype, np.dtype)
            and s.dtype.kind in "biuf"):
        validity = np.ones(len(s), dtype=np.bool_)
        return s.to_numpy(dtype=dt.np_dtype), validity
    validity = (~s.isna()).to_numpy(dtype=np.bool_)
    if dt.is_string:
        vals = s.to_numpy(dtype=object)
        if not validity.all():
            vals = vals.copy()
            vals[~validity] = None  # replace NaN placeholders with None
        return vals, validity
    if dt == dtypes.DATE32:
        if str(s.dtype).startswith("datetime64") or str(s.dtype) == "object":
            vals = pd.to_datetime(s).to_numpy(dtype="datetime64[D]")
            return vals.astype(np.int64).astype(np.int32), validity
        return s.to_numpy(dtype=np.int32, na_value=0), validity
    if dt == dtypes.TIMESTAMP_US:
        if str(s.dtype).startswith("datetime64"):
            # already datetime64: unit-cast directly — pd.to_datetime on
            # an existing datetime column pays a should_cache element
            # sweep per batch, pure overhead on the scan upload hot path
            out = s.to_numpy(dtype="datetime64[us]").astype(np.int64)
            if not validity.all():
                out = np.where(validity, out, 0)
            return out, validity
        if str(s.dtype) == "object":
            vals = pd.to_datetime(s).to_numpy(dtype="datetime64[us]")
            out = vals.astype(np.int64)
            out = np.where(validity, out, 0)
            return out, validity
        return s.to_numpy(dtype=np.int64, na_value=0), validity
    fill = dtypes.null_fill_value(dt)
    return s.to_numpy(dtype=dt.np_dtype, na_value=fill), validity


def _numpy_to_pandas(values: np.ndarray, validity: np.ndarray,
                     dt: DType) -> pd.Series:
    has_nulls = not bool(validity.all()) if len(validity) else False
    if dt.is_string:
        s = pd.Series(values, dtype="str")
        return s
    if dt == dtypes.DATE32:
        out = values.astype("datetime64[D]").astype("datetime64[s]")
        s = pd.Series(out)
        if has_nulls:
            s = s.mask(~validity)
        # pandas cannot hold datetime64[D]; mark the logical date type so
        # host dtype dispatch (series_dtype) does not read it as timestamp
        s.attrs["srt_logical_dtype"] = "date32"
        return s
    if dt == dtypes.TIMESTAMP_US:
        out = values.astype("datetime64[us]")
        s = pd.Series(out)
        if has_nulls:
            s = s.mask(~validity)
        return s
    if has_nulls:
        s = pd.Series(values, dtype=dt.pandas_nullable)
        return s.mask(~validity)
    # keep plain numpy dtype when no nulls: fast path and exact CPU parity
    return pd.Series(values)
