"""Host<->device transition operators.

The analogues of the reference's GpuRowToColumnarExec / GpuColumnarToRowExec
/ HostColumnarToGpu / GpuBringBackToHost (GpuRowToColumnarExec.scala,
GpuColumnarToRowExec.scala, GpuBringBackToHost.scala). The transition
overrides pass (sql/overrides.py) inserts these at every CPU/TPU boundary.
"""

from __future__ import annotations

from typing import Iterator, List

import pandas as pd

from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, Schema, SplitAttrs,
)
from spark_rapids_tpu.exec.base import ExecContext, Partition, PhysicalPlan


def scan_cache_for(ctx: ExecContext, source, schema: Schema,
                   max_rows: int, pushed_filters=None):
    """Per-source device-batch cache (spark.rapids.sql.cacheDeviceScans),
    or None when disabled. The entry holds a strong reference to the
    source object: keys include id(source), and without the reference a
    GC'd source's id could be reused by a different dataset and serve its
    cached batches. Pushed filters are part of the key: a scan pruned for
    one predicate must not serve a query that needs more row groups.
    Entries live until session.clear_device_cache()."""
    if ctx.session is None or not ctx.conf.get_bool(
            "spark.rapids.sql.cacheDeviceScans", False):
        return None
    store = ctx.session.device_scan_cache
    fkey = tuple(pushed_filters) if pushed_filters else None
    # pruned-column views are fresh objects per query; key on the base
    # source identity so re-executions hit (schema names in the key keep
    # distinct projections apart)
    base = getattr(source, "_base", source)
    key = (id(base), tuple(schema.names), max_rows, fkey)
    if key not in store:
        store[key] = (source, {})
    return store[key][1]


def note_scan_stats(session, df: pd.DataFrame, declared=()) -> None:
    """Union each scanned int column's (min, max) into the session's
    advisory stats registry (session.column_stats). Called ONLY from scan
    uploads (TpuScanExec / HostToDeviceExec-over-scan), so derived columns
    can never seed it; the dense-key join verifies the bounds on device
    before relying on them (exec/tpujoin.py). Columns in ``declared``
    had their bounds read from the file footers when the scan was
    planned (TpuScanExec._declare_stats) and are not measured again."""
    if session is None:
        return
    from spark_rapids_tpu.exec.statsutil import note_bounds
    from spark_rapids_tpu.obs.trace import TRACER
    with TRACER.span("scan.host.stats", rows=len(df)) as sp:
        measured = 0
        for name in df.columns:
            if name in declared:
                continue
            s = df[name]
            if not (pd.api.types.is_integer_dtype(s.dtype)
                    and not pd.api.types.is_bool_dtype(s.dtype)):
                continue
            measured += 1
            # min/max skip NA natively; count() avoids the dropna() copy
            # this scan-upload hot path would otherwise pay per column
            if not int(s.count()):
                continue
            note_bounds(session, str(name), int(s.min()), int(s.max()))
        if sp is not None:
            sp.set(columns=measured)


def upload_blocked_chars(ctx: ExecContext) -> int:
    """Max byte stride for the blocked char-slab upload layout
    (spark.rapids.sql.dict.blockedChars, docs/gatherfree.md), or 0 when
    disabled — string columns that fail dictionary encoding and fit the
    stride then upload as fixed-stride slabs and move through the whole
    operator stack without 1-D char gathers. Requires dict.enabled owner
    switch too: with the gather-free mode off entirely, uploads are
    byte-identical legacy."""
    if not ctx.conf.get_bool("spark.rapids.sql.dict.enabled", True):
        return 0
    if not ctx.conf.get_bool("spark.rapids.sql.dict.blockedChars", True):
        return 0
    return max(0, ctx.conf.get_int(
        "spark.rapids.sql.dict.blockedChars.maxStride", 64))


def scan_dict_numerics(ctx: ExecContext, source) -> bool:
    """Whether file-scan uploads dictionary-probe NUMERIC columns
    (spark.rapids.sql.scan.dictEncodeNumerics, default off with the
    pipelined reader): the probe + per-batch encode cost an element-wise
    pass per column on the scan upload hot path, integer grouping keys
    already ride the dense-key path, and float dictionary keys are rare.
    In-memory uploads keep full probing — their small-table dictionaries
    pre-seed the aggregation fast path (TpuScanExec) and upload once per
    session. The legacy serial reader (prefetchDepth=0) also keeps full
    probing: the rollback path reproduces pre-pipeline behavior exactly."""
    if source is None or not hasattr(source, "paths"):
        return True
    if int(ctx.conf.get("spark.rapids.sql.scan.prefetchDepth", 2) or 0) \
            <= 0:
        return True
    return ctx.conf.get_bool("spark.rapids.sql.scan.dictEncodeNumerics",
                             False)


def scan_raw_parts(ctx: ExecContext, source, pushed_filters):
    """deviceDecode routing (spark.rapids.sql.scan.deviceDecode):
    RawRowGroup partitions from the source's raw-page reader, or None
    when the conf is off / the source has no raw path — callers then take
    the classic cpu_partitions route, byte-identical to pre-deviceDecode
    behavior."""
    if not ctx.conf.get_bool("spark.rapids.sql.scan.deviceDecode", False):
        return None
    if not hasattr(source, "raw_partitions"):
        return None
    if pushed_filters and hasattr(source, "prune_splits"):
        return source.raw_partitions(ctx, pushed_filters)
    return source.raw_partitions(ctx)


def upload_partition(ctx: ExecContext, part: Partition, schema: Schema,
                     max_rows: int, dict_state: dict, cache, i: int,
                     mesh_devs=None, is_scan: bool = True,
                     dict_numerics: bool = True,
                     declared_stats=()) -> Iterator[DeviceBatch]:
    """Shared host->device upload runner for TpuScanExec and
    HostToDeviceExec: pandas frames from ``part`` -> chunked, capacity-
    bucketed DeviceBatches, with device-scan-cache replay/fill and HBM
    metering.

    With the scan pipeline on (spark.rapids.sql.scan.prefetchDepth > 0)
    uploads are DOUBLE-BUFFERED: batch i+1's host buffer build +
    ``device_put`` are dispatched before batch i is yielded, so the
    transfer commits while the consumer computes on batch i. Each yielded
    batch re-publishes ITS origin file to the task context right before
    the yield — the read-ahead already moved the thread-local on.
    prefetchDepth=0 keeps the strict pull-driven serial order.
    """
    from spark_rapids_tpu.exec import taskctx
    from spark_rapids_tpu.obs.progress import PROGRESS
    from spark_rapids_tpu.obs.trace import TRACER
    sem = ctx.session.semaphore if ctx.session else None
    if getattr(ctx, "small_query", False) \
            and not getattr(ctx, "small_query_keep_sem", False):
        # tiny-query fast path: a single resident batch of a NON-
        # expanding plan cannot oversubscribe HBM — the admission lock is
        # pure fixed cost here (release on the drain side is a tolerated
        # no-op). Plans with joins/explode keep the semaphore: their
        # working set is not bounded by the leaf row counts.
        sem = None
    if sem is not None:
        sem.acquire_if_necessary()
    if cache is not None and i in cache:
        # replay with each batch's origin file restored so
        # input_file_name() stays correct on cache hits; the catalog
        # faults spilled batches back to the device
        catalog = ctx.session.buffer_catalog
        for fname, bid in cache[i]:
            taskctx.set_input_file(fname)
            yield catalog.acquire_batch(bid)
        taskctx.clear_input_file()
        return
    out = [] if cache is not None else None
    dm = ctx.session.device_manager if ctx.session else None
    double_buffer = int(ctx.conf.get(
        "spark.rapids.sql.scan.prefetchDepth", 2) or 0) > 0
    dict_on = ctx.conf.get_bool("spark.rapids.sql.dict.enabled", True)
    blocked = upload_blocked_chars(ctx)

    def uploads():
        df = chunk = prepared = None
        for df in part():
            fname = taskctx.input_file()
            # asked of the class: a frame's own __getattr__ hashes its
            # column index at the first name it is asked for (0.15 ms a
            # fresh frame), which belongs inside the spans below
            if getattr(type(df), "is_raw_rowgroup", False):
                # deviceDecode path: the split is a RawRowGroup of
                # encoded-page decode plans, not a pandas frame — decode
                # on device (ops/parquet_decode.py). Owns its own
                # sync_scope / transfer attribution / progress notes.
                from spark_rapids_tpu.ops.parquet_decode import (
                    decode_rowgroup,
                )
                if is_scan and df.fallback_df is not None:
                    note_scan_stats(ctx.session, df.fallback_df)
                dev_gen = decode_rowgroup(
                    ctx, df, schema, max_rows, dict_state, i,
                    device=(mesh_devs[i % len(mesh_devs)]
                            if mesh_devs else None))
                while True:
                    # span scoped to the decode step only, not the
                    # consumer compute between chunk yields
                    with TRACER.span("scan.deviceDecode", partition=i,
                                     rows=df.n):
                        batch = next(dev_gen, None)
                    if batch is None:
                        break
                    yield fname, batch
                continue
            if is_scan:
                note_scan_stats(ctx.session, df, declared_stats)
            for lo in range(0, max(len(df), 1), max_rows):
                prepared = None
                if double_buffer and lo == 0 and len(df) <= max_rows:
                    # whole-frame chunk: decode already produced a fresh
                    # RangeIndex frame; the reset_index copy is pure cost
                    # on the upload hot path (legacy reader keeps it —
                    # rollback reproduces the old path exactly)
                    chunk = df
                    # a scan's own frame, whole: what its decode worker
                    # left in the device layout goes with it (any other
                    # frame may have changed under the same names)
                    if is_scan:
                        prepared = df.attrs.get("srt_prepared")
                else:
                    # a sibling of scan.upload, not a child: the copy of
                    # every byte of a re-chunked split happens out here
                    with TRACER.span("scan.chunk", partition=i) as _sp:
                        chunk = df.iloc[lo:lo + max_rows].reset_index(
                            drop=True)
                        hints = getattr(df, "attrs", {}).get(
                            "srt_dict_fact")
                        if hints:
                            # re-chunked split: slice the worker's
                            # hints positionally so they survive
                            # (from_pandas drops length-mismatched hints);
                            # its whole-split buffers do not
                            chunk.attrs["srt_dict_fact"] = SplitAttrs({
                                nm: (codes[lo:lo + max_rows], u, None)
                                for nm, (codes, u, _) in hints.items()})
                        if _sp is not None:
                            _sp.set(rows=len(chunk))
                with TRACER.span("scan.upload", partition=i,
                                 rows=len(chunk)):
                    import time as _time

                    from spark_rapids_tpu.obs import compileledger
                    from spark_rapids_tpu.obs.syncledger import sync_scope
                    _t0 = _time.perf_counter()
                    with sync_scope("scan.upload",
                                    detail=f"partition={i}") as _sc:
                        batch = DeviceBatch.from_pandas(
                            chunk, schema=schema, dict_state=dict_state,
                            dict_encode=dict_on,
                            dict_numerics=dict_numerics,
                            blocked_chars=blocked,
                            device=(mesh_devs[i % len(mesh_devs)]
                                    if mesh_devs else None),
                            prepared=prepared)
                        _sc.add_bytes(batch.device_memory_size())
                    # host->device transfer attribution (host buffer
                    # build + device_put dispatch) against the upload
                    # operator — the "transfer" component of its profile
                    # breakdown row (obs/profile.py)
                    compileledger.note_transfer(
                        _time.perf_counter() - _t0, "h2d")
                if PROGRESS.enabled:  # live upload progress
                    PROGRESS.scan_upload(len(chunk))
                yield fname, batch
        # the partition's generator has ended and dropped its reference:
        # the last decoded frame (30-100 MB of a scan's split, and the
        # buffers its decode worker prepared) is freed by these three
        # names, so the free has a span (bytes: what the prefetcher
        # charged for the split, carried in the frame's attrs)
        with TRACER.span("scan.host.release", partition=i,
                         bytes=getattr(df, "attrs", {}).get("srt_nbytes")):
            df = chunk = prepared = None

    def account(fname: str, batch: DeviceBatch) -> None:
        with TRACER.span("scan.host.meter", partition=i) as sp:
            if out is not None:
                # cached batches live in the spillable catalog
                # (budget-metered, evictable)
                from spark_rapids_tpu.memory.spill import SpillPriorities
                bid = ctx.session.buffer_catalog.add_batch(
                    batch, SpillPriorities.CACHED_SCAN)
                out.append((fname, bid))
            elif dm is not None:
                dm.meter_batch(batch)
            if sp is not None:
                sp.set(bytes=batch.device_memory_size())

    try:
        gen = uploads()
        if double_buffer:
            # dispatch the NEXT chunk's host build + device_put before
            # handing the current batch downstream: device_put is async,
            # so the transfer commits while the consumer computes, and
            # the decode prefetcher keeps feeding the next splits
            # meanwhile. (An off-thread upload step was measured SLOWER
            # here: host buffer building is GIL/core-bound and a fourth
            # thread just thrashes the decode pool on small boxes.)
            # The CURRENT batch is metered/cataloged BEFORE the next
            # build so the read-ahead never holds more than one
            # unmetered batch — metering can trigger synchronous spill,
            # and budget enforcement must see batch i before i+1's
            # device_put allocates.
            pending = next(gen, None)
            while pending is not None:
                fname, batch = pending
                account(fname, batch)
                nxt = next(gen, None)
                taskctx.set_input_file(fname)
                yield batch
                pending = nxt
        else:
            for fname, batch in gen:
                account(fname, batch)
                taskctx.set_input_file(fname)
                yield batch
        if out is not None:
            if i in cache:  # concurrent filler won the publish
                out, published = None, out
                for _f, bid in published:
                    ctx.session.buffer_catalog.remove(bid)
            else:
                cache[i] = out
    except BaseException:
        # abandoned/failed scan: unpublished bids would leak catalog
        # buffers forever (clear_device_cache only walks published
        # entries)
        if out is not None and cache.get(i) is not out:
            for _f, bid in out:
                ctx.session.buffer_catalog.remove(bid)
        raise
    finally:
        taskctx.clear_input_file()


class HostToDeviceExec(PhysicalPlan):
    """pandas partition chunks -> DeviceBatch, chunked to the conf'd batch
    size and padded to capacity buckets."""

    columnar_output = True

    def __init__(self, child: PhysicalPlan):
        super().__init__([child])

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child = self.children[0]
        schema = child.output_schema()
        max_rows = ctx.conf.batch_size_rows

        # device-resident scan cache: re-executing a query over the same
        # source skips the re-upload — the HBM analogue of a cached
        # DataFrame, symmetric with the CPU path holding pandas in RAM
        cache = None
        from spark_rapids_tpu.exec.cpu import CpuScanExec
        is_scan = isinstance(child, CpuScanExec)
        child_parts = None
        if is_scan:
            # deviceDecode: build RawRowGroup partitions straight from
            # the source (the child scan node's own wrapper expects
            # pandas frames; decode attribution lands on this node)
            child_parts = scan_raw_parts(ctx, child.source,
                                         child.pushed_filters)
        if child_parts is None:
            child_parts = child.executed_partitions(ctx)
        if is_scan:
            cache = scan_cache_for(ctx, child.source, schema, max_rows,
                                   getattr(child, "pushed_filters", None))

        # shared dictionary registry across every batch of this transition
        # (see TpuScanExec: bounds program-shape churn to one dict/scan)
        dict_state: dict = {}

        dict_numerics = scan_dict_numerics(
            ctx, getattr(child, "source", None)) if is_scan else True

        def make(i: int, part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                return upload_partition(ctx, part, schema, max_rows,
                                        dict_state, cache, i,
                                        is_scan=is_scan,
                                        dict_numerics=dict_numerics)
            return run
        return [make(i, p) for i, p in enumerate(child_parts)]


class DeviceToHostExec(PhysicalPlan):
    columnar_output = False

    def __init__(self, child: PhysicalPlan):
        super().__init__([child])

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)

        def make(part: Partition) -> Partition:
            def run() -> Iterator[pd.DataFrame]:
                import time as _time

                from spark_rapids_tpu.obs import compileledger
                from spark_rapids_tpu.obs.syncledger import sync_scope
                sem = ctx.session.semaphore if ctx.session else None
                try:
                    for batch in part():
                        t0 = _time.perf_counter()
                        with sync_scope("transition.d2h"):
                            df = batch.to_pandas()
                        # device->host fetch seconds against this
                        # transition operator (profile breakdown)
                        compileledger.note_transfer(
                            _time.perf_counter() - t0, "d2h")
                        yield df
                finally:
                    if sem is not None:
                        sem.release()
            return run
        return [make(p) for p in child_parts]
