"""Data sources: in-memory tables and files.

File sources follow the reference's scan split: footer/metadata work and
pruning on the host, columnar decode batched (GpuParquetScan.scala pattern);
pyarrow performs the host decode, the HostToDevice transition uploads.
"""

from __future__ import annotations

import itertools
import math
from typing import List, Optional, Tuple

import numpy as np
import pandas as pd

from spark_rapids_tpu.columnar.batch import (
    PreparedColumns, Schema, SplitAttrs, bucket_capacity,
)
from spark_rapids_tpu.exec.base import ExecContext, Partition
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.obs.trace import TRACER


_DATA_UID_COUNTER = itertools.count(1)

# uploads at or under this size get a CONTENT-derived uid: queries built
# inside a function frequently re-create small lookup frames (a name
# mapping, a 12-row month sequence) on every call, and a fresh
# counter-uid per upload changes every downstream plan fingerprint —
# capacity speculation and subtree reuse then miss on every run, each
# miss costing a blocking device->host sync round trip
_CONTENT_UID_MAX_BYTES = 1 << 16


def _content_uid(df: pd.DataFrame, num_partitions: int):
    """Deterministic digest of a small frame's data+schema+partitioning,
    or None when the frame is too big to hash cheaply or unhashable."""
    import hashlib
    try:
        if int(df.memory_usage(deep=True).sum()) > _CONTENT_UID_MAX_BYTES:
            return None
        h = hashlib.blake2b(digest_size=8)
        h.update(("|".join(f"{c}:{t}" for c, t in
                           zip(map(str, df.columns), map(str, df.dtypes)))
                  + f"|p{num_partitions}|n{len(df)}").encode())
        h.update(pd.util.hash_pandas_object(df, index=False)
                 .to_numpy().tobytes())
        return "c" + h.hexdigest()
    except (TypeError, ValueError):
        return None


class DataSource:
    schema: Schema

    def data_uid(self) -> str:
        """Stable identity of the *data* behind this source for the
        session's adaptive caches: two scans of the same source object
        (or projection views of it, ``with_columns``) share a uid; a new
        upload gets a fresh one (a process-unique counter, never an
        ``id()`` that the allocator could reuse). Small in-memory frames
        use a content digest so re-created identical lookup tables keep
        plan fingerprints stable across executions; stale-stats risk is
        nil because every adaptive consumer verifies on device."""
        base = getattr(self, "_base", self)
        uid = getattr(base, "_data_uid", None)
        if uid is None:
            if isinstance(base, InMemorySource):
                uid = _content_uid(base.df, base.num_partitions)
            if uid is None:
                uid = next(_DATA_UID_COUNTER)
            base._data_uid = uid
        return f"{type(base).__name__}#{uid}"

    def describe(self) -> str:
        return type(self).__name__

    def cpu_partitions(self, ctx: ExecContext) -> List[Partition]:
        raise NotImplementedError

    def estimated_size_bytes(self) -> Optional[int]:
        """Size hint for broadcast-join planning (None = unknown)."""
        return None


class InMemorySource(DataSource):
    """createDataFrame equivalent: a pandas DataFrame split into partitions."""

    def __init__(self, df: pd.DataFrame, num_partitions: int = 1):
        self.df = df
        self.num_partitions = max(1, num_partitions)
        self.schema = Schema.from_pandas(df)

    def describe(self) -> str:
        return f"InMemory[{len(self.df)} rows x {len(self.df.columns)} cols]"

    def with_columns(self, columns: List[str]) -> "InMemorySource":
        """Projection-pushdown view: scan only the referenced columns.
        Cheap (pandas column view, no copy) and it keeps every later
        device kernel — filters especially — at the query's true width."""
        keep = [c for c in self.df.columns if c in columns]
        src = InMemorySource.__new__(InMemorySource)
        src.df = self.df[keep]
        src.num_partitions = self.num_partitions
        src.schema = Schema(
            keep, [self.schema.dtypes[self.schema.index_of(c)]
                   for c in keep])
        src._base = getattr(self, "_base", self)
        return src

    def estimated_size_bytes(self) -> Optional[int]:
        # deep=True so object/string columns count their payload, not just
        # the 8-byte pointers — a shallow count broadcasts huge tables
        return int(self.df.memory_usage(deep=True).sum())

    def cpu_partitions(self, ctx: ExecContext) -> List[Partition]:
        n = len(self.df)
        per = math.ceil(n / self.num_partitions) if n else 0
        if per == 0:
            def empty():
                yield self.df.iloc[0:0]

            def nothing():
                return iter(())
            return [empty] + [nothing] * (self.num_partitions - 1)

        def slice_task(i: int):
            def decode():
                return self.df.iloc[i * per:(i + 1) * per] \
                    .reset_index(drop=True)
            return decode
        from spark_rapids_tpu.sql.scan_pipeline import build_partitions
        return build_partitions(
            ctx, [(None, slice_task(i)) for i in range(self.num_partitions)])


def _expand_paths(paths: List[str], suffix: str):
    """Resolve directories to their data files, hive-style: a directory
    scan recurses and ``key=value`` path segments under the root become
    per-file partition values (the reference appends them as scalar
    columns per partition, ColumnarPartitionReaderWithPartitionValues)."""
    import os
    out = []  # (file_path, {partition_key: value})
    for p in paths:
        if not os.path.isdir(p):
            out.append((p, {}))
            continue
        for root, _dirs, files in sorted(os.walk(p)):
            rel = os.path.relpath(root, p)
            pvals = {}
            if rel != ".":
                for seg in rel.split(os.sep):
                    if "=" in seg:
                        k, v = seg.split("=", 1)
                        pvals[k] = v
            for f in sorted(files):
                if f.endswith(suffix) and not f.startswith(("_", ".")):
                    out.append((os.path.join(root, f), dict(pvals)))
    return out


_HIVE_NULL = "__HIVE_DEFAULT_PARTITION__"


def _infer_partition_value(text: str):
    if text == _HIVE_NULL:  # the writer's NULL sentinel round-trips to NULL
        return None
    try:
        return int(text)
    except ValueError:
        try:
            return float(text)
        except ValueError:
            return text


def _partition_key_dtype(values):
    """Common dtype over every directory value of one key (dtype module
    constant). Mixed or unparseable -> STRING."""
    from spark_rapids_tpu.columnar import dtype as dtmod
    kinds = {type(_infer_partition_value(v)) for v in values
             if _infer_partition_value(v) is not None}
    if kinds == {int}:
        return dtmod.INT64
    if kinds <= {int, float} and kinds:
        return dtmod.FLOAT64
    return dtmod.STRING


class ParquetSource(DataSource):
    """Parquet scan: row-group pruned, one partition per row-group chunk
    (reference: GpuParquetScan.scala:204-373 does footer parse + row-group
    clipping on the CPU before device decode). Directory inputs resolve
    hive-partitioned layouts (``key=value`` dirs)."""

    def __init__(self, paths: List[str], columns: Optional[List[str]] = None):
        import pyarrow.parquet as pq
        paths = [paths] if isinstance(paths, str) else list(paths)
        self._files = _expand_paths(paths, ".parquet")
        if not self._files:
            raise FileNotFoundError(f"no parquet files under {paths}")
        self.paths = [f for f, _ in self._files]
        self._pq = pq
        # footer parses ride the shared (path, mtime)-keyed metadata
        # cache (sql/parquet_raw.py) — split planning, _rg_stats and the
        # deviceDecode page reader all reuse ONE parse per file instead
        # of re-opening ParquetFile per consumer
        from spark_rapids_tpu.sql import parquet_raw as praw
        arrow_schema = praw.file_metadata(
            self.paths[0]).schema.to_arrow_schema()
        names, dts = [], []
        from spark_rapids_tpu.columnar import dtypes as dtmod
        for field in arrow_schema:
            if columns and field.name not in columns:
                continue
            names.append(field.name)
            dts.append(dtmod.from_arrow(field.type))
        self.columns = list(names)  # data columns only (pkeys append below)
        # partition-value columns appended after data columns, typed by
        # inference over EVERY directory value (mixed kinds -> string)
        self._pkeys = sorted({k for _, pv in self._files for k in pv})
        self._pkey_dtypes = {}
        for k in self._pkeys:
            dt = _partition_key_dtype([pv[k] for _, pv in self._files
                                       if k in pv])
            self._pkey_dtypes[k] = dt
            names.append(k)
            dts.append(dt)
        self.schema = Schema(names, dts)
        # partition plan: (path, row_group_index, partition_values)
        self.splits = []
        for p, pvals in self._files:
            for rg in range(praw.file_metadata(p).num_row_groups):
                self.splits.append((p, rg, pvals))

    def describe(self) -> str:
        return f"Parquet[{len(self.paths)} files, {len(self.splits)} row groups]"

    def estimated_size_bytes(self) -> Optional[int]:
        import os
        return sum(os.path.getsize(p) for p in self.paths)

    def with_columns(self, columns: List[str]) -> "ParquetSource":
        """Cheap projection view (no footer re-parse): read only
        ``columns`` (data columns clipped; partition keys kept if named)."""
        import copy
        src = copy.copy(self)
        src._base = getattr(self, "_base", self)
        src.columns = [c for c in self.columns if c in columns]
        src._pkeys = [k for k in self._pkeys if k in columns]
        names = list(src.columns) + list(src._pkeys)
        idx = {n: i for i, n in enumerate(self.schema.names)}
        src.schema = Schema(names,
                            [self.schema.dtypes[idx[n]] for n in names])
        return src

    # row-group-stats cache bound: footers are tiny, but a long session
    # scanning many files would otherwise grow the dict forever
    _RG_STATS_CACHE_CAP = 4096

    def _rg_stats(self, path: str, rg: int):
        """{col: (min, max, null_count, num_values)} from the footer.
        Keyed by (path, mtime, rg): a rewritten file's stale stats must
        not keep pruning row groups of its replacement. Insertion-ordered
        dict, oldest-half eviction past the cap."""
        from spark_rapids_tpu.sql import parquet_raw as praw
        base = getattr(self, "_base", self)
        cache = base.__dict__.setdefault("_stats_cache", {})
        mtime = praw.file_mtime(path)
        if (path, mtime, rg) not in cache:
            if len(cache) >= self._RG_STATS_CACHE_CAP:
                for k in list(cache)[:len(cache)
                                     - self._RG_STATS_CACHE_CAP // 2]:
                    del cache[k]
            # footer via the shared (path, mtime) metadata cache — no
            # ParquetFile re-open per split
            md = praw.file_metadata(path, mtime).row_group(rg)
            stats = {}
            for ci in range(md.num_columns):
                col = md.column(ci)
                s = col.statistics
                if s is None:
                    stats[col.path_in_schema] = (None, None, None, None)
                else:
                    stats[col.path_in_schema] = (
                        s.min if s.has_min_max else None,
                        s.max if s.has_min_max else None,
                        s.null_count, s.num_values)
            cache[(path, mtime, rg)] = stats
        return cache[(path, mtime, rg)]

    def prune_splits(self, filters) -> Tuple[list, int]:
        """(surviving splits, pruned count): row-group statistics +
        partition-value pruning for the pushed conjuncts
        (ParquetFilters, GpuParquetScan.scala:204-246)."""
        from spark_rapids_tpu.sql.pushdown import (
            maybe_matches, partition_value_matches,
        )
        keep = []
        for (p, rg, pvals) in self.splits:
            ok = True
            for name, op, value in filters:
                if name in self._pkeys:
                    pv = (_infer_partition_value(pvals[name])
                          if name in pvals else None)
                    if not partition_value_matches(pv, op, value):
                        ok = False
                        break
                    continue
                if name not in self.columns:
                    continue
                mn, mx, nulls, nvals = self._rg_stats(p, rg).get(
                    name, (None, None, None, None))
                if not maybe_matches(mn, mx, nulls, nvals, op, value):
                    ok = False
                    break
            if ok:
                keep.append((p, rg, pvals))
        return keep, len(self.splits) - len(keep)

    def declared_int_bounds(self, filters=None) -> dict:
        """{column: (min, max)} of the requested integer data columns
        over the splits that survive ``filters``, read from the cached
        footers before any batch is. A column is declared only when every
        surviving row group that holds a non-null value of it carries
        min/max statistics; its entry is None where no surviving row
        group holds a value (all nulls, or nothing survived). Files
        written without statistics declare nothing."""
        splits = self.prune_splits(filters)[0] if filters else self.splits
        # the schema lists the data columns first, then partition keys
        out = {c: None for c, dt in zip(self.columns, self.schema.dtypes)
               if dt.is_integral}
        for p, rg, _ in splits:
            if not out:
                break
            stats = self._rg_stats(p, rg)
            for name in list(out):
                mn, mx, _, nvals = stats.get(name, (None,) * 4)
                if mn is None or mx is None:
                    if nvals != 0:  # values, or nothing known, unbounded
                        del out[name]
                    continue
                prev = out[name]
                out[name] = ((int(mn), int(mx)) if prev is None else
                             (min(int(mn), prev[0]), max(int(mx), prev[1])))
        return out

    def _read_row_group(self, path: str, rg: int):
        """One row group as an Arrow table: read, decompress and page
        decode are one call into Arrow's C++, so one span."""
        with TRACER.span("scan.decode.read", file=path,
                         row_group=rg) as sp:
            table = self._pq.ParquetFile(path).read_row_group(
                rg, columns=self.columns)
            if sp is not None:
                sp.set(bytes=table.nbytes)
        return table

    def _append_partition_values(self, df: pd.DataFrame,
                                 pvals) -> pd.DataFrame:
        """The hive partition keys of the split's path as columns."""
        for k in self._pkeys:
            v = (_infer_partition_value(pvals[k])
                 if k in pvals else None)
            dt = self._pkey_dtypes[k]
            if v is not None and not dt.is_string:
                v = dt.np_dtype.type(v)
            elif v is not None:
                v = str(v)
            df[k] = pd.Series([v] * len(df),
                              dtype=dt.pandas_nullable
                              if not dt.is_string else object)
        return df

    def cpu_partitions(self, ctx: ExecContext,
                       filters=None) -> List[Partition]:
        splits = self.splits
        if filters:
            splits, pruned = self.prune_splits(filters)
            if ctx.metrics_enabled:
                ctx.metric_add(self.describe(), "numRowGroupsPruned",
                               pruned)

        from spark_rapids_tpu.sql.scan_pipeline import (
            build_partitions, pipeline_config,
        )
        # prefetchDepth=0 selects the LEGACY reader end to end (the
        # reference's PERFILE mode keeps its own code path the same way):
        # synchronous decode through the full arrow->pandas conversion,
        # no hints — the safe rollback path reproduces pre-pipeline
        # behavior exactly, not just its thread count
        pipelined = pipeline_config(ctx.conf)[0] > 0
        direct = pipelined and ctx.conf.get_bool(
            "spark.rapids.sql.scan.directDecode", True)

        def decode_task(path: str, rg: int, pvals):
            def decode():
                table = self._read_row_group(path, rg)
                with TRACER.span("scan.decode.convert") as sp:
                    df = self._append_partition_values(
                        _arrow_decode(table, direct), pvals)
                    if pipelined:
                        df = _attach_prepared(
                            _attach_dict_hints(df, table), table)
                    if sp is not None:
                        sp.set(rows=len(df))
                return df
            return decode
        if not splits:
            def empty():
                yield _empty_from_schema(self.schema)
            return [empty]
        return build_partitions(
            ctx, [(p, decode_task(p, rg, pv)) for p, rg, pv in splits])

    def raw_partitions(self, ctx: ExecContext,
                       filters=None) -> List[Partition]:
        """deviceDecode split plan (spark.rapids.sql.scan.deviceDecode):
        decode workers produce RawRowGroup decode plans (raw page bytes +
        run tables, ops/parquet_decode.py) instead of pandas frames; the
        consumer decodes them ON DEVICE. Rides the same prefetch
        machinery as cpu_partitions — bounded queue, backpressure,
        prefetchDepth=0 serial rollback. Row groups where NO column can
        ride the device path degrade to the classic pandas frame."""
        splits = self.splits
        if filters:
            splits, pruned = self.prune_splits(filters)
            if ctx.metrics_enabled:
                ctx.metric_add(self.describe(), "numRowGroupsPruned",
                               pruned)
        from spark_rapids_tpu.exec.transitions import upload_blocked_chars
        from spark_rapids_tpu.sql.scan_pipeline import (
            build_partitions, pipeline_config,
        )
        pipelined = pipeline_config(ctx.conf)[0] > 0
        direct = pipelined and ctx.conf.get_bool(
            "spark.rapids.sql.scan.directDecode", True)
        blocked = upload_blocked_chars(ctx)
        page_cache = getattr(ctx.session, "page_cache", None) \
            if ctx.session else None
        columns = list(self.columns)
        dtypes_by_name = dict(zip(self.schema.names, self.schema.dtypes))

        def decode_task(path: str, rg: int, pvals):
            def decode():
                from spark_rapids_tpu.ops.parquet_decode import (
                    prepare_rowgroup,
                )
                raw = prepare_rowgroup(path, rg, pvals, columns,
                                       dtypes_by_name, blocked,
                                       page_cache=page_cache,
                                       direct=direct)
                if getattr(raw, "is_raw_rowgroup", False):
                    return raw
                # whole-split host fallback: finish exactly like the
                # classic decode_task (partition-value columns appended)
                df = raw
                if df is None:
                    table = self._read_row_group(path, rg)
                with TRACER.span("scan.decode.convert") as sp:
                    if df is None:
                        df = _arrow_decode(table, direct)
                    df = self._append_partition_values(df, pvals)
                    if sp is not None:
                        sp.set(rows=len(df))
                return df
            return decode
        if not splits:
            def empty():
                yield _empty_from_schema(self.schema)
            return [empty]
        return build_partitions(
            ctx, [(p, decode_task(p, rg, pv)) for p, rg, pv in splits])


class CsvSource(DataSource):
    """CSV scan via pyarrow.csv host parse (reference: Table.readCSV from
    GpuBatchScanExec.scala:477, with host-side line splitting)."""

    def __init__(self, paths, schema: Optional[Schema] = None,
                 header: bool = True):
        import pyarrow.csv as pacsv
        paths = [paths] if isinstance(paths, str) else list(paths)
        self.paths = [f for f, _ in _expand_paths(paths, ".csv")] or paths
        self.header = header
        self._pacsv = pacsv
        if schema is not None:
            self.schema = schema
        else:
            t = pacsv.read_csv(self.paths[0])
            from spark_rapids_tpu.columnar import dtypes as dtmod
            names = [f.name for f in t.schema]
            dts = [dtmod.from_arrow(f.type) for f in t.schema]
            self.schema = Schema(names, dts)

    def describe(self) -> str:
        return f"CSV[{len(self.paths)} files]"

    def cpu_partitions(self, ctx: ExecContext) -> List[Partition]:
        pacsv = self._pacsv
        from spark_rapids_tpu.sql.scan_pipeline import (
            build_partitions, pipeline_config,
        )
        pipelined = pipeline_config(ctx.conf)[0] > 0
        direct = pipelined and ctx.conf.get_bool(
            "spark.rapids.sql.scan.directDecode", True)

        def decode_task(path: str):
            def decode():
                t = pacsv.read_csv(path)
                df = _arrow_decode(t, direct)
                df.columns = list(self.schema.names)
                return _attach_dict_hints(df, t) if pipelined else df
            return decode
        return build_partitions(
            ctx, [(p, decode_task(p)) for p in self.paths])


class OrcSource(DataSource):
    """ORC scan: stripe-partitioned host decode via pyarrow.orc (reference:
    GpuOrcScan.scala:711 decodes via Table.readORC after host-side stripe
    clipping; OrcFilters SARG pushdown is host-side there too)."""

    def __init__(self, paths, columns: Optional[List[str]] = None):
        import pyarrow.orc as paorc
        paths = [paths] if isinstance(paths, str) else list(paths)
        self.paths = [f for f, _ in _expand_paths(paths, ".orc")] or paths
        self._paorc = paorc
        f = paorc.ORCFile(self.paths[0])
        from spark_rapids_tpu.columnar import dtypes as dtmod
        names, dts = [], []
        for field in f.schema:
            if columns and field.name not in columns:
                continue
            names.append(field.name)
            dts.append(dtmod.from_arrow(field.type))
        self.columns = names
        self.schema = Schema(names, dts)
        # partition plan: (path, stripe index)
        self.splits = []
        for p in self.paths:
            fh = paorc.ORCFile(p)
            for s in range(fh.nstripes):
                self.splits.append((p, s))

    def describe(self) -> str:
        return f"ORC[{len(self.paths)} files, {len(self.splits)} stripes]"

    def estimated_size_bytes(self) -> Optional[int]:
        import os
        return sum(os.path.getsize(p) for p in self.paths)

    def with_columns(self, columns: List[str]) -> "OrcSource":
        import copy
        src = copy.copy(self)
        src._base = getattr(self, "_base", self)
        src.columns = [c for c in self.columns if c in columns]
        idx = {n: i for i, n in enumerate(self.schema.names)}
        src.schema = Schema(list(src.columns),
                            [self.schema.dtypes[idx[n]]
                             for n in src.columns])
        return src

    def _stripe_index(self, col: str):
        """{(path, stripe): (min, max, null_count, num_values)} for one
        column, built lazily by reading just that column per stripe once —
        pyarrow's ORC reader exposes no footer stripe statistics (the
        reference reads them natively, sql/rapids/OrcFilters.scala), so
        this one-time index plays their role across queries."""
        base = getattr(self, "_base", self)
        cache = base.__dict__.setdefault("_stripe_stats", {})
        if col not in cache:
            import pyarrow.compute as pc
            idx = {}
            for p in self.paths:
                fh = self._paorc.ORCFile(p)
                for s in range(fh.nstripes):
                    t = fh.read_stripe(s, columns=[col])
                    arr = t.column(0) if hasattr(t, "column") else t[0]
                    n = len(arr)
                    nulls = arr.null_count
                    if n - nulls > 0:
                        mn = pc.min(arr).as_py()
                        mx = pc.max(arr).as_py()
                    else:
                        mn = mx = None
                    idx[(p, s)] = (mn, mx, nulls, n - nulls)
            cache[col] = idx
        return cache[col]

    def prune_splits(self, filters) -> Tuple[list, int]:
        from spark_rapids_tpu.sql.pushdown import maybe_matches
        keep = []
        for (p, s) in self.splits:
            ok = True
            for name, op, value in filters:
                if name not in self.columns:
                    continue
                mn, mx, nulls, nvals = self._stripe_index(name).get(
                    (p, s), (None, None, None, None))
                if not maybe_matches(mn, mx, nulls, nvals, op, value):
                    ok = False
                    break
            if ok:
                keep.append((p, s))
        return keep, len(self.splits) - len(keep)

    def cpu_partitions(self, ctx: ExecContext,
                       filters=None) -> List[Partition]:
        paorc = self._paorc
        splits = self.splits
        if filters:
            splits, pruned = self.prune_splits(filters)
            if ctx.metrics_enabled:
                ctx.metric_add(self.describe(), "numStripesPruned", pruned)

        from spark_rapids_tpu.sql.scan_pipeline import (
            build_partitions, pipeline_config,
        )
        pipelined = pipeline_config(ctx.conf)[0] > 0
        direct = pipelined and ctx.conf.get_bool(
            "spark.rapids.sql.scan.directDecode", True)

        def decode_task(path: str, stripe: int):
            def decode():
                f = paorc.ORCFile(path)
                table = f.read_stripe(stripe, columns=self.columns)
                import pyarrow as pa
                if isinstance(table, pa.RecordBatch):
                    table = pa.Table.from_batches([table])
                df = _arrow_decode(table, direct)
                if pipelined:
                    df = _attach_prepared(_attach_dict_hints(df, table),
                                          table)
                return df
            return decode
        if not splits:
            def empty():
                yield _empty_from_schema(self.schema)
            return [empty]
        return build_partitions(
            ctx, [(p, decode_task(p, s)) for p, s in splits])


def _arrow_to_pandas(table) -> pd.DataFrame:
    df = table.to_pandas(types_mapper=_types_mapper)
    return df


_ARROW_HINTS = {o: REGISTRY.counter("scan.hint.arrowColumns", outcome=o)
                for o in ("hinted", "card", "nul")}


def _arrow_dictionary(col):
    """(dictionary, [indices of every chunk]) of an Arrow string column,
    every chunk under ONE dictionary: Arrow's own hash, bytes compared
    exactly, the interpreter lock released, no Python object a row."""
    import pyarrow.compute as pc
    enc = pc.dictionary_encode(col)
    if enc.num_chunks > 1:
        enc = enc.unify_dictionaries()
    return enc.chunk(0).dictionary, [c.indices for c in enc.chunks]


def _arrow_dict_hint(col):
    """``column.dict_factorize_hint`` for a string column of the scan's
    Arrow table, made from the column itself: the same probe (the first
    ``_DICT_PROBE`` rows, the same gate), the same codes (first
    appearance, -1 at a null) as int32, the same uniques as ``str`` — and
    beside them ``column.dict_ready_buffers``' (validity, codes, values),
    the column as the device takes it. None when the column is no
    dictionary candidate or one of its values holds a NUL byte: a NUL in
    any row is a NUL in that row's value, and Arrow keeps 'a' and 'a\\x00'
    apart where pandas' ``factorize`` merges them, so the at most
    DICT_MAX_CARD values are all there is to look at."""
    from spark_rapids_tpu.columnar.column import (
        _DICT_PROBE, DICT_MAX_CARD, dict_ready_buffers,
    )
    n = len(col)
    if n == 0:
        return None
    dictionary, indices = _arrow_dictionary(col.slice(0, _DICT_PROBE))
    if len(dictionary) > DICT_MAX_CARD \
            or len(dictionary) > max(64, min(n, _DICT_PROBE) // 4):
        _ARROW_HINTS["card"].add(1)
        return None
    if n > _DICT_PROBE:  # else the probe was the column
        dictionary, indices = _arrow_dictionary(col)
    if not 0 < len(dictionary) <= DICT_MAX_CARD:  # 0: every row is null
        _ARROW_HINTS["card"].add(1)
        return None
    uniques = dictionary.to_pylist()
    if any("\x00" in u for u in uniques):
        _ARROW_HINTS["nul"].add(1)
        return None
    has_null = col.null_count > 0
    codes = [(c.fill_null(-1) if c.null_count else c).to_numpy()
             for c in indices]
    codes = codes[0] if len(codes) == 1 else np.concatenate(codes)
    _ARROW_HINTS["hinted"].add(1)
    return codes, uniques, dict_ready_buffers(
        codes, has_null, uniques, bucket_capacity(n))


def _attach_dict_hints(df: pd.DataFrame, table) -> pd.DataFrame:
    """Precompute per-column dictionary encodings ON THE DECODE WORKER
    (the scan pipeline runs this inside the split's decode task) and
    attach them as ``df.attrs["srt_dict_fact"]`` keyed by column name:
    (codes, uniques, ready). The host->device upload then builds the
    column codes-only (columnar/batch.py) and, where the batch's values
    are the scan's dictionary, ships ``ready`` as it is
    (column.host_dict_encode_hinted); otherwise it pays an O(cardinality)
    remap of the codes.

    ``table``: the Arrow table ``df`` was converted from, column for
    column by position. Its string columns are encoded by Arrow
    (_arrow_dict_hint): the frame's own column is never read, so it stays
    what every fallback path expects; a column of any other layout gets
    no hint. Columns of ``df`` past the table's are the caller's
    constants (hive partition values, one path component a frame) and go
    through pandas (column.dict_factorize_hint); their one value is all
    there is to check for a NUL. A column with a NUL byte gets NO hint
    either way: the upload then takes the unhinted path, finds the NUL in
    the chars it builds and closes the column's dictionary for the scan.

    Only string columns are hinted: file-scan uploads skip the numeric
    dictionary probe entirely (exec/transitions.py scan_dict_numerics);
    datetime and nullable-extension columns convert through fills and
    unit casts, so they would need a value-space translation the hint
    cannot do."""
    import pyarrow as pa

    from spark_rapids_tpu.columnar.column import dict_factorize_hint
    hints = SplitAttrs()
    for i in range(df.shape[1]):
        name = str(df.columns[i])
        if i < table.num_columns:
            col = table.column(i)
            if pa.types.is_string(col.type) \
                    or pa.types.is_large_string(col.type):
                h = _arrow_dict_hint(col)
                if h is not None:
                    hints[name] = h
                    validity, codes, _values = h[2]
                    hints.nbytes += h[0].nbytes + codes.nbytes \
                        + (validity.nbytes if col.null_count else 0)
            continue  # any other layout: nothing known, so no hint
        s = df.iloc[:, i]
        if (isinstance(s.dtype, np.dtype) and s.dtype.kind == "O") \
                or str(s.dtype) in ("str", "string"):
            h = dict_factorize_hint(s.to_numpy(dtype=object),
                                    is_string=True)
            if h is not None and not any(
                    isinstance(u, str) and "\x00" in u for u in h[1]):
                hints[name] = h + (None,)
    if hints:
        df.attrs["srt_dict_fact"] = hints
    return df


# prepared_fixed_buffers' ``scale`` for an Arrow timestamp unit
_TO_MICROS = {"s": 1_000_000, "ms": 1_000, "us": 1, "ns": -1_000}


def _attach_prepared(df: pd.DataFrame, table) -> pd.DataFrame:
    """Leave every fixed-width column the upload would only copy in the
    layout the device takes, ON THE DECODE WORKER, as
    ``df.attrs["srt_prepared"]`` (columnar/batch.py PreparedColumns) keyed
    by column name: ``upload.build`` on the query's thread then ships the
    buffers and allocates nothing (exec/transitions.py hands them over
    for a frame that comes straight from a scan and was not cut).

    ``table``: the Arrow table ``df`` was converted from, column for
    column by position. A column is prepared when it is an integer, a
    float, a date or a timestamp of the engine's types, holds no null
    and arrived as one chunk: a full batch's data is Arrow's own buffer
    seen as numpy, a partial batch's is written once behind the null
    fill, and a timestamp of any unit becomes int64 micros in that one
    pass (the value ``_pandas_to_numpy`` yields, never through pandas).
    Everything else (nulls, booleans, strings, zoned timestamps, a
    timestamp that holds pandas' NaT sentinel) is left to the upload's
    own build, which needs the frame's column as it always did."""
    from spark_rapids_tpu.columnar import dtype as dtmod
    from spark_rapids_tpu.columnar.column import prepared_fixed_buffers
    n = table.num_rows
    if n == 0 or len(df) != n:
        return df
    cap = bucket_capacity(n)
    prepared = PreparedColumns(n)
    for i in range(table.num_columns):
        col = table.column(i)
        if col.null_count or col.num_chunks != 1:
            continue
        try:
            dt = dtmod.from_arrow(col.type)
        except TypeError:
            continue
        if dt.is_string or dt == dtmod.BOOL:  # bit-packed in Arrow
            continue
        chunk = col.chunk(0)
        values = np.frombuffer(chunk.buffers()[1], dt.np_dtype, count=n,
                               offset=chunk.offset * dt.itemsize)
        scale = 1
        if dt == dtmod.TIMESTAMP_US:
            if col.type.tz is not None \
                    or values.min() == np.iinfo(np.int64).min:
                continue
            scale = _TO_MICROS[col.type.unit]
        data, validity = prepared_fixed_buffers(values, dt, cap, scale)
        prepared[str(df.columns[i])] = (dt, data, validity)
        if data is not values:  # padded or converted: memory of its own
            prepared.nbytes += data.nbytes
    if prepared:
        df.attrs["srt_prepared"] = prepared
    return df


def _arrow_decode(table, direct: bool = True) -> pd.DataFrame:
    """arrow Table -> pandas for the scan hot path.

    ``direct``: non-nullable primitive (int/float/bool) columns convert
    arrow -> numpy -> Series directly (zero-copy where arrow allows),
    skipping the pandas nullable-extension materialization — on wide
    numeric scans that conversion is a large share of decode time.
    Columns with nulls, strings, dates/timestamps and dictionaries fall
    back to ``_arrow_to_pandas`` per column, so values (incl. null
    masks) are identical either way; only the no-null numeric dtype
    differs (plain numpy instead of the nullable extension, which every
    downstream consumer already handles — _pandas_to_numpy branches on
    exactly this)."""
    if not direct or table.num_rows == 0 or table.num_columns == 0:
        return _arrow_to_pandas(table)
    import pyarrow as pa
    series: List = []
    fallback_idx = []
    for i in range(table.num_columns):
        col = table.column(i)
        t = col.type
        if (col.null_count == 0
                and (pa.types.is_integer(t) or pa.types.is_floating(t)
                     or pa.types.is_boolean(t))):
            series.append(pd.Series(col.to_numpy(zero_copy_only=False),
                                    copy=False))
        else:
            series.append(None)
            fallback_idx.append(i)
    if fallback_idx:
        fb = _arrow_to_pandas(table.select(fallback_idx))
        for j, i in enumerate(fallback_idx):
            series[i] = fb.iloc[:, j].reset_index(drop=True)
    df = pd.concat(series, axis=1)
    df.columns = list(table.column_names)
    return df


def _types_mapper(pa_type):
    import pyarrow as pa
    # map nullable ints to pandas extension dtypes so nulls survive
    m = {pa.int8(): pd.Int8Dtype(), pa.int16(): pd.Int16Dtype(),
         pa.int32(): pd.Int32Dtype(), pa.int64(): pd.Int64Dtype(),
         pa.float32(): pd.Float32Dtype(), pa.float64(): pd.Float64Dtype(),
         pa.bool_(): pd.BooleanDtype()}
    return m.get(pa_type)


def _empty_from_schema(schema: Schema) -> pd.DataFrame:
    from spark_rapids_tpu.exec.cpu import _empty_df
    return _empty_df(schema)
