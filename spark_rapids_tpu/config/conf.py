"""Typed, self-documenting configuration registry.

Design mirrors the reference's ``RapidsConf`` (reference:
sql-plugin/src/main/scala/com/nvidia/spark/rapids/RapidsConf.scala:30-866):
every knob is a registered ``ConfEntry`` with a key, a type, a default, a doc
string and an optional validator; ``TpuConf`` wraps a plain dict of settings
with typed accessors; ``help_text()`` generates the docs table the same way
``RapidsConf.help`` does (reference: RapidsConf.scala:133-146).

Key names intentionally keep the ``spark.rapids.*`` namespace so a user of the
reference finds the same switches here.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


@dataclass(frozen=True)
class ConfEntry:
    key: str
    conv: Callable[[str], Any]
    default: Any
    doc: str
    internal: bool = False
    validator: Optional[Callable[[Any], Optional[str]]] = None

    def convert(self, raw: Any) -> Any:
        if isinstance(raw, str):
            value = self.conv(raw)
        else:
            value = raw
        if self.validator is not None:
            err = self.validator(value)
            if err:
                raise ValueError(f"invalid value for {self.key}: {err}")
        return value


_REGISTRY: Dict[str, ConfEntry] = {}
_REGISTRY_LOCK = threading.Lock()


def _to_bool(s: str) -> bool:
    low = s.strip().lower()
    if low in ("true", "1", "yes"):
        return True
    if low in ("false", "0", "no"):
        return False
    raise ValueError(f"not a boolean: {s!r}")


def _to_bytes(s: str) -> int:
    """Parse '1g', '512m', '16k' or raw integers into bytes."""
    s = s.strip().lower()
    mults = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40, "b": 1}
    if s and s[-1] in mults:
        return int(float(s[:-1]) * mults[s[-1]])
    return int(s)


def register(key: str, conv: Callable[[str], Any], default: Any, doc: str,
             internal: bool = False,
             validator: Optional[Callable[[Any], Optional[str]]] = None) -> ConfEntry:
    entry = ConfEntry(key, conv, default, doc, internal, validator)
    with _REGISTRY_LOCK:
        if key in _REGISTRY and _REGISTRY[key].doc != doc:
            raise ValueError(f"conf key registered twice: {key}")
        _REGISTRY[key] = entry
    return entry


def conf_entries() -> Dict[str, ConfEntry]:
    return dict(_REGISTRY)


def _fraction(lo: float, hi: float) -> Callable[[Any], Optional[str]]:
    def check(v: Any) -> Optional[str]:
        if not (lo <= float(v) <= hi):
            return f"must be within [{lo}, {hi}], got {v}"
        return None
    return check


def _positive(v: Any) -> Optional[str]:
    return None if v > 0 else f"must be positive, got {v}"


# ---------------------------------------------------------------------------
# Entry definitions. Groups mirror RapidsConf.scala:241-604.
# ---------------------------------------------------------------------------

# --- general / top level ---------------------------------------------------
SQL_ENABLED = register(
    "spark.rapids.sql.enabled", _to_bool, True,
    "Enable (true) or disable (false) TPU acceleration of SQL plans. When "
    "disabled every operator executes on the CPU path.")

AGG_FUSE_FILTER = register(
    "spark.rapids.sql.agg.fuseFilter", _to_bool, True,
    "Fuse a Filter (and intervening deterministic Projects) below a "
    "partial hash aggregate into the aggregation kernel as a row mask, "
    "skipping the filter's per-column compaction gathers (indexed ops run "
    "at ~5M rows/s on TPU; the fused dense predicate is ~free).")

EXCHANGE_FUSE_FILTER = register(
    "spark.rapids.sql.exchange.fuseFilter", _to_bool, True,
    "Let a collapsed exchange (or a broadcast materialization) claim a "
    "deterministic Filter directly below it and run it a batch at a time "
    "as the child's batches arrive. A batch of at most four columns, each "
    "fixed-width or a dictionary string, is compacted by the filter's own "
    "sorting kernel and the collapse concatenates by block copies; any "
    "other batch (strings with chars or a slab, five columns or more) "
    "hands the concat a keep mask, and the concat compacts every part in "
    "one gather. Off: nothing is claimed and the filter runs as the "
    "operator it is.")

ADAPTIVE_CAPACITY = register(
    "spark.rapids.sql.adaptiveCapacity.enabled", _to_bool, True,
    "Adaptive (AQE-style) output-capacity speculation: the session "
    "remembers each join's expansion sizes per structural plan "
    "fingerprint and later executions of the same query skip the "
    "per-join device->host capacity sync, expanding straight into the "
    "remembered buckets. The exact sizes are still computed on device; "
    "ONE deferred fetch at query end verifies every speculated capacity "
    "covered its actual size and the query transparently re-executes "
    "without speculation on any miss — correctness never depends on the "
    "cache. Each skipped sync is one blocking host-device round trip "
    "per join per execution. Also the verification substrate of "
    "spark.rapids.sql.agg.denseKeys, which this conf gates.")

AGG_DENSE_KEYS = register(
    "spark.rapids.sql.agg.denseKeys", _to_bool, True,
    "Bounded-int composite grouping keys: when every group key is a "
    "fixed-width integer with advisory scan-stat bounds fitting 62 bits "
    "of combined slot space, the grouping sort runs on ONE exact "
    "composite key (2 sort operands instead of 4, no hashing, no image "
    "refinement) and it is the ONLY grouping path compiled. Over a "
    "Parquet scan whose footers carry min/max statistics the bounds are "
    "declared when the scan is planned, so dense grouping engages on a "
    "plan's FIRST execution; over any other source (ORC, CSV, in-memory "
    "frames, Parquet written without statistics) the bounds are "
    "measured as batches upload and it engages from the SECOND. The "
    "device-computed bounds check joins the deferred speculation "
    "verification: a stale-stats miss transparently re-executes the "
    "query without dense grouping and blocklists the plan. Requires "
    "spark.rapids.sql.adaptiveCapacity.enabled (the verification "
    "machinery); disabling that disables dense grouping too.")

AGG_FUSE_COUNT_DISTINCT = register(
    "spark.rapids.sql.agg.fuseCountDistinct", _to_bool, True,
    "Fuse the two-level aggregation that count(DISTINCT) (and the "
    "distinct().group_by().count() spelling) expands into — distinct "
    "over G1 keys, then count grouped by G2 — into ONE sorted pass over "
    "the G1 tuple (exec/aggfuse.py): distinct-tuple boundaries and "
    "group boundaries come from the same sorted images, halving the "
    "dominant cost of distinct-heavy queries. Single-chip only; on a "
    "mesh the chain's exchanges carry real distribution.")

REUSE_SUBTREES = register(
    "spark.rapids.sql.reuseSubtrees.enabled", _to_bool, True,
    "Within-query reuse of identical deterministic subtrees (the "
    "ReuseExchange analogue, exec/reuse.py): branches referencing the "
    "same joined/aggregated intermediate (scalar-subquery thresholds, "
    "self-join views) materialize it once and replay the batches.")

AGG_SKIP_RATIO = register(
    "spark.rapids.sql.agg.skipAggPassReductionRatio", float, 0.45,
    "Adaptive partial-aggregation skip: after the first batch of a "
    "partial hash aggregate, if output_groups/input_rows exceeds this "
    "ratio (the pass barely reduces), remaining batches bypass the "
    "grouping kernel and are projected straight into the partial layout "
    "(count=1, sum=value) for the final aggregate to reduce once. On a "
    "single chip the exchange is a local concat, so the partial pass "
    "only pays at STRONG reduction — it always costs a full input sort, "
    "and a weakly-reduced merge input sorts at the same bucketed "
    "capacity anyway (q18's 0.76-ratio orderkey aggregation measured "
    "faster skipped). 1.0 disables skipping.",
    validator=_fraction(0.0, 1.0))

AGG_HASH_ENABLED = register(
    "spark.rapids.sql.agg.hashAggEnabled", _to_bool, False,
    "Open-addressing hash aggregation "
    "(ops/tablekernels.hash_grouped_aggregate): rows claim slots in a "
    "load-factor-1/2 table and each sum/min/max/count is one segment "
    "reduction at table width — no sort. Engages for "
    "exact-one-word key images (fixed-width values, dictionary codes) "
    "where the dense-key path cannot and the payload-sort path is the "
    "fallback today; batches whose table exceeds "
    "spark.rapids.sql.agg.hash.maxTableSlots recurse through the "
    "out-of-core hash fan-out into in-budget sub-aggregations "
    "(docs/hashagg.md). Off by default this round.")

AGG_HASH_MAX_SLOTS = register(
    "spark.rapids.sql.agg.hash.maxTableSlots", int, 1 << 17,
    "Slot-count bound of the hash-aggregation table "
    "(spark.rapids.sql.agg.hashAggEnabled): at the default 128Ki slots "
    "a 2-image key table is 2MiB plus accumulators. Batches sizing past "
    "the bound split by key hash (exec/outofcore.py) and aggregate per "
    "bucket — a handful of in-budget passes instead of one oversized "
    "table.", validator=_positive)

AGG_RUNTIME_SKIP = register(
    "spark.rapids.sql.agg.runtimeSkip", _to_bool, True,
    "AQE-style RUNTIME decision for the partial-aggregation skip: "
    "instead of committing to the first execution's first-batch ratio "
    "forever (the session-cache heuristic this replaces), the partial "
    "pass measures output_groups/input_rows per batch and flips to "
    "passthrough MID-STREAM once the cumulative measured ratio exceeds "
    "spark.rapids.sql.agg.skipAggPassReductionRatio — already-reduced "
    "partials flush as-is (the final aggregate reduces any mix). Each "
    "decision is journaled (aggSkipDecision event) with the measured "
    "rate, and decided signatures still seed the session cache so later "
    "executions skip from batch 0. false restores the legacy "
    "first-batch-only heuristic.")

CACHE_DEVICE_SCANS = register(
    "spark.rapids.sql.cacheDeviceScans", _to_bool, False,
    "Keep uploaded scan batches resident in device memory across query "
    "executions of the same source (the device-side analogue of a cached "
    "DataFrame). Trades HBM for re-upload cost; essential when the "
    "host-device link is high-latency.")

EXPLAIN = register(
    "spark.rapids.sql.explain", str, "NONE",
    "Explain why some parts of a query were or were not placed on the TPU. "
    "Possible values: NONE (default), ALL (full tag tree), NOT_ON_TPU "
    "(only nodes that did not make it).")

# --- memory pool & spill (ref RapidsConf.scala:241-307) --------------------
ALLOC_FRACTION = register(
    "spark.rapids.memory.tpu.allocFraction", float, 0.9,
    "Fraction of per-chip HBM the framework budgets for columnar buffers. The "
    "device store spills to host once the budget is exceeded.",
    validator=_fraction(0.0, 1.0))

HBM_DEBUG = register(
    "spark.rapids.memory.tpu.debug", _to_bool, False,
    "If true, log every device-store allocation/free for leak hunting.")

HOST_SPILL_STORAGE_SIZE = register(
    "spark.rapids.memory.host.spillStorageSize", _to_bytes, 1 << 30,
    "Amount of host memory used to cache spilled device buffers before "
    "spilling them further to disk.")

PINNED_POOL_SIZE = register(
    "spark.rapids.memory.pinnedPool.size", _to_bytes, 0,
    "Size of the aligned host staging pool used for device transfers. 0 "
    "disables pooling and allocates on demand.")

# --- batch sizing (ref RapidsConf.scala:309-328) ---------------------------
BATCH_SIZE_ROWS = register(
    "spark.rapids.sql.batchSizeRows", int, 1 << 20,
    "Target number of rows per columnar batch. Batches are padded up to a "
    "power-of-two capacity bucket to bound XLA recompilation.",
    validator=_positive)

MAX_READER_BATCH_SIZE_ROWS = register(
    "spark.rapids.sql.reader.batchSizeRows", int, 1 << 21,
    "Maximum rows a file reader materializes per batch.",
    validator=_positive)

CAPACITY_GROWTH = register(
    "spark.rapids.sql.batchCapacityGrowth", float, 2.0,
    "Growth factor between consecutive batch capacity buckets. 2.0 means "
    "power-of-two bucketing; smaller values trade recompiles for padding.",
    validator=_fraction(1.1, 4.0))

SHUFFLE_LOCAL_COLLAPSE = register(
    "spark.rapids.sql.shuffle.localCollapse", _to_bool, True,
    "When no device mesh is configured, collapse device-side shuffle "
    "exchanges to a single output partition instead of materializing n "
    "hash/range buckets. On one chip the buckets are pure overhead (they "
    "serialize anyway) and bucket-count readback costs a device->host "
    "round trip per window; the collapsed exchange is one fused concat "
    "with zero synchronization. Multi-chip meshes ignore this and "
    "exchange for real over ICI collectives.")

COLLECT_FUSED_FETCH_BYTES = register(
    "spark.rapids.sql.collect.fusedFetchBytes", _to_bytes, 4 << 20,
    "collect() fetches results in one device->host round trip (row counts "
    "and full-capacity buffers together) when the padded result size is "
    "under this threshold; larger results use two round trips (counts, "
    "then exact-length buffers). Trades one round trip's latency "
    "against fetching padding.")

# --- op enable/disable incl. incompat (ref RapidsConf.scala:339-430) -------
INCOMPATIBLE_OPS = register(
    "spark.rapids.sql.incompatibleOps.enabled", _to_bool, False,
    "Enable operators that produce results that differ from standard CPU "
    "semantics in corner cases (e.g. float aggregation ordering).")

IMPROVED_FLOAT_OPS = register(
    "spark.rapids.sql.improvedFloatOps.enabled", _to_bool, False,
    "Use TPU-optimized float operations that may not be bit-identical to the "
    "CPU implementations.")

ALLOW_FLOAT32_EXEC = register(
    "spark.rapids.sql.fast32BitFloat.enabled", _to_bool, False,
    "Execute float64 expressions in float32 on the TPU for speed. Results are "
    "approximate; off by default.")

HAS_NANS = register(
    "spark.rapids.sql.hasNans", _to_bool, True,
    "If float data may contain NaN; some ops tag themselves off the TPU when "
    "NaNs are possible and the kernel cannot match CPU NaN semantics.")

ENABLE_CAST_STRING_TO_NUMERIC = register(
    "spark.rapids.sql.castStringToInteger.enabled", _to_bool, False,
    "Enable casting strings to integral types on the TPU. Disabled by default "
    "because overflow corner cases differ from the CPU.")

ENABLE_CAST_STRING_TO_FLOAT = register(
    "spark.rapids.sql.castStringToFloat.enabled", _to_bool, False,
    "Enable casting strings to floating point on the TPU.")

ENABLE_CAST_FLOAT_TO_STRING = register(
    "spark.rapids.sql.castFloatToString.enabled", _to_bool, False,
    "Enable casting floating point to strings on the TPU; formatting differs "
    "from Java's in corner cases.")

ENABLE_CAST_STRING_TO_DATE = register(
    "spark.rapids.sql.castStringToDate.enabled", _to_bool, False,
    "Enable casting strings to dates on the TPU (yyyy-MM-dd prefix form, "
    "roundtrip-validated calendar). Disabled by default like the "
    "reference's string-to-timestamp classification.")

# --- file formats (ref RapidsConf.scala:433-474) ---------------------------
PARQUET_ENABLED = register(
    "spark.rapids.sql.format.parquet.enabled", _to_bool, True,
    "Enable Parquet input/output acceleration.")
PARQUET_READ_ENABLED = register(
    "spark.rapids.sql.format.parquet.read.enabled", _to_bool, True,
    "Enable accelerated Parquet scans.")
PARQUET_WRITE_ENABLED = register(
    "spark.rapids.sql.format.parquet.write.enabled", _to_bool, True,
    "Enable accelerated Parquet writes.")
CSV_ENABLED = register(
    "spark.rapids.sql.format.csv.enabled", _to_bool, True,
    "Enable CSV input acceleration.")
CSV_READ_ENABLED = register(
    "spark.rapids.sql.format.csv.read.enabled", _to_bool, True,
    "Enable accelerated CSV scans.")
METRICS_ENABLED = register(
    "spark.rapids.sql.metrics.enabled", _to_bool, True,
    "Collect per-operator SQL metrics (rows/batches/time; the reference's "
    "GpuMetricNames, GpuExec.scala:24-41) and per-query profile reports "
    "(session.profile_report()). Disabling removes every timer from the "
    "batch hot path. Profiler trace ranges are separate: see the "
    "spark.rapids.tpu.trace.* keys.")

ORC_ENABLED = register(
    "spark.rapids.sql.format.orc.enabled", _to_bool, True,
    "Enable ORC input/output acceleration.")
ORC_READ_ENABLED = register(
    "spark.rapids.sql.format.orc.read.enabled", _to_bool, True,
    "Enable accelerated ORC scans.")
ORC_WRITE_ENABLED = register(
    "spark.rapids.sql.format.orc.write.enabled", _to_bool, True,
    "Enable accelerated ORC writes.")

# --- scan pipeline (sql/scan_pipeline.py; the reference's MULTITHREADED/
# COALESCING reader modes, GpuParquetScan + GpuMultiFileReader) -------------
_non_negative = (lambda v: None if v >= 0
                 else f"must be >= 0, got {v}")

SCAN_PREFETCH_DEPTH = register(
    "spark.rapids.sql.scan.prefetchDepth", int, 2,
    "How many DECODED scan splits (Parquet row groups, ORC stripes, CSV "
    "files, in-memory slices) may wait ahead of the consuming task, "
    "beyond those still decoding. The shared host pool decodes ahead of "
    "the consumer, overlapping host decode with device upload/compute "
    "(the reference's MULTITHREADED reader, GpuParquetScan): one scan "
    "keeps as many decodes in flight as the pool has threads "
    "(spark.rapids.sql.scan.decodeThreads), refilled as each ends, so at "
    "most decodeThreads + prefetchDepth splits are submitted and not yet "
    "taken, of which at most decodeThreads are undecoded. Any positive "
    "value also gates the double-buffered upload in the host->device "
    "transition (batch i+1's device_put dispatched while batch i "
    "computes). 0 selects the LEGACY serial reader end to end (the "
    "reference's PERFILE mode analogue): synchronous full arrow->pandas "
    "decode on the consuming thread in strict pull order, pre-pipeline "
    "behavior exactly — the safe rollback path.",
    validator=_non_negative)

SCAN_DECODE_THREADS = register(
    "spark.rapids.sql.scan.decodeThreads", int, 0,
    "Worker threads in the process-wide scan decode pool (pyarrow "
    "releases the GIL, so decode genuinely overlaps python-side "
    "upload/compute). 0 = auto: min(4, max(2, cpu_count - 1)), leaving "
    "a core for the consuming task thread.",
    validator=_non_negative)

SCAN_PREFETCH_MAX_BYTES = register(
    "spark.rapids.sql.scan.prefetchMaxBytes", _to_bytes, 256 << 20,
    "Host-memory budget for decoded-but-unconsumed prefetched frames "
    "across one scan; submission stalls past it (clamped to "
    "spark.rapids.memory.host.spillStorageSize so prefetch never "
    "outgrows the spill framework's own host budget). A split is charged "
    "once decoded, so one scan's worst case is this budget plus the "
    "decodeThreads splits that were decoding when it filled.")

SCAN_DICT_NUMERICS = register(
    "spark.rapids.sql.scan.dictEncodeNumerics", _to_bool, False,
    "Dictionary-probe NUMERIC columns on FILE-scan uploads. Off by "
    "default: the probe + per-batch encode cost an element-wise pass "
    "per column per batch on the scan upload hot path, integer grouping "
    "keys already ride the dense-key path "
    "(spark.rapids.sql.agg.denseKeys), and float dictionary keys are "
    "rare. String columns are always probed, and in-memory uploads keep "
    "full probing (their small-table dictionaries pre-seed the "
    "aggregation fast path).")

SCAN_DIRECT_DECODE = register(
    "spark.rapids.sql.scan.directDecode", _to_bool, True,
    "Arrow->numpy direct decode for non-nullable primitive (int/float/"
    "bool) columns, skipping the pandas nullable-extension "
    "materialization on the scan hot path; columns with nulls, strings, "
    "dates and dictionaries fall back to the full arrow->pandas "
    "conversion. Value-identical either way. Part of the pipelined "
    "reader: ignored when spark.rapids.sql.scan.prefetchDepth is 0 (the "
    "legacy reader keeps the full conversion).")

SCAN_DEVICE_DECODE = register(
    "spark.rapids.sql.scan.deviceDecode", _to_bool, False,
    "Device-resident Parquet decode (docs/scan_device.md): read raw "
    "column-chunk bytes + page headers only (no host arrow "
    "materialization), upload encoded page payloads as flat word "
    "buffers, and decode PLAIN / RLE-dictionary / DELTA_BINARY_PACKED "
    "pages with the ops/parquet_decode kernels straight into dictionary-"
    "coded and char-slab device columns. Unsupported encodings/types "
    "fall back per column to the host decode path (journaled as "
    "scanDeviceFallback). Off by default: the legacy and pipelined host "
    "readers are byte-identical to pre-deviceDecode behavior.")

SCAN_PAGE_CACHE = register(
    "spark.rapids.sql.scan.pageCache.enabled", _to_bool, True,
    "Encoded-page cache tier for the deviceDecode path: column-chunk "
    "decode plans (run tables + encoded page bytes) cached by (path, "
    "mtime, row-group, column) so hot tables re-decode from cached — "
    "and, budget permitting, device-resident — pages instead of "
    "re-reading and re-uploading. Encoded pages are 5-20x smaller than "
    "decoded slabs. No effect while deviceDecode is off.")

SCAN_PAGE_CACHE_BYTES = register(
    "spark.rapids.sql.scan.pageCache.maxBytes", _to_bytes, 256 << 20,
    "Host-memory budget for the encoded-page cache (LRU past it).")

SCAN_PAGE_CACHE_DEVICE_BYTES = register(
    "spark.rapids.sql.scan.pageCache.deviceMaxBytes", _to_bytes, 64 << 20,
    "Device (HBM) budget for page-cache entries PROMOTED to device "
    "residency after their first upload; colder entries demote to the "
    "host tier (encoded bytes dropped from HBM, host plan kept).")

# --- gather-free execution (docs/gatherfree.md) ----------------------------
DICT_ENABLED = register(
    "spark.rapids.sql.dict.enabled", _to_bool, True,
    "Dictionary-encode low-cardinality string columns at upload and carry "
    "the encoded (codes-only) representation end-to-end through "
    "filter/join/agg/sort/exchange, decoding to chars only at "
    "collect()/write. Comparison, hashing and grouping run on int32 "
    "codes; per-value image tables (order-preserving prefix chunks, "
    "polynomial hashes) make cross-batch consumers exact without any "
    "char-space gathers. false disables dictionary encoding entirely — "
    "byte-identical legacy (chars + offsets) execution everywhere.")

DICT_MERGE_EXCHANGE = register(
    "spark.rapids.sql.dict.mergeOnExchange", _to_bool, True,
    "When batches with DIFFERENT dictionaries for the same string column "
    "meet at an exchange/concat boundary, union the (static, host-side) "
    "dictionaries and remap each part's codes through an O(cardinality) "
    "table instead of decoding to char slabs. Keeps columns codes-only "
    "across exchange boundaries. false falls back to decoding at the "
    "boundary (legacy).")

DICT_HASH_VALUES = register(
    "spark.rapids.sql.dict.hashValues", _to_bool, True,
    "Hash dictionary-encoded string columns for exchange partitioning and "
    "join keys through per-VALUE hash tables (the dictionary's values "
    "hashed once, rows gather by code) instead of the char-scanning "
    "polynomial hashes. Bit-identical hash values by construction — this "
    "only removes the char reads. false recomputes hashes from chars.")

DICT_WIRE = register(
    "spark.rapids.sql.dict.wire", _to_bool, True,
    "Ship dictionary-encoded string columns over the shuffle wire as "
    "int32 codes + the dictionary values (wire format v2) instead of "
    "materialized char slabs, and rebuild them codes-only on the reduce "
    "side. false writes legacy v1 chars+offsets frames (dictionary "
    "columns decode host-side at serialization, still with no device "
    "char gather).")

DICT_BLOCKED_CHARS = register(
    "spark.rapids.sql.dict.blockedChars", _to_bool, True,
    "Blocked char-slab movement for plain (non-dictionary) string "
    "columns: rows are carried as a fixed-stride (capacity, stride/8) "
    "uint64 slab so row movement (gathers, join expands, concats) is a "
    "2-D lane-contiguous row gather — the stacked-gather form measured "
    "4-6x cheaper than the 1-D char-index gather — and sort/group/hash "
    "images derive densely from the slab words with no char gathers at "
    "all. Packed chars+offsets materialize lazily only when an operator "
    "actually needs them. Applies to columns whose longest row fits "
    "spark.rapids.sql.dict.blockedChars.maxStride. false keeps the "
    "legacy packed layout everywhere.")

DICT_BLOCKED_MAX_STRIDE = register(
    "spark.rapids.sql.dict.blockedChars.maxStride", int, 64,
    "Largest per-row byte stride (rounded up to a power of two, min 8) a "
    "string column may have and still ride the blocked char-slab "
    "representation; longer columns keep the packed layout. The slab "
    "costs capacity x stride bytes of HBM, so this bounds padding bloat "
    "for mostly-short columns with rare long rows.", validator=_positive)

SMALL_QUERY_ENABLED = register(
    "spark.rapids.sql.smallQuery.enabled", _to_bool, True,
    "Tiny-query overhead-floor fast path: when every leaf source of a "
    "plan reports a known row count and the total fits one resident "
    "batch under spark.rapids.sql.smallQuery.maxRows, plan every "
    "exchange single-partition (hash/range partitioning degenerates to "
    "a LOCAL collapse — no row hashing, no partition-id sort, no "
    "per-bucket slices), skip the collapse's capacity-shrink "
    "device->host sync, and skip the task-admission semaphore. The "
    "packed result fetch already coalesces the whole output into one "
    "transfer. false restores the general path exactly.")

SMALL_QUERY_MAX_ROWS = register(
    "spark.rapids.sql.smallQuery.maxRows", int, 32768,
    "Row-count ceiling (summed over all leaf sources with known counts) "
    "under which the small-query fast path engages. Also clamped to one "
    "batch: inputs above spark.rapids.sql.batchSizeRows never engage.",
    validator=_positive)

SMALL_QUERY_LITE = register(
    "spark.rapids.sql.smallQuery.liteBookkeeping", _to_bool, True,
    "With the small-query fast path engaged, replace the per-batch-pull "
    "operator bookkeeping (per-batch timers, tracer spans, ledger "
    "scopes) with one per-partition record per operator. Per-operator "
    "SQL metrics stay populated (one batch entry per partition); "
    "profile syncEachOp, tracing, live progress and cancellation scopes "
    "all force the full wrapper back on. Pure fixed-cost removal for "
    "queries whose wall time is dominated by Python dispatch.")

# --- test hooks (ref RapidsConf.scala:476-501) -----------------------------
TEST_ENABLED = register(
    "spark.rapids.sql.test.enabled", _to_bool, False,
    "Intended for framework tests only. When true a query fails if any "
    "operator not in the allowed list runs on the CPU "
    "(the reference's assertIsOnTheGpu behavior, "
    "GpuTransitionOverrides.scala:225-263).")

TEST_ALLOWED_NONTPU = register(
    "spark.rapids.sql.test.allowedNonTpu", str, "",
    "Comma-separated list of operator class names allowed on the CPU when "
    "test mode is enabled.")

# --- hashAgg (ref RapidsConf.scala:503-518) --------------------------------
HASH_AGG_REPLACE_MODE = register(
    "spark.rapids.sql.hashAgg.replaceMode", str, "all",
    "Which aggregation modes to replace: 'all', 'partial', or 'final'.")

# --- execution -------------------------------------------------------------
CONCURRENT_TPU_TASKS = register(
    "spark.rapids.sql.concurrentTpuTasks", int, 1,
    "Number of concurrent tasks admitted to the TPU at once (the reference's "
    "GpuSemaphore admission model, GpuSemaphore.scala:101-161).",
    validator=_positive)

NUM_TASK_THREADS = register(
    "spark.rapids.sql.taskThreads", int, 4,
    "Host-side worker threads executing partitions (Spark task equivalent).",
    validator=_positive)

SHUFFLE_PARTITIONS = register(
    "spark.rapids.sql.shuffle.partitions", int, 8,
    "Default number of shuffle output partitions (spark.sql.shuffle.partitions "
    "equivalent).", validator=_positive)

BROADCAST_THRESHOLD = register(
    "spark.rapids.sql.autoBroadcastJoinThreshold", _to_bytes, 10 << 20,
    "Maximum estimated build-side size for which a join uses a broadcast "
    "exchange instead of hash-partitioned exchanges "
    "(spark.sql.autoBroadcastJoinThreshold equivalent). -1 disables.")

STAGE_FUSION = register(
    "spark.rapids.sql.stageFusion.enabled", _to_bool, True,
    "Trace chains of narrow operators (project/filter/partial-agg) into a "
    "single XLA executable so the compiler fuses them. TPU-first feature with "
    "no reference equivalent: cuDF dispatches one kernel per op.")

FUSION_STAGE_ENABLED = register(
    "spark.rapids.sql.fusion.stageEnabled", _to_bool, False,
    "Whole-stage fusion (exec/stagecompiler/): cut the converted physical "
    "plan into fusible pipelines at exchange/scan/fallback boundaries and "
    "emit ONE jit-compiled program per pipeline (TpuFusedStageExec) "
    "instead of one dispatch per operator — chains of deterministic "
    "Project/Filter (with interleaved batch coalescing absorbed) run as a "
    "single XLA executable with the intermediate buffers donated inside "
    "the program. false (default) keeps today's per-operator plans "
    "byte-identical. Fused stages report "
    "their member-operator pipeline to the compile ledger, profile tree, "
    "progress records and flight recorder.")

FUSION_MIN_OPS = register(
    "spark.rapids.sql.fusion.minOperators", int, 2,
    "Minimum number of compute operators (projects/filters) a pipeline "
    "must contain before whole-stage fusion replaces it with a fused "
    "stage; shorter chains keep their standalone kernels (fusing one "
    "operator only renames its dispatch).", validator=_positive)

FUSION_DONATE = register(
    "spark.rapids.sql.fusion.donateInputs", _to_bool, False,
    "Donate the input batch's device buffers to the fused-stage program "
    "(jax donate_argnums), letting XLA reuse them for the stage's "
    "intermediates. Only applied when the stage input is a known "
    "single-consumer producer (exchange/join/aggregate output) AND "
    "spark.rapids.sql.reuseSubtrees.enabled is false — the reuse pass "
    "rewrites the tree after stage cutting and replays the same batches "
    "to every consumer of a shared subtree, which donation must never "
    "touch. Off by default: within one fused program XLA already reuses "
    "intermediate buffers, donation only adds the input itself.")

JOIN_EXACT_LONG_STRINGS = register(
    "spark.rapids.sql.join.exactLongStrings", _to_bool, True,
    "String join keys longer than the 64-byte sort prefix are verified "
    "with extended-prefix re-sorting and full-length compares of "
    "candidate ties (exact, default). false keeps the dual 64-bit hash "
    "tiebreak: faster on long-string keys but probabilistic equality "
    "beyond 64 bytes (incompat).")

# --- shuffle transport (ref RapidsConf.scala:520-601) ----------------------
SHUFFLE_FETCH_RETRIES = register(
    "spark.rapids.shuffle.maxFetchRetries", int, 3,
    "Bounded retries PER PEER GROUP when a shuffle fetch fails over the "
    "transport before the error propagates: a failure re-fetches only "
    "that peer's blocks (the in-process analogue of the reference "
    "mapping transport errors into Spark's stage retry).")

SHUFFLE_TRANSPORT_ENABLED = register(
    "spark.rapids.shuffle.transport.enabled", _to_bool, False,
    "Enable the accelerated shuffle manager: shuffle blocks stay in device "
    "memory (spilling through the store framework) and move between workers "
    "over the mesh interconnect instead of the host serializer path.")

SHUFFLE_TRANSPORT_CLASS = register(
    "spark.rapids.shuffle.transport.class", str, "inprocess",
    "Transport implementation for the accelerated shuffle manager: "
    "'inprocess' (direct-call, single process) or 'socket' (real TCP "
    "loopback framing — the wire path the reference runs over UCX, "
    "UCXShuffleTransport.scala). The SPI accepts other implementations "
    "by class path.")

SHUFFLE_EXECUTORS = register(
    "spark.rapids.shuffle.executors", int, 1,
    "Number of simulated executors for the accelerated shuffle manager: "
    "map tasks stripe across this many ShuffleEnvs (each with its own "
    "transport endpoint and server), so reduce-side fetches of other "
    "executors' blocks traverse the full serializer->server->client wire "
    "path instead of the local catalog.", validator=_positive)

SHUFFLE_MAX_INFLIGHT = register(
    "spark.rapids.shuffle.maxMetadataFetchesInFlight", int, 128,
    "Bound on simultaneous in-flight shuffle fetches per task.",
    validator=_positive)

SHUFFLE_BOUNCE_BUFFER_SIZE = register(
    "spark.rapids.shuffle.bounceBuffers.size", _to_bytes, 4 << 20,
    "Size of each staging (bounce) buffer used when moving shuffle data "
    "between tiers or peers.")

SHUFFLE_BOUNCE_BUFFER_COUNT = register(
    "spark.rapids.shuffle.bounceBuffers.count", int, 16,
    "Number of staging buffers per direction.", validator=_positive)

SHUFFLE_TRANSPORT_MODE = register(
    "spark.rapids.tpu.shuffle.transport.mode", str, "legacy",
    "Per-edge shuffle transport selection (shuffle/manager.py "
    "ShuffleTransportKind). 'legacy' (default) reproduces the historical "
    "selection byte-identically: a configured device mesh routes "
    "hash/range (and device-count roundrobin) exchanges over the ICI "
    "mesh collective, spark.rapids.shuffle.transport.enabled routes them "
    "through the catalog+transport shuffle manager (inprocess/socket "
    "wire), everything else collapses locally. 'auto' picks per edge: "
    "in-slice edges (a mesh is configured and the partitioning is mesh-"
    "compatible) ride ICI, cross-host edges (a multi-executor transport "
    "pool is configured) ride the socket/DCN manager path, the rest stay "
    "local. 'ici' forces the mesh collective for every compatible edge "
    "(local fallback without a mesh); 'manager' forces the shuffle-"
    "manager wire path; 'local' forces single-process collapse — the "
    "rollback switch.",
    validator=(lambda v: None if str(v) in
               ("legacy", "auto", "ici", "manager", "local")
               else f"must be one of legacy|auto|ici|manager|local, "
                    f"got {v}"))

# --- out-of-core (larger-than-HBM) operators (exec/outofcore.py: grace
# hash join, external merge sort, spillable agg maps on the 3-tier spill
# store — PAPER.md L2's multi-tier store driven by measured sizes) ----------
OOC_ENABLED = register(
    "spark.rapids.tpu.outOfCore.enabled", _to_bool, False,
    "Out-of-core execution for join/aggregate/sort: when an operator's "
    "measured device working set exceeds the working-set budget "
    "(spark.rapids.tpu.outOfCore.partitionBytes), its input is hash- (or "
    "for sort, range-) partitioned into spillable fan-out buckets "
    "registered on the 3-tier store (HBM->host->disk, memory/spill.py) "
    "and processed one bucket at a time: grace hash join (build-side "
    "fragments recursed when still over budget), external merge sort, "
    "and per-bucket aggregate merges. Fan-out is chosen from the same "
    "measured batch sizes AQE collects. false (default) keeps every "
    "operator's in-HBM path byte-identical.")

OOC_PARTITION_BYTES = register(
    "spark.rapids.tpu.outOfCore.partitionBytes", _to_bytes, 0,
    "Working-set budget of one out-of-core operator: partitioning fans "
    "out until each bucket is expected to fit in this many bytes, and "
    "the device store is synchronously spilled down to it while buckets "
    "accumulate. 0 (default) = auto: half the metered HBM budget "
    "(spark.rapids.memory.tpu.allocFraction x device HBM). Tests set a "
    "tiny value to force spilling at toy scale.")

OOC_FANOUT = register(
    "spark.rapids.tpu.outOfCore.fanout", int, 0,
    "Fixed fan-out (bucket count) for out-of-core partitioning. 0 "
    "(default) = auto from measured sizes: the next power of two of "
    "total_bytes / partitionBytes, clamped to [2, 64].",
    validator=_non_negative)

OOC_MAX_RECURSION = register(
    "spark.rapids.tpu.outOfCore.maxRecursion", int, 3,
    "Grace hash join recursion bound: a bucket whose build fragment "
    "still exceeds the working-set budget is re-partitioned with a "
    "different hash up to this many levels; past it the fragment joins "
    "in one pass regardless (correct, just memory-hungry — mirrors the "
    "reference's sub-partitioning bound).", validator=_positive)

EXPORT_COLUMNAR_RDD = register(
    "spark.rapids.sql.exportColumnarRdd", _to_bool, False,
    "Expose query output as device-resident columnar data for ML frameworks "
    "(the reference's ColumnarRdd zero-copy export, ColumnarRdd.scala:41-50).")

# --- observability (obs/: tracing + profile reports) -----------------------
TRACE_ENABLED = register(
    "spark.rapids.tpu.trace.enabled", _to_bool, False,
    "Collect structured tracer spans (exec operators, shuffle fetches, "
    "spill tier transitions, semaphore waits, kernel-cache events) during "
    "query execution. Implied by a non-empty spark.rapids.tpu.trace.path. "
    "The NVTX-range analogue (NvtxWithMetrics.scala:17-44); see "
    "docs/observability.md for the span classification.")

TRACE_PATH = register(
    "spark.rapids.tpu.trace.path", str, "",
    "When set, every query execution exports its spans as Chrome "
    "trace-event JSON to this file (overwritten per query), viewable in "
    "Perfetto (ui.perfetto.dev) or chrome://tracing. Setting a path "
    "enables tracing.")

TRACE_JAX_ANNOTATIONS = register(
    "spark.rapids.tpu.trace.jaxAnnotations", _to_bool, False,
    "Mirror tracer spans into jax.profiler.TraceAnnotation ranges so they "
    "appear in a captured jax/XLA profiler trace alongside the compiler's "
    "own events. Off by default: annotations cost a context manager per "
    "span even when no jax profiler session is active.")

EVENT_LOG_ENABLED = register(
    "spark.rapids.tpu.eventLog.enabled", _to_bool, False,
    "Write the process-wide structured event journal (obs/events.py): "
    "query start/end with conf fingerprint and plan digest, per-operator "
    "CPU-fallback reasons, spill/memory-pressure events, shuffle fetch "
    "retries/failures, compile-cache misses and scan-pipeline stalls, as "
    "line-delimited JSON. The durable cross-query record "
    "tools/qualification.py mines (the reference's history-server "
    "event-log role). Implied by a non-empty "
    "spark.rapids.tpu.eventLog.path.")

EVENT_LOG_PATH = register(
    "spark.rapids.tpu.eventLog.path", str, "",
    "Destination of the event journal (appended, rotated at "
    "spark.rapids.tpu.eventLog.maxFileBytes). Setting a path enables the "
    "journal; enabled with no path writes ./tpu-eventlog.jsonl.")

EVENT_LOG_MAX_BYTES = register(
    "spark.rapids.tpu.eventLog.maxFileBytes", _to_bytes, 16 << 20,
    "Size bound of the active event-log file; past it the file rotates "
    "to <path>.1 (older rotations shift up). Rotation and write-failure "
    "counts surface in the profile report's observability section.",
    validator=_positive)

EVENT_LOG_ROTATIONS = register(
    "spark.rapids.tpu.eventLog.rotatedFiles", int, 2,
    "How many rotated event-log files (<path>.1 .. <path>.N) to keep; "
    "0 truncates in place at the size bound instead of rotating.",
    validator=_non_negative)

# --- adaptive query execution (sql/adaptive/; the reference's AQE role:
# GpuShuffleExchangeExec reports MapOutputStatistics so Spark re-plans at
# runtime — coalesced partitions, demoted broadcasts, split skew) ----------
ADAPTIVE_ENABLED = register(
    "spark.rapids.sql.adaptive.enabled", _to_bool, False,
    "Adaptive query execution: cut the physical plan into query stages at "
    "hash-exchange boundaries, materialize each stage's map side, fold the "
    "observed per-partition sizes into MapOutputStatistics and re-optimize "
    "the not-yet-executed remainder (partition coalescing, dynamic "
    "broadcast conversion, skew-join splitting — sql/adaptive/). false "
    "(default) keeps the LEGACY single-shot planner byte-identical. "
    "Ignored on a device mesh (mesh exchanges are real ICI collectives; "
    "host-side stage materialization would defeat them).")

ADAPTIVE_COALESCE_ENABLED = register(
    "spark.rapids.sql.adaptive.coalesce.enabled", _to_bool, True,
    "With AQE on, merge adjacent reduce partitions whose combined "
    "measured size is below "
    "spark.rapids.sql.adaptive.coalesce.minPartitionSize, so the reduce "
    "side runs fewer, fuller tasks (Spark's CoalesceShufflePartitions). "
    "Join inputs coalesce jointly (combined sizes) to stay "
    "co-partitioned.")

ADAPTIVE_COALESCE_MIN_SIZE = register(
    "spark.rapids.sql.adaptive.coalesce.minPartitionSize", _to_bytes,
    8 << 20,
    "Target (and minimum) measured byte size of one post-coalesce reduce "
    "partition; adjacent partitions merge until the group reaches it. "
    "Also the advisory target size of one skew-split sub-partition.",
    validator=_positive)

ADAPTIVE_BROADCAST_ENABLED = register(
    "spark.rapids.sql.adaptive.broadcast.enabled", _to_bool, True,
    "With AQE on, replace a planned shuffled-hash join with a broadcast "
    "hash join when the build side's MEASURED materialized size comes in "
    "under spark.rapids.sql.autoBroadcastJoinThreshold (which the static "
    "planner could not prove from estimates). The already-materialized "
    "map output is reused as the broadcast table — the source is never "
    "re-read — and a not-yet-materialized stream-side shuffle is elided "
    "entirely.")

ADAPTIVE_SKEW_ENABLED = register(
    "spark.rapids.sql.adaptive.skewJoin.enabled", _to_bool, True,
    "With AQE on, split a skewed reduce partition of a shuffled join "
    "into map-range sub-partitions on the skewed side and replicate the "
    "matching partition on the other side (Spark's "
    "OptimizeSkewedJoin). A partition is skewed when its measured size "
    "exceeds skewedPartitionFactor x the median AND "
    "skewedPartitionThreshold.")

ADAPTIVE_SKEW_FACTOR = register(
    "spark.rapids.sql.adaptive.skewJoin.skewedPartitionFactor", float, 5.0,
    "Multiple of the median reduce-partition size beyond which a join "
    "partition counts as skewed.", validator=_positive)

ADAPTIVE_SKEW_THRESHOLD = register(
    "spark.rapids.sql.adaptive.skewJoin.skewedPartitionThreshold",
    _to_bytes, 4 << 20,
    "Minimum measured byte size for a reduce partition to count as "
    "skewed (guards the factor test against tiny shuffles).",
    validator=_positive)

FLIGHT_RECORDER_SIZE = register(
    "spark.rapids.tpu.eventLog.flightRecorderSize", int, 256,
    "Entries in the always-on flight-recorder ring (last N events, plus "
    "spans while tracing is on), auto-dumped into the event log when a "
    "query fails and exposed as session.dump_flight_recorder(). The ring "
    "runs even with the event log and tracer disabled — one deque append "
    "per (rare) event.", validator=_positive)

EVENT_LOG_COMPRESS = register(
    "spark.rapids.tpu.eventLog.compress", _to_bool, False,
    "Gzip-compress rotated event-log segments: at the size bound the "
    "active file compresses to <path>.1.gz instead of renaming to "
    "<path>.1 (the active file stays plaintext so appends never pay "
    "per-event compression). tools/qualification.py, "
    "tools/trace_summary.py and tools/history_server.py read plaintext "
    "and gzip segments transparently (magic-byte sniff), including "
    "mixed chains from toggling this mid-run. Bounds the on-disk "
    "footprint of long sweeps (~10-20x smaller rotated segments on "
    "typical JSONL).")

# --- live monitoring UI (obs/monitor.py: Prometheus /metrics, query-
# progress API, per-tenant accounting; the headless Spark-UI analogue) -----
UI_ENABLED = register(
    "spark.rapids.tpu.ui.enabled", _to_bool, False,
    "Serve the embedded live monitoring service (obs/monitor.py): "
    "GET /metrics (process-wide registry in Prometheus text format), "
    "/healthz, /api/status (device + HBM pool watermarks, semaphore "
    "permits, event-log drop counts), /api/queries + /api/query/<id> "
    "(live per-query progress: plan tree with per-operator rows/batches/"
    "time so far, AQE stage progress and decisions, scan/shuffle/spill "
    "counters), /api/tenants (per-tenant accounting from "
    "session.set_job_group tags), and a minimal HTML live view at /. "
    "false (default): no server thread starts and the progress "
    "heartbeat path is a single disabled-flag check — zero overhead.")

UI_PORT = register(
    "spark.rapids.tpu.ui.port", int, 4040,
    "TCP port of the live monitoring service (the Spark-UI port by "
    "convention). 0 binds an ephemeral port (tests); the bound port is "
    "available as obs.monitor.server().port. A bind failure logs a "
    "warning and disables the UI for the process instead of failing "
    "queries.", validator=_non_negative)

UI_HOST = register(
    "spark.rapids.tpu.ui.host", str, "127.0.0.1",
    "Bind address of the live monitoring service. Loopback by default; "
    "set 0.0.0.0 to expose it beyond the host (the service is read-only "
    "but unauthenticated — front it appropriately).")

UI_RECENT_QUERIES = register(
    "spark.rapids.tpu.ui.recentQueries", int, 64,
    "How many recently-finished queries /api/queries keeps alongside the "
    "in-flight set (a bounded ring; oldest evicted first).",
    validator=_positive)

# --- compile & dispatch ledger (obs/compileledger.py: per-operator XLA
# compile attribution, recompile-cause analysis — the instrument behind
# tools/compile_report.py and the fusion work's timed_compiles->0 goal) ----
COMPILE_LEDGER_ENABLED = register(
    "spark.rapids.tpu.compileLedger.enabled", _to_bool, True,
    "Record every XLA backend compile in the process-wide compile ledger "
    "(obs/compileledger.py): triggering plan operator, query, kernel "
    "identity, input shape/dtype signature, persistent-cache outcome and "
    "compile seconds, in a bounded in-memory ring. Feeds the profile "
    "report's 'compiles' section, enriched backendCompile journal "
    "events, the live monitor's srt_compile_* series and /api/query "
    "compile stats, flight-recorder failure dumps, and "
    "tools/compile_report.py's recompile-cause analysis. On by default: "
    "compiles are rare and the steady-state dispatch overhead is one "
    "flag check plus two thread-local stores per kernel call.")

COMPILE_LEDGER_MAX_ENTRIES = register(
    "spark.rapids.tpu.compileLedger.maxEntries", int, 2048,
    "Entries kept in the compile ledger's bounded ring (oldest evicted "
    "first). 2048 covers ~50 fully-cold warm-up queries at the observed "
    "19-36 compiles per query.", validator=_positive)

# --- host-sync ledger (obs/syncledger.py: per-site attribution of every
# device<->host blocking point, the device-occupancy instrument behind
# ROADMAP item 4's syncs-per-query metric and perfdiff's sync gate) --------
SYNC_LEDGER_ENABLED = register(
    "spark.rapids.tpu.sync.ledger.enabled", _to_bool, True,
    "Record every device<->host blocking point (collect/exchange "
    "fetches, shrink/range-bounds/split-count syncs, out-of-core "
    "working-set measurement, scan-pipeline stalls, semaphore waits) in "
    "the process-wide host-sync ledger (obs/syncledger.py): sync site, "
    "wall seconds, bytes moved, triggering plan operator, query and "
    "thread, in a bounded in-memory ring. Feeds the profile report's "
    "'syncs' section and device-occupancy estimate, hostSync journal "
    "events, the sync track in the Chrome trace export, the live "
    "monitor's srt_host_sync* series and /api/query sync stats, "
    "flight-recorder failure dumps and the benchmark's "
    "syncs_per_query. On by default: "
    "syncs are the expensive operation being measured, so the "
    "bookkeeping is noise next to the blocked wall time it accounts.")

SYNC_LEDGER_MAX_ENTRIES = register(
    "spark.rapids.tpu.sync.ledger.maxEntries", int, 4096,
    "Entries kept in the host-sync ledger's bounded ring (oldest "
    "evicted first). Steady-state queries record a handful of syncs "
    "each; 4096 covers a long run between watermark reads.",
    validator=_positive)

SYNC_LEDGER_EVENT_MIN_SECONDS = register(
    "spark.rapids.tpu.sync.ledger.eventMinSeconds", float, 0.0,
    "Minimum blocked seconds before a sync also lands as a hostSync "
    "journal event (the ledger entry and Prometheus series record it "
    "regardless). 0 journals every sync; raise it on chatty "
    "deployments where per-batch scalar syncs would dominate the "
    "event log.", validator=_non_negative)

DEBUG_TRANSFER_GUARD = register(
    "spark.rapids.tpu.debug.transferGuard", str, "off",
    "Coverage audit for the host-sync ledger: run query execution "
    "under jax's device->host transfer guard. 'log' logs every "
    "explicit device fetch that happens OUTSIDE a sync_scope; "
    "'disallow' raises on it (sync scopes re-enter 'allow', so every "
    "tracked site passes). Off by default — a debugging instrument, "
    "not a production conf; guard levels only fire on real "
    "accelerator platforms (CPU-backend fetches are same-device "
    "copies).",
    validator=lambda v: None if v in ("off", "log", "disallow")
    else f"must be off|log|disallow, got {v}")

# --- zero-warm-up serving (utils/kernelcache.py shape buckets,
# obs/compilecache.py shared cache, serving/prewarm.py AOT replay — the
# ledger's recompile-cause analysis ACTED on: one compile serves a
# dimension range, each kernel compiles once per cluster, and history
# pre-warms a fresh process before traffic arrives) ------------------------
COMPILE_SHAPE_BUCKETS = register(
    "spark.rapids.tpu.compile.shapeBuckets", _to_bool, False,
    "Bucket-padded kernel signatures on the batch path: SECONDARY shape "
    "dimensions the recompile-cause analyzer flags as varying (join "
    "build-table capacities, join-expansion output capacities, "
    "aggregation group capacities, hash-table sizes, string char-slab "
    "capacities) are padded up to a coarser bucket ladder at the "
    "cached-kernel dispatch boundary (utils/kernelcache.bucket_dim), so "
    "ONE compile serves a dimension range instead of one per observed "
    "bucket. Row counts stay exact (num_rows is data; the padding region "
    "is masked exactly like today's capacity padding), so results are "
    "value-identical — only capacities grow. false (default) is "
    "byte-identical to the unpadded engine. Batch ROW "
    "capacities (spark.rapids.sql.batchSizeRows buckets) are already "
    "the stable primary dimension and are never re-padded.")

COMPILE_SHAPE_BUCKETS_MIN = register(
    "spark.rapids.tpu.compile.shapeBuckets.minBucket", int, 4096,
    "Floor of the coarse secondary-dimension bucket ladder: every padded "
    "dimension is at least this, collapsing the small buckets "
    "(8..minBucket/2) — the long tail of per-query build-table and "
    "char-slab compiles — into one compiled shape. Padding cost is "
    "bounded by minBucket elements per small dimension.",
    validator=_positive)

COMPILE_SHAPE_BUCKETS_GROWTH = register(
    "spark.rapids.tpu.compile.shapeBuckets.growth", float, 2.0,
    "Growth factor between coarse secondary-dimension buckets above the "
    "floor. 2.0 keeps the analyzer's power-of-two ladder; 4.0 halves the "
    "number of compiled shapes again at the cost of up to 4x padding on "
    "those dimensions.", validator=_fraction(1.1, 16.0))

COMPILE_SHARED_CACHE_DIR = register(
    "spark.rapids.tpu.compile.sharedCache.dir", str, "",
    "Directory of the CROSS-PROCESS compile manifest "
    "(obs/compilecache.py SharedCompileCache). When set, every backend "
    "compile appends a file-locked record to <dir>/manifest.jsonl "
    "(versioned keys carry the jax version + backend + machine so a "
    "foreign executable is never attributed as warm), the census of "
    "what a fleet of workers has compiled. The executables themselves "
    "live in jax's persistent cache, whose directory is the process "
    "environment's (JAX_COMPILATION_CACHE_DIR, else <checkout>/"
    ".jax_cache on an accelerator) and is never re-pointed by this "
    "conf: workers that share that directory compile each kernel once "
    "per CLUSTER, not once per process. Hit/miss/steal/write counters "
    "surface as srt_sharedcache_* Prometheus series ('steal' = this "
    "process reused an executable another process compiled). Empty "
    "(default) disables — the per-process behavior is unchanged.")

COMPILE_AOT_MANIFEST = register(
    "spark.rapids.tpu.compile.aot.manifest", str, "",
    "Path of an AOT pre-warm manifest (tools/compile_report.py "
    "--aot-manifest, distilled from a sweep's event log): observed "
    "kernel identities + shape signatures + replayable argument specs. "
    "When set, the session starts a background pre-warm pass "
    "(serving/prewarm.py): as each listed kernel is built, every "
    "historical shape signature recorded for it is compiled (and its "
    "jit dispatch cache warmed) on a worker thread — overlapping "
    "planning/scan instead of serializing into first-query latency, "
    "and pulling executables straight out of the shared cache when one "
    "is configured. Cancellable, budget-capped "
    "(compile.aot.budgetSeconds); progress (warmed/pending/skipped) "
    "surfaces at /api/status and as srt_aot_* series. Empty (default) "
    "disables.")

COMPILE_AOT_BUDGET = register(
    "spark.rapids.tpu.compile.aot.budgetSeconds", float, 120.0,
    "Wall-clock budget of the AOT pre-warm pass; once spent, remaining "
    "manifest entries are left to warm on demand (counted as pending, "
    "never blocking queries — the pass runs strictly in the "
    "background). 0 disables the cap.", validator=_non_negative)

COMPILE_LEDGER_COST_ANALYSIS = register(
    "spark.rapids.tpu.compileLedger.costAnalysis", _to_bool, False,
    "After each backend compile, re-lower the kernel and attach XLA "
    "cost_analysis() FLOPs and bytes-accessed to its ledger entry. Off "
    "by default: the re-trace measurably slows warm-up (it re-runs "
    "tracing for every freshly compiled kernel); enable it for roofline "
    "attribution passes.")

# --- concurrent query serving (serving/: admission scheduler, per-tenant
# HBM quotas, cross-query plan/result caches — the reference's long-lived
# driver-plugin service role grown into a multi-tenant front-end) ----------
SERVING_WORKERS = register(
    "spark.rapids.tpu.serving.workers", int, 4,
    "Worker threads in the admission scheduler's pool "
    "(serving/scheduler.py): how many queries execute concurrently. "
    "Device admission is still bounded separately by "
    "spark.rapids.sql.concurrentTpuTasks and the per-tenant permit "
    "budgets.", validator=_positive)

SERVING_MAX_QUEUED = register(
    "spark.rapids.tpu.serving.maxQueuedQueries", int, 128,
    "Bound on TOTAL queued (admitted but not yet running) jobs across "
    "all tenant lanes; a submission past it is load-shed immediately "
    "(job status 'shed', a queryShed journal event, serving.shed "
    "counters) instead of building an unbounded backlog.",
    validator=_positive)

SERVING_DEFAULT_DEADLINE = register(
    "spark.rapids.tpu.serving.defaultDeadlineSeconds", float, 0.0,
    "Default per-query deadline for scheduler-submitted jobs, counted "
    "from submission; 0 disables. A job still queued past its deadline "
    "never starts; a running one cancels cooperatively at its next "
    "batch-pull boundary (queryTimeout journal event with the "
    "flight-recorder tail attached). Per-job deadline_s overrides.",
    validator=_non_negative)

SERVING_TENANT_DEFAULT_PERMITS = register(
    "spark.rapids.tpu.serving.tenant.defaultPermits", int, 0,
    "Default per-tenant device-admission budget: the maximum task "
    "semaphore permits one tenant's tasks may hold concurrently, so a "
    "single tenant cannot occupy every concurrentTpuTasks slot and "
    "starve the device for the rest. 0 = no tenant bound (global limit "
    "only). Override per tenant with "
    "spark.rapids.tpu.serving.tenant.<name>.permits; per-tenant "
    "holder/waiter gauges surface at /api/scheduler and /metrics.",
    validator=_non_negative)

SERVING_TENANT_DEFAULT_WEIGHT = register(
    "spark.rapids.tpu.serving.tenant.defaultWeight", float, 1.0,
    "Default weighted-fair share of a tenant's lane in the admission "
    "scheduler: the dispatcher serves the non-empty lane with the "
    "least virtual time and serving advances it by 1/weight, so a "
    "weight-3 tenant is dispatched 3x as often under contention. "
    "Override per tenant with "
    "spark.rapids.tpu.serving.tenant.<name>.weight.",
    validator=_positive)

SERVING_PLAN_CACHE = register(
    "spark.rapids.tpu.serving.planCache.enabled", _to_bool, True,
    "Cross-query plan cache (serving/caches.py): repeat submissions of "
    "the same query shape under the same explicit conf and the same "
    "source data versions (file mtimes / in-memory content digests) "
    "skip the tag+convert planning pass entirely and execute a clone "
    "of the cached physical plan — zero re-planning, and identical "
    "operator signatures keep every compiled kernel warm "
    "(timed_compiles stays 0). Keyed by (plan digest, conf "
    "fingerprint, source versions); a conf change or a rewritten "
    "table misses. AQE queries are excluded (their plans are runtime-"
    "re-planned per execution; see exchangeReuse instead).")

SERVING_PLAN_CACHE_MAX = register(
    "spark.rapids.tpu.serving.planCache.maxEntries", int, 256,
    "LRU entry bound of the cross-query plan cache.",
    validator=_positive)

SERVING_RESULT_CACHE = register(
    "spark.rapids.tpu.serving.resultCache.enabled", _to_bool, False,
    "Opt-in cross-query RESULT cache for identical dashboard-style "
    "queries: a repeat submission under the same (plan digest, conf "
    "fingerprint, source versions) key answers straight from the "
    "cached host frames with zero execution (resultCacheHit journal "
    "event, srt_resultcache_* series). Only deterministic, non-writing "
    "plans are cached; hits return defensive copies. Off by default: "
    "serving workloads opt in per session.")

SERVING_RESULT_CACHE_MAX = register(
    "spark.rapids.tpu.serving.resultCache.maxEntries", int, 64,
    "LRU entry bound of the result cache.", validator=_positive)

SERVING_RESULT_CACHE_MAX_BYTES = register(
    "spark.rapids.tpu.serving.resultCache.maxBytes", _to_bytes,
    256 << 20,
    "Byte bound of the result cache (pandas deep memory usage of the "
    "cached frames); a single result larger than this is never cached "
    "and the LRU evicts oldest-first past it.", validator=_positive)

SERVING_EXCHANGE_REUSE = register(
    "spark.rapids.tpu.serving.exchangeReuse.enabled", _to_bool, False,
    "Opt-in cross-query AQE exchange reuse (serving/caches.py): a new "
    "adaptive query whose exchange subtree digest (structure + source "
    "data versions + conf fingerprint) matches an already-materialized "
    "shuffle stage ADOPTS that stage's map output and statistics "
    "instead of recomputing it (aqeExchangeReuse journal event, "
    "srt_exchangereuse_* series). Stages are refcounted, so eviction "
    "never frees frames a running query still reads. Requires "
    "spark.rapids.sql.adaptive.enabled.")

SERVING_EXCHANGE_REUSE_MAX_BYTES = register(
    "spark.rapids.tpu.serving.exchangeReuse.maxBytes", _to_bytes,
    256 << 20,
    "Byte bound on materialized stage output retained for cross-query "
    "exchange reuse (measured shuffle bytes; oldest evicted first).",
    validator=_positive)

# --- fleet serving tier (serving/fleet/: multi-process router + worker
# replicas, shared warm state, rolling restarts — the replicated-service
# deployment story over the single-process serving layer above) -----------
FLEET_WORKERS = register(
    "spark.rapids.tpu.fleet.workers", int, 0,
    "Number of WORKER PROCESSES in the fleet serving tier "
    "(serving/fleet/): a front-end router process spreads tenants "
    "across this many worker processes, each a full session "
    "bootstrapped from the shared conf. 0 (default) disables the fleet "
    "tier entirely — the single-process serving path is byte-identical "
    "(serving/fleet is never even imported).", validator=_non_negative)

FLEET_DIR = register(
    "spark.rapids.tpu.fleet.dir", str, "",
    "Shared state directory of the fleet: the cross-process compile "
    "cache lands in <dir>/compilecache, the shared warm manifest "
    "(plan-identity -> replayable argspec records, the rolling-restart "
    "pre-warm source) in <dir>/warm.jsonl, and per-replica event logs "
    "in <dir>/events-<replica>.jsonl. Empty (default) lets the router "
    "create a per-fleet temporary directory.")

FLEET_SPILLOVER_DEPTH = register(
    "spark.rapids.tpu.fleet.spillover.queueDepth", int, 4,
    "Queue-depth threshold past which the router abandons a tenant's "
    "sticky replica for THIS submission and routes to the least-loaded "
    "replica instead (placement reason 'spillover', a fleetPlacement "
    "journal event, srt_fleet_placement_churn_total). Sticky placement "
    "resumes as soon as the home replica's queue drains below the "
    "threshold, so plan caches stay hot in steady state.",
    validator=_positive)

FLEET_PLACEMENT_OVERRIDES = register(
    "spark.rapids.tpu.fleet.placement.overrides", str, "",
    "Explicit tenant -> replica pins overriding the consistent-hash "
    "ring, as 'tenantA=r0,tenantB=r2' (replica ids are r0..rN-1). A "
    "pinned tenant still spills over past fleet.spillover.queueDepth "
    "and is re-placed if its replica is lost or draining.")

FLEET_ROUTER_HOST = register(
    "spark.rapids.tpu.fleet.router.host", str, "127.0.0.1",
    "Bind host of the router's HTTP endpoint (/api/fleet aggregating "
    "per-worker /api/status and /api/scheduler, /metrics with "
    "per-replica srt_fleet_* series, /healthz).")

FLEET_ROUTER_PORT = register(
    "spark.rapids.tpu.fleet.router.port", int, 0,
    "TCP port of the router's HTTP endpoint; 0 (default) binds an "
    "ephemeral port (the bound URL is FleetMonitor.url).",
    validator=_non_negative)

FLEET_WARM_MANIFEST = register(
    "spark.rapids.tpu.fleet.warmManifest", str, "",
    "Path of the fleet's SHARED WARM MANIFEST: every real backend "
    "compile (never persistent-cache hits) appends one flock-serialized "
    "JSONL record carrying the kernel identity, shape signature and "
    "replayable argument spec (obs/compilecache.py append path; "
    "obs/compileledger.py provides the entry). The file is directly "
    "consumable as a spark.rapids.tpu.compile.aot.manifest, so ANY "
    "replica's first compile pre-warms every later replica — the "
    "rolling-restart replacement replays it BEFORE taking traffic. "
    "Empty (default) disables the sidecar.")

FLEET_DRAIN_TIMEOUT = register(
    "spark.rapids.tpu.fleet.restart.drainTimeoutSeconds", float, 60.0,
    "Rolling restart: how long to wait for a quiesced worker's "
    "in-flight jobs to finish under their own deadlines before the "
    "swap proceeds anyway (the old worker is stopped; still-running "
    "jobs surface as failed with replica attribution).",
    validator=_non_negative)

FLEET_READY_TIMEOUT = register(
    "spark.rapids.tpu.fleet.prewarm.readyTimeoutSeconds", float, 120.0,
    "Rolling restart: how long to wait for the replacement worker's "
    "AOT pre-warm pass (shared warm manifest + shared XLA cache) to go "
    "idle before it takes traffic. Past the timeout the swap proceeds "
    "with whatever warmth the replacement has (workerReady journal "
    "event records the pre-warm snapshot either way).",
    validator=_non_negative)

FLEET_WORKER_START_TIMEOUT = register(
    "spark.rapids.tpu.fleet.worker.startTimeoutSeconds", float, 120.0,
    "How long the router waits for a spawned worker process to answer "
    "its first ping (session bootstrap included) before declaring the "
    "spawn failed.", validator=_positive)

UI_SIGNAL_DIAGNOSTICS = register(
    "spark.rapids.tpu.ui.signalDiagnostics", _to_bool, True,
    "Install a SIGUSR1 handler at session creation that dumps the "
    "flight recorder, all-thread stack traces and current query-progress "
    "snapshots into the event log (kill -USR1 <pid>) — hung-query "
    "debugging without a REPL. Main-thread sessions only; the handler "
    "itself never raises. Independent of ui.enabled: the dump works "
    "with the HTTP service off.")


class TpuConf:
    """Immutable snapshot of settings, with typed accessors.

    Mirrors the accessor style of the reference's ``RapidsConf`` class.
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = {}
        if settings:
            for k, v in settings.items():
                self.set(k, v)

    def set(self, key: str, value: Any) -> "TpuConf":
        entry = _REGISTRY.get(key)
        if entry is not None:
            self._settings[key] = entry.convert(value)
        else:
            # Unregistered keys are allowed (per-op enable keys are generated
            # dynamically, GpuOverrides.scala:122-130) and treated as strings.
            self._settings[key] = value
        return self

    def get(self, key: str, default: Any = None) -> Any:
        if key in self._settings:
            return self._settings[key]
        entry = _REGISTRY.get(key)
        if entry is not None:
            return entry.default
        return default

    def get_bool(self, key: str, default: bool) -> bool:
        v = self.get(key, default)
        return _to_bool(v) if isinstance(v, str) else bool(v)

    def get_int(self, key: str, default: int) -> int:
        return int(self.get(key, default))

    def copy(self) -> "TpuConf":
        c = TpuConf()
        c._settings = dict(self._settings)
        return c

    # Typed accessors -------------------------------------------------------
    @property
    def sql_enabled(self) -> bool: return self.get(SQL_ENABLED.key)
    @property
    def explain(self) -> str: return str(self.get(EXPLAIN.key)).upper()
    @property
    def alloc_fraction(self) -> float: return self.get(ALLOC_FRACTION.key)
    @property
    def hbm_debug(self) -> bool: return self.get(HBM_DEBUG.key)
    @property
    def host_spill_storage_size(self) -> int: return self.get(HOST_SPILL_STORAGE_SIZE.key)
    @property
    def pinned_pool_size(self) -> int: return self.get(PINNED_POOL_SIZE.key)
    @property
    def batch_size_rows(self) -> int: return self.get(BATCH_SIZE_ROWS.key)
    @property
    def max_reader_batch_size_rows(self) -> int: return self.get(MAX_READER_BATCH_SIZE_ROWS.key)
    @property
    def capacity_growth(self) -> float: return self.get(CAPACITY_GROWTH.key)
    @property
    def incompatible_ops_enabled(self) -> bool: return self.get(INCOMPATIBLE_OPS.key)
    @property
    def improved_float_ops(self) -> bool: return self.get(IMPROVED_FLOAT_OPS.key)
    @property
    def has_nans(self) -> bool: return self.get(HAS_NANS.key)
    @property
    def test_enabled(self) -> bool: return self.get(TEST_ENABLED.key)
    @property
    def test_allowed_nontpu(self) -> List[str]:
        raw = str(self.get(TEST_ALLOWED_NONTPU.key) or "")
        return [s.strip() for s in raw.split(",") if s.strip()]
    @property
    def hash_agg_replace_mode(self) -> str: return self.get(HASH_AGG_REPLACE_MODE.key)
    @property
    def concurrent_tpu_tasks(self) -> int: return self.get(CONCURRENT_TPU_TASKS.key)
    @property
    def num_task_threads(self) -> int: return self.get(NUM_TASK_THREADS.key)
    @property
    def shuffle_partitions(self) -> int: return self.get(SHUFFLE_PARTITIONS.key)
    @property
    def broadcast_threshold(self) -> int: return self.get(BROADCAST_THRESHOLD.key)
    @property
    def stage_fusion_enabled(self) -> bool: return self.get(STAGE_FUSION.key)
    @property
    def shuffle_transport_enabled(self) -> bool: return self.get(SHUFFLE_TRANSPORT_ENABLED.key)
    @property
    def shuffle_bounce_buffer_size(self) -> int: return self.get(SHUFFLE_BOUNCE_BUFFER_SIZE.key)
    @property
    def shuffle_bounce_buffer_count(self) -> int: return self.get(SHUFFLE_BOUNCE_BUFFER_COUNT.key)
    @property
    def export_columnar_rdd(self) -> bool: return self.get(EXPORT_COLUMNAR_RDD.key)
    @property
    def adaptive_enabled(self) -> bool: return self.get(ADAPTIVE_ENABLED.key)
    @property
    def adaptive_coalesce_enabled(self) -> bool:
        return self.get(ADAPTIVE_COALESCE_ENABLED.key)
    @property
    def adaptive_coalesce_min_size(self) -> int:
        return self.get(ADAPTIVE_COALESCE_MIN_SIZE.key)
    @property
    def adaptive_broadcast_enabled(self) -> bool:
        return self.get(ADAPTIVE_BROADCAST_ENABLED.key)
    @property
    def adaptive_skew_enabled(self) -> bool:
        return self.get(ADAPTIVE_SKEW_ENABLED.key)
    @property
    def adaptive_skew_factor(self) -> float:
        return float(self.get(ADAPTIVE_SKEW_FACTOR.key))
    @property
    def adaptive_skew_threshold(self) -> int:
        return self.get(ADAPTIVE_SKEW_THRESHOLD.key)

    def is_operator_enabled(self, key: str, incompat: bool = False,
                            disabled_by_default: bool = False) -> bool:
        """Per-operator enable check with the incompat/disabled classification
        (reference: GpuOverrides.scala:122-130, RapidsMeta.scala:185-200)."""
        if key in self._settings:
            return self.get_bool(key, True)
        if disabled_by_default:
            return False
        if incompat and not self.incompatible_ops_enabled:
            return False
        return True


def help_text(include_internal: bool = False) -> str:
    """Generate the configs doc table (reference: RapidsConf.scala:133-146
    writes docs/configs.md the same way)."""
    lines = ["Name | Description | Default", "-----|-------------|--------"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal and not include_internal:
            continue
        lines.append(f"{e.key} | {e.doc} | {e.default}")
    return "\n".join(lines)
