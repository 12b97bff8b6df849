"""Process-wide executable cache.

XLA compilation is expensive (hundreds of ms per kernel); the reference
faces the same with per-task compilation and SURVEY.md section 7 hard-part 5
calls for a process-wide executable cache. Exec operators build their device
kernels through ``cached_jit(signature, builder)``: identical operators
across queries (same expression trees, same static params) share one
``jax.jit`` wrapper, and jax's own cache then shares compiled executables
per input shape (capacity bucket).
"""

from __future__ import annotations

import os
import re
import threading
from typing import Any, Callable, Dict

_CACHE: Dict[str, Any] = {}
_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0}

# Observability handles, resolved once: the hit path runs per kernel
# fetch (per batch per operator) and must stay one lock + one counter add
# on top of the cache dict get.
from spark_rapids_tpu.obs.metrics import REGISTRY as _REGISTRY  # noqa: E402
from spark_rapids_tpu.obs.trace import TRACER as _TRACER  # noqa: E402

_HITS = _REGISTRY.counter("kernelCache.hits")
_MISSES = _REGISTRY.counter("kernelCache.misses")
_BUILD_TIME = _REGISTRY.timer("kernelCache.buildTime")


# ---------------------------------------------------------------------------
# Shape buckets (spark.rapids.tpu.compile.shapeBuckets): coarse padding of
# SECONDARY shape dimensions at the dispatch boundary
# ---------------------------------------------------------------------------
#
# The recompile-cause analyzer (obs/compileledger.analyze) names the
# dimensions that vary across one kernel's compiles: join build-table
# capacities, expansion output capacities, aggregation group capacities,
# hash-table sizes, char-slab capacities. Each is already a power-of-two
# bucket VALUE, but the ladder has ~17 rungs (8..1M) and every rung is
# its own XLA program — the long warm-up tail. ``bucket_dim`` re-pads an
# already-bucketed dimension up a COARSER ladder (floor ``minBucket``,
# growth ``growth``) so one compile serves a dimension range. Row counts
# are data (DeviceBatch.num_rows) and the padding region is masked the
# same way capacity padding always is, so results are value-identical;
# disabled (the default) it returns its input unchanged — byte-identical
# shapes. Batch ROW capacities (the primary dimension) never route
# through here.

_BUCKETS = {"enabled": False, "min": 4096, "growth": 2.0}


def configure_shape_buckets(enabled: bool, min_bucket: int = 4096,
                            growth: float = 2.0) -> None:
    _BUCKETS["enabled"] = bool(enabled)
    _BUCKETS["min"] = max(8, int(min_bucket))
    _BUCKETS["growth"] = max(1.1, float(growth))


def configure_shape_buckets_from_conf(conf) -> bool:
    # SRT_SHAPE_BUCKETS=1/0 overrides the conf for a whole process —
    # the validation lever that runs an UNMODIFIED test suite or sweep
    # with padding forced on (oracle verification across the tier-1
    # suite, docs/aot.md) or forced off
    env = os.environ.get("SRT_SHAPE_BUCKETS")
    enabled = (env != "0") if env is not None else conf.get_bool(
        "spark.rapids.tpu.compile.shapeBuckets", False)
    configure_shape_buckets(
        enabled,
        min_bucket=int(conf.get(
            "spark.rapids.tpu.compile.shapeBuckets.minBucket", 4096)),
        growth=float(conf.get(
            "spark.rapids.tpu.compile.shapeBuckets.growth", 2.0)))
    return _BUCKETS["enabled"]


def shape_buckets_enabled() -> bool:
    return _BUCKETS["enabled"]


def bucket_dim(n: int) -> int:
    """Pad a secondary shape dimension up the coarse ladder (identity
    when shape buckets are off — the byte-identical contract)."""
    if not _BUCKETS["enabled"] or n <= 0:
        return n
    import math
    b = _BUCKETS["min"]
    growth = _BUCKETS["growth"]
    while b < n:
        b = int(math.ceil(b * growth))
    return b


# ---------------------------------------------------------------------------
# Build hook (serving/prewarm.py): the AOT pre-warmer is told when a
# kernel it holds historical shape signatures for comes into existence,
# so it can compile every recorded shape in the background while the
# first query is still planning/scanning.
# ---------------------------------------------------------------------------

_BUILD_HOOK: Any = None


def set_build_hook(hook) -> None:
    """Register (or clear, with None) the kernel-build observer:
    ``hook(signature, fn)`` fires after a kernel is first BUILT and
    cached (never on cache hits — those return before the hook site).
    One observer; never raises into the build path."""
    global _BUILD_HOOK
    _BUILD_HOOK = hook


def clear_build_hook(hook) -> None:
    """Clear the observer only if it is still ``hook``: a cancelled
    pre-warm pass must not tear down a NEWER pass's registration."""
    global _BUILD_HOOK
    if _BUILD_HOOK is hook:
        _BUILD_HOOK = None


def cache_snapshot() -> Dict[str, Any]:
    """signature -> cached kernel fn (for the pre-warmer's scan of
    kernels built before it started)."""
    with _LOCK:
        return dict(_CACHE)


def kernel_family(signature: str) -> str:
    """The signature's text before the first ``|`` (``aggupd``, ``join``,
    ``concat``...), cut to an identifier: the name of the host span
    around a kernel's dispatch and of its program on the device."""
    return re.sub(r"\W", "_", signature.split("|", 1)[0]) or "kernel"


def name_program(fn, family: str):
    """Name the device program of a ``jax.jit``-wrapped Python function
    ``jit_srt_<family>`` (a lambda's is ``jit__lambda_``, which puts no
    device second down to an operator). jax reads the wrapped function's
    name when it first traces, so this runs before the first call. The
    name is a pure function of the family — no counter, no shape — or the
    persistent compile cache, which hashes it, stops hitting across
    processes. Returns ``fn``."""
    inner = getattr(fn, "__wrapped__", None)
    if inner is not None:
        try:
            inner.__name__ = inner.__qualname__ = "srt_" + family
        except (AttributeError, TypeError):
            pass
    return fn


def _wrap_ledgered(signature: str, fn, span_attrs=None):
    """Dispatch context of a cached kernel. The ``dispatch.<family>``
    span times the host side of every call (an asynchronous dispatch:
    the device's own time by family is read from the profiler trace,
    where ``name_program`` put the family); ``span_attrs(*args)``, where
    given, names the call's attributes on it, asked only while tracing is
    on. Compile ledger
    (obs/compileledger.py): every call publishes its signature +
    argument references to a thread-local for the call's duration, so a
    backend compile fired inside it knows its kernel identity and input
    shape signature. The steady-state (no-compile) overhead is two flag
    checks, two thread-local stores and a try/finally; with the ledger
    disabled it is the flag checks alone."""
    from spark_rapids_tpu.obs import compileledger as _cl
    span = "dispatch." + kernel_family(signature)

    def spanned(a):
        if span_attrs is not None and _TRACER.enabled:
            return _TRACER.span(span, **span_attrs(*a))
        return _TRACER.span(span)

    def wrapped(*a, **kw):
        if not _cl.LEDGER.enabled:
            with spanned(a):
                return fn(*a, **kw)
        d = _cl.dispatch_begin(signature, a, kw)
        try:
            with spanned(a):
                out = fn(*a, **kw)
        finally:
            entries = _cl.dispatch_end(d)
        if entries and _cl.LEDGER.capture_cost:
            # a compile just happened (warm-up path): opt-in FLOPs/bytes
            # attribution via a re-lower of the now-cached executable
            for e in entries:
                _cl.LEDGER.attach_cost(e, fn, a, kw)
        return out
    return wrapped


def cached_jit(signature: str, builder: Callable[[], Any],
               span_attrs=None):
    """Return the cached kernel for ``signature``, building it once;
    ``span_attrs``: see ``_wrap_ledgered``.

    Hit/miss/build-time counters feed the process-wide observability
    registry (obs/metrics.py REGISTRY, names kernelCache.*); when the
    tracer is on, builds emit spans (the XLA executable compile itself
    happens lazily at first call — the build span covers kernel
    CONSTRUCTION, backend_compile listeners cover compilation). The
    built kernel's program is named after the signature's family
    (``name_program``), tracing or not. Every cached kernel is wrapped
    with the compile-ledger dispatch context so the backend compiles it
    eventually triggers attribute to this signature + the calling plan
    operator (obs/compileledger.py)."""
    with _LOCK:
        fn = _CACHE.get(signature)
        if fn is not None:
            _STATS["hits"] += 1
        else:
            _STATS["misses"] += 1
    if fn is not None:
        _HITS.add(1)
        return fn
    _MISSES.add(1)
    import time
    t0 = time.perf_counter()
    with _TRACER.span("kernelcache.build", signature=signature[:160]):
        fn = name_program(builder(), kernel_family(signature))
    _BUILD_TIME.record(time.perf_counter() - t0)
    fn = _wrap_ledgered(signature, fn, span_attrs)
    with _LOCK:
        fn = _CACHE.setdefault(signature, fn)
    hook = _BUILD_HOOK
    if hook is not None:
        try:
            hook(signature, fn)
        except Exception:  # noqa: BLE001 — prewarm must not fail builds
            pass
    return fn


def cache_stats() -> Dict[str, int]:
    with _LOCK:
        return dict(_STATS, size=len(_CACHE))


def clear() -> None:
    with _LOCK:
        _CACHE.clear()


def expr_signature(e) -> str:
    """Deterministic structural signature of a bound expression tree.

    Walks the tree and serializes every instance attribute (patterns,
    cast targets, literal values, ordinals...), not just repr() — many
    nodes' repr prints only class name + children, which would collide
    cache keys for e.g. startswith('a') vs startswith('b')."""
    parts = [type(e).__name__]
    for k in sorted(vars(e)):
        if k == "children":
            continue
        v = vars(e)[k]
        parts.append(f"{k}={v!r}")
    kids = ",".join(expr_signature(c) for c in getattr(e, "children", ()))
    return f"{'|'.join(parts)}({kids})"


def schema_signature(schema) -> str:
    return repr(schema)
