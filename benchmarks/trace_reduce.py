"""From a profiler trace to busy and idle seconds, launches, the device
operations that took most time, and the idle gaps by what the host was doing.

Two halves. ``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain interval lists; ``reduce`` works on those lists alone, so that it
is checked on hand-made intervals (``tests/test_trace_reduce.py``). Times are
seconds on the trace's own clock: the device planes and the host plane share
it, which is what lets a gap on the device be named by a host span. The host
spans are the engine's ``TRACER`` spans, which it writes into the profiler's
trace as ``TraceAnnotation``s under ``spark.rapids.tpu.trace.jaxAnnotations``,
and the harness's own ``bench.window`` around the traced executions.
"""

from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.window"
QUERY_SPAN = "Query"
TOP = 10
# a span names a gap when it covers at least this share of it
COVER = 0.5
MAX_NAMED_GAPS = 2000


def merge(intervals):
    """Sorted, disjoint union of [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given the merged busy intervals."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _innermost(spans, lo, hi):
    """Of the spans [(name, start, end)] that cover at least COVER of the
    gap [lo, hi), the shortest: spans nest, so that is the innermost."""
    need = COVER * (hi - lo)
    best = None
    for name, a, b in spans:
        if min(b, hi) - max(a, lo) >= need \
                and (best is None or b - a < best[1]):
            best = (name, b - a)
    return None if best is None else best[0]


def _name_gap(lo, hi, main_spans, other_spans, queries):
    """What the host was doing in an idle gap: the innermost span of the
    thread that runs the query, else the innermost span of another thread
    (a scan pool's), else ``Query`` alone (plan rewrite and dispatch: no
    span marks them yet), else the harness between two queries."""
    name = _innermost(main_spans, lo, hi) or _innermost(other_spans, lo, hi)
    if name is not None:
        return name
    if _innermost(queries, lo, hi) is not None:
        return "Query.unspanned"
    return "between.queries"


def reduce(device_ops, launches, host_spans, window):
    """``device_ops``: {device: [(name, start, end)]} of operations that ran
    on each device. ``launches``: {device: [(name, start, end)]} of
    executables launched. ``host_spans``: [(name, thread, start, end)].
    ``window``: (start, end, thread) of the traced window.

    Returns busy_s and idle share averaged over the devices, launches
    summed over them, the operations that took most time and the idle
    seconds of the first device by what the host was doing."""
    lo, hi, main = window
    window_s = hi - lo
    main_spans, other_spans, queries = [], [], []
    for name, thread, a, b in host_spans:
        if name.startswith("bench.") or min(b, hi) <= max(a, lo):
            continue
        if name == QUERY_SPAN:
            queries.append((name, a, b))
        elif thread == main:
            main_spans.append((name, a, b))
        else:
            other_spans.append((name, a, b))

    busy_each, op_seconds = [], {}
    idle_by = {}  # name -> [seconds, gaps, longest]

    def add_idle(name, seconds):
        row = idle_by.setdefault(name, [0.0, 0, 0.0])
        row[0] += seconds
        row[1] += 1
        row[2] = max(row[2], seconds)
    for i, dev in enumerate(sorted(device_ops)):
        ops = device_ops[dev]
        busy = merge(clip([(a, b) for _, a, b in ops], lo, hi))
        busy_each.append(total(busy))
        for name, a, b in ops:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + d
        if i == 0:
            # the longest gaps are named one by one; beyond MAX_NAMED_GAPS
            # the shortest are pooled, which moves few seconds
            idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
            for a, b in idle[:MAX_NAMED_GAPS]:
                add_idle(_name_gap(a, b, main_spans, other_spans, queries),
                         b - a)
            for a, b in idle[MAX_NAMED_GAPS:]:
                add_idle("unnamed.short", b - a)
    n_launch = sum(1 for dev in launches for _, a, b in launches[dev]
                   if lo <= a < hi)
    busy_s = sum(busy_each) / len(busy_each) if busy_each else 0.0

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "launches": n_launch,
        "device_ops": ranked(op_seconds),
        "idle_gaps": ranked({k: v[0] for k, v in idle_by.items()}),
        "idle_gap_detail": {k: {"gaps": v[1], "longest_s": v[2]}
                            for k, v in idle_by.items()},
    }


# ---------------------------------------------------------------------------
# the xplane file
# ---------------------------------------------------------------------------

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME_CHARS = 96


def op_name(text: str) -> str:
    """The trace names a device operation by its whole HLO line; keep the
    result's name, the opcode and the first shape, which tell operations
    of different programs apart."""
    return " ".join(text.split())[:OP_NAME_CHARS]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, span_names, platform: str = "tpu"):
    """(device_ops, launches, host_spans, window, what was found) of one
    trace. ``span_names``: the host annotations to keep (the engine's span
    names; everything else on the host plane is jax's own)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    device_ops, launches, host_spans, window = {}, {}, [], None
    found = []
    prefix = f"/device:{platform.upper()}:"
    keep = set(span_names) | {WINDOW_SPAN}
    for plane in profile.planes:
        lines = list(plane.lines)
        found.append((plane.name, [ln.name for ln in lines]))
        if plane.name.startswith(prefix):
            for ln in lines:
                if ln.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in ln.events]
                elif ln.name == MODULES_LINE:
                    launches[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in ln.events]
        elif plane.name == "/host:CPU":
            for t, ln in enumerate(lines):
                for e in ln.events:
                    if e.name in keep:
                        a = e.start_ns * 1e-9
                        b = a + e.duration_ns * 1e-9
                        if e.name == WINDOW_SPAN:
                            window = (a, b, t)
                        host_spans.append((e.name, t, a, b))
    return device_ops, launches, host_spans, window, found


def reduce_trace(trace_dir: str, span_names, platform: str = "tpu"):
    """The reduction of the newest trace under ``trace_dir``; raises where
    the trace holds no traced window or no device operation."""
    path = find_xplane(trace_dir)
    device_ops, launches, host_spans, window, found = load(
        path, span_names, platform)
    if window is None:
        raise RuntimeError(f"{path}: no {WINDOW_SPAN} span; planes {found}")
    if not any(device_ops.values()):
        raise RuntimeError(
            f"{path}: no operation on a {platform} device; planes {found}")
    out = reduce(device_ops, launches, host_spans, window)
    out["planes"] = found
    return out
