"""Structured span tracer with Chrome ``trace_event`` JSON export.

The reference wraps every operator and shuffle/memory transition in NVTX
ranges (NvtxWithMetrics.scala:17-44) so Nsight shows where a query's wall
time went; the analogue here is a process-wide tracer whose spans export to
the Chrome trace-event format, viewable in Perfetto (ui.perfetto.dev) or
chrome://tracing:

    with TRACER.span("TpuHashAggregateExec", batch_rows=n):
        ...
    TRACER.instant("shuffle.fetch.retry", peer=peer)
    TRACER.export_chrome("/tmp/query.trace.json")

Design constraints:

  * ZERO hot-path cost when disabled: ``span()`` is one attribute check and
    returns a shared ``nullcontext`` — no allocation, no clock read. The
    session enables the tracer per query from ``spark.rapids.tpu.trace.*``.
  * Thread-safe: executor/shuffle-server threads append under one lock;
    events carry the emitting thread id so Perfetto lanes them correctly.
  * Span nesting is tracked per-thread (``depth``/``parent`` ride the event
    args) so reports and tests can validate structure without re-deriving
    it from timestamps.
  * One query, one identifier: the session publishes the journal's query
    id (``q-<n>``) in a thread-local (``set_query``), the scan prefetcher
    hands it to its pool threads (``bind_query``), and every event carries
    it as ``query`` beside ``depth``/``parent``. A query's start
    (``begin_query``) drops the events of queries that have ended and
    keeps those of queries still running on other threads.
  * Optional ``jax.profiler.TraceAnnotation`` passthrough
    (``spark.rapids.tpu.trace.jaxAnnotations``): the same spans appear in a
    captured jax/XLA profiler trace alongside the compiler's own events.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

_NULL = contextlib.nullcontext()


class Span:
    """One open span; append-on-exit keeps partially-entered spans out of
    the export. Usable only through ``Tracer.span``."""

    __slots__ = ("tracer", "name", "args", "_t0", "_jax_cm")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self._jax_cm = None

    def set(self, **kw) -> "Span":
        """Attach result attributes discovered mid-span (row counts...)."""
        self.args.update(kw)
        return self

    def __enter__(self) -> "Span":
        tr = self.tracer
        stack = tr._stack()
        self.args["depth"] = len(stack)
        if stack:
            self.args["parent"] = stack[-1].name
        stack.append(self)
        if tr.jax_annotations:
            try:
                from jax.profiler import TraceAnnotation
                self._jax_cm = TraceAnnotation(self.name)
                self._jax_cm.__enter__()
            except ImportError:  # pragma: no cover
                self._jax_cm = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        if self._jax_cm is not None:
            self._jax_cm.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self.tracer._emit(self.name, self._t0, dur, self.args)
        return False


class Tracer:
    """Process-wide event collector. ``enabled`` is the only hot-path
    state; everything else is touched per-span."""

    def __init__(self):
        self.enabled = False
        self.jax_annotations = False
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._tls = threading.local()
        self._running: set = set()  # ids of the queries between set/end
        self._epoch = time.perf_counter()
        # cap so a forgotten enabled tracer cannot grow without bound over
        # a long session (~100 bytes/event -> ~50 MB worst case)
        self.max_events = 500_000
        self._dropped = 0
        # flight-recorder mirror (obs/events.py installs it): called with
        # each recorded event dict while tracing is enabled, so the
        # always-on ring holds recent spans too. None = no mirroring.
        self.flight_hook = None

    # -- configuration ------------------------------------------------------
    def configure(self, enabled: bool,
                  jax_annotations: bool = False) -> None:
        self.enabled = bool(enabled)
        self.jax_annotations = bool(jax_annotations)

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self._dropped = 0
        self._epoch = time.perf_counter()

    # -- the query an event belongs to ---------------------------------------
    def begin_query(self) -> None:
        """A query starts on this thread, its id not yet known: drop the
        events of queries that have ended (and those of no query) and keep
        the events of queries still running on other threads. For one
        client that is ``clear()``: the buffer then holds the query that
        just started and nothing older."""
        self._tls.query = None
        with self._lock:
            if not self._running:
                self._events = []
                self._dropped = 0
                self._epoch = time.perf_counter()
            else:
                self._events = [e for e in self._events
                                if e["args"].get("query") in self._running]

    def set_query(self, qid: str) -> None:
        """Publish the running query's id for this thread's events."""
        self._tls.query = qid
        with self._lock:
            self._running.add(qid)

    def end_query(self, qid: str) -> None:
        """The query has ended: its events stay until the next
        ``begin_query``, and this thread's later events (the collect's
        concat) still carry its id."""
        with self._lock:
            self._running.discard(qid)

    def bind_query(self, qid: Optional[str]) -> None:
        """A helper thread (the scan decode pool) works for ``qid``."""
        self._tls.query = qid

    def current_query(self) -> Optional[str]:
        return getattr(self._tls, "query", None)

    # -- recording ----------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, **args):
        """Context manager timing a region. Yields the ``Span`` (so callers
        can ``sp.set(rows=...)``) or None when tracing is disabled."""
        if not self.enabled:
            return _NULL
        return Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event (retries, drops, faults)."""
        if not self.enabled:
            return
        stack = self._stack()
        if stack:
            args.setdefault("parent", stack[-1].name)
        self._emit(name, time.perf_counter(), None, args, phase="i")

    def current_span(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def _emit(self, name: str, t0: float, dur: Optional[float],
              args: Dict[str, Any], phase: str = "X") -> None:
        qid = self.current_query()
        if qid is not None:
            args.setdefault("query", qid)
        ev = {"name": name, "ph": phase, "pid": os.getpid(),
              "tid": threading.get_ident(),
              "ts": round((t0 - self._epoch) * 1e6, 1),
              "args": args}
        if dur is not None:
            ev["dur"] = round(dur * 1e6, 1)
        if phase == "i":
            ev["s"] = "t"  # instant scope: thread
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            self._events.append(ev)
        hook = self.flight_hook
        if hook is not None:
            try:
                hook(ev)
            except Exception:  # noqa: BLE001 — observability must not fail
                pass

    @property
    def dropped(self) -> int:
        """Events dropped at the buffer cap (surfaced in the profile
        report's ``observability`` section — truncation must be loud)."""
        with self._lock:
            return self._dropped

    # -- export -------------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def export_chrome(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Chrome trace-event JSON (object form). Writes to ``path`` when
        given; always returns the document."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        doc: Dict[str, Any] = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "spark-rapids-tpu/obs"},
        }
        if dropped:
            doc["otherData"]["droppedEvents"] = dropped
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


TRACER = Tracer()
