"""Host-sync ledger: device-occupancy attribution per blocking point.

ROADMAP item 4's success metric — stage-boundary host syncs per query
dropping to <= 1 collect — had no measuring instrument: the engine's
``jax.device_get`` / ``int(num_rows)`` / ``np.asarray`` blocking points
are scattered across the exec/shuffle/adaptive/scan layers with zero
accounting. This module is that instrument, the third attribution axis
next to the compile ledger (obs/compileledger.py) and the
device/transfer/dispatch breakdown:

  * a process-wide **ledger** (``SYNC_LEDGER``) where every device<->host
    blocking point lands as one structured entry: the sync site (a
    bounded-cardinality kind string like ``collect.fetch`` or
    ``exchange.shrink``), optional free-form detail, wall seconds, bytes
    moved, the triggering plan operator (from the exec op-context the
    operator hot path maintains, obs/compileledger.current_op), the
    query id (from the event journal's window) and the thread;
  * the **``sync_scope``** context manager every blocking site runs
    inside. Scopes are reentrancy-aware: the OUTERMOST scope records, so
    a named call-site scope (``collect.fetch`` around the drain) wins
    over the fallback scopes inside ``DeviceBatch``'s fetch helpers —
    and the fallbacks guarantee any fetch path not explicitly wrapped
    still attributes *somewhere*. Inner scopes fold their byte counts
    into the enclosing scope so sizes survive nesting;
  * a **transfer-guard audit** (``spark.rapids.tpu.debug.transferGuard``)
    that proves the ledger's coverage: query execution runs under
    ``jax.transfer_guard_device_to_host`` in log/disallow mode while
    every ``sync_scope`` body re-enters ``allow`` — an untracked
    device->host transfer outside any scope is logged (or raises),
    so "every blocking fetch is a named ledger entry" is testable;
  * **occupancy + rollup** helpers: ``rollup(entries)`` groups a query's
    entries by site, ``occupancy_pct(sync_s, wall_s)`` derives the
    busy-vs-idle-gap estimate the profile report and trace summary
    surface (sync seconds are host-blocking time the device sits idle,
    modulo the transfer itself).

Wiring: the known site families — collect/fetch and upload completion
(exec/transitions.py, session._drain), exchange shrink / range-bounds /
split-count fetches (exec/tpu.py), the ``LazyExchangeStats`` fold
(shuffle/ici.py, shuffle/manager.py), AQE stage materialization
(sql/adaptive/executor.py), out-of-core working-set measurement
(exec/outofcore.py), runtime-skip ratio sampling (exec/tpu.py),
semaphore waits (memory/semaphore.py), scan-pipeline stalls
(sql/scan_pipeline.py) and the profile sync wrapper (exec/base.py).
Everything is conf-gated on ``spark.rapids.tpu.sync.ledger.enabled``
(ON by default — the ledger is a bounded deque and syncs are the
expensive operation being measured, so the bookkeeping is noise).

Consumers: the profile report's ``syncs`` section (obs/profile.py), a
"sync" track in the Chrome trace export (spans named ``sync.<site>``),
``hostSync`` journal events + flight-recorder tails (obs/events.py),
``srt_host_syncs_total`` / ``srt_host_sync_seconds_total`` Prometheus
series and live per-query counts on ``/api/query/<id>``
(obs/monitor.py), the qualification report's sync-share ranking
(tools/qualification.py) and the benchmark's ``syncs_per_query``
(benchmarks/readers/ledger_delta.py).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

DEFAULT_MAX_ENTRIES = 4096
# flight-recorder / diagnostics tail size (mirrors compileledger)
DUMP_TAIL = 32

_tls = threading.local()

# transfer-guard audit mode: None (off) | "log" | "disallow". Set from
# conf by the session per query; read by every sync_scope enter.
_GUARD = {"mode": None}


def _scope_stack() -> List["sync_scope"]:
    st = getattr(_tls, "scopes", None)
    if st is None:
        st = _tls.scopes = []
    return st


class sync_scope:
    """``with sync_scope("collect.fetch", detail=..., nbytes=n):`` — one
    device<->host blocking point. Times the body, records an entry on
    the OUTERMOST scope of this thread (inner scopes only fold their
    bytes up), and re-enters ``transfer_guard("allow")`` while the
    coverage audit runs so tracked transfers pass a ``disallow`` guard.
    """

    __slots__ = ("kind", "detail", "nbytes", "_t0", "_outer", "_trace",
                 "_guard")

    def __init__(self, kind: str, detail: Optional[str] = None,
                 nbytes: int = 0):
        self.kind = kind
        self.detail = detail
        self.nbytes = int(nbytes)
        self._trace = None
        self._guard = None

    def add_bytes(self, n: int) -> "sync_scope":
        """Attach bytes discovered mid-scope (a fetch whose payload size
        is only known after assembly)."""
        self.nbytes += int(n)
        return self

    def __enter__(self) -> "sync_scope":
        st = _scope_stack()
        self._outer = not st
        st.append(self)
        if self._outer:
            if _GUARD["mode"] is not None:
                self._guard = _allow_transfers()
                if self._guard is not None:
                    self._guard.__enter__()
            from spark_rapids_tpu.obs.trace import TRACER
            if TRACER.enabled:
                self._trace = TRACER.span("sync." + self.kind,
                                          site=self.kind)
                self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        seconds = time.perf_counter() - self._t0
        st = _scope_stack()
        if st and st[-1] is self:
            st.pop()
        if not self._outer:
            # nested under a named scope: surface the bytes, not a
            # second entry (the outer scope's seconds already cover us)
            if st and self.nbytes:
                st[-1].nbytes += self.nbytes
            return False
        if self._trace is not None:
            if self.nbytes:
                self._trace.set(bytes=self.nbytes)
            self._trace.__exit__(exc_type, exc, tb)
        if self._guard is not None:
            self._guard.__exit__(exc_type, exc, tb)
        if exc_type is None:
            SYNC_LEDGER.record(self.kind, seconds, nbytes=self.nbytes,
                               detail=self.detail)
        return False


class SyncLedger:
    """Process-wide bounded record of host-sync points. Thread-safe:
    executor / shuffle / scan-prefetch threads all block independently."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self._lock = threading.Lock()
        self.enabled = True
        self.event_min_seconds = 0.0
        self._entries: collections.deque = collections.deque(
            maxlen=max(1, max_entries))
        self._seq = 0
        self.total_recorded = 0
        self.total_seconds = 0.0
        self.total_bytes = 0

    # -- configuration ------------------------------------------------------
    def configure(self, enabled: bool = True,
                  max_entries: Optional[int] = None,
                  event_min_seconds: Optional[float] = None) -> None:
        with self._lock:
            self.enabled = bool(enabled)
            if event_min_seconds is not None:
                self.event_min_seconds = float(event_min_seconds)
            if max_entries is not None and \
                    self._entries.maxlen != max(1, int(max_entries)):
                self._entries = collections.deque(
                    self._entries, maxlen=max(1, int(max_entries)))

    def configure_from_conf(self, conf) -> bool:
        self.configure(
            conf.get_bool("spark.rapids.tpu.sync.ledger.enabled", True),
            max_entries=int(conf.get(
                "spark.rapids.tpu.sync.ledger.maxEntries",
                DEFAULT_MAX_ENTRIES)),
            event_min_seconds=float(conf.get(
                "spark.rapids.tpu.sync.ledger.eventMinSeconds", 0.0)))
        return self.enabled

    @property
    def seq(self) -> int:
        with self._lock:
            return self._seq

    # -- recording ----------------------------------------------------------
    def record(self, kind: str, seconds: float, nbytes: int = 0,
               detail: Optional[str] = None) -> Optional[Dict[str, Any]]:
        """One blocking sync that completed. Assembles the entry from the
        thread's op context plus the journal's query window, appends it,
        mirrors it into the metrics registry (the ``srt_host_sync*``
        Prometheus series) and emits the ``hostSync`` journal event.
        Never raises."""
        if not self.enabled:
            return None
        try:
            return self._record(kind, seconds, nbytes, detail)
        except Exception:  # noqa: BLE001 — observability must not fail
            return None

    def _record(self, kind: str, seconds: float, nbytes: int,
                detail: Optional[str]) -> Dict[str, Any]:
        from spark_rapids_tpu.obs import compileledger
        from spark_rapids_tpu.obs.events import EVENTS
        cur = compileledger.current_op()
        op = cur[0] if cur is not None else None
        entry: Dict[str, Any] = {
            "ts": round(time.time(), 6),
            "query": EVENTS.current_query,
            "site": kind,
            "op": op,
            "seconds": round(seconds, 6),
            "bytes": int(nbytes),
            "thread": threading.get_ident(),
        }
        if detail:
            entry["detail"] = str(detail)[:200]
        with self._lock:
            self._seq += 1
            entry["seq"] = self._seq
            self._entries.append(entry)
            self.total_recorded += 1
            self.total_seconds += seconds
            self.total_bytes += int(nbytes)
        # srt_host_syncs_total / srt_host_sync_seconds_total: the site
        # label is the bounded kind string, never the free-form detail
        from spark_rapids_tpu.obs.metrics import REGISTRY
        REGISTRY.counter("host_syncs", site=kind).add(1)
        REGISTRY.timer("host_sync", site=kind).record(seconds)
        if nbytes:
            REGISTRY.counter("host_sync.bytes", site=kind).add(nbytes)
        if EVENTS.enabled and seconds >= self.event_min_seconds:
            EVENTS.emit("hostSync", site=kind,
                        seconds=round(seconds, 6), bytes=int(nbytes),
                        op=(op or "")[:200] or None)
        return entry

    # -- introspection ------------------------------------------------------
    def entries(self, since_seq: int = 0,
                query: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            out = [dict(e) for e in self._entries if e["seq"] > since_seq]
        if query is not None:
            out = [e for e in out if e.get("query") == query]
        return out

    def tail(self, n: int = DUMP_TAIL) -> List[Dict[str, Any]]:
        """Compact newest-last tail for flight-recorder / diagnostics
        dumps, mirroring the compile-ledger tail."""
        with self._lock:
            return [dict(e) for e in list(self._entries)[-max(1, n):]]

    def query_stats(self, query: str) -> Dict[str, Any]:
        """Live per-query sync summary for the monitor's
        ``/api/query/<id>``: count, seconds, bytes, top sites."""
        ents = self.entries(query=query)
        roll = rollup(ents)
        return {"syncs": roll["count"], "seconds": roll["seconds"],
                "bytes": roll["bytes"], "sites": roll["bySite"][:10]}

    def reset_for_tests(self) -> None:
        with self._lock:
            self._entries.clear()
            self._seq = 0
            self.total_recorded = 0
            self.total_seconds = 0.0
            self.total_bytes = 0
            self.enabled = True
            self.event_min_seconds = 0.0


SYNC_LEDGER = SyncLedger()


# ---------------------------------------------------------------------------
# Rollup + occupancy derivation
# ---------------------------------------------------------------------------

def rollup(entries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Group ledger entries (or ``hostSync`` journal events) by site:
    ``{"count", "seconds", "bytes", "bySite": [{site, syncs, seconds,
    bytes, op}]}``, sites ranked by seconds. ``op`` is the most
    time-expensive triggering operator of each site (short name)."""
    by_site: Dict[str, Dict[str, Any]] = {}
    total_s = 0.0
    total_b = 0
    for e in entries:
        secs = float(e.get("seconds", 0.0) or 0.0)
        nb = int(e.get("bytes", 0) or 0)
        total_s += secs
        total_b += nb
        site = e.get("site") or "(unattributed)"
        g = by_site.setdefault(site, {"site": site, "syncs": 0,
                                      "seconds": 0.0, "bytes": 0,
                                      "_ops": {}})
        g["syncs"] += 1
        g["seconds"] += secs
        g["bytes"] += nb
        op = e.get("op")
        if op:
            short = op.split("(", 1)[0].strip()
            g["_ops"][short] = g["_ops"].get(short, 0.0) + secs
    out = []
    for g in sorted(by_site.values(), key=lambda g: -g["seconds"]):
        ops = g.pop("_ops")
        g["seconds"] = round(g["seconds"], 6)
        if ops:
            g["op"] = max(ops.items(), key=lambda kv: kv[1])[0]
        out.append(g)
    return {"count": sum(g["syncs"] for g in out),
            "seconds": round(total_s, 6), "bytes": total_b,
            "bySite": out}


def occupancy_pct(sync_seconds: float,
                  wall_s: Optional[float]) -> Optional[float]:
    """Device-occupancy estimate of a query: the share of its wall NOT
    spent blocked on a recorded host sync. An estimate, not a
    measurement — overlapping syncs on different threads double-count,
    and the device may pipeline work under a partial sync — but the
    run-over-run TREND is exactly the idle-gap signal ROADMAP item 4
    gates on. None when the wall is unknown."""
    if not wall_s or wall_s <= 0:
        return None
    idle = min(max(sync_seconds, 0.0) / wall_s, 1.0)
    return round(100.0 * (1.0 - idle), 2)


# ---------------------------------------------------------------------------
# Transfer-guard coverage audit
# ---------------------------------------------------------------------------

def set_guard_mode(mode: Optional[str]) -> None:
    """Arm/disarm the audit: sync scopes re-enter ``allow`` while a mode
    is set. The session calls this around query execution from
    ``spark.rapids.tpu.debug.transferGuard``."""
    _GUARD["mode"] = mode if mode in ("log", "disallow") else None


def guard_mode() -> Optional[str]:
    return _GUARD["mode"]


def guard_context(mode: Optional[str]):
    """Device->host transfer guard for the query execution body:
    ``log`` logs every untracked explicit fetch, ``disallow`` raises on
    it. Uses the ``*_explicit`` guard levels — the engine's blocking
    fetches ARE explicit ``jax.device_get`` calls, which the plain
    levels deliberately exempt. Returns a no-op context for off/unknown
    modes or when jax lacks transfer guards."""
    import contextlib
    if mode not in ("log", "disallow"):
        return contextlib.nullcontext()
    try:
        import jax
        return jax.transfer_guard_device_to_host(f"{mode}_explicit")
    except Exception:  # noqa: BLE001 — audit is best-effort
        return contextlib.nullcontext()


def _allow_transfers():
    """``allow`` guard re-entered by each outermost sync scope while the
    audit is armed; None when jax lacks transfer guards."""
    try:
        import jax
        return jax.transfer_guard_device_to_host("allow")
    except Exception:  # noqa: BLE001
        return None
