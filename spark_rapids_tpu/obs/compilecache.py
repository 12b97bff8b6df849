"""Persistent-compile-cache attribution: jax monitoring -> metrics registry.

First-run warmup is mostly XLA compiles, and without first-class
attribution that cost hides inside per-query wall time. jax emits
monitoring events for both the backend compiler and the persistent
executable cache (placed by ``configure_compile_cache``, package
__init__); this module mirrors them into the process-wide registry (obs/metrics.py REGISTRY) so
warmup shows up per query in ``session.profile_report()`` (the
``compileCache`` summary section, obs/profile.py) and in
``tools/trace_summary.py``'s warmup-attribution line:

    compileCache.backendCompiles / backendCompileTime  — XLA compiles that
        actually ran (cache misses end up here)
    compileCache.persistentHits / persistentMisses     — persistent-cache
        lookups (a hit skips the backend compile entirely)
    compileCache.timeSaved                              — compile seconds
        the persistent cache avoided (jax's own estimate)
    compileCache.retrievalTime                          — time spent
        deserializing cached executables

Each backend compile additionally lands in the compile LEDGER
(obs/compileledger.py) carrying the triggering plan operator, kernel
identity and shape signature — the per-cause attribution this module's
bare counters cannot give.

Double-install guard: listener registration is once per PROCESS, not per
module instance. jax's monitoring registry keeps listeners for the
interpreter's lifetime with no dedup, so a re-registration (repeated
session creation after a module reload, a second interpreter-level
import under a different name) would double-count every compile. The
installed marker therefore lives on the ``jax.monitoring`` module itself
— the one object all importers share — and the registered callbacks
resolve their counters at event time, so a test-time
``REGISTRY.clear()`` can never leave them feeding orphaned counter
objects.
"""

from __future__ import annotations

import hashlib
import json
import os
import socket
import threading
from typing import Any, Dict, Optional

_LOCK = threading.Lock()
# the marker attribute set on jax's monitoring module: survives a reload
# of THIS module, which a module-local flag would not
_MARKER = "_srt_compile_listeners_installed"


def locked_append(path: str, payload: bytes) -> bool:
    """Append ``payload`` to ``path`` as ONE durable record: O_APPEND +
    an exclusive flock held across the write, and the write itself looped
    to completion so a short write can never publish a record prefix.

    O_APPEND alone keeps small writes atomic on local filesystems, but
    the fleet manifest is multi-writer on arbitrary (possibly networked)
    volumes where that guarantee does not hold and a single ``os.write``
    may land partially. Under the flock no reader-with-lock or
    writer-with-lock ever observes a torn record; the read side's
    torn-tail tolerance stays as a belt for lockless readers.
    """
    try:
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    except OSError:
        return False
    try:
        try:
            import fcntl
            fcntl.flock(fd, fcntl.LOCK_EX)
        except (ImportError, OSError):
            pass  # O_APPEND alone still lands whole small lines
        view = memoryview(payload)
        while view:
            try:
                n = os.write(fd, view)
            except OSError:
                return False  # record may be torn: readers skip it
            if n <= 0:
                return False
            view = view[n:]
    finally:
        try:
            os.close(fd)  # releases the flock
        except OSError:
            pass
    return True


# ---------------------------------------------------------------------------
# Cross-process shared persistent compile cache
# ---------------------------------------------------------------------------

class SharedCompileCache:
    """Fleet-wide compile-once coordination
    (``spark.rapids.tpu.compile.sharedCache.dir``).

    Two halves:

      * the EXECUTABLES live in jax's persistent compilation cache — the
        mechanism that actually lets a fresh process skip the XLA
        compile. Its directory is the process's own
        (JAX_COMPILATION_CACHE_DIR, else the package default — see
        ``configure_compile_cache``); this class never re-points it, so
        a fleet shares executables by sharing that environment;
      * the MANIFEST (``<dir>/manifest.jsonl``) is the durable fleet
        record: one file-locked appended line per backend compile that
        actually ran, carrying the versioned key, kernel identity, aval
        signature, op, seconds and the writing (pid, host). It feeds the
        hit/miss/STEAL counters — a "steal" is this process reusing an
        executable another process compiled, the cluster-amortization
        the whole layer exists for — and doubles as a cluster-wide
        warm-shape census.

    Thread-safe; every filesystem touch is best-effort (a broken shared
    volume degrades to per-process behavior, never fails a query).
    Counters resolve through the registry at event time so a test-time
    ``REGISTRY.clear()`` cannot orphan them.
    """

    VERSION = "srtcc-1"

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self.directory = ""
        self._manifest_path = ""
        self._index: Dict[str, Dict[str, Any]] = {}
        self._index_size = -1
        self._ident = (os.getpid(), socket.gethostname())
        self._key_prefix: Optional[str] = None
        # fleet warm-state sidecar (spark.rapids.tpu.fleet.warmManifest):
        # a flock-serialized JSONL of REPLAYABLE compile records — same
        # append discipline as the manifest, but carrying kernelKey +
        # argspec so serving/prewarm.py can AOT-replay them in a fresh
        # replica. Independent of the shared-cache enabled state: a
        # fleet can share warm shapes without sharing an XLA cache dir.
        self.warm_manifest_path = ""

    # -- configuration ------------------------------------------------------
    def configure_from_conf(self, conf) -> bool:
        d = str(conf.get("spark.rapids.tpu.compile.sharedCache.dir", "")
                or "")
        self.configure_warm_manifest(
            str(conf.get("spark.rapids.tpu.fleet.warmManifest", "")
                or ""))
        return self.configure(d)

    def configure_warm_manifest(self, path: str) -> None:
        """Point (or un-point) the warm-state sidecar at ``path``."""
        with self._lock:
            self.warm_manifest_path = path or ""

    def configure(self, directory: str) -> bool:
        with self._lock:
            if not directory:
                self.enabled = False
                self.directory = ""
                return False
            if self.enabled and directory == self.directory:
                return True
            try:
                os.makedirs(directory, exist_ok=True)
            except OSError:  # shared volume problems: per-process behavior
                self.enabled = False
                return False
            self.directory = directory
            self._manifest_path = os.path.join(directory,
                                               "manifest.jsonl")
            self._index = {}
            self._index_size = -1
            self.enabled = True
            self._refresh_locked()
            return True

    def _prefix(self) -> str:
        """Versioned key prefix: cache format + jax version + resolved
        backend + machine, so executables compiled by an incompatible
        stack are never counted as this fleet's warmth (the
        machine-feature/SIGILL concern of the package-level CPU
        policy)."""
        if self._key_prefix is None:
            import platform

            import jax
            try:
                backend = jax.default_backend()
            except Exception:  # noqa: BLE001 — no device yet
                backend = "?"
            self._key_prefix = "|".join(
                (self.VERSION, jax.__version__, backend,
                 platform.machine()))
        return self._key_prefix

    def key_for(self, kernel: Optional[str], avals) -> str:
        blob = "|".join((self._prefix(), kernel or "?",
                         ",".join(avals or ())))
        return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:20]

    # -- manifest -----------------------------------------------------------
    def _refresh_locked(self) -> None:
        """Re-read the manifest when its size changed (another process
        appended): the steal census must see foreign records."""
        try:
            size = os.path.getsize(self._manifest_path)
        except OSError:
            return
        if size == self._index_size:
            return
        idx: Dict[str, Dict[str, Any]] = {}
        try:
            with open(self._manifest_path, "r", encoding="utf-8",
                      errors="replace") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail from a crashed writer
                    if isinstance(rec, dict) and "key" in rec:
                        idx.setdefault(rec["key"], rec)
        except OSError:
            return
        self._index = idx
        self._index_size = size

    def _append_locked(self, rec: Dict[str, Any]) -> bool:
        """One flock-serialized line append: concurrent workers on a
        shared volume interleave whole lines, never bytes
        (``locked_append``)."""
        line = (json.dumps(rec, default=str) + "\n").encode("utf-8")
        return locked_append(self._manifest_path, line)

    # -- event hooks --------------------------------------------------------
    def note_compile(self, entry: Dict[str, Any]) -> None:
        """One backend compile that actually ran (the ledger's record
        path). Persistent-cache HITS are deserializations of an
        executable that is already shared — only real compiles append a
        manifest record."""
        if entry.get("outcome") == "hit":
            return
        self._note_warm(entry)
        if not self.enabled:
            return
        from spark_rapids_tpu.obs.metrics import REGISTRY
        # key on the full-signature hash (kernelKey): the readable
        # kernel string is truncated for event-size hygiene and two
        # long signatures could collide at the cut
        key = self.key_for(entry.get("kernelKey")
                           or entry.get("kernel"), entry.get("avals"))
        rec = {"key": key, "kernel": entry.get("kernel"),
               "op": entry.get("op"), "avals": entry.get("avals"),
               "seconds": entry.get("seconds"),
               "pid": self._ident[0], "host": self._ident[1],
               "ts": entry.get("ts")}
        with self._lock:
            if not self.enabled:
                return
            ok = self._append_locked(rec)
            if ok:
                self._index.setdefault(key, rec)
        if ok:
            REGISTRY.counter("sharedCache.writes").add(1)

    def _note_warm(self, entry: Dict[str, Any]) -> None:
        """Append a REPLAYABLE record to the fleet warm-state sidecar.
        Only entries carrying an argspec are useful — prewarm replays
        the build from it — so un-attributed compiles are skipped. The
        JSONL shape matches ``prewarm.load_manifest``'s entry schema
        (kernel/kernelKey/avals/argspec/op/seconds), so the sidecar is
        directly consumable as ``compile.aot.manifest``."""
        with self._lock:
            path = self.warm_manifest_path
        if not path or not entry.get("argspec"):
            return
        rec = {"kernel": entry.get("kernel"),
               "kernelKey": entry.get("kernelKey"),
               "avals": entry.get("avals"),
               "argspec": entry.get("argspec"),
               "op": entry.get("op"),
               "seconds": entry.get("seconds"),
               "pid": self._ident[0], "host": self._ident[1],
               "ts": entry.get("ts")}
        try:
            line = (json.dumps(rec, default=str) + "\n").encode("utf-8")
        except (TypeError, ValueError):
            return
        if locked_append(path, line):
            from spark_rapids_tpu.obs.metrics import REGISTRY
            REGISTRY.counter("fleet.warmManifest.writes").add(1)

    def note_cache_event(self, outcome: str, dispatch) -> None:
        """Persistent-cache lookup outcome from the jax monitoring
        stream, attributed against the fleet manifest: a hit whose
        manifest record was written by ANOTHER process is a steal —
        cross-process amortization working."""
        if not self.enabled:
            return
        from spark_rapids_tpu.obs.metrics import REGISTRY
        if outcome == "miss":
            REGISTRY.counter("sharedCache.misses").add(1)
            return
        stolen = False
        if dispatch is not None:
            from spark_rapids_tpu.obs.compileledger import (
                aval_signature, kernel_key,
            )
            try:
                key = self.key_for(
                    kernel_key(dispatch.kernel),
                    aval_signature(dispatch.args, dispatch.kwargs))
            except Exception:  # noqa: BLE001 — accounting only
                key = None
            if key is not None:
                with self._lock:
                    self._refresh_locked()
                    rec = self._index.get(key)
                stolen = (rec is not None and
                          (rec.get("pid"), rec.get("host"))
                          != self._ident)
        REGISTRY.counter("sharedCache.steals" if stolen
                         else "sharedCache.hits").add(1)

    # -- introspection ------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        from spark_rapids_tpu.obs.metrics import REGISTRY
        with self._lock:
            self._refresh_locked()
            known = len(self._index)
        out = {"enabled": self.enabled, "dir": self.directory,
               "knownKernels": known}
        for name in ("hits", "misses", "steals", "writes"):
            out[name] = REGISTRY.counter(f"sharedCache.{name}").value
        return out

    def manifest_entries(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            self._refresh_locked()
            return dict(self._index)

    def reset_for_tests(self) -> None:
        with self._lock:
            self.enabled = False
            self.directory = ""
            self._manifest_path = ""
            self._index = {}
            self._index_size = -1
            self._key_prefix = None
            self.warm_manifest_path = ""


SHARED = SharedCompileCache()


def install() -> bool:
    """Register the jax monitoring listeners once per process. Returns
    True when the listeners are active (already-installed counts)."""
    with _LOCK:
        try:
            from jax import monitoring
        except ImportError:  # pragma: no cover - jax is a hard dep
            return False
        if getattr(monitoring, _MARKER, False):
            return True

        def on_event(name: str, **kw) -> None:
            from spark_rapids_tpu.obs import compileledger
            from spark_rapids_tpu.obs.compileledger import LEDGER
            from spark_rapids_tpu.obs.events import EVENTS
            from spark_rapids_tpu.obs.metrics import REGISTRY
            if name == "/jax/compilation_cache/cache_hits":
                REGISTRY.counter("compileCache.persistentHits").add(1)
                LEDGER.note_cache_event("hit")
                SHARED.note_cache_event(
                    "hit", compileledger.current_dispatch())
            elif name == "/jax/compilation_cache/cache_misses":
                REGISTRY.counter("compileCache.persistentMisses").add(1)
                LEDGER.note_cache_event("miss")
                SHARED.note_cache_event("miss", None)
                # a miss means a real XLA compile is coming: the durable
                # warmup fact the qualification report attributes
                EVENTS.emit("compileCacheMiss")
            elif name == "/jax/compilation_cache/compile_requests_use_cache":
                REGISTRY.counter("compileCache.requests").add(1)

        def on_duration(name: str, secs: float, **kw) -> None:
            from spark_rapids_tpu.obs import compileledger
            from spark_rapids_tpu.obs.compileledger import LEDGER
            from spark_rapids_tpu.obs.metrics import REGISTRY
            if compileledger.recording_suppressed():
                # instrument-internal compile (attach_cost's AOT
                # re-lower): not a warm-up fact, skip all accounting
                return
            if "backend_compile" in name:
                REGISTRY.counter("compileCache.backendCompiles").add(1)
                REGISTRY.timer("compileCache.backendCompileTime") \
                    .record(secs)
                # the ledger assembles the attributed entry AND emits the
                # enriched backendCompile journal event; disabled, it
                # falls back to the bare event so the journal never goes
                # dark
                if LEDGER.record_compile(secs) is None:
                    from spark_rapids_tpu.obs.events import EVENTS
                    EVENTS.emit("backendCompile", seconds=round(secs, 4))
            elif "compile_time_saved" in name:
                REGISTRY.timer("compileCache.timeSaved").record(secs)
            elif "cache_retrieval_time" in name:
                REGISTRY.timer("compileCache.retrievalTime").record(secs)

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        setattr(monitoring, _MARKER, True)
        return True
