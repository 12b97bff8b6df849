"""Fleet worker process: one full session behind a JSON-lines protocol.

``python -m spark_rapids_tpu.serving.fleet.worker <spec.json>`` boots a
complete ``TpuSparkSession`` from the spec's conf dict (shared compile
cache, warm manifest, optionally an AOT pre-warm manifest — see
``warmstate.worker_conf``) and serves requests over stdin/stdout, one
JSON object per line. The router (``router.ProcessWorker``) is the only
intended client.

Requests (every request carries ``id``; every reply echoes it):

  ``{"op": "ping"}``            -> ``{"pong": true, "pid", "replica"}``
  ``{"op": "submit", "tenant", "description", "deadline_s",
     "queued_elapsed_s", "want_result", "query": {...}}``
                                -> ASYNC reply when the job is terminal:
                                   ``{"status", "error", "wall_s",
                                   "rows", "result"?, "query_id"}``.
                                   ``queued_elapsed_s`` is the router's
                                   queue time — the scheduler counts the
                                   deadline from the ORIGINAL submission
                                   (serving/scheduler.py).
  ``{"op": "status"}``          -> ``{"status": <monitor
                                   status_snapshot>, "scheduler":
                                   <scheduler snapshot>, "compiles":
                                   {"backend", "cacheHits", "real"}}``
  ``{"op": "drain", "timeout"}``-> ``{"drained": bool, "queueDepth"}``
  ``{"op": "oracle", "query"}`` -> ``{"result": <split-json frame>}``
                                   (the CPU-path oracle for the same
                                   query, ``spark.rapids.sql.enabled``
                                   off)
  ``{"op": "exit"}``            -> drains and exits 0.

Query specs (``"query"``):

  ``{"kind": "noop"}``                       tiny 8-row frame
  ``{"kind": "sleep", "seconds": s}``        sleep then the tiny frame
                                             (drain/queue-depth tests)
  ``{"kind": "suite", "suite": "tpch",
     "query": "q1", "sf": 0.05}``            a real benchmark query;
                                             suite tables build once per
                                             (suite, sf) and are reused

A spec may carry ``primeQueries`` (a list of query specs — the router's
recent dispatch history): the worker replays them during boot, BEFORE
the ready reply, so a rolling restart's replacement builds its kernels
and drains its AOT pre-warm pass while still out of rotation
(``_prime``).

Stdout carries ONLY protocol lines: the real fd 1 is duped away and
fd 1 rebound to stderr before the session boots, so stray engine
prints can never corrupt the channel.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional


def _serialize_frame(df) -> Optional[str]:
    if df is None:
        return None
    try:
        return df.to_json(orient="split", double_precision=15)
    except Exception:  # noqa: BLE001 — a reply must always go out
        return None


def deserialize_frame(payload: Optional[str]):
    """Router-side inverse of the worker's result serialization."""
    if not payload:
        return None
    import io

    import pandas as pd
    return pd.read_json(io.StringIO(payload), orient="split")


class _WorkerServer:
    def __init__(self, spec: Dict[str, Any], out):
        self.spec = spec
        self.replica = str(spec.get("replica", "r0"))
        self.out = out
        self.out_lock = threading.Lock()
        self.compiles = {"backend": 0, "cacheHits": 0}
        self.prime = {"queries": 0, "failed": 0, "seconds": 0.0}
        self.session = None
        self.sched = None
        self._suites: Dict[tuple, Dict[str, Callable]] = {}
        self._suite_lock = threading.Lock()

    # -- protocol ------------------------------------------------------------
    def reply(self, req_id, doc: Dict[str, Any]) -> None:
        doc = dict(doc, id=req_id)
        with self.out_lock:
            self.out.write(json.dumps(doc, default=str) + "\n")
            self.out.flush()

    # -- bootstrap -----------------------------------------------------------
    def start(self) -> None:
        # real-compile accounting BEFORE the session exists: the
        # rolling-restart invariant ("replacement performs zero real XLA
        # compiles") is asserted against these counters, so the AOT
        # pre-warm pass itself must be counted too
        from jax import monitoring

        def on_duration(name: str, secs: float, **kw) -> None:
            if "backend_compile" in name:
                self.compiles["backend"] += 1

        def on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.compiles["cacheHits"] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

        from spark_rapids_tpu.session import TpuSparkSession
        builder = TpuSparkSession.builder()
        for k, v in (self.spec.get("conf") or {}).items():
            builder = builder.config(k, v)
        self.session = builder.get_or_create()
        self.sched = self.session.serving_scheduler(
            workers=int(self.spec.get("schedulerWorkers", 2)),
            max_queue=int(self.spec["maxQueue"])
            if self.spec.get("maxQueue") else None)
        self._prime()

    def _prime(self) -> None:
        """Replay the spec's ``primeQueries`` (the router's recent
        dispatch history) BEFORE the ready reply. Each replay builds the
        query's kernels, which pops their entries from the AOT pre-warm
        pass (serving/prewarm.py's build hook), which replays every
        OTHER historical shape of those kernels — all served from the
        shared XLA cache, so a rolling restart's replacement takes its
        first traffic with zero real compiles left to pay."""
        queries = self.spec.get("primeQueries") or []
        self.prime = {"queries": 0, "failed": 0, "seconds": 0.0}
        t0 = time.perf_counter()
        for q in queries:
            try:
                out = self.thunk(q)(self.session)
                collect = getattr(out, "collect", None)
                if callable(collect):
                    collect()
                self.prime["queries"] += 1
            except Exception:  # noqa: BLE001 — a stale spec must not block boot
                self.prime["failed"] += 1
        if queries:
            from spark_rapids_tpu.serving import prewarm
            p = prewarm.active()
            if p is not None:
                # let the build-hook-triggered shape replays finish so
                # the warm-up is COMPLETE, not merely started
                p.wait_idle(timeout=float(
                    self.spec.get("prewarmIdleTimeout", 60.0)))
        self.prime["seconds"] = round(time.perf_counter() - t0, 3)

    # -- query construction --------------------------------------------------
    def _tiny(self, s):
        import pandas as pd
        return s.create_dataframe(
            pd.DataFrame({"a": list(range(8)), "b": [1.0] * 8}), 2)

    def _suite(self, name: str, sf: float) -> Dict[str, Callable]:
        key = (name, sf)
        with self._suite_lock:
            built = self._suites.get(key)
            if built is not None:
                return built
            if name == "tpch":
                from spark_rapids_tpu.models.tpch import (
                    QUERIES, TpchTables,
                )
                tables = TpchTables.generate(self.session, sf,
                                             num_partitions=4)
            elif name == "tpcxbb":
                from spark_rapids_tpu.models.tpcxbb import (
                    QUERIES, TpcxbbTables,
                )
                tables = TpcxbbTables.generate(self.session, sf,
                                               num_partitions=4)
            else:
                raise ValueError(f"unknown suite {name!r}")
            built = {q: (lambda s, q=q: QUERIES[q](s, tables))
                     for q in QUERIES}
            self._suites[key] = built
            return built

    def thunk(self, query: Dict[str, Any]) -> Callable:
        kind = query.get("kind", "noop")
        if kind == "noop":
            return self._tiny
        if kind == "sleep":
            seconds = float(query.get("seconds", 0.1))

            def _sleep(s):
                time.sleep(seconds)
                return self._tiny(s)
            return _sleep
        if kind == "suite":
            fns = self._suite(str(query["suite"]),
                              float(query.get("sf", 0.05)))
            return fns[str(query["query"])]
        raise ValueError(f"unknown query kind {kind!r}")

    # -- ops -----------------------------------------------------------------
    def op_submit(self, req_id, req: Dict[str, Any]) -> None:
        want_result = bool(req.get("want_result"))
        try:
            fn = self.thunk(req.get("query") or {})
        except Exception as e:  # noqa: BLE001 — reported to the router
            self.reply(req_id, {"status": "failed",
                                "error": f"{type(e).__name__}: {e}"[:300]})
            return
        job = self.sched.submit(
            fn, tenant=str(req.get("tenant", "default")),
            description=str(req.get("description", "")),
            deadline_s=req.get("deadline_s"),
            queued_elapsed_s=float(req.get("queued_elapsed_s", 0.0)))

        def waiter() -> None:
            job.wait()
            doc: Dict[str, Any] = {
                "status": job.status, "error": job.error,
                "wall_s": job.wall_s, "query_id": job.query_id,
                "rows": (len(job.result)
                         if job.result is not None else None),
            }
            if want_result and job.status == "succeeded":
                doc["result"] = _serialize_frame(job.result)
            self.reply(req_id, doc)

        if job.done():  # shed / dead-on-arrival: reply inline
            waiter()
        else:
            threading.Thread(target=waiter, daemon=True,
                             name=f"fleet-wait-{job.id}").start()

    def op_status(self, req_id) -> None:
        from spark_rapids_tpu.obs.monitor import status_snapshot
        comp = dict(self.compiles)
        comp["real"] = max(comp["backend"] - comp["cacheHits"], 0)
        self.reply(req_id, {"replica": self.replica,
                            "status": status_snapshot(),
                            "scheduler": self.sched.snapshot(),
                            "compiles": comp,
                            "prime": dict(self.prime)})

    def op_oracle(self, req_id, req: Dict[str, Any]) -> None:
        fn = self.thunk(req.get("query") or {})
        prev = self.session.conf.get("spark.rapids.sql.enabled", True)
        try:
            self.session.set_conf("spark.rapids.sql.enabled", False)
            out = fn(self.session).collect()
        finally:
            self.session.set_conf("spark.rapids.sql.enabled", prev)
        self.reply(req_id, {"result": _serialize_frame(out)})

    # -- main loop -----------------------------------------------------------
    def serve(self) -> None:
        self.reply(None, {"ready": True, "replica": self.replica,
                          "pid": os.getpid()})
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            try:
                req = json.loads(line)
            except json.JSONDecodeError:
                continue
            req_id = req.get("id")
            op = req.get("op")
            try:
                if op == "exit":
                    break
                if op == "ping":
                    self.reply(req_id, {"pong": True, "pid": os.getpid(),
                                        "replica": self.replica})
                elif op == "submit":
                    self.op_submit(req_id, req)
                elif op == "status":
                    self.op_status(req_id)
                elif op == "drain":
                    ok = self.sched.drain(
                        timeout=float(req.get("timeout", 30.0)))
                    self.reply(req_id, {
                        "drained": ok,
                        "queueDepth": self.sched.queue_depth()})
                elif op == "oracle":
                    self.op_oracle(req_id, req)
                else:
                    self.reply(req_id,
                               {"error": f"unknown op {op!r}"})
            except Exception as e:  # noqa: BLE001 — reported, never fatal
                self.reply(req_id,
                           {"error": f"{type(e).__name__}: {e}"[:300]})
        try:
            self.sched.close(cancel_pending=True, timeout=30.0)
        except Exception:  # noqa: BLE001 — already exiting
            pass


def main(argv: Optional[list] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args:
        print("usage: python -m spark_rapids_tpu.serving.fleet.worker "
              "<spec.json>", file=sys.stderr)
        return 2
    with open(args[0], "r", encoding="utf-8") as f:
        spec = json.load(f)
    # the protocol channel is the ORIGINAL stdout; fd 1 itself is
    # rebound to stderr so engine prints can never tear a reply line
    out = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)
    server = _WorkerServer(spec, out)
    try:
        server.start()
    except Exception as e:  # noqa: BLE001 — boot failure, reported
        server.reply(None, {"fatal": f"{type(e).__name__}: {e}"[:300]})
        return 1
    server.serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
