"""Fleet serving tier: router placement, deadline/shed propagation,
rolling restarts (serving/fleet/, docs/fleet.md).

Tier-1 runs the whole router surface over ``LocalWorker`` — a real
``QueryScheduler`` per replica, no subprocess boot — so every routing
semantic (sticky, override, spill-over, shed attribution, dead-on-
arrival deadlines, drain, crash -> ``workerLost`` -> re-placement,
restart swap) costs milliseconds. One subprocess test pins the
byte-identical-off acceptance: a default-conf serving session never
imports the fleet package.

The slow tier boots REAL ``fleet/worker.py`` processes: the N=3
mixed-tenant sweep (scheduling scale-out ≥ 0.8·N on sleep-bound work —
this box has one core, so compute cannot scale but scheduling must;
real tpch queries oracle-verified alongside) and the pinned rolling
restart (replacement performs ZERO real XLA compiles before first
traffic, zero shed — the fleet face of test_zero_warmup.py).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from spark_rapids_tpu.obs.events import EVENTS
from spark_rapids_tpu.serving.fleet.placement import (
    HashRing, PlacementPolicy, parse_overrides,
)
from spark_rapids_tpu.serving.fleet.router import (
    FleetRouter, LocalWorker, snapshot_all,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _local_fleet(session, n=2, workers=1, max_queue=None,
                 spillover_depth=4, overrides=None):
    handles = {f"r{i}": LocalWorker(f"r{i}", session, workers=workers,
                                    max_queue=max_queue)
               for i in range(n)}
    return FleetRouter(handles, spillover_depth=spillover_depth,
                       overrides=overrides), handles


# ---------------------------------------------------------------------------
# Placement policy (pure unit)
# ---------------------------------------------------------------------------

class TestPlacementPolicy:
    def test_sticky_is_deterministic(self):
        p = PlacementPolicy(["r0", "r1", "r2"])
        depths = {"r0": 0, "r1": 0, "r2": 0}
        first = p.place("alice", depths)
        for _ in range(5):
            assert p.place("alice", depths) == first
        assert first[1] == "sticky"

    def test_ring_spreads_tenants(self):
        ring = HashRing(["r0", "r1", "r2"])
        homes = {ring.lookup(f"tenant-{i}", ["r0", "r1", "r2"])
                 for i in range(50)}
        assert homes == {"r0", "r1", "r2"}

    def test_override_wins_over_hash(self):
        p = PlacementPolicy(["r0", "r1"], overrides={"alice": "r1"})
        assert p.place("alice", {"r0": 0, "r1": 0}) == ("r1", "override")

    def test_parse_overrides_string(self):
        assert parse_overrides("alice=r1,bob=r0") == {"alice": "r1",
                                                     "bob": "r0"}

    def test_spillover_past_depth_to_least_loaded(self):
        p = PlacementPolicy(["r0", "r1", "r2"], spillover_depth=2)
        sticky = p.place("alice", {"r0": 0, "r1": 0, "r2": 0})[0]
        depths = {r: 0 for r in ("r0", "r1", "r2")}
        depths[sticky] = 2  # at the threshold: spill
        rid, reason = p.place("alice", depths)
        assert rid != sticky and reason == "spillover"

    def test_drained_replica_not_a_candidate(self):
        p = PlacementPolicy(["r0", "r1"])
        sticky = p.place("alice", {"r0": 0, "r1": 0})[0]
        other = "r1" if sticky == "r0" else "r0"
        # sticky home not eligible (draining/lost): falls to survivor
        rid, _ = p.place("alice", {other: 0})
        assert rid == other
        assert p.place("alice", {}) is None


# ---------------------------------------------------------------------------
# Router over LocalWorker: the full surface, near-free
# ---------------------------------------------------------------------------

class TestLocalFleet:
    def test_sticky_and_result_roundtrip(self, session):
        router, _ = _local_fleet(session, n=3)
        try:
            jobs = []
            for _ in range(3):  # sequential: depths stay 0, no spill
                j = router.submit({"kind": "noop"}, tenant="alice",
                                  want_result=True)
                assert j.wait(30.0) == "succeeded", j.error
                jobs.append(j)
            assert len({j.replica for j in jobs}) == 1
            assert jobs[0].reason == "sticky"
            df = jobs[0].result()
            assert list(df.columns) == ["a", "b"] and len(df) == 8
        finally:
            router.shutdown()

    def test_override_routes_tenant(self, session):
        router, _ = _local_fleet(session, n=2,
                                 overrides="alice=r1,bob=r0")
        try:
            ja = router.submit({"kind": "noop"}, tenant="alice")
            jb = router.submit({"kind": "noop"}, tenant="bob")
            assert ja.wait(30.0) == "succeeded"
            assert jb.wait(30.0) == "succeeded"
            assert (ja.replica, ja.reason) == ("r1", "override")
            assert (jb.replica, jb.reason) == ("r0", "override")
        finally:
            router.shutdown()

    def test_spillover_moves_excess_load(self, session):
        router, _ = _local_fleet(session, n=2, spillover_depth=1)
        try:
            jobs = [router.submit({"kind": "sleep", "seconds": 0.4},
                                  tenant="alice") for _ in range(3)]
            assert router.drain(timeout=30.0)
            assert all(j.status == "succeeded" for j in jobs)
            assert {j.replica for j in jobs} == {"r0", "r1"}
            assert "spillover" in {j.reason for j in jobs}
        finally:
            router.shutdown()

    def test_worker_shed_surfaces_with_replica_attribution(
            self, session):
        EVENTS.reset_for_tests()
        router, _ = _local_fleet(session, n=1, max_queue=1)
        try:
            jobs = [router.submit({"kind": "sleep", "seconds": 0.5},
                                  tenant="alice") for _ in range(4)]
            assert router.drain(timeout=30.0)
            statuses = [j.status for j in jobs]
            assert "shed" in statuses and "succeeded" in statuses
            shed = [j for j in jobs if j.status == "shed"]
            assert all(j.replica == "r0" for j in shed)
            assert router.snapshot()["shedTotal"] == len(shed)
            evs = [e for e in EVENTS.flight_events()
                   if e["kind"] == "queryShed" and e.get("replica")]
            assert evs and evs[0]["replica"] == "r0"
            assert evs[0]["tenant"] == "alice"
        finally:
            router.shutdown()

    def test_deadline_burned_in_router_queue_sheds_on_arrival(
            self, session):
        """Satellite: the deadline counts from ROUTER submission — a
        job whose budget was consumed by router queueing alone is
        dead on arrival at the worker's scheduler, never started."""
        router, _ = _local_fleet(session, n=1)
        try:
            router.quiesce("r0")  # no eligible replica: queue holds
            j = router.submit({"kind": "noop"}, tenant="alice",
                              deadline_s=0.15)
            time.sleep(0.4)  # burn the whole budget upstream
            router.restore("r0")
            assert j.wait(30.0) == "timeout"
            assert "expired before admission" in (j.error or "")
        finally:
            router.shutdown()

    def test_deadline_survives_router_queue_when_budget_remains(
            self, session):
        router, _ = _local_fleet(session, n=1)
        try:
            j = router.submit({"kind": "noop"}, tenant="alice",
                              deadline_s=30.0)
            assert j.wait(30.0) == "succeeded", j.error
        finally:
            router.shutdown()

    def test_crash_loses_inflight_and_replaces_tenant(self, session):
        EVENTS.reset_for_tests()
        router, handles = _local_fleet(session, n=2)
        try:
            # long enough to be in flight at crash, short enough that
            # the crashed scheduler's close() join stays cheap
            hang = router.submit({"kind": "sleep", "seconds": 2.0},
                                 tenant="alice")
            deadline = time.monotonic() + 10.0
            while hang.replica is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert hang.replica is not None
            handles[hang.replica].crash()
            assert hang.wait(10.0) == "lost"
            assert "lost" in (hang.error or "")
            evs = [e for e in EVENTS.flight_events()
                   if e["kind"] == "workerLost"]
            assert evs and evs[0]["replica"] == hang.replica
            assert evs[0]["inflightFailed"] == 1
            # survivor takes the tenant's next submission
            j2 = router.submit({"kind": "noop"}, tenant="alice")
            assert j2.wait(30.0) == "succeeded", j2.error
            assert j2.replica != hang.replica
            snap = router.snapshot(include_workers=False)
            assert snap["workersLost"] == 1
            states = {w["replica"]: w["state"] for w in snap["workers"]}
            assert states[hang.replica] == "lost"
        finally:
            router.shutdown()

    def test_quiesce_drain_restore(self, session):
        router, _ = _local_fleet(session, n=1)
        try:
            j = router.submit({"kind": "sleep", "seconds": 0.3},
                              tenant="alice")
            deadline = time.monotonic() + 10.0
            while j.replica is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert router.quiesce("r0") == 1
            assert router.wait_drained("r0", timeout=10.0)
            assert j.status == "succeeded"
            # drained + quiesced: a new submission holds in the router
            j2 = router.submit({"kind": "noop"}, tenant="alice")
            time.sleep(0.3)
            assert j2.status == "queued"
            assert router.queue_depth() == 1
            router.restore("r0")
            assert j2.wait(30.0) == "succeeded", j2.error
        finally:
            router.shutdown()

    def test_rolling_restart_swaps_handle_zero_shed(self, session):
        EVENTS.reset_for_tests()
        router, handles = _local_fleet(session, n=1)
        try:
            pre = [router.submit({"kind": "sleep", "seconds": 0.2},
                                 tenant="alice") for _ in range(2)]
            replacement = LocalWorker("r0", session)
            out = router.rolling_restart("r0", lambda: replacement,
                                         drain_timeout=30.0,
                                         ready_timeout=10.0)
            assert out["drained"] and out["ready"]
            assert router.worker("r0") is replacement
            post = router.submit({"kind": "noop"}, tenant="alice")
            assert post.wait(30.0) == "succeeded", post.error
            assert all(j.status == "succeeded" for j in pre)
            assert router.snapshot()["shedTotal"] == 0
            kinds = [e["kind"] for e in EVENTS.flight_events()]
            assert "workerDrain" in kinds and "workerReady" in kinds
        finally:
            router.shutdown()

    def test_snapshot_shape_and_monitor_route(self, session):
        router, _ = _local_fleet(session, n=2)
        try:
            j = router.submit({"kind": "noop"}, tenant="alice")
            assert j.wait(30.0) == "succeeded"
            snap = router.snapshot(include_workers=True)
            for key in ("workers", "placement", "placementChurn",
                        "shedTotal", "workersLost", "routerQueueDepth",
                        "jobs", "closed"):
                assert key in snap
            assert snap["placement"]["alice"] == j.replica
            live = {w["replica"]: w for w in snap["workers"]}
            assert live[j.replica]["completed"]["succeeded"] == 1
            assert "scheduler" in live[j.replica]
            # the live monitor's /api/fleet resolves through here
            fleets = snapshot_all()["fleets"]
            assert any(f["jobs"] == 1 for f in fleets)
        finally:
            router.shutdown()
        assert snapshot_all()["fleets"] == []  # shutdown deregisters

    def test_closed_router_rejects_submissions(self, session):
        router, _ = _local_fleet(session, n=1)
        router.shutdown()
        with pytest.raises(RuntimeError):
            router.submit({"kind": "noop"})


# ---------------------------------------------------------------------------
# Acceptance pin: fleet off == fleet never loaded
# ---------------------------------------------------------------------------

class TestByteIdenticalOff:
    def test_default_conf_serving_never_imports_fleet(self):
        """With every ``spark.rapids.tpu.fleet.*`` conf at its default
        the single-process path is byte-identical to the pre-fleet
        tree: the fleet package (and so every one of its code paths)
        is never even imported by a session + scheduler run."""
        prog = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "import pandas as pd\n"
            "from spark_rapids_tpu.session import TpuSparkSession\n"
            "from spark_rapids_tpu.serving.scheduler import "
            "QueryScheduler\n"
            "s = TpuSparkSession.builder().app_name('off').\\\n"
            "    get_or_create()\n"
            "sched = QueryScheduler(s, workers=1)\n"
            "job = sched.submit(lambda sess: sess.create_dataframe(\n"
            "    pd.DataFrame({'a': [1, 2]}), 1))\n"
            "job.wait(); sched.close()\n"
            "assert job.status == 'succeeded', job.error\n"
            "bad = [m for m in sys.modules\n"
            "       if m.startswith('spark_rapids_tpu.serving.fleet')]\n"
            "assert not bad, bad\n"
            "print('FLEET_FREE')\n" % _REPO)
        out = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True,
            text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr[-1000:]
        assert "FLEET_FREE" in out.stdout


class TestOneProcessPerChip:
    def test_more_workers_than_chips_is_refused_at_once(
            self, tmp_path, monkeypatch):
        """A chip belongs to one process: the launcher counts chips
        without a backend and refuses before spawning anything."""
        from spark_rapids_tpu.memory import discovery
        from spark_rapids_tpu.serving.fleet import router as fr
        monkeypatch.setattr(discovery, "local_chip_ordinals", lambda: [0])
        spawned = []
        monkeypatch.setattr(fr, "ProcessWorker",
                            lambda *a, **kw: spawned.append(a))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match=r"1 TPU chip\(s\)"):
            fr.launch_process_fleet(2, str(tmp_path / "fleet"))
        assert time.monotonic() - t0 < 1.0 and not spawned
        assert not (tmp_path / "fleet").exists()


# ---------------------------------------------------------------------------
# Slow tier: real fleet/worker.py processes
# ---------------------------------------------------------------------------

def _boot_fleet(n, d, **kw):
    from spark_rapids_tpu.serving.fleet.router import (
        launch_process_fleet,
    )
    return launch_process_fleet(
        n, str(d), base_conf={"spark.rapids.tpu.ui.enabled": False},
        **kw)


@pytest.mark.slow
class TestProcessFleet:
    def test_n3_mixed_tenant_sweep_scales_and_verifies(self, tmp_path):
        """Acceptance: the N=3 fleet beats 0.8·N single-worker
        throughput on sleep-bound work (scheduling scale-out — one CPU
        core here, so compute cannot scale but the tier must), with the
        real mixed-tenant queries oracle-verified per tenant and zero
        cross-tenant leaks."""
        tenants = ["alice", "bob", "carol"]
        spec_q1 = {"kind": "suite", "suite": "tpch", "query": "q1",
                   "sf": 0.01}
        spec_q6 = {"kind": "suite", "suite": "tpch", "query": "q6",
                   "sf": 0.01}

        def warm_replicas(router, rids):
            # one noop straight at each handle: the Collect kernel
            # compiles once per process OUTSIDE the timed window
            for rid in rids:
                rep = router.worker(rid).ask(
                    {"op": "submit", "query": {"kind": "noop"},
                     "tenant": "warm", "description": "warm"},
                    timeout=120.0)
                assert rep and rep.get("status") == "succeeded", rep

        def sleep_qps(router, n_jobs, seconds=0.25):
            t0 = time.perf_counter()
            jobs = [router.submit(
                {"kind": "sleep", "seconds": seconds},
                tenant=tenants[i % len(tenants)]) for i in range(n_jobs)]
            assert router.drain(timeout=120.0)
            assert all(j.status == "succeeded" for j in jobs), \
                [(j.status, j.error) for j in jobs]
            return n_jobs / (time.perf_counter() - t0)

        single = _boot_fleet(1, tmp_path / "f1")
        try:
            warm_replicas(single, ["r0"])
            qps1 = sleep_qps(single, 8)
        finally:
            single.shutdown()

        fleet = _boot_fleet(3, tmp_path / "f3")
        try:
            # mixed-tenant real queries, oracle-verified per tenant
            oracle = {}
            for q in (spec_q1, spec_q6):
                rep = fleet.worker("r0").oracle(q, timeout=300.0)
                assert rep and rep.get("result"), rep
                from spark_rapids_tpu.serving.fleet.worker import (
                    deserialize_frame,
                )
                oracle[q["query"]] = deserialize_frame(rep["result"])
            jobs = [(t, q, fleet.submit(q, tenant=t, want_result=True))
                    for t in tenants for q in (spec_q1, spec_q6)]
            assert fleet.drain(timeout=600.0)
            from benchmarks.match import results_match
            for t, q, j in jobs:
                assert j.status == "succeeded", (t, j.status, j.error)
                assert results_match(j.result(), oracle[q["query"]]), \
                    f"{t}/{q['query']}: result drifted from oracle"
            snap = fleet.snapshot(include_workers=False)
            assert snap["shedTotal"] == 0 and snap["workersLost"] == 0

            warm_replicas(fleet, ["r0", "r1", "r2"])
            qps3 = sleep_qps(fleet, 24)
            assert qps3 >= 0.8 * 3 * qps1, \
                f"fleet qps {qps3:.2f} < 0.8*3*{qps1:.2f}"
        finally:
            fleet.shutdown()

    def test_rolling_restart_zero_real_compiles_zero_shed(
            self, tmp_path, monkeypatch):
        """Acceptance pin (the fleet face of test_zero_warmup.py): the
        replacement worker boots from the shared warm manifest + jax's
        persistent cache and replays the router's recent queries BEFORE taking
        traffic, so its first real query performs ZERO real XLA
        compiles — and the restart itself sheds nothing."""
        spec = {"kind": "suite", "suite": "tpch", "query": "q6",
                "sf": 0.01}
        # the executables ride jax's persistent cache, which workers
        # inherit from the launcher's environment (XLA:CPU keeps it off
        # unless asked)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "xla"))
        fleet = _boot_fleet(2, tmp_path / "fleet")
        try:
            warm = fleet.submit(spec, tenant="alice", want_result=True)
            assert warm.wait(300.0) == "succeeded", warm.error
            rid = warm.replica
            out = fleet.restart_process_worker(
                rid, prewarm=True, drain_timeout=60.0,
                ready_timeout=300.0)
            assert out["drained"] and out["ready"], out
            prime = (out["aot"] or {}).get("prime") or {}
            assert prime.get("queries", 0) >= 1, out["aot"]

            # first real traffic on the replacement: zero real compiles
            st0 = fleet.worker(rid).status(timeout=30.0)
            j = fleet.submit(spec, tenant="alice", want_result=True)
            assert j.wait(300.0) == "succeeded", j.error
            assert j.replica == rid  # placement sticky across restart
            st1 = fleet.worker(rid).status(timeout=30.0)
            for st in (st0, st1):
                comp = st["compiles"]
                assert comp["real"] == 0, \
                    f"replacement compiled for real: {comp}"
            assert fleet.snapshot()["shedTotal"] == 0
        finally:
            fleet.shutdown()
