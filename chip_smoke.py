"""chip_smoke.py — does the SQL engine still start, and answer right, on the chip?

Drives the main path once through the entry points a user calls, at TPC-H
SF1 (the smallest scale factor the TPC-H specification defines, and
BASELINE.json's first staged configuration), on one TPU:

  pass 1 (cold)    resolve the device (platform must be ``tpu``), build the
                   native library, generate the tables from ``--seed`` as
                   Snappy Parquet, then ``TpuSparkSession.builder()`` with
                   the engine's default confs plus
                   ``spark.rapids.sql.test.enabled=true`` ->
                   ``session.read.parquet`` -> q6, q1, q3, q5 twice each
                   through ``collect()``, then q6 and q3 together through
                   ``session.serving_scheduler(workers=2)``. Every answer is
                   compared with the CPU oracle (``spark.rapids.sql.enabled=
                   false``, outside the timings) under benchmarks/match.py's
                   tolerance.
  pass 2 (warm)    a fresh process on the same compile cache: q6 and q1
                   again; real XLA compiles vs persistent-cache hits.
  pass 3 (facts)   platform facts the engine's design leans on: does
                   block_until_ready block, what one small blocking fetch
                   costs, whether the compiler takes an f64 -> u64 bitcast.
  pass 4 (fleet)   one worker process for each chip: one worker more than
                   there are chips is refused at once, and a one-chip worker
                   serves q6.

The parent imports the standard library only and runs one child at a time,
so each child is the only process holding the chip. Any exception,
mismatch, CPU fallback, wrong platform or missing native library fails the
run: the exit code is non-zero and no result line is printed. On success
the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--sf``, ``--chips``, ``--queries`` are for runs by hand: ``--chips 4``
runs the same passes over ``session.set_mesh(4)`` and checks that the work
really spread over the mesh.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

QUERIES = ("q6", "q1", "q3", "q5")
SERVED = ("q6", "q3")
WARM_QUERIES = ("q6", "q1")
# the whole run, compilation included, has 1200 s: the parent stops at this
# many and each child gets what is left of them (793 s measured cold on one
# v5e chip, PR 21; 640 s of it pass 1)
RUN_BUDGET_S = 1170


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


# ---------------------------------------------------------------------------
# children: each is the one process holding the chip while it runs
# ---------------------------------------------------------------------------

def _resolve_device(args) -> None:
    """First thing a chip-holding child does: where did jax land? Sets no
    platform itself — the environment's choice is what is being checked.
    Leaves the device as jax reports it for the parent's result line."""
    import importlib.metadata

    import jax
    import jaxlib
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        _fail(f"jax resolved platform {d.platform!r}, not 'tpu' — no "
              "accelerator, nothing to smoke")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} but jax sees {len(devs)} device(s)")
    print(f"device: platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"bytes_limit={d.memory_stats()['bytes_limit']}", flush=True)
    with open(os.path.join(args.workdir, "device.json"), "w") as f:
        json.dump({"platform": d.platform, "kind": d.device_kind,
                   "count": len(devs)}, f)


def _ensure_tables(args) -> str:
    """TPC-H tables from --seed as Snappy Parquet, >= 8 row groups per
    large table; written once per (workdir, sf, seed)."""
    from spark_rapids_tpu.models import tpch_data
    out = os.path.join(args.workdir,
                       f"tpch-sf{args.sf:g}-seed{args.seed}")
    done = os.path.join(out, "_DONE")
    if not os.path.exists(done):
        t0 = time.perf_counter()
        tpch_data.write_parquet(out, args.sf, seed=args.seed, row_groups=8)
        with open(done, "w") as f:
            f.write("ok\n")
        nbytes = sum(os.path.getsize(os.path.join(out, n))
                     for n in os.listdir(out))
        print(f"data: TPC-H sf={args.sf:g} seed={args.seed} -> {out} "
              f"({nbytes / 1e6:.0f} MB parquet, "
              f"lineitem {int(tpch_data.LINEITEM_ROWS_PER_SF * args.sf)} "
              f"rows) in {time.perf_counter() - t0:.1f}s", flush=True)
    return out


class _Counters:
    """Deltas of the engine's own compile and host-sync counters."""

    def __init__(self):
        from spark_rapids_tpu.obs.metrics import REGISTRY
        from spark_rapids_tpu.obs.syncledger import SYNC_LEDGER
        self._reg, self._ledger = REGISTRY, SYNC_LEDGER
        self.mark()

    def _now(self):
        return (self._reg.counter("compileCache.backendCompiles").value,
                self._reg.counter("compileCache.persistentHits").value,
                self._ledger.seq,
                self._reg.timer("compileCache.backendCompileTime").value)

    def mark(self):
        self._at = self._now()

    def since_mark(self) -> dict:
        """A persistent-cache hit fires a backend-compile event too (the
        deserialize), so real XLA compiles = events - hits."""
        c0, h0, s0, t0 = self._at
        c1, h1, s1, t1 = self._now()
        return {"compiles": (c1 - c0) - (h1 - h0), "cache_hits": h1 - h0,
                "syncs": s1 - s0, "compile_s": t1 - t0}


def _open_session(args):
    """The session a user gets: engine defaults, plus the one conf that
    makes a silent CPU fallback an error."""
    from spark_rapids_tpu import nativelib
    from spark_rapids_tpu.models.tpch import TpchTables
    from spark_rapids_tpu.session import TpuSparkSession
    if not nativelib.native_available():
        _fail(f"native library absent: {nativelib.load_error()}")
    print("native_available=True", flush=True)
    data = _ensure_tables(args)
    session = TpuSparkSession.builder().config(
        "spark.rapids.sql.test.enabled", True).get_or_create()
    dm = session.device_manager
    if dm.platform != "tpu":
        _fail(f"device manager resolved {dm.platform!r}")
    print(f"compile cache: {dm.compile_cache_dir}", flush=True)
    if args.chips > 1:
        session.set_mesh(args.chips)
    return session, TpchTables.from_parquet(session, data)


def _oracle(session, build):
    """The CPU path's answer for the same query (exec/cpu.py)."""
    session.set_conf("spark.rapids.sql.test.enabled", False)
    session.set_conf("spark.rapids.sql.enabled", False)
    try:
        return build(session).collect()
    finally:
        session.set_conf("spark.rapids.sql.enabled", True)
        session.set_conf("spark.rapids.sql.test.enabled", True)


def _timed_collect(session, build, counters):
    """One collect(): wall ends after the answer is a host DataFrame."""
    counters.mark()
    t0 = time.perf_counter()
    out = build(session).collect()
    return out, time.perf_counter() - t0, counters.since_mark()


def _run_queries(args, names, served) -> None:
    from benchmarks.match import results_match

    from spark_rapids_tpu.models.tpch import QUERIES as TPCH
    session, tables = _open_session(args)
    counters = _Counters()
    builds = {q: (lambda s, q=q: TPCH[q](s, tables)) for q in names}
    oracles = {}
    mesh_problems = []
    for q in names:
        first, first_s, c1 = _timed_collect(session, builds[q], counters)
        second, second_s, c2 = _timed_collect(session, builds[q], counters)
        oracles[q] = _oracle(session, builds[q])
        ok = results_match(first, oracles[q]) \
            and results_match(second, oracles[q])
        print(f"query {q}: first_s={first_s:.3f} second_s={second_s:.3f} "
              f"compiles={c1['compiles']}+{c2['compiles']} "
              f"compile_s={c1['compile_s']:.1f}+{c2['compile_s']:.1f} "
              f"cache_hits={c1['cache_hits']}+{c2['cache_hits']} "
              f"syncs={c1['syncs']}/{c2['syncs']} rows={len(second)} "
              f"verified={ok}", flush=True)
        if not ok:
            _fail(f"{q}: answer differs from the CPU oracle")
        if args.chips > 1:
            mesh_problems = _mesh_report(session, args.chips)
    if served:
        sched = session.serving_scheduler(workers=2)
        try:
            t0 = time.perf_counter()
            jobs = [(q, sched.submit(builds[q], tenant=q, description=q))
                    for q in served]
            for q, job in jobs:
                ok = results_match(job.get(600), oracles[q])
                print(f"served {q}: status={job.status} "
                      f"wall_s={job.wall_s:.3f} verified={ok}", flush=True)
                if not ok:
                    _fail(f"served {q}: answer differs from the CPU oracle")
            print(f"served: {len(jobs)} jobs in "
                  f"{time.perf_counter() - t0:.3f}s", flush=True)
        finally:
            sched.close()
    if mesh_problems:
        _fail("; ".join(mesh_problems))


def _mesh_report(session, chips: int) -> list:
    """Did the work really spread over the mesh? Prints what the run has
    shown so far and returns what is still wrong: every device must have
    metered data and none most of it, exchanges must have run as mesh
    collectives, and the HBM limit must be each device's own."""
    import jax

    from spark_rapids_tpu.parallel.distributed import exchange_stats_log
    dm = session.device_manager
    mesh_devs = jax.devices()[:chips]
    peaks = {str(d): b for d, b in dm.per_device_peaks().items()}
    limits = {str(d): dm.hbm_per_device.get(d) for d in mesh_devs}
    print(f"mesh: per-device peak bytes {peaks}; "
          f"{len(exchange_stats_log)} mesh exchanges, last "
          f"{exchange_stats_log[-1] if exchange_stats_log else None}; "
          f"hbm bytes_limit per device {limits}", flush=True)
    problems = []
    missing = [str(d) for d in mesh_devs if not peaks.get(str(d))]
    if missing:
        problems.append(f"mesh devices metered no data: {missing}")
    elif max(peaks.values()) > 0.5 * sum(peaks.values()):
        problems.append(f"one device held most of the data: {peaks}")
    if not exchange_stats_log:
        problems.append("no exchange ran as a mesh collective")
    for d in mesh_devs:
        if dm.hbm_per_device.get(d) != d.memory_stats()["bytes_limit"]:
            problems.append(
                f"{d}: device manager holds {dm.hbm_per_device.get(d)}, "
                f"device reports {d.memory_stats()['bytes_limit']}")
    return problems


def _asked(args) -> tuple:
    return tuple(args.queries.split(",")) if args.queries else QUERIES


def _phase_cold(args) -> None:
    names = _asked(args)
    _run_queries(args, names, tuple(q for q in SERVED if q in names))


def _phase_warm(args) -> None:
    from spark_rapids_tpu.obs.metrics import REGISTRY
    _run_queries(
        args, tuple(q for q in WARM_QUERIES if q in _asked(args)), ())
    events = REGISTRY.counter("compileCache.backendCompiles").value
    hits = REGISTRY.counter("compileCache.persistentHits").value
    print(f"warm process: programs={events} cache_hits={hits} "
          f"real_compiles={events - hits}", flush=True)
    if hits == 0 or events - hits > 0.1 * events:
        _fail("the second process did not find (nearly) every program in "
              "the compile cache")


def _phase_facts(args) -> None:
    """Three platform facts; reported, they decide nothing here."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import spark_rapids_tpu  # noqa: F401 — enables x64 like the engine

    # fact 1: does block_until_ready block? A program long enough to time:
    # if it blocks, a fetch after it costs a small fetch, not the program.
    @jax.jit
    def long_program(x):
        return jax.lax.fori_loop(
            0, 400, lambda _, a: (a @ a) * (1.0 / 4096) + x, x)
    x = jnp.full((2048, 2048), 0.5, jnp.float32)
    np.asarray(long_program(x)[0, 0])  # compile and settle
    t0 = time.perf_counter()
    y = long_program(x)
    dispatch_s = time.perf_counter() - t0
    jax.block_until_ready(y)
    block_s = time.perf_counter() - t0
    np.asarray(y[0, 0])
    fetch_after_block_s = time.perf_counter() - t0 - block_s
    t0 = time.perf_counter()
    np.asarray(long_program(x)[0, 0])
    fetch_only_s = time.perf_counter() - t0
    print(f"fact block_until_ready: dispatch_s={dispatch_s:.6f} "
          f"block_s={block_s:.6f} fetch_after_block_s="
          f"{fetch_after_block_s:.6f} fetch_without_block_s="
          f"{fetch_only_s:.6f} blocks={block_s > 0.5 * fetch_only_s}",
          flush=True)

    # fact 2: one blocking fetch of a small array, median of many
    bump = jax.jit(lambda a: a + 1)
    small = jnp.arange(8, dtype=jnp.int32)
    fetch, round_trip = [], []
    for _ in range(200):
        out = jax.block_until_ready(bump(small))
        t0 = time.perf_counter()
        np.asarray(out)
        fetch.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(bump(small))
        round_trip.append(time.perf_counter() - t0)
    print(f"fact small fetch (32 bytes, n=200): ready-array fetch median_s="
          f"{np.median(fetch)} p90_s={np.percentile(fetch, 90)}; "
          f"dispatch+fetch median_s={np.median(round_trip)} "
          f"p90_s={np.percentile(round_trip, 90)}", flush=True)

    # fact 3: the compiler's f64 bitcast (why ops/floatbits.py is
    # arithmetic); reported, decides nothing here
    try:
        bits = jax.jit(lambda v: jax.lax.bitcast_convert_type(
            v, jnp.uint64))(jnp.asarray([1.5, -2.25], jnp.float64))
        verdict = f"compiled, bits={[hex(int(b)) for b in np.asarray(bits)]}"
    except Exception as e:  # noqa: BLE001 — the message is the finding
        verdict = f"{type(e).__name__}: {str(e).strip().splitlines()[0][:200]}"
    print(f"fact f64->u64 bitcast: {verdict}", flush=True)


def _phase_fleet(args) -> None:
    """The router process never initialises a backend: it counts chips
    from PCI and hands each worker one through its environment."""
    from benchmarks.match import results_match

    from spark_rapids_tpu.memory import discovery
    from spark_rapids_tpu.serving.fleet.router import launch_process_fleet
    from spark_rapids_tpu.serving.fleet.worker import deserialize_frame
    chips = discovery.local_chip_ordinals()
    print(f"fleet: chips counted without a backend: {chips}", flush=True)
    if len(chips) < args.chips:
        _fail(f"counted {len(chips)} chip(s), expected {args.chips}")
    fleet_dir = os.path.join(args.workdir, "fleet")
    t0 = time.perf_counter()
    try:
        launch_process_fleet(len(chips) + 1, fleet_dir,
                             start_timeout=60.0).shutdown()
    except RuntimeError as e:
        refused_s = time.perf_counter() - t0
        print(f"fleet: {len(chips) + 1} workers refused in "
              f"{refused_s:.3f}s: {e}", flush=True)
        if refused_s > 5.0 or str(len(chips)) not in str(e):
            _fail("the refusal was slow or does not name the chip count")
    else:
        _fail(f"{len(chips) + 1} workers started on {len(chips)} chip(s)")
    n = min(len(chips), 2)
    router = launch_process_fleet(n, fleet_dir, start_timeout=120.0)
    try:
        spec = {"kind": "suite", "suite": "tpch", "query": "q6", "sf": 0.01}
        for rid in sorted(router.worker_env):
            worker = router.worker(rid)
            reply = worker.ask(
                {"op": "submit", "query": spec, "tenant": rid,
                 "description": "chip_smoke", "want_result": True}, 100.0)
            oracle = worker.oracle(spec, timeout=100.0)
            if not reply or reply.get("status") != "succeeded" \
                    or not oracle or not oracle.get("result"):
                _fail(f"fleet worker {rid}: {reply} / oracle {oracle}")
            ok = results_match(deserialize_frame(reply["result"]),
                               deserialize_frame(oracle["result"]))
            platform = worker.status()["status"]["device"]["platform"]
            print(f"fleet: worker {rid} env="
                  f"TPU_VISIBLE_CHIPS={router.worker_env[rid]['TPU_VISIBLE_CHIPS']} "
                  f"platform={platform} q6 wall_s={reply.get('wall_s')} "
                  f"verified={ok}", flush=True)
            if not ok or platform != "tpu":
                _fail(f"fleet worker {rid} answered wrong or off the chip")
    finally:
        router.shutdown()


PHASES = {"cold": _phase_cold, "warm": _phase_warm,
          "facts": _phase_facts, "fleet": _phase_fleet}


def _child(args) -> None:
    if args.phase != "fleet":  # the fleet's router must stay off the chip
        _resolve_device(args)
    PHASES[args.phase](args)
    print(f"phase {args.phase}: ok", flush=True)


# ---------------------------------------------------------------------------
# parent: standard library only, one child at a time
# ---------------------------------------------------------------------------

def _run_phase(phase: str, argv, deadline: float) -> None:
    """One child in its own process group, swept when it ends however it
    ends — nothing this script started outlives it."""
    print(f"--- phase {phase} ---", flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase] + argv,
        start_new_session=True)
    try:
        rc = proc.wait(timeout=max(deadline - t0, 1.0))
    except subprocess.TimeoutExpired:
        _fail(f"phase {phase} still running when the run's "
              f"{RUN_BUDGET_S}s were up")
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0:
        _fail(f"phase {phase} exited {rc}")
    print(f"--- phase {phase} done in {time.monotonic() - t0:.1f}s ---",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", default="",
                    help="comma list; default q6,q1,q3,q5")
    ap.add_argument("--workdir", default=os.path.join(
        tempfile.gettempdir(), "srt_chip_smoke"))
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)
    device_file = os.path.join(args.workdir, "device.json")
    if args.phase:
        _child(args)
        return
    if os.path.exists(device_file):
        os.remove(device_file)  # a result is this run's or nobody's
    argv = ["--sf", str(args.sf), "--chips", str(args.chips), "--seed",
            str(args.seed), "--queries", args.queries, "--workdir",
            args.workdir]
    deadline = time.monotonic() + RUN_BUDGET_S
    _run_phase("cold", argv, deadline)
    _run_phase("warm", argv, deadline)
    _run_phase("facts", argv, deadline)
    _run_phase("fleet", argv, deadline)
    with open(device_file) as f:
        device = json.load(f)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
