"""One run of one benchmark cell: ``python benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.

A cell (``BENCHMARK.json`` ``workloads``) is a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<mix>.json``) of
queries (``queries/<q>.py``). One run:

  set-up   resolve the device (anything but the accelerator the configuration
           names is an error: no time is reported from another device); make
           the tables the mix's queries read from ``--seed`` (``data.py``;
           kept under ``benchmarks/.data/`` for a second run with the same
           seed) and the plain pandas reference answers, in a child process
           that ends before the first query; open the session a
           user gets (engine defaults plus the configuration's
           ``engine_confs``); run whole passes over the mix's queries until
           a pass loads no program, compiled or from the cache; compare one
           warm answer with the reference.
  window   ``--trace 0``: a closed loop of one client for ``--seconds``:
           ``build(session, tables).collect()`` to a host pandas frame, the
           next query as soon as the answer is there. Every answer is kept
           and compared with the reference after the window.
           ``--trace 1``: the same loop for at most 3 executions or 10 s,
           under the jax profiler with the engine's host spans on
           (``trace.enabled``, ``trace.jaxAnnotations``); the per-layer
           metrics (``layer_metrics/<name>.json``, read by
           ``readers/<kind>.py``) come from this run alone.

The last line of stdout is the result object; details go on earlier lines.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python can say

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_DIR = os.path.join(HERE, ".data")
TRACE_DIR = os.path.join(HERE, ".trace")

MAX_WARM_PASSES = 5
TRACED_EXECUTIONS = 3
TRACED_SECONDS = 10.0


def fail(msg: str):
    raise SystemExit(f"benchmarks/run.py: {msg}")


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module of its own."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        fail(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    """(cell, configuration, traffic, {query: module}, benchmark)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        fail(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[0]
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    if (traffic["loop"], traffic["clients"], traffic["entry"]) \
            != ("closed", 1, "collect"):
        fail(f"traffic {cell['traffic']}: only a closed loop of one client "
             "through collect() is built; 'served' and open loops are "
             "reserved (PERF.md, Open questions)")
    queries = {q: load_module("queries", q) for q in traffic["queries"]}
    return cell, config, traffic, queries, bench


def cell_metrics(bench, section: str, workload: str):
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def schedule(traffic, seed: int):
    """The order of the queries: a seeded shuffle of the mix's list, cycled,
    so every seed brings the same queries in another order."""
    order = list(traffic["queries"])
    random.Random(seed).shuffle(order)
    while True:
        yield from order


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def resolve_devices(config, rehearse: bool):
    import jax
    devs = jax.devices()
    d = devs[0]
    if not rehearse:
        if d.platform != config["platform"]:
            fail(f"jax resolved platform {d.platform!r}, the configuration "
                 f"asks for {config['platform']!r}: no accelerator, no number")
        if len(devs) < config["chips"]:
            fail(f"the cell asks for {config['chips']} chip(s), jax sees "
                 f"{len(devs)}")
    peaks = load_json(HERE, "peaks.json").get(d.device_kind)
    if peaks is None and not rehearse:
        fail(f"device kind {d.device_kind!r} is not in peaks.json")
    say(f"device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__}")
    return devs, peaks


def reads_of(queries) -> dict:
    """{table: [columns]} that the mix's queries read."""
    reads = {}
    for mod in queries.values():
        for table, cols in mod.READS.items():
            reads.setdefault(table, set()).update(cols)
    return {t: sorted(cols) for t, cols in sorted(reads.items())}


def make_data(sf: float, seed: int, query_names) -> None:
    """Tables and reference answers for (sf, seed), whatever of them is
    missing. Runs in a process of its own (``ensure_data``)."""
    import data
    import pyarrow.parquet as pq
    queries = {q: load_module("queries", q) for q in query_names}
    t0 = time.perf_counter()
    os.makedirs(DATA_DIR, exist_ok=True)
    out, written = data.ensure_tables(DATA_DIR, sf, seed, reads_of(queries))
    if written:
        say(f"data: generated {written} at sf={sf:g} seed={seed} in "
            f"{time.perf_counter() - t0:.1f}s -> {out}")
    for q, mod in queries.items():
        path = os.path.join(out, f"reference-{q}.parquet")
        if os.path.exists(path):
            continue
        t1 = time.perf_counter()
        frames = {t: pq.read_table(os.path.join(out, f"{t}.parquet"),
                                   columns=list(cols)).to_pandas()
                  for t, cols in mod.READS.items()}
        tmp = path + f".tmp{os.getpid()}"
        mod.reference(frames).to_parquet(tmp, index=False)
        os.replace(tmp, path)
        say(f"reference: {q} in {time.perf_counter() - t1:.1f}s")


def ensure_data(sf: float, seed: int, queries):
    """(directory, {query: reference frame}) for (sf, seed).

    The tables and the references are made by a child process that ends
    before the first query: making them leaves gigabytes of freed memory
    with the allocator, and a process in that state ran Q6 at SF10 in
    1.2 s where a fresh one, which is what a user has, takes 2.1 s
    (PERF.md, PR 24). The child never imports jax."""
    import multiprocessing

    import data
    import pandas as pd
    sys.stdout.flush()
    child = multiprocessing.get_context("spawn").Process(
        target=make_data, args=(sf, seed, list(queries)))
    child.start()
    child.join()
    if child.exitcode != 0:
        fail(f"making the data exited {child.exitcode}")
    out = data.dataset_dir(DATA_DIR, sf, seed)
    return out, {q: pd.read_parquet(
        os.path.join(out, f"reference-{q}.parquet")) for q in queries}


def open_session(config, data_dir, queries, rehearse: bool):
    """The session a user gets (chip_smoke.py's): engine defaults plus the
    configuration's confs."""
    from spark_rapids_tpu import nativelib
    from spark_rapids_tpu.session import TpuSparkSession
    if not nativelib.native_available():
        fail(f"native library absent: {nativelib.load_error()}")
    builder = TpuSparkSession.builder()
    for key, value in config["engine_confs"].items():
        builder = builder.config(key, value)
    session = builder.get_or_create()
    dm = session.device_manager
    if dm.platform != config["platform"] and not rehearse:
        fail(f"device manager resolved {dm.platform!r}")
    say(f"compile cache: {dm.compile_cache_dir}")
    tables = {t: session.read.parquet(os.path.join(data_dir, f"{t}.parquet"))
              for t in reads_of(queries)}
    return session, tables


class Counters:
    """The engine's compile counters and host-sync ledger (chip_smoke.py's
    ``_Counters``). A persistent-cache hit fires a backend-compile event too
    (the load), so real compiles = events - hits."""

    def __init__(self):
        from spark_rapids_tpu.obs.metrics import REGISTRY
        from spark_rapids_tpu.obs.syncledger import SYNC_LEDGER
        self.registry, self.ledger = REGISTRY, SYNC_LEDGER

    def programs(self):
        """(backend-compile events, persistent-cache hits) so far."""
        return (self.registry.counter("compileCache.backendCompiles").value,
                self.registry.counter("compileCache.persistentHits").value)


def execute(session, tables, mod):
    """One execution: wall ends after the answer is a host frame."""
    t0 = time.perf_counter()
    out = mod.build(session, tables).collect()
    return out, t0, time.perf_counter() - t0


def warm_up(session, tables, queries, counters):
    """Whole passes over the mix's queries until one loads no program.
    Returns (first execution's seconds, passes, real compiles, cache hits,
    {query: a warm answer})."""
    first_s, answers = None, {}
    e0, h0 = counters.programs()
    for n in range(1, MAX_WARM_PASSES + 1):
        before = counters.programs()
        for q, mod in queries.items():
            answers[q], _, wall = execute(session, tables, mod)
            if first_s is None:
                first_s = wall
        e1, h1 = counters.programs()
        say(f"warm-up pass {n}: programs loaded {e1 - before[0]} "
            f"(cache hits {h1 - before[1]})")
        if e1 == before[0]:
            return first_s, n, (e1 - e0) - (h1 - h0), h1 - h0, answers
    fail(f"still loading programs after {MAX_WARM_PASSES} warm-up passes")


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def run_loop(session, tables, queries, order, seconds, max_executions=None,
             after_each=None):
    """The closed loop of one client. Returns (window start, [(query, start,
    wall, answer or None)]); an execution that raises is kept with no
    answer and its traceback goes to stderr."""
    done = []
    start = time.perf_counter()
    while time.perf_counter() - start < seconds \
            and (max_executions is None or len(done) < max_executions):
        q = next(order)
        try:
            out, t0, wall = execute(session, tables, queries[q])
        except Exception:  # noqa: BLE001 — counted as failed, run goes on
            traceback.print_exc()
            out, t0, wall = None, time.perf_counter(), float("nan")
        done.append((q, t0, wall, out))
        if after_each is not None:
            after_each()
    return start, done


def count_failed(done, refs) -> int:
    from match import results_match
    return sum(1 for q, _, _, out in done
               if out is None or not results_match(out, refs[q]))


def quantile_90(values):
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def end_to_end(start, done, setup_s):
    walls = [w for _, _, w, out in done if out is not None]
    last_end = max(t0 + w for _, t0, w, out in done if out is not None)
    return {
        "query_s": statistics.median(walls),
        "query_p90_s": quantile_90(walls) if len(walls) >= 2 else walls[0],
        "queries_per_s": len(walls) / (last_end - start),
        "setup_s": setup_s,
    }


def traced_window(session, tables, queries, order, seconds, counters):
    """The short traced loop. Returns (start, done, SimpleNamespace of what
    the readers read)."""
    import jax
    from spark_rapids_tpu.obs.trace import TRACER
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    session.set_conf("spark.rapids.tpu.trace.jaxAnnotations", True)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    spans = []

    def harvest():  # the engine clears its tracer at every query's start
        spans.extend((e["name"], e["dur"] * 1e-6) for e in TRACER.events()
                     if "dur" in e)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    run = SimpleNamespace(
        counters_before=counters.registry.values(),
        ledger_before=counters.ledger.seq)
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            start, done = run_loop(
                session, tables, queries, order,
                min(seconds, TRACED_SECONDS), TRACED_EXECUTIONS, harvest)
    finally:
        jax.profiler.stop_trace()
    run.counters_after = counters.registry.values()
    run.ledger_after = counters.ledger.seq
    run.spans = spans
    session.set_conf("spark.rapids.tpu.trace.enabled", False)
    session.set_conf("spark.rapids.tpu.trace.jaxAnnotations", False)
    return start, done, run


def per_layer(bench, workload, run, executions):
    """The cell's per-layer metrics: each by its own file and reader. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    sys.path.insert(0, os.path.join(HERE, "readers"))
    out = {}
    for m in cell_metrics(bench, "per_layer", workload):
        spec = load_json(HERE, "layer_metrics", f"{m['name']}.json")
        value = load_module("readers", spec["reader"]).read(spec["arg"], run)
        if value is None:
            continue
        if spec["per"] == "execution":
            value = value / executions
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=None, metavar="SF",
                    help="a rehearsal of the control flow on whatever device "
                    "jax finds, at this scale factor; the result line says "
                    "which device, so it is never a measurement")
    args = ap.parse_args()
    rehearse = args.rehearse is not None
    if not os.path.isdir(os.path.join(ROOT, "spark_rapids_tpu")):
        fail(f"no spark_rapids_tpu package beside {HERE}: nothing to measure")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    cell, config, traffic, queries, bench = load_cell(args.workload)
    sf = args.rehearse if rehearse else config["scale_factor"]
    devs, peaks = resolve_devices(config, rehearse)
    devs = devs[:config["chips"]]
    data_dir, refs = ensure_data(sf, args.seed, queries)
    session, tables = open_session(config, data_dir, queries, rehearse)
    counters = Counters()
    first_s, passes, compiled, hits, warm = warm_up(
        session, tables, queries, counters)
    from match import results_match
    warm_ok = all(results_match(warm[q], refs[q]) for q in queries)
    say(f"warm-up: {passes} passes, first execution {first_s:.3f}s, "
        f"{compiled} programs compiled, {hits} from the cache; "
        f"warm answers match the reference: {warm_ok}")

    order = schedule(traffic, args.seed)
    before = counters.programs()
    setup_s = time.perf_counter() - T0
    if args.trace:
        start, done, run = traced_window(
            session, tables, queries, order, args.seconds, counters)
    else:
        start, done = run_loop(session, tables, queries, order, args.seconds)
    after = counters.programs()
    window_compiles = (after[0] - before[0]) - (after[1] - before[1])
    failed = count_failed(done, refs)
    say(f"window: {len(done)} executions, {failed} failed; programs loaded "
        f"in the window {after[0] - before[0]} (real compiles "
        f"{window_compiles})")
    good = [w for _, _, w, out in done if out is not None]
    if good:
        third = max(1, len(good) // 3)
        say(f"window: walls min {min(good):.4f} median "
            f"{statistics.median(good):.4f} max {max(good):.4f}s, medians "
            f"of the first and last third "
            f"{statistics.median(good[:third]):.4f} "
            f"{statistics.median(good[-third:]):.4f}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs)}
    result = {"correct": bool(warm_ok and failed == 0 and good),
              "attempted": len(done), "failed": failed}
    if args.trace:
        import trace_reduce
        span_names = {name for name, _ in run.spans}
        run.trace = trace_reduce.reduce_trace(
            TRACE_DIR, span_names, devs[0].platform) \
            if not rehearse else None
        run.devices, run.peaks = devs, peaks
        run.facts = {"first_query_s": first_s,
                     "bytes_read": sum(queries[q].bytes_read(sf)
                                       for q, _, _, _ in done)}
        result["metrics"] = per_layer(bench, args.workload, run, len(done))
        if run.trace is not None:
            say(f"trace: planes {run.trace['planes']}")
            say(f"trace: idle gaps {run.trace['idle_gap_detail']}")
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            result["breakdown"] = {"device_ops": run.trace["device_ops"],
                                   "idle_gaps": run.trace["idle_gaps"]}
    elif not good:
        result["metrics"] = {}  # every execution raised: nothing to report
    else:
        values = end_to_end(start, done, setup_s)
        say(f"window: p90 {values['query_p90_s']:.4f}s over {len(good)}")
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, "end_to_end", args.workload)}
    result["device"] = device
    if rehearse:
        result["rehearsal_sf"] = sf
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
