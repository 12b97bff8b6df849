"""Per-query cost attribution: where does steady-state wall time go?

Separates, for one workload query in steady state:
  - host_get_s / host_get_n / host_get_bytes: blocking device->host
    fetch round trips (count, arrays, bytes, seconds)
  - sync_compute_s: device compute attributed per operator by a
    syncEachOp run (upper bound — sync inflates small ops)
  - python_s: wall minus fetch time (host-side trace/build/pandas)

Usage:
    python tools/attribute_query.py tpcxbb.q28 [mortgage.etl ...]
Env: BENCH_SF (default 0.5), ATTR_JSON=path to also dump JSON.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

_get_stats = {"n": 0, "secs": 0.0, "bytes": 0, "arrays": 0, "calls": []}
_real_device_get = jax.device_get


def _nbytes(tree):
    total = 0
    arrays = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
            arrays += 1
    return total, arrays


def _counted_device_get(tree):
    t0 = time.perf_counter()
    out = _real_device_get(tree)
    dt = time.perf_counter() - t0
    nb, arrs = _nbytes(tree)
    _get_stats["n"] += 1
    _get_stats["secs"] += dt
    _get_stats["bytes"] += nb
    _get_stats["arrays"] += arrs
    _get_stats["calls"].append((round(dt, 4), nb, arrs))
    return out


def _reset():
    _get_stats.update({"n": 0, "secs": 0.0, "bytes": 0, "arrays": 0,
                       "calls": []})


def main():
    names = sys.argv[1:] or ["tpcxbb.q28"]
    sf = float(os.environ.get("BENCH_SF", "0.5"))
    jax.device_get = _counted_device_get
    import spark_rapids_tpu.columnar.batch as _b  # ensure module binding
    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("spark_rapids_tpu"):
            if getattr(mod, "jax", None) is not None and \
                    hasattr(mod.jax, "device_get"):
                pass  # modules use jax.device_get attribute lookup - fine

    from spark_rapids_tpu.session import TpuSparkSession
    session = TpuSparkSession.builder().config(
        "spark.rapids.sql.enabled", True).config(
        "spark.rapids.sql.cacheDeviceScans", True).get_or_create()

    suites = {}

    def build(sn):
        if sn in suites:
            return suites[sn]
        if sn == "tpch":
            from spark_rapids_tpu.models.tpch import QUERIES, TpchTables
            t = TpchTables.generate(session, sf, num_partitions=4)
            suites[sn] = (QUERIES, t)
        elif sn == "tpcxbb":
            from spark_rapids_tpu.models.tpcxbb import (
                QUERIES, TpcxbbTables,
            )
            t = TpcxbbTables.generate(session, sf * 20, num_partitions=4)
            suites[sn] = (QUERIES, t)
        elif sn == "mortgage":
            from spark_rapids_tpu.models import mortgage, mortgage_data
            perf = session.create_dataframe(
                mortgage_data.gen_performance(sf * 20), 4)
            acq = session.create_dataframe(
                mortgage_data.gen_acquisition(sf * 20), 4)
            session.set_conf(
                "spark.rapids.sql.exec.CartesianProductExec", True)
            qs = {"etl": lambda s, t: mortgage.run_etl(s, perf, acq),
                  "agg_join": lambda s, t: mortgage.aggregates_with_join(
                      s, perf, acq),
                  "percentiles":
                  lambda s, t: mortgage.aggregates_with_percentiles(
                      s, perf)}
            suites[sn] = (qs, None)
        return suites[sn]

    report = {}
    for name in names:
        sn, q = (name.split(".", 1) if "." in name else ("tpch", name))
        queries, tables = build(sn)
        fn = queries[q]

        def run():
            return fn(session, tables).collect()

        # warm: compiles + adaptive paths settle (dense/speculation need
        # run 3 to fully engage)
        for _ in range(4):
            run()
        # steady state, 3 iters, take the min; count gets in that iter
        best = None
        for _ in range(3):
            _reset()
            t0 = time.perf_counter()
            out = run()
            wall = time.perf_counter() - t0
            if best is None or wall < best["wall_s"]:
                best = {"wall_s": wall,
                        "host_get_n": _get_stats["n"],
                        "host_get_s": _get_stats["secs"],
                        "host_get_bytes": _get_stats["bytes"],
                        "host_get_arrays": _get_stats["arrays"],
                        "calls": list(_get_stats["calls"]),
                        "rows_out": len(out)}
        # syncEachOp pass for device-compute attribution
        session.set_conf("spark.rapids.sql.profile.syncEachOp", True)
        session.capture_plans = True
        _reset()
        t0 = time.perf_counter()
        run()
        sync_wall = time.perf_counter() - t0
        session.set_conf("spark.rapids.sql.profile.syncEachOp", False)
        session.capture_plans = False
        plan = session.captured_plans[-1]
        times = session.last_node_times
        ops = []
        for node in plan.walk():
            incl = times.get(id(node))
            if incl is None:
                continue
            excl = incl - sum(times.get(id(c), 0.0) for c in node.children)
            ops.append((round(excl, 4), node.describe()[:90]))
        ops.sort(reverse=True)
        best["sync_wall_s"] = sync_wall
        best["sync_ops_total_s"] = round(sum(e for e, _ in ops), 4)
        best["top_ops"] = ops[:8]
        best["python_s"] = round(best["wall_s"] - best["host_get_s"], 4)
        report[name] = best
        print(f"\n=== {name} ===")
        print(f"wall={best['wall_s']:.3f}s  "
              f"gets: n={best['host_get_n']} "
              f"({best['host_get_arrays']} arrays, "
              f"{best['host_get_bytes']/1e6:.2f}MB, "
              f"{best['host_get_s']:.3f}s)  "
              f"non-fetch={best['python_s']:.3f}s  rows={best['rows_out']}")
        for dt, nb, arrs in best["calls"]:
            print(f"  get: {dt:.3f}s  {nb/1e6:.3f}MB  {arrs} arrays")
        print(f"syncEachOp wall={sync_wall:.3f}s, op-attributed "
              f"{best['sync_ops_total_s']:.3f}s; top ops:")
        for ex, op in best["top_ops"]:
            print(f"  {ex:8.3f}s  {op}")
        sys.stdout.flush()

    out_path = os.environ.get("ATTR_JSON")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, default=str)


if __name__ == "__main__":
    main()
