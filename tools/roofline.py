"""Roofline/utilization report for the dominant kernel of key queries.

For each query: run steady-state with SRT_KERNEL_PROFILE=1 (per-kernel
wall with forced completion + per-call argument/result bytes), pick the
top kernel by total time, and report achieved bytes/s against the
chip's HBM peak, plus model FLOP/s for the one-hot reduction kernels
(the only FLOP-dense kernels in the engine — everything else is
bandwidth/latency-bound data movement).

Per-call times include one forced completion fetch; its cost is measured
at the start of the run (median of many on a ready array) and subtracted
per call. Peaks come from ``PEAKS``, keyed by the device jax resolved: a
device that is not in the table is an error, so a CPU run cannot produce
a "% of HBM peak".

Usage:
  SRT_KERNEL_PROFILE=1 python tools/roofline.py [query ...]
      run the probe and write roofline.json + roofline.md into
      ROOFLINE_OUT_DIR (default docs/); when a previous roofline.json is
      there it is compared against first.
  python tools/roofline.py --compare BASE.json NEW.json
      compare two artifacts without running anything.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--compare" not in sys.argv \
        and os.environ.get("SRT_KERNEL_PROFILE") != "1":
    print("re-exec with SRT_KERNEL_PROFILE=1", file=sys.stderr)
    os.environ["SRT_KERNEL_PROFILE"] = "1"
    os.execv(sys.executable, [sys.executable] + sys.argv)

# Published peaks of one chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 — 393 is the
# int8 figure — and 819 GB/s of HBM bandwidth).
PEAKS = {"TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbs": 819.0}}

# tpcxbb.q5 joined the default probes with the hash-aggregation round:
# its partial HashAggregate(keys=[wcs_user_sk]) is PARITY.md's canonical
# click-scale grouping tail (~54% exclusive) and the kernel the
# roofline-class gate watches (BENCH_HASH_AGG=1 captures the one-pass
# hash partial pass instead of the default sort+segment baseline)
QUERIES = [a for a in sys.argv[1:] if not a.startswith("-")] \
    or ["q1", "q9", "q16", "tpcxbb.q5", "tpcxbb.q28", "mortgage.etl"]
OUT_DIR = os.environ.get("ROOFLINE_OUT_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs")


def load_artifact(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc.get("queries"), dict), f"{path}: not a roofline artifact"
    return doc


def compare_artifacts(base: dict, new: dict) -> str:
    """Per-query GB/s + wall deltas between two roofline artifacts: the
    per-round proof that the gather-bound kernels moved toward memory
    speed (or quietly fell back)."""
    lines = ["| query | GB/s base | GB/s new | Δ | % peak new | "
             "wall base | wall new |", "|---|---|---|---|---|---|---|"]
    common = sorted(set(base["queries"]) & set(new["queries"]))
    for q in common:
        b, n = base["queries"][q], new["queries"][q]
        d = (n["gbs"] / b["gbs"] - 1.0) * 100 if b.get("gbs") else 0.0
        lines.append(
            f"| {q} | {b.get('gbs')} | {n.get('gbs')} | {d:+.0f}% "
            f"| {n.get('pct_hbm_peak')}% | {b.get('wall_s')}s "
            f"| {n.get('wall_s')}s |")
    for q in sorted(set(base["queries"]) - set(new["queries"])):
        lines.append(f"| {q} | (dropped from new) | | | | | |")
    for q in sorted(set(new["queries"]) - set(base["queries"])):
        lines.append(f"| {q} | (new) | {new['queries'][q].get('gbs')} "
                     f"| | {new['queries'][q].get('pct_hbm_peak')}% | "
                     f"| {new['queries'][q].get('wall_s')}s |")
    return "\n".join(lines)


def write_artifacts(doc: dict) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    jpath = os.path.join(OUT_DIR, "roofline.json")
    prev = None
    if os.path.exists(jpath):
        try:
            prev = load_artifact(jpath)
        except Exception:
            prev = None
    with open(jpath, "w") as f:
        json.dump(doc, f, indent=1)
    md = ["# Roofline capture (tools/roofline.py)", "",
          f"SF={doc['sf']}, device {doc['device_kind']}, HBM peak "
          f"{doc['hbm_peak_gbs']} GB/s.", "",
          "| query | top kernel | calls | t(s) | t-sync(s) | MB moved "
          "| GB/s | % HBM peak | wall(s) |", "|---|---|---|---|---|---|---|---|---|"]
    for q, r in doc["queries"].items():
        md.append(f"| {q} | `{r['kernel']}` | {r['calls']} | {r['total_s']} "
                  f"| {r['compute_s']} | {r['mb_moved']} | {r['gbs']} "
                  f"| {r['pct_hbm_peak']} | {r['wall_s']} |")
    if prev is not None:
        md += ["", "## vs previous committed artifact", "",
               compare_artifacts(prev, doc)]
        print("\n-- vs previous docs/roofline.json --")
        print(compare_artifacts(prev, doc))
    with open(os.path.join(OUT_DIR, "roofline.md"), "w") as f:
        f.write("\n".join(md) + "\n")
    print(f"roofline: wrote {jpath} and roofline.md")


def measure_sync_baseline(n: int = 50) -> float:
    """Median seconds of the forced completion every profiled kernel
    call pays (utils/kernelcache._force_complete), on a ready array."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.utils import kernelcache
    bump = jax.jit(lambda a: a + 1)
    x = jnp.arange(8, dtype=jnp.int32)
    times = []
    for _ in range(n):
        # a fresh array each time: a fetched one keeps its host copy
        ready = jax.block_until_ready(bump(x))
        t0 = time.perf_counter()
        kernelcache._force_complete(ready)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main():
    import jax

    from spark_rapids_tpu.session import TpuSparkSession
    from spark_rapids_tpu.utils import kernelcache

    kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        sys.exit(f"roofline: no published peaks for device_kind {kind!r} "
                 f"(have {sorted(PEAKS)}); a roofline share needs a chip "
                 "in the table")
    hbm_peak_gbs = PEAKS[kind]["hbm_gbs"]
    sync_baseline_s = measure_sync_baseline()

    session = TpuSparkSession.builder().config(
        "spark.rapids.sql.enabled", True).config(
        "spark.rapids.sql.cacheDeviceScans", True).config(
        "spark.rapids.sql.agg.hashAggEnabled",
        os.environ.get("BENCH_HASH_AGG", "0") != "0").get_or_create()
    sf = float(os.environ.get("BENCH_SF", "0.5"))
    suites = {}

    def thunk(name):
        sn, q = (name.split(".", 1) if "." in name else ("tpch", name))
        if sn not in suites:
            if sn == "tpch":
                from spark_rapids_tpu.models.tpch import (
                    QUERIES as QS, TpchTables,
                )
                suites[sn] = (QS, TpchTables.generate(
                    session, sf, num_partitions=4))
            elif sn == "tpcxbb":
                from spark_rapids_tpu.models.tpcxbb import (
                    QUERIES as QS, TpcxbbTables,
                )
                suites[sn] = (QS, TpcxbbTables.generate(
                    session, sf * 20, num_partitions=4))
            else:
                from spark_rapids_tpu.models import mortgage, mortgage_data
                perf = session.create_dataframe(
                    mortgage_data.gen_performance(sf * 20), 4)
                acq = session.create_dataframe(
                    mortgage_data.gen_acquisition(sf * 20), 4)
                session.set_conf(
                    "spark.rapids.sql.exec.CartesianProductExec", True)
                suites[sn] = ({
                    "etl": lambda s, t: mortgage.run_etl(s, perf, acq),
                    "agg_join": lambda s, t: mortgage.aggregates_with_join(
                        s, perf, acq),
                    "percentiles":
                    lambda s, t: mortgage.aggregates_with_percentiles(
                        s, perf)}, None)
        qs, tables = suites[sn]
        return lambda: qs[q](session, tables).collect()

    rows = []
    for name in QUERIES:
        fn = thunk(name)
        for _ in range(4):
            fn()
        kernelcache.kernel_profile_reset()
        t0 = time.perf_counter()
        fn()
        wall = time.perf_counter() - t0
        prof = kernelcache.kernel_profile()
        top = sorted(((v[1], v) + (k,) for k, v in prof.items()),
                     reverse=True)
        secs, (calls, total_s, nbytes), sig = top[0]
        compute_s = max(total_s - sync_baseline_s * calls, 1e-4)
        gbs = nbytes / compute_s / 1e9
        flops_txt = "—"
        if "aggupd" in sig or "aggmrg" in sig or "dense" in sig:
            # one-hot reduction: FLOPs ~= 2 * N * T * K; not separable
            # from the signature alone — report the bytes-side only and
            # note the MXU share in the doc
            pass
        rows.append((name, sig[:60], calls, round(total_s, 3),
                     round(compute_s, 3), round(nbytes / 1e6, 1),
                     round(gbs, 2), round(100 * gbs / hbm_peak_gbs, 2),
                     flops_txt, round(wall, 3)))
        print(f"{name}: top kernel {sig[:80]} calls={calls} "
              f"t={total_s:.3f}s (-sync {compute_s:.3f}s) "
              f"{nbytes/1e6:.1f}MB -> {gbs:.2f} GB/s "
              f"({100*gbs/hbm_peak_gbs:.2f}% of HBM peak)", flush=True)

    print("\n| query | top kernel | calls | t(s) | t-sync(s) | MB moved "
          "| GB/s | % HBM peak |")
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r[0]} | `{r[1]}` | {r[2]} | {r[3]} | {r[4]} | {r[5]} "
              f"| {r[6]} | {r[7]} |")

    write_artifacts({
        "sf": sf,
        "device_kind": kind,
        "hbm_peak_gbs": hbm_peak_gbs,
        "sync_baseline_s": sync_baseline_s,
        "queries": {
            r[0]: {"kernel": r[1], "calls": r[2], "total_s": r[3],
                   "compute_s": r[4], "mb_moved": r[5], "gbs": r[6],
                   "pct_hbm_peak": r[7], "wall_s": r[9]}
            for r in rows},
    })


if __name__ == "__main__":
    if "--compare" in sys.argv:
        i = sys.argv.index("--compare")
        try:
            b, n = sys.argv[i + 1], sys.argv[i + 2]
        except IndexError:
            print("usage: roofline.py --compare BASE.json NEW.json",
                  file=sys.stderr)
            sys.exit(2)
        print(compare_artifacts(load_artifact(b), load_artifact(n)))
        sys.exit(0)
    main()
