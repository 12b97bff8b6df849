"""Compare two bench sweep artifacts; nonzero exit on regression.

The CI gate the bench trajectory lacked: given a BASE and a NEW sweep,
report per-query speedup deltas above a noise threshold and the geomean
drift, and exit 1 when NEW regresses. The artifacts are what a run of
``bench.py`` leaves in its working directory (none is kept in the repo:
a record is only as good as the device it names, so compare two runs
made on the same chip). Accepts any of these shapes:

  * ``BENCH_DETAIL.json`` — ``{"queries": {name: {"speedup": ...}}}``
    (the per-query sidecar ``bench.py`` writes);
  * a wrapper ``{"parsed": summary, "tail": stderr}`` around a captured
    run; per-query speedups are recovered from the tail's
    ``bench: <q> tpu=..s cpu=..s speedup=..x`` lines, the geomean from
    ``parsed.value``;
  * a bare summary line — ``{"metric": ..., "value": geomean}``
    (geomean-only comparison);
  * ``BENCH_SERVE.json`` — the serve-mode artifact ``bench.py
    --concurrency N`` writes; when BOTH sides are serve artifacts the
    gate switches to **throughput**: NEW qps dropping more than
    ``--threshold`` below BASE (or NEW failing oracle verification)
    exits 1.

Besides speedups, the gate also compares **steady-state compile counts**
(``timed_compiles`` — XLA backend compiles during the timed iterations,
which a healthy query keeps at ZERO): a query whose warm-run compile
count grew between BASE and NEW re-traces in steady state, a compile
pathology that inflates wall time no speedup threshold reliably
catches. Any increase on a common query exits 1, same as a speedup
regression (``--ignore-compiles`` disables).

It also gates the **dispatch share** of the per-query device/transfer/
dispatch breakdown bench.py records in BENCH_DETAIL
(``dispatch_share``): a query whose dispatch fraction grows more than
``--dispatch-threshold`` (default 0.10 absolute) between sweeps got
MORE dispatch-bound — the pathology whole-stage fusion exists to
collapse (docs/fusion.md). ``--ignore-dispatch`` disables.

And it gates **warm-up** (docs/aot.md): a common query whose REAL
warm-up compile count (``warm_compiles``; persistent-cache hits already
excluded by bench.py) grew between sweeps, or a suite whose cold
first-query wall (``first_run_s`` / the summary's ``cold_start``) rose
more than ``--warmup-threshold`` (default 0.50 relative), exits 1 —
the zero-warm-up contract of the shape-bucket / shared-cache / AOT
layer. ``--ignore-warmup`` disables.

And it gates the **out-of-core stress tier** (``BENCH_STRESS.json``
from ``bench.py --stress``, docs/spill.md): when BOTH sides are stress
artifacts the gate compares stress throughput (rows/s dropping more
than ``--threshold`` regresses, like serve-mode qps), spill-count
drift (total spill events growing more than
``--stress-spill-threshold``, default 0.50 relative — the working-set
management got worse), and oracle verification. ``--ignore-stress``
reports the deltas without gating.

And it gates the **fleet tier** (``BENCH_FLEET.json`` from ``bench.py
--fleet N``, docs/fleet.md): when NEW is a fleet artifact the gate
switches to the **scaling ratio** — against a single-process serve
baseline (``BENCH_SERVE.json``), N-worker qps below ``--fleet-scaling``
(default 0.8) x N x the baseline qps exits 1 (the fleet is not earning
its processes), as does fleet p99 growing beyond
``--fleet-p99-threshold`` (default 0.50 relative) or failed oracle
verification; against another fleet artifact it gates qps/p99 drift
like serve mode. ``--ignore-fleet`` reports without gating.

And it gates **host syncs** (docs/observability.md, the sync ledger):
a common query whose steady-state blocking host-sync count
(``host_syncs`` — syncs per timed iteration) grew more than
``--sync-threshold`` (default 0.25 relative), or whose sync-blocked
wall share (``sync_s``/``tpu_s``) grew more than ``--sync-threshold``
absolute, exits 1 — the device went idle on host orchestration more
than it used to. ``--ignore-syncs`` disables.

And it gates **roofline class** (tools/roofline.py): pass ``--roofline
OLD.json NEW.json`` with two ``tools/roofline.py`` artifacts and any
common query whose dominant kernel's HBM-utilization class dropped
(high > elementwise [3-12%] > low [0.5-3%] > gather-built [<0.5%])
exits 1 — the ratchet that keeps a kernel PR from silently falling
back to a gather-built spelling. ``--ignore-roofline`` reports the
class moves without gating.

Exit codes: 0 = no regression, 1 = regression (any common query slower
than ``--threshold``, default 10%, geomean drift below
``--geomean-threshold``, default 5%, or a steady-state compile-count
increase), 2 = unusable input.

Usage:
    python tools/perfdiff.py BASE.json NEW.json [--threshold 0.10]
           [--geomean-threshold 0.05] [--ignore-compiles] [--json OUT]

Workflow (docs/observability.md): archive each round's detail file and
gate merges with
``python tools/perfdiff.py BENCH_prev.json BENCH_DETAIL.json``.
"""

from __future__ import annotations

import argparse
import json
import math
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

_TAIL_RE = re.compile(
    r"bench: (\S+) tpu=([\d.]+)s cpu=([\d.]+)s speedup=([\d.]+)x")
_TAIL_COMPILES_RE = re.compile(
    r"bench: (\S+) tpu=[\d.]+s cpu=[\d.]+s speedup=[\d.]+x "
    r"\(timed_compiles=(\d+)")


def _read_doc(path: str) -> Dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a JSON object")
    return doc


def load_sweep(path: str) -> Tuple[Dict[str, float], Optional[float]]:
    """-> (per-query speedups, recorded geomean or None)."""
    return sweep_from_doc(_read_doc(path), path)


def sweep_from_doc(doc: Dict[str, Any],
                   path: str) -> Tuple[Dict[str, float], Optional[float]]:
    if isinstance(doc.get("queries"), dict):
        per = {name: float(rec["speedup"])
               for name, rec in doc["queries"].items()
               if isinstance(rec, dict) and "speedup" in rec}
        return per, None
    if "parsed" in doc or "tail" in doc:
        per = {m.group(1): float(m.group(4))
               for m in _TAIL_RE.finditer(str(doc.get("tail", "")))}
        parsed = doc.get("parsed") or {}
        geo = float(parsed["value"]) if "value" in parsed else None
        return per, geo
    if "value" in doc and "metric" in doc:
        return {}, float(doc["value"])
    raise ValueError(
        f"{path}: unrecognized sweep shape (expected BENCH_DETAIL "
        "'queries' dict, BENCH_r* 'parsed'/'tail' wrapper, or a summary "
        "line with 'metric'/'value')")


def load_compiles(path: str) -> Dict[str, int]:
    """Per-query steady-state compile counts (``timed_compiles``) from a
    sweep artifact; empty when the shape does not carry them (bare
    summary lines)."""
    return compiles_from_doc(_read_doc(path))


def compiles_from_doc(doc: Dict[str, Any]) -> Dict[str, int]:
    if isinstance(doc.get("queries"), dict):
        return {name: int(rec["timed_compiles"])
                for name, rec in doc["queries"].items()
                if isinstance(rec, dict) and "timed_compiles" in rec}
    if "parsed" in doc or "tail" in doc:
        return {m.group(1): int(m.group(2))
                for m in _TAIL_COMPILES_RE.finditer(
                    str(doc.get("tail", "")))}
    return {}


def dispatch_from_doc(doc: Dict[str, Any]) -> Dict[str, float]:
    """Per-query dispatch-time share of the device/transfer/dispatch
    breakdown (``bench.py`` records it in BENCH_DETAIL under
    ``dispatch_share``); empty for artifact shapes without it."""
    if isinstance(doc.get("queries"), dict):
        return {name: float(rec["dispatch_share"])
                for name, rec in doc["queries"].items()
                if isinstance(rec, dict) and "dispatch_share" in rec}
    return {}


def scan_from_doc(doc: Dict[str, Any]) -> Dict[str, float]:
    """Per-query SCAN-INCLUSIVE speedups (cpu_s / tpu_scan_off_s) from a
    BENCH_DETAIL-shaped artifact — the honesty axis of VERDICT r5
    Missing #2: measured scan cost must stay paid-for run over run.
    Empty for artifact shapes without scan-off probes."""
    if isinstance(doc.get("queries"), dict):
        out = {}
        for name, rec in doc["queries"].items():
            if (isinstance(rec, dict) and rec.get("tpu_scan_off_s")
                    and rec.get("cpu_s")):
                out[name] = float(rec["cpu_s"]) / float(rec["tpu_scan_off_s"])
        return out
    return {}


def scan_modes_from_doc(doc: Dict[str, Any]) -> Dict[str, str]:
    """Per-query scan decode-mode verdicts (``host``/``mixed``/``device``)
    from a BENCH_DETAIL-shaped artifact's ``--include-scan`` records
    (bench.py's deviceDecode pass, docs/scan_device.md). Empty for
    artifact shapes without the scan sidecar."""
    if isinstance(doc.get("queries"), dict):
        out = {}
        for name, rec in doc["queries"].items():
            if isinstance(rec, dict):
                mode = (rec.get("scan") or {}).get("scan_decode_mode")
                if mode in ("host", "mixed", "device"):
                    out[name] = mode
        return out
    return {}


def syncs_from_doc(doc: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Per-query steady-state host-sync facts from a BENCH_DETAIL-shaped
    artifact (``bench.py`` records ``host_syncs`` — blocking device<->
    host points per timed iteration, obs/syncledger.py — and ``sync_s``):
    ``counts`` maps query -> syncs-per-iteration, ``shares`` maps
    query -> sync-blocked fraction of steady-state wall (sync_s/tpu_s).
    Empty maps for artifact shapes without them."""
    out: Dict[str, Dict[str, float]] = {"counts": {}, "shares": {}}
    if isinstance(doc.get("queries"), dict):
        for name, rec in doc["queries"].items():
            if not isinstance(rec, dict) or "host_syncs" not in rec:
                continue
            out["counts"][name] = float(rec["host_syncs"])
            if rec.get("sync_s") is not None and rec.get("tpu_s"):
                out["shares"][name] = (float(rec["sync_s"])
                                       / float(rec["tpu_s"]))
    return out


def losers_from_doc(doc: Dict[str, Any],
                    per: Dict[str, float]) -> Optional[int]:
    """``n_below_1x`` of a sweep: the summary's recorded count when
    present, else derived from per-query speedups; None when neither is
    available."""
    for container in (doc, doc.get("parsed") or {}):
        if isinstance(container, dict) and "n_below_1x" in container:
            try:
                return int(container["n_below_1x"])
            except (TypeError, ValueError):
                pass
    if per:
        return sum(1 for v in per.values() if v < 1.0)
    return None


# HBM-utilization classes of a query's dominant kernel, ranked: the
# gather-built kernels sit under 0.5% of HBM peak, healthy elementwise
# data movement in the 3-12% band. The roofline gate
# fails when a common query's class RANK drops between two
# tools/roofline.py artifacts — intra-class GB/s noise never gates.
ROOFLINE_CLASSES = [("gather", 0.5), ("low", 3.0),
                    ("elementwise", 12.0), ("high", float("inf"))]


def roofline_class(pct_hbm_peak: float) -> Tuple[int, str]:
    """(rank, name) of a %-of-HBM-peak utilization figure."""
    for rank, (name, bound) in enumerate(ROOFLINE_CLASSES):
        if float(pct_hbm_peak) < bound:
            return rank, name
    return len(ROOFLINE_CLASSES) - 1, ROOFLINE_CLASSES[-1][0]


def roofline_deltas(base_doc: Dict[str, Any],
                    new_doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per-common-query class movement between two roofline artifacts
    (tools/roofline.py ``{"queries": {name: {"pct_hbm_peak": ...}}}``)."""
    bq, nq = base_doc.get("queries"), new_doc.get("queries")
    if not isinstance(bq, dict) or not isinstance(nq, dict):
        raise ValueError("not a roofline artifact (no 'queries' map)")
    out = []
    for q in sorted(set(bq) & set(nq)):
        bp = bq[q].get("pct_hbm_peak")
        np_ = nq[q].get("pct_hbm_peak")
        if bp is None or np_ is None:
            continue
        br, bc = roofline_class(bp)
        nr, nc = roofline_class(np_)
        out.append({"query": q, "base_pct": float(bp),
                    "new_pct": float(np_), "base_class": bc,
                    "new_class": nc, "regressed": nr < br})
    if not out:
        raise ValueError("roofline artifacts share no gateable queries")
    return out


def warmup_from_doc(doc: Dict[str, Any]) -> Dict[str, Any]:
    """Warm-up facts of a sweep artifact (``bench.py``'s cold-process
    metrics): per-query REAL warm-up compile counts
    (``warm_compiles``, persistent-cache hits already excluded by the
    worker) and the per-suite cold first-query wall (``first_run_s`` of
    each suite's first scored query; the summary's ``cold_start`` block
    when present). Empty maps for artifact shapes without them."""
    out: Dict[str, Any] = {"warm_compiles": {}, "first_query_s": {}}
    queries = doc.get("queries")
    if isinstance(queries, dict):
        for name, rec in queries.items():
            if not isinstance(rec, dict):
                continue
            if "warm_compiles" in rec:
                out["warm_compiles"][name] = int(rec["warm_compiles"])
            suite = name.split(".", 1)[0] if "." in name else "tpch"
            if rec.get("first_run_s") is not None \
                    and suite not in out["first_query_s"]:
                out["first_query_s"][suite] = float(rec["first_run_s"])
    cold = (doc.get("parsed") or {}).get("cold_start") \
        if ("parsed" in doc or "tail" in doc) else doc.get("cold_start")
    if isinstance(cold, dict):
        for suite, rec in cold.items():
            if isinstance(rec, dict) \
                    and rec.get("first_query_s") is not None:
                out["first_query_s"].setdefault(
                    suite, float(rec["first_query_s"]))
    return out


def serve_from_doc(doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Serve-mode artifact (``BENCH_SERVE.json`` from ``bench.py
    --concurrency N``): throughput + latency quantiles. None when the
    doc is not a serve artifact."""
    if "qps" not in doc or "latency_s" not in doc:
        return None
    lat = doc.get("latency_s") or {}
    return {"qps": float(doc["qps"]) if doc["qps"] else None,
            "p50": lat.get("p50"), "p99": lat.get("p99"),
            "concurrency": doc.get("concurrency"),
            "verified": doc.get("verified")}


def compare_serve(base: Dict[str, Any], new: Dict[str, Any],
                  threshold: float) -> Dict[str, Any]:
    """Serve-mode throughput gate: NEW qps dropping more than
    ``threshold`` below BASE regresses (same bound as a per-query
    speedup), as does a NEW sweep that failed verification."""
    qb, qn = base.get("qps"), new.get("qps")
    drift = (qn / qb - 1.0) if qb and qn else None
    regressed = (drift is not None and drift < -threshold) \
        or new.get("verified") is False
    return {
        "mode": "serve",
        "concurrency_base": base.get("concurrency"),
        "concurrency_new": new.get("concurrency"),
        "qps_base": qb, "qps_new": qn,
        "qps_drift_pct": round(100.0 * drift, 2)
        if drift is not None else None,
        "p99_base": base.get("p99"), "p99_new": new.get("p99"),
        "threshold_pct": round(100.0 * threshold, 2),
        "new_verified": new.get("verified"),
        "regressed": regressed,
    }


def render_serve_text(rep: Dict[str, Any]) -> str:
    lines = [
        f"perfdiff (serve mode): qps {rep['qps_base']} -> "
        f"{rep['qps_new']}"
        + (f" ({rep['qps_drift_pct']:+.2f}%)"
           if rep["qps_drift_pct"] is not None else "")
        + f", p99 {rep['p99_base']}s -> {rep['p99_new']}s"]
    if rep["new_verified"] is False:
        lines.append("-- NEW serve sweep FAILED result verification")
    if rep["regressed"] and rep["qps_drift_pct"] is not None \
            and rep["qps_drift_pct"] < -rep["threshold_pct"]:
        lines.append(f"-- THROUGHPUT REGRESSION: qps drift "
                     f"{rep['qps_drift_pct']:+.2f}% exceeds "
                     f"-{rep['threshold_pct']:.0f}%")
    lines.append("RESULT: " + ("REGRESSED" if rep["regressed"]
                               else "ok"))
    return "\n".join(lines)


def fleet_from_doc(doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Fleet-tier artifact (``BENCH_FLEET.json`` from ``bench.py
    --fleet N``): multi-process throughput + per-replica shape. None
    when the doc is not a fleet artifact."""
    if doc.get("mode") != "fleet" or "qps" not in doc:
        return None
    lat = doc.get("latency_s") or {}
    return {"qps": float(doc["qps"]) if doc["qps"] else None,
            "p50": lat.get("p50"), "p99": lat.get("p99"),
            "workers": int(doc.get("workers") or 0),
            "shed": doc.get("shed"),
            "placement_churn": doc.get("placement_churn"),
            "verified": doc.get("verified")}


def compare_fleet(base: Dict[str, Any], new: Dict[str, Any],
                  threshold: float, fleet_scaling: float = 0.8,
                  p99_threshold: float = 0.50) -> Dict[str, Any]:
    """Fleet gate, two shapes by what BASE is:

    * BASE is a single-process SERVE artifact: the scaling gate — an
      N-worker fleet must deliver at least ``fleet_scaling`` x N x the
      baseline qps (AlpaServe's near-linear placement-aware scaling;
      below it the tier costs processes without earning throughput);
    * BASE is another FLEET artifact: plain drift, like serve mode —
      qps dropping more than ``threshold`` regresses.

    Either way, fleet p99 growing more than ``p99_threshold`` relative
    over BASE p99, or NEW failing oracle verification, regresses."""
    scaling_mode = "workers" not in base  # serve baseline
    qb, qn = base.get("qps"), new.get("qps")
    workers = new.get("workers") or 0
    if scaling_mode:
        required = (fleet_scaling * workers * qb) \
            if qb and workers else None
        qps_bad = (required is not None
                   and (qn or 0.0) < required)
        drift = None
        ratio = round(qn / (qb * workers), 4) \
            if qb and qn and workers else None
    else:
        required = None
        ratio = None
        drift = (qn / qb - 1.0) if qb and qn else None
        qps_bad = drift is not None and drift < -threshold
    pb, pn = base.get("p99"), new.get("p99")
    p99_growth = (pn / pb - 1.0) if pb and pn else None
    p99_bad = p99_growth is not None and p99_growth > p99_threshold
    regressed = qps_bad or p99_bad or new.get("verified") is False
    return {
        "mode": "fleet",
        "gate": "scaling" if scaling_mode else "drift",
        "workers": workers,
        "qps_base": qb, "qps_new": qn,
        "qps_required": round(required, 4)
        if required is not None else None,
        "scaling_ratio": ratio,
        "fleet_scaling": fleet_scaling,
        "qps_drift_pct": round(100.0 * drift, 2)
        if drift is not None else None,
        "p99_base": pb, "p99_new": pn,
        "p99_growth_pct": round(100.0 * p99_growth, 2)
        if p99_growth is not None else None,
        "p99_threshold_pct": round(100.0 * p99_threshold, 2),
        "threshold_pct": round(100.0 * threshold, 2),
        "shed_new": new.get("shed"),
        "placement_churn_new": new.get("placement_churn"),
        "new_verified": new.get("verified"),
        "qps_regressed": qps_bad, "p99_regressed": p99_bad,
        "regressed": regressed,
    }


def render_fleet_text(rep: Dict[str, Any]) -> str:
    lines = [
        f"perfdiff (fleet mode, {rep['gate']} gate, "
        f"{rep['workers']} workers): qps {rep['qps_base']} -> "
        f"{rep['qps_new']}"
        + (f" (per-worker scaling {rep['scaling_ratio']:.2f}x, "
           f"required >= {rep['qps_required']})"
           if rep["scaling_ratio"] is not None else "")
        + (f" ({rep['qps_drift_pct']:+.2f}%)"
           if rep["qps_drift_pct"] is not None else "")
        + f", p99 {rep['p99_base']}s -> {rep['p99_new']}s"
        + (f" ({rep['p99_growth_pct']:+.2f}%)"
           if rep["p99_growth_pct"] is not None else "")]
    if rep.get("shed_new"):
        lines.append(f"-- NEW fleet shed {rep['shed_new']} jobs")
    if rep["new_verified"] is False:
        lines.append("-- NEW fleet sweep FAILED result verification")
    if rep.get("ignored"):
        lines.append("-- fleet gate IGNORED (--ignore-fleet)")
    else:
        if rep["qps_regressed"] and rep["gate"] == "scaling":
            lines.append(
                f"-- FLEET SCALING REGRESSION: {rep['workers']}-worker "
                f"qps {rep['qps_new']} below "
                f"{rep['fleet_scaling']:.2f} x {rep['workers']} x "
                f"baseline ({rep['qps_required']})")
        elif rep["qps_regressed"]:
            lines.append(f"-- THROUGHPUT REGRESSION: qps drift "
                         f"{rep['qps_drift_pct']:+.2f}% exceeds "
                         f"-{rep['threshold_pct']:.0f}%")
        if rep["p99_regressed"]:
            lines.append(f"-- LATENCY REGRESSION: p99 growth "
                         f"{rep['p99_growth_pct']:+.2f}% exceeds "
                         f"+{rep['p99_threshold_pct']:.0f}%")
    lines.append("RESULT: " + ("REGRESSED" if rep["regressed"]
                               else "ok"))
    return "\n".join(lines)


def stress_from_doc(doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Stress-tier artifact (``BENCH_STRESS.json`` from ``bench.py
    --stress``): out-of-core throughput + spill counts. None when the
    doc is not a stress artifact."""
    if doc.get("mode") != "stress" or "spill_events_total" not in doc:
        return None
    return {
        "throughput": doc.get("throughput_rows_per_s"),
        "spills": int(doc.get("spill_events_total") or 0),
        "verified": doc.get("verified"),
        "budget_bytes": doc.get("budget_bytes"),
    }


def compare_stress(base: Dict[str, Any], new: Dict[str, Any],
                   threshold: float,
                   spill_threshold: float = 0.50) -> Dict[str, Any]:
    """Stress-tier gate: NEW rows/s dropping more than ``threshold``
    below BASE regresses (same bound as serve-mode qps); NEW's total
    spill-event count growing more than ``spill_threshold`` relative
    regresses (the out-of-core layer started thrashing); a NEW sweep
    failing oracle verification regresses unconditionally."""
    tb, tn = base.get("throughput"), new.get("throughput")
    if tb and tn:
        drift = tn / tb - 1.0
    elif tb and not tn:
        # BASE measured throughput, NEW has none (null/0 = no query
        # produced a positive wall): a total collapse is the WORST
        # regression and must not sail through the gate
        drift = -1.0
    else:
        drift = None
    sb, sn = base.get("spills", 0), new.get("spills", 0)
    if sb > 0:
        spill_growth = (sn - sb) / sb
    else:
        spill_growth = None if sn == 0 else float("inf")
    regressed = ((drift is not None and drift < -threshold)
                 or (spill_growth is not None
                     and spill_growth > spill_threshold)
                 or new.get("verified") is False)
    return {
        "mode": "stress",
        "throughput_base": tb, "throughput_new": tn,
        "throughput_drift_pct": round(100.0 * drift, 2)
        if drift is not None else None,
        "spills_base": sb, "spills_new": sn,
        "spill_growth_pct": (round(100.0 * spill_growth, 2)
                             if spill_growth not in (None, float("inf"))
                             else ("inf" if spill_growth == float("inf")
                                   else None)),
        "threshold_pct": round(100.0 * threshold, 2),
        "spill_threshold_pct": round(100.0 * spill_threshold, 2),
        "new_verified": new.get("verified"),
        "regressed": regressed,
    }


def render_stress_text(rep: Dict[str, Any]) -> str:
    lines = [
        f"perfdiff (stress mode): rows/s {rep['throughput_base']} -> "
        f"{rep['throughput_new']}"
        + (f" ({rep['throughput_drift_pct']:+.2f}%)"
           if rep["throughput_drift_pct"] is not None else "")
        + f", spill events {rep['spills_base']} -> {rep['spills_new']}"
        + (f" ({rep['spill_growth_pct']:+.2f}%)"
           if isinstance(rep["spill_growth_pct"], (int, float)) else
           (" (inf%)" if rep["spill_growth_pct"] == "inf" else ""))]
    if rep["new_verified"] is False:
        lines.append("-- NEW stress sweep FAILED result verification")
    if rep.get("ignored"):
        lines.append("-- stress gate IGNORED (--ignore-stress)")
    elif rep["regressed"]:
        lines.append("-- STRESS REGRESSION (throughput drop beyond "
                     f"-{rep['threshold_pct']:.0f}%, spill growth beyond "
                     f"+{rep['spill_threshold_pct']:.0f}%, or failed "
                     "verification)")
    lines.append("RESULT: " + ("REGRESSED" if rep["regressed"] else "ok"))
    return "\n".join(lines)


def _geomean(values) -> Optional[float]:
    vals = [v for v in values if v and v > 0]
    if not vals:
        return None
    return math.exp(sum(math.log(v) for v in vals) / len(vals))


def compare(base: Dict[str, float], base_geo: Optional[float],
            new: Dict[str, float], new_geo: Optional[float],
            threshold: float, geo_threshold: float,
            base_compiles: Optional[Dict[str, int]] = None,
            new_compiles: Optional[Dict[str, int]] = None,
            base_dispatch: Optional[Dict[str, float]] = None,
            new_dispatch: Optional[Dict[str, float]] = None,
            dispatch_threshold: float = 0.10,
            base_warmup: Optional[Dict[str, Any]] = None,
            new_warmup: Optional[Dict[str, Any]] = None,
            warmup_threshold: float = 0.50,
            base_scan: Optional[Dict[str, float]] = None,
            new_scan: Optional[Dict[str, float]] = None,
            scan_threshold: float = 0.10,
            base_losers: Optional[int] = None,
            new_losers: Optional[int] = None,
            gate_losers: bool = True,
            base_syncs: Optional[Dict[str, Dict[str, float]]] = None,
            new_syncs: Optional[Dict[str, Dict[str, float]]] = None,
            sync_threshold: float = 0.25,
            base_scan_modes: Optional[Dict[str, str]] = None,
            new_scan_modes: Optional[Dict[str, str]] = None) \
        -> Dict[str, Any]:
    common = sorted(set(base) & set(new))
    deltas = []
    for q in common:
        d = new[q] / base[q] - 1.0 if base[q] > 0 else 0.0
        deltas.append({"query": q, "base": base[q], "new": new[q],
                       "delta_pct": round(100.0 * d, 2),
                       "regressed": d < -threshold,
                       "improved": d > threshold})
    deltas.sort(key=lambda r: r["delta_pct"])
    # geomean drift over the COMMON set when both sides have per-query
    # data (apples to apples); without overlap fall back to whole-sweep
    # geomeans — recorded, or derived from whichever per-query data
    # exists (the dropped/new listings flag the set mismatch)
    if common:
        gb = _geomean(base[q] for q in common)
        gn = _geomean(new[q] for q in common)
    else:
        gb = base_geo if base_geo is not None else \
            _geomean(base.values())
        gn = new_geo if new_geo is not None else _geomean(new.values())
    drift = (gn / gb - 1.0) if (gb and gn) else None
    regressions = [r for r in deltas if r["regressed"]]
    geo_regressed = drift is not None and drift < -geo_threshold
    # steady-state recompile gate: timed_compiles growing between sweeps
    # means the engine re-traces during timed iterations now — a compile
    # pathology, gated exactly like a speedup regression (ROADMAP item
    # 2's success metric is timed_compiles -> 0 everywhere)
    compile_deltas = []
    for q in sorted(set(base_compiles or {}) & set(new_compiles or {})):
        b, n = base_compiles[q], new_compiles[q]
        if b != n:
            compile_deltas.append({"query": q, "base": b, "new": n,
                                   "regressed": n > b})
    compile_regressions = [d["query"] for d in compile_deltas
                           if d["regressed"]]
    # dispatch-share gate: the breakdown's dispatch fraction growing
    # between sweeps means the engine got MORE dispatch-bound — the
    # exact pathology whole-stage fusion exists to collapse. An absolute
    # share increase beyond dispatch_threshold regresses.
    dispatch_deltas = []
    for q in sorted(set(base_dispatch or {}) & set(new_dispatch or {})):
        b, n = base_dispatch[q], new_dispatch[q]
        if abs(n - b) > 1e-9:
            dispatch_deltas.append({
                "query": q, "base": round(b, 4), "new": round(n, 4),
                "regressed": (n - b) > dispatch_threshold})
    dispatch_regressions = [d["query"] for d in dispatch_deltas
                            if d["regressed"]]
    # warm-up gate: a query whose REAL warm-up compile count grew
    # between sweeps lost part of its zero-warm-up story (shape buckets
    # / shared cache / AOT replay, docs/aot.md) — gated like a
    # steady-state recompile. The cold first-query wall is gated per
    # suite with its own (looser) threshold: cold walls carry one-off
    # I/O noise a 10% bound would false-positive on.
    bw = base_warmup or {"warm_compiles": {}, "first_query_s": {}}
    nw = new_warmup or {"warm_compiles": {}, "first_query_s": {}}
    warmup_deltas = []
    for q in sorted(set(bw["warm_compiles"]) & set(nw["warm_compiles"])):
        b, n = bw["warm_compiles"][q], nw["warm_compiles"][q]
        if b != n:
            warmup_deltas.append({"query": q, "base": b, "new": n,
                                  "regressed": n > b})
    warmup_regressions = [d["query"] for d in warmup_deltas
                          if d["regressed"]]
    first_query_deltas = []
    for sn in sorted(set(bw["first_query_s"]) & set(nw["first_query_s"])):
        b, n = bw["first_query_s"][sn], nw["first_query_s"][sn]
        d = n / b - 1.0 if b > 0 else 0.0
        first_query_deltas.append({
            "suite": sn, "base": round(b, 4), "new": round(n, 4),
            "delta_pct": round(100.0 * d, 2),
            "regressed": d > warmup_threshold})
    first_query_regressions = [d["suite"] for d in first_query_deltas
                               if d["regressed"]]
    # scan-inclusive gate (--scan-threshold): the cpu/scan-off speedup of
    # a query dropping beyond the threshold means the engine's PAID scan
    # path regressed even if the cached steady state held (VERDICT r5
    # Missing #2 — measured must stay paid-for)
    scan_deltas = []
    for q in sorted(set(base_scan or {}) & set(new_scan or {})):
        b, n = base_scan[q], new_scan[q]
        d = n / b - 1.0 if b > 0 else 0.0
        if abs(d) > 1e-9:
            scan_deltas.append({"query": q, "base": round(b, 3),
                                "new": round(n, 3),
                                "delta_pct": round(100.0 * d, 2),
                                "regressed": d < -scan_threshold})
    scan_regressions = [d["query"] for d in scan_deltas if d["regressed"]]
    scan_geo_b = _geomean((base_scan or {}).values()) \
        if base_scan else None
    scan_geo_n = _geomean((new_scan or {}).values()) if new_scan else None
    scan_drift = (scan_geo_n / scan_geo_b - 1.0) \
        if (scan_geo_b and scan_geo_n) else None
    scan_geo_regressed = (scan_drift is not None
                          and scan_drift < -scan_threshold)
    # loser-count gate: n_below_1x growing between sweeps is the "zero
    # margin" photo-finish failure mode — a sweep can hold its geomean
    # while quietly pushing more queries under 1x (--ignore-losers opts
    # out). When the two sweeps cover DIFFERENT query sets (a grown
    # suite), whole-sweep counts would false-positive on the new-only
    # queries — like every other gate, restrict to the common set then.
    if common and (set(base) != set(new)):
        base_losers = sum(1 for q in common if base[q] < 1.0)
        new_losers = sum(1 for q in common if new[q] < 1.0)
    losers_regressed = (gate_losers and base_losers is not None
                        and new_losers is not None
                        and new_losers > base_losers)
    # host-sync gate (--sync-threshold): a query's steady-state blocking
    # syncs per iteration growing more than sync_threshold relative, or
    # its sync-blocked wall SHARE growing more than sync_threshold
    # absolute, regresses — the device sat idle on host orchestration
    # more than it used to (obs/syncledger.py, ROADMAP item 4's
    # "syncs per query -> <= 1 collect" trajectory)
    bsy = base_syncs or {"counts": {}, "shares": {}}
    nsy = new_syncs or {"counts": {}, "shares": {}}
    sync_deltas = []
    for q in sorted(set(bsy["counts"]) & set(nsy["counts"])):
        b, n = bsy["counts"][q], nsy["counts"][q]
        if abs(n - b) < 1e-9:
            continue
        growth = (n - b) / max(b, 1.0)
        sync_deltas.append({"query": q, "base": b, "new": n,
                            "growth_pct": round(100.0 * growth, 1),
                            "regressed": growth > sync_threshold})
    sync_regressions = [d["query"] for d in sync_deltas
                        if d["regressed"]]
    sync_share_deltas = []
    for q in sorted(set(bsy["shares"]) & set(nsy["shares"])):
        b, n = bsy["shares"][q], nsy["shares"][q]
        if abs(n - b) < 1e-9:
            continue
        sync_share_deltas.append({
            "query": q, "base": round(b, 4), "new": round(n, 4),
            "regressed": (n - b) > sync_threshold})
    sync_share_regressions = [d["query"] for d in sync_share_deltas
                              if d["regressed"]]
    # decode-mode gate (--ignore-scan-mode opts out): a query whose scan
    # decode mode drops rank between sweeps (device -> mixed/host, or
    # mixed -> host) silently fell off the device decode path — the scan
    # may still pass its timing gates while every page quietly rides the
    # pandas fallback again (docs/scan_device.md). Rank order:
    # host < mixed < device; only a DROP regresses (host -> device is
    # the improvement this gate exists to protect).
    mode_rank = {"host": 0, "mixed": 1, "device": 2}
    scan_mode_deltas = []
    for q in sorted(set(base_scan_modes or {}) & set(new_scan_modes or {})):
        b, n = base_scan_modes[q], new_scan_modes[q]
        if b != n:
            scan_mode_deltas.append({
                "query": q, "base": b, "new": n,
                "regressed": mode_rank.get(n, 0) < mode_rank.get(b, 0)})
    scan_mode_regressions = [d["query"] for d in scan_mode_deltas
                             if d["regressed"]]
    return {
        "scan_mode_deltas": scan_mode_deltas,
        "scan_mode_regressions": scan_mode_regressions,
        "sync_deltas": sync_deltas,
        "sync_regressions": sync_regressions,
        "sync_share_deltas": sync_share_deltas,
        "sync_share_regressions": sync_share_regressions,
        "sync_threshold": round(sync_threshold, 4),
        "scan_deltas": scan_deltas,
        "scan_regressions": scan_regressions,
        "scan_threshold_pct": round(100.0 * scan_threshold, 2),
        "scan_geomean_base": round(scan_geo_b, 4) if scan_geo_b else None,
        "scan_geomean_new": round(scan_geo_n, 4) if scan_geo_n else None,
        "scan_geomean_drift_pct": round(100.0 * scan_drift, 2)
        if scan_drift is not None else None,
        "scan_geomean_regressed": scan_geo_regressed,
        "n_below_1x_base": base_losers,
        "n_below_1x_new": new_losers,
        "losers_regressed": losers_regressed,
        "warmup_deltas": warmup_deltas,
        "warmup_regressions": warmup_regressions,
        "first_query_deltas": first_query_deltas,
        "first_query_regressions": first_query_regressions,
        "warmup_threshold": round(warmup_threshold, 4),
        "compile_deltas": compile_deltas,
        "compile_regressions": compile_regressions,
        "dispatch_deltas": dispatch_deltas,
        "dispatch_regressions": dispatch_regressions,
        "dispatch_threshold": round(dispatch_threshold, 4),
        "common_queries": len(common),
        "only_in_base": sorted(set(base) - set(new)),
        "only_in_new": sorted(set(new) - set(base)),
        "threshold_pct": round(100.0 * threshold, 2),
        "geomean_threshold_pct": round(100.0 * geo_threshold, 2),
        "geomean_base": round(gb, 4) if gb else None,
        "geomean_new": round(gn, 4) if gn else None,
        "geomean_drift_pct": round(100.0 * drift, 2)
        if drift is not None else None,
        "geomean_regressed": geo_regressed,
        "regressions": [r["query"] for r in regressions],
        "improvements": [r["query"] for r in deltas if r["improved"]],
        "deltas": deltas,
        "regressed": bool(regressions) or geo_regressed
        or bool(compile_regressions) or bool(dispatch_regressions)
        or bool(warmup_regressions) or bool(first_query_regressions)
        or bool(scan_regressions) or scan_geo_regressed
        or losers_regressed or bool(sync_regressions)
        or bool(sync_share_regressions) or bool(scan_mode_regressions),
    }


def render_text(rep: Dict[str, Any]) -> str:
    lines = []
    gb, gn = rep["geomean_base"], rep["geomean_new"]
    drift = rep["geomean_drift_pct"]
    lines.append(
        f"perfdiff: {rep['common_queries']} common queries, geomean "
        f"{gb if gb is not None else '?'} -> "
        f"{gn if gn is not None else '?'}"
        + (f" ({drift:+.2f}%)" if drift is not None else ""))
    shown = [r for r in rep["deltas"]
             if r["regressed"] or r["improved"]]
    if shown:
        lines.append(f"{'query':<18} {'base':>8} {'new':>8} {'delta':>8}")
        for r in shown:
            mark = " REGRESSED" if r["regressed"] else ""
            lines.append(f"{r['query']:<18} {r['base']:>8.3f} "
                         f"{r['new']:>8.3f} {r['delta_pct']:>+7.1f}%"
                         f"{mark}")
    else:
        lines.append(f"no per-query deltas beyond the "
                     f"{rep['threshold_pct']:.0f}% noise threshold")
    for key, label in (("only_in_base", "dropped from new"),
                       ("only_in_new", "new queries")):
        if rep[key]:
            lines.append(f"-- {label}: {', '.join(rep[key][:10])}"
                         + (" ..." if len(rep[key]) > 10 else ""))
    if rep["geomean_regressed"]:
        lines.append(f"-- GEOMEAN REGRESSION: drift {drift:+.2f}% "
                     f"exceeds -{rep['geomean_threshold_pct']:.0f}%")
    for d in rep.get("compile_deltas", []):
        mark = " STEADY-STATE RECOMPILE REGRESSION" if d["regressed"] \
            else " (improved)"
        lines.append(f"-- timed_compiles {d['query']}: "
                     f"{d['base']} -> {d['new']}{mark}")
    for d in rep.get("dispatch_deltas", []):
        if d["regressed"]:
            lines.append(f"-- dispatch_share {d['query']}: "
                         f"{d['base']:.2f} -> {d['new']:.2f} "
                         "DISPATCH-SHARE REGRESSION")
    for d in rep.get("warmup_deltas", []):
        mark = " WARM-UP COMPILE REGRESSION" if d["regressed"] \
            else " (improved)"
        lines.append(f"-- warm_compiles {d['query']}: "
                     f"{d['base']} -> {d['new']}{mark}")
    for d in rep.get("first_query_deltas", []):
        if d["regressed"]:
            lines.append(f"-- first-query wall [{d['suite']}]: "
                         f"{d['base']:.2f}s -> {d['new']:.2f}s "
                         f"({d['delta_pct']:+.1f}%) COLD-START "
                         "REGRESSION")
    if rep.get("scan_geomean_base") is not None \
            and rep.get("scan_geomean_new") is not None:
        lines.append(
            f"-- scan-inclusive geomean: {rep['scan_geomean_base']} -> "
            f"{rep['scan_geomean_new']}"
            + (f" ({rep['scan_geomean_drift_pct']:+.2f}%)"
               if rep.get("scan_geomean_drift_pct") is not None else "")
            + (" SCAN-INCLUSIVE REGRESSION"
               if rep.get("scan_geomean_regressed") else ""))
    for d in rep.get("scan_deltas", []):
        if d["regressed"]:
            lines.append(f"-- scan-inclusive {d['query']}: "
                         f"{d['base']:.2f}x -> {d['new']:.2f}x "
                         f"({d['delta_pct']:+.1f}%) SCAN-INCLUSIVE "
                         "REGRESSION")
    for d in rep.get("scan_mode_deltas", []):
        mark = " DECODE-MODE REGRESSION" if d["regressed"] \
            else " (improved)"
        lines.append(f"-- scan decode mode {d['query']}: "
                     f"{d['base']} -> {d['new']}{mark}")
    for d in rep.get("sync_deltas", []):
        if d["regressed"]:
            lines.append(f"-- host_syncs {d['query']}: "
                         f"{d['base']:.0f} -> {d['new']:.0f} "
                         f"({d['growth_pct']:+.1f}%) HOST-SYNC "
                         "REGRESSION")
    for d in rep.get("sync_share_deltas", []):
        if d["regressed"]:
            lines.append(f"-- sync share {d['query']}: "
                         f"{d['base']:.2f} -> {d['new']:.2f} "
                         "HOST-SYNC-SHARE REGRESSION")
    if rep.get("n_below_1x_base") is not None \
            and rep.get("n_below_1x_new") is not None:
        mark = " LOSER-COUNT REGRESSION" if rep.get("losers_regressed") \
            else ""
        lines.append(f"-- n_below_1x: {rep['n_below_1x_base']} -> "
                     f"{rep['n_below_1x_new']}{mark}")
    for d in rep.get("roofline_deltas", []):
        if d["regressed"] or d["base_class"] != d["new_class"]:
            mark = " ROOFLINE-CLASS REGRESSION" if d["regressed"] \
                else " (improved)"
            lines.append(
                f"-- roofline {d['query']}: {d['base_class']} "
                f"({d['base_pct']:.2f}% peak) -> {d['new_class']} "
                f"({d['new_pct']:.2f}% peak){mark}")
    lines.append("RESULT: " + ("REGRESSED" if rep["regressed"] else "ok"))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-query speedup deltas + geomean drift between "
                    "two bench sweeps; exit 1 on regression")
    ap.add_argument("base", help="baseline sweep artifact")
    ap.add_argument("new", help="candidate sweep artifact")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="per-query noise threshold as a fraction "
                         "(default 0.10 = 10%%)")
    ap.add_argument("--geomean-threshold", type=float, default=0.05,
                    help="geomean drift regression bound (default 0.05)")
    ap.add_argument("--ignore-compiles", action="store_true",
                    help="do not gate on steady-state (timed_compiles) "
                         "compile-count increases")
    ap.add_argument("--ignore-dispatch", action="store_true",
                    help="do not gate on per-query dispatch-share "
                         "increases (the device/transfer/dispatch "
                         "breakdown bench.py records)")
    ap.add_argument("--dispatch-threshold", type=float, default=0.10,
                    help="absolute dispatch-share increase that counts "
                         "as a regression (default 0.10 = 10 share "
                         "points)")
    ap.add_argument("--ignore-warmup", action="store_true",
                    help="do not gate on warm-up regressions (per-query "
                         "warm_compiles growth, per-suite cold "
                         "first-query wall)")
    ap.add_argument("--warmup-threshold", type=float, default=0.50,
                    help="relative cold first-query wall increase that "
                         "counts as a regression (default 0.50 = 50%%; "
                         "cold walls carry one-off I/O noise)")
    ap.add_argument("--ignore-stress", action="store_true",
                    help="report stress-tier (BENCH_STRESS.json) deltas "
                         "without gating on them")
    ap.add_argument("--stress-spill-threshold", type=float, default=0.50,
                    help="relative spill-event-count growth between "
                         "stress sweeps that counts as a regression "
                         "(default 0.50 = 50%%)")
    ap.add_argument("--fleet-scaling", type=float, default=0.8,
                    help="required per-worker scaling when gating a "
                         "fleet artifact (BENCH_FLEET.json) against a "
                         "single-process serve baseline: N-worker qps "
                         "must reach this fraction x N x baseline qps "
                         "(default 0.8)")
    ap.add_argument("--fleet-p99-threshold", type=float, default=0.50,
                    help="relative fleet p99 growth over the baseline "
                         "that counts as a regression (default 0.50)")
    ap.add_argument("--ignore-fleet", action="store_true",
                    help="report fleet-tier deltas without gating on "
                         "them")
    ap.add_argument("--scan-threshold", type=float, default=0.10,
                    help="relative scan-INCLUSIVE speedup drop (per "
                         "query and geomean, from the sweep's scan-off "
                         "probes) that counts as a regression (default "
                         "0.10 = 10%%)")
    ap.add_argument("--ignore-scan", action="store_true",
                    help="do not gate on scan-inclusive drift")
    ap.add_argument("--ignore-scan-mode", action="store_true",
                    help="do not gate on scan decode-mode rank drops "
                         "(device -> mixed/host between sweeps)")
    ap.add_argument("--sync-threshold", type=float, default=0.25,
                    help="host-sync growth bound (default 0.25): "
                         "relative for per-iteration sync COUNTS "
                         "(host_syncs), absolute for the sync-blocked "
                         "wall SHARE (sync_s/tpu_s)")
    ap.add_argument("--ignore-syncs", action="store_true",
                    help="do not gate on steady-state host-sync count "
                         "or sync-share growth")
    ap.add_argument("--ignore-losers", action="store_true",
                    help="do not gate on n_below_1x (sub-1x query "
                         "count) growth between sweeps")
    ap.add_argument("--roofline", nargs=2, metavar=("OLD", "NEW"),
                    default=None,
                    help="also gate on two tools/roofline.py artifacts: "
                         "a common query whose dominant kernel's "
                         "HBM-utilization class dropped (gather < low < "
                         "elementwise < high) is a regression")
    ap.add_argument("--ignore-roofline", action="store_true",
                    help="report roofline class moves without gating "
                         "on them")
    ap.add_argument("--json", metavar="OUT", default="",
                    help="also write the machine-shape diff ('-' = "
                         "stdout)")
    args = ap.parse_args(argv)
    try:
        base_doc = _read_doc(args.base)
        new_doc = _read_doc(args.new)
        # stress-tier artifacts (bench.py --stress) gate on out-of-core
        # throughput + spill-count drift
        base_stress = stress_from_doc(base_doc)
        new_stress = stress_from_doc(new_doc)
        if base_stress is not None and new_stress is not None:
            rep = compare_stress(base_stress, new_stress, args.threshold,
                                 args.stress_spill_threshold)
            if args.ignore_stress:
                rep["ignored"] = True
                rep["regressed"] = False
            if args.json == "-":
                print(json.dumps(rep, indent=1))
            else:
                print(render_stress_text(rep))
                if args.json:
                    with open(args.json, "w") as f:
                        json.dump(rep, f, indent=1)
            return 1 if rep["regressed"] else 0
        if (base_stress is None) != (new_stress is None):
            raise ValueError(
                "cannot compare a stress-tier artifact against a sweep "
                "artifact (one side has 'spill_events_total', the other "
                "does not)")
        # fleet-tier artifacts (bench.py --fleet N) dispatch BEFORE the
        # serve pair: a fleet doc also carries qps/latency_s, and its
        # gate is the scaling ratio against a serve baseline, not qps
        # drift
        base_fleet = fleet_from_doc(base_doc)
        new_fleet = fleet_from_doc(new_doc)
        if new_fleet is not None:
            if base_fleet is None:
                base_for_fleet = serve_from_doc(base_doc)
                if base_for_fleet is None:
                    raise ValueError(
                        "a fleet-tier artifact gates against a serve-"
                        "mode baseline (BENCH_SERVE.json) or another "
                        "fleet artifact")
            else:
                base_for_fleet = base_fleet
            rep = compare_fleet(base_for_fleet, new_fleet,
                                args.threshold, args.fleet_scaling,
                                args.fleet_p99_threshold)
            if args.ignore_fleet:
                rep["ignored"] = True
                rep["regressed"] = False
            if args.json == "-":
                print(json.dumps(rep, indent=1))
            else:
                print(render_fleet_text(rep))
                if args.json:
                    with open(args.json, "w") as f:
                        json.dump(rep, f, indent=1)
            return 1 if rep["regressed"] else 0
        if base_fleet is not None:
            raise ValueError(
                "cannot compare a fleet-tier baseline against a "
                "non-fleet candidate artifact")
        # serve-mode artifacts (bench.py --concurrency) gate on
        # throughput instead of per-query speedups
        base_serve = serve_from_doc(base_doc)
        new_serve = serve_from_doc(new_doc)
        if base_serve is not None and new_serve is not None:
            rep = compare_serve(base_serve, new_serve, args.threshold)
            if args.json == "-":
                print(json.dumps(rep, indent=1))
            else:
                print(render_serve_text(rep))
                if args.json:
                    with open(args.json, "w") as f:
                        json.dump(rep, f, indent=1)
            return 1 if rep["regressed"] else 0
        if (base_serve is None) != (new_serve is None):
            raise ValueError(
                "cannot compare a serve-mode artifact against a sweep "
                "artifact (one side has 'qps', the other does not)")
        base, base_geo = sweep_from_doc(base_doc, args.base)
        new, new_geo = sweep_from_doc(new_doc, args.new)
        base_c = {} if args.ignore_compiles \
            else compiles_from_doc(base_doc)
        new_c = {} if args.ignore_compiles \
            else compiles_from_doc(new_doc)
        base_d = {} if args.ignore_dispatch \
            else dispatch_from_doc(base_doc)
        new_d = {} if args.ignore_dispatch \
            else dispatch_from_doc(new_doc)
        base_w = None if args.ignore_warmup \
            else warmup_from_doc(base_doc)
        new_w = None if args.ignore_warmup \
            else warmup_from_doc(new_doc)
        base_s = {} if args.ignore_scan else scan_from_doc(base_doc)
        new_s = {} if args.ignore_scan else scan_from_doc(new_doc)
        base_sm = {} if args.ignore_scan_mode \
            else scan_modes_from_doc(base_doc)
        new_sm = {} if args.ignore_scan_mode \
            else scan_modes_from_doc(new_doc)
        base_sy = {"counts": {}, "shares": {}} if args.ignore_syncs \
            else syncs_from_doc(base_doc)
        new_sy = {"counts": {}, "shares": {}} if args.ignore_syncs \
            else syncs_from_doc(new_doc)
        base_l = losers_from_doc(base_doc, base)
        new_l = losers_from_doc(new_doc, new)
        roof = None
        if args.roofline is not None:
            roof = roofline_deltas(_read_doc(args.roofline[0]),
                                   _read_doc(args.roofline[1]))
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        print(f"perfdiff: {e}", file=sys.stderr)
        return 2
    # BOTH sides must carry data: an empty NEW (crashed/truncated
    # sweep) sailing through with exit 0 is exactly what a gate must
    # reject
    for path, per, geo in ((args.base, base, base_geo),
                           (args.new, new, new_geo)):
        if not per and geo is None:
            print(f"perfdiff: {path}: no speedups found",
                  file=sys.stderr)
            return 2
    rep = compare(base, base_geo, new, new_geo,
                  args.threshold, args.geomean_threshold,
                  base_compiles=base_c, new_compiles=new_c,
                  base_dispatch=base_d, new_dispatch=new_d,
                  dispatch_threshold=args.dispatch_threshold,
                  base_warmup=base_w, new_warmup=new_w,
                  warmup_threshold=args.warmup_threshold,
                  base_scan=base_s, new_scan=new_s,
                  scan_threshold=args.scan_threshold,
                  base_losers=base_l, new_losers=new_l,
                  gate_losers=not args.ignore_losers,
                  base_syncs=base_sy, new_syncs=new_sy,
                  sync_threshold=args.sync_threshold,
                  base_scan_modes=base_sm, new_scan_modes=new_sm)
    if roof is not None:
        rep["roofline_deltas"] = roof
        regressed = any(d["regressed"] for d in roof)
        rep["roofline_regressed"] = regressed and not args.ignore_roofline
        rep["regressed"] = rep["regressed"] or rep["roofline_regressed"]
    if args.json == "-":
        print(json.dumps(rep, indent=1))
    else:
        print(render_text(rep))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rep, f, indent=1)
    return 1 if rep["regressed"] else 0


if __name__ == "__main__":
    sys.exit(main())
