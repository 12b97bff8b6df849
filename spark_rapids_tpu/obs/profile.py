"""Per-query profile report: the executed plan tree annotated with
inclusive/exclusive time, rows, batches, plus the query-scoped deltas of
the process-wide subsystem counters (spill bytes/events, shuffle fetch
retries, kernel-cache hits/misses/compile time).

The reference answers "where did this query's time go" with the Spark UI's
per-operator SQL metrics + NVTX timelines; this report is the headless
equivalent: ``session.profile_report()`` renders it, ``session.
profile_json()`` returns the machine shape for tooling
(tools/trace_summary.py consumes it).

Inclusive/exclusive semantics: operator time is measured around each
batch-pull in ``PhysicalPlan.executed_partitions``, so a parent's time
includes the children it pulls through; exclusive time subtracts the
children's inclusive time (clamped at zero — pipelined operators across
threads can overlap).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional


def _node_profile(node, ctx, op_metrics: Dict[str, Any]) -> Dict[str, Any]:
    st = ctx.node_stats.get(id(node))
    incl = st["time"] if st else 0.0
    children = [_node_profile(c, ctx, op_metrics) for c in node.children]
    excl = max(incl - sum(c["inclusive_s"] for c in children), 0.0)
    out: Dict[str, Any] = {
        "op": node.describe(),
        "inclusive_s": round(incl, 6),
        "exclusive_s": round(excl, 6),
        "rows": st["rows"] if st else 0,
        "batches": st["batches"] if st else 0,
        "children": children,
    }
    members = getattr(node, "member_ops", None)
    if members:
        # fused stage (exec/stagecompiler): the profile row stands for
        # the whole member pipeline — name it
        out["members"] = [m[:200] for m in members]
    bd = _node_breakdown(node, ctx)
    if bd is not None:
        out["breakdown"] = bd
    metrics = op_metrics.get(node.describe())
    if metrics:
        out["metrics"] = dict(metrics)
    return out


def _node_breakdown(node, ctx) -> Optional[Dict[str, float]]:
    """Split one operator's EXCLUSIVE wall time into device compute,
    host<->device transfer and python-dispatch gap, from the components
    the exec hot path records (obs/compileledger.note_breakdown):

      * ``device_s``   — sync_s: time the device spent draining THIS
        operator's queued kernels (profile.syncEachOp mode syncs after
        every batch, and every child synced before yielding, so the
        queue holds only this operator's work);
      * ``transfer_s`` — seconds the transfer sites (scan/exchange
        uploads, collect/exchange fetches) reported against this node;
      * ``dispatch_s`` — the remainder of the exclusive pull time:
        python-side tracing/dispatch/orchestration gap.

    The three sum to the node's exclusive time (clamped at zero), which
    is exactly what distinguishes "kernel is slow" from "we're
    dispatch-bound". None when nothing was recorded for this node
    (profile sync off and no transfers)."""
    bd = getattr(ctx, "node_breakdown", None)
    st = bd.get(id(node)) if bd else None
    if not st:
        return None
    device = st.get("sync_s", 0.0)
    transfer = st.get("transfer_s", 0.0)
    pull = st.get("pull_s")
    if pull is not None:
        # children's pull+sync happened inside this node's pull: remove
        # their inclusive share to get this operator's own python time
        child_s = 0.0
        for c in node.children:
            cst = bd.get(id(c)) or {}
            child_s += cst.get("pull_s", 0.0) + cst.get("sync_s", 0.0)
        dispatch = max(pull - child_s - transfer, 0.0)
    else:
        dispatch = 0.0
    return {"device_s": round(device, 6),
            "transfer_s": round(transfer, 6),
            "dispatch_s": round(dispatch, 6),
            "total_s": round(device + transfer + dispatch, 6)}


def scan_decode_mode(scan: Dict[str, Any]) -> str:
    """Per-query decode-mode verdict from a scan counter delta
    (docs/scan_device.md): ``device`` when every decoded column of every
    split rode the deviceDecode kernels, ``mixed`` when any column (or
    whole split) fell back to the host decode, ``host`` when no split
    took the device path at all (deviceDecode off, or no parquet scan)."""
    def n(key: str) -> int:
        try:
            return int(scan.get(key, 0) or 0)
        except (TypeError, ValueError):
            return 0
    if not n("scan.device.splits"):
        return "host"
    if n("scan.device.fallbackColumns") or n("scan.device.hostReads"):
        return "mixed"
    return "device"


def build_profile(plan, ctx, global_delta: Optional[Dict[str, Any]] = None,
                  wall_s: Optional[float] = None,
                  obs_before: Optional[tuple] = None) -> "ProfileReport":
    """Assemble the report from the executed plan + its ExecContext.
    ``global_delta`` is the per-query diff of the process-wide registry
    (obs.metrics.registry_delta) carrying spill/fetch/compile activity;
    ``obs_before`` is the query-start snapshot of (tracer dropped,
    event-log dropped, event-log rotations, event-log rotate failures,
    compile-ledger seq) so truncation reports as a per-query delta like
    everything else — and the ``compiles`` section covers exactly this
    query's ledger entries."""
    op_metrics = ctx.op_metrics()
    tree = _node_profile(plan, ctx, op_metrics)
    summary: Dict[str, Any] = {}
    delta = dict(global_delta or {})

    def take(prefix: str) -> Dict[str, Any]:
        got = {k: v for k, v in delta.items() if k.startswith(prefix)}
        for k in got:
            del delta[k]
        return got

    summary["spill"] = take("spill.")
    # shuffle-skew section BEFORE the generic shuffle take so the skew
    # counters land in their own section (obs/shuffleobs.py); the ratio
    # gauges are state, not flow — appended only when this query actually
    # materialized a measured shuffle (the counter delta says so)
    summary["shuffleSkew"] = take("shuffle.skew.")
    summary["adaptive"] = take("aqe.")
    summary["shuffle"] = take("shuffle.")
    summary["kernelCache"] = take("kernelCache.")
    summary["scan"] = take("scan.")
    summary["pageCache"] = take("pagecache.")
    summary["compileCache"] = take("compileCache.")
    if summary["shuffleSkew"]:
        from spark_rapids_tpu.obs.metrics import REGISTRY
        for m in REGISTRY.metrics():
            if m.kind == "gauge" and m.name.startswith("shuffle.skew."):
                summary["shuffleSkew"].setdefault(m.name, m.value)
    if summary["scan"]:
        summary["scan"]["scan.decode.mode"] = scan_decode_mode(
            summary["scan"])
    if summary["pageCache"]:
        from spark_rapids_tpu.obs.metrics import REGISTRY
        for m in REGISTRY.metrics():
            if m.kind == "gauge" and m.name.startswith("pagecache."):
                summary["pageCache"].setdefault(m.name, m.value)
    if delta:
        summary["other"] = delta
    mem = op_metrics.get("memory")
    if mem:
        summary["memory"] = dict(mem)
    # silent-truncation visibility: tracer events dropped at the buffer
    # cap, event-journal write failures and file rotations (obs/events.py)
    # during THIS query — a profile that says "no spills" must not be
    # hiding a clipped record
    from spark_rapids_tpu.obs.events import EVENTS
    from spark_rapids_tpu.obs.trace import TRACER
    t0, e0, r0, f0, ledger0, sync0 = (tuple(obs_before) + (0,) * 6)[:6] \
        if obs_before else (0, 0, 0, 0, 0, 0)
    # compile attribution (obs/compileledger.py): this query's ledger
    # entries summarized by (operator, kernel) cause — who compiled,
    # which shapes, how many seconds of the wall went to the compiler
    from spark_rapids_tpu.obs.compileledger import LEDGER, analyze
    ledger_entries = LEDGER.entries(since_seq=ledger0)
    if ledger_entries:
        rep = analyze(ledger_entries, top_n=8)
        summary["compiles"] = {
            "count": rep["total_compiles"],
            "seconds": rep["total_seconds"],
            "attributedPct": rep["attributed_pct"],
            "causes": [
                {"op": g["op"], "kernel": (g["kernel"] or "")[:120],
                 "compiles": g["compiles"], "seconds": g["seconds"],
                 "signatures": g["signatures"]}
                for g in rep["groups"]],
        }
    # host-sync attribution (obs/syncledger.py): this query's blocking
    # device<->host points rolled up by site, plus the device-occupancy
    # estimate — the idle-gap share ROADMAP item 4 gates on
    from spark_rapids_tpu.obs.syncledger import (
        SYNC_LEDGER, occupancy_pct, rollup,
    )
    sync_entries = SYNC_LEDGER.entries(since_seq=sync0)
    if sync_entries:
        roll = rollup(sync_entries)
        summary["syncs"] = {
            "count": roll["count"],
            "seconds": roll["seconds"],
            "bytes": roll["bytes"],
            "occupancyPct": occupancy_pct(roll["seconds"], wall_s),
            "bySite": roll["bySite"][:8],
        }
        # per-node sync rows: entries attribute by the triggering
        # operator's describe() string — annotate matching plan rows
        by_op: Dict[str, List[float]] = {}
        for e in sync_entries:
            if e.get("op"):
                acc = by_op.setdefault(e["op"], [0, 0.0])
                acc[0] += 1
                acc[1] += float(e.get("seconds", 0.0) or 0.0)

        def annotate(node: Dict[str, Any]) -> None:
            got = by_op.get(node["op"])
            if got:
                node["syncs"] = got[0]
                node["sync_s"] = round(got[1], 6)
            for c in node["children"]:
                annotate(c)
        annotate(tree)
    obs = {}
    if TRACER.dropped - t0 > 0:
        obs["trace.droppedEvents"] = TRACER.dropped - t0
    if EVENTS.dropped - e0 > 0:
        obs["eventLog.droppedEvents"] = EVENTS.dropped - e0
    if EVENTS.rotations - r0 > 0:
        obs["eventLog.rotations"] = EVENTS.rotations - r0
    if EVENTS.rotate_failures - f0 > 0:
        obs["eventLog.rotateFailures"] = EVENTS.rotate_failures - f0
    if obs:
        summary["observability"] = obs
    return ProfileReport(tree, summary, wall_s=wall_s)


class ProfileReport:
    def __init__(self, tree: Dict[str, Any], summary: Dict[str, Any],
                 wall_s: Optional[float] = None):
        self.tree = tree
        self.summary = summary
        self.wall_s = wall_s

    # -- machine shape ------------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        doc: Dict[str, Any] = {"version": 1, "plan": self.tree,
                               "summary": self.summary}
        if self.wall_s is not None:
            doc["wall_s"] = round(self.wall_s, 6)
        return doc

    def save(self, path: str) -> None:
        import os
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)

    # -- human shape --------------------------------------------------------
    def render(self) -> str:
        lines: List[str] = []
        if self.wall_s is not None:
            lines.append(f"query wall: {self.wall_s:.3f}s")

        def rec(node: Dict[str, Any], indent: int) -> None:
            line = ("  " * indent
                    + f"{node['op']}  "
                    + f"[incl {node['inclusive_s']:.3f}s "
                    + f"excl {node['exclusive_s']:.3f}s "
                    + f"rows {node['rows']} batches {node['batches']}]")
            bd = node.get("breakdown")
            if bd:
                line += (f" [device {bd['device_s']:.3f}s "
                         f"transfer {bd['transfer_s']:.3f}s "
                         f"dispatch {bd['dispatch_s']:.3f}s]")
            if node.get("syncs"):
                line += (f" [syncs {node['syncs']} "
                         f"{node.get('sync_s', 0.0):.3f}s]")
            lines.append(line)
            for c in node["children"]:
                rec(c, indent + 1)
        rec(self.tree, 0)
        for section, vals in self.summary.items():
            if not vals:
                continue
            lines.append(f"-- {section}")
            for k, v in sorted(vals.items()):
                if isinstance(v, list):
                    # ranked sub-records (the compiles section's causes)
                    lines.append(f"   {k}:")
                    for item in v:
                        if isinstance(item, dict):
                            body = " ".join(f"{ik}={iv}" for ik, iv
                                            in item.items())
                            lines.append(f"     - {body}")
                        else:
                            lines.append(f"     - {item}")
                    continue
                if isinstance(v, float):
                    v = round(v, 6)
                lines.append(f"   {k}: {v}")
        return "\n".join(lines)
