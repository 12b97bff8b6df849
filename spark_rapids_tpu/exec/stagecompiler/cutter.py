"""Pipeline cutting for whole-stage fusion.

Walks the CONVERTED physical plan (post TpuOverrides + transitions +
coalesce insertion) and replaces each maximal chain of fusible unary
operators with one ``TpuFusedStageExec``. Everything that is not a
deterministic Project/Filter/Coalesce is a stage boundary — exchanges
(AQE cuts its query stages at the same edges, sql/adaptive/executor
``_is_stage_boundary``; this is the non-AQE twin over the converted
tree), scans, joins, aggregates, host<->device transitions and CPU
fallback operators all end a pipeline.

Two deliberate exclusions keep fusion-ON from regressing existing
fusions:

  * a (Coalesce +) Filter directly below a shuffle/broadcast exchange is
    left out of the chain whenever ``spark.rapids.sql.exchange
    .fuseFilter`` is on — the exchange's collapse claims exactly that
    filter and runs it a batch at a time under its drain, by the
    filter's own sorting kernel or as a keep mask for the concat
    (exec/tpu._fused_filter_source), which beats running the compaction
    inside a fused program;
  * chains with fewer than ``spark.rapids.sql.fusion.minOperators``
    compute members do not fuse (fusing one operator only renames its
    dispatch).

Input donation (``fusion.donateInputs``) engages only when the stage
input comes from a known single-consumer producer: exchange reads, join
and aggregate outputs, and coalesce concats mint fresh buffers per
consumer, while scan-cache batches, broadcast tables and reused
subtrees are shared across consumers/queries and must never be donated.
"""

from __future__ import annotations

from typing import List, Optional

from spark_rapids_tpu.exec.base import PhysicalPlan

FUSION_ENABLED_KEY = "spark.rapids.sql.fusion.stageEnabled"
FUSION_MIN_OPS_KEY = "spark.rapids.sql.fusion.minOperators"
FUSION_DONATE_KEY = "spark.rapids.sql.fusion.donateInputs"


def _is_fusible(node: PhysicalPlan) -> bool:
    from spark_rapids_tpu.exec import tpu as tpuexec
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    if isinstance(node, TpuCoalesceBatchesExec):
        return True
    if isinstance(node, (tpuexec.TpuProjectExec, tpuexec.TpuFilterExec)):
        return not node._impure
    return False


def _is_compute(node: PhysicalPlan) -> bool:
    """Does this member do real device work? Coalesces are re-batching
    and pure-selection projects are ZERO-COPY column views unfused
    (exec/tpu.TpuProjectExec: 'a jitted identity kernel would copy every
    buffer') — neither counts toward minOperators, so a chain of views
    alone never fuses into a program that would copy what the views
    merely re-arranged. They still ride along inside a chain with real
    compute, where they are free."""
    from spark_rapids_tpu.exec import tpu as tpuexec
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    if isinstance(node, TpuCoalesceBatchesExec):
        return False
    if isinstance(node, tpuexec.TpuProjectExec) and node._pure_selection:
        return False
    return True


def _parent_claims_filter(parent: Optional[PhysicalPlan],
                          top: PhysicalPlan, conf) -> bool:
    """Does the consumer claim a directly-below Filter for its own collapse
    (exec/tpu._fused_filter_source)? Broadcast materializations always
    do; shuffle exchanges only on the single/collapse path — hash/range
    kinds with local collapse on, no accelerated shuffle manager, and no
    padded (aggregate) producer below. A mesh also disables the collapse
    but is session state the cutter cannot see, so mesh sessions keep
    the conservative skip (the filter stays a standalone dispatch there,
    exactly as before fusion)."""
    from spark_rapids_tpu.exec.tpu import TpuShuffleExchangeExec
    from spark_rapids_tpu.exec.tpujoin import TpuBroadcastExchangeExec
    if not conf.get_bool("spark.rapids.sql.exchange.fuseFilter", True):
        return False
    if isinstance(parent, TpuBroadcastExchangeExec):
        return True
    if not isinstance(parent, TpuShuffleExchangeExec):
        return False
    # an aggregate/limit producer keeps the shrinking exchange path,
    # which never claims the filter — for the single kind too
    # (exec/tpu.py checks _padded_producer before _fused_filter_source
    # on both)
    if TpuShuffleExchangeExec._padded_producer(top):
        return False
    kind = parent.partitioning[0]
    if kind == "single":
        return True
    if kind not in ("hash", "range"):
        return False  # roundrobin never collapses
    if conf.get_bool("spark.rapids.shuffle.transport.enabled", False):
        return False  # manager path partitions for real
    return conf.get_bool("spark.rapids.sql.shuffle.localCollapse", True)


def _fresh_producer(node: PhysicalPlan) -> bool:
    """Does this producer mint fresh device buffers per consumer pull —
    safe to donate into the fused program? Conservative allow-list;
    scans (device scan cache) and broadcasts (shared table) are exactly
    what it excludes. A coalesce can never be the stage input (it is
    fusible, so the chain walk absorbs it)."""
    from spark_rapids_tpu.exec import tpu as tpuexec
    from spark_rapids_tpu.exec.tpujoin import TpuShuffledHashJoinExec
    return isinstance(node, (tpuexec.TpuShuffleExchangeExec,
                             tpuexec.TpuHashAggregateExec,
                             TpuShuffledHashJoinExec))


def _try_fuse(top: PhysicalPlan, parent: Optional[PhysicalPlan],
              conf, min_ops: int, donate_conf: bool) -> PhysicalPlan:
    """Fuse the maximal fusible chain starting at ``top`` (downward),
    returning the rewritten node (or ``top`` untouched)."""
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.stagecompiler.fusedexec import (
        TpuFusedStageExec,
    )
    from spark_rapids_tpu.exec.tpu import TpuFilterExec
    chain: List[PhysicalPlan] = []
    cur = top
    while _is_fusible(cur) and len(cur.children) == 1:
        chain.append(cur)
        cur = cur.children[0]
    if not chain:
        return top
    # leading coalesces stay OUTSIDE the stage: a coalesce at the chain
    # top re-batches what the CONSUMER sees (insert_coalesce put it
    # there for the consumer's dispatch count), and absorbing it as
    # identity would hand the consumer one low-occupancy fragment per
    # input batch — the interior/bottom absorption rules don't apply
    skip = 0
    while skip < len(chain) and isinstance(chain[skip],
                                           TpuCoalesceBatchesExec):
        skip += 1
    # ...and the exchange-claimed filter below them stays out too.
    # _fused_filter_source looks through exactly ONE coalesce
    # (exec/tpu.py), so a filter under two stacked coalesces is NOT
    # claimed and stays eligible for fusion
    if (_parent_claims_filter(parent, top, conf) and skip <= 1
            and skip < len(chain)
            and isinstance(chain[skip], TpuFilterExec)):
        skip += 1
    fused_nodes = chain[skip:]
    if sum(1 for m in fused_nodes if _is_compute(m)) < min_ops:
        return top
    child = fused_nodes[-1].children[0]
    donate = donate_conf and _fresh_producer(child)
    fused = TpuFusedStageExec(child, list(reversed(fused_nodes)),
                              donate=donate)
    # rebuild the unfused prefix (shallow copies) above the fused stage
    out: PhysicalPlan = fused
    for node in reversed(chain[:skip]):
        node = node.map_children(lambda c: c)
        node.children = [out]
        out = node
    return out


def compile_stages(plan: PhysicalPlan, conf) -> PhysicalPlan:
    """Entry point (sql/overrides.TransitionOverrides wires it in, so
    the legacy, AQE per-stage and plan-cache paths all fuse). Returns
    the plan UNTOUCHED (same object) when the conf is off — the
    byte-identical rollback contract."""
    if not conf.get_bool(FUSION_ENABLED_KEY, False):
        return plan
    min_ops = max(1, conf.get_int(FUSION_MIN_OPS_KEY, 2))
    # donation is decided BEFORE reuse dedup runs (reuse_common_subtrees
    # rewrites the tree after this pass and would replay the SAME batch
    # objects to every consumer of a shared subtree — donating those
    # would hand later consumers deleted buffers), so it only engages
    # when subtree reuse is off; _fresh_producer cannot see a rewrite
    # that has not happened yet
    donate_conf = (conf.get_bool(FUSION_DONATE_KEY, False)
                   and not conf.get_bool(
                       "spark.rapids.sql.reuseSubtrees.enabled", True))

    def rec(node: PhysicalPlan) -> PhysicalPlan:
        new_children = []
        for c in node.children:
            c2 = rec(c)
            if not _is_fusible(node):
                # chains cut only at their maximal top: a fusible parent
                # extends the chain upward and cuts at ITS consumer
                c2 = _try_fuse(c2, node, conf, min_ops, donate_conf)
            new_children.append(c2)
        out = node.map_children(lambda c: c)
        out.children = new_children
        return out

    root = rec(plan)
    return _try_fuse(root, None, conf, min_ops, donate_conf)
