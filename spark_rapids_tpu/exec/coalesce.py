"""Batch coalescing (reference: GpuCoalesceBatches + CoalesceGoal,
GpuCoalesceBatches.scala:38-165, inserted by
GpuTransitionOverrides.scala:64-147).

Fragmenting producers (scans with many small row groups, filters, joins)
emit batches far below the target size; every downstream operator then pays
one kernel dispatch per fragment, and each distinct capacity bucket compiles
its own XLA program. ``TpuCoalesceBatchesExec`` accumulates child batches to
the ``spark.rapids.sql.batchSizeRows`` target (or everything, for
``RequireSingleBatch``) and concatenates them in one fused device kernel.
"""

from __future__ import annotations

from typing import Iterator, List

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.exec.base import ExecContext, Partition, PhysicalPlan


class CoalesceGoal:
    """Target for coalescing (reference: CoalesceGoal/TargetSize/
    RequireSingleBatch, GpuCoalesceBatches.scala)."""


class TargetSize(CoalesceGoal):
    def __init__(self, rows: int):
        self.rows = rows

    def __repr__(self) -> str:
        return f"TargetSize({self.rows})"


class RequireSingleBatch(CoalesceGoal):
    def __repr__(self) -> str:
        return "RequireSingleBatch"


def coalesce_iter(batches, goal: CoalesceGoal, schema: Schema,
                  growth: float, coarse: bool = False
                  ) -> Iterator[DeviceBatch]:
    """Accumulate a batch stream to ``goal`` and concatenate — the one
    coalescing loop, shared by TpuCoalesceBatchesExec and the fused
    stage's input re-batching (exec/stagecompiler/fusedexec.py).

    Capacity-based accounting: an exact count would cost a blocking
    device->host scalar sync per batch;
    the bucketed capacity over-estimates by at most 2x, which only makes
    coalesced outputs slightly smaller than the goal.

    ``coarse``: pad the concatenated capacity up the shape-bucket ladder
    (spark.rapids.tpu.compile.shapeBuckets; identity when off) — the
    fused-stage re-batching uses it so small tail fragments land on the
    same compiled capacity as each other instead of one program per
    tail size."""
    from spark_rapids_tpu.exec.tpu import _concat_device
    single = isinstance(goal, RequireSingleBatch)
    target = 0 if single else goal.rows
    pending: List[DeviceBatch] = []
    pending_rows = 0
    for batch in batches:
        rows = batch.num_rows_hint()
        if rows == 0 and pending:
            continue  # drop known-empty fragments
        pending.append(batch)
        pending_rows += rows
        if not single and pending_rows >= target:
            yield _concat_device(pending, schema, growth, coarse=coarse)
            pending, pending_rows = [], 0
    if pending:
        yield _concat_device(pending, schema, growth, coarse=coarse)


class TpuCoalesceBatchesExec(PhysicalPlan):
    columnar_output = True

    def __init__(self, child: PhysicalPlan, goal: CoalesceGoal):
        super().__init__([child])
        self.goal = goal

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def describe(self) -> str:
        return f"TpuCoalesceBatchesExec({self.goal!r})"

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        schema = self.output_schema()
        growth = ctx.conf.capacity_growth

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                yield from coalesce_iter(part(), self.goal, schema,
                                         growth)
            return run
        return [make(p) for p in child_parts]


# producers whose output batches can be much smaller than the target
# (the reference's insertCoalesce walks goals the same way)
def is_fragmenting(plan: PhysicalPlan) -> bool:
    from spark_rapids_tpu.exec import tpu, tpujoin
    return isinstance(plan, (tpu.TpuScanExec, tpu.TpuFilterExec,
                             tpujoin.TpuShuffledHashJoinExec,
                             tpujoin.TpuBroadcastNestedLoopJoinExec,
                             tpu.TpuExpandExec))


def _reads_input_file(plan: PhysicalPlan) -> bool:
    """Does this operator evaluate input_file_name()? Coalescing would drain
    the scan past the file boundary before evaluation, so such consumers
    must see uncoalesced batches (the reference disables coalesce the same
    way, GpuTransitionOverrides.scala:110-123)."""
    from spark_rapids_tpu.sql.exprs.core import walk
    from spark_rapids_tpu.sql.exprs.nondet import InputFileName
    exprs = []
    if hasattr(plan, "exprs"):
        exprs.extend(e for _, e in plan.exprs)
    if getattr(plan, "condition", None) is not None:
        exprs.append(plan.condition)
    return any(isinstance(n, InputFileName) for e in exprs for n in walk(e))


def insert_coalesce(plan: PhysicalPlan, conf) -> PhysicalPlan:
    """Insert TpuCoalesceBatchesExec above fragmenting producers feeding
    TPU consumers (GpuTransitionOverrides.scala:64-147). Disabled for the
    whole query when any operator evaluates input_file_name(): coalescing
    drains a scan past its file boundary before any ancestor evaluates,
    so even a distant consumer would read a cleared/stale path."""
    if any(_reads_input_file(node) for node in plan.walk()):
        return plan
    return _insert(plan, conf)


def _insert(plan: PhysicalPlan, conf) -> PhysicalPlan:
    new_children = []
    for c in plan.children:
        c2 = _insert(c, conf)
        if (getattr(plan, "columnar_output", False)
                and not isinstance(plan, TpuCoalesceBatchesExec)
                and is_fragmenting(c2)):
            c2 = TpuCoalesceBatchesExec(c2, TargetSize(conf.batch_size_rows))
        new_children.append(c2)
    out = plan.map_children(lambda x: x)
    out.children = new_children
    return out
