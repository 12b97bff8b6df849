"""Fused count-distinct execution.

The DataFrame layer (like Spark's RewriteDistinctAggregates) expands
``group_by(G2).agg(count(distinct K))`` — and the hand-written
distinct().group_by().count() spelling — into a two-level aggregation:

    Agg(final G2, count) / Exch / Agg(partial G2, count)
      / Agg(final G1) / Exch / Agg(partial G1) / child      G1 = G2 + K

The reference executes that chain as two full cuDF hash aggregations
(aggregate.scala:40-225 keeps the expansion; each level is a real pass).
On this backend every aggregation pass pays a sort + segment sweep. This
pass recognizes the chain on the FINAL physical plan and replaces it
with one operator running a single sorted pass over the G1 key tuple
(ops/aggregate.count_distinct_reduce): distinct-tuple boundaries and
G2-group boundaries come from the same sorted images. On one v5e chip
(my chip runs, PR 35; PERF.md section 6), TPC-H q16 at SF30, 2.7M rows
in one 2^22-slot batch: the chain's four aggregates 0.83 s of device
time an execution and 513 s of a cold run's compiles, the one operator
0.023 s and 76 s; at SF100 over 2^24 slots the operator as it stood
before PR 35 (seven chained sort passes and eighteen gathers) took
4.54 s where the chain took 5.71 s, and the operator as it is 0.136 s
(101 s of compile).

Both spellings reach the one operator and kernel. The level-2 function
says which count it is: count(*) (the hand-written chain) counts every
distinct tuple, NULL a value like another; count(K) over the distinct
key K (what ``F.count_distinct`` expands to) is SQL's count(DISTINCT K),
where a tuple whose K is NULL is not counted and a group of such tuples
alone reads 0 and stays. Which it is is static in the traced program.

Gated to: single-chip (no mesh — the chain's exchanges carry real
distribution on a mesh), bare-column keys, a lone count(*) (count(lit 1))
or count(K) of the one key that G1 adds to G2, and results that are plain
key references or the count.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes
from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import ExecContext, Partition, PhysicalPlan
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.obs.trace import TRACER
from spark_rapids_tpu.utils.kernelcache import cached_jit

# counted as the one program is dispatched, by what the host knows without
# a sync: the row count where it has been fetched, else the capacity, so
# under a join or a projection they read slots dispatched, not live rows
_INPUT_ROWS = REGISTRY.counter("agg.distinct.inputRows")
_INPUT_BYTES = REGISTRY.counter("agg.distinct.inputBytes")
_BATCHES = REGISTRY.counter("agg.distinct.batches")

# the spelling a fused chain came from, by its level-2 function
FORMS = {False: "distinct_count", True: "count_distinct"}


class TpuCountDistinctExec(PhysicalPlan):
    """One-pass grouped distinct count (see module docstring).

    ``out_plan``: for each output column, ("key", child_col_idx) or
    ("count", None), in output-schema order. ``skip_null``: the count is
    count(DISTINCT rest key), not count(*) over the distinct tuples."""

    columnar_output = True
    # the groups come out in the input's capacity
    padded_output = True

    def __init__(self, child: PhysicalPlan, out_schema: Schema,
                 out_plan: List[Tuple[str, Optional[int]]],
                 g2_idx: List[int], rest_idx: List[int],
                 skip_null: bool = False):
        super().__init__([child])
        self._schema = out_schema
        self.out_plan = list(out_plan)
        self.g2_idx = list(g2_idx)
        self.rest_idx = list(rest_idx)
        self.skip_null = skip_null
        sig = (f"cdist|{tuple(g2_idx)}|{tuple(rest_idx)}"
               f"|{tuple(out_plan)}|{out_schema!r}|n{int(skip_null)}")
        self._sig = sig

        def kernel(batch: DeviceBatch) -> DeviceBatch:
            from spark_rapids_tpu.ops.aggregate import count_distinct_reduce
            keys, counts, n_groups = count_distinct_reduce(
                batch, self.g2_idx, self.rest_idx, skip_null=skip_null)
            live = jnp.arange(batch.capacity, dtype=jnp.int32) < n_groups
            cols = [keys[ci] if kind == "key"
                    else DeviceColumn(dtypes.INT64, counts, live)
                    for kind, ci in self.out_plan]
            return DeviceBatch(self._schema, cols, n_groups)
        self._kernel = cached_jit(
            sig, lambda: jax.jit(kernel),
            lambda b: {"capacity": b.capacity, "form": FORMS[skip_null]})

    def output_schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return (f"TpuCountDistinctExec(g2={self.g2_idx}, "
                f"distinct={self.rest_idx}, {FORMS[self.skip_null]})")

    def fingerprint_extra(self) -> str:
        return self._sig

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        growth = ctx.conf.capacity_growth

        def run():
            from spark_rapids_tpu.exec.tpu import _concat_device, _row_bytes
            batches = [b for p in child_parts for b in p()]
            if not batches:
                yield DeviceBatch.empty(self._schema)
                return
            child_schema = self.children[0].output_schema()
            nbytes = sum(b.device_memory_size() for b in batches)
            # coarse materialization: the fused pass's kernel signature
            # rides the merged capacity — the shape-bucket ladder keeps
            # it stable across input sizes (compile.shapeBuckets)
            with TRACER.span("agg.distinct.collapse", batches=len(batches),
                             bytes=nbytes) as sp:
                merged = _concat_device(batches, child_schema, growth,
                                        coarse=True)
                if sp is not None:
                    sp.set(capacity=merged.capacity)
            rows = sum(b.num_rows_hint() for b in batches)
            _BATCHES.add(len(batches))
            _INPUT_ROWS.add(rows)
            _INPUT_BYTES.add(rows * _row_bytes(child_schema))
            yield self._kernel(merged)
        return [run]


def _strip_alias(e):
    from spark_rapids_tpu.sql.exprs.core import Alias
    while isinstance(e, Alias):
        e = e.children[0]
    return e


def _counted(e, g1_names: List[str]) -> Optional[str]:
    """What a level-2 count counts: ``"*"`` for count(*) (count(lit 1)),
    the G1 key's name for count(K) over a reference to the inner
    aggregate's output (the physical plan's are bound), None for anything
    else."""
    from spark_rapids_tpu.sql.exprs.aggregates import Count
    from spark_rapids_tpu.sql.exprs.core import BoundRef, Literal
    e = _strip_alias(e)
    if not isinstance(e, Count):
        return None
    arg = _strip_alias(e.children[0])
    if isinstance(arg, Literal):
        return "*"
    if isinstance(arg, BoundRef) and 0 <= arg.index < len(g1_names):
        return g1_names[arg.index]
    return None


def _skip_coalesce(node: PhysicalPlan) -> PhysicalPlan:
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    while isinstance(node, TpuCoalesceBatchesExec):
        node = node.children[0]
    return node


def _match_chain(node: PhysicalPlan):
    """Match AggF(G2,count)/Exch/AggP(G2)/AggF(G1)/Exch/AggP(G1)/child
    (TpuCoalesceBatchesExec freely interleaved). Returns the replacement
    exec or None."""
    from spark_rapids_tpu.exec.tpu import (
        TpuHashAggregateExec, TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.sql.exprs.core import BoundRef, Col

    def agg(n, mode):
        n = _skip_coalesce(n)
        return n if (isinstance(n, TpuHashAggregateExec)
                     and n.mode == mode) else None

    def exch(n):
        n = _skip_coalesce(n)
        return n if isinstance(n, TpuShuffleExchangeExec) else None

    fo = agg(node, "final")
    if fo is None or fo.pre_mask is not None:
        return None
    po = fo
    ex_o = exch(fo.children[0])
    if ex_o is None:
        return None
    po = agg(ex_o.children[0], "partial")
    if po is None or po.plan is not fo.plan or po.pre_mask is not None:
        return None
    fi = agg(po.children[0], "final")
    if fi is None or fi.pre_mask is not None:
        return None
    ex_i = exch(fi.children[0])
    if ex_i is None:
        return None
    pi = agg(ex_i.children[0], "partial")
    if pi is None or pi.plan is not fi.plan or pi.pre_mask is not None:
        return None
    child = _skip_coalesce(pi.children[0])

    plan_o, plan_i = fo.plan, fi.plan
    # inner must be a pure distinct: no aggregate functions, results are
    # exactly the grouping columns
    if plan_i.agg_fns:
        return None
    g1_names = [n for n, _ in plan_i.grouping]
    if [n for n, _ in plan_i.results] != g1_names:
        return None
    # outer: one count and all other results bare G2 key references. The
    # count is count(*), or count(K) of the one key G1 adds to G2: a count
    # of any other column is another question than how many distinct
    # tuples a group holds
    if len(plan_o.agg_fns) != 1:
        return None
    counted = _counted(plan_o.agg_fns[0], g1_names)
    g2_names = [n for n, _ in plan_o.grouping]
    rest_names = [n for n in g1_names if n not in set(g2_names)]
    if counted is None or (counted != "*" and rest_names != [counted]):
        return None
    # an empty outer grouping (global count-distinct) must NOT fuse: the
    # unfused final aggregate runs force_single_group and returns one
    # row (count 0) on empty input, while the fused kernel would return
    # zero rows — a silent result-shape divergence (ADVICE r4 #1)
    if not g2_names:
        return None
    if not set(g2_names) <= set(g1_names):
        return None
    # outer grouping exprs must be bare references to the SAME-named
    # inner G1 output — a computed expr aliased to an inner output name
    # (e.g. (col('size')+1).alias('size')) would pass the name-subset
    # check and silently group on the raw child column (ADVICE r4 #2)
    for n, e in plan_o.grouping:
        e = _strip_alias(e)
        if isinstance(e, BoundRef):
            if not (0 <= e.index < len(g1_names)
                    and g1_names[e.index] == n):
                return None
        elif isinstance(e, Col):
            if e.name != n or n not in g1_names:
                return None
        else:
            return None
    # inner grouping exprs must be bare columns of the real child
    child_schema = child.output_schema()
    g1_child_idx = {}
    for n, e in plan_i.grouping:
        e = _strip_alias(e)
        if isinstance(e, BoundRef):
            g1_child_idx[n] = e.index
        elif isinstance(e, Col) and e.name in child_schema.names:
            g1_child_idx[n] = child_schema.index_of(e.name)
        else:
            return None
    # outer results: bare key references or the count
    out_plan: List[Tuple[str, Optional[int]]] = []
    for name, e in plan_o.results:
        e = _strip_alias(e)
        if e is plan_o.agg_fns[0]:
            out_plan.append(("count", None))
            continue
        if isinstance(e, Col) and e.name in g2_names:
            out_plan.append(("key", g1_child_idx[e.name]))
            continue
        if isinstance(e, BoundRef) and e.name in g2_names:
            out_plan.append(("key", g1_child_idx[e.name]))
            continue
        return None
    if sum(1 for k, _ in out_plan if k == "count") != 1:
        return None
    g2_idx = [g1_child_idx[n] for n in g2_names]
    rest_idx = [g1_child_idx[n] for n in rest_names]
    return TpuCountDistinctExec(child, plan_o.output_schema, out_plan,
                                g2_idx, rest_idx, skip_null=counted != "*")


def fuse_count_distinct(plan: PhysicalPlan) -> PhysicalPlan:
    """Bottom-up rewrite replacing every matched chain."""
    plan.children = [fuse_count_distinct(c) for c in plan.children]
    replaced = _match_chain(plan)
    if replaced is None:
        return plan
    REGISTRY.counter("agg.distinct.plans",
                     form=FORMS[replaced.skip_null]).add(1)
    return replaced
