"""The plan-rewrite engine: tag, convert, insert transitions.

This is the heart of the framework, the re-design of the reference's
GpuOverrides + RapidsMeta + GpuTransitionOverrides
(GpuOverrides.scala:1704-1761, RapidsMeta.scala:64-284,
GpuTransitionOverrides.scala:34-289):

  1. every CPU physical operator is wrapped in an ``ExecMeta``;
  2. ``tag()`` walks children-first, accumulating human-readable
     ``will_not_work`` reasons (per-op conf keys, expression support,
     dtype gates — the same checks RapidsMeta.tagForGpu performs);
  3. ``convert()`` replaces cleanly-tagged nodes with Tpu*Exec equivalents,
     leaving tagged-off subtrees on the CPU;
  4. ``TransitionOverrides`` inserts HostToDevice / DeviceToHost at every
     boundary;
  5. ``explain_text()`` renders the tag tree — the reference's hallmark
     "explain why not" feature (spark.rapids.sql.explain).

Per-operator enable keys are auto-generated ``spark.rapids.sql.exec.<Name>``
exactly like GpuOverrides.scala:122-130.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

from spark_rapids_tpu.config.conf import TpuConf
from spark_rapids_tpu.exec import cpu, tpu
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.exec.transitions import DeviceToHostExec, HostToDeviceExec
from spark_rapids_tpu.sql.exprs.core import Expression, first_unsupported
from spark_rapids_tpu.sql.sources import (
    CsvSource, InMemorySource, OrcSource, ParquetSource,
)


class ExecRule:
    """(CPU exec class) -> conversion recipe + doc + conf key
    (reference: ReplacementRule/ExecRule, GpuOverrides.scala:62-266)."""

    def __init__(self, cpu_class: Type[PhysicalPlan], desc: str,
                 tag_fn: Callable[["ExecMeta"], None],
                 convert_fn: Callable[["ExecMeta", List[PhysicalPlan]],
                                      PhysicalPlan],
                 incompat: Optional[str] = None,
                 disabled_by_default: bool = False):
        self.cpu_class = cpu_class
        self.desc = desc
        self.tag_fn = tag_fn
        self.convert_fn = convert_fn
        self.incompat = incompat
        self.disabled_by_default = disabled_by_default

    @property
    def conf_key(self) -> str:
        name = self.cpu_class.__name__.removeprefix("Cpu")
        return f"spark.rapids.sql.exec.{name}"


class ExprMeta:
    """Per-expression meta tree built during tagging — the explain output
    names the exact offending expression NODE, not just the operator
    (reference: BaseExprMeta and the expression meta tree,
    RapidsMeta.scala:566-726)."""

    def __init__(self, expr: Expression, schema):
        from spark_rapids_tpu.sql.exprs.core import (
            Expression as ExprBase,
        )
        self.expr = expr
        reason = expr.device_supported(schema)
        if reason is None and type(expr).eval_device is ExprBase.eval_device:
            reason = "has no TPU implementation"
        self.reason = reason
        self.children = [ExprMeta(c, schema) for c in expr.children]

    @property
    def subtree_ok(self) -> bool:
        return self.reason is None and all(c.subtree_ok
                                           for c in self.children)

    def first_reason(self):
        """Pre-order first failing node's message, formatted exactly like
        first_unsupported (the single support traversal serves both the
        operator reason and the explain tree)."""
        if self.reason is not None:
            if self.reason == "has no TPU implementation":
                return f"{self.expr.pretty_name} has no TPU implementation"
            return f"{self.expr.pretty_name}: {self.reason}"
        for c in self.children:
            r = c.first_reason()
            if r:
                return r
        return None

    def explain_lines(self, depth: int = 0) -> List[str]:
        marker = "*" if self.reason is None else "!"
        line = "  " * depth + f"{marker} <{self.expr.pretty_name}> " \
            f"{self.expr!r}"
        if self.reason:
            line += f"  <-- {self.reason}"
        out = [line]
        for c in self.children:
            out.extend(c.explain_lines(depth + 1))
        return out


class ExecMeta:
    """Wraps one CPU physical operator during tagging
    (reference: SparkPlanMeta, RapidsMeta.scala:402-545)."""

    def __init__(self, plan: PhysicalPlan, rule: Optional[ExecRule],
                 conf: TpuConf, parent: Optional["ExecMeta"]):
        self.plan = plan
        self.rule = rule
        self.conf = conf
        self.parent = parent
        self.children: List[ExecMeta] = []
        self.reasons: List[str] = []
        # (label, ExprMeta) per checked expression (RapidsMeta.scala:566+)
        self.expr_metas: List[tuple] = []

    def will_not_work(self, reason: str) -> None:
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons

    def tag(self) -> None:
        for c in self.children:
            c.tag()
        if self.rule is None:
            self.will_not_work(
                f"no TPU replacement rule for {self.plan.name}")
            return
        if not self.conf.is_operator_enabled(
                self.rule.conf_key,
                incompat=self.rule.incompat is not None,
                disabled_by_default=self.rule.disabled_by_default):
            extra = ""
            if self.rule.incompat and not self.conf.incompatible_ops_enabled:
                extra = (f" (incompatible: {self.rule.incompat}; enable with "
                         f"{self.rule.conf_key}=true or "
                         "spark.rapids.sql.incompatibleOps.enabled=true)")
            self.will_not_work(f"{self.plan.name} is disabled by conf "
                               f"{self.rule.conf_key}{extra}")
            return
        self.rule.tag_fn(self)

    def check_exprs(self, exprs: List[Expression], what: str = "") -> None:
        schema = (self.plan.children[0].output_schema()
                  if self.plan.children else self.plan.output_schema())
        for e in exprs:
            em = ExprMeta(e, schema)
            reason = em.first_reason()
            if reason:
                self.expr_metas.append((what or "expr", em))
                prefix = f"{what}: " if what else ""
                self.will_not_work(prefix + reason)

    def convert(self) -> PhysicalPlan:
        """convertIfNeeded (RapidsMeta.scala:529-544)."""
        new_children = [c.convert() for c in self.children]
        if self.can_run_on_tpu and self.rule is not None:
            return self.rule.convert_fn(self, new_children)
        return self._keep_on_cpu(new_children)

    def _keep_on_cpu(self, new_children: List[PhysicalPlan]) -> PhysicalPlan:
        import copy
        new = copy.copy(self.plan)
        new.children = new_children
        return new

    def explain_lines(self, depth: int = 0) -> List[str]:
        """RapidsMeta.explain tree printer (RapidsMeta.scala:245-283);
        expression meta subtrees print under their operator so the
        offending expression NODE is named (RapidsMeta.scala:566-726)."""
        marker = "*" if self.can_run_on_tpu else "!"
        line = "  " * depth + f"{marker} {self.plan.describe()}"
        if self.reasons:
            line += "  <-- " + "; ".join(self.reasons)
        out = [line]
        for what, em in self.expr_metas:
            out.append("  " * (depth + 1) + f"@{what}:")
            out.extend(em.explain_lines(depth + 2))
        for c in self.children:
            out.extend(c.explain_lines(depth + 1))
        return out


# ---------------------------------------------------------------------------
# Rule table (reference: GpuOverrides.scala:1582-1699)
# ---------------------------------------------------------------------------

def _tag_project(meta: ExecMeta) -> None:
    meta.check_exprs([e for _, e in meta.plan.exprs], "projection")


def _convert_project(meta: ExecMeta, children) -> PhysicalPlan:
    return tpu.TpuProjectExec(children[0], meta.plan.exprs)


def _tag_filter(meta: ExecMeta) -> None:
    meta.check_exprs([meta.plan.condition], "filter condition")


def _convert_filter(meta: ExecMeta, children) -> PhysicalPlan:
    return tpu.TpuFilterExec(children[0], meta.plan.condition)


def _tag_agg(meta: ExecMeta) -> None:
    plan = meta.plan.plan  # AggPlan
    mode = meta.plan.mode
    replace = meta.conf.hash_agg_replace_mode
    if replace != "all" and replace != mode:
        meta.will_not_work(
            f"hashAgg replace mode {replace!r} excludes {mode} aggregation")
    schema = plan.child_schema
    for name, e in plan.grouping:
        reason = first_unsupported(e, schema)
        if reason:
            meta.will_not_work(f"group key {name}: {reason}")
            meta.expr_metas.append((f"group key {name}",
                                    ExprMeta(e, schema)))
    for fn in plan.agg_fns:
        reason = fn.device_supported(schema)
        if reason:
            meta.will_not_work(reason)
        for c in fn.children:
            r = first_unsupported(c, schema)
            if r:
                meta.will_not_work(f"aggregate input: {r}")
                meta.expr_metas.append(
                    (f"aggregate input of {fn.pretty_name}",
                     ExprMeta(c, schema)))
    if mode == "final":
        for name, e in plan.finalize_exprs():
            r = first_unsupported(e, plan.partial_schema)
            if r:
                meta.will_not_work(f"result {name}: {r}")
    _STRING_RED_KINDS = ("count_valid", "min", "max", "first", "last",
                         "first_valid", "last_valid")
    for fn, ops in zip(plan.agg_fns, plan.update_plan):
        for kind, input_idx, idt in ops:
            if idt.is_string and kind not in _STRING_RED_KINDS:
                meta.will_not_work(
                    f"{kind} over string values is not supported on TPU")


def _convert_agg(meta: ExecMeta, children) -> PhysicalPlan:
    return tpu.TpuHashAggregateExec(children[0], meta.plan.plan,
                                    meta.plan.mode)


def _tag_sort(meta: ExecMeta) -> None:
    schema = meta.plan.children[0].output_schema()
    for o in meta.plan.orders:
        reason = first_unsupported(o.expr, schema)
        if reason:
            meta.will_not_work(f"sort key: {reason}")


def _convert_sort(meta: ExecMeta, children) -> PhysicalPlan:
    return tpu.TpuSortExec(children[0], meta.plan.orders)


def _tag_exchange(meta: ExecMeta) -> None:
    kind = meta.plan.partitioning[0]
    if kind not in ("hash", "single", "roundrobin", "range"):
        meta.will_not_work(f"partitioning {kind!r} not supported on TPU")


def _convert_exchange(meta: ExecMeta, children) -> PhysicalPlan:
    return tpu.TpuShuffleExchangeExec(children[0], meta.plan.partitioning)


def _tag_scan(meta: ExecMeta) -> None:
    src = meta.plan.source
    c = meta.conf
    if isinstance(src, ParquetSource):
        if not (c.get("spark.rapids.sql.format.parquet.enabled")
                and c.get("spark.rapids.sql.format.parquet.read.enabled")):
            meta.will_not_work("Parquet scan disabled by conf")
    elif isinstance(src, CsvSource):
        if not (c.get("spark.rapids.sql.format.csv.enabled")
                and c.get("spark.rapids.sql.format.csv.read.enabled")):
            meta.will_not_work("CSV scan disabled by conf")
    elif isinstance(src, OrcSource):
        if not (c.get("spark.rapids.sql.format.orc.enabled")
                and c.get("spark.rapids.sql.format.orc.read.enabled")):
            meta.will_not_work("ORC scan disabled by conf")
    elif isinstance(src, InMemorySource):
        pass
    else:
        meta.will_not_work(f"source {src.describe()} has no TPU scan")


def _convert_scan(meta: ExecMeta, children) -> PhysicalPlan:
    return tpu.TpuScanExec(meta.plan.source, meta.plan.output_schema(),
                           getattr(meta.plan, "pushed_filters", None))


def _tag_join(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.tpujoin import SUPPORTED_JOIN_TYPES
    if meta.plan.join_type not in SUPPORTED_JOIN_TYPES:
        meta.will_not_work(
            f"join type {meta.plan.join_type!r} not supported on TPU")


def _convert_join(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.tpujoin import TpuShuffledHashJoinExec
    return TpuShuffledHashJoinExec(
        children[0], children[1], meta.plan.join_type, meta.plan.left_keys,
        meta.plan.right_keys,
        exact_long_strings=meta.conf.get_bool(
            "spark.rapids.sql.join.exactLongStrings", True))


def _tag_nothing(meta: ExecMeta) -> None:
    pass


_RULES: Dict[Type[PhysicalPlan], ExecRule] = {}


def _register(rule: ExecRule) -> None:
    _RULES[rule.cpu_class] = rule


_register(ExecRule(cpu.CpuProjectExec, "columnar projection",
                   _tag_project, _convert_project))
_register(ExecRule(cpu.CpuFilterExec, "columnar filter",
                   _tag_filter, _convert_filter))
_register(ExecRule(cpu.CpuHashAggregateExec, "hash aggregate",
                   _tag_agg, _convert_agg))
_register(ExecRule(cpu.CpuSortExec, "device sort",
                   _tag_sort, _convert_sort))
_register(ExecRule(cpu.CpuShuffleExchangeExec, "columnar shuffle exchange",
                   _tag_exchange, _convert_exchange))
_register(ExecRule(cpu.CpuScanExec, "columnar scan",
                   _tag_scan, _convert_scan))
def _tag_expand(meta: ExecMeta) -> None:
    for proj in meta.plan.projections:
        meta.check_exprs([e for _, e in proj], "expand projection")


_register(ExecRule(cpu.CpuExpandExec, "expand (rollup/cube engine)",
                   _tag_expand,
                   lambda m, ch: tpu.TpuExpandExec(ch[0],
                                                   m.plan.projections)))
_register(ExecRule(cpu.CpuJoinExec, "shuffled hash join",
                   _tag_join, _convert_join))


def _convert_broadcast_join(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.tpujoin import TpuBroadcastHashJoinExec
    return TpuBroadcastHashJoinExec(children[0], children[1],
                                    meta.plan.join_type, meta.plan.left_keys,
                                    meta.plan.right_keys)


_register(ExecRule(cpu.CpuBroadcastHashJoinExec, "broadcast hash join",
                   _tag_join, _convert_broadcast_join))
def _convert_cartesian(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.tpujoin import TpuCartesianProductExec
    return TpuCartesianProductExec(children[0], children[1])


# Deviation from the reference's default (GpuOverrides gates
# CartesianProduct off): on this backend a device-resident cartesian is
# strictly better than the fallback, which pays TWO blocking
# device->host result fetches plus a re-upload — scalar-subquery cross
# joins (tpch q11's threshold) hit it on every query.
# The conf remains available to disable.
_register(ExecRule(cpu.CpuCartesianProductExec, "cartesian product",
                   _tag_nothing, _convert_cartesian))


def _tag_bnlj(meta: ExecMeta) -> None:
    cond = meta.plan.condition
    if cond is not None:
        reason = first_unsupported(cond, meta.plan.output_schema())
        if reason:
            meta.will_not_work(f"join condition: {reason}")


def _convert_bnlj(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.tpujoin import TpuBroadcastNestedLoopJoinExec
    return TpuBroadcastNestedLoopJoinExec(children[0], children[1],
                                          meta.plan.join_type,
                                          meta.plan.condition)


_register(ExecRule(cpu.CpuBroadcastNestedLoopJoinExec,
                   "broadcast nested loop join",
                   _tag_bnlj, _convert_bnlj, disabled_by_default=True))
def _convert_broadcast(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.tpujoin import TpuBroadcastExchangeExec
    return TpuBroadcastExchangeExec(children[0])


def _tag_window(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.windowexec import resolve_descriptor
    cs = meta.plan.children[0].output_schema()
    for name, w in meta.plan.window_exprs:
        _, vexpr, err = resolve_descriptor(w, cs)
        if err:
            meta.will_not_work(f"window column {name}: {err}")
            continue
        for e in (w.spec.partition_cols
                  + [o.expr for o in w.spec.orders]
                  + ([vexpr] if vexpr is not None else [])):
            reason = first_unsupported(e, cs)
            if reason:
                meta.will_not_work(f"window column {name}: {reason}")


def _convert_window(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.windowexec import TpuWindowExec
    return TpuWindowExec(children[0], meta.plan.window_exprs)


_register(ExecRule(cpu.CpuBroadcastExchangeExec, "broadcast exchange",
                   _tag_nothing, _convert_broadcast))


def _register_window_rule() -> None:
    from spark_rapids_tpu.exec.windowexec import CpuWindowExec
    _register(ExecRule(CpuWindowExec, "windowed computation",
                       _tag_window, _convert_window))


_register_window_rule()


def _tag_write(meta: ExecMeta) -> None:
    c = meta.conf
    fmt = meta.plan.fmt
    if fmt == "parquet":
        if not (c.get("spark.rapids.sql.format.parquet.enabled")
                and c.get("spark.rapids.sql.format.parquet.write.enabled")):
            meta.will_not_work("Parquet write disabled by conf")
    elif fmt == "orc":
        if not (c.get("spark.rapids.sql.format.orc.enabled")
                and c.get("spark.rapids.sql.format.orc.write.enabled")):
            meta.will_not_work("ORC write disabled by conf")
    elif fmt == "csv":
        # the reference does not accelerate CSV writes either; ours rides
        # the same columnar D2H path so it is enabled by default
        if not c.get("spark.rapids.sql.format.csv.enabled"):
            meta.will_not_work("CSV write disabled by conf")
    else:
        meta.will_not_work(f"write format {fmt!r} has no TPU path")


def _convert_write(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.write import TpuWriteExec
    return TpuWriteExec(children[0], meta.plan.path, meta.plan.fmt,
                        meta.plan.mode, meta.plan.partition_cols)


def _register_write_rule() -> None:
    from spark_rapids_tpu.exec.write import CpuWriteExec
    _register(ExecRule(CpuWriteExec, "data writing command",
                       _tag_write, _convert_write))


_register_write_rule()
def _tag_generate(meta: ExecMeta) -> None:
    plan = meta.plan
    cs = plan.children[0].output_schema()
    if not cs.dtypes[plan.col_idx].is_string:
        meta.will_not_work("explode source must be a string column")
    if len(plan.delim.encode("utf-8")) != 1:
        meta.will_not_work(
            f"delimiter {plan.delim!r}: only single-byte delimiters run on "
            "TPU (multi-byte/regex split stays on CPU)")
    elif plan.delim in "\\^$.|?*+()[]{}":
        meta.will_not_work(
            f"delimiter {plan.delim!r} is a regex metacharacter (Spark "
            "split() patterns are regexes); runs on CPU")


def _convert_generate(meta: ExecMeta, children) -> PhysicalPlan:
    from spark_rapids_tpu.exec.generate import TpuGenerateExec
    p = meta.plan
    return TpuGenerateExec(children[0], p.col_idx, p.delim, p.out_name,
                           p.with_pos, p.pos_name)


def _register_generate_rule() -> None:
    from spark_rapids_tpu.exec.generate import CpuGenerateExec
    _register(ExecRule(CpuGenerateExec, "explode-style generator",
                       _tag_generate, _convert_generate))


_register_generate_rule()
_register(ExecRule(cpu.CpuLocalLimitExec, "local limit", _tag_nothing,
                   lambda m, ch: tpu.TpuLocalLimitExec(ch[0], m.plan.limit)))
_register(ExecRule(cpu.CpuGlobalLimitExec, "global limit", _tag_nothing,
                   lambda m, ch: tpu.TpuGlobalLimitExec(ch[0], m.plan.limit)))
_register(ExecRule(cpu.CpuCollectLimitExec,
                   "collect limit (reference GpuOverrides.scala:1641-1643)",
                   _tag_nothing,
                   lambda m, ch: tpu.TpuCollectLimitExec(ch[0],
                                                         m.plan.limit)))
_register(ExecRule(cpu.CpuCoalescePartitionsExec,
                   "partition coalesce (reference GpuOverrides.scala:1611)",
                   _tag_nothing,
                   lambda m, ch: tpu.TpuCoalescePartitionsExec(ch[0],
                                                               m.plan.n)))
_register(ExecRule(cpu.CpuUnionExec, "columnar union", _tag_nothing,
                   lambda m, ch: tpu.TpuUnionExec(ch)))
_register(ExecRule(cpu.CpuRangeExec, "device range source", _tag_nothing,
                   lambda m, ch: tpu.TpuRangeExec(
                       m.plan.start, m.plan.end, m.plan.step,
                       m.plan.num_partitions, m.plan.col_name)))


def _run_after_tag_rules(root: ExecMeta) -> None:
    """Cross-tree tag fixups after per-node tagging (the reference's
    runAfterTagRules, RapidsMeta.scala:430-485): decisions that depend on
    NEIGHBORING nodes' tags, not just the node itself."""
    _fixup_join_hash_consistency(root)
    _fixup_exchange_overhead(root)


def _fixup_join_hash_consistency(meta: ExecMeta) -> None:
    """A shuffled hash join and the exchanges feeding it must agree on the
    partitioning hash function. If the join stays on CPU, its child TPU
    exchanges fall back too (CPU join would read TPU-hash-partitioned
    rows); if a feeding exchange stays on CPU, the join falls back
    (reference makeShuffleConsistent, RapidsMeta.scala:430-445)."""
    from spark_rapids_tpu.exec.cpu import (
        CpuBroadcastHashJoinExec, CpuCartesianProductExec, CpuJoinExec,
        CpuShuffleExchangeExec,
    )
    for c in meta.children:
        _fixup_join_hash_consistency(c)
    # only SHUFFLED equi-joins depend on partitioning-hash agreement;
    # broadcast/cartesian joins consume stream partitions independently
    if (not isinstance(meta.plan, CpuJoinExec)
            or isinstance(meta.plan, (CpuBroadcastHashJoinExec,
                                      CpuCartesianProductExec))):
        return
    exch_children = [c for c in meta.children
                     if isinstance(c.plan, CpuShuffleExchangeExec)]
    if not exch_children:
        return
    if not meta.can_run_on_tpu:
        for c in exch_children:
            if c.can_run_on_tpu:
                c.will_not_work(
                    "the shuffled join it feeds stays on CPU, so the "
                    "partitioning hash must stay on CPU for consistency")
    elif any(not c.can_run_on_tpu for c in exch_children):
        meta.will_not_work(
            "an input exchange stays on CPU, so the join must use the "
            "CPU partitioning hash for consistency")
        for c in exch_children:
            if c.can_run_on_tpu:
                c.will_not_work(
                    "the shuffled join it feeds stays on CPU, so the "
                    "partitioning hash must stay on CPU for consistency")


def _fixup_exchange_overhead(meta: ExecMeta) -> None:
    """An exchange with no columnar neighbors only adds two transitions
    around a shuffle — keep it on CPU (reference's exchange-overhead
    fixup, RapidsMeta.scala:447-454)."""
    from spark_rapids_tpu.exec.cpu import CpuShuffleExchangeExec
    for c in meta.children:
        _fixup_exchange_overhead(c)
    if not isinstance(meta.plan, CpuShuffleExchangeExec):
        return
    if not meta.can_run_on_tpu:
        return
    parent_columnar = meta.parent is not None and meta.parent.can_run_on_tpu
    child_columnar = any(c.can_run_on_tpu for c in meta.children)
    if not parent_columnar and not child_columnar:
        meta.will_not_work(
            "columnar exchange between CPU operators only adds "
            "host<->device transition overhead")


class TpuOverrides:
    """The preColumnarTransitions rule (GpuOverrides.apply,
    GpuOverrides.scala:1704-1761)."""

    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.root_meta: Optional[ExecMeta] = None

    def wrap(self, plan: PhysicalPlan,
             parent: Optional[ExecMeta] = None) -> ExecMeta:
        rule = _RULES.get(type(plan))
        meta = ExecMeta(plan, rule, self.conf, parent)
        meta.children = [self.wrap(c, meta) for c in plan.children]
        return meta

    def apply(self, plan: PhysicalPlan) -> PhysicalPlan:
        self.root_meta = self.wrap(plan)
        self.root_meta.tag()
        _run_after_tag_rules(self.root_meta)
        explain = self.conf.explain
        if explain in ("ALL", "NOT_ON_TPU"):
            print(self.explain_text(explain))
        return self.root_meta.convert()

    def explain_text(self, mode: str = "ALL") -> str:
        assert self.root_meta is not None
        lines = self.root_meta.explain_lines()
        if mode == "NOT_ON_TPU":
            lines = [ln for ln in lines if ln.lstrip().startswith("!")]
        return "\n".join(lines)

    def fallback_metas(self) -> List[ExecMeta]:
        """Every tagged-off operator meta after apply(), pre-order — the
        machine-readable twin of the "!" explain lines. The session turns
        each into one ``cpuFallback`` journal event (obs/events.py) so
        the explain-why-not record survives the query."""
        assert self.root_meta is not None
        out: List[ExecMeta] = []
        stack = [self.root_meta]
        while stack:
            meta = stack.pop()
            if meta.reasons:
                out.append(meta)
            stack.extend(reversed(meta.children))
        return out


class TransitionOverrides:
    """postColumnarTransitions: insert transitions at CPU/TPU boundaries
    (GpuTransitionOverrides.scala:152-169) and coalesce batches above
    fragmenting producers (insertCoalesce, :64-147)."""

    def __init__(self, conf: TpuConf):
        self.conf = conf

    def apply(self, plan: PhysicalPlan) -> PhysicalPlan:
        from spark_rapids_tpu.exec.coalesce import insert_coalesce
        from spark_rapids_tpu.exec.fusion import (
            fuse_filter_into_aggregate, fuse_selection_into_filter,
        )
        from spark_rapids_tpu.exec.stagecompiler import compile_stages
        # fuse BEFORE coalesce insertion: a fused-away Filter is no longer
        # a fragmenting producer, so no coalesce node appears above it.
        # Whole-stage fusion runs LAST, over the final operator layout
        # (coalesce nodes included — the stage absorbs them), so the
        # legacy, AQE per-stage and plan-cache paths all cut identically.
        return compile_stages(
            insert_coalesce(
                fuse_filter_into_aggregate(
                    fuse_selection_into_filter(self._apply(plan),
                                               self.conf),
                    self.conf),
                self.conf),
            self.conf)

    def _apply(self, plan: PhysicalPlan) -> PhysicalPlan:
        # a TPU operator consumes device batches; a CPU operator consumes
        # host DataFrames — insert the matching transition under each child.
        # columnar_input (terminal commands like TpuWriteExec) overrides
        # the output-kind default.
        wants_columnar = getattr(plan, "columnar_input",
                                 plan.columnar_output)
        new_children = []
        for c in plan.children:
            c2 = self._apply(c)
            if wants_columnar and not c2.columnar_output:
                c2 = HostToDeviceExec(c2)
            elif not wants_columnar and c2.columnar_output:
                c2 = DeviceToHostExec(c2)
            new_children.append(c2)
        out = plan.map_children(lambda c: c)
        out.children = new_children
        return out


def assert_is_on_tpu(plan: PhysicalPlan, conf: TpuConf) -> None:
    """Test-mode enforcement (GpuTransitionOverrides.assertIsOnTheGpu,
    GpuTransitionOverrides.scala:225-263): fail the query if a
    non-allow-listed operator stayed on the CPU."""
    # only the transitions themselves are implicitly allowed; a scan that
    # stayed on the CPU must be named via spark.rapids.sql.test.allowedNonTpu
    # exactly like any other fallback (the reference asserts scans too,
    # GpuTransitionOverrides.scala:225-263)
    allowed = set(conf.test_allowed_nontpu) | {
        "HostToDeviceExec", "DeviceToHostExec",
    }
    offenders = []
    for node in plan.walk():
        on_tpu = (node.columnar_output
                  or getattr(node, "columnar_input", False))
        if not on_tpu and node.name not in allowed:
            offenders.append(node.name)
    if offenders:
        raise AssertionError(
            f"operators did not run on the TPU: {sorted(set(offenders))} "
            "(spark.rapids.sql.test.enabled=true)")
