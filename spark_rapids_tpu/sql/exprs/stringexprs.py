"""String expressions (reference: sql/rapids/stringFunctions.scala, 698 LoC).

Device kernels live in ops/strings.py. Like the reference, complex regex is
restricted: LIKE patterns of literal segments separated by ``%`` run on
device, ``_`` and escapes tag the plan off (GpuOverrides.scala:334-379
applies the same kind of restriction).

A pattern predicate (startswith/endswith/contains/LIKE) over a dictionary
column is decided once a dictionary value on the host while the program is
traced (``decide_by_value``): the rows are one gather by code, and a
codes-only column stays lazy."""

from __future__ import annotations

import re
from typing import List, Optional

import jax.numpy as jnp
import numpy as np
import pandas as pd

from spark_rapids_tpu.columnar import dtypes
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.columnar.dtype import DType
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.ops import strings as string_ops
from spark_rapids_tpu.sql.exprs.core import (
    BoundRef, DevCol, DevScalar, DevValue, EvalContext, Expression, Literal,
)
from spark_rapids_tpu.sql.exprs.hostutil import host_unary_values, rebuild_series


class StringLength(Expression):
    def __init__(self, child: Expression):
        super().__init__([child])

    def dtype(self, schema: Schema) -> DType:
        return dtypes.INT32

    def sql_name(self, schema=None) -> str:
        return f"length({self.children[0].sql_name(schema)})"

    def device_supported(self, schema: Schema) -> Optional[str]:
        # byte-length == char-length only for ASCII; see ops/strings.py note
        return None

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return DevCol(dtypes.INT32, string_ops.lengths_of(v), v.validity)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        data = np.array([len(x.encode("utf-8")) if x is not None else 0
                         for x in values], dtype=np.int32)
        return rebuild_series(data, validity, dtypes.INT32, index)


class _CaseMap(Expression):
    upper = True

    def __init__(self, child: Expression):
        super().__init__([child])

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        fn = "upper" if self.upper else "lower"
        return f"{fn}({self.children[0].sql_name(schema)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return (string_ops.upper_ascii(v) if self.upper
                else string_ops.lower_ascii(v))

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        # ASCII-only to match the device kernel
        fn = str.upper if self.upper else str.lower
        data = np.array([_ascii_case(x, self.upper) if x is not None else None
                         for x in values], dtype=object)
        return rebuild_series(data, validity, dtypes.STRING, index)


def _ascii_case(s: str, upper: bool) -> str:
    out = []
    for ch in s:
        o = ord(ch)
        if upper and 97 <= o <= 122:
            out.append(chr(o - 32))
        elif not upper and 65 <= o <= 90:
            out.append(chr(o + 32))
        else:
            out.append(ch)
    return "".join(out)


class Upper(_CaseMap):
    upper = True


class Lower(_CaseMap):
    upper = False


class Substring(Expression):
    def __init__(self, child: Expression, pos: int, length: int = -1):
        super().__init__([child])
        self.pos = pos
        self.length = length

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return (f"substring({self.children[0].sql_name(schema)}, {self.pos}, "
                f"{self.length})")

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return string_ops.substring(ctx, v, self.pos, self.length)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        out = np.empty(len(values), dtype=object)
        for i, x in enumerate(values):
            if x is None:
                out[i] = None
                continue
            b = x.encode("utf-8")
            if self.pos > 0:
                start = self.pos - 1
            elif self.pos == 0:
                start = 0
            else:
                start = max(len(b) + self.pos, 0)
            end = len(b) if self.length < 0 else min(start + self.length, len(b))
            out[i] = b[start:end].decode("utf-8", errors="replace")
        return rebuild_series(out, validity, dtypes.STRING, index)


def decide_by_value(col: DevCol, match) -> Optional[jnp.ndarray]:
    """``match(value)`` over a dictionary column, or None where ``col`` has
    no dictionary. ``dict_values`` is static in the pytree, so the predicate
    is decided here, on the host, once a value while the program is traced,
    into a ``(card + 1,)`` table whose last entry (the NULL sentinel and the
    padding) is false; the rows are one gather by code. Neither chars nor
    offsets are read: a codes-only column stays lazy."""
    if col.dict_values is None or col.dict_codes is None:
        return None
    card = len(col.dict_values)
    table = np.zeros(card + 1, np.bool_)
    table[:card] = [bool(match(v)) for v in col.dict_values]
    return jnp.asarray(table)[jnp.clip(col.dict_codes, 0, card)]


class _LiteralPatternPredicate(Expression):
    """Base for startswith/endswith/contains/LIKE with a literal pattern."""
    fn_name = "?"

    def __init__(self, child: Expression, pattern: str):
        super().__init__([child])
        self.pattern = pattern

    def dtype(self, schema: Schema) -> DType:
        return dtypes.BOOL

    def sql_name(self, schema=None) -> str:
        return f"{self.fn_name}({self.children[0].sql_name(schema)}, {self.pattern!r})"

    def device_kernel(self, ctx, col):
        raise NotImplementedError

    def host_match(self, s: str) -> bool:
        raise NotImplementedError

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        data, validity = decide_by_value(v, self.host_match), v.validity
        if data is None:
            data, validity = self.device_kernel(ctx, v)
        return DevCol(dtypes.BOOL, data, validity)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        data = np.array([self.host_match(x) if x is not None else False
                         for x in values], dtype=np.bool_)
        return rebuild_series(data, validity, dtypes.BOOL, index)


class StartsWith(_LiteralPatternPredicate):
    fn_name = "startswith"
    def device_kernel(self, ctx, col):
        return string_ops.starts_with(ctx, col, self.pattern)
    def host_match(self, s: str) -> bool:
        return s.startswith(self.pattern)


class EndsWith(_LiteralPatternPredicate):
    fn_name = "endswith"
    def device_kernel(self, ctx, col):
        return string_ops.ends_with(ctx, col, self.pattern)
    def host_match(self, s: str) -> bool:
        return s.endswith(self.pattern)


class Contains(_LiteralPatternPredicate):
    fn_name = "contains"
    def device_kernel(self, ctx, col):
        return string_ops.contains(ctx, col, self.pattern)
    def host_match(self, s: str) -> bool:
        return self.pattern in s


class Like(_LiteralPatternPredicate):
    """SQL LIKE with a literal pattern of literal segments separated by
    ``%`` (``a%``, ``%a``, ``%a%``, ``a%b``, ``%a%b%``...): over a
    dictionary column decided once a value, over chars by the kernels of
    ops/strings.py. ``_`` and escapes tag off (the reference restricts
    regex the same way, GpuOverrides.scala:334-379)."""
    fn_name = "like"

    def __init__(self, child: Expression, pattern: str):
        super().__init__(child, pattern)
        self._kind, self._needle = _classify_like(pattern)
        self._regex = re.compile(_like_to_regex(pattern), re.DOTALL)

    def sql_name(self, schema=None) -> str:
        return f"({self.children[0].sql_name(schema)} LIKE {self.pattern!r})"

    def device_supported(self, schema: Schema) -> Optional[str]:
        if self._kind is None:
            return (f"LIKE pattern {self.pattern!r} has {self._needle}, "
                    "which is not supported on TPU (literal segments "
                    "separated by % are)")
        return None

    def host_match(self, s: str) -> bool:
        return self._regex.fullmatch(s) is not None

    def device_kernel(self, ctx, col):
        if self._kind == "exact":
            return string_ops.string_equal_literal(ctx, col, self._needle)
        if self._kind == "prefix":
            return string_ops.starts_with(ctx, col, self._needle)
        if self._kind == "suffix":
            return string_ops.ends_with(ctx, col, self._needle)
        if self._kind == "contains":
            return string_ops.contains(ctx, col, self._needle)
        if self._kind == "segments":
            return string_ops.like_segments(ctx, col, *self._needle)
        raise RuntimeError(self._kind)


def _classify_like(p: str):
    """(kind, needle) of a LIKE pattern the device runs: literal segments
    separated by ``%``. The one-segment kinds keep their own kernels;
    ``segments`` carries (head, middle segments, tail) for
    ``string_ops.like_segments``. (None, what stands in the way) for a
    pattern with ``_`` or an escape."""
    if "_" in p:
        return None, "the single-character wildcard _"
    if "\\" in p:
        return None, "an escape (\\)"
    parts = p.split("%")
    if len(parts) == 1:
        return "exact", p
    head, tail = parts[0], parts[-1]
    middle = tuple(seg for seg in parts[1:-1] if seg)
    if not middle and not (head and tail):
        if head:
            return "prefix", head
        if tail:
            return "suffix", tail
        return "contains", ""
    if len(middle) == 1 and not head and not tail:
        return "contains", middle[0]
    return "segments", (head, middle, tail)


_DICT_ROWS = REGISTRY.counter("expr.dictPredicate.rows")
_REBUILT_BYTES = REGISTRY.counter("strings.charsRebuilt.bytes")


def _pattern_sites(exprs):
    """Every pattern predicate in the bound trees ``exprs``, as (its
    ``expr.dictPredicate.batches{fn}`` counter, the ordinal of the column it
    reads where its child is a bare reference, else None, the ordinals of
    the string columns under a child that derives a string)."""
    sites = []

    def refs_under(e, out):
        if isinstance(e, BoundRef) and e._dtype.is_string:
            out.append(e.index)
        for c in e.children:
            refs_under(c, out)
        return out

    def visit(e):
        if isinstance(e, _LiteralPatternPredicate):
            child = e.children[0]
            batches = REGISTRY.counter("expr.dictPredicate.batches",
                                       fn=e.fn_name)
            if isinstance(child, BoundRef):
                sites.append((batches, child.index, ()))
            else:
                sites.append((batches, None, tuple(refs_under(child, []))))
        for c in e.children:
            visit(c)
    for e in exprs:
        visit(e)
    return sites


def counting_pattern_predicates(exprs):
    """A wrapper for the kernels whose first argument is the batch ``exprs``
    are bound to: ``wrap(kernel)`` is ``kernel`` with the string pattern
    predicates of ``exprs`` counted at every call (a jitted program's trace
    runs once; this runs every dispatch), from what the host knows without
    a sync. A predicate over a bare reference to a dictionary column is
    answered from the dictionary: ``expr.dictPredicate.rows`` takes the
    batch's rows (``num_rows_hint``), ``expr.dictPredicate.batches{fn}``
    one. A predicate over a derived string (``substring``, ``concat``...)
    reads bytes, so each codes-only column under it is rebuilt from its
    codes in the program: ``strings.charsRebuilt.bytes`` takes the char
    capacity of each, and is touched with 0 where nothing was rebuilt.
    ``wrap(kernel)`` is ``kernel`` itself where ``exprs`` hold no such
    predicate."""
    sites = _pattern_sites(exprs)
    if not sites:
        return lambda kernel: kernel

    def wrap(kernel):
        def run(batch, *rest):
            rebuilt = 0
            for batches, ref, derived_from in sites:
                if ref is not None:
                    if batch.columns[ref].dict_values is not None:
                        _DICT_ROWS.add(batch.num_rows_hint())
                        batches.add(1)
                    continue
                for i in derived_from:
                    col = batch.columns[i]
                    if col.is_lazy and not col.has_slab:
                        rebuilt += col.rebuilt_char_capacity()
            _REBUILT_BYTES.add(rebuilt)
            return kernel(batch, *rest)
        return run
    return wrap


def _like_to_regex(p: str) -> str:
    out = []
    for ch in p:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


class ConcatStrings(Expression):
    def __init__(self, children: List[Expression]):
        super().__init__(children)

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"concat({', '.join(c.sql_name(schema) for c in self.children)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        cols = [ctx.broadcast(c.eval_device(ctx)) for c in self.children]
        return string_ops.concat_columns(ctx, cols)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        parts = [host_unary_values(c.eval_host(df)) for c in self.children]
        n = len(df)
        validity = parts[0][1].copy()
        for _, v, _ in parts[1:]:
            validity &= v
        out = np.empty(n, dtype=object)
        for i in range(n):
            if validity[i]:
                out[i] = "".join(p[0][i] for p in parts)
            else:
                out[i] = None
        return rebuild_series(out, validity, dtypes.STRING, parts[0][2])


class _TrimBase(Expression):
    """trim/ltrim/rtrim with an optional literal trim-char set."""
    fn_name = "trim"
    left = True
    right = True

    def __init__(self, child: Expression, chars: Optional[str] = None):
        super().__init__([child])
        # Spark's trim/ltrim/rtrim strip only the space character
        self.chars = chars if chars is not None else " "

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"{self.fn_name}({self.children[0].sql_name(schema)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return string_ops.trim(ctx, v, self.chars, self.left, self.right)

    def _host_one(self, s: str) -> str:
        if self.left and self.right:
            return s.strip(self.chars)
        if self.left:
            return s.lstrip(self.chars)
        return s.rstrip(self.chars)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        out = np.array([self._host_one(x) if x is not None else None
                        for x in values], dtype=object)
        return rebuild_series(out, validity, dtypes.STRING, index)


class Trim(_TrimBase):
    fn_name, left, right = "trim", True, True


class LTrim(_TrimBase):
    fn_name, left, right = "ltrim", True, False


class RTrim(_TrimBase):
    fn_name, left, right = "rtrim", False, True


class _PadBase(Expression):
    fn_name = "lpad"
    left = True

    def __init__(self, child: Expression, n: int, pad: str = " "):
        super().__init__([child])
        self.n = int(n)
        self.pad = pad or " "

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"{self.fn_name}({self.children[0].sql_name(schema)}, {self.n})"

    def device_supported(self, schema: Schema) -> Optional[str]:
        if len(self.pad.encode("utf-8")) != 1:
            return "only single-byte pad characters run on TPU"
        return None

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return string_ops.pad(ctx, v, self.n, self.pad, self.left)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        out = np.empty(len(values), dtype=object)
        for i, x in enumerate(values):
            if x is None:
                out[i] = None
            elif len(x) >= self.n:
                out[i] = x[:self.n]
            elif self.left:
                out[i] = self.pad * (self.n - len(x)) + x
            else:
                out[i] = x + self.pad * (self.n - len(x))
        return rebuild_series(out, validity, dtypes.STRING, index)


class LPad(_PadBase):
    fn_name, left = "lpad", True


class RPad(_PadBase):
    fn_name, left = "rpad", False


class StringLocate(Expression):
    """locate(substr, str, pos) / instr(str, substr): 1-based, 0 = absent."""

    def __init__(self, child: Expression, substr: str, start_pos: int = 1):
        super().__init__([child])
        self.substr = substr
        self.start_pos = int(start_pos)

    def dtype(self, schema: Schema) -> DType:
        return dtypes.INT32

    def sql_name(self, schema=None) -> str:
        return (f"locate({self.substr!r}, "
                f"{self.children[0].sql_name(schema)}, {self.start_pos})")

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return DevCol(dtypes.INT32,
                      string_ops.locate(ctx, v, self.substr, self.start_pos),
                      v.validity)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        out = np.zeros(len(values), dtype=np.int32)
        for i, x in enumerate(values):
            if x is None:
                continue
            out[i] = x.find(self.substr, self.start_pos - 1) + 1
        return rebuild_series(out, validity, dtypes.INT32, index)


class StringReplace(Expression):
    """replace(str, search, replacement) with literal arguments."""

    def __init__(self, child: Expression, search: str, replacement: str):
        super().__init__([child])
        self.search = search
        self.replacement = replacement

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return (f"replace({self.children[0].sql_name(schema)}, "
                f"{self.search!r}, {self.replacement!r})")

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return string_ops.replace_literal(ctx, v, self.search,
                                          self.replacement)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        out = np.array([x.replace(self.search, self.replacement)
                        if x is not None else None
                        for x in values], dtype=object)
        return rebuild_series(out, validity, dtypes.STRING, index)


class InitCap(Expression):
    def __init__(self, child: Expression):
        super().__init__([child])

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"initcap({self.children[0].sql_name(schema)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        assert isinstance(v, DevCol)
        return string_ops.initcap_ascii(v)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(self.children[0].eval_host(df))

        def one(s):
            out = []
            prev_space = True
            for ch in s:
                o = ord(ch)
                if prev_space and 97 <= o <= 122:
                    out.append(chr(o - 32))
                elif not prev_space and 65 <= o <= 90:
                    out.append(chr(o + 32))
                else:
                    out.append(ch)
                prev_space = ch == " "
            return "".join(out)
        out = np.array([one(x) if x is not None else None for x in values],
                       dtype=object)
        return rebuild_series(out, validity, dtypes.STRING, index)


class RegexpReplace(Expression):
    """regexp_replace: general regex stays on the CPU (the reference also
    restricts the regex dialect, GpuOverrides.scala:334-379); literal
    patterns collapse to StringReplace during planning via
    maybe_literal_regex()."""

    def __init__(self, child: Expression, pattern: str, replacement: str):
        super().__init__([child])
        self.pattern = pattern
        self.replacement = replacement

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return (f"regexp_replace({self.children[0].sql_name(schema)}, "
                f"{self.pattern!r})")

    def device_supported(self, schema: Schema) -> Optional[str]:
        return (f"regular expression {self.pattern!r} is not supported on "
                "TPU (only literal patterns run on device)")

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        import re
        rx = re.compile(self.pattern)
        values, validity, index = host_unary_values(self.children[0].eval_host(df))
        out = np.array([rx.sub(self.replacement, x) if x is not None else None
                        for x in values], dtype=object)
        return rebuild_series(out, validity, dtypes.STRING, index)


_REGEX_META = set("\\^$.|?*+()[]{}")


def maybe_literal_regex(pattern: str) -> Optional[str]:
    """If a regex pattern contains no metacharacters it is a plain literal."""
    if any(ch in _REGEX_META for ch in pattern):
        return None
    return pattern


def make_regexp_replace(child: Expression, pattern: str,
                        replacement: str) -> Expression:
    lit = maybe_literal_regex(pattern)
    if lit is not None and "$" not in replacement:
        return StringReplace(child, lit, replacement)
    return RegexpReplace(child, pattern, replacement)


class ConcatWs(Expression):
    """concat_ws(sep, s1, s2, ...): joins NON-NULL parts; never NULL
    (reference: GpuConcatWs, GpuOverrides string rules)."""

    def __init__(self, sep: str, children: List[Expression]):
        super().__init__(children)
        self.sep = str(sep)

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        parts = ", ".join(c.sql_name(schema) for c in self.children)
        return f"concat_ws({self.sep!r}, {parts})"

    def device_supported(self, schema: Schema) -> Optional[str]:
        if not self.children:
            return "concat_ws with no arguments"
        for c in self.children:
            if not c.dtype(schema).is_string:
                return "concat_ws over non-string inputs"
        return None

    def eval_device(self, ctx: EvalContext) -> DevValue:
        cols = [ctx.broadcast(c.eval_device(ctx)) for c in self.children]
        return string_ops.concat_ws_columns(ctx, self.sep, cols)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        n = len(df)
        if not self.children:
            return rebuild_series(np.full(n, "", dtype=object),
                                  np.ones(n, np.bool_), dtypes.STRING,
                                  df.index)
        parts = [host_unary_values(c.eval_host(df)) for c in self.children]
        out = np.empty(n, dtype=object)
        for i in range(n):
            vals = [str(p[0][i]) for p in parts if p[1][i]]
            out[i] = self.sep.join(vals)
        return rebuild_series(out, np.ones(n, np.bool_), dtypes.STRING,
                              parts[0][2])


class Translate(Expression):
    """translate(str, matching, replace) with literal maps."""

    def __init__(self, child: Expression, matching: str, replace: str):
        super().__init__([child])
        self.matching = str(matching)
        self.replace = str(replace)

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return (f"translate({self.children[0].sql_name(schema)}, "
                f"{self.matching!r}, {self.replace!r})")

    def device_supported(self, schema: Schema) -> Optional[str]:
        if any(ord(c) > 127 for c in self.matching + self.replace):
            return "translate with non-ASCII map is not supported on TPU"
        return None

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        col = ctx.broadcast(v)
        return string_ops.translate_string(ctx, col, self.matching,
                                           self.replace)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(
            self.children[0].eval_host(df))
        table = {ord(m): (self.replace[i] if i < len(self.replace) else None)
                 for i, m in enumerate(self.matching)}
        out = np.empty(len(values), dtype=object)
        for i, s in enumerate(values):
            out[i] = s.translate(table) if validity[i] else None
        return rebuild_series(out, validity, dtypes.STRING, index)


class StringReverse(Expression):
    """BYTE-oriented reverse, exact for ASCII (the framework's string
    kernels are byte-indexed, see ops/strings.py); multi-byte UTF-8 input
    reverses bytes, not codepoints — a documented divergence from Spark.
    The host twin mirrors the byte semantics so the differential oracle
    agrees with the device."""

    def __init__(self, child: Expression):
        super().__init__([child])

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"reverse({self.children[0].sql_name(schema)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        col = ctx.broadcast(self.children[0].eval_device(ctx))
        return string_ops.reverse_string(ctx, col)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(
            self.children[0].eval_host(df))
        out = np.array(
            [s.encode("utf-8")[::-1].decode("utf-8", errors="replace")
             if v else None for s, v in zip(values, validity)],
            dtype=object)
        return rebuild_series(out, validity, dtypes.STRING, index)


class StringRepeat(Expression):
    def __init__(self, child: Expression, n: int):
        super().__init__([child])
        self.n = int(n)

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"repeat({self.children[0].sql_name(schema)}, {self.n})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        col = ctx.broadcast(self.children[0].eval_device(ctx))
        return string_ops.repeat_string(ctx, col, self.n)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(
            self.children[0].eval_host(df))
        out = np.array([s * max(self.n, 0) if v else None
                        for s, v in zip(values, validity)], dtype=object)
        return rebuild_series(out, validity, dtypes.STRING, index)


class Ascii(Expression):
    """First BYTE of the UTF-8 encoding, exact for ASCII (byte-indexed
    kernels, see ops/strings.py); for multi-byte leading characters Spark
    returns the codepoint while this returns the lead byte — a documented
    divergence. The host twin mirrors the byte semantics so the
    differential oracle agrees with the device."""

    def __init__(self, child: Expression):
        super().__init__([child])

    def dtype(self, schema: Schema) -> DType:
        return dtypes.INT32

    def sql_name(self, schema=None) -> str:
        return f"ascii({self.children[0].sql_name(schema)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        col = ctx.broadcast(self.children[0].eval_device(ctx))
        return string_ops.ascii_first(ctx, col)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(
            self.children[0].eval_host(df))
        out = np.array([(s.encode("utf-8")[0] if s else 0) if v else 0
                        for s, v in zip(values, validity)], dtype=np.int32)
        return rebuild_series(out, validity, dtypes.INT32, index)


class Chr(Expression):
    """chr(n) over the ASCII/byte range (n % 256; negative -> '')."""

    def __init__(self, child: Expression):
        super().__init__([child])

    def dtype(self, schema: Schema) -> DType:
        return dtypes.STRING

    def sql_name(self, schema=None) -> str:
        return f"char({self.children[0].sql_name(schema)})"

    def eval_device(self, ctx: EvalContext) -> DevValue:
        v = self.children[0].eval_device(ctx)
        col = ctx.broadcast(v)
        return string_ops.chr_from_int(ctx, col.data, col.validity)

    def eval_host(self, df: pd.DataFrame) -> pd.Series:
        values, validity, index = host_unary_values(
            self.children[0].eval_host(df))
        out = np.empty(len(values), dtype=object)
        for i, (x, v) in enumerate(zip(values, validity)):
            if not v:
                out[i] = None
            elif int(x) < 0:
                out[i] = ""
            else:
                out[i] = chr(int(x) % 256)
        return rebuild_series(out, validity, dtypes.STRING, index)
