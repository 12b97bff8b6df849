"""Generate (explode) physical operators (reference: GpuGenerateExec.scala,
194 LoC — explode-style generators; posexplode unsupported cases tagged
there, supported here via the fused device kernel).

The supported generator is ``explode(split(strcol, delim))`` — with a
single-byte literal delimiter it runs fused on device; anything else
(multi-byte delimiters, regex split) stays on the CPU with a readable tag
reason, the reference's fallback classification.
"""

from __future__ import annotations

from typing import Iterator, List

import jax
import numpy as np
import pandas as pd

from spark_rapids_tpu.columnar import dtypes
from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, Schema, bucket_capacity,
)
from spark_rapids_tpu.columnar.column import _char_bucket
from spark_rapids_tpu.exec.base import ExecContext, Partition, PhysicalPlan
from spark_rapids_tpu.ops import generate as gen_ops
from spark_rapids_tpu.utils.kernelcache import cached_jit


def generate_output_schema(child: Schema, with_pos: bool, pos_name: str,
                           out_name: str) -> Schema:
    """Generate output = child columns [+ pos INT32] + token STRING — the
    single definition shared by the logical node and both execs."""
    names = list(child.names)
    dts = list(child.dtypes)
    if with_pos:
        names.append(pos_name)
        dts.append(dtypes.INT32)
    names.append(out_name)
    dts.append(dtypes.STRING)
    return Schema(names, dts)


class CpuGenerateExec(PhysicalPlan):
    """Host explode: pandas str.split + explode. Null strings yield no rows;
    empty strings yield one empty token (Spark split semantics)."""

    def __init__(self, child: PhysicalPlan, col_idx: int, delim: str,
                 out_name: str, with_pos: bool, pos_name: str = "pos"):
        super().__init__([child])
        self.col_idx = col_idx
        self.delim = delim
        self.out_name = out_name
        self.with_pos = with_pos
        self.pos_name = pos_name

    def output_schema(self) -> Schema:
        return generate_output_schema(self.children[0].output_schema(),
                                      self.with_pos, self.pos_name,
                                      self.out_name)

    def describe(self) -> str:
        pos = "pos" if self.with_pos else ""
        return f"CpuGenerateExec({pos}explode(split(c{self.col_idx}, " \
               f"{self.delim!r})) AS {self.out_name})"

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        cs = self.children[0].output_schema()

        def make(part: Partition) -> Partition:
            def run():
                for df in part():
                    src = df.iloc[:, self.col_idx]
                    rows: List[int] = []
                    toks: List[str] = []
                    poss: List[int] = []
                    splitter = _make_splitter(self.delim)
                    for r, v in enumerate(src):
                        if pd.isna(v):
                            continue
                        for p, tok in enumerate(splitter(str(v))):
                            rows.append(r)
                            toks.append(tok)
                            poss.append(p)
                    out = df.iloc[rows].reset_index(drop=True)
                    if self.with_pos:
                        out[self.pos_name] = pd.Series(
                            np.asarray(poss, dtype=np.int32))
                    out[self.out_name] = pd.Series(toks, dtype="str")
                    yield out
            return run
        return [make(p) for p in child_parts]


class TpuGenerateExec(PhysicalPlan):
    columnar_output = True

    def __init__(self, child: PhysicalPlan, col_idx: int, delim: str,
                 out_name: str, with_pos: bool, pos_name: str = "pos"):
        super().__init__([child])
        self.col_idx = col_idx
        self.delim = delim
        self.out_name = out_name
        self.with_pos = with_pos
        self.pos_name = pos_name
        byte = delim.encode("utf-8")
        assert len(byte) == 1, "device split needs a single-byte delimiter"
        self._delim_byte = byte[0]
        sig = (f"generate|{col_idx}|{self._delim_byte}|{with_pos}"
               f"|{out_name}|{pos_name}")
        self._totals = cached_jit(sig + "|totals", lambda: jax.jit(
            lambda b: gen_ops.explode_totals(b, col_idx, self._delim_byte)))
        self._expand = cached_jit(sig + "|expand", lambda: jax.jit(
            lambda b, out_cap, ccaps, tcap: gen_ops.explode_split(
                b, col_idx, self._delim_byte, out_name, out_cap, ccaps,
                tcap, with_pos, pos_name),
            static_argnums=(1, 2, 3)))

    def output_schema(self) -> Schema:
        return generate_output_schema(self.children[0].output_schema(),
                                      self.with_pos, self.pos_name,
                                      self.out_name)

    def describe(self) -> str:
        pos = "pos" if self.with_pos else ""
        return f"TpuGenerateExec({pos}explode(split(c{self.col_idx}, " \
               f"{self.delim!r})) AS {self.out_name})"

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child_parts = self.children[0].executed_partitions(ctx)
        growth = ctx.conf.capacity_growth
        schema = self.output_schema()

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                emitted = False
                for batch in part():
                    import jax
                    sizes = [int(x) for x in
                             jax.device_get(self._totals(batch))]
                    total = sizes[0]
                    if total == 0:
                        continue
                    ccaps = tuple(_char_bucket(c) for c in sizes[1:-1])
                    tcap = _char_bucket(sizes[-1])
                    from spark_rapids_tpu.utils.kernelcache import (
                        bucket_dim,
                    )
                    out_cap = bucket_dim(bucket_capacity(total, growth))
                    emitted = True
                    yield self._expand(batch, out_cap, ccaps, tcap)
                if not emitted:
                    yield DeviceBatch.empty(schema)
            return run
        return [make(p) for p in child_parts]


_REGEX_META = set("\\^$.|?*+()[]{}")


def _make_splitter(delim: str):
    """Spark's split() is regex-based: metacharacter patterns go through
    re.split on the host (and are tagged off the device)."""
    if any(ch in _REGEX_META for ch in delim):
        import re
        rx = re.compile(delim)
        return lambda s: rx.split(s)
    return lambda s: s.split(delim)
