"""Which program families a benchmark cell's query launches.

Every ``cached_jit`` kernel is dispatched under a ``dispatch.<family>``
span (utils/kernelcache._wrap_ledgered) and its device program is named
``jit_srt_<family>``, which is what the benchmark's ``programs_per_query``
and ``*_device_s`` metrics read. Each benchmark query runs here over tiny
``models/tpch_data`` tables under the engine's defaults, and the set of
families it launched must equal the list below: a change of which family a
cell's query launches fails here before it costs a chip run."""

import importlib.util
import os

import pytest

from spark_rapids_tpu.models import tpch_data
from spark_rapids_tpu.obs.trace import TRACER

QUERY_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "queries")
SF = 0.002

AGG = {"aggupd", "aggmrg", "aggfin"}
# A filter a collapse has claimed launches ``filter`` where its batch is
# sort-compactable (exec/tpu._fused_filter_source: every such collapse of
# q3, q5, q13 and q18 here), and the collapse is then the unmasked
# ``concat``; it launches ``filtermask`` + ``concatmask`` where the batch
# is not: q16's ``part`` at this size, whose ``p_type`` has too many values
# a row for a dictionary and uploads as a slab (on the chip it is a
# dictionary column and compacts too).
FAMILIES = {
    "q1": AGG | {"concat", "sort", "shrink", "packfetch"},
    "q3": AGG | {"filter", "join", "concat", "shrink", "sort", "limitstep",
                 "packfetch"},
    "q5": AGG | {"filter", "join", "concat", "sort", "packfetch"},
    "q6": AGG | {"concat", "packfetch"},
    "q13": AGG | {"filter", "join", "concat", "shrink", "sort", "packfetch"},
    "q16": {"filter", "filtermask", "concat", "concatmask", "join", "cdist",
            "shrink", "sort", "packfetch"},
    "q18": AGG | {"filter", "join", "concat", "shrink", "sort", "limitstep",
                  "packfetch"},
}


def _benchmark_query(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_queries_{name}", os.path.join(QUERY_DIR, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tables(session, tmp_path, reads):
    """The tables a query reads, as Parquet scans of three row groups
    each, so a table is several batches as it is on the chip and a
    collapse has something to concatenate. ``tpch_data`` draws
    ``l_orderkey`` over four times as many keys as there are lines, so the
    lines are folded onto 400 of the orders (about 30 lines an order):
    Q18's ``sum(l_quantity) > 300`` then keeps rows and every operator
    above it runs on input."""
    frames = {t: tpch_data.gen_table(t, SF) for t in reads}
    if "orders" in frames and "lineitem" in frames:
        keys = frames["orders"]["o_orderkey"].to_numpy()[:400]
        li = frames["lineitem"]
        frames["lineitem"] = li.assign(
            l_orderkey=keys[li["l_orderkey"].to_numpy() % len(keys)])
    tables = {}
    for t, df in frames.items():
        path = str(tmp_path / f"{t}.parquet")
        df.to_parquet(path, index=False,
                      row_group_size=max(len(df) // 3, 1))
        tables[t] = session.read.parquet(path)
    return tables


@pytest.mark.parametrize("name", sorted(FAMILIES, key=lambda q: int(q[1:])))
def test_default_plan_families(name, session, tmp_path):
    mod = _benchmark_query(name)
    tables = _tables(session, tmp_path, mod.READS)
    session.set_conf("spark.rapids.sql.test.enabled", True)
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    launched = set()
    try:
        for _ in range(2):  # a second execution may plan from what it saw
            out = mod.build(session, tables).collect()
            launched |= {e["name"][len("dispatch."):]
                         for e in TRACER.events()
                         if e["name"].startswith("dispatch.")}
    finally:
        session.set_conf("spark.rapids.tpu.trace.enabled", False)
    assert len(out) > 0
    assert launched == FAMILIES[name]
