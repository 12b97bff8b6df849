"""TPC-H Q13 as the benchmark's cell ``tpch-sf10-outer.q13`` runs it, at a
small size on the CPU: the engine through Parquet and ``collect()`` against
the plain pandas reference of ``benchmarks/queries/q13.py`` on
``benchmarks/data.py`` tables, under the plan SF10 gets, and what that
configuration leans on (the NOT LIKE answered from the dictionary, the left
outer join's null-extended rows, ``count`` of a column over them, the
counters the cell's per-layer metrics read)."""

import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.batch import bucket_capacity
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SF = 0.02          # 3,000 customers, 30,000 orders of which 20,000 stay
SEED = 2**31 + 13
ROW_GROUPS = 6     # a split is a row group: several batches a table
# at SF10 neither side is under the broadcast threshold (and a left outer
# join may broadcast its right side alone); at this size both are, so the
# tests lower it until the plan is the one SF10 gets
SF10_PLAN = {"spark.rapids.sql.test.enabled": True,
             "spark.rapids.sql.autoBroadcastJoinThreshold": 20 << 10}


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "q13outer_" + "_".join(parts)[:-3].replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


data = _bench_module("data.py")
match = _bench_module("match.py")
q13 = _bench_module("queries", "q13.py")


@pytest.fixture(scope="module")
def frames():
    return {t: data.gen_table(t, SF, SEED).select(cols).to_pandas()
            for t, cols in q13.READS.items()}


@pytest.fixture(scope="module")
def parquet_dir(tmp_path_factory, frames):
    root = tmp_path_factory.mktemp("q13")
    for t, cols in q13.READS.items():
        table = data.gen_table(t, SF, SEED).select(cols)
        pq.write_table(table, str(root / f"{t}.parquet"),
                       row_group_size=-(-len(table) // ROW_GROUPS))
    return root


@pytest.fixture
def tables(session, parquet_dir):
    for key, value in SF10_PLAN.items():
        session.set_conf(key, value)
    return {t: session.read.parquet(str(parquet_dir / f"{t}.parquet"))
            for t in q13.READS}


def _kept(frames):
    """The orders the NOT LIKE keeps, by the words' order in the value."""
    comment = frames["orders"].o_comment
    first = comment.str.find("special")
    matches = (first >= 0) & (comment.str.rfind("requests") > first)
    return frames["orders"][~matches]


def test_the_plan_is_the_one_sf10_gets(session, tables, capsys):
    plan = q13.build(session, tables).explain()
    capsys.readouterr()
    joins = [line.strip() for line in plan.splitlines() if "Join" in line]
    assert len(joins) == 1 and "JoinExec(left" in joins[0], plan
    assert "BroadcastExchange" not in plan, plan
    assert "Like" in plan and "Contains" not in plan, plan
    assert plan.count("HashAggregateExec") == 4, plan


def test_some_customers_have_no_order_at_this_size(frames):
    kept = _kept(frames)
    assert 0.6 < len(kept) / len(frames["orders"]) < 0.72   # a third goes
    orderless = set(frames["customer"].c_custkey) - set(kept.o_custkey)
    assert 1 <= len(orderless) <= 20
    want = q13.reference(frames)
    assert want[want.c_count == 0].custdist.tolist() == [len(orderless)]
    assert want.custdist.sum() == len(frames["customer"])
    assert (want.c_count * want.custdist).sum() == len(kept)


def test_q13_matches_the_pandas_reference(session, tables, frames):
    want = q13.reference(frames)
    # thrice: the second aggregate's key is computed, no scan declares its
    # bounds, and the plans settle from what earlier executions left
    for _ in range(3):
        got = q13.build(session, tables).collect()
        assert match.results_match(got, want), f"{got}\n{want}"
    # the answer is integers alone, in the specification's order
    assert [str(d) for d in got.dtypes] == ["int64", "int64"]
    assert got.values.tolist() == want.values.tolist()


def test_count_of_a_column_skips_the_null_extended_rows(session, tables,
                                                        frames):
    """``count(*)`` over the join's output would put every orderless
    customer in ``c_count`` 1: the cell's exact comparison tells them
    apart."""
    want = q13.reference(frames)
    orders = tables["orders"].filter(
        ~F.col("o_comment").like("%special%requests%"))
    joined = tables["customer"].join(
        orders, left_on=["c_custkey"], right_on=["o_custkey"], how="left")
    starred = (joined.group_by("c_custkey")
               .agg(F.count("*").alias("c_count"))
               .group_by("c_count").agg(F.count("*").alias("custdist"))
               .collect())
    by_count = dict(zip(starred.c_count, starred.custdist))
    ref = dict(zip(want.c_count, want.custdist))
    assert 0 not in by_count and by_count[1] == ref[1] + ref[0]
    assert not match.results_match(
        starred.sort_values(["custdist", "c_count"], ascending=False)
        .reset_index(drop=True), want)
    # the null-extended rows themselves: one a customer without orders
    nulls = joined.filter(F.col("o_orderkey").isNull()).collect()
    assert len(nulls) == ref[0]
    assert nulls.o_custkey.isna().all() and nulls.c_custkey.notna().all()


def test_two_contains_is_another_predicate(session):
    """What the cell's data cannot show: the words in the other order."""
    session.set_conf("spark.rapids.sql.test.enabled", True)
    df = session.create_dataframe(pd.DataFrame({
        "o_comment": ["special requests", "requests are special",
                      "specialrequests", "quick ideas", None]}), 2)
    like = df.filter(F.col("o_comment").like("%special%requests%")).collect()
    both = df.filter(F.col("o_comment").contains("special")
                     & F.col("o_comment").contains("requests")).collect()
    assert sorted(like.o_comment) == ["special requests", "specialrequests"]
    assert len(both) == 3


def _settled_growth(session, tables):
    """Counter growth over the third execution of Q13, and its answer."""
    for _ in range(2):
        q13.build(session, tables).collect()
    before = REGISTRY.values()
    got = q13.build(session, tables).collect()
    after = REGISTRY.values()

    def grown(name, **labels):
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        return after.get(key, 0) - before.get(key, 0)
    return grown, got, after


def test_q13_counters_read_what_a_hand_count_says(session, tables, frames):
    """ROW_GROUPS batches a table, each at the capacity bucket of its rows;
    a collapse's output has the bucket of its inputs' capacities and no row
    count on the host, so it counts at that capacity."""
    grown, got, after = _settled_growth(session, tables)
    orders, customers = len(frames["orders"]), len(frames["customer"])
    kept = len(_kept(frames))
    ref = q13.reference(frames)
    orderless = int(ref[ref.c_count == 0].custdist.iloc[0])
    cap = bucket_capacity
    orders_cap = cap(ROW_GROUPS * cap(orders // ROW_GROUPS))
    customers_cap = cap(ROW_GROUPS * cap(customers // ROW_GROUPS))
    # the four metrics this PR adds. Every order's comment is answered
    # from the dictionary, a batch of the scan at a time, by the rows its
    # upload left on the host
    assert grown("expr.dictPredicate.rows") == orders
    assert grown("expr.dictPredicate.batches", fn="like") == ROW_GROUPS
    # touched, with nothing: o_comment stays codes
    assert grown("strings.charsRebuilt.bytes") == 0
    assert ("strings.charsRebuilt.bytes", ()) in after
    # one expand: every kept order and one row an orderless customer
    assert grown("join.expand.outRows", type="left") \
        == cap(kept + orderless)
    # the build is the collapse of the filtered orders (two int64 keys and
    # their validity a slot, at the collapse's capacity); the stream is the
    # collapse of the customers (one key)
    assert grown("join.stream.rows", type="left") == customers_cap
    assert grown("join.inputBytes") == orders_cap * 18 + customers_cap * 9
    assert match.results_match(got, ref)


def test_dispatch_join_spans_name_the_join_type_and_the_out_cap(
        session, tables, frames):
    from spark_rapids_tpu.obs.trace import TRACER
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    try:
        q13.build(session, tables).collect()
        spans = [e for e in TRACER.events() if e["name"] == "dispatch.join"]
    finally:
        session.set_conf("spark.rapids.tpu.trace.enabled", False)
    assert spans and all(e["args"]["type"] == "left" for e in spans)
    caps = [e["args"]["out_cap"] for e in spans if "out_cap" in e["args"]]
    ref = q13.reference(frames)
    rows = len(_kept(frames)) + int(ref[ref.c_count == 0].custdist.iloc[0])
    assert caps == [bucket_capacity(rows)]


@pytest.mark.parametrize("how,rows", [
    ("inner", 4), ("left", 6), ("right", 5), ("full", 7),
    ("leftsemi", 2), ("leftanti", 2)])
def test_join_counters_count_every_join_type(session, how, rows):
    """``join.inputBytes`` takes both inputs once, whatever the type;
    ``join.expand.outRows`` every type that expands."""
    session.set_conf("spark.rapids.sql.test.enabled", True)
    session.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
    left = pd.DataFrame({"lk": np.array([1, 2, 2, 3], np.int64)})
    right = pd.DataFrame({"rk": np.array([2, 2, 4], np.int64),
                          "w": np.array([1.0, 2.0, 3.0])})
    before = REGISTRY.values()
    got = (session.create_dataframe(left, 1)
           .join(session.create_dataframe(right, 1), left_on=["lk"],
                 right_on=["rk"], how=how).collect())
    after = REGISTRY.values()
    assert len(got) == rows

    def grown(name, **labels):
        key = (name, tuple(sorted(labels.items())))
        return after.get(key, 0) - before.get(key, 0)
    # an int64 key and its validity a left row; a key, a float64 and their
    # validity a right row (a semi join's plan prunes the float); a right
    # outer join streams the right side against a build of the left, both
    # counted by their rows all the same
    stream, build = (3, 4) if how == "right" else (4, 3)
    stream_width = 18 if how == "right" else 9
    build_width = 18 if how in ("inner", "left", "full") else 9
    assert grown("join.stream.rows", type=how) == stream
    # a build side the host holds no row count for counts its capacity
    assert grown("join.inputBytes") - stream * stream_width in (
        build * build_width, bucket_capacity(build) * build_width)
    expands = how not in ("leftsemi", "leftanti")
    assert (grown("join.expand.outRows", type=how) > 0) is expands
