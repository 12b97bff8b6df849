"""TPC-H Q18 as the benchmark's cell ``tpch-sf10-state.q18`` runs it, at a
small size on the CPU: the engine through Parquet and ``collect()`` against
the plain pandas reference of ``benchmarks/queries/q18.py`` on
``benchmarks/data.py`` tables where the HAVING keeps rows, and what that
configuration leans on (the partial pass's skip, the semi join, the top-100
tie, the counters the cell's per-layer metrics read)."""

import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.batch import DeviceBatch, bucket_capacity
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SF = 0.02          # 120,000 lines over 30,000 orders: about 100 pass
SEED = 2**31 + 18
ROW_GROUPS = 6     # a split is a row group: several batches a table
SKIP_RATIO = "spark.rapids.sql.agg.skipAggPassReductionRatio"
# at SF10 the planner shuffles both lineitem sides and orders and broadcasts
# customer alone; at this size every table is under the default threshold,
# so the tests lower it until the plan is the one SF10 gets
SF10_PLAN = {"spark.rapids.sql.test.enabled": True,
             "spark.rapids.sql.autoBroadcastJoinThreshold": 200 << 10}


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "q18state_" + "_".join(parts)[:-3].replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


data = _bench_module("data.py")
match = _bench_module("match.py")
q18 = _bench_module("queries", "q18.py")


def _counter(name):
    """A registry counter summed over its labels."""
    return sum(v for (n, _), v in REGISTRY.values().items() if n == name)


@pytest.fixture(scope="module")
def frames():
    return {t: data.gen_table(t, SF, SEED).select(cols).to_pandas()
            for t, cols in q18.READS.items()}


@pytest.fixture(scope="module")
def parquet_dir(tmp_path_factory, frames):
    root = tmp_path_factory.mktemp("q18")
    for t, cols in q18.READS.items():
        table = data.gen_table(t, SF, SEED).select(cols)
        pq.write_table(table, str(root / f"{t}.parquet"),
                       row_group_size=-(-len(table) // ROW_GROUPS))
    return root


@pytest.fixture
def tables(session, parquet_dir):
    for key, value in SF10_PLAN.items():
        session.set_conf(key, value)
    return {t: session.read.parquet(str(parquet_dir / f"{t}.parquet"))
            for t in q18.READS}


def test_the_plan_is_the_one_sf10_gets(session, tables, capsys):
    plan = q18.build(session, tables).explain()
    capsys.readouterr()
    joins = [line.strip() for line in plan.splitlines() if "Join" in line]
    assert len(joins) == 3 and all("JoinExec(" in j for j in joins), plan
    assert plan.count("BroadcastExchange") == 1, plan   # customer


def test_the_having_keeps_rows_at_this_size(frames):
    qty = frames["lineitem"].groupby("l_orderkey")["l_quantity"].sum()
    assert 50 <= (qty > 300).sum() <= 200
    assert len(q18.reference(frames)) == min(100, (qty > 300).sum())


def test_q18_matches_the_pandas_reference(session, tables, frames):
    want = q18.reference(frames)
    # thrice: the plan changes between the first and the third execution
    # (the dense-key plan, the partial skip and the capacity speculation
    # engage from what earlier executions left with the session)
    for _ in range(3):
        got = q18.build(session, tables).collect()
        assert match.results_match(got, want), f"{got}\n{want}"
    assert list(got["o_totalprice"]) == list(want["o_totalprice"])


def test_partial_pass_skips_and_the_answer_does_not_depend_on_it(
        session, tables, frames):
    want = q18.reference(frames)
    before = _counter("agg.partial.passthroughRows")
    for _ in range(2):
        got = q18.build(session, tables).collect()
    skipped = _counter("agg.partial.passthroughRows") - before
    # 20,000 lines a batch over 30,000 order keys keep about 14,600
    # groups: 0.73 of the input, above the default ratio of 0.45
    assert skipped > 0
    assert match.results_match(got, want)
    session.set_conf(SKIP_RATIO, 1.0)   # a ratio no pass can exceed
    before = _counter("agg.partial.passthroughRows")
    merged = _counter("agg.merge.inputRows")
    got = q18.build(session, tables).collect()
    assert _counter("agg.partial.passthroughRows") == before
    assert _counter("agg.merge.inputRows") > merged
    assert match.results_match(got, want)


def test_semi_join_keeps_exactly_the_reference_orders(session, tables,
                                                      frames):
    qty = frames["lineitem"].groupby("l_orderkey")["l_quantity"].sum()
    want = sorted(qty[qty > 300].index)
    big = (tables["lineitem"].group_by("l_orderkey")
           .agg(F.sum("l_quantity").alias("sum_qty"))
           .filter(F.col("sum_qty") > 300))
    semi = REGISTRY.counter("join.stream.rows", type="leftsemi")
    before = semi.value
    got = (tables["orders"]
           .join(big, left_on=["o_orderkey"], right_on=["l_orderkey"],
                 how="leftsemi")
           .select("o_orderkey").collect())
    assert sorted(got["o_orderkey"]) == want
    # the stream side is every order, in one collapsed batch or several
    assert semi.value - before >= len(frames["orders"])


def _tie_frames():
    """150 orders of 7 lines of 50 (350 each, all pass). Orders 95..104 by
    price share one ``o_totalprice``, so the top 100 ends inside the tie
    and ``o_orderdate`` decides which five of the ten are kept."""
    n = 150
    keys = np.arange(1, n + 1, dtype=np.int64) * 4
    price = 1000.0 * np.arange(n, 0, -1, dtype=np.float64)
    price[95:105] = price[95]
    days = np.arange(n)
    days[95:105] = days[95:105][::-1]     # later keys carry earlier dates
    orders = pd.DataFrame({
        "o_orderkey": keys, "o_custkey": (np.arange(n) % 30) + 1,
        "o_orderdate": (np.datetime64("1995-01-01", "s")
                        + days * np.timedelta64(86400, "s")),
        "o_totalprice": price})
    lineitem = pd.DataFrame({"l_orderkey": np.repeat(keys, 7),
                             "l_quantity": np.full(7 * n, 50.0)})
    custkey = np.arange(1, 31, dtype=np.int64)
    customer = pd.DataFrame({"c_custkey": custkey,
                             "c_name": [f"Customer#{k}" for k in custkey]})
    return {"lineitem": lineitem, "orders": orders, "customer": customer}


def test_tie_at_the_top_100_boundary_resolves_by_orderdate(session):
    session.set_conf("spark.rapids.sql.test.enabled", True)
    frames = _tie_frames()
    want = q18.reference(frames)
    tables = {t: session.create_dataframe(df, 3) for t, df in frames.items()}
    got = q18.build(session, tables).collect()
    assert match.results_match(got, want), f"{got}\n{want}"
    # of the tied orders (positions 95..104 by key) the five latest keys
    # have the five earliest dates, and are the ones in the answer
    tied = set(frames["orders"]["o_orderkey"][95:105])
    assert set(got["o_orderkey"]) & tied \
        == set(frames["orders"]["o_orderkey"][100:105])
    assert list(got["o_orderkey"]) == list(want["o_orderkey"])


def _batch(rows, known):
    b = DeviceBatch.from_pandas(pd.DataFrame({
        "k": np.arange(rows, dtype=np.int64),
        "v": np.ones(rows, dtype=np.float64)}))
    if not known:
        b._host_rows = None
    return b


def test_counting_rows_reads_the_hint_else_the_capacity():
    from spark_rapids_tpu.exec.tpu import _counting_rows
    c = REGISTRY.counter("test.q18.countingRows")
    kernel = _counting_rows(c, lambda b, *rest: (b, rest))
    known, unknown = _batch(1000, True), _batch(1000, False)
    assert kernel(known)[0] is known and c.value == 1000
    assert kernel(unknown, 7)[1] == (7,)
    assert c.value == 1000 + bucket_capacity(1000)


def test_collapse_counters_and_span_read_what_was_concatenated(session):
    from spark_rapids_tpu.exec.tpu import _collapse_concat
    from spark_rapids_tpu.obs.trace import TRACER
    batches = [_batch(n, True) for n in (1000, 3000, 500)]
    nbytes = sum(b.device_memory_size() for b in batches)
    # int64 + float64 + two validity bytes a slot, at each batch's capacity
    assert nbytes >= sum(b.capacity for b in batches) * 18
    before = (_counter("exchange.collapse.batches"),
              _counter("exchange.collapse.bytes"))
    was = TRACER.enabled
    TRACER.configure(True)
    try:
        out = _collapse_concat(batches, batches[0].schema, 2.0)
        spans = [e for e in TRACER.events()
                 if e["name"] == "exchange.collapse"]
    finally:
        TRACER.configure(was)
    assert out.num_rows_host() == 4500
    assert _counter("exchange.collapse.batches") - before[0] == 3
    assert _counter("exchange.collapse.bytes") - before[1] == nbytes
    assert spans[-1]["args"]["batches"] == 3
    assert spans[-1]["args"]["bytes"] == nbytes


def _settled_growth(session, tables):
    """Counter growth over the third execution of Q18 (the plan has settled
    by then), and that execution's answer."""
    for _ in range(2):
        q18.build(session, tables).collect()
    before = REGISTRY.values()
    got = q18.build(session, tables).collect()
    after = REGISTRY.values()

    def grown(name, **labels):
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        return after.get(key, 0) - before.get(key, 0)
    return grown, got


def test_q18_counters_read_what_a_hand_count_says(session, tables, frames):
    """ROW_GROUPS batches a table, each at the capacity bucket of its rows;
    a collapse's output has the bucket of its inputs' capacities and no row
    count on the host, so it counts at that capacity."""
    grown, got = _settled_growth(session, tables)
    lines, orders = len(frames["lineitem"]), len(frames["orders"])
    qty = frames["lineitem"].groupby("l_orderkey")["l_quantity"].sum()
    kept = int((qty > 300).sum())
    cap = bucket_capacity
    lines_cap = cap(ROW_GROUPS * cap(lines // ROW_GROUPS))
    orders_cap = cap(ROW_GROUPS * cap(orders // ROW_GROUPS))
    # the aggregate over l_orderkey skips its partial pass: every line is
    # passed on, counted by the row count its upload left on the host
    assert grown("agg.partial.passthroughRows") == lines
    # its final merge takes the collapse of those batches; the five-key
    # final merge takes one shrunk batch of one row an order kept
    assert grown("agg.merge.inputRows") == lines_cap + kept
    # at a key, a float64 sum and two validity bytes a row; the five-key
    # layout is a 4-byte string code, four 8-byte keys and the sum
    assert grown("agg.merge.inputBytes") == lines_cap * 18 + kept * 50
    # seven collapses: lineitem twice and orders at ROW_GROUPS batches,
    # the HAVING's output, the customer join's, the five-key partial's and
    # the sort's range exchange at one batch each
    assert grown("exchange.collapse.batches") == 3 * ROW_GROUPS + 4
    # key, sum and two validity bytes a lineitem slot, twice; four columns
    # and their validity an orders slot; an int32 row count a batch
    big = (2 * ROW_GROUPS * (cap(lines // ROW_GROUPS) * 18 + 4)
           + ROW_GROUPS * (cap(orders // ROW_GROUPS) * 36 + 4))
    assert big <= grown("exchange.collapse.bytes") <= big + (64 << 10)
    # the semi join streams the collapse of orders; the customer join
    # streams what the semi join kept, at the capacity it arrived in, and
    # the last join the rows that are left, known on the host by then
    assert grown("join.stream.rows", type="leftsemi") == orders_cap
    assert grown("join.stream.rows", type="inner") == orders_cap + kept
    assert match.results_match(got, q18.reference(frames))


def _gather_form(batch, keep):
    """filter_batch as it was before the carrying sort: the compaction
    permutation and a gather of every column by it."""
    from spark_rapids_tpu.ops import rowops
    from spark_rapids_tpu.ops.tablekernels import compact_permutation
    perm, rows = compact_permutation(keep & batch.row_mask())
    return rowops.gather_batch(batch, perm, rows)


@pytest.mark.parametrize("columns", [
    ["k"], ["k", "v"], ["k", "v", "f"], ["k", "v", "f", "d"]])
def test_filter_compaction_by_sort_equals_the_gather_form(columns, rng):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rowops
    n = 10_000
    df = pd.DataFrame({
        "k": pd.Series(rng.integers(0, 1000, n)).astype("Int64").mask(
            pd.Series(rng.random(n) < 0.1)),
        "v": rng.uniform(0, 400, n),
        "f": rng.random(n) < 0.5,
        "d": (np.datetime64("1995-01-01", "s")
              + rng.integers(0, 2000, n) * np.timedelta64(86400, "s"))})
    kept = df["v"].to_numpy() > 300
    batch = DeviceBatch.from_pandas(df[columns])
    # padding slots ask to be kept too: the row mask has the last word
    keep = jnp.asarray(np.r_[kept, np.ones(batch.capacity - n, bool)])
    got = rowops.filter_batch(batch, keep)
    assert got.num_rows_host() == int(kept.sum())
    pd.testing.assert_frame_equal(got.to_pandas(),
                                  _gather_form(batch, keep).to_pandas())
    pd.testing.assert_frame_equal(
        got.to_pandas(), df[columns][kept].reset_index(drop=True),
        check_dtype=False)


def test_filter_of_a_string_or_a_wide_batch_keeps_the_gather_form(rng):
    """Strings and batches of more than four columns are not carried by a
    sort; the answer is the same filter."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rowops
    n = 3000
    wide = pd.DataFrame({f"c{i}": rng.integers(0, 99, n) for i in range(5)})
    named = pd.DataFrame({"k": rng.integers(0, 99, n),
                          "s": [f"Customer#{i}" for i in range(n)]})
    for df in (wide, named):
        kept = df.iloc[:, 0].to_numpy() < 30
        batch = DeviceBatch.from_pandas(df)
        keep = jnp.asarray(np.r_[kept, np.zeros(batch.capacity - n, bool)])
        pd.testing.assert_frame_equal(
            rowops.filter_batch(batch, keep).to_pandas(),
            df[kept].reset_index(drop=True), check_dtype=False)


def test_inner_join_with_a_build_side_far_wider_than_its_stream(
        session, rng):
    """Q18's last join in small: a 300-row left side with duplicate keys, a
    string column and nulls streams past a right side of 90,000 rows
    collapsed into one batch; left columns first, every pair once."""
    session.set_conf("spark.rapids.sql.test.enabled", True)
    session.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
    left = pd.DataFrame({
        "lk": pd.Series(rng.integers(0, 200, 300)).astype("Int64").mask(
            pd.Series(rng.random(300) < 0.05)),
        "name": [f"n{i % 37}" for i in range(300)]})
    n = 90_000
    right = pd.DataFrame({"rk": rng.integers(0, 5000, n),
                          "w": rng.uniform(0, 1, n)})
    inner = REGISTRY.counter("join.stream.rows", type="inner")
    before = inner.value
    got = (session.create_dataframe(left, 2)
           .join(session.create_dataframe(right, 3), left_on=["lk"],
                 right_on=["rk"]).collect())
    assert 300 <= inner.value - before < n          # the left side streamed
    want = left.dropna().astype({"lk": "int64"}).merge(
        right, left_on="lk", right_on="rk")
    assert list(got.columns) == ["lk", "name", "rk", "w"]
    assert match.results_match(got, want[list(got.columns)])


@pytest.mark.parametrize("top_key,dense", [
    (1 << 20, True),
    ((1 << 24) + 5, True),      # above the cap that was: Q18's o_orderkey
    (1 << 27, True),
    ((1 << 27) + 1, True),      # past the packed table: the compact one
    (1 << 28, True),
    ((1 << 28) + 1, False)])    # a table of more than 2 GB, even compact
def test_dense_probe_takes_a_key_range_up_to_its_cap(session, top_key,
                                                     dense):
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.columnar.dtype import INT64
    from spark_rapids_tpu.exec.base import ExecContext, PhysicalPlan
    from spark_rapids_tpu.exec.tpujoin import TpuShuffledHashJoinExec
    join = TpuShuffledHashJoinExec(PhysicalPlan(), PhysicalPlan(), "inner",
                                   [0], [0])
    session.column_stats["q18_cap_bk"] = (1, top_key)
    found = join._dense_plan(ExecContext(session.conf, session),
                             Schema(["q18_cap_bk"], [INT64]))
    assert (found is not None) is dense
    if dense:
        assert found[0] == 1 and found[1] >= top_key
