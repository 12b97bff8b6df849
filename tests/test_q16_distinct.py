"""TPC-H Q16 as the benchmark's cell ``tpch-distinct.q16`` runs it, at a
tiny size on the CPU: ``count(distinct ps_suppkey)`` as the specification
writes it and as the hand-written ``distinct().group_by().count()`` chain,
both through the one-pass operator (``exec/aggfuse.py``), against a plain
pandas reference written here, on seeded tables of this file's own with
what the cell's generator never draws: a null distinct key, a group whose
every distinct key is null, and an empty input."""

import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.batch import bucket_capacity
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SEED = 2**31 + 16
SIZES = (49, 14, 23, 45, 19, 3, 36, 9)
BRANDS = ["Brand#45", "Brand#11", "Brand#12", "Brand#13"]
TYPES = ["MEDIUM POLISHED TIN", "MEDIUM PLATED TIN", "SMALL POLISHED BRASS",
         "MEDIUM POLISHEDX", "LARGE BRUSHED STEEL"]
COMMENTS = ["", "Customer Complaints about everything", "quick deliveries",
            "Complaints of no Customer"]
ROW_GROUPS = 5     # a split is a row group: five stream batches
# the group whose every ps_suppkey is null: it reads 0 and is kept
NULL_GROUP = ("Brand#11", "SMALL POLISHED BRASS", 9)
SPELLINGS = ("count_distinct", "distinct_count")
# at SF100 ten coalesced join outputs reach the operator; at this size they
# would be one, so the tests lower the batch target until every stream
# batch's join output arrives on its own
SF100_PLAN = {"spark.rapids.sql.test.enabled": True,
              "spark.rapids.sql.batchSizeRows": 1024}


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "q16distinct_" + "_".join(parts)[:-3].replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


match = _bench_module("match.py")
q16 = _bench_module("queries", "q16.py")


@pytest.fixture(scope="module")
def frames():
    rng = np.random.default_rng(SEED)
    n_supp, n_part, n_ps = 60, 240, 6000
    supplier = pd.DataFrame({
        "s_suppkey": np.arange(1, n_supp + 1),
        "s_comment": rng.choice(COMMENTS, n_supp)})
    part = pd.DataFrame({
        "p_partkey": np.arange(1, n_part + 1),
        "p_brand": rng.choice(BRANDS, n_part),
        "p_type": rng.choice(TYPES, n_part),
        "p_size": rng.choice(SIZES[-3:] + (7, 50), n_part).astype(np.int32)})
    part.loc[:3, ["p_brand", "p_type", "p_size"]] = NULL_GROUP
    partsupp = pd.DataFrame({
        "ps_partkey": rng.integers(1, n_part + 1, n_ps),
        "ps_suppkey": pd.Series(rng.integers(1, n_supp + 1, n_ps))
        .astype("Int64").mask(pd.Series(rng.random(n_ps) < 0.05))})
    of_null_group = part.p_partkey[
        (part.p_brand == NULL_GROUP[0]) & (part.p_type == NULL_GROUP[1])
        & (part.p_size == NULL_GROUP[2])]
    partsupp.loc[partsupp.ps_partkey.isin(of_null_group), "ps_suppkey"] = pd.NA
    return {"supplier": supplier, "part": part, "partsupp": partsupp}


@pytest.fixture(scope="module")
def parquet_dir(tmp_path_factory, frames):
    root = tmp_path_factory.mktemp("q16")
    for t, frame in frames.items():
        table = pa.Table.from_pandas(frame, preserve_index=False)
        groups = ROW_GROUPS if t == "partsupp" else 2
        pq.write_table(table, str(root / f"{t}.parquet"),
                       row_group_size=-(-len(table) // groups))
    empty = pa.Table.from_pandas(frames["partsupp"].iloc[:0],
                                 preserve_index=False)
    pq.write_table(empty, str(root / "partsupp-empty.parquet"))
    return root


@pytest.fixture
def tables(session, parquet_dir):
    for key, value in SF100_PLAN.items():
        session.set_conf(key, value)
    return {t: session.read.parquet(str(parquet_dir / f"{t}.parquet"))
            for t in q16.READS}


def _joined(frames):
    """The rows the two joins and the three predicates keep, plain pandas:
    the anti join keeps a null ``ps_suppkey`` (it matches no supplier)."""
    supplier, part, partsupp = (frames[t] for t in
                                ("supplier", "part", "partsupp"))
    first = supplier.s_comment.str.find("Customer")
    bad = supplier.s_suppkey[
        (first >= 0) & (supplier.s_comment.str.rfind("Complaints") > first)]
    part = part[(part.p_brand != "Brand#45")
                & (part.p_type.str[:15] != "MEDIUM POLISHED")
                & part.p_size.isin(SIZES)]
    kept = partsupp[~partsupp.ps_suppkey.isin(bad).fillna(False)
                    .astype(bool)]
    return kept.merge(part, left_on="ps_partkey", right_on="p_partkey")


def _ordered(out):
    return (out.sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                            ascending=[False, True, True, True],
                            kind="stable").reset_index(drop=True))


def _reference(frames, spelling):
    j = _joined(frames)
    keys = ["p_brand", "p_type", "p_size"]
    if spelling == "count_distinct":   # nunique skips a null
        out = j.groupby(keys, sort=False)["ps_suppkey"].nunique()
    else:                              # count(*) of the distinct tuples
        out = (j[keys + ["ps_suppkey"]].drop_duplicates()
               .groupby(keys, sort=False).size())
    return _ordered(out.rename("supplier_cnt").reset_index())


def _build(session, tables, spelling):
    if spelling == "count_distinct":
        return q16.build(session, tables)
    complaints = tables["supplier"].filter(
        F.col("s_comment").like("%Customer%Complaints%"))
    part = tables["part"].filter(
        (F.col("p_brand") != "Brand#45")
        & ~F.col("p_type").like("MEDIUM POLISHED%")
        & F.col("p_size").isin(*SIZES))
    return (tables["partsupp"]
            .join(complaints, left_on=["ps_suppkey"], right_on=["s_suppkey"],
                  how="leftanti")
            .join(part, left_on=["ps_partkey"], right_on=["p_partkey"])
            .select("p_brand", "p_type", "p_size", "ps_suppkey").distinct()
            .group_by("p_brand", "p_type", "p_size")
            .agg(F.count("*").alias("supplier_cnt"))
            .order_by(F.col("supplier_cnt").desc(), "p_brand", "p_type",
                      "p_size"))


def _plan_names(session):
    return [type(n).__name__ for n in session.last_plan.walk()]


def test_the_data_hold_what_the_tests_lean_on(frames, parquet_dir):
    """Pairs repeated inside and across the five stream batches, an
    excluded supplier in every batch, nulls, ties."""
    ps = frames["partsupp"]
    size = -(-len(ps) // ROW_GROUPS)
    meta = pq.ParquetFile(str(parquet_dir / "partsupp.parquet")).metadata
    assert meta.num_row_groups == ROW_GROUPS
    bad = set(frames["supplier"].s_suppkey[
        frames["supplier"].s_comment == COMMENTS[1]])
    assert bad and COMMENTS[3] in set(frames["supplier"].s_comment)
    pairs = ps.dropna()[["ps_partkey", "ps_suppkey"]]
    batch = np.arange(len(ps))[pairs.index] // size
    for b in range(ROW_GROUPS):
        mine = pairs[batch == b]
        assert set(mine.ps_suppkey) & bad
        assert mine.duplicated().any()                 # inside a batch
    batches_of_a_pair = pairs.assign(b=batch).drop_duplicates().groupby(
        ["ps_partkey", "ps_suppkey"]).b.nunique()
    assert (batches_of_a_pair > 1).any()               # across batches
    assert ps.ps_suppkey.isna().sum() > 100
    want = _reference(frames, "count_distinct")
    assert want.supplier_cnt.duplicated().any()        # ties
    assert len(want) > 20


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("spelling", SPELLINGS)
def test_q16_matches_the_pandas_reference(session, tables, frames, spelling,
                                          fuse):
    session.set_conf("spark.rapids.sql.agg.fuseCountDistinct", fuse)
    want = _reference(frames, spelling)
    got = _build(session, tables, spelling).collect()
    names = _plan_names(session)
    assert ("TpuCountDistinctExec" in names) == fuse, names
    if fuse:   # one operator, and no aggregate over the four-key tuple
        assert names.count("TpuCountDistinctExec") == 1
        assert "TpuHashAggregateExec" not in names, names
    assert match.results_match(got, want), f"{got}\n{want}"
    # in the specification's order, ties broken by brand, type and size
    assert got.values.tolist() == want.values.tolist()
    assert [str(d) for d in got.dtypes][2:] == ["int32", "int64"]


def test_the_specification_form_plans_as_the_one_pass_operator(session,
                                                                tables):
    _build(session, tables, "count_distinct").collect()
    tree = session.last_plan.tree_string()
    lines = [ln.strip() for ln in tree.splitlines()]
    assert lines[0].startswith("TpuSortExec"), tree
    assert lines[1] == "TpuShuffleExchangeExec(range)", tree
    assert lines[2] == ("TpuCountDistinctExec(g2=[0, 1, 2], distinct=[3], "
                        "count_distinct)"), tree
    assert sum("JoinExec(leftanti" in ln for ln in lines) == 1, tree
    assert sum("JoinExec(inner" in ln for ln in lines) == 1, tree
    assert "HashAggregateExec" not in tree, tree


def test_a_null_is_not_counted_and_an_all_null_group_reads_zero(
        session, tables, frames):
    got = _build(session, tables, "count_distinct").collect()
    row = got[(got.p_brand == NULL_GROUP[0]) & (got.p_type == NULL_GROUP[1])
              & (got.p_size == NULL_GROUP[2])]
    assert row.supplier_cnt.tolist() == [0]
    assert got.iloc[-1].tolist() == list(NULL_GROUP) + [0]   # sorts last
    # count(*) over the distinct tuples counts the null as a value: the
    # two spellings differ exactly where a group holds a null key
    starred = _build(session, tables, "distinct_count").collect()
    j = _joined(frames)
    has_null = (j[j.ps_suppkey.isna()]
                .groupby(["p_brand", "p_type", "p_size"]).size())
    merged = got.merge(starred, on=["p_brand", "p_type", "p_size"])
    assert len(merged) == len(got) == len(starred)
    differs = merged[merged.supplier_cnt_x != merged.supplier_cnt_y]
    assert len(differs) == len(has_null) > 1
    assert (differs.supplier_cnt_y - differs.supplier_cnt_x == 1).all()


def test_no_excluded_supplier_is_counted(session, tables, frames):
    """The anti join under every stream batch: a group's count never
    exceeds the suppliers that are not on the list."""
    got = _build(session, tables, "count_distinct").collect()
    supplier = frames["supplier"]
    allowed = (supplier.s_comment != COMMENTS[1]).sum()
    assert got.supplier_cnt.max() <= allowed < len(supplier)
    # 'Complaints ... Customer' is the words in the other order: kept
    unfiltered = frames["partsupp"].merge(
        frames["part"], left_on="ps_partkey", right_on="p_partkey")
    assert got.supplier_cnt.sum() < unfiltered.groupby(
        ["p_brand", "p_type", "p_size"]).ps_suppkey.nunique().sum()


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("spelling", SPELLINGS)
def test_an_empty_input_gives_an_empty_frame(session, tables, parquet_dir,
                                             spelling, fuse):
    session.set_conf("spark.rapids.sql.agg.fuseCountDistinct", fuse)
    tables = dict(tables, partsupp=session.read.parquet(
        str(parquet_dir / "partsupp-empty.parquet")))
    got = _build(session, tables, spelling).collect()
    assert len(got) == 0
    assert list(got.columns) == ["p_brand", "p_type", "p_size",
                                 "supplier_cnt"]


def test_the_benchmark_reference_agrees_where_no_key_is_null(frames):
    """``benchmarks/queries/q16.py`` ``reference`` on this file's tables
    without their nulls equals this file's own."""
    clean = dict(frames, partsupp=frames["partsupp"].dropna().astype(
        {"ps_suppkey": "int64"}))
    want = _reference(clean, "count_distinct")
    assert match.results_match(q16.reference(clean), want)
    assert q16.reference(clean).values.tolist() == want.values.tolist()


def _growth(run):
    before = REGISTRY.values()
    out = run()
    after = REGISTRY.values()

    def grown(name, **labels):
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        return after.get(key, 0) - before.get(key, 0)
    return grown, out


def test_q16_counters_read_what_the_cell_s_metrics_need(session, tables,
                                                        frames):
    """More than one batch into the operator, counted as it is dispatched
    by what the host knows: a join's output has no row count on the host,
    so it counts at its capacity."""
    q16.build(session, tables).collect()
    grown, got = _growth(lambda: q16.build(session, tables).collect())
    assert grown("agg.distinct.batches") == ROW_GROUPS
    rows = grown("agg.distinct.inputRows")
    assert len(_joined(frames)) <= rows <= ROW_GROUPS * bucket_capacity(
        len(_joined(frames)))
    # two 4-byte codes, an int32, an int64 and four validity bytes a row
    assert grown("agg.distinct.inputBytes") == rows * 24
    assert grown("join.stream.rows", type="leftanti") \
        == len(frames["partsupp"])
    # every part's type (NOT LIKE 'x%') and every supplier's comment
    # (LIKE '%a%b%') answered from the dictionary, no chars rebuilt
    assert grown("expr.dictPredicate.rows") \
        == len(frames["part"]) + len(frames["supplier"])
    assert grown("strings.charsRebuilt.bytes") == 0
    assert match.results_match(got, _reference(frames, "count_distinct"))


@pytest.mark.parametrize("spelling", SPELLINGS)
def test_counters_and_spans_of_a_known_input(session, spelling):
    """Three batches of a known capacity: the counters grow by those rows
    at the least width of a row, the collapse's span says what it
    concatenated, the dispatch's which count it makes."""
    from spark_rapids_tpu.obs.trace import TRACER
    session.set_conf("spark.rapids.sql.test.enabled", True)
    rng = np.random.default_rng(SEED)
    n = 3000
    frame = pd.DataFrame({"g": rng.choice(["a", "b", "c"], n),
                          "k": rng.integers(0, 50, n)})
    pairs = session.create_dataframe(frame, 3)
    if spelling == "count_distinct":
        query = pairs.group_by("g").agg(F.count_distinct("k").alias("c"))
    else:
        query = (pairs.distinct().group_by("g")
                 .agg(F.count("*").alias("c")))
    query.collect()      # the plan is cached from here on
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    try:
        grown, got = _growth(query.collect)
        events = TRACER.events()
    finally:
        session.set_conf("spark.rapids.tpu.trace.enabled", False)
    assert dict(zip(got.g, got.c)) \
        == frame.groupby("g").k.nunique().to_dict()
    assert grown("agg.distinct.batches") == 3
    # the expansion projects __dist, and a projection's output has no row
    # count on the host (its capacity); the chain by hand reads the upload
    rows = 3 * bucket_capacity(n // 3) if spelling == "count_distinct" else n
    assert grown("agg.distinct.inputRows") == rows
    assert grown("agg.distinct.inputBytes") == rows * (4 + 1 + 8 + 1)
    collapse = [e["args"] for e in events
                if e["name"] == "agg.distinct.collapse"]
    assert len(collapse) == 1 and collapse[0]["batches"] == 3
    assert collapse[0]["capacity"] == bucket_capacity(
        3 * bucket_capacity(n // 3))
    assert collapse[0]["bytes"] >= collapse[0]["capacity"] * 14
    dispatch = [e["args"] for e in events if e["name"] == "dispatch.cdist"]
    assert [(d["capacity"], d["form"]) for d in dispatch] \
        == [(collapse[0]["capacity"], spelling)]


@pytest.mark.parametrize("spelling", SPELLINGS)
def test_a_fused_chain_is_counted_by_its_spelling(session, tables, spelling):
    """``agg.distinct.plans{form}`` counts a chain as it is fused: once
    when the plan is made, not when the plan cache hands it back, and
    never with the fusion off."""
    # a batch target no other test uses: the plan cache has not seen it
    session.set_conf("spark.rapids.sql.batchSizeRows",
                     1500 + SPELLINGS.index(spelling))
    other = SPELLINGS[1 - SPELLINGS.index(spelling)]
    grown, _ = _growth(lambda: _build(session, tables, spelling).collect())
    assert grown("agg.distinct.plans", form=spelling) == 1
    assert grown("agg.distinct.plans", form=other) == 0
    grown, _ = _growth(lambda: _build(session, tables, spelling).collect())
    assert grown("agg.distinct.plans", form=spelling) == 0
    session.set_conf("spark.rapids.sql.agg.fuseCountDistinct", False)
    grown, _ = _growth(lambda: _build(session, tables, spelling).collect())
    assert grown("agg.distinct.plans", form=spelling) == 0


def test_expand_totals_size_no_chars_for_a_dictionary_column():
    """The totals of a join size char buffers for the strings an expand
    copies by their chars; a dictionary column moves by its codes, so it
    reads 0 (at SF100 those totals were 11 s of the query), and a plain
    string column still reads the chars its matches need."""
    import jax
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.ops import joins as join_ops
    rng = np.random.default_rng(SEED)
    n = 600
    build = pd.DataFrame({
        "k": np.arange(n),
        "brand": rng.choice(BRANDS, n),                         # dictionary
        "name": [f"part-{i:04d}-{'x' * (i % 7)}" for i in range(n)]})
    stream = pd.DataFrame({"k": rng.integers(0, 2 * n, 900),
                           "type": rng.choice(TYPES, 900)})     # dictionary
    b, s = DeviceBatch.from_pandas(build), DeviceBatch.from_pandas(stream)
    assert b.columns[1].dict_values is not None
    assert b.columns[2].dict_values is None
    assert s.columns[1].dict_values is not None
    counts, bstart, bperm = join_ops.join_probe(b, s, [0], [0])
    sizes = [int(x) for x in jax.device_get(join_ops.expand_totals(
        b, s, counts, counts, bperm, bstart))]
    matched = stream.merge(build, on="k")
    assert sizes == [len(matched), 0, 0, int(matched.name.str.len().sum())]


def test_a_join_that_carries_dictionary_and_plain_strings(session):
    session.set_conf("spark.rapids.sql.test.enabled", True)
    session.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
    rng = np.random.default_rng(SEED)
    n = 500
    part = pd.DataFrame({
        "pk": np.arange(n), "brand": rng.choice(BRANDS, n),
        "name": [f"part-{i:04d}-{'x' * (i % 7)}" for i in range(n)]})
    ps = pd.DataFrame({"fk": rng.integers(0, n + 50, 2000),
                       "type": rng.choice(TYPES, 2000)})
    got = (session.create_dataframe(ps, 2)
           .join(session.create_dataframe(part, 2), left_on=["fk"],
                 right_on=["pk"]).collect())
    want = ps.merge(part, left_on="fk", right_on="pk")
    assert match.results_match(got, want)
