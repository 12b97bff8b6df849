"""Each query's plain pandas reference against the engine's own answer, at
a tiny scale factor on whatever device the tests run on (the CPU)."""

import os

import pyarrow.parquet as pq
import pytest

import data
import run as harness
from match import results_match

SF = 0.02
SEED = 2**31 + 5
QUERIES = sorted(f[:-3] for f in os.listdir(os.path.join(harness.HERE,
                                                         "queries"))
                 if f.endswith(".py"))


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tpch"))
    return data.ensure_tables(root, SF, SEED, data.TABLE_NAMES)[0]


@pytest.fixture(scope="module")
def session():
    from spark_rapids_tpu.session import TpuSparkSession
    return TpuSparkSession.builder().config(
        "spark.rapids.sql.test.enabled", True).get_or_create()


def test_the_four_first_queries_ship():
    assert {"q1", "q3", "q5", "q6"} <= set(QUERIES)


@pytest.mark.parametrize("q", QUERIES)
def test_reference_agrees_with_the_engine(q, dataset, session):
    mod = harness.load_module("queries", q)
    frames = {t: pq.read_table(os.path.join(dataset, f"{t}.parquet"),
                               columns=cols).to_pandas()
              for t, cols in mod.READS.items()}
    want = mod.reference(frames)
    assert len(want) > 0
    tables = {t: session.read.parquet(os.path.join(dataset, f"{t}.parquet"))
              for t in mod.READS}
    got = mod.build(session, tables).collect()
    assert results_match(got, want), f"{got}\n{want}"
    assert mod.bytes_read(1.0) == data.bytes_read(mod.READS, 1.0) > 0


@pytest.mark.parametrize("q", QUERIES)
def test_reference_imports_nothing_of_the_engine(q):
    import ast
    path = os.path.join(harness.HERE, "queries", f"{q}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    ref = [n for n in tree.body
           if isinstance(n, ast.FunctionDef) and n.name == "reference"][0]
    names = [a.name for n in ast.walk(ref) if isinstance(n, ast.Import)
             for a in n.names] \
        + [n.module for n in ast.walk(ref) if isinstance(n, ast.ImportFrom)]
    top = [a.name for n in tree.body if isinstance(n, ast.Import)
           for a in n.names] \
        + [n.module for n in tree.body if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names + top if m.startswith("spark_rapids_tpu")]
