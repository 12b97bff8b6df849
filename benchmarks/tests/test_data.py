import numpy as np
import pyarrow.parquet as pq
import pytest

import data

SF = 0.02
BIG_SEED = 2**31 + 11  # more than 32 signed bits hold


@pytest.mark.parametrize("table", data.TABLE_NAMES)
def test_same_seed_same_table(table):
    a = data.gen_table(table, SF, BIG_SEED)
    assert a.equals(data.gen_table(table, SF, BIG_SEED))
    assert len(a) == data.table_rows(table, SF)
    if table not in data.FIXED_ROWS:
        assert not a.equals(data.gen_table(table, SF, BIG_SEED + 1))


def test_lineitem_foreign_key_holds():
    li = data.gen_table("lineitem", SF, 3)
    orders = data.gen_table("orders", SF, 3)
    keys = orders["o_orderkey"].to_numpy()
    assert len(np.unique(keys)) == len(keys)
    assert np.isin(li["l_orderkey"].to_numpy(), keys).all()
    # and the join is not a quarter of lineitem, as in the program's copy
    assert li["l_orderkey"].to_numpy().max() <= keys.max()


def test_keys_run_on_across_row_groups(monkeypatch):
    monkeypatch.setattr(data, "MIN_GROUP_ROWS", 1000)
    orders = data.gen_table("orders", SF, 3)
    assert len(data._groups(len(orders))) == data.ROW_GROUPS
    assert (orders["o_orderkey"].to_numpy()
            == 4 * np.arange(1, len(orders) + 1)).all()


def test_ensure_tables_writes_once_and_keeps_one_seed(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(data, "MIN_GROUP_ROWS", 10_000)
    root = str(tmp_path)
    out, written = data.ensure_tables(root, SF, 5, ["lineitem", "nation"])
    assert written == ["lineitem", "nation"]
    meta = [pq.read_metadata(str(p)) for p in
            sorted((tmp_path / "tpch-sf0.02-seed5" / "lineitem.parquet")
                   .iterdir())]
    assert len(meta) == data.ROW_GROUPS
    assert all(m.num_row_groups == 1 for m in meta)
    assert sum(m.num_rows for m in meta) == data.table_rows("lineitem", SF)
    want = data.gen_table("lineitem", SF, 5)
    # Parquet keeps timestamp[s] as timestamp[ms]: cast back to compare
    read = pq.read_table(out + "/lineitem.parquet").cast(want.schema)
    assert read.equals(want)
    assert data.ensure_tables(root, SF, 5, ["lineitem"])[1] == []
    assert data.ensure_tables(root, SF, 5, ["orders"])[1] == ["orders"]
    data.ensure_tables(root, SF, 6, ["nation"])
    assert sorted(p.name for p in tmp_path.iterdir()) \
        == ["tpch-sf0.02-seed6"]


def test_bytes_read_counts_each_column_once_at_its_width():
    # q6 at SF10: four 8-byte columns of 60M rows
    reads = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                          "l_extendedprice"]}
    assert data.bytes_read(reads, 10) == 4 * 8 * 60_000_000
    # a string column counts as one 4-byte code a row, an int32 as 4
    assert data.bytes_read({"lineitem": ["l_returnflag", "l_linenumber"]},
                           1) == 8 * 6_000_000
