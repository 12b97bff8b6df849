"""The benchmark's TPC-H generator: tables from ``--seed`` as Snappy Parquet.

A copy of ``spark_rapids_tpu/models/tpch_data.py`` kept with the yardstick
(a later PR may change the program's generator, not this one), with three
changes:

* **The foreign key of TPC-H clause 1.4.2 holds.** The original draws
  ``l_orderkey`` uniformly over 1..4n while ``o_orderkey`` is every fourth
  integer, so only a quarter of lineitem joins an order. Here ``l_orderkey``
  is drawn from the order keys.
* It generates a table a row group at a time, each row group from its own
  stream ``(seed, table, group)`` and into its own part file, on a few
  threads, and builds the string columns in Arrow from small dictionaries
  instead of object arrays: the data are made anew in a run whenever the
  seed changes, so this is set-up every later check pays.
* It writes only the tables asked for (a cell's queries name theirs).

Every other departure from dbgen is the original's and is listed under
``assumed`` in ``configs/*.json``: uniform draws where dbgen has its own
streams, money and quantity as float64, dates as ``timestamp[s]``, a reduced
set of comment and name columns, uniform lines per order in place of 1-7.

Imports numpy and pyarrow only — nothing of the engine, nothing of jax.
"""

from __future__ import annotations

import functools
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

# rows at scale factor 1 (TPC-H clause 4.2.5); nation and region are fixed
ROWS_PER_SF = {"lineitem": 6_000_000, "orders": 1_500_000,
               "customer": 150_000, "part": 200_000, "supplier": 10_000,
               "partsupp": 800_000}
FIXED_ROWS = {"nation": 25, "region": 5}
TABLE_NAMES = tuple(ROWS_PER_SF) + tuple(FIXED_ROWS)

ROW_GROUPS = 8          # to a large table
MIN_GROUP_ROWS = 1 << 16  # small tables stay whole
GEN_THREADS = 8

_EPOCH_1992 = int(np.datetime64("1992-01-01", "D").astype(int))
_DATE_RANGE_DAYS = 2526  # 1992-01-01 .. 1998-12-01

_P_TYPE_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
_P_TYPE_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
_P_TYPE_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
_P_NAME_WORDS = ["almond", "antique", "aquamarine", "azure", "beige",
                 "bisque", "black", "blanched", "blue", "blush", "brown",
                 "burlywood", "burnished", "chartreuse", "chiffon", "choco",
                 "coral", "cornflower", "cream", "cyan", "dark", "deep",
                 "dim", "dodger", "drab", "firebrick", "floral", "forest",
                 "frosted", "gainsboro", "ghost", "goldenrod", "green",
                 "grey", "honeydew", "hot", "indian", "ivory", "khaki",
                 "lace", "lavender", "lawn", "lemon", "light", "lime",
                 "linen", "magenta", "maroon", "medium", "metallic"]
_NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
            "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
            "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
            "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
            "UNITED KINGDOM", "UNITED STATES"]
_NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                  3, 4, 2, 3, 3, 1]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]


def table_rows(name: str, sf: float) -> int:
    if name in FIXED_ROWS:
        return FIXED_ROWS[name]
    return max(1, int(ROWS_PER_SF[name] * sf))


# ---------------------------------------------------------------------------
# column builders
# ---------------------------------------------------------------------------

def _pick(rng, words, n) -> pa.Array:
    """``n`` uniform draws from a short list of strings."""
    codes = rng.integers(0, len(words), n).astype(np.int32)
    return pa.DictionaryArray.from_arrays(
        codes, pa.array(words, pa.string())).dictionary_decode()


def _dates(days) -> pa.Array:
    return pa.array(days.astype("datetime64[D]").astype("datetime64[s]"))


def _numbered(prefix: str, numbers) -> pa.Array:
    return pc.binary_join_element_wise(
        pa.scalar(prefix), pc.cast(pa.array(numbers), pa.string()), "")


def _lineitem(rng, lo, n, sf):
    ship = _EPOCH_1992 + rng.integers(0, _DATE_RANGE_DAYS, n)
    return {
        # clause 1.4.2: every l_orderkey is an o_orderkey (4, 8, .. 4n)
        "l_orderkey": 4 * rng.integers(1, table_rows("orders", sf) + 1, n),
        "l_partkey": rng.integers(1, max(2, table_rows("part", sf)), n),
        "l_suppkey": rng.integers(1, max(2, table_rows("supplier", sf)), n),
        "l_linenumber": rng.integers(1, 8, n).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, n), 2),
        "l_discount": np.round(rng.integers(0, 11, n) * 0.01, 2),
        "l_tax": np.round(rng.integers(0, 9, n) * 0.01, 2),
        "l_returnflag": _pick(rng, ["A", "N", "R"], n),
        "l_linestatus": _pick(rng, ["O", "F"], n),
        "l_shipdate": _dates(ship),
        "l_commitdate": _dates(ship + rng.integers(-30, 60, n)),
        "l_receiptdate": _dates(ship + rng.integers(1, 30, n)),
        "l_shipmode": _pick(rng, ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK",
                                  "MAIL", "FOB"], n),
        "l_shipinstruct": _pick(rng, ["DELIVER IN PERSON", "COLLECT COD",
                                      "NONE", "TAKE BACK RETURN"], n),
    }


def _orders(rng, lo, n, sf):
    return {
        "o_orderkey": np.arange(lo + 1, lo + n + 1, dtype=np.int64) * 4,
        "o_custkey": rng.integers(1, max(2, table_rows("customer", sf)), n),
        "o_orderstatus": _pick(rng, ["O", "F", "P"], n),
        "o_totalprice": np.round(rng.uniform(850.0, 560000.0, n), 2),
        "o_orderdate": _dates(
            _EPOCH_1992 + rng.integers(0, _DATE_RANGE_DAYS - 151, n)),
        "o_orderpriority": _pick(rng, ["1-URGENT", "2-HIGH", "3-MEDIUM",
                                       "4-NOT SPECIFIED", "5-LOW"], n),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _pick(rng, ["", "special requests sleep",
                                 "above the ideas",
                                 "special packages wake among the requests",
                                 "furiously pending deposits",
                                 "quick ideas"], n),
    }


def _customer(rng, lo, n, sf):
    keys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    phone = pc.binary_join_element_wise(
        pc.cast(pa.array(rng.integers(10, 35, n)), pa.string()),
        pc.cast(pa.array(rng.integers(100, 999, n)), pa.string()), "-")
    return {
        "c_custkey": keys,
        "c_name": _numbered("Customer#", keys),
        "c_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "c_mktsegment": _pick(rng, ["AUTOMOBILE", "BUILDING", "FURNITURE",
                                    "MACHINERY", "HOUSEHOLD"], n),
        "c_phone": phone,
    }


def _supplier(rng, lo, n, sf):
    keys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    return {
        "s_suppkey": keys,
        "s_name": _numbered("Supplier#", keys),
        "s_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "s_address": _numbered("addr ", keys - 1),
        "s_comment": _pick(rng, ["", "Customer Complaints about everything",
                                 "quick deliveries", "slept furiously"], n),
    }


def _part(rng, lo, n, sf):
    brands = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
    return {
        "p_partkey": np.arange(lo + 1, lo + n + 1, dtype=np.int64),
        "p_name": pc.binary_join_element_wise(
            _pick(rng, _P_NAME_WORDS, n), _pick(rng, _P_NAME_WORDS, n),
            _pick(rng, _P_NAME_WORDS, n), " "),
        "p_mfgr": _numbered("Manufacturer#", rng.integers(1, 6, n)),
        "p_brand": _pick(rng, brands, n),
        "p_type": pc.binary_join_element_wise(
            _pick(rng, _P_TYPE_1, n), _pick(rng, _P_TYPE_2, n),
            _pick(rng, _P_TYPE_3, n), " "),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": _pick(rng, ["SM CASE", "SM BOX", "MED BAG", "MED BOX",
                                   "LG CASE", "LG BOX", "JUMBO PKG",
                                   "WRAP JAR"], n),
        "p_retailprice": np.round(rng.uniform(900.0, 2000.0, n), 2),
    }


def _partsupp(rng, lo, n, sf):
    return {
        "ps_partkey": rng.integers(1, max(2, table_rows("part", sf)), n),
        "ps_suppkey": rng.integers(1, max(2, table_rows("supplier", sf)), n),
        "ps_availqty": rng.integers(1, 10000, n).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n), 2),
    }


def _nation(rng, lo, n, sf):
    return {"n_nationkey": np.arange(25, dtype=np.int32),
            "n_name": pa.array(_NATIONS, pa.string()),
            "n_regionkey": np.asarray(_NATION_REGION, dtype=np.int32)}


def _region(rng, lo, n, sf):
    return {"r_regionkey": np.arange(5, dtype=np.int32),
            "r_name": pa.array(_REGIONS, pa.string())}


_BUILDERS = {"lineitem": _lineitem, "orders": _orders, "customer": _customer,
             "part": _part, "supplier": _supplier, "partsupp": _partsupp,
             "nation": _nation, "region": _region}


# ---------------------------------------------------------------------------
# tables
# ---------------------------------------------------------------------------

def _groups(n: int):
    """[(first row, rows)] of a table's row groups."""
    k = max(1, min(ROW_GROUPS, n // MIN_GROUP_ROWS))
    size = -(-n // k)
    return [(lo, min(size, n - lo)) for lo in range(0, n, size)]


def gen_group(name: str, sf: float, seed: int, group: int) -> pa.Table:
    """One row group of one table, from its own stream."""
    lo, n = _groups(table_rows(name, sf))[group]
    rng = np.random.default_rng(
        [int(seed), TABLE_NAMES.index(name), int(group)])
    cols = _BUILDERS[name](rng, lo, n, sf)
    return pa.table({k: v if isinstance(v, (pa.Array, pa.ChunkedArray))
                     else pa.array(v) for k, v in cols.items()})


def gen_table(name: str, sf: float, seed: int) -> pa.Table:
    n_groups = len(_groups(table_rows(name, sf)))
    return pa.concat_tables(
        gen_group(name, sf, seed, g) for g in range(n_groups))


def _write_group(table_dir: str, name: str, sf: float, seed: int,
                 group: int) -> None:
    pq.write_table(gen_group(name, sf, seed, group),
                   os.path.join(table_dir, f"part-{group:05d}.parquet"))


def dataset_dir(root: str, sf: float, seed: int) -> str:
    return os.path.join(root, f"tpch-sf{sf:g}-seed{seed}")


def ensure_tables(root: str, sf: float, seed: int, tables) -> tuple:
    """The directory that holds ``tables`` for (sf, seed), generating what
    is missing. Returns (directory, tables written now).

    A table is a directory ``<name>.parquet/`` of Snappy Parquet part files
    (pyarrow's default codec), one row group each, as Spark writes a table:
    the groups are generated and written side by side on GEN_THREADS
    threads. It is written under a temporary name and renamed, so a killed
    run leaves no half table. Data sets of the same scale factor and
    another seed are removed first: one seed's tables at a time bound the
    disk whatever seeds the runs of a check bring."""
    out = dataset_dir(root, sf, seed)
    os.makedirs(out, exist_ok=True)
    for other in os.listdir(root):
        if other.startswith(f"tpch-sf{sf:g}-seed") \
                and other != os.path.basename(out):
            shutil.rmtree(os.path.join(root, other))
    for stale in os.listdir(out):
        if ".tmp" in stale:
            shutil.rmtree(os.path.join(out, stale))
    missing = [t for t in tables
               if not os.path.isdir(os.path.join(out, f"{t}.parquet"))]
    tmp = {t: os.path.join(out, f"{t}.parquet.tmp{os.getpid()}")
           for t in missing}
    for d in tmp.values():
        os.makedirs(d)
    with ThreadPoolExecutor(GEN_THREADS) as pool:
        jobs = [pool.submit(_write_group, tmp[t], t, sf, seed, g)
                for t in missing
                for g in range(len(_groups(table_rows(t, sf))))]
        for job in jobs:
            job.result()
    for t in missing:
        os.replace(tmp[t], os.path.join(out, f"{t}.parquet"))
    return out, missing


# ---------------------------------------------------------------------------
# bytes a query must read, for roofline shares
# ---------------------------------------------------------------------------

# bytes of one value as the engine holds it on the device: the numeric and
# date columns at their Arrow width, a string column as one 4-byte code a
# row (every string column here that a shipped query reads has a handful of
# distinct values, so its characters are a rounding error)
@functools.lru_cache(maxsize=None)
def schema(name: str) -> pa.Schema:
    return gen_group(name, 0.0001, 0, 0).schema


def column_width(name: str, column: str) -> int:
    field = schema(name).field(column)
    if pa.types.is_string(field.type) or pa.types.is_large_string(field.type):
        return 4
    return field.type.bit_width // 8


def bytes_read(reads: dict, sf: float) -> int:
    """Bytes of the columns ``reads`` ({table: [column, ...]}) at scale
    factor ``sf``, each column counted once: the least a query that reads
    them can move through HBM."""
    return sum(table_rows(t, sf) * column_width(t, c)
               for t, cols in reads.items() for c in cols)
