"""Synthetic TPC-H-like data generator.

The reference ships TPC-H-like workloads fed from pre-converted files
(integration_tests/.../tpch/TpchLikeSpark.scala); this generator produces
statistically similar tables in-memory (or to Parquet) at a given scale
factor so benchmarks and tests are self-contained. Distributions follow the
TPC-H spec shapes (uniform quantities 1..50, discounts 0..0.10, 7-year date
range, A/N/R return flags), not dbgen's exact streams.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import pandas as pd

LINEITEM_ROWS_PER_SF = 6_000_000
ORDERS_ROWS_PER_SF = 1_500_000
CUSTOMER_ROWS_PER_SF = 150_000
PART_ROWS_PER_SF = 200_000
SUPPLIER_ROWS_PER_SF = 10_000
PARTSUPP_ROWS_PER_SF = 800_000

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

_EPOCH_1992 = np.datetime64("1992-01-01", "D").astype(int)
_DATE_RANGE_DAYS = 2526  # 1992-01-01 .. 1998-12-01


def gen_lineitem(sf: float, seed: int = 7) -> pd.DataFrame:
    n = max(1, int(LINEITEM_ROWS_PER_SF * sf))
    rng = np.random.default_rng(seed)
    orderkey = rng.integers(1, max(2, int(ORDERS_ROWS_PER_SF * sf)) * 4, n)
    ship_days = _EPOCH_1992 + rng.integers(0, _DATE_RANGE_DAYS, n)
    returnflag = np.array(["A", "N", "R"], dtype=object)[
        rng.integers(0, 3, n)]
    linestatus = np.array(["O", "F"], dtype=object)[rng.integers(0, 2, n)]
    commit_days = ship_days + rng.integers(-30, 60, n)
    receipt_days = ship_days + rng.integers(1, 30, n)
    shipmode = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                         "FOB"], dtype=object)[rng.integers(0, 7, n)]
    shipinstruct = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                             "TAKE BACK RETURN"], dtype=object)[
        rng.integers(0, 4, n)]
    return pd.DataFrame({
        "l_orderkey": orderkey.astype(np.int64),
        "l_partkey": rng.integers(1, max(2, int(PART_ROWS_PER_SF * sf)), n),
        "l_suppkey": rng.integers(1, max(2, int(SUPPLIER_ROWS_PER_SF * sf)), n),
        "l_linenumber": rng.integers(1, 8, n).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, n), 2),
        "l_discount": np.round(rng.integers(0, 11, n) * 0.01, 2),
        "l_tax": np.round(rng.integers(0, 9, n) * 0.01, 2),
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": ship_days.astype("datetime64[D]").astype("datetime64[s]"),
        "l_commitdate": commit_days.astype("datetime64[D]").astype("datetime64[s]"),
        "l_receiptdate": receipt_days.astype("datetime64[D]").astype("datetime64[s]"),
        "l_shipmode": shipmode,
        "l_shipinstruct": shipinstruct,
    })


def gen_orders(sf: float, seed: int = 11) -> pd.DataFrame:
    n = max(1, int(ORDERS_ROWS_PER_SF * sf))
    rng = np.random.default_rng(seed)
    order_days = _EPOCH_1992 + rng.integers(0, _DATE_RANGE_DAYS - 151, n)
    status = np.array(["O", "F", "P"], dtype=object)[rng.integers(0, 3, n)]
    prio = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                     "5-LOW"], dtype=object)[rng.integers(0, 5, n)]
    comment_bits = np.array(["", "special requests sleep", "above the ideas",
                             "special packages wake among the requests",
                             "furiously pending deposits", "quick ideas"],
                            dtype=object)[rng.integers(0, 6, n)]
    return pd.DataFrame({
        "o_orderkey": np.arange(1, n + 1, dtype=np.int64) * 4,
        "o_custkey": rng.integers(1, max(2, int(CUSTOMER_ROWS_PER_SF * sf)), n),
        "o_orderstatus": status,
        "o_totalprice": np.round(rng.uniform(850.0, 560000.0, n), 2),
        "o_orderdate": order_days.astype("datetime64[D]").astype("datetime64[s]"),
        "o_orderpriority": prio,
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": comment_bits,
    })


def gen_customer(sf: float, seed: int = 13) -> pd.DataFrame:
    n = max(1, int(CUSTOMER_ROWS_PER_SF * sf))
    rng = np.random.default_rng(seed)
    segment = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
                        "HOUSEHOLD"], dtype=object)[rng.integers(0, 5, n)]
    cc = np.char.add(rng.integers(10, 35, n).astype(str), "-")
    phone = np.char.add(cc, rng.integers(100, 999, n).astype(str)).astype(object)
    return pd.DataFrame({
        "c_custkey": np.arange(1, n + 1, dtype=np.int64),
        "c_name": np.char.add("Customer#", np.arange(1, n + 1).astype(str))
                    .astype(object),
        "c_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "c_mktsegment": segment,
        "c_phone": phone,
    })


def gen_supplier(sf: float, seed: int = 17) -> pd.DataFrame:
    n = max(1, int(SUPPLIER_ROWS_PER_SF * sf))
    rng = np.random.default_rng(seed)
    comment = np.array(["", "Customer Complaints about everything",
                        "quick deliveries", "slept furiously"],
                       dtype=object)[rng.integers(0, 4, n)]
    return pd.DataFrame({
        "s_suppkey": np.arange(1, n + 1, dtype=np.int64),
        "s_name": np.char.add("Supplier#", np.arange(1, n + 1).astype(str))
                    .astype(object),
        "s_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "s_address": np.char.add("addr ", np.arange(n).astype(str))
                       .astype(object),
        "s_comment": comment,
    })


def gen_part(sf: float, seed: int = 19) -> pd.DataFrame:
    n = max(1, int(PART_ROWS_PER_SF * sf))
    rng = np.random.default_rng(seed)
    brand = np.array([f"Brand#{i}{j}" for i in range(1, 6)
                      for j in range(1, 6)], dtype=object)
    container = np.array(["SM CASE", "SM BOX", "MED BAG", "MED BOX",
                          "LG CASE", "LG BOX", "JUMBO PKG", "WRAP JAR"],
                         dtype=object)
    w = np.asarray(_P_NAME_WORDS, dtype=object)
    name = (w[rng.integers(0, len(w), n)] + " "
            + w[rng.integers(0, len(w), n)] + " "
            + w[rng.integers(0, len(w), n)])
    ptype = (np.asarray(_P_TYPE_1, dtype=object)[rng.integers(0, 6, n)] + " "
             + np.asarray(_P_TYPE_2, dtype=object)[rng.integers(0, 5, n)] + " "
             + np.asarray(_P_TYPE_3, dtype=object)[rng.integers(0, 5, n)])
    return pd.DataFrame({
        "p_partkey": np.arange(1, n + 1, dtype=np.int64),
        "p_name": name,
        "p_mfgr": np.char.add("Manufacturer#",
                              rng.integers(1, 6, n).astype(str)).astype(object),
        "p_brand": brand[rng.integers(0, len(brand), n)],
        "p_type": ptype,
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": container[rng.integers(0, len(container), n)],
        "p_retailprice": np.round(rng.uniform(900.0, 2000.0, n), 2),
    })


def gen_partsupp(sf: float, seed: int = 23) -> pd.DataFrame:
    n = max(1, int(PARTSUPP_ROWS_PER_SF * sf))
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "ps_partkey": rng.integers(1, max(2, int(PART_ROWS_PER_SF * sf)),
                                   n).astype(np.int64),
        "ps_suppkey": rng.integers(1, max(2, int(SUPPLIER_ROWS_PER_SF * sf)),
                                   n).astype(np.int64),
        "ps_availqty": rng.integers(1, 10000, n).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n), 2),
    })


def gen_nation() -> pd.DataFrame:
    names = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
             "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
             "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
             "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
             "UNITED KINGDOM", "UNITED STATES"]
    regions = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
               4, 2, 3, 3, 1]
    return pd.DataFrame({
        "n_nationkey": np.arange(25, dtype=np.int32),
        "n_name": pd.Series(names),
        "n_regionkey": np.asarray(regions, dtype=np.int32),
    })


def gen_region() -> pd.DataFrame:
    return pd.DataFrame({
        "r_regionkey": np.arange(5, dtype=np.int32),
        "r_name": pd.Series(["AFRICA", "AMERICA", "ASIA", "EUROPE",
                             "MIDDLE EAST"]),
    })


ALL_TABLES = {
    "lineitem": gen_lineitem,
    "orders": gen_orders,
    "customer": gen_customer,
    "supplier": gen_supplier,
    "part": gen_part,
    "partsupp": gen_partsupp,
}


TABLE_NAMES = tuple(ALL_TABLES) + ("nation", "region")


def gen_table(name: str, sf: float,
              seed: Optional[int] = None) -> pd.DataFrame:
    """One table by name. ``seed`` is a run's seed, from which every
    table draws its own stream; None keeps each generator's fixed
    default (the data the tests pin)."""
    if name == "nation":
        return gen_nation()
    if name == "region":
        return gen_region()
    if seed is None:
        return ALL_TABLES[name](sf)
    return ALL_TABLES[name](
        sf, seed=seed * len(ALL_TABLES) + list(ALL_TABLES).index(name))


def write_parquet(out_dir: str, sf: float, tables=None,
                  seed: Optional[int] = None, row_groups: int = 1) -> None:
    """Write tables as Snappy Parquet (pyarrow's default codec), one
    file each. ``row_groups``: how many row groups a table is cut into
    (the scan plans one partition per row group), never below 64Ki rows
    a group — small tables stay whole."""
    import os
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(out_dir, exist_ok=True)
    names = tables or TABLE_NAMES
    for name in names:
        df = gen_table(name, sf, seed)
        pq.write_table(
            pa.Table.from_pandas(df, preserve_index=False),
            os.path.join(out_dir, f"{name}.parquet"),
            row_group_size=max(-(-len(df) // row_groups), 1 << 16))
