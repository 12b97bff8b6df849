"""TPC-H Q5, local supplier volume (clause 2.4.5), with the specification's
validation parameters: region ASIA, 1994."""

import datetime

READS = {"region": ["r_regionkey", "r_name"],
         "nation": ["n_nationkey", "n_name", "n_regionkey"],
         "customer": ["c_custkey", "c_nationkey"],
         "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
         "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                      "l_discount"],
         "supplier": ["s_suppkey", "s_nationkey"]}


def build(session, tables):
    """Copied from ``spark_rapids_tpu/models/tpch.py`` q5."""
    from spark_rapids_tpu.sql import functions as F
    orders = tables["orders"].filter(
        (F.col("o_orderdate") >= datetime.date(1994, 1, 1))
        & (F.col("o_orderdate") < datetime.date(1995, 1, 1)))
    revenue = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    return (tables["region"].filter(F.col("r_name") == "ASIA")
            .join(tables["nation"], left_on=["r_regionkey"],
                  right_on=["n_regionkey"])
            .join(tables["customer"], left_on=["n_nationkey"],
                  right_on=["c_nationkey"])
            .join(orders, left_on=["c_custkey"], right_on=["o_custkey"])
            .join(tables["lineitem"], left_on=["o_orderkey"],
                  right_on=["l_orderkey"])
            .join(tables["supplier"], left_on=["l_suppkey", "n_nationkey"],
                  right_on=["s_suppkey", "s_nationkey"])
            .group_by("n_name")
            .agg(F.sum(revenue).alias("revenue"))
            .order_by(F.col("revenue").desc()))


def reference(frames):
    """Plain pandas over the generated frames."""
    import pandas as pd
    orders = frames["orders"]
    orders = orders[(orders.o_orderdate >= pd.Timestamp(1994, 1, 1))
                    & (orders.o_orderdate < pd.Timestamp(1995, 1, 1))]
    region = frames["region"]
    j = (region[region.r_name == "ASIA"]
         .merge(frames["nation"], left_on="r_regionkey",
                right_on="n_regionkey")
         .merge(frames["customer"], left_on="n_nationkey",
                right_on="c_nationkey")
         .merge(orders, left_on="c_custkey", right_on="o_custkey")
         .merge(frames["lineitem"], left_on="o_orderkey",
                right_on="l_orderkey")
         .merge(frames["supplier"], left_on=["l_suppkey", "n_nationkey"],
                right_on=["s_suppkey", "s_nationkey"]))
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    out = j.groupby("n_name", sort=False).agg(revenue=("revenue", "sum"))
    return (out.reset_index()
            .sort_values("revenue", ascending=False, kind="stable")
            .reset_index(drop=True))


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
