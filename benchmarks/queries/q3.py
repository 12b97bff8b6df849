"""TPC-H Q3, shipping priority (clause 2.4.3), with the specification's
validation parameters: segment BUILDING, date 1995-03-15."""

import datetime

READS = {"customer": ["c_custkey", "c_mktsegment"],
         "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                    "o_shippriority"],
         "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice",
                      "l_discount"]}


def build(session, tables):
    """Copied from ``spark_rapids_tpu/models/tpch.py`` q3."""
    from spark_rapids_tpu.sql import functions as F
    cutoff = datetime.date(1995, 3, 15)
    cust = tables["customer"].filter(F.col("c_mktsegment") == "BUILDING")
    orders = tables["orders"].filter(F.col("o_orderdate") < cutoff)
    li = tables["lineitem"].filter(F.col("l_shipdate") > cutoff)
    revenue = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    return (cust.join(orders, left_on=["c_custkey"], right_on=["o_custkey"])
            .join(li, left_on=["o_orderkey"], right_on=["l_orderkey"])
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(revenue).alias("revenue"))
            .order_by(F.col("revenue").desc(), "o_orderdate")
            .limit(10))


def reference(frames):
    """Plain pandas over the generated frames."""
    import pandas as pd
    cutoff = pd.Timestamp(1995, 3, 15)
    cust = frames["customer"]
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = frames["orders"]
    orders = orders[orders.o_orderdate < cutoff]
    li = frames["lineitem"]
    li = li[li.l_shipdate > cutoff]
    j = (cust.merge(orders, left_on="c_custkey", right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey"))
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    out = (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                     sort=False)
           .agg(revenue=("revenue", "sum")).reset_index())
    return (out.sort_values(["revenue", "o_orderdate"],
                            ascending=[False, True], kind="stable")
            .head(10).reset_index(drop=True))


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
