"""TPC-H Q1, pricing summary report (clause 2.4.1), with the
specification's validation parameter: DELTA 90 days (1998-09-02)."""

import datetime

READS = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                      "l_quantity", "l_extendedprice", "l_discount",
                      "l_tax"]}


def build(session, tables):
    """Copied from ``spark_rapids_tpu/models/tpch.py`` q1."""
    from spark_rapids_tpu.sql import functions as F
    li = tables["lineitem"]
    disc_price = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    charge = (F.col("l_extendedprice") * (1 - F.col("l_discount"))
              * (1 + F.col("l_tax")))
    return (li.filter(F.col("l_shipdate") <= datetime.date(1998, 9, 2))
            .group_by("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base_price"),
                 F.sum(disc_price).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count("*").alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


def reference(frames):
    """Plain pandas over the generated frames."""
    import pandas as pd
    li = frames["lineitem"]
    li = li[li.l_shipdate <= pd.Timestamp(1998, 9, 2)]
    disc_price = li.l_extendedprice * (1 - li.l_discount)
    li = li.assign(disc_price=disc_price,
                   charge=disc_price * (1 + li.l_tax))
    out = li.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    return out.reset_index()


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
