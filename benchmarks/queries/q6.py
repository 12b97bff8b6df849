"""TPC-H Q6, forecasting revenue change (clause 2.4.6), with the
specification's validation parameters: 1994, discount 0.06, quantity 24."""

import datetime

READS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice"]}


def build(session, tables):
    """Copied from ``spark_rapids_tpu/models/tpch.py`` q6."""
    from spark_rapids_tpu.sql import functions as F
    li = tables["lineitem"]
    return (li.filter(
        (F.col("l_shipdate") >= datetime.date(1994, 1, 1))
        & (F.col("l_shipdate") < datetime.date(1995, 1, 1))
        & (F.col("l_discount") >= 0.05) & (F.col("l_discount") <= 0.07)
        & (F.col("l_quantity") < 24.0))
        .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
             .alias("revenue")))


def reference(frames):
    """Plain pandas over the generated frames."""
    import pandas as pd
    li = frames["lineitem"]
    keep = ((li.l_shipdate >= pd.Timestamp(1994, 1, 1))
            & (li.l_shipdate < pd.Timestamp(1995, 1, 1))
            & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
            & (li.l_quantity < 24.0))
    kept = li[keep]
    return pd.DataFrame(
        {"revenue": [(kept.l_extendedprice * kept.l_discount).sum()]})


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
