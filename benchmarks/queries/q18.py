"""TPC-H Q18, large volume customer (clause 2.4.18), with the
specification's validation parameter: QUANTITY 300."""

READS = {"lineitem": ["l_orderkey", "l_quantity"],
         "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                    "o_totalprice"],
         "customer": ["c_custkey", "c_name"]}


def build(session, tables):
    """Copied from ``spark_rapids_tpu/models/tpch.py`` q18."""
    from spark_rapids_tpu.sql import functions as F
    big = (tables["lineitem"].group_by("l_orderkey")
           .agg(F.sum("l_quantity").alias("sum_qty"))
           .filter(F.col("sum_qty") > 300))
    return (tables["orders"]
            .join(big, left_on=["o_orderkey"], right_on=["l_orderkey"],
                  how="leftsemi")
            .join(tables["customer"], left_on=["o_custkey"],
                  right_on=["c_custkey"])
            .join(tables["lineitem"], left_on=["o_orderkey"],
                  right_on=["l_orderkey"])
            .group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                      "o_totalprice")
            .agg(F.sum("l_quantity").alias("sum_qty"))
            .order_by(F.col("o_totalprice").desc(), "o_orderdate")
            .limit(100))


def reference(frames):
    """Plain pandas over the generated frames. An order's quantities are
    summed once (the query's second pass over lineitem sums the same lines
    of the orders the first kept); they are whole numbers below 2**53, so
    the float64 sum is exact in any order."""
    qty = (frames["lineitem"].groupby("l_orderkey", sort=False)
           .agg(sum_qty=("l_quantity", "sum")).reset_index())
    big = qty[qty.sum_qty > 300]
    j = (frames["orders"]
         .merge(big, left_on="o_orderkey", right_on="l_orderkey")
         .merge(frames["customer"], left_on="o_custkey",
                right_on="c_custkey"))
    out = j[["c_name", "c_custkey", "o_orderkey", "o_orderdate",
             "o_totalprice", "sum_qty"]]
    return (out.sort_values(["o_totalprice", "o_orderdate"],
                            ascending=[False, True], kind="stable")
            .head(100).reset_index(drop=True))


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
