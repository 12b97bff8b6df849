"""TPC-H Q13, customer distribution (clause 2.4.13), with the
specification's validation parameters: WORD1 special, WORD2 requests."""

READS = {"orders": ["o_orderkey", "o_custkey", "o_comment"],
         "customer": ["c_custkey"]}


def build(session, tables):
    """Copied from ``spark_rapids_tpu/models/tpch.py`` q13."""
    from spark_rapids_tpu.sql import functions as F
    orders = tables["orders"].filter(
        ~F.col("o_comment").like("%special%requests%"))
    counts = (tables["customer"]
              .join(orders, left_on=["c_custkey"], right_on=["o_custkey"],
                    how="left")
              .group_by("c_custkey")
              .agg(F.count("o_orderkey").alias("c_count")))
    return (counts.group_by("c_count")
            .agg(F.count("*").alias("custdist"))
            .order_by(F.col("custdist").desc(), F.col("c_count").desc()))


def reference(frames):
    """Plain pandas over the generated frames. The pattern is a regular
    expression over the whole value; a customer with no order left keeps
    one null-extended row, which ``count`` of the order key skips, so the
    customer comes out with ``c_count`` 0. The answer is integers alone."""
    orders = frames["orders"]
    matches = orders.o_comment.str.contains(r"(?s)special.*requests")
    orders = orders[~matches.fillna(False).astype(bool)]
    j = frames["customer"].merge(orders, how="left", left_on="c_custkey",
                                 right_on="o_custkey")
    counts = (j.groupby("c_custkey", sort=False)
              .agg(c_count=("o_orderkey", "count")).reset_index())
    out = (counts.groupby("c_count", sort=False).size()
           .rename("custdist").reset_index())
    return (out.sort_values(["custdist", "c_count"], ascending=False,
                            kind="stable").reset_index(drop=True))


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
