"""TPC-H Q16, parts/supplier relationship (clause 2.4.16), with the
specification's validation parameters: BRAND Brand#45, TYPE MEDIUM
POLISHED, SIZE1..8 = 49, 14, 23, 45, 19, 3, 36, 9."""

READS = {"partsupp": ["ps_partkey", "ps_suppkey"],
         "part": ["p_partkey", "p_brand", "p_type", "p_size"],
         "supplier": ["s_suppkey", "s_comment"]}

SIZES = (49, 14, 23, 45, 19, 3, 36, 9)


def build(session, tables):
    """The query as the specification writes it. ``ps_suppkey NOT IN
    (select s_suppkey ...)`` is the ``leftanti`` join: ``s_suppkey`` is the
    supplier's primary key and holds no null, and ``ps_suppkey`` is half of
    partsupp's primary key and holds none either (NOT IN would drop a null
    ``ps_suppkey`` where the anti join keeps it; the count skips it in
    both, and ``tests/test_q16_distinct.py`` holds the anti join's side)."""
    from spark_rapids_tpu.sql import functions as F
    complaints = tables["supplier"].filter(
        F.col("s_comment").like("%Customer%Complaints%"))
    part = tables["part"].filter(
        (F.col("p_brand") != "Brand#45")
        & ~F.col("p_type").like("MEDIUM POLISHED%")
        & F.col("p_size").isin(*SIZES))
    return (tables["partsupp"]
            .join(complaints, left_on=["ps_suppkey"], right_on=["s_suppkey"],
                  how="leftanti")
            .join(part, left_on=["ps_partkey"], right_on=["p_partkey"])
            .group_by("p_brand", "p_type", "p_size")
            .agg(F.count_distinct("ps_suppkey").alias("supplier_cnt"))
            .order_by(F.col("supplier_cnt").desc(), "p_brand", "p_type",
                      "p_size"))


def reference(frames):
    """Plain pandas over the generated frames: the suppliers whose comment
    holds 'Customer' and later 'Complaints' are excluded by key, the parts
    are filtered before the merge (so the merge is 60M rows against 3M at
    SF100, not against 20M), ``nunique`` folds a (part, supplier) pair that
    the generator drew twice, and the sort is stable over the four keys.
    The answer is two string columns and two of integers."""
    supplier = frames["supplier"]
    bad = supplier.s_suppkey[supplier.s_comment.str.contains(
        r"(?s)Customer.*Complaints").fillna(False).astype(bool)]
    part = frames["part"]
    part = part[(part.p_brand != "Brand#45")
                & ~part.p_type.str.startswith("MEDIUM POLISHED")
                .fillna(False).astype(bool)
                & part.p_size.isin(SIZES)]
    partsupp = frames["partsupp"]
    partsupp = partsupp[~partsupp.ps_suppkey.isin(bad)]
    j = partsupp.merge(part, left_on="ps_partkey", right_on="p_partkey")
    out = (j.groupby(["p_brand", "p_type", "p_size"], sort=False)
           ["ps_suppkey"].nunique().rename("supplier_cnt").reset_index())
    return (out.sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                            ascending=[False, True, True, True],
                            kind="stable").reset_index(drop=True))


def bytes_read(sf):
    """The bytes of the columns this query reads, each once."""
    from data import bytes_read as of_columns
    return of_columns(READS, sf)
