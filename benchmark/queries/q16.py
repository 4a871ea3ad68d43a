"""TPC-H Q16, parts/supplier relationship: how many suppliers can
deliver parts of a brand, type and size, leaving out suppliers with
complaints.  String predicates on two dimension tables, an anti join and
an inner join through ``partsupp``, a count of distinct suppliers by
(brand, type, size), every group returned in a total order.  Validation
substitution values BRAND Brand#45, TYPE MEDIUM POLISHED, SIZES 49, 14,
23, 45, 19, 3, 36, 9."""

TABLES = {"part": ["p_partkey", "p_brand", "p_type", "p_size"],
          "partsupp": ["ps_partkey", "ps_suppkey"],
          "supplier": ["s_suppkey", "s_comment"]}
#: ORDER BY supplier_cnt DESC, p_brand, p_type, p_size: the last three
#: are the group's key, so the order is total
ORDERED = True

BRAND, TYPE_PREFIX = "Brand#45", "MEDIUM POLISHED"
SIZES = (49, 14, 23, 45, 19, 3, 36, 9)


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col, lit = F.col, F.lit
    part = t["part"].filter(
        (col("p_brand") != lit(BRAND))
        & ~col("p_type").like(TYPE_PREFIX + "%")
        & col("p_size").isin(*SIZES))
    # ps_suppkey NOT IN (select s_suppkey ...): s_suppkey is a key and
    # never null, so the anti join is the NOT IN
    complained = t["supplier"].filter(
        col("s_comment").like("%Customer%Complaints%"))
    ps = (t["partsupp"].select("ps_partkey", "ps_suppkey")
          .join(complained.select("s_suppkey"),
                on=(["ps_suppkey"], ["s_suppkey"]), how="anti")
          .join(part.select("p_partkey", "p_brand", "p_type", "p_size"),
                on=(["ps_partkey"], ["p_partkey"]), how="inner"))
    # count(distinct ps_suppkey): a distinct, then a count
    return (ps.select("p_brand", "p_type", "p_size", "ps_suppkey")
            .distinct()
            .group_by("p_brand", "p_type", "p_size")
            .agg(F.count("ps_suppkey").alias("supplier_cnt"))
            .sort(col("supplier_cnt").desc(), col("p_brand").asc(),
                  col("p_type").asc(), col("p_size").asc()))


def reference(t):
    part = t["part"]
    part = part[(part.p_brand != BRAND)
                & ~part.p_type.str.startswith(TYPE_PREFIX)
                & part.p_size.isin(SIZES)]
    supp = t["supplier"]
    complained = supp[supp.s_comment.str.contains(
        "Customer.*Complaints", regex=True)].s_suppkey
    ps = t["partsupp"]
    ps = ps[~ps.ps_suppkey.isin(complained)]
    j = ps.merge(part, left_on="ps_partkey", right_on="p_partkey")
    out = (j.groupby(["p_brand", "p_type", "p_size"]).ps_suppkey.nunique()
           .reset_index(name="supplier_cnt")
           .sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                        ascending=[False, True, True, True], kind="stable"))
    return [(r.p_brand, r.p_type, int(r.p_size), int(r.supplier_cnt))
            for r in out.itertuples(index=False)]


def min_bytes(rows):
    """Each input column once at its mean width (``p_brand`` 8 B,
    ``p_type`` 20.6 B, ``s_comment`` 62.5 B), nothing for the
    intermediates a better plan might not materialise, and the result's
    rows: at most 24 brands x 145 types x 8 sizes, fewer where the parts
    kept do not fill them."""
    groups = min(24 * 145 * 8, rows["part"] * 24 * 145 * 8 // (25 * 150 * 50))
    return int(rows["partsupp"] * (8 + 8)
               + rows["part"] * (8 + 8 + 20.6 + 4)
               + rows["supplier"] * (8 + 62.5)
               + groups * (8 + 20.6 + 4 + 8))
