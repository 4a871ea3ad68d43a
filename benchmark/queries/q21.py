"""TPC-H Q21, suppliers who kept orders waiting: a nation's suppliers
counted by the completed orders ('F') on which theirs was the only late
line among the lines of several suppliers.  Written as Spark 3.0 plans
it from the SQL (``RewritePredicateSubquery``): the late lines l1 joined
to ``supplier``, the ``EXISTS`` over ``lineitem l2`` a semi join on the
order key with the residual ``l_suppkey <> l2_suppkey``, the ``NOT
EXISTS`` over the late lines l3 an anti join with the same kind of
residual, then ``orders`` and ``nation``, a count by ``s_name``, the
hundred largest.  ``lineitem`` is read three times.  Validation
substitution value NATION SAUDI ARABIA."""

TABLES = {"supplier": ["s_suppkey", "s_name", "s_nationkey"],
          "lineitem": ["l_orderkey", "l_suppkey", "l_receiptdate",
                       "l_commitdate"],
          "orders": ["o_orderkey", "o_orderstatus"],
          "nation": ["n_nationkey", "n_name"]}
#: ORDER BY numwait DESC, s_name: s_name (``Supplier#`` and nine digits
#: of the key) is unique, so the order is total and ties in numwait
#: cannot reorder rows
ORDERED = True

NATION = "SAUDI ARABIA"


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col, lit = F.col, F.lit
    lineitem = t["lineitem"]
    late = lineitem.filter(col("l_receiptdate") > col("l_commitdate"))
    # supplier is the broadcast side, as Spark builds it, and only the
    # columns a later operator reads go on (Spark's Project)
    l1 = (late.select("l_orderkey", "l_suppkey")
          .join(t["supplier"].select("s_suppkey", "s_name", "s_nationkey"),
                on=(["l_suppkey"], ["s_suppkey"]), how="inner")
          .select("l_orderkey", "l_suppkey", "s_name", "s_nationkey"))
    # EXISTS (select * from lineitem l2 where l2.l_orderkey =
    # l1.l_orderkey and l2.l_suppkey <> l1.l_suppkey)
    l2 = lineitem.select(col("l_orderkey").alias("l2_orderkey"),
                         col("l_suppkey").alias("l2_suppkey"))
    # NOT EXISTS (... l3 ... and l3.l_receiptdate > l3.l_commitdate)
    l3 = late.select(col("l_orderkey").alias("l3_orderkey"),
                     col("l_suppkey").alias("l3_suppkey"))
    waiting = (l1.join(l2, on=(["l_orderkey"], ["l2_orderkey"]),
                       how="semi",
                       condition=col("l_suppkey") != col("l2_suppkey"))
               .join(l3, on=(["l_orderkey"], ["l3_orderkey"]),
                     how="anti",
                     condition=col("l_suppkey") != col("l3_suppkey")))
    done = t["orders"].filter(col("o_orderstatus") == lit("F"))
    nation = t["nation"].filter(col("n_name") == lit(NATION))
    return (waiting
            .join(done.select("o_orderkey"),
                  on=(["l_orderkey"], ["o_orderkey"]), how="inner")
            .join(nation.select("n_nationkey"),
                  on=(["s_nationkey"], ["n_nationkey"]), how="inner")
            .group_by("s_name")
            .agg(F.count("*").alias("numwait"))
            .sort(col("numwait").desc(), col("s_name").asc())
            .limit(100))


def reference(t):
    """The subqueries as the SQL says them, one pair at a time: l1
    merged with the lines of its order, the pairs whose suppliers differ
    kept, ``isin`` for EXISTS and ``~isin`` over the late lines for NOT
    EXISTS; then the joins, the count and the order."""
    li = t["lineitem"]
    late = li[li.l_receiptdate > li.l_commitdate]
    l1 = late[["l_orderkey", "l_suppkey"]].reset_index(drop=True)
    l1["line"] = l1.index

    def correlated(other):
        pairs = l1.merge(other[["l_orderkey", "l_suppkey"]],
                         on="l_orderkey", suffixes=("", "_other"))
        return pairs.line[pairs.l_suppkey != pairs.l_suppkey_other]

    l1 = l1[l1.line.isin(correlated(li))
            & ~l1.line.isin(correlated(late))]
    orders = t["orders"]
    nation = t["nation"]
    j = (l1.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
         .merge(orders[orders.o_orderstatus == "F"], left_on="l_orderkey",
                right_on="o_orderkey")
         .merge(nation[nation.n_name == NATION], left_on="s_nationkey",
                right_on="n_nationkey"))
    out = (j.groupby("s_name").size().reset_index(name="numwait")
           .sort_values(["numwait", "s_name"], ascending=[False, True],
                        kind="stable").head(100))
    return [(r.s_name, int(r.numwait)) for r in out.itertuples(index=False)]


def min_bytes(rows):
    """Each input column once at its width (``s_name`` is ``Supplier#``
    and nine digits, 18 B; ``lineitem``'s four columns once, though the
    plan scans them three times; ``o_orderstatus`` and ``n_name`` a byte
    and a nation's name, 16 B at most), nothing for the pairs or any
    other intermediate, and the hundred result rows."""
    return (rows["lineitem"] * (8 + 8 + 4 + 4)
            + rows["orders"] * (8 + 1)
            + rows["supplier"] * (8 + 18 + 8)
            + rows["nation"] * (8 + 16)
            + 100 * (18 + 8))
