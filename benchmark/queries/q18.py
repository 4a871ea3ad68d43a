"""TPC-H Q18, large volume customer: the orders whose lines' quantities
sum past a threshold, with their customer, date, price and that sum, the
hundred dearest first.  A group-by of every lineitem row by its order
(1.47M groups at SF 1) of which the HAVING keeps a fraction of a
percent, a semi join of ``orders`` against the survivors, then the joins
back to ``customer`` and ``lineitem`` and a second, small group-by.
Validation substitution value QUANTITY 300."""

TABLES = {"customer": ["c_custkey", "c_name"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"],
          "lineitem": ["l_orderkey", "l_quantity"]}
#: ORDER BY o_totalprice DESC, o_orderdate, first 100 rows: prices are
#: random money over 56M cent values, so two of the first hundred (or
#: the hundredth and the one after it) sharing price *and* date is a
#: 1e-4 event a seed; the sums are of whole quantities and exact
ORDERED = True

QUANTITY = 300.0


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col, lit = F.col, F.lit
    # o_orderkey IN (select l_orderkey ... group by l_orderkey having
    # sum(l_quantity) > QUANTITY): a semi join against the aggregate
    big = (t["lineitem"].group_by(col("l_orderkey").alias("big_orderkey"))
           .agg(F.sum("l_quantity").alias("big_quantity"))
           .filter(col("big_quantity") > lit(QUANTITY)))
    j = (t["orders"].select("o_orderkey", "o_custkey", "o_orderdate",
                            "o_totalprice")
         .join(big.select("big_orderkey"),
               on=(["o_orderkey"], ["big_orderkey"]), how="semi")
         .join(t["customer"].select("c_custkey", "c_name"),
               on=(["o_custkey"], ["c_custkey"]), how="inner")
         .join(t["lineitem"].select("l_orderkey", "l_quantity"),
               on=(["o_orderkey"], ["l_orderkey"]), how="inner"))
    return (j.group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                       "o_totalprice")
            .agg(F.sum("l_quantity").alias("sum_quantity"))
            .sort(col("o_totalprice").desc(), col("o_orderdate").asc())
            .limit(100))


def reference(t):
    li = t["lineitem"]
    total = li.groupby("l_orderkey").l_quantity.sum()
    big = total[total > QUANTITY].index
    orders = t["orders"]
    orders = orders[orders.o_orderkey.isin(big)]
    j = (t["customer"].merge(orders, left_on="c_custkey",
                             right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey"))
    out = (j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                      "o_totalprice"])
           .agg(sum_quantity=("l_quantity", "sum")).reset_index()
           .sort_values(["o_totalprice", "o_orderdate"],
                        ascending=[False, True], kind="stable").head(100))
    return [(r.c_name, int(r.c_custkey), int(r.o_orderkey),
             r.o_orderdate.date(), float(r.o_totalprice),
             float(r.sum_quantity)) for r in out.itertuples(index=False)]


def min_bytes(rows):
    """Each input column once at its width (``c_name`` is ``Customer#``
    and nine digits, 18 B; ``lineitem``'s two columns once, though the
    plan scans them twice), nothing for the intermediates a better plan
    might not materialise, and the hundred result rows."""
    return (rows["lineitem"] * (8 + 8)
            + rows["orders"] * (8 + 8 + 4 + 8)
            + rows["customer"] * (8 + 18)
            + 100 * (18 + 8 + 8 + 4 + 8 + 8))
