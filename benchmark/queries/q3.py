"""TPC-H Q3, shipping priority: customer x orders x lineitem, a
group-by on the order and the ten largest revenues.  Validation
substitution values SEGMENT BUILDING, DATE 1995-03-15."""
import datetime

TABLES = {"customer": ["c_custkey", "c_mktsegment"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"],
          "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice",
                       "l_discount"]}
#: ORDER BY revenue DESC, o_orderdate: revenues are f64 sums of random
#: money, so no two of the first ten tie
ORDERED = True


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col, lit = F.col, F.lit
    day = lit(datetime.date(1995, 3, 15))
    cust = t["customer"].filter(col("c_mktsegment") == lit("BUILDING"))
    orders = t["orders"].filter(col("o_orderdate") < day)
    li = t["lineitem"].filter(col("l_shipdate") > day)
    j = (cust.select("c_custkey")
         .join(orders, on=(["c_custkey"], ["o_custkey"]), how="inner")
         .join(li, on=(["o_orderkey"], ["l_orderkey"]), how="inner"))
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (j.group_by("o_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(rev).alias("revenue"))
            .select("o_orderkey", "revenue", "o_orderdate", "o_shippriority")
            .sort(col("revenue").desc(), col("o_orderdate").asc())
            .limit(10))


def reference(t):
    import pandas as pd

    day = pd.Timestamp(1995, 3, 15)
    cust = t["customer"]
    cust = cust[cust.c_mktsegment == "BUILDING"][["c_custkey"]]
    orders = t["orders"]
    orders = orders[orders.o_orderdate < day]
    li = t["lineitem"]
    li = li[li.l_shipdate > day]
    j = (cust.merge(orders, left_on="c_custkey", right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey"))
    j = j.assign(revenue=j.l_extendedprice * (1.0 - j.l_discount))
    out = (j.groupby(["o_orderkey", "o_orderdate", "o_shippriority"])
           .agg(revenue=("revenue", "sum")).reset_index()
           .sort_values(["revenue", "o_orderdate"],
                        ascending=[False, True], kind="stable").head(10))
    return [(int(r.o_orderkey), float(r.revenue), r.o_orderdate.date(),
             int(r.o_shippriority)) for r in out.itertuples(index=False)]


def min_bytes(rows):
    """Each input column once at its width (the segment string at its
    mean 9 B), nothing for the intermediates a better plan might not
    materialise, and ten result rows."""
    return (rows["customer"] * (8 + 9)
            + rows["orders"] * (8 + 8 + 4 + 4)
            + rows["lineitem"] * (8 + 4 + 8 + 8) + 10 * 24)
