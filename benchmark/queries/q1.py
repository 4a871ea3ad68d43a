"""TPC-H Q1, pricing summary report: fused filter/project and a
group-by of four groups over nearly all of lineitem.  Validation
substitution value DELTA 90 (shipdate <= 1998-09-02)."""
import datetime

TABLES = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                       "l_quantity", "l_extendedprice", "l_discount",
                       "l_tax"]}
#: ORDER BY l_returnflag, l_linestatus: a total order over the groups
ORDERED = True


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col, lit = F.col, F.lit
    li = t["lineitem"].filter(
        col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    charge = disc_price * (lit(1.0) + col("l_tax"))
    return (li.group_by("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base_price"),
                 F.sum(disc_price).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count("l_quantity").alias("count_order"))
            .sort("l_returnflag", "l_linestatus"))


def reference(t):
    import pandas as pd

    li = t["lineitem"]
    li = li[li.l_shipdate <= pd.Timestamp(1998, 9, 2)]
    disc_price = li.l_extendedprice * (1.0 - li.l_discount)
    li = li.assign(disc_price=disc_price,
                   charge=disc_price * (1.0 + li.l_tax))
    out = (li.groupby(["l_returnflag", "l_linestatus"], sort=True)
           .agg(sum_qty=("l_quantity", "sum"),
                sum_base_price=("l_extendedprice", "sum"),
                sum_disc_price=("disc_price", "sum"),
                sum_charge=("charge", "sum"),
                avg_qty=("l_quantity", "mean"),
                avg_price=("l_extendedprice", "mean"),
                avg_disc=("l_discount", "mean"),
                count_order=("l_quantity", "count"))
           .reset_index())
    return [(r.l_returnflag, r.l_linestatus, float(r.sum_qty),
             float(r.sum_base_price), float(r.sum_disc_price),
             float(r.sum_charge), float(r.avg_qty), float(r.avg_price),
             float(r.avg_disc), int(r.count_order))
            for r in out.itertuples(index=False)]


def min_bytes(rows):
    """Seven columns once: a date (4 B), two one-character flags (1 B
    each, their offsets not counted), four f64; the four result rows
    are nothing beside them."""
    return rows["lineitem"] * (4 + 1 + 1 + 4 * 8)
