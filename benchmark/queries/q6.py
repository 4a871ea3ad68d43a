"""TPC-H Q6, forecasting revenue change: scan, filter, one reduction.
Validation substitution values (DATE 1994-01-01, DISCOUNT 0.06,
QUANTITY 24), as ``spark_rapids_tpu/benchmarks/tpch.py`` encodes them."""
import datetime

#: what the query reads: table -> columns (the reference loads these)
TABLES = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice"]}
#: the answer is one row
ORDERED = True


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col, lit = F.col, F.lit
    li = t["lineitem"].filter(
        (col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
        & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
        & (col("l_discount") >= lit(0.05))
        & (col("l_discount") <= lit(0.07))
        & (col("l_quantity") < lit(24.0)))
    return li.agg(F.sum(col("l_extendedprice") * col("l_discount"))
                  .alias("revenue"))


def reference(t):
    """Plain pandas over the same files; dates are datetime64."""
    import pandas as pd

    li = t["lineitem"]
    keep = ((li.l_shipdate >= pd.Timestamp(1994, 1, 1))
            & (li.l_shipdate < pd.Timestamp(1995, 1, 1))
            & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
            & (li.l_quantity < 24.0))
    sel = li[keep]
    return [(float((sel.l_extendedprice * sel.l_discount).sum()),)]


def min_bytes(rows):
    """The least the device must read and write for one answer: the
    four columns once at their widths (date 4 B, three f64), and 8 B
    out."""
    return rows["lineitem"] * (4 + 8 + 8 + 8) + 8
