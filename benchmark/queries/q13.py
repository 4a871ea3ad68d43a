"""TPC-H Q13, customer distribution: how many customers placed how many
orders, leaving out orders whose comment mentions special requests.
Written as Spark 3.0 plans it from the SQL: the ``not like`` on the
right side of the left outer join is pushed into ``orders``' filter,
``customer`` LEFT OUTER JOIN the orders kept (``orders`` the build side),
a count of the non-null ``o_orderkey`` by ``c_custkey``, then a count of
customers by that count, in a total order.  Validation substitution
values WORD1 special, WORD2 requests."""
import re

TABLES = {"customer": ["c_custkey"],
          "orders": ["o_orderkey", "o_custkey", "o_comment"]}
#: ORDER BY custdist DESC, c_count DESC: c_count is the group's key, so
#: the order is total
ORDERED = True

WORD1, WORD2 = "special", "requests"
PATTERN = f"%{WORD1}%{WORD2}%"

#: the generator's mean logical o_comment length: four of
#: ``datagen.COMMENT_WORDS`` (6.75 bytes on average) and three spaces,
#: and " special handle requests" (24 bytes) on 5% of the orders
COMMENT_MEAN_BYTES = 4 * 6.75 + 3 + 0.05 * 24


def build(t):
    from spark_rapids_tpu.plan import functions as F

    col = F.col
    kept = t["orders"].filter(~col("o_comment").like(PATTERN))
    c_orders = (t["customer"].select("c_custkey")
                .join(kept.select("o_orderkey", "o_custkey"),
                      on=(["c_custkey"], ["o_custkey"]), how="left")
                .group_by("c_custkey")
                .agg(F.count("o_orderkey").alias("c_count")))
    return (c_orders.group_by("c_count")
            .agg(F.count("*").alias("custdist"))
            .sort(col("custdist").desc(), col("c_count").desc()))


def reference(t):
    """The SQL as it reads: LIKE '%special%requests%' as the regular
    expression ``special.*requests`` (DOTALL, as LIKE's ``%`` spans any
    character), a left merge, ``count`` of the non-null order keys by
    customer, ``value_counts`` of those counts, the order."""
    orders = t["orders"]
    kept = orders[~orders.o_comment.str.contains(
        f"{WORD1}.*{WORD2}", regex=True, flags=re.DOTALL)]
    j = t["customer"][["c_custkey"]].merge(
        kept[["o_orderkey", "o_custkey"]], how="left",
        left_on="c_custkey", right_on="o_custkey")
    c_count = j.groupby("c_custkey").o_orderkey.count()
    out = (c_count.value_counts().rename_axis("c_count")
           .reset_index(name="custdist")
           .sort_values(["custdist", "c_count"], ascending=[False, False],
                        kind="stable"))
    return [(int(r.c_count), int(r.custdist))
            for r in out.itertuples(index=False)]


def min_bytes(rows):
    """Each input column once: ``o_orderkey``, ``o_custkey`` and
    ``o_comment`` at the generator's mean logical length, ``c_custkey``;
    nothing for the joined pairs or either group-by's groups."""
    return int(rows["orders"] * (8 + 8 + COMMENT_MEAN_BYTES)
               + rows["customer"] * 8)
