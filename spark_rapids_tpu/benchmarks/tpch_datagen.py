"""Deterministic TPC-H-like data generator.

Reference analogue: the checked-in SF-tiny datasets under
``integration_tests/src/test/resources/tpch/`` plus the schema/setup half of
``integration_tests/.../tpch/TpchLikeSpark.scala``.  This is NOT dbgen — it is
a seeded numpy generator producing the eight TPC-H tables at an arbitrary
(tiny) scale, with value distributions shaped so that every one of the 22
query-shaped workloads selects a non-trivial subset (date ranges 1992-1998,
Brand#MN / container / type vocabularies, segment / priority / shipmode
enums, comment strings that occasionally contain the Q9/Q13/Q20 needles).

All date columns are DATE32 (int32 days since epoch).
"""
from __future__ import annotations

import datetime as dt

import numpy as np

from .. import types as T

EPOCH = dt.date(1970, 1, 1)


def days(y: int, m: int, d: int) -> int:
    return (dt.date(y, m, d) - EPOCH).days


REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, regionkey) — the 25 standard TPC-H nations
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "chartreuse", "chiffon", "chocolate", "coral", "cornflower",
          "cream", "cyan", "dark", "deep", "dim", "dodger", "drab",
          "firebrick", "floral", "forest", "frosted", "gainsboro",
          "ghost", "goldenrod", "green", "grey", "honeydew", "hot",
          "indian", "ivory", "khaki", "lace", "lavender"]
COMMENT_WORDS = ["carefully", "quickly", "furiously", "slyly", "blithely",
                 "express", "regular", "final", "ironic", "pending",
                 "bold", "even", "silent", "unusual", "special",
                 "requests", "deposits", "packages", "accounts", "ideas"]


def _strings(rng, n, choices):
    return np.array(choices, dtype=object)[rng.integers(0, len(choices), n)]


# Nation draw is biased toward the nations the query workloads name
# (FRANCE/GERMANY for Q7, ASIA nations for Q5, SAUDI ARABIA for Q21,
# CANADA for Q20, BRAZIL for Q8) so tiny datasets still produce matches.
_NATION_WEIGHTS = np.ones(25)
for _k in (2, 3, 6, 7, 8, 9, 12, 18, 20, 21):
    _NATION_WEIGHTS[_k] = 4.0
_NATION_WEIGHTS = _NATION_WEIGHTS / _NATION_WEIGHTS.sum()


_FOCUS_NATIONS = np.array([20, 3, 6, 7, 2, 8, 9, 12], dtype=np.int64)


def _nations(rng, n):
    out = rng.choice(25, size=n, p=_NATION_WEIGHTS).astype(np.int64)
    # guarantee each workload-named nation appears once the table has
    # enough rows (tiny supplier tables would otherwise miss CANADA etc.)
    k = min(n, len(_FOCUS_NATIONS))
    out[:k] = _FOCUS_NATIONS[:k]
    return out


def _comment(rng, n, k=4):
    words = np.array(COMMENT_WORDS, dtype=object)
    idx = rng.integers(0, len(words), (n, k))
    return np.array([" ".join(words[r]) for r in idx], dtype=object)


def _schema(cols):
    return T.Schema([T.Field(name, dtype) for name, dtype in cols])


def generate(sf: float = 0.001, seed: int = 42):
    """Return {table: (Schema, {col: np.ndarray})} at ~sf × TPC-H scale."""
    rng = np.random.default_rng(seed)
    n_supp = max(3, int(10_000 * sf))
    n_part = max(8, int(200_000 * sf))
    n_psupp = n_part * 4
    n_cust = max(5, int(150_000 * sf))
    n_ord = max(10, int(1_500_000 * sf))
    n_line = int(n_ord * 4)

    out = {}

    # region / nation -------------------------------------------------------
    out["region"] = (_schema([("r_regionkey", T.INT64),
                              ("r_name", T.STRING),
                              ("r_comment", T.STRING)]),
                     {"r_regionkey": np.arange(5, dtype=np.int64),
                      "r_name": np.array(REGIONS, dtype=object),
                      "r_comment": _comment(rng, 5)})
    out["nation"] = (_schema([("n_nationkey", T.INT64),
                              ("n_name", T.STRING),
                              ("n_regionkey", T.INT64),
                              ("n_comment", T.STRING)]),
                     {"n_nationkey": np.arange(25, dtype=np.int64),
                      "n_name": np.array([n for n, _ in NATIONS],
                                         dtype=object),
                      "n_regionkey": np.array([r for _, r in NATIONS],
                                              dtype=np.int64),
                      "n_comment": _comment(rng, 25)})

    # supplier ---------------------------------------------------------------
    sk = np.arange(1, n_supp + 1, dtype=np.int64)
    s_comment = _comment(rng, n_supp)
    # Q16 needle: some suppliers have complaints, with text between the
    # two words as the specification has it (%Customer%Complaints%)
    mask = rng.random(n_supp) < 0.1
    s_comment[mask] = np.char.add(
        s_comment[mask].astype(str),
        " Customer even Complaints").astype(object)
    out["supplier"] = (_schema([("s_suppkey", T.INT64),
                                ("s_name", T.STRING),
                                ("s_address", T.STRING),
                                ("s_nationkey", T.INT64),
                                ("s_phone", T.STRING),
                                ("s_acctbal", T.FLOAT64),
                                ("s_comment", T.STRING)]),
                       {"s_suppkey": sk,
                        "s_name": np.array([f"Supplier#{i:09d}" for i in sk],
                                           dtype=object),
                        "s_address": _comment(rng, n_supp, 2),
                        "s_nationkey": _nations(rng, n_supp),
                        "s_phone": np.array(
                            [f"{rng.integers(10, 35)}-{rng.integers(100, 1000)}"
                             f"-{rng.integers(100, 1000)}-{rng.integers(1000, 10000)}"
                             for _ in sk], dtype=object),
                        "s_acctbal": np.round(
                            rng.uniform(-999.99, 9999.99, n_supp), 2),
                        "s_comment": s_comment})

    # part -------------------------------------------------------------------
    pk = np.arange(1, n_part + 1, dtype=np.int64)
    p_name = np.array(
        [" ".join(rng.choice(COLORS, size=3, replace=False))
         for _ in pk], dtype=object)
    # Q20 needle: ~8% of part names start with "forest"
    fmask = rng.random(n_part) < 0.08
    p_name[fmask] = np.array(
        ["forest " + " ".join(rng.choice(COLORS, size=2, replace=False))
         for _ in range(int(fmask.sum()))], dtype=object)
    p_type = np.array(
        [f"{TYPE_S1[a]} {TYPE_S2[b]} {TYPE_S3[c]}"
         for a, b, c in zip(rng.integers(0, 6, n_part),
                            rng.integers(0, 5, n_part),
                            rng.integers(0, 5, n_part))], dtype=object)
    p_type[::29] = "ECONOMY ANODIZED STEEL"  # Q8's exact-match needle
    # brand digits and container sizes correlated for ~half the parts so
    # the Q17/Q19 (brand, container) conjunctions select non-empty sets
    brand_m = rng.integers(1, 6, n_part)
    brand_n = rng.integers(1, 6, n_part)
    cont_a = rng.integers(0, 5, n_part)
    cont_b = rng.integers(0, 8, n_part)
    corr = rng.random(n_part) < 0.5
    brand_m[corr & (cont_a == 0)] = 1   # SM * -> Brand#1n
    brand_m[corr & (cont_a == 2)] = 2   # MED * -> Brand#2n
    brand_m[corr & (cont_a == 1)] = 3   # LG * -> Brand#3n
    # (MED BOX & Brand#23 for Q17 happens naturally via the correlation)
    out["part"] = (_schema([("p_partkey", T.INT64),
                            ("p_name", T.STRING),
                            ("p_mfgr", T.STRING),
                            ("p_brand", T.STRING),
                            ("p_type", T.STRING),
                            ("p_size", T.INT32),
                            ("p_container", T.STRING),
                            ("p_retailprice", T.FLOAT64),
                            ("p_comment", T.STRING)]),
                   {"p_partkey": pk,
                    "p_name": p_name,
                    "p_mfgr": np.array(
                        [f"Manufacturer#{m}" for m in
                         rng.integers(1, 6, n_part)], dtype=object),
                    "p_brand": np.array(
                        [f"Brand#{m}{n}" for m, n in
                         zip(brand_m, brand_n)], dtype=object),
                    "p_type": p_type,
                    "p_size": rng.integers(1, 51, n_part).astype(np.int32),
                    "p_container": np.array(
                        [f"{CONTAINER_1[a]} {CONTAINER_2[b]}"
                         for a, b in zip(cont_a, cont_b)],
                        dtype=object),
                    "p_retailprice": np.round(
                        900 + (pk % 1000) * 0.1 + (pk % 100), 2)
                    .astype(np.float64),
                    "p_comment": _comment(rng, n_part, 2)})

    # partsupp ---------------------------------------------------------------
    ps_part = np.repeat(pk, 4)
    ps_supp = ((ps_part + np.tile(np.arange(4, dtype=np.int64), n_part)
                * (n_supp // 4 + 1)) % n_supp) + 1
    out["partsupp"] = (_schema([("ps_partkey", T.INT64),
                                ("ps_suppkey", T.INT64),
                                ("ps_availqty", T.INT32),
                                ("ps_supplycost", T.FLOAT64),
                                ("ps_comment", T.STRING)]),
                       {"ps_partkey": ps_part,
                        "ps_suppkey": ps_supp,
                        "ps_availqty": rng.integers(1, 10_000, n_psupp)
                        .astype(np.int32),
                        "ps_supplycost": np.round(
                            rng.uniform(1.0, 1000.0, n_psupp), 2),
                        "ps_comment": _comment(rng, n_psupp, 2)})

    # customer ---------------------------------------------------------------
    ck = np.arange(1, n_cust + 1, dtype=np.int64)
    out["customer"] = (_schema([("c_custkey", T.INT64),
                                ("c_name", T.STRING),
                                ("c_address", T.STRING),
                                ("c_nationkey", T.INT64),
                                ("c_phone", T.STRING),
                                ("c_acctbal", T.FLOAT64),
                                ("c_mktsegment", T.STRING),
                                ("c_comment", T.STRING)]),
                       {"c_custkey": ck,
                        "c_name": np.array(
                            [f"Customer#{i:09d}" for i in ck], dtype=object),
                        "c_address": _comment(rng, n_cust, 2),
                        "c_nationkey": _nations(rng, n_cust),
                        "c_phone": np.array(
                            [f"{rng.integers(10, 35)}-{rng.integers(100, 1000)}"
                             f"-{rng.integers(100, 1000)}-{rng.integers(1000, 10000)}"
                             for _ in ck], dtype=object),
                        "c_acctbal": np.round(
                            rng.uniform(-999.99, 9999.99, n_cust), 2),
                        "c_mktsegment": _strings(rng, n_cust, SEGMENTS),
                        "c_comment": _comment(rng, n_cust)})

    # orders -----------------------------------------------------------------
    ok = np.arange(1, n_ord + 1, dtype=np.int64) * 4 - 3  # sparse keys
    o_date = rng.integers(days(1992, 1, 1), days(1998, 8, 3), n_ord) \
        .astype(np.int32)
    o_comment = _comment(rng, n_ord)
    mask = rng.random(n_ord) < 0.05  # Q13 needle
    o_comment[mask] = np.char.add(
        o_comment[mask].astype(str), " special handle requests").astype(object)
    out["orders"] = (_schema([("o_orderkey", T.INT64),
                              ("o_custkey", T.INT64),
                              ("o_orderstatus", T.STRING),
                              ("o_totalprice", T.FLOAT64),
                              ("o_orderdate", T.DATE32),
                              ("o_orderpriority", T.STRING),
                              ("o_clerk", T.STRING),
                              ("o_shippriority", T.INT32),
                              ("o_comment", T.STRING)]),
                     {"o_orderkey": ok,
                      # top ~15% of custkeys place no orders (Q22 anti join)
                      "o_custkey": rng.integers(
                          1, max(2, int(n_cust * 0.85)) + 1, n_ord)
                      .astype(np.int64),
                      "o_orderstatus": _strings(rng, n_ord, ["O", "F", "P"]),
                      "o_totalprice": np.round(
                          rng.uniform(850.0, 560_000.0, n_ord), 2),
                      "o_orderdate": o_date,
                      "o_orderpriority": _strings(rng, n_ord, PRIORITIES),
                      "o_clerk": np.array(
                          [f"Clerk#{c:09d}" for c in
                           rng.integers(1, max(2, n_ord // 100), n_ord)],
                          dtype=object),
                      "o_shippriority": np.zeros(n_ord, dtype=np.int32),
                      "o_comment": o_comment})

    # lineitem ---------------------------------------------------------------
    li_ord_idx = np.sort(rng.integers(0, n_ord, n_line))
    l_ok = ok[li_ord_idx]
    l_part = rng.integers(1, n_part + 1, n_line).astype(np.int64)
    l_supp = ps_supp[(l_part - 1) * 4 + rng.integers(0, 4, n_line)]
    l_odate = o_date[li_ord_idx]
    l_ship = (l_odate + rng.integers(1, 122, n_line)).astype(np.int32)
    l_commit = (l_odate + rng.integers(30, 91, n_line)).astype(np.int32)
    l_receipt = (l_ship + rng.integers(1, 31, n_line)).astype(np.int32)
    shipped = l_ship <= days(1995, 6, 17)
    rf = np.where(shipped,
                  np.where(rng.random(n_line) < 0.5, "R", "A"), "N") \
        .astype(object)
    out["lineitem"] = (_schema([("l_orderkey", T.INT64),
                                ("l_partkey", T.INT64),
                                ("l_suppkey", T.INT64),
                                ("l_linenumber", T.INT32),
                                ("l_quantity", T.FLOAT64),
                                ("l_extendedprice", T.FLOAT64),
                                ("l_discount", T.FLOAT64),
                                ("l_tax", T.FLOAT64),
                                ("l_returnflag", T.STRING),
                                ("l_linestatus", T.STRING),
                                ("l_shipdate", T.DATE32),
                                ("l_commitdate", T.DATE32),
                                ("l_receiptdate", T.DATE32),
                                ("l_shipinstruct", T.STRING),
                                ("l_shipmode", T.STRING),
                                ("l_comment", T.STRING)]),
                       {"l_orderkey": l_ok,
                        # (l_partkey, l_suppkey) drawn FROM partsupp, as in
                        # real TPC-H (lineitem references partsupp)
                        "l_partkey": l_part,
                        "l_suppkey": l_supp,
                        "l_linenumber": (np.arange(n_line) % 7 + 1)
                        .astype(np.int32),
                        "l_quantity": rng.integers(1, 51, n_line)
                        .astype(np.float64),
                        "l_extendedprice": np.round(
                            rng.uniform(900.0, 105_000.0, n_line), 2),
                        "l_discount": np.round(
                            rng.integers(0, 11, n_line) * 0.01, 2),
                        "l_tax": np.round(
                            rng.integers(0, 9, n_line) * 0.01, 2),
                        "l_returnflag": rf,
                        "l_linestatus": np.where(shipped, "F", "O")
                        .astype(object),
                        "l_shipdate": l_ship,
                        "l_commitdate": l_commit,
                        "l_receiptdate": l_receipt,
                        "l_shipinstruct": _strings(rng, n_line, INSTRUCTS),
                        "l_shipmode": _strings(rng, n_line, SHIPMODES),
                        "l_comment": _comment(rng, n_line, 2)})
    return out


def dataframes(session, sf: float = 0.001, seed: int = 42):
    """Create the eight tables as in-memory DataFrames on ``session``."""
    return {name: session.create_dataframe(cols, schema)
            for name, (schema, cols) in generate(sf, seed).items()}


def write_parquet(session, path: str, sf: float = 0.001, seed: int = 42):
    """Materialize the tables as parquet dirs (for the IO-path benchmark)."""
    import os
    for name, df in dataframes(session, sf, seed).items():
        df.write_parquet(os.path.join(path, name))
