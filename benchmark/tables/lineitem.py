"""TPC-H ``lineitem`` (in-repo generator's distributions, NOT dbgen):
four lines an order on average, (partkey, suppkey) drawn from
``partsupp``'s pairs, ship/commit/receipt dates after the order's."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g
from benchmark.harness import load_module

STREAM = 2
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]


def generate(rows, seed):
    n, n_ord = rows["lineitem"], rows["orders"]
    n_part, n_supp = rows["part"], rows["supplier"]
    orders = load_module("tables", "orders")
    _, o_date = orders.order_dates(n_ord, seed)
    rng = g.rng_for(seed, STREAM)
    order_idx = np.sort(rng.integers(0, n_ord, n))
    odate = o_date[order_idx]
    partkey = rng.integers(1, n_part + 1, n)
    # partsupp gives part p the suppliers (p + j*(S/4+1)) mod S + 1, j<4
    suppkey = (partkey + rng.integers(0, 4, n) * (n_supp // 4 + 1)) \
        % n_supp + 1
    ship = (odate + rng.integers(1, 122, n)).astype(np.int32)
    commit = (odate + rng.integers(30, 91, n)).astype(np.int32)
    receipt = (ship + rng.integers(1, 31, n)).astype(np.int32)
    shipped = ship <= g.days(1995, 6, 17)
    flag = np.where(shipped, rng.integers(0, 2, n), 2).astype(np.int32)

    def date(a):
        return pa.array(a, pa.int32()).cast(pa.date32())

    return pa.table({
        "l_orderkey": orders.order_keys(n_ord)[order_idx],
        "l_partkey": partkey.astype(np.int64),
        "l_suppkey": suppkey.astype(np.int64),
        "l_linenumber": (np.arange(n) % 7 + 1).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": g.money(rng, 900.0, 105_000.0, n),
        "l_discount": np.round(rng.integers(0, 11, n) * 0.01, 2),
        "l_tax": np.round(rng.integers(0, 9, n) * 0.01, 2),
        "l_returnflag": g.from_vocabulary(flag, ["R", "A", "N"]),
        "l_linestatus": g.from_vocabulary(shipped.astype(np.int32),
                                          ["O", "F"]),
        "l_shipdate": date(ship),
        "l_commitdate": date(commit),
        "l_receiptdate": date(receipt),
        "l_shipinstruct": g.pick(rng, n, INSTRUCTS),
        "l_shipmode": g.pick(rng, n, SHIPMODES),
        "l_comment": g.comments(rng, n, 2),
    })
