"""TPC-H ``orders`` (in-repo generator's distributions, NOT dbgen)."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g

STREAM = 1
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]


def order_keys(n_orders):
    """Sparse keys, as dbgen's: 1, 5, 9, ..."""
    return np.arange(1, n_orders + 1, dtype=np.int64) * 4 - 3


def order_dates(n_orders, seed):
    """The first draw of this table's stream, so that ``lineitem`` can
    make the same dates without making the table."""
    rng = g.rng_for(seed, STREAM)
    dates = rng.integers(g.days(1992, 1, 1), g.days(1998, 8, 3), n_orders)
    return rng, dates.astype(np.int32)


def generate(rows, seed):
    n = rows["orders"]
    rng, o_date = order_dates(n, seed)
    # the top ~15% of customers place no orders (q22's anti join)
    custkey = rng.integers(1, max(2, int(rows["customer"] * 0.85)) + 1, n)
    comment = g.comments(rng, n, 4).to_numpy(zero_copy_only=False)
    needle = rng.random(n) < 0.05   # q13
    comment[needle] = comment[needle] + " special handle requests"
    clerks = max(2, n // 100)
    return pa.table({
        "o_orderkey": order_keys(n),
        "o_custkey": custkey.astype(np.int64),
        "o_orderstatus": g.pick(rng, n, ["O", "F", "P"]),
        "o_totalprice": g.money(rng, 850.0, 560_000.0, n),
        "o_orderdate": pa.array(o_date, pa.int32()).cast(pa.date32()),
        "o_orderpriority": g.pick(rng, n, PRIORITIES),
        "o_clerk": pa.DictionaryArray.from_arrays(
            pa.array(rng.integers(0, clerks - 1, n, dtype=np.int32)),
            g.numbered("Clerk#", np.arange(1, clerks))),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": pa.array(comment, pa.string()),
    })
