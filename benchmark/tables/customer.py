"""TPC-H ``customer`` (in-repo generator's distributions, NOT dbgen)."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g

STREAM = 3
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
#: nations the query workloads name are drawn four times as often
FOCUS_NATIONS = (2, 3, 6, 7, 8, 9, 12, 18, 20, 21)


def nation_keys(rng, n):
    weights = np.ones(25)
    weights[list(FOCUS_NATIONS)] = 4.0
    return rng.choice(25, size=n, p=weights / weights.sum()).astype(np.int64)


def phones(rng, n):
    import pyarrow.compute as pc

    parts = [pa.array(rng.integers(lo, hi, n)).cast(pa.string())
             for lo, hi in ((10, 35), (100, 1000), (100, 1000),
                            (1000, 10000))]
    return pc.binary_join_element_wise(*parts, "-")


def generate(rows, seed):
    n = rows["customer"]
    rng = g.rng_for(seed, STREAM)
    key = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "c_custkey": key,
        "c_name": g.numbered("Customer#", key),
        "c_address": g.comments(rng, n, 2),
        "c_nationkey": nation_keys(rng, n),
        "c_phone": phones(rng, n),
        "c_acctbal": g.money(rng, -999.99, 9999.99, n),
        "c_mktsegment": g.pick(rng, n, SEGMENTS),
        "c_comment": g.comments(rng, n, 4),
    })
