"""TPC-H ``partsupp``: four suppliers a part, by the formula
``tables/lineitem.py`` draws its (partkey, suppkey) pairs from (the
in-repo generator's: ``(p + j*(S/4+1)) mod S + 1``, j < 4).  The
comment no query here reads keeps the in-repo generator's two words
(NOT dbgen)."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g

STREAM = 5
SUPPLIERS_A_PART = 4


def supplier_keys(partkey, j, n_supp):
    """The ``j``-th supplier of each part."""
    return (partkey + j * (n_supp // SUPPLIERS_A_PART + 1)) % n_supp + 1


def generate(rows, seed):
    n, n_part, n_supp = rows["partsupp"], rows["part"], rows["supplier"]
    if n != SUPPLIERS_A_PART * n_part:
        raise ValueError(f"partsupp has {SUPPLIERS_A_PART} rows a part: "
                         f"{n} rows for {n_part} parts")
    rng = g.rng_for(seed, STREAM)
    partkey = np.repeat(np.arange(1, n_part + 1, dtype=np.int64),
                        SUPPLIERS_A_PART)
    j = np.tile(np.arange(SUPPLIERS_A_PART, dtype=np.int64), n_part)
    return pa.table({
        "ps_partkey": partkey,
        "ps_suppkey": supplier_keys(partkey, j, n_supp),
        "ps_availqty": rng.integers(1, 10_000, n).astype(np.int32),
        "ps_supplycost": g.money(rng, 1.0, 1000.0, n),
        "ps_comment": g.comments(rng, n, 2),
    })
