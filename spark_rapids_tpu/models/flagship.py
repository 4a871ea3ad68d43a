"""Flagship single-chip pipeline: a TPC-H Q1-shaped query compiled to
ONE XLA program.

Reference analogue: the §3.3 executor hot loop (scan -> project/filter
-> partial agg -> exchange -> final agg) and TPC-H Q1
(integration_tests tpch/TpchLikeSpark.scala Q1) — the reference runs it
as a chain of cudf kernel launches; here the whole chain traces into a
single jitted program so XLA fuses the elementwise work into the sort +
segment-reduce of the aggregate.

Used by __graft_entry__.entry() and the pipeline test.
"""
from __future__ import annotations

from typing import Callable, List, Tuple

import numpy as np

from .. import types as T
from ..data.column import DeviceBatch, HostBatch, host_to_device


def lineitem_like(n_rows: int, seed: int = 0) -> HostBatch:
    """Synthetic numeric lineitem slice (Q1 columns; dates as int32
    days, flags as int32 codes so the pipeline is pure-MXU-friendly)."""
    rng = np.random.RandomState(seed)
    schema = T.Schema([
        T.Field("l_quantity", T.FLOAT64),
        T.Field("l_extendedprice", T.FLOAT64),
        T.Field("l_discount", T.FLOAT64),
        T.Field("l_tax", T.FLOAT64),
        T.Field("l_returnflag", T.INT32),
        T.Field("l_linestatus", T.INT32),
        T.Field("l_shipdate", T.INT32),
    ])
    data = {
        "l_quantity": rng.randint(1, 51, n_rows).astype(np.float64),
        "l_extendedprice": (rng.rand(n_rows) * 1e5).round(2),
        "l_discount": (rng.rand(n_rows) * 0.1).round(2),
        "l_tax": (rng.rand(n_rows) * 0.08).round(2),
        "l_returnflag": rng.randint(0, 3, n_rows).astype(np.int32),
        "l_linestatus": rng.randint(0, 2, n_rows).astype(np.int32),
        "l_shipdate": rng.randint(8000, 11000, n_rows).astype(np.int32),
    }
    return HostBatch.from_pydict(data, schema)


def q1_dataframe(sess, hb: HostBatch, cutoff: int = 10471):
    """where l_shipdate <= cutoff
       group by l_returnflag, l_linestatus
       agg sum(qty), sum(price), sum(disc_price), sum(charge),
           avg(qty), avg(price), avg(disc), count(*)"""
    from ..plan import functions as F

    df = sess.create_dataframe(hb, n_partitions=1)
    df = df.filter(df["l_shipdate"] <= F.lit(cutoff))
    df = df.with_column("disc_price",
                        df["l_extendedprice"] * (F.lit(1.0)
                                                 - df["l_discount"]))
    df = df.with_column("charge",
                        df["l_extendedprice"]
                        * (F.lit(1.0) - df["l_discount"])
                        * (F.lit(1.0) + df["l_tax"]))
    return df.group_by("l_returnflag", "l_linestatus").agg(
        F.sum("l_quantity").alias("sum_qty"),
        F.sum("l_extendedprice").alias("sum_base_price"),
        F.sum("disc_price").alias("sum_disc_price"),
        F.sum("charge").alias("sum_charge"),
        F.avg("l_quantity").alias("avg_qty"),
        F.avg("l_extendedprice").alias("avg_price"),
        F.avg("l_discount").alias("avg_disc"),
        F.count("*").alias("count_order"),
    )


def _compute_chain(phys) -> List[Callable]:
    """Bottom-up chain of pure per-batch kernels from a planned exec
    tree.  Exchange/transition/coalesce nodes contribute nothing: on a
    single chip with one batch, partial->final chaining IS the
    single-partition exchange."""
    from ..exec.base import TpuExec

    chain: List[Callable] = []

    def walk(p):
        for c in p.children:
            walk(c)
        if not isinstance(p, TpuExec):
            return
        fn = getattr(p, "compute_batch", None)
        if fn is None and hasattr(p, "_compute"):
            fn = p._compute
        if fn is not None:
            chain.append(fn)

    walk(phys)
    return chain


def build_q1_pipeline(n_rows: int = 1 << 16, seed: int = 0
                      ) -> Tuple[Callable, DeviceBatch]:
    """Returns (fn, example_batch): fn is a jittable pure function
    DeviceBatch -> DeviceBatch running the full Q1 pipeline."""
    from ..session import Session

    sess = Session(tpu_enabled=True)
    hb = lineitem_like(n_rows, seed)
    df = q1_dataframe(sess, hb)
    phys = sess.physical_plan(df.plan)
    chain = _compute_chain(phys)
    assert chain, "planner produced no TPU kernels for the flagship query"

    def fn(batch: DeviceBatch) -> DeviceBatch:
        for k in chain:
            batch = k(batch)
            if isinstance(batch, tuple):
                # a fused segment returns one batch per output stream
                (batch,) = batch
        return batch

    example = host_to_device(hb)
    return fn, example
