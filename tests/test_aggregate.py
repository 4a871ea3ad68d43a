"""Hash aggregate equality tests — CPU oracle vs TPU engine.

Reference analogues: HashAggregatesSuite, hash_aggregate_test.py.
"""
import pytest
from conftest import jaxpr_eqns

from spark_rapids_tpu import f
from spark_rapids_tpu.ops.kernels import gather as G
from spark_rapids_tpu.testing import datagen as dg
from spark_rapids_tpu.testing.asserts import (
    assert_tpu_and_cpu_are_equal_collect,
)


def _data(n=500, seed=0):
    return dg.gen_batch({
        "k": dg.IntGen(dg.T.INT32, min_val=-5, max_val=5),
        "k2": dg.IntGen(dg.T.INT64, min_val=0, max_val=3),
        "v": dg.IntGen(dg.T.INT64, min_val=-1000, max_val=1000),
        "x": dg.FloatGen(dg.T.FLOAT64),
        "s": dg.StringGen(max_len=8),
    }, n, seed)


@pytest.mark.parametrize("agg_fn", [
    lambda df: f.sum(df["v"]),
    lambda df: f.count(df["v"]),
    lambda df: f.count("*"),
    lambda df: f.min(df["v"]),
    lambda df: f.max(df["x"]),
    lambda df: f.avg(df["v"]),
    lambda df: f.avg(df["x"]),
    lambda df: f.min(df["x"]),
], ids=["sum", "count", "count_star", "min", "max_f", "avg", "avg_f",
        "min_f"])
def test_groupby_single_agg(agg_fn):
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k").agg(agg_fn(df).alias("out")),
        _data(), ignore_order=True)


def test_groupby_multi_key_multi_agg():
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k", "k2").agg(
            f.sum(df["v"]).alias("sv"),
            f.count("*").alias("c"),
            f.min(df["x"]).alias("mn"),
            f.max(df["v"]).alias("mx"),
            f.avg(df["x"]).alias("av"),
        ), _data(1000, 3), ignore_order=True)


def test_global_agg():
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.agg(
            f.sum(df["v"]).alias("sv"),
            f.count("*").alias("c"),
            f.min(df["v"]).alias("mn"),
            f.max(df["x"]).alias("mx"),
            f.avg(df["v"]).alias("av"),
        ), _data(700, 5))


def test_global_agg_empty_input():
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.filter(df["v"] > 10**9).agg(
            f.sum(df["v"]).alias("sv"),
            f.count("*").alias("c"),
            f.min(df["v"]).alias("mn"),
        ), _data(100, 1))


def test_groupby_string_key():
    data = dg.gen_batch({
        "sk": dg.StringGen(max_len=3, charset="abc"),
        "v": dg.IntGen(dg.T.INT64, min_val=-50, max_val=50),
    }, 400, 11)
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("sk").agg(
            f.sum(df["v"]).alias("sv"), f.count("*").alias("c")),
        data, ignore_order=True)


def test_groupby_string_minmax():
    data = dg.gen_batch({
        "k": dg.IntGen(dg.T.INT32, min_val=0, max_val=4),
        "s": dg.StringGen(max_len=6),
    }, 300, 13)
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k").agg(
            f.min(df["s"]).alias("mn"), f.max(df["s"]).alias("mx"),
            f.count(df["s"]).alias("c")),
        data, ignore_order=True)


def test_groupby_nullable_float_key():
    """Null keys group together; -0.0 and 0.0 group together; NaNs group
    together (Spark normalization semantics)."""
    data = {
        "k": [0.0, -0.0, None, float("nan"), float("nan"), 1.5, None, 0.0],
        "v": [1, 2, 3, 4, 5, 6, 7, 8],
    }
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k").agg(f.sum(df["v"]).alias("sv"),
                                        f.count("*").alias("c")),
        data, ignore_order=True)


def test_groupby_all_null_values():
    data = {
        "k": [1, 1, 2, 2, 3],
        "v": [None, None, 5, None, None],
    }
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k").agg(
            f.sum(df["v"]).alias("sv"), f.count(df["v"]).alias("c"),
            f.min(df["v"]).alias("mn"), f.avg(df["v"]).alias("av")),
        data, ignore_order=True)


def test_distinct():
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.select("k", "k2").distinct(),
        _data(400, 17), ignore_order=True)


def test_groupby_expression_key():
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by((df["k"] % 3).alias("m")).agg(
            f.sum(df["v"]).alias("sv")),
        _data(300, 19), ignore_order=True)


def test_first_last_after_sort():
    # first/last are order-sensitive: sort within partitions first so both
    # engines see the same order
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.sort_within_partitions("v", "x", "k")
        .group_by("k").agg(f.first(df["v"]).alias("fv"),
                           f.last(df["v"]).alias("lv")),
        _data(200, 23), ignore_order=True)


def test_aggregate_on_device_plan_placement():
    """Both aggregate stages must land on the device (strict mode)."""
    from spark_rapids_tpu import Session

    sess = Session({
        "spark.rapids.tpu.sql.test.enabled": True,
        "spark.rapids.tpu.sql.test.allowedNonTpu":
            "ShuffleExchangeExec",
    })
    df = sess.create_dataframe({"k": [1, 1, 2], "v": [1.0, 2.0, 3.0]})
    out = df.group_by("k").agg(f.sum(df["v"]).alias("s")).collect()
    assert sorted(out) == [(1, 3.0), (2, 3.0)]


def test_first_last_ignore_nulls_semantics():
    """Spark: first(col) default keeps nulls (first ROW's value);
    ignore_nulls=True skips to the first non-null."""
    data = {"k": [1, 1, 1, 2, 2], "v": [None, 5, 6, None, None]}
    rows = assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.sort_within_partitions("v")
        .group_by("k").agg(
            f.first(df["v"]).alias("f_keep"),
            f.first(df["v"], ignore_nulls=True).alias("f_skip"),
            f.last(df["v"], ignore_nulls=True).alias("l_skip"),
        ), data, ignore_order=True, n_partitions=1)
    by_k = {r[0]: r[1:] for r in rows}
    assert by_k[1] == (None, 5, 6)
    assert by_k[2] == (None, None, None)


def test_groupby_null_vs_nan_key_boundary():
    """A NULL float key (whose backing data may be NaN) must not merge
    with an adjacent valid-NaN key group."""
    nan = float("nan")
    data = {"k": [nan, None, nan, None, 1.0], "v": [1, 2, 3, 4, 5]}
    rows = assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k").agg(f.sum(df["v"]).alias("s"),
                                        f.count("*").alias("c")),
        data, ignore_order=True)
    assert len(rows) == 3


def test_functions_accept_column_names():
    """f.sum("v") means column v, not the literal string (pyspark)."""
    assert_tpu_and_cpu_are_equal_collect(
        lambda df: df.group_by("k").agg(f.sum("v").alias("s"),
                                        f.max("v").alias("m")),
        {"k": [1, 1, 2], "v": [10, 20, 30]}, ignore_order=True)


def test_q1_shaped_aggregate_scatters_nothing_row_wide():
    """A partial aggregate of q1's shape (2 string keys, 11 buffers)
    reduces its sorted rows by scans: no scatter takes an index a row
    (the 2^22-bin scatter-adds it once was must not come back)."""
    import jax

    from spark_rapids_tpu import Session
    from spark_rapids_tpu.data.column import HostBatch, host_to_device
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

    n = 3000
    data = dg.gen_batch({
        "flag": dg.StringGen(max_len=1, nullable=False),
        "status": dg.StringGen(max_len=1, nullable=False),
        "qty": dg.FloatGen(dg.T.FLOAT64, no_nans=True),
        "price": dg.FloatGen(dg.T.FLOAT64, no_nans=True),
        "disc": dg.FloatGen(dg.T.FLOAT64, no_nans=True),
        "tax": dg.FloatGen(dg.T.FLOAT64, no_nans=True),
    }, n, 7)
    sess = Session()
    df = sess.create_dataframe(data, n_partitions=1)
    disc_price = df["price"] * (f.lit(1.0) - df["disc"])
    q = df.group_by("flag", "status").agg(
        f.sum(df["qty"]).alias("sum_qty"),
        f.sum(df["price"]).alias("sum_base_price"),
        f.sum(disc_price).alias("sum_disc_price"),
        f.sum(disc_price * (f.lit(1.0) + df["tax"])).alias("sum_charge"),
        f.avg(df["qty"]).alias("avg_qty"),
        f.avg(df["price"]).alias("avg_price"),
        f.avg(df["disc"]).alias("avg_disc"),
        f.count(df["qty"]).alias("count_order"))
    todo, partial = [sess.physical_plan(q.plan)], None
    while todo:
        node = todo.pop()
        if isinstance(node, TpuHashAggregateExec) and node.mode == "partial":
            partial = node
        todo.extend(node.children)
    assert partial is not None and len(partial.keys) == 2
    assert len(partial.buffer_schema.fields) == 2 + 11

    batch = host_to_device(data if isinstance(data, HostBatch)
                           else HostBatch.from_pydict(data))
    padded = batch.padded_rows
    assert padded == 4096
    jaxpr = jax.make_jaxpr(partial.compute_batch)(batch)
    eqns = list(jaxpr_eqns(jaxpr.jaxpr))
    wide = [(e.primitive.name, e.invars[1].aval.shape) for e in eqns
            if e.primitive.name.startswith("scatter")
            and e.invars[1].aval.shape[:1] >= (padded // 2,)]
    assert not wide, wide
    assert any(e.primitive.name == "scan" for e in eqns)
    # the counts: one prefix sum over a stack with a row a distinct
    # validity (11 buffers, 8 inputs: an average's two share theirs)
    stacked = [e.invars[0].aval.shape for e in eqns
               if e.primitive.name == "cumsum" and e.invars[0].aval.ndim == 3]
    assert stacked == [(8, padded // G._SCAN_BLOCK, G._SCAN_BLOCK)], stacked
