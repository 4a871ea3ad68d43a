"""The chip's compiler, asked from the CPU sandbox.

The TPU's compiler is installed wherever jax[tpu] is, and compiles for a
chip that is described and not attached (``on-chip-measurement`` §2.3).
These tests hand it a few programs of the served path — at 2^14 rows, a
few seconds each — so that what it refuses (PR 21: every bitcast out of
f64, which the chip holds as two f32) or bloats fails here, on every PR,
at no chip time.  Nothing runs on a device and nothing here is a timing.

Everything that touches the topology lives in module-scoped fixtures that
are not autouse: only the xdist worker that is given this file loads the
TPU library, and a worker that cannot describe the chip skips.
"""
import os

import numpy as np
import pytest
from conftest import REPO

import spark_rapids_tpu  # noqa: F401  (x64 on, pytrees registered)
from spark_rapids_tpu import types as T
from spark_rapids_tpu.data.column import DeviceBatch, DeviceColumn

ROWS = 1 << 14


@pytest.fixture(scope="module")
def topo():
    """Four described v5e chips (2x2), with jax's persistent cache off
    around the module (an entry compiled for a described chip is
    written but cannot be read back without one)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_tpu(monkeypatch):
    """The engine picks its float64 paths from ``jax.default_backend()``,
    which is ``cpu`` in this process: make it answer ``tpu`` so the
    chip's branch is the one traced."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(one_chip, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=one_chip)


def _column(one_chip, dtype, width=None):
    """A DeviceColumn of shapes (no arrays: a described chip holds none)."""
    valid = _shape(one_chip, (ROWS,), np.bool_)
    if dtype.is_string:
        return DeviceColumn(dtype, _shape(one_chip, (ROWS, width), np.uint8),
                            valid, _shape(one_chip, (ROWS,), np.int32))
    return DeviceColumn(dtype, _shape(one_chip, (ROWS,), dtype.np_dtype),
                        valid)


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _compile(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).compile()


# --------------------------------------------------------------------------
# sort keys and hashes: every dtype family the chip treats differently
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,width", [(T.FLOAT64, None), (T.INT64, None),
                                         (T.STRING, 25)],
                         ids=["float64", "int64", "string"])
def test_lexsort_compiles_for_v5e(one_chip, as_tpu, dtype, width):
    """The device lexsort (sort, group-by, join and range exchange all
    go through it) keyed on each dtype branch of the key encoding, one
    descending nulls-last column like q3's ``ORDER BY revenue DESC``."""
    from spark_rapids_tpu.ops.kernels import segment as seg

    def order(col, pad_valid):
        return seg.lexsort_device([col], [True], [False], pad_valid)

    compiled = _compile(order, _column(one_chip, dtype, width),
                        _shape(one_chip, (ROWS,), np.bool_))
    # one sort in the program however many words the key has
    assert compiled.as_text().count(" sort(") <= 2


def test_bitcast_out_of_float64_is_refused_by_the_chip(one_chip):
    """The finding the float64 paths are built around.  If a later
    compiler accepts this, the IEEE-image path can serve the chip too
    and ``float64_words_pair`` / ``device_hash_gap`` can go."""
    from spark_rapids_tpu.ops.kernels import segment as seg

    with pytest.raises(Exception, match="X64 element types"):
        _compile(seg.float64_words_ieee,
                 _shape(one_chip, (ROWS,), np.float64))


def test_int64_device_hash_compiles_and_float64_is_tagged(one_chip,
                                                           as_tpu):
    import jax.numpy as jnp

    from spark_rapids_tpu.utils import hashing

    seed = _shape(one_chip, (ROWS,), np.uint32)
    _compile(hashing.hash_device_column, _column(one_chip, T.INT64), seed)
    _compile(hashing.hash_device_column, _column(one_chip, T.STRING, 25),
             seed)
    # float64: no Spark-compatible hash exists on the chip — the plan
    # rules tag it (hashing.device_hash_gap), a stray trace raises
    assert "IEEE" in hashing.device_hash_gap(T.FLOAT64)
    assert hashing.device_hash_gap(T.INT64) is None
    with pytest.raises(TypeError, match="IEEE"):
        hashing.hash_device_column(
            DeviceColumn(T.FLOAT64, jnp.zeros((8,)),
                         jnp.ones((8,), jnp.bool_)),
            jnp.zeros((8,), jnp.uint32))


def test_float64_hash_key_is_tagged_at_plan_time(as_tpu):
    """On the chip a hash exchange keyed on a double stays on the host
    engine and says why in ``explain()`` — never a failed dispatch."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu import f

    sess = srt.Session()
    df = sess.create_dataframe({"k": [1.5, 2.5, 1.5], "v": [1, 2, 3]})
    report = df.group_by("k").agg(f.sum("v")).explain()
    tagged = [ln for ln in report.splitlines()
              if "ShuffleExchangeExec" in ln]
    assert tagged and all(ln.strip().startswith("!") and "IEEE" in ln
                          for ln in tagged), report
    keyed_on_int = df.group_by("v").agg(f.sum("k")).explain()
    assert "* ShuffleExchangeExec" in keyed_on_int, keyed_on_int


def test_group_by_exchange_hashes_only_the_keys_the_chip_can_hash(
        monkeypatch):
    """Equal groups agree on every subset of their keys, so a group-by
    over a double and keys that hash partitions on the latter alone
    (``plan/planner.py:_exchange_keys``) and its exchange stays on the
    device, the double a grouping key all the same.  Run here with the
    gap declared on this backend too: a device hash of the double would
    raise inside the trace."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu import f
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.utils import hashing

    monkeypatch.setattr(
        hashing, "device_hash_gap",
        lambda dtype: "no IEEE bits" if dtype == T.FLOAT64 else None)
    rng = np.random.default_rng(34)
    data = {"k": rng.integers(0, 40, 600).tolist(),
            "price": (rng.integers(0, 3, 600) + 0.25).tolist(),
            "v": rng.integers(0, 9, 600).tolist()}

    def grouped(sess):
        return (sess.create_dataframe(data, n_partitions=4)
                .group_by("price", "k").agg(f.sum("v").alias("s")))

    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    df = grouped(sess)
    assert "! ShuffleExchangeExec" not in df.explain()

    exchange, = [n for n in _walk(sess.physical_plan(df.plan))
                 if isinstance(n, TpuShuffleExchangeExec)]
    assert [k.sql() for k in exchange.partitioning.keys] == ["k"]
    got = df.collect()
    assert len(got) == len({(p, k) for p, k in
                            zip(data["price"], data["k"])})
    assert sorted(got) == sorted(
        grouped(srt.Session(tpu_enabled=False)).collect())


def test_mesh_runner_names_the_float64_hash_gap(as_tpu):
    """The mesh runner adds hash exchanges of its own (join-colocation
    repair, complete-mode aggregates, window partition-by) on keys no
    plan rule looked at.  Here the planned exchange on the double is
    tagged to the host, the window above it stays on the device, and
    the runner would re-exchange on the double itself: that ends the
    lowering with the reason, not with a TypeError inside a trace."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.ops.windowexprs import over, row_number, window
    from spark_rapids_tpu.parallel.runner import (DistributedUnsupported,
                                                  run_distributed)

    sess = srt.Session()
    df = sess.create_dataframe({"k": [1.5, 2.5, 1.5, 2.5],
                                "t": [1, 2, 3, 4]}, n_partitions=2)
    keyed_on_double = df.with_window("w", over(
        row_number(), window().partition_by("k").order_by("t")))
    report = keyed_on_double.explain()
    assert "* WindowExec" in report and "! ShuffleExchangeExec" in report
    with pytest.raises(DistributedUnsupported, match="IEEE"):
        run_distributed(sess, keyed_on_double, n_devices=4)
    keyed_on_int = df.with_window("w", over(
        row_number(), window().partition_by("t").order_by("k")))
    assert sorted(run_distributed(sess, keyed_on_int,
                                  n_devices=4).to_rows()) == [
        (1.5, 1, 1), (1.5, 3, 1), (2.5, 2, 1), (2.5, 4, 1)]


# --------------------------------------------------------------------------
# compaction and the q1 pipeline
# --------------------------------------------------------------------------
def test_compaction_compiles_with_small_temp(one_chip):
    """Filter compaction (prefix sum + one scatter, no sort) over an
    f64 + i32 + bool batch: the program's temp must stay a small
    multiple of its input (a lane-padded layout once cost 512 B/row)."""
    from spark_rapids_tpu.ops.kernels.gather import compact

    schema = T.Schema([T.Field("a", T.FLOAT64), T.Field("b", T.INT32),
                       T.Field("c", T.BOOL)])
    batch = DeviceBatch(schema, [_column(one_chip, f.dtype)
                                 for f in schema],
                        _shape(one_chip, (), np.int32))
    compiled = _compile(compact, batch,
                        _shape(one_chip, (ROWS,), np.bool_))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 4 * mem.argument_size_in_bytes, mem
    assert " sort(" not in compiled.as_text()


def test_q1_pipeline_compiles_for_v5e(one_chip, as_tpu):
    """The whole flagship q1 chain — fused filter/project segment,
    sort-based partial aggregate, final aggregate — as one program."""
    import jax

    from spark_rapids_tpu.models.flagship import build_q1_pipeline

    fn, example = build_q1_pipeline(n_rows=ROWS, seed=0)
    shapes = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype), example)
    mem = _compile(fn, shapes).memory_analysis()
    assert mem.temp_size_in_bytes <= 64 * mem.argument_size_in_bytes, mem


@pytest.mark.parametrize("mode", ["partial", "final"])
def test_group_by_with_a_float64_key_compiles_for_v5e(one_chip, as_tpu,
                                                      mode):
    """The shape of TPC-H q18's second group-by, planned as the chip
    plans it: an 18-byte string, two integers and a double as keys, and
    a sum.  The
    double is ordered and compared as its pair of float32 words and is
    hashed nowhere: the exchange between the two aggregates partitions
    on the others (``plan/planner.py:_exchange_keys``)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu import f
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec

    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    df = (sess.create_dataframe(
        {"c_name": ["Customer#000000001", "Customer#000000002"],
         "c_custkey": [1, 2], "o_orderkey": [1, 2],
         "o_totalprice": [10.5, 20.25], "l_quantity": [3.0, 4.0]},
        n_partitions=2)
        .group_by("c_name", "c_custkey", "o_orderkey", "o_totalprice")
        .agg(f.sum("l_quantity").alias("sum_quantity")))

    nodes = list(_walk(sess.physical_plan(df.plan)))   # strict: no raise
    exchange, = [n for n in nodes if isinstance(n, TpuShuffleExchangeExec)]
    assert [k.sql() for k in exchange.partitioning.keys] == \
        ["c_name", "c_custkey", "o_orderkey"]
    agg, = [n for n in nodes if isinstance(n, TpuHashAggregateExec)
            and n.mode == mode]
    schema = agg.children[0].schema
    batch = DeviceBatch(schema, [_column(one_chip, fld.dtype, 18)
                                 for fld in schema],
                        _shape(one_chip, (), np.int32))
    out = _compile(agg.kernel_twin().compute_batch, batch).out_info
    assert [c.dtype for c in out.columns] == \
        [fld.dtype for fld in agg.schema]


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_absorbed_filter_aggregate_compiles_for_v5e_with_no_scatter(
        one_chip, as_tpu, query):
    """q6's and q1's partial aggregate with the filter under it run as
    its prologue (plan/fusion.py): one program for the chip, and none of
    compaction's scatter in it: the keep mask is all the filter hands
    on."""
    import datetime

    import spark_rapids_tpu as srt
    from spark_rapids_tpu import f
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.ops.kernels.gather import compact

    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    schema = T.Schema(
        [T.Field("l_shipdate", T.DATE32), T.Field("l_returnflag", T.STRING),
         T.Field("l_linestatus", T.STRING)]
        + [T.Field(n, T.FLOAT64) for n in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax")])
    df = sess.create_dataframe(
        {"l_shipdate": [8825, 8826],    # days: March 1994
         "l_returnflag": ["A", "N"], "l_linestatus": ["F", "O"],
         "l_quantity": [3.0, 30.0], "l_extendedprice": [10.5, 20.25],
         "l_discount": [0.06, 0.01], "l_tax": [0.02, 0.04]},
        schema=schema)
    if query == "q6":
        q = df.filter(
            (f.col("l_shipdate") >= f.lit(datetime.date(1994, 1, 1)))
            & (f.col("l_discount") >= f.lit(0.05))
            & (f.col("l_quantity") < f.lit(24.0))).agg(
            f.sum(f.col("l_extendedprice") * f.col("l_discount"))
            .alias("revenue"))
    else:
        q = (df.filter(f.col("l_shipdate")
                       <= f.lit(datetime.date(1998, 9, 2)))
             .group_by("l_returnflag", "l_linestatus")
             .agg(f.sum("l_quantity").alias("sum_qty"),
                  f.avg("l_discount").alias("avg_disc"),
                  f.count("l_quantity").alias("count_order")))

    agg, = [n for n in _walk(sess.physical_plan(q.plan))
            if isinstance(n, TpuHashAggregateExec) and n.absorbed]
    schema = agg.children[0].schema
    batch = DeviceBatch(schema, [_column(one_chip, fld.dtype, 1)
                                 for fld in schema],
                        _shape(one_chip, (), np.int32))
    text = _compile(agg.kernel_twin().compute_batch, batch).as_text()
    assert " scatter(" not in text
    if query == "q6":
        # a keyless aggregate reads its one segment's end, not every
        # row's: no gather of a batch's worth of indices is left
        wide = [ln for ln in text.splitlines()
                if " gather(" in ln and f"[{ROWS}]" in ln.split("=")[1]
                .split("gather(")[0]]
        assert wide == [], wide[:2]
        assert " conditional(" not in text
    else:
        # a keyed aggregate counts its groups on the device, and its
        # one-row-a-segment reads stand in ONE conditional, a branch a
        # read width (``segment.read_tiers``), which the chip's compiler
        # keeps a conditional
        from spark_rapids_tpu.ops.kernels.segment import read_tiers

        switch, = [ln for ln in text.splitlines() if " conditional(" in ln]
        assert switch.count("%region") == len(read_tiers(ROWS)) == 4
    # what the standalone filter ran: the scatter is compaction's
    packed = _compile(compact, batch, _shape(one_chip, (ROWS,), np.bool_))
    assert " scatter(" in packed.as_text()


def test_exchange_trim_and_build_compile_for_v5e(one_chip, as_tpu):
    """The exchange's write of a sparse batch: the trim to a 128-row
    bucket, then partition ids' sort and the packed build at that
    size — a string, an f64 and an i64 column, as q1's groups are.
    What leaves the trim is 128 rows, whatever came in."""
    import jax.numpy as jnp

    from spark_rapids_tpu.shuffle import device_shuffle as DS

    schema = T.Schema([T.Field("s", T.STRING), T.Field("x", T.FLOAT64),
                       T.Field("n", T.INT64)])
    batch = DeviceBatch(schema, [_column(one_chip, f.dtype, 16)
                                 for f in schema],
                        _shape(one_chip, (), np.int32))
    mem = _compile(lambda b: DS.trim(b, 128), batch).memory_analysis()
    assert mem.output_size_in_bytes * 64 <= mem.argument_size_in_bytes, mem

    def write(b):
        cut = DS.trim(b, 128)
        pids = jnp.arange(128, dtype=jnp.int32) % 2
        return DS.packed_build(cut, pids, 2)

    block, counts, _starts = _compile(write, batch).out_info
    assert block.padded_rows == 128 and counts.shape == (2,)


def test_mesh_exchange_compiles_for_four_chips(topo):
    """The distributed runner's stage shape — a collective exchange
    (``all_to_all`` of i64 and f64 tiles) plus the capacity demand
    replicated with ``pmax`` — for a 2x2 mesh.  The demand goes as
    int32: the chip's compiler lowers no 64-bit all-reduce but a sum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.parallel.runner import _max_dest_count

    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    spread = NamedSharding(mesh, P("dp"))
    schema = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])

    def stacked(shape, dtype):
        return jax.ShapeDtypeStruct((4,) + shape, np.dtype(dtype),
                                    sharding=spread)

    batch = DeviceBatch(
        schema, [DeviceColumn(f.dtype, stacked((ROWS,), f.dtype.np_dtype),
                              stacked((ROWS,), np.bool_))
                 for f in schema], stacked((), np.int32))

    def stage(demand_dtype):
        def per_shard(b):
            b = X.squeeze_leading(b)
            pids = X.device_partition_ids(b, [0], 4)
            out = X.collective_exchange(b, pids, 4, "dp", capacity=ROWS)
            demand = _max_dest_count(pids, 4).astype(demand_dtype)
            return X.unsqueeze_leading(out), jax.lax.pmax(demand, "dp")

        return jax.shard_map(per_shard, mesh=mesh, in_specs=P("dp"),
                             out_specs=(P("dp"), P()))

    assert "all-to-all(" in _compile(stage(jnp.int32), batch).as_text()
    with pytest.raises(Exception, match="Sum all reduce"):
        _compile(stage(jnp.int64), batch)


def test_group_by_switch_compiles_for_four_chips(topo, as_tpu):
    """A keyed aggregate's stage body under ``shard_map`` on a 2x2 mesh:
    each shard counts its own groups, so the switch over read widths
    (``segment.read_tiers``) has a shard-varying index, and every branch
    gives back values as shard-varying as the others.  The chip's
    compiler takes it with the conditional kept and no collective in
    the program."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import spark_rapids_tpu as srt
    from spark_rapids_tpu import f
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.parallel import exchange as X

    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    df = sess.create_dataframe({"k": [1, 2], "v": [3.0, 4.0]})
    q = df.group_by("k").agg(f.sum("v").alias("s"), f.count("v").alias("c"))
    agg, = [n for n in _walk(sess.physical_plan(q.plan))
            if isinstance(n, TpuHashAggregateExec) and n.mode == "partial"]

    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    spread = NamedSharding(mesh, P("dp"))

    def stacked(shape, dtype):
        return jax.ShapeDtypeStruct((4,) + shape, np.dtype(dtype),
                                    sharding=spread)

    schema = agg.children[0].schema
    batch = DeviceBatch(
        schema, [DeviceColumn(fld.dtype,
                              stacked((ROWS,), fld.dtype.np_dtype),
                              stacked((ROWS,), np.bool_))
                 for fld in schema], stacked((), np.int32))
    twin = agg.kernel_twin()

    def per_shard(b):
        return X.unsqueeze_leading(twin.compute_batch(X.squeeze_leading(b)))

    text = _compile(jax.shard_map(per_shard, mesh=mesh, in_specs=P("dp"),
                                  out_specs=P("dp")), batch).as_text()
    assert text.count(" conditional(") == 1
    assert "all-reduce(" not in text and "all-to-all(" not in text


def test_join_probe_and_expand_compile_for_four_chips(topo, as_tpu):
    """The mesh runner's join stage body (``join_static``: probe, emit
    counts, expand) under ``shard_map`` on a 2x2 mesh.  The probe's
    scans start their carries from the inputs and nothing in it
    scatters, so the chip's compiler takes it (a scatter whose indices
    and updates both come from an iota aborts its fusion pass); the
    program holds the lexsort's two sorts and the probe's two, and where
    the expand maps its slots by sort (as many slots as left rows) that
    mapping's two."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_tpu.ops.kernels import join as J

    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    spread = NamedSharding(mesh, P("dp"))

    def stacked(dtype):
        return jax.ShapeDtypeStruct((4, ROWS), np.dtype(dtype),
                                    sharding=spread)

    def key():
        return DeviceColumn(T.INT64, stacked(np.int64), stacked(np.bool_))

    def per_shard(lk, rk, l_ok, r_ok):
        lk, rk = (DeviceColumn(c.dtype, c.data[0], c.validity[0])
                  for c in (lk, rk))
        p = J.probe([lk], [rk], l_ok[0], r_ok[0])
        emit, r_extra, total = J.emit_counts(p, "full", l_ok[0], r_ok[0])
        pairs = J.expand_pairs(p, emit, r_extra, ROWS)
        return tuple(x[None] for x in pairs) + (total[None],)

    stage = jax.shard_map(per_shard, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp"))
    text = _compile(stage, key(), key(), stacked(np.bool_),
                    stacked(np.bool_)).as_text()
    assert J.expand_by_sort(ROWS, ROWS)
    assert text.count(" sort(") == 6, text.count(" sort(")


@pytest.mark.parametrize("value", [T.INT32, T.INT64], ids=["int32", "int64"])
def test_semi_join_bounds_compile_for_v5e(one_chip, as_tpu, value):
    """A semi join whose condition is one comparison (``l <> r``):
    the bounds program, its sort, its one stacked read of the key and
    value words, the max-scan of int32 or int64 values and the sort
    back. It holds the lexsort's sorts and the sort back, and no pair
    layout: its temporaries stay a small multiple of its input."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec
    from spark_rapids_tpu.plan import functions as F

    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    ls = T.Schema([T.Field("k", T.INT64), T.Field("a", value)])
    rs = T.Schema([T.Field("k2", T.INT64), T.Field("b", value)])
    lf = sess.create_dataframe({"k": [1], "a": [2]}, schema=ls)
    rf = sess.create_dataframe({"k2": [1], "b": [3]}, schema=rs)
    df = lf.join(rf, on=(["k"], ["k2"]), how="semi",
                 condition=F.col("a") != F.col("b"))
    op, = [n for n in _walk(sess.physical_plan(df.plan))
           if isinstance(n, TpuHashJoinExec)]
    assert op.path == "bounds"

    def batch(schema):
        return DeviceBatch(schema, [_column(one_chip, f.dtype)
                                    for f in schema],
                           _shape(one_chip, (), np.int32))

    compiled = _compile(op._bounds_kernel.fn, batch(ls), batch(rs))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 64 * mem.argument_size_in_bytes, mem
    assert compiled.as_text().count(" sort(") >= 2


# --------------------------------------------------------------------------
# what the chip's float64 branch computes (runs on the CPU backend)
# --------------------------------------------------------------------------
def test_float64_pair_words_order_like_the_oracle(as_tpu):
    """``float64_words_pair`` on doubles that a pair of float32 holds
    exactly — all the chip can hold — orders them as the numpy oracle
    orders their IEEE images, NaN, infinities and signed zeros
    included, ascending and descending."""
    import jax.numpy as jnp

    from spark_rapids_tpu.data.column import HostColumn
    from spark_rapids_tpu.ops.kernels import segment as seg

    rng = np.random.default_rng(3)
    hi = (rng.standard_normal(4000) * 1e4).astype(np.float32)
    lo = (hi * rng.uniform(-1, 1, 4000) * 2.0 ** -26).astype(np.float32)
    vals = np.concatenate([
        hi.astype(np.float64) + lo.astype(np.float64),
        hi[:500].astype(np.float64),  # equal hi words, lo decides
        [0.0, -0.0, np.inf, -np.inf, np.nan, np.nan, 1.0, 1.0]])
    valid = rng.random(len(vals)) > 0.05
    col = DeviceColumn(T.FLOAT64, jnp.asarray(vals), jnp.asarray(valid))
    host = HostColumn(T.FLOAT64, vals, valid)
    for desc in (False, True):
        got = np.asarray(seg.lexsort_device([col], [desc], [not desc]))
        want = seg.lexsort_np([host], [desc], [not desc])
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# the compile cache's one rule
# --------------------------------------------------------------------------
def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    from spark_rapids_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
