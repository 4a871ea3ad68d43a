"""Collective exchange + distributed two-phase aggregate over a virtual
8-device CPU mesh (the multi-chip fixture the reference never had for
its UCX path — SURVEY §4 'TPU-build implication')."""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.data.column import (HostBatch, host_to_device,
                                          device_to_host)


def _mesh(n):
    from spark_rapids_tpu.parallel.mesh import make_mesh

    return make_mesh(n)


def test_bucket_rows_roundtrip():
    import jax.numpy as jnp

    from spark_rapids_tpu.parallel import exchange as X

    pids = jnp.asarray([2, 0, 1, 0, 4, 2, 4, 4], dtype=jnp.int32)
    # sentinel 4 = invalid rows (num_parts=4)
    rows, valid = X.bucket_rows(pids, 4, 8)
    rows = np.asarray(rows)
    valid = np.asarray(valid)
    assert valid.sum() == 5
    assert set(rows[0][valid[0]].tolist()) == {1, 3}
    assert set(rows[1][valid[1]].tolist()) == {2}
    assert set(rows[2][valid[2]].tolist()) == {0, 5}
    assert set(rows[3][valid[3]].tolist()) == set()


@pytest.mark.parametrize("n_dev", [2, 8])
def test_collective_exchange_repartitions_all_rows(n_dev):
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.parallel.mesh import DATA_AXIS

    mesh = _mesh(n_dev)
    rng = np.random.RandomState(7)
    schema = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])
    locals_, all_rows = [], []
    for p in range(n_dev):
        n = int(rng.randint(3, 30))
        k = rng.randint(0, 50, n)
        v = rng.rand(n)
        all_rows += list(zip(k.tolist(), v.tolist()))
        locals_.append(host_to_device(
            HostBatch.from_pydict({"k": k, "v": v}, schema),
            min_bucket_rows=32))

    def step(local):
        pids = X.device_partition_ids(local, [0], n_dev)
        return X.collective_exchange(local, pids, n_dev, DATA_AXIS)

    spmd = jax.jit(X.exchange_step(mesh, step))
    stacked = X.stack_to_mesh(mesh, X.stack_partitions(locals_))
    out_parts = X.unstack_partitions(spmd(stacked))

    # every input row lands exactly once; rows with equal keys colocate
    got = []
    key_home = {}
    for p, db in enumerate(out_parts):
        hb = device_to_host(db)
        for k, v in zip(hb.column("k").to_pylist(),
                        hb.column("v").to_pylist()):
            got.append((k, v))
            assert key_home.setdefault(k, p) == p
    assert sorted(got) == sorted(all_rows)


def _assert_rows_equal(got, exp):
    assert len(got) == len(exp), (len(got), len(exp))
    for g, e in zip(sorted(got), sorted(exp)):
        assert len(g) == len(e)
        for a, b in zip(g, e):
            if isinstance(a, float) and b is not None:
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9), (g, e)
            else:
                assert a == b, (g, e)


def test_distributed_runner_filter_agg():
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    rng = np.random.RandomState(0)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}

    def q(sess):
        df = sess.create_dataframe(dict(data))
        return (df.filter(df["v"] > 10).group_by("k")
                .agg(F.sum("v").alias("s"), F.count("v").alias("c")))

    sess = Session()
    got = run_distributed(sess, q(sess), mesh=_mesh(8)).to_rows()
    exp = q(Session(tpu_enabled=False)).collect()
    _assert_rows_equal(got, exp)


@pytest.mark.parametrize("threshold", [0, None],
                         ids=["shuffled", "broadcast"])
def test_distributed_runner_join_modes(threshold):
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    rng = np.random.RandomState(1)
    orders = {"o_custkey": rng.randint(0, 50, 400),
              "o_total": rng.rand(400) * 1000}
    cust = {"c_custkey": np.arange(50),
            "c_nation": rng.randint(0, 5, 50)}

    def q(sess):
        o = sess.create_dataframe(dict(orders))
        c = sess.create_dataframe(dict(cust))
        j = o.join(c, on=(["o_custkey"], ["c_custkey"]), how="inner")
        return j.group_by("c_nation").agg(
            F.sum("o_total").alias("rev"), F.count("o_total").alias("n"))

    conf = {} if threshold is None else \
        {"spark.rapids.tpu.sql.broadcastSizeThreshold": threshold}
    sess = Session(dict(conf))
    got = run_distributed(sess, q(sess), mesh=_mesh(8)).to_rows()
    exp = q(Session(tpu_enabled=False)).collect()
    _assert_rows_equal(got, exp)


def test_distributed_join_runs_cross_a_scan_block():
    """Shuffled joins on four devices whose key runs are longer than a
    block of the probe's scans (1024 rows): a shard's ``join_static``
    counts a run block by block, and answers as one device does."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.ops.kernels.gather import _SCAN_BLOCK
    from spark_rapids_tpu.parallel.runner import run_distributed

    rng = np.random.RandomState(31)
    lk = rng.randint(0, 40, 9000).astype(np.int64)
    lk[rng.rand(9000) < 0.4] = 17           # one run of ~3600 left rows
    rk = np.concatenate([np.arange(40), [17, 17, 3]]).astype(np.int64)
    assert (lk == 17).sum() > 3 * _SCAN_BLOCK

    def q(sess, how):
        l = sess.create_dataframe({"k": lk, "v": np.arange(9000)})
        r = sess.create_dataframe({"rk": rk, "w": np.arange(len(rk))})
        return l.join(r, on=(["k"], ["rk"]), how=how)

    conf = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0}
    for how in ("inner", "full"):
        sess = Session(dict(conf))
        got = run_distributed(sess, q(sess, how), mesh=_mesh(4)).to_rows()
        one = q(Session(dict(conf)), how).collect()
        assert len(one) > 9000 + 2 * 3 * _SCAN_BLOCK
        _assert_rows_equal(got, one)


@pytest.mark.parametrize("threshold", [0, None],
                         ids=["shuffled", "broadcast"])
@pytest.mark.parametrize("how", ["semi", "anti"])
def test_distributed_conditional_semi_anti_join_keeps_its_condition(
        how, threshold):
    """A semi/anti join with a condition the bounds refuse (an operand
    that reads both sides) runs its pair kernel inside the stage program
    (``join_static``): the pairs of one key (300 x 40 on a shard, far
    past the first capacity) overflow, the runner retries at their
    count, and the answer is the host engine's, never the unconditioned
    one."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    rng = np.random.RandomState(38)
    lk = rng.randint(0, 30, 1200).astype(np.int64)
    lk[:300] = 7
    ls = rng.randint(0, 4, 1200).astype(np.int64)
    rk = np.concatenate([np.full(40, 7),
                         rng.choice([k for k in range(30) if k != 7], 200)])
    rs = rng.randint(0, 4, len(rk)).astype(np.int64)
    rs[:40] = 2                     # key 7: only s != 2 finds a pair

    def q(sess, condition=True):
        l = sess.create_dataframe({"k": lk, "s": ls})
        r = sess.create_dataframe({"rk": rk.astype(np.int64), "rs": rs})
        return l.join(r, on=(["k"], ["rk"]), how=how,
                      condition=(F.col("s") - F.col("rs") != F.lit(0))
                      if condition else None)

    conf = {} if threshold is None else \
        {"spark.rapids.tpu.sql.broadcastSizeThreshold": threshold}
    sess = Session(dict(conf))
    got = run_distributed(sess, q(sess), mesh=_mesh(4)).to_rows()
    exp = q(Session(tpu_enabled=False)).collect()
    plain = q(Session(tpu_enabled=False), condition=False).collect()
    assert sorted(map(tuple, exp)) != sorted(map(tuple, plain))
    _assert_rows_equal(got, exp)
    assert sess.last_metrics["distributed.stageRetries"] >= 1


@pytest.mark.parametrize("threshold", [0, None],
                         ids=["shuffled", "broadcast"])
@pytest.mark.parametrize("op", ["!=", "<", ">="])
@pytest.mark.parametrize("how", ["semi", "anti"])
def test_distributed_semi_anti_join_decides_by_the_bounds(how, op,
                                                          threshold):
    """A semi/anti join whose condition is one comparison of a left and
    a right column is decided in the stage program by each key's least
    and greatest right value (``join_static``): no pair, so no demand
    and no retry, and the answer is the one chip's and the host's."""
    import operator

    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    rng = np.random.RandomState(42)
    lk = rng.randint(0, 30, 1200).astype(np.int64)
    lk[:300] = 7
    ls = [None if i % 13 == 5 else int(v)
          for i, v in enumerate(rng.randint(0, 6, 1200))]
    rk = np.concatenate([np.full(40, 7),
                         rng.choice([k for k in range(30) if k != 7], 200)])
    rs = [None if i % 11 == 3 else int(v)
          for i, v in enumerate(rng.randint(0, 6, len(rk)))]
    rs[:40] = [2] * 40              # key 7: one right value, 2
    cmp = {"!=": operator.ne, "<": operator.lt, ">=": operator.ge}[op]

    def q(sess, condition=True):
        l = sess.create_dataframe({"k": lk, "s": ls})
        r = sess.create_dataframe({"rk": rk.astype(np.int64), "rs": rs})
        return l.join(r, on=(["k"], ["rk"]), how=how,
                      condition=cmp(F.col("s"), F.col("rs"))
                      if condition else None)

    conf = {} if threshold is None else \
        {"spark.rapids.tpu.sql.broadcastSizeThreshold": threshold}
    def rows(got):      # NULLs among them: no order of their own
        return sorted(map(tuple, got), key=repr)

    sess = Session(dict(conf))
    got = run_distributed(sess, q(sess), mesh=_mesh(4)).to_rows()
    exp = rows(q(Session(tpu_enabled=False)).collect())
    one = Session(dict(conf))
    assert rows(q(one).collect()) == exp
    assert one.last_metrics["join.conditionByBounds"] >= 1
    assert rows(q(Session(tpu_enabled=False), condition=False)
                .collect()) != exp
    assert rows(got) == exp
    assert sess.last_metrics.get("distributed.stageRetries", 0) == 0


def test_distributed_global_sort_order_preserved():
    """Global sort above a join+agg must come back in sorted order even
    though the range exchange below it executes as a host leaf (the
    runner gathers to one shard before sorting)."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu import f
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    rng = np.random.RandomState(9)
    fact = {"k": rng.randint(0, 30, 600), "v": rng.rand(600) * 50}
    dim = {"dk": np.arange(30), "grp": rng.randint(0, 4, 30)}

    def q(sess):
        fd = sess.create_dataframe(dict(fact))
        dd = sess.create_dataframe(dict(dim))
        j = fd.join(dd, on=(["k"], ["dk"]), how="inner") \
            .filter(f.col("v") > 5)
        return (j.group_by("grp")
                .agg(F.sum("v").alias("s"), F.count("v").alias("n"))
                .sort(f.col("s").desc()))

    sess = Session({"spark.rapids.tpu.sql.broadcastSizeThreshold": 0})
    got = run_distributed(sess, q(sess), mesh=_mesh(8)).to_rows()
    exp = q(Session(tpu_enabled=False)).collect()
    assert [r[0] for r in got] == [r[0] for r in exp]
    _assert_rows_equal(got, exp)


@pytest.mark.parametrize("qnum", [5, 16])
def test_distributed_tpch_query(qnum):
    """VERDICT r1 #2 'done' criterion: q5/q16-shaped multi-join TPC-H
    queries oracle-equal on the virtual 8-device mesh."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
    from spark_rapids_tpu.parallel.runner import run_distributed

    sess = Session()
    tables = tpch_datagen.dataframes(sess, sf=0.002, seed=7)
    got = run_distributed(sess, tpch.QUERIES[qnum](tables),
                          mesh=_mesh(8)).to_rows()

    cpu = Session(tpu_enabled=False)
    ctables = tpch_datagen.dataframes(cpu, sf=0.002, seed=7)
    exp = tpch.QUERIES[qnum](ctables).collect()
    _assert_rows_equal(got, exp)


def test_retile_trims_to_the_row_bucket_not_the_shard_count():
    """A stacked stage output is [n_shards, padded, ...].  The trim
    between stages compares the rows' bucket with axis 1; read from
    axis 0 (the shard count, below every bucket) it never trimmed, and
    each stage handed its capacity growth on to the next — PR 21 saw
    q3 at 2^25 rows a shard for 10 rows of answer."""
    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.parallel.runner import DistributedRunner

    schema = T.Schema([T.Field("k", T.INT64), T.Field("s", T.STRING)])
    counts = [3, 1, 0, 200]
    shards = [HostBatch.from_pydict(
        {"k": np.arange(n), "s": [f"r{i}" for i in range(n)]}, schema)
        for n in counts]
    mesh = _mesh(4)
    wide = DistributedRunner(mesh, min_bucket_rows=4096)
    stacked = wide._place(wide._stack_host(shards))
    assert stacked.columns[0].data.shape[:2] == (4, 4096)
    runner = DistributedRunner(mesh)

    out = runner._retile(stacked)
    for c in out.columns:
        assert c.data.shape[:2] == (4, 256) and \
            c.validity.shape == (4, 256), c.data.shape
    assert out.columns[1].lengths.shape == (4, 256)
    assert len({s.device.id
                for s in out.columns[0].data.addressable_shards}) == 4
    for n, part in zip(counts, X.unstack_partitions(out)):
        hb = device_to_host(part)
        assert hb.column("k").to_pylist() == list(range(n))
        assert hb.column("s").to_pylist() == [f"r{i}" for i in range(n)]
    # already at its bucket: handed back as it is
    assert runner._retile(out) is out


def test_distributed_stages_run_at_the_trimmed_width(caplog):
    """End to end: after a join and an aggregate have shrunk the rows,
    the later stage programs — and a broadcast build side — are
    dispatched at the bucket of what is left, not at the capacities the
    earlier stages grew to."""
    import logging
    import re

    from spark_rapids_tpu import Session
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
    from spark_rapids_tpu.parallel.runner import run_distributed

    sess = Session()
    tables = tpch_datagen.dataframes(sess, sf=0.002, seed=7)
    with caplog.at_level(logging.INFO,
                         logger="spark_rapids_tpu.parallel.runner"):
        got = run_distributed(sess, tpch.QUERIES[5](tables),
                              mesh=_mesh(4)).to_rows()
    assert got
    widths = {m.group(1): int(m.group(2)) for m in (
        re.match(r"(stage\[\d+\](?:\.broadcast\[\d+\])?) attempt 0: "
                 r"dispatching \(\d+ inputs, up to (\d+) rows", r.message)
        for r in caplog.records) if m}
    last = max(int(k[6:-1]) for k in widths if k.endswith("]")
               and ".broadcast" not in k)
    # q5 ends in five groups: the stages after the aggregate's exchange
    assert widths[f"stage[{last}]"] == 128, widths
    assert widths[f"stage[{last - 1}]"] == 128, widths
    # every program answered on its first attempt and said so
    answered = [r.message for r in caplog.records
                if "answered in" in r.message]
    assert len(answered) == len(widths) and \
        not any("overflowed" in a for a in answered), answered
    # ... in turn, each after its own announcement: a request that is
    # cut short has said which program it was in
    said = [r.message for r in caplog.records
            if "dispatching" in r.message or "answered in" in r.message]
    assert said[0].startswith("stage[0]") and all(
        a.split(":")[0] == d.split(":")[0] and "dispatching" in d
        and "answered in" in a for d, a in zip(said[::2], said[1::2]))


def test_distributed_broadcast_build_reused_across_retries():
    """One all_gather of the broadcast build side per query: the
    replicated batch is precomputed outside the stage retry loop, so a
    capacity-overflow retry re-runs the join but NOT the gather
    (reference: one broadcast relation per exchange,
    GpuBroadcastExchangeExec.scala:215-247; r3 Weak: re-gather per
    retry)."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
    from spark_rapids_tpu.parallel.collective import IciCollectiveTransport
    from spark_rapids_tpu.parallel.runner import DistributedRunner
    from spark_rapids_tpu.plan.physical import ExecContext

    # every key equal: join output (600*100 per shard-row pair) vastly
    # exceeds the initial static capacity, forcing a capacity retry
    left = {"k": np.zeros(600, dtype=np.int64),
            "v": np.arange(600, dtype=np.int64)}
    right = {"rk": np.zeros(100, dtype=np.int64),
             "w": np.arange(100, dtype=np.int64)}
    sess = Session()
    l = sess.create_dataframe(dict(left))
    r = sess.create_dataframe(dict(right))
    j = l.join(r, on=(["k"], ["rk"]), how="inner")
    phys = sess.physical_plan(j.plan)

    joins = []

    def walk(n):
        if isinstance(n, TpuBroadcastHashJoinExec):
            joins.append(n)
        for c in getattr(n, "children", []):
            walk(c)

    walk(phys)
    assert joins, "expected a broadcast join"
    op = joins[0]
    calls = {"join": 0}
    orig = op.join_static

    def counting_join(*a, **kw):
        calls["join"] += 1
        return orig(*a, **kw)

    op.join_static = counting_join

    class CountingTransport(IciCollectiveTransport):
        def __init__(self, axis):
            super().__init__(axis)
            self.replicates = 0

        def replicate(self, b):
            self.replicates += 1
            return super().replicate(b)

    mesh = _mesh(8)
    ct = CountingTransport(mesh.axis_names[0])
    got = DistributedRunner(mesh, transport=ct).run(
        phys, ExecContext(sess.conf, sess)).to_rows()

    cpu = Session(tpu_enabled=False)
    exp = cpu.create_dataframe(dict(left)).join(
        cpu.create_dataframe(dict(right)),
        on=(["k"], ["rk"]), how="inner").collect()
    _assert_rows_equal(got, exp)
    assert calls["join"] >= 2, "expected a capacity retry"
    assert ct.replicates == 1, \
        f"build side gathered {ct.replicates}x (must be once per query)"


def test_distributed_range_exchange_spreads_shards():
    """The explicit RangePartitioning exchange node distributes by
    sampled device bounds (reference: GpuRangePartitioner.scala:33-104)
    — rows must land on many shards in key order, not funnel to shard 0
    (r3 Weak: the v1 single-shard funnel)."""
    from spark_rapids_tpu import Session, f
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.parallel.runner import DistributedRunner
    from spark_rapids_tpu.plan.physical import ExecContext
    from spark_rapids_tpu.shuffle.partitioning import RangePartitioning

    rng = np.random.RandomState(33)
    n = 4000
    data = {"v": rng.randint(-10000, 10000, n),
            "w": rng.rand(n).round(6)}

    sess = Session()
    df = sess.create_dataframe(dict(data)).sort(f.col("v"))
    phys = sess.physical_plan(df.plan)

    # the plan must carry a DEVICE range exchange (no host fallback)
    found = []

    def walk(node):
        if isinstance(node, TpuShuffleExchangeExec) and \
                isinstance(node.partitioning, RangePartitioning):
            found.append(node)
        for c in getattr(node, "children", []):
            walk(c)

    walk(phys)
    assert found, "sort plan lost its device range exchange"

    captured = {}

    class Capture(DistributedRunner):
        def _collect_output(self, out, stages):
            captured["num_rows"] = np.asarray(out.num_rows)
            return super()._collect_output(out, stages)

    got = Capture(_mesh(8)).run(phys, ExecContext(sess.conf, sess))
    exp = sess.create_dataframe(dict(data)).sort(f.col("v")).collect()
    got_rows = got.to_rows()
    assert len(got_rows) == len(exp)
    assert [g[0] for g in got_rows] == [e[0] for e in exp]
    shards_with_rows = int((captured["num_rows"] > 0).sum())
    assert shards_with_rows >= 4, \
        f"range exchange funneled rows to {shards_with_rows} shard(s)"


def test_distributed_range_sort_no_gather():
    """Distributed sort of raw rows: range-exchange by sampled key
    bounds (device, traced) then per-shard sort — shard i's rows all
    order before shard i+1's, so collecting shards in order yields the
    global order without ever funneling data to one shard."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu import f
    from spark_rapids_tpu.parallel.runner import run_distributed

    rng = np.random.RandomState(21)
    n = 4000
    data = {"v": rng.randint(-10000, 10000, n),
            "x": (rng.rand(n) * 100).round(6),
            "s": [f"tag{i % 17}" for i in range(n)]}

    def q(sess):
        df = sess.create_dataframe(dict(data))
        return df.sort(f.col("v"), f.col("x"), f.col("s"))

    sess = Session()
    got = run_distributed(sess, q(sess), mesh=_mesh(8)).to_rows()
    exp = q(Session(tpu_enabled=False)).collect()
    assert len(got) == len(exp)
    for g, e in zip(got, exp):
        assert g[0] == e[0]
        assert abs(g[1] - e[1]) < 1e-9
        assert g[2] == e[2]


def test_distributed_range_sort_desc_nulls():
    from spark_rapids_tpu import Session
    from spark_rapids_tpu import f
    from spark_rapids_tpu.parallel.runner import run_distributed

    rng = np.random.RandomState(23)
    n = 1500
    vals = [None if i % 11 == 0 else int(v)
            for i, v in enumerate(rng.randint(-500, 500, n))]
    data = {"v": vals, "i": list(range(n))}

    def q(sess):
        df = sess.create_dataframe(dict(data))
        return df.sort(f.col("v").desc().nulls_first_(), f.col("i"))

    sess = Session()
    got = run_distributed(sess, q(sess), mesh=_mesh(8)).to_rows()
    exp = q(Session(tpu_enabled=False)).collect()
    assert got == exp


# ---------------------------------------------------------------------
# stage programs that outlive their request (kernel cache, PR 28)
# ---------------------------------------------------------------------
def _stage_counters(sess):
    m = sess.last_metrics
    return (m["distributed.stagePrograms.compiles"],
            m["distributed.stagePrograms.hits"],
            m["distributed.stageRetries"])


def _mesh_entries():
    """(key, kernel) of every stage program the kernel cache holds."""
    from spark_rapids_tpu.exec.kernel_cache import GLOBAL

    with GLOBAL._lock:
        return [(k[0], v) for k, v in GLOBAL._entries.items()
                if k[0][0] == "mesh"]


@pytest.fixture()
def q3_cell(tmp_path):
    """The four-chip cell's own files at 1/1000 of its rows: session,
    q3's DataFrame, the pandas reference's rows, entry and config."""
    import json
    import os

    from benchmark.harness import BENCHMARK_DIR, datagen, load_module
    from benchmark.run import reference_answers
    from spark_rapids_tpu import Session

    with open(os.path.join(BENCHMARK_DIR, "configs",
                           "tpch_sf1_chip4.json")) as f:
        config = json.load(f)
    q3 = load_module("queries", "q3")
    rows = {t: max(4, n // 1000) for t, n in config["rows"].items()}
    made = datagen.write_tables(str(tmp_path), sorted(q3.TABLES), rows, 5,
                                config["parquet"])
    sess = Session(dict(config["conf"]))
    tables = {t: sess.read_parquet(os.path.join(str(tmp_path), t))
              for t in made}
    want = reference_answers(str(tmp_path), {"q3": q3})["q3"]
    yield (sess, q3.build(tables), want, q3,
           load_module("entries", "run_distributed"), config)
    sess.close()


def _q3_difference(q3, want, got, config):
    from benchmark.harness import compare

    return compare.difference(
        want, got, q3.ORDERED,
        config["guarantees"]["f64_relative_tolerance"])


def test_q3_shuffled_on_four_devices_equals_the_reference(q3_cell):
    sess, df, want, q3, entry, config = q3_cell
    assert config["conf"][
        "spark.rapids.tpu.sql.broadcastSizeThreshold"] == 0
    got = entry.run(sess, df, config)
    assert len(want) == 10
    assert _q3_difference(q3, want, got, config) is None
    assert entry.faults(sess.last_metrics, config) == []
    compiles, hits, retries = _stage_counters(sess)
    assert compiles >= 8 and hits == 0
    # every stage went through the kernel cache under the one name
    entries = _mesh_entries()
    assert len(entries) == compiles - retries
    assert {k.name for _key, k in entries} == {"mesh_stage"}


def test_second_identical_request_compiles_nothing(q3_cell):
    sess, df, want, q3, entry, config = q3_cell
    from benchmark.harness.probes import CompileWatch

    first = entry.run(sess, df, config)
    stages = len(_mesh_entries())
    watch = CompileWatch()
    mark = watch.snapshot()
    second = entry.run(sess, df, config)
    assert _stage_counters(sess) == (0, stages, 0)
    # by JAX's own events too: the leaves, trims and collect included
    assert watch.since(mark)["xla_compiles"] == 0
    assert second == first
    assert _q3_difference(q3, want, second, config) is None
    assert len(_mesh_entries()) == stages


def _filter_agg(sess, data, bound):
    from spark_rapids_tpu.plan import functions as F

    df = sess.create_dataframe(dict(data))
    return (df.filter(df["v"] > bound).group_by("k")
            .agg(F.sum("v").alias("s"), F.count("v").alias("c")))


def test_a_request_differing_in_one_literal_misses_the_stage_cache():
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed

    rng = np.random.RandomState(3)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}
    sess, cpu = Session(), Session(tpu_enabled=False)
    run_distributed(sess, _filter_agg(sess, data, 10.0), mesh=_mesh(4))
    stages = len(_mesh_entries())
    run_distributed(sess, _filter_agg(sess, data, 10.0), mesh=_mesh(4))
    assert _stage_counters(sess) == (0, stages, 0)
    got = run_distributed(sess, _filter_agg(sess, data, 11.0),
                          mesh=_mesh(4)).to_rows()
    compiles, hits, _ = _stage_counters(sess)
    # the stage that holds the filter is another program; the stages
    # above it read no literal and are found again
    assert compiles >= 1 and compiles + hits == stages
    _assert_rows_equal(got, _filter_agg(cpu, data, 11.0).collect())


def _absorbing(plan):
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

    nodes, todo = [], [plan]
    while todo:
        nodes.append(todo.pop())
        todo.extend(nodes[-1].children)
    return [n for n in nodes
            if isinstance(n, TpuHashAggregateExec) and n.absorbed]


@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "keyless"])
def test_filter_under_group_by_runs_absorbed_on_the_mesh(keyed):
    """The fusion pass folds the filter into the partial aggregate
    (``fusion.filtersAbsorbed``); the stage lowers the aggregate through
    ``compute_batch``, which carries the prologue, and is signed with
    the absorbed members: the mesh's answer is the one chip's, and two
    stages differing only in the absorbed predicate are two programs."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    rng = np.random.RandomState(8)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}

    def q(sess, bound):
        if keyed:
            return _filter_agg(sess, data, bound)
        df = sess.create_dataframe(dict(data))
        return df.filter(df["v"] > bound).agg(
            F.sum("v").alias("s"), F.count("*").alias("c"))

    sess, one_chip = Session(), Session()
    got = run_distributed(sess, q(sess, 10.0), mesh=_mesh(4)).to_rows()
    assert sess.last_metrics["fusion.filtersAbsorbed"] == 1
    off = Session({"spark.rapids.tpu.sql.fusion.enabled": False})
    unabsorbed = run_distributed(off, q(off, 10.0),
                                 mesh=_mesh(4)).to_rows()
    assert off.last_metrics["fusion.filtersAbsorbed"] == 0
    if not keyed:
        # the mesh answers a keyless aggregate with a row a shard, the
        # shards that hold nothing with NULLs (as it did before
        # aggregates absorbed): the one row that counts is the chip's
        assert len(got) == len(unabsorbed) == 4
        got, unabsorbed = ([r for r in rows if r != (None, None)]
                           for rows in (got, unabsorbed))
    _assert_rows_equal(got, unabsorbed)
    _assert_rows_equal(got, q(one_chip, 10.0).collect())
    _assert_rows_equal(got, q(Session(tpu_enabled=False), 10.0).collect())

    def signed(bound):
        (agg,) = _absorbing(sess.physical_plan(q(sess, bound).plan))
        return agg.signature, _one_operator_stage_key(agg)

    assert signed(10.0) == signed(10.0) != signed(11.0)


def test_shards_of_one_stage_read_their_groups_at_different_widths():
    """``reduce_sorted`` picks its read's width from the segment count,
    which under ``shard_map`` differs shard by shard: four shards of one
    program take four different branches (a handful of groups, a
    sixteenth of the rows and one more, every row its own, none at
    all), each shard's rows equal to the plain read's below its tier
    and zeros past it, as on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from spark_rapids_tpu.data.column import DeviceColumn
    from spark_rapids_tpu.ops.kernels import segment as seg
    from spark_rapids_tpu.parallel.mesh import DATA_AXIS

    n = seg._TIER_FLOOR
    tiers = seg.read_tiers(n)
    counts = [5, tiers[1] + 1, n, 0]
    rng = np.random.RandomState(37)
    change = np.ones((4, n), np.bool_)       # padding rows: each its own
    for shard, groups in enumerate(counts):
        real = min(n, 3 * groups)
        change[shard, :real] = np.arange(real) % 3 == 0
    values = rng.randint(1, 99, (4, n)).astype(np.int64)
    floats = rng.rand(4, n) + 1.0
    valid = rng.rand(4, n) < 0.7
    order = np.stack([rng.permutation(n) for _ in range(4)]).astype(np.int32)

    def reduce(change, order, values, floats, valid, count):
        specs = [(DeviceColumn(T.INT64, values, valid), "sum"),
                 (DeviceColumn(T.FLOAT64, floats, valid), "max"),
                 (DeviceColumn(T.INT64, values, valid), "count"),
                 (DeviceColumn(T.INT64, values, valid), "first"),
                 (DeviceColumn(T.FLOAT64, floats, valid), "last_any")]
        return [(d, ok) for d, ok, _ in
                seg.reduce_sorted(change, order, specs, segments=count)]

    def per_shard(change, order, values, floats, valid, count):
        out = reduce(change[0], order[0], values[0], floats[0], valid[0],
                     count[0])
        return jax.tree_util.tree_map(lambda x: x[None], out)

    spec = P(DATA_AXIS)
    args = [jnp.asarray(a) for a in (change, order, values, floats, valid)]
    got = jax.jit(jax.shard_map(
        per_shard, mesh=_mesh(4), in_specs=(spec,) * 6, out_specs=spec))(
        *args, jnp.asarray(counts, jnp.int32))
    for shard, groups in enumerate(counts):
        m = next(m for m in tiers if m >= groups)
        plain = reduce(*(a[shard] for a in args), None)
        for (data, ok), (want, want_ok) in zip(got, plain):
            for g, w in ((data[shard], want), (ok[shard], want_ok)):
                g, w = np.asarray(g), np.asarray(w)
                np.testing.assert_array_equal(g[:m], w[:m])
                assert not g[m:].any() and (m == n or w[m:].any())


def test_group_by_on_the_mesh_where_the_shards_hold_unlike_group_counts(
        monkeypatch):
    """The same through ``run_distributed``: the leaf's rows are split
    row-wise, so the first shard of the partial aggregate's stage holds
    three groups and the last every row its own, in buckets wide enough
    for the tiers; the answer is the host engine's."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.ops.kernels import segment as seg
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    n = seg._TIER_FLOOR
    rng = np.random.RandomState(41)
    k = np.concatenate([rng.randint(0, 3, 3 * n),
                        10 + np.arange(n)]).astype(np.int64)
    data = {"k": k, "v": rng.rand(4 * n) * 100}

    def q(sess):
        df = sess.create_dataframe(dict(data))
        return (df.filter(df["v"] > 1).group_by("k")
                .agg(F.sum("v").alias("s"), F.count("v").alias("c"),
                     F.max("v").alias("hi")))

    widths, read_tiers = [], seg.read_tiers
    monkeypatch.setattr(seg, "read_tiers", lambda rows: (
        widths.append(rows), read_tiers(rows))[1])
    sess = Session()
    got = run_distributed(sess, q(sess), mesh=_mesh(4)).to_rows()
    assert max(widths) >= n         # the partial aggregate's shards
    _assert_rows_equal(got, q(Session(tpu_enabled=False)).collect())


def test_complete_mode_aggregate_absorbs_on_the_mesh():
    """A ``complete`` aggregate colocates its groups itself: with an
    absorbed chain the keys are read off the chain's rows, the dropped
    rows travel nowhere, and the raw rows that do pass again."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.collective import make_transport
    from spark_rapids_tpu.parallel.runner import DistributedRunner
    from spark_rapids_tpu.plan import functions as F
    from spark_rapids_tpu.plan import physical as P
    from spark_rapids_tpu.plan.optimizer import optimize
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from spark_rapids_tpu.plan.planner import Planner
    from spark_rapids_tpu.plan.transitions import TpuTransitionOverrides

    rng = np.random.RandomState(9)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}

    def complete(sess):
        df = sess.create_dataframe(dict(data), n_partitions=2)
        q = (df.with_column("g", F.col("k") % 5)
             .filter(F.col("v") > 25.0).group_by("g")
             .agg(F.sum("v").alias("s"), F.count("*").alias("c")))
        final = Planner(sess.conf).plan(optimize(q.plan))
        partial = final.children[0].children[0]
        return P.HashAggregateExec(
            partial.children[0], "complete", q.plan.keys, partial.specs,
            ["s", "c"])

    cpu = Session(tpu_enabled=False)
    df = cpu.create_dataframe(dict(data))
    want = (df.with_column("g", F.col("k") % 5)
            .filter(F.col("v") > 25.0).group_by("g")
            .agg(F.sum("v").alias("s"), F.count("*").alias("c"))).collect()
    assert len(want) == 5

    sess = Session()
    phys = TpuTransitionOverrides(sess.conf).apply(
        TpuOverrides(sess.conf).apply(complete(sess)))
    (agg,) = _absorbing(phys)
    assert agg.mode == "complete" and len(agg.absorbed) == 2
    mesh = _mesh(4)
    runner = DistributedRunner(
        mesh, transport=make_transport(sess.conf, mesh.axis_names[0]))
    got = runner.run(phys, P.ExecContext(sess.conf, sess)).to_rows()
    _assert_rows_equal(got, want)


def test_another_schema_misses_the_stage_cache():
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed

    rng = np.random.RandomState(6)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}
    whole = dict(data, v=data["v"].astype(np.int64))
    sess, cpu = Session(), Session(tpu_enabled=False)
    run_distributed(sess, _filter_agg(sess, data, 10.0), mesh=_mesh(4))
    stages = len(_mesh_entries())
    # the same plan text over a bigint column: every stage reads or
    # hands on another dtype, so none is found
    got = run_distributed(sess, _filter_agg(sess, whole, 10.0),
                          mesh=_mesh(4)).to_rows()
    assert _stage_counters(sess)[:2] == (stages, 0)
    _assert_rows_equal(got, _filter_agg(cpu, whole, 10.0).collect())


def test_another_mesh_misses_the_stage_cache():
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed

    rng = np.random.RandomState(4)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}
    sess, cpu = Session(), Session(tpu_enabled=False)
    run_distributed(sess, _filter_agg(sess, data, 10.0), mesh=_mesh(4))
    stages = len(_mesh_entries())
    got = run_distributed(sess, _filter_agg(sess, data, 10.0),
                          mesh=_mesh(2)).to_rows()
    assert _stage_counters(sess)[:2] == (stages, 0)
    assert len(_mesh_entries()) == 2 * stages
    _assert_rows_equal(got, _filter_agg(cpu, data, 10.0).collect())


def _skewed_join(sess, n_equal):
    """600 x 100 rows; the first ``n_equal`` left keys meet every right
    row, so the join's demand is ``n_equal * 100`` at the same shapes."""
    k = np.arange(1, 601, dtype=np.int64) * 1000
    k[:n_equal] = 0
    l = sess.create_dataframe({"k": k,
                               "v": np.arange(600, dtype=np.int64)})
    r = sess.create_dataframe({"rk": np.zeros(100, dtype=np.int64),
                               "w": np.arange(100, dtype=np.int64)})
    return l.join(r, on=(["k"], ["rk"]), how="inner")


def test_next_request_starts_at_the_settled_capacities():
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed

    conf = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0}
    sess, cpu = Session(dict(conf)), Session(tpu_enabled=False)
    first = run_distributed(sess, _skewed_join(sess, 100),
                            mesh=_mesh(4)).to_rows()
    compiles, _hits, retries = _stage_counters(sess)
    assert retries >= 1, "expected a capacity overflow"
    stages = compiles - retries
    again = run_distributed(sess, _skewed_join(sess, 100),
                            mesh=_mesh(4)).to_rows()
    # one dispatch a stage, from where the last request ended
    assert _stage_counters(sess) == (0, stages, 0)
    _assert_rows_equal(again, first)
    _assert_rows_equal(first, _skewed_join(cpu, 100).collect())
    # new data that overflows the settled capacity still grows it,
    # and compiles for that
    more = run_distributed(sess, _skewed_join(sess, 600),
                           mesh=_mesh(4)).to_rows()
    compiles, _hits, retries = _stage_counters(sess)
    assert retries >= 1 and compiles >= 1
    _assert_rows_equal(more, _skewed_join(cpu, 600).collect())


def test_a_cached_stage_program_keeps_no_request_alive():
    import gc
    import weakref

    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import DistributedRunner
    from spark_rapids_tpu.plan.physical import ExecContext

    rng = np.random.RandomState(5)
    data = {"k": rng.randint(0, 20, 300), "v": rng.rand(300) * 100}
    sess = Session()
    phys = sess.physical_plan(_filter_agg(sess, data, 10.0).plan)
    runner = DistributedRunner(_mesh(4))
    ctx = ExecContext(sess.conf, sess)
    assert runner.run(phys, ctx).num_rows > 0
    assert runner.stage_compiles >= 2
    from spark_rapids_tpu.exec.base import TpuExec

    nodes, todo = [], [phys]
    while todo:
        nodes.append(todo.pop())
        todo.extend(nodes[-1].children)
    # every device operator, the leaves' uploads among them
    nodes = [n for n in nodes if isinstance(n, TpuExec)]
    assert len(nodes) >= 5
    gone = [weakref.ref(o) for o in (runner, ctx, *nodes)]
    del runner, ctx, phys, nodes
    gc.collect()
    entries = _mesh_entries()
    assert len(entries) >= 2
    assert [w() for w in gone if w() is not None] == []
    # what an entry does keep: a runner that only lowers
    for _key, kern in entries:
        low = kern.fn.lowering
        assert not low.shard_device_ids and low.stage_compiles == 0


def _mesh_program_identities():
    """(name, fingerprint, capacity names) of every stage program of a
    shuffled join + aggregate, for the two-process test below."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    sess = Session({"spark.rapids.tpu.sql.broadcastSizeThreshold": 0})
    j = _skewed_join(sess, 100)
    run_distributed(sess, j.group_by("k").agg(F.sum("w").alias("s")),
                    mesh=_mesh(4))
    return sorted((k.name, k.fingerprint, list(k.fn.aux_keys))
                  for _key, k in _mesh_entries())


def test_two_processes_agree_on_every_stage_program():
    # name, key and the order of the capacity outputs are part of the
    # HLO module or decide it, so of the persistent compile cache's
    # key: an id or an address in any of them makes every process
    # compile every stage again
    import json
    import os
    import subprocess
    import sys

    from conftest import cpu_worker_env

    code = ("import json, sys; sys.path.insert(0, %r); "
            "import conftest, test_distributed as t; "
            "print('IDS' + json.dumps(t._mesh_program_identities()))"
            % os.path.dirname(os.path.abspath(__file__)))
    outs = []
    for seed in ("1", "2"):
        env = cpu_worker_env()
        env["PYTHONHASHSEED"] = seed
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append([ln for ln in p.stdout.splitlines()
                     if ln.startswith("IDS")][-1])
    assert outs[0] == outs[1]
    ids = json.loads(outs[0][3:])
    assert len(ids) >= 3 and {i[0] for i in ids} == {"mesh_stage"}
    assert any(i[2] for i in ids), "no capacity-checked program"


# ---------------------------------------------------------------------
# the stage key is made of the operators' own signatures
# ---------------------------------------------------------------------
def _held_kernel_keys(op):
    """The keys ``op`` registered its own kernels under."""
    from spark_rapids_tpu.exec.kernel_cache import GLOBAL, _CachedKernel

    held = [k for v in vars(op).values()
            for k in (v if isinstance(v, (list, tuple)) else [v])
            if isinstance(k, _CachedKernel)]
    with GLOBAL._lock:
        return [key[0] for key, kern in GLOBAL._entries.items()
                if any(kern is h for h in held)]


def _one_operator_stage_key(op):
    """The stage-program key of a stage of ``op`` alone over inputs
    that say no partitioning."""
    from spark_rapids_tpu.parallel.runner import (DistributedRunner,
                                                  _InputRef)

    tree = (op.kernel_twin(), *[_InputRef(i) for i in
                                range(len(op.children))])
    return DistributedRunner(_mesh(2))._program_key(tree, None, "test")


def _signed_plan(kind):
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.plan import functions as F
    from spark_rapids_tpu.plan import logical as L

    sess = Session({
        "spark.rapids.tpu.sql.fusion.enabled": kind == "TpuFusedSegmentExec",
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0})
    df = sess.create_dataframe({"k": np.arange(64, dtype=np.int64) % 5,
                                "v": np.arange(64, dtype=np.float64)})
    other = sess.create_dataframe({"rk": np.arange(5, dtype=np.int64),
                                   "w": np.arange(5, dtype=np.int64)})
    if kind == "TpuExpandExec":
        q = L.DataFrame(sess, L.Expand(
            df.plan, [[F.col("k").expr, F.col("v").expr],
                      [F.col("k").expr, (F.col("v") * F.lit(2)).expr]],
            ["k", "v"]))
    elif kind == "TpuGenerateExec":
        q = df.explode([F.col("k"), F.col("k") + 1], name="e")
    else:
        q = (df.filter(df["v"] > 3.0).with_column("u", F.col("v") * 2)
             .join(other, on=(["k"], ["rk"]), how="inner")
             .group_by("k").agg(F.sum("u").alias("s")).sort("k"))
    return sess.physical_plan(q.plan)


def _ops_of(plan, kind):
    nodes, todo = [], [plan]
    while todo:
        nodes.append(todo.pop())
        todo.extend(nodes[-1].children)
    ops = [n for n in nodes
           if kind in [c.__name__ for c in type(n).__mro__]]
    assert ops, sorted({type(n).__name__ for n in nodes})
    return ops


@pytest.mark.parametrize("kind", [
    "TpuProjectExec", "TpuFilterExec", "TpuExpandExec", "TpuGenerateExec",
    "TpuFusedSegmentExec", "TpuHashJoinExec", "TpuHashAggregateExec",
    "TpuSortExec"])
def test_kernels_and_stage_key_read_the_operators_signature(kind):
    """Every keyed kernel an operator holds is keyed by its
    ``signature`` (an expand's, a kernel a projection list, by the
    signature's fields), and a stage of the operator alone is keyed by
    its kind and that same tuple."""
    for op in _ops_of(_signed_plan(kind), kind):
        keys = _held_kernel_keys(op)
        assert keys, f"{kind} registered no keyed kernel"
        for key in keys:
            if kind == "TpuExpandExec":
                src, lists, out = op.signature
                assert key[0] == "expand" and key[1] == src \
                    and key[2] in lists and key[3] == out, key
            else:
                assert op.signature in key, key
        stage = _one_operator_stage_key(op)
        assert stage[1][0] == (type(op).__name__, op.signature)


def test_a_field_of_the_signature_changes_the_stage_key():
    """A literal or a schema the filter reads is in its signature and so
    in its stage's key; an operator whose signature is None (a window)
    has its stage compiled for the request."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.ops.windowexprs import over, row_number, window
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    def filter_key(v_type, bound):
        sess = Session()
        df = sess.create_dataframe(
            {"k": list(range(8)), "v": [float(i) for i in range(8)]},
            schema=T.Schema([T.Field("k", T.INT64),
                             T.Field("v", v_type)]))
        (op,) = _ops_of(sess.physical_plan(
            df.filter(df["v"] > bound).plan), "TpuFilterExec")
        return op.signature, _one_operator_stage_key(op)

    sig, key = filter_key(T.FLOAT64, 3.0)
    assert (sig, key) == filter_key(T.FLOAT64, 3.0)
    for other in (filter_key(T.FLOAT64, 4.0), filter_key(T.FLOAT32, 3.0)):
        assert other[0] != sig and other[1] != key

    def ranked(sess):
        df = sess.create_dataframe({"k": np.arange(40) % 4,
                                    "t": np.arange(40)[::-1]})
        return df.with_window("r", over(
            row_number(), window().partition_by("k").order_by("t")))

    sess = Session()
    (win,) = _ops_of(sess.physical_plan(ranked(sess).plan),
                     "TpuWindowExec")
    assert win.signature is None and _one_operator_stage_key(win) is None
    first = run_distributed(sess, ranked(sess), mesh=_mesh(2)).to_rows()
    again = run_distributed(sess, ranked(sess), mesh=_mesh(2)).to_rows()
    assert _stage_counters(sess)[0] >= 1        # compiled again
    _assert_rows_equal(again, first)
    _assert_rows_equal(first, ranked(Session(tpu_enabled=False)).collect())


@pytest.mark.parametrize("path", ["expand", "semi", "bounds", "pairs"])
def test_one_chip_and_mesh_take_the_same_join_path(path, monkeypatch):
    """The join chooses its program once (``TpuHashJoinExec.path``):
    one chip (``_join``) and a mesh stage (``join_static``) run the body
    of that path and no other, to the same rows as the host engine."""
    from spark_rapids_tpu import Session
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec
    from spark_rapids_tpu.parallel.runner import run_distributed
    from spark_rapids_tpu.plan import functions as F

    bodies = {"expand": "_expand", "semi": "_semi_anti",
              "bounds": "_semi_bounds", "pairs": "_semi_pairs"}
    ran = []
    for body in bodies.values():
        def spy(self, *a, _body=body, _orig=getattr(TpuHashJoinExec, body)):
            ran.append(_body)
            return _orig(self, *a)

        monkeypatch.setattr(TpuHashJoinExec, body, spy)
    how = {"expand": "inner"}.get(path, "semi")
    condition = {"bounds": F.col("s") < F.col("rs"),
                 "pairs": F.col("s") + F.col("rs") > F.lit(4)}.get(path)
    keys = np.random.RandomState(6).randint(0, 9, 200)

    def q(sess):
        l = sess.create_dataframe({"k": keys, "s": np.arange(200) % 7})
        r = sess.create_dataframe({"rk": np.arange(12) % 6,
                                   "rs": np.arange(12) % 5})
        return l.join(r, on=(["k"], ["rk"]), how=how, condition=condition)

    conf = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0}
    one = Session(dict(conf))
    df = q(one)
    (op,) = _ops_of(one.physical_plan(df.plan), "TpuHashJoinExec")
    assert op.path == path
    rows = df.collect()
    assert set(ran) == {bodies[path]}
    ran.clear()
    mesh = Session(dict(conf))
    got = run_distributed(mesh, q(mesh), mesh=_mesh(4)).to_rows()
    assert set(ran) == {bodies[path]}
    _assert_rows_equal(got, rows)
    _assert_rows_equal(rows, q(Session(tpu_enabled=False)).collect())
