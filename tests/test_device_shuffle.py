"""Device-resident shuffle (shuffle/device_shuffle.py + exchange).

The central invariant: results are BIT-IDENTICAL between
``shuffle.mode=device`` (packed blocks resident in HBM, one jitted
partition-build kernel per input batch, readers slice on device) and
``shuffle.mode=host`` (every block staged + CRC32C-stamped immediately
— the pre-device behavior), including under fault injection, OOM
pressure, and concurrent submission.  The ``shuffle.*`` metrics and
``shuffle_fallback``/``degrade`` events make every degradation of the
device path visible.
"""
import numpy as np
import pytest
from conftest import REPO, cpu_worker_env

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.shuffle import device_shuffle as DS

FAST = {
    "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
    "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
}

#: force real exchanges (no broadcast shortcut) like the fault suite
SHUFFLED = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
            "spark.rapids.tpu.sql.taskRetries": 3}

TEL = {"spark.rapids.tpu.telemetry.enabled": True}


def _inject(mode, fault_type, site="", skip=0, **extra):
    conf = dict(FAST)
    conf.update({
        "spark.rapids.tpu.fault.injection.mode": mode,
        "spark.rapids.tpu.fault.injection.type": fault_type,
        "spark.rapids.tpu.fault.injection.site": site,
        "spark.rapids.tpu.fault.injection.skipCount": skip,
    })
    conf.update(extra)
    return conf


def _norm(rows):
    return sorted(
        (tuple((None if v is None else
                (round(v, 9) if isinstance(v, float) else v))
               for v in r) for r in rows),
        key=repr)


def _join_agg_query(sess):
    rng = np.random.RandomState(11)
    orders = {"o_custkey": rng.randint(0, 40, 300).tolist(),
              "o_total": [round(float(v), 6)
                          for v in rng.rand(300) * 1000]}
    cust = {"c_custkey": list(range(40)),
            "c_nation": rng.randint(0, 5, 40).tolist()}
    o = sess.create_dataframe(orders)
    c = sess.create_dataframe(cust)
    j = o.join(c, on=(["o_custkey"], ["c_custkey"]), how="inner")
    return j.group_by("c_nation").agg(
        F.sum("o_total").alias("rev"), F.count("o_total").alias("n"))


def _mode_conf(mode, **extra):
    conf = dict(SHUFFLED, **FAST)
    conf["spark.rapids.tpu.shuffle.mode"] = mode
    conf.update(extra)
    return conf


# ==========================================================================
# resolve_mode policy
# ==========================================================================
def test_resolve_mode_policy():
    assert DS.resolve_mode("device") == "device"
    assert DS.resolve_mode("host") == "host"
    assert DS.resolve_mode(None) == "device"          # auto + headroom
    assert DS.resolve_mode("auto", headroom=0) == "host"
    assert DS.resolve_mode("auto", headroom=-5) == "host"
    # the ladder's forced re-execution wins over everything
    assert DS.resolve_mode("device", force_host=True) == "host"
    with pytest.raises(ValueError):
        DS.resolve_mode("bogus")


# ==========================================================================
# packed build/slice kernel round trip
# ==========================================================================
def test_packed_build_slice_roundtrip():
    """One build + n_out slices must reproduce exactly the rows the
    direct per-partition compaction produces, partition by partition."""
    import jax.numpy as jnp

    from spark_rapids_tpu.data.column import (HostBatch, device_to_host,
                                              host_to_device)

    rng = np.random.RandomState(5)
    hb = HostBatch.from_pydict({
        "k": rng.randint(0, 1000, 200).tolist(),
        "s": [f"row{i}" for i in range(200)]})
    b = host_to_device(hb)
    n_out = 4
    pids = jnp.asarray(rng.randint(0, n_out, b.padded_rows),
                       dtype=jnp.int32)
    block, counts, starts = DS.packed_build(b, pids, n_out)
    counts = np.asarray(counts)
    starts = np.asarray(starts)
    pids_np = np.asarray(pids)
    assert counts.sum() == 200
    # real rows sorted to the front: the spill serializer (which trims
    # to num_rows) must lose only padding
    assert int(np.asarray(block.num_rows)) == 200
    got_all = []
    for p in range(n_out):
        n = int(counts[p])
        if n == 0:
            continue
        out = DS.packed_slice(block, jnp.int32(int(starts[p])),
                              jnp.int32(n))
        hp = device_to_host(out)
        rows = list(zip(hp.column("k").to_pylist(),
                        hp.column("s").to_pylist()))
        # every row of partition p carries pid p
        want = [(k, s) for i, (k, s) in enumerate(
            zip(hb.column("k").to_pylist(), hb.column("s").to_pylist()))
            if int(pids_np[i]) == p]
        assert sorted(rows) == sorted(want), p
        got_all.extend(rows)
    assert sorted(got_all) == sorted(
        zip(hb.column("k").to_pylist(), hb.column("s").to_pylist()))


def test_shuffle_stats_delta_reporting():
    DS.GLOBAL.reset()
    mark = DS.GLOBAL.counters()
    DS.GLOBAL.add("deviceBytes", 100)
    DS.GLOBAL.add("numFallbacks")
    got = DS.GLOBAL.metrics_since(mark)
    assert got["shuffle.deviceBytes"] == 100
    assert got["shuffle.numFallbacks"] == 1
    assert got["shuffle.hostBytes"] == 0


# ==========================================================================
# device/host mode bit-identity + metrics
# ==========================================================================
def test_mode_bit_identity_and_metrics():
    s_dev = srt.Session(_mode_conf("device"))
    dev = _join_agg_query(s_dev).collect()
    m_dev = s_dev.last_metrics
    assert m_dev.get("shuffle.deviceBytes", 0) > 0, m_dev
    assert m_dev.get("shuffle.hostBytes", 0) == 0, m_dev

    s_host = srt.Session(_mode_conf("host"))
    host = _join_agg_query(s_host).collect()
    m_host = s_host.last_metrics
    assert m_host.get("shuffle.hostBytes", 0) > 0, m_host
    assert m_host.get("shuffle.deviceBytes", 0) == 0, m_host

    assert _norm(dev) == _norm(host)


def test_auto_mode_prefers_device_with_headroom():
    sess = srt.Session(_mode_conf("auto"))
    _join_agg_query(sess).collect()
    m = sess.last_metrics
    assert m.get("shuffle.deviceBytes", 0) > 0, m
    assert m.get("shuffle.hostBytes", 0) == 0, m


@pytest.mark.parametrize("qnum", [1, 3, 5, 6, 16])
def test_tpch_mode_bit_identity(qnum):
    """q1/q3/q5/q6/q16 return identical rows under device and host
    shuffle (the oracle-vs-tpu comparison lives in test_tpch; this
    pins the two DATA PATHS against each other exactly)."""
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen

    def run(mode):
        sess = srt.Session(_mode_conf(mode))
        tables = tpch_datagen.dataframes(sess, sf=0.0007, seed=7)
        return tpch.QUERIES[qnum](tables).collect()

    assert _norm(run("device")) == _norm(run("host"))


# ==========================================================================
# count before pack: blocks built at the bucket of their live rows
# ==========================================================================
def _record_dispatches(monkeypatch, factory, what):
    """Wrap the kernel a ``DS`` factory hands the exchange so that
    every dispatch appends ``what(batch, *args)`` to the returned
    list."""
    calls = []
    make = getattr(DS, factory)

    def counting(*key):
        kern = make(*key)

        def call(batch, *args, **kw):
            calls.append(what(batch, *args))
            return kern(batch, *args, **kw)

        return call

    monkeypatch.setattr(DS, factory, counting)
    return calls


@pytest.fixture
def trim_calls(monkeypatch):
    """The ``out_rows`` of every ``jit_shuffle_trim`` dispatch."""
    return _record_dispatches(monkeypatch, "trim_kernel",
                              lambda batch, out_rows: out_rows)


@pytest.fixture
def build_calls(monkeypatch):
    """The padded rows of every ``jit_shuffle_packedBuild`` dispatch."""
    return _record_dispatches(monkeypatch, "packed_build_kernel",
                              lambda batch, *args: batch.padded_rows)


def _four_groups(sess, n=5000):
    rng = np.random.RandomState(3)
    df = sess.create_dataframe({"k": rng.randint(0, 4, n).tolist(),
                                "v": rng.rand(n).tolist()},
                               n_partitions=1)
    return df.group_by("k").agg(F.sum("v").alias("s"),
                                F.count("v").alias("c"))


def test_sparse_block_is_packed_at_its_live_bucket(trim_calls,
                                                   build_calls):
    """Four groups leave the partial aggregate in its input's bucket
    (8192 rows): the write must pack them at 128."""
    sess = srt.Session(_mode_conf("device"))
    dev = _four_groups(sess).collect()
    m = sess.last_metrics
    assert m["shuffle.trimmedBlocks"] >= 1, m
    assert m["shuffle.trimmedRows"] == 8192 - 128, m
    assert trim_calls == [128] and build_calls == [128]
    # one int64 column of the untrimmed block alone is 64 KiB
    assert 0 < m["shuffle.deviceBytes"] < 8192 * 8, m
    host = _four_groups(srt.Session(_mode_conf("host"))).collect()
    assert _norm(dev) == _norm(host)
    assert len(dev) == 4


def test_dense_exchange_is_not_trimmed(trim_calls, build_calls):
    """Inputs that fill their bucket are packed as they arrive: no
    trim program, the build at the input's padding."""
    conf = _mode_conf(
        "device", **{"spark.rapids.tpu.sql.adaptive.enabled": False})
    sess = srt.Session(conf)
    n = 300
    df = sess.create_dataframe({"x": list(range(n))},
                               n_partitions=1).repartition(3)
    assert sorted(r[0] for r in df.collect()) == list(range(n))
    m = sess.last_metrics
    assert m["shuffle.trimmedBlocks"] == 0, m
    assert m["shuffle.trimmedRows"] == 0, m
    assert trim_calls == [] and build_calls == [512]
    assert m["shuffle.deviceBytes"] > 0, m


def test_empty_input_batch_is_dropped_unbuilt(trim_calls, build_calls):
    """A batch whose rows were all filtered away is removed at the
    flush: nothing is hashed, trimmed or built for it."""
    conf = _mode_conf(
        "device", **{"spark.rapids.tpu.sql.adaptive.enabled": False})
    sess = srt.Session(conf)
    df = sess.create_dataframe({"x": list(range(300))}, n_partitions=1)
    assert df.filter(df["x"] < 0).repartition(3).collect() == []
    m = sess.last_metrics
    assert trim_calls == [] and build_calls == []
    assert m["shuffle.deviceBytes"] == 0, m
    assert m["shuffle.trimmedBlocks"] == 0, m


_TRIM_COLUMNS = {
    # the widest value among the first rows: one byte-matrix width
    "string": (T.STRING, lambda i: None if i % 7 == 3
               else ("abcdefgh-%d" % (i % 10))[:1 + 9 * ((i + 1) % 3)]),
    "nullable_numeric": (T.FLOAT64, lambda i: None if i % 5 == 1
                         else i * 0.5),
    "date": (T.DATE32, lambda i: 19000 + i),
}


@pytest.mark.parametrize("buckets", [(1024, 128), (4096, 512)])
@pytest.mark.parametrize("kind", sorted(_TRIM_COLUMNS))
def test_trim_program_keeps_live_rows(kind, buckets):
    """The trim cuts padding only — rows, validity, lengths and
    ``num_rows`` survive — and compiles once per output bucket."""
    from spark_rapids_tpu.data.column import (DeviceBatch, HostBatch,
                                              device_to_host,
                                              host_to_device)
    from spark_rapids_tpu.exec import kernel_cache

    big, small = buckets
    dtype, value = _TRIM_COLUMNS[kind]
    schema = T.Schema([T.Field("c", dtype), T.Field("i", T.INT64)])
    kern = DS.trim_kernel(schema)
    assert kern.name == "shuffle_trim"

    def check(n):
        hb = HostBatch.from_pydict(
            {"c": [value(i) for i in range(n)], "i": list(range(n))},
            schema)
        b = host_to_device(hb, min_bucket_rows=big)
        assert b.padded_rows == big
        cut = kern(b, small)
        assert cut.padded_rows == small and cut.num_rows == n
        for have, had in zip(cut.columns, b.columns):
            np.testing.assert_array_equal(
                np.asarray(have.validity), np.asarray(had.validity)[:small])
            if had.lengths is None:
                assert have.lengths is None
            else:
                np.testing.assert_array_equal(
                    np.asarray(have.lengths),
                    np.asarray(had.lengths)[:small])
        got = device_to_host(DeviceBatch(cut.schema, cut.columns, n))
        assert got.to_rows() == hb.to_rows()

    check(small - 3)
    misses = kernel_cache.GLOBAL.counters()["misses"]
    check(small // 2 + 1)  # another count, the same bucket
    assert kernel_cache.GLOBAL.counters()["misses"] == misses


# ==========================================================================
# fault injection on the device path
# ==========================================================================
@pytest.mark.fault_injection
def test_device_corrupt_recomputes_from_lineage():
    """A corrupted device-resident block (demoted + bit-flipped by the
    injector at the device write site) must be caught by the CRC on
    promote, recomputed from lineage, and end bit-identical."""
    clean = _join_agg_query(srt.Session(_mode_conf("device"))).collect()
    sess = srt.Session(_mode_conf(
        "device", **_inject("nth", "corrupt",
                            site="exchange.write.device")))
    got = _join_agg_query(sess).collect()
    assert _norm(got) == _norm(clean)
    m = sess.last_metrics
    assert m.get("fault.numChecksumFailures", 0) >= 1, m


@pytest.mark.fault_injection
def test_device_oom_spills_blocks_and_completes():
    """An injected OOM at a device write checkpoint makes the retry
    framework spill the already-resident packed blocks; the spill is
    the per-buffer degradation (hostBytes + numFallbacks accrue, a
    shuffle_fallback event fires) and the query still completes
    bit-identical with readers promoting from host."""
    from spark_rapids_tpu.memory.spill import SpillFramework

    clean = _join_agg_query(srt.Session(_mode_conf("device"))).collect()
    # fresh framework: the clean run's cached uploads would otherwise
    # absorb the spill-to-half target before any shuffle block
    SpillFramework._instance = SpillFramework()
    # many small writes (tiny reader batches, coalescing off), OOM at
    # the 4th device write: three packed blocks are already resident
    # and spillable when the recovery runs
    sess = srt.Session(_mode_conf(
        "device",
        **_inject("nth", "oom", site="exchange.write.device", skip=3),
        **dict(TEL, **{
            "spark.rapids.tpu.sql.reader.batchSizeRows": 64,
            "spark.rapids.tpu.shuffle.targetBatchRows": 0,
        })))
    got = _join_agg_query(sess).collect()
    assert _norm(got) == _norm(clean)
    m = sess.last_metrics
    assert m.get("shuffle.hostBytes", 0) > 0, m
    assert m.get("shuffle.numFallbacks", 0) >= 1, m
    events = [e for e in sess.last_profile.events.snapshot()
              if e["event"] == "shuffle_fallback"]
    assert events and events[0]["reason"] == "spill", events


# ==========================================================================
# degradation ladder: device-shuffle -> host-shuffle -> CPU
# ==========================================================================
@pytest.mark.fault_injection
def test_ladder_device_to_host_shuffle_rung():
    """An always-corrupt drill scoped to the DEVICE write site exhausts
    the device attempt; the ladder's host-shuffle rung re-executes with
    exchanges staged (the drill's site no longer matches) and the query
    completes there — below the CPU rung, with the fallback visible."""
    conf = _mode_conf("device", **_inject(
        "always", "corrupt", site="exchange.write.device"))
    conf.update(TEL)
    conf["spark.rapids.tpu.sql.taskRetries"] = 0
    sess = srt.Session(conf)
    got = _join_agg_query(sess).collect()
    oracle = _join_agg_query(srt.Session(tpu_enabled=False)).collect()
    assert _norm(got) == _norm(oracle)
    m = sess.last_metrics
    assert m.get("fault.numShuffleFallbacks", 0) >= 1, m
    # recovered ABOVE the CPU rung: degradeLevel untouched
    assert m.get("fault.degradeLevel", 0) == 0, m
    events = sess.last_profile.events.snapshot()
    kinds = {e["event"] for e in events}
    assert "shuffle_fallback" in kinds, kinds
    rungs = [e.get("rung") for e in events if e["event"] == "degrade"]
    assert "host-shuffle" in rungs, events


@pytest.mark.fault_injection
def test_ladder_walks_host_rung_then_cpu():
    """An always-crash drill matching BOTH write sites fails the device
    attempt AND the host-shuffle rung; the query must still return
    correct rows via the CPU rung, with each rung's event emitted."""
    conf = _mode_conf("device", **_inject(
        "always", "stage_crash", site="exchange.write"))
    conf.update(TEL)
    conf["spark.rapids.tpu.sql.taskRetries"] = 0
    sess = srt.Session(conf)
    got = _join_agg_query(sess).collect()
    oracle = _join_agg_query(srt.Session(tpu_enabled=False)).collect()
    assert _norm(got) == _norm(oracle)
    m = sess.last_metrics
    assert m.get("fault.numShuffleFallbacks", 0) >= 1, m
    assert m.get("fault.degradeLevel") == 2, m
    events = sess.last_profile.events.snapshot()
    rungs = [e.get("rung") for e in events if e["event"] == "degrade"]
    assert "host-shuffle" in rungs and "cpu" in rungs, rungs


# ==========================================================================
# coalesce-before-exchange (shuffle.targetBatchRows)
# ==========================================================================
def test_coalesce_cuts_build_dispatches():
    """With tiny reader batches, coalescing to targetBatchRows must cut
    the kernel dispatches of the exchange write (one build per merged
    batch instead of one per scan batch) — measured through the
    kernel-cache telemetry, not timing."""
    # static shuffled plan on purpose: AQE's dynamic broadcast
    # conversion would bypass the exchange write whose dispatch
    # economics this measures
    small = {"spark.rapids.tpu.sql.reader.batchSizeRows": 32,
             "spark.rapids.tpu.sql.adaptive.enabled": False}

    sess_off = srt.Session(_mode_conf(
        "device", **dict(small, **{
            "spark.rapids.tpu.shuffle.targetBatchRows": 0})))
    off_rows = _join_agg_query(sess_off).collect()
    off = sess_off.last_metrics.get("kernelCache.dispatches", 0)

    sess_on = srt.Session(_mode_conf("device", **small))
    on_rows = _join_agg_query(sess_on).collect()
    on = sess_on.last_metrics.get("kernelCache.dispatches", 0)

    assert _norm(off_rows) == _norm(on_rows)
    assert 0 < on < off, (on, off)


def test_exchange_declares_target_rows_goal():
    from spark_rapids_tpu.exec.base import TargetRows
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec

    class _Plan:
        from spark_rapids_tpu.shuffle.partitioning import \
            SinglePartitioning
        partitioning = SinglePartitioning()
        n_out = 1

    class _Child:
        from spark_rapids_tpu import types as T
        schema = T.Schema([T.Field("x", T.INT64)])
        children = ()

    ex = TpuShuffleExchangeExec(_Child(), _Plan())
    goals = ex.children_coalesce_goal
    assert len(goals) == 1 and isinstance(goals[0], TargetRows)
    assert goals[0].rows is None  # conf-resolved at execute time


def test_target_rows_goal_lattice():
    from spark_rapids_tpu.exec.base import (RequireSingleBatch,
                                            TargetRows)

    assert TargetRows(10).max_with(TargetRows(20)).rows == 20
    assert TargetRows(None).max_with(TargetRows(20)).rows is None
    assert isinstance(TargetRows(10).max_with(RequireSingleBatch()),
                      RequireSingleBatch)


# ==========================================================================
# concurrent submission
# ==========================================================================
def test_concurrent_submit_device_mode_bit_identity():
    """Concurrent device-mode queries through the scheduler return the
    same rows as the serial host-mode run — the shared device arena and
    spill framework must not let neighbors corrupt each other's packed
    blocks."""
    serial = _norm(_join_agg_query(
        srt.Session(_mode_conf("host"))).collect())
    sess = srt.Session(_mode_conf("device"))
    try:
        handles = [sess.submit(_join_agg_query(sess).plan)
                   for _ in range(3)]
        for h in handles:
            assert _norm(h.result(timeout=120).to_rows()) == serial
    finally:
        sess.shutdown_scheduler()


# ==========================================================================
# host-staging + spill interplay
# ==========================================================================
def test_host_mode_blocks_are_crc_stamped_immediately():
    """mode=host serializes + CRC-stamps every block at write time —
    the stamp exists BEFORE any spill pressure, which is the point of
    the staged path (integrity over latency)."""
    from spark_rapids_tpu.memory.spill import SpillFramework

    sess = srt.Session(_mode_conf("host"))
    out = _join_agg_query(sess).collect()
    assert out
    # stage_to_host of an unknown / non-device buffer is a 0-byte no-op
    fw = SpillFramework.get()
    assert fw.stage_to_host(999999999) == 0


# ==========================================================================
# 2-process collective shuffle bit-identity (slow tier)
# ==========================================================================
@pytest.mark.slow
def test_two_process_collective_shuffle_bit_identity():
    """A 2-process multi-controller run of the join+agg plan returns
    oracle-equal rows under BOTH shuffle modes, with the collective
    dispatch wall accrued to ``shuffle.collectiveTime`` on every
    controller (tests/mp_shuffle_worker.py does the in-process
    asserts; this harness checks every worker reached them)."""
    import os
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coordinator = f"127.0.0.1:{port}"
    script = os.path.join(os.path.dirname(__file__),
                          "mp_shuffle_worker.py")
    repo = REPO
    env = cpu_worker_env()

    procs = [subprocess.Popen(
        [sys.executable, script, coordinator, "2", str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=repo) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process shuffle workers timed out:\n"
                    + "\n".join(o or "" for o in outs))
    if any("Multiprocess computations aren't implemented" in (o or "")
           for o in outs):
        pytest.skip("this jax build's CPU backend lacks multi-process "
                    "collectives (same limitation as "
                    "test_multiprocess) — nothing to exchange over")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"worker {pid} rc={p.returncode}:\n{out[-4000:]}"
        for mode in ("device", "host"):
            assert f"MPS MODE OK pid={pid} mode={mode}" in out, \
                out[-4000:]
        assert f"MPS RESULT OK pid={pid}" in out, out[-4000:]
