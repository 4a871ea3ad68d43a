"""Distributed fault-tolerance layer (spark_rapids_tpu/fault/).

The central invariant, extending PR-1's OOM contract to the full fault
model: with the generalized deterministic injector driving faults
(``corrupt`` / ``delay`` / ``stage_crash``) through the engine's
checkpoints — spill writes/reads, exchange steps, stage boundaries —
every injected run must complete with results bit-identical to an
injection-free run, the ``fault.*`` counters must make the recovery
visible, and a query that exhausts its bounded retries must return
correct results through the degradation ladder (single-process / CPU
rung) instead of raising.
"""
import threading
import time

import numpy as np
import pytest
from conftest import REPO, cpu_worker_env

import spark_rapids_tpu as srt
from spark_rapids_tpu.fault import (FaultInjector, fault_stats,
                                    install_fault_injector)
from spark_rapids_tpu.fault.errors import (TpuFaultError,
                                           TpuPayloadCorruption,
                                           TpuStageCrash, TpuStageTimeout)
from spark_rapids_tpu.plan import functions as F

#: fast-recovery confs shared by injection tests (CI must not sleep
#: through its budget; the backoff code is real either way)
FAST = {
    "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
    "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
}


def _inject(mode, fault_type, site="", skip=0, seed=0, delay_ms=50.0,
            **extra):
    conf = dict(FAST)
    conf.update({
        "spark.rapids.tpu.fault.injection.mode": mode,
        "spark.rapids.tpu.fault.injection.type": fault_type,
        "spark.rapids.tpu.fault.injection.site": site,
        "spark.rapids.tpu.fault.injection.skipCount": skip,
        "spark.rapids.tpu.fault.injection.seed": seed,
        "spark.rapids.tpu.fault.injection.delayMs": delay_ms,
    })
    conf.update(extra)
    return conf


def _norm(rows):
    return sorted(
        (tuple((None if v is None else
                (round(v, 9) if isinstance(v, float) else v))
               for v in r) for r in rows),
        key=repr)


# ==========================================================================
# FaultInjector unit tests
# ==========================================================================
def test_fault_injector_site_filter_counts_only_matches():
    inj = FaultInjector(mode="nth", skip_count=1,
                        fault_type="stage_crash", site="stage.run")
    inj.check("spill.write")   # filtered out: no count
    inj.check("stage.run")     # matching checkpoint #0
    with pytest.raises(TpuStageCrash) as ei:
        inj.check("stage.run")  # matching checkpoint #1 -> fire
    assert ei.value.injected and ei.value.site == "stage.run"
    inj.check("stage.run")      # one-shot: disarmed
    assert inj.injections_fired == 1
    assert inj.checkpoints_seen == 3  # only matching sites counted


def test_fault_injector_corrupt_only_fires_on_write_hook():
    inj = FaultInjector(mode="always", fault_type="corrupt")
    inj.check("spill.write")  # corrupt never raises from check()
    assert inj.injections_fired == 0
    assert inj.should_corrupt("spill.write")
    assert inj.injections_fired == 1
    # and the raising types never fire through the corrupt hook
    crash = FaultInjector(mode="always", fault_type="stage_crash")
    assert not crash.should_corrupt("spill.write")


def test_fault_injector_delay_sleeps_instead_of_raising():
    inj = FaultInjector(mode="nth", skip_count=0, fault_type="delay",
                        delay_ms=80.0)
    t0 = time.monotonic()
    inj.check("stage.run")
    assert time.monotonic() - t0 >= 0.05
    assert inj.injections_fired == 1


def test_fault_injector_validates_inputs():
    with pytest.raises(ValueError):
        FaultInjector(mode="bogus")
    with pytest.raises(ValueError):
        FaultInjector(fault_type="bogus")


def test_oom_injector_is_a_fault_injector_specialization():
    """The PR-1 OomInjector surface is preserved as the ``oom``
    specialization of the generalized injector."""
    from spark_rapids_tpu.memory.retry import (OomInjector, TpuRetryOOM,
                                               TpuSplitAndRetryOOM)

    inj = OomInjector(mode="nth", skip_count=0, oom_type="split")
    assert isinstance(inj, FaultInjector)
    with pytest.raises(TpuSplitAndRetryOOM):
        inj.check("x")
    inj2 = OomInjector(mode="always")
    with pytest.raises(TpuRetryOOM) as ei:
        inj2.check("y")
    assert ei.value.injected


# ==========================================================================
# Spill-frame CRC32C integrity
# ==========================================================================
def _device_batch(n=64):
    from spark_rapids_tpu.data.column import HostBatch, host_to_device

    return host_to_device(HostBatch.from_pydict(
        {"x": list(range(n)), "s": [f"v{i}" for i in range(n)]}))


def test_spill_frame_checksum_roundtrip_clean():
    from spark_rapids_tpu.data.column import device_to_host
    from spark_rapids_tpu.memory.spill import SpillFramework

    fw = SpillFramework()
    bid = fw.add_batch(_device_batch())
    fw.spill_device_to_target(0)
    buf = fw.catalog.get(bid)
    assert buf.crc is not None
    hb = device_to_host(fw.acquire_batch(bid))
    assert hb.column("x").to_pylist() == list(range(64))
    fw.release_batch(bid)
    fw.remove_batch(bid)


def test_spill_frame_corruption_detected_on_read():
    from spark_rapids_tpu.memory.spill import SpillFramework

    fw = SpillFramework()
    bid = fw.add_batch(_device_batch())
    fw.spill_device_to_target(0)
    fw.catalog.get(bid).corrupt_payload()
    before = fault_stats.get("numChecksumFailures")
    with pytest.raises(TpuPayloadCorruption) as ei:
        fw.acquire_batch(bid)
    assert "crc32c" in str(ei.value)
    assert fault_stats.get("numChecksumFailures") == before + 1
    fw.remove_batch(bid)


def test_injected_corruption_on_spill_write_is_detected():
    """An armed ``corrupt`` injector damages the next spill-catalog
    write; the read must detect it — never consume garbage."""
    from spark_rapids_tpu.memory.spill import SpillFramework, StorageTier

    fw = SpillFramework()
    install_fault_injector(FaultInjector(
        mode="nth", skip_count=0, fault_type="corrupt",
        site="spill.write"))
    try:
        bid = fw.add_batch(_device_batch())
        buf = fw.catalog.get(bid)
        assert buf.tier == StorageTier.HOST  # demoted by the injection
        with pytest.raises(TpuPayloadCorruption):
            fw.acquire_batch(bid)
    finally:
        install_fault_injector(None)
        fw.remove_batch(bid)


def test_disk_spill_keeps_checksum_verification():
    from spark_rapids_tpu.memory.spill import SpillFramework, StorageTier

    fw = SpillFramework(host_limit_bytes=1)  # everything -> disk
    bid = fw.add_batch(_device_batch())
    fw.spill_device_to_target(0)
    buf = fw.catalog.get(bid)
    assert buf.tier == StorageTier.DISK
    # flip a byte in the disk file: the read path must catch it
    with open(buf._disk_path, "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(TpuPayloadCorruption) as ei:
        fw.acquire_batch(bid)
    assert "spill.read.disk" in str(ei.value)
    fw.remove_batch(bid)


# ==========================================================================
# ShuffleCatalog slot release (stage re-execution / abort regression)
# ==========================================================================
def test_shuffle_catalog_releases_slots_of_failed_attempt():
    from spark_rapids_tpu.memory.spill import SpillFramework
    from spark_rapids_tpu.shuffle.catalog import ShuffleCatalog

    fw = SpillFramework()
    cat = ShuffleCatalog(fw)
    sid = cat.register_shuffle()
    ids = [fw.add_batch(_device_batch(8)) for _ in range(3)]
    for mid, bid in enumerate(ids):
        cat.add_buffer(sid, mid, bid)
    assert cat.slot_count(sid) == 3
    # a failed write attempt releases its entries WITHOUT unregistering
    cat.drop_buffers(sid, ids[:2])
    assert cat.slot_count(sid) == 1
    assert all(fw.catalog.get(b) is None for b in ids[:2])
    # the retry re-registers fresh buffers under the same shuffle id
    nid = fw.add_batch(_device_batch(8))
    cat.add_buffer(sid, 0, nid)
    assert cat.slot_count(sid) == 2
    cat.unregister_shuffle(sid)
    assert cat.slot_count() == 0
    assert fw.catalog.get(ids[2]) is None and fw.catalog.get(nid) is None


@pytest.mark.fault_injection
def test_shuffle_retry_does_not_leak_catalog_slots():
    """End-to-end: a crashed shuffle write re-executes from lineage and
    the dead attempt's catalog slots are released (regression: retries
    used to leak the failed attempt's ids in the shuffle index)."""
    sess = srt.Session(_inject(
        "nth", "stage_crash", site="exchange.write", skip=1, **{
            "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
            "spark.rapids.tpu.sql.taskRetries": 3,
        }))
    df = sess.create_dataframe({
        "k": [i % 7 for i in range(96)],
        "v": [float(i) for i in range(96)]})
    got = df.group_by("k").agg(F.sum("v").alias("s")).collect()
    exp = srt.Session(tpu_enabled=False).create_dataframe({
        "k": [i % 7 for i in range(96)],
        "v": [float(i) for i in range(96)]}).group_by("k").agg(
        F.sum("v").alias("s")).collect()
    assert _norm(got) == _norm(exp)
    # query-end cleanup + per-attempt release: no slots survive
    assert sess.shuffle_catalog.slot_count() == 0


# ==========================================================================
# Local-engine recovery: bit-identical under injection
# ==========================================================================
def _join_agg_query(sess):
    rng = np.random.RandomState(3)
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


SHUFFLED = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
            "spark.rapids.tpu.sql.taskRetries": 3}


@pytest.mark.fault_injection
def test_local_corrupt_exchange_payload_bit_identical():
    """A corrupted shuffle map-output payload is detected by the CRC on
    read, the producing write re-executes from lineage, and the result
    is bit-identical to the injection-free device run."""
    clean = _join_agg_query(srt.Session(dict(SHUFFLED))).collect()
    sess = srt.Session(_inject("nth", "corrupt", site="exchange.write",
                               **SHUFFLED))
    got = _join_agg_query(sess).collect()
    assert _norm(got) == _norm(clean)
    m = sess.last_metrics
    assert m.get("fault.numChecksumFailures", 0) >= 1, m
    oracle = _join_agg_query(srt.Session(tpu_enabled=False)).collect()
    assert _norm(got) == _norm(oracle)


@pytest.mark.fault_injection
@pytest.mark.parametrize("site", ["exchange.write", "exchange.read",
                                  "spill.read"])
def test_local_stage_crash_sites_bit_identical(site):
    clean = _join_agg_query(srt.Session(dict(SHUFFLED))).collect()
    sess = srt.Session(_inject("nth", "stage_crash", site=site,
                               **SHUFFLED))
    got = _join_agg_query(sess).collect()
    assert _norm(got) == _norm(clean), site
    assert "fault.degradeLevel" in sess.last_metrics


@pytest.mark.fault_injection
def test_local_delay_injection_bit_identical():
    clean = _join_agg_query(srt.Session(dict(SHUFFLED))).collect()
    sess = srt.Session(_inject("nth", "delay", site="exchange.write",
                               delay_ms=30.0, **SHUFFLED))
    got = _join_agg_query(sess).collect()
    assert _norm(got) == _norm(clean)


@pytest.mark.fault_injection
def test_session_ladder_degrades_to_cpu_rung():
    """mode=always stage crashes with task retries exhausted: the query
    must still return correct results via the CPU-exec rung (the bottom
    of the ladder), with the degradation visible in the metrics."""
    conf = _inject("always", "stage_crash", site="exchange.write", **{
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
        "spark.rapids.tpu.sql.taskRetries": 0,
    })
    sess = srt.Session(conf)
    got = _join_agg_query(sess).collect()
    oracle = _join_agg_query(srt.Session(tpu_enabled=False)).collect()
    assert _norm(got) == _norm(oracle)
    assert sess.last_metrics.get("fault.degradeLevel") == 2, \
        sess.last_metrics


@pytest.mark.fault_injection
@pytest.mark.parametrize("device_float_sums", [True, False])
def test_ladder_rungs_agree_past_a_scan_block(device_float_sums):
    """5000 rows in three groups, so every group crosses blocks of the
    device's segmented scan: the CPU rung's float sums (row by row) are
    the device rung's (block by block) to rounding, 1e-12 relative;
    counts, integer sums and keys to the bit.  With ``variableFloatAgg``
    off the float sums never leave the host engine and the rungs are
    identical."""
    import math

    from spark_rapids_tpu.ops.kernels.segment import _SCAN_BLOCK

    rng = np.random.RandomState(11)
    n = 5000
    assert n // 3 > _SCAN_BLOCK
    data = {"k": rng.randint(0, 3, n).tolist(),
            "v": (rng.rand(n) * 1000).tolist(),
            "i": rng.randint(-1000, 1000, n).tolist()}
    conf = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
            "spark.rapids.tpu.sql.taskRetries": 0,
            "spark.rapids.tpu.sql.variableFloatAgg.enabled":
                device_float_sums}

    def query(sess):
        df = sess.create_dataframe(data, n_partitions=1)
        return sorted(df.group_by("k").agg(
            F.sum("v").alias("s"), F.avg("v").alias("a"),
            F.sum("i").alias("si"), F.count("v").alias("n"),
            F.max("v").alias("m")).collect())

    clean_sess = srt.Session(dict(conf))
    clean = query(clean_sess)
    assert clean_sess.last_metrics.get("fault.degradeLevel") == 0
    sess = srt.Session(_inject("always", "stage_crash",
                               site="exchange.write", **conf))
    got = query(sess)
    assert sess.last_metrics.get("fault.degradeLevel") == 2
    rtol = 1e-12 if device_float_sums else 0.0
    assert len(got) == len(clean) == 3
    for g, c in zip(got, clean):
        for (a, b), exact in zip(zip(g, c), [1, 0, 0, 1, 1, 1]):
            if exact:
                assert a == b, (g, c)
            else:
                assert math.isclose(a, b, rel_tol=rtol, abs_tol=0.0), (g, c)


@pytest.mark.fault_injection
def test_degrade_disabled_surfaces_the_fault():
    conf = _inject("always", "stage_crash", site="exchange.write", **{
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
        "spark.rapids.tpu.sql.taskRetries": 0,
        "spark.rapids.tpu.fault.degrade.enabled": False,
    })
    with pytest.raises(TpuFaultError):
        _join_agg_query(srt.Session(conf)).collect()


def test_clean_run_reports_zero_fault_counters():
    sess = srt.Session()
    df = sess.create_dataframe({"x": [1.0, 2.0, 3.0]})
    df.select((df["x"] * 2.0).alias("y")).collect()
    m = sess.last_metrics
    assert m.get("fault.degradeLevel") == 0
    assert m.get("fault.numStageRetries") == 0
    assert m.get("fault.numChecksumFailures") == 0
    assert m.get("fault.numWatchdogTrips") == 0


# ==========================================================================
# Stage watchdog + bounded stage re-execution (unit, no jax)
# ==========================================================================
def _runner(n=2):
    from spark_rapids_tpu.parallel.mesh import make_mesh
    from spark_rapids_tpu.parallel.runner import DistributedRunner

    return DistributedRunner(make_mesh(n))


class _Ctx:
    def __init__(self, **kv):
        from spark_rapids_tpu.config import TpuConf

        self.conf = TpuConf(dict(FAST, **kv))


def test_watchdog_trips_on_hung_stage():
    r = _runner()
    ctx = _Ctx(**{"spark.rapids.tpu.fault.stageTimeoutMs": 100,
                  "spark.rapids.tpu.fault.maxStageRetries": 0})
    before = fault_stats.get("numWatchdogTrips")
    with pytest.raises(TpuStageTimeout):
        r._recover(lambda: time.sleep(2.0), ctx, "stage[test]")
    assert fault_stats.get("numWatchdogTrips") == before + 1


def test_recover_bounded_reexecution_then_success():
    r = _runner()
    ctx = _Ctx(**{"spark.rapids.tpu.fault.maxStageRetries": 3})
    calls = []

    def fn():
        calls.append(1)
        if len(calls) < 3:
            raise TpuStageCrash("boom", injected=True)
        return "ok"

    before = fault_stats.get("numStageRetries")
    assert r._recover(fn, ctx, "stage[test]") == "ok"
    assert len(calls) == 3
    assert fault_stats.get("numStageRetries") == before + 2


def test_recover_exhaustion_reraises_for_the_ladder():
    r = _runner()
    ctx = _Ctx(**{"spark.rapids.tpu.fault.maxStageRetries": 1})

    def fn():
        raise TpuStageCrash("persistent")

    with pytest.raises(TpuStageCrash):
        r._recover(fn, ctx, "stage[test]")


def test_recover_does_not_catch_non_fault_errors():
    r = _runner()
    ctx = _Ctx(**{"spark.rapids.tpu.fault.maxStageRetries": 5})
    calls = []

    def fn():
        calls.append(1)
        raise ValueError("a genuine bug")

    with pytest.raises(ValueError):
        r._recover(fn, ctx, "stage[test]")
    assert len(calls) == 1, "non-fault errors must not re-execute"


# ==========================================================================
# Distributed runner under injection (virtual 8-device CPU mesh)
# ==========================================================================
def _dist_query(sess):
    rng = np.random.RandomState(5)
    df = sess.create_dataframe({
        "k": rng.randint(0, 20, 240).tolist(),
        "v": [round(float(x), 6) for x in rng.rand(240) * 100]})
    return df.filter(df["v"] > 10).group_by("k").agg(
        F.sum("v").alias("s"), F.count("v").alias("c"))


def _dist_run(conf=None):
    from spark_rapids_tpu.parallel.mesh import make_mesh
    from spark_rapids_tpu.parallel.runner import run_distributed

    sess = srt.Session(dict(conf or {}))
    out = run_distributed(sess, _dist_query(sess), mesh=make_mesh(8))
    return sess, _norm(out.to_rows())


@pytest.mark.fault_injection
@pytest.mark.parametrize("fault_type,site,skips", [
    ("stage_crash", "stage.run", (0, 1)),
    ("stage_crash", "leaf.drain", (0, 1)),
    ("corrupt", "host.stack", (0,)),
])
def test_distributed_injection_sweep_bit_identical(fault_type, site,
                                                   skips):
    """Injected stage crashes and host round-trip corruption recover
    via bounded stage re-execution with bit-identical results."""
    _, clean = _dist_run(dict(FAST))
    for skip in skips:
        sess, got = _dist_run(_inject("nth", fault_type, site=site,
                                      skip=skip))
        assert got == clean, (fault_type, site, skip)
        m = sess.last_metrics
        assert m.get("fault.numStageRetries", 0) >= 1, (site, skip, m)
        if fault_type == "corrupt":
            assert m.get("fault.numChecksumFailures", 0) >= 1, m


@pytest.mark.fault_injection
def test_distributed_delay_trips_watchdog_and_recovers():
    """An injected straggler at the stage boundary trips the
    ``fault.stageTimeoutMs`` watchdog; the abandoned attempt re-executes
    and results stay bit-identical."""
    _, clean = _dist_run(dict(FAST))
    sess, got = _dist_run(_inject(
        "nth", "delay", site="stage.run", delay_ms=30000.0, **{
            "spark.rapids.tpu.fault.stageTimeoutMs": 3000,
        }))
    assert got == clean
    m = sess.last_metrics
    assert m.get("fault.numWatchdogTrips", 0) >= 1, m
    assert m.get("fault.numStageRetries", 0) >= 1, m


@pytest.mark.fault_injection
def test_distributed_ladder_degrades_to_single_process():
    """Persistent stage crashes exhaust fault.maxStageRetries: the
    ladder falls back to the single-process rung and still returns
    correct results, with degradeLevel=1 in the metrics."""
    from spark_rapids_tpu.fault.ladder import run_with_fault_tolerance
    from spark_rapids_tpu.parallel.mesh import make_mesh

    sess = srt.Session(_inject("always", "stage_crash", site="stage.run",
                               **{
        "spark.rapids.tpu.fault.maxStageRetries": 1,
    }))
    out = run_with_fault_tolerance(sess, _dist_query(sess),
                                   mesh=make_mesh(8))
    oracle = _dist_query(srt.Session(tpu_enabled=False)).collect()
    assert _norm(out.to_rows()) == _norm(oracle)
    m = sess.last_metrics
    assert m.get("fault.degradeLevel") == 1, m
    assert m.get("fault.numStageRetries", 0) >= 1, m


# ==========================================================================
# Prefetch-queue watchdog (exec/transitions.py satellite)
# ==========================================================================
def test_bounded_put_honors_stop_flag():
    import queue

    from spark_rapids_tpu.exec.transitions import _bounded_put

    q = queue.Queue(maxsize=1)
    q.put("full")
    stop = threading.Event()
    stop.set()
    assert _bounded_put(q, "x", stop, timeout_s=60.0) is False


def test_bounded_put_surfaces_watchdog_on_dead_consumer():
    import queue

    from spark_rapids_tpu.exec.transitions import _bounded_put

    q = queue.Queue(maxsize=1)
    q.put("full")  # nobody ever drains: the consumer is dead
    stop = threading.Event()
    t0 = time.monotonic()
    with pytest.raises(TpuStageTimeout):
        _bounded_put(q, "x", stop, timeout_s=0.3)
    assert time.monotonic() - t0 < 5.0, "must not busy-loop forever"


def test_next_prefetched_detects_dead_producer():
    import queue

    from spark_rapids_tpu.exec.transitions import _next_prefetched

    q = queue.Queue(maxsize=1)
    err = [None]
    dead = threading.Thread(target=lambda: None)
    dead.start()
    dead.join()
    with pytest.raises(TpuStageTimeout):
        _next_prefetched(q, dead, err)
    # and a recorded producer error is surfaced verbatim
    err[0] = RuntimeError("decode failed")
    with pytest.raises(RuntimeError, match="decode failed"):
        _next_prefetched(q, dead, err)


# ==========================================================================
# Semaphore watchdog as a retryable/degradable fault (satellite)
# ==========================================================================
def test_semaphore_timeout_is_a_typed_fault():
    from spark_rapids_tpu.memory.semaphore import (DeviceSemaphore,
                                                   DeviceSemaphoreTimeout)

    assert issubclass(DeviceSemaphoreTimeout, TpuFaultError)
    sem = DeviceSemaphore(1, acquire_timeout=0.3)
    holding = threading.Event()
    release = threading.Event()

    def holder():
        sem.acquire_if_necessary()
        holding.set()
        release.wait(timeout=30)
        sem.release_task()

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert holding.wait(timeout=30)
    with pytest.raises(DeviceSemaphoreTimeout):
        sem.acquire_if_necessary()
    release.set()
    t.join(timeout=30)


def test_semaphore_timeout_conf_is_wired():
    """fault.semaphoreTimeoutMs is a documented conf and reaches the
    DeviceSemaphore the DeviceManager builds."""
    from spark_rapids_tpu.config import FAULT_SEMAPHORE_TIMEOUT_MS, lookup

    assert lookup("spark.rapids.tpu.fault.semaphoreTimeoutMs") \
        is FAULT_SEMAPHORE_TIMEOUT_MS
    assert not FAULT_SEMAPHORE_TIMEOUT_MS.is_internal
    # 0 = built-in default; the stage-recovery protocol treats the
    # timeout as recoverable
    from spark_rapids_tpu.memory.semaphore import DeviceSemaphoreTimeout
    from spark_rapids_tpu.parallel.runner import RECOVERABLE_FAULTS

    assert DeviceSemaphoreTimeout in RECOVERABLE_FAULTS \
        or issubclass(DeviceSemaphoreTimeout, RECOVERABLE_FAULTS)


# ==========================================================================
# 2-process multi-controller crash/straggler (slow tier)
# ==========================================================================
@pytest.mark.slow
@pytest.mark.fault_injection
@pytest.mark.parametrize("fault", ["crash", "straggler"])
def test_two_process_fault_recovery(fault):
    """A 2-process CPU multi-controller run survives (a) a replicated
    stage crash re-executed in lockstep on every controller, and (b) a
    one-sided straggler delaying one controller's leaf drain — results
    stay oracle-equal on every controller."""
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
                          "mp_fault_worker.py")
    repo = REPO
    env = cpu_worker_env()

    procs = [subprocess.Popen(
        [sys.executable, script, coordinator, "2", str(pid), fault],
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
        pytest.fail("fault-injected multi-process workers timed out:\n"
                    + "\n".join(o or "" for o in outs))
    if any("Multiprocess computations aren't implemented" in (o or "")
           for o in outs):
        pytest.skip("this jax build's CPU backend lacks multi-process "
                    "collectives (same limitation as "
                    "test_multiprocess) — nothing to recover over")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"worker {pid} rc={p.returncode}:\n{out[-4000:]}"
        assert f"MPF RESULT OK pid={pid} fault={fault}" in out, \
            out[-4000:]
        if fault == "crash":
            assert f"MPF RETRIES pid={pid} n=" in out, out[-4000:]
