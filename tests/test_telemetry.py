"""Query telemetry subsystem (spark_rapids_tpu/telemetry/).

Contract under test (ISSUE 4 acceptance): with ``telemetry.enabled``
a query — including one under deterministic fault injection — yields a
``Session.profile_report()`` with one span per physical exec (wall +
device-sync, rows/batches) and a JSONL event log containing the
injected retry/fault/degrade events; with it off, every emitter is a
no-op and the metrics snapshot is unchanged.
"""
import glob
import json
import os
import re

import numpy as np
import pytest
from conftest import REPO, cpu_worker_env

import spark_rapids_tpu as srt
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.telemetry import spans as tspans
from spark_rapids_tpu.telemetry.events import (EventLog, emit_event,
                                               read_event_log,
                                               replay_summary)
from spark_rapids_tpu.telemetry.export import (json_snapshot,
                                               prometheus_text)

TEL = {"spark.rapids.tpu.telemetry.enabled": True}
FAST = {
    "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
    "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
}


def _agg_df(sess, n=64):
    rng = np.random.RandomState(3)
    df = sess.create_dataframe({
        "g": rng.randint(0, 5, n),
        "v": (rng.rand(n) * 10).round(6)})
    return df.group_by("g").agg(F.sum("v").alias("s"),
                                F.count("v").alias("n"))


# ==========================================================================
# Span tree shape
# ==========================================================================
def test_span_tree_one_span_per_exec():
    sess = srt.Session(dict(TEL))
    _agg_df(sess).collect()
    prof = sess.last_profile
    assert prof is not None
    execs = prof.exec_spans()
    # one exec-kind span per physical exec name of the plan
    for name in ("HostToDeviceExec", "DeviceToHostExec",
                 "TpuHashAggregateExec", "TpuShuffleExchangeExec"):
        assert name in execs, sorted(execs)
    # transitions carry rows/batches and device-sync wall
    h2d = execs["HostToDeviceExec"]
    assert h2d["rows"] > 0 and h2d["batches"] > 0
    assert h2d["device_sync_ns"] > 0
    assert h2d["wall_ns"] > 0
    # root is the query span and parents every exec span
    tree = prof.span_tree()
    assert tree["kind"] == "query"
    assert prof.wall_ns > 0
    kids = {c["name"] for c in tree["children"]}
    assert "HostToDeviceExec" in kids


def test_profile_report_renders_explain_analyze():
    sess = srt.Session(dict(TEL))
    _agg_df(sess).collect()
    report = sess.profile_report()
    assert "Query profile" in report
    assert "Physical plan (annotated)" in report
    assert "HostToDevice" in report and "wall=" in report
    assert "operators by wall" in report
    assert "Span tree" in report
    assert "query_begin: 1" in report


def test_profiles_ring_is_bounded():
    sess = srt.Session(dict(TEL, **{
        "spark.rapids.tpu.telemetry.maxQueryProfiles": 2}))
    df = _agg_df(sess)
    for _ in range(3):
        df.collect()
    assert len(sess.profiles) == 2
    assert sess.profiles[-1] is sess.last_profile


# ==========================================================================
# Event log: round-trip + emitters under fault injection
# ==========================================================================
def test_event_log_roundtrip_and_retry_events(tmp_path):
    conf = dict(TEL, **FAST)
    conf.update({
        "spark.rapids.tpu.telemetry.eventLog.dir": str(tmp_path),
        # one injected OOM at the first upload checkpoint drives the
        # retry recovery path
        "spark.rapids.tpu.memory.oomInjection.mode": "nth",
        "spark.rapids.tpu.memory.oomInjection.skipCount": 0,
    })
    sess = srt.Session(conf)
    _agg_df(sess).collect()
    assert sess.last_metrics.get("retry.numRetries", 0) >= 1

    files = glob.glob(str(tmp_path / "events-*.jsonl"))
    assert len(files) == 1
    events = read_event_log(files[0])
    kinds = {e["event"] for e in events}
    assert {"query_begin", "query_end", "fault_injected",
            "retry"} <= kinds, kinds
    # write -> parse -> replay: the file round-trips to the same
    # stream the in-memory ring holds
    summary = replay_summary(events)
    ring = replay_summary(sess.last_profile.events.snapshot())
    assert summary["counts"] == ring["counts"]
    assert summary["queries"] == ring["queries"]
    # every record is one flat JSON object with the core fields
    for e in events:
        assert e["query"] == summary["queries"][0]
        assert isinstance(e["ts"], float)


@pytest.mark.fault_injection
def test_degrade_and_fault_events_reach_the_profile():
    """A query that exhausts fault recovery and degrades to the CPU
    rung must leave the injected fault AND the degrade decision in the
    event log of its profile (late events land in the same ring)."""
    conf = dict(TEL, **FAST)
    conf.update({
        "spark.rapids.tpu.fault.injection.mode": "always",
        "spark.rapids.tpu.fault.injection.type": "stage_crash",
        "spark.rapids.tpu.fault.injection.site": "exchange.write",
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
        "spark.rapids.tpu.sql.taskRetries": 0,
    })
    sess = srt.Session(conf)
    _agg_df(sess).collect()
    assert sess.last_metrics.get("fault.degradeLevel") == 2
    prof = sess.last_profile
    assert prof is not None
    kinds = {e["event"] for e in prof.events.snapshot()}
    assert "fault_injected" in kinds, kinds
    assert "degrade" in kinds, kinds
    degrade = [e for e in prof.events.snapshot()
               if e["event"] == "degrade"][-1]
    assert degrade["level"] == 2 and degrade["rung"] == "cpu"
    # the profile's metrics reflect the final merged counters
    assert prof.metrics.get("fault.degradeLevel") == 2


def test_event_ring_is_bounded_and_counts_drops():
    log = EventLog("qtest", max_events=4)
    for i in range(10):
        log.emit("spill", i=i)
    assert len(log) == 4
    assert log.dropped == 6
    assert [e["i"] for e in log.snapshot()] == [6, 7, 8, 9]


def test_sink_serializes_numpy_scalars(tmp_path):
    """Emitter fields are unvalidated kwargs from ~15 engine call
    sites; numpy scalars (spill sizes, byte counts from array math)
    must land in the JSONL sink, not silently vanish from it."""
    log = EventLog("qnp", max_events=8, sink_dir=str(tmp_path))
    log.emit("spill", bytes=np.int64(5), frac=np.float32(0.5))
    events = read_event_log(str(tmp_path / "events-qnp.jsonl"))
    assert len(events) == 1 and events[0]["event"] == "spill"
    assert log.sink_path is not None  # sink still healthy


def test_emit_event_is_noop_and_safe_without_binding():
    tspans.deactivate()
    emit_event("spill", bytes=1)  # must not raise, must not bind
    assert tspans.current() is None


@pytest.mark.fault_injection
def test_tpch_under_injection_profiles_every_exec(tmp_path):
    """The acceptance shape: a TPC-H query under fault injection
    yields a profile with one span per physical exec (wall +
    device-sync, rows/batches) AND a JSONL event log containing the
    injected retry events."""
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
    from spark_rapids_tpu.session import Session

    conf = dict(TEL, **FAST)
    conf.update({
        "spark.rapids.tpu.telemetry.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.memory.oomInjection.mode": "nth",
        "spark.rapids.tpu.memory.oomInjection.skipCount": 1,
    })
    sess = Session(conf)
    tables = tpch_datagen.dataframes(sess, sf=0.0007, seed=7)
    tpch.QUERIES[1](tables).collect()
    prof = sess.last_profile
    assert prof is not None
    execs = prof.exec_spans()
    # every exec of q1's physical plan that registered metrics has a
    # span with its measured wall; the transitions carry rows + sync
    assert {"HostToDeviceExec", "DeviceToHostExec",
            "TpuHashAggregateExec"} <= set(execs), sorted(execs)
    assert execs["HostToDeviceExec"]["rows"] > 0
    assert execs["HostToDeviceExec"]["device_sync_ns"] > 0
    report = sess.profile_report()
    assert "TpuHashAggregate" in report
    files = glob.glob(str(tmp_path / "events-*.jsonl"))
    assert len(files) == 1
    kinds = {e["event"] for e in read_event_log(files[0])}
    assert "fault_injected" in kinds and "retry" in kinds, kinds


# ==========================================================================
# Disabled mode: no-ops, snapshot unchanged
# ==========================================================================
def test_disabled_mode_keeps_metrics_snapshot_identical():
    on = srt.Session(dict(TEL))
    _agg_df(on).collect()
    on_keys = set(on.last_metrics)

    off = srt.Session()
    _agg_df(off).collect()
    off_keys = set(off.last_metrics)

    assert off.last_profile is None and off.profiles == []
    assert off.profile_report() == ""
    # the telemetry-only deviceSyncTime metrics exist ONLY under
    # telemetry; everything else is the identical key set
    sync = {k for k in on_keys if k.endswith(".deviceSyncTime")}
    assert sync, on_keys
    assert not any(k.endswith(".deviceSyncTime") for k in off_keys)
    assert on_keys - sync == off_keys
    # two disabled runs produce the identical key set (stability)
    off2 = srt.Session()
    _agg_df(off2).collect()
    assert set(off2.last_metrics) == off_keys


# ==========================================================================
# _finalize_metrics: no double counting across consecutive queries
# ==========================================================================
def test_counters_not_double_counted_across_queries():
    conf = dict(TEL, **FAST)
    conf.update({
        "spark.rapids.tpu.memory.oomInjection.mode": "nth",
        "spark.rapids.tpu.memory.oomInjection.skipCount": 0,
    })
    sess = srt.Session(conf)
    df = _agg_df(sess)
    df.collect()
    first = sess.last_metrics.get("retry.numRetries", 0)
    assert first >= 1
    # the injector re-arms per query (nth fires once per run): the
    # second run must report ITS OWN counters, not accumulate
    df.collect()
    assert sess.last_metrics.get("retry.numRetries", 0) == first
    # and a clean session reports zeros, not inherited counters
    clean = srt.Session(dict(TEL))
    _agg_df(clean).collect()
    assert clean.last_metrics.get("retry.numRetries", 0) == 0
    assert clean.last_metrics.get("fault.numStageRetries") == 0


# ==========================================================================
# trace_range (satellite): one exception-safe path + span coupling
# ==========================================================================
def test_trace_range_metric_coupling_survives_exceptions():
    from spark_rapids_tpu.utils.metrics import Metric
    from spark_rapids_tpu.utils.tracing import trace_range

    m = Metric("t", "ns")
    with pytest.raises(ValueError):
        with trace_range("boom", m):
            raise ValueError("x")
    assert m.value > 0


def test_trace_range_aggregates_into_current_span():
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.utils.tracing import trace_range

    tele = tspans.QueryTelemetry(TpuConf(dict(TEL)))
    tspans.activate(tele)
    try:
        with tspans.span("work", kind="stage") as sp:
            with trace_range("inner"):
                with trace_range("inner"):  # re-entrant: counted once
                    pass
            with trace_range("other"):
                pass
        assert set(sp.range_ns) == {"inner", "other"}
        assert sp.range_ns["inner"] > 0
    finally:
        tspans.deactivate()


def test_capture_attached_propagates_binding_to_worker():
    import threading

    from spark_rapids_tpu.config import TpuConf

    tele = tspans.QueryTelemetry(TpuConf(dict(TEL)))
    tspans.activate(tele)
    seen = {}

    def work():
        seen["tele"] = tspans.current()

    try:
        cap = tspans.capture()
        t = threading.Thread(target=tspans.bound(cap, work))
        t.start()
        t.join()
        assert seen["tele"] is tele
    finally:
        tspans.deactivate()


# ==========================================================================
# Regression: profiles never back-fill from a previous query
# ==========================================================================
def test_distributed_profile_uses_own_query_metrics():
    """A distributed run after a (bigger) native run must back-fill
    its exec spans from ITS OWN ctx snapshot, not the session's
    previous last_metrics."""
    from spark_rapids_tpu.parallel.runner import run_distributed

    sess = srt.Session(dict(TEL))
    a = sess.create_dataframe({"k": [1, 2] * 32, "v": [1.0] * 64})
    a.group_by("k").agg(f_sum_s()).collect()  # query A: 64 rows
    b = sess.create_dataframe({"k": [1, 2] * 16, "v": [2.0] * 32})
    run_distributed(sess, b.group_by("k").agg(f_sum_s()), n_devices=8)
    prof = sess.last_profile
    h2d = prof.exec_spans().get("HostToDeviceExec")
    if h2d is not None:  # leaf execs registered on this mesh layout
        assert h2d["rows"] == 32, h2d
    # none of query A's per-exec families may leak into B's profile
    assert not [k for k in prof.metrics
                if k.startswith("TpuShuffleExchangeExec")]


def f_sum_s():
    return F.sum("v").alias("s")


def test_bad_event_log_dir_degrades_to_ring():
    """A misconfigured eventLog.dir must never fail the query — the
    log degrades to the in-memory ring."""
    sess = srt.Session(dict(TEL, **{
        "spark.rapids.tpu.telemetry.eventLog.dir": "/proc/nope/x"}))
    d = sess.create_dataframe({"x": [1.0, 2.0]})
    rows = d.select((d["x"] * 2).alias("y")).collect()
    assert sorted(rows) == [(2.0,), (4.0,)]
    prof = sess.last_profile
    assert prof is not None
    assert prof.events.sink_path is None
    assert {e["event"] for e in prof.events.snapshot()} >= {
        "query_begin", "query_end"}


@pytest.mark.fault_injection
def test_ladder_degrade_event_lands_in_reported_profile():
    """rung 0 -> 1: the degrade decision must be visible in the
    profile the user reads (last_profile = the rung-1 query's), with
    the cross-rung merged counters."""
    from spark_rapids_tpu.fault.ladder import run_with_fault_tolerance

    conf = dict(TEL, **FAST)
    conf.update({
        "spark.rapids.tpu.fault.injection.mode": "always",
        "spark.rapids.tpu.fault.injection.type": "stage_crash",
        "spark.rapids.tpu.fault.injection.site": "stage.run",
        "spark.rapids.tpu.fault.maxStageRetries": 0,
    })
    sess = srt.Session(conf)
    df = sess.create_dataframe({"k": [1, 2, 1], "v": [1.0, 2.0, 3.0]})
    run_with_fault_tolerance(
        sess, df.group_by("k").agg(F.sum("v").alias("s")), n_devices=8)
    assert sess.last_metrics.get("fault.degradeLevel") == 1
    kinds = {e["event"] for e in sess.last_profile.events.snapshot()}
    assert "degrade" in kinds, kinds
    assert sess.last_profile.metrics.get("fault.degradeLevel") == 1


def test_disabled_query_clears_stale_last_profile():
    """After a telemetry-enabled query, a later disabled query on the
    same session must not leave the old profile posing as 'the most
    recent execution' (history stays in session.profiles)."""
    sess = srt.Session(dict(TEL))
    d = sess.create_dataframe({"x": [1.0, 2.0]})
    d.select((d["x"] * 2).alias("y")).collect()
    assert sess.last_profile is not None
    kept = sess.last_profile
    sess.conf = sess.conf.set(
        "spark.rapids.tpu.telemetry.enabled", False)
    d2 = sess.create_dataframe({"x": [3.0]})
    d2.select((d2["x"] * 2).alias("y")).collect()
    assert sess.last_profile is None
    assert sess.profile_report() == ""
    assert kept in sess.profiles  # history survives


def test_columnar_export_finishes_telemetry():
    """The ML export path owns its ExecContext, so it must finish the
    query telemetry too — stopping the HbmSampler thread and emitting
    query_end (a leaked sampler polls the DeviceManager forever)."""
    import threading

    sess = srt.Session(dict(TEL, **{
        "spark.rapids.tpu.sql.exportColumnarRdd": True,
        "spark.rapids.tpu.telemetry.sampleHbmMs": 5}))
    d = sess.create_dataframe({"x": [1.0, 2.0, 3.0]})
    batches = sess.execute_columnar(
        d.select((d["x"] * 2).alias("y")).plan)
    assert batches
    prof = sess.last_profile
    assert prof is not None
    kinds = [e["event"] for e in prof.events.snapshot()]
    assert kinds.count("query_end") == 1, kinds
    assert not [t for t in threading.enumerate()
                if t.name == "hbm-sampler" and t.is_alive()]


def test_hbm_watermark_uses_peak_column():
    from spark_rapids_tpu.config import TpuConf

    tele = tspans.QueryTelemetry(TpuConf(dict(TEL)))
    # a spike freed between samples: allocated back at 10, peak at 99
    tele.hbm_timeline = [(1.0, 10, 10), (2.0, 10, 99)]
    tele.finished = True
    from spark_rapids_tpu.telemetry.profile import QueryProfile

    prof = QueryProfile(tele, metrics={})
    assert "peak=99B" in prof.render()
    text = prometheus_text({}, hbm_timeline=prof.hbm_timeline)
    assert "hbm_watermark_bytes 99" in text


# ==========================================================================
# Exporters
# ==========================================================================
_PROM_LINE = re.compile(
    r'^spark_rapids_tpu_metric\{exec="[A-Za-z0-9_]*",'
    r'name="[A-Za-z0-9_]+"(,(tenant|exchange)="[^"]+")?'
    r'(,query="[^"]+")?\} -?[0-9.e+-]+$')


def test_prometheus_export_format_and_stability():
    sess = srt.Session(dict(TEL))
    _agg_df(sess).collect()
    snap = sess.last_metrics
    text1 = prometheus_text(snap, query_id=sess.last_profile.query_id)
    text2 = prometheus_text(snap, query_id=sess.last_profile.query_id)
    assert text1 == text2  # deterministic ordering
    lines = [ln for ln in text1.splitlines()
             if ln and not ln.startswith("#")]
    assert lines
    for ln in lines:
        assert _PROM_LINE.match(ln), ln
    # per-exec metrics carry the exec label
    assert any('exec="HostToDeviceExec"' in ln for ln in lines)
    # counter families export with an empty exec label
    assert any('exec="",name="fault_degradeLevel"' in ln
               for ln in lines)


def test_prometheus_tenant_and_exchange_labels():
    metrics = {
        "scheduler.tenant.alpha.finished": 3,
        "scheduler.tenant.alpha.latencyP95Ms": 12.5,
        "scheduler.tenant.beta-2.shed": 1,
        "shuffle.exchange2.spillBytes": 4096,
        "fault.degradeLevel": 0,
    }
    text = prometheus_text(metrics)
    assert ('spark_rapids_tpu_metric{exec="",'
            'name="scheduler_tenant_finished",tenant="alpha"} 3') in text
    assert ('spark_rapids_tpu_metric{exec="",'
            'name="scheduler_tenant_shed",tenant="beta-2"} 1') in text
    assert ('spark_rapids_tpu_metric{exec="",'
            'name="shuffle_exchange_spillBytes",exchange="2"} 4096') \
        in text
    # every line still matches the canonical grammar, and unlabeled
    # families render exactly as before
    lines = [ln for ln in text.splitlines()
             if ln and not ln.startswith("#")]
    for ln in lines:
        assert _PROM_LINE.match(ln), ln
    assert 'exec="",name="fault_degradeLevel"} 0' in text


def test_prometheus_histogram_exposition():
    from spark_rapids_tpu.telemetry.histogram import LatencyHistogram
    h = LatencyHistogram(window_s=60.0)
    for v in (0.5, 1.0, 2.0, 1000.0):
        h.observe(v, now=100.0)
    text = prometheus_text({}, histograms=[
        ("queue_wait_ms", {}, h),
        ("query_latency_ms", {"tenant": "alpha"}, h),
    ])
    assert "# TYPE spark_rapids_tpu_queue_wait_ms histogram" in text
    assert "# TYPE spark_rapids_tpu_query_latency_ms histogram" in text
    # cumulative buckets are monotone and the +Inf bucket equals _count
    buckets = re.findall(
        r'spark_rapids_tpu_queue_wait_ms_bucket\{le="([^"]+)"\} (\d+)',
        text)
    counts = [int(c) for _le, c in buckets]
    assert counts == sorted(counts)
    assert buckets[-1][0] == "+Inf" and counts[-1] == 4
    assert "spark_rapids_tpu_queue_wait_ms_count 4" in text
    assert "spark_rapids_tpu_queue_wait_ms_sum 1003.5" in text
    # labeled series put the labels before le=
    assert ('spark_rapids_tpu_query_latency_ms_bucket{tenant="alpha",'
            'le="+Inf"} 4') in text
    assert ('spark_rapids_tpu_query_latency_ms_count{tenant="alpha"} 4'
            ) in text


def test_json_snapshot_round_trips():
    sess = srt.Session(dict(TEL))
    _agg_df(sess).collect()
    prof = sess.last_profile
    doc = json.loads(json_snapshot(
        sess.last_metrics, query_id=prof.query_id,
        events=prof.events.snapshot(),
        hbm_timeline=prof.hbm_timeline))
    assert doc["query"] == prof.query_id
    assert doc["metrics"] == {k: v for k, v in
                              sess.last_metrics.items()}
    assert doc["events"]["counts"]["query_begin"] == 1
    assert json_snapshot(sess.last_metrics) == \
        json_snapshot(dict(sess.last_metrics))  # stable


# ==========================================================================
# HBM watermark sampler
# ==========================================================================
@pytest.mark.slow
def test_hbm_watermark_timeline_sampled():
    sess = srt.Session(dict(TEL, **{
        "spark.rapids.tpu.telemetry.sampleHbmMs": 5}))
    _agg_df(sess, n=4096).collect()
    prof = sess.last_profile
    # at least the t0 + closing samples; ts monotone; peak >= allocated
    assert len(prof.hbm_timeline) >= 2
    ts = [t[0] for t in prof.hbm_timeline]
    assert ts == sorted(ts)
    for _t, allocated, peak in prof.hbm_timeline:
        assert peak >= 0 and allocated >= 0
    assert "HBM watermark" in prof.render()


# ==========================================================================
# Multiprocess event ship-back
# ==========================================================================
def test_extend_shipped_merges_peer_events():
    log = EventLog("qtest", max_events=8)
    log.emit("query_begin")
    log.extend_shipped([{"ts": 1.0, "event": "spill", "query": "qpeer",
                         "proc": 1}])
    events = log.snapshot()
    assert len(events) == 2
    assert events[-1]["proc"] == 1


def test_gather_events_single_process_returns_no_peers():
    from spark_rapids_tpu.telemetry.events import (
        gather_multiprocess_events)

    # single controller: the collective degenerates to "no peers" —
    # the local ring must stay untouched
    assert gather_multiprocess_events(
        [{"ts": 1.0, "event": "query_begin", "query": "q"}]) == []


@pytest.mark.slow
def test_two_process_event_shipback():
    import socket
    import subprocess
    import sys

    def _free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    coordinator = f"127.0.0.1:{_free_port()}"
    script = os.path.join(os.path.dirname(__file__),
                          "mp_telemetry_worker.py")
    env = cpu_worker_env()
    repo = REPO
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
        pytest.fail("mp telemetry workers timed out:\n"
                    + "\n".join(o or "" for o in outs))
    if any("Multiprocess computations aren't implemented" in (o or "")
           for o in outs):
        pytest.skip("this jax build cannot run multi-process "
                    "collectives on the CPU backend")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"worker {pid} rc={p.returncode}:\n{out[-4000:]}"
        assert f"MP TELEMETRY OK pid={pid}" in out, out[-4000:]


# ==========================================================================
# Doc drift: every registered conf key is documented
# ==========================================================================
def test_every_conf_key_documented_in_configs_md():
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.plan.overrides import _ensure_registry

    _ensure_registry()  # auto-derived per-operator keys register lazily
    doc_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "configs.md")
    with open(doc_path) as f:
        doc = f.read()
    missing = [key for key, e in C._REGISTRY.items()
               if not e.is_internal and f"`{key}`" not in doc]
    assert not missing, \
        f"conf keys missing from docs/configs.md: {missing} — " \
        "regenerate with config.dump_markdown()"
