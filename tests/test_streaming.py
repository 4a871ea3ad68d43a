"""Incremental streaming execution (spark_rapids_tpu/streaming/).

The central invariant: EVERY micro-batch result equals a cold full
recompute of the same cumulative input — under growing sources, fault
injection, a hygiene sweep racing a live stream, and a SIGKILL between
micro-batches resumed in a fresh process.  Streaming only ever saves
work (merged exchange checkpoints + resume), never changes an answer.
Equal means: keys, counts, integer sums, minima, maxima and picks to
the bit; a floating-point sum or average to rounding (``FLOAT_RTOL``).
A tick's delta is aggregated by the HOST engine, which adds a group's
rows one by one, and the cold recompute by the device engine, which
since PR 29 adds them block by block (``ops/kernels/segment
.segmented_scan``): two orders of one sum.  With
``sql.variableFloatAgg.enabled=false`` float aggregates stay on the
host engine on both sides and the identity is to the bit again
(``test_q1_without_device_float_sums_is_bit_identical``):

* a tick over grown sources merges each eligible exchange's delta
  frames onto its committed base (``stream_incremental_merge``) and
  the cumulative query resumes it — ``recompute_fraction`` < 1.0;
* the source ledger commit AFTER the result is the exactly-once
  marker: a batch error (deadline, injection past retries) leaves the
  ledger untouched and the next tick retries the same cumulative set;
* a committed file being rewritten breaks the append-only contract and
  degrades that tick to a full recompute — still the right answer;
* the stream's checkpoint state is PINNED: TTL/maxBytes sweeps skip it
  while the stream lives, and reclaim it after ``stop()``.
"""
import json
import math
import os
import signal
import subprocess
import sys
import textwrap

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
from spark_rapids_tpu.io.arrow_convert import host_batch_to_arrow

FAST = {
    "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
    "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
}


def _conf(root, **extra):
    conf = dict(FAST)
    conf.update({
        "spark.rapids.tpu.recovery.enabled": True,
        "spark.rapids.tpu.recovery.dir": str(root),
        "spark.rapids.tpu.streaming.enabled": True,
        "spark.rapids.tpu.telemetry.enabled": True,
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
    })
    conf.update(extra)
    return conf


@pytest.fixture(scope="module")
def li_table():
    """The full sf=0.001 lineitem as ONE arrow table — sliced into
    parquet chunks that "arrive" over the course of a stream."""
    sess = srt.Session(dict(FAST))
    li = tpch_datagen.dataframes(sess, sf=0.001)["lineitem"]
    return pa.concat_tables(
        [host_batch_to_arrow(b) for b in li.plan.batches])


def _cuts(tbl, k):
    return [i * tbl.num_rows // k for i in range(k + 1)]


def _write_chunk(data_dir, tbl, cuts, i):
    os.makedirs(data_dir, exist_ok=True)
    pq.write_table(tbl.slice(cuts[i], cuts[i + 1] - cuts[i]),
                   os.path.join(data_dir, f"part-{i:03d}.parquet"))


def _tpch_query(sess, qnum, data_dir):
    tables = tpch_datagen.dataframes(sess, sf=0.001)
    tables["lineitem"] = sess.read_parquet(str(data_dir))
    return tpch.QUERIES[qnum](tables)


#: how far a float sum of the host engine and one of the device engine
#: may lie apart, relative: rounding of ~10^3 additions a group, a few
#: 1e-16 each, in another order
FLOAT_RTOL = 1e-12


def _norm(rows):
    """Rows in the order of their values that are no floats (a group-by's
    keys and counts; q3's order key)."""
    return sorted((tuple(r) for r in rows), key=lambda r: repr(
        [v for v in r if not isinstance(v, float)]))


def _same(got, want, rtol=FLOAT_RTOL):
    """Both ``_norm``-ed: every value equal, floats to ``rtol`` relative
    (0.0: to the bit)."""
    assert len(got) == len(want), (got, want)
    for g, w in zip(got, want):
        assert len(g) == len(w), (g, w)
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                assert math.isclose(a, b, rel_tol=rtol, abs_tol=0.0), (g, w)
            else:
                assert a == b and type(a) is type(b), (g, w)
    return True


def _batch_rows(hb):
    return _norm(zip(*[c.to_pylist() for c in hb.columns]))


def _oracle(qnum, data_dir, **extra):
    """Cold full recompute of the current cumulative input: fresh
    session, no recovery, no streaming."""
    sess = srt.Session(dict(FAST, **{
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0}, **extra))
    return _norm(_tpch_query(sess, qnum, data_dir).collect())


def _stream_events(handle, etype):
    return [e for e in handle.events() if e["event"] == etype]


# ==========================================================================
# Equality with a cold recompute over growing sources
# ==========================================================================
def test_q1_growing_fact_table_equals_cold_recompute(li_table, tmp_path):
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 4)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    sess = srt.Session(_conf(tmp_path / "rec"))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        h.process_available()
        p1 = h.progress()
        assert p1["streaming.batchId"] == 1
        assert p1["streaming.recomputeFraction"] == 1.0  # cold start

        _write_chunk(data, li_table, cuts, 2)
        out2 = h.process_available()
        p2 = h.progress()
        assert _same(_batch_rows(out2), _oracle(1, data))
        assert p2["streaming.mergedExchanges"] >= 1, p2
        assert p2["streaming.stagesResumed"] >= 1, p2
        assert p2["streaming.recomputeFraction"] < 1.0, p2
        assert _stream_events(h, "stream_incremental_merge")

        _write_chunk(data, li_table, cuts, 3)
        out3 = h.process_available()
        p3 = h.progress()
        assert _same(_batch_rows(out3), _oracle(1, data))
        assert p3["streaming.recomputeFraction"] < 1.0, p3
        assert len(_stream_events(h, "stream_batch_commit")) == 3
    finally:
        h.stop()
    assert _stream_events(h, "stream_stop")


def _stream_three_ticks(li_table, tmp_path, k, **extra):
    """q1 over ``k`` chunks: two at the cold start, then one a tick;
    (stream rows, cold recompute rows) after each of the later ticks."""
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, k)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    sess = srt.Session(_conf(tmp_path / "rec", **extra))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    pairs = []
    try:
        h.process_available()
        for i in range(2, k):
            _write_chunk(data, li_table, cuts, i)
            out = h.process_available()
            assert h.progress()["streaming.mergedExchanges"] >= 1
            pairs.append((_batch_rows(out), _oracle(1, data, **extra)))
    finally:
        h.stop()
    return pairs


def test_q1_ticks_past_a_scan_block_equal_to_rounding(li_table, tmp_path):
    """Ticks of ~2000 rows, twice the device scan's block and more: each
    of q1's four groups crosses block edges in the device's partial
    aggregate, so its float sums are the host delta's to rounding; keys
    and counts stay exact (``_same`` holds them to ``==``).  The bound
    is stated, not fitted: three times tighter still passes."""
    from spark_rapids_tpu.ops.kernels.segment import _SCAN_BLOCK

    assert li_table.num_rows // 3 > _SCAN_BLOCK + _SCAN_BLOCK // 2
    for got, want in _stream_three_ticks(li_table, tmp_path, 3):
        assert _same(got, want)
        assert _same(got, want, rtol=FLOAT_RTOL / 3)
        assert any(isinstance(v, float) for v in want[0])


def test_q1_without_device_float_sums_is_bit_identical(li_table, tmp_path):
    """The way back to the bit: with ``variableFloatAgg`` off, float
    aggregates run on the host engine in the stream and in the cold
    recompute alike, and every value is identical."""
    off = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": False}
    for got, want in _stream_three_ticks(li_table, tmp_path, 3, **off):
        assert _same(got, want, rtol=0.0)


@pytest.mark.slow
def test_q3_join_pipeline_equals_cold_recompute(li_table, tmp_path):
    """q3 joins the growing fact table with two static in-memory
    dimensions: the lineitem-side join exchange merges incrementally,
    the static-side exchanges resume UNCHANGED (same fingerprint), the
    post-join aggregate recomputes — and the result stays
    bit-identical."""
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    sess = srt.Session(_conf(tmp_path / "rec"))
    h = sess.stream(_tpch_query(sess, 3, data), trigger=0)
    try:
        h.process_available()
        _write_chunk(data, li_table, cuts, 2)
        out2 = h.process_available()
        p2 = h.progress()
        assert _same(_batch_rows(out2), _oracle(3, data))
        assert p2["streaming.stagesResumed"] >= 1, p2
        assert p2["streaming.recomputeFraction"] < 1.0, p2
    finally:
        h.stop()


# ==========================================================================
# Bit-identity under fault injection
# ==========================================================================
def _query_events(sess, etype):
    prof = sess.last_profile
    return [e for e in (prof.events.snapshot() if prof else [])
            if e["event"] == etype]


@pytest.mark.fault_injection
def test_corrupt_injection_on_exchange_write_keeps_the_answer(
        li_table, tmp_path):
    """Corruption on the exchange WRITE path (the only site a
    ``corrupt`` injector can fire — read-side CRC catches it at the
    checkpoint read-back) disables checkpointing for the batch; the
    stream degrades to full recompute but the committed answer must
    not change."""
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    sess = srt.Session(_conf(tmp_path / "rec", **{
        "spark.rapids.tpu.fault.injection.mode": "nth",
        "spark.rapids.tpu.fault.injection.type": "corrupt",
        "spark.rapids.tpu.fault.injection.site": "exchange.write",
        "spark.rapids.tpu.fault.injection.skipCount": 2,
        "spark.rapids.tpu.sql.taskRetries": 3,
    }))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        h.process_available()
        fired = len(_query_events(sess, "fault_injected"))
        _write_chunk(data, li_table, cuts, 2)
        out2 = h.process_available()
        fired += len(_query_events(sess, "fault_injected"))
        assert fired, "the corruption drill never fired — vacuous test"
        assert _same(_batch_rows(out2), _oracle(1, data))
    finally:
        h.stop()


@pytest.mark.fault_injection
def test_stage_crash_injection_mid_stream_keeps_the_answer(
        li_table, tmp_path):
    """A stage crash during a micro-batch retries through the normal
    recovery ladder (resuming checkpointed stages, merged ones
    included) and commits the same answer."""
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    sess = srt.Session(_conf(tmp_path / "rec", **{
        "spark.rapids.tpu.fault.injection.mode": "nth",
        "spark.rapids.tpu.fault.injection.type": "stage_crash",
        "spark.rapids.tpu.fault.injection.site": "exchange.read",
        "spark.rapids.tpu.fault.injection.skipCount": 2,
        "spark.rapids.tpu.sql.taskRetries": 3,
    }))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        h.process_available()
        fired = len(_query_events(sess, "fault_injected"))
        _write_chunk(data, li_table, cuts, 2)
        out2 = h.process_available()
        fired += len(_query_events(sess, "fault_injected"))
        assert fired, "the crash drill never fired — vacuous test"
        assert _same(_batch_rows(out2), _oracle(1, data))
    finally:
        h.stop()


# ==========================================================================
# Ledger semantics
# ==========================================================================
@pytest.mark.slow
def test_no_new_files_skips_tick(li_table, tmp_path):
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 2)
    _write_chunk(data, li_table, cuts, 0)
    sess = srt.Session(_conf(tmp_path / "rec"))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        assert h.process_available() is not None
        assert h.process_available() is None  # nothing new arrived
        skips = _stream_events(h, "stream_tick_skip")
        assert skips and skips[-1]["reason"] == "no_new_files"
        assert len(_stream_events(h, "stream_batch_commit")) == 1
    finally:
        h.stop()


@pytest.mark.slow
def test_rewritten_source_degrades_to_full_recompute(li_table, tmp_path):
    """Rewriting a COMMITTED file breaks the append-only contract: the
    tick must flag it, drop the incremental path, and still produce
    exactly the cold answer over the files as they now are."""
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    sess = srt.Session(_conf(tmp_path / "rec"))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        h.process_available()
        # rewrite the committed chunk with DIFFERENT rows (and size)
        pq.write_table(
            li_table.slice(cuts[0], cuts[2] - cuts[0]),
            os.path.join(str(data), "part-000.parquet"))
        out2 = h.process_available()
        assert _same(_batch_rows(out2), _oracle(1, data))
        skips = _stream_events(h, "stream_incremental_skip")
        assert any(e["reason"] == "source_rewritten" for e in skips)
    finally:
        h.stop()


@pytest.mark.slow
def test_max_batch_files_caps_and_drains_backlog(li_table, tmp_path):
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 4)
    _write_chunk(data, li_table, cuts, 0)
    sess = srt.Session(_conf(tmp_path / "rec", **{
        "spark.rapids.tpu.streaming.maxBatchFiles": 1}))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        h.process_available()
        # three files arrive at once; the cap admits one per tick
        _write_chunk(data, li_table, cuts, 1)
        _write_chunk(data, li_table, cuts, 2)
        _write_chunk(data, li_table, cuts, 3)
        h.process_available()
        p2 = h.progress()
        assert p2["streaming.filesTotal"] == 2, p2
        assert p2["streaming.backlogFiles"] == 2, p2
        caps = _stream_events(h, "stream_batch_capped")
        assert caps and caps[-1]["deferred_files"] == 2
        h.process_available()
        out4 = h.process_available()
        p4 = h.progress()
        assert p4["streaming.filesTotal"] == 4, p4
        assert p4["streaming.backlogFiles"] == 0, p4
        assert _same(_batch_rows(out4), _oracle(1, data))
    finally:
        h.stop()


def test_batch_deadline_miss_leaves_ledger_unadvanced(li_table, tmp_path):
    """``streaming.batchDeadlineMs`` rides the scheduler's cooperative
    deadline: a missed batch raises, emits ``stream_batch_error``, and
    does NOT commit — the next stream over the same state starts from
    batch 0 and serves the full, correct answer."""
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 2)
    _write_chunk(data, li_table, cuts, 0)
    sess = srt.Session(_conf(tmp_path / "rec", **{
        "spark.rapids.tpu.streaming.batchDeadlineMs": 1}))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        with pytest.raises(Exception):
            h.process_available()
        errs = _stream_events(h, "stream_batch_error")
        assert errs and errs[-1]["batch_id"] == 1
        assert not _stream_events(h, "stream_batch_commit")
    finally:
        h.stop()

    sess2 = srt.Session(_conf(tmp_path / "rec"))
    h2 = sess2.stream(_tpch_query(sess2, 1, data), trigger=0)
    try:
        assert not h2.resumed  # nothing was ever committed
        out = h2.process_available()
        assert _same(_batch_rows(out), _oracle(1, data))
    finally:
        h2.stop()


def test_stream_requires_conf_and_file_sources(li_table, tmp_path):
    data = tmp_path / "lineitem"
    _write_chunk(data, li_table, _cuts(li_table, 2), 0)
    sess = srt.Session(dict(FAST))
    with pytest.raises(RuntimeError, match="streaming.enabled"):
        sess.stream(_tpch_query(sess, 1, data))

    sess2 = srt.Session(_conf(tmp_path / "rec"))
    tables = tpch_datagen.dataframes(sess2, sf=0.001)
    with pytest.raises(ValueError, match="file source"):
        sess2.stream(tpch.QUERIES[1](tables))  # all in-memory

    hive = tmp_path / "hive" / "k=1"
    _write_chunk(hive, li_table, _cuts(li_table, 2), 0)
    with pytest.raises(ValueError, match="Hive-partitioned"):
        sess2.stream(
            _tpch_query(sess2, 1, tmp_path / "hive"), trigger=0)


@pytest.mark.slow
def test_trigger_loop_commits_batches(li_table, tmp_path):
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 2)
    _write_chunk(data, li_table, cuts, 0)
    sess = srt.Session(_conf(tmp_path / "rec"))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=50)
    try:
        out = h.await_batch(timeout=120)
        assert _same(_batch_rows(out), _oracle(1, data))
        _write_chunk(data, li_table, cuts, 1)
        out2 = h.await_batch(timeout=120)
        assert _same(_batch_rows(out2), _oracle(1, data))
    finally:
        h.stop()
    with pytest.raises(RuntimeError):
        h.process_available()


# ==========================================================================
# Pinned state vs the hygiene sweep (regression: a TTL/maxBytes sweep
# racing a live stream must never evict its aggregate state)
# ==========================================================================
@pytest.mark.slow
def test_sweep_during_live_stream_spares_pinned_state(li_table, tmp_path):
    from spark_rapids_tpu.recovery.store import CheckpointStore

    root = tmp_path / "rec"
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    sess = srt.Session(_conf(root))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    store = CheckpointStore(str(root))
    try:
        h.process_available()
        qdir = store.query_dir(h.stream_fp)
        assert os.path.isdir(qdir)
        # an aggressive sweep (everything expired AND over budget)
        # must spare the live stream's pinned state
        res = store.sweep(ttl_seconds=1e-9, max_bytes=1)
        assert os.path.isdir(qdir), res
        _write_chunk(data, li_table, cuts, 2)
        out2 = h.process_available()
        p2 = h.progress()
        assert _same(_batch_rows(out2), _oracle(1, data))
        assert p2["streaming.stagesResumed"] >= 1, p2  # state survived
    finally:
        h.stop()
    # stop() unpins: now the same sweep may reclaim the state
    store.sweep(ttl_seconds=1e-9, max_bytes=1)
    assert not os.path.isdir(store.query_dir(h.stream_fp))


# ==========================================================================
# SIGKILL between micro-batches, resume in a fresh process
# ==========================================================================
_CHILD = textwrap.dedent("""\
    import json, os, signal, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, {repo!r})
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen

    mode = sys.argv[1]       # "crash" | "resume" | "oracle"
    root = sys.argv[2]
    data = sys.argv[3]
    conf = {{
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
        "spark.rapids.tpu.recovery.enabled": mode != "oracle",
        "spark.rapids.tpu.recovery.dir": root,
        "spark.rapids.tpu.streaming.enabled": True,
        "spark.rapids.tpu.telemetry.enabled": True,
    }}
    sess = srt.Session(conf)
    tables = tpch_datagen.dataframes(sess, sf=0.001)
    tables["lineitem"] = sess.read_parquet(data)
    df = tpch.QUERIES[1](tables)

    def norm(rows):
        return [list(r) for r in rows]

    if mode == "oracle":
        print("RESULT:" + json.dumps({{"rows": norm(df.collect())}}))
        sys.exit(0)
    h = sess.stream(df, trigger=0)
    if mode == "crash":
        h.process_available()   # batch 1 commits (ledger + checkpoints)
        os.kill(os.getpid(), signal.SIGKILL)   # die between batches
    out = h.process_available()
    rows = norm(zip(*[c.to_pylist() for c in out.columns]))
    print("RESULT:" + json.dumps({{
        "rows": rows, "resumed": bool(h.resumed),
        "progress": h.progress()}}))
""")


def _run_child(mode, root, data):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=repo),
         mode, str(root), str(data)],
        capture_output=True, text=True, timeout=300)


def _child_result(proc):
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(
        f"child produced no RESULT\nstdout: {proc.stdout}\n"
        f"stderr: {proc.stderr}")


@pytest.mark.slow
def test_sigkill_between_batches_resumes_in_fresh_process(
        li_table, tmp_path):
    root, data = tmp_path / "rec", tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    _write_chunk(data, li_table, cuts, 1)
    crashed = _run_child("crash", root, data)
    assert crashed.returncode == -signal.SIGKILL, crashed.stderr

    _write_chunk(data, li_table, cuts, 2)  # arrives while "down"
    got = _child_result(_run_child("resume", root, data))
    assert got["resumed"] is True  # the durable ledger was found
    prog = got["progress"]
    assert prog["streaming.batchId"] == 2, prog  # continued, not restarted
    assert prog["streaming.stagesResumed"] > 0, prog
    assert prog["streaming.recomputeFraction"] < 1.0, prog
    oracle = _child_result(_run_child("oracle", root, data))
    assert _same(_norm(got["rows"]), _norm(oracle["rows"]))


# ==========================================================================
# Unit coverage: ledger + plan-shape normalization (no engine)
# ==========================================================================
def test_split_new_files_prefix_contract():
    from spark_rapids_tpu.streaming.ledger import split_new_files

    a = {"path": "a", "size": 1, "mtime_ns": 10}
    b = {"path": "b", "size": 2, "mtime_ns": 20}
    c = {"path": "c", "size": 3, "mtime_ns": 30}
    assert split_new_files([], [a, b]) == (True, [a, b])
    assert split_new_files([a], [a, b, c]) == (True, [b, c])
    assert split_new_files([a, b], [a, b]) == (True, [])
    # rewritten / truncated committed prefix breaks the contract
    assert split_new_files([a, b], [a]) == (False, [])
    a2 = dict(a, mtime_ns=11)
    assert split_new_files([a], [a2, b]) == (False, [])


def test_normalize_plan_text_erases_growing_counts():
    from spark_rapids_tpu.streaming.incremental import normalize_plan_text

    t1 = ("ShuffleExchange[HashPartitioning([k1, k2], 3)]\n"
          "  ShuffleExchange[RangePartitioning(3)]\n"
          "    FileScan[parquet](3 files)")
    t2 = ("ShuffleExchange[HashPartitioning([k1, k2], 8)]\n"
          "  ShuffleExchange[RangePartitioning(8)]\n"
          "    FileScan[parquet](17 files)")
    assert normalize_plan_text(t1) == normalize_plan_text(t2)
    # but keys and operators still distinguish shapes
    t3 = t1.replace("k2", "k9")
    assert normalize_plan_text(t1) != normalize_plan_text(t3)


# ==========================================================================
# Batch-latency histogram in the export surface (ISSUE 13)
# ==========================================================================
def test_batch_latency_histogram_in_progress_and_prometheus(
        li_table, tmp_path):
    data = tmp_path / "lineitem"
    cuts = _cuts(li_table, 3)
    _write_chunk(data, li_table, cuts, 0)
    sess = srt.Session(_conf(tmp_path / "rec"))
    h = sess.stream(_tpch_query(sess, 1, data), trigger=0)
    try:
        h.process_available()
        _write_chunk(data, li_table, cuts, 1)
        h.process_available()
        prog = h.progress()
        for p in ("P50", "P95", "P99"):
            assert f"streaming.batchLatency{p}Ms" in prog, sorted(prog)
        assert prog["streaming.batchLatencyP50Ms"] <= \
            prog["streaming.batchLatencyP99Ms"]
        assert prog["streaming.batchLatencyP50Ms"] > 0
        # live streams surface through the session's export/prometheus
        # aggregation, one labeled histogram series per stream
        em = sess.export_metrics()
        assert any(k.startswith("streaming.batchLatency") for k in em)
        text = sess.metrics_text()
        assert ("# TYPE spark_rapids_tpu_stream_batch_latency_ms "
                "histogram") in text
        assert f'le="+Inf"}} 2' in text
        assert f'stream="{h.stream_id}"' in text
    finally:
        h.stop()
    # a stopped stream drops out of the aggregation
    assert not any(k.startswith("streaming.")
                   for k in sess.export_metrics())
