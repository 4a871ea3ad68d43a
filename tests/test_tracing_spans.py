"""The program's own spans and program names (ISSUE 25).

Every host span goes through ``utils.tracing.trace_range``: with
``sql.trace.enabled`` it opens a ``jax.profiler.TraceAnnotation`` on
the thread that does the work, and without it constructs none.  Here
the annotation is replaced by a recorder, so the tests see what a
profiler would: which spans a request opens, on which thread, inside
which other span, and that each is closed before its batch is handed
on.  Device programs are named after their operator at the one place
they are jitted (``exec/kernel_cache.py``)."""
import os
import subprocess
import sys
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.exec.kernel_cache import (_CachedKernel, jit_kernel,
                                                program_name)
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.utils import tracing

TRACED = {"spark.rapids.tpu.sql.trace.enabled": True,
          "spark.rapids.tpu.sql.test.enabled": True}

#: spans that do their own work and pull nothing: nothing opens inside
#: (but a child of their own, see CHILDREN)
LEAVES = {"Plan", "ScanDecode", "PrefetchWait", "HostToDevice",
          "TpuShuffleWrite.counts", "TpuShuffleRead",
          "DeviceToHost.wait", "DeviceToHost.copy", "TpuFilter",
          "TpuProject", "TpuExpand", "TpuCoalesce.concat", "TpuWindow"}


#: the two that name a part of themselves: the string columns'
#: conversion in the decode, their encoding in the upload (ISSUE 32)
CHILDREN = {"ScanDecode": {"ScanDecode.strings"},
            "HostToDevice": {"HostToDevice.strings"}}


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: per thread, the
    spans in the order they opened, each with its parent and whether it
    closed as the innermost open one."""

    def __init__(self):
        self.lock = threading.Lock()
        self.made = 0
        self.spans = []          # (name, thread, parent, metadata)
        self.misnested = []
        self._open = {}          # thread -> stack of names

    def annotation(self, name, **metadata):
        rec = self

        class Annotation:
            def __enter__(self):
                thread = threading.current_thread().name
                with rec.lock:
                    stack = rec._open.setdefault(thread, [])
                    rec.spans.append((name, thread,
                                      stack[-1] if stack else None,
                                      metadata))
                    stack.append(name)
                return self

            def __exit__(self, *exc):
                thread = threading.current_thread().name
                with rec.lock:
                    stack = rec._open[thread]
                    if stack[-1] != name:
                        rec.misnested.append((name, list(stack)))
                    stack.remove(name)

        with self.lock:
            self.made += 1
        return Annotation()

    def names(self):
        return {s[0] for s in self.spans}

    def parents(self, name):
        return {s[2] for s in self.spans if s[0] == name}

    def threads(self, name):
        return {s[1] for s in self.spans if s[0] == name}

    def still_open(self):
        return {t: list(s) for t, s in self._open.items() if s}


@pytest.fixture()
def recorder(monkeypatch):
    import jax.profiler

    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.annotation)
    was = tracing._ENABLED
    yield rec
    tracing.enable(was)


def _parquet(tmp_path, n=5000, files=2):
    for i in range(files):
        pq.write_table(
            pa.table({"k": np.arange(n) % 7,
                      "v": np.arange(n, dtype=np.float64)}),
            os.path.join(str(tmp_path), f"part-{i}.parquet"))
    return str(tmp_path)


def _filter_sum(sess, path):
    return sess.read_parquet(path).filter(F.col("k") > 2) \
        .agg(F.sum("v").alias("s"))


# ==========================================================================
# host spans
# ==========================================================================
def test_a_parquet_filter_sum_opens_every_span_where_the_work_is(
        tmp_path, recorder):
    sess = srt.Session(dict(TRACED))
    q = _filter_sum(sess, _parquet(tmp_path))
    rows = q.collect()
    want = 2 * float(sum(v for v in range(5000) if v % 7 > 2))
    assert rows == [(want,)]
    client = threading.current_thread().name

    assert {"Query", "Plan", "ScanDecode", "PrefetchWait", "HostToDevice",
            "TpuShuffleWrite", "TpuShuffleWrite.counts", "TpuShuffleRead",
            "DeviceToHost", "DeviceToHost.wait",
            "DeviceToHost.copy"} <= recorder.names()
    # who is inside whom
    assert recorder.parents("Query") == {None}
    assert recorder.parents("Plan") == {"Query"}
    assert recorder.parents("DeviceToHost.wait") == {"DeviceToHost"}
    assert recorder.parents("DeviceToHost.copy") == {"DeviceToHost"}
    assert recorder.parents("TpuShuffleWrite.counts") == \
        {"TpuShuffleWrite"}
    # the pull of the exchange is inside its write: the wait for the
    # decode thread and the upload are its children, with their names
    assert recorder.parents("PrefetchWait") == {"TpuShuffleWrite"}
    assert recorder.parents("HostToDevice") == {"TpuShuffleWrite"}
    # the read side is no part of the write
    assert "TpuShuffleWrite" not in recorder.parents("TpuShuffleRead")
    # the decode runs beside the client's thread, one producer a file
    decoders = recorder.threads("ScanDecode")
    assert client not in decoders
    assert all(t.startswith("h2d-prefetch-") for t in decoders)
    assert len(decoders) == 2
    assert recorder.parents("ScanDecode") == {None}
    assert recorder.threads("PrefetchWait") == {client}
    # the request carries its number as metadata, not in its name
    assert [s[3] for s in recorder.spans if s[0] == "Query"] == \
        [{"query_id": 1}]
    # every span closed, innermost first, and nothing opened inside a
    # span that pulls nothing (one left open across a ``yield`` would
    # have its consumer's spans inside it)
    assert recorder.misnested == [] and recorder.still_open() == {}
    inside_a_leaf = [s for s in recorder.spans if s[2] in LEAVES
                     and s[0] not in CHILDREN.get(s[2], ())]
    assert inside_a_leaf == []


def test_a_string_column_opens_the_two_children_and_nothing_else(
        tmp_path, recorder):
    pq.write_table(pa.table({"k": np.arange(300) % 7,
                             "s": [f"row {i}" for i in range(300)]}),
                   os.path.join(str(tmp_path), "part-0.parquet"))
    sess = srt.Session(dict(TRACED))
    rows = sess.read_parquet(str(tmp_path)).filter(F.col("k") > 2) \
        .select("s").collect()
    assert len(rows) == sum(1 for i in range(300) if i % 7 > 2)
    assert recorder.parents("ScanDecode.strings") == {"ScanDecode"}
    assert recorder.parents("HostToDevice.strings") == {"HostToDevice"}
    assert recorder.misnested == [] and recorder.still_open() == {}
    assert [s for s in recorder.spans if s[2] in LEAVES | {
        "ScanDecode.strings", "HostToDevice.strings"}
        and s[0] not in CHILDREN.get(s[2], ())] == []


def test_the_stage_loop_opens_a_span_a_stage_and_one_a_replan(recorder):
    # ISSUE 34: the adaptive driver's two steps, children of the request
    sess = srt.Session(dict(TRACED))
    df = sess.create_dataframe({"k": [1, 2, 1, 2] * 64,
                                "v": list(range(256))}, n_partitions=2)
    rows = df.group_by("k").agg(F.sum("v").alias("s")).sort("k").collect()
    assert [r[0] for r in rows] == [1, 2]
    stages = sess.last_metrics["aqe.numStages"]
    assert stages >= 2      # the aggregate's exchange and the sort's
    assert recorder.parents("AqeStage") == {"Query"}
    assert recorder.parents("AqeReplan") == {"Query"}
    # one of each a stage, in turn, the stage carrying its ordinal and
    # its exchange's id as metadata, not in its name
    loop = [s for s in recorder.spans if s[0].startswith("Aqe")]
    assert [s[0] for s in loop] == ["AqeStage", "AqeReplan"] * stages
    assert [s[3]["stage"] for s in loop[::2]] == list(range(stages))
    ids = [s[3]["exchange"] for s in loop[::2]]
    assert len(set(ids)) == stages and all(
        f"shuffle.exchange{i}.rowsTotal" in sess.last_metrics for i in ids)
    assert all(s[3] == {} for s in loop[1::2])
    # the exchange's write is the stage's work; the re-plan pulls nothing
    assert recorder.parents("TpuShuffleWrite") == {"AqeStage"}
    assert [s for s in recorder.spans if s[2] == "AqeReplan"] == []
    assert recorder.misnested == [] and recorder.still_open() == {}


def test_a_repeat_plans_nothing_but_still_has_its_plan_span(
        tmp_path, recorder):
    sess = srt.Session(dict(TRACED))
    q = _filter_sum(sess, _parquet(tmp_path, files=1))
    q.collect()
    first = sess.last_metrics["Session.planTime"]
    q.collect()
    again = sess.last_metrics["Session.planTime"]
    assert [s[3]["query_id"] for s in recorder.spans
            if s[0] == "Query"] == [1, 2]
    assert sum(1 for s in recorder.spans if s[0] == "Plan") == 2
    # the second request finds its plan in the cache
    assert 0 < again < first


def test_counters_sit_at_the_same_boundaries(tmp_path):
    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    _filter_sum(sess, _parquet(tmp_path)).collect()
    m = sess.last_metrics
    assert m["FileScanExec.decodedRows"] == 10000
    assert m["FileScanExec.decodedBatches"] == 2
    # two columns of 8 bytes and their validity
    assert m["FileScanExec.decodedBytes"] >= 10000 * 16
    assert m["HostToDeviceExec.prefetchWaits"] >= 1
    assert m["DeviceToHostExec.copiedBytes"] >= 8
    assert m["Session.planTime"] > 0


def test_with_tracing_off_no_annotation_is_ever_made(tmp_path, recorder):
    tracing.enable(False)
    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    q = _filter_sum(sess, _parquet(tmp_path))
    assert len(q.collect()) == 1
    df = sess.create_dataframe({"k": [1, 2, 1, 2] * 64,
                                "v": list(range(256))}, n_partitions=2)
    assert len(df.group_by("k").agg(F.sum("v").alias("s"))
               .sort("k").collect()) == 2
    assert recorder.made == 0 and recorder.spans == []


def _expand(df):
    return L.DataFrame(df.session, L.Expand(
        df.plan, [[F.col("k").expr, F.col("v").expr],
                  [F.col("k").expr, (F.col("v") * F.lit(2)).expr]],
        ["k", "v"]))


def _windowed(df):
    from spark_rapids_tpu.ops.windowexprs import over, row_number, window

    return df.with_window("w", over(
        row_number(), window().partition_by("k").order_by("v")))


@pytest.mark.parametrize("build,span", [
    (_expand, "TpuExpand"), (_windowed, "TpuCoalesce.concat")],
    ids=["expand", "coalesce_concat"])
def test_a_span_is_closed_before_its_batch_is_handed_on(
        recorder, build, span):
    # both used to ``yield`` inside their range, and so charged their
    # consumer's time (here: the download) to themselves
    sess = srt.Session(dict(TRACED))
    df = sess.create_dataframe({"k": [1, 2, 3, 4] * 64,
                                "v": list(range(256))}, n_partitions=2)
    rows = build(df).collect()
    assert len(rows) in (256, 512)
    assert span in recorder.names()
    assert [s for s in recorder.spans if s[2] == span] == []
    assert recorder.misnested == [] and recorder.still_open() == {}


def test_trace_steps_closes_each_range_at_the_hand_over(recorder):
    tracing.enable(True)
    seen = []

    def steps():
        for i in range(3):
            seen.append(("made", i, list(recorder.still_open().values())))
            yield i

    for i in tracing.trace_steps("Step", steps()):
        seen.append(("got", i, recorder.still_open()))
    # open while the step runs, closed while the consumer has the item
    assert [s[2] for s in seen if s[0] == "made"] == [[["Step"]]] * 3
    assert [s[2] for s in seen if s[0] == "got"] == [{}] * 3
    # one range a step, and one for the step that finds the end
    assert [s[0] for s in recorder.spans] == ["Step"] * 4


def test_an_abandoned_drain_closes_its_source():
    closed = []

    def steps():
        try:
            yield 1
            yield 2
        finally:
            closed.append(True)

    it = tracing.trace_steps("Step", steps())
    assert next(it) == 1
    it.close()
    assert closed == [True]


# ==========================================================================
# device programs named after their operator
# ==========================================================================
def _compute(b):
    return b


def packed_build(b, pids, n_out):
    return b


@pytest.mark.parametrize("key,fn,kind,want", [
    (("filter", ("sig",), ("cond",)), _compute, None, "filter__compute"),
    (("project", ("in",), ("e",), ("out",)), _compute, None,
     "project__compute"),
    (("agg", "partial", ("sig",), "batch"), _compute, None, "agg_batch"),
    (("agg", "final", ("sig",), "merge_final"), lambda b: b, None,
     "agg_merge_final"),
    (("join", "TpuShuffledHashJoinExec", "inner", "count"), _compute,
     None, "join_count"),
    (("shuffle.packedBuild", 4, ("sig",)), packed_build, None,
     "shuffle_packedBuild"),
    (None, _compute, "window", "window__compute"),
    (None, lambda b: b, "shuffle.rangePasses", "shuffle_rangePasses"),
    (None, _compute, None, "_compute"),     # unnamed: JAX's own naming
])
def test_program_name(key, fn, kind, want):
    assert program_name(key, fn, kind) == want


def test_the_jitted_program_carries_the_name_and_the_body_stays_raw():
    import jax.numpy as jnp

    k = jit_kernel(lambda a, n: a[:n] + 1, key=("unit.op", 3, "phase"),
                   static_argnums=(1,))
    assert k.name == "unit_op_phase"
    assert k.fn.__name__ == "<lambda>"    # runner and fusion reuse it
    assert list(k(jnp.arange(4), 2)) == [1, 2]
    assert "@jit_unit_op_phase" in \
        k._jfn.lower(jnp.arange(4), 2).as_text()[:200]


#: the operator kinds the engine's call sites may name a program after
KINDS = ("project_", "filter_", "expand_", "sort_", "window_", "fused_",
         "agg_", "join_", "shuffle_", "generate_", "write_")


def _tpch_program_names():
    """Names of every kernel-cache program that building and running
    TPC-H q1, q3 and q6 (and a window, an expand, a range sort) makes,
    in the order they are made."""
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen

    names = []
    made = _CachedKernel.__init__

    def spy(self, *a, **kw):
        made(self, *a, **kw)
        names.append(self.name)

    _CachedKernel.__init__ = spy
    try:
        sess = srt.Session()
        tables = tpch_datagen.dataframes(sess, sf=0.0005, seed=3)
        for q in (1, 3, 6):
            tpch.QUERIES[q](tables).collect()
        df = sess.create_dataframe({"k": [1, 2, 3, 4] * 64,
                                    "v": list(range(256))},
                                   n_partitions=2)
        _windowed(df).collect()
        _expand(df).collect()
        df.sort("v").collect()
    finally:
        _CachedKernel.__init__ = made
    return names


def test_every_site_names_its_program_after_its_operator():
    names = _tpch_program_names()
    assert len(names) > 20
    assert [n for n in names if not n.startswith(KINDS)] == []
    # the names the old traces could not tell apart are gone
    assert not {"_compute", "_count", "compute_batch", "packed_build",
                "packed_slice", "<lambda>"} & set(names)
    assert {"agg_batch", "join_count", "shuffle_packedBuild",
            "shuffle_packedSlice", "window__compute",
            "expand_compute"} <= set(names)
    assert any(n.startswith("fused_") or n.startswith("filter_")
               for n in names)


def test_two_processes_agree_on_every_program_name():
    # the name is part of the HLO module, so of the persistent compile
    # cache's key: an id, an address or a counter in it would make
    # every process compile everything again
    from conftest import cpu_worker_env

    code = ("import json, sys; sys.path.insert(0, %r); "
            "import test_tracing_spans as t; "
            "print('NAMES' + json.dumps(t._tpch_program_names()))"
            % os.path.dirname(os.path.abspath(__file__)))
    outs = []
    for seed in ("1", "2"):
        env = cpu_worker_env()
        env["PYTHONHASHSEED"] = seed
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append([ln for ln in p.stdout.splitlines()
                     if ln.startswith("NAMES")][-1])
    assert outs[0] == outs[1]
    assert len(outs[0]) > 200
