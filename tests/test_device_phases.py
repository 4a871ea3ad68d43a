"""Names inside the programs, and the reader of them (PR 36).

* every ``utils/tracing.device_phase`` scope of docs/observability.md's
  table stands in the lowered text, with debug info, of the kernel that
  owns it — and with the scopes switched off the lowered text *without*
  debug info is equal byte for byte: a scope changes no program and no
  compile-cache key;
* ``telemetry/device_trace.py`` reads two planes recorded on a TPU v5e:
  the benchmark's ``small_v5e`` (program seconds against the harness's
  own reduction, the plane's peak bandwidth) and ``phases_v5e``
  (``tests/record_phases_xplane.py``: innermost-scope attribution, a
  ``while`` that wraps its body's events, the ``(unscoped)`` rest);
* ``utils/tracing.last_profile_dir()`` and the benchmark's four readers
  over it, which read exactly 0.0 from a program that says nothing.
"""
import contextlib
import os
import re
import sys

import numpy as np
import pytest
from conftest import REPO

import spark_rapids_tpu as srt
from benchmark.harness import load_module
from benchmark.harness import trace as harness_trace
from spark_rapids_tpu import f
from spark_rapids_tpu.exec import kernel_cache
from spark_rapids_tpu.telemetry import device_trace
from spark_rapids_tpu.utils import tracing

SMALL = os.path.join(REPO, "benchmark", "tests", "data",
                     "small_v5e.xplane.pb.gz")
PHASES = os.path.join(REPO, "tests", "data", "phases_v5e.xplane.pb.gz")

#: program -> the scopes its lowered text must name
KERNELS = {
    "agg_batch": {"TpuHashAggregate", "lexsort", "reorder", "segments",
                  "agg.prologue", "TpuFilter"},
    "join_count": {"join.probe", "join.emitCounts", "lexsort", "reorder",
                   "segments"},
    "join_expand": {"join.expandSearch", "join.expandGather", "reorder"},
    "join_semi": {"join.probe", "gather.partitionOrder", "reorder"},
    "join_semiPairs": {"join.pairRows", "join.condition",
                       "gather.partitionOrder", "reorder"},
    "join_semiBounds": {"join.probe", "join.condition", "lexsort",
                        "reorder", "gather.partitionOrder"},
    "shuffle__hash_pids": {"shuffle.hashPids"},
    "shuffle_packedBuild": {"shuffle.packedBuild", "reorder"},
    "shuffle_packedSlice": {"shuffle.packedSlice", "reorder"},
    "shuffle_trim": {"shuffle.trim"},
    "fused__compute": {"TpuFilter", "TpuProject", "TpuFusedSegment",
                       "gather.partitionOrder", "reorder", "strings.match"},
    "mesh_stage": {"TpuShuffleWrite", "shuffle.hashPids",
                   "shuffle.packedBuild", "TpuShuffledHashJoinExec",
                   "join.probe", "join.expandSearch", "TpuHashAggregate",
                   "lexsort", "segments", "reorder",
                   "gather.partitionOrder"},
}


@pytest.fixture(scope="module")
def dispatched():
    """{program: [(kernel, args)]}: the first dispatch of every shape of
    the kernels a few toy queries run, one chip and a four-device mesh."""
    seen = {}
    call = kernel_cache._CachedKernel.__call__

    def recording(self, *args, metrics=None):
        shapes = {id(k._jfn) for k, _ in seen.get(self.name, ())}
        if id(self._jfn) not in shapes:
            seen.setdefault(self.name, []).append((self, args))
        return call(self, *args, metrics=metrics)

    kernel_cache._CachedKernel.__call__ = recording
    try:
        sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
        rng = np.random.default_rng(0)
        n = 3000
        fact = sess.create_dataframe({
            "k": rng.integers(0, 50, n).tolist(),
            "g": rng.integers(0, 5, n).tolist(),
            "v": rng.random(n).tolist()})
        dim = sess.create_dataframe({
            "k": list(range(40)), "w": [float(i) for i in range(40)]})
        agg = fact.filter(fact["v"] > 0.1).group_by("g").agg(
            f.sum("v").alias("s"))
        assert len(agg.collect()) == 5
        joined = fact.join(dim, on="k").group_by("g").agg(
            f.sum("w").alias("s"))
        assert len(joined.collect()) == 5
        assert fact.join(dim, on="k", how="left_semi").collect()
        assert fact.join(dim.select(f.col("k").alias("k2"), "w"),
                         on=(["k"], ["k2"]), how="left_anti",
                         condition=f.col("v") * 40 > f.col("w")).collect()
        assert fact.join(dim.select(f.col("k").alias("k2"),
                                    f.col("k").alias("j")),
                         on=(["k"], ["k2"]), how="left_semi",
                         condition=f.col("g") < f.col("j")).collect()
        fused = fact.filter(fact["v"] > 0.1).select(
            (fact["v"] * 2).alias("x"), fact["k"]).filter(f.col("x") < 1.5)
        assert fused.collect()
        notes = sess.create_dataframe({
            "k": list(range(40)),
            "s": [f"{'special' if i % 3 else 'plain'} requests {i}"
                  for i in range(40)]})
        liked = notes.filter(~notes["s"].like("%special%requests%")).select(
            (notes["k"] * 2).alias("x")).filter(f.col("x") < 60)
        assert len(liked.collect()) == 10
        from spark_rapids_tpu.parallel.runner import run_distributed

        mesh = srt.Session({
            "spark.rapids.tpu.sql.test.enabled": True,
            "spark.rapids.tpu.sql.broadcastSizeThreshold": 0})
        mfact = mesh.create_dataframe({
            "k": rng.integers(0, 50, n).tolist(),
            "g": rng.integers(0, 5, n).tolist()})
        mdim = mesh.create_dataframe({
            "k": list(range(40)), "w": [float(i) for i in range(40)]})
        out = run_distributed(mesh, mfact.join(mdim, on="k").group_by(
            "g").agg(f.sum("w").alias("s")), n_devices=4)
        assert out.num_rows == 5
    finally:
        kernel_cache._CachedKernel.__call__ = call
    return seen


def _fresh(kernel):
    """A new jit of the kernel's body under the program's name: a new
    function object, so that nothing JAX traced before is found again."""
    import jax

    def program(*args):
        return kernel.fn(*args)

    program.__name__ = program.__qualname__ = kernel.name
    return jax.jit(program, static_argnums=kernel.static_argnums)


def _scopes(text):
    """The scopes of the ops' name stacks (``jit(f)/lexsort/sort``; a
    ``shard_map`` body's are relative: ``TpuSort/lexsort/sort``)."""
    return {part for path in re.findall(r'loc\("([^"]+)"\(', text)
            for part in path.split("/")[:-1]}


@pytest.mark.parametrize("program", sorted(KERNELS))
def test_kernel_names_its_phases_in_the_lowered_text(dispatched, program):
    assert program in dispatched, sorted(dispatched)
    named = set()
    for kernel, args in dispatched[program]:
        named |= _scopes(_fresh(kernel).lower(*args).as_text(
            debug_info=True))
    assert KERNELS[program] <= named, sorted(KERNELS[program] - named)
    # and nothing that looks like a phase or an operator is off the table
    for scope in named:
        if "." in scope or scope.startswith("Tpu"):
            assert scope in tracing.DEVICE_PHASES or \
                scope.startswith(tracing.READ_TAGS) or \
                device_trace.operator_of(f"jit(x)/{scope}/op:") == scope


def test_every_phase_of_the_table_is_named_by_some_kernel():
    owned = set().union(*KERNELS.values())
    assert set(tracing.DEVICE_PHASES) <= owned


@pytest.mark.parametrize("program", sorted(KERNELS))
def test_phases_change_no_program(dispatched, program, monkeypatch):
    import jax

    kernel, args = dispatched[program][0]
    with_scopes = _fresh(kernel).lower(*args).as_text()
    assert "loc(" not in with_scopes      # no debug info: what is keyed
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    debug = _fresh(kernel).lower(*args).as_text(debug_info=True)
    assert not _scopes(debug) & (set(tracing.DEVICE_PHASES)
                                 | {"TpuHashAggregate", "TpuFilter"})
    assert _fresh(kernel).lower(*args).as_text() == with_scopes


# ==========================================================================
# the reader
# ==========================================================================
def test_reader_agrees_with_the_harness_on_a_recorded_plane():
    mine = device_trace.load(SMALL)
    theirs = harness_trace.load(SMALL)
    assert sorted(mine.devices) == theirs.active_devices == [0]
    assert mine.devices[0].peak_hbm_gbps == pytest.approx(819.16, abs=0.01)
    assert len(mine.markers) == theirs.queries == 2
    lo, hi = theirs.window
    assert mine.window == (pytest.approx(lo * 1000, abs=1000),
                           pytest.approx(hi * 1000, abs=1000))
    want = theirs.module_seconds(0)
    got = device_trace.module_seconds(mine, 0, mine.window)
    assert sorted(got) == sorted(want) == ["jit_scale", "jit_shift"]
    for program, seconds in want.items():
        assert got[program] == pytest.approx(seconds, rel=0.01)
    # the ops' metadata: JAX's path, the source line, the bytes
    rows = device_trace.reduce(mine, 0, by="primitive", window=mine.window,
                               queries=2)
    assert set(rows) == {"jit_scale", "jit_shift"}
    # (the device's clock runs a millisecond ahead of the host's here:
    # the first request's two ``scale`` events end before its marker
    # starts, so the window holds one of each shape)
    scale = rows["jit_scale"]["reduce_sum"]
    assert scale.ops == 1 and \
        scale.bytes_accessed == (4194308 + 8388612) / 2
    assert scale.seconds == pytest.approx(want["jit_scale"] / 2, rel=0.01)
    assert 0 < scale.gbps < 819.16 and \
        scale.peak_share == pytest.approx(100 * scale.gbps / 819.1576, 1e-4)
    by_source = device_trace.reduce(mine, 0, by="source")
    assert [k.rsplit("/", 1)[-1] for k in by_source["jit_shift"]] == \
        ["record_small_xplane.py:27"]
    assert set(device_trace.reduce(mine, 0)["jit_scale"]) == \
        {device_trace.UNSCOPED}


def test_paths_split_into_phase_operator_and_primitive():
    path = "jit(mesh_stage)/TpuShuffledHashJoinExec/join.probe/lexsort/" \
           "while/body/closed_call/gather:"
    assert device_trace.phase_of(path) == "lexsort"
    assert device_trace.operator_of(path) == "TpuShuffledHashJoinExec"
    assert device_trace.primitive_of(path) == "gather"
    nested = "jit(agg_batch)/agg.prologue/TpuFilter/TpuProject/mul:"
    assert device_trace.phase_of(nested) == "agg.prologue"
    assert device_trace.operator_of(nested) == "TpuFilter"
    bare = "jit(scale)/reduce_sum:"
    assert device_trace.phase_of(bare) == device_trace.UNSCOPED
    assert device_trace.operator_of(bare) == device_trace.UNSCOPED
    # the compiler's own ops carry no path at all: no scope reaches them
    assert device_trace.phase_of("") == device_trace.operator_of("") == \
        device_trace.primitive_of("") == device_trace.NO_PATH
    with pytest.raises(ValueError):
        device_trace.reduce(device_trace.load(SMALL), 0, by="line")


def test_a_wrapper_adds_only_the_time_none_of_its_events_ran():
    def op(start, end, path):
        return device_trace.Op(start, end, "jit_p", path, "", 100, "")

    trace = device_trace.DeviceTrace({0: device_trace.Device(100.0, [
        op(0, 100, "jit(p)/lexsort/while:"),
        op(10, 40, "jit(p)/lexsort/while/body/reorder/gather:"),
        op(50, 90, "jit(p)/lexsort/while/body/sort:"),
        op(100, 130, "jit(p)/reduce_sum:"),
        op(130, 150, ""),       # a loop the compiler left without a path
        op(135, 145, "jit(p)/segments/while/body/closed_call/add:"),
        op(150, 160, ""),       # the compiler's own: an output fusion
    ], [(0, 160, "jit_p")])}, [(0, 80), (80, 160)])
    rows = device_trace.reduce(trace, 0)["jit_p"]
    ps = 1e-12
    assert rows["reorder"].seconds == pytest.approx(30 * ps)
    assert rows["lexsort"].seconds == pytest.approx((40 + 30) * ps)
    assert rows["lexsort"].ops == 1         # the while is no leaf
    assert rows["lexsort"].bytes_accessed == 100
    assert rows[device_trace.UNSCOPED].seconds == pytest.approx(30 * ps)
    assert rows["segments"].seconds == pytest.approx(20 * ps)
    assert rows[device_trace.NO_PATH].seconds == pytest.approx(10 * ps)
    assert sum(r.seconds for r in rows.values()) == pytest.approx(160 * ps)
    # clipped to the second request's half, a request's share of it
    half = device_trace.reduce(trace, 0, window=(80, 160), queries=1)
    assert half["jit_p"]["lexsort"].seconds == pytest.approx(
        (10 + 10) * ps)
    assert "reorder" not in half["jit_p"]
    assert device_trace.seconds_where(
        trace, 0, "primitive", "gather", trace.window, 2) == \
        pytest.approx(15 * ps)
    text = "\n".join(device_trace.render(trace))
    assert "-- Device phases (by phase; device 0" in text
    assert "2 request(s)" in text and "jit_p:" in text


def test_read_tiers_within_segments_by_seconds_and_events():
    """``--by tier``: which width ``reduce_sorted``'s switch took, and
    what it cost.  Two aggregates of a request: the first reads 2^16
    rows (two gathers and a pad), the second all 2^22; the scans before
    the switch, the ``conditional``'s own time and a join's ``segments``
    are the phase's untiered rest, a ``reorder`` is outside the phase."""
    def op(start, end, path):
        return device_trace.Op(start, end, "jit_agg_batch", path, "", 8, "")

    base = "jit(agg_batch)/TpuHashAggregate/segments"
    small, full = (f"{base}/cond/branch_{i}_fun/readTier.{m}"
                   for i, m in ((0, 1 << 16), (3, 1 << 22)))
    trace = device_trace.DeviceTrace({0: device_trace.Device(100.0, [
        op(0, 50, f"{base}/while/body/closed_call/add:"),
        op(50, 60, "jit(agg_batch)/TpuHashAggregate/reorder/gather:"),
        op(60, 100, f"{base}/cond:"),
        op(62, 70, f"{small}/gather:"),
        op(70, 76, f"{small}/gather:"),
        op(76, 98, f"{small}/jit(_pad)/pad:"),
        op(100, 400, f"{base}/cond:"),
        op(100, 390, f"{full}/gather:"),
        op(400, 420, "jit(agg_batch)/join.probe/segments/cumsum:"),
    ], [(0, 420, "jit_agg_batch")])}, [(0, 420)])
    ps = 1e-12
    assert device_trace.tier_of(f"{small}/gather:") == "readTier.65536"
    assert device_trace.tier_of(f"{base}/cond:") == device_trace.UNSCOPED
    assert device_trace.phase_of(f"{small}/jit(_pad)/pad:") == "segments"
    rows = device_trace.reduce(trace, 0, by="tier", within="segments",
                               window=trace.window)["jit_agg_batch"]
    assert set(rows) == {"readTier.65536", "readTier.4194304",
                         device_trace.UNSCOPED}
    assert rows["readTier.65536"].seconds == pytest.approx(36 * ps)
    assert rows["readTier.65536"].ops == 3
    assert rows["readTier.4194304"].seconds == pytest.approx(290 * ps)
    assert rows["readTier.4194304"].ops == 1
    # the scan, the two conditionals' own time (4 + 10), the join's
    assert rows[device_trace.UNSCOPED].seconds == pytest.approx(
        (50 + 14 + 20) * ps)
    # the tiers take nothing out of the phase the metrics read
    by_phase = device_trace.reduce(trace, 0, window=trace.window)[
        "jit_agg_batch"]
    assert by_phase["segments"].seconds == pytest.approx(
        sum(r.seconds for r in rows.values()))
    assert by_phase["reorder"].seconds == pytest.approx(10 * ps)
    text = "\n".join(device_trace.render(trace, by="tier",
                                         within="segments"))
    assert "by tier within segments" in text and "readTier.65536" in text


def test_stacked_and_own_reads_within_reorder_by_seconds_and_events():
    """``--by tier --within reorder``: ``take_rows``' stacked gathers
    (``readWords.<k>``) and the parts read alone (``readOwn``: a float64,
    a string's bytes), each under its caller's phase; the stack's fill
    and the rest of the phase stand outside both."""
    def op(start, end, path):
        return device_trace.Op(start, end, "jit_fused", path, "", 8, "")

    base = "jit(fused)/TpuFilter/reorder"
    trace = device_trace.DeviceTrace({0: device_trace.Device(100.0, [
        op(0, 10, f"{base}/concatenate:"),
        op(10, 70, f"{base}/readWords.6/gather:"),
        op(70, 90, f"{base}/readOwn/gather:"),
        op(90, 100, f"{base}/readOwn/gather:"),
        op(100, 130, "jit(fused)/TpuFilter/join.condition/readWords.3/"
                     "gather:"),
        op(130, 140, "jit(fused)/TpuFilter/gather.partitionOrder/scatter:"),
    ], [(0, 140, "jit_fused")])}, [(0, 140)])
    ps = 1e-12
    assert device_trace.tier_of(f"{base}/readWords.6/gather:") == \
        "readWords.6"
    assert device_trace.tier_of(f"{base}/readOwn/gather:") == "readOwn"
    assert device_trace.tier_of(f"{base}/concatenate:") == \
        device_trace.UNSCOPED
    # the tags are no phases: the read stays its caller's
    assert device_trace.phase_of(f"{base}/readWords.6/gather:") == "reorder"
    rows = device_trace.reduce(trace, 0, by="tier", within="reorder",
                               window=trace.window)["jit_fused"]
    assert set(rows) == {"readWords.6", "readOwn", device_trace.UNSCOPED}
    assert rows["readWords.6"].seconds == pytest.approx(60 * ps)
    assert rows["readWords.6"].ops == 1
    assert rows["readOwn"].seconds == pytest.approx(30 * ps)
    assert rows["readOwn"].ops == 2
    assert rows[device_trace.UNSCOPED].seconds == pytest.approx(10 * ps)
    by_phase = device_trace.reduce(trace, 0, window=trace.window)["jit_fused"]
    assert by_phase["reorder"].seconds == pytest.approx(
        sum(r.seconds for r in rows.values()))
    assert by_phase["join.condition"].seconds == pytest.approx(30 * ps)
    text = "\n".join(device_trace.render(trace, by="tier", within="reorder"))
    assert "by tier within reorder" in text and "readWords.6" in text \
        and "readOwn" in text


def test_recorded_phases_innermost_scope_while_and_the_unscoped_rest():
    trace = device_trace.load(PHASES)
    theirs = harness_trace.load(PHASES)
    assert len(trace.markers) == 2 and sorted(trace.devices) == [0]
    rows = device_trace.reduce(trace, 0, window=trace.window,
                               queries=2)["jit_phased"]
    assert set(rows) == {"lexsort", "reorder", device_trace.UNSCOPED,
                         device_trace.NO_PATH}
    assert all(r.seconds > 0 for r in rows.values())
    assert rows[device_trace.UNSCOPED].ops == 1.5     # the sums
    # the loop is one ``while`` event around its body's events on the
    # device's line; it is no leaf, and adds only its own time
    dev = trace.devices[0]
    loops = [op for op in dev.ops if op.category == "while"]
    assert len(loops) == 2
    inside = [op for op in dev.ops if op is not loops[0]
              and loops[0].start <= op.start and op.end <= loops[0].end]
    assert {device_trace.phase_of(op.tf_op) for op in inside} == \
        {"lexsort", "reorder", device_trace.NO_PATH}
    # the compiler left the loop itself without a path: it stands where
    # its body does, as a ``while`` of the phase ``lexsort``
    assert not loops[0].tf_op
    by_primitive = device_trace.reduce(trace, 0, by="primitive",
                                       window=trace.window, queries=2)
    loop_own = by_primitive["jit_phased"]["while"]
    assert loop_own.ops == 0
    assert loop_own.seconds < 0.2 * (loops[0].end - loops[0].start) / 1e12
    # the gathers inside the loop stand under lexsort/.../reorder: the
    # innermost scope names the phase, so reorder holds them and the
    # one after the loop; by primitive they are all gathers
    gathers = by_primitive["jit_phased"]["gather"]
    assert gathers.seconds == pytest.approx(rows["reorder"].seconds,
                                            rel=0.05)
    # nothing counts twice and nothing is lost: the keys sum to the
    # device's busy time and to the program's module seconds
    total = sum(r.seconds for r in rows.values())
    assert total == pytest.approx(theirs.busy_s(0) / 2, rel=0.01)
    assert total == pytest.approx(theirs.module_seconds(0)["jit_phased"]
                                  / 2, rel=0.02)
    table = "\n".join(device_trace.render(trace))
    assert "jit_phased" in table and "lexsort" in table


def test_the_cli_prints_a_table_a_program(capsys):
    assert device_trace.main([SMALL, "--by", "primitive", "--program",
                              "jit_scale"]) == 0
    out = capsys.readouterr().out
    assert "peak 819.16 GB/s" in out and "2 request(s)" in out
    assert "jit_scale" in out and "jit_shift" not in out
    assert "reduce_sum" in out
    assert device_trace.main([SMALL, "--whole", "--by", "source"]) == 0
    assert "record_small_xplane.py:22" in capsys.readouterr().out


def test_profile_report_renders_the_device_phases_section():
    sess = srt.Session({"spark.rapids.tpu.telemetry.enabled": True})
    sess.create_dataframe({"v": [1.0, 2.0]}).collect()
    assert "-- Device phases" not in sess.profile_report()
    report = sess.profile_report(device_trace=SMALL)
    assert "-- Device phases (by phase; device 0, peak 819.16 GB/s" in report
    assert "jit_scale" in report


# ==========================================================================
# where the trace is, and the benchmark's readers over it
# ==========================================================================
def test_jax_still_keeps_the_profilers_directory_where_we_read_it():
    import jax._src.profiler as jax_profiler

    state = jax_profiler._profile_state
    assert hasattr(state, "log_dir"), \
        "jax moved _profile_state.log_dir: tracing.note_profile_dir " \
        "reads it"


def test_last_profile_dir(tmp_path):
    import jax

    was = tracing._ENABLED
    try:
        tracing.enable(False)
        with tracing.trace_range("Query"):
            pass
        assert tracing.last_profile_dir() is None
        tracing.enable(True)
        with tracing.trace_range("Query"):    # no profiler session open
            pass
        assert tracing.last_profile_dir() is None
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.trace_range("Plan"):   # not the request's span
                pass
            assert tracing.last_profile_dir() is None
            with tracing.trace_range("Query"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert tracing.last_profile_dir() == str(tmp_path)
        with tracing.trace_range("Query"):      # a later query, outside
            pass                                # any session: still noted
        assert tracing.last_profile_dir() == str(tmp_path)
        tracing.enable(False)                   # off: it says nothing
        assert tracing.last_profile_dir() is None
    finally:
        tracing.enable(was)
        tracing._profile_dir = None


READERS = {"gather_device_s": ("primitive", "gather"),
           "lexsort_device_s": ("phase", "lexsort"),
           "reorder_device_s": ("phase", "reorder"),
           "expand_search_device_s": ("phase", "join.expandSearch"),
           "segments_device_s": ("phase", "segments")}


@pytest.fixture()
def traced_dir(tmp_path, monkeypatch):
    """The recorded plane where the harness looks for a run's xplane,
    and a program that says so."""
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    os.symlink(PHASES, run / "host.xplane.pb.gz")
    monkeypatch.setattr(tracing, "_ENABLED", True)
    monkeypatch.setattr(tracing, "_profile_dir", str(tmp_path))
    return harness_trace.load(PHASES)


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("why", ["nothing noted", "no device_trace",
                                 "no xplane there"])
def test_reader_is_exactly_zero_where_the_program_says_nothing(
        name, why, traced_dir, tmp_path, monkeypatch):
    if why == "nothing noted":
        monkeypatch.setattr(tracing, "_profile_dir", None)
    elif why == "no device_trace":      # as on a tree from before PR 36
        import spark_rapids_tpu.telemetry as package

        monkeypatch.delattr(package, "device_trace")
        monkeypatch.setitem(
            sys.modules, "spark_rapids_tpu.telemetry.device_trace", None)
    else:
        monkeypatch.setattr(tracing, "_profile_dir",
                            str(tmp_path / "elsewhere"))
    value = load_module("layer_metrics", name).reduce(traced_dir, {})
    assert value == 0.0 and isinstance(value, float)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_the_programs_phases(name, traced_dir):
    by, key = READERS[name]
    value = load_module("layer_metrics", name).reduce(traced_dir, {})
    trace = device_trace.load(PHASES)
    want = device_trace.seconds_where(trace, 0, by, key, trace.window, 2)
    assert value == pytest.approx(want, rel=1e-6)
    # the recorded program sorts and reorders; it searches and segments
    # nothing
    assert (value > 0) == (name in ("gather_device_s", "lexsort_device_s",
                                    "reorder_device_s"))


def test_the_phase_metrics_are_listed_for_every_accepted_cell():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("gather_device_s")
    assert names[at:at + 5] == [
        "gather_device_s", "lexsort_device_s", "reorder_device_s",
        "expand_search_device_s", "segments_device_s"]
    for name in READERS:
        assert listed[name] == {
            "name": name, "unit": "s/query", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "query_s_p50", "workloads": cells}
