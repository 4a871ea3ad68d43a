"""The reduction from xplane to numbers: on intervals made by hand,
and on a small xplane recorded on a TPU v5e by
``record_small_xplane.py`` (two marked requests; ``scale`` on two
shapes and ``shift`` on one; spans ``outer`` > ``inner`` with 20 ms
asleep inside ``inner`` and 10 ms inside ``outer`` alone)."""
import os

import pytest

from benchmark.harness import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "small_v5e.xplane.pb.gz")


def test_union_merges_nested_and_overlapping_and_clips():
    ivs = [(0, 10, "while"), (2, 5, "fusion"), (8, 14, "x"), (20, 30, "y"),
           (40, 50, "outside")]
    assert trace.union(ivs, 1, 35) == [(1, 14), (20, 30)]


def test_gaps_are_the_complement():
    assert trace.gaps([(1, 14), (20, 30)], 0, 35) == \
        [(0, 1), (14, 20), (30, 35)]
    assert trace.gaps([], 3, 7) == [(3, 7)]


def test_innermost_segments_name_the_deepest_open_span():
    spans = [(0, 100, "query"), (10, 60, "ShuffleWrite"),
             (20, 30, "HostToDevice"), (40, 50, "HostToDevice"),
             (70, 80, "asarray")]
    assert trace.innermost_segments(spans) == [
        (0, 10, "query"), (10, 20, "ShuffleWrite"),
        (20, 30, "HostToDevice"), (30, 40, "ShuffleWrite"),
        (40, 50, "HostToDevice"), (50, 60, "ShuffleWrite"),
        (60, 70, "query"), (70, 80, "asarray"), (80, 100, "query")]


def test_idle_goes_to_the_innermost_span_and_the_rest_to_no_span():
    segments = trace.innermost_segments(
        [(10, 60, "ShuffleWrite"), (20, 30, "HostToDevice")])
    idle = trace.overlap_by_name([(0, 15), (25, 45), (55, 70)], segments)
    assert idle == pytest.approx({
        "(no host span)": (10 + 10) / 1e9,
        "ShuffleWrite": (5 + 15 + 5) / 1e9,
        "HostToDevice": 5 / 1e9})


def test_module_name_drops_the_fingerprint_only():
    assert trace.module_name("jit_compute_batch(10167472635018975354)") \
        == "jit_compute_batch"
    assert trace.module_name("jit__compute") == "jit__compute"
    assert trace.module_name("jit_f(x)(12)") == "jit_f(x)"


def made_trace():
    devices = {0: {"ops": [(100, 200, "%fusion.1"), (150, 180, "%inner"),
                           (300, 400, "%all-to-all.2")],
                   "modules": [(100, 200, "jit_a(1)"), (300, 350, "jit_a(2)"),
                               (350, 400, "jit_b(3)")]}}
    host = {"python": [(50, 450, trace.MARKER), (60, 320, "Write"),
                       (210, 260, "HostToDevice")],
            "worker/7": [(120, 170, "HostToDevice")]}
    return trace.Trace(devices, host)


def test_trace_reduces_a_made_up_window():
    t = made_trace()
    assert (t.queries, t.window, t.client_line) == (1, (50, 450), "python")
    assert t.busy_s(0) == pytest.approx(200e-9)
    assert t.module_seconds(0) == pytest.approx(
        {"jit_a": 150e-9, "jit_b": 50e-9})
    assert t.idle_by_host_span(0) == pytest.approx({
        trace.MARKER: (10 + 50) / 1e9, "Write": (40 + 10 + 40) / 1e9,
        "HostToDevice": 50e-9})
    assert t.span_seconds("HostToDevice") == pytest.approx(100e-9)
    assert t.op_seconds(0, lambda n: n.startswith("%all-to-all")) == \
        pytest.approx(100e-9)


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_recorded_window_is_the_two_marked_requests(recorded):
    assert recorded.queries == 2
    assert list(recorded.devices) == [0]
    assert recorded.has_device
    # 2 x (20 + 10) ms asleep, and little else
    assert 0.060 < recorded.window_s < 0.080


def test_recorded_modules_are_grouped_by_name(recorded):
    by_name = recorded.module_seconds(0)
    assert sorted(by_name) == ["jit_scale", "jit_shift"]
    raw = recorded.devices[0]["modules"]
    prints = {n for _, _, n in raw if n.startswith("jit_scale(")}
    assert len(prints) == 2          # two shapes, two fingerprints
    lo, hi = recorded.window
    assert by_name["jit_scale"] == pytest.approx(sum(
        (e - s) / 1e9 for s, e, n in raw
        if n.startswith("jit_scale(") and s >= lo and e <= hi))


def test_recorded_busy_and_idle_fill_the_window(recorded):
    busy = recorded.busy_s(0)
    idle = recorded.idle_by_host_span(0)
    assert 0 < busy < 0.005
    assert busy + sum(idle.values()) == pytest.approx(recorded.window_s)
    # the operations of a module lie inside the module's interval
    assert busy <= sum(recorded.module_seconds(0).values()) * 1.001


def test_recorded_idle_is_owned_by_the_span_that_slept(recorded):
    idle = recorded.idle_by_host_span(0)
    assert 0.040 <= idle["inner"] < 0.046
    assert 0.020 <= idle["outer"] < 0.030
    assert idle.get(trace.MARKER, 0.0) < 0.002
