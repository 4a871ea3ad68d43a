"""The readers of the program's own spans and program names, on a
window made by hand: two requests; the client waits for the decode
thread, uploads, runs a filter and the exchange's two programs, and
downloads.  And on traces that lack what they read (a parent commit's),
where each gives nothing and does not raise."""
import os

import pytest

from benchmark.harness import load_module, trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "small_v5e.xplane.pb.gz")

NEW = ["plan_span_ms", "scan_decode_s", "prefetch_wait_idle_s",
       "exchange_device_s", "d2h_copy_s"]


def spanned_trace():
    devices = {0: {
        "ops": [(300, 500, "%fusion.1"), (520, 560, "%gather.2"),
                (560, 580, "%slice.3"),
                (1300, 1500, "%fusion.1"), (1520, 1580, "%gather.2")],
        "modules": [(300, 500, "jit_filter__compute(1)"),
                    (520, 560, "jit_shuffle_packedBuild(2)"),
                    (560, 580, "jit_shuffle_packedSlice(3)"),
                    (1300, 1500, "jit_filter__compute(1)"),
                    (1520, 1580, "jit_shuffle_packedBuild(4)")]}}
    client = [
        (0, 1000, trace.MARKER), (0, 1000, "Query"), (0, 10, "Plan"),
        (10, 600, "TpuShuffleWrite"), (20, 250, "PrefetchWait"),
        (250, 290, "HostToDevice"),
        (600, 900, "DeviceToHost"), (600, 700, "DeviceToHost.wait"),
        (700, 900, "DeviceToHost.copy"),
        (1000, 2000, trace.MARKER), (1000, 2000, "Query"),
        (1000, 1004, "Plan"), (1010, 1600, "TpuShuffleWrite"),
        (1020, 1250, "PrefetchWait"), (1250, 1290, "HostToDevice"),
        (1600, 1900, "DeviceToHost"), (1600, 1650, "DeviceToHost.wait"),
        (1650, 1900, "DeviceToHost.copy")]
    host = {"python": client,
            "h2d-prefetch-0": [(15, 240, "ScanDecode"),
                               (245, 400, "ScanDecode")],
            "h2d-prefetch-1": [(1015, 1240, "ScanDecode")]}
    return trace.Trace(devices, host)


def read(name, t):
    return load_module("layer_metrics", name).reduce(t, {})


def test_each_reader_on_made_up_spans():
    t = spanned_trace()
    assert t.queries == 2
    assert read("plan_span_ms", t) == pytest.approx(1e3 * 14e-9 / 2)
    # both decode threads count, side by side with the client or not
    assert read("scan_decode_s", t) == \
        pytest.approx((225 + 155 + 225) * 1e-9 / 2)
    # the device is idle all through both waits: nothing ran before 300
    assert read("prefetch_wait_idle_s", t) == pytest.approx(460e-9 / 2)
    # the filter's program is not the exchange's
    assert read("exchange_device_s", t) == \
        pytest.approx((40 + 20 + 60) * 1e-9 / 2)
    assert read("d2h_copy_s", t) == pytest.approx(450e-9 / 2)


def test_the_wait_no_longer_reads_as_the_exchange():
    t = spanned_trace()
    idle = t.idle_by_host_span(0)
    # TpuShuffleWrite keeps only what no deeper span owns
    assert idle["TpuShuffleWrite"] == pytest.approx(
        (10 + 10 + 20 + 20 + 10 + 10 + 20 + 20) * 1e-9)
    assert idle["PrefetchWait"] == pytest.approx(460e-9)
    assert read("shuffle_write_idle_s", t) == pytest.approx(120e-9 / 2)


def test_a_busy_device_under_the_wait_reads_zero_not_nothing():
    t = spanned_trace()
    t.devices[0]["ops"].append((0, 2000, "%while.9"))
    assert read("prefetch_wait_idle_s", t) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_the_spans_gives_nothing(name):
    # the recorded v5e trace is of a program with none of these spans
    # and no kernel-cache program: what a parent commit's run looks like
    assert read(name, trace.load(RECORDED)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_a_window_gives_nothing(name):
    assert read(name, trace.Trace({}, {})) is None
