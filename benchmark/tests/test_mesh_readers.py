"""The readers the mesh cell and its one-chip control brought, on a
window made by hand: one request over four devices with two stage
programs, the trims between them and an all-to-all; and one request on
one device with the join's own programs.  On a trace that has a device
and nothing of theirs each reads 0.0, never nothing: a cell owes every
metric that lists it, on the parent's side too.  And both new cells,
rehearsed end to end in the sandbox."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import BENCHMARK_DIR, load_module, trace

ROOT = os.path.dirname(BENCHMARK_DIR)
NEW = ["mesh_stage_s", "mesh_trim_s", "mesh_collective_s", "join_device_s"]
NEW_CELLS = {"tpch_sf1_chip1.join_q3": 1,
             "tpch_sf1_chip4.join_q3_shuffled": 4}


def read(name, t):
    return load_module("layer_metrics", name).reduce(t, {})


def mesh_trace():
    ops = [(100, 300, "%fusion.1"), (300, 340, "%all-to-all.2"),
           (340, 350, "%all-reduce.3"), (600, 800, "%fusion.4")]
    modules = [(100, 350, "jit_mesh_stage(1)"),
               (600, 800, "jit_mesh_stage(2)")]
    devices = {d: {"ops": list(ops), "modules": list(modules)}
               for d in range(4)}
    # device 3 waits longer in its all-to-all
    devices[3]["ops"][1] = (280, 340, "%all-to-all.2")
    client = [
        (0, 1000, trace.MARKER), (0, 1000, "Query"),
        (10, 90, "MeshLeaf"), (60, 90, "MeshPlace"),
        (90, 400, "MeshStage"), (400, 450, "MeshTrim"),
        (450, 820, "MeshStage"), (820, 850, "MeshTrim"),
        (850, 990, "MeshCollect")]
    return trace.Trace(devices, {"python": client})


def join_trace():
    devices = {0: {
        "ops": [(100, 900, "%fusion.1")],
        "modules": [(100, 300, "jit_join_count(1)"),
                    (300, 400, "jit_join_count(2)"),
                    (400, 700, "jit_join_expand(3)"),
                    (700, 750, "jit_join_semi(4)"),
                    (750, 900, "jit_agg_batch(5)")]}}
    client = [(0, 1000, trace.MARKER), (0, 1000, "Query")]
    return trace.Trace(devices, {"python": client})


def bare_trace(devices=1):
    """A device that ran something, and none of what these read."""
    planes = {d: {"ops": [(100, 200, "%fusion.1")],
                  "modules": [(100, 200, "jit_filter__compute(1)")]}
              for d in range(devices)}
    client = [(0, 500, trace.MARKER), (0, 500, "Query"),
              (500, 1000, trace.MARKER), (500, 1000, "Query")]
    return trace.Trace(planes, {"python": client})


def test_mesh_spans_are_read_a_request():
    t = mesh_trace()
    assert t.queries == 1 and t.active_devices == [0, 1, 2, 3]
    assert read("mesh_stage_s", t) == pytest.approx((310 + 370) * 1e-9)
    assert read("mesh_trim_s", t) == pytest.approx((50 + 30) * 1e-9)
    # the device that sat longest in a collective: 280..350 on device 3
    assert read("mesh_collective_s", t) == pytest.approx(70e-9)
    # on the mesh the joins run inside the stage program
    assert read("join_device_s", t) == 0.0


def test_a_collective_is_told_by_its_opcode():
    """On the v5e an event's name is its HLO text, and the instruction
    is called after the JAX primitive: ``%all_to_all``, ``%pmax``."""
    t = mesh_trace()
    for d in t.devices.values():
        d["ops"][1] = (d["ops"][1][0], d["ops"][1][1],
                       "%all_to_all.20 = pred[4,4,1024]{2,1,0:T(4,128)} "
                       "all-to-all(pred[4,4,1024]{2,1,0} %reshape.364), "
                       "channel_id=1")
        d["ops"][2] = (340, 350, "%pmax.6 = s32[]{:T(128)} all-reduce("
                       "s32[]{:T(128)} %slice_reduce_fusion.2), "
                       "channel_id=1, to_apply=%max")
        # an operand called after a collective is not one
        d["ops"][3] = (600, 800, "%fusion.4 = f32[8]{0} fusion(f32[8]{0} "
                       "%all-reduce.3), kind=kLoop, calls=%fused")
    assert read("mesh_collective_s", t) == pytest.approx(70e-9)
    # the reader PR 23 left goes by the instruction's name and misses
    # both (on the chip it read the all-reduces alone, 1/78 of these)
    assert read("collective_s", t) is None


def test_join_programs_are_told_from_the_rest():
    t = join_trace()
    assert read("join_device_s", t) == \
        pytest.approx((200 + 100 + 300 + 50) * 1e-9)
    assert read("mesh_stage_s", t) == 0.0
    assert read("mesh_trim_s", t) == 0.0
    assert read("mesh_collective_s", t) == 0.0


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("devices", [1, 4])
def test_a_device_and_nothing_of_theirs_reads_zero(name, devices):
    t = bare_trace(devices)
    assert t.has_device and t.queries == 2
    assert read(name, t) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_no_device_reads_nothing_and_does_not_raise(name):
    # a rehearsal on the CPU: spans, no device plane
    t = trace.Trace({}, {"python": [(0, 10, trace.MARKER),
                                    (2, 8, "MeshStage")]})
    assert not t.has_device
    assert read(name, t) is None


@pytest.mark.parametrize("cell", sorted(NEW_CELLS))
def test_what_a_new_cell_owes(cell):
    """The metrics that apply to a new cell: none of those an accepted
    cell alone can read, and every one of this PR's for the cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert cell in {w["name"] for w in bench["workloads"]}
    owed = {m["name"] for m in bench["per_layer"]
            if "workloads" not in m or cell in m["workloads"]}
    # TpuShuffleWrite is never opened on the mesh (PR 27's refusal);
    # the others list their cells since PR 25
    assert not owed & {"shuffle_write_idle_s", "plan_span_ms",
                       "scan_decode_s", "prefetch_wait_idle_s",
                       "exchange_device_s", "d2h_copy_s"}
    mine = {"tpch_sf1_chip1.join_q3": {"join_device_s"},
            "tpch_sf1_chip4.join_q3_shuffled":
                {"mesh_collective_s", "mesh_stage_s", "mesh_trim_s"}}[cell]
    assert mine <= owed
    assert not owed & ({"join_device_s", "mesh_collective_s",
                        "mesh_stage_s", "mesh_trim_s"} - mine)
    ends = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert ends == {"query_s_p50", "queries_per_hour", "setup_s"}
    # refused before any run otherwise: 200 characters a line of words
    for entry in bench["configs"] + bench["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200, (entry["name"], key)


@pytest.mark.parametrize("cell", sorted(NEW_CELLS))
def test_the_rehearsal_ends_correct(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCHMARK_DIR, "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "3",
         "--trace", "1", "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["device"]["count"] == NEW_CELLS[cell]
    assert last["rehearsal_values"]["compiles_in_window"]["value"] == 0.0
