"""The comparison that decides ``correct``, the traffic generator, and
that every metric BENCHMARK.json names has its file and agrees with it."""
import datetime
import json
import os

import pytest

from benchmark.harness import compare, load_module, loop, probes

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
    BENCH = json.load(f)


@pytest.mark.parametrize("want,got,ordered,same", [
    ([(1, "a", 2.0)], [(1, "a", 2.0 + 1e-9)], True, True),
    ([(1, "a", 2.0)], [(1, "a", 2.0 + 1e-4)], True, False),
    ([(1,)], [(1.0,)], True, True),          # a count against a float sum
    ([(1,)], [(2,)], True, False),
    ([("a",)], [("b",)], True, False),
    ([(1,), (2,)], [(2,), (1,)], True, False),
    ([(1,), (2,)], [(2,), (1,)], False, True),
    ([(1,)], [(1,), (1,)], True, False),
    ([(datetime.date(1995, 1, 18),)], [(9148,)], True, True),
    ([(None,)], [(None,)], True, True),
    ([(None,)], [(0,)], True, False),
])
def test_difference(want, got, ordered, same):
    assert (compare.difference(want, got, ordered, 1e-6) is None) == same


def test_every_seed_offers_the_same_round_in_another_order():
    mix = {"loop": "closed", "clients": 1,
           "queries": ["a", "a", "b", "c", "d", "e"]}
    rounds = [loop.schedule(mix, seed) for seed in (1, 2**31 + 5, 3)]
    assert all(sorted(r) == sorted(mix["queries"]) for r in rounds)
    assert len({tuple(r) for r in rounds}) > 1
    assert loop.schedule(mix, 3) == rounds[2]


@pytest.mark.parametrize("mix", [
    {"loop": "open", "clients": 1, "queries": ["a"]},
    {"loop": "closed", "clients": 4, "queries": ["a"]},
    {"loop": "closed", "clients": 1, "queries": []},
])
def test_a_shape_there_is_no_code_for_is_refused(mix):
    with pytest.raises(ValueError):
        loop.schedule(mix, 1)


def test_window_counts_the_request_in_flight_and_every_fault():
    calls = []

    def run(q):
        calls.append(q)
        if len(calls) == 2:
            raise RuntimeError("boom")
        return [(len(calls),)]

    def check(q, rows):
        return ["wrong"] if rows == [(3,)] else []

    w = loop.run_window(["a", "b"], 0.05, run, check)
    assert w["attempted"] == len(calls) >= 3
    assert w["failed"] == 2 and len(w["samples"]) == w["attempted"] - 2
    assert w["elapsed_s"] >= 0.05
    assert calls[:4] == ["a", "b", "a", "b"][:len(calls[:4])]


def test_host_operators_reads_the_marks():
    text = ("  * TpuSortExec\n  ! FileScanExec parquet\n"
            "  @ TpuHashAggregateExec partly\n  ! CpuProjectExec\n")
    assert probes.host_operators(text, ["FileScanExec"]) == \
        ["CpuProjectExec", "TpuHashAggregateExec"]


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_file_agrees_with_benchmark_json(m):
    mod = load_module("layer_metrics", m["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == \
        (m["unit"], m["layer"], m["moves"])
    assert callable(mod.reduce)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_file_agrees_with_benchmark_json(m):
    mod = load_module("end_to_end", m["name"])
    assert mod.UNIT == m["unit"]
    window = {"samples": [1.0, 2.0, 3.0, 4.0], "elapsed_s": 10.0,
              "setup_s": 5.0}
    assert mod.reduce(window) > 0


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        root = os.path.dirname(BENCH_DIR)
        with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
            config = json.load(f)
        assert config["chips"] == cell["chips"]
        assert os.path.isfile(os.path.join(BENCH_DIR, "entries",
                                           f"{config['entry']}.py"))
        with open(os.path.join(BENCH_DIR, "traffic",
                               f"{cell['traffic']}.json")) as f:
            mix = json.load(f)
        for q in mix["queries"]:
            assert os.path.isfile(os.path.join(BENCH_DIR, "queries",
                                               f"{q}.py"))
