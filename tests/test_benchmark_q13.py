"""TPC-H Q13 as the benchmark's cell
``tpch_sf10_outer_chip1.comments_q13`` runs it, small and on the CPU:
the query file's pandas reference against both engines through the
cell's entry point; the plan a TPU makes of it (the LIKE, the left outer
join, both aggregates and the sort on the device, only the scan on the
host); the outer join's counters; ``min_bytes``; the repo's own
``tpch.q13`` against the reference where the two words stand the other
way round; that ``BENCHMARK.json`` finds the cell's files; the two
readers this cell brings; and a rehearsal of the cell through
``benchmark/run.py``."""
import json
import os
import subprocess
import sys

import pandas as pd
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from benchmark.harness import compare, datagen, load_module, probes, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch_sf10_outer_chip1.comments_q13"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "tpch_sf10_outer_chip1.json")) as f:
    CONFIG = json.load(f)
#: SF 10 over 2,000: 750 customers, 7,500 orders
ROWS = {t: max(4, n // 2000) for t, n in CONFIG["rows"].items()}
SEED = 2**31 + 40
Q13 = load_module("queries", "q13")
ENTRY = load_module("entries", CONFIG["entry"])
READERS = ["string_match_device_s", "string_match_roofline_share"]


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("q13") / "tables")
    datagen.write_tables(path, sorted(Q13.TABLES), ROWS, SEED,
                         dict(CONFIG["parquet"], rows_per_row_group=2048))
    return path


@pytest.fixture(scope="module")
def frames(tables_dir):
    return {t: pq.read_table(os.path.join(tables_dir, t), columns=cols)
            .to_pandas(date_as_object=False)
            for t, cols in Q13.TABLES.items()}


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _without_a_kept_order(frames):
    """Customers none of whose orders survives the NOT LIKE."""
    o = frames["orders"]
    kept = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    c = frames["customer"]
    return int((~c.c_custkey.isin(kept.o_custkey)).sum())


# -- the query and its reference --------------------------------------
@pytest.mark.parametrize("engine", ["host", "device"])
def test_reference_equals_the_engine_through_the_entry(engine, frames,
                                                       tables_dir):
    want = Q13.reference(frames)
    assert len(want) > 5
    assert all(type(a) is int and type(b) is int for a, b in want)
    assert want == sorted(want, key=lambda r: (-r[1], -r[0]))
    assert sum(n for _, n in want) == ROWS["customer"]
    # the customers without an order are there, counted under c_count 0
    assert dict(want)[0] == _without_a_kept_order(frames) > 0
    sess = srt.Session(tpu_enabled=False) if engine == "host" \
        else srt.Session(dict(CONFIG["conf"]))
    df = Q13.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q13.TABLES})
    got = ENTRY.run(sess, df, CONFIG)
    assert compare.difference(
        want, got, Q13.ORDERED,
        CONFIG["guarantees"]["f64_relative_tolerance"]) is None
    if engine == "device":
        m = sess.last_metrics
        assert ENTRY.faults(m, CONFIG) == []
        # one left join program a stream batch (a Parquet file a
        # partition), and every customer without a kept order emitted
        # once with a null right side
        assert m["join.outerJoins"] >= 1
        assert m["join.unmatchedLeftRows"] == _without_a_kept_order(frames)
        assert m["FileScanExec.decodedRows"] == \
            ROWS["customer"] + ROWS["orders"]


def test_the_tpu_planner_leaves_only_the_scan_on_the_host(tables_dir,
                                                           monkeypatch):
    """Planned as a TPU plans it, the LIKE's filter, the left outer join,
    both aggregates, their exchanges and the sort stay on the device,
    the scans alone on the host; strict mode plans it without a raise."""
    import jax

    from spark_rapids_tpu.exec.joins import TpuHashJoinExec

    sess = srt.Session(dict(CONFIG["conf"]))
    df = Q13.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q13.TABLES})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = df.explain()
    assert probes.host_operators(
        text, CONFIG["guarantees"]["host_operators"]) == []
    ops = [ln.strip() for ln in text.splitlines() if ln.strip()]
    assert [ln.split()[1] for ln in ops if ln.startswith("!")] == \
        ["FileScanExec", "FileScanExec"]
    for name in ("FilterExec", "HashJoinExec", "HashAggregateExec",
                 "ShuffleExchangeExec", "SortExec"):
        assert any(ln.startswith(f"* {name}") for ln in ops), name
    joins = [n for n in _walk(sess.physical_plan(df.plan))
             if isinstance(n, TpuHashJoinExec)]
    assert [j.how for j in joins] == ["left"]
    assert joins[0].condition is None


def test_min_bytes_counts_each_input_column_once():
    rows = CONFIG["rows"]
    assert Q13.COMMENT_MEAN_BYTES == pytest.approx(31.2)
    assert Q13.min_bytes(rows) == int(
        rows["orders"] * (16 + 31.2) + rows["customer"] * 8)
    assert Q13.min_bytes(rows) == pytest.approx(720e6, rel=0.001)


def test_comments_match_the_generator_as_assumed(frames):
    """``assumed`` in the configuration: 19-39 characters, up to 63 with
    the appended ``special handle requests``."""
    lengths = frames["orders"].o_comment.str.len()
    assert lengths.min() >= 19 and lengths.max() <= 63
    assert lengths.mean() == pytest.approx(Q13.COMMENT_MEAN_BYTES, abs=1.0)


# -- the repo's own tpch.q13 ------------------------------------------
def test_tpch_q13_keeps_requests_before_special():
    """``o_comment not like '%special%requests%'``: a comment with
    ``requests`` before ``special`` is kept, one with ``special`` before
    ``requests`` (adjacent or not) is not; both engines agree with the
    benchmark's reference."""
    from spark_rapids_tpu.benchmarks import tpch

    comments = ["requests then special", "special requests",
                "specialrequests", "a special, b requests c", "plain",
                "requests special requests", "special", None]
    orders = {"o_orderkey": [10 * (i + 1) for i in range(len(comments))],
              "o_custkey": [1, 1, 2, 2, 3, 3, 4, 4],
              "o_comment": comments}
    customer = {"c_custkey": [1, 2, 3, 4, 5]}
    want = Q13.reference({"orders": pd.DataFrame(orders).dropna(),
                          "customer": pd.DataFrame(customer)})
    # kept: customer 1's first, 3's "plain", 4's "special"; the null
    # comment is neither like nor not like, so its order is not kept
    assert want == [(1, 3), (0, 2)]
    for tpu in (False, True):
        sess = srt.Session(tpu_enabled=tpu)
        t = {"orders": sess.create_dataframe(orders),
             "customer": sess.create_dataframe(customer)}
        got = tpch.QUERIES[13](t).collect()
        assert [tuple(r) for r in got] == want


# -- BENCHMARK.json finds the cell's files ----------------------------
def test_benchmark_json_finds_the_cells_files():
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    config, = [c for c in BENCH["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == CONFIG["chips"] == 1
    assert config["file"] == "benchmark/configs/tpch_sf10_outer_chip1.json"
    assert config["source"] == CONFIG["source"]
    assert config["reduced"] == CONFIG["reduced"] == []
    assert CONFIG["scale_factor"] == 10.0 and CONFIG["entry"] == \
        "execute_new_plan"
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf10_chip1.json")) as f:
        sf10 = json.load(f)
    assert CONFIG["rows"] == sf10["rows"] and CONFIG["conf"] == sf10["conf"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    assert traffic["queries"] == ["q13"]
    assert (traffic["loop"], traffic["clients"]) == ("closed", 1)
    for name in READERS:
        m, = [m for m in BENCH["per_layer"] if m["name"] == name]
        reader = load_module("layer_metrics", name)
        assert (m["unit"], m["layer"], m["moves"]) == \
            (reader.UNIT, reader.LAYER, reader.MOVES)
        assert m["workloads"] == [CELL]
    owed = [m["name"] for m in BENCH["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    assert set(READERS) < set(owed) and "hbm_roofline_share" in owed


# -- the readers of what the program records --------------------------
def q13_trace():
    devices = {0: {
        "ops": [(100, 480, "%fusion.1")],
        "modules": [(100, 480, "jit_fused__compute(1)")]}}
    client = [(0, 1000, trace.MARKER), (0, 1000, "Query")]
    return trace.Trace(devices, {"python": client})


def _phases(monkeypatch, rows):
    """A program's phases as ``device_trace`` would read them from an
    xplane: ``rows`` is {phase: (seconds, bytes)} of one program."""
    from spark_rapids_tpu.telemetry import device_trace
    from spark_rapids_tpu.utils import tracing

    phases = load_module("layer_metrics", READERS[0]).phases
    table = {"jit_fused__compute": {
        k: device_trace.Row(s, 1.0, b, b / s / 1e9, 0.0)
        for k, (s, b) in rows.items()}}
    monkeypatch.setattr(tracing, "last_profile_dir", lambda: "/x")
    monkeypatch.setattr(phases.harness_trace, "find_xplane",
                        lambda d: d + "/x.xplane.pb")
    monkeypatch.setattr(phases, "_loaded", lambda p: type(
        "Loaded", (), {"devices": {0: None}})())
    monkeypatch.setattr(device_trace, "reduce", lambda *a, **k: table)
    monkeypatch.setattr(device_trace, "seconds_by_key", lambda *a, **k: {
        key: row.seconds for key, row in table["jit_fused__compute"].items()})
    phases._reduced.cache_clear()


def test_readers_on_a_made_up_reduction(monkeypatch):
    _phases(monkeypatch, {"strings.match": (0.2, 0.2 * 204.75e9),
                          "reorder": (0.5, 1e9)})
    notes = {"peaks_file": os.path.join(ROOT, "benchmark", "harness",
                                        "peaks.json"),
             "device_kind": "TPU v5 lite"}
    seconds = load_module("layer_metrics", READERS[0])
    share = load_module("layer_metrics", READERS[1])
    assert seconds.reduce(q13_trace(), notes) == pytest.approx(0.2)
    assert share.reduce(q13_trace(), notes) == pytest.approx(25.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_zero_where_there_is_nothing_to_read(name):
    """The parent's program names no ``strings.match``; a trace without
    a device or a request has nothing: 0.0, never None, no raise."""
    reader = load_module("layer_metrics", name)
    bare = trace.Trace(
        {0: {"ops": [(10, 20, "%fusion.1")],
             "modules": [(10, 20, "jit_filter__compute(1)")]}},
        {"python": [(0, 100, trace.MARKER), (5, 9, "HostToDevice")]})
    no_device = trace.Trace({}, {"python": [(0, 100, trace.MARKER)]})
    no_request = trace.Trace({}, {"python": []})
    for t in (bare, no_device, no_request):
        value = reader.reduce(t, {})
        assert value == 0.0 and isinstance(value, float)


# -- the cell through the harness -------------------------------------
def test_rehearsal_of_the_cell_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # one CPU device: the cell has one chip
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
         "--trace", "1", "--rehearsal", "500"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["rehearsal"] is True
    assert last["metrics"] == {}
    values = last["rehearsal_values"]
    assert values["compiles_in_window"]["value"] == 0.0
    # the CPU trace has no device plane: the device readers give 0.0
    assert values["string_match_device_s"]["value"] == 0.0
    assert values["string_match_roofline_share"]["value"] == 0.0
