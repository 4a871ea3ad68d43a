"""TPC-H Q21 as the benchmark's cell
``tpch_sf1_exists_chip1.waits_q21`` runs it (ISSUE 38), small and on
the CPU: the query file's pandas reference against both engines through
the cell's entry point; the plan a TPU makes of it (both conditional
joins on the device, only the scan on the host); ``min_bytes``; the
specification's nations; that ``BENCHMARK.json`` finds the cell's files;
the two readers this cell brings; and a rehearsal of the cell through
``benchmark/run.py``."""
import json
import os
import subprocess
import sys

import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from benchmark.harness import compare, datagen, load_module, probes, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch_sf1_exists_chip1.waits_q21"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "tpch_sf1_exists_chip1.json")) as f:
    CONFIG = json.load(f)
#: SF 1 over 100, and every nation: SAUDI ARABIA's suppliers are there
ROWS = dict({t: max(4, n // 100) for t, n in CONFIG["rows"].items()},
            nation=25)
SEED = 2**31 + 38
Q21 = load_module("queries", "q21")
NATION = load_module("tables", "nation")
ENTRY = load_module("entries", CONFIG["entry"])
READERS = ["join_condition_device_s", "join_condition_roofline_share"]


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("q21") / "tables")
    datagen.write_tables(path, sorted(Q21.TABLES), ROWS, SEED,
                         dict(CONFIG["parquet"], rows_per_row_group=8192))
    return path


@pytest.fixture(scope="module")
def frames(tables_dir):
    return {t: pq.read_table(os.path.join(tables_dir, t), columns=cols)
            .to_pandas(date_as_object=False)
            for t, cols in Q21.TABLES.items()}


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


# -- the query and its reference --------------------------------------
@pytest.mark.parametrize("engine", ["host", "device"])
def test_reference_equals_the_engine_through_the_entry(engine, frames,
                                                       tables_dir):
    want = Q21.reference(frames)
    assert 1 < len(want) <= 100
    assert [type(v).__name__ for v in want[0]] == ["str", "int"]
    assert want == sorted(want, key=lambda r: (-r[1], r[0]))
    sess = srt.Session(tpu_enabled=False) if engine == "host" \
        else srt.Session(dict(CONFIG["conf"]))
    df = Q21.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q21.TABLES})
    got = ENTRY.run(sess, df, CONFIG)
    assert compare.difference(
        want, got, Q21.ORDERED,
        CONFIG["guarantees"]["f64_relative_tolerance"]) is None
    if engine == "device":
        m = sess.last_metrics
        assert ENTRY.faults(m, CONFIG) == []
        # lineitem read three times, every table in the request (of
        # nation's two files the scan prunes the one whose n_name range
        # cannot hold SAUDI ARABIA)
        read = 3 * ROWS["lineitem"] + ROWS["orders"] + ROWS["supplier"]
        assert read < m["FileScanExec.decodedRows"] <= read + 25
        # both conditional joins ran on the device, decided by the
        # bounds of l2's and l3's suppliers: one program a stream batch
        # (a Parquet file a partition), and no pair laid out
        assert m["join.conditionByBounds"] == 2 * CONFIG["parquet"][
            "files_per_table"]
        assert m.get("join.conditionJoins", 0) == 0
        assert m.get("join.conditionPairs", 0) == 0


@pytest.mark.parametrize("how", ["semi", "anti"])
def test_the_tpu_planner_leaves_only_the_scan_on_the_host(
        how, tables_dir, monkeypatch):
    """Planned as a TPU plans it, both joins stay on the device with
    their inequality, the scan alone on the host; strict mode plans it
    without a raise."""
    import jax

    from spark_rapids_tpu.exec.joins import TpuHashJoinExec

    sess = srt.Session(dict(CONFIG["conf"]))
    df = Q21.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q21.TABLES})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = df.explain()
    assert probes.host_operators(
        text, CONFIG["guarantees"]["host_operators"]) == []
    suffix = "2" if how == "semi" else "3"
    line, = [ln for ln in text.splitlines()
             if f"[{how}, (NOT (l_suppkey == l{suffix}_suppkey))]" in ln]
    assert line.strip().startswith("* HashJoinExec")
    joins = [n for n in _walk(sess.physical_plan(df.plan))
             if isinstance(n, TpuHashJoinExec) and n.how == how]
    assert len(joins) == 1 and joins[0].condition is not None


def test_min_bytes_counts_each_input_column_once():
    rows = CONFIG["rows"]
    assert Q21.min_bytes(rows) == (
        rows["lineitem"] * 24 + rows["orders"] * 9
        + rows["supplier"] * 34 + 25 * 24 + 100 * 26)
    assert Q21.min_bytes(rows) == pytest.approx(157.8e6, rel=0.01)


def test_nation_holds_the_specifications_twenty_five():
    t = NATION.generate({"nation": 25}, SEED)
    assert t.column("n_nationkey").to_pylist() == list(range(25))
    names = t.column("n_name").to_pylist()
    assert names[20] == "SAUDI ARABIA" == Q21.NATION
    assert (names[0], names[24]) == ("ALGERIA", "UNITED STATES")
    assert len(set(names)) == 25
    assert sorted(set(t.column("n_regionkey").to_pylist())) == \
        list(range(5))
    assert NATION.generate({"nation": 4}, SEED).num_rows == 4


def test_supplier_names_are_eighteen_bytes(frames):
    assert set(frames["supplier"].s_name.str.len()) == {18}


# -- BENCHMARK.json finds the cell's files ----------------------------
def test_benchmark_json_finds_the_cells_files():
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    config, = [c for c in BENCH["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == CONFIG["chips"] == 1
    assert config["file"] == "benchmark/configs/tpch_sf1_exists_chip1.json"
    assert config["source"] == CONFIG["source"]
    assert config["reduced"] == CONFIG["reduced"] == []
    assert CONFIG["scale_factor"] == 1.0 and CONFIG["entry"] == \
        "execute_new_plan"
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    assert traffic["queries"] == ["q21"]
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
def q21_trace():
    devices = {0: {
        "ops": [(100, 480, "%fusion.1")],
        "modules": [(100, 200, "jit_join_count(1)"),
                    (200, 480, "jit_join_semiPairs(2)")]}}
    client = [(0, 1000, trace.MARKER), (0, 1000, "Query")]
    return trace.Trace(devices, {"python": client})


def _phases(monkeypatch, rows):
    """A program's phases as ``device_trace`` would read them from an
    xplane: ``rows`` is {phase: (seconds, bytes)} of one program."""
    from spark_rapids_tpu.telemetry import device_trace
    from spark_rapids_tpu.utils import tracing

    phases = load_module("layer_metrics", "join_condition_device_s").phases
    table = {"jit_join_semiPairs": {
        k: device_trace.Row(s, 1.0, b, b / s / 1e9, 0.0)
        for k, (s, b) in rows.items()}}
    monkeypatch.setattr(tracing, "last_profile_dir", lambda: "/x")
    monkeypatch.setattr(phases.harness_trace, "find_xplane",
                        lambda d: d + "/x.xplane.pb")
    monkeypatch.setattr(phases, "_loaded", lambda p: type(
        "Loaded", (), {"devices": {0: None}})())
    monkeypatch.setattr(device_trace, "reduce", lambda *a, **k: table)
    monkeypatch.setattr(device_trace, "seconds_by_key", lambda *a, **k: {
        key: row.seconds for key, row in table["jit_join_semiPairs"].items()})
    phases._reduced.cache_clear()


def test_readers_on_a_made_up_reduction(monkeypatch):
    _phases(monkeypatch, {"join.condition": (0.5, 0.5 * 409.5e9),
                          "join.pairRows": (0.25, 1e9)})
    notes = {"peaks_file": os.path.join(ROOT, "benchmark", "harness",
                                        "peaks.json"),
             "device_kind": "TPU v5 lite"}
    seconds = load_module("layer_metrics", READERS[0])
    share = load_module("layer_metrics", READERS[1])
    assert seconds.reduce(q21_trace(), notes) == pytest.approx(0.5)
    assert share.reduce(q21_trace(), notes) == pytest.approx(50.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_zero_where_there_is_nothing_to_read(name):
    """The parent's program names no ``join.condition``; a trace without
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
         "--trace", "1", "--rehearsal", "250"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["rehearsal"] is True
    assert last["metrics"] == {}
    values = last["rehearsal_values"]
    assert values["compiles_in_window"]["value"] == 0.0
    # the CPU trace has no device plane: the device readers give 0.0
    assert values["join_condition_device_s"]["value"] == 0.0
    assert values["join_condition_roofline_share"]["value"] == 0.0
