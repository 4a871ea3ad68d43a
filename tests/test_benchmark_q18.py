"""TPC-H Q18 as the benchmark's cell
``tpch_sf1_newplan_chip1.groups_q18`` runs it (ISSUE 34), small and on
the CPU: the query file's pandas reference against both engines through
the cell's entry point, at the specification's QUANTITY and at a lower
one where the limit cuts; what the adaptive planner does with the semi
join; the two readers this cell brings; that ``BENCHMARK.json`` finds
the cell's files; and a rehearsal of the cell through
``benchmark/run.py``."""
import json
import os
import re
import subprocess
import sys

import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from benchmark.harness import compare, datagen, load_module, probes, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch_sf1_newplan_chip1.groups_q18"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "tpch_sf1_newplan_chip1.json")) as f:
    CONFIG = json.load(f)
#: SF 1 over 250: the ratios of the schema kept
ROWS = {t: max(4, n // 250) for t, n in CONFIG["rows"].items()}
SEED = 2**31 + 34
Q18 = load_module("queries", "q18")
ENTRY = load_module("entries", CONFIG["entry"])
READERS = ["agg_device_s", "aqe_replan_ms"]


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("q18") / "tables")
    datagen.write_tables(path, sorted(Q18.TABLES), ROWS, SEED,
                         dict(CONFIG["parquet"], rows_per_row_group=4096))
    return path


@pytest.fixture(scope="module")
def frames(tables_dir):
    return {t: pq.read_table(os.path.join(tables_dir, t), columns=cols)
            .to_pandas(date_as_object=False)
            for t, cols in Q18.TABLES.items()}


def passing(frames, quantity):
    total = frames["lineitem"].groupby("l_orderkey").l_quantity.sum()
    return int((total > quantity).sum())


# -- the query and its reference --------------------------------------
@pytest.mark.parametrize("engine", ["host", "device"])
@pytest.mark.parametrize("quantity", [300.0, 200.0],
                         ids=["spec_300", "limit_cuts_200"])
def test_reference_equals_the_engine_through_the_entry(
        engine, quantity, frames, tables_dir, monkeypatch):
    monkeypatch.setattr(Q18, "QUANTITY", quantity)
    kept = passing(frames, quantity)
    if quantity == 300.0:
        assert 0 < kept < 100       # the HAVING keeps a fraction
    else:
        assert kept > 100           # ... or more than the limit lets by
    want = Q18.reference(frames)
    assert len(want) == min(kept, 100)
    assert [type(v).__name__ for v in want[0]] == \
        ["str", "int", "int", "date", "float", "float"]
    prices = [r[4] for r in want]
    assert prices == sorted(prices, reverse=True)
    assert all(r[5] > quantity and r[5] == int(r[5]) for r in want)

    sess = srt.Session(tpu_enabled=False) if engine == "host" \
        else srt.Session(dict(CONFIG["conf"]))
    df = Q18.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q18.TABLES})
    if engine == "device":
        assert probes.host_operators(
            df.explain(), CONFIG["guarantees"]["host_operators"]) == []
    got = ENTRY.run(sess, df, CONFIG)
    assert compare.difference(
        want, got, Q18.ORDERED,
        CONFIG["guarantees"]["f64_relative_tolerance"]) is None
    if engine == "device":
        m = sess.last_metrics
        assert ENTRY.faults(m, CONFIG) == []
        # every table read in the request, lineitem twice
        assert m["FileScanExec.decodedRows"] == \
            ROWS["customer"] + ROWS["orders"] + 2 * ROWS["lineitem"]
        # the semi join's build side is an aggregate the static planner
        # cannot size: planned shuffled, converted once the HAVING's
        # survivors are counted, and orders never goes through an
        # exchange
        assert m["aqe.numJoinsConverted"] == 1
        assert m["aqe.streamExchangesDeferred"] == 1
        exchanged = {k: v for k, v in m.items()
                     if re.fullmatch(r"shuffle\.exchange\d+\.rowsTotal", k)}
        assert exchanged and ROWS["orders"] not in exchanged.values()
        assert kept in exchanged.values()


@pytest.mark.parametrize("query", ["cell", "benchmarks_tpch"])
def test_the_tpu_planner_leaves_only_the_scan_on_the_host(
        query, tables_dir, monkeypatch):
    """The chip holds an f64 as two f32, so it cannot hash a FLOAT64
    the way Spark does (``utils/hashing.py:device_hash_gap``; the CPU
    backend has no such gap).  Planned as a TPU plans it, Q18 to the
    letter (``o_totalprice`` a fifth grouping key) keeps every operator
    but the scan on the device: the group-by's exchange hashes the four
    keys the chip can hash and the price stays a grouping key only."""
    import jax

    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec

    sess = srt.Session(dict(CONFIG["conf"]))
    tables = {t: sess.read_parquet(os.path.join(tables_dir, t))
              for t in Q18.TABLES}
    df = Q18.build(tables) if query == "cell" else tpch.q18(tables)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    allowed = CONFIG["guarantees"]["host_operators"]
    assert probes.host_operators(df.explain(), allowed) == []
    phys = sess.physical_plan(df.plan)        # strict mode: no raise
    hashed = [n.describe() for n in _walk(phys)
              if isinstance(n, TpuShuffleExchangeExec)
              and "Hash" in n.describe()]
    five_keys, = [d for d in hashed if "c_name" in d]
    assert "o_orderdate" in five_keys and "o_totalprice" not in five_keys


def test_an_exchange_keyed_by_float64_alone_is_tagged_for_the_host(
        tables_dir, monkeypatch):
    """Where no grouping key hashes on the chip there is no subset to
    partition on: the exchange keeps its key, the plan says so, and
    strict mode refuses it before anything runs."""
    import jax

    from spark_rapids_tpu.plan import functions as F

    sess = srt.Session(dict(CONFIG["conf"]))
    orders = sess.read_parquet(os.path.join(tables_dir, "orders"))
    by_price = orders.group_by("o_totalprice").agg(
        F.count("o_custkey").alias("n"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "ShuffleExchangeExec" in probes.host_operators(
        by_price.explain(), CONFIG["guarantees"]["host_operators"])
    with pytest.raises(AssertionError, match="ShuffleExchangeExec"):
        sess.physical_plan(by_price.plan)


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def test_min_bytes_counts_each_input_column_once():
    rows = CONFIG["rows"]
    assert Q18.min_bytes(rows) == (
        rows["lineitem"] * 16 + rows["orders"] * 28
        + rows["customer"] * (8 + 18) + 100 * 54)
    assert Q18.min_bytes(rows) == pytest.approx(141.9e6, rel=0.01)


def test_customer_names_are_eighteen_bytes(frames):
    assert set(frames["customer"].c_name.str.len()) == {18}


# -- BENCHMARK.json finds the cell's files ----------------------------
def test_benchmark_json_finds_the_cells_files():
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    config, = [c for c in BENCH["configs"] if c["name"] == cell["config"]]
    assert cell["chips"] == CONFIG["chips"] == 1
    assert config["file"] == "benchmark/configs/tpch_sf1_newplan_chip1.json"
    assert config["source"] == CONFIG["source"]
    assert config["reduced"] == CONFIG["reduced"] == []
    assert CONFIG["scale_factor"] == 1.0 and Q18.QUANTITY == 300.0
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    assert traffic["queries"] == ["q18"]
    assert (traffic["loop"], traffic["clients"]) == ("closed", 1)
    for name in READERS:
        m, = [m for m in BENCH["per_layer"] if m["name"] == name]
        reader = load_module("layer_metrics", name)
        assert (m["unit"], m["layer"], m["moves"]) == \
            (reader.UNIT, reader.LAYER, reader.MOVES)
        assert m["workloads"] == [CELL]
    # the cell reports every accepted metric that has no list of cells
    owed = [m["name"] for m in BENCH["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    assert set(READERS) < set(owed) and "hbm_roofline_share" in owed


# -- the readers of what the program records --------------------------
def q18_trace():
    """Two requests: the aggregate's programs beside an exchange's and a
    join's on the device, a re-plan after each of three stages."""
    devices = {0: {
        "ops": [(100, 480, "%fusion.1"), (1100, 1480, "%fusion.1")],
        "modules": [(100, 200, "jit_agg_batch(1)"),
                    (200, 260, "jit_shuffle_packedBuild(2)"),
                    (260, 300, "jit_agg_merge_final(3)"),
                    (300, 480, "jit_join_count(4)"),
                    (1100, 1210, "jit_agg_batch(1)"),
                    (1300, 1480, "jit_join_count(4)")]}}
    client = [
        (0, 1000, trace.MARKER), (0, 1000, "Query"),
        (90, 270, "AqeStage"), (270, 280, "AqeReplan"),
        (280, 400, "AqeStage"), (400, 430, "AqeReplan"),
        (1000, 2000, trace.MARKER), (1000, 2000, "Query"),
        (1090, 1270, "AqeStage"), (1270, 1290, "AqeReplan")]
    return trace.Trace(devices, {"python": client})


@pytest.mark.parametrize("name,want", [
    ("agg_device_s", (100 + 40 + 110) * 1e-9 / 2),
    ("aqe_replan_ms", (10 + 30 + 20) * 1e-6 / 2),
])
def test_reader_on_a_made_up_trace(name, want):
    reader = load_module("layer_metrics", name)
    assert reader.reduce(q18_trace(), {}) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_zero_where_there_is_nothing_to_read(name):
    """A parent commit's trace has no ``AqeReplan`` span, q6's no
    aggregate program, a trace without a device no modules: 0.0, never
    None, no raise."""
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
    # the span is read where the program records it (the CPU trace has
    # no device plane, so the device reader gives its 0.0)
    assert values["aqe_replan_ms"]["value"] > 0
    assert values["agg_device_s"]["value"] == 0.0
