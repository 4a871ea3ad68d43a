"""TPC-H Q16 as the benchmark's cell ``tpch_sf10_chip1.strings_q16``
runs it (ISSUE 32), small and on the CPU: the query file's pandas
reference against both engines under the configuration's conf, the
three generators' promises, the three readers of what the program
records for strings, the two spans and two counters themselves, and a
rehearsal of the cell through ``benchmark/run.py``."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from benchmark.harness import compare, datagen, load_module, trace
from test_tracing_spans import TRACED, recorder  # noqa: F401 (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch_sf10_chip1.strings_q16"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "tpch_sf10_chip1.json")) as f:
    CONFIG = json.load(f)
#: SF 10 over 2,500: the ratios of the schema kept
ROWS = {t: max(4, n // 2500) for t, n in CONFIG["rows"].items()}
SEED = 2**31 + 32
Q16 = load_module("queries", "q16")
TABLES = {name: load_module("tables", name) for name in Q16.TABLES}


@pytest.fixture(scope="module")
def made():
    return {name: mod.generate(ROWS, SEED) for name, mod in TABLES.items()}


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("q16") / "tables")
    datagen.write_tables(path, sorted(TABLES), ROWS, SEED,
                         dict(CONFIG["parquet"], rows_per_row_group=1024))
    return path


@pytest.fixture(scope="module")
def frames(tables_dir):
    return {t: pq.read_table(os.path.join(tables_dir, t), columns=cols)
            .to_pandas() for t, cols in Q16.TABLES.items()}


# -- the query and its reference --------------------------------------
def test_every_predicate_selects_and_rejects_something(frames):
    part, supp, ps = frames["part"], frames["supplier"], frames["partsupp"]
    for kept in (part.p_brand != Q16.BRAND,
                 ~part.p_type.str.startswith(Q16.TYPE_PREFIX),
                 part.p_size.isin(Q16.SIZES),
                 ~supp.s_comment.str.contains("Customer.*Complaints"),
                 ~ps.ps_suppkey.isin(supp.s_suppkey[
                     supp.s_comment.str.contains("Customer.*Complaints")])):
        assert 0 < kept.sum() < len(kept)
    want = Q16.reference(frames)
    assert len(want) > 100
    counts = [r[3] for r in want]
    assert counts == sorted(counts, reverse=True) and len(set(counts)) > 1
    assert Q16.min_bytes(CONFIG["rows"]) == pytest.approx(217e6, rel=0.02)


@pytest.mark.parametrize("engine", ["host", "device"])
def test_reference_equals_the_engine(engine, frames, tables_dir):
    sess = srt.Session(tpu_enabled=False) if engine == "host" \
        else srt.Session(dict(CONFIG["conf"]))
    df = Q16.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q16.TABLES})
    if engine == "device":
        from benchmark.harness import probes

        assert probes.host_operators(
            df.explain(), CONFIG["guarantees"]["host_operators"]) == []
    got = df.collect()
    assert compare.difference(Q16.reference(frames), got, Q16.ORDERED,
                              CONFIG["guarantees"]
                              ["f64_relative_tolerance"]) is None
    if engine == "device":
        assert sess.last_metrics["fault.degradeLevel"] == 0


# -- the generators' promises -----------------------------------------
def test_part_follows_the_specification_where_q16_reads_it(made):
    part = made["part"].to_pandas()
    assert part.p_partkey.tolist() == list(range(1, ROWS["part"] + 1))
    brands = set(part.p_brand)
    assert brands == {f"Brand#{m}{n}" for m in "12345" for n in "12345"}
    assert part.p_type.nunique() == 150
    assert part.p_type.str.len().max() == TABLES["part"].TYPE_WIDTH == 25
    assert all(len(t.split(" ")) == 3 for t in part.p_type.unique())
    assert set(part.p_size) == set(range(1, 51))


def test_partsupp_gives_a_part_four_suppliers_by_the_formula(made):
    ps = made["partsupp"].to_pandas()
    n_part, n_supp = ROWS["part"], ROWS["supplier"]
    assert len(ps) == 4 * n_part
    p = ps.ps_partkey.to_numpy()
    assert (p == np.repeat(np.arange(1, n_part + 1), 4)).all()
    j = np.tile(np.arange(4), n_part)
    assert (ps.ps_suppkey.to_numpy()
            == (p + j * (n_supp // 4 + 1)) % n_supp + 1).all()
    assert ps.groupby("ps_partkey").ps_suppkey.nunique().eq(4).all()
    assert ps.ps_suppkey.between(1, n_supp).all()


def test_supplier_comments_hold_the_two_needles_in_their_ratio(made):
    supplier = TABLES["supplier"]
    comment = made["supplier"].to_pandas().s_comment
    n = len(comment)
    width = comment.str.encode("utf-8").str.len()
    assert width.min() >= 25 and width.max() == 100
    assert width.nunique() > 20
    complains = comment.str.contains("Customer.*Complaints")
    recommends = comment.str.contains("Customer.*Recommends")
    marked = max(1, n * 5 // 10_000)
    assert complains.sum() == recommends.sum() == marked
    assert not (complains & recommends).any()
    # text between the words: only the two-wildcard pattern finds them
    assert not comment.str.contains("Customer Complaints").any()
    assert not comment.str.contains("Customer Recommends").any()
    _, bad, good = supplier.marked_rows(n, SEED)
    assert bad.tolist() == np.flatnonzero(complains).tolist()
    assert good.tolist() == np.flatnonzero(recommends).tolist()
    # at the specification's scale: 5 rows of every 10,000, each way
    _, bad, good = supplier.marked_rows(CONFIG["rows"]["supplier"], SEED)
    assert len(bad) == len(good) == 50 and not set(bad) & set(good)


def test_every_batch_reaches_each_string_columns_greatest_width(tables_dir):
    """A byte matrix is as wide as its batch's longest string: a width
    that moved with the batch or the seed would be a new program."""
    widest = {"p_brand": 8, "p_type": 25, "s_comment": 100}
    for table, cols in Q16.TABLES.items():
        directory = os.path.join(tables_dir, table)
        files = sorted(f for f in os.listdir(directory)
                       if f.endswith(".parquet"))
        assert len(files) == CONFIG["parquet"]["files_per_table"]
        for name in files:
            pf = pq.ParquetFile(os.path.join(directory, name))
            for g in range(pf.metadata.num_row_groups):
                group = pf.read_row_group(g, columns=cols).to_pandas()
                for col, width in widest.items():
                    if col in group:
                        assert group[col].str.len().max() == width


def test_same_seed_same_tables_other_seed_other_tables():
    for name, mod in TABLES.items():
        again = mod.generate(ROWS, SEED)
        assert again.equals(mod.generate(ROWS, SEED))
        assert not again.equals(mod.generate(ROWS, SEED + 1)), name


# -- the readers of what the program records --------------------------
def strings_trace():
    """Two requests: the part scan's producer converts two string
    columns a batch, the client encodes them inside its upload, the
    anti join's program runs twice a request beside the inner join's."""
    devices = {0: {
        "ops": [(300, 420, "%fusion.1"), (1300, 1420, "%fusion.1")],
        "modules": [(300, 340, "jit_join_semi(1)"),
                    (340, 360, "jit_join_semi(2)"),
                    (360, 420, "jit_join_count(3)"),
                    (1300, 1350, "jit_join_semi(1)"),
                    (1360, 1420, "jit_join_count(3)")]}}
    client = [
        (0, 1000, trace.MARKER), (0, 1000, "Query"),
        (250, 290, "HostToDevice"), (255, 265, "HostToDevice.strings"),
        (270, 285, "HostToDevice.strings"),
        (1000, 2000, trace.MARKER), (1000, 2000, "Query"),
        (1250, 1290, "HostToDevice"), (1255, 1280, "HostToDevice.strings")]
    host = {"python": client,
            "h2d-prefetch-0": [(15, 240, "ScanDecode"),
                               (20, 100, "ScanDecode.strings"),
                               (110, 200, "ScanDecode.strings")],
            "h2d-prefetch-1": [(1015, 1240, "ScanDecode"),
                               (1020, 1100, "ScanDecode.strings")]}
    return trace.Trace(devices, host)


@pytest.mark.parametrize("name,want", [
    ("string_decode_s", (80 + 90 + 80) * 1e-9 / 2),
    ("string_encode_s", (10 + 15 + 25) * 1e-9 / 2),
    ("semi_join_device_s", (40 + 20 + 50) * 1e-9 / 2),
])
def test_reader_on_a_made_up_trace(name, want):
    reader = load_module("layer_metrics", name)
    assert reader.reduce(strings_trace(), {}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["string_decode_s", "string_encode_s",
                                  "semi_join_device_s"])
def test_reader_gives_zero_where_there_is_nothing_to_read(name):
    """A parent commit's trace has no such span, q6's no such program,
    a trace without a device no modules: 0.0, never None, no raise."""
    reader = load_module("layer_metrics", name)
    bare = trace.Trace(
        {0: {"ops": [(10, 20, "%fusion.1")],
             "modules": [(10, 20, "jit_filter__compute(1)")]}},
        {"python": [(0, 100, trace.MARKER), (5, 9, "HostToDevice")],
         "h2d-prefetch-0": [(1, 4, "ScanDecode")]})
    no_device = trace.Trace({}, {"python": [(0, 100, trace.MARKER)]})
    no_request = trace.Trace({}, {"python": []})
    for t in (bare, no_device, no_request):
        value = reader.reduce(t, {})
        assert value == 0.0 and isinstance(value, float)


# -- the spans and counters themselves --------------------------------
def test_string_spans_open_under_their_parents(tables_dir, recorder):  # noqa: F811
    sess = srt.Session(dict(CONFIG["conf"], **TRACED))
    part = sess.read_parquet(os.path.join(tables_dir, "part"))
    rows = part.select("p_partkey", "p_brand", "p_type").collect()
    assert len(rows) == ROWS["part"]
    assert recorder.parents("ScanDecode.strings") == {"ScanDecode"}
    assert recorder.parents("HostToDevice.strings") == {"HostToDevice"}
    # a span a string column a batch, on the thread of its parent
    batches = sess.last_metrics["FileScanExec.decodedBatches"]
    assert batches >= 2
    for child in ("ScanDecode.strings", "HostToDevice.strings"):
        assert sum(1 for s in recorder.spans if s[0] == child) \
            == 2 * batches
    assert recorder.threads("ScanDecode.strings") == \
        recorder.threads("ScanDecode")
    assert recorder.threads("HostToDevice.strings") == \
        recorder.threads("HostToDevice")
    assert recorder.misnested == [] and recorder.still_open() == {}
    # a scan of numbers opens neither
    recorder.spans.clear()
    sess.read_parquet(os.path.join(tables_dir, "partsupp")) \
        .select("ps_partkey", "ps_suppkey").collect()
    assert "ScanDecode" in recorder.names()
    assert not {"ScanDecode.strings", "HostToDevice.strings"} \
        & recorder.names()


def test_string_counters_give_logical_and_padded_bytes(tables_dir, frames):
    sess = srt.Session(dict(CONFIG["conf"]))
    part = sess.read_parquet(os.path.join(tables_dir, "part"))
    part.select("p_brand", "p_type").collect()
    m = sess.last_metrics
    logical = int(frames["part"].p_brand.str.len().sum()
                  + frames["part"].p_type.str.len().sum())
    assert m["FileScanExec.decodedStringBytes"] == logical
    # two files of 400 rows, each a 512-row bucket, 8 and 25 bytes wide
    n_files = CONFIG["parquet"]["files_per_table"]
    per_file = -(-ROWS["part"] // n_files)
    bucket = 1 << (per_file - 1).bit_length()
    assert m["HostToDeviceExec.stringMatrixBytes"] == \
        n_files * bucket * (8 + 25)
    assert m["HostToDeviceExec.stringMatrixBytes"] > logical
    assert m["HostToDeviceExec.stringColumnsFromArrow"] == 2 * n_files
    assert m["HostToDeviceExec.stringColumnsFromObjects"] == 0
    # numbers alone: the counters are there and read nothing
    sess.read_parquet(os.path.join(tables_dir, "partsupp")) \
        .select("ps_suppkey").collect()
    m = sess.last_metrics
    assert m["FileScanExec.decodedStringBytes"] == 0
    assert m["HostToDeviceExec.stringMatrixBytes"] == 0
    assert m["HostToDeviceExec.stringColumnsFromArrow"] == 0


# -- what the cell's entry point forced --------------------------------
def test_a_request_that_arrives_as_a_new_plan_reads_and_compiles(tables_dir):
    """``entries/execute_new_plan.py``: the same logical plan as a new
    object is planned again and reads all three tables; sent again as
    the same object it finds the broadcast relations an earlier request
    built.  Either way a second request compiles nothing: the exchange's
    own programs are keyed, not made anew with every physical plan."""
    from benchmark.harness import probes

    watch = probes.CompileWatch()
    sess = srt.Session(dict(CONFIG["conf"]))
    df = Q16.build({t: sess.read_parquet(os.path.join(tables_dir, t))
                    for t in Q16.TABLES})
    new_plan = load_module("entries", "execute_new_plan")
    same_plan = load_module("entries", "execute")
    first = new_plan.run(sess, df, CONFIG)
    every_table = sum(ROWS[t] for t in Q16.TABLES)
    assert sess.last_metrics["FileScanExec.decodedRows"] == every_table
    for _ in range(2):
        mark = watch.snapshot()
        assert new_plan.run(sess, df, CONFIG) == first
        m = sess.last_metrics
        assert new_plan.faults(m, CONFIG) == []
        assert m["FileScanExec.decodedRows"] == every_table
        assert m["FileScanExec.decodedStringBytes"] > 0
        # every string column went up from Arrow's buffers (ISSUE 33):
        # p_brand and p_type a part batch, s_comment a supplier batch
        assert m["HostToDeviceExec.stringColumnsFromObjects"] == 0
        assert m["HostToDeviceExec.stringColumnsFromArrow"] >= 3
        assert m["kernelCache.misses"] == 0
        assert watch.since(mark)["xla_compiles"] == 0
    assert same_plan.run(sess, df, CONFIG) == first
    assert same_plan.run(sess, df, CONFIG) == first
    m = sess.last_metrics
    # the stream side alone: part and supplier came from the registry
    assert m["FileScanExec.decodedRows"] == ROWS["partsupp"]
    assert m["FileScanExec.decodedStringBytes"] == 0


# -- the cell through the harness -------------------------------------
def test_rehearsal_of_the_cell_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # one CPU device: the cell has one chip
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["rehearsal"] is True
    assert last["metrics"] == {}
    values = last["rehearsal_values"]
    for name in ("string_decode_s", "string_encode_s",
                 "semi_join_device_s", "compiles_in_window"):
        assert re.fullmatch(r"[\d.e+-]+", repr(values[name]["value"]))
    assert values["compiles_in_window"]["value"] == 0.0
    # the spans are read where the program records them (the CPU trace
    # has no device plane, so the device reader gives its 0.0)
    assert values["string_decode_s"]["value"] > 0
    assert values["string_encode_s"]["value"] > 0
