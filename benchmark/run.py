"""One cell of the benchmark, once, in this process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, the queries, the entry
point and the metrics are all found by name: in ``BENCHMARK.json`` and
in files under ``benchmark/`` (README.md there).  This file knows none
of them.  The last line of standard output is the result; the lines
before it are notes.
"""
import time

T0 = time.perf_counter()    # set-up is counted from here

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.harness import load_module      # noqa: E402

#: a rehearsal in the sandbox divides every table's rows by this,
#: unless told otherwise
REHEARSAL_SHRINK = 1000
#: a query is run until one whole execution compiles nothing, at most
#: this often
WARM_UP_MOST = 3


def note(_what, **kw):
    print(json.dumps({"note": _what, **kw}, default=str), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def named(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        sys.exit(f"benchmark: no {what} {name!r} in BENCHMARK.json")
    return found[0]


def reduce_metrics(kind, listed, cell_name, *inputs):
    """The cell's metrics of one kind, each by the file of its name; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in listed:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = load_module(kind, m["name"]).reduce(*inputs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def top(seconds_by_name, most=10):
    return [[name, secs] for name, secs in
            sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:most]]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, nargs="?", default=0,
                    const=REHEARSAL_SHRINK, metavar="SHRINK",
                    help="for the sandbox: the CPU backend is allowed, every "
                         "table has 1/SHRINK of its rows (default "
                         f"{REHEARSAL_SHRINK}), and no metric is reported")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="copy the traced run's xplane (gzipped) here")
    return ap.parse_args(argv)


def claim_devices(chips, rehearsal):
    """The devices the cell runs on; no TPU, or too few, ends the run
    with no result."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (rehearsal and platform == "cpu"):
        sys.exit(f"benchmark: needs a TPU, jax.devices() gave "
                 f"{platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} devices, "
                 f"jax.devices() gave {len(devices)}")
    return devices[:chips]


def reference_answers(data_dir, queries):
    """The plain reference's rows for each query, from the columns the
    queries read; a date column comes as datetime64, not as objects."""
    import pyarrow.parquet as pq

    wanted = {}
    for q in queries.values():
        for table, cols in q.TABLES.items():
            wanted.setdefault(table, set()).update(cols)
    frames = {table: pq.read_table(os.path.join(data_dir, table),
                                   columns=sorted(cols))
              .to_pandas(date_as_object=False)
              for table, cols in wanted.items()}
    return {name: q.reference(frames) for name, q in queries.items()}


def warm_up(queries, run, check, watch):
    """Run each query until one whole execution compiles nothing and
    reads nothing from the persistent cache."""
    errors, seconds = [], []
    for q in queries:
        for attempt in range(WARM_UP_MOST):
            mark = watch.snapshot()
            t0 = time.perf_counter()
            got = run(q)
            seconds.append(time.perf_counter() - t0)
            compiled = watch.since(mark)
            errors += [f"warm-up {q}#{attempt}: {f}" for f in check(q, got)]
            note("warm_up", query=q, attempt=attempt, seconds=seconds[-1],
                 **compiled)
            if not any(compiled[k] for k in (
                    "xla_compiles", "persistent_cache_hits",
                    "persistent_cache_misses")):
                break
    return errors, seconds


def main(argv=None):
    args = parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = named(bench["workloads"], args.workload, "workload")
    config = load_json(ROOT, named(bench["configs"], cell["config"],
                                   "configuration")["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    if cell["chips"] != config["chips"]:
        sys.exit(f"benchmark: the cell asks for {cell['chips']} chips, its "
                 f"configuration for {config['chips']}")
    try:
        import spark_rapids_tpu as srt
    except ImportError as exc:
        sys.exit(f"benchmark: the system under test is not here: {exc}")
    import jax

    from benchmark.harness import compare, datagen, loop, probes, trace

    used = claim_devices(cell["chips"], args.rehearsal)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used)}
    watch = probes.CompileWatch()
    from spark_rapids_tpu import native
    from spark_rapids_tpu.utils import compile_cache

    native_built = native.available()       # builds native/ if absent
    cache_dir = compile_cache.enable()
    note("start", workload=cell["name"], seed=args.seed,
         seconds=args.seconds, trace=args.trace,
         rehearsal=bool(args.rehearsal), device=device,
         native="built from native/src" if native_built else "python fallback",
         compile_cache_dir=cache_dir,
         compile_cache_entries=probes.cache_entries(cache_dir),
         jax=jax.__version__)

    round_ = loop.schedule(traffic, args.seed)
    queries = {q: load_module("queries", q) for q in sorted(set(round_))}
    entry = load_module("entries", config["entry"])
    rows = {t: max(4, n // (args.rehearsal or 1))
            for t, n in config["rows"].items()}

    scratch = tempfile.mkdtemp(prefix="benchmark_run_")
    sess = None
    try:
        # -- data: made anew from the seed, every run -------------------
        t0 = time.perf_counter()
        data_dir = os.path.join(scratch, "tables")
        made = datagen.write_tables(
            data_dir, sorted({t for q in queries.values() for t in q.TABLES}),
            rows, args.seed, config["parquet"])
        note("data", generator="benchmark/tables (in-repo distributions, "
             "NOT dbgen)", scale_factor=config["scale_factor"], tables=made,
             seconds=time.perf_counter() - t0)

        t0 = time.perf_counter()
        answers = reference_answers(data_dir, queries)
        note("reference", kind="pandas over the same Parquet files",
             rows={q: len(a) for q, a in answers.items()},
             seconds=time.perf_counter() - t0)

        # -- the system under test --------------------------------------
        conf = dict(config["conf"])
        if args.trace:
            conf.update(config["trace_conf"])
        sess = srt.Session(conf)
        tables = {t: sess.read_parquet(os.path.join(data_dir, t))
                  for t in made}
        frames = {q: mod.build(tables) for q, mod in queries.items()}
        guarantees = config["guarantees"]
        plan_faults = {
            q: [f"explain() marks {op} for the host"
                for op in probes.host_operators(
                    df.explain(), guarantees["host_operators"])]
            for q, df in frames.items()}

        def run(q):
            return entry.run(sess, frames[q], config)

        def check(q, got):
            faults = plan_faults[q] + entry.faults(sess.last_metrics, config)
            diff = compare.difference(answers[q], got, queries[q].ORDERED,
                                      guarantees["f64_relative_tolerance"])
            return faults + [diff] if diff else faults

        setup_mark = watch.snapshot()
        setup_errors, warm_s = warm_up(queries, run, check, watch)
        setup_compiles = watch.since(setup_mark)

        # -- the window -------------------------------------------------
        profiler, plan_s = None, []
        if args.trace:
            def time_planning(q):
                t0 = time.perf_counter()
                sess.physical_plan(frames[q].plan)
                plan_s.append(time.perf_counter() - t0)

            # from the second request, unless the window holds few
            profiler = loop.Profiler(
                os.path.join(scratch, "trace"),
                start_at=0 if 2 * warm_s[-1] >= args.seconds else 1,
                marker=trace.MARKER, before_request=time_planning)
        window_mark = watch.snapshot()
        setup_s = time.perf_counter() - T0
        window = loop.run_window(round_, args.seconds, run, check, profiler)
        window_compiles = watch.since(window_mark)
        note("window", **{k: v for k, v in window.items() if k != "samples"},
             samples=len(window["samples"]), setup_errors=setup_errors[:10],
             **window_compiles)
        window["setup_s"] = setup_s

        peaks = [p for p in probes.memory_peaks(used) if p]
        device["memory_peak_bytes"] = max(peaks, default=None)
        out = {"correct": not setup_errors and window["failed"] == 0
               and window["attempted"] > 0,
               "attempted": window["attempted"], "failed": window["failed"]}
        breakdown = None
        if args.trace:
            xplane = trace.find_xplane(profiler.directory)
            if args.keep_trace:
                trace.keep(xplane, args.keep_trace)
            reduced = trace.load(xplane)
            notes = {
                "chips": len(used),
                "plan_s": plan_s,
                "first_query_s": warm_s[0],
                "setup_compiles": setup_compiles,
                "window_compiles": window_compiles,
                "memory_peak_bytes": peaks,
                "min_bytes_per_query": sum(
                    queries[q].min_bytes(rows) for q in profiler.traced)
                / max(1, len(profiler.traced)),
                "device_kind": used[0].device_kind,
                "peaks_file": os.path.join(HERE, "harness", "peaks.json"),
            }
            metrics = reduce_metrics("layer_metrics", bench["per_layer"],
                                     cell["name"], reduced, notes)
            if reduced.has_device:
                ids = reduced.active_devices
                device["busy_s"] = sum(map(reduced.busy_s, ids)) / len(ids)
                device["window_s"] = reduced.window_s
                breakdown = {
                    "device_ops": top(reduced.module_seconds(
                        max(ids, key=reduced.busy_s))),
                    "idle_gaps": top(reduced.idle_by_host_span(
                        min(ids, key=reduced.busy_s)))}
            note("traced", queries=reduced.queries,
                 window_s=reduced.window_s, client_line=reduced.client_line,
                 compile_cache_entries=probes.cache_entries(cache_dir))
        else:
            metrics = reduce_metrics("end_to_end", bench["end_to_end"],
                                     cell["name"], window)
    finally:
        if sess is not None:
            sess.close()
        shutil.rmtree(scratch, ignore_errors=True)

    if args.rehearsal:
        # a CPU timing never stands under a metric's name
        out.update(rehearsal=True, metrics={}, rehearsal_values=metrics)
    else:
        out["metrics"] = metrics
    out["device"] = device
    if breakdown:
        out["breakdown"] = breakdown
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
