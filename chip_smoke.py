"""chip_smoke.py — drives the served path once on the chip and checks it.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # only the mesh path, on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # toy size, no chip

One process.  Its first act is to read ``jax.devices()``: without a TPU
it exits non-zero naming the platform it found — no probe, no retry, no
CPU fallback (``--rehearse`` walks the same phases at toy size on the
CPU backend and never reports ``"ok": true``).

One chip: TPC-H tables from the in-repo generator ("NOT dbgen") at
SF 1, made from ``--seed``, written to Parquet and read back with
``Session.read_parquet`` — the path users take.  q6, q1, q3 and q16 run
through ``Session.execute`` on a default-conf session in strict mode with
the degrade ladder off, cold then warm; then one ``submit().result()``
and one ``prepare()``/``execute(params)``.  Each answer must equal the
host oracle's (``Session(tpu_enabled=False)`` on the same files).

Four chips: only the mesh path and what it is compared with — q3 (its
joins shuffled, so ``all_to_all`` runs) and q5 through
``run_distributed`` against the host oracle.  The runner's stage
programs are announced as they are dispatched and as they answer, and
a query that outlives its deadline ends the process naming the stage.

Every phase prints one JSON line of notes; nothing here is a benchmark.
Any failed check raises, so the process exits non-zero and the last line
is never printed.  On success the LAST stdout line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
import argparse
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: strict mode (an unexpected host fallback raises), no ladder (a device
#: fault cannot be answered from the CPU plan), and leave to look at a
#: result's device arrays before they are downloaded
CONF = {
    "spark.rapids.tpu.sql.test.enabled": True,
    "spark.rapids.tpu.fault.degrade.enabled": False,
    "spark.rapids.tpu.sql.exportColumnarRdd": True,
}

#: operators that ``explain()`` marks ``!`` by design, each with the
#: reason.  The Parquet scan decodes with pyarrow on the host and its
#: batches are uploaded (ROADMAP Design 8); nothing else may fall back.
HOST_BY_DESIGN = {
    "FileScanExec": "Parquet is decoded on the host by pyarrow, then "
                    "uploaded",
}

#: TPC-H scale factor of the one-chip run (6M lineitem rows), and of a
#: rehearsal on the CPU backend
SF = 1.0
REHEARSAL_SF = 0.001
#: the mesh path's SF.  Cut from 1.0 before PR 21's one four-chip call,
#: when the runner's trim between stages did not work and q3's lineitem
#: shard grew 256x, to 2^25 rows, at SF 0.1.  The trim is repaired
#: (parallel/runner.py:_retile); no four-chip run has been made since,
#: so the cut stays until one at SF 1 has passed (PERF.md section 7)
MESH_SF = 0.1
#: seconds one mesh query may take, compiling included, before the
#: script ends itself and says which stage program it was in: a call on
#: four chips that is cut by the tool's own limit says nothing and may
#: take the machine with it
MESH_QUERY_DEADLINE_S = 900
#: a single XLA compile this long gets a line of its own as it ends
SLOW_COMPILE_S = 10.0

#: what tests/test_tpch.py allows f64 sums (the TPU holds an f64 as two
#: f32: answers equal the oracle's to a tolerance, never bit for bit)
FLOAT_TOLERANCE = 1e-6
#: queries whose output has no total order (tests/test_tpch.py)
UNORDERED = {5, 6, 16}


def note(phase, **kw):
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


class CompileWatch:
    """Counts what JAX compiles and what its persistent cache answers,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_secs = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_secs += secs
            if secs >= SLOW_COMPILE_S:
                note("compile", seconds=round(secs, 1))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return {"xla_compiles": self.compiles,
                "xla_compile_s": round(self.compile_secs, 2),
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses}

    def since(self, mark):
        now = self.snapshot()
        return {k: round(now[k] - mark[k], 2) for k in now}


class StageNotes(logging.Handler):
    """The mesh runner logs every stage program as it is dispatched and
    as it answers (attempt, seconds, capacities that overflowed).  Each
    becomes a flushed line here, so a run that is cut short has said
    where it was; the last one is kept for the deadline's message."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.last = "no stage program dispatched yet"
        runner_log = logging.getLogger("spark_rapids_tpu.parallel.runner")
        runner_log.setLevel(logging.INFO)
        runner_log.addHandler(self)

    def emit(self, record):
        self.last = record.getMessage()
        note("mesh.program", said=self.last)


def deadline(seconds, what, stages, scratch):
    """A timer that ends the process with a non-zero code, naming
    ``what`` and the runner's last word, once ``seconds`` have passed;
    cancel it when ``what`` is done.  It does not raise: the main thread
    may be inside a compile or a collective that nothing interrupts."""
    def fire():
        print(f"chip_smoke: {what} passed its {seconds} s deadline; the "
              f"runner's last word: {stages.last}", file=sys.stderr,
              flush=True)
        shutil.rmtree(scratch, ignore_errors=True)
        os._exit(1)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()
    return timer


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


def memory_note(devices, arena=None):
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d.id)] = {"bytes_limit": stats.get("bytes_limit"),
                          "peak_bytes_in_use":
                              stats.get("peak_bytes_in_use")}
    if arena is not None:
        out["logical_arena"] = {"arena_bytes": arena.arena_bytes,
                                "peak_bytes": arena.peak_bytes}
    return out


def build_native(empty_first):
    """Build the native library from ``native/src`` and say whether it
    is in use.  On the chip the build directory is emptied first: a
    stale ``native/build`` that rode along on the disk proves nothing.
    A rehearsal leaves it alone — it runs inside the test suite, whose
    other processes load the same library from the same checkout."""
    if empty_first:
        shutil.rmtree(os.path.join(HERE, "native", "build"),
                      ignore_errors=True)
    from spark_rapids_tpu import native

    built = native.available()
    toolchain = bool(shutil.which("make") and shutil.which("g++"))
    check(built or not toolchain,
          "make and g++ are present but native/src did not build")
    return "built from native/src" if built else \
        "python fallback (no make/g++ here)"


def make_tables(args, scratch):
    """Generate the tables, write them as Parquet (the generator's own
    writer) and note what was made; returns the directory."""
    import pyarrow.parquet as pq

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.benchmarks import tpch_datagen

    t0 = time.perf_counter()
    path = os.path.join(scratch, "tpch")
    tpch_datagen.write_parquet(srt.Session(tpu_enabled=False), path,
                               sf=args.sf, seed=args.seed)
    rows, nbytes = {}, 0
    for table in sorted(os.listdir(path)):
        for r, _, files in os.walk(os.path.join(path, table)):
            for f in files:
                nbytes += os.path.getsize(os.path.join(r, f))
                if f.endswith(".parquet"):
                    rows[table] = rows.get(table, 0) + pq.ParquetFile(
                        os.path.join(r, f)).metadata.num_rows
    note("data", sf=args.sf, seed=args.seed, generator="in-repo, NOT dbgen",
         rows=rows, parquet_bytes=nbytes,
         seconds=round(time.perf_counter() - t0, 1))
    return path


def read_tables(sess, path):
    return {name: sess.read_parquet(os.path.join(path, name))
            for name in sorted(os.listdir(path))}


def check_explain(df):
    """No ``!`` in the plan report except the host-by-design scans."""
    marks = [ln.strip() for ln in df.explain().splitlines()
             if ln.strip().startswith(("!", "@"))]
    bad = [ln for ln in marks if ln.split()[1] not in HOST_BY_DESIGN]
    check(not bad, f"operators off the device: {bad}")
    return {ln.split()[1]: HOST_BY_DESIGN[ln.split()[1]] for ln in marks}


def check_rows(qn, oracle_rows, rows):
    from spark_rapids_tpu.testing.asserts import assert_rows_equal

    assert_rows_equal(oracle_rows, rows, ignore_order=qn in UNORDERED,
                      approximate_float=FLOAT_TOLERANCE)


def timed_execute(sess, df, watch):
    """One ``Session.execute``: seconds (the rows are on the host when
    it returns, so the device is done), the kernel cache's and XLA's
    compile counters, the degrade level."""
    mark = watch.snapshot()
    t0 = time.perf_counter()
    batch = sess.execute(df.plan)
    secs = time.perf_counter() - t0
    rows = batch.to_rows()
    m = sess.last_metrics
    check(m["fault.degradeLevel"] == 0,
          f"degraded to level {m['fault.degradeLevel']}")
    stats = {"seconds": round(secs, 3),
             "kernel_programs_compiled": m.get("kernelCache.misses"),
             "kernel_compile_s": round(
                 m.get("kernelCache.compileTimeNs", 0) / 1e9, 2),
             **watch.since(mark)}
    return rows, stats


def result_platforms(sess, df):
    """Platforms of the devices that hold the final stage's arrays,
    read before anything is downloaded (the columnar export peels the
    device->host transition off the same cached plan)."""
    return sorted({d.platform for b in sess.execute_columnar(df.plan)
                   for c in b.columns for d in c.data.devices()})


def run_one_chip(args, devices, watch, scratch):
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.exec.kernel_cache import GLOBAL as kernel_cache

    platform = devices[0].platform
    cache = args.compile_cache
    path = make_tables(args, scratch)

    queries = (6, 1, 3, 16)
    t0 = time.perf_counter()
    host = srt.Session(tpu_enabled=False)
    host_tables = read_tables(host, path)
    oracle = {qn: tpch.QUERIES[qn](host_tables).collect() for qn in queries}
    note("oracle", rows={f"q{qn}": len(r) for qn, r in oracle.items()},
         seconds=round(time.perf_counter() - t0, 1))

    sess = srt.Session(dict(CONF))
    note("session", conf=CONF, compile_cache_dir=cache,
         compile_cache_entries=cache_entries(cache),
         upload="one batched jax.device_put per batch",
         donation_active=kernel_cache.donation_active(),
         memory=memory_note(devices[:1], sess.device_manager))
    tables = read_tables(sess, path)

    for qn in queries:
        df = tpch.QUERIES[qn](tables)
        host_ops = check_explain(df)
        cold_rows, cold = timed_execute(sess, df, watch)
        check_rows(qn, oracle[qn], cold_rows)
        warm_rows, warm = timed_execute(sess, df, watch)
        check_rows(qn, oracle[qn], warm_rows)
        check(warm["kernel_programs_compiled"] == 0,
              f"q{qn} warm run compiled "
              f"{warm['kernel_programs_compiled']} kernel programs")
        held_on = result_platforms(sess, df)
        check(held_on == [platform],
              f"q{qn} result arrays are on {held_on}, not {platform}")
        note(f"q{qn}", rows=len(cold_rows), equals_oracle=True,
             host_by_design=host_ops, result_arrays_on=held_on,
             cold=cold, warm=warm,
             memory=memory_note(devices[:1], sess.device_manager))

    # the other two front doors: the scheduler, a prepared statement
    q6 = tpch.QUERIES[6](tables)
    t0 = time.perf_counter()
    got = sess.submit(q6).result(timeout=600).to_rows()
    check_rows(6, oracle[6], got)
    note("submit", query="q6", equals_oracle=True,
         seconds=round(time.perf_counter() - t0, 3))

    stmt = sess.prepare(q6)
    check(0.05 in stmt.defaults and 0.07 in stmt.defaults,
          f"q6's literals were not extracted: {stmt.defaults}")
    rebound = [0.03 if v == 0.05 else 0.05 if v == 0.07 else v
               for v in stmt.defaults]
    t0 = time.perf_counter()
    got = stmt.execute(rebound).to_rows()
    want = host.execute(host.prepare(tpch.QUERIES[6](host_tables))
                        .bind(rebound)).to_rows()
    check_rows(6, want, got)
    check(got != oracle[6], "the rebinding did not change q6's answer")
    note("prepared", query="q6", rebound="l_discount in [0.03, 0.05]",
         equals_oracle=True, seconds=round(time.perf_counter() - t0, 3))
    sess.close()

    # the persistent cache, hit from a fresh in-process start: drop every
    # compiled program, then a second Session answers q6 again
    import jax

    entries_after = cache_entries(cache)
    kernel_cache.reset()
    jax.clear_caches()
    second = srt.Session(dict(CONF))
    df = tpch.QUERIES[6](read_tables(second, path))
    again_rows, again = timed_execute(second, df, watch)
    check_rows(6, oracle[6], again_rows)
    if cache is not None:
        # programs under compile_cache.MIN_COMPILE_SECS are not kept and
        # compile again; the ones that were kept must be found (a miss
        # here is a program that hovers around the threshold)
        check(again["persistent_cache_hits"] > 0,
              f"second session missed the compile cache: {again}")
    second.close()
    note("compile_cache", dir=cache, entries_after=entries_after,
         second_session_q6=again, total=watch.snapshot())


def run_four_chips(args, devices, watch, scratch):
    """Only the mesh path and what it is compared with: q3 and q5
    through ``run_distributed`` over four devices, q3 with broadcast
    joins off so a shuffled join's all_to_all really runs."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.parallel.runner import run_distributed

    path = make_tables(args, scratch)
    host = srt.Session(tpu_enabled=False)
    host_tables = read_tables(host, path)
    stages = StageNotes()

    for qn, extra in ((3, {"spark.rapids.tpu.sql.broadcastSizeThreshold":
                           0}), (5, {})):
        t0 = time.perf_counter()
        oracle = tpch.QUERIES[qn](host_tables).collect()
        oracle_s = time.perf_counter() - t0
        sess = srt.Session({**CONF, **extra})
        df = tpch.QUERIES[qn](read_tables(sess, path))
        host_ops = check_explain(df)
        mark = watch.snapshot()
        timer = deadline(MESH_QUERY_DEADLINE_S, f"q{qn} on the mesh",
                         stages, scratch)
        t0 = time.perf_counter()
        got = run_distributed(sess, df, n_devices=4).to_rows()
        secs = time.perf_counter() - t0
        timer.cancel()
        check_rows(qn, oracle, got)
        m = sess.last_metrics
        check(m["fault.degradeLevel"] == 0,
              f"degraded to level {m['fault.degradeLevel']}")
        check(m.get("distributed.numShardDevices") == 4,
              f"leaf shards sit on {m.get('distributed.numShardDevices')}"
              " devices, not 4")
        check(m.get("shuffle.collectiveTimeNs", 0) > 0,
              f"no exchange-bearing mesh program ran for q{qn}")
        note(f"q{qn}.mesh", conf=extra, rows=len(got), equals_oracle=True,
             host_by_design=host_ops, shard_devices=4,
             collective_dispatch_s=round(
                 m["shuffle.collectiveTimeNs"] / 1e9, 2),
             seconds=round(secs, 2), oracle_seconds=round(oracle_s, 1),
             compile_cache_dir=args.compile_cache, **watch.since(mark),
             memory=memory_note(devices[:4]))
        sess.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh path, over four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the phases at toy size on the CPU backend")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not (
            args.rehearse and device["platform"] == "cpu"):
        sys.exit(f"chip_smoke: needs a TPU, jax.devices() gave platform "
                 f"{device['platform']!r} ({device['kind']})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, jax.devices() gave {len(devices)}")
    args.sf = REHEARSAL_SF if args.rehearse else \
        MESH_SF if args.chips == 4 else SF
    watch = CompileWatch()
    native = build_native(empty_first=not args.rehearse)
    from spark_rapids_tpu.utils import compile_cache

    args.compile_cache = compile_cache.enable()
    note("start", device=device, sf=args.sf, chips=args.chips,
         rehearsal=args.rehearse, native=native, jax=jax.__version__)

    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            run_four_chips(args, devices, watch, scratch)
        else:
            run_one_chip(args, devices, watch, scratch)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    if args.chips == 4:
        device["count"] = 4
    if device["platform"] != "tpu":
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}), flush=True)
        return
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
