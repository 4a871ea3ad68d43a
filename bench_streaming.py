"""Micro-batch streaming bench (ISSUE 12 tentpole).

Streams TPC-H q1 over a lineitem directory that grows by one parquet
chunk per tick and reports what a continuous-query operator cares
about:

* per-batch latency p50/p99 — split into the cold first tick and the
  warm incremental tail (the whole point of the subsystem),
* recompute fraction per tick (resumed stages / stamped stages) —
  must drop below 1.0 from the second tick on,
* merged-exchange and resumed-stage counts from the stream's own
  ``streaming.*`` progress metrics,
* correctness — the final batch is compared bit-for-bit against a
  cold full recompute of the same cumulative input, in every round,
* fault counters — injection rounds (``--inject all``) corrupt the
  exchange write path / crash the exchange read path mid-stream and
  report how many injections fired and how many checkpoints were
  quarantined while the answers stayed bit-identical.

Usage::

    python bench_streaming.py                       # 6 ticks, no faults
    python bench_streaming.py --inject all          # + corrupt round
    python bench_streaming.py --ticks 8 --out STREAM_r02.json

The artifact (default ``STREAM_r01.json``) is written atomically — a
kill mid-run never leaves a truncated JSON.
"""
import argparse
import os
import shutil
import sys
import tempfile
import time

FAST = {
    "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
    "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
}

#: artifact schema version (see bench.py SCHEMA_VERSION): comparison
#: tooling refuses to diff artifacts across versions
SCHEMA_VERSION = 2

INJECT_CONFS = {
    "none": {},
    # corrupt fires on WRITE sites only (read-side CRC catches it at
    # the checkpoint read-back, which disables checkpointing for that
    # batch — the stream degrades to full recompute, never to a wrong
    # answer), so recompute fraction is NOT asserted for this round
    "corrupt": {
        "spark.rapids.tpu.fault.injection.mode": "nth",
        "spark.rapids.tpu.fault.injection.type": "corrupt",
        "spark.rapids.tpu.fault.injection.site": "exchange.write",
        "spark.rapids.tpu.fault.injection.skipCount": 2,
        "spark.rapids.tpu.sql.taskRetries": 3,
    },
    "crash": {
        "spark.rapids.tpu.fault.injection.mode": "nth",
        "spark.rapids.tpu.fault.injection.type": "stage_crash",
        "spark.rapids.tpu.fault.injection.site": "exchange.read",
        "spark.rapids.tpu.fault.injection.skipCount": 2,
        "spark.rapids.tpu.sql.taskRetries": 3,
    },
}

#: rounds where injected damage may disable checkpointing, so the
#: warm recompute fraction is reported but not asserted
NO_FRACTION_ASSERT = {"corrupt"}


def _pct(vals, q):
    if not vals:
        return None
    s = sorted(vals)
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return round(s[i], 3)


def _norm(rows):
    return sorted(
        (tuple((None if v is None else
                (round(v, 9) if isinstance(v, float) else v))
               for v in r) for r in rows),
        key=repr)


def _chunks(tbl, k):
    return [i * tbl.num_rows // k for i in range(k + 1)]


def run_round(inject, args, li_table, workdir):
    import pyarrow.parquet as pq

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.benchmarks import tpch, tpch_datagen

    root = os.path.join(workdir, f"rec-{inject}")
    data = os.path.join(workdir, f"lineitem-{inject}")
    os.makedirs(data)
    # ticks batches consume chunks 0..ticks (the first batch sees two
    # files), plus one chunk reserved for the post-restart resume probe
    cuts = _chunks(li_table, args.ticks + 2)

    def write_chunk(i):
        pq.write_table(li_table.slice(cuts[i], cuts[i + 1] - cuts[i]),
                       os.path.join(data, f"part-{i:03d}.parquet"))

    conf = dict(FAST)
    conf.update({
        "spark.rapids.tpu.recovery.enabled": True,
        "spark.rapids.tpu.recovery.dir": root,
        "spark.rapids.tpu.streaming.enabled": True,
        "spark.rapids.tpu.telemetry.enabled": True,
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
    })
    conf.update(INJECT_CONFS[inject])
    sess = srt.Session(conf)

    def query(s):
        tables = tpch_datagen.dataframes(s, sf=args.sf)
        tables["lineitem"] = s.read_parquet(data)
        return tpch.QUERIES[args.query](tables)

    write_chunk(0)
    write_chunk(1)  # start with 2 files so the plan shape is warm
    handle = sess.stream(query(sess), trigger=0)
    ticks = []
    faults = {"injections_fired": 0, "checkpoints_quarantined": 0}
    last_out = None
    for b in range(1, args.ticks + 1):
        if b > 1:
            write_chunk(b)
        last_out = handle.process_available()
        prog = handle.progress()
        ticks.append({
            "batch_id": prog["streaming.batchId"],
            "files_total": prog["streaming.filesTotal"],
            "latency_ms": prog["streaming.batchLatencyMs"],
            "recompute_fraction": prog["streaming.recomputeFraction"],
            "stages_resumed": prog["streaming.stagesResumed"],
            "stages_total": prog["streaming.stagesTotal"],
            "merged_exchanges": prog["streaming.mergedExchanges"],
        })
        prof = sess.last_profile
        if prof is not None:
            for e in prof.events.snapshot():
                if e["event"] == "fault_injected":
                    faults["injections_fired"] += 1
                elif e["event"] == "checkpoint_quarantine":
                    faults["checkpoints_quarantined"] += 1
        print(f"  [{inject}] batch {prog['streaming.batchId']}: "
              f"{prog['streaming.batchLatencyMs']:.0f}ms, "
              f"recompute={prog['streaming.recomputeFraction']}, "
              f"resumed={prog['streaming.stagesResumed']}"
              f"/{prog['streaming.stagesTotal']}, "
              f"merged={prog['streaming.mergedExchanges']}")
    final = handle.process_available()  # no new files -> skipped tick
    assert final is None, "tick without new files must skip"
    handle.stop()

    # correctness: cold full recompute of the same cumulative input
    oracle_sess = srt.Session(dict(FAST, **{
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0}))
    want = _norm(query(oracle_sess).collect())
    got = _norm(zip(*[c.to_pylist() for c in last_out.columns]))
    mismatches = int(got != want)

    # re-open the stream after stop() — the resume path: ledger + pinned
    # checkpoints survive the handle, one more chunk exercises merge
    resume_sess = srt.Session(conf)
    h2 = resume_sess.resume_stream(query(resume_sess), trigger=0)
    assert h2.resumed, "durable ledger must survive stop()"
    write_chunk(args.ticks + 1)  # reserved chunk: resume + merge
    out = h2.process_available()
    resumed_prog = h2.progress()
    h2.stop()
    oracle2 = srt.Session(dict(FAST, **{
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0}))
    want2 = _norm(query(oracle2).collect())
    got2 = _norm(zip(*[c.to_pylist() for c in out.columns]))
    mismatches += int(got2 != want2)

    warm = [t["latency_ms"] for t in ticks[1:]]
    fractions = [t["recompute_fraction"] for t in ticks]
    result = {
        "inject": inject,
        "ticks": ticks,
        "first_batch_ms": ticks[0]["latency_ms"] if ticks else None,
        "warm_p50_ms": _pct(warm, 0.50),
        "warm_p99_ms": _pct(warm, 0.99),
        "recompute_fraction_after_first": fractions[1:],
        "max_warm_recompute_fraction": max(fractions[1:], default=None),
        "resume_after_restart": {
            "resumed_ledger": True,
            "stages_resumed": resumed_prog["streaming.stagesResumed"],
            "recompute_fraction":
                resumed_prog["streaming.recomputeFraction"],
        },
        "faults": faults,
        "mismatches": mismatches,
        "bit_identical": mismatches == 0,
    }
    if inject not in NO_FRACTION_ASSERT:
        assert all(f < 1.0 for f in fractions[1:]), (
            "incremental reuse never engaged: recompute fractions "
            f"{fractions}")
    if inject != "none":
        assert faults["injections_fired"] > 0, (
            f"round {inject!r} never injected — vacuous drill")
    assert mismatches == 0, "streamed result diverged from cold oracle"
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ticks", type=int, default=6,
                    help="number of committed micro-batches (>= 2)")
    ap.add_argument("--sf", type=float, default=0.001,
                    help="TPC-H scale factor for the generated data")
    ap.add_argument("--query", type=int, default=1,
                    help="TPC-H query number to stream")
    ap.add_argument("--inject",
                    choices=["none", "all", "corrupt", "crash"],
                    default="none",
                    help="fault rounds to run on top of the clean one")
    ap.add_argument("--out", default="STREAM_r01.json")
    args = ap.parse_args(argv)
    if args.ticks < 2:
        ap.error("--ticks must be >= 2 (one cold + one incremental)")

    import pyarrow as pa

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.benchmarks import tpch_datagen
    from spark_rapids_tpu.io.arrow_convert import host_batch_to_arrow
    from spark_rapids_tpu.utils import fsio

    t0 = time.time()
    gen = srt.Session(dict(FAST))
    li = tpch_datagen.dataframes(gen, sf=args.sf)["lineitem"]
    li_table = pa.concat_tables(
        [host_batch_to_arrow(b) for b in li.plan.batches])
    print(f"lineitem: {li_table.num_rows} rows across {args.ticks} "
          "chunks")

    rounds = ["none"]
    if args.inject == "all":
        rounds += [r for r in INJECT_CONFS if r != "none"]
    elif args.inject != "none":
        rounds.append(args.inject)

    workdir = tempfile.mkdtemp(prefix="srt-stream-bench-")
    results = {}
    try:
        for inject in rounds:
            print(f"round: inject={inject}")
            results[inject] = run_round(inject, args, li_table, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    doc = {
        "metric": "streaming_microbatch",
        "schema_version": SCHEMA_VERSION,
        "query": args.query,
        "sf": args.sf,
        "ticks": args.ticks,
        "rows": li_table.num_rows,
        "elapsed_s": round(time.time() - t0, 1),
        "rounds": results,
    }
    fsio.atomic_write_json(os.path.abspath(args.out), doc)
    print(f"wrote {args.out}")
    clean = results["none"]
    print(f"first batch {clean['first_batch_ms']:.0f}ms, warm p50 "
          f"{clean['warm_p50_ms']}ms / p99 {clean['warm_p99_ms']}ms, "
          f"max warm recompute fraction "
          f"{clean['max_warm_recompute_fraction']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
