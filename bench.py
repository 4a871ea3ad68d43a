"""Driver benchmark: TPCx-BB mini + TPC-H suite on the device engine.

Prints one JSON *progress* line per query as it completes, then the
summary line LAST: {"metric", "value", "unit", "vs_baseline", ...} —
so a timeout still leaves per-query evidence behind.

One process, on whatever ``jax.devices()`` gives: nothing here picks,
probes or changes a platform.  The platform, ``device_kind`` and device
count found are stamped into the summary, a phase that raises ends the
run with a non-zero exit, and a number is a device number only when the
stamp says ``tpu``.  (``chip_smoke.py`` is the script that REFUSES to
run without a chip; rebuilding this benchmark around chip cells is
ROADMAP Speed 1.)

The TPCx-BB mini-suite (the BASELINE north star) runs FIRST; TPC-H and
the microbenches follow in the remaining budget.

value = aggregate effective throughput (GB/s of query input bytes) over
five TPC-H queries — q1 (agg-heavy), q3/q5 (join-heavy), q6 (filter),
q16 (strings + anti join) — end-to-end through the engine (host->device
upload, device kernels, device->host collect), with the batch target
lowered so multi-batch/out-of-core operator paths are exercised.

vs_baseline = suite throughput over the best CPU engine per query: the
in-repo host oracle vs a pandas (BLAS/numpy-backed) implementation of
the same queries — the defensible external CPU baseline available in
this image (reference frames vs CPU Spark, README.md:18-20).

The whole run works against a wall-clock budget (``SRT_BENCH_BUDGET_S``,
default 270s): iteration counts shrink once the deadline nears, and the
trailing microbenches are skipped.  Compiled programs are kept in JAX's
persistent cache where ``spark_rapids_tpu.utils.compile_cache`` puts it.

Extra fields (recorded alongside, same JSON object):
  per_query:   best seconds / GB/s / speedup per query
  noise_pct:   per-query iteration spread (max-min)/best * 100
  shuffle:     device shuffle-write microbench (tile prep for the
               collective exchange, parallel/exchange.py) in GB/s
  q1_pipeline: the historical single-kernel Q1 Mrows/s (r01/r02 metric)
"""
import json
import os
import sys
import time

SF = float(os.environ.get("SRT_BENCH_SF", "0.1"))
QUERY_TABLES = {
    1: ["lineitem"],
    3: ["customer", "orders", "lineitem"],
    5: ["region", "nation", "customer", "orders", "lineitem", "supplier"],
    6: ["lineitem"],
    16: ["part", "partsupp", "supplier"],
}
ITERS = 3
#: Artifact schema version, stamped into every summary (BENCH_LAST
#: and the line printed to stdout).  ``--compare``
#: refuses to diff artifacts across versions: a regression gate that
#: silently compares renamed/re-scoped fields reports garbage.  Bump
#: whenever per_query/kernels field semantics change.
SCHEMA_VERSION = 2
#: wall-clock budget: ``--budget-s`` on the CLI or SRT_BENCH_BUDGET_S.
#: Past the budget, remaining queries are marked ``"skipped": "budget"``
#: and the partial summary still lands atomically.
BUDGET_S = float(os.environ.get("SRT_BENCH_BUDGET_S", "270"))
_T0 = time.perf_counter()
# default (large) batch targets: the bench measures peak engine
# throughput — one batch per partition, one compiled program per op.
PRESSURE_CONF = {}
# the out-of-core section (_ooc_bench) runs q3 under THIS conf — small
# batch target so the grace join / chunked operator paths engage.  r4
# had to retreat from pressure confs because per-bucket-pair shapes
# traced ~200s of grace-join programs; the shape-unification fix
# (exec/joins.py _join_grace) bounds that to one program per level,
# and compile_frac in the output guards the regression.
OOC_CONF = {
    "spark.rapids.tpu.sql.batchSizeBytes": 8 * 1024 * 1024,
    "spark.rapids.tpu.sql.reader.batchSizeRows": 1 << 17,
}


def _deadline() -> float:
    return _T0 + BUDGET_S


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _best(fn, iters=ITERS, warmup=1, deadline=None):
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
        if deadline is not None and time.perf_counter() > deadline:
            break
    best = min(times)
    noise = (max(times) - best) / best * 100.0
    return best, noise


def _pandas_tables(raw):
    import pandas as pd

    return {name: pd.DataFrame(
        {c: v for c, v in cols.items()})
        for name, (schema, cols) in raw.items()}


def _d(y, m, d):
    from spark_rapids_tpu.benchmarks.tpch_datagen import days

    return days(y, m, d)


def _pandas_queries():
    import pandas as pd

    def q1(t):
        li = t["lineitem"]
        li = li[li.l_shipdate <= _d(1998, 9, 2)].copy()
        li["disc_price"] = li.l_extendedprice * (1.0 - li.l_discount)
        li["charge"] = li.disc_price * (1.0 + li.l_tax)
        g = li.groupby(["l_returnflag", "l_linestatus"]).agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            count_order=("l_quantity", "count"))
        return g.reset_index().sort_values(
            ["l_returnflag", "l_linestatus"])

    def q3(t):
        cust = t["customer"]
        cust = cust[cust.c_mktsegment == "BUILDING"][["c_custkey"]]
        orders = t["orders"]
        orders = orders[orders.o_orderdate < _d(1995, 3, 15)]
        li = t["lineitem"]
        li = li[li.l_shipdate > _d(1995, 3, 15)].copy()
        j = cust.merge(orders, left_on="c_custkey", right_on="o_custkey")
        j = j.merge(li, left_on="o_orderkey", right_on="l_orderkey")
        j["revenue"] = j.l_extendedprice * (1.0 - j.l_discount)
        g = (j.groupby(["o_orderkey", "o_orderdate", "o_shippriority"])
             ["revenue"].sum().reset_index())
        return g.sort_values(["revenue", "o_orderdate"],
                             ascending=[False, True]).head(10)

    def q5(t):
        region = t["region"]
        region = region[region.r_name == "ASIA"]
        nation = t["nation"].merge(region, left_on="n_regionkey",
                                   right_on="r_regionkey")
        orders = t["orders"]
        orders = orders[(orders.o_orderdate >= _d(1994, 1, 1))
                        & (orders.o_orderdate < _d(1995, 1, 1))]
        j = t["customer"].merge(nation[["n_nationkey", "n_name"]],
                                left_on="c_nationkey",
                                right_on="n_nationkey")
        j = j[["c_custkey", "c_nationkey", "n_name"]].merge(
            orders[["o_orderkey", "o_custkey"]],
            left_on="c_custkey", right_on="o_custkey")
        j = j.merge(t["lineitem"][["l_orderkey", "l_suppkey",
                                   "l_extendedprice", "l_discount"]],
                    left_on="o_orderkey", right_on="l_orderkey")
        j = j.merge(t["supplier"][["s_suppkey", "s_nationkey"]],
                    left_on=["l_suppkey", "c_nationkey"],
                    right_on=["s_suppkey", "s_nationkey"])
        j = j.copy()
        j["revenue"] = j.l_extendedprice * (1.0 - j.l_discount)
        return (j.groupby("n_name")["revenue"].sum().reset_index()
                .sort_values("revenue", ascending=False))

    def q6(t):
        li = t["lineitem"]
        m = ((li.l_shipdate >= _d(1994, 1, 1))
             & (li.l_shipdate < _d(1995, 1, 1))
             & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
             & (li.l_quantity < 24.0))
        sel = li[m]
        return pd.DataFrame(
            {"revenue": [(sel.l_extendedprice * sel.l_discount).sum()]})

    def q16(t):
        part = t["part"]
        part = part[(part.p_brand != "Brand#45")
                    & ~part.p_type.str.startswith("MEDIUM POLISHED")
                    & part.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
        bad = t["supplier"]
        bad = bad[bad.s_comment.str.contains("Customer Complaints")]
        ps = t["partsupp"][["ps_partkey", "ps_suppkey"]]
        ps = ps[~ps.ps_suppkey.isin(bad.s_suppkey)]
        j = ps.merge(part[["p_partkey", "p_brand", "p_type", "p_size"]],
                     left_on="ps_partkey", right_on="p_partkey")
        g = (j.groupby(["p_brand", "p_type", "p_size"])["ps_suppkey"]
             .nunique().reset_index(name="supplier_cnt"))
        return g.sort_values(
            ["supplier_cnt", "p_brand", "p_type", "p_size"],
            ascending=[False, True, True, True])

    return {1: q1, 3: q3, 5: q5, 6: q6, 16: q16}


def _table_bytes(raw):
    from spark_rapids_tpu.data.column import HostBatch

    out = {}
    for name, (schema, cols) in raw.items():
        hb = HostBatch.from_pydict({c: v for c, v in cols.items()}, schema)
        out[name] = hb.estimate_bytes()
    return out


def _shuffle_microbench():
    """Shuffle-write path, one entry per ``shuffle.mode``:

    * ``device`` — partition ids + the packed partition-build kernel;
      the block stays in HBM (zero host copies by construction, the
      property the host-sync analysis rule pins at the AST level).
    * ``host``   — the staged path the device mode replaced: d2h of
      the whole batch, CRC32C stamp of every column frame, h2d
      promote.  The device/host GB/s ratio is the headline win.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.data.column import (HostBatch, device_to_host,
                                              host_to_device)
    from spark_rapids_tpu.fault.integrity import checksum_frame
    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.shuffle import device_shuffle as DS

    n = 1 << 20
    rng = np.random.RandomState(0)
    hb = HostBatch.from_pydict({
        "k": rng.randint(0, 1 << 30, n).astype(np.int64),
        "a": rng.rand(n),
        "b": rng.rand(n),
        "c": rng.randint(0, 100, n).astype(np.int64),
    })
    db = host_to_device(hb)
    nbytes = db.device_bytes()
    P = 8

    def device_write(batch):
        pids = X.device_partition_ids(batch, [0], P)
        return DS.packed_build(batch, pids, P)

    jfn = jax.jit(device_write)
    jax.block_until_ready(jfn(db))

    def run_device():
        jax.block_until_ready(jfn(db))

    dev_best, dev_noise = _best(run_device, iters=ITERS)

    pid_fn = jax.jit(
        lambda batch: X.device_partition_ids(batch, [0], P))
    jax.block_until_ready(pid_fn(db))

    def run_host():
        jax.block_until_ready(pid_fn(db))
        staged = device_to_host(db, trim=False)
        for col in staged.columns:
            checksum_frame(np.ascontiguousarray(col.data).view(np.uint8)
                           if col.data.dtype != np.uint8 else col.data)
        jax.block_until_ready(host_to_device(staged).columns[0].data)

    host_best, host_noise = _best(run_host, iters=ITERS)
    return {
        "rows": n, "bytes": nbytes,
        "device": {"gb_per_s": round(nbytes / dev_best / 1e9, 3),
                   "noise_pct": round(dev_noise, 1),
                   "host_copy_bytes": 0},
        "host": {"gb_per_s": round(nbytes / host_best / 1e9, 3),
                 "noise_pct": round(host_noise, 1)},
        "device_vs_host": round(host_best / dev_best, 2),
    }


def _q3_exchange_breakdown():
    """Wall decomposition of one q3-shaped exchange round at 128K rows
    (sized so the emulated-mesh collective fits the bench budget):
    the packed partition-build kernel (map side), the mesh collective
    dispatch (`exchange_step` over every local device), and the
    reduce-side concat of the received slices.  On a 1-device mesh the
    collective degenerates to a copy — the number is still emitted so
    every run produces the same JSON shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.data.column import HostBatch, host_to_device
    from spark_rapids_tpu.exec.coalesce import concat_device_batches
    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.parallel.mesh import make_mesh
    from spark_rapids_tpu.shuffle import device_shuffle as DS

    n = 1 << 17
    rng = np.random.RandomState(3)
    # q3's exchange ships (custkey, orderkey, revenue terms)
    hb = HostBatch.from_pydict({
        "o_custkey": rng.randint(0, 150_000, n).astype(np.int64),
        "l_orderkey": rng.randint(0, n, n).astype(np.int64),
        "l_extendedprice": rng.rand(n) * 1e5,
        "l_discount": rng.rand(n) * 0.1,
    })
    db = host_to_device(hb)
    P = 8

    build = jax.jit(lambda b: DS.packed_build(
        b, X.device_partition_ids(b, [0], P), P))
    block, counts, starts = build(db)
    jax.block_until_ready(block.columns[0].data)
    def run_build():
        blk, _c, _s = build(db)
        jax.block_until_ready(blk.columns[0].data)

    build_s, _ = _best(run_build, iters=ITERS)

    mesh = make_mesh()
    n_dev = mesh.devices.size
    per = db.padded_rows // n_dev

    def local(b):
        pids = X.device_partition_ids(b, [0], n_dev)
        return X.collective_exchange(b, pids, n_dev,
                                     mesh.axis_names[0], capacity=per)

    stacked = X.stack_to_mesh(
        mesh, X.stack_partitions(_even_split(db, n_dev)))
    step = X.exchange_step(mesh, local)
    jax.block_until_ready(step(stacked).columns[0].data)
    # a virtual CPU mesh's collective carries a large fixed dispatch
    # cost: bound it to 2 timed iters under a hard deadline
    coll_s, _ = _best(
        lambda: jax.block_until_ready(step(stacked).columns[0].data),
        iters=2, warmup=0,
        deadline=time.perf_counter() + 30)

    got = DS.fetch_counts([(counts, starts)])
    c_np, s_np = got[0]
    slices = [DS.packed_slice(block, jnp.int32(int(s_np[p])),
                              jnp.int32(int(c_np[p])))
              for p in range(P) if int(c_np[p])]
    jax.block_until_ready(slices[0].columns[0].data)
    concat_s, _ = _best(
        lambda: jax.block_until_ready(
            concat_device_batches(slices, 128).columns[0].data),
        iters=ITERS)

    return {"rows": n, "n_devices": int(n_dev),
            "partition_build_s": round(build_s, 5),
            "collective_s": round(coll_s, 5),
            "concat_s": round(concat_s, 5)}


def _even_split(db, k):
    """Split a DeviceBatch into k equal-padded shards (bench-local
    helper for mesh placement)."""
    from spark_rapids_tpu.data.column import DeviceBatch, DeviceColumn
    import jax.numpy as jnp

    per = db.padded_rows // k
    out = []
    for i in range(k):
        lo, hi = i * per, (i + 1) * per
        cols = [DeviceColumn(c.dtype, c.data[lo:hi], c.validity[lo:hi],
                             c.lengths[lo:hi]
                             if c.lengths is not None else None)
                for c in db.columns]
        nr = jnp.clip(jnp.asarray(db.num_rows, dtype=jnp.int32) - lo,
                      0, per)
        out.append(DeviceBatch(db.schema, cols, nr))
    return out


def _q6_scan_breakdown(raw, iters=3):
    """Scan-bound q6 from PARQUET files: end-to-end wall vs host-decode
    wall, so scan-bound queries stop silently measuring pyarrow
    (VERDICT r3 #9).  decode_frac is the share of the end-to-end time a
    pure host pyarrow decode of the projected columns takes; the
    decode/upload prefetch pipeline (exec/transitions.py) is what keeps
    the device busy under it (reference intent: semaphore held only for
    device work, GpuParquetScan.scala:554-556)."""
    import shutil
    import tempfile

    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.io.scans import expand_paths
    from spark_rapids_tpu.session import Session

    schema, cols = raw["lineitem"]
    tmp = tempfile.mkdtemp(prefix="srt_bench_q6_")
    try:
        path = os.path.join(tmp, "lineitem")
        host = Session(tpu_enabled=False)
        host.create_dataframe(
            {c: v for c, v in cols.items()}, schema,
            n_partitions=4).write_parquet(path)
        files = [f for f in expand_paths([path])]
        fbytes = sum(os.path.getsize(f) for f in files)

        tpu = Session(dict(PRESSURE_CONF))
        q6 = tpch.QUERIES[6]({"lineitem": tpu.read_parquet(path)})
        total_s, _ = _best(lambda: q6.collect(), iters=iters, warmup=1)

        import pyarrow.parquet as paq

        needed = ["l_shipdate", "l_discount", "l_quantity",
                  "l_extendedprice"]

        def decode_only():
            for f in files:
                paq.read_table(f, columns=needed)

        decode_s, _ = _best(decode_only, iters=iters, warmup=1)
        return {"total_s": round(total_s, 4),
                "host_decode_s": round(decode_s, 4),
                "decode_frac": round(decode_s / total_s, 3),
                "file_gb_per_s": round(fbytes / total_s / 1e9, 3)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _aqe_decisions(metrics):
    """The aqe.num* decision counters from a finished query's metrics
    (how many joins converted / partitions coalesced / skew splits the
    adaptive driver actually performed)."""
    return {k.split(".", 1)[1]: int(v) for k, v in (metrics or {}).items()
            if k.startswith("aqe.num")}


def _aqe_exchange_delta(raw, deadline=None):
    """AQE satellite: q3/q5 wall and exchange wall, adaptive on vs
    off, on force-shuffled plans (the static broadcast shortcut at
    this scale factor would leave dynamic conversion nothing to do).
    The decision counts ride along so a delta is attributable to
    specific rewrites rather than noise."""
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.session import Session

    def exchange_wall_s(m):
        return sum(v for k, v in (m or {}).items()
                   if "ShuffleExchangeExec" in k
                   and k.endswith("totalTime")) / 1e9

    out = {}
    for qn in (3, 5):
        rec = {}
        for mode, enabled in (("adaptive", True), ("static", False)):
            sess = Session({
                **PRESSURE_CONF,
                "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
                "spark.rapids.tpu.sql.adaptive.enabled": enabled})
            tables = {name: sess.create_dataframe(
                {c: v for c, v in cols.items()}, schema)
                for name, (schema, cols) in raw.items()}
            df = tpch.QUERIES[qn](tables)
            df.collect()  # compile-inclusive warmup
            wall, _ = _best(lambda: df.collect(), iters=3, warmup=0,
                            deadline=deadline)
            m = sess.last_metrics or {}
            rec[mode] = {"wall_s": round(wall, 4),
                         "exchange_wall_s": round(exchange_wall_s(m), 4)}
            if enabled:
                rec["decisions"] = _aqe_decisions(m)
        rec["exchange_delta_s"] = round(
            rec["static"]["exchange_wall_s"]
            - rec["adaptive"]["exchange_wall_s"], 4)
        out[f"q{qn}"] = rec
    return out


def _ooc_bench(raw, sizes, deadline):
    """Out-of-core perf: TPC-H q3 (the query that blew the r4 budget)
    under OOC_CONF, so the grace-join/chunked-agg machinery gets a
    throughput number alongside the in-core suite.  first_run_s - q3_s
    is dominated by tracing/compiling; compile_frac near 1 with a huge
    first_run_s is the r4 trace-storm signature."""
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.session import Session

    names = ("customer", "orders", "lineitem")
    sess = Session(dict(OOC_CONF))
    tables = {name: sess.create_dataframe(
        {c: v for c, v in cols.items()}, schema)
        for name, (schema, cols) in raw.items() if name in names}
    df = tpch.QUERIES[3](tables)
    t0 = time.perf_counter()
    df.collect()
    warm_s = time.perf_counter() - t0
    if time.perf_counter() + warm_s > deadline:
        return {"q3_first_run_s": round(warm_s, 4), "partial": True}
    best, _ = _best(lambda: df.collect(), iters=2, warmup=0,
                    deadline=deadline)
    qbytes = sum(sizes[t] for t in names)
    return {"q3_s": round(best, 4),
            "gb_per_s": round(qbytes / best / 1e9, 3),
            "first_run_s": round(warm_s, 4),
            "compile_frac": round(max(warm_s - best, 0.0)
                                  / max(warm_s, 1e-9), 3)}


def _tpcxbb_mini(deadline):
    """TPCx-BB mini-suite (the BASELINE north-star workload): four
    representative queries — q1 (retail basket join+agg), q9 (gated
    multi-predicate agg), q26 (clustering features), q30 (item
    affinity self-join) — steady-state seconds each."""
    from spark_rapids_tpu.benchmarks import tpcxbb, tpcxbb_datagen
    from spark_rapids_tpu.session import Session

    sess = Session(dict(PRESSURE_CONF))
    tables = tpcxbb_datagen.dataframes(sess, sf=0.01, seed=99)
    out = {}
    for qn in (1, 9, 26, 30):
        if time.perf_counter() > deadline:
            break
        df = tpcxbb.QUERIES[qn](tables)
        # warmup (cold XLA traces can be minutes on a fresh backend)
        # counts against the budget: time it, and stop the section
        # rather than the whole bench if it ate the slack
        t0 = time.perf_counter()
        df.collect()
        warm_s = time.perf_counter() - t0
        if time.perf_counter() + warm_s > deadline:
            out[f"q{qn}"] = round(warm_s, 4)  # cold number, better
            break                             # than silence
        best, _ = _best(lambda: df.collect(), iters=2, warmup=0,
                        deadline=deadline)
        out[f"q{qn}"] = round(best, 4)
    if not out:
        return None
    if len(out) == 4:  # geomean only over the FULL set — a partial
        # geomean silently drops the slow queries and reads as a win
        prod = 1.0
        for v in out.values():
            prod *= max(v, 1e-6)
        out["geomean_s"] = round(prod ** 0.25, 4)
    else:
        out["partial"] = True
    return out


def _q1_pipeline_mrows():
    import jax

    from spark_rapids_tpu.models.flagship import build_q1_pipeline

    n_rows = 1 << 20
    fn, example = build_q1_pipeline(n_rows=n_rows, seed=0)
    jfn = jax.jit(fn)
    # keep the operands device-resident: re-uploading host args every
    # iteration measures the upload, not the kernel
    example = jax.device_put(example)
    jax.block_until_ready(example)
    jfn(example).block_until_ready()

    def run():
        jfn(example).block_until_ready()

    best, noise = _best(run, iters=ITERS)
    return {"mrows_per_s": round(n_rows / best / 1e6, 1),
            "noise_pct": round(noise, 1)}


def _transfer_split(sess, wall_s):
    """upload/readback/compute wall decomposition of the most recent
    collect (VERDICT r4 #7): HostToDevice/DeviceToHost exec nanosecond
    metrics vs total wall.  d2h_s includes any device compute the final
    sync flushes — the split is a transfer-vs-engine attribution, not a
    kernel profile."""
    m = getattr(sess, "last_metrics", {}) or {}
    h2d = sum(v for k, v in m.items()
              if "HostToDevice" in k and k.endswith("totalTime")) / 1e9
    d2h = sum(v for k, v in m.items()
              if "DeviceToHost" in k and k.endswith("totalTime")) / 1e9
    return {"h2d_s": round(h2d, 4), "d2h_s": round(d2h, 4),
            "compute_s": round(max(wall_s - h2d - d2h, 0.0), 4)}


def _kernel_rows(sess, top_n=8):
    """Per-kernel roofline attribution of the most recent collect:
    dispatch counts, wall, rows/bytes throughput, and padding waste
    per compiled-kernel fingerprint, ranked by wall time (the warm
    iterations ride the kernel cache, so this is steady-state compute
    attribution, not compile time)."""
    stats = getattr(sess, "last_kernel_profile", None)
    if not stats:
        return None
    from spark_rapids_tpu.telemetry.profiler import roofline_rows

    return roofline_rows(stats,
                         getattr(sess, "last_h2d_ceiling_bps", 0.0),
                         top_n=top_n)


def _wall_per_dispatch(row):
    w, d = row.get("wall_s"), row.get("dispatches")
    if isinstance(w, (int, float)) and isinstance(d, (int, float)) and d:
        return w / d
    return None


#: elastic peer-loss drill fields emitted by the multichip dryrun
#: (__graft_entry__._dryrun_impl prints the MULTICHIP_ELASTIC marker
#: into the artifact's captured tail)
ELASTIC_FIELDS = ("degraded_devices", "respeculated_shards",
                  "mesh_shrink_count")

#: absolute floor for warm-p50 serving regressions: cache hits land in
#: single-digit milliseconds, where scheduler jitter easily exceeds the
#: relative threshold without meaning anything
SERVING_P50_FLOOR_MS = 50.0


def _elastic_summary(art):
    """The elastic drill counters of a MULTICHIP artifact, or None.

    Accepts either top-level fields or the ``MULTICHIP_ELASTIC {json}``
    marker line inside the artifact's captured ``tail`` (the external
    driver stores the dryrun's stdout there); the LAST marker wins."""
    if not isinstance(art, dict):
        return None
    if all(k in art for k in ELASTIC_FIELDS):
        return {k: art[k] for k in ELASTIC_FIELDS}
    tail = art.get("tail")
    if not isinstance(tail, str):
        return None
    out = None
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("MULTICHIP_ELASTIC "):
            continue
        try:
            rec = json.loads(line[len("MULTICHIP_ELASTIC "):])
        except ValueError:
            continue
        if isinstance(rec, dict):
            out = {k: rec.get(k, 0) for k in ELASTIC_FIELDS}
    return out


def compare_summaries(old, new, threshold=0.20):
    """Regression gate core: diff two bench summary artifacts.

    Returns a list of regression records — per-query warm (``tpu_s``)
    and cold (``cold_s``) times, and per-kernel wall-per-dispatch
    matched by kernel fingerprint — where the new value exceeds the
    old by more than ``threshold`` (default 20%).  Raises ValueError
    when the artifacts carry different ``schema_version``s: diffing
    renamed/re-scoped fields would report garbage, so the gate refuses
    and tells the caller to re-baseline instead.

    MULTICHIP artifacts additionally diff the elastic peer-loss drill
    (``_elastic_summary``): the drill DELIBERATELY kills a peer and
    stalls a shard, so the baseline's counters are the expected
    behaviour — detection regressing to zero (no mesh shrink where the
    baseline shrank, no speculative win where the baseline
    respeculated) or MORE devices degraded than the baseline are
    regressions.

    SERVING artifacts (``bench_serving.py`` — rounds carrying a
    ``warm`` replay phase) additionally diff the serving caches:
    per-tier warm p50 past the threshold AND a
    ``SERVING_P50_FLOOR_MS`` absolute floor (sub-floor jitter on
    single-digit-millisecond cache hits is noise, not regression), and
    lost cache-hit coverage — a ``cache_hit_rate`` that fell more than
    ``threshold`` below the baseline's means submissions that used to
    be served from the cache are executing again.  Artifacts without
    serving rounds skip this section entirely.
    """
    ov, nv = old.get("schema_version"), new.get("schema_version")
    if ov != nv:
        raise ValueError(
            f"schema mismatch: baseline artifact has schema_version="
            f"{ov!r} but the new artifact has {nv!r}; regression "
            f"deltas across schemas are meaningless — re-run the "
            f"bench to produce a fresh baseline")
    limit = 1.0 + threshold
    regs = []
    old_pq = old.get("per_query") or {}
    new_pq = new.get("per_query") or {}
    for q in sorted(set(old_pq) & set(new_pq)):
        o, n = old_pq[q], new_pq[q]
        if not isinstance(o, dict) or not isinstance(n, dict):
            continue
        for field in ("tpu_s", "cold_s"):
            b, v = o.get(field), n.get(field)
            if isinstance(b, (int, float)) and isinstance(v, (int, float)) \
                    and b > 0 and v > b * limit:
                regs.append({"query": q, "field": field,
                             "old": b, "new": v,
                             "ratio": round(v / b, 2)})
        by_fp = {r.get("kernel"): r for r in (o.get("kernels") or [])
                 if isinstance(r, dict)}
        for r in (n.get("kernels") or []):
            if not isinstance(r, dict):
                continue
            base = by_fp.get(r.get("kernel"))
            if base is None:
                continue  # new/recompiled kernel: no baseline to diff
            bwpd, nwpd = _wall_per_dispatch(base), _wall_per_dispatch(r)
            # sub-100µs dispatches are launch-latency noise, not
            # kernel-performance signal — skip them
            if bwpd and nwpd and bwpd > 1e-4 and nwpd > bwpd * limit:
                regs.append({"query": q, "kernel": r.get("kernel"),
                             "field": "wall_per_dispatch_s",
                             "old": round(bwpd, 6),
                             "new": round(nwpd, 6),
                             "ratio": round(nwpd / bwpd, 2)})
    o_el, n_el = _elastic_summary(old), _elastic_summary(new)
    if o_el is not None and n_el is not None:
        for field, bad_when in (("mesh_shrink_count", "lost"),
                                ("respeculated_shards", "lost"),
                                ("degraded_devices", "grew")):
            b, v = o_el.get(field), n_el.get(field)
            if not isinstance(b, (int, float)) \
                    or not isinstance(v, (int, float)):
                continue
            if (bad_when == "lost" and b > 0 and v <= 0) or \
                    (bad_when == "grew" and v > b):
                regs.append({"query": "elastic_drill", "field": field,
                             "old": b, "new": v})
    old_rounds = old.get("rounds") if isinstance(old.get("rounds"),
                                                 dict) else {}
    new_rounds = new.get("rounds") if isinstance(new.get("rounds"),
                                                 dict) else {}
    for mode in sorted(set(old_rounds) & set(new_rounds)):
        ow = (old_rounds[mode] or {}).get("warm") \
            if isinstance(old_rounds[mode], dict) else None
        nw = (new_rounds[mode] or {}).get("warm") \
            if isinstance(new_rounds[mode], dict) else None
        if not isinstance(ow, dict) or not isinstance(nw, dict):
            continue
        o_tiers = ow.get("per_tier") or {}
        n_tiers = nw.get("per_tier") or {}
        for tier in sorted(set(o_tiers) & set(n_tiers)):
            b = (o_tiers[tier] or {}).get("p50_ms")
            v = (n_tiers[tier] or {}).get("p50_ms")
            if isinstance(b, (int, float)) and isinstance(v, (int, float)) \
                    and b > 0 and v > b * limit \
                    and v - b > SERVING_P50_FLOOR_MS:
                regs.append({"query": f"serving.{mode}.{tier}",
                             "field": "warm_p50_ms",
                             "old": b, "new": v,
                             "ratio": round(v / b, 2)})
        b, v = ow.get("cache_hit_rate"), nw.get("cache_hit_rate")
        if isinstance(b, (int, float)) and isinstance(v, (int, float)) \
                and b > 0 and v < b - threshold:
            regs.append({"query": f"serving.{mode}",
                         "field": "cache_hit_rate", "old": b, "new": v})
    return regs


def compare_main(old_path, new_path, threshold=0.20):
    """CLI wrapper for the regression gate.  Exit codes: 0 = no
    regressions, 1 = regressions found, 2 = unusable inputs (missing
    file, bad JSON, schema mismatch)."""
    try:
        with open(old_path, "r", encoding="utf-8") as f:
            old = json.load(f)
        with open(new_path, "r", encoding="utf-8") as f:
            new = json.load(f)
    except (OSError, ValueError) as e:
        _emit({"compare": "error",
               "detail": f"{type(e).__name__}: {e}"[:300]})
        return 2
    try:
        regs = compare_summaries(old, new, threshold=threshold)
    except ValueError as e:
        _emit({"compare": "schema_mismatch", "detail": str(e),
               "old_schema": old.get("schema_version"),
               "new_schema": new.get("schema_version")})
        return 2
    _emit({"compare": "regressions" if regs else "ok",
           "threshold_pct": round(threshold * 100, 1),
           "old": os.path.basename(old_path),
           "new": os.path.basename(new_path),
           "regressions": regs})
    return 1 if regs else 0


def _atomic_write_json(path, obj) -> None:
    """Write a BENCH_* artifact atomically via the engine's shared
    temp+fsync+rename helper (spark_rapids_tpu/utils/fsio.py — the same
    discipline checkpoint manifests and spill frames use).  A
    crash/kill mid-write leaves the previous artifact intact instead
    of a truncated JSON — readers always see either the old file or
    the complete new one."""
    from spark_rapids_tpu.utils import fsio

    fsio.atomic_write_json(path, obj)


#: memoized verdict of the static-analysis gate (None = not yet run)
_ANALYSIS_GATE = None


def _analysis_gate() -> bool:
    """Whether artifacts may be persisted: the static-analysis engine
    (docs/static_analysis.md) must report no new findings — a
    measurement of a tree that violates the engine's own invariants is
    not a baseline worth comparing future runs against.  Fails OPEN on
    an engine crash: the gate protects artifact quality, it must never
    be the thing that loses a run's evidence."""
    global _ANALYSIS_GATE
    if _ANALYSIS_GATE is None:
        try:
            from spark_rapids_tpu.analysis import (AnalysisContext,
                                                   run_rules)
            from spark_rapids_tpu.analysis.baseline import (
                DEFAULT_BASELINE, Baseline)
            findings = run_rules(AnalysisContext())
            new, _supp, _stale = Baseline.load(
                DEFAULT_BASELINE).split(findings)
            if new:
                _emit({"analysis_gate": "refused",
                       "new_findings": len(new),
                       "first": new[0].render(),
                       "hint": "python -m spark_rapids_tpu.analysis"})
            _ANALYSIS_GATE = not new
        except Exception as e:  # noqa: BLE001 — gate fails open
            _emit({"analysis_gate": "fail-open", "error": repr(e)})
            _ANALYSIS_GATE = True
    return _ANALYSIS_GATE


def _persist_tpu_artifact(summary, path) -> None:
    """Write ``summary`` (stamped ``captured_at``) to ``path``
    atomically (temp-file + rename): a mid-write kill keeps the
    previous file.  Refuses to write while the static-analysis gate
    reports new findings."""
    import datetime

    if not _analysis_gate():
        return
    rec = dict(summary)
    rec["captured_at"] = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    _atomic_write_json(path, rec)


def _persist_last_summary(summary) -> None:
    """Every round's summary (complete or budget-truncated) lands
    atomically in BENCH_LAST.json — what ``--compare`` gates."""
    _persist_tpu_artifact(summary, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_LAST.json"))


def main():
    import jax

    from spark_rapids_tpu.utils import compile_cache

    compile_cache.enable()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _emit({"progress": "device", **device})

    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.benchmarks.tpch_datagen import generate
    from spark_rapids_tpu.data.column import register_pytrees
    from spark_rapids_tpu.session import Session

    register_pytrees()
    raw = generate(SF, seed=42)
    sizes = _table_bytes(raw)
    pq = _pandas_queries()
    pt = _pandas_tables(raw)

    # the per-kernel profiler feeds the per-query "kernels" roofline
    # section; its enabled-mode cost is one counter update per dispatch
    tpu = Session({**PRESSURE_CONF,
                   "spark.rapids.tpu.telemetry.profiler.enabled": True})
    cpu = Session(dict(PRESSURE_CONF), tpu_enabled=False)

    def mk_tables(sess):
        return {name: sess.create_dataframe(
            {c: v for c, v in cols.items()}, schema)
            for name, (schema, cols) in raw.items()}

    t_tpu = mk_tables(tpu)
    t_cpu = mk_tables(cpu)

    # budget split: queries get everything up to 80% of the budget; the
    # trailing microbenches run only if time remains
    deadline = _T0 + BUDGET_S * 0.8

    # the north-star workload runs FIRST — run in the leftovers it was
    # starved into a silent null
    tpcxbb_mini = _tpcxbb_mini(min(_T0 + BUDGET_S * 0.45, _deadline()))
    if tpcxbb_mini is not None:
        _emit({"progress": "tpcxbb_mini", **tpcxbb_mini})

    per_query = {}
    skipped = []
    tot_bytes = tot_tpu_s = tot_cpu_s = 0.0
    for qn, tables in QUERY_TABLES.items():
        if time.perf_counter() > deadline and per_query:
            # budget exhausted: keep the partial suite instead of
            # blowing the driver's timeout and reporting nothing
            skipped.append(f"q{qn}")
            per_query[f"q{qn}"] = {"skipped": "budget"}
            _emit({"progress": f"q{qn}", "skipped": "budget",
                   "elapsed_s": round(time.perf_counter() - _T0, 1)})
            continue
        qbytes = sum(sizes[t] for t in tables)
        df = tpch.QUERIES[qn](t_tpu)
        # cold = first collect, trace+compile inclusive; the warm
        # steady-state iterations ride the kernel cache
        t0q = time.perf_counter()
        df.collect()
        cold_s = time.perf_counter() - t0q
        tpu_s, noise = _best(lambda: df.collect(), warmup=0,
                             deadline=deadline)
        m = tpu.last_metrics or {}
        kernels = _kernel_rows(tpu)
        disp = m.get("kernelCache.dispatches", 0)
        kc_hit = round(m.get("kernelCache.hits", 0) / disp, 3) \
            if disp else None
        split = _transfer_split(tpu, tpu_s)
        # evidence FIRST: the device number lands before any
        # (unbounded) CPU-side baseline run can blow the budget
        _emit({"progress": f"q{qn}.tpu", "tpu_s": round(tpu_s, 4),
               "cold_s": round(cold_s, 4),
               "kernel_cache_hit_rate": kc_hit,
               "gb_per_s": round(qbytes / tpu_s / 1e9, 3), **split,
               "elapsed_s": round(time.perf_counter() - _T0, 1)})

        # CPU side: pandas always; the (slow, row-at-a-time) host
        # oracle only while budget remains
        pd_s, _ = _best(lambda: pq[qn](pt), iters=3, warmup=1,
                        deadline=deadline)
        host_s = float("inf")
        if time.perf_counter() < deadline:
            cdf = tpch.QUERIES[qn](t_cpu)
            host_s, _ = _best(lambda: cdf.collect(), iters=1, warmup=0)
        cpu_s = min(host_s, pd_s)

        rec = {
            "tpu_s": round(tpu_s, 4),      # warm steady-state best
            "cold_s": round(cold_s, 4),    # compile-inclusive first run
            "kernel_cache_hit_rate": kc_hit,
            "gb_per_s": round(qbytes / tpu_s / 1e9, 3),
            "noise_pct": round(noise, 1),
            "cpu_best_s": round(cpu_s, 4),
            "cpu_engine": "host" if host_s <= pd_s else "pandas",
            "speedup": round(cpu_s / tpu_s, 2),
            "aqe": _aqe_decisions(m),
            **split,
        }
        if kernels:
            rec["kernels"] = kernels
        per_query[f"q{qn}"] = rec
        _emit({"progress": f"q{qn}", **rec,
               "elapsed_s": round(time.perf_counter() - _T0, 1)})
        tot_bytes += qbytes
        tot_tpu_s += tpu_s
        tot_cpu_s += cpu_s

    suite_gbs = tot_bytes / tot_tpu_s / 1e9
    cpu_gbs = tot_bytes / tot_cpu_s / 1e9

    remaining = _deadline() - time.perf_counter()
    shuffle = _shuffle_microbench() if remaining > 20 else None
    if shuffle is not None:
        _emit({"progress": "shuffle_write", **shuffle})
    remaining = _deadline() - time.perf_counter()
    q3_exchange = _q3_exchange_breakdown() if remaining > 60 else None
    if q3_exchange is not None:
        _emit({"progress": "q3_exchange", **q3_exchange})
    remaining = _deadline() - time.perf_counter()
    q6_scan = _q6_scan_breakdown(raw) if remaining > 25 else None
    if q6_scan is not None:
        _emit({"progress": "q6_scan", **q6_scan})
    remaining = _deadline() - time.perf_counter()
    aqe_delta = _aqe_exchange_delta(raw, deadline=_deadline() - 20) \
        if remaining > 45 else None
    if aqe_delta is not None:
        _emit({"progress": "aqe_delta", **aqe_delta})
    remaining = _deadline() - time.perf_counter()
    ooc = _ooc_bench(raw, sizes, _deadline() - 25) \
        if remaining > 60 else None
    if ooc is not None:
        _emit({"progress": "ooc", **ooc})
    remaining = _deadline() - time.perf_counter()
    q1p = _q1_pipeline_mrows() if remaining > 15 else None

    summary = {
        "metric": "tpch_suite_throughput",
        "value": round(suite_gbs, 3),
        "unit": "GB/s",
        "vs_baseline": round(suite_gbs / cpu_gbs, 3),
        "schema_version": SCHEMA_VERSION,
        "h2d_ceiling_gb_per_s": round(
            getattr(tpu, "last_h2d_ceiling_bps", 0.0) / 1e9, 3),
        "sf": SF,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "queries": sorted(QUERY_TABLES),
        "skipped": skipped,
        "iters": ITERS,
        "budget_s": BUDGET_S,
        "elapsed_s": round(time.perf_counter() - _T0, 1),
        "per_query": per_query,
        "shuffle_write": shuffle,
        "q3_exchange": q3_exchange,
        "q6_scan": q6_scan,
        "aqe_delta": aqe_delta,
        "ooc": ooc,
        "tpcxbb_mini": tpcxbb_mini,
        "q1_pipeline": q1p,
    }
    _emit(summary)
    _persist_last_summary(summary)


def _parse_args(argv):
    import argparse

    ap = argparse.ArgumentParser(
        description="TPC-H suite bench (see module docstring)")
    ap.add_argument(
        "--budget-s", type=float, default=None, metavar="SECONDS",
        help="wall-clock budget; past it remaining queries are "
             "skipped with a 'budget' marker and the partial summary "
             "is still written atomically (default: "
             "SRT_BENCH_BUDGET_S or 270)")
    ap.add_argument(
        "--compare", metavar="OLD.json", default=None,
        help="regression gate: diff a fresh run (or --new) against "
             "this baseline artifact; >20%% slower per-query "
             "warm/cold times or per-kernel wall-per-dispatch exits "
             "nonzero (1 = regressions, 2 = schema mismatch / "
             "unreadable artifact)")
    ap.add_argument(
        "--new", metavar="NEW.json", default=None,
        help="with --compare: diff these two artifacts directly "
             "without running the bench")
    return ap.parse_args(argv)


if __name__ == "__main__":
    _args = _parse_args(sys.argv[1:])
    if _args.budget_s is not None:
        BUDGET_S = _args.budget_s
    if _args.compare and _args.new:
        # compare-only mode: no bench run, no jax init
        sys.exit(compare_main(_args.compare, _args.new))
    main()
    if _args.compare:
        # fresh run just landed atomically in BENCH_LAST.json — gate it
        sys.exit(compare_main(_args.compare, os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_LAST.json")))
