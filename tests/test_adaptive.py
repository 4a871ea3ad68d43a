"""Adaptive query execution (spark_rapids_tpu/adaptive/).

The contracts under test:

* **Bit-identity** — every AQE rewrite (partition coalescing, skew
  splitting, dynamic broadcast conversion) produces results identical
  to the non-adaptive plan: same values, same row placement after the
  engine's re-partitioning rules.  Pinned on TPC-H q1/q3/q5/q6/q16 and
  on synthetic trigger cases, including under deterministic
  corrupt/OOM injection and concurrent ``Session.submit``.
* **Trigger boundaries** — each rewrite fires exactly when its conf
  says so (``adaptive.targetPartitionBytes``,
  ``adaptive.skewedPartitionFactor`` + ``thresholdBytes``,
  ``adaptive.autoBroadcastJoinThreshold``), observable through the
  structured ``aqe_*`` events and ``aqe.*`` metrics.
* **Fresh stats on retry** — a re-executed stage re-records its drain
  statistics; the planner never re-plans from stale numbers.
* **Histograms always on** — per-exchange partition row histograms
  surface in ``last_metrics`` / ``profile_report()`` / the Prometheus
  export even with ``adaptive.enabled=false``.
"""
import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.adaptive.stats import (StageStats, coalesce_groups,
                                             split_partition_segments)
from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.testing.asserts import assert_rows_equal

SF = 0.0007
SEED = 7

TELE = {"spark.rapids.tpu.telemetry.enabled": True}
#: force static shuffled joins (the tiny test data broadcasts under the
#: default 10MB static threshold, which would leave the dynamic
#: conversion nothing to do); the ADAPTIVE threshold stays default
SHUFFLED = {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0}
FAST = {
    "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
    "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
}


def _sess(*confs, adaptive=True):
    conf = {"spark.rapids.tpu.sql.adaptive.enabled": adaptive}
    for c in confs:
        conf.update(c)
    return srt.Session(conf)


def _events(sess):
    prof = sess.last_profile
    return [e["event"] for e in prof.events.snapshot()] if prof else []


def _norm(rows):
    return sorted(
        (tuple((None if v is None else
                (round(v, 6) if isinstance(v, float) else v))
               for v in r) for r in rows),
        key=repr)


def _join_agg_df(sess, n=300, keys=40):
    rng = np.random.RandomState(3)
    orders = {"o_custkey": rng.randint(0, keys, n).tolist(),
              "o_total": [round(float(v), 6)
                          for v in rng.rand(n) * 1000]}
    cust = {"c_custkey": list(range(keys)),
            "c_nation": rng.randint(0, 5, keys).tolist()}
    o = sess.create_dataframe(orders)
    c = sess.create_dataframe(cust)
    j = o.join(c, on=(["o_custkey"], ["c_custkey"]), how="inner")
    return j.group_by("c_nation").agg(
        F.sum("o_total").alias("rev"), F.count("o_total").alias("n"))


def _skewed_join_df(sess):
    """~1500 rows of key 0 against a uniform tail: one hash partition
    dwarfs the median."""
    rng = np.random.RandomState(11)
    keys = [0] * 1500 + rng.randint(1, 40, 120).tolist()
    left = {"k": keys,
            "v": [round(float(v), 6) for v in rng.rand(len(keys))]}
    right = {"k": list(range(40)),
             "tag": rng.randint(0, 7, 40).tolist()}
    lf = sess.create_dataframe(left, n_partitions=8)
    rf = sess.create_dataframe(right, n_partitions=8)
    return lf.join(rf, on=(["k"], ["k"]), how="inner")


# ==========================================================================
# Pure helpers (adaptive/stats.py)
# ==========================================================================
def test_coalesce_groups_boundaries():
    # adjacent merging up to target, never reordering
    assert coalesce_groups([10, 10, 10, 10], 20) == [(0, 1), (2, 3)]
    # an over-target partition stays alone; neighbors still merge
    assert coalesce_groups([5, 100, 5, 5], 20) == [(0,), (1,), (2, 3)]
    # everything fits into one
    assert coalesce_groups([1, 1, 1], 100) == [(0, 1, 2)]
    # target smaller than every partition: identity grouping
    assert coalesce_groups([10, 10], 1) == [(0,), (1,)]
    assert coalesce_groups([], 10) == []


def test_split_partition_segments_reproduces_row_sequence():
    rng = np.random.RandomState(5)
    item_counts = [rng.randint(0, 9, 4).astype(np.int64)
                   for _ in range(6)]
    p = 2
    rows = [(i, r) for i, c in enumerate(item_counts)
            for r in range(int(c[p]))]
    for k in (1, 2, 3, 5, 50):
        slices = split_partition_segments(item_counts, p, k)
        got = [(i, r) for segs in slices
               for (i, lo, hi) in segs for r in range(lo, hi)]
        assert got == rows, f"k={k} broke the row sequence"
        for segs in slices:
            assert all(hi > lo for (_, lo, hi) in segs)
    # empty partition: no slices
    empty = [np.zeros(4, dtype=np.int64)]
    assert split_partition_segments(empty, 1, 3) == []


def test_stage_stats_overwrite_on_retry_and_metrics():
    st = StageStats()
    eid = st.allocate_id()
    st.record_exchange(eid, items=[(1, np.array([7, 1]), None)],
                       n_out=2, device_path=True, total_bytes=100,
                       partitioning="HashPartitioning")
    # a retried drain re-records: FRESH numbers replace the stale ones
    st.record_exchange(eid, items=[(2, np.array([3, 5]), None)],
                       n_out=2, device_path=True, total_bytes=64,
                       partitioning="HashPartitioning")
    obs = st.get(eid)
    assert obs.total_rows == 8 and obs.total_bytes == 64
    assert [obs.rows_for(p) for p in (0, 1)] == [3, 5]
    m = st.metrics()
    assert m[f"shuffle.exchange{eid}.partRowsMax"] == 5
    assert m[f"shuffle.exchange{eid}.rowsTotal"] == 8
    assert st.observed_peak_bytes() == 64


# ==========================================================================
# Rewrite trigger / no-trigger boundaries
# ==========================================================================
def test_broadcast_conversion_trigger_and_equality():
    off = _join_agg_df(_sess(SHUFFLED, adaptive=False)).collect()
    sess = _sess(SHUFFLED, TELE)
    got = _join_agg_df(sess).collect()
    assert _norm(got) == _norm(off)
    m = sess.last_metrics
    assert m.get("aqe.numJoinsConverted", 0) >= 1, sorted(m)[:10]
    assert "aqe_broadcast_join" in _events(sess)


def test_broadcast_conversion_no_trigger_when_threshold_zero():
    conf = {"spark.rapids.tpu.sql.adaptive.autoBroadcastJoinThreshold": 0}
    off = _join_agg_df(_sess(SHUFFLED, adaptive=False)).collect()
    sess = _sess(SHUFFLED, TELE, conf)
    got = _join_agg_df(sess).collect()
    assert _norm(got) == _norm(off)
    assert "aqe.numJoinsConverted" not in sess.last_metrics
    assert "aqe_broadcast_join" not in _events(sess)


def _join_against_filtered_agg(sess, how, min_qty):
    """``orders`` joined with the orders whose lines sum past
    ``min_qty``: the build side is an aggregate two exchanges up, which
    the static planner cannot size, so the join is planned shuffled."""
    rng = np.random.RandomState(5)
    n_ord = 3000
    lines = {"l_orderkey": np.sort(rng.randint(0, n_ord, 12000)).tolist(),
             "l_quantity": rng.randint(1, 51, 12000).astype(float).tolist()}
    orders = {"o_orderkey": list(range(n_ord)),
              "o_total": [round(float(v), 2)
                          for v in rng.rand(n_ord) * 1000]}
    li = sess.create_dataframe(lines, n_partitions=4)
    o = sess.create_dataframe(orders, n_partitions=4)
    big = (li.group_by(F.col("l_orderkey").alias("ok"))
           .agg(F.sum("l_quantity").alias("qty"))
           .filter(F.col("qty") > F.lit(min_qty)))
    if how == "semi":
        big = big.select("ok")
    return o.join(big, on=(["o_orderkey"], ["ok"]), how=how)


def _exchanged_rows(metrics):
    return sorted(v for k, v in metrics.items()
                  if k.startswith("shuffle.exchange")
                  and k.endswith(".rowsTotal"))


@pytest.mark.parametrize("how", ["semi", "inner"])
def test_stream_side_waits_for_a_build_side_still_being_computed(how):
    """The stream side's exchange used to run while the aggregate under
    the build side was still stages away, so the conversion always came
    too late (ISSUE 34)."""
    off = _join_against_filtered_agg(
        _sess(adaptive=False), how, 250.0).collect()
    host = _join_against_filtered_agg(
        srt.Session(tpu_enabled=False), how, 250.0).collect()
    sess = _sess(TELE)
    got = _join_against_filtered_agg(sess, how, 250.0).collect()
    assert 0 < len(got) < 3000
    assert _norm(got) == _norm(off) == _norm(host)
    m = sess.last_metrics
    assert m["aqe.numJoinsConverted"] == 1
    assert m["aqe.streamExchangesDeferred"] == 1
    assert "aqe_broadcast_join" in _events(sess)
    # the aggregate's exchange and the survivors', never the 3,000
    # stream rows
    assert m["aqe.numStages"] == 2
    assert _exchanged_rows(m)[0] == len(got) and \
        3000 not in _exchanged_rows(m)


def test_deferred_stream_side_runs_when_the_build_side_lands_too_big():
    # nothing can be converted under this threshold: the stream side
    # waits, then runs all the same, and the shuffled join answers
    conf = {"spark.rapids.tpu.sql.adaptive.autoBroadcastJoinThreshold": 64}
    off = _join_against_filtered_agg(
        _sess(adaptive=False), "semi", 100.0).collect()
    sess = _sess(conf)
    got = _join_against_filtered_agg(sess, "semi", 100.0).collect()
    assert len(got) > 500 and _norm(got) == _norm(off)
    m = sess.last_metrics
    assert "aqe.numJoinsConverted" not in m
    assert m["aqe.streamExchangesDeferred"] == 1
    assert m["aqe.numStages"] == 3
    assert 3000 in _exchanged_rows(m)


def test_a_join_of_two_leaf_scans_keeps_its_stage_order():
    from spark_rapids_tpu.adaptive.executor import _pick_ready
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec

    sess = _sess(SHUFFLED)
    df = _join_agg_df(sess)
    phys = sess.physical_plan(df.plan)
    join, = [n for n in _walk(phys)
             if isinstance(n, TpuShuffledHashJoinExec)]
    ready = _pick_ready(phys)
    # both sides are ready at once: the build side first, nothing waits
    assert len(ready) == 2
    assert any(ready[0] is n for n in _walk(join.children[1]))
    assert any(ready[1] is n for n in _walk(join.children[0]))
    df.collect()
    assert "aqe.streamExchangesDeferred" not in sess.last_metrics


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def test_coalesce_trigger_and_no_trigger_boundary():
    # default 64MB target: the tiny partitions all merge
    sess = _sess(TELE)
    got = _join_agg_df(sess).collect()
    assert sess.last_metrics.get("aqe.numPartitionsCoalesced", 0) >= 1
    assert "aqe_coalesce_partitions" in _events(sess)
    # 1-byte target: nothing fits together — identity grouping
    tiny = {"spark.rapids.tpu.sql.adaptive.targetPartitionBytes": 1}
    sess2 = _sess(TELE, tiny)
    got2 = _join_agg_df(sess2).collect()
    assert "aqe.numPartitionsCoalesced" not in sess2.last_metrics
    assert _norm(got) == _norm(got2)


#: skew rewrite confs: conversion disabled (it outranks skew on these
#: tiny build sides), aggressive factor/threshold so the synthetic
#: skew qualifies
SKEW = {"spark.rapids.tpu.sql.adaptive.autoBroadcastJoinThreshold": 0,
        "spark.rapids.tpu.sql.adaptive.skewedPartitionFactor": 1.5,
        "spark.rapids.tpu.sql.adaptive.skewedPartitionThresholdBytes": 1,
        "spark.rapids.tpu.sql.adaptive.maxSkewSlices": 4}


def test_skew_split_trigger_and_equality():
    off = _skewed_join_df(_sess(SHUFFLED, adaptive=False)).collect()
    sess = _sess(SHUFFLED, TELE, SKEW)
    got = _skewed_join_df(sess).collect()
    assert _norm(got) == _norm(off)
    m = sess.last_metrics
    assert m.get("aqe.numSkewSplits", 0) >= 1, \
        sorted(k for k in m if k.startswith(("aqe.", "shuffle.ex")))
    assert "aqe_skew_split" in _events(sess)


def _drained_exchange(mode):
    """A hash exchange over filtered batches — ~50 live rows each in
    the 4096-row bucket of their input — executed directly, so its
    readers can be asked for whole partitions and for segments."""
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.plan.physical import ExecContext

    sess = _sess(SHUFFLED, {"spark.rapids.tpu.shuffle.mode": mode},
                 adaptive=False)
    rng = np.random.RandomState(17)
    n = 5000
    df = sess.create_dataframe(
        {"k": rng.randint(0, 1000, n).tolist(),
         "v": [round(float(v), 6) for v in rng.rand(n)]},
        n_partitions=2)
    plan = df.filter(df["v"] < 0.02).repartition(3, "k").plan

    def find(p):
        if isinstance(p, TpuShuffleExchangeExec):
            return p
        for c in p.children:
            hit = find(c)
            if hit is not None:
                return hit

    ex = find(sess.physical_plan(plan))
    return ex.execute_columnar(ExecContext(sess.conf, sess))


def _rows_of(batches):
    from spark_rapids_tpu.data.column import device_to_host

    return [r for b in batches for r in device_to_host(b).to_rows()]


def test_segment_read_over_trimmed_block_keeps_row_sequence():
    """Segments index a block's rows by the counts/starts of the build
    that made it; a block packed at the bucket of its live rows must
    give the same sequence — whole, split, and as the host path has
    it."""
    from spark_rapids_tpu.shuffle import device_shuffle as DS

    mark = DS.GLOBAL.counters()
    dev = _drained_exchange("device")
    items = dev.aqe_materialize()
    trimmed = DS.GLOBAL.metrics_since(mark)
    assert trimmed["shuffle.trimmedBlocks"] == len(items) == 2, trimmed
    assert trimmed["shuffle.trimmedRows"] == 2 * (4096 - 128), trimmed
    item_counts = [it[1] for it in items]
    whole = [_rows_of(dev.aqe_read(p)()) for p in range(3)]
    assert sum(map(len, whole)) \
        == int(sum(c.sum() for c in item_counts)) > 0
    for p in range(3):
        for k in (2, 3, 7):
            slices = split_partition_segments(item_counts, p, k)
            got = [r for segs in slices
                   for r in _rows_of(dev.aqe_read(p, segs)())]
            assert got == whole[p], (p, k)
    # a new session installs a new spill framework: the device
    # exchange's blocks are read out before the host one is made
    host = _drained_exchange("host")
    assert [_rows_of(host.aqe_read(p)()) for p in range(3)] == whole


def test_skew_split_no_trigger_at_default_factor():
    # uniform keys never exceed 4x the median
    sess = _sess(SHUFFLED, TELE, {
        "spark.rapids.tpu.sql.adaptive.autoBroadcastJoinThreshold": 0})
    off = _join_agg_df(_sess(SHUFFLED, adaptive=False)).collect()
    got = _join_agg_df(sess).collect()
    assert _norm(got) == _norm(off)
    assert "aqe.numSkewSplits" not in sess.last_metrics
    assert "aqe_skew_split" not in _events(sess)


# ==========================================================================
# TPC-H bit-identity, adaptive on vs off
# ==========================================================================
_UNORDERED = {5, 6, 16}


def _run_tpch(qnum, *confs, adaptive):
    sess = _sess(*confs, adaptive=adaptive)
    tables = tpch_datagen.dataframes(sess, sf=SF, seed=SEED)
    return tpch.QUERIES[qnum](tables).collect(), sess


@pytest.mark.parametrize("qnum", [1, 3, 5, 6, 16])
def test_tpch_adaptive_bit_identity(qnum):
    off, _ = _run_tpch(qnum, SHUFFLED, adaptive=False)
    on, sess = _run_tpch(qnum, SHUFFLED, adaptive=True)
    assert_rows_equal(off, on, ignore_order=qnum in _UNORDERED,
                      approximate_float=1e-6)
    assert sess.last_metrics.get("aqe.numStages", 0) >= 1


def test_tpch_q3_conversion_and_q1_coalesce_events():
    """The acceptance demos: a real TPC-H query converting a join and
    one coalescing partitions, asserted via structured events."""
    _, s3 = _run_tpch(3, SHUFFLED, TELE, adaptive=True)
    assert s3.last_metrics.get("aqe.numJoinsConverted", 0) >= 1
    assert "aqe_broadcast_join" in _events(s3)
    _, s1 = _run_tpch(1, TELE, adaptive=True)
    assert s1.last_metrics.get("aqe.numPartitionsCoalesced", 0) >= 1
    assert "aqe_coalesce_partitions" in _events(s1)
    # the profile renders the FINAL plan, AdaptiveSparkPlan-style
    report = s1.profile_report()
    assert "AdaptiveSparkPlan isFinalPlan=true" in report
    assert "-- Adaptive execution --" in report


def _inject(fault_type, site, skip=0):
    return {**FAST,
            "spark.rapids.tpu.fault.injection.mode": "nth",
            "spark.rapids.tpu.fault.injection.type": fault_type,
            "spark.rapids.tpu.fault.injection.site": site,
            "spark.rapids.tpu.fault.injection.skipCount": skip,
            "spark.rapids.tpu.sql.taskRetries": 3}


@pytest.mark.fault_injection
def test_tpch_q3_adaptive_under_corrupt_injection():
    """A corrupted exchange write re-executes the stage lineage; the
    adaptive driver re-plans from the FRESH drain's stats and the
    result stays bit-identical."""
    off, _ = _run_tpch(3, SHUFFLED, adaptive=False)
    on, sess = _run_tpch(3, SHUFFLED, TELE,
                         _inject("corrupt", "exchange.write"),
                         adaptive=True)
    assert_rows_equal(off, on, ignore_order=False,
                      approximate_float=1e-6)
    assert sess.last_metrics.get("aqe.numStages", 0) >= 1


@pytest.mark.oom_injection
def test_tpch_q3_adaptive_under_oom_injection():
    oom = {**FAST,
           "spark.rapids.tpu.memory.oomInjection.mode": "nth",
           "spark.rapids.tpu.memory.oomInjection.skipCount": 2}
    off, _ = _run_tpch(3, SHUFFLED, adaptive=False)
    on, sess = _run_tpch(3, SHUFFLED, oom, adaptive=True)
    assert_rows_equal(off, on, ignore_order=False,
                      approximate_float=1e-6)
    assert sess.last_metrics.get("aqe.numStages", 0) >= 1


# ==========================================================================
# Concurrent submission
# ==========================================================================
def test_adaptive_under_concurrent_submit():
    sess = _sess(SHUFFLED, TELE)
    serial = _join_agg_df(_sess(SHUFFLED, adaptive=False)).collect()
    handles = [sess.submit(_join_agg_df(sess)) for _ in range(3)]
    for h in handles:
        got = h.result(timeout=180).to_rows()
        assert _norm(got) == _norm(serial)
        assert h.metrics.get("aqe.numStages", 0) >= 1, \
            sorted(h.metrics)[:10]
    sess.shutdown_scheduler()


def test_adaptive_rebases_scheduler_reservation():
    sess = _sess(SHUFFLED, TELE, {
        "spark.rapids.tpu.scheduler.reservationFraction": 0.5})
    h = sess.submit(_join_agg_df(sess))
    h.result(timeout=180)
    freed = h.metrics.get("aqe.reservationFreedBytes", 0)
    assert freed > 0, sorted(k for k in h.metrics
                             if k.startswith("aqe."))
    assert any(e["event"] == "aqe_reservation_rebase"
               for e in h.events())
    sess.shutdown_scheduler()


# ==========================================================================
# Histograms surface with adaptive OFF
# ==========================================================================
def test_partition_histograms_surface_with_adaptive_off():
    from spark_rapids_tpu.telemetry.export import prometheus_text

    sess = _sess(SHUFFLED, TELE, adaptive=False)
    _join_agg_df(sess).collect()
    m = sess.last_metrics
    hist = [k for k in m if k.startswith("shuffle.exchange")]
    assert any(k.endswith("partRowsP50") for k in hist), sorted(m)[:12]
    assert not any(k.startswith("aqe.") for k in m)
    report = sess.profile_report()
    assert "-- Exchange partition histograms --" in report
    assert "AdaptiveSparkPlan" not in report
    text = prometheus_text(m)
    assert "shuffle" in text and "partRowsP50" in text


# ==========================================================================
# Satellite: static broadcast estimate respects column pruning
# ==========================================================================
def _find_joins(node, out):
    from spark_rapids_tpu.plan import physical as P

    if isinstance(node, P.HashJoinExec):
        out.append(node)
    for c in node.children:
        _find_joins(c, out)


def test_static_broadcast_estimate_scales_with_projection():
    from spark_rapids_tpu.plan.optimizer import optimize
    from spark_rapids_tpu.plan.planner import Planner

    n = 512
    wide = {f"c{i}": list(range(n)) for i in range(10)}  # 10 int64 cols
    left = {"k": list(range(64))}

    def plan_for(threshold, project):
        sess = srt.Session(
            {"spark.rapids.tpu.sql.broadcastSizeThreshold": threshold})
        lf = sess.create_dataframe(left)
        rf = sess.create_dataframe(wide)
        if project:
            rf = rf.select("c0")
        j = lf.join(rf, on=(["k"], ["c0"]), how="inner")
        joins = []
        _find_joins(Planner(sess.conf).plan(optimize(j.plan)), joins)
        assert len(joins) == 1
        return joins[0]

    # a threshold between the PRUNED build size (~1 of 10 int64
    # columns) and the full relation: only the projection-scaled
    # estimate lets the join broadcast
    threshold = 2 * 8 * n
    assert plan_for(threshold, project=True).broadcast, \
        "projected build side should broadcast under the scaled estimate"
    assert not plan_for(threshold, project=False).broadcast, \
        "unprojected wide build side must still exceed the threshold"
