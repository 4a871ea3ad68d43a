"""Whole-stage fusion: plan-rewrite rules, bit-identity, dispatch
counts.

The fusion pass (plan/fusion.py) collapses maximal chains of row-local
execs into one TpuFusedSegmentExec whose single jitted kernel threads
the filter keep-mask through the segment and compacts once at exit.
These tests pin the three contracts the optimisation rests on:

1. **Rewrite rules** — what fuses, where segments stop (exchanges,
   aggregates, sorts, joins, transitions, nondeterminism, the
   maxSegmentExecs cap), the one consumer that takes a chain in (an
   update-phase aggregate absorbs the Filter/Project chain under it and
   reads the keep mask: nothing compacts), and the clean round-trip
   with ``fusion.enabled=false``.
2. **Bit-identity** — fused vs unfused device plans produce EXACTLY
   the same rows (same values, same order) across the TPC-H suite and
   under fault/OOM injection.
3. **Dispatch economics** — a Project→Filter→Project chain costs ONE
   kernel dispatch per batch fused vs three unfused, counted through
   the KernelCache telemetry.
"""
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.basic import TpuFilterExec
from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
from spark_rapids_tpu.exec.fused import TpuFusedSegmentExec
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.testing.asserts import assert_rows_equal

SF = 0.0007
SEED = 7

FUSED_OFF = {"spark.rapids.tpu.sql.fusion.enabled": False}


def _walk(plan):
    yield plan
    for c in plan.children:
        yield from _walk(c)


def _segments(plan):
    return [n for n in _walk(plan) if isinstance(n, TpuFusedSegmentExec)]


def _absorbing(plan):
    """The aggregates of ``plan`` that run an absorbed chain."""
    return [n for n in _walk(plan)
            if isinstance(n, TpuHashAggregateExec) and n.absorbed]


def _collect_and_plan(sess, df):
    sess.start_capture()
    rows = df.collect()
    return rows, sess.captured_plans()[-1]


def _chain_df(sess):
    """A Project -> Filter -> Project chain over two columns."""
    df = sess.create_dataframe(
        {"a": list(range(1, 41)), "b": [i * 10 for i in range(1, 41)]},
        n_partitions=2)
    return (df.select("a", "b", (F.col("a") + F.col("b")).alias("s"))
            .filter(F.col("a") > 5)
            .select("s"))


# ==========================================================================
# rewrite rules
# ==========================================================================
def test_project_filter_project_fuses_into_one_segment():
    sess = srt.Session()
    rows, plan = _collect_and_plan(sess, _chain_df(sess))
    segs = _segments(plan)
    assert len(segs) == 1, plan.tree_string()
    assert len(segs[0].members) == 3
    # EXPLAIN surface: the member list is visible in describe()
    d = segs[0].describe()
    assert "TpuFusedSegment[3:" in d
    assert "TpuProject" in d and "TpuFilter" in d
    assert rows == [(i + i * 10,) for i in range(6, 41)]


def test_fusion_disabled_round_trips():
    on = srt.Session()
    off = srt.Session(dict(FUSED_OFF))
    rows_on, plan_on = _collect_and_plan(on, _chain_df(on))
    rows_off, plan_off = _collect_and_plan(off, _chain_df(off))
    assert _segments(plan_on) and not _segments(plan_off)
    assert rows_on == rows_off
    oracle = _chain_df(srt.Session(tpu_enabled=False)).collect()
    assert rows_on == oracle


def test_single_row_local_exec_is_not_fused():
    sess = srt.Session()
    df = sess.create_dataframe({"a": [1, 2, 3]})
    _, plan = _collect_and_plan(sess, df.select((F.col("a") * 2)
                                                .alias("d")))
    assert not _segments(plan)


def test_segment_stops_at_aggregate_and_sort():
    sess = srt.Session()
    df = sess.create_dataframe(
        {"k": [1, 2, 1, 2, 3] * 8, "v": list(range(40))})
    q = (df.with_column("w", F.col("v") + 1)
         .filter(F.col("w") > 3)
         .group_by("k").agg(F.sum("w").alias("sw"))
         .with_column("x", F.col("sw") * 2)
         .filter(F.col("x") > 0)
         .sort("k"))
    rows, plan = _collect_and_plan(sess, q)
    for seg in _segments(plan):
        kinds = {type(m).__name__ for m in seg.members}
        assert kinds <= {"TpuProjectExec", "TpuFilterExec",
                         "TpuExpandExec", "TpuGenerateExec"}
    # the aggregate and the sort are still standalone nodes
    names = [type(n).__name__ for n in _walk(plan)]
    assert "TpuHashAggregateExec" in names and "TpuSortExec" in names
    # the chain under the group-by is no node any more: the partial
    # aggregate names it as what it absorbed, and the segment over the
    # final aggregate (it feeds a sort, not an aggregate) stands
    (partial,) = _absorbing(plan)
    assert partial.mode == "partial"
    d = partial.describe()
    assert "absorbed: TpuProject[" in d and "TpuFilter[" in d, d
    assert [len(s.members) for s in _segments(plan)] == [2]
    assert "TpuFilterExec" not in names
    oracle_sess = srt.Session(tpu_enabled=False)
    odf = oracle_sess.create_dataframe(
        {"k": [1, 2, 1, 2, 3] * 8, "v": list(range(40))})
    oracle = (odf.with_column("w", F.col("v") + 1)
              .filter(F.col("w") > 3)
              .group_by("k").agg(F.sum("w").alias("sw"))
              .with_column("x", F.col("sw") * 2)
              .filter(F.col("x") > 0)
              .sort("k")).collect()
    assert rows == oracle


def test_nondeterministic_exprs_break_the_segment():
    """rand() is position-dependent: deferring the filter's compaction
    would change which physical row feeds it — such projections must
    not join a segment."""
    sess = srt.Session()
    df = sess.create_dataframe({"a": list(range(20))})
    q = (df.filter(F.col("a") > 2)
         .with_column("r", F.rand(42))
         .filter(F.col("a") < 15))
    _, plan = _collect_and_plan(sess, q)
    for seg in _segments(plan):
        for m in seg.members:
            for e in getattr(m, "exprs", []):
                assert e.deterministic, seg.describe()


def test_max_segment_execs_caps_chain_length():
    sess = srt.Session({"spark.rapids.tpu.sql.fusion.maxSegmentExecs": 2})
    df = sess.create_dataframe({"a": list(range(30))})
    q = (df.with_column("b", F.col("a") + 1)
         .with_column("c", F.col("b") + 1)
         .filter(F.col("c") > 4)
         .with_column("d", F.col("c") * 2)
         .select("d"))
    rows, plan = _collect_and_plan(sess, q)
    segs = _segments(plan)
    assert segs, plan.tree_string()
    assert all(len(s.members) <= 2 for s in segs)
    oracle = srt.Session(dict(FUSED_OFF))
    rows_off, _ = _collect_and_plan(
        oracle,
        (oracle.create_dataframe({"a": list(range(30))})
         .with_column("b", F.col("a") + 1)
         .with_column("c", F.col("b") + 1)
         .filter(F.col("c") > 4)
         .with_column("d", F.col("c") * 2)
         .select("d")))
    assert rows == rows_off


def test_single_batch_goal_coalesce_lands_above_segment():
    """A consumer with a children-coalesce goal (sort) must see its
    coalesce between itself and the fused segment, exactly where the
    unfused plan would put it (fusion runs before coalesce insertion)."""
    sess = srt.Session()
    df = sess.create_dataframe(
        {"a": list(range(20))}, n_partitions=2)
    q = (df.with_column("b", F.col("a") * 3)
         .filter(F.col("b") > 6)
         .sort_within_partitions("b"))
    _, plan = _collect_and_plan(sess, q)
    segs = _segments(plan)
    assert segs
    coalesces = [n for n in _walk(plan)
                 if isinstance(n, TpuCoalesceBatchesExec)]
    assert any(isinstance(c.children[0], TpuFusedSegmentExec)
               for c in coalesces), plan.tree_string()


def test_explode_generate_fuses_and_matches_oracle():
    sess = srt.Session()
    df = sess.create_dataframe({"a": [1, 2, 3, 4]})
    q = (df.with_column("b", F.col("a") * 10)
         .explode([F.col("a"), F.col("b")], name="e")
         .filter(F.col("e") > 5))
    rows, plan = _collect_and_plan(sess, q)
    segs = _segments(plan)
    assert segs and any(
        type(m).__name__ == "TpuGenerateExec"
        for s in segs for m in s.members), plan.tree_string()
    oracle = (srt.Session(tpu_enabled=False)
              .create_dataframe({"a": [1, 2, 3, 4]})
              .with_column("b", F.col("a") * 10)
              .explode([F.col("a"), F.col("b")], name="e")
              .filter(F.col("e") > 5)).collect()
    assert rows == oracle


# ==========================================================================
# the aggregate absorbs the chain under it (keep mask, no compaction)
# ==========================================================================
N_ROWS = 600


def _masked_data(n=N_ROWS):
    """Nulls in the keys, the values and the predicate's column; ``p``
    is 0..99 so ``p < t`` keeps about t% of its non-null rows."""
    return {
        "i": list(range(n)),
        "k": [None if i % 23 == 0 else i % 7 for i in range(n)],
        "s": [None if i % 31 == 0 else "abc"[i % 3] * (1 + i % 2)
              for i in range(n)],
        "v": [None if i % 13 == 0 else (i * 37) % 101 - 50
              for i in range(n)],
        "x": [None if i % 17 == 0 else ((i * 29) % 97) / 7.0
              for i in range(n)],
        "p": [None if i % 11 == 0 else (i * 53) % 100 for i in range(n)],
    }


_KEEPS = {
    "all": lambda: F.col("i") >= 0,
    "none": lambda: F.col("p") < 0,
    "2pct": lambda: F.col("p") < 2,     # a NULL p drops the row
    "98pct": lambda: F.col("p") < 98,
}


def _aggs():
    return [F.count("*").alias("n"), F.count("x").alias("nx"),
            F.sum("v").alias("sv"), F.sum("x").alias("sx"),
            F.avg("x").alias("ax"), F.min("v").alias("lo"),
            F.max("x").alias("hi"), F.min("s").alias("s0")]


def _three_ways(build, conf=None, n_partitions=2, data=None):
    """``build(df)`` on the device with fusion on and off and on the
    host oracle: (rows on, rows off, oracle rows, plan on, plan off,
    the fused run's last_metrics)."""
    data = data or _masked_data()
    out = []
    for extra, tpu in (({}, True), (FUSED_OFF, True), ({}, False)):
        sess = srt.Session(dict(conf or {}, **extra), tpu_enabled=tpu)
        df = sess.create_dataframe(dict(data), n_partitions=n_partitions)
        rows, plan = _collect_and_plan(sess, build(df))
        out.append((rows, plan, sess.last_metrics))
    (on, plan_on, m_on), (off, plan_off, m_off), (oracle, _, _) = out
    assert m_off["fusion.filtersAbsorbed"] == 0
    assert not _absorbing(plan_off)
    return on, off, oracle, plan_on, plan_off, m_on


def _assert_same(on, off, oracle, exact):
    if exact:
        assert on == off        # to the bit
    else:
        assert_rows_equal(off, on, approximate_float=1e-12)
    assert_rows_equal(oracle, on, ignore_order=True,
                      approximate_float=1e-9)


@pytest.mark.parametrize("keep", list(_KEEPS))
@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "keyless"])
def test_filter_under_group_by_is_absorbed(keyed, keep):
    def build(df):
        kept = df.filter(_KEEPS[keep]())
        if keyed:
            return kept.group_by("k").agg(*_aggs()).sort("k")
        return kept.agg(*_aggs())

    on, off, oracle, plan_on, plan_off, m = _three_ways(build)
    (agg,) = _absorbing(plan_on)
    assert agg.mode == "partial" and len(agg.keys) == int(keyed)
    assert [type(x) for x in agg.absorbed] == [TpuFilterExec]
    assert "absorbed: TpuFilter[" in agg.describe()
    assert "absorbed" in plan_on.tree_string()
    # the filter is a node with fusion off and none with it on
    assert any(isinstance(n, TpuFilterExec) for n in _walk(plan_off))
    assert not any(isinstance(n, TpuFilterExec) for n in _walk(plan_on))
    assert m["fusion.filtersAbsorbed"] == 1
    if keep == "none":      # no group at all; the keyless row stands
        assert len(on) == (0 if keyed else 1)
    # keyed: the stable sort puts each group's kept rows where the
    # compaction did, so even float sums are bit-identical; keyless:
    # the device adds other blocks, float sums are equal to rounding
    _assert_same(on, off, oracle, exact=keyed)
    if not keyed:
        ints = [0, 1, 2, 5, 7]     # counts, the int sum, min, the string
        assert [on[0][j] for j in ints] == [off[0][j] for j in ints]
        assert on[0][6] == off[0][6]            # max of a float: exact


@pytest.mark.parametrize("ignore_nulls", [False, True],
                         ids=["any", "ignore_nulls"])
@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "keyless"])
def test_first_last_over_an_absorbed_filter(keyed, ignore_nulls):
    """``first`` / ``last`` without ignore-nulls read a segment's first
    (last) ROW: sorted by key the segment holds kept rows only, but the
    keyless segment spans the dropped rows too, so there the filter
    stays a node.  Decided from the update ops, at plan time."""
    def build(df):
        kept = df.filter(F.col("p") < 40)
        aggs = [F.first("v", ignore_nulls).alias("f"),
                F.last("x", ignore_nulls).alias("l"),
                F.count("*").alias("n")]
        if keyed:
            return kept.group_by("k").agg(*aggs).sort("k")
        return kept.agg(*aggs)

    on, off, oracle, plan_on, _, m = _three_ways(build, n_partitions=1)
    absorbs = keyed or ignore_nulls
    assert len(_absorbing(plan_on)) == int(absorbs)
    assert m["fusion.filtersAbsorbed"] == int(absorbs)
    assert any(isinstance(n, TpuFilterExec)
               for n in _walk(plan_on)) == (not absorbs)
    _assert_same(on, off, oracle, exact=True)


def test_project_filter_project_chain_under_a_group_by_is_absorbed():
    def build(df):
        return (df.select("k", "p", (F.col("v") * 2).alias("w"), "x")
                .filter((F.col("p") < 60) & (F.col("w") > -50))
                .select("k", (F.col("w") + F.col("x")).alias("y"))
                .group_by("k").agg(F.sum("y").alias("sy"),
                                   F.count("*").alias("n")).sort("k"))

    on, off, oracle, plan_on, plan_off, m = _three_ways(build)
    (agg,) = _absorbing(plan_on)
    assert [type(x).__name__ for x in agg.absorbed] == [
        "TpuProjectExec", "TpuFilterExec", "TpuProjectExec"]
    d = agg.describe()
    assert d.index("TpuProject[") < d.index("TpuFilter[") \
        < d.rindex("TpuProject["), d
    # the chain was the plan's only segment
    assert not _segments(plan_on) and m["fusion.filtersAbsorbed"] == 1
    _assert_same(on, off, oracle, exact=True)


def test_string_key_over_an_absorbed_filter():
    def build(df):
        return (df.filter(F.col("p") < 50).group_by("s")
                .agg(F.sum("x").alias("sx"), F.max("s").alias("hi"),
                     F.count("v").alias("nv")).sort("s"))

    on, off, oracle, plan_on, _, _ = _three_ways(build)
    assert len(_absorbing(plan_on)) == 1
    assert len(on) == 7         # six strings and the NULL group
    _assert_same(on, off, oracle, exact=True)


@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "keyless"])
def test_complete_mode_absorbs(keyed):
    """The planner builds partial + final; a ``complete`` aggregate is
    an update-phase one too (``final`` is not: it reads buffers)."""
    from spark_rapids_tpu.plan import physical as P
    from spark_rapids_tpu.plan.optimizer import optimize
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from spark_rapids_tpu.plan.planner import Planner
    from spark_rapids_tpu.plan.transitions import TpuTransitionOverrides

    def run(conf, tpu=True):
        sess = srt.Session(dict(conf), tpu_enabled=tpu)
        df = sess.create_dataframe(_masked_data(), n_partitions=1)
        kept = df.filter(F.col("p") < 30)
        q = kept.group_by("k").agg(*_aggs()) if keyed \
            else kept.agg(*_aggs())
        final = Planner(sess.conf).plan(optimize(q.plan))
        partial = final.children[0].children[0]
        assert (final.mode, partial.mode) == ("final", "partial")
        phys = P.HashAggregateExec(
            partial.children[0], "complete", q.plan.keys, partial.specs,
            [f.name for f in final.schema.fields[len(partial.keys):]])
        if tpu:
            phys = TpuTransitionOverrides(sess.conf).apply(
                TpuOverrides(sess.conf).apply(phys))
        ctx = P.ExecContext(sess.conf, sess)
        return sorted(P.collect_batches(phys.execute(ctx), phys.schema,
                                        ctx).to_rows(), key=repr), phys

    on, plan_on = run({})
    off, plan_off = run(FUSED_OFF)
    oracle, _ = run({}, tpu=False)
    (agg,) = _absorbing(plan_on)
    assert agg.mode == "complete" and not _absorbing(plan_off)
    _assert_same(on, off, oracle, exact=keyed)


def test_final_mode_never_absorbs():
    """A filter over a group-by feeds the NEXT group-by's partial
    aggregate, which absorbs it; the final aggregates read buffers off
    an exchange and absorb nothing."""
    def build(df):
        return (df.group_by("k").agg(F.sum("v").alias("sv"))
                .filter(F.col("sv") > 0)
                .group_by("sv").agg(F.count("*").alias("n")).sort("sv"))

    on, off, oracle, plan_on, _, m = _three_ways(build)
    assert [a.mode for a in _absorbing(plan_on)] == ["partial"]
    assert m["fusion.filtersAbsorbed"] == 1
    _assert_same(on, off, oracle, exact=True)


def test_nondeterministic_predicate_is_not_absorbed():
    # rand() runs on the device only as an incompatible op
    sess = srt.Session(
        {"spark.rapids.tpu.sql.incompatibleOps.enabled": True,
         "spark.rapids.tpu.sql.expr.Rand": True})
    df = sess.create_dataframe(_masked_data())
    q = df.filter(F.rand(7) < 0.5).group_by("k").agg(
        F.count("*").alias("n"))
    plan = sess.physical_plan(q.plan)
    assert not _absorbing(plan)
    assert any(isinstance(n, TpuFilterExec) for n in _walk(plan))
    # nor a deterministic filter under an aggregate of rand(): its
    # values depend on where the kept rows stand
    q = df.filter(F.col("p") < 50).agg(F.sum(F.rand(7)).alias("r"))
    plan = sess.physical_plan(q.plan)
    assert not _absorbing(plan)
    assert any(isinstance(n, TpuFilterExec) for n in _walk(plan))


@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "keyless"])
def test_absorbed_chain_runs_in_the_chunked_path(keyed):
    """A partition of several batches goes through ``_update_kernel``
    and the running merge: the prologue is in that kernel too."""
    calls = []
    real = TpuHashAggregateExec._agg_chunked

    def build(df):
        kept = df.filter(F.col("p") < 70)
        if keyed:
            return kept.group_by("k").agg(*_aggs()).sort("k")
        return kept.agg(*_aggs())

    def counted(self, *a, **kw):
        calls.append(bool(self.absorbed))
        return real(self, *a, **kw)

    mp = pytest.MonkeyPatch()
    mp.setattr(TpuHashAggregateExec, "_agg_chunked", counted)
    try:
        on, off, oracle, plan_on, _, _ = _three_ways(
            build, n_partitions=1,
            conf={"spark.rapids.tpu.sql.reader.batchSizeRows": 64,
                  "spark.rapids.tpu.sql.batchSizeBytes": 4096})
    finally:
        mp.undo()
    assert len(_absorbing(plan_on)) == 1
    assert True in calls, "the absorbing aggregate never chunked"
    _assert_same(on, off, oracle, exact=False)


@pytest.mark.oom_injection
@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "keyless"])
def test_absorbed_chain_survives_a_forced_split(keyed):
    """A split-type OOM at the aggregate's own checkpoint halves the
    RAW input: each half runs the prologue again in
    ``_update_kernel`` (``with_split_retry``), the buffers merge."""
    from spark_rapids_tpu.memory import retry as R

    def build(df):
        kept = df.filter(F.col("p") < 70)
        if keyed:
            return kept.group_by("k").agg(*_aggs()).sort("k")
        return kept.agg(*_aggs())

    fired = []
    real = R.maybe_inject_oom

    def inject(site="", nbytes=0):
        if site == "TpuHashAggregate" and not fired:
            fired.append(site)
            raise R.TpuSplitAndRetryOOM("injected at the aggregate",
                                        injected=True)
        return real(site, nbytes)

    splits = []
    real_split = TpuHashAggregateExec._agg_split

    def counted(self, *a, **kw):
        splits.append(bool(self.absorbed))
        return real_split(self, *a, **kw)

    clean, _, oracle, _, _, _ = _three_ways(build, n_partitions=1)
    mp = pytest.MonkeyPatch()
    mp.setattr(R, "maybe_inject_oom", inject)
    mp.setattr(TpuHashAggregateExec, "_agg_split", counted)
    try:
        sess = srt.Session({
            "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
            "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0})
        df = sess.create_dataframe(_masked_data(), n_partitions=1)
        got, plan = _collect_and_plan(sess, build(df))
    finally:
        mp.undo()
    assert fired and splits == [True], (fired, splits)
    assert len(_absorbing(plan)) == 1
    assert sess.last_metrics.get("retry.numSplitRetries", 0) >= 1
    _assert_same(got, clean, oracle, exact=False)


def test_an_aggregate_that_absorbs_nothing_keeps_its_kernel_keys():
    """The kernel-cache keys of a plain group-by say what they said
    before aggregates could absorb, and nothing more (one program as
    before in q3, q16, q18), and two aggregates that differ only in the
    absorbed predicate share no program."""
    from spark_rapids_tpu.exec.fused import members_signature
    from spark_rapids_tpu.exec.kernel_cache import (GLOBAL, _CachedKernel,
                                                    expr_signature,
                                                    schema_signature)

    def keys_of(op):
        held = [v for v in vars(op).values()
                if isinstance(v, _CachedKernel)]
        with GLOBAL._lock:
            return {key[0] for key, kern in GLOBAL._entries.items()
                    if any(kern is h for h in held)}

    def partial_of(sess, df):
        (agg,) = [n for n in _walk(sess.physical_plan(df.plan))
                  if isinstance(n, TpuHashAggregateExec)
                  and n.mode == "partial"]
        return agg

    sess = srt.Session()
    df = sess.create_dataframe(_masked_data())
    plain = partial_of(sess, df.group_by("k").agg(F.sum("v").alias("s")))
    assert not plain.absorbed
    sig = ("partial", schema_signature(plain.children[0].schema),
           expr_signature(plain.keys), ("sum(v)",),
           schema_signature(plain.schema))
    assert plain.signature == sig
    assert keys_of(plain) == {("agg", sig, phase) for phase in (
        "batch", "update", "merge", "merge_final")}

    def filtered(bound):
        return partial_of(sess, df.filter(F.col("p") < bound)
                          .group_by("k").agg(F.sum("v").alias("s")))

    a, b, again = filtered(10), filtered(20), filtered(10)
    assert a.absorbed and b.absorbed
    assert keys_of(a) == keys_of(again)
    assert not keys_of(a) & keys_of(b)
    assert not keys_of(a) & keys_of(plain)
    # the members' signatures and the chain's input schema are in it
    for key in keys_of(a):
        assert key[1] == a.signature
        assert members_signature(a.absorbed) in a.signature
        assert schema_signature(a.children[0].children[0].schema) in key[1]


def test_an_absorbing_aggregate_survives_with_new_children():
    """The adaptive executor rebuilds the unexecuted rest of a plan by
    ``with_new_children`` after every stage: the copy keeps the
    prologue, the schema and the kernels."""
    sess = srt.Session()
    df = sess.create_dataframe(_masked_data())
    q = df.filter(F.col("p") < 50).group_by("k").agg(
        F.sum("v").alias("s"))
    (agg,) = _absorbing(sess.physical_plan(q.plan))
    copy = agg.with_new_children(list(agg.children))
    assert copy is not agg and copy.absorbed == agg.absorbed
    assert copy.schema == agg.schema and copy._kernel is agg._kernel
    assert copy._update_kernel is agg._update_kernel
    assert copy.describe() == agg.describe()
    # and end to end under the stage loop
    on = srt.Session({"spark.rapids.tpu.sql.adaptive.enabled": True})
    rows = sorted(on.create_dataframe(_masked_data(), n_partitions=2)
                  .filter(F.col("p") < 50).group_by("k")
                  .agg(F.sum("v").alias("s")).collect(), key=repr)
    oracle = sorted(srt.Session(tpu_enabled=False)
                    .create_dataframe(_masked_data())
                    .filter(F.col("p") < 50).group_by("k")
                    .agg(F.sum("v").alias("s")).collect(), key=repr)
    assert rows == oracle
    assert on.last_metrics["fusion.filtersAbsorbed"] == 1


@pytest.mark.parametrize("query,absorbed", [
    ("q1", 1), ("q6", 1), ("q3", 0), ("q16", 0), ("q18", 0)])
def test_filters_absorbed_in_the_benchmark_cells_plans(
        query, absorbed, tmp_path):
    """``fusion.filtersAbsorbed`` as the six cells' plans give it: q1's
    and q6's filter stands directly under their partial aggregate; in
    q3, q16 and q18 every filter feeds a join or an exchange."""
    import json
    import os

    from benchmark.harness import datagen, load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (cell,) = [w for w in bench["workloads"]
               if w["traffic"].endswith(query) and w["chips"] == 1]
    (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(root, cfg["file"])) as fh:
        config = json.load(fh)
    mod = load_module("queries", query)
    rows = {t: max(4, n // 2000) for t, n in config["rows"].items()}
    path = str(tmp_path / "tables")
    datagen.write_tables(path, sorted(mod.TABLES), rows, 2**31 + 35,
                         dict(config["parquet"], rows_per_row_group=4096))
    sess = srt.Session(dict(config["conf"]))
    df = mod.build({t: sess.read_parquet(os.path.join(path, t))
                    for t in mod.TABLES})
    plan = sess.physical_plan(df.plan)
    assert len(_absorbing(plan)) == absorbed, plan.tree_string()
    if absorbed:
        df.collect()
        assert sess.last_metrics["fusion.filtersAbsorbed"] == 1
        off = srt.Session(dict(config["conf"], **FUSED_OFF))
        plan_off = off.physical_plan(mod.build(
            {t: off.read_parquet(os.path.join(path, t))
             for t in mod.TABLES}).plan)
        # with fusion off the plan is the one before this rewrite
        assert not _absorbing(plan_off) and any(
            isinstance(n, TpuFilterExec) for n in _walk(plan_off))


# ==========================================================================
# dispatch economics (the acceptance criterion)
# ==========================================================================
def test_fused_chain_is_one_dispatch_per_batch():
    """Project->Filter->Project over N single-batch partitions: the
    fused plan issues exactly N kernel dispatches; the unfused plan
    issues 3N (one per member per batch)."""
    n_parts = 4
    data = {"a": list(range(1, 81)), "b": [i * 2 for i in range(1, 81)]}

    def run(conf):
        sess = srt.Session(dict(conf))
        df = sess.create_dataframe(data, n_partitions=n_parts)
        q = (df.select("a", "b", (F.col("a") + F.col("b")).alias("s"))
             .filter(F.col("a") > 10)
             .select("s"))
        rows = q.collect()
        return rows, sess.last_metrics

    rows_f, m_f = run({})
    rows_u, m_u = run(FUSED_OFF)
    assert rows_f == rows_u
    assert m_f["kernelCache.dispatches"] == n_parts, m_f
    assert m_u["kernelCache.dispatches"] == 3 * n_parts, m_u


# ==========================================================================
# TPC-H bit-identity (fused vs unfused device plans)
# ==========================================================================
def _tpch_rows(qnum, conf=None, tpu=True):
    sess = srt.Session(dict(conf or {}), tpu_enabled=tpu)
    tables = tpch_datagen.dataframes(sess, sf=SF, seed=SEED)
    df = tpch.QUERIES[qnum](tables)
    sess.start_capture()
    rows = df.collect()
    return rows, sess.captured_plans()[-1]


@pytest.mark.parametrize("qnum", [1, 3, 5, 6, 16])
def test_tpch_fused_vs_unfused_bit_identical(qnum):
    fused, plan_f = _tpch_rows(qnum)
    unfused, plan_u = _tpch_rows(qnum, conf=FUSED_OFF)
    if qnum == 6:
        # q6's filter is absorbed by a KEYLESS sum: no sort brings the
        # kept rows together, so the device adds other blocks of rows
        # than after a compaction and the one f64 is equal to rounding,
        # not to the bit (everything else, q1's keyed sums too, is)
        ((got,),), ((want,),) = fused, unfused
        assert got == pytest.approx(want, rel=1e-12)
    else:
        # same rows, same order, same bits — compaction deferral must
        # be invisible (exact ==, no float tolerance)
        assert fused == unfused, f"q{qnum} diverged under fusion"
    assert not _segments(plan_u) and not _absorbing(plan_u)
    # q1's and q6's single pre-aggregate filter is absorbed by the
    # partial aggregate over it (no >=2 chain, no segment); the
    # scan-filter->project chains of q3/q5/q16 must fuse, and feed
    # joins, which take dense rows
    if qnum in (3, 5, 16):
        assert _segments(plan_f), f"q{qnum} produced no fused segment"
    else:
        (agg,) = _absorbing(plan_f)
        assert [type(m) for m in agg.absorbed] == [TpuFilterExec]
        assert not any(isinstance(n, TpuFilterExec)
                       for n in _walk(plan_f))


@pytest.mark.fault_injection
def test_tpch_q3_fused_bit_identical_under_corrupt_injection():
    """Shuffle-payload corruption recovery re-executes the producing
    stage from lineage — the fused plan must come out bit-identical to
    its own injection-free run."""
    conf = {
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0,
        "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
        "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
        "spark.rapids.tpu.fault.injection.mode": "nth",
        "spark.rapids.tpu.fault.injection.type": "corrupt",
        "spark.rapids.tpu.fault.injection.site": "exchange.write",
        "spark.rapids.tpu.fault.injection.skipCount": 0,
    }
    clean, _ = _tpch_rows(3, conf={
        "spark.rapids.tpu.sql.broadcastSizeThreshold": 0})
    injected, plan = _tpch_rows(3, conf=conf)
    assert injected == clean
    assert _segments(plan)


@pytest.mark.oom_injection
def test_tpch_q3_fused_bit_identical_under_oom_injection():
    conf = {
        "spark.rapids.tpu.memory.retry.backoffBaseMs": 0.1,
        "spark.rapids.tpu.memory.retry.backoffMaxMs": 2.0,
        "spark.rapids.tpu.memory.oomInjection.mode": "nth",
        "spark.rapids.tpu.memory.oomInjection.skipCount": 1,
        "spark.rapids.tpu.memory.oomInjection.oomType": "retry",
    }
    clean, _ = _tpch_rows(3)
    injected, plan = _tpch_rows(3, conf=conf)
    assert injected == clean
    assert _segments(plan)


# ==========================================================================
# telemetry surfaces
# ==========================================================================
def test_profile_attributes_metrics_to_fused_segment():
    sess = srt.Session({"spark.rapids.tpu.telemetry.enabled": True})
    df = sess.create_dataframe(
        {"a": list(range(1, 21)), "b": [i * 2 for i in range(1, 21)]})
    (df.select("a", "b", (F.col("a") + F.col("b")).alias("s"))
     .filter(F.col("a") > 3)
     .select("s")).collect()
    report = sess.profile_report()
    assert "TpuFusedSegment" in report, report
    assert "Kernel cache" in report and "hitRate" in report, report
    m = sess.last_metrics
    assert any(k.startswith("TpuFusedSegmentExec.") for k in m), m
    assert m.get("kernelCache.dispatches", 0) >= 1, m
