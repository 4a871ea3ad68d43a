"""Device join vs CPU oracle (reference test analogue: join_test.py +
HashAggregatesSuite-style dual-session equality)."""
import numpy as np
import pytest
from conftest import jaxpr_eqns

import spark_rapids_tpu as srt
from spark_rapids_tpu import f
from spark_rapids_tpu import types as T


def _norm(rows):
    return sorted(
        (tuple((None if v is None else
                (round(v, 9) if isinstance(v, float) else v))
               for v in r) for r in rows),
        key=repr)


def _run_both(build, how_assert_on_tpu=True):
    tpu = srt.Session()
    cpu = srt.Session(tpu_enabled=False)
    tq = build(tpu)
    cq = build(cpu)
    if how_assert_on_tpu:
        ex = tq.explain()
        assert "Join" in ex and "will run on TPU" in ex, ex
    got = _norm(tq.collect())
    want = _norm(cq.collect())
    assert got == want, f"\nTPU: {got}\nCPU: {want}"


LEFT = {"k": [1, 2, 2, 3, None, 5, 6],
        "a": [10.0, 20.0, 21.0, 30.0, 40.0, 50.0, 60.0]}
RIGHT = {"k": [2, 2, 3, 4, None, 6],
         "b": ["x", "y", "z", "w", "n", "q"]}


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "semi", "anti"])
def test_join_types_match_oracle(how):
    def build(sess):
        l = sess.create_dataframe(LEFT)
        r = sess.create_dataframe(RIGHT)
        return l.join(r, on="k", how=how)

    _run_both(build)


def test_join_duplicate_heavy_keys():
    rng = np.random.RandomState(11)
    lk = rng.randint(0, 8, 300).tolist()
    rk = rng.randint(0, 8, 200).tolist()

    def build(sess):
        l = sess.create_dataframe({"k": lk,
                                   "a": list(range(300))})
        r = sess.create_dataframe({"k": rk,
                                   "b": list(range(200))})
        return l.join(r, on="k", how="inner")

    _run_both(build)


def test_join_string_keys():
    def build(sess):
        l = sess.create_dataframe({"k": ["aa", "bb", None, "cc", "aa"],
                                   "a": [1, 2, 3, 4, 5]})
        r = sess.create_dataframe({"k": ["aa", "cc", "dd", None],
                                   "b": [9.0, 8.0, 7.0, 6.0]})
        return l.join(r, on="k", how="left")

    _run_both(build)


def test_join_mixed_dtype_keys():
    s_int = T.Schema([T.Field("k", T.INT32), T.Field("a", T.INT64)])
    s_dbl = T.Schema([T.Field("k", T.FLOAT64), T.Field("b", T.INT64)])

    def build(sess):
        l = sess.create_dataframe({"k": [1, 2, 3], "a": [1, 2, 3]}, s_int)
        r = sess.create_dataframe({"k": [1.0, 3.0, 4.5],
                                   "b": [10, 30, 45]}, s_dbl)
        return l.join(r, on="k", how="inner")

    _run_both(build)


def test_inner_join_with_condition():
    def build(sess):
        l = sess.create_dataframe(LEFT)
        r = sess.create_dataframe(RIGHT)
        return l.join(r, on="k", how="inner",
                      condition=f.col("a") > f.lit(15.0))

    _run_both(build)


def test_outer_join_with_condition_falls_back():
    sess = srt.Session()
    l = sess.create_dataframe(LEFT)
    r = sess.create_dataframe(RIGHT)
    # a residual condition on an outer join must fall back
    j = l.join(r, on="k", how="left", condition=f.col("a") > f.lit(15.0))
    ex = j.explain()
    assert "cannot run on TPU" in ex
    cpu = srt.Session(tpu_enabled=False)
    lc = cpu.create_dataframe(LEFT)
    rc = cpu.create_dataframe(RIGHT)
    jc = lc.join(rc, on="k", how="left",
                 condition=f.col("a") > f.lit(15.0))
    assert _norm(j.collect()) == _norm(jc.collect())


def test_empty_sides():
    for lrows, rrows in [(0, 4), (4, 0), (0, 0)]:
        def build(sess, lrows=lrows, rrows=rrows):
            s1 = T.Schema([T.Field("k", T.INT64), T.Field("a", T.INT64)])
            s2 = T.Schema([T.Field("k", T.INT64), T.Field("b", T.INT64)])
            l = sess.create_dataframe(
                {"k": list(range(lrows)), "a": list(range(lrows))}, s1)
            r = sess.create_dataframe(
                {"k": list(range(rrows)), "b": list(range(rrows))}, s2)
            return l.join(r, on="k", how="left")

        _run_both(build, how_assert_on_tpu=False)


def test_broadcast_artifact_reused_across_collects():
    """The broadcast build side is materialized ONCE and shared across
    repeated collects of the same plan; the artifact dies with the plan
    (reference: GpuBroadcastExchangeExec.scala:215-247 builds the
    broadcast relation once and Spark caches it)."""
    import gc

    from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec

    sess = srt.Session()
    l = sess.create_dataframe(
        {"k": list(range(100)), "v": list(range(100))})
    r = sess.create_dataframe(
        {"rk": list(range(0, 100, 2)), "w": list(range(50))})
    j = l.join(r, on=(["k"], ["rk"]), how="inner")

    phys, _ctx = sess.prepare_execution(j.plan)
    phys._exec_lock.release()
    found = []

    def walk(n):
        if isinstance(n, TpuBroadcastHashJoinExec):
            found.append(n)
        for c in getattr(n, "children", []):
            walk(c)

    walk(phys)
    assert found, "small build side must plan as a broadcast join"

    reg = sess.broadcast_registry
    base = reg.builds
    a = _norm(j.collect())
    b = _norm(j.collect())
    assert a == b and len(a) == 50
    assert reg.builds == base + 1, \
        "build side must materialize exactly once across collects"
    assert len(reg) >= 1

    # plan dropped -> artifact purged (no session-lifetime leak)
    del j, phys, found
    gc.collect()
    reg._purge_dead()
    assert len(reg) == 0


# --------------------------------------------------------------------------
# the probe at the kernel, against a numpy oracle, to the bit
# --------------------------------------------------------------------------
def _codes(l_cols, r_cols, l_ok, r_ok):
    """Dense int64 rank of every row's key under Spark's order and
    equality (NaN equals NaN and is greatest, -0.0 equals 0.0), over
    both sides; -1 for a row that never joins (null key, padding)."""
    nl = len(l_ok)
    code = np.zeros(nl + len(r_ok), dtype=np.int64)
    ok = np.concatenate([l_ok, r_ok])
    for (lv, lvalid), (rv, rvalid) in zip(l_cols, r_cols):
        vals = np.concatenate([lv, rv])
        if vals.dtype.kind == "f":
            vals = np.where(vals == 0.0, 0.0, vals)
        ok = ok & np.concatenate([lvalid, rvalid])
        uniq, inv = np.unique(vals, return_inverse=True)
        code = code * (len(uniq) + 1) + inv.reshape(-1)
    code = np.where(ok, np.unique(code, return_inverse=True)[1].reshape(-1),
                    -1)
    return code[:nl], code[nl:]


def _probe_oracle(cl, cr):
    """(order_r's joining prefix, lo, cnt, has_r) from the key codes."""
    by_key = np.argsort(np.where(cr >= 0, cr, np.iinfo(np.int64).max),
                        kind="stable")
    n_join = int((cr >= 0).sum())
    keys_r = cr[by_key[:n_join]]
    lo = np.searchsorted(keys_r, cl, side="left")
    hi = np.searchsorted(keys_r, cl, side="right")
    live = cl >= 0
    lo, cnt = np.where(live, lo, 0), np.where(live, hi - lo, 0)
    has_r = (cr >= 0) & np.isin(cr, cl[live])
    return by_key[:n_join], lo, cnt, has_r


def _device_key(values, valid):
    import jax.numpy as jnp

    from spark_rapids_tpu.data import strings
    from spark_rapids_tpu.data.column import DeviceColumn

    if values.dtype.kind == "U":
        data, lengths = strings.encode(values.astype(object), None)
        return DeviceColumn(T.STRING, jnp.asarray(data), jnp.asarray(valid),
                            jnp.asarray(lengths))
    return DeviceColumn(T.from_numpy(values.dtype), jnp.asarray(values),
                        jnp.asarray(valid))


def _ints(lo, hi):
    return lambda rng, n, side: rng.integers(lo, hi, n).astype(np.int64)


def _long_run(at, length, n):
    """Keys rising a row, but for one key that ``length`` rows share
    from sorted position ``at``: a run across the scans' block edges."""
    def make(rng, m, side):
        keys = rng.permutation(n)[:m].astype(np.int64) * 2 + 1
        # a share of each side joins the run; the run's key sorts where
        # `at` rows lie below it
        keys[rng.random(m) < length / n] = 2 * at
        return keys
    return make


def _floats(rng, n, side):
    return rng.choice(np.array([0.0, -0.0, np.nan, 1.5, -2.25, np.inf,
                                -np.inf, 3e300]), n)


def _strings(rng, n, side):
    return rng.choice(np.array(["", "a", "ab", "abc", "b", "ba",
                                "lineitem", "line"]), n)


# (left rows, right rows, a key maker (rng, rows, side) a column, share of
#  null keys, padding rows on each side)
PROBE_CASES = {
    "unique_build_keys": (1500, 700, [lambda rng, n, side: rng.permutation(
        4000)[:n].astype(np.int64)], 0.0, 0),
    "duplicate_heavy_both_sides": (900, 600, [_ints(0, 8)], 0.0, 0),
    "one_key_for_every_row": (700, 500, [_ints(7, 8)], 0.0, 0),
    "disjoint_sides": (600, 600, [lambda rng, n, side: (
        rng.integers(0, 500, n) * 2 + side).astype(np.int64)], 0.0, 0),
    "null_keys_and_padding": (1000, 800, [_ints(0, 300)], 0.2, 150),
    "run_crosses_a_scan_block": (2600, 1900, [_long_run(700, 1800, 4500)],
                                 0.05, 40),
    "run_crosses_the_second_level": (
        1_150_000, 50_000, [_long_run(1_058_000, 20_000, 1_200_000)],
        0.0, 1000),
    "rows_not_a_multiple_of_1024": (1531, 1100, [_ints(0, 900)], 0.1, 77),
    "int64_wide": (800, 800, [lambda rng, n, side: rng.choice(np.array(
        [-2 ** 63, -2 ** 32 - 1, -1, 0, 1, 2 ** 32, 2 ** 32 + 1,
         2 ** 63 - 1], dtype=np.int64), n)], 0.1, 30),
    "float64_nan_and_signed_zero": (900, 700, [_floats], 0.1, 30),
    "string": (900, 700, [_strings], 0.1, 30),
    "two_columns": (1200, 900, [_ints(0, 6), _strings], 0.1, 30),
}


@pytest.mark.parametrize("case", list(PROBE_CASES))
def test_probe_equals_the_numpy_oracle_to_the_bit(case):
    """``J.probe``'s four arrays against what numpy says of the same
    keys: for each left row the run ``order_r[lo:lo+cnt]`` is exactly
    its matches in key order, rows that never join count nothing and
    stand last in ``order_r``."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.kernels import join as J

    nl, nr, makers, null_share, padding = PROBE_CASES[case]
    rng = np.random.default_rng(len(case))
    sides = []
    for side, n in enumerate((nl, nr)):
        cols = [(make(rng, n, side), rng.random(n) >= null_share)
                for make in makers]
        sides.append((cols, np.arange(n) < n - padding))
    (l_cols, l_ok), (r_cols, r_ok) = sides
    cl, cr = _codes(l_cols, r_cols, l_ok, r_ok)
    if case == "disjoint_sides":
        assert not np.isin(cl, cr).any()
    if case.startswith("run_crosses"):
        # the longest run of one key spans the edge it is named for
        edge = 1024 if case.endswith("block") else 1024 * 1024
        both = np.sort(np.concatenate([cl[cl >= 0], cr[cr >= 0]]))
        top = np.bincount(both).argmax()
        first, last = np.searchsorted(both, [top, top + 1])
        assert first // edge < (last - 1) // edge, (first, last)

    p = J.probe([_device_key(*c) for c in l_cols],
                [_device_key(*c) for c in r_cols],
                jnp.asarray(l_ok), jnp.asarray(r_ok))
    order_r, lo, cnt, has_r = (np.asarray(x) for x in p)
    assert (order_r.dtype, lo.dtype, cnt.dtype, has_r.dtype) == (
        np.int32, np.int32, np.int32, np.bool_)
    joining, want_lo, want_cnt, want_has = _probe_oracle(cl, cr)
    np.testing.assert_array_equal(order_r[:len(joining)], joining)
    np.testing.assert_array_equal(np.sort(order_r), np.arange(nr))
    np.testing.assert_array_equal(lo, want_lo)
    np.testing.assert_array_equal(cnt, want_cnt)
    np.testing.assert_array_equal(has_r, want_has)


def test_count_program_holds_no_search_and_no_row_wide_scatter():
    """The join's count program finds every run by scans: no
    ``searchsorted``, no loop but the lexsort's (a search is a ``while``
    that gathers every query a step), no scatter with an index a row
    (the id scatter and the split of the sort by side must not come
    back), and the key changes from one stacked gather by the order."""
    import jax

    from spark_rapids_tpu.data.column import HostBatch, host_to_device
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec

    rng = np.random.default_rng(31)
    left = {"k": rng.integers(0, 900, 3000), "a": rng.random(3000)}
    right = {"k": rng.permutation(1200)[:1000], "b": rng.random(1000)}
    sess = srt.Session()
    q = sess.create_dataframe(left).join(sess.create_dataframe(right),
                                         on="k", how="inner")
    todo, join = [sess.physical_plan(q.plan)], None
    while todo:
        node = todo.pop()
        if isinstance(node, TpuHashJoinExec):
            join = node
        todo.extend(node.children)
    assert join is not None, q.explain()

    lb = host_to_device(HostBatch.from_pydict(left))
    rb = host_to_device(HostBatch.from_pydict(right))
    n = lb.padded_rows + rb.padded_rows
    assert n == 4096 + 1024
    eqns = list(jaxpr_eqns(
        jax.make_jaxpr(join.kernel_twin()._count)(lb, rb).jaxpr))
    named = [e.params.get("name") for e in eqns
             if e.primitive.name in ("pjit", "jit")]
    assert "searchsorted" not in named, named
    # one int64 key is three words: the lexsort's loop over two of them
    # is the one loop that gathers (the scans' loops add, row by row)
    gathering = [e for e in eqns if e.primitive.name in ("scan", "while")
                 and any(i.primitive.name == "gather" for i in jaxpr_eqns(
                     (e.params.get("jaxpr") or e.params["body_jaxpr"]).jaxpr))]
    assert len(gathering) == 1, gathering
    wide = [(e.primitive.name, e.invars[1].aval.shape) for e in eqns
            if e.primitive.name.startswith("scatter")
            and e.invars[1].aval.shape[:1] >= (rb.padded_rows,)]
    assert not wide, wide
    by_order = [e.outvars[0].aval.shape for e in eqns
                if e.primitive.name == "gather"
                and e.outvars[0].aval.shape[-1:] == (n,)
                and e.outvars[0].aval.ndim == 2]
    assert by_order == [(3, n)], by_order
    assert sum(e.primitive.name == "sort" for e in eqns) == 4


# --------------------------------------------------------------------------
# the expand at the kernel, by sort and by search, against a numpy oracle
# --------------------------------------------------------------------------
def _expand_oracle(p, emit, r_extra, c_out):
    """(lidx, ridx, slot_valid) of ``expand_pairs`` from the probe's
    arrays: each left row's ``emit`` slots in row order, its k-th the
    right row ``order_r[lo + k]`` (-1 where it has no match), then the
    unmatched right rows in row order, then -1s."""
    order_r, lo, cnt = (np.asarray(x) for x in (p.order_r, p.lo, p.cnt))
    emit, r_extra = np.asarray(emit), np.asarray(r_extra)
    li = np.repeat(np.arange(len(emit)), emit)
    k = np.arange(len(li)) - np.repeat(np.cumsum(emit) - emit, emit)
    ri = np.where(cnt[li] > 0, order_r[np.clip(lo[li] + k, 0, None)
                                       % len(order_r)], -1)
    extra = np.flatnonzero(r_extra)
    lidx = np.concatenate([li, np.full(len(extra), -1)])
    ridx = np.concatenate([ri, extra])
    n = len(lidx)
    assert n <= c_out
    pad = np.full(c_out - n, -1)
    return (np.concatenate([lidx, pad]), np.concatenate([ridx, pad]),
            np.arange(c_out) < n)


# (left rows, right rows, key maker, share of null keys, padding rows,
#  slots past the bucket of the output's rows: a factor)
EXPAND_CASES = {
    "nothing_emitted": (900, 600, lambda rng, n, side: (
        rng.integers(0, 500, n) * 2 + side).astype(np.int64), 0.0, 0, 1),
    "every_left_row_unmatched": (700, 500, lambda rng, n, side: (
        rng.integers(0, 500, n) * 2 + side).astype(np.int64), 0.1, 20, 1),
    "c_out_far_above_the_rows": (600, 400, _ints(0, 300), 0.0, 0, 8),
    "padding_and_null_keys": (1000, 800, _ints(0, 300), 0.2, 150, 1),
    "one_left_row_owns_every_slot": (1, 3000, _ints(7, 8), 0.0, 0, 1),
    "runs_cross_a_scan_block": (2600, 1900, _long_run(700, 1800, 4500),
                                0.05, 40, 1),
    "few_slots_over_a_wide_side": (30_000, 300, lambda rng, n, side: (
        rng.permutation(60_000)[:n]).astype(np.int64), 0.0, 0, 1),
}


@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
@pytest.mark.parametrize("case", list(EXPAND_CASES))
def test_expand_by_sort_and_by_search_equal_the_oracle_to_the_bit(case,
                                                                 how):
    """Both ways of mapping a slot to its left row give the oracle's
    three arrays, and ``expand_pairs`` gives what the rule picks."""
    import jax.numpy as jnp

    from spark_rapids_tpu.data.column import bucket_rows
    from spark_rapids_tpu.ops.kernels import join as J

    nl, nr, make, null_share, padding, wider = EXPAND_CASES[case]
    rng = np.random.default_rng(len(case))
    sides = []
    for side, n in enumerate((nl, nr)):
        n_pad = bucket_rows(n + padding)
        keys = np.zeros(n_pad, np.int64)
        keys[:n] = make(rng, n, side)
        valid = np.arange(n_pad) < n
        valid[:n] &= rng.random(n) >= null_share
        rows = np.arange(n_pad) < n + padding
        sides.append((_device_key(keys, valid), jnp.asarray(rows)))
    (lk, l_rm), (rk, r_rm) = sides
    p = J.probe([lk], [rk], l_rm, r_rm)
    emit, r_extra, total = J.emit_counts(p, how, l_rm, r_rm)
    c_out = bucket_rows(int(total)) * wider
    want = _expand_oracle(p, emit, r_extra, c_out)
    if case == "nothing_emitted" and how == "inner":
        assert int(total) == 0
    if case == "one_left_row_owns_every_slot" and how == "inner":
        assert int(total) == nr and (np.asarray(emit) > 0).sum() == 1
    got = {}
    for by_sort in (True, False):
        got[by_sort] = [np.asarray(x) for x in J._expand_pairs(
            p, emit, r_extra, c_out, by_sort)]
        for a, b in zip(got[by_sort], want):
            np.testing.assert_array_equal(a, b)
    rule = J.expand_by_sort(emit.shape[0], c_out)
    for a, b in zip(J.expand_pairs(p, emit, r_extra, c_out), got[rule]):
        np.testing.assert_array_equal(np.asarray(a), b)
    # a left or full join emits every left row: never few slots
    assert rule == (case != "few_slots_over_a_wide_side"
                    or how in ("left", "full"))


#: (nl, c_out) -> the mapping PERF.md's microbenchmark table says is the
#: faster on a v5e (section 6): q21's ``supplier`` join, q16's
#: inner join, a mesh shard after its exchange, a small expansion over a
#: large side
MEASURED = {(1 << 21, 1 << 21): True, (1 << 23, 1 << 21): True,
            (1 << 24, 1 << 21): True, (1 << 23, 1 << 15): False}


@pytest.mark.parametrize("shape", list(MEASURED), ids=str)
def test_the_shape_rule_picks_what_the_chip_measured(shape):
    from spark_rapids_tpu.ops.kernels import join as J

    assert J.expand_by_sort(*shape) == MEASURED[shape]


@pytest.mark.parametrize("nl, c_out", [(1 << 21, 1 << 21),
                                       (1 << 23, 1 << 21),
                                       (1 << 23, 1 << 15)])
def test_expand_program_searches_only_where_the_rule_says(nl, c_out):
    """At a q21- or q16-like shape the expand holds no ``searchsorted``
    and no loop that gathers (a search is a ``while`` that gathers every
    slot a step): its rows come from two sorts.  Where few slots stand
    over a wide side the search is still there."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.kernels import join as J

    nr = 1 << 17

    def arr(n, dt):
        return jax.ShapeDtypeStruct((n,), jnp.dtype(dt))

    p = J.Probe(arr(nr, jnp.int32), arr(nl, jnp.int32),
                arr(nl, jnp.int32), arr(nr, jnp.bool_))
    jaxpr = jax.make_jaxpr(lambda p, e, r: J.expand_pairs(p, e, r, c_out))(
        p, arr(nl, jnp.int32), arr(nr, jnp.bool_)).jaxpr
    eqns = list(jaxpr_eqns(jaxpr))
    named = [e.params.get("name") for e in eqns
             if e.primitive.name in ("pjit", "jit")]
    gathering = [e for e in eqns if e.primitive.name in ("scan", "while")
                 and any(i.primitive.name == "gather" for i in jaxpr_eqns(
                     (e.params.get("jaxpr") or e.params["body_jaxpr"]).jaxpr))]
    sorts = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "sort"]
    if J.expand_by_sort(nl, c_out):
        assert "searchsorted" not in named and not gathering, named
        assert sorts == [(nl + c_out,)] * 2, sorts
    else:
        assert "searchsorted" in named and gathering
        assert not sorts, sorts


@pytest.mark.parametrize("wide", [False, True])
def test_session_counts_the_expands_by_how_they_map_their_slots(
        wide, monkeypatch):
    """``join.expandBySort`` / ``join.expandBySearch`` in
    ``Session.last_metrics`` count the inner join's expand programs by
    the mapping ``expand_by_sort`` picked: a join whose output is as
    wide as its left side sorts, a few matches over a wide side search.
    The counter and the kernel, while it traces, ask the rule of the
    same shapes."""
    from spark_rapids_tpu.data.column import bucket_rows
    from spark_rapids_tpu.ops.kernels import join as J

    asked = []
    rule = J.expand_by_sort

    def recorded(nl, c_out):
        asked.append((int(nl), int(c_out)))
        return rule(nl, c_out)

    monkeypatch.setattr(J, "expand_by_sort", recorded)
    n = 20_000 if wide else 300
    # column names of this test alone: a program no other test compiled,
    # so the expand traces here
    key, build = f"rule_probe_{n}", f"rule_build_{n}"
    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    left = sess.create_dataframe({key: list(range(n)), "a": list(range(n))},
                                 n_partitions=1)
    right = sess.create_dataframe({build: [3, 5, 7], "b": [1, 2, 3]},
                                  n_partitions=1)
    got = left.join(right, on=([key], [build])).collect()
    assert sorted(got) == [(3, 3, 3, 1), (5, 5, 5, 2), (7, 7, 7, 3)]
    m = sess.last_metrics
    shape = (bucket_rows(n), bucket_rows(3))
    assert asked == [shape, shape], asked
    assert rule(*shape) == (not wide)
    assert (m["join.expandBySort"], m["join.expandBySearch"]) == (
        (0, 1) if wide else (1, 0))
