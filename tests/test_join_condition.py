"""A semi or anti join with a condition, on the device (ISSUE 38).

Spark plans a correlated ``EXISTS`` / ``NOT EXISTS`` whose correlation
is not only equalities as a LeftSemi / LeftAnti join with a residual
condition (TPC-H q21).  Where the condition is one comparison of a left
value with a right one (``<``, ``<=``, ``>``, ``>=``, ``<>``) over
integers, dates or timestamps, the device decides it from each key's
least and greatest right value (``ops/kernels/join.py:some_holds``) and lays
out no pair.  Any other condition keeps its pairs: the device lays them
out by one sort and scans (``pair_rows``), evaluates the condition on
every pair and keeps a left row where any pair is TRUE (NULL is no
match).  Each case here is answered three ways: the device path on the
CPU backend, the host engine (``plan/physical.py``), and a plain loop
over the pairs.  Then: the split of the stream side where the pairs do
not fit, Q21's shape through a strict session with its counters, and
the lowered text of the programs the bounds and the expand's sort path
leave alone, which must be what it was before either.
"""
import hashlib
import operator

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import joins
from spark_rapids_tpu.exec.joins import TpuHashJoinExec
from spark_rapids_tpu.plan import functions as F

STRICT = {"spark.rapids.tpu.sql.test.enabled": True}
LEFT = T.Schema([T.Field("k", T.INT64, True), T.Field("s", T.INT64, True)])
RIGHT = T.Schema([T.Field("k2", T.INT64, True),
                  T.Field("s2", T.INT64, True)])


def _many(n_left, n_right, key=7):
    """One key with ``n_left`` x ``n_right`` pairs beside a few others."""
    return ({"k": [key] * n_left + [1, 2],
             "s": list(range(n_left)) + [5, 6]},
            {"k2": [key] * n_right + [1, 3],
             "s2": [0] * n_right + [5, 6]})


CASES = {
    # null keys on either side never match; a NULL s makes every pair
    # of its row NULL (no match), a NULL s2 the pairs it stands in
    "nulls": ({"k": [1, None, 2, 2, 3, None, 4],
               "s": [10, 11, None, 12, 13, 14, 15]},
              {"k2": [1, 2, None, 2, 3, 4, None],
               "s2": [11, None, 12, 12, 13, 15, 15]}),
    # a left key no right row has: anti keeps it, semi drops it
    "no_key_match": ({"k": [1, 2, 3, 9], "s": [1, 2, 3, 4]},
                     {"k2": [1, 2, 3], "s2": [5, 5, 5]}),
    # every pair of key 1 fails the condition, of key 2 one holds
    "every_pair_fails": ({"k": [1, 1, 2, 2], "s": [4, 4, 4, 5]},
                         {"k2": [1, 1, 1, 2, 2], "s2": [4, 4, 4, 4, 4]}),
    "duplicates_both_sides": (
        {"k": [1, 1, 1, 2, 2, 3, 3, 3, 3], "s": [1, 2, 3, 1, 1, 5, 6, 7, 5]},
        {"k2": [3, 1, 3, 1, 2, 2, 3], "s2": [5, 1, 5, 2, 1, 1, 6]}),
    "empty_left": ({"k": [], "s": []}, {"k2": [1, 2], "s2": [1, 2]}),
    "empty_right": ({"k": [1, 2, None], "s": [1, 2, 3]},
                    {"k2": [], "s2": []}),
    # 17 x 8 = 136 pairs of key 7: past a 128-slot bucket, into 256
    "crosses_a_bucket": _many(17, 8),
}

#: what the bounds decide besides: a key whose right values are all
#: NULL (no match, whatever the operator), a NULL left value beside
#: right values, keys of one right row, the int64 extremes (the least
#: one's words are the scan's identity, 0) and a key over three blocks
#: of the scan
BOUNDS_CASES = dict(CASES, **{
    "right_values_all_null": ({"k": [1, 1, 2], "s": [0, 5, None]},
                              {"k2": [1, 1, 2, 2], "s2": [None, None, 3, 4]}),
    "null_left_value": ({"k": [1, 1, 2, 2], "s": [None, 1, None, 9]},
                        {"k2": [1, 1, 2], "s2": [1, 2, 9]}),
    "one_right_row_a_key": ({"k": [1, 2, 3, 3, 4], "s": [1, 1, 2, 4, 0]},
                            {"k2": [1, 2, 3, 4], "s2": [1, 2, 3, None]}),
    # one key's right rows over three blocks of the scan (1024 rows),
    # its values spread over the int64 range
    "crosses_scan_blocks": (
        {"k": [7, 7, 7, 7, 8], "s": [-2**62, 0, 2**62, None, 1]},
        {"k2": [7] * 2500 + [8],
         "s2": [(i * 7919 % 2500 - 1250) * 2**50 for i in range(2500)]
         + [1]}),
    "extremes": ({"k": [1, 1, 2, 2, 3],
                  "s": [-2**63, 2**63 - 1, -2**63, 0, 2**63 - 1]},
                 {"k2": [1, 2, 2, 3, 3],
                  "s2": [-2**63, 2**63 - 1, -2**63, 2**63 - 1, None]}),
})

#: the operators the bounds take: each builds the condition of two
#: columns and compares a left value with a right one in the loop
OPERATORS = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
             ">=": operator.ge, "!=": operator.ne}


def _expected(left, right, how, holds=operator.ne):
    """The left rows a plain loop over every pair keeps."""
    keep = []
    for k, s in zip(left["k"], left["s"]):
        hit = any(k is not None and k == k2 and s is not None
                  and s2 is not None and holds(s, s2)
                  for k2, s2 in zip(right["k2"], right["s2"]))
        if hit == (how == "semi"):
            keep.append((k, s))
    return sorted(keep, key=repr)


def _pairs(left, right):
    return sum(k is not None and k == k2
               for k in left["k"] for k2 in right["k2"])


#: conditions the bounds refuse, each TRUE where ``s != s2`` is (the
#: keys of a pair are equal): they keep their pairs
PAIR_CONDITIONS = {
    "operand_reads_both_sides": lambda c: (c("s") - c("s2")) != F.lit(0),
    "and": lambda c: (c("s") != c("s2")) & (c("k") == c("k2")),
    "or": lambda c: (c("s") != c("s2")) | (c("k") != c("k2")),
}


def _query(sess, left, right, how, n_partitions=1,
           condition=PAIR_CONDITIONS["operand_reads_both_sides"]):
    lf = sess.create_dataframe(left, schema=LEFT, n_partitions=n_partitions)
    rf = sess.create_dataframe(right, schema=RIGHT, n_partitions=1)
    return lf.join(rf, on=(["k"], ["k2"]), how=how,
                   condition=condition(F.col))


def _device_join(sess, df):
    op, = [n for n in _walk(sess.physical_plan(df.plan))
           if isinstance(n, TpuHashJoinExec)]
    return op


@pytest.mark.parametrize("form", sorted(PAIR_CONDITIONS))
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("how", ["semi", "anti"])
def test_device_equals_host_and_the_pairs(how, case, form):
    left, right = CASES[case]
    want = _expected(left, right, how)
    cond = PAIR_CONDITIONS[form]
    host = _query(srt.Session(tpu_enabled=False), left, right, how,
                  condition=cond)
    assert sorted(host.collect(), key=repr) == want
    sess = srt.Session(STRICT)
    df = _query(sess, left, right, how, condition=cond)
    assert f"* HashJoinExec [{how}, (" in df.explain()
    assert _device_join(sess, df).path == "pairs"
    assert sorted(df.collect(), key=repr) == want
    m = sess.last_metrics
    assert "join.conditionByBounds" not in m
    assert m["join.conditionJoins"] == 1
    assert m["join.conditionPairs"] == _pairs(left, right)
    slots = m["join.conditionPairSlots"]
    assert slots >= max(m["join.conditionPairs"], 128)
    assert slots & (slots - 1) == 0
    if case == "crosses_a_bucket":
        assert (m["join.conditionPairs"], slots) == (136 + 1, 256)


@pytest.mark.parametrize("case", sorted(BOUNDS_CASES))
@pytest.mark.parametrize("left_first", [True, False],
                         ids=["left_op_right", "right_op_left"])
@pytest.mark.parametrize("op", sorted(OPERATORS))
@pytest.mark.parametrize("how", ["semi", "anti"])
def test_bounds_decide_as_the_pairs_do(how, op, left_first, case):
    """One comparison of a left and a right column, in either order,
    decided by each key's least and greatest right value: the device
    keeps the rows the host engine and the loop over the pairs keep, and
    lays out no pair."""
    left, right = BOUNDS_CASES[case]
    holds = OPERATORS[op]
    if left_first:
        def cond(c):
            return OPERATORS[op](c("s"), c("s2"))
    else:       # ``s2 <op'> s`` with op' the flipped operator
        def cond(c):
            return OPERATORS[joins._FLIPPED[op]](c("s2"), c("s"))
    want = _expected(left, right, how, holds)
    host = _query(srt.Session(tpu_enabled=False), left, right, how,
                  condition=cond)
    assert sorted(host.collect(), key=repr) == want
    sess = srt.Session(STRICT)
    df = _query(sess, left, right, how, condition=cond)
    assert f"* HashJoinExec [{how}, (" in df.explain()
    join = _device_join(sess, df)
    assert join.path == "bounds" and join._bounds[0] == op
    assert sorted(df.collect(), key=repr) == want
    m = sess.last_metrics
    assert m["join.conditionByBounds"] == 1
    for name in ("join.conditionJoins", "join.conditionPairs",
                 "join.conditionPairSlots"):
        assert m.get(name, 0) == 0


@pytest.mark.parametrize("types", [
    ("int8", T.INT8, T.INT8), ("int16", T.INT16, T.INT16),
    ("int32", T.INT32, T.INT32), ("int32_int64", T.INT32, T.INT64),
    ("date", T.DATE32, T.DATE32), ("timestamp", T.TIMESTAMP, T.TIMESTAMP)],
    ids=lambda t: t[0])
@pytest.mark.parametrize("how", ["semi", "anti"])
def test_bounds_over_every_integer_width(how, types):
    """The bounds of an int8, an int16, an int32 (a word each), a date,
    an int64 or a timestamp (two words), and an int32 beside an int64,
    compared with a left value of each, NULLs among them."""
    _, lt, rt = types
    rng = np.random.default_rng(42)
    n = 80
    ls = T.Schema([T.Field("k", T.INT64, True), T.Field("a", lt, True)])
    rs = T.Schema([T.Field("k2", T.INT64, True), T.Field("b", rt, True)])
    big = 100 if lt in (T.INT8, T.INT16) else 2**31 - 1

    def values(seed):
        v = np.random.default_rng(seed).integers(-big, big, n).tolist()
        return [None if i % 7 == 3 else x for i, x in enumerate(v)]

    left = {"k": rng.integers(0, 12, n).tolist(), "a": values(1)}
    right = {"k2": rng.integers(0, 12, n).tolist(), "b": values(2)}
    def q(sess, cond):
        lf = sess.create_dataframe(left, schema=ls, n_partitions=1)
        rf = sess.create_dataframe(right, schema=rs, n_partitions=1)
        return lf.join(rf, on=(["k"], ["k2"]), how=how, condition=cond)

    for cond in (F.col("a") < F.col("b"), F.col("a") != F.col("b"),
                 F.col("b") <= F.col("a")):
        host = sorted(q(srt.Session(tpu_enabled=False), cond).collect(),
                      key=repr)
        sess = srt.Session(STRICT)
        device = sorted(q(sess, cond).collect(), key=repr)
        assert device == host and 0 < len(host) < n
        assert sess.last_metrics["join.conditionByBounds"] == 1


def test_the_bounds_refuse_what_they_cannot_decide():
    """A float, a string, ``=``, ``<=>``, ``AND``, an operand of both
    sides, a comparison of two right columns: no bounds, pairs."""
    sess = srt.Session(STRICT)
    lf = sess.create_dataframe({"k": [1], "s": [1], "x": [1.5], "t": ["a"]},
                               n_partitions=1)
    rf = sess.create_dataframe({"k2": [1], "s2": [1], "y": [2.5],
                                "u": ["b"]}, n_partitions=1)
    c = F.col
    refused = [c("x") < c("y"), c("t") != c("u"), c("s") == c("s2"),
               c("s").eq_null_safe(c("s2")),
               (c("s") < c("s2")) & (c("s") > F.lit(0)),
               c("s") + c("s2") > F.lit(1), c("s2") != c("k2")]
    taken = [c("s") < c("s2"), c("s2") >= c("s"), c("s") + F.lit(1) !=
             c("s2") * c("k2"), F.lit(3) > c("s2")]
    for cond, want in [(x, None) for x in refused] + [(x, 1) for x in taken]:
        df = lf.join(rf, on=(["k"], ["k2"]), how="semi", condition=cond)
        op = _device_join(sess, df)
        assert (op._bounds is None) == (want is None), cond
        assert op.path == ("pairs" if want is None else "bounds"), cond


@pytest.mark.parametrize("how", ["semi", "anti"])
@pytest.mark.parametrize("cause", ["too_many_pairs", "injected_oom"])
def test_the_stream_side_splits_where_the_pairs_do_not_fit(
        how, cause, monkeypatch):
    """Past ``_PAIR_SLOTS_MOST`` slots (here 128), or at an injected
    split-and-retry OOM at the join's checkpoint, the left batch is
    halved by ``with_split_retry`` and each half laid out alone: every
    pair is still evaluated and the answer does not change."""
    from spark_rapids_tpu.memory import retry as R

    left, right = _many(17, 8)
    if cause == "too_many_pairs":
        monkeypatch.setattr(TpuHashJoinExec, "_PAIR_SLOTS_MOST", 128)
    else:
        real, fired = R.maybe_inject_oom, []

        def inject(site="", nbytes=0):
            if site.endswith("HashJoinExec.join") and not fired:
                fired.append(site)
                raise R.TpuSplitAndRetryOOM("injected", injected=True)
            return real(site, nbytes)

        monkeypatch.setattr(R, "maybe_inject_oom", inject)
    sess = srt.Session(STRICT)
    got = _query(sess, left, right, how).collect()
    assert sorted(got, key=repr) == _expected(left, right, how)
    m = sess.last_metrics
    assert m["retry.numSplitRetries"] >= 1
    assert m["join.conditionJoins"] >= 2
    assert m["join.conditionPairs"] == _pairs(left, right)
    if cause == "too_many_pairs":
        assert m["join.conditionPairSlots"] <= 128 * m["join.conditionJoins"]


def test_a_string_condition_reads_both_sides_rows():
    """A condition over a string column of each side: the left one is
    read at its pairs by row, the right one in key order."""
    data_l = {"k": [1, 1, 2, 3, None], "a": ["x", "yy", "z", None, "x"]}
    data_r = {"k2": [1, 1, 2, 3], "b": ["x", "x", "zz", "q"]}

    def q(sess, how):
        lf = sess.create_dataframe(data_l, n_partitions=1)
        rf = sess.create_dataframe(data_r, n_partitions=1)
        return lf.join(rf, on=(["k"], ["k2"]), how=how,
                       condition=(F.col("a") != F.col("b"))
                       & (F.col("k") < F.lit(3)))

    for how in ("semi", "anti"):
        host = sorted(q(srt.Session(tpu_enabled=False), how).collect(),
                      key=repr)
        device = sorted(q(srt.Session(STRICT), how).collect(), key=repr)
        assert device == host
    assert host == sorted([(1, "x"), (3, None), (None, "x")], key=repr)


@pytest.mark.parametrize("how", ["semi", "anti"])
def test_a_condition_over_every_width_of_column(how):
    """The right side's reads travel as 32-bit words in one gather,
    each float64 in one of its own: a condition over an int8, an int16,
    a date, a float32 and a float64 of each side, NULLs among them."""
    widths = [("b", T.INT8), ("h", T.INT16), ("d", T.DATE32),
              ("f", T.FLOAT32), ("x", T.FLOAT64)]
    rng = np.random.default_rng(3)
    n = 60

    def side(suffix, keys):
        data = {"k" + suffix: keys}
        for name, _ in widths:
            vals = rng.integers(-3, 4, n).tolist()
            data[name + suffix] = [None if i % 9 == 4 else
                                   (float(v) if name in "fx" else v)
                                   for i, v in enumerate(vals)]
        return data, T.Schema(
            [T.Field("k" + suffix, T.INT64, True)]
            + [T.Field(name + suffix, dt, True) for name, dt in widths])

    left, ls = side("", rng.integers(0, 6, n).tolist())
    right, rs = side("2", rng.integers(0, 6, n).tolist())
    col = F.col
    cond = ((col("b") + col("h") < col("b2") + col("h2"))
            | (col("d") > col("d2")) & (col("f") != col("f2"))
            | (col("x") * 2 == col("x2")))

    def q(sess):
        lf = sess.create_dataframe(left, schema=ls, n_partitions=1)
        rf = sess.create_dataframe(right, schema=rs, n_partitions=1)
        return lf.join(rf, on=(["k"], ["k2"]), how=how, condition=cond)

    host = sorted(q(srt.Session(tpu_enabled=False)).collect(), key=repr)
    device = sorted(q(srt.Session(STRICT)).collect(), key=repr)
    assert device == host and 0 < len(host) < n


# ==========================================================================
# Q21's shape through a strict session
# ==========================================================================
def _q21_tables(rng, n_orders=300):
    lines = rng.poisson(4, n_orders)
    order = np.repeat(np.arange(n_orders) * 4 + 1, lines)
    n = len(order)
    supp = rng.integers(1, 21, n)
    commit = rng.integers(0, 60, n)
    receipt = commit + rng.integers(-30, 31, n)
    return {
        "supplier": {"s_suppkey": list(range(1, 21)),
                     "s_name": [f"Supplier#{i:09d}" for i in range(1, 21)],
                     "s_nationkey": [i % 3 for i in range(1, 21)]},
        "lineitem": {"l_orderkey": order.tolist(),
                     "l_suppkey": supp.tolist(),
                     "l_commitdate": commit.tolist(),
                     "l_receiptdate": receipt.tolist()},
        "orders": {"o_orderkey": (np.arange(n_orders) * 4 + 1).tolist(),
                   "o_orderstatus": rng.choice(["F", "O"], n_orders)
                   .tolist()},
        "nation": {"n_nationkey": [0, 1, 2],
                   "n_name": ["ALGERIA", "SAUDI ARABIA", "PERU"]}}


def _q21(sess, data):
    t = {name: sess.create_dataframe(cols, n_partitions=1)
         for name, cols in data.items()}
    col, lit = F.col, F.lit
    late = t["lineitem"].filter(col("l_receiptdate") > col("l_commitdate"))
    l1 = t["supplier"].join(late.select("l_orderkey", "l_suppkey"),
                            on=(["s_suppkey"], ["l_suppkey"]))
    l2 = t["lineitem"].select(col("l_orderkey").alias("l2_orderkey"),
                              col("l_suppkey").alias("l2_suppkey"))
    l3 = late.select(col("l_orderkey").alias("l3_orderkey"),
                     col("l_suppkey").alias("l3_suppkey"))
    return (l1.join(l2, on=(["l_orderkey"], ["l2_orderkey"]), how="semi",
                    condition=col("l_suppkey") != col("l2_suppkey"))
            .join(l3, on=(["l_orderkey"], ["l3_orderkey"]), how="anti",
                  condition=col("l_suppkey") != col("l3_suppkey"))
            .join(t["orders"].filter(col("o_orderstatus") == lit("F")),
                  on=(["l_orderkey"], ["o_orderkey"]))
            .join(t["nation"].filter(col("n_name") == lit("SAUDI ARABIA")),
                  on=(["s_nationkey"], ["n_nationkey"]))
            .group_by("s_name").agg(F.count("*").alias("numwait"))
            .sort(col("numwait").desc(), col("s_name").asc()).limit(100))


def test_q21_shape_in_strict_mode_counts_its_pairs():
    """Both of Q21's conditions (``l_suppkey <> l2_suppkey``, ``<>
    l3_suppkey``) are decided by the bounds: one program a join and
    stream batch, no pair laid out."""
    data = _q21_tables(np.random.default_rng(21))
    li = data["lineitem"]
    o, s = np.array(li["l_orderkey"]), np.array(li["l_suppkey"])
    late = np.array(li["l_receiptdate"]) > np.array(li["l_commitdate"])
    same = o[:, None] == o[None, :]
    exists = (same & (s[:, None] != s[None, :]))[late].any(axis=1)
    kept = np.flatnonzero(late)[exists]
    waits = kept[~(same & late[None, :] & (s[:, None] != s[None, :]))[
        kept].any(axis=1)]

    sess = srt.Session(STRICT)
    df = _q21(sess, data)
    text = df.explain()
    for how, side in (("semi", "l2"), ("anti", "l3")):
        assert (f"* HashJoinExec [{how}, (NOT (l_suppkey == "
                f"{side}_suppkey))]") in text
    assert "!" not in "".join(ln.split()[0] for ln in text.splitlines()
                              if "LocalScanExec" not in ln)
    got = df.collect()
    host = _q21(srt.Session(tpu_enabled=False), data).collect()
    assert got == host and len(got) > 1
    m = sess.last_metrics
    assert m["join.conditionByBounds"] == 2
    assert m.get("join.conditionJoins", 0) == 0
    assert m.get("join.conditionPairs", 0) == 0
    # the answer from the lines the loop keeps
    status = dict(zip(data["orders"]["o_orderkey"],
                      data["orders"]["o_orderstatus"]))
    name = dict(zip(data["supplier"]["s_suppkey"],
                    data["supplier"]["s_name"]))
    nation = dict(zip(data["supplier"]["s_suppkey"],
                      data["supplier"]["s_nationkey"]))
    count = {}
    for i in waits:
        if status[o[i]] == "F" and nation[s[i]] == 1:
            count[name[s[i]]] = count.get(name[s[i]], 0) + 1
    assert got == sorted(count.items(), key=lambda kv: (-kv[1], kv[0]))


# ==========================================================================
# the programs the expand's sort path leaves alone keep their text
# ==========================================================================
#: sha256 of the lowered text (no debug info) of the unconditioned semi
#: join's program, the inner join's count, the conditional semi join's
#: pair program (of ``s <> s2``, which the bounds decide since: it is
#: planned here with the bounds refused), and the inner join's expand
#: where few slots stand over a wide left side (the search path), for
#: the fixed input of ``_lowered_join_programs``, under this JAX.  The count's is commit
#: 4264f4c's (before the expand could sort): its compile-cache key is
#: that commit's.  The other three read their rows by one stacked gather
#: (``gather.take_rows``) since, and are recorded from there.  The expand
#: at 2048 slots over 1024 rows sorts where it searched then: its text
#: changed, and it holds no search
PARENT_TEXT = {
    "jax": "0.9.0",
    "join_semi":
        "c08538ff76f7e639a1624e4fdadaa8ed39f94f4213669270c5388908301d0412",
    "join_count":
        "9d93e29a6eb7bdc4962d777b9cdf1deebbe38c26eab1f7da1d546c815919b028",
    "join_semiPairs":
        "6da666a0714f41fbd833913d3bef9dfeccf9aac34c76039d07c8953e1b7dcb5a",
    "join_expand_search":
        "ae53760b12cd592830859cc776231af5be2da174e8f39db864bc0a161dcec122"}


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _lowered_join_programs(monkeypatch):
    import jax.numpy as jnp

    from spark_rapids_tpu.data.column import DeviceBatch, DeviceColumn

    sess = srt.Session(STRICT)
    left = sess.create_dataframe({"k": [1, 2], "s": [3, 4]}, schema=LEFT)
    right = sess.create_dataframe({"k2": [1, 2], "s2": [3, 4]},
                                  schema=RIGHT)

    def batch(schema, n, rows):
        cols = [DeviceColumn(f.dtype,
                             (jnp.arange(n, dtype=jnp.int64) * (i + 3)) % 97,
                             jnp.arange(n) % 11 != 0)
                for i, f in enumerate(schema)]
        return DeviceBatch(schema, cols, rows)

    lb, rb = batch(LEFT, 1024, 1000), batch(RIGHT, 512, 500)
    out = {}
    for how in ("semi", "semiPairs", "inner"):
        df = left.join(right, on=(["k"], ["k2"]), how=how[:4],
                       condition=F.col("s") != F.col("s2")
                       if how == "semiPairs" else None)
        with monkeypatch.context() as patch:
            patch.setattr(joins, "_bounds_of", lambda *a: None)
            op = _device_join(sess, df)
        if how == "semi":
            out["join_semi"] = op._semi_kernel._jfn.lower(lb, rb).as_text()
            continue
        if how == "semiPairs":
            out["join_semiBounds"] = _device_join(
                sess, df)._bounds_kernel._jfn.lower(lb, rb).as_text()
        if how == "semiPairs":
            pr, emit, _, _ = op._count_kernel(lb, rb)
            out["join_semiPairs"] = op._pairs_kernel._jfn.lower(
                2048, lb, rb, pr, emit).as_text()
            continue
        out["join_count"] = op._count_kernel._jfn.lower(lb, rb).as_text()
        pr, emit, r_extra, _ = op._count_kernel(lb, rb)
        out["join_expand"] = op._expand_kernel._jfn.lower(
            2048, lb, rb, pr, emit, r_extra).as_text()
        # few slots over a wide left side: the search stays
        wide = batch(LEFT, 1 << 16, 1000)
        pr, emit, r_extra, _ = op._count_kernel(wide, rb)
        out["join_expand_search"] = op._expand_kernel._jfn.lower(
            128, wide, rb, pr, emit, r_extra).as_text()
    return out


@pytest.fixture(scope="module")
def lowered():
    with pytest.MonkeyPatch.context() as monkeypatch:
        return _lowered_join_programs(monkeypatch)


@pytest.mark.parametrize("program", ["join_semi", "join_count",
                                     "join_expand", "join_semiPairs",
                                     "join_expand_search"])
def test_unconditioned_programs_lower_to_the_parents_text(lowered, program):
    import jax

    text = lowered[program]
    name = "jit_join_expand" if program.startswith("join_expand") \
        else f"jit_{program}"
    assert name in text and "loc(" not in text
    if program == "join_expand":
        # the slots find their rows by sort: the search is gone
        assert "searchsorted" not in text and "sort" in text
        return
    assert ("searchsorted" in text) == (program == "join_expand_search")
    if jax.__version__ != PARENT_TEXT["jax"]:
        pytest.skip("the parent's text was recorded under jax "
                    + PARENT_TEXT["jax"])
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[program]


def test_the_bounds_program_lays_out_no_pair(lowered):
    """``s <> s2`` over 1024 left and 512 right rows: no array of the
    bounds' program is longer than the rows of both sides (padded to a
    whole block of the scan), where the pair program's layout spans its
    2048 slots and a marker a row."""
    import re

    from spark_rapids_tpu.ops.kernels.gather import _SCAN_BLOCK

    def longest(text):
        return max(int(d) for d in re.findall(r"tensor<(\d+)", text))

    text = lowered["join_semiBounds"]
    assert "jit_join_semiBounds" in text and "searchsorted" not in text
    assert longest(text) == -(-(1024 + 512) // _SCAN_BLOCK) * _SCAN_BLOCK
    assert longest(lowered["join_semiPairs"]) == 1024 + 2048
