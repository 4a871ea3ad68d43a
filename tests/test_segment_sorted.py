"""The sorted-segment reductions against the host oracle.

``ops/kernels/segment.reduce_sorted`` reduces rows whose segments are
contiguous by scans, a sort and gathers; ``segment_reduce_np`` /
``segment_pick_np`` scatter by segment id.  Same rows in, same
per-segment rows out: integers and counts exactly, float sums to 1e-12
(a segment that crosses a block of the scan adds up block by block).
"""
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64 on)
from spark_rapids_tpu import types as T
from spark_rapids_tpu.data.column import DeviceColumn
from spark_rapids_tpu.ops.kernels import segment as seg

B = seg._SCAN_BLOCK

OPS = ["sum", "count", "min", "max", "first", "last", "first_any",
       "last_any"]
DTYPES = [np.int32, np.int64, np.float32, np.float64]
VALIDITY = ["all", "none", "random"]


def _change(layout, rng):
    """(n, flags of the rows that start a segment) for a named layout."""
    if layout == "one_segment":
        flags = np.zeros(300, np.bool_)
    elif layout == "every_row":
        flags = np.ones(300, np.bool_)
    elif layout == "block_edge":        # a boundary exactly on a block edge
        flags = np.zeros(2 * B, np.bool_)
        flags[[7, B, B + 1]] = True
    elif layout == "below_block":
        flags = rng.random(B - 24) < 0.02
    elif layout == "one_block":
        flags = rng.random(B) < 0.02
    elif layout == "four_blocks":       # segments that span blocks
        flags = rng.random(4 * B) < 0.002
    elif layout == "ragged":            # past a block and no multiple of it
        flags = rng.random(2 * B + 37) < 0.002
    elif layout == "blocks_of_blocks":  # the blocks' carries span blocks too
        flags = rng.random(2 * B * B) < 2.0 / (B * B)
    elif layout == "padding_tail":      # a batch: few groups, then padding
        flags = rng.random(2 * B) < 0.003
        flags[B + B // 2:] = True
    else:
        raise ValueError(layout)
    flags[0] = True
    return len(flags), flags


LAYOUTS = ["one_segment", "every_row", "block_edge", "below_block",
           "one_block", "four_blocks", "ragged", "padding_tail"]


def _values(dtype, n, rng):
    if np.issubdtype(dtype, np.floating):
        return (rng.standard_normal(n) * 1e3).astype(dtype)
    return rng.integers(-1000, 1000, n).astype(dtype)


def _valid(kind, n, rng):
    if kind == "random":
        return rng.random(n) < 0.6
    return np.full(n, kind == "all")


def _shuffled(rng, seg_ids, *columns):
    """The rows as a batch holds them before its stable sort: (order,
    columns in batch order), ``columns`` being in sorted order.  Stable:
    a segment's rows keep their batch order."""
    place = rng.permutation(len(seg_ids))
    order = place[np.lexsort((place, seg_ids))]     # sorted row -> batch row
    unsorted = []
    for col in columns:
        out = np.empty_like(col)
        out[order] = col
        unsorted.append(out)
    return order.astype(np.int32), unsorted


def _column(values, valid):
    import jax.numpy as jnp

    return DeviceColumn(T.from_numpy(values.dtype), jnp.asarray(values),
                        jnp.asarray(valid))


def _check(op, dtype, validity, layout, seed, shuffle=False):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n, change = _change(layout, rng)
    values, valid = _values(dtype, n, rng), _valid(validity, n, rng)
    seg_ids = np.cumsum(change) - 1
    n_seg = int(seg_ids[-1]) + 1
    want, want_ok = seg.segment_reduce_np(values, valid, seg_ids, n, op)

    order = None
    if shuffle:
        order, (values, valid) = _shuffled(rng, seg_ids, values, valid)
        order = jnp.asarray(order)
    (got, got_ok, _), = seg.reduce_sorted(
        jnp.asarray(change), order, [(_column(values, valid), op)])
    got, got_ok = np.asarray(got)[:n_seg], np.asarray(got_ok)[:n_seg]

    np.testing.assert_array_equal(got_ok, want_ok[:n_seg])
    want = want[:n_seg][got_ok]
    if op == "sum" and np.issubdtype(dtype, np.floating):
        np.testing.assert_allclose(got[got_ok], want, rtol=1e-12)
    else:
        np.testing.assert_array_equal(got[got_ok], want)


@pytest.mark.parametrize("validity", VALIDITY)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("op", OPS)
def test_reduce_matches_oracle(op, dtype, validity):
    _check(op, dtype, validity, "padding_tail", 11)


@pytest.mark.parametrize("layout", LAYOUTS[:-1])
@pytest.mark.parametrize("op,dtype", [("sum", np.float64), ("sum", np.int64),
                                      ("min", np.float32), ("last", np.int32),
                                      ("first_any", np.int64)])
def test_reduce_over_layouts(op, dtype, layout):
    _check(op, dtype, "random", layout, 23)


@pytest.mark.parametrize("op,dtype", [("sum", np.float64), ("sum", np.int64),
                                      ("max", np.float64)])
def test_reduce_where_the_carries_span_blocks(op, dtype):
    """2 B^2 rows: the blocks' last values fill two blocks of their own,
    whose carries take a third level."""
    _check(op, dtype, "random", "blocks_of_blocks", 29)


@pytest.mark.parametrize("op", OPS)
def test_reduce_sorts_the_batch_rows_itself(op):
    """Given the sort's permutation, the operands come as the batch
    holds them and are stacked before they are sorted."""
    _check(op, np.float64, "random", "padding_tail", 31, shuffle=True)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("op", ["first", "last", "first_any", "last_any"])
def test_pick_matches_oracle(op, layout):
    """The row a pick reads is the oracle's: pick row numbers."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n, change = _change(layout, rng)
    eligible = np.ones(n, np.bool_) if op.endswith("_any") \
        else rng.random(n) < 0.3
    seg_ids = np.cumsum(change) - 1
    n_seg = int(seg_ids[-1]) + 1
    want, want_has = seg.segment_pick_np(eligible, seg_ids, n, op)

    (got, got_has, _), = seg.reduce_sorted(
        jnp.asarray(change), None,
        [(_column(np.arange(n, dtype=np.int32), eligible), op)])
    got, got_has = np.asarray(got)[:n_seg], np.asarray(got_has)[:n_seg]
    np.testing.assert_array_equal(got_has, want_has[:n_seg])
    np.testing.assert_array_equal(got[got_has], want[:n_seg][got_has])


def test_specs_of_one_kind_share_a_scan_and_each_keeps_its_answer():
    """Many specs at once (stacked by op and dtype, one count a
    validity) answer as each would alone."""
    import jax.numpy as jnp

    rng = np.random.default_rng(41)
    n, change = _change("four_blocks", rng)
    seg_ids = np.cumsum(change) - 1
    n_seg = int(seg_ids[-1]) + 1
    valid = _valid("random", n, rng)
    cols = [_values(np.float64, n, rng) for _ in range(3)] \
        + [_values(np.int32, n, rng)]
    specs = [(cols[0], "sum"), (cols[1], "sum"), (cols[0], "count"),
             (cols[2], "min"), (cols[3], "min"), (cols[3], "first"),
             (cols[1], "last_any"), (cols[3], "sum")]
    shared = jnp.asarray(valid)
    got = seg.reduce_sorted(jnp.asarray(change), None, [
        (DeviceColumn(T.from_numpy(v.dtype), jnp.asarray(v), shared), op)
        for v, op in specs])
    for (values, op), (data, ok, _) in zip(specs, got):
        want, want_ok = seg.segment_reduce_np(values, valid, seg_ids, n, op)
        ok = np.asarray(ok)[:n_seg]
        np.testing.assert_array_equal(ok, want_ok[:n_seg])
        check = np.testing.assert_allclose if values.dtype == np.float64 \
            and op == "sum" else np.testing.assert_array_equal
        check(np.asarray(data)[:n_seg][ok], want[:n_seg][ok])


@pytest.mark.parametrize("segments", [1, 3])
def test_leading_segments_alone_read_as_all_of_them_would(segments):
    """``segments``: the caller reads only that many leading segments (a
    keyless aggregate: its one), so only their ends are gathered; those
    rows answer to the bit as without the hint, the rest are zeros."""
    import jax.numpy as jnp

    rng = np.random.default_rng(43)
    n, change = _change("four_blocks", rng)
    valid = jnp.asarray(_valid("random", n, rng))
    strings = np.frombuffer(rng.bytes(n * 3), np.uint8).reshape(n, 3) % 3
    specs = [(DeviceColumn(T.from_numpy(v.dtype), jnp.asarray(v), valid),
              op) for v, op in [
        (_values(np.float64, n, rng), "sum"),
        (_values(np.int32, n, rng), "count"),
        (_values(np.int32, n, rng), "min"),
        (_values(np.int64, n, rng), "first"),
        (_values(np.float64, n, rng), "last_any")]]
    specs.append((DeviceColumn(
        T.STRING, jnp.asarray(strings + ord("a")), valid,
        jnp.full((n,), 3, jnp.int32)), "max"))
    whole = seg.reduce_sorted(jnp.asarray(change), None, specs)
    some = seg.reduce_sorted(jnp.asarray(change), None, specs,
                             segments=segments)
    for want, got in zip(whole, some):
        for w, g in zip(want, got):
            if w is None:
                assert g is None
                continue
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g)[:segments],
                                          np.asarray(w)[:segments])
            assert not np.asarray(g)[segments:].any()


def test_a_block_sums_in_row_order_and_blocks_to_rounding():
    """Inside a block the scan adds a row at a time, as the oracle does
    (a sum that cancels catastrophically still comes out equal); a
    segment over several blocks adds the blocks' sums, so it equals the
    row-by-row sum to rounding and no closer."""
    import jax.numpy as jnp

    big = np.finfo(np.float64).max
    values = np.array([1e4, -big, -1e6, big, 3.5, 2.25] * 20)
    change = np.zeros(len(values), np.bool_)
    change[[0, 6, 60]] = True

    def scan(values, change):
        return np.asarray(seg.segmented_scan(
            jnp.asarray(values)[None, :], jnp.asarray(change), jnp.add))[0]

    def by_rows(values, change):
        want = values.copy()
        for i in range(1, len(values)):
            if not change[i]:
                want[i] = want[i - 1] + values[i]
        return want

    np.testing.assert_array_equal(scan(values, change),
                                  by_rows(values, change))
    values = np.random.default_rng(3).standard_normal(4 * B) * 1e3 + 5e2
    change = np.zeros(4 * B, np.bool_)
    got, want = scan(values, change), by_rows(values, change)
    np.testing.assert_array_equal(got[:B], want[:B])
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert (got != want).any()


# ==========================================================================
# a traced ``segments``: the read's width is picked on the device
# ==========================================================================
N = seg._TIER_FLOOR          # the smallest batch whose reads are tiered
TIERS = seg.read_tiers(N)


def _tiered_case(seed, ordered=True):
    """(change, order, specs, column data by spec) over ``N`` rows: 300
    real segments of a few rows, then padding rows, each its own
    segment, as an aggregate's batch has them.  No data is zero, so a
    row that was read tells itself from one that was not."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    change = np.ones(N, np.bool_)
    change[:1500] = rng.random(1500) < 0.2
    change[0] = True
    seg_ids = np.cumsum(change) - 1
    valid = rng.random(N) < 0.6                 # nulls
    other = rng.random(N) < 0.9

    def values(dtype):
        v = _values(dtype, N, rng)
        return np.where(v == 0, dtype(7), v)

    strings = np.frombuffer(rng.bytes(N * 3), np.uint8).reshape(N, 3) % 3 \
        + ord("a")
    lengths = np.full(N, 3, np.int32)
    cols = [(values(np.float64), valid, "sum"),
            (values(np.int64), valid, "sum"),
            (values(np.int32), valid, "min"),
            (values(np.float32), other, "max"),
            (values(np.int32), valid, "count"),
            (values(np.int64), other, "first"),
            (values(np.int32), valid, "last"),
            (values(np.float64), other, "first_any"),
            (values(np.int64), valid, "last_any"),
            (strings, valid, "min"),
            (strings, other, "first_any")]
    order = None
    if ordered:
        order, unsorted = _shuffled(
            rng, seg_ids, lengths, *[c for v, ok, _ in cols for c in (v, ok)])
        lengths, unsorted = unsorted[0], unsorted[1:]
        cols = [(unsorted[2 * i], unsorted[2 * i + 1], op)
                for i, (_, _, op) in enumerate(cols)]
        order = jnp.asarray(order)
    shared = {}

    def column(v, ok):
        ok = shared.setdefault(ok.tobytes(), jnp.asarray(ok))
        if v.ndim == 2:
            return DeviceColumn(T.STRING, jnp.asarray(v), ok,
                                jnp.asarray(lengths))
        return DeviceColumn(T.from_numpy(v.dtype), jnp.asarray(v), ok)

    specs = [(column(v, ok), op) for v, ok, op in cols]
    return jnp.asarray(change), order, specs, seg_ids, cols


def _tiered(change, order, specs):
    """jit of ``reduce_sorted`` with its segment count an argument."""
    import jax

    ops = [op for _, op in specs]

    def run(change, order, columns, segments):
        return seg.reduce_sorted(change, order, list(zip(columns, ops)),
                                 segments=segments)

    return jax.jit(run), (change, order, [c for c, _ in specs])


EDGES = sorted({0, 1, 300} | {m + d for m in TIERS[:-1] for d in (0, 1)}
               | {N})


@pytest.fixture(scope="module", params=[True, False],
                ids=["ordered", "order_none"])
def tiered(request):
    change, order, specs, seg_ids, cols = _tiered_case(
        47, ordered=request.param)
    run, args = _tiered(change, order, specs)
    plain = seg.reduce_sorted(change, order, specs)
    return run, args, plain, seg_ids, cols


@pytest.mark.parametrize("segments", EDGES)
def test_traced_segment_count_reads_its_tier_and_zeros_past_it(tiered,
                                                               segments):
    """Every tier, and both edges of each (``segments == m``, ``m + 1``):
    the rows below the tier's width equal the plain read's to the bit
    (so every row below ``segments`` does), the rows past it are zeros:
    what tells the tier that ran."""
    import jax.numpy as jnp

    run, args, plain, _, _ = tiered
    m = next(m for m in TIERS if m >= segments)
    for want, got in zip(plain, run(*args, jnp.int32(segments))):
        for w, g in zip(want, got):
            if w is None:
                assert g is None
                continue
            w, g = np.asarray(w), np.asarray(g)
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(g[:m], w[:m])
            assert not g[m:].any()
            if m < N:       # the plain read holds the padding rows' own
                assert w[m:].any() or w.dtype == np.bool_


@pytest.mark.parametrize("spec", range(11))
def test_traced_segment_count_answers_as_the_oracle(tiered, spec):
    """The 300 real segments of each spec (sum, min, max, count, first,
    last, the ``_any`` picks, a string minimum and a string pick, with
    nulls) against ``segment_reduce_np``, read through the first tier."""
    import jax.numpy as jnp

    run, args, _, seg_ids, cols = tiered
    values, valid, op = cols[spec]
    if args[1] is not None:     # the oracle takes the rows in sorted order
        order = np.asarray(args[1])
        values, valid = values[order], valid[order]
    n_seg = int((np.asarray(args[0])[:1500]).sum())
    data, ok, lengths = run(*args, jnp.int32(n_seg))[spec]
    data, ok = np.asarray(data)[:n_seg], np.asarray(ok)[:n_seg]
    if values.ndim == 2:
        assert (np.asarray(lengths)[:n_seg][ok] == 3).all()
        values = np.array([bytes(r).decode() for r in values], object)
        data = np.array([bytes(r).decode() for r in data], object)
    want, want_ok = seg.segment_reduce_np(values, valid, seg_ids, N, op)
    np.testing.assert_array_equal(ok, want_ok[:n_seg])
    if op == "sum" and values.dtype == np.float64:
        np.testing.assert_allclose(data[ok], want[:n_seg][ok], rtol=1e-12)
    else:
        np.testing.assert_array_equal(data[ok], want[:n_seg][ok])


def test_tiers_are_a_function_of_the_row_count_alone():
    assert seg.read_tiers(N - 1) == (N - 1,)      # below the floor: plain
    assert seg.read_tiers(128) == (128,)
    assert TIERS == (N // 64, N // 16, N // 4, N)
    assert seg.read_tiers(1 << 22) == (1 << 16, 1 << 18, 1 << 20, 1 << 22)


def _lowered(n, segments, debug_info=False):
    import jax
    import jax.numpy as jnp

    valid = jnp.ones((n,), jnp.bool_)
    cols = [DeviceColumn(T.FLOAT64, jnp.ones((n,)), valid),
            DeviceColumn(T.INT32, jnp.ones((n,), jnp.int32), valid)]

    def run(change, columns, count):
        return seg.reduce_sorted(
            change, None, list(zip(columns, ["sum", "first_any"])),
            segments=count if segments == "traced" else segments)

    return jax.jit(run).lower(valid, cols, jnp.int32(3)).as_text(
        debug_info=debug_info)


def test_keyed_call_lowers_to_one_conditional_a_branch_a_tier():
    import re

    text = _lowered(N, "traced", debug_info=True)
    assert text.count("stablehlo.case") == 1
    named = set(re.findall(r"segments/cond/branch_(\d)_fun/(readTier\.\d+)/",
                           text))
    assert named == {(str(i), f"readTier.{m}") for i, m in enumerate(TIERS)}
    # every gather of the program stands inside a branch: nothing is
    # read a row a slot on the way to the switch
    gathers = re.findall(r'loc\("([^"]+/gather)"', text)
    assert gathers and all("/readTier." in g for g in gathers)
    # a batch below the floor keeps the plain read, whatever the count
    small = _lowered(N // 2, "traced")
    assert "stablehlo.case" not in small
    assert small == _lowered(N // 2, None)


#: sha256 of the lowered text (no debug info) of ``_lowered(N, 1)`` and
#: ``_lowered(N, None)`` on the commit before the tiers (ed88954, PR 36),
#: under this JAX: the static paths' programs, and so their compile-cache
#: keys, are the parent's
PARENT_TEXT = {
    "jax": "0.9.0",
    1: "5cd5eadc1979e0d21df2f1d788574db9e44b8c6f26b482ed8a422e78f58172de",
    None: "c2cc9c76698a8355f1b441f2cca8267a1ac252b0ab1248d247b7951686d34ce9"}


@pytest.mark.parametrize("segments", [1, None], ids=["keyless", "plain"])
def test_static_segment_count_lowers_to_the_parents_text(segments):
    import hashlib

    import jax

    text = _lowered(N, segments)
    assert "stablehlo.case" not in text
    if jax.__version__ != PARENT_TEXT["jax"]:
        pytest.skip("the parent's text was recorded under jax "
                    + PARENT_TEXT["jax"])
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_TEXT[segments]


@pytest.mark.parametrize("groups", [5, N // 4 + 3, N],
                         ids=["few", "a_quarter", "all_distinct"])
def test_group_by_answers_the_same_whatever_tier_its_groups_take(
        groups, monkeypatch):
    """A keyed aggregate hands the kernel its group count
    (``exec/aggregate.py:_reduce``): a filter, a group-by on two keys
    (one a string, with nulls) and every kind of buffer over a
    ``N``-row bucket, against the host engine and against the plan with
    fusion off (its filter a program of its own)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu import f

    read_tiers = seg.read_tiers
    rng = np.random.default_rng(53)
    k = rng.permutation(N) % groups
    data = {"k": k.tolist(),
            "s": [None if i % 7 == 0 else f"g{i % 3}" for i in k.tolist()],
            "v": [None if x < 0.1 else float(x) for x in rng.random(N)],
            "w": rng.integers(-50, 50, N).tolist()}

    widths = []
    monkeypatch.setattr(seg, "read_tiers", lambda n: (
        widths.append(n), read_tiers(n))[1])

    def q(sess):
        df = sess.create_dataframe(data, n_partitions=1)
        return (df.filter(df["w"] > -45).group_by("k", "s").agg(
            f.sum("v").alias("sv"), f.min("w").alias("lo"),
            f.max("w").alias("hi"), f.count("v").alias("c"),
            f.avg("w").alias("a"), f.min("s").alias("s_lo")))

    def rows(sess):
        return sorted(q(sess).collect(), key=lambda r: (r[0], r[1] or ""))

    got = rows(srt.Session({"spark.rapids.tpu.sql.test.enabled": True}))
    assert N in widths      # the update phase met the whole bucket
    unfused = rows(srt.Session({
        "spark.rapids.tpu.sql.fusion.enabled": False}))
    want = rows(srt.Session(tpu_enabled=False))
    assert len(got) == len(want) >= groups * 0.9
    for g, u, w in zip(got, unfused, want):
        assert g[:2] == u[:2] == w[:2]
        assert g[3:6] == u[3:6] == w[3:6] and g[7] == u[7] == w[7]
        for i in (2, 6):        # float sums: to rounding across engines
            assert g[i] == u[i]
            assert (g[i] is None) == (w[i] is None)
            assert g[i] is None or g[i] == pytest.approx(w[i], rel=1e-12)
