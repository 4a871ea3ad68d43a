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
