"""The one read of rows by index (``ops/kernels/gather.py:take_rows``).

Every part of a list of columns whose bits fit 32-bit words travels in
ONE stacked gather; a float64 or a string's bytes in one of its own.  The
read must give every part's bits as numpy's indexing does, and every
caller the arrays the per-part loops it replaced gave: those loops are
kept here, as the reference."""
import re

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.data.column import (DeviceBatch, DeviceColumn,
                                          HostBatch, HostColumn,
                                          host_to_device)
from spark_rapids_tpu.ops.kernels import gather as G
from spark_rapids_tpu.utils import tracing

ROWS = 200      # padded to 256: the last rows are padding

DTYPES = {"bool": T.BOOL, "int8": T.INT8, "int16": T.INT16,
          "int32": T.INT32, "int64": T.INT64, "float32": T.FLOAT32,
          "float64": T.FLOAT64, "date": T.DATE32, "timestamp": T.TIMESTAMP,
          "string": T.STRING}


def _values(dtype, rng, n):
    if dtype.id is T.TypeId.STRING:
        return np.array(["".join(rng.choice(list("abcxyz"), rng.randint(13)))
                         for _ in range(n)], dtype=object)
    if dtype.id is T.TypeId.BOOL:
        return rng.rand(n) < 0.5
    np_dt = dtype.np_dtype
    if np.issubdtype(np_dt, np.floating):
        x = (rng.randn(n) * 1e6).astype(np_dt)
        x[:4] = [np.inf, -np.inf, -0.0, np.nan]     # every bit pattern kind
        return x
    info = np.iinfo(np_dt)      # the full range: sign and high words
    return rng.randint(info.min, info.max, n, dtype=np_dt)


def _device_column(dtype, seed=0, n=ROWS) -> DeviceColumn:
    rng = np.random.RandomState(seed)
    col = HostColumn.from_numpy(_values(dtype, rng, n), dtype,
                                validity=rng.rand(n) > 0.2)
    schema = T.Schema([T.Field("c", dtype)])
    # one byte-matrix width for every seed: the mesh stacks its parts
    return host_to_device(HostBatch(schema, [col]),
                          string_widths={0: 16}).columns[0]


def _bits(x):
    """An array as bits, so NaN and -0.0 compare as themselves."""
    x = np.asarray(x)
    if np.issubdtype(x.dtype, np.floating):
        return x.view(np.dtype(f"u{x.dtype.itemsize}"))
    return x


def _assert_parts_equal(got: DeviceColumn, want_parts):
    data, validity, lengths = want_parts
    for g, w in ((got.data, data), (got.validity, validity),
                 (got.lengths, lengths)):
        if w is None:
            assert g is None
            continue
        g = np.asarray(g)
        assert g.dtype == np.asarray(w).dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _numpy_read(col: DeviceColumn, idx, valid_mask=None):
    safe = np.clip(idx, 0, col.padded_rows - 1)
    validity = np.asarray(col.validity)[safe]
    if valid_mask is not None:
        validity = validity & valid_mask
    return (np.asarray(col.data)[safe], validity,
            None if col.lengths is None else np.asarray(col.lengths)[safe])


def _index(kind, n, rng):
    if kind == "permutation":
        return rng.permutation(n).astype(np.int32)
    if kind == "tiles":         # the mesh's [P, C] tiles
        return rng.randint(0, n, (4, n // 2)).astype(np.int32)
    idx = rng.randint(0, n, n).astype(np.int32)     # a null-extended side
    idx[rng.rand(n) < 0.3] = -1
    return idx


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("kind", ["permutation", "tiles", "null_extended"])
@pytest.mark.parametrize("name", sorted(DTYPES))
def test_take_rows_equals_numpy_indexing_of_each_part(name, kind, masked):
    col = _device_column(DTYPES[name])
    rng = np.random.RandomState(1)
    idx = _index(kind, col.padded_rows, rng)
    mask = None
    if masked:
        mask = idx >= 0 if kind == "null_extended" else rng.rand(*idx.shape) < .6
    (got,) = G.take_rows([col], idx, mask)
    _assert_parts_equal(got, _numpy_read(col, idx, mask))


def test_one_call_over_every_dtype_reads_each_as_alone():
    cols = [_device_column(d, seed=i) for i, d in enumerate(DTYPES.values())]
    idx = _index("null_extended", ROWS + 56, np.random.RandomState(2))
    for got, col in zip(G.take_rows(cols, idx, idx >= 0), cols):
        assert got.dtype == col.dtype
        _assert_parts_equal(got, _numpy_read(col, idx, idx >= 0))


def test_an_empty_list_reads_nothing():
    import jax.numpy as jnp

    assert G.take_rows([], jnp.arange(8, dtype=jnp.int32)) == []
    assert G.take_rows([], jnp.arange(8, dtype=jnp.int32),
                       jnp.ones((8,), jnp.bool_)) == []


# --------------------------------------------------------------------------
# the callers against the per-part loops they replaced
# --------------------------------------------------------------------------
def _old_gather_column(col, order, valid_mask=None):
    validity = col.validity[order]
    if valid_mask is not None:
        validity = validity & valid_mask
    return DeviceColumn(col.dtype, col.data[order], validity,
                        col.lengths[order] if col.lengths is not None
                        else None)


def _old_gather_side(columns, idx, slot_valid):
    import jax.numpy as jnp

    out = []
    for c in columns:
        safe = jnp.clip(idx, 0, c.data.shape[0] - 1)
        out.append(DeviceColumn(
            c.dtype, c.data[safe], c.validity[safe] & (idx >= 0) & slot_valid,
            c.lengths[safe] if c.lengths is not None else None))
    return out


def _old_gather_tiles(batch, rows, valid):
    return [DeviceColumn(c.dtype, c.data[rows], c.validity[rows] & valid,
                         c.lengths[rows] if c.lengths is not None else None)
            for c in batch.columns]


def _old_compact(cols, present, schema):
    import jax.numpy as jnp

    order = G.partition_order(present)
    num_rows = present.sum().astype(jnp.int32)
    return DeviceBatch(schema, [
        DeviceColumn(c.dtype, c.data[order],
                     c.validity[order] & present[order],
                     c.lengths[order] if c.lengths is not None else None)
        for c in cols], num_rows)


def _mixed_batch(num_rows=ROWS, seed=0):
    cols = [_device_column(d, seed=seed + i)
            for i, d in enumerate(DTYPES.values())]
    schema = T.Schema([T.Field(n, d) for n, d in DTYPES.items()])
    return DeviceBatch(schema, cols, num_rows)


def _assert_batches_equal(got, want):
    assert int(got.num_rows) == int(want.num_rows)
    for g, w in zip(got.columns, want.columns, strict=True):
        _assert_parts_equal(g, (np.asarray(w.data), np.asarray(w.validity),
                                None if w.lengths is None
                                else np.asarray(w.lengths)))


def test_compact_reads_as_the_per_column_loop_did():
    import jax.numpy as jnp

    batch = _mixed_batch(num_rows=180)
    keep = jnp.asarray(np.random.RandomState(3).rand(batch.padded_rows) < .5)
    got = G.compact(batch, keep)
    keep = keep & batch.row_mask()
    order = G.partition_order(keep)
    count = keep.sum().astype(jnp.int32)
    mask = jnp.arange(batch.padded_rows) < count
    want = DeviceBatch(batch.schema,
                       [_old_gather_column(c, order, mask)
                        for c in batch.columns], count)
    _assert_batches_equal(got, want)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_payload_reads_as_gather_side_did(how):
    """The expand's payload, both sides, at the pairs of a real probe:
    ``idx >= 0`` stands for the old ``(idx >= 0) & slot_valid``."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.kernels import join as J

    rng = np.random.RandomState(4)
    left, right = _mixed_batch(150), _mixed_batch(120)

    def keys(n):
        return DeviceColumn(T.INT64, jnp.asarray(rng.randint(0, 40, 256)),
                            jnp.arange(256) < n)

    pr = J.probe([keys(150)], [keys(120)], left.row_mask(),
                 right.row_mask())
    emit, r_extra, total = J.emit_counts(pr, how, left.row_mask(),
                                         right.row_mask())
    c_out = 1 << int(total).bit_length()
    lidx, ridx, slot_valid = J.expand_pairs(pr, emit, r_extra, c_out)
    for cols, idx in ((left.columns, lidx), (right.columns, ridx)):
        for got, want in zip(G.take_rows(cols, idx, idx >= 0),
                             _old_gather_side(cols, idx, slot_valid)):
            _assert_parts_equal(got, (np.asarray(want.data),
                                      np.asarray(want.validity),
                                      None if want.lengths is None
                                      else np.asarray(want.lengths)))


def test_mesh_exchange_reads_as_its_tile_and_compact_loops_did():
    """``collective_exchange`` and ``gather_replicate`` on four virtual
    devices against the same exchange built from the loops they had."""
    import jax

    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.parallel.mesh import DATA_AXIS, make_mesh

    n_dev = 4
    mesh = make_mesh(n_dev)
    parts = [_mixed_batch(100 + 30 * p, seed=10 * p) for p in range(n_dev)]

    def old_exchange(local):
        pids = X.device_partition_ids(local, [4], n_dev)
        cap = local.padded_rows
        rows, valid = X.bucket_rows(pids, n_dev, cap)
        recv = []
        for c in _old_gather_tiles(local, rows, valid):
            def swap(a):
                a = jax.lax.all_to_all(a, DATA_AXIS, 0, 0, tiled=True)
                return a.reshape((n_dev * cap,) + a.shape[2:])

            recv.append(DeviceColumn(c.dtype, swap(c.data), swap(c.validity),
                                     None if c.lengths is None
                                     else swap(c.lengths)))
        present = jax.lax.all_to_all(valid, DATA_AXIS, 0, 0, tiled=True)
        return _old_compact(recv, present.reshape(n_dev * cap), local.schema)

    def new_exchange(local):
        pids = X.device_partition_ids(local, [4], n_dev)
        return X.collective_exchange(local, pids, n_dev, DATA_AXIS)

    def old_replicate(local):
        present = jax.lax.all_gather(local.row_mask(), DATA_AXIS, tiled=True)
        cols = [DeviceColumn(c.dtype, *(
            None if a is None else
            jax.lax.all_gather(a, DATA_AXIS, tiled=True)
            for a in (c.data, c.validity, c.lengths))) for c in local.columns]
        return _old_compact(cols, present, local.schema)

    def new_replicate(local):
        return X.gather_replicate(local, DATA_AXIS)

    stacked = X.stack_to_mesh(mesh, X.stack_partitions(parts))
    for new, old in ((new_exchange, old_exchange),
                     (new_replicate, old_replicate)):
        got = X.unstack_partitions(jax.jit(X.exchange_step(mesh, new))(stacked))
        want = X.unstack_partitions(
            jax.jit(X.exchange_step(mesh, old))(stacked))
        for g, w in zip(got, want, strict=True):
            _assert_batches_equal(g, w)


# --------------------------------------------------------------------------
# one gather in the program, and the scopes that say so
# --------------------------------------------------------------------------
def test_compact_of_two_int64_columns_is_one_stacked_gather():
    """Two INT64 columns are four data words and two validity words: ONE
    gather of six words, in ``reorder/readWords.6``, and no part alone."""
    import jax
    import jax.numpy as jnp

    n = 1024
    schema = T.Schema([T.Field("a", T.INT64), T.Field("b", T.INT64)])
    batch = DeviceBatch(schema, [
        DeviceColumn(T.INT64, jnp.arange(n, dtype=jnp.int64),
                     jnp.ones((n,), jnp.bool_)) for _ in range(2)], n - 5)
    text = jax.jit(G.compact).lower(
        batch, jnp.arange(n) % 3 == 0).as_text(debug_info=True)
    assert len(re.findall(r"\"stablehlo\.gather\"", text)) == 1
    paths = re.findall(r'loc\("([^"]+)"', text)
    gathers = [p for p in paths if p.endswith("/gather")]
    assert gathers and all(
        re.search(r"(^|/)reorder/(.*/)?readWords\.6/gather$", p)
        for p in gathers), gathers
    assert not any(tracing.READ_OWN in p for p in paths)


def test_a_float64_and_a_string_read_alone_beside_the_stack():
    import jax
    import jax.numpy as jnp

    cols = [_device_column(T.FLOAT64), _device_column(T.STRING),
            _device_column(T.INT32)]
    text = jax.jit(G.take_rows).lower(
        cols, jnp.arange(256, dtype=jnp.int32)).as_text(debug_info=True)
    # the f64's data, the string's bytes; one stack of the f64's validity,
    # the string's validity and lengths, the int32's data and validity
    assert len(re.findall(r"\"stablehlo\.gather\"", text)) == 3
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    assert {p.rsplit("/", 2)[-2] for p in paths if p.endswith("/gather")} \
        == {tracing.READ_OWN, f"{tracing.READ_WORDS}5"}
