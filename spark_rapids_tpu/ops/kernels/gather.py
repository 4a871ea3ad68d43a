"""Device gather/compaction kernels.

Row selection (filter, sort, join output) on TPU is expressed as
permutation + gather over static shapes: a boolean keep-mask becomes a
permutation that compacts kept rows to the front, with the logical row
count carried as a traced scalar — no dynamic shapes, no recompiles.
(Reference analogue: cudf Table.filter / gather; SURVEY §7 Hard parts.)
"""
from __future__ import annotations

from typing import List

from ...data.column import DeviceBatch, DeviceColumn
from ...utils.tracing import READ_OWN, READ_WORDS, device_phase


def _words(x):
    """``x`` ([n]) as rows of 32-bit words ([k, n] uint32) and the way
    back (words read at an index of any shape -> ``x``'s dtype); None
    where its bits cannot travel so (a float64, which the chip holds as
    two f32 and will not bitcast; a 2-D string matrix)."""
    import jax.numpy as jnp
    from jax import lax

    dt = x.dtype
    if x.ndim != 1 or jnp.issubdtype(dt, jnp.floating) and dt.itemsize > 4:
        return None
    if dt == jnp.bool_:
        return x.astype(jnp.uint32)[None], lambda w: w[0] != 0
    if dt.itemsize == 8:
        return (lax.bitcast_convert_type(x, jnp.uint32).T,
                lambda w: lax.bitcast_convert_type(jnp.moveaxis(w, 0, -1),
                                                   dt))
    if dt.itemsize < 4:
        return (lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)[
            None], lambda w: lax.bitcast_convert_type(w[0], jnp.int32)
            .astype(dt))
    return (lax.bitcast_convert_type(x, jnp.uint32)[None],
            lambda w: lax.bitcast_convert_type(w[0], dt))


def take_rows(columns: List[DeviceColumn], idx,
              valid_mask=None) -> List[DeviceColumn]:
    """The rows ``idx`` (int32, 1-D, or 2-D for the mesh's [P, C] tiles;
    clipped, so -1 reads row 0) of each column, every validity ANDed
    with ``valid_mask`` (shaped as ``idx``) where given.  The one way
    rows are read by index; it runs under the caller's scope (``reorder``
    for a compaction, a sort or a payload; ``join.condition`` for a
    condition's pair-side reads).

    Every array whose bits fit 32-bit words travels in ONE stacked
    gather, scope ``readWords.<words>``: a TPU gather is priced by its
    indices, not by the width of its rows (PERF.md, section 7).  A float64
    or a string's bytes each take a gather of their own, scope
    ``readOwn``.  The scopes are no phases: ``telemetry/device_trace.py
    --by tier`` counts by them."""
    import jax
    import jax.numpy as jnp

    parts = [a for c in columns for a in (c.data, c.validity, c.lengths)]
    rows, backs, out, at = [], {}, {}, 0
    for i, a in enumerate(parts):
        if a is None:
            continue
        w = _words(a)
        if w is None:
            with jax.named_scope(READ_OWN):
                out[i] = a[jnp.clip(idx, 0, a.shape[0] - 1)]
        else:
            backs[i] = (at, w[0].shape[0], w[1])
            rows.append(w[0])
            at += w[0].shape[0]
    if rows:
        stack = jnp.concatenate(rows)
        with jax.named_scope(f"{READ_WORDS}{at}"):
            got = stack[:, jnp.clip(idx, 0, stack.shape[1] - 1)]
        for i, (lo, k, back) in backs.items():
            out[i] = back(got[lo:lo + k])
    if valid_mask is not None:
        for i in range(1, len(parts), 3):
            out[i] = out[i] & valid_mask
    return [DeviceColumn(c.dtype, out[3 * j], out[3 * j + 1],
                         out.get(3 * j + 2))
            for j, c in enumerate(columns)]


def gather_batch(batch: DeviceBatch, order, num_rows,
                 valid_mask=None) -> DeviceBatch:
    """``batch``'s rows ``order`` as a batch of ``num_rows`` rows, in
    scope ``reorder``."""
    with device_phase("reorder"):
        cols = take_rows(batch.columns, order, valid_mask)
    return DeviceBatch(batch.schema, cols, num_rows)


#: rows per block of the two-level prefix sum
_SCAN_BLOCK = 1024


def prefix_sum(x):
    """Inclusive prefix sum along the last axis of an array.

    Two levels — a scan inside blocks of ``_SCAN_BLOCK`` rows, then the
    blocks' totals scanned the same way and added back — because the
    TPU compiler takes 40 s over one flat ``cumsum`` of 2^20 int32 (73 s
    for int64) and 4 s over this, and every compaction, segment-id and
    join-expansion program holds one."""
    import jax.numpy as jnp

    n = x.shape[-1]
    if n <= _SCAN_BLOCK or n % _SCAN_BLOCK:
        return jnp.cumsum(x, axis=-1)
    inner = jnp.cumsum(
        x.reshape(x.shape[:-1] + (n // _SCAN_BLOCK, _SCAN_BLOCK)), axis=-1)
    totals = inner[..., -1]
    return (inner + (prefix_sum(totals) - totals)[..., None]).reshape(x.shape)


@device_phase("gather.partitionOrder")
def partition_order(first):
    """int32 permutation moving the rows where ``first`` (bool[n]) is
    True to the front, both groups in their original order — what a
    stable argsort of ``~first`` gives, from one prefix sum and one
    scatter instead of a sort (a sort of 2^20 bool keys costs the TPU
    compiler 53 s)."""
    import jax.numpy as jnp

    n = first.shape[0]
    ahead = prefix_sum(first.astype(jnp.int32))    # True rows up to here
    behind = prefix_sum((~first).astype(jnp.int32))
    # the destinations are built without the row index: an iota feeding
    # both the indices and the updates of one scatter aborts the TPU
    # compiler's fusion pass (scatter_emitter.cc "operand_indices.size()
    # == 1") in the mesh runner's join stages
    dest = jnp.where(first, ahead - 1, ahead[-1] + behind - 1)
    return jnp.zeros((n,), dtype=jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)


def compact(batch: DeviceBatch, keep) -> DeviceBatch:
    """Compact rows where ``keep`` (bool[padded]) to the front; the new
    logical row count is sum(keep).  Stable."""
    import jax.numpy as jnp

    keep = keep & batch.row_mask()
    order = partition_order(keep)
    count = keep.sum().astype(jnp.int32)
    kept_mask = jnp.arange(batch.padded_rows, dtype=jnp.int32) < count
    return gather_batch(batch, order, count, kept_mask)
