"""Device gather/compaction kernels.

Row selection (filter, sort, join output) on TPU is expressed as
permutation + gather over static shapes: a boolean keep-mask becomes a
permutation that compacts kept rows to the front, with the logical row
count carried as a traced scalar — no dynamic shapes, no recompiles.
(Reference analogue: cudf Table.filter / gather; SURVEY §7 Hard parts.)
"""
from __future__ import annotations

from typing import Optional

from ...data.column import DeviceBatch, DeviceColumn
from ...utils.tracing import device_phase


def gather_column(col: DeviceColumn, order, valid_mask=None) -> DeviceColumn:
    """Permute one column by ``order`` (int32[n]); optionally AND the
    permuted validity with ``valid_mask`` (already in output order)."""
    with device_phase("reorder"):
        data = col.data[order]
        validity = col.validity[order]
        if valid_mask is not None:
            validity = validity & valid_mask
        lengths = col.lengths[order] if col.lengths is not None else None
    return DeviceColumn(col.dtype, data, validity, lengths)


def gather_batch(batch: DeviceBatch, order, num_rows,
                 valid_mask=None) -> DeviceBatch:
    cols = [gather_column(c, order, valid_mask) for c in batch.columns]
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
