"""Device batch coalescing.

Reference analogue: GpuCoalesceBatches.scala — concatenates small batches
toward a CoalesceGoal (TargetSize bytes, or RequireSingleBatch for
operators like sort/build-side joins).  Device concat re-buckets the rows
(host-visible row counts force a sync here, same place the reference
synchronizes at batch boundaries)."""
from __future__ import annotations

from typing import List

import numpy as np

from ..config import (BATCH_SIZE_BYTES, BUCKET_MIN_ROWS,
                      SHUFFLE_TARGET_BATCH_ROWS)
from ..data.column import DeviceBatch, DeviceColumn, bucket_rows
from ..utils import metrics as M
from ..utils.tracing import trace_range
from .base import (
    CoalesceGoal,
    DevicePartitionedData,
    RequireSingleBatch,
    TargetRows,
    TargetSize,
    TpuExec,
)


def concat_device_batches(batches: List[DeviceBatch],
                          min_bucket: int = 128) -> DeviceBatch:
    """Concatenate device batches row-wise into one bucketed batch
    (reference: ConcatAndConsumeAll / Table.concatenate)."""
    import jax
    import jax.numpy as jnp

    assert batches
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    # one batched readback — per-batch int(num_rows) is a device sync
    # each
    counts = [int(n) for n in
              jax.device_get([b.num_rows for b in batches])]
    total = sum(counts)
    padded = bucket_rows(total, min_bucket)
    cols: List[DeviceColumn] = []
    for ci in range(len(schema)):
        parts = [b.columns[ci] for b in batches]
        dtype = parts[0].dtype
        if dtype.is_string:
            w = max(p.data.shape[1] for p in parts)
            datas = []
            for p, n in zip(parts, counts):
                d = p.data[:n]
                if d.shape[1] < w:
                    d = jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                datas.append(d)
            data = jnp.concatenate(datas, axis=0)
            data = jnp.pad(data, ((0, padded - total), (0, 0)))
            lengths = jnp.concatenate(
                [p.lengths[:n] for p, n in zip(parts, counts)])
            lengths = jnp.pad(lengths, (0, padded - total))
        else:
            data = jnp.concatenate(
                [p.data[:n] for p, n in zip(parts, counts)])
            data = jnp.pad(data, (0, padded - total))
            lengths = None
        validity = jnp.concatenate(
            [p.validity[:n] for p, n in zip(parts, counts)])
        validity = jnp.pad(validity, (0, padded - total),
                           constant_values=False)
        cols.append(DeviceColumn(dtype, data, validity, lengths))
    return DeviceBatch(schema, cols, total)


class TpuCoalesceBatchesExec(TpuExec):
    def __init__(self, child, goal: CoalesceGoal):
        super().__init__([child])
        self.goal = goal
        #: a mesh stage lowers it as its child: nothing of it is read
        self.signature = ()

    @property
    def schema(self):
        return self.children[0].schema

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)
        min_bucket = ctx.conf.get(BUCKET_MIN_ROWS)
        target = self.goal.target \
            if isinstance(self.goal, TargetSize) \
            and self.goal.target is not None \
            else ctx.conf.get(BATCH_SIZE_BYTES)
        rows_target = None
        if isinstance(self.goal, TargetRows):
            rows_target = self.goal.rows if self.goal.rows is not None \
                else ctx.conf.get(SHUFFLE_TARGET_BATCH_ROWS)

        def make(pid):
            def it():
                if isinstance(self.goal, RequireSingleBatch):
                    batches = list(child.iterator(pid))
                    if not batches:
                        return
                    with trace_range("TpuCoalesce.concat",
                                     self.metrics[M.TOTAL_TIME]):
                        out = concat_device_batches(batches, min_bucket)
                    yield out
                    return
                if rows_target is not None:
                    if rows_target <= 0:  # disabled: passthrough
                        yield from child.iterator(pid)
                        return
                    # accumulate by PADDED rows — a host num_rows sync
                    # per input batch would cost the RTTs the coalesce
                    # exists to amortize (padding only over-fills)
                    pending: List[DeviceBatch] = []
                    pending_rows = 0
                    for db in child.iterator(pid):
                        r = db.padded_rows
                        if pending and pending_rows + r > rows_target:
                            yield concat_device_batches(pending,
                                                        min_bucket)
                            pending, pending_rows = [], 0
                        pending.append(db)
                        pending_rows += r
                    if pending:
                        yield concat_device_batches(pending, min_bucket)
                    return
                pending: List[DeviceBatch] = []
                pending_bytes = 0
                for db in child.iterator(pid):
                    b = db.device_bytes()
                    if pending and pending_bytes + b > target:
                        yield concat_device_batches(pending, min_bucket)
                        pending, pending_bytes = [], 0
                    pending.append(db)
                    pending_bytes += b
                if pending:
                    yield concat_device_batches(pending, min_bucket)

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"TpuCoalesceBatches[{self.goal!r}]"
