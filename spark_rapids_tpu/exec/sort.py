"""Device sort.

Reference analogue: GpuSortExec.scala — per-partition sort via cudf
``Table.orderBy`` with nulls-first/last handling.  The reference requires
a single batch per partition (coalesceGoal=RequireSingleBatch) and has no
external sort; this exec goes further: a partition larger than the batch
target is sorted out-of-core — each input batch becomes a sorted run cut
into spill-registered tiles, then a k-way tile merge streams the globally
sorted output (SURVEY §5's multi-tile sort demand).

The in-core sort is the device lexsort (order-preserving uint32 key
words + a chain of stable single-key sorts — XLA's sort lowers onto the
TPU's sorting network), followed by a gather.

Global sorts get a range exchange below them from the planner, exactly as
Spark's EnsureRequirements provides for the reference.
"""
from __future__ import annotations

from collections import deque
from typing import List

import numpy as np

from ..data.column import (DeviceBatch, bucket_rows, device_to_host,
                           slice_device_batch)
from ..memory import retry as R
from ..ops.expression import as_device_column, as_host_column
from ..ops.kernels import gather as G
from ..ops.kernels import segment as seg
from ..utils import metrics as M
from ..utils.tracing import trace_range, trace_steps
from .base import DevicePartitionedData, TargetSize, TpuExec


class _Tile:
    """One spill-registered tile of a sorted run: the catalog id plus the
    tile's last row (host, full schema — it doubles as the merge
    threshold sentinel) and its sort-key values for host-side compares."""

    __slots__ = ("buf_id", "last_row", "key_cols")

    def __init__(self, buf_id, last_row, key_cols):
        self.buf_id = buf_id
        self.last_row = last_row    # 1-row HostBatch (full schema)
        self.key_cols = key_cols    # 1-row key HostColumns


class TpuSortExec(TpuExec):
    SPAN = "TpuSort"

    def __init__(self, child, keys):
        super().__init__([child])
        self.keys = keys  # List[functions.SortKey], exprs already bound
        from .kernel_cache import (jit_kernel, schema_signature,
                                   sort_key_signature)

        self.signature = (schema_signature(child.schema),
                          sort_key_signature(keys))
        twin = self.kernel_twin()
        self._kernel = jit_kernel(twin._compute,
                                  key=("sort", self.signature))
        self._order_kernel = jit_kernel(twin._order,
                                        key=("sort_order", self.signature))

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def children_coalesce_goal(self):
        # multi-batch partitions run the external tile merge
        return [TargetSize()]

    def _order(self, batch):
        padded = batch.padded_rows
        rm = batch.row_mask()
        key_cols = [as_device_column(k.expr.eval_tpu(batch), padded)
                    for k in self.keys]
        # mask computed keys so padding rows can't influence ordering
        key_cols = [type(c)(c.dtype, c.data, c.validity & rm, c.lengths)
                    for c in key_cols]
        return seg.lexsort_device(
            key_cols,
            descending=[not k.ascending for k in self.keys],
            nulls_first=[k.nulls_first for k in self.keys],
            pad_valid=rm)

    def _compute(self, batch):
        order = self._order(batch)
        return G.gather_batch(batch, order, batch.num_rows)

    # ------------------------------------------------------------------
    # external merge
    # ------------------------------------------------------------------
    def _host_key_cols(self, row: "HostBatch"):
        return [as_host_column(k.expr.eval_cpu(row), row.num_rows)
                for k in self.keys]

    def _make_tiles(self, sorted_run: DeviceBatch, tile_rows: int,
                    fw, rctx) -> List[_Tile]:
        from ..memory.spill import SpillPriorities

        n = int(sorted_run.num_rows)
        tiles = []
        for start in range(0, n, tile_rows):
            stop = min(start + tile_rows, n)
            tile = slice_device_batch(sorted_run, start, stop)
            last = device_to_host(slice_device_batch(sorted_run,
                                                     stop - 1, stop, 1))
            buf_id = R.retry_call(
                lambda t=tile: fw.add_batch(
                    t, priority=SpillPriorities.output_for_read()),
                rctx)
            tiles.append(_Tile(buf_id, last, self._host_key_cols(last)))
        return tiles

    def _argmin_run(self, heads: List[_Tile]) -> int:
        """Index of the run whose current threshold row orders first."""
        if len(heads) == 1:
            return 0
        from ..data.column import HostColumn

        cols = [HostColumn.concat([h.key_cols[i] for h in heads])
                for i in range(len(self.keys))]
        order = seg.lexsort_np(
            cols,
            [not k.ascending for k in self.keys],
            [k.nulls_first for k in self.keys])
        return int(order[0])

    def _split_sorted(self, combined: DeviceBatch, order_np: np.ndarray,
                      sentinel_idx: int):
        """Split the sorted view of ``combined`` at the sentinel row:
        rows ordering <= sentinel (emitted) vs the rest (carried)."""
        import jax.numpy as jnp

        pos = int(np.nonzero(order_np == sentinel_idx)[0][0])
        n_real = int(combined.num_rows)  # includes the sentinel

        def take(idx: np.ndarray) -> DeviceBatch:
            cnt = len(idx)
            padded = bucket_rows(cnt)
            full = np.zeros(padded, dtype=np.int32)
            full[:cnt] = idx
            mask = jnp.arange(padded, dtype=jnp.int32) < cnt
            return G.gather_batch(combined, jnp.asarray(full), cnt, mask)

        emit = take(order_np[:pos]) if pos else None
        carry = take(order_np[pos + 1:n_real])
        return emit, carry

    def _merge_tiles(self, runs: List[deque], fw):
        """K-way merge of sorted, tiled runs.  Classic invariant: every
        unloaded row of run r orders >= the last row of r's most recently
        loaded tile, so carry rows ordering <= min over active runs of
        that threshold are final and stream out."""
        from .coalesce import concat_device_batches

        heads: List[_Tile] = []   # current threshold per active run
        loaded: List[DeviceBatch] = []
        for q in runs:
            t = q.popleft()
            heads.append(t)
            loaded.append(fw.acquire_batch(t.buf_id))
            fw.release_batch(t.buf_id)
            fw.remove_batch(t.buf_id)
        carry = concat_device_batches(loaded) if len(loaded) > 1 \
            else loaded[0]
        from ..scheduler.cancel import check_cancel

        active = list(range(len(runs)))
        while active:
            # a k-way merge over spilled runs can drain for a long
            # time between allocation checkpoints — poll cancellation
            # once per emitted tile
            check_cancel("sort.merge")
            # emit everything ordering <= the smallest active threshold
            k = self._argmin_run([heads[i] for i in active])
            r = active[k]
            from ..data.column import host_to_device

            sentinel = host_to_device(heads[r].last_row, 1)
            combined = concat_device_batches([carry, sentinel], 1)
            order_np = np.asarray(self._order_kernel(combined))
            emit, carry = self._split_sorted(
                combined, order_np, int(carry.num_rows))
            if emit is not None:
                yield emit
            # advance the bottleneck run
            if runs[r]:
                t = runs[r].popleft()
                heads[r] = t
                chunk = fw.acquire_batch(t.buf_id)
                fw.release_batch(t.buf_id)
                fw.remove_batch(t.buf_id)
                carry = concat_device_batches([carry, chunk])
            else:
                active.remove(r)
        if int(carry.num_rows) > 0:
            yield self._kernel(carry)

    def _sort_one(self, b: DeviceBatch) -> DeviceBatch:
        """Sort one batch, with an OOM-injection checkpoint at the
        attempt boundary (the retryable unit)."""
        R.maybe_inject_oom("TpuSort")
        return self._kernel(b)

    def _sort_chunked(self, batches, rctx):
        """Out-of-core path: sort each batch into a tiled run, then
        stream the k-way merge.  A batch too big to sort in one go is
        halved by the retry framework — each sorted piece simply becomes
        its own run, and the k-way merge restores the total order."""
        from ..memory.spill import SpillFramework

        fw = SpillFramework.get()
        runs: List[deque] = []
        tile_rows = None
        pending_first = None  # first run stays whole until a second shows
        for b in batches:
            for s in R.with_split_retry(b, self._sort_one, ctx=rctx):
                if int(s.num_rows) == 0:
                    continue
                if pending_first is None and not runs:
                    pending_first = s
                    continue
                if pending_first is not None:
                    tile_rows = bucket_rows(
                        max(1, int(pending_first.num_rows) // 4))
                    runs.append(deque(self._make_tiles(
                        pending_first, tile_rows, fw, rctx)))
                    pending_first = None
                runs.append(deque(self._make_tiles(s, tile_rows, fw,
                                                   rctx)))
        if pending_first is not None:
            yield pending_first
            return
        if not runs:
            return
        yield from self._merge_tiles(runs, fw)

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)
        rctx = R.RetryContext.for_exec(ctx, "TpuSortExec")

        def make(pid):
            def it():
                batches = child.iterator(pid)
                first = next(batches, None)
                if first is None:
                    return
                second = next(batches, None)

                def chunked(runs):
                    # the out-of-core merge streams, so it runs after
                    # the range below has closed: each of its steps
                    # gets a range of its own, closed at the hand-over
                    return trace_steps(self.SPAN,
                                       self._sort_chunked(runs, rctx),
                                       self.metrics[M.TOTAL_TIME])

                with trace_range(self.SPAN,
                                 self.metrics[M.TOTAL_TIME]):
                    if second is None:
                        try:
                            # allow_split: a genuine OOM that exhausts
                            # its retries escalates to the external
                            # merge below instead of failing the task
                            out = [R.retry_call(
                                lambda: self._sort_one(first), rctx,
                                allow_split=True)]
                        except R.TpuSplitAndRetryOOM:
                            if R.can_split(first, rctx):
                                # halve and route through the external
                                # merge: each half is a sorted run
                                halves = R.split_or_raise(first, rctx)
                                out = chunked(halves)
                            else:
                                # at the floor: plain retries (a split
                                # request degrades inside retry_call)
                                out = [R.retry_call(
                                    lambda: self._sort_one(first),
                                    rctx)]
                    else:
                        from itertools import chain

                        out = chunked(chain([first, second], batches))
                for b in out:
                    self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                    yield b

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        ks = ", ".join(
            f"{k.expr.sql()} {'ASC' if k.ascending else 'DESC'}"
            for k in self.keys)
        return f"TpuSort[{ks}]"


def register(register_exec):
    from ..plan import physical as P

    register_exec(
        P.SortExec,
        convert=lambda meta, ch: TpuSortExec(ch[0], meta.plan.keys),
        desc="device lexsort (stable multi-key radix passes)",
        exprs_of=lambda plan: [k.expr for k in plan.keys])
