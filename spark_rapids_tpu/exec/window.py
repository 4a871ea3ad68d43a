"""Device window exec.

Reference analogue: GpuWindowExec.scala:34-92 + GpuWindowExpression
(cudf rolling-window ops).  cudf evaluates frames with per-row rolling
kernels; the TPU formulation is scan-based over one global sort:

  * one lexsort by (partition keys, order keys) groups every window
    partition contiguously (same sort the reference's exchange+sort
    would do),
  * count/sum/avg over ANY rows frame become two gathers into an
    exclusive prefix sum,
  * min/max use segment-reset associative scans (unbounded ends) or a
    sparse-table doubling query (bounded frames — O(log width) levels,
    any width),
  * row_number/rank/dense_rank are index arithmetic on segment starts.

  * first/last over frames are index gathers: the frame edge row
    directly, or (ignoreNulls) a next/previous-valid-index scan.

Everything for all window expressions traces into ONE jitted program.
Falls back to the host engine for string-typed frame aggregates.
"""
from __future__ import annotations

from typing import List

from .. import types as T
from ..data.column import DeviceBatch, DeviceColumn
from ..ops.aggregates import (AggregateFunction, Average, Count, First,
                              Last, Sum)
from ..ops.expression import as_device_column
from ..ops.kernels import gather as G
from ..ops.kernels import segment as seg
from ..ops.windowexprs import (DenseRank, Rank, RowNumber,
                               WindowExpression)
from ..utils import metrics as M
from ..utils.tracing import device_phase, trace_range
from .base import DevicePartitionedData, RequireSingleBatch, TpuExec


def _supported_reason(wx: WindowExpression):
    """None if the expression runs on device, else the fallback reason
    (mirrors GpuWindowExpressionMeta tagging)."""
    func = wx.func
    if isinstance(func, (RowNumber, Rank, DenseRank)):
        return None
    if not isinstance(func, AggregateFunction):
        return f"window function {type(func).__name__} not on device"
    if isinstance(func, (First, Last)):
        if func.child is not None and func.child.dtype.is_string:
            return "string window aggregates run on the host engine"
        return None
    name = getattr(func, "name", type(func).__name__.lower())
    if isinstance(func, (Count, Sum, Average)) or name in ("min", "max"):
        child = func.child
        if child is not None and child.dtype.id is T.TypeId.STRING \
                and name in ("min", "max", "sum", "average", "avg"):
            return "string window aggregates run on the host engine"
        return None
    return f"window aggregate {name} runs on the host engine"


def _seg_scan(comb_val, vals, seg_ids, reverse=False):
    """Segment-reset associative scan: running reduce within each
    contiguous segment."""
    import jax
    import jax.numpy as jnp

    def comb(a, b):
        va, sa = a
        vb, sb = b
        return (jnp.where(sb == sa, comb_val(va, vb), vb), sb)

    out, _ = jax.lax.associative_scan(comb, (vals, seg_ids),
                                      reverse=reverse)
    return out


class TpuWindowExec(TpuExec):
    SPAN = "TpuWindow"

    def __init__(self, child, plan):
        super().__init__([child])
        self.plan = plan  # window_cpu.WindowExec (exprs already bound)
        self.window_exprs = plan.window_exprs
        self._schema = plan.schema
        from .kernel_cache import jit_kernel

        # window frames/specs have no compact canonical fingerprint —
        # compile privately (key=None), dispatch counters still apply
        self._kernel = jit_kernel(self._compute, kind="window")

    @property
    def schema(self):
        return self._schema

    @property
    def children_coalesce_goal(self):
        return [RequireSingleBatch()]

    # ------------------------------------------------------------------
    def _compute(self, batch: DeviceBatch) -> DeviceBatch:
        import jax
        import jax.numpy as jnp

        n = batch.padded_rows
        rm = batch.row_mask()
        out_cols = list(batch.columns)
        for wx in self.window_exprs:
            out_cols.append(self._one_window(batch, wx, n, rm))
        return DeviceBatch(self._schema, out_cols, batch.num_rows)

    def _one_window(self, batch, wx: WindowExpression, n, rm
                    ) -> DeviceColumn:
        import jax
        import jax.numpy as jnp

        spec = wx.spec
        part_cols = [as_device_column(e.eval_tpu(batch), n)
                     for e in spec.partition_by]
        order_cols = [as_device_column(k.expr.eval_tpu(batch), n)
                      for k in spec.order_by]
        desc = [False] * len(part_cols) + \
            [not k.ascending for k in spec.order_by]
        nf = [True] * len(part_cols) + \
            [k.nulls_first for k in spec.order_by]
        all_cols = part_cols + order_cols
        if all_cols:
            order = seg.lexsort_device(all_cols, desc, nf, pad_valid=rm)
        else:
            order = jnp.arange(n, dtype=jnp.int32)
        rm_s = rm[order]
        if part_cols:
            with device_phase("reorder"):
                sorted_parts = G.take_rows(part_cols, order)
            seg_ids = seg.segment_ids_device(sorted_parts, pad_valid=rm_s)
        else:
            # padding rows still need their own segments
            seg_ids = jnp.where(
                rm_s, 0,
                jnp.arange(n, dtype=jnp.int32) + 1).astype(jnp.int32)

        idx = jnp.arange(n, dtype=jnp.int64)
        seg_start = jax.ops.segment_min(idx, seg_ids, num_segments=n)[
            seg_ids].astype(jnp.int32)
        seg_end = (jax.ops.segment_max(idx, seg_ids, num_segments=n)[
            seg_ids] + 1).astype(jnp.int32)

        func = wx.func
        i32 = jnp.arange(n, dtype=jnp.int32)
        if isinstance(func, RowNumber):
            data = (i32 - seg_start + 1).astype(jnp.int32)
            valid = rm_s
        elif isinstance(func, (Rank, DenseRank)):
            if order_cols:
                with device_phase("reorder"):
                    sorted_all = G.take_rows(all_cols, order)
                ok_ids = seg.segment_ids_device(sorted_all,
                                                pad_valid=rm_s)
            else:  # no ordering: every row is its own tie group
                ok_ids = i32
            ok_start = jax.ops.segment_min(idx, ok_ids, num_segments=n)[
                ok_ids].astype(jnp.int32)
            if isinstance(func, Rank):
                data = (ok_start - seg_start + 1).astype(jnp.int32)
            else:
                first_ok_of_seg = ok_ids[jnp.clip(seg_start, 0, n - 1)]
                data = (ok_ids - first_ok_of_seg + 1).astype(jnp.int32)
            valid = rm_s
        else:
            data, valid = self._frame_agg(batch, wx, order, rm_s,
                                          seg_ids, seg_start, seg_end, n)

        # scatter back to original row order
        inv = jnp.zeros((n,), dtype=jnp.int32).at[order].set(i32)
        out_dtype = wx.dtype
        data = data[inv]
        if data.dtype != out_dtype.jnp_dtype:
            data = data.astype(out_dtype.jnp_dtype)
        return DeviceColumn(out_dtype, data, valid[inv] & rm)

    # ------------------------------------------------------------------
    def _frame_agg(self, batch, wx, order, rm_s, seg_ids, seg_start,
                   seg_end, n):
        import jax
        import jax.numpy as jnp

        func = wx.func
        frame = wx.spec.resolved_frame()
        child = func.child
        if child is None:  # count(*)
            vals = jnp.ones((n,), dtype=jnp.int64)
            valid = rm_s
        else:
            c = as_device_column(child.eval_tpu(batch), n)
            vals = c.data[order]
            valid = c.validity[order] & rm_s

        i32 = jnp.arange(n, dtype=jnp.int32)
        # frame [lo, hi) clamped to the segment (host oracle semantics)
        if frame.lower is None:
            lo = seg_start
        else:
            lo = jnp.clip(i32 + frame.lower, seg_start, seg_end)
        if frame.upper is None:
            hi = seg_end
        else:
            hi = jnp.clip(i32 + frame.upper + 1, seg_start, seg_end)
        hi = jnp.maximum(hi, lo)

        name = getattr(func, "name", "")
        cntP = jnp.concatenate([jnp.zeros((1,), jnp.int64),
                                jnp.cumsum(valid.astype(jnp.int64))])
        cnt = cntP[hi] - cntP[lo]
        if isinstance(func, (First, Last)):
            # index gathers on the frame edges (reference: cudf
            # rolling nth_element; here the sorted layout makes first =
            # row at lo, last = row at hi-1, and ignoreNulls the
            # next/previous VALID index via an associative scan)
            idx64 = jnp.arange(n, dtype=jnp.int64)
            nonempty = lo < hi
            if isinstance(func, First):
                if func.ignore_nulls:
                    cand = jnp.where(valid, idx64, jnp.int64(n))
                    nxt = jax.lax.associative_scan(jnp.minimum, cand,
                                                   reverse=True)
                    j = nxt[jnp.clip(lo, 0, n - 1)]
                    ok = nonempty & (j < hi)
                else:
                    j = lo.astype(jnp.int64)
                    ok = nonempty
            else:
                if func.ignore_nulls:
                    cand = jnp.where(valid, idx64, jnp.int64(-1))
                    prv = jax.lax.associative_scan(jnp.maximum, cand)
                    j = prv[jnp.clip(hi - 1, 0, n - 1)]
                    ok = nonempty & (j >= lo)
                else:
                    j = (hi - 1).astype(jnp.int64)
                    ok = nonempty
            jc = jnp.clip(j, 0, n - 1).astype(jnp.int32)
            out = vals[jc]
            out_valid = ok if func.ignore_nulls else ok & valid[jc]
            return out, out_valid
        if isinstance(func, Count):
            return cnt, jnp.ones((n,), dtype=jnp.bool_)
        if isinstance(func, (Sum, Average)):
            acc_t = jnp.float64 \
                if jnp.issubdtype(vals.dtype, jnp.floating) else jnp.int64
            z = jnp.where(valid, vals, 0).astype(acc_t)
            sumP = jnp.concatenate([jnp.zeros((1,), acc_t),
                                    jnp.cumsum(z)])
            s = sumP[hi] - sumP[lo]
            if isinstance(func, Average):
                s = s.astype(jnp.float64) / jnp.maximum(cnt, 1)
            return s, cnt > 0
        # min / max
        is_min = name == "min"
        if jnp.issubdtype(vals.dtype, jnp.floating):
            ident = jnp.asarray(jnp.inf if is_min else -jnp.inf,
                                vals.dtype)
        else:
            info = jnp.iinfo(vals.dtype)
            ident = jnp.asarray(info.max if is_min else info.min,
                                vals.dtype)
        masked = jnp.where(valid, vals, ident)
        comb = jnp.minimum if is_min else jnp.maximum
        if frame.lower is None and frame.upper is None:
            fn = jax.ops.segment_min if is_min else jax.ops.segment_max
            per_seg = fn(masked, seg_ids, num_segments=n)
            return per_seg[seg_ids], cnt > 0
        if frame.lower is None:
            run = _seg_scan(comb, masked, seg_ids)          # [start, i]
            out = run[jnp.clip(hi - 1, 0, n - 1)]
            return out, cnt > 0
        if frame.upper is None:
            run = _seg_scan(comb, masked, seg_ids, reverse=True)
            out = run[jnp.clip(lo, 0, n - 1)]               # [i, end)
            return out, cnt > 0
        # bounded both: sparse-table (doubling) range min/max — O(log w)
        # levels instead of a width-long unroll, so ANY frame width
        # compiles (the old _MAX_WIDTH=256 unroll cap is gone).
        # m_k[i] = comb over [i, i+2^k); query [lo, hi) = comb of the
        # two overlapping power-of-two windows at the edges.
        width = frame.upper - frame.lower + 1
        # clamp by the row count: ln <= n, so levels past
        # bit_length(n) can never be selected
        n_levels = max(1, int(min(width, n)).bit_length())
        levels = [masked]
        for k in range(1, n_levels):
            prev = levels[-1]
            sh = 1 << (k - 1)
            shifted = jnp.concatenate(
                [prev[sh:], jnp.full((sh,), ident, vals.dtype)])
            levels.append(comb(prev, shifted))
        table = jnp.stack(levels)                       # [L, n]
        ln = (hi - lo).astype(jnp.int64)
        # floor(log2(ln)) — exact: x64 float log2 is exact for ints
        lvl = jnp.floor(jnp.log2(jnp.maximum(ln, 1).astype(
            jnp.float64))).astype(jnp.int32)
        lvl = jnp.clip(lvl, 0, n_levels - 1)
        two_l = (jnp.int64(1) << lvl.astype(jnp.int64)).astype(jnp.int32)
        a = table[lvl, jnp.clip(lo, 0, n - 1)]
        b = table[lvl, jnp.clip(hi - two_l, 0, n - 1)]
        out = jnp.where(ln > 0, comb(a, b), ident)
        return out, cnt > 0

    # ------------------------------------------------------------------
    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                batches = list(child.iterator(pid))
                if not batches:
                    return
                from .coalesce import concat_device_batches

                batch = concat_device_batches(batches) \
                    if len(batches) > 1 else batches[0]
                with trace_range(self.SPAN,
                                 self.metrics[M.TOTAL_TIME]):
                    out = self._kernel(batch)
                self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return (f"TpuWindow[{', '.join(w.sql() for w in self.window_exprs)}]")


# ==========================================================================
# rule registration
# ==========================================================================
def register(register_exec):
    from .window_cpu import WindowExec

    def tag(meta):
        for wx in meta.plan.window_exprs:
            reason = _supported_reason(wx)
            if reason:
                meta.will_not_work_on_tpu(reason)

    def exprs_of(plan):
        out = []
        for wx in plan.window_exprs:
            out.extend(wx.spec.partition_by)
            out.extend(k.expr for k in wx.spec.order_by)
            if isinstance(wx.func, AggregateFunction) \
                    and wx.func.child is not None:
                out.append(wx.func.child)
        return out

    register_exec(
        WindowExec,
        convert=lambda meta, ch: TpuWindowExec(ch[0], meta.plan),
        desc="scan-based window functions on TPU",
        tag=tag,
        exprs_of=exprs_of)
