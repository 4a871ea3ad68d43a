"""Device hash aggregate.

Reference analogue: GpuHashAggregateExec (aggregate.scala:227-396) — the
mode-aware (partial/final/complete) columnar aggregate.  The reference
lowers to cudf's hash groupBy; hash tables scatter randomly, which is
hostile to the TPU memory model, so this exec is sort-based: lexsort rows
by key, flag the key-change boundaries, then reduce the sorted segments
by scans (``ops/kernels/segment.reduce_sorted``: no scatter, and a
*static* output size, the row bucket, so shapes stay XLA-friendly;
SURVEY §7 Hard parts: sort + segment-reduce).

The whole aggregate — key eval, sort, segment ids, every buffer reduction,
and the finalize expressions — traces into ONE jitted XLA program per
(schema, row-bucket), so XLA fuses the elementwise work into the sort and
reduction loops.

An update-phase aggregate (``partial`` / ``complete``) may have ABSORBED
the row-local Filter/Project chain that stood directly under it
(plan/fusion.py): the chain's node is gone from the plan, its members run
as a prologue of every kernel that evaluates raw input, and the filters'
keep mask joins the row mask.  Nothing compacts — the aggregate treats a
masked-out row as it always treated a padding row (invalid key, invalid
in every update, sorted last), so dense rows were never needed.
"""
from __future__ import annotations

from .. import types as T
from ..data.column import DeviceBatch, DeviceColumn
from ..memory import retry as R
from ..ops.aggregates import AggregateFunction
from ..ops.expression import BoundReference, as_device_column
from ..ops.kernels import gather as G
from ..ops.kernels import segment as seg
from ..utils import metrics as M
from ..utils.tracing import device_phase, trace_range
from .base import DevicePartitionedData, TpuExec
from .fused import members_signature, run_members


class TpuHashAggregateExec(TpuExec):
    """Sort-based group-by on device; reads its bound keys/specs/schema
    off ``plan``, the host plan node or an aggregate rebuilt (modes are
    identical), and holds none of it.

    Out-of-core: a partition bigger than the batch-size goal arrives as
    several batches; each is aggregated to its buffer form and merged into
    a running grouped result — the same concat+merge loop the reference
    runs per batch (aggregate.scala:240-335).  The running result is
    registered with the spill catalog between merges so memory pressure
    can evict it."""

    SPAN = "TpuHashAggregate"

    def __init__(self, child, plan, absorbed=()):
        super().__init__([child])
        self.mode = plan.mode
        self.keys = plan.keys
        self.specs = plan.specs
        self._schema = plan.schema
        #: the row-local members (TpuFilterExec / TpuProjectExec, closest
        #: to the source first) that stood between ``child`` and this
        #: aggregate until the fusion pass folded them in; ``keys`` and
        #: the specs are bound to the last member's schema
        self.absorbed = list(absorbed)
        assert not self.absorbed or self.mode != "final", \
            "a final aggregate reads buffers, not raw rows"
        from .kernel_cache import (expr_signature, jit_kernel,
                                   schema_signature)

        # an absorbed chain is part of every body that reads raw rows
        self.signature = (self.mode, schema_signature(child.schema),
                          expr_signature(self.keys),
                          tuple(sp.func.sql() for sp in self.specs),
                          schema_signature(self._schema)) + (
            (members_signature(self.absorbed),) if self.absorbed else ())
        twin = self.kernel_twin()
        self._kernel = jit_kernel(twin.compute_batch,
                                  key=("agg", self.signature, "batch"))
        # chunked-path kernels (used only when a partition spans batches)
        self._update_kernel = jit_kernel(
            lambda b: twin._compute(b, "update", "buffers"),
            key=("agg", self.signature, "update"))
        self._merge_kernel = jit_kernel(
            lambda b: twin._compute(b, "merge", "buffers"),
            key=("agg", self.signature, "merge"))
        # only reached from _agg_chunked when mode is final/complete
        # (partial returns the running buffers before finalize)
        self._merge_final_kernel = jit_kernel(
            lambda b: twin._compute(b, "merge", "final"),
            key=("agg", self.signature, "merge_final"))

    def kernel_twin(self):
        # the absorbed members still link to the chain they stood in: a
        # cached kernel must not pin that subtree (as exec/fused.py)
        twin = super().kernel_twin()
        twin.absorbed = [m.kernel_twin() for m in self.absorbed]
        return twin

    def prologue(self, batch: DeviceBatch):
        """Raw input through the absorbed members: (the rows as the
        aggregate's expressions read them, the filters' keep mask)."""
        with device_phase("agg.prologue"):
            # no Expand among them: one stream
            (out,) = run_members(self.absorbed, batch)
        return out

    def compute_batch(self, batch: DeviceBatch) -> DeviceBatch:
        """The mode's full aggregation over one batch (trace-safe; also
        the per-shard form the distributed runner lowers through)."""
        phase = "merge" if self.mode == "final" else "update"
        emit = "buffers" if self.mode == "partial" else "final"
        return self._compute(batch, phase, emit)

    @property
    def schema(self):
        return self._schema

    @property
    def buffer_schema(self) -> T.Schema:
        """Schema of the pre-finalize form: group keys + agg buffers
        (for a partial agg this IS the output schema)."""
        from ..plan.physical import _buffer_fields

        nkeys = len(self.keys)
        if self.mode == "partial":
            return self._schema
        key_fields = [
            T.Field(f.name, k.dtype)
            for f, k in zip(self._schema.fields[:nkeys], self.keys)
        ] if self.mode == "complete" else \
            list(self.children[0].schema.fields[:nkeys])
        return T.Schema(key_fields + _buffer_fields(self.specs))

    @property
    def children_coalesce_goal(self):
        # chunked concat+merge handles multi-batch partitions; the goal is
        # the session batch-size target (reference: aggregate.scala loops
        # concat+merge per batch at the same goal)
        from .base import TargetSize

        return [TargetSize()]

    # ------------------------------------------------------------------
    def _compute(self, batch: DeviceBatch, phase: str,
                 emit: str) -> DeviceBatch:
        """One aggregation pass.  ``phase``: "update" evaluates key/value
        expressions over raw input rows; "merge" treats the batch as
        buffer-form (keys + buffers).  ``emit``: "buffers" outputs the
        grouped buffer form; "final" applies the finalize expressions."""
        rm = batch.row_mask()
        held = None
        if phase == "update" and self.absorbed:
            # a row the absorbed filters drop is absent as a padding
            # row is: invalid in every key and input, sorted last
            batch, keep = self.prologue(batch)
            held, rm = rm, rm & keep
        # the aggregate's own ops under its name, beside the absorbed
        # members' (and, in a mesh stage, the other operators')
        with device_phase(self.SPAN):
            return self._reduce(batch, phase, emit, rm, held)

    def _reduce(self, batch: DeviceBatch, phase: str, emit: str, rm,
                held) -> DeviceBatch:
        """``_compute`` past the prologue: ``rm`` the rows that count,
        ``held`` the batch's own row mask where a filter was absorbed."""
        import jax.numpy as jnp

        nkeys = len(self.keys)
        padded = batch.padded_rows

        # ----- keys ----------------------------------------------------
        if phase == "merge":
            key_cols = [batch.columns[i] for i in range(nkeys)]
        else:
            key_cols = [as_device_column(k.eval_tpu(batch), padded)
                        for k in self.keys]
        key_cols = [DeviceColumn(c.dtype, c.data, c.validity & rm,
                                 c.lengths) for c in key_cols]

        # ----- sort + segments -----------------------------------------
        idx = jnp.arange(padded, dtype=jnp.int32)
        # padding rows sort last, each its own segment
        pad_sorted = idx < rm.sum()
        if nkeys:
            order = seg.lexsort_device(key_cols, pad_valid=rm)
            with device_phase("reorder"):
                sorted_keys = G.take_rows(key_cols, order)
            change = seg.segment_change_device(sorted_keys,
                                               pad_valid=pad_sorted)
            n_real = (change & pad_sorted).sum().astype(jnp.int32)
        else:
            order = None
            # row 0 starts the one real segment; under an absorbed filter
            # no sort brings the kept rows to the front, so it spans
            # every row the batch holds
            change = ~(pad_sorted if held is None else held)
            n_real = jnp.asarray(1, dtype=jnp.int32)
        out_valid_seg = idx < n_real

        # ----- reductions: every buffer's, and each key's first row, as
        # (column over the rows as they stand, op, buffer dtype) ---------
        specs = [(c, "first_any", c.dtype) for c in key_cols]
        if phase == "update":
            specs += self._update_specs(batch, rm)
        else:
            specs += self._merge_specs(batch, rm, nkeys)
        out_cols = []
        # the real segments alone are read: a keyless aggregate has its
        # one, a keyed one counts them on the device and the kernel picks
        # its read's width there (the rows past them are masked below)
        for (data, valid, lengths), (_, _, dtype) in zip(
                seg.reduce_sorted(change, order, [sp[:2] for sp in specs],
                                  segments=n_real if nkeys else 1),
                specs):
            if lengths is None and data.dtype != dtype.jnp_dtype:
                data = data.astype(dtype.jnp_dtype)
            out_cols.append(DeviceColumn(dtype, data, valid & out_valid_seg,
                                         lengths))

        if emit == "buffers":
            return DeviceBatch(self.buffer_schema, out_cols, n_real)
        return self._finalize(out_cols[:nkeys], out_cols[nkeys:], n_real,
                              padded, out_valid_seg)

    # ------------------------------------------------------------------
    def _update_specs(self, batch, rm) -> list:
        import jax.numpy as jnp

        padded = batch.padded_rows
        specs = []
        for sp in self.specs:
            func: AggregateFunction = sp.func
            if func.child is None:  # count(*)
                inputs = [DeviceColumn(
                    T.INT64, jnp.ones((padded,), dtype=jnp.int64), rm)]
            else:
                c = as_device_column(func.child.eval_tpu(batch), padded)
                inputs = [DeviceColumn(c.dtype, c.data, c.validity & rm,
                                       c.lengths)]
            for (op, which), bt in zip(func.updates, func.buffer_dtypes()):
                specs.append((inputs[which], op, bt))
        return specs

    def _merge_specs(self, batch, rm, nkeys) -> list:
        specs = []
        col_idx = nkeys
        for sp in self.specs:
            func: AggregateFunction = sp.func
            for op, bt in zip(func.merges, func.buffer_dtypes()):
                c = batch.columns[col_idx]
                specs.append((DeviceColumn(c.dtype, c.data, c.validity & rm,
                                           c.lengths), op, bt))
                col_idx += 1
        return specs

    # ------------------------------------------------------------------
    def _finalize(self, out_keys, buffers, n_real, padded,
                  out_valid_seg) -> DeviceBatch:
        from ..plan.physical import _buffer_fields

        buf_schema = T.Schema(_buffer_fields(self.specs))
        buf_batch = DeviceBatch(buf_schema, buffers, n_real)
        out_cols = list(out_keys)
        bi = 0
        nkeys = len(self.keys)
        for sp, f in zip(self.specs, self._schema.fields[nkeys:]):
            nbuf = len(sp.func.buffer_dtypes())
            refs = [BoundReference(bi + j, buffers[bi + j].dtype, True)
                    for j in range(nbuf)]
            final_expr = sp.func.finalize(refs)
            c = as_device_column(final_expr.eval_tpu(buf_batch), padded)
            if c.dtype != f.dtype and f.dtype.id is not T.TypeId.STRING \
                    and c.dtype.id is not T.TypeId.STRING:
                c = DeviceColumn(f.dtype,
                                 c.data.astype(f.dtype.jnp_dtype),
                                 c.validity, c.lengths)
            c = DeviceColumn(c.dtype, c.data, c.validity & out_valid_seg,
                             c.lengths)
            out_cols.append(c)
            bi += nbuf
        return DeviceBatch(self._schema, out_cols, n_real)

    # ------------------------------------------------------------------
    def _to_buffers_fn(self):
        """Buffer-form transform of one raw input piece (identity for
        ``final`` mode, whose input already IS buffer form), with an
        OOM-injection checkpoint at the attempt boundary."""
        inner = (lambda b: b) if self.mode == "final" \
            else self._update_kernel

        def fn(b):
            R.maybe_inject_oom("TpuHashAggregate.update")
            return inner(b)

        return fn

    def _agg_chunked(self, first: DeviceBatch, rest,
                     rctx) -> DeviceBatch:
        """Out-of-core path: per-batch buffer-form agg + running merge
        (reference: aggregate.scala:240-335 concat+merge loop).  The
        running result sits in the spill catalog between merges so the
        alloc-pressure handler can evict it while the next input batch
        is being produced/aggregated.  Each per-batch pass runs through
        the retry framework: an OOM retries after spill+backoff, a
        split request halves the input batch — buffer forms of the
        pieces merge into the running result exactly like whole
        batches."""
        from itertools import chain

        from ..memory.spill import SpillFramework, SpillPriorities
        from .coalesce import concat_device_batches

        fw = SpillFramework.get()
        to_buffers = self._to_buffers_fn()

        running = None  # merged buffer form so far (device batch)
        rid = None      # spill-catalog id while running is parked

        def park():
            # running sits in the spill catalog while the NEXT piece is
            # being produced/aggregated, so pressure can evict it
            nonlocal rid
            if running is not None and rid is None:
                rid = R.retry_call(
                    lambda: fw.add_batch(
                        running,
                        priority=SpillPriorities.ACTIVE_ON_DECK),
                    rctx)

        def unpark():
            nonlocal rid, running
            if rid is not None:
                running = R.retry_call(
                    lambda: fw.acquire_batch(rid), rctx)
                fw.release_batch(rid)
                fw.remove_batch(rid)
                rid = None

        for nxt in chain([first], rest):
            park()
            for part in R.with_split_retry(nxt, to_buffers, ctx=rctx):
                unpark()
                if running is None:
                    running = part
                else:
                    combined = concat_device_batches([running, part])
                    running = R.retry_call(
                        lambda c=combined: self._merge_kernel(c), rctx)
                park()
        unpark()
        if self.mode == "partial":
            return running
        # re-merging the grouped running result is the identity on every
        # buffer (one row per segment), so this pass just re-groups and
        # applies the finalize expressions
        return self._merge_final_kernel(running)

    def _agg_split(self, batch: DeviceBatch, rctx) -> DeviceBatch:
        """Split-and-retry escalation for the single-batch path: halve
        the input, aggregate each piece to buffer form (recursively
        splittable), then merge — the same composition the chunked
        out-of-core path uses, so results match the unsplit kernel."""
        from .coalesce import concat_device_batches

        to_buffers = self._to_buffers_fn()
        running = None
        for part in R.with_split_retry(batch, to_buffers, ctx=rctx,
                                       initial_split=True):
            running = part if running is None else R.retry_call(
                lambda c=concat_device_batches([running, part]):
                self._merge_kernel(c), rctx)
        if self.mode == "partial":
            return running
        return self._merge_final_kernel(running)

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)
        rctx = R.RetryContext.for_exec(ctx, "TpuHashAggregateExec")

        def make(pid):
            def it():
                batches = child.iterator(pid)
                first = next(batches, None)
                if first is None:
                    if self.keys or self.mode == "partial":
                        return
                    # global agg over empty input still yields one row
                    from ..data.column import host_to_device
                    from ..plan.physical import _empty_batch

                    first = host_to_device(
                        _empty_batch(self.children[0].schema))
                second = next(batches, None)

                def agg_full(b):
                    R.maybe_inject_oom("TpuHashAggregate")
                    return self._kernel(b)

                with trace_range(self.SPAN,
                                 self.metrics[M.TOTAL_TIME]):
                    if second is None:
                        try:
                            # allow_split: a genuine OOM that exhausts
                            # its retries escalates to the split path
                            # below instead of failing the task
                            out = R.retry_call(
                                lambda: agg_full(first), rctx,
                                allow_split=True)
                        except R.TpuSplitAndRetryOOM:
                            if R.can_split(first, rctx):
                                out = self._agg_split(first, rctx)
                            else:
                                # at the floor: plain retries (a split
                                # request degrades inside retry_call)
                                out = R.retry_call(
                                    lambda: agg_full(first), rctx)
                    else:
                        from itertools import chain

                        out = self._agg_chunked(
                            first, chain([second], batches), rctx)
                self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        absorbed = ", absorbed: " + " -> ".join(
            m.describe() for m in self.absorbed) if self.absorbed else ""
        return (f"TpuHashAggregate[{self.mode}, keys={len(self.keys)}, "
                f"aggs={[sp.func.sql() for sp in self.specs]}"
                f"{absorbed}]")


# ==========================================================================
# rule registration
# ==========================================================================
def register(register_exec):
    from ..config import HASH_AGG_REPLACE_MODE
    from ..plan import physical as P

    def tag(meta):
        from ..config import ALLOW_FLOAT_AGG

        if not meta.conf.get(ALLOW_FLOAT_AGG):
            # reference: GpuHashAggregateMeta rejects float aggregation
            # unless variableFloatAgg is enabled (order-dependent sums)
            for sp in meta.plan.specs:
                child = sp.func.child
                if child is not None and child.dtype.is_floating:
                    meta.will_not_work_on_tpu(
                        f"aggregation over floating column "
                        f"({sp.func.sql()}) disabled; enable "
                        "spark.rapids.tpu.sql.variableFloatAgg.enabled")
                    break
        # reference: hashAgg.replaceMode gates which modes convert
        # (aggregate.scala GpuHashAggregateMeta + RapidsConf:483-493)
        allowed = str(meta.conf.get(HASH_AGG_REPLACE_MODE)).lower()
        if allowed != "all":
            modes = {m.strip() for m in allowed.split("|")}
            mode = meta.plan.mode
            if mode == "complete":
                mode = "partial"  # complete ~ single-phase partial+final
            if mode not in modes:
                meta.will_not_work_on_tpu(
                    f"aggregation mode {meta.plan.mode} excluded by "
                    f"hashAgg.replaceMode={allowed}")

    def exprs_of(plan: P.HashAggregateExec):
        out = list(plan.keys)
        for sp in plan.specs:
            out.append(sp.func)
        return out

    register_exec(
        P.HashAggregateExec,
        convert=lambda meta, ch: TpuHashAggregateExec(ch[0], meta.plan),
        desc="sort-based segment-reduce group-by on TPU",
        tag=tag,
        exprs_of=exprs_of)
