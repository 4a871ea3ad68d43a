"""Physical plan — the host (CPU) engine.

The reference accelerates an existing host engine (Spark).  This framework
is standalone, so the host engine lives here: columnar numpy operators over
``HostBatch`` partitions.  It serves three roles, same as CPU Spark does in
the reference's world:
  1. the CPU oracle the equality test harness compares the TPU engine to,
  2. the transparent fallback path for operators tagged off the device,
  3. the baseline for benchmark speedups.

Execution model: a plan executes to ``PartitionedData`` — N lazy partition
iterators of HostBatches (Spark RDD[ColumnarBatch] analogue); exchanges are
pipeline breakers that materialize through the shuffle layer.
"""
from __future__ import annotations

import logging
import math
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from .. import types as T
from ..data.column import HostBatch, HostColumn
from ..ops import miscexprs
from ..ops.aggregates import AggregateExpression, AggregateFunction
from ..ops.expression import (
    Alias,
    BoundReference,
    Expression,
    Scalar,
    as_host_column,
    bind_references,
    output_name,
)
from ..ops.kernels import segment as seg
from ..utils import hashing
from ..utils.metrics import MetricsRegistry
from . import functions as F

log = logging.getLogger(__name__)


class ExecContext:
    """Per-query execution context: conf, metrics, runtime services.

    ``scheduled=True`` marks a query running under the concurrent
    ``QueryScheduler``: its injectors are PRIVATE (bound thread-locally
    on the creating worker thread and propagated via
    ``telemetry.spans.capture()``) instead of (re)installed into the
    process-wide slots, and the process-global fault counters are not
    reset — one query's fault drill must not poison a concurrent
    neighbor.  ``cancel_token`` is the query's cooperative-cancellation
    token, bound to the creating thread the same way."""

    def __init__(self, conf, session=None, *, scheduled: bool = False,
                 cancel_token=None, force_host_shuffle: bool = False):
        self.conf = conf
        self.session = session
        self.metrics = MetricsRegistry()
        self.scheduled = scheduled
        self.cancel_token = cancel_token
        #: the ladder's host-shuffle rung: a re-execution with this set
        #: forces every exchange onto the host-staged path regardless
        #: of shuffle.mode (see Session._execute_host_shuffle_rung)
        self.force_host_shuffle = force_host_shuffle
        #: shuffle ids registered during this query, freed at query end
        #: (reference: per-shuffle cleanup, ShuffleBufferCatalog.scala)
        self.shuffle_ids: List[int] = []
        #: runtime stage statistics (adaptive/stats.py): every exchange
        #: write drain records its per-partition histogram here from
        #: numbers its gated readback already pulled to the host —
        #: collected unconditionally (histograms surface in profiles /
        #: Prometheus even with adaptive.enabled=false)
        from ..adaptive.stats import StageStats

        self.stage_stats = StageStats()
        #: per-query telemetry (telemetry.enabled) — bound to the
        #: creating thread; worker spawn sites capture() the binding.
        #: None when disabled (begin() also clears any stale binding)
        self.telemetry = None
        if session is not None:
            from ..telemetry.spans import QueryTelemetry

            self.telemetry = QueryTelemetry.begin(conf, session)
        if cancel_token is not None:
            from ..scheduler import cancel as _cancel

            _cancel.activate(cancel_token)
        # (re)arm the OOM fault injector from this query's conf — per
        # query so an oomInjection.skipCount sweep restarts its
        # checkpoint counter every run (device sessions only; a host
        # oracle session must not disarm a device session's injector)
        if session is not None and \
                getattr(session, "device_manager", None) is not None:
            from ..fault.injector import (FaultInjector,
                                          bind_scoped_fault_injector,
                                          install_fault_injector)
            from ..fault.stats import GLOBAL as _fault_stats
            from ..memory.retry import (OomInjector,
                                        bind_scoped_injector,
                                        install_injector)

            if scheduled:
                # per-query failure isolation: private injectors bound
                # to this worker thread (capture() propagates them);
                # the process slots — and the global fault counters —
                # belong to direct execute() callers
                self.scoped_oom_injector = OomInjector.from_conf(conf)
                self.scoped_fault_injector = \
                    FaultInjector.from_conf(conf)
                bind_scoped_injector(self.scoped_oom_injector)
                bind_scoped_fault_injector(self.scoped_fault_injector)
            else:
                install_injector(OomInjector.from_conf(conf))
                # the generalized fault injector + per-query fault
                # counters follow the same per-query (re)arm discipline
                install_fault_injector(FaultInjector.from_conf(conf))
                _fault_stats.reset()
        # kernel-cache counter snapshot: lets the session report
        # per-query hits/misses/compile wall from the process-wide cache
        from ..exec.kernel_cache import GLOBAL as _kernel_cache

        self.kernel_cache_mark = _kernel_cache.counters()
        # shuffle-stats snapshot — same delta-reporting discipline as
        # the kernel cache (session merges metrics_since at query end)
        from ..shuffle.device_shuffle import GLOBAL as _shuffle_stats

        self.shuffle_stats_mark = _shuffle_stats.counters()


class PartitionedData:
    def __init__(self, parts: List[Callable[[], Iterator[HostBatch]]]):
        self.parts = parts

    @property
    def n_partitions(self):
        return len(self.parts)

    def iterator(self, pid: int) -> Iterator[HostBatch]:
        miscexprs.context.partition_id = pid
        miscexprs.context.row_offset = 0
        return self.parts[pid]()


def _empty_batch(schema: T.Schema) -> HostBatch:
    return HostBatch(schema, [HostColumn.nulls(0, f.dtype) for f in schema])


def collect_batches(data: PartitionedData, schema: T.Schema,
                    ctx: "ExecContext" = None) -> HostBatch:
    """Drain every partition; with a context, partitions run as
    concurrent tasks on a thread pool — host decode/IO of one task
    overlaps device compute of another, with the device semaphore as
    admission control (reference: GpuSemaphore.scala:58-98 + the 2-4
    tasks/GPU guidance in docs/tuning-guide.md:85-100)."""
    n = data.n_partitions
    threads = 1
    retries = 0
    sem = None
    backoff_base = backoff_max = None
    backoff_rng = None
    if ctx is not None:
        from ..config import (RETRY_BACKOFF_BASE_MS, RETRY_BACKOFF_MAX_MS,
                              RETRY_BACKOFF_SEED, TASK_RETRIES,
                              TASK_THREADS)

        retries = max(0, ctx.conf.get(TASK_RETRIES))
        if n > 1:
            threads = min(ctx.conf.get(TASK_THREADS), n)
        if ctx.session is not None and ctx.session.device_manager:
            sem = ctx.session.device_manager.semaphore
        backoff_base = ctx.conf.get(RETRY_BACKOFF_BASE_MS)
        backoff_max = ctx.conf.get(RETRY_BACKOFF_MAX_MS)
        import random as _random

        backoff_rng = _random.Random(ctx.conf.get(RETRY_BACKOFF_SEED))

    def drain_with_retry(pid: int):
        """One 'task': drain a partition, retrying on failure
        (reference: Spark reschedules a failed task — the engine's
        iterators rebuild their pipeline state on re-call, and a failed
        shuffle write re-arms its election, so a transient failure
        re-executes the partition's lineage; the shuffle client's
        FetchRetry plays the same role, RapidsShuffleClient.scala:378).
        AssertionError is deterministic (strict-test-mode fallbacks,
        invariant checks) and is never retried, and neither is anything
        derived from KeyboardInterrupt/SystemExit (the user/interpreter
        asked to stop — re-executing the lineage would fight them).
        Retries back off with bounded exponential delay + seeded jitter
        (memory/retry.py) instead of hammering a contended device.
        Known divergence: batches emitted before the failure already
        counted in operator metrics, so a retried partition inflates
        NUM_OUTPUT_* — the same eager-accumulator behavior query
        metrics have under any partially-consumed iterator."""
        import time as _time

        from ..memory.retry import backoff_delay_s
        from ..scheduler.cancel import TpuQueryCancelled

        for attempt in range(retries + 1):
            try:
                return list(data.iterator(pid))
            except (KeyboardInterrupt, SystemExit):
                raise
            except AssertionError:
                raise
            except TpuQueryCancelled:
                # cancellation must terminate, not re-execute — but the
                # task's permits still unwind
                if sem is not None:
                    sem.release_task()
                raise
            except Exception:
                if sem is not None:
                    # drop ONLY this task's permits — a blanket release
                    # would strand concurrently-running healthy tasks
                    sem.release_task()
                if attempt == retries:
                    raise
                # unified attempt budget: a task retry is one recovery
                # attempt against fault.maxTotalAttempts (no-op when
                # unarmed — scheduled queries)
                from ..fault.budget import GLOBAL as _budget

                _budget.charge("task_retry", site="drain_with_retry")
                # backoff_base/max are always set here: retries > 0
                # implies ctx is not None, which populated them
                delay = backoff_delay_s(attempt, backoff_base,
                                        backoff_max, backoff_rng)
                log.warning("task for partition %d failed "
                            "(attempt %d/%d) — retrying in %.1fms",
                            pid, attempt + 1, retries + 1, delay * 1e3,
                            exc_info=True)
                _time.sleep(delay)
        raise AssertionError("retry loop must return or raise")

    if threads <= 1:
        # the inline path runs tasks ON the calling thread, so the
        # calling thread IS the task thread and must drop its device
        # hold when the drain ends — without this, a scheduler worker
        # draining a single-partition plan exits still holding a
        # permit, and the pool loses it for the life of the process
        # (the serial path masked it: the main thread idempotently
        # re-acquires its own stale hold on the next query)
        batches = []
        try:
            for pid in range(n):
                batches.extend(drain_with_retry(pid))
        finally:
            if sem is not None:
                sem.release_task()
    else:
        from concurrent.futures import ThreadPoolExecutor

        from ..telemetry import spans as tspans

        def run_task(pid: int):
            try:
                return drain_with_retry(pid)
            finally:
                if sem is not None:
                    sem.release_task()

        # pool workers inherit no thread-locals: capture the telemetry
        # binding here, attach per task
        cap = tspans.capture()
        with ThreadPoolExecutor(max_workers=threads) as pool:
            per_pid = list(pool.map(tspans.bound(cap, run_task),
                                    range(n)))
        batches = [b for bs in per_pid for b in bs]
    if not batches:
        return _empty_batch(schema)
    return HostBatch.concat(batches)


# ==========================================================================
# Base
# ==========================================================================
class PhysicalPlan:
    def __init__(self, children: Sequence["PhysicalPlan"] = ()):  # noqa
        self.children = list(children)

    @property
    def schema(self) -> T.Schema:
        raise NotImplementedError

    @property
    def name(self):
        return type(self).__name__

    def execute(self, ctx: ExecContext) -> PartitionedData:
        raise NotImplementedError

    def with_new_children(self, children):
        import copy

        node = copy.copy(self)
        node.children = list(children)
        return node

    def describe(self) -> str:
        return self.name

    def tree_string(self, indent: int = 0, annotate=None) -> str:
        pre = "  " * indent
        note = annotate(self) if annotate else ""
        s = f"{pre}{note}{self.describe()}"
        for c in self.children:
            s += "\n" + c.tree_string(indent + 1, annotate)
        return s

    def __repr__(self):  # pragma: no cover
        return self.tree_string()


# ==========================================================================
# Scans
# ==========================================================================
class LocalScanExec(PhysicalPlan):
    def __init__(self, batches: List[HostBatch], schema: T.Schema,
                 n_partitions: int = 1):
        super().__init__()
        self.batches = batches
        self._schema = schema
        self.n_partitions = max(1, n_partitions)

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        n = self.n_partitions
        buckets: List[List[HostBatch]] = [[] for _ in range(n)]
        if len(self.batches) >= n:
            for i, b in enumerate(self.batches):
                buckets[i % n].append(b)
        else:
            # split rows evenly
            total = sum(b.num_rows for b in self.batches)
            if total:
                big = HostBatch.concat(self.batches) \
                    if len(self.batches) > 1 else self.batches[0]
                per = math.ceil(total / n)
                for i in range(n):
                    lo, hi = i * per, min((i + 1) * per, total)
                    if lo < hi:
                        buckets[i].append(big.slice(lo, hi))

        def make(pid):
            return lambda: iter(buckets[pid])

        return PartitionedData([make(i) for i in range(n)])

    def describe(self):
        return f"LocalScan[{self._schema.names}]"


# ==========================================================================
# Row-level operators
# ==========================================================================
class ProjectExec(PhysicalPlan):
    """Reference analogue: GpuProjectExec (basicPhysicalOperators.scala:65)."""

    def __init__(self, child: PhysicalPlan, exprs: List[Expression]):
        super().__init__([child])
        self.exprs = [bind_references(e, child.schema) for e in exprs]
        self._schema = T.Schema([
            T.Field(output_name(raw, i), b.dtype, b.nullable)
            for i, (raw, b) in enumerate(zip(exprs, self.exprs))])

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def make(pid):
            def it():
                for batch in child.iterator(pid):
                    cols = [as_host_column(e.eval_cpu(batch),
                                           batch.num_rows)
                            for e in self.exprs]
                    miscexprs.context.row_offset += batch.num_rows
                    yield HostBatch(self._schema, cols)

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"Project[{', '.join(e.sql() for e in self.exprs)}]"


class FilterExec(PhysicalPlan):
    """Reference analogue: GpuFilterExec."""

    def __init__(self, child: PhysicalPlan, condition: Expression):
        super().__init__([child])
        self.condition = bind_references(condition, child.schema)

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def make(pid):
            def it():
                for batch in child.iterator(pid):
                    c = self.condition.eval_cpu(batch)
                    col = as_host_column(c, batch.num_rows)
                    keep = col.data.astype(np.bool_) & col.is_valid()
                    miscexprs.context.row_offset += batch.num_rows
                    yield batch.take(np.nonzero(keep)[0])

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"Filter[{self.condition.sql()}]"


class UnionExec(PhysicalPlan):
    @property
    def schema(self):
        return self.children[0].schema

    def __init__(self, children: List[PhysicalPlan]):
        super().__init__(children)

    def execute(self, ctx):
        parts = []
        for ch in self.children:
            data = ch.execute(ctx)
            parts.extend(data.parts)
        return PartitionedData(parts)


class CoalescePartitionsExec(PhysicalPlan):
    """Merge all partitions into one (logical coalesce(1))."""

    def __init__(self, child: PhysicalPlan):
        super().__init__([child])

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def it():
            for pid in range(child.n_partitions):
                yield from child.iterator(pid)

        return PartitionedData([it])


class LocalLimitExec(PhysicalPlan):
    def __init__(self, child: PhysicalPlan, n: int):
        super().__init__([child])
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def make(pid):
            def it():
                remaining = self.n
                for batch in child.iterator(pid):
                    if remaining <= 0:
                        break
                    if batch.num_rows <= remaining:
                        remaining -= batch.num_rows
                        yield batch
                    else:
                        yield batch.slice(0, remaining)
                        remaining = 0

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])


class GlobalLimitExec(PhysicalPlan):
    """Expects a single-partition child (planner inserts the exchange)."""

    def __init__(self, child: PhysicalPlan, n: int):
        super().__init__([child])
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        return LocalLimitExec(self.children[0], self.n).execute(ctx)


class ExpandExec(PhysicalPlan):
    """Reference analogue: GpuExpandExec — one output batch slice per
    projection list per input batch."""

    def __init__(self, child: PhysicalPlan,
                 projections: List[List[Expression]],
                 output_names: List[str]):
        super().__init__([child])
        self.projections = [[bind_references(e, child.schema) for e in ps]
                            for ps in projections]
        first = self.projections[0]
        self._schema = T.Schema([T.Field(n, b.dtype, True) for n, b in
                                 zip(output_names, first)])

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def make(pid):
            def it():
                for batch in child.iterator(pid):
                    for ps in self.projections:
                        cols = []
                        for f, e in zip(self._schema, ps):
                            c = as_host_column(e.eval_cpu(batch),
                                               batch.num_rows)
                            if c.dtype != f.dtype and \
                                    c.dtype.id is not T.TypeId.STRING:
                                if c.dtype.id is T.TypeId.NULL:
                                    c = HostColumn.nulls(batch.num_rows,
                                                         f.dtype)
                                else:
                                    c = HostColumn(
                                        f.dtype,
                                        c.data.astype(f.dtype.np_dtype),
                                        c.validity)
                            cols.append(c)
                        yield HostBatch(self._schema, cols)

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])


class GenerateExec(PhysicalPlan):
    """explode over literal element expressions (reference scope:
    GpuGenerateExec supports explode of array literals)."""

    def __init__(self, child: PhysicalPlan, elements: List[Expression],
                 out_name: str, position: bool = False):
        super().__init__([child])
        self.elements = [bind_references(e, child.schema)
                         for e in elements]
        self.position = position
        fields = list(child.schema.fields)
        if position:
            fields.append(T.Field("pos", T.INT32, False))
        fields.append(T.Field(out_name, self.elements[0].dtype, True))
        self._schema = T.Schema(fields)

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)
        k = len(self.elements)

        def make(pid):
            def it():
                for batch in child.iterator(pid):
                    n = batch.num_rows
                    rep = np.repeat(np.arange(n), k)
                    base = batch.take(rep)
                    cols = list(base.columns)
                    if self.position:
                        cols.append(HostColumn(
                            T.INT32, np.tile(np.arange(k, dtype=np.int32),
                                             n), None))
                    elem_cols = [as_host_column(e.eval_cpu(batch), n)
                                 for e in self.elements]
                    out_dtype = self._schema.fields[-1].dtype
                    if out_dtype.id is T.TypeId.STRING:
                        data = np.empty(n * k, dtype=object)
                    else:
                        data = np.zeros(n * k, dtype=out_dtype.np_dtype)
                    validity = np.ones(n * k, dtype=np.bool_)
                    for j, ec in enumerate(elem_cols):
                        data[j::k] = ec.data
                        validity[j::k] = ec.is_valid()
                    cols.append(HostColumn(
                        out_dtype, data,
                        None if validity.all() else validity))
                    yield HostBatch(self._schema, cols)

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])


# ==========================================================================
# Sort
# ==========================================================================
class SortExec(PhysicalPlan):
    """Per-partition sort (reference analogue: GpuSortExec; global sorts
    get a range exchange below them from the planner)."""

    def __init__(self, child: PhysicalPlan, keys: List[F.SortKey]):
        super().__init__([child])
        self.keys = [F.SortKey(bind_references(k.expr, child.schema),
                               k.ascending, k.nulls_first) for k in keys]

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def make(pid):
            def it():
                batches = list(child.iterator(pid))
                if not batches:
                    return
                batch = HostBatch.concat(batches) if len(batches) > 1 \
                    else batches[0]
                key_cols = [as_host_column(k.expr.eval_cpu(batch),
                                           batch.num_rows)
                            for k in self.keys]
                order = seg.lexsort_np(
                    key_cols,
                    [not k.ascending for k in self.keys],
                    [k.nulls_first for k in self.keys])
                yield batch.take(order)

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])

    def describe(self):
        ks = ", ".join(
            f"{k.expr.sql()} {'ASC' if k.ascending else 'DESC'}"
            for k in self.keys)
        return f"Sort[{ks}]"


# ==========================================================================
# Aggregate
# ==========================================================================
@dataclass
class AggSpec:
    func: AggregateFunction  # child already bound to input schema
    name: str


def _buffer_fields(specs: List[AggSpec]) -> List[T.Field]:
    fields = []
    for i, sp in enumerate(specs):
        for j, bt in enumerate(sp.func.buffer_dtypes()):
            fields.append(T.Field(f"_buf{i}_{j}", bt, True))
    return fields


class HashAggregateExec(PhysicalPlan):
    """Sort-based group-by on the host engine (reference analogue:
    GpuHashAggregateExec, aggregate.scala:227 — mode-aware partial/final).

    mode: 'partial'  -> outputs keys + partial buffers
          'final'    -> inputs keys + buffers, merges, finalizes
          'complete' -> single-stage group + finalize
    """

    def __init__(self, child: PhysicalPlan, mode: str,
                 key_exprs: List[Expression], specs: List[AggSpec],
                 out_names: Optional[List[str]] = None):
        super().__init__([child])
        self.mode = mode
        self.keys = [bind_references(k, child.schema) for k in key_exprs]
        self.specs = specs
        key_fields = [T.Field(output_name(k, i), self.keys[i].dtype,
                              self.keys[i].nullable)
                      for i, k in enumerate(key_exprs)]
        if mode == "partial":
            self._schema = T.Schema(key_fields + _buffer_fields(specs))
        else:
            names = out_names or [sp.name for sp in self.specs]
            self._schema = T.Schema(key_fields + [
                T.Field(n, sp.func.dtype, True)
                for n, sp in zip(names, specs)])

    @property
    def schema(self):
        return self._schema

    # ------------------------------------------------------------------
    def _group(self, batch: HostBatch):
        nkeys = len(self.keys)
        if self.mode == "final":
            key_cols = [batch.columns[i] for i in range(nkeys)]
        else:
            key_cols = [as_host_column(k.eval_cpu(batch), batch.num_rows)
                        for k in self.keys]
        if not key_cols:
            n = batch.num_rows
            return [], np.zeros(n, dtype=np.int64), 1
        order, seg_ids, seg_starts = seg.group_segments_np(key_cols)
        n_seg = len(seg_starts)
        sorted_keys = [c.take(order) for c in key_cols]
        out_keys = [c.take(seg_starts) for c in sorted_keys]
        return out_keys, (order, seg_ids), n_seg

    def _update_ops(self, sp: AggSpec):
        return sp.func.updates

    def execute(self, ctx):
        child = self.children[0].execute(ctx)

        def make(pid):
            def it():
                batches = list(child.iterator(pid))
                if not batches:
                    if self.keys or self.mode == "partial":
                        return
                    # global agg over empty input still yields one row
                    batches = [_empty_batch(self.children[0].schema)]
                batch = HostBatch.concat(batches) if len(batches) > 1 \
                    else batches[0]
                yield self._aggregate_batch(batch)

            return it

        return PartitionedData([make(i) for i in range(child.n_partitions)])

    def _aggregate_batch(self, batch: HostBatch) -> HostBatch:
        nkeys = len(self.keys)
        out_keys, grouping, n_seg = self._group(batch)
        if nkeys:
            order, seg_ids = grouping
        else:
            order = np.arange(batch.num_rows)
            seg_ids = grouping if isinstance(grouping, np.ndarray) \
                else np.zeros(batch.num_rows, dtype=np.int64)

        out_cols: List[HostColumn] = list(out_keys)
        if self.mode == "partial" or self.mode == "complete":
            buffers = []
            for i, sp in enumerate(self.specs):
                func = sp.func
                if func.child is None:  # count(*)
                    vals = np.ones(batch.num_rows, dtype=np.int64)[order]
                    valid = np.ones(batch.num_rows, dtype=np.bool_)[order]
                    inputs = [(vals, valid)]
                else:
                    c = as_host_column(func.child.eval_cpu(batch),
                                       batch.num_rows)
                    inputs = [(c.data[order], c.is_valid()[order])]
                for (op, which), bt in zip(func.updates,
                                           func.buffer_dtypes()):
                    vals, valid = inputs[which]
                    data, ok = seg.segment_reduce_np(
                        vals, valid, seg_ids, n_seg, op)
                    if data.dtype != bt.np_dtype and \
                            bt.id is not T.TypeId.STRING:
                        data = data.astype(bt.np_dtype)
                    buffers.append(HostColumn(
                        bt, data, None if ok.all() else ok))
            if self.mode == "partial":
                return HostBatch(self._schema, out_cols + buffers)
            # complete: finalize directly from buffers
            return self._finalize(out_cols, buffers, n_seg)
        # final: merge buffers then finalize
        buffers = []
        col_idx = nkeys
        for sp in self.specs:
            func = sp.func
            for op in func.merges:
                c = batch.columns[col_idx]
                data, ok = seg.segment_reduce_np(
                    c.data[order], c.is_valid()[order], seg_ids, n_seg, op)
                if c.dtype.id is not T.TypeId.STRING and \
                        data.dtype != c.dtype.np_dtype:
                    data = data.astype(c.dtype.np_dtype)
                buffers.append(HostColumn(c.dtype, data,
                                          None if ok.all() else ok))
                col_idx += 1
        return self._finalize(out_cols, buffers, n_seg)

    def _finalize(self, out_keys, buffers, n_seg) -> HostBatch:
        buf_schema = T.Schema(_buffer_fields(self.specs))
        buf_batch = HostBatch(buf_schema, buffers)
        out_cols = list(out_keys)
        bi = 0
        for sp, f in zip(self.specs,
                         self._schema.fields[len(self.keys):]):
            nbuf = len(sp.func.buffer_dtypes())
            refs = [BoundReference(bi + j, buffers[bi + j].dtype, True)
                    for j in range(nbuf)]
            final_expr = sp.func.finalize(refs)
            c = as_host_column(final_expr.eval_cpu(buf_batch), n_seg)
            if c.dtype != f.dtype and f.dtype.id is not T.TypeId.STRING \
                    and c.dtype.id is not T.TypeId.STRING:
                c = HostColumn(f.dtype, c.data.astype(f.dtype.np_dtype),
                               c.validity)
            out_cols.append(c)
            bi += nbuf
        return HostBatch(self._schema, out_cols)

    def describe(self):
        return (f"HashAggregate[{self.mode}, keys={len(self.keys)}, "
                f"aggs={[sp.func.sql() for sp in self.specs]}]")


# ==========================================================================
# Joins (host engine: dict-based hash join — the oracle)
# ==========================================================================
def _key_tuples(batch: HostBatch, key_exprs) -> List:
    cols = [as_host_column(k.eval_cpu(batch), batch.num_rows)
            for k in key_exprs]
    n = batch.num_rows
    out = []
    for i in range(n):
        key = []
        has_null = False
        for c in cols:
            v = c[i]
            if v is None:
                has_null = True
                break
            if isinstance(v, float):
                if v != v:  # NaN normalizes for join keys
                    v = float("nan")
                elif v == 0.0:
                    v = 0.0
            key.append(v)
        out.append(None if has_null else tuple(key))
    return out


class HashJoinExec(PhysicalPlan):
    """Host hash join (build = right side).  Supports inner/left/right/
    full/semi/anti with optional residual condition — a superset of the
    reference's GpuHashJoin (inner/left/semi/anti, GpuHashJoin.scala:25)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 left_keys, right_keys, how: str,
                 condition: Optional[Expression], broadcast: bool = False):
        super().__init__([left, right])
        self.left_keys = [bind_references(k, left.schema)
                          for k in left_keys]
        self.right_keys = [bind_references(k, right.schema)
                           for k in right_keys]
        self.how = how
        self.broadcast = broadcast
        lf = list(left.schema.fields)
        rf = list(right.schema.fields)
        if how in ("semi", "anti"):
            self._schema = T.Schema(lf)
            #: what a semi/anti join's condition reads: a left row beside
            #: one right row of an equal key (Spark's LeftSemi/LeftAnti
            #: residual condition sees both sides)
            self.pair_schema = T.Schema(lf + rf)
        else:
            if how in ("left", "full"):
                rf = [T.Field(f.name, f.dtype, True) for f in rf]
            if how in ("right", "full"):
                lf = [T.Field(f.name, f.dtype, True) for f in lf]
            self._schema = self.pair_schema = T.Schema(lf + rf)
        self.condition = bind_references(condition, self.pair_schema) \
            if condition is not None else None

    @property
    def schema(self):
        return self._schema

    def _join_partition(self, lbatch: HostBatch,
                        rbatch: HostBatch) -> HostBatch:
        lkeys = _key_tuples(lbatch, self.left_keys)
        rkeys = _key_tuples(rbatch, self.right_keys)
        build = {}
        for i, k in enumerate(rkeys):
            if k is not None:
                build.setdefault(k, []).append(i)
        lidx, ridx = [], []
        matched_r = np.zeros(rbatch.num_rows, dtype=np.bool_)
        for i, k in enumerate(lkeys):
            rows = build.get(k) if k is not None else None
            if rows:
                for r in rows:
                    lidx.append(i)
                    ridx.append(r)
                    matched_r[r] = True
            elif self.how in ("left", "full"):
                lidx.append(i)
                ridx.append(-1)
        if self.how in ("right", "full"):
            for r in range(rbatch.num_rows):
                if not matched_r[r]:
                    lidx.append(-1)
                    ridx.append(r)
        lidx = np.asarray(lidx, dtype=np.int64)
        ridx = np.asarray(ridx, dtype=np.int64)

        if self.how in ("semi", "anti"):
            has_match = np.zeros(lbatch.num_rows, dtype=np.bool_)
            if self.condition is None:
                has_match[lidx[lidx >= 0]] = True
            else:
                out = self._materialize(lbatch, rbatch, lidx, ridx)
                cond = as_host_column(self.condition.eval_cpu(out),
                                      out.num_rows)
                ok = cond.data.astype(np.bool_) & cond.is_valid()
                has_match[lidx[ok]] = True
            keep = has_match if self.how == "semi" else ~has_match
            return lbatch.take(np.nonzero(keep)[0])

        out = self._materialize(lbatch, rbatch, lidx, ridx)
        if self.condition is not None:
            cond = as_host_column(self.condition.eval_cpu(out),
                                  out.num_rows)
            ok = cond.data.astype(np.bool_) & cond.is_valid()
            if self.how == "inner":
                out = out.take(np.nonzero(ok)[0])
            else:
                # outer joins: failed condition -> unmatched (nulls)
                keep = ok | (lidx < 0) | (ridx < 0)
                out = out.take(np.nonzero(keep)[0])
        return out

    def _materialize(self, lbatch, rbatch, lidx, ridx) -> HostBatch:
        cols = []
        ln = lbatch.num_rows
        rn = rbatch.num_rows
        lsafe = np.clip(lidx, 0, max(ln - 1, 0))
        rsafe = np.clip(ridx, 0, max(rn - 1, 0))
        for c in lbatch.columns:
            taken = c.take(lsafe) if ln else HostColumn.nulls(len(lidx),
                                                              c.dtype)
            v = taken.is_valid() & (lidx >= 0)
            cols.append(HostColumn(c.dtype, taken.data,
                                   None if v.all() else v))
        for c in rbatch.columns:
            taken = c.take(rsafe) if rn else HostColumn.nulls(len(ridx),
                                                              c.dtype)
            v = taken.is_valid() & (ridx >= 0)
            cols.append(HostColumn(c.dtype, taken.data,
                                   None if v.all() else v))
        return HostBatch(self.pair_schema, cols)

    def execute(self, ctx):
        left = self.children[0].execute(ctx)
        right = self.children[1].execute(ctx)
        if self.broadcast:
            rbatches = []
            for pid in range(right.n_partitions):
                rbatches.extend(right.iterator(pid))
            rbatch = HostBatch.concat(rbatches) if rbatches else \
                _empty_batch(self.children[1].schema)

            def make(pid):
                def it():
                    lb = list(left.iterator(pid))
                    lbatch = HostBatch.concat(lb) if lb else \
                        _empty_batch(self.children[0].schema)
                    yield self._join_partition(lbatch, rbatch)

                return it

            return PartitionedData([make(i)
                                    for i in range(left.n_partitions)])
        assert left.n_partitions == right.n_partitions, \
            "shuffled join requires co-partitioned children"

        def make(pid):
            def it():
                lb = list(left.iterator(pid))
                rb = list(right.iterator(pid))
                lbatch = HostBatch.concat(lb) if lb else \
                    _empty_batch(self.children[0].schema)
                rbatch = HostBatch.concat(rb) if rb else \
                    _empty_batch(self.children[1].schema)
                yield self._join_partition(lbatch, rbatch)

            return it

        return PartitionedData([make(i) for i in range(left.n_partitions)])

    def describe(self):
        kind = "BroadcastHashJoin" if self.broadcast else "ShuffledHashJoin"
        return f"{kind}[{self.how}]"


# ==========================================================================
# Exchange
# ==========================================================================
class ShuffleExchangeExec(PhysicalPlan):
    """Host-path exchange (reference analogue: GpuShuffleExchangeExec with
    the CPU slicing path, Plugin.scala:54-130).  The partitioner computes
    a target partition per row; rows regroup across partitions through an
    in-memory shuffle store."""

    def __init__(self, child: PhysicalPlan, partitioning):
        super().__init__([child])
        self.partitioning = partitioning  # shuffle.partitioning.Partitioning

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def n_out(self):
        return self.partitioning.num_partitions

    def execute(self, ctx):
        # stage-checkpoint resume (recovery/): a validated checkpoint —
        # written by ANY rung, device included; the frame format is
        # mode-independent — replaces the whole child subtree
        rec = getattr(ctx, "recovery", None) if ctx is not None else None
        rfp = getattr(self, "_recovery_fp", None)
        if rec is not None and rfp is not None:
            from ..recovery.manager import schema_signature

            resumed = rec.try_resume(
                rfp, n_out=self.n_out,
                schema_sig=schema_signature(self.schema))
            if resumed is not None:
                return self._resumed_data(ctx, *resumed)
        child = self.children[0].execute(ctx)
        self.partitioning.prepare(child, self.children[0].schema)
        store: List[List[HostBatch]] = [[] for _ in range(self.n_out)]
        for pid in range(child.n_partitions):
            for batch in child.iterator(pid):
                if batch.num_rows == 0:
                    continue
                pids = self.partitioning.partition_ids(batch)
                for out_pid in range(self.n_out):
                    sel = np.nonzero(pids == out_pid)[0]
                    if len(sel):
                        store[out_pid].append(batch.take(sel))
        if rec is not None and rfp is not None:
            self._maybe_checkpoint(rec, rfp, store)

        def make(out_pid):
            return lambda: iter(store[out_pid])

        return PartitionedData([make(i) for i in range(self.n_out)])

    def _resumed_data(self, ctx, manifest, parts):
        """Serve a checkpoint ``try_resume`` already CRC-verified:
        deserialize each partition's frames back into HostBatches and
        record a resumed-stage observation so downstream sizing sees
        real numbers."""
        from ..native import serializer

        schema = self.schema
        store = [[serializer.deserialize(f, schema) for f in frames]
                 for frames in parts]
        stage_stats = getattr(ctx, "stage_stats", None) \
            if ctx is not None else None
        if stage_stats is not None:
            stage_stats.record_resumed(
                stage_stats.allocate_id(), n_out=self.n_out,
                part_rows=manifest.get("part_rows") or [],
                total_bytes=int(manifest.get("total_bytes", 0)),
                partitioning=type(self.partitioning).__name__,
                name=self.describe())

        def make(out_pid):
            return lambda: iter(store[out_pid])

        return PartitionedData([make(i) for i in range(self.n_out)])

    def _maybe_checkpoint(self, rec, rfp, store) -> None:
        """Persist the completed host exchange as a durable stage
        checkpoint; any failure disables checkpointing for the rest of
        the query (recovery is an optimization, never a failure mode)."""
        if not rec.should_checkpoint(rfp):
            return
        from ..native import serializer
        from ..recovery.manager import schema_signature

        try:
            frames = [[(serializer.serialize(b), b.num_rows)
                       for b in plist] for plist in store]
        except Exception as e:  # noqa: BLE001
            rec.disable(f"checkpoint serialization failed "
                        f"({type(e).__name__}: {e})")
            return
        rec.checkpoint_exchange(
            rfp, schema_sig=schema_signature(self.schema),
            n_out=self.n_out,
            part_rows=[sum(r for _f, r in plist) for plist in frames],
            total_bytes=sum(int(f.nbytes)
                            for plist in frames for f, _r in plist),
            partitioning=type(self.partitioning).__name__,
            frames=frames)

    def describe(self):
        return f"ShuffleExchange[{self.partitioning.describe()}]"


# ==========================================================================
# Write
# ==========================================================================
class DataWritingCommandExec(PhysicalPlan):
    """Reference analogue: the host InsertIntoHadoopFsRelationCommand —
    the rewrite engine tags it and converts supported writes to
    TpuDataWritingCommandExec (exec/write.py), like
    GpuOverrides.scala:1568-1580."""

    def __init__(self, child: PhysicalPlan, fmt: str, path: str,
                 options: dict, partition_by: List[str],
                 bucket_by: Optional[List[str]] = None):
        super().__init__([child])
        self.fmt = fmt
        self.path = path
        self.options = options
        self.partition_by = partition_by
        self.bucket_by = bucket_by or []

    @property
    def schema(self):
        return T.Schema([])

    def execute(self, ctx):
        from ..io import writers

        if self.bucket_by:
            raise NotImplementedError(
                "bucketed writes are not supported")
        child = self.children[0].execute(ctx)
        tracker = writers.write_partitions(
            child, self.children[0].schema, self.fmt, self.path,
            self.options, self.partition_by)
        if ctx is not None and getattr(ctx, "session", None) is not None:
            ctx.session.last_write_stats = tracker
        return PartitionedData([lambda: iter(())])
