"""Adaptive execution driver — the "AdaptiveSparkPlanExec" of this
engine.

``maybe_execute_adaptive(phys, ctx)`` runs an eligible physical plan
stage by stage: it picks a deepest unexecuted exchange, materializes it
(the writer-election drain — whose ONE gated readback also fills
``ctx.stage_stats``), swaps the exchange for a
:class:`MaterializedStageExec` leaf, and hands the now-partially-
executed plan to the :class:`~..adaptive.planner.AdaptivePlanner` so
the UNEXECUTED suffix can be rewritten around exact runtime sizes.
When no exchange remains, the final plan executes normally.

Build sides of shuffled joins materialize first — that is what gives
the broadcast-conversion rewrite its window: the build side's real
bytes are known while the stream-side exchange can still be skipped.
A build side that is itself stages away (an aggregate under it, as in
``IN (select ... group by ... having ...)``) keeps that window open:
the join's stream-side exchange waits until the build side has no
exchange left to run.

The original ``phys`` tree is never mutated (``with_new_children``
copies every ancestor on a replacement path), so the session's
WeakKeyDictionary plan cache never observes an adaptive rewrite.
"""
from __future__ import annotations

import logging
import random
import time
from typing import List, Optional

from ..exec.base import DevicePartitionedData, TpuExec
from ..exec.coalesce import TpuCoalesceBatchesExec
from ..exec.exchange import TpuShuffleExchangeExec
from ..exec.joins import TpuShuffledHashJoinExec
from ..telemetry.events import emit_event
from ..utils.tracing import trace_range

log = logging.getLogger(__name__)


def _strip_coalesce(node):
    while isinstance(node, TpuCoalesceBatchesExec):
        node = node.children[0]
    return node


# ==========================================================================
# MaterializedStageExec — an executed exchange as a plan leaf
# ==========================================================================
class MaterializedStageExec(TpuExec):
    """A drained shuffle exchange, readable as a plan leaf.

    ``specs`` describes how the materialized partitions are regrouped
    for readers — the AQE rewrites operate purely on it:

    * ``("parts", (p0, p1, ...))`` — one output partition chaining the
      original partitions in order (identity when one id per spec,
      coalescing when several);
    * ``("slice", p, ((item, row_lo, row_hi), ...))`` — one output
      partition reading a contiguous row slice of original partition
      ``p`` (skew splitting).

    Reads go through the exchange's retained reader closure
    (``data.aqe_read``), so spill/restore, corruption recovery and
    fault injection behave exactly as a non-adaptive read of the same
    buffers would.
    """

    def __init__(self, exchange: TpuShuffleExchangeExec,
                 data: DevicePartitionedData, stats,
                 specs: Optional[List[tuple]] = None, note: str = ""):
        super().__init__([])
        self.exchange = exchange
        self.data = data
        self.stats = stats  # ExchangeObservation or None (stats miss)
        self.specs = (list(specs) if specs is not None
                      else [("parts", (p,))
                            for p in range(data.n_partitions)])
        self.note = note

    # ------------------------------------------------------------------
    @property
    def schema(self):
        return self.exchange.schema

    @property
    def coalesce_after(self):
        return self.exchange.coalesce_after

    def is_identity(self) -> bool:
        return self.specs == [("parts", (p,))
                              for p in range(self.data.n_partitions)]

    def with_specs(self, specs: List[tuple],
                   note: str = "") -> "MaterializedStageExec":
        import copy

        node = copy.copy(self)
        node.specs = list(specs)
        node.note = note
        return node

    def describe(self) -> str:
        what = self.note or ("identity" if self.is_identity()
                             else "regrouped")
        return (f"TpuAQEShuffleRead[{what}] <- "
                f"{self.exchange.describe()}")

    # ------------------------------------------------------------------
    def execute_columnar(self, ctx) -> DevicePartitionedData:
        self._init_metrics(ctx)
        read = self.data.aqe_read
        parts = []
        for spec in self.specs:
            if spec[0] == "parts":
                ids = spec[1]
                if len(ids) == 1:
                    parts.append(read(ids[0]))
                else:
                    def chained(ids=ids):
                        for p in ids:
                            yield from read(p)()

                    parts.append(chained)
            else:  # ("slice", p, segments)
                _, p, segments = spec
                parts.append(read(p, list(segments)))
        return DevicePartitionedData(parts)


# ==========================================================================
# Plan surgery helpers
# ==========================================================================
def replace_node(plan, target, replacement):
    """Replace every identity-occurrence of ``target``, rebuilding the
    ancestors on each path with ``with_new_children`` (non-mutating —
    the cached original plan is shared with future executions)."""
    if plan is target:
        return replacement
    new_children = [replace_node(c, target, replacement)
                    for c in plan.children]
    if any(n is not o for n, o in zip(new_children, plan.children)):
        return plan.with_new_children(new_children)
    return plan


def _contains_exchange(node) -> bool:
    if isinstance(node, TpuShuffleExchangeExec):
        return True
    return any(_contains_exchange(c) for c in node.children)


def _pick_ready(plan, metrics=None) -> List[TpuShuffleExchangeExec]:
    """The exchanges whose whole input is executable now (no exchange
    below them), in the order to run them.

    Build sides of shuffled joins come first — materializing the build
    side before its stream side is what lets the broadcast rewrite
    skip the stream exchange entirely.  For the same reason the
    stream-side exchange of a join the rewrite may convert comes last
    while that join's build side still has an exchange to run: run
    now, it would be read "already executed" by the time the build
    side's size is known.  Whenever one waits, an exchange under its
    join's build side is ready, so a waiting exchange is picked only
    once everything else has run; each pick that passes one over
    counts in ``aqe.streamExchangesDeferred``."""
    ready: List[TpuShuffleExchangeExec] = []
    seen = set()
    build_ids, deferred = set(), set()

    def visit(node):
        if isinstance(node, TpuShuffleExchangeExec) \
                and id(node) not in seen \
                and not any(_contains_exchange(c)
                            for c in node.children):
            seen.add(id(node))
            ready.append(node)
        if isinstance(node, TpuShuffledHashJoinExec):
            stream, build = map(_strip_coalesce, node.children)
            build_ids.add(id(build))
            if node.how in TpuShuffledHashJoinExec._STREAM_SPLITTABLE \
                    and isinstance(build, TpuShuffleExchangeExec) \
                    and any(_contains_exchange(c)
                            for c in build.children):
                deferred.add(id(stream))
        for c in node.children:
            visit(c)

    visit(plan)

    def rank(e):
        return 2 if id(e) in deferred else 0 if id(e) in build_ids else 1

    ready.sort(key=rank)
    if metrics is not None and ready and rank(ready[-1]) == 2:
        metrics["aqe.streamExchangesDeferred"].add(1)
    return ready


# ==========================================================================
# Nondeterminism bail-out
# ==========================================================================
def _has_nondeterministic(plan) -> bool:
    """True if ANY expression anywhere in the plan is nondeterministic
    (rand, monotonically_increasing_id, spark_partition_id).  Those
    depend on partition id / row offset, which AQE regrouping changes
    by design — adaptive execution simply declines such plans, the
    same way fusion declines such segments."""
    from ..ops.expression import Expression
    from ..plan.physical import PhysicalPlan

    def exprs_from(obj, deep: bool):
        out: List[Expression] = []
        d = getattr(obj, "__dict__", None)
        if not d:
            return out
        for k, v in d.items():
            if k == "children":
                continue
            _scan(v, out, deep)
        return out

    def _scan(v, out, deep):
        if isinstance(v, Expression):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                _scan(x, out, deep)
        elif isinstance(v, dict):
            for x in v.values():
                _scan(x, out, deep)
        elif isinstance(v, PhysicalPlan):
            # an embedded plan descriptor (e.g. a TpuHashJoinExec's
            # bound logical join) — scan its expressions, one level
            if deep:
                out.extend(exprs_from(v, deep=False))
        elif isinstance(getattr(v, "expr", None), Expression):
            out.append(v.expr)  # SortKey and friends
        elif deep and not callable(v):
            # opaque holder (partitioning, coalesce goal, ...) — scan
            # its attributes one level for bound expressions
            out.extend(exprs_from(v, deep=False))

    def walk(node):
        yield node
        for m in getattr(node, "members", ()):  # fused segments
            yield m
        for c in node.children:
            yield from walk(c)

    for node in walk(plan):
        for e in exprs_from(node, deep=True):
            if not e.deterministic:
                return True
    return False


# ==========================================================================
# Stage materialization (+ the per-stage retry protocol)
# ==========================================================================
def _materialize_stage(exch: TpuShuffleExchangeExec, ctx,
                       ordinal: int) -> MaterializedStageExec:
    """Run one exchange's write drain to completion on the driver
    thread (span ``AqeStage``: the exchange's id is known once the
    lazy ``execute_columnar`` has allocated it), and record what it
    wrote."""
    data = exch.execute_columnar(ctx)
    with trace_range("AqeStage", exchange=data.aqe_exchange_id,
                     stage=ordinal):
        _drain_stage(data, ctx)
    obs = ctx.stage_stats.get(data.aqe_exchange_id)
    if obs is not None:
        fields = {"exchange": obs.exchange_id,
                  "partitions": obs.n_out,
                  "rows": obs.total_rows,
                  "bytes": obs.total_bytes,
                  "device_path": obs.device_path}
        h = obs.histogram()
        if h is not None:
            fields.update(rows_min=h["min"], rows_p50=h["p50"],
                          rows_max=h["max"], skew_pct=h["skewPct"])
        emit_event("aqe_stage_stats", **fields)
    return MaterializedStageExec(exch, data, obs)


def _drain_stage(data: DevicePartitionedData, ctx) -> None:
    """The drain, with the SAME retry discipline a reader task
    applies (plan/physical.py:drain_with_retry): bounded retries with
    seeded backoff, never for KeyboardInterrupt/SystemExit/AssertionError,
    cancellation terminates; the drain re-arms its writer election on
    failure so a retry re-executes the stage lineage — and re-records
    FRESH stage stats (``StageStats.record_exchange`` overwrites)."""
    from ..config import (RETRY_BACKOFF_BASE_MS, RETRY_BACKOFF_MAX_MS,
                          RETRY_BACKOFF_SEED, TASK_RETRIES)
    from ..memory.retry import backoff_delay_s
    from ..scheduler.cancel import TpuQueryCancelled

    retries = max(0, ctx.conf.get(TASK_RETRIES))
    sem = None
    if ctx.session is not None and ctx.session.device_manager:
        sem = ctx.session.device_manager.semaphore
    backoff_rng = random.Random(ctx.conf.get(RETRY_BACKOFF_SEED))
    backoff_base = ctx.conf.get(RETRY_BACKOFF_BASE_MS)
    backoff_max = ctx.conf.get(RETRY_BACKOFF_MAX_MS)
    try:
        for attempt in range(retries + 1):
            try:
                data.aqe_materialize()
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except AssertionError:
                raise
            except TpuQueryCancelled:
                raise
            except Exception:
                if sem is not None:
                    sem.release_task()  # don't hold permits asleep
                if attempt == retries:
                    raise
                # unified attempt budget (fault.maxTotalAttempts): a
                # stage retry is one recovery attempt
                from ..fault.budget import GLOBAL as _budget

                _budget.charge("stage_retry", site="aqe.materialize")
                delay = backoff_delay_s(attempt, backoff_base,
                                        backoff_max, backoff_rng)
                log.warning(
                    "adaptive stage drain failed (attempt %d/%d) — "
                    "retrying in %.1fms", attempt + 1, retries + 1,
                    delay * 1e3, exc_info=True)
                time.sleep(delay)
    finally:
        # the driver thread IS the drain's task thread — drop its
        # device hold per stage, mirroring the inline collect path
        if sem is not None:
            sem.release_task()


def _rebase_reservation(ctx) -> None:
    """Shrink the scheduler's per-query HBM reservation to what the
    query's stages actually materialize (with working-set headroom) —
    admission control stops charging the conservative planner estimate
    once real sizes exist."""
    if not ctx.scheduled or ctx.session is None:
        return
    sched = getattr(ctx.session, "_scheduler", None)
    rebase = getattr(sched, "rebase_reservation", None)
    if rebase is None:
        return
    peak = ctx.stage_stats.observed_peak_bytes()
    if peak <= 0:
        return
    # 4x: input stage + its shuffled output + kernel scratch headroom
    freed = rebase(peak * 4)
    if freed > 0:
        ctx.metrics["aqe.reservationFreedBytes"].add(freed)
        emit_event("aqe_reservation_rebase",
                   observed_peak_bytes=peak, freed_bytes=freed)


# ==========================================================================
# The driver
# ==========================================================================
def maybe_execute_adaptive(phys, ctx):
    """Execute ``phys`` adaptively if eligible; return its result data
    (whatever ``phys.execute(ctx)`` would return), or None to tell the
    session to take the normal non-adaptive path."""
    from ..config import ADAPTIVE_ENABLED
    from ..scheduler.cancel import check_cancel
    from .planner import AdaptivePlanner

    if ctx.session is None or not ctx.conf.get(ADAPTIVE_ENABLED):
        return None
    if getattr(ctx.session, "device_manager", None) is None:
        return None
    if not _contains_exchange(phys):
        return None  # no stage boundary — nothing to adapt
    if _has_nondeterministic(phys):
        log.debug("adaptive execution skipped: nondeterministic plan")
        return None

    plan = phys
    n_stages = 0
    while True:
        check_cancel("aqe.stage_loop")
        ready = _pick_ready(plan, ctx.metrics)
        if not ready:
            break
        stage = _materialize_stage(ready[0], ctx, n_stages)
        n_stages += 1
        plan = replace_node(plan, ready[0], stage)
        with trace_range("AqeReplan"):
            plan = AdaptivePlanner(ctx).rewrite(plan)
        _rebase_reservation(ctx)
    ctx.aqe_final_phys = plan
    ctx.metrics["aqe.numStages"].add(n_stages)
    emit_event("aqe_final_plan", stages=n_stages,
               plan=plan.tree_string())
    return plan.execute(ctx)
