"""The AQE rewrites — applied to the unexecuted plan suffix between
stages.

Reference analogue: Spark 3.0's AQE optimizer rules, in their relative
order — DynamicJoinSelection (broadcast demotion) runs while the
stream-side exchange is still unexecuted (that is the whole point:
skipping it), OptimizeSkewedJoin next (it must see both sides, before
their partitions are regrouped), CoalesceShufflePartitions last (it
must not merge a partition skew just decided to split).

Every rewrite function emits its structured ``aqe_*`` decision event —
the ``decision-event`` analysis rule enforces the pairing
mechanically —
and bumps an ``aqe.*`` int counter that rides ``Session.last_metrics``
into the Prometheus export.

Bit-identity argument per rewrite:

* broadcast conversion — the stream side keeps its pre-exchange
  partitioning and row order; the build side is the SAME materialized
  partitions concatenated.  Hash join output values depend only on the
  joined multiset, and everything downstream of the join either
  re-partitions (another exchange) or is row-local.
* skew split — a skewed partition is cut into CONTIGUOUS row slices
  (``stats.split_partition_segments``), each joined against a replica
  of the full build partition; slices concatenated in order reproduce
  the unsplit partition's stream sequence exactly.
* coalescing — only ADJACENT partitions merge, and a co-partitioned
  join gets the identical grouping on both sides, so reader concat
  order equals the non-adaptive per-partition concat order.
"""
from __future__ import annotations

import logging
from typing import List, Optional, Tuple

from ..config import (ADAPTIVE_AUTO_BROADCAST_THRESHOLD,
                      ADAPTIVE_MAX_SKEW_SLICES, ADAPTIVE_SKEW_FACTOR,
                      ADAPTIVE_SKEW_THRESHOLD_BYTES,
                      ADAPTIVE_TARGET_PARTITION_BYTES)
from ..exec.coalesce import TpuCoalesceBatchesExec
from ..exec.exchange import TpuShuffleExchangeExec
from ..exec.joins import (TpuBroadcastHashJoinExec, TpuHashJoinExec,
                          TpuShuffledHashJoinExec)
from ..telemetry.events import emit_event
from .executor import MaterializedStageExec
from .stats import coalesce_groups, split_partition_segments

log = logging.getLogger(__name__)

#: join types a broadcast/skew rewrite may touch: the stream side must
#: be row-local (each stream row's output independent of its partition)
_REWRITABLE_JOINS = TpuHashJoinExec._STREAM_SPLITTABLE


def _through_coalesce(node):
    """Strip TpuCoalesceBatchesExec wrappers; returns (core, rewrap)
    where ``rewrap(new_core)`` rebuilds the wrapper chain on top of a
    replacement core (non-mutating — every wrapper is copied)."""
    wrappers = []
    while isinstance(node, TpuCoalesceBatchesExec):
        wrappers.append(node)
        node = node.children[0]

    def rewrap(core):
        for w in reversed(wrappers):
            core = w.with_new_children([core])
        return core

    return node, rewrap


def _identity_stage(node) -> Optional[MaterializedStageExec]:
    """The node (through coalesce wrappers) as a not-yet-regrouped
    materialized stage, else None."""
    core, _ = _through_coalesce(node)
    if isinstance(core, MaterializedStageExec) and core.is_identity():
        return core
    return None


class AdaptivePlanner:
    """Applies the three rewrites to a plan whose deepest exchanges
    have been replaced by :class:`MaterializedStageExec` nodes."""

    def __init__(self, ctx):
        self.ctx = ctx
        conf = ctx.conf
        self.broadcast_threshold = conf.get(
            ADAPTIVE_AUTO_BROADCAST_THRESHOLD)
        self.target_partition_bytes = conf.get(
            ADAPTIVE_TARGET_PARTITION_BYTES)
        self.skew_factor = conf.get(ADAPTIVE_SKEW_FACTOR)
        self.skew_threshold_bytes = conf.get(
            ADAPTIVE_SKEW_THRESHOLD_BYTES)
        self.max_skew_slices = max(2, conf.get(ADAPTIVE_MAX_SKEW_SLICES))
        self.n_rewrites = 0

    def _bump(self, metric: str, delta: int = 1) -> None:
        self.ctx.metrics[metric].add(delta)
        self.n_rewrites += 1

    # ------------------------------------------------------------------
    def rewrite(self, plan):
        plan = self.rewrite_broadcast(plan)
        plan = self.rewrite_skew(plan)
        plan = self.rewrite_coalesce(plan)
        return plan

    # ------------------------------------------------------------------
    def rewrite_broadcast(self, plan):
        """Demote a shuffled-hash join to broadcast when the
        MATERIALIZED build side landed under the runtime threshold and
        the stream-side exchange has not executed yet — the stream
        exchange is dropped from the plan entirely."""
        new_children = [self.rewrite_broadcast(c) for c in plan.children]
        if any(n is not o for n, o in zip(new_children, plan.children)):
            plan = plan.with_new_children(new_children)
        if not isinstance(plan, TpuShuffledHashJoinExec):
            return plan
        if plan.how not in _REWRITABLE_JOINS:
            return plan
        if self.broadcast_threshold <= 0:
            return plan
        session = getattr(self.ctx, "session", None)
        if session is None or \
                getattr(session, "broadcast_registry", None) is None:
            return plan
        build = _identity_stage(plan.children[1])
        if build is None or build.stats is None:
            return plan
        stream_core, _ = _through_coalesce(plan.children[0])
        if not isinstance(stream_core, TpuShuffleExchangeExec):
            return plan  # stream already executed — nothing to skip
        observed = build.stats.total_bytes
        if observed > self.broadcast_threshold:
            return plan
        # stream side: keep the exchange's OWN subtree (including its
        # input-coalesce goal) and re-target the join-side TargetSize
        # wrapper(s) at it — the broadcast join declares the same
        # stream goal the shuffled join did
        _, rewrap_stream = _through_coalesce(plan.children[0])
        new_stream = rewrap_stream(stream_core.children[0])
        converted = TpuBroadcastHashJoinExec(
            new_stream, plan.children[1], plan.plan)
        emit_event("aqe_broadcast_join",
                   how=plan.how,
                   build_exchange=build.stats.exchange_id,
                   observed_bytes=observed,
                   threshold_bytes=int(self.broadcast_threshold))
        self._bump("aqe.numJoinsConverted")
        log.info("AQE: converted %s to broadcast (build side %dB <= "
                 "%dB), skipping the stream exchange", plan.describe(),
                 observed, self.broadcast_threshold)
        return converted

    # ------------------------------------------------------------------
    def _skewed_partitions(self, obs) -> Tuple[List[int], int]:
        import numpy as np

        rows = obs.part_rows
        med = max(int(np.median(rows)), 1)
        skewed = [p for p in range(obs.n_out)
                  if int(rows[p]) > self.skew_factor * med
                  and obs.bytes_for(p) > self.skew_threshold_bytes]
        return skewed, med

    def rewrite_skew(self, plan):
        """Split a skewed stream-side partition of a co-partitioned
        join into contiguous row slices, each replicated against the
        full matching build-side partition."""
        new_children = [self.rewrite_skew(c) for c in plan.children]
        if any(n is not o for n, o in zip(new_children, plan.children)):
            plan = plan.with_new_children(new_children)
        if not isinstance(plan, TpuShuffledHashJoinExec):
            return plan
        if plan.how not in _REWRITABLE_JOINS:
            return plan
        stream = _identity_stage(plan.children[0])
        build = _identity_stage(plan.children[1])
        if stream is None or build is None:
            return plan
        obs = stream.stats
        if obs is None or not obs.device_path \
                or obs.item_counts is None or obs.n_out <= 1:
            return plan
        skewed, med = self._skewed_partitions(obs)
        if not skewed:
            return plan
        stream_specs: List[tuple] = []
        build_specs: List[tuple] = []
        n_slices_total = 0
        for p in range(obs.n_out):
            if p not in skewed:
                stream_specs.append(("parts", (p,)))
                build_specs.append(("parts", (p,)))
                continue
            rows_p = obs.rows_for(p)
            k = min(self.max_skew_slices,
                    max(2, -(-rows_p // med)))  # ceil div
            slices = split_partition_segments(obs.item_counts, p, k)
            if len(slices) <= 1:  # degenerate: keep the partition
                stream_specs.append(("parts", (p,)))
                build_specs.append(("parts", (p,)))
                continue
            for segs in slices:
                stream_specs.append(("slice", p, tuple(segs)))
                build_specs.append(("parts", (p,)))
            n_slices_total += len(slices)
            emit_event("aqe_skew_split",
                       exchange=obs.exchange_id, partition=p,
                       rows=rows_p, median_rows=med,
                       slices=len(slices))
        if not n_slices_total:
            return plan
        _, rewrap_l = _through_coalesce(plan.children[0])
        _, rewrap_r = _through_coalesce(plan.children[1])
        note = f"skew split {len(skewed)} -> {n_slices_total} slices"
        new_join = plan.with_new_children([
            rewrap_l(stream.with_specs(stream_specs, note=note)),
            rewrap_r(build.with_specs(
                build_specs, note=f"build replicas for {note}"))])
        self._bump("aqe.numSkewSplits", len(skewed))
        log.info("AQE: %s on %s", note, plan.describe())
        return new_join

    # ------------------------------------------------------------------
    def _stage_groups(self, part_bytes) -> Optional[List[tuple]]:
        groups = coalesce_groups(part_bytes,
                                 int(self.target_partition_bytes))
        if len(groups) >= len(part_bytes):
            return None  # nothing to merge
        return groups

    def rewrite_coalesce(self, plan):
        """Merge adjacent small post-shuffle partitions up to the
        target.  Join children coalesce as a PAIR with the identical
        grouping (the shuffled join asserts co-partitioning); any other
        materialized stage coalesces on its own histogram."""
        # pass 1: join pairs (and remember their stages so pass 2
        # leaves them alone)
        joint_handled = set()

        def visit(node):
            new_children = [visit(c) for c in node.children]
            if any(n is not o for n, o in
                   zip(new_children, node.children)):
                node = node.with_new_children(new_children)
            if isinstance(node, TpuShuffledHashJoinExec):
                l_stage = _identity_stage(node.children[0])
                r_stage = _identity_stage(node.children[1])
                if l_stage is not None and r_stage is not None:
                    joint_handled.add(id(l_stage))
                    joint_handled.add(id(r_stage))
                    node = self._coalesce_join(node, l_stage, r_stage)
                elif l_stage is not None or r_stage is not None:
                    # one side still unexecuted: regrouping the ready
                    # side alone would break the co-partition contract
                    joint_handled.add(id(l_stage or r_stage))
            return node

        plan = visit(plan)
        return self._coalesce_standalone(plan, joint_handled)

    def _coalesce_join(self, join, l_stage, r_stage):
        lo, ro = l_stage.stats, r_stage.stats
        if lo is None or ro is None or not lo.has_partition_rows \
                or not ro.has_partition_rows or lo.n_out != ro.n_out \
                or lo.n_out <= 1:
            return join
        combined = [lo.bytes_for(p) + ro.bytes_for(p)
                    for p in range(lo.n_out)]
        groups = self._stage_groups(combined)
        if groups is None:
            return join
        specs = [("parts", g) for g in groups]
        note = f"coalesced {lo.n_out} -> {len(groups)}"
        _, rewrap_l = _through_coalesce(join.children[0])
        _, rewrap_r = _through_coalesce(join.children[1])
        emit_event("aqe_coalesce_partitions",
                   exchanges=[lo.exchange_id, ro.exchange_id],
                   before=lo.n_out, after=len(groups),
                   target_bytes=int(self.target_partition_bytes))
        self._bump("aqe.numPartitionsCoalesced", lo.n_out - len(groups))
        log.info("AQE: %s on both sides of %s", note, join.describe())
        return join.with_new_children([
            rewrap_l(l_stage.with_specs(specs, note=note)),
            rewrap_r(r_stage.with_specs(specs, note=note))])

    def _coalesce_standalone(self, plan, joint_handled):
        def visit(node):
            new_children = [visit(c) for c in node.children]
            if any(n is not o for n, o in
                   zip(new_children, node.children)):
                node = node.with_new_children(new_children)
            if isinstance(node, MaterializedStageExec) \
                    and id(node) not in joint_handled \
                    and node.is_identity():
                regrouped = self._coalesce_one(node)
                if regrouped is not None:
                    node = regrouped
            return node

        return visit(plan)

    def _coalesce_one(self, stage):
        obs = stage.stats
        if obs is None or not obs.has_partition_rows or obs.n_out <= 1:
            return None
        groups = self._stage_groups(
            [obs.bytes_for(p) for p in range(obs.n_out)])
        if groups is None:
            return None
        note = f"coalesced {obs.n_out} -> {len(groups)}"
        emit_event("aqe_coalesce_partitions",
                   exchanges=[obs.exchange_id],
                   before=obs.n_out, after=len(groups),
                   target_bytes=int(self.target_partition_bytes))
        self._bump("aqe.numPartitionsCoalesced",
                   obs.n_out - len(groups))
        log.info("AQE: %s on %s", note, obs.name)
        return stage.with_specs([("parts", g) for g in groups],
                                note=note)
