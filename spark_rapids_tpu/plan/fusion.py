"""Whole-stage fusion: the post-planner physical rewrite.

Collapses maximal chains of row-local device execs into one
``TpuFusedSegmentExec`` (exec/fused.py) whose single jitted kernel
composes the member compute bodies — one XLA dispatch per batch per
segment instead of one per operator, and no intermediate DeviceBatch
materialized in HBM between members.

Runs inside ``TpuTransitionOverrides.apply`` AFTER transition
cancellation (a cancelled DeviceToHost/HostToDevice pair can join two
row-local chains) and BEFORE coalesce insertion (the segment inherits
the bottom member's child goal and the members' ``coalesce_after``, so
coalesce placement around the segment matches the unfused plan).

Segment boundaries — fusion stops at:
  * anything not row-local: exchanges, aggregates, sorts, joins,
    limits, unions, coalesces and transitions (they are simply not in
    the fusable set) — with ONE consumer that takes the chain in: an
    update-phase aggregate (see below);
  * nondeterministic expressions (rand(), partition-id/row-position
    dependent values change meaning when compaction is deferred);
  * ``fusion.maxSegmentExecs`` — a longer chain becomes several
    segments.

One consumer further — the aggregate absorbs the chain under it.  A
``partial`` / ``complete`` ``TpuHashAggregateExec`` whose child is a
``TpuFilterExec``, or a segment of Filter and Project members holding a
Filter, takes those members as a prologue of its own kernels
(exec/aggregate.py) and the chain's node leaves the plan: the aggregate
reads the filters' keep mask where it read the row mask, so the
compaction at the segment's exit, whose dense rows it never needed, is
not run at all.  Decided from the plan alone: every expression
deterministic, and no update that reads "the segment's first (last)
row" over a keyless aggregate, whose one segment is not sorted
(``first`` / ``last`` without ignore-nulls: the chain stays a node).
``final`` never absorbs (its input is buffer form).  ``describe()``
names what was absorbed; ``count_absorbed`` is the plan's
``fusion.filtersAbsorbed`` in ``Session.last_metrics``.
"""
from __future__ import annotations

from ..config import (FUSION_ENABLED, FUSION_MAX_SEGMENT_EXECS,
                      KERNEL_CACHE_DONATION, TpuConf)
from ..exec.aggregate import TpuHashAggregateExec
from ..exec.basic import TpuExpandExec, TpuFilterExec, TpuProjectExec
from ..exec.fused import TpuFusedSegmentExec
from ..exec.generate import TpuGenerateExec
from ..exec.transitions import HostToDeviceExec
from . import physical as P

#: the row-local execs whose compute bodies compose (ISSUE: Project,
#: Filter, Expand, Generate-where-row-local, adjacent projections)
_ROW_LOCAL = (TpuProjectExec, TpuFilterExec, TpuExpandExec,
              TpuGenerateExec)


def _member_exprs(node):
    if isinstance(node, TpuProjectExec):
        return node.exprs
    if isinstance(node, TpuFilterExec):
        return [node.condition]
    if isinstance(node, TpuExpandExec):
        return [e for ps in node.projections for e in ps]
    if isinstance(node, TpuGenerateExec):
        return node.elements
    return []


def count_absorbed(plan: P.PhysicalPlan) -> int:
    """How many aggregates of ``plan`` run an absorbed chain."""
    own = isinstance(plan, TpuHashAggregateExec) and bool(plan.absorbed)
    return int(own) + sum(count_absorbed(c) for c in plan.children)


class TpuFusionPass:
    def __init__(self, conf: TpuConf):
        self.enabled = bool(conf.get(FUSION_ENABLED))
        self.max_members = max(2, int(conf.get(FUSION_MAX_SEGMENT_EXECS)))
        self.donation = bool(conf.get(KERNEL_CACHE_DONATION))

    def apply(self, plan: P.PhysicalPlan) -> P.PhysicalPlan:
        if not self.enabled:
            return plan
        return self._rewrite(plan)

    # ------------------------------------------------------------------
    def _fusable(self, node) -> bool:
        return isinstance(node, _ROW_LOCAL) \
            and len(node.children) == 1 \
            and all(e.deterministic for e in _member_exprs(node))

    def _chain(self, top) -> list:
        """The maximal fusable chain that starts at ``top``, top first
        (at most ``maxSegmentExecs`` long)."""
        chain = []
        while len(chain) < self.max_members and self._fusable(top):
            chain.append(top)
            top = top.children[0]
        return chain

    def _rewrite(self, plan: P.PhysicalPlan) -> P.PhysicalPlan:
        chain = self._chain(plan)
        if len(chain) >= 2:
            child = self._rewrite(chain[-1].children[0])
            return TpuFusedSegmentExec(
                list(reversed(chain)), child,
                donate=self.donation and self._single_consumer(child))
        if isinstance(plan, TpuHashAggregateExec):
            members = list(reversed(self._chain(plan.children[0])))
            if self._absorbable(plan, members):
                return TpuHashAggregateExec(
                    self._rewrite(members[0].children[0]), plan,
                    absorbed=members)
        children = [self._rewrite(c) for c in plan.children]
        if children != list(plan.children):
            plan = plan.with_new_children(children)
        return plan

    @staticmethod
    def _absorbable(agg: TpuHashAggregateExec, members) -> bool:
        """Whether ``agg`` may run the chain directly under it
        (``members``, execution order) as its prologue: the rule in the
        module docstring."""
        if agg.mode == "final" or not all(
                isinstance(m, (TpuFilterExec, TpuProjectExec))
                for m in members) or not any(
                isinstance(m, TpuFilterExec) for m in members):
            return False
        funcs = [sp.func for sp in agg.specs]
        reads_rows = list(agg.keys) + \
            [f.child for f in funcs if f.child is not None]
        if not all(e.deterministic for e in reads_rows):
            return False
        # a keyless segment is not sorted: its first (last) ROW may be
        # one the filters dropped
        return bool(agg.keys) or not any(
            op.endswith("_any") for f in funcs for op, _ in f.updates)

    # ------------------------------------------------------------------
    @staticmethod
    def _single_consumer(child) -> bool:
        """Donation safety: the segment may donate its input buffers
        only when the producer builds a FRESH batch per drain.  File
        scans upload fresh every execution; LocalScan uploads are
        cached on the exec and spill-registered (exec/transitions.py),
        so a donated buffer would corrupt the next collect.  Everything
        else (exchange reads, coalesce pass-through of catalog-held
        batches) may retain references — stay conservative."""
        return isinstance(child, HostToDeviceExec) and \
            not isinstance(child.children[0], P.LocalScanExec)
