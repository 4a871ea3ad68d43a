"""Whole-stage fused segment exec.

Reference analogue: the per-operator kernel-dispatch overhead named by
"Data Path Fusion in GPU for Analytical Query Processing" (PAPERS.md)
— every row-local exec used to compile and dispatch its own jitted
kernel per batch, materializing an intermediate DeviceBatch in HBM
between operators.  ``TpuFusedSegmentExec`` replaces a maximal chain of
row-local execs (built by plan/fusion.py) with ONE exec whose single
jitted kernel composes the member compute bodies:

* **Project / Expand / Generate** members contribute their existing
  ``_compute`` bodies unchanged (Expand branches the segment into one
  stream per projection list; Generate repeats the carried mask k×).
* **Filter** members do NOT compact: the keep mask is threaded through
  the segment and the surviving streams compact ONCE at segment exit.
  Row-local deterministic expressions commute with the stable
  compaction, so results are bit-identical to the unfused plan — same
  rows, same order, same padded bucket.

A segment ends at the first consumer that is not row-local.  One such
consumer does not need the compaction at the exit: an update-phase
aggregate directly over a Filter/Project chain ABSORBS the members
(plan/fusion.py, exec/aggregate.py) and runs them through the same
composition, ``run_members``, reading the keep mask and compacting
nothing; no segment node is built for that chain.

The kernel is compiled through the shared KernelCache; when the fusion
pass proved the input batch single-consumer (fresh file-scan uploads),
the input's buffers are donated to the kernel on backends that honor
donation.
"""
from __future__ import annotations

from typing import List

from ..data.column import DeviceBatch
from ..ops.kernels.gather import compact
from ..utils import metrics as M
from ..utils.tracing import device_phase, trace_range
from .base import DevicePartitionedData, TpuExec
from .basic import TpuExpandExec, TpuFilterExec, TpuProjectExec
from .generate import TpuGenerateExec
from .kernel_cache import jit_kernel, schema_signature


def members_signature(members) -> tuple:
    """A chain of row-local members as their own signatures say them:
    what a body composing them (``run_members``) reads."""
    return tuple((type(m).__name__, m.signature) for m in members)


def _apply_member(m, streams):
    """Advance every (batch, keep-mask) stream through member ``m``
    (trace-time composition; mask=None means 'nothing filtered')."""
    import jax.numpy as jnp

    out = []
    for b, keep in streams:
        if isinstance(m, TpuFilterExec):
            k = m._keep(b)
            out.append((b, k if keep is None else keep & k))
        elif isinstance(m, TpuExpandExec):
            out.extend((fn(b), keep) for fn in m._kernel_fns)
        elif isinstance(m, TpuGenerateExec):
            nb = m._compute(b)
            out.append((nb, None if keep is None
                        else jnp.repeat(keep, len(m.elements))))
        else:  # TpuProjectExec
            out.append((m._compute(b), keep))
    return out


def run_members(members, batch: DeviceBatch):
    """``batch`` through a bottom-up chain of row-local members with
    every filter's compaction deferred: the surviving (batch, keep-mask)
    streams.  The fused segment compacts each at its exit; an aggregate
    that absorbed the chain (exec/aggregate.py) reads the mask and
    compacts nothing.  Each member's ops stand under its own name, as
    its host span has it when it runs alone."""
    streams = [(batch, None)]
    for m in members:
        with device_phase(m.span_name):
            streams = _apply_member(m, streams)
    return streams


class TpuFusedSegmentExec(TpuExec):
    """One jitted kernel over a bottom-up chain of row-local members.

    ``members`` is in execution order (closest-to-source first);
    ``child`` is the segment input (the bottom member's child)."""

    SPAN = "TpuFusedSegment"

    def __init__(self, members: List[TpuExec], child, donate: bool = False):
        super().__init__([child])
        assert len(members) >= 2, "a segment fuses at least two execs"
        self.members = list(members)
        self._schema = self.members[-1].schema
        self.signature = (schema_signature(child.schema),
                          members_signature(self.members))
        self._kernel = jit_kernel(
            self.kernel_twin()._compute, key=("fused", self.signature),
            donate_argnums=(0,) if donate else ())

    def kernel_twin(self):
        # the members still carry their original children links (the
        # chain below the segment) — a cached fused kernel must not pin
        # that subtree either, so the twin detaches every member too
        twin = super().kernel_twin()
        twin.members = [m.kernel_twin() for m in self.members]
        return twin

    @property
    def schema(self):
        return self._schema

    @property
    def coalesce_after(self):
        # a filter/expand/generate anywhere in the segment can shrink
        # or fragment output batches exactly like the unfused member
        return any(m.coalesce_after for m in self.members)

    @property
    def children_coalesce_goal(self):
        return self.members[0].children_coalesce_goal

    # ---------------- the fused kernel body ----------------------------
    def _compute(self, batch: DeviceBatch):
        # ONE compaction per surviving stream at segment exit — the
        # deferred form of each member filter's compact()
        streams = run_members(self.members, batch)
        with device_phase(self.SPAN):
            return tuple(b if keep is None else compact(b, keep)
                         for b, keep in streams)

    # ---------------- execution ----------------------------------------
    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                for db in child.iterator(pid):
                    with trace_range(self.SPAN,
                                     self.metrics[M.TOTAL_TIME]):
                        outs = self._kernel(db, metrics=self.metrics)
                    for out in outs:
                        self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                        yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        inner = " -> ".join(m.describe() for m in self.members)
        return f"TpuFusedSegment[{len(self.members)}: {inner}]"
