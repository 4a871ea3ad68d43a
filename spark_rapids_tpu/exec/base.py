"""TPU exec base.

Reference analogue: GpuExec.scala — ``supportsColumnar``, the standard
metric set, per-exec coalesce goals.  A TpuExec executes to device
partitions (``DevicePartitionedData`` of DeviceBatches in HBM); its
row-oriented ``execute`` is only reachable through a DeviceToHostExec
transition inserted by the rewrite engine.

Each exec compiles ONE jitted kernel; jax's compile cache keys on the
(schema, row-bucket) shapes, so batches sharing a bucket reuse the
executable — the static-shape answer to cudf's dynamic kernels.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence

from .. import types as T
from ..data.column import DeviceBatch
from ..plan.physical import ExecContext, PhysicalPlan
from ..utils import metrics as M


# --------------------------------------------------------------------------
# Coalesce goals (reference: CoalesceGoal lattice, GpuCoalesceBatches.scala)
# --------------------------------------------------------------------------
class CoalesceGoal:
    def max_with(self, other: "CoalesceGoal") -> "CoalesceGoal":
        if isinstance(self, RequireSingleBatch) or \
                isinstance(other, RequireSingleBatch):
            return RequireSingleBatch()
        if isinstance(self, TargetSize) and isinstance(other, TargetSize):
            if self.target is None:
                return self
            if other.target is None:
                return other
            return self if self.target >= other.target else other
        if isinstance(self, TargetRows) and isinstance(other, TargetRows):
            if self.rows is None:
                return self
            if other.rows is None:
                return other
            return self if self.rows >= other.rows else other
        return self


class TargetSize(CoalesceGoal):
    """``target=None`` means "use the session's batchSizeBytes" — the goal
    declared by out-of-core operators that chunk their input (reference:
    TargetSize(conf.gpuTargetBatchSizeBytes))."""

    def __init__(self, target: Optional[int] = None):
        self.target = target

    def __repr__(self):  # pragma: no cover
        return f"TargetSize({self.target})"


class TargetRows(CoalesceGoal):
    """Row-count coalesce goal (``rows=None`` resolves the session's
    ``shuffle.targetBatchRows`` at execute time) — declared by the
    shuffle exchange so a stream of tiny scan batches is merged before
    the per-batch partition-build kernel dispatches; zero disables."""

    def __init__(self, rows: Optional[int] = None):
        self.rows = rows

    def __repr__(self):  # pragma: no cover
        return f"TargetRows({self.rows})"


class RequireSingleBatch(CoalesceGoal):
    def __repr__(self):  # pragma: no cover
        return "RequireSingleBatch"


class DevicePartitionedData:
    def __init__(self, parts: List[Callable[[], Iterator[DeviceBatch]]]):
        self.parts = parts

    @property
    def n_partitions(self):
        return len(self.parts)

    def iterator(self, pid: int) -> Iterator[DeviceBatch]:
        from ..ops import miscexprs

        miscexprs.context.partition_id = pid
        miscexprs.context.row_offset = 0
        return self.parts[pid]()


class _SchemaStub:
    """Stands in for a child subtree on a kernel twin (kernel_twin):
    compute bodies may read ``children[i].schema`` while tracing, but a
    cached kernel must never retain the live child exec."""

    __slots__ = ("schema",)

    def __init__(self, schema):
        self.schema = schema


class TpuExec(PhysicalPlan):
    """Base of all device operators."""

    #: the operator's name on both clocks: its host span
    #: (``trace_range``) and its scope inside a program composed of
    #: several operators' bodies (``device_phase``); None: the class's
    SPAN: Optional[str] = None

    #: what every lowering of the operator reads of it (its own kernels
    #: and the mesh's ``_lower_op``): kinds, bound expressions, schemas,
    #: said once in ``__init__``.  Its kernels are keyed by it and the
    #: mesh's stage programs are built from it; None: cannot be shared
    signature: Optional[tuple] = None

    def __init__(self, children: Sequence[PhysicalPlan] = ()):  # noqa
        super().__init__(children)
        self.metrics = {}

    @property
    def span_name(self) -> str:
        return self.SPAN or type(self).__name__

    # standard metric names (reference: GpuMetricNames)
    def _init_metrics(self, ctx: ExecContext):
        reg = ctx.metrics
        prefix = f"{self.name}."
        self.metrics = {
            M.NUM_OUTPUT_ROWS: reg.metric(prefix + M.NUM_OUTPUT_ROWS),
            M.NUM_OUTPUT_BATCHES: reg.metric(prefix + M.NUM_OUTPUT_BATCHES),
            M.TOTAL_TIME: reg.metric(prefix + M.TOTAL_TIME, "ns"),
            M.PEAK_DEVICE_MEMORY: reg.metric(
                prefix + M.PEAK_DEVICE_MEMORY, "max"),
            # compile-inclusive wall of first-shape dispatches, fed by
            # the KernelCache when this exec's dispatch compiled
            M.COMPILE_TIME: reg.metric(prefix + M.COMPILE_TIME, "ns"),
        }
        # telemetry: one exec-kind span per physical exec name, plus the
        # deviceSyncTime metric the transitions feed — both exist ONLY
        # while a query telemetry is active, so the disabled snapshot
        # stays byte-identical to the un-instrumented engine
        from ..telemetry import spans as tspans

        if tspans.current() is not None:
            self.metrics[M.DEVICE_SYNC_TIME] = reg.metric(
                prefix + M.DEVICE_SYNC_TIME, "ns")
            tspans.register_exec(self)

    def kernel_twin(self) -> "TpuExec":
        """A children-detached shallow copy for KernelCache registration.

        A registered cache entry outlives the query — that is the point
        of cross-query kernel sharing — so a kernel bound to ``self``
        would pin the whole plan subtree (and anything the subtree
        finalizes on collection, e.g. HostToDeviceExec's cached upload
        buffers) for the life of the process.  The twin keeps the
        expression/schema state the compute body needs, swaps each
        child for a schema-only stub and leaves off ``plan``, the host
        plan node (it holds the host subtree; no body reads it).
        """
        import copy

        twin = copy.copy(self)
        twin.children = [_SchemaStub(c.schema) for c in self.children]
        vars(twin).pop("plan", None)
        return twin

    @property
    def supports_columnar(self) -> bool:
        return True

    # goals the exec imposes on each child's batches
    @property
    def children_coalesce_goal(self) -> List[CoalesceGoal]:
        return [None] * len(self.children)

    # goal describing this exec's own output batching
    @property
    def coalesce_after(self) -> bool:
        """True if output batches may be tiny and benefit from coalescing
        above (reference: GpuExec.coalesceAfter)."""
        return False

    def execute_columnar(self, ctx: ExecContext) -> DevicePartitionedData:
        raise NotImplementedError(f"{self.name}.execute_columnar")

    def execute(self, ctx: ExecContext):
        """Row path is reached only through transitions — mirror of the
        reference's GpuExec.doExecute throwing."""
        raise RuntimeError(
            f"{self.name} does not support host execution; a "
            "DeviceToHostExec transition should have been inserted")

    def _sem(self, ctx: ExecContext):
        dm = ctx.session.device_manager if ctx.session else None
        return dm.semaphore if dm else None
