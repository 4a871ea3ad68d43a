"""Basic device operators: Project, Filter, Union, Limit, Expand, Coalesce.

Reference analogue: basicPhysicalOperators.scala (GpuProjectExec:65,
GpuFilterExec:126, GpuUnionExec:179, GpuCoalesceExec:202), limit.scala,
GpuExpandExec.scala.
"""
from __future__ import annotations

from typing import List

from .. import types as T
from ..data.column import DeviceBatch
from ..ops.expression import Expression, as_device_column, bind_references, \
    output_name
from ..ops.kernels.gather import compact
from ..utils import metrics as M
from ..utils.tracing import trace_range
from .base import DevicePartitionedData, TpuExec
from .kernel_cache import expr_signature, jit_kernel, schema_signature


class TpuProjectExec(TpuExec):
    SPAN = "TpuProject"

    def __init__(self, child, exprs: List[Expression],
                 schema: T.Schema = None):
        super().__init__([child])
        self.exprs = [bind_references(e, child.schema) for e in exprs]
        if schema is None:
            schema = T.Schema([
                T.Field(output_name(raw, i), b.dtype, b.nullable)
                for i, (raw, b) in enumerate(zip(exprs, self.exprs))])
        self._schema = schema
        self.signature = (schema_signature(child.schema),
                          expr_signature(self.exprs),
                          schema_signature(schema))
        self._kernel = jit_kernel(self.kernel_twin()._compute,
                                  key=("project", self.signature))

    @property
    def schema(self):
        return self._schema

    def _compute(self, batch: DeviceBatch) -> DeviceBatch:
        cols = [as_device_column(e.eval_tpu(batch), batch.padded_rows)
                for e in self.exprs]
        # padding rows must stay invalid
        mask = batch.row_mask()
        cols = [type(c)(c.dtype, c.data, c.validity & mask, c.lengths)
                for c in cols]
        return DeviceBatch(self._schema, cols, batch.num_rows)

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                for db in child.iterator(pid):
                    with trace_range(self.SPAN,
                                     self.metrics[M.TOTAL_TIME]):
                        out = self._kernel(db, metrics=self.metrics)
                    self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                    yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"TpuProject[{', '.join(e.sql() for e in self.exprs)}]"


class TpuFilterExec(TpuExec):
    SPAN = "TpuFilter"

    def __init__(self, child, condition: Expression):
        super().__init__([child])
        self.condition = bind_references(condition, child.schema)
        self.signature = (schema_signature(child.schema),
                          expr_signature([self.condition]))
        self._kernel = jit_kernel(self.kernel_twin()._compute,
                                  key=("filter", self.signature))

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def coalesce_after(self):
        return True

    def _keep(self, batch: DeviceBatch):
        """The keep mask of ``condition`` over ``batch`` — shared with
        the fused-segment kernel, which threads the mask through the
        segment instead of compacting per filter."""
        c = as_device_column(self.condition.eval_tpu(batch),
                             batch.padded_rows)
        return c.data & c.validity

    def _compute(self, batch: DeviceBatch) -> DeviceBatch:
        return compact(batch, self._keep(batch))

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                for db in child.iterator(pid):
                    with trace_range(self.SPAN,
                                     self.metrics[M.TOTAL_TIME]):
                        out = self._kernel(db, metrics=self.metrics)
                    self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                    yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"TpuFilter[{self.condition.sql()}]"


class TpuUnionExec(TpuExec):
    def __init__(self, children):
        super().__init__(children)
        self.signature = tuple(schema_signature(c.schema)
                               for c in children)

    @property
    def schema(self):
        return self.children[0].schema

    def execute_columnar(self, ctx):
        parts = []
        for ch in self.children:
            data = ch.execute_columnar(ctx)
            parts.extend(data.parts)
        return DevicePartitionedData(parts)

    def describe(self):
        return "TpuUnion"


class TpuLocalLimitExec(TpuExec):
    def __init__(self, child, n: int):
        super().__init__([child])
        self.n = n
        self.signature = (schema_signature(child.schema), int(n))

    @property
    def schema(self):
        return self.children[0].schema

    def execute_columnar(self, ctx):
        import jax.numpy as jnp

        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                remaining = self.n
                for db in child.iterator(pid):
                    if remaining <= 0:
                        break
                    n_rows = int(db.num_rows)
                    if n_rows <= remaining:
                        remaining -= n_rows
                        yield db
                    else:
                        # shrink logical count; padded arrays unchanged,
                        # but rows past the limit must become padding
                        mask = jnp.arange(db.padded_rows,
                                          dtype=jnp.int32) < remaining
                        cols = [type(c)(c.dtype, c.data,
                                        c.validity & mask, c.lengths)
                                for c in db.columns]
                        yield DeviceBatch(db.schema, cols, remaining)
                        remaining = 0

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"TpuLocalLimit[{self.n}]"


class TpuGlobalLimitExec(TpuLocalLimitExec):
    def describe(self):
        return f"TpuGlobalLimit[{self.n}]"


class TpuExpandExec(TpuExec):
    """Reference analogue: GpuExpandExec — one projected batch per
    projection list per input batch."""

    SPAN = "TpuExpand"

    def __init__(self, child, projections: List[List[Expression]],
                 output_names: List[str]):
        super().__init__([child])
        self.projections = [[bind_references(e, child.schema) for e in ps]
                            for ps in projections]
        first = self.projections[0]
        self._schema = T.Schema([T.Field(n, b.dtype, True)
                                 for n, b in zip(output_names, first)])
        # raw bodies kept for the fused-segment / distributed lowering;
        # built on the kernel twin so neither the registered kernels nor
        # a fused segment holding _kernel_fns pins this exec's subtree
        twin = self.kernel_twin()
        self._kernel_fns = [twin._mk_kernel(ps) for ps in self.projections]
        self.signature = (schema_signature(child.schema),
                          tuple(expr_signature(ps)
                                for ps in self.projections),
                          schema_signature(self._schema))
        # a kernel a projection list, shared by its own list alone
        src, lists, out = self.signature
        self._kernels = [jit_kernel(fn, key=("expand", src, ps, out))
                         for fn, ps in zip(self._kernel_fns, lists)]

    @property
    def schema(self):
        return self._schema

    @property
    def coalesce_after(self):
        return True

    def _mk_kernel(self, ps):
        def compute(batch: DeviceBatch) -> DeviceBatch:
            mask = batch.row_mask()
            cols = []
            for f, e in zip(self._schema, ps):
                c = as_device_column(e.eval_tpu(batch), batch.padded_rows)
                if c.dtype != f.dtype and not f.dtype.is_string \
                        and not c.dtype.is_string:
                    c = type(c)(f.dtype, c.data.astype(f.dtype.jnp_dtype),
                                c.validity, c.lengths)
                cols.append(type(c)(c.dtype, c.data, c.validity & mask,
                                    c.lengths))
            return DeviceBatch(self._schema, cols, batch.num_rows)

        return compute

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                for db in child.iterator(pid):
                    for k in self._kernels:
                        with trace_range(self.SPAN,
                                         self.metrics[M.TOTAL_TIME]):
                            out = k(db, metrics=self.metrics)
                        yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return f"TpuExpand[{len(self.projections)} projections]"
