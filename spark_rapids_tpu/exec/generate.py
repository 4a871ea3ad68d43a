"""Device Generate (explode) exec.

Reference analogue: GpuGenerateExec (GpuGenerateExec.scala:101) — the
reference supports exactly explode of per-row literal-array patterns
(outer=false), which is the statically-shaped case: every input row
yields k output rows, so the exploded batch has padded_rows × k rows and
XLA compiles one fixed-shape kernel.  Row-major interleaving matches the
host engine's output order (row's k elements are consecutive).
"""
from __future__ import annotations

from typing import List

import numpy as np

from .. import types as T
from ..data.column import DeviceBatch, DeviceColumn
from ..ops.expression import Expression, as_device_column, bind_references
from ..utils import metrics as M
from ..utils.tracing import trace_range
from .base import DevicePartitionedData, TpuExec
from .kernel_cache import expr_signature, jit_kernel, schema_signature


class TpuGenerateExec(TpuExec):
    SPAN = "TpuGenerate"

    def __init__(self, child, plan):
        super().__init__([child])
        self.elements: List[Expression] = [
            bind_references(e, child.schema) for e in plan.elements]
        self.position = plan.position
        self._schema = plan_schema = plan.schema
        self._out_dtype = plan_schema.fields[-1].dtype
        self.signature = (schema_signature(child.schema),
                          expr_signature(self.elements),
                          bool(self.position), str(self._out_dtype),
                          schema_signature(plan_schema))
        self._kernel = jit_kernel(self.kernel_twin()._compute,
                                  key=("generate", self.signature))

    @property
    def schema(self):
        return self._schema

    @property
    def coalesce_after(self):
        return True

    def _compute(self, batch: DeviceBatch) -> DeviceBatch:
        import jax.numpy as jnp

        k = len(self.elements)
        p = batch.padded_rows
        mask = batch.row_mask()
        cols = []
        # pass-through columns: each input row repeated k times
        for c in batch.columns:
            cols.append(DeviceColumn(
                c.dtype,
                jnp.repeat(c.data, k, axis=0),
                jnp.repeat(c.validity & mask, k),
                jnp.repeat(c.lengths, k) if c.lengths is not None
                else None))
        if self.position:
            cols.append(DeviceColumn(
                T.INT32,
                jnp.tile(jnp.arange(k, dtype=jnp.int32), p),
                jnp.repeat(mask, k), None))
        # element columns evaluated per row, interleaved row-major
        elems = [as_device_column(e.eval_tpu(batch), p)
                 for e in self.elements]
        if self._out_dtype.id is T.TypeId.STRING:
            max_len = max(int(c.data.shape[1]) for c in elems)
            padded = [jnp.pad(c.data,
                              ((0, 0), (0, max_len - c.data.shape[1])))
                      for c in elems]
            data = jnp.stack(padded, axis=1).reshape(p * k, max_len)
            lengths = jnp.stack([c.lengths for c in elems],
                                axis=1).reshape(p * k)
        else:
            data = jnp.stack(
                [c.data.astype(self._out_dtype.jnp_dtype) for c in elems],
                axis=1).reshape(p * k)
            lengths = None
        validity = jnp.stack([c.validity for c in elems],
                             axis=1).reshape(p * k) & jnp.repeat(mask, k)
        cols.append(DeviceColumn(self._out_dtype, data, validity, lengths))
        # logical rows end at num_rows*k only when every logical row sits
        # before the padding — true here because repeat keeps row order
        return DeviceBatch(self._schema, cols, batch.num_rows * k)

    def execute_columnar(self, ctx):
        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)

        def make(pid):
            def it():
                for db in child.iterator(pid):
                    with trace_range(self.SPAN,
                                     self.metrics[M.TOTAL_TIME]):
                        out = self._kernel(db, metrics=self.metrics)
                    self.metrics[M.NUM_OUTPUT_ROWS].add(int(out.num_rows))
                    self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                    yield out

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child.n_partitions)])

    def describe(self):
        return (f"TpuGenerate[{len(self.elements)} elements"
                f"{', pos' if self.position else ''}]")


def register(register_exec):
    from ..plan import physical as P

    def tag(meta):
        # exploded row count must be static: every element expression
        # evaluates per input row (the reference's literal-array scope)
        for e in meta.plan.elements:
            if not e.deterministic:
                meta.will_not_work_on_tpu(
                    "nondeterministic explode elements")

    register_exec(
        P.GenerateExec,
        convert=lambda meta, ch: TpuGenerateExec(ch[0], meta.plan),
        desc="statically-shaped explode on device",
        tag=tag,
        exprs_of=lambda plan: list(plan.elements))
