"""Device write command.

Reference analogue: GpuDataWritingCommandExec + GpuFileFormatWriter
(rule at GpuOverrides.scala:1568-1580 with the meta at :260-314
rejecting bucketed and non-parquet/orc output;
GpuFileFormatWriter.scala:340 sort-for-dynamic-partitioning;
GpuFileFormatDataWriter.scala:417 single + dynamic partition writers;
BasicColumnarWriteStatsTracker).

The write command goes through the rewrite engine like any other
operator: tagged, visible in explain (``*``/``!``), and converted to
this device exec.  Dynamic-partition output is sorted by the partition
keys ON DEVICE (one lexsort + gather per input partition — the
reference sorts for the dynamic writer exactly here), downloaded in ONE
transfer, and split at group boundaries found vectorized on the host
(no per-row Python; r4's host writer built a python tuple per row).
The arrow encode itself stays host-side by design — the same split the
scans use (SURVEY §7: device owns compute/ordering, host owns codec).
"""
from __future__ import annotations

import os
import threading

from .. import types as T
from ..data.column import DeviceBatch, device_to_host
from ..ops.kernels import segment as seg
from ..ops.kernels.gather import gather_batch
from ..utils import metrics as M
from ..utils.tracing import trace_range
from ..io.scans import partition_dir_name
from .base import DevicePartitionedData, TpuExec
from .coalesce import concat_device_batches


class TpuDataWritingCommandExec(TpuExec):
    """Consumes the device child, produces zero rows; file IO happens
    when the (empty) output partitions are drained so writes stream
    per-partition like every other exec."""

    def __init__(self, child, plan):
        super().__init__([child])
        self.plan = plan  # physical.DataWritingCommandExec
        from .kernel_cache import jit_kernel

        self._sort_kernel = jit_kernel(self._sort_by_keys,
                                       kind="write")

    @property
    def schema(self):
        return T.Schema([])

    def _key_idx(self):
        child_schema = self.children[0].schema
        return [child_schema.index_of(k)
                for k in self.plan.partition_by]

    def _sort_by_keys(self, b: DeviceBatch) -> DeviceBatch:
        cols = [b.columns[i] for i in self._key_idx()]
        order = seg.lexsort_device(cols, pad_valid=b.row_mask())
        return gather_batch(b, order, b.num_rows)

    # ------------------------------------------------------------------
    def execute_columnar(self, ctx) -> DevicePartitionedData:
        from ..io import writers

        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)
        sem = self._sem(ctx)
        plan = self.plan
        tracker = writers.WriteStatsTracker()
        if ctx is not None and getattr(ctx, "session", None) is not None:
            ctx.session.last_write_stats = tracker
        os.makedirs(plan.path, exist_ok=True)
        ext = {"parquet": "parquet", "orc": "orc"}[plan.fmt]
        n_parts = child.n_partitions
        # _SUCCESS only lands after EVERY partition committed (the
        # reference's driver-side job commit); partitions may drain
        # concurrently, hence the counter
        barrier = {"left": n_parts}
        lock = threading.Lock()

        def finish_one():
            with lock:
                barrier["left"] -= 1
                if barrier["left"] == 0:
                    with open(os.path.join(plan.path, "_SUCCESS"), "w"):
                        pass

        def make(pid):
            def it():
                with trace_range("TpuWrite",
                                 self.metrics[M.TOTAL_TIME]):
                    batches = list(child.iterator(pid))
                    if batches:
                        b = concat_device_batches(batches) \
                            if len(batches) > 1 else batches[0]
                        if plan.partition_by:
                            self._write_dynamic(b, pid, ext, tracker,
                                                sem)
                        else:
                            hb = device_to_host(b)
                            if sem:
                                sem.release_if_necessary()
                            fname = os.path.join(
                                plan.path, f"part-{pid:05d}.{ext}")
                            writers._write_one([hb], hb.schema,
                                               plan.fmt, fname,
                                               plan.options, tracker)
                            self.metrics[M.NUM_OUTPUT_ROWS].add(
                                hb.num_rows)
                    elif sem:
                        sem.release_if_necessary()
                finish_one()
                return
                yield  # noqa: unreachable — makes this a generator

            return it

        return DevicePartitionedData([make(i) for i in range(n_parts)])

    # ------------------------------------------------------------------
    def _write_dynamic(self, b: DeviceBatch, pid: int, ext: str,
                       tracker, sem) -> None:
        """Device sort by partition keys, ONE download, vectorized
        boundary split, per-directory encode."""
        import numpy as np

        from ..io import writers

        plan = self.plan
        key_idx = self._key_idx()
        hb = device_to_host(self._sort_kernel(b))
        if sem:
            sem.release_if_necessary()
        n = hb.num_rows
        if n == 0:
            return
        child_schema = hb.schema
        keep_idx = [i for i in range(len(child_schema))
                    if i not in key_idx]
        out_schema = T.Schema([child_schema.fields[i] for i in keep_idx])
        # neighbor-difference over the sorted keys -> group starts.
        # NaN compares equal to NaN here: every NaN row maps to the same
        # k=nan directory, so splitting them would overwrite one file
        # per row (losing all but the last).
        neq = np.zeros(max(n - 1, 0), dtype=bool)
        for i in key_idx:
            c = hb.columns[i]
            vals = c.data
            valid = c.is_valid()
            both = valid[1:] & valid[:-1]
            dv = np.not_equal(vals[1:], vals[:-1])
            if np.issubdtype(vals.dtype, np.floating):
                dv &= ~(np.isnan(vals[1:]) & np.isnan(vals[:-1]))
            neq |= (valid[1:] != valid[:-1]) | (both & dv)
        starts = np.concatenate(
            [[0], np.flatnonzero(neq) + 1, [n]]).astype(np.int64)
        for s, e in zip(starts[:-1], starts[1:]):
            sub = hb.slice(int(s), int(e))
            parts = []
            for k, i in zip(plan.partition_by, key_idx):
                c = sub.columns[i]
                v = c.data[0] if (c.validity is None
                                  or bool(c.validity[0])) else None
                parts.append(partition_dir_name(k, v))
            out = writers.HostBatch(
                out_schema, [sub.columns[i] for i in keep_idx])
            dirname = os.path.join(plan.path, *parts)
            os.makedirs(dirname, exist_ok=True)
            writers._write_one(
                [out], out_schema, plan.fmt,
                os.path.join(dirname, f"part-{pid:05d}.{ext}"),
                plan.options, tracker)
            self.metrics[M.NUM_OUTPUT_ROWS].add(int(e - s))

    def describe(self):
        part = f", partition_by={self.plan.partition_by}" \
            if self.plan.partition_by else ""
        return f"TpuDataWritingCommand[{self.plan.fmt}{part}]"


# ==========================================================================
# rule registration
# ==========================================================================
def register(register_exec):
    from ..plan import physical as P

    def tag(meta):
        plan = meta.plan
        if plan.fmt not in ("parquet", "orc"):
            # reference meta rejects CSV/JSON/text output
            # (GpuOverrides.scala:260-314)
            meta.will_not_work_on_tpu(
                f"output format {plan.fmt} is not supported on TPU "
                "(parquet/orc only, like the reference)")
        if getattr(plan, "bucket_by", None):
            meta.will_not_work_on_tpu(
                "bucketed output is not supported "
                "(reference: GpuOverrides.scala:260-314)")
        child_schema = plan.children[0].schema
        for k in plan.partition_by:
            try:
                f = child_schema.fields[child_schema.index_of(k)]
            except (KeyError, ValueError):
                meta.will_not_work_on_tpu(
                    f"partition column {k} not found in input")
                continue
            if not T.is_supported_type(f.dtype):
                meta.will_not_work_on_tpu(
                    f"partition column {k} has unsupported type "
                    f"{f.dtype}")

    register_exec(
        P.DataWritingCommandExec,
        convert=lambda meta, ch: TpuDataWritingCommandExec(
            ch[0], meta.plan),
        desc="device write command (parquet/orc, dynamic partitions "
             "sorted on device)",
        tag=tag)
