"""Exchange partitioning strategies.

Capability parity with the reference's four GPU partitioners (SURVEY §2.8):
  * HashPartitioning      (GpuHashPartitioning.scala — cudf murmur3 kernel)
  * RangePartitioning     (GpuRangePartitioning.scala + GpuRangePartitioner
                           reservoir-sample sketch + bounds)
  * RoundRobinPartitioning(GpuRoundRobinPartitioning.scala)
  * SinglePartitioning    (GpuSinglePartitioning.scala)

Hash partitioning uses the Spark-compatible murmur3 (utils/hashing.py) on
both engines, so row placement is bit-identical to the host oracle — the
same property the reference gets from cudf's spark-murmur3.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from .. import types as T
from ..data.column import HostBatch, HostColumn
from ..ops.expression import Expression, as_host_column, bind_references
from ..ops.kernels import segment as seg
from ..utils import hashing


class Partitioning:
    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions

    def bind(self, schema: T.Schema) -> "Partitioning":
        return self

    def prepare(self, child_data, schema: T.Schema) -> None:
        """Hook run once before partitioning (range sampling)."""

    def partition_ids(self, batch: HostBatch) -> np.ndarray:
        raise NotImplementedError

    def describe(self) -> str:
        return f"{type(self).__name__}({self.num_partitions})"

    def signature(self):
        """What a mesh stage's lowering reads of the partitioning (its
        kind, its keys as they print), for the stage program's key;
        None: a partitioning that cannot say it, whose stage is
        compiled for its request."""
        return None


class SinglePartitioning(Partitioning):
    def __init__(self):
        super().__init__(1)

    def partition_ids(self, batch):
        return np.zeros(batch.num_rows, dtype=np.int32)

    def signature(self):
        return (type(self).__name__,)


class RoundRobinPartitioning(Partitioning):
    def __init__(self, num_partitions: int):
        super().__init__(num_partitions)
        self._next = 0

    def partition_ids(self, batch):
        n = batch.num_rows
        start = self._next
        self._next = (start + n) % self.num_partitions
        return ((start + np.arange(n)) % self.num_partitions).astype(
            np.int32)

    def signature(self):
        return (type(self).__name__,)


class HashPartitioning(Partitioning):
    def __init__(self, keys: List[Expression], num_partitions: int):
        super().__init__(num_partitions)
        self.keys = keys
        self._bound: Optional[List[Expression]] = None

    def bind(self, schema):
        self._bound = [bind_references(k, schema) for k in self.keys]
        return self

    def key_columns(self, batch: HostBatch) -> List[HostColumn]:
        assert self._bound is not None, "partitioning not bound"
        return [as_host_column(k.eval_cpu(batch), batch.num_rows)
                for k in self._bound]

    def partition_ids(self, batch):
        cols = self.key_columns(batch)
        h = hashing.hash_batch_np(cols)
        return hashing.pmod(h, self.num_partitions)

    def describe(self):
        return (f"HashPartitioning([{', '.join(k.sql() for k in self.keys)}]"
                f", {self.num_partitions})")

    def signature(self):
        # the keys bind against a schema the operator's signature holds
        return ("hash", tuple(k.sql() for k in self.keys))


class RangePartitioning(Partitioning):
    """Reservoir-sample the child to pick split bounds, then place rows by
    binary search (reference: GpuRangePartitioner.scala:33-104 +
    SamplingUtils.scala)."""

    SAMPLE_SIZE_PER_PARTITION = 1000

    def __init__(self, sort_keys, num_partitions: int, seed: int = 42):
        super().__init__(num_partitions)
        self.sort_keys = sort_keys  # List[functions.SortKey]
        self.seed = seed
        self._bound_keys = None
        self._bounds_batch: Optional[HostBatch] = None

    def bind(self, schema):
        from ..plan import functions as F

        self._bound_keys = [
            F.SortKey(bind_references(k.expr, schema), k.ascending,
                      k.nulls_first)
            for k in self.sort_keys]
        return self

    def prepare(self, child_data, schema):
        """Sample key columns across partitions and compute bounds."""
        assert self._bound_keys is not None
        rng = np.random.default_rng(self.seed)
        target = self.SAMPLE_SIZE_PER_PARTITION * self.num_partitions
        sampled: List[HostBatch] = []
        total = 0
        for pid in range(child_data.n_partitions):
            for batch in child_data.iterator(pid):
                if batch.num_rows == 0:
                    continue
                key_cols = [as_host_column(k.expr.eval_cpu(batch),
                                           batch.num_rows)
                            for k in self._bound_keys]
                kb = HostBatch(
                    T.Schema([T.Field(f"k{i}", c.dtype, True)
                              for i, c in enumerate(key_cols)]), key_cols)
                take = min(batch.num_rows,
                           max(1, target // max(child_data.n_partitions, 1)))
                idx = rng.choice(batch.num_rows, size=take,
                                 replace=batch.num_rows < take)
                sampled.append(kb.take(np.sort(idx)))
                total += take
        if not sampled:
            self._bounds_batch = None
            return
        allk = HostBatch.concat(sampled)
        order = seg.lexsort_np(
            allk.columns,
            [not k.ascending for k in self._bound_keys],
            [k.nulls_first for k in self._bound_keys])
        sorted_keys = allk.take(order)
        n = sorted_keys.num_rows
        cuts = [int(round(n * (i + 1) / self.num_partitions))
                for i in range(self.num_partitions - 1)]
        cuts = [min(max(c, 0), n - 1) for c in cuts]
        self._bounds_batch = sorted_keys.take(np.asarray(cuts,
                                                         dtype=np.int64))

    def partition_ids(self, batch):
        n = batch.num_rows
        if self._bounds_batch is None or self._bounds_batch.num_rows == 0:
            return np.zeros(n, dtype=np.int32)
        key_cols = [as_host_column(k.expr.eval_cpu(batch), n)
                    for k in self._bound_keys]
        nb = self._bounds_batch.num_rows
        # row r belongs to the first bound b with row <= bound_b
        pids = np.full(n, nb, dtype=np.int32)
        for b in range(nb - 1, -1, -1):
            le = self._row_le_bound(key_cols, b)
            pids = np.where(le, b, pids)
        return pids

    def _row_le_bound(self, key_cols: List[HostColumn],
                      b: int) -> np.ndarray:
        """row <= bounds[b] under the sort order (vectorized lexicographic
        compare with null placement)."""
        n = key_cols[0].num_rows
        lt = np.zeros(n, dtype=np.bool_)
        eq = np.ones(n, dtype=np.bool_)
        for k, col in zip(self._bound_keys, key_cols):
            bcol = self._bounds_batch.columns[
                self._bound_keys.index(k)]
            bval = bcol[b]
            v_valid = col.is_valid()
            b_null = bval is None
            if col.dtype.is_string:
                data = np.asarray([x if isinstance(x, str) else ""
                                   for x in col.data], dtype=object)
                bv = bval if bval is not None else ""
                raw_lt = np.asarray(data < bv, dtype=np.bool_)
                raw_eq = np.asarray(data == bv, dtype=np.bool_)
            else:
                bv = bval if bval is not None else 0
                raw_lt = np.asarray(col.data < bv, dtype=np.bool_)
                raw_eq = np.asarray(col.data == bv, dtype=np.bool_)
            if not k.ascending:
                raw_lt = ~raw_lt & ~raw_eq
            # null handling: null sorts first iff nulls_first
            if k.nulls_first:
                k_lt = np.where(v_valid,
                                raw_lt & (not b_null),
                                ~np.full(n, b_null))
                k_eq = np.where(v_valid,
                                raw_eq & (not b_null),
                                np.full(n, b_null))
            else:
                k_lt = np.where(v_valid,
                                raw_lt | np.full(n, b_null),
                                np.zeros(n, np.bool_))
                k_eq = np.where(v_valid,
                                raw_eq & (not b_null),
                                np.full(n, b_null))
            lt = lt | (eq & k_lt)
            eq = eq & k_eq
        return lt | eq

    def describe(self):
        return f"RangePartitioning({self.num_partitions})"

    def signature(self):
        from ..exec.kernel_cache import sort_key_signature

        return ("range", sort_key_signature(self._bound_keys),
                sort_key_signature(self.sort_keys, bound=False))
