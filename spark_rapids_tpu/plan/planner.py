"""Planner: logical plan -> physical (host) plan.

Plays the role Spark's strategies + EnsureRequirements play for the
reference: lowers logical nodes to physical operators and inserts the
exchanges (partial/final aggregation split, co-partitioned joins, range
exchange under global sorts, single exchange under global limits).  The
TPU plan-rewrite engine then runs *after* this, exactly like the
reference's columnar transitions run on Spark's final physical plan.
"""
from __future__ import annotations

from typing import List, Optional

from ..config import BROADCAST_THRESHOLD, SHUFFLE_PARTITIONS
from ..ops.aggregates import AggregateExpression
from ..ops.expression import Alias, Expression, output_name
from ..shuffle.partitioning import (
    HashPartitioning,
    RangePartitioning,
    RoundRobinPartitioning,
    SinglePartitioning,
)
from . import functions as F
from . import logical as L
from . import physical as P

class Planner:
    def __init__(self, conf):
        self.conf = conf
        self.shuffle_partitions = conf.get(SHUFFLE_PARTITIONS)
        self.broadcast_threshold = conf.get(BROADCAST_THRESHOLD)

    def plan(self, node: L.LogicalPlan) -> P.PhysicalPlan:
        fn = getattr(self, f"_plan_{type(node).__name__}", None)
        if fn is None:
            raise NotImplementedError(f"no strategy for {node.name}")
        return fn(node)

    # ------------------------------------------------------------------
    def _plan_LocalRelation(self, node: L.LocalRelation):
        return P.LocalScanExec(node.batches, node.schema,
                               node.n_partitions)

    def _plan_FileScan(self, node: L.FileScan):
        from ..io import scans

        return scans.create_scan_exec(node, self.conf)

    def _plan_Project(self, node: L.Project):
        return P.ProjectExec(self.plan(node.children[0]), node.exprs)

    def _plan_Filter(self, node: L.Filter):
        return P.FilterExec(self.plan(node.children[0]), node.condition)

    def _plan_Union(self, node: L.Union):
        return P.UnionExec([self.plan(c) for c in node.children])

    def _plan_Limit(self, node: L.Limit):
        child = self.plan(node.children[0])
        local = P.LocalLimitExec(child, node.n)
        exchange = P.ShuffleExchangeExec(local, SinglePartitioning())
        return P.GlobalLimitExec(exchange, node.n)

    def _plan_Repartition(self, node: L.Repartition):
        child = self.plan(node.children[0])
        if node.keys:
            part = HashPartitioning(node.keys, node.n).bind(child.schema)
        else:
            part = RoundRobinPartitioning(node.n)
        return P.ShuffleExchangeExec(child, part)

    def _plan_Sort(self, node: L.Sort):
        child = self.plan(node.children[0])
        if node.global_sort and self._n_partitions(child) > 1:
            part = RangePartitioning(
                node.keys, self._n_partitions(child)).bind(child.schema)
            child = P.ShuffleExchangeExec(child, part)
        return P.SortExec(child, node.keys)

    def _plan_Expand(self, node: L.Expand):
        return P.ExpandExec(self.plan(node.children[0]), node.projections,
                            node.output_names)

    def _plan_Generate(self, node: L.Generate):
        return P.GenerateExec(self.plan(node.children[0]), node.elements,
                              node.output_name, node.position)

    def _plan_WriteFile(self, node: L.WriteFile):
        return P.DataWritingCommandExec(
            self.plan(node.children[0]), node.fmt, node.path, node.options,
            node.partition_by, node.bucket_by)

    def _plan_Window(self, node: L.Window):
        from ..exec.window_cpu import WindowExec

        child = self.plan(node.children[0])
        # co-partition by the window partition keys so per-partition
        # computation is global-correct (Spark requires the same
        # distribution; reference relies on the exchange already present)
        specs = [w.spec for w in node.window_exprs]
        first_keys = specs[0].partition_by
        same = all([k.sql() for k in s.partition_by]
                   == [k.sql() for k in first_keys] for s in specs)
        if first_keys and same and self._n_partitions(child) > 1:
            child = P.ShuffleExchangeExec(
                child, HashPartitioning(
                    first_keys, min(self.shuffle_partitions,
                                    self._n_partitions(child))
                ).bind(child.schema))
        elif self._n_partitions(child) > 1:
            child = P.ShuffleExchangeExec(child, SinglePartitioning())
        return WindowExec(child, node.window_exprs, node.names)

    # ------------------------------------------------------------------
    def _plan_Aggregate(self, node: L.Aggregate):
        child = self.plan(node.children[0])
        specs: List[P.AggSpec] = []
        out_names = []
        for j, a in enumerate(node.aggregates):
            name = output_name(a, len(node.keys) + j)
            inner = a.child if isinstance(a, Alias) else a
            assert isinstance(inner, AggregateExpression), \
                f"non-aggregate in agg list: {inner}"
            func = inner.func
            if func.child is not None:
                import copy

                func = copy.copy(func)
                from ..ops.expression import bind_references

                func.child = bind_references(func.child, child.schema)
            specs.append(P.AggSpec(func, name))
            out_names.append(name)

        partial = P.HashAggregateExec(child, "partial", node.keys, specs)
        if node.keys:
            part = HashPartitioning(
                self._exchange_keys(partial.schema, len(node.keys)),
                min(self.shuffle_partitions,
                    max(self._n_partitions(child), 1)))
        else:
            part = SinglePartitioning()
        exchange = P.ShuffleExchangeExec(
            partial, part.bind(partial.schema))
        final_keys = [F.col(n).expr
                      for n in partial.schema.names[: len(node.keys)]]
        return P.HashAggregateExec(exchange, "final", final_keys, specs,
                                   out_names)

    def _plan_Join(self, node: L.Join):
        left = self.plan(node.children[0])
        right = self.plan(node.children[1])
        est = self._estimate_bytes(node.children[1])
        can_broadcast = (est is not None
                         and self.broadcast_threshold > 0
                         and est <= self.broadcast_threshold
                         and node.how in ("inner", "left", "semi", "anti"))
        if can_broadcast:
            return P.HashJoinExec(left, right, node.left_keys,
                                  node.right_keys, node.how,
                                  node.condition, broadcast=True)
        n = min(self.shuffle_partitions,
                max(self._n_partitions(left), self._n_partitions(right), 1))
        lex = P.ShuffleExchangeExec(
            left, HashPartitioning(node.left_keys, n).bind(left.schema))
        rex = P.ShuffleExchangeExec(
            right, HashPartitioning(node.right_keys, n).bind(right.schema))
        return P.HashJoinExec(lex, rex, node.left_keys, node.right_keys,
                              node.how, node.condition, broadcast=False)

    @staticmethod
    def _exchange_keys(schema, n_keys: int) -> List[Expression]:
        """The grouping keys a group-by's exchange hash-partitions on.
        Equal groups meet in one partition when they agree on ANY
        subset of their keys, so a key the device cannot hash the way
        Spark does (``utils/hashing.py:device_hash_gap``: FLOAT64 on a
        TPU) stays a grouping key and is left out of the hash, and the
        exchange stays on the device (TPC-H q18 groups by
        ``o_totalprice`` beside four keys that hash).  Where no key
        hashes, all stay: the exchange's rule tags it for the host."""
        from ..utils import hashing

        fields = schema.fields[:n_keys]
        hashable = [f for f in fields
                    if hashing.device_hash_gap(f.dtype) is None]
        return [F.col(f.name).expr for f in hashable or fields]

    # ------------------------------------------------------------------
    @staticmethod
    def _n_partitions(p: P.PhysicalPlan) -> int:
        if isinstance(p, P.LocalScanExec):
            return p.n_partitions
        if isinstance(p, P.ShuffleExchangeExec):
            return p.n_out
        if p.children:
            return max(Planner._n_partitions(c) for c in p.children)
        n = getattr(p, "n_partitions", 1)
        return n

    @staticmethod
    def _estimate_bytes(node: L.LogicalPlan) -> Optional[int]:
        """Static size estimate for broadcast decisions (the reference
        relies on Spark's stats; here LocalRelations and file sizes)."""
        if isinstance(node, L.LocalRelation):
            return sum(b.estimate_bytes() for b in node.batches)
        if isinstance(node, L.FileScan):
            import os

            try:
                return sum(os.path.getsize(p) for p in node.paths)
            except OSError:
                return None
        if isinstance(node, L.Project):
            # column pruning: a projection narrows what a broadcast
            # would actually materialize — charging the child's FULL
            # size (all file columns) overshoots and flips borderline
            # joins to shuffle.  Scale by the projected/child row-width
            # fraction (exact for fixed-width columns, nominal for
            # strings).
            est = Planner._estimate_bytes(node.children[0])
            if est is None:
                return None
            child_w = Planner._schema_row_width(node.children[0].schema)
            proj_w = Planner._schema_row_width(node.schema)
            return int(est * proj_w / child_w)
        if isinstance(node, L.Filter):
            return Planner._estimate_bytes(node.children[0])
        if isinstance(node, L.Limit):
            est = Planner._estimate_bytes(node.children[0])
            return est
        return None

    @staticmethod
    def _schema_row_width(schema) -> int:
        """Nominal bytes per row of a schema: exact itemsize for
        fixed-width columns, 16B nominal for strings (matches the
        file-size heuristic's variable-length reality well enough for
        a pruning ratio)."""
        from .. import types as T

        width = 0
        for f in schema:
            if f.dtype.id is T.TypeId.STRING:
                width += 16
            else:
                width += int(getattr(f.dtype.np_dtype, "itemsize", 8))
        return max(width, 1)
