"""The plan-rewrite engine — tag, explain, convert.

Capability parity with the reference's heart (GpuOverrides.scala 1765 LoC +
RapidsMeta.scala 725 LoC): every physical node is wrapped in a meta that
``tag_for_tpu()`` annotates with ``will_not_work_on_tpu(reason)`` strings;
supported subtrees convert to TpuExec operators with host<->device
transitions spliced at the boundaries; ``explain`` renders the annotated
report (``*`` = runs on TPU, ``!`` = cannot, ``@`` = could but disabled).

Per-operator enable/disable conf keys are auto-derived from the rule
registry exactly like the reference (GpuOverrides.scala:118-123):
``spark.rapids.tpu.sql.exec.<Name>`` / ``...sql.expr.<Name>``.
"""
from __future__ import annotations

import logging
from typing import Callable, Dict, List, Optional, Type

from .. import types as T
from ..config import (
    INCOMPATIBLE_OPS,
    TpuConf,
    register_op_enable_key,
)
from ..ops import aggregates as agg
from ..ops.expression import Expression
from . import physical as P

log = logging.getLogger(__name__)


# ==========================================================================
# Rules
# ==========================================================================
class ExprRule:
    def __init__(self, cls: Type[Expression], desc: str = "",
                 incompat: Optional[str] = None,
                 tag: Optional[Callable] = None):
        self.cls = cls
        self.desc = desc or cls.__name__
        self.incompat = incompat
        self.tag = tag
        self.conf_entry = register_op_enable_key(
            "expr", cls.__name__, desc or f"enable expression "
            f"{cls.__name__} on TPU", default=incompat is None)


class ExecRule:
    def __init__(self, cls: Type[P.PhysicalPlan], convert: Callable,
                 desc: str = "", incompat: Optional[str] = None,
                 tag: Optional[Callable] = None,
                 exprs_of: Optional[Callable] = None):
        self.cls = cls
        self.convert = convert  # (meta, device_children) -> TpuExec
        self.desc = desc or cls.__name__
        self.incompat = incompat
        self.tag = tag
        self.exprs_of = exprs_of or (lambda plan: [])
        self.conf_entry = register_op_enable_key(
            "exec", cls.__name__, desc or f"enable operator "
            f"{cls.__name__} on TPU", default=incompat is None)


EXPR_RULES: Dict[Type[Expression], ExprRule] = {}
EXEC_RULES: Dict[Type[P.PhysicalPlan], ExecRule] = {}


def register_expr(cls, **kw):
    EXPR_RULES[cls] = ExprRule(cls, **kw)


def register_exec(cls, convert, **kw):
    EXEC_RULES[cls] = ExecRule(cls, convert, **kw)


def find_expr_rule(e: Expression) -> Optional[ExprRule]:
    for klass in type(e).__mro__:
        if klass in EXPR_RULES:
            return EXPR_RULES[klass]
    return None


# ==========================================================================
# Metas (reference: RapidsMeta.scala)
# ==========================================================================
class BaseMeta:
    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.cannot_replace_reasons: List[str] = []

    def will_not_work_on_tpu(self, reason: str) -> None:
        if reason not in self.cannot_replace_reasons:
            self.cannot_replace_reasons.append(reason)

    @property
    def can_this_be_replaced(self) -> bool:
        return not self.cannot_replace_reasons


class ExprMeta(BaseMeta):
    def __init__(self, expr: Expression, conf: TpuConf):
        super().__init__(conf)
        self.expr = expr
        self.children = [ExprMeta(c, conf) for c in expr.children]

    def tag_for_tpu(self) -> None:
        e = self.expr
        rule = find_expr_rule(e)
        name = type(e).__name__
        if rule is None:
            self.will_not_work_on_tpu(
                f"no TPU rule for expression {name}")
        else:
            if not rule.conf_entry.get(dict(self.conf.items())):
                self.will_not_work_on_tpu(
                    f"expression {name} disabled by "
                    f"{rule.conf_entry.key}")
            if rule.incompat and not self.conf.get(INCOMPATIBLE_OPS):
                self.will_not_work_on_tpu(
                    f"{name} is incompatible ({rule.incompat}); enable "
                    f"{INCOMPATIBLE_OPS.key} to allow")
            if rule.tag is not None:
                rule.tag(self)
        try:
            dt = e.dtype
            if not T.is_supported_type(dt):
                self.will_not_work_on_tpu(
                    f"expression {name} produces unsupported type {dt}")
        except Exception:  # noqa: BLE001 - unresolved exprs
            pass
        if not e.tpu_supported:
            self.will_not_work_on_tpu(
                f"expression {name} has no device implementation "
                "for these inputs")
        for c in self.children:
            c.tag_for_tpu()

    @property
    def can_expr_tree_be_replaced(self) -> bool:
        return self.can_this_be_replaced and all(
            c.can_expr_tree_be_replaced for c in self.children)

    def all_reasons(self) -> List[str]:
        out = list(self.cannot_replace_reasons)
        for c in self.children:
            out.extend(c.all_reasons())
        return out


class AggMeta(BaseMeta):
    """Meta for an AggregateFunction inside an agg exec."""

    def __init__(self, func: agg.AggregateFunction, conf: TpuConf):
        super().__init__(conf)
        self.func = func
        self.children = [ExprMeta(c, conf) for c in func.children]

    def tag_for_tpu(self):
        name = type(self.func).__name__
        if self.func.child is not None:
            dt = self.func.child.dtype
            if dt.is_string and isinstance(self.func,
                                           (agg.Sum, agg.Average)):
                self.will_not_work_on_tpu(f"{name} on strings")
            if not T.is_supported_type(dt):
                self.will_not_work_on_tpu(
                    f"{name} input type {dt} not supported")
        for c in self.children:
            c.tag_for_tpu()

    @property
    def can_expr_tree_be_replaced(self):
        return self.can_this_be_replaced and all(
            c.can_expr_tree_be_replaced for c in self.children)

    def all_reasons(self):
        out = list(self.cannot_replace_reasons)
        for c in self.children:
            out.extend(c.all_reasons())
        return out


class ExecMeta(BaseMeta):
    """SparkPlanMeta analogue."""

    def __init__(self, plan: P.PhysicalPlan, conf: TpuConf):
        super().__init__(conf)
        self.plan = plan
        self.rule = self._find_rule()
        self.children = [ExecMeta(c, conf) for c in plan.children]
        exprs = self.rule.exprs_of(plan) if self.rule else []
        self.expr_metas: List[BaseMeta] = []
        for e in exprs:
            if isinstance(e, agg.AggregateFunction):
                self.expr_metas.append(AggMeta(e, conf))
            else:
                self.expr_metas.append(ExprMeta(e, conf))

    def _find_rule(self) -> Optional[ExecRule]:
        for klass in type(self.plan).__mro__:
            if klass in EXEC_RULES:
                return EXEC_RULES[klass]
        return None

    def tag_for_tpu(self) -> None:
        name = type(self.plan).__name__
        if self.rule is None:
            self.will_not_work_on_tpu(f"no TPU rule for operator {name}")
        else:
            if not self.rule.conf_entry.get(dict(self.conf.items())):
                self.will_not_work_on_tpu(
                    f"operator disabled by {self.rule.conf_entry.key}")
            if self.rule.incompat and not self.conf.get(INCOMPATIBLE_OPS):
                self.will_not_work_on_tpu(
                    f"{name} is incompatible ({self.rule.incompat})")
        # output type gate (reference: GpuOverrides.isSupportedType)
        try:
            for f in self.plan.schema:
                if not T.is_supported_type(f.dtype):
                    self.will_not_work_on_tpu(
                        f"unsupported output type {f.dtype} "
                        f"in column {f.name}")
        except NotImplementedError:
            pass
        for em in self.expr_metas:
            em.tag_for_tpu()
            if not em.can_expr_tree_be_replaced:
                kind = em.func.sql() if isinstance(em, AggMeta) \
                    else em.expr.sql()
                self.will_not_work_on_tpu(
                    f"expression not supported: {kind} "
                    f"({'; '.join(em.all_reasons())})")
        if self.rule is not None and self.rule.tag is not None:
            self.rule.tag(self)
        for c in self.children:
            c.tag_for_tpu()

    # ------------------------------------------------------------------
    def convert_if_needed(self) -> P.PhysicalPlan:
        from ..exec.base import TpuExec
        from ..exec.transitions import DeviceToHostExec, HostToDeviceExec

        converted = [c.convert_if_needed() for c in self.children]
        if self.can_this_be_replaced and self.rule is not None:
            device_children = [
                c if isinstance(c, TpuExec) else HostToDeviceExec(c)
                for c in converted]
            return self.rule.convert(self, device_children)
        host_children = [
            DeviceToHostExec(c) if isinstance(c, TpuExec) else c
            for c in converted]
        if list(self.plan.children) == host_children:
            return self.plan
        return self.plan.with_new_children(host_children)

    # ------------------------------------------------------------------
    def explain(self, all_mode: bool = True, indent: int = 0) -> str:
        name = type(self.plan).__name__
        condition = getattr(self.plan, "condition", None)
        if condition is not None and hasattr(self.plan, "how"):
            # where a join's residual condition runs is part of the plan
            name += f" [{self.plan.how}, {condition.sql()}]"
        if self.can_this_be_replaced:
            mark, note = "*", "will run on TPU"
        else:
            disabled = any("disabled by" in r
                           for r in self.cannot_replace_reasons)
            mark = "@" if disabled else "!"
            note = ("could run on TPU but is disabled: "
                    if disabled else "cannot run on TPU because ")
            note += "; ".join(self.cannot_replace_reasons)
        line = f"{'  ' * indent}{mark} {name} -> {note}"
        lines = [line] if (all_mode or mark != "*") else []
        for c in self.children:
            sub = c.explain(all_mode, indent + 1)
            if sub:
                lines.append(sub)
        return "\n".join(lines)


# ==========================================================================
# The rewrite rule (reference: GpuOverrides.apply:1709-1724)
# ==========================================================================
class TpuOverrides:
    def __init__(self, conf: TpuConf):
        self.conf = conf
        _ensure_registry()

    def wrap(self, plan: P.PhysicalPlan) -> ExecMeta:
        return ExecMeta(plan, self.conf)

    def apply(self, plan: P.PhysicalPlan) -> P.PhysicalPlan:
        meta = self.wrap(plan)
        meta.tag_for_tpu()
        mode = self.conf.explain
        if mode not in ("NONE", ""):
            report = meta.explain(all_mode=(mode == "ALL"))
            if report:
                log.warning("TPU plan overrides:\n%s", report)
        return meta.convert_if_needed()

    def explain(self, plan: P.PhysicalPlan) -> str:
        meta = self.wrap(plan)
        meta.tag_for_tpu()
        return meta.explain(all_mode=self.conf.explain != "NOT_ON_TPU")


# ==========================================================================
# Degradation-ladder transition (fault tolerance)
# ==========================================================================
def cpu_exec_plan(conf: TpuConf, logical_plan) -> P.PhysicalPlan:
    """The bottom rung of the graceful-degradation ladder: plan
    ``logical_plan`` WITHOUT applying any TPU overrides — the pure host
    physical plan (the reference's transparent CPU fallback, applied to
    the whole query after device-side fault recovery is exhausted).
    Bit-identical results are the contract: the host engine is the
    oracle the TPU plan is tested against."""
    from .optimizer import optimize
    from .planner import Planner

    return Planner(conf).plan(optimize(logical_plan))


# ==========================================================================
# Registry population
# ==========================================================================
_REGISTRY_DONE = False


def _ensure_registry():
    global _REGISTRY_DONE
    if _REGISTRY_DONE:
        return
    _REGISTRY_DONE = True
    _register_expression_rules()
    _register_exec_rules()


def _register_expression_rules():
    from ..ops import (
        arithmetic as ar,
        bitwise as bw,
        cast as cst,
        conditional as cond,
        datetimeexprs as dt,
        mathexprs as m,
        miscexprs as misc,
        nullexprs as ne,
        predicates as pr,
        stringexprs as s,
    )
    from ..ops import expression as ex

    # leaves / structural
    for cls in (ex.Literal, ex.BoundReference, ex.Alias,
                ex.UnresolvedAttribute):
        register_expr(cls)
    # arithmetic (reference: arithmetic.scala rules at GpuOverrides:454+)
    for cls in (ar.Add, ar.Subtract, ar.Multiply, ar.Divide,
                ar.IntegralDivide, ar.Remainder, ar.Pmod, ar.UnaryMinus,
                ar.UnaryPositive, ar.Abs, ar.Least, ar.Greatest):
        register_expr(cls)
    # predicates
    for cls in (pr.EqualTo, pr.LessThan, pr.LessThanOrEqual,
                pr.GreaterThan, pr.GreaterThanOrEqual, pr.EqualNullSafe,
                pr.Not, pr.And, pr.Or, pr.IsNull, pr.IsNotNull, pr.IsNaN,
                pr.AtLeastNNonNulls, pr.In, pr.InSet):
        register_expr(cls)
    # conditional / null
    for cls in (cond.If, cond.CaseWhen, ne.Coalesce, ne.NaNvl):
        register_expr(cls)
    # cast & float normalization — string directions conf-gated like
    # the reference (GpuCast.scala:30-77, RapidsConf.scala:373-403)
    from ..config import (CAST_STRING_TO_FLOAT, CAST_STRING_TO_INTEGER,
                          CAST_STRING_TO_TIMESTAMP)

    def tag_cast(meta):
        e = meta.expr
        try:
            src, dst = e.child.dtype, e.to
        except Exception:  # noqa: BLE001 - unresolved child
            return
        if not src.is_string:
            return
        if dst.is_integral and not meta.conf.get(CAST_STRING_TO_INTEGER):
            meta.will_not_work_on_tpu(
                "string->integral cast disabled by "
                f"{CAST_STRING_TO_INTEGER.key}")
        if dst.is_floating and not meta.conf.get(CAST_STRING_TO_FLOAT):
            meta.will_not_work_on_tpu(
                "string->float cast on device can differ by a few ULPs "
                f"from the host parse; enable {CAST_STRING_TO_FLOAT.key}")
        if dst.id in (T.TypeId.DATE32, T.TypeId.TIMESTAMP) \
                and not meta.conf.get(CAST_STRING_TO_TIMESTAMP):
            meta.will_not_work_on_tpu(
                "string->date/timestamp cast disabled by "
                f"{CAST_STRING_TO_TIMESTAMP.key}")

    register_expr(cst.Cast, tag=tag_cast)
    register_expr(cst.NormalizeNaNAndZero)
    register_expr(cst.KnownFloatingPointNormalized)
    # math: Spark computes in double; bit-exact transcendentals differ on
    # XLA for a few ULPs -> incompat-gated like the reference's
    # improvedFloatOps family
    for cls in (m.Sqrt, m.Cbrt, m.Floor, m.Ceil, m.Signum, m.Rint,
                m.ToDegrees, m.ToRadians, m.Pow, m.Atan2):
        register_expr(cls)
    for cls in (m.Acos, m.Asin, m.Atan, m.Acosh, m.Asinh, m.Atanh,
                m.Cos, m.Sin, m.Tan, m.Cot, m.Cosh, m.Sinh, m.Tanh,
                m.Exp, m.Expm1, m.Log, m.Log1p, m.Log2, m.Log10,
                m.Logarithm):
        register_expr(cls)
    # bitwise
    for cls in (bw.BitwiseAnd, bw.BitwiseOr, bw.BitwiseXor, bw.BitwiseNot,
                bw.ShiftLeft, bw.ShiftRight, bw.ShiftRightUnsigned):
        register_expr(cls)
    # datetime
    for cls in (dt.Year, dt.Month, dt.DayOfMonth, dt.Hour, dt.Minute,
                dt.Second, dt.DateAdd, dt.DateSub, dt.DateDiff,
                dt.TimeAdd, dt.TimeSub, dt.ToUnixTimestamp,
                dt.UnixTimestampParse, dt.FromUnixTime):
        register_expr(cls)
    # strings
    register_expr(s.Upper, incompat="ASCII-only case mapping on device")
    register_expr(s.Lower, incompat="ASCII-only case mapping on device")
    for cls in (s.Length, s.Substring, s.SubstringIndex, s.StringReplace,
                s.StringTrim, s.StringTrimLeft, s.StringTrimRight,
                s.Contains, s.StartsWith, s.EndsWith, s.StringLocate,
                s.ConcatStrings, s.Like, s.RegExpReplace, s.InitCap):
        register_expr(cls)
    # nondeterministic / context
    register_expr(misc.Rand, incompat="different RNG than the host engine")
    for cls in (misc.SparkPartitionID, misc.MonotonicallyIncreasingID,
                misc.InputFileName, misc.InputFileBlockStart,
                misc.InputFileBlockLength):
        register_expr(cls)


def _register_exec_rules():
    from ..exec import basic as B

    def exprs_of_project(plan: P.ProjectExec):
        return list(plan.exprs)

    register_exec(
        P.ProjectExec,
        convert=lambda meta, ch: B.TpuProjectExec(
            ch[0], meta.plan.exprs, meta.plan.schema),
        desc="columnar projection on TPU",
        exprs_of=exprs_of_project)

    register_exec(
        P.FilterExec,
        convert=lambda meta, ch: B.TpuFilterExec(ch[0],
                                                 meta.plan.condition),
        desc="columnar filter with sort-compaction on TPU",
        exprs_of=lambda plan: [plan.condition])

    register_exec(
        P.UnionExec,
        convert=lambda meta, ch: B.TpuUnionExec(ch),
        desc="columnar union")

    register_exec(
        P.LocalLimitExec,
        convert=lambda meta, ch: B.TpuLocalLimitExec(ch[0], meta.plan.n),
        desc="local limit on device batches")

    register_exec(
        P.GlobalLimitExec,
        convert=lambda meta, ch: B.TpuGlobalLimitExec(ch[0], meta.plan.n),
        desc="global limit on device batches")

    register_exec(
        P.ExpandExec,
        convert=lambda meta, ch: B.TpuExpandExec(
            ch[0], meta.plan.projections, meta.plan.schema.names),
        desc="grouping-sets expand on device",
        exprs_of=lambda plan: [e for ps in plan.projections for e in ps])

    # aggregate / sort / join / exchange rules are registered by their
    # exec modules (imported here so registration happens exactly once)
    from ..exec import register_rules as _exec_register_rules

    _exec_register_rules(register_exec)
