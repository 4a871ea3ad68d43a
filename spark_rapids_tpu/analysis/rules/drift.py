"""Drift rules: conf-drift, event-drift, decision-event.

Drift is the failure mode of every registry that is documented (or
mirrored) somewhere else: conf keys vs ``docs/configs.md``, emitted
event names vs the telemetry catalog, and the "every
admission/preemption/AQE/streaming decision emits its event" contract
the observability docs promise.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..engine import AnalysisContext, Rule
from ..findings import Finding
from ..resolver import FuncInfo, ModuleIndex, terminal_name
from . import common

#: conf keys created at runtime (per-op enable keys) — exempt from the
#: reverse docs check because the registry, not config.py, names them
DYNAMIC_KEY_PREFIXES = ("spark.rapids.tpu.sql.",)

_DOC_KEY_RE = re.compile(r"^\|\s*`([^`]+)`", re.MULTILINE)


def _conf_literals(mi: ModuleIndex) -> List[Tuple[str, int, bool]]:
    """(key, lineno, is_internal) for every literal conf("...") chain,
    internal-ness judged per enclosing top-level statement (the
    builder chain lives inside one statement)."""
    out = []
    for stmt in ast.walk(mi.tree):
        if not isinstance(stmt, ast.stmt):
            continue
        internal = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr == "internal" for n in ast.walk(stmt))
        for n in ast.walk(stmt):
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Name) and \
                    n.func.id == "conf" and n.args and \
                    isinstance(n.args[0], ast.Constant) and \
                    isinstance(n.args[0].value, str):
                out.append((n.args[0].value, n.lineno, internal))
    return out


class ConfDriftRule(Rule):
    id = "conf-drift"
    title = "every public conf key is documented in docs/configs.md"

    def run(self, ctx: AnalysisContext) -> Iterable[Finding]:
        out: List[Finding] = []
        rel = common.PKG + "config.py"
        mi = ctx.resolver.module(rel)
        if mi is None:
            return [self.finding("health", rel, 0, "config.py missing")]
        docs = ctx.project.read_text("docs/configs.md")
        if docs is None:
            return [self.finding(
                "missing-docs", "docs/configs.md", 0,
                "docs/configs.md does not exist — regenerate it from "
                "the conf registry (dump_markdown)")]
        entries = _conf_literals(mi)
        documented = set(_DOC_KEY_RE.findall(docs))
        public = [(k, ln) for k, ln, internal in entries
                  if not internal]
        for key, lineno in public:
            if key not in documented:
                out.append(self.finding(
                    "undocumented-key", rel, lineno,
                    f"conf key {key!r} is not documented in "
                    f"docs/configs.md — regenerate the docs",
                    detail=f"key:{key}"))
        known = {k for k, _ln, _i in entries}
        for key in sorted(documented):
            if key not in known and \
                    not key.startswith(DYNAMIC_KEY_PREFIXES):
                out.append(self.finding(
                    "stale-doc", "docs/configs.md", 0,
                    f"docs/configs.md documents {key!r} which is no "
                    f"longer registered in config.py",
                    detail=f"stale:{key}"))
        out.extend(self.health(
            len(public) >= 10, rel,
            f"expected >=10 public conf keys, saw {len(public)}"))
        return out


def _event_arg_literals(call: ast.Call) -> Optional[List[str]]:
    """Literal event name(s) of an emission call: a plain string, or
    an IfExp both of whose branches are literals (the overload
    enter/exit idiom).  None = genuinely computed."""
    if not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [arg.value]
    if isinstance(arg, ast.IfExp) and \
            isinstance(arg.body, ast.Constant) and \
            isinstance(arg.body.value, str) and \
            isinstance(arg.orelse, ast.Constant) and \
            isinstance(arg.orelse.value, str):
        return [arg.body.value, arg.orelse.value]
    return None


def _emit_sites(ctx: AnalysisContext, rels: Iterable[str]
                ) -> List[Tuple[FuncInfo, ast.Call, Optional[str]]]:
    """(function, call, literal-or-None) for every event emission —
    ``emit_event`` everywhere, plus the funnel's own ``.emit()``
    inside telemetry/ (query_begin/query_end bypass the module-level
    helper).  IfExp-of-literals sites expand to one entry per name."""
    out = []
    for fi in ctx.resolver.functions(rels):
        in_telemetry = fi.module.startswith(common.PKG + "telemetry/")
        for call in fi.own_calls:
            name = terminal_name(call.func)
            if name != "emit_event" and \
                    not (in_telemetry and name == "emit"):
                continue
            lits = _event_arg_literals(call)
            if lits is None:
                out.append((fi, call, None))
            else:
                for lit in lits:
                    out.append((fi, call, lit))
    return out


class EventDriftRule(Rule):
    id = "event-drift"
    title = "emitted events match the telemetry catalog, literally"

    def run(self, ctx: AnalysisContext) -> Iterable[Finding]:
        out: List[Finding] = []
        events_rel = common.PKG + "telemetry/events.py"
        mi = ctx.resolver.module(events_rel)
        if mi is None:
            return [self.finding("health", events_rel, 0,
                                 "telemetry/events.py missing")]
        catalog = self._catalog(mi)
        if catalog is None:
            return [self.finding(
                "missing-catalog", events_rel, 0,
                "telemetry/events.py must define EVENT_CATALOG (a "
                "frozenset of every event name) — the drift source "
                "of truth")]
        rels = [r for r in ctx.project.files()
                if r.startswith(common.PKG)
                and not r.startswith(common.PKG + "analysis/")]
        emitted: Set[str] = set()
        for fi, call, lit in _emit_sites(ctx, rels):
            if lit is None:
                if fi.module.startswith(common.PKG + "telemetry/"):
                    # the funnel's own forwarding paths (emit_event ->
                    # log.emit, span re-emission) carry computed names
                    # by construction
                    continue
                out.append(self.finding(
                    "non-literal", fi.module, call.lineno,
                    f"{fi.qualname}() emits a computed event name — "
                    f"event names must be string literals so the "
                    f"catalog check can see them",
                    detail=f"{fi.qualname}:non-literal"))
                continue
            emitted.add(lit)
            if lit not in catalog:
                out.append(self.finding(
                    "uncataloged", fi.module, call.lineno,
                    f"event {lit!r} is not in EVENT_CATALOG "
                    f"(telemetry/events.py) — add it with its "
                    f"payload contract",
                    detail=f"event:{lit}"))
            if fi.module.startswith(common.PKG + "streaming/") and \
                    not lit.startswith("stream_"):
                out.append(self.finding(
                    "namespace", fi.module, call.lineno,
                    f"streaming/ emits {lit!r} — streaming events "
                    f"live in the stream_ namespace",
                    detail=f"namespace:{lit}"))
        for name in sorted(catalog - emitted):
            out.append(self.finding(
                "stale-catalog", events_rel, 0,
                f"EVENT_CATALOG lists {name!r} but nothing emits it",
                detail=f"stale:{name}"))
        out.extend(self.health(
            len(emitted) >= 15, events_rel,
            f"expected >=15 distinct emitted events, "
            f"saw {len(emitted)}"))
        return out

    @staticmethod
    def _catalog(mi: ModuleIndex) -> Optional[Set[str]]:
        value = mi.module_assigns.get("EVENT_CATALOG")
        if value is None:
            return None
        if isinstance(value, ast.Call):
            # frozenset({...}) / frozenset((...,))
            value = value.args[0] if value.args else None
        if isinstance(value, (ast.Set, ast.List, ast.Tuple)):
            out = set()
            for e in value.elts:
                if isinstance(e, ast.Constant) and \
                        isinstance(e.value, str):
                    out.add(e.value)
            return out
        return None


#: scheduler decision functions allowed to skip emission, with why
QOS_ALLOWLIST: Dict[str, str] = {
    "scheduler/query_scheduler.py:_maybe_preempt_locked":
        "dispatcher-side decision; the worker emits preempt_victim "
        "with the full task context after the hand-off",
    "scheduler/qos.py:count_shed_locked":
        "pure counter bump under _cv; overload_shed is emitted by "
        "the admission path that calls it",
}

AQE_REQUIRED = {
    "adaptive/planner.py": {"aqe_broadcast_join", "aqe_skew_split",
                            "aqe_coalesce_partitions"},
    "adaptive/executor.py": {"aqe_stage_stats", "aqe_final_plan"},
}

STREAM_REQUIRED = {
    "stream_start", "stream_stop", "stream_tick_skip",
    "stream_batch_start", "stream_batch_commit", "stream_batch_capped",
    "stream_batch_error", "stream_incremental_merge",
    "stream_incremental_skip",
}

_QOS_DECISION_RE = re.compile(r"shed|preempt")
_STREAM_DECISION_RE = re.compile(r"skip|cap|shed")


def _reaches_emit(fi: FuncInfo, mi: ModuleIndex,
                  seen: Optional[Set[str]] = None) -> bool:
    """Transitive within-module: does fi (or a same-module callee)
    call emit_event?"""
    seen = seen if seen is not None else set()
    if fi.qualname in seen:
        return False
    seen.add(fi.qualname)
    if "emit_event" in fi.own_call_names:
        return True
    for name in fi.own_call_names:
        for callee in mi.by_name.get(name, ()):
            if _reaches_emit(callee, mi, seen):
                return True
    return False


class DecisionEventRule(Rule):
    id = "decision-event"
    title = "every scheduling/AQE/streaming decision emits its event"

    def run(self, ctx: AnalysisContext) -> Iterable[Finding]:
        out: List[Finding] = []
        out.extend(self._aqe(ctx))
        out.extend(self._qos(ctx))
        out.extend(self._stream(ctx))
        return out

    def _aqe(self, ctx: AnalysisContext) -> List[Finding]:
        out: List[Finding] = []
        for suffix, required in AQE_REQUIRED.items():
            rel = common.PKG + suffix
            mi = ctx.resolver.module(rel)
            if mi is None:
                out.append(self.finding("health", rel, 0,
                                        f"{suffix} missing"))
                continue
            emitted = {lit for _fi, _c, lit in
                       _emit_sites(ctx, [rel]) if lit}
            for name in sorted(required - emitted):
                out.append(self.finding(
                    "aqe-required", rel, 0,
                    f"{suffix} must emit {name!r} (the AQE decision "
                    f"audit trail the observability docs promise)",
                    detail=f"required:{name}"))
            # every mutation of the decision counters is an audited
            # decision site: it must emit an aqe_* event itself
            for fi in mi.functions:
                if "_bump" in fi.own_call_names:
                    aqe = {lit for _f, _c, lit in
                           _emit_sites(ctx, [rel])
                           if lit and _f.qualname == fi.qualname and
                           lit.startswith("aqe_")}
                    if not aqe:
                        out.append(self.finding(
                            "aqe-decision", rel, fi.lineno,
                            f"{fi.qualname}() bumps an AQE decision "
                            f"counter without emitting an aqe_* event",
                            detail=f"{fi.qualname}:aqe-decision"))
        recorders = sum(
            1 for fi in ctx.resolver.functions(ctx.project.files())
            if "record_exchange" in fi.own_call_names)
        out.extend(self.health(
            recorders >= 1, common.PKG + "adaptive/stats.py",
            f"expected >=1 record_exchange caller, saw {recorders}"))
        return out

    def _qos(self, ctx: AnalysisContext) -> List[Finding]:
        out: List[Finding] = []
        matched = 0
        for mi in ctx.resolver.modules(
                common.scoped(ctx, prefixes=("scheduler/",))):
            for fi in mi.functions:
                if not _QOS_DECISION_RE.search(fi.name):
                    continue
                matched += 1
                key = next(
                    (k for k in QOS_ALLOWLIST
                     if mi.rel.endswith(k.split(":", 1)[0]) and
                     fi.name == k.split(":", 1)[1]), None)
                if key is not None:
                    continue
                if not _reaches_emit(fi, mi):
                    out.append(self.finding(
                        "qos-decision", mi.rel, fi.lineno,
                        f"{fi.qualname}() makes a shed/preempt "
                        f"decision but never reaches emit_event "
                        f"(within {mi.rel}) — admission decisions "
                        f"must be observable",
                        detail=f"{fi.qualname}:qos-decision"))
        out.extend(self.health(
            matched >= 4, common.PKG + "scheduler",
            f"expected >=4 shed/preempt decision functions, "
            f"saw {matched}"))
        return out

    def _stream(self, ctx: AnalysisContext) -> List[Finding]:
        out: List[Finding] = []
        rels = common.scoped(ctx, prefixes=("streaming/",))
        emitted_all: Set[str] = set()
        for _fi, _c, lit in _emit_sites(ctx, rels):
            if lit:
                emitted_all.add(lit)
        for name in sorted(STREAM_REQUIRED - emitted_all):
            out.append(self.finding(
                "stream-required", common.PKG + "streaming", 0,
                f"streaming/ must emit {name!r} (the continuous-"
                f"query lifecycle audit trail)",
                detail=f"required:{name}"))
        decisions = 0
        for mi in ctx.resolver.modules(rels):
            for fi in mi.functions:
                if not _STREAM_DECISION_RE.search(fi.name):
                    continue
                decisions += 1
                if not _reaches_emit(fi, mi):
                    out.append(self.finding(
                        "stream-decision", mi.rel, fi.lineno,
                        f"{fi.qualname}() makes a skip/cap/shed "
                        f"decision but never reaches emit_event",
                        detail=f"{fi.qualname}:stream-decision"))
        out.extend(self.health(
            decisions >= 3, common.PKG + "streaming",
            f"expected >=3 streaming decision functions, "
            f"saw {decisions}"))
        return out
