"""Compile/dispatch-path rules: jit-direct, stopwatch, profiler-guard.

All three guard the KernelCache contract: every compile goes through
``jit_kernel`` (one cache, one profiler hook, one place to account
compile time), timing around dispatches belongs to the profiler (an ad
hoc stopwatch around a ``jit_kernel`` call measures async dispatch,
not kernel time), and the profiler hook inside ``_CachedKernel`` must
stay a single attribute read when disabled.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from ..engine import AnalysisContext, Rule
from ..findings import Finding
from ..resolver import own_body_nodes, terminal_name
from . import common

KERNEL_CACHE = "exec/kernel_cache.py"


class JitDirectRule(Rule):
    id = "jit-direct"
    title = "exec/ compiles only through jit_kernel (KernelCache)"

    def run(self, ctx: AnalysisContext) -> Iterable[Finding]:
        out: List[Finding] = []
        rels = common.scoped(ctx, prefixes=("exec/",),
                             exclude=(KERNEL_CACHE,))
        jit_kernel_sites = 0
        for fi in ctx.resolver.functions(rels):
            for call in fi.own_calls:
                name = terminal_name(call.func)
                if name == "jit":
                    out.append(self.finding(
                        "direct-jit", fi.module, call.lineno,
                        f"{fi.qualname}() calls jit() directly — "
                        f"compile through jit_kernel so the cache "
                        f"and compile-time accounting see it",
                        detail=f"{fi.qualname}:jit"))
                elif name == "jit_kernel":
                    jit_kernel_sites += 1
        out.extend(self.health(
            jit_kernel_sites >= 10, common.PKG + KERNEL_CACHE,
            f"expected >=10 jit_kernel call sites in exec/, "
            f"saw {jit_kernel_sites}"))
        return out


class StopwatchRule(Rule):
    id = "stopwatch"
    title = "no ad-hoc perf_counter timing around jit_kernel dispatches"

    def run(self, ctx: AnalysisContext) -> Iterable[Finding]:
        out: List[Finding] = []
        rels = common.scoped(ctx, prefixes=("exec/",),
                             exclude=(KERNEL_CACHE,))
        for fi in ctx.resolver.functions(rels):
            names = fi.own_call_names
            timed = names & {"perf_counter", "perf_counter_ns"}
            if timed and "jit_kernel" in names:
                out.append(self.finding(
                    "adhoc-timing", fi.module, fi.lineno,
                    f"{fi.qualname}() wraps a jit_kernel dispatch in "
                    f"{sorted(timed)} — dispatch is async; kernel "
                    f"timing belongs to the KernelProfiler",
                    detail=f"{fi.qualname}:stopwatch"))
        return out


class ProfilerGuardRule(Rule):
    id = "profiler-guard"
    title = "profiler hook in the dispatch path is one attribute read"

    def run(self, ctx: AnalysisContext) -> Iterable[Finding]:
        out: List[Finding] = []
        rel = common.PKG + KERNEL_CACHE
        mi = ctx.resolver.module(rel)
        if mi is None:
            return [self.finding("health", rel, 0,
                                 "kernel_cache.py missing/unparseable")]
        calls = [fi for fi in mi.functions
                 if fi.class_name == "_CachedKernel" and
                 fi.name == "__call__"]
        if not calls:
            out.append(self.finding(
                "guard", rel, 0,
                "_CachedKernel.__call__ not found — the dispatch-path "
                "profiler guard cannot be verified"))
            return out
        fi = calls[0]
        # the guard: prof = PROFILER if PROFILER.enabled else None
        guard_ok = any(
            isinstance(n, ast.IfExp) and
            isinstance(n.test, ast.Attribute) and
            n.test.attr == "enabled" and
            isinstance(n.orelse, ast.Constant) and
            n.orelse.value is None
            for n in own_body_nodes(fi.node))
        if not guard_ok:
            out.append(self.finding(
                "guard", rel, fi.lineno,
                "_CachedKernel.__call__ must bind the profiler via "
                "`prof = PROFILER if PROFILER.enabled else None` — "
                "one attribute read on the disabled path",
                detail="guard-shape"))
        # every record_dispatch stays behind an `... is not None` If
        guarded_ids = set()
        for n in own_body_nodes(fi.node):
            if isinstance(n, ast.If) and \
                    isinstance(n.test, ast.Compare) and \
                    any(isinstance(op, ast.IsNot)
                        for op in n.test.ops):
                for stmt in n.body:
                    for sub in ast.walk(stmt):
                        guarded_ids.add(id(sub))
        dispatches = [c for c in fi.own_calls
                      if terminal_name(c.func) == "record_dispatch"]
        for c in dispatches:
            if id(c) not in guarded_ids:
                out.append(self.finding(
                    "guard", rel, c.lineno,
                    "record_dispatch call not under an "
                    "`if prof is not None:` guard",
                    detail="record_dispatch-unguarded"))
        out.extend(self.health(
            len(dispatches) >= 1, rel,
            "no record_dispatch site in _CachedKernel.__call__"))
        prof = ctx.resolver.module(common.PKG + "telemetry/profiler.py")
        have = set(prof.by_name) if prof is not None else set()
        need = {"record_dispatch", "mark", "since"}
        out.extend(self.health(
            need <= have, common.PKG + "telemetry/profiler.py",
            f"KernelProfiler API incomplete: missing {sorted(need - have)}"))
        return out
