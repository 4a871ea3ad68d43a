"""Shared helpers for the rule catalog."""
from __future__ import annotations

import ast
import re
from typing import Iterable, Iterator, List, Optional, Set, Tuple

from ..resolver import FuncInfo, dotted_name, own_body_nodes, terminal_name

#: package prefix of every analyzed source file (repo-root relative)
PKG = "spark_rapids_tpu/"

#: thread/pool spawn constructors whose targets must run with telemetry
#: bindings captured
SPAWN_NAMES = frozenset({"Thread", "ThreadPoolExecutor", "Timer",
                         "ProcessPoolExecutor"})

#: the telemetry re-binding helpers (telemetry/spans.py)
CAPTURE_NAMES = frozenset({"capture", "bound", "attached"})

#: with-item expressions whose terminal name matches this are treated
#: as lock acquisitions
LOCK_NAME_RE = re.compile(r"(^|_)(lock|cv|cond|mutex)", re.IGNORECASE)


def call_names(node: ast.AST) -> Set[str]:
    """Terminal names of every call in the subtree."""
    return {terminal_name(n.func) for n in ast.walk(node)
            if isinstance(n, ast.Call)}


def own_call_nodes(fn: ast.AST) -> List[ast.Call]:
    return [n for n in own_body_nodes(fn) if isinstance(n, ast.Call)]


def has_name(node: ast.AST, name: str) -> bool:
    """Whether ``name`` appears as a Name or attribute anywhere in the
    subtree."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id == name:
            return True
        if isinstance(n, ast.Attribute) and n.attr == name:
            return True
    return False


def string_literals(node: ast.AST) -> Set[str]:
    return {n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def is_lock_expr(expr: ast.AST) -> bool:
    """Heuristic: the context expression of a ``with`` item is a lock
    when its terminal name smells like one (``_lock``, ``_cv``,
    ``cond``, ``mutex``...)."""
    name = ""
    if isinstance(expr, ast.Attribute):
        name = expr.attr
    elif isinstance(expr, ast.Name):
        name = expr.id
    elif isinstance(expr, ast.Call):
        # with self._lock.acquire_timeout(...)-style helpers
        name = terminal_name(expr.func)
    return bool(name) and bool(LOCK_NAME_RE.search(name))


def lock_identity(module: str, class_name: Optional[str],
                  expr: ast.AST) -> str:
    """Stable identity of an acquired lock: ``module:Class.attr`` for
    ``self``-rooted locks, ``module:NAME`` for module globals, and the
    dotted chain otherwise."""
    if isinstance(expr, ast.Call):
        expr = expr.func
    dn = dotted_name(expr)
    if dn.startswith("self.") and class_name:
        return f"{module}:{class_name}.{dn[5:]}"
    if dn and "." not in dn:
        return f"{module}:{dn}"
    return f"{module}:{dn or '<expr>'}"


def iter_with_locks(fn: ast.AST) -> Iterator[Tuple[ast.With, ast.AST]]:
    """Yield (With node, lock context-expr) for every with-lock in the
    function's own body."""
    for n in own_body_nodes(fn):
        if isinstance(n, ast.With):
            for item in n.items:
                if is_lock_expr(item.context_expr):
                    yield n, item.context_expr


def guarded_node_ids(fn: ast.AST) -> Set[int]:
    """ids of AST nodes lexically inside any with-lock body of ``fn``
    (own body — nested defs own their bodies)."""
    out: Set[int] = set()
    for w, _expr in iter_with_locks(fn):
        for stmt in w.body:
            for n in ast.walk(stmt):
                out.add(id(n))
    return out


def finally_node_ids(fn: ast.AST) -> Set[int]:
    """ids of nodes inside any ``finally`` block or exception handler
    of the function's own body — the unwind-reachable positions the
    resource rule accepts releases in."""
    out: Set[int] = set()
    for n in own_body_nodes(fn):
        blocks: List[List[ast.stmt]] = []
        if isinstance(n, ast.Try):
            blocks.append(n.finalbody)
        elif isinstance(n, ast.ExceptHandler):
            blocks.append(n.body)
        for body in blocks:
            for stmt in body:
                for sub in ast.walk(stmt):
                    out.add(id(sub))
    return out


def statement_sequences(fn: ast.AST) -> Iterator[List[ast.stmt]]:
    """Every statement list (block) in the function's own body,
    including the top-level body — used for the adjacent-statement
    release shape."""
    yield fn.body
    for n in own_body_nodes(fn):
        for field in ("body", "orelse", "finalbody"):
            block = getattr(n, field, None)
            if isinstance(block, list) and block and \
                    isinstance(block[0], ast.stmt):
                yield block


def iter_spawn_sites(tree: ast.AST) -> Iterator[ast.Call]:
    for n in ast.walk(tree):
        if isinstance(n, ast.Call) and \
                terminal_name(n.func) in SPAWN_NAMES:
            yield n


def spawn_target_names(call: ast.Call) -> Set[str]:
    """Function names a spawn call may invoke: every resolvable
    Name/Attribute terminal in its args/keywords (this unwraps
    ``target=tspans.bound(tspans.capture(), self._loop)`` to
    ``{_loop, bound, capture}``)."""
    out: Set[str] = set()
    for sub in list(call.args) + [k.value for k in call.keywords]:
        for n in ast.walk(sub):
            if isinstance(n, ast.Attribute):
                out.add(n.attr)
            elif isinstance(n, ast.Name):
                out.add(n.id)
    return out


def scoped(ctx, prefixes: Iterable[str] = (), files: Iterable[str] = (),
           exclude: Iterable[str] = ()) -> List[str]:
    """Package-prefixed scope selection."""
    return ctx.project.select(
        prefixes=[PKG + p for p in prefixes],
        files=[PKG + f for f in files],
        exclude=[PKG + f for f in exclude])


def func_loc(fi: FuncInfo) -> str:
    return f"{fi.module}:{fi.qualname}"
