"""Project file discovery and cached AST parsing.

A :class:`Project` roots at the repository directory (the parent of the
``spark_rapids_tpu`` package) and discovers every analyzable source
file once: the whole package tree.  Parses are cached per file, so
the N rules that walk overlapping scopes cost one ``ast.parse`` per
file, which is what keeps the full engine run well under its 10s
budget.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional

PACKAGE = "spark_rapids_tpu"


def default_root() -> str:
    """The repo root: parent of the installed package directory."""
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg_dir)


class Project:
    """Discovered source files + cached parses under ``root``."""

    def __init__(self, root: Optional[str] = None):
        self.root = os.path.abspath(root or default_root())
        self._files: Optional[List[str]] = None
        self._trees: Dict[str, ast.Module] = {}
        self._sources: Dict[str, str] = {}
        #: files that failed to parse: relpath -> error string
        self.parse_errors: Dict[str, str] = {}

    # ---------------- discovery ----------------------------------------
    def files(self) -> List[str]:
        """Every analyzable source file, as sorted repo-root-relative
        posix paths."""
        if self._files is not None:
            return self._files
        out: List[str] = []
        pkg = os.path.join(self.root, PACKAGE)
        for dirpath, dirnames, filenames in os.walk(pkg):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, fn),
                                          self.root)
                    out.append(rel.replace(os.sep, "/"))
        self._files = sorted(out)
        return self._files

    def select(self, prefixes: Iterable[str] = (),
               files: Iterable[str] = (),
               exclude: Iterable[str] = ()) -> List[str]:
        """Scope helper: files under any of ``prefixes`` plus the named
        ``files`` (when they exist), minus exact ``exclude`` paths."""
        prefixes = tuple(prefixes)
        wanted = set(files)
        excluded = set(exclude)
        out = []
        for rel in self.files():
            if rel in excluded:
                continue
            if rel in wanted or any(rel.startswith(p) for p in prefixes):
                out.append(rel)
        return out

    # ---------------- parsing ------------------------------------------
    def path(self, rel: str) -> str:
        return os.path.join(self.root, rel.replace("/", os.sep))

    def source(self, rel: str) -> str:
        src = self._sources.get(rel)
        if src is None:
            with open(self.path(rel), encoding="utf-8") as f:
                src = f.read()
            self._sources[rel] = src
        return src

    def tree(self, rel: str) -> Optional[ast.Module]:
        """Parsed AST for ``rel``, or None on a syntax error (recorded
        in :attr:`parse_errors` — the engine reports those as findings
        so a broken file can never silently drop out of every scope)."""
        if rel in self._trees:
            return self._trees[rel]
        if rel in self.parse_errors:
            return None
        if not os.path.isfile(self.path(rel)):
            # rules may probe well-known paths (custodian modules)
            # that a partial tree simply lacks
            return None
        try:
            tree = ast.parse(self.source(rel), filename=rel)
        except SyntaxError as e:
            self.parse_errors[rel] = f"{type(e).__name__}: {e}"
            return None
        self._trees[rel] = tree
        return tree

    def read_text(self, rel: str) -> Optional[str]:
        """Raw text of an arbitrary repo-relative file (docs etc.), or
        None when it does not exist."""
        p = os.path.join(self.root, rel.replace("/", os.sep))
        if not os.path.isfile(p):
            return None
        with open(p, encoding="utf-8") as f:
            return f.read()
