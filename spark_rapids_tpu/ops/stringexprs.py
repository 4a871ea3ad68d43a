"""String expressions.

Capability parity with the reference's stringFunctions.scala: Upper, Lower,
InitCap, StringLocate, Substring, SubstringIndex, StringReplace, Trim
family, StartsWith, EndsWith, Contains, Concat, Like, RegExpReplace,
Length.

Device path: ops with static output width run on the fixed-width byte
matrix (kernels/stringkernels.py).  Regex-class ops (Like, RegExpReplace,
InitCap, SubstringIndex, StringReplace) evaluate on the host engine only —
the same bail-out the reference takes for unsupported regex escapes
(GpuOverrides.scala:326-371).
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

from .. import types as T
from ..data.column import DeviceColumn, HostColumn
from ..utils.tracing import device_phase
from .expression import (
    Expression,
    Literal,
    Scalar,
    as_device_column,
    as_host_column,
)
from .kernels import stringkernels as sk


def _host_str_map(col: HostColumn, fn) -> np.ndarray:
    n = col.num_rows
    out = np.empty(n, dtype=object)
    valid = col.is_valid()
    for i in range(n):
        if valid[i] and col.data[i] is not None:
            out[i] = fn(col.data[i])
    return out


class _StrUnary(Expression):
    """String->string unary with host fn + optional device kernel."""

    def __init__(self, child):
        super().__init__([child])

    @property
    def dtype(self):
        return T.STRING

    def host_fn(self, s: str) -> str:
        raise NotImplementedError

    def device_kernel(self, bm, lengths):
        return None

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        return HostColumn(T.STRING, _host_str_map(c, self.host_fn),
                          c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        result = self.device_kernel(c.data, c.lengths)
        if result is None:
            raise NotImplementedError
        bm, ln = result
        return DeviceColumn(T.STRING, bm, c.validity, ln)

    @property
    def tpu_supported(self):
        try:
            import jax.numpy as jnp  # noqa: F401

            probe = self.device_kernel.__func__ is not _StrUnary.device_kernel
        except Exception:  # noqa: BLE001
            probe = False
        return probe


class Upper(_StrUnary):
    """ASCII uppercase on device (documented incompat for non-ASCII,
    mirroring the reference's incompat annotation on cudf upper)."""

    def host_fn(self, s):
        return s.upper()

    def device_kernel(self, bm, lengths):
        return sk.upper(bm, lengths)


class Lower(_StrUnary):
    def host_fn(self, s):
        return s.lower()

    def device_kernel(self, bm, lengths):
        return sk.lower(bm, lengths)


class InitCap(_StrUnary):
    def host_fn(self, s):
        return " ".join(w[:1].upper() + w[1:].lower() if w else w
                        for w in s.split(" "))


class StringTrim(_StrUnary):
    side = "both"

    def host_fn(self, s):
        if self.side == "both":
            return s.strip(" ")
        return s.lstrip(" ") if self.side == "left" else s.rstrip(" ")

    def device_kernel(self, bm, lengths):
        return sk.trim_ws(bm, lengths, bm.shape[1],
                          left=self.side in ("both", "left"),
                          right=self.side in ("both", "right"))


class StringTrimLeft(StringTrim):
    side = "left"


class StringTrimRight(StringTrim):
    side = "right"


class Length(Expression):
    def __init__(self, child):
        super().__init__([child])

    @property
    def dtype(self):
        return T.INT32

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        n = c.num_rows
        out = np.zeros(n, dtype=np.int32)
        valid = c.is_valid()
        for i in range(n):
            if valid[i] and c.data[i] is not None:
                out[i] = len(c.data[i])
        return HostColumn(T.INT32, out, c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        return DeviceColumn(T.INT32, sk.length(c.data, c.lengths),
                            c.validity)


class Substring(Expression):
    """substring(str, pos, len) — pos is 1-based; 0 behaves like 1;
    negative counts from the end (Spark semantics)."""

    def __init__(self, child, pos: int, length: Optional[int] = None):
        super().__init__([child])
        self.pos = int(pos)
        self.length = int(length) if length is not None else None

    @property
    def dtype(self):
        return T.STRING

    def _py(self, s: str) -> str:
        pos, ln = self.pos, self.length
        if pos > 0:
            start = pos - 1
        elif pos == 0:
            start = 0
        else:
            start = max(len(s) + pos, 0)
        end = len(s) if ln is None else start + max(ln, 0)
        return s[start:end]

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        return HostColumn(T.STRING, _host_str_map(c, self._py), c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        start = self.pos - 1 if self.pos > 0 else (0 if self.pos == 0
                                                   else self.pos)
        ln = self.length if self.length is not None else c.data.shape[1]
        out_w = min(max(ln, 1), c.data.shape[1])
        bm, lens = sk.substring(c.data, c.lengths, start, ln, out_w)
        return DeviceColumn(T.STRING, bm, c.validity, lens)

    @property
    def tpu_supported(self):
        # byte==char only for ASCII; multibyte falls back (documented)
        return True


class SubstringIndex(Expression):
    def __init__(self, child, delim: str, count: int):
        super().__init__([child])
        self.delim = delim
        self.count = count

    @property
    def dtype(self):
        return T.STRING

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)

        def fn(s):
            parts = s.split(self.delim)
            if self.count > 0:
                return self.delim.join(parts[: self.count])
            if self.count < 0:
                return self.delim.join(parts[self.count:])
            return ""

        return HostColumn(T.STRING, _host_str_map(c, fn), c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        bm, ln = sk.substring_index(
            c.data, c.lengths, self.delim.encode("utf-8"), self.count)
        return DeviceColumn(T.STRING, bm, c.validity, ln)

    @property
    def tpu_supported(self):
        # single-byte delimiters cannot self-overlap, so the device
        # match-count kernel is exact vs str.split; multi-byte
        # delimiters stay on host
        return len(self.delim.encode("utf-8")) == 1 and \
            self.children[0].tpu_supported


class StringReplace(Expression):
    def __init__(self, child, search: str, replace: str):
        super().__init__([child])
        self.search = search
        self.replace = replace

    @property
    def dtype(self):
        return T.STRING

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        return HostColumn(
            T.STRING,
            _host_str_map(c, lambda s: s.replace(self.search, self.replace)),
            c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        bm, ln = sk.replace_single(c.data, c.lengths,
                                   self.search.encode("utf-8"),
                                   self.replace.encode("utf-8"))
        return DeviceColumn(T.STRING, bm, c.validity, ln)

    @property
    def tpu_supported(self):
        # a single search byte cannot self-overlap -> exact on device;
        # longer patterns stay on host
        return len(self.search.encode("utf-8")) == 1 and \
            self.children[0].tpu_supported


class _NeedlePredicate(Expression):
    """contains/startswith/endswith with literal needle."""

    kernel = None  # set in subclass
    py_fn = None

    def __init__(self, child, needle):
        super().__init__([child, needle if isinstance(needle, Expression)
                          else Literal(needle, T.STRING)])

    @property
    def dtype(self):
        return T.BOOL

    def _needle(self) -> Optional[str]:
        n = self.children[1]
        if isinstance(n, Literal):
            return n.value
        return None

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        needle = self._needle()
        n = c.num_rows
        out = np.zeros(n, dtype=np.bool_)
        valid = c.is_valid()
        for i in range(n):
            if valid[i] and c.data[i] is not None:
                out[i] = type(self).py_fn(c.data[i], needle)
        return HostColumn(T.BOOL, out, c.validity)

    def eval_tpu(self, batch):
        needle = self._needle()
        if needle is None:
            raise NotImplementedError("non-literal needle")
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        data = type(self).kernel(c.data, c.lengths, needle.encode("utf-8"))
        return DeviceColumn(T.BOOL, data, c.validity)

    @property
    def tpu_supported(self):
        return self._needle() is not None


class Contains(_NeedlePredicate):
    kernel = staticmethod(sk.contains)
    py_fn = staticmethod(lambda s, n: n in s)


class StartsWith(_NeedlePredicate):
    kernel = staticmethod(sk.startswith)
    py_fn = staticmethod(lambda s, n: s.startswith(n))


class EndsWith(_NeedlePredicate):
    kernel = staticmethod(sk.endswith)
    py_fn = staticmethod(lambda s, n: s.endswith(n))


class StringLocate(Expression):
    """locate(substr, str, pos) — 1-based, 0 when absent."""

    def __init__(self, substr: str, child, pos: int = 1):
        super().__init__([child])
        self.substr = substr
        self.pos = pos

    @property
    def dtype(self):
        return T.INT32

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        n = c.num_rows
        out = np.zeros(n, dtype=np.int32)
        valid = c.is_valid()
        for i in range(n):
            if valid[i] and c.data[i] is not None:
                out[i] = c.data[i].find(self.substr, self.pos - 1) + 1
        return HostColumn(T.INT32, out, c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        data = sk.locate(c.data, c.lengths, self.substr.encode("utf-8"),
                         self.pos)
        return DeviceColumn(T.INT32, data, c.validity)


class ConcatStrings(Expression):
    def __init__(self, exprs):
        super().__init__(list(exprs))

    @property
    def dtype(self):
        return T.STRING

    def eval_cpu(self, batch):
        n = batch.num_rows
        cols = [as_host_column(e.eval_cpu(batch), n) for e in self.children]
        out = np.empty(n, dtype=object)
        validity = np.ones(n, dtype=np.bool_)
        for c in cols:
            validity &= c.is_valid()
        for i in range(n):
            if validity[i]:
                out[i] = "".join(str(c.data[i]) for c in cols)
        return HostColumn(T.STRING, out,
                          None if validity.all() else validity)

    def eval_tpu(self, batch):
        import jax.numpy as jnp

        n = batch.padded_rows
        cols = [as_device_column(e.eval_tpu(batch), n)
                for e in self.children]
        bm, ln = sk.concat([(c.data, c.lengths) for c in cols])
        validity = jnp.ones((n,), dtype=jnp.bool_)
        for c in cols:
            validity = validity & c.validity
        return DeviceColumn(T.STRING, bm, validity, ln)


class Like(Expression):
    """SQL LIKE with literal pattern.

    Device path (reference: the cudf regex translation with escape
    bail-outs, stringFunctions.scala Like + rules
    GpuOverrides.scala:326-371): patterns built only from literal text
    and ``%`` lower onto the byte-matrix kernels — prefix/suffix/
    contains and the general multi-``%`` shape via greedy leftmost
    segment matching (correct for ``%`` because it matches any length).
    Patterns using ``_`` (single-char, character-based) bail out to the
    exact host regex, mirroring the reference's bail-outs.  Byte-level
    segment matching is exact for valid UTF-8 (self-synchronizing: a
    valid segment cannot match starting mid-character)."""

    def __init__(self, child, pattern: str, escape: str = "\\"):
        super().__init__([child])
        self.pattern = pattern
        self.escape = escape
        self._re = re.compile(self._to_regex(pattern, escape), re.DOTALL)
        self._match = self._re.match  # LIKE regex is ^…$-anchored
        self._segs = self._parse_segments(pattern, escape)

    @staticmethod
    def _parse_segments(pattern: str, escape: str):
        """Split into literal byte segments on unescaped ``%``.
        Returns None when the pattern uses ``_`` — host regex only."""
        segs, cur, i = [], [], 0
        while i < len(pattern):
            ch = pattern[i]
            if ch == escape and i + 1 < len(pattern):
                cur.append(pattern[i + 1])
                i += 2
                continue
            if ch == "%":
                segs.append("".join(cur))
                cur = []
            elif ch == "_":
                return None
            else:
                cur.append(ch)
            i += 1
        segs.append("".join(cur))
        return [s.encode("utf-8") for s in segs]

    @staticmethod
    def _to_regex(pattern: str, escape: str) -> str:
        out, i = ["^"], 0
        while i < len(pattern):
            ch = pattern[i]
            if ch == escape and i + 1 < len(pattern):
                out.append(re.escape(pattern[i + 1]))
                i += 2
                continue
            if ch == "%":
                out.append(".*")
            elif ch == "_":
                out.append(".")
            else:
                out.append(re.escape(ch))
            i += 1
        out.append("$")
        return "".join(out)

    @property
    def dtype(self):
        return T.BOOL

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        n = c.num_rows
        out = np.zeros(n, dtype=np.bool_)
        valid = c.is_valid()
        for i in range(n):
            if valid[i] and c.data[i] is not None:
                out[i] = self._match(c.data[i]) is not None
        return HostColumn(T.BOOL, out, c.validity)

    def eval_tpu(self, batch):
        c = as_device_column(self.children[0].eval_tpu(batch),
                             batch.padded_rows)
        with device_phase("strings.match"):
            ok = self._match_device(c.data, c.lengths)
        return DeviceColumn(T.BOOL, ok, c.validity)

    def _match_device(self, bm, ln):
        """bool[n]: the row's logical bytes match the pattern (null and
        padding rows are the caller's, through the validity)."""
        import jax.numpy as jnp

        segs = self._segs
        n = bm.shape[0]
        if len(segs) == 1:
            # no wildcard at all: exact (length + prefix) equality
            needle = segs[0]
            return sk.startswith(bm, ln, needle) & (ln == len(needle))
        first, last, mids = segs[0], segs[-1], segs[1:-1]
        ok = (sk.startswith(bm, ln, first) if first
              else jnp.ones((n,), dtype=jnp.bool_))
        cursor = jnp.full((n,), len(first), dtype=jnp.int32)
        for seg in mids:
            if not seg:
                continue
            pos1 = sk.locate_from(bm, ln, seg, cursor)
            ok = ok & (pos1 > 0)
            cursor = jnp.where(pos1 > 0, pos1 - 1 + len(seg), cursor)
        if last:
            return ok & sk.endswith(bm, ln, last) & \
                (ln - len(last) >= cursor)
        return ok & (ln >= cursor)

    @property
    def tpu_supported(self):
        # %-only patterns lower onto the byte-matrix kernels; `_`
        # (character-based) bails out to the host regex
        return self._segs is not None and self.children[0].tpu_supported


class RegExpReplace(Expression):
    def __init__(self, child, pattern: str, replacement: str):
        super().__init__([child])
        self.pattern = pattern
        self.replacement = replacement
        self._re = re.compile(pattern)

    @property
    def dtype(self):
        return T.STRING

    def eval_cpu(self, batch):
        c = as_host_column(self.children[0].eval_cpu(batch), batch.num_rows)
        repl = re.sub(r"\$(\d)", r"\\\1", self.replacement)
        return HostColumn(
            T.STRING,
            _host_str_map(c, lambda s: self._re.sub(repl, s)),
            c.validity)
