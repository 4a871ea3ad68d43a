"""Per-kernel dispatch counts.

Every ``jit_kernel`` dispatch in ``exec/kernel_cache.py`` reports to the
process-global :data:`PROFILER` (mirroring the KernelCache GLOBAL): per
kernel *fingerprint* it accumulates dispatch count, enqueue wall, input
and output rows/bytes, and the padding waste from power-of-two shape
bucketing: counts that follow from shapes and are exact on any backend.
What a kernel's *device* seconds and bytes a second are is not here (an
enqueue's wall on an asynchronous device is neither): that is
``telemetry/device_trace.py``, from a profiler trace.

Hot-path discipline (enforced by the ``profiler-guard`` and
``host-sync`` analysis rules):

* the disabled cost is ONE attribute read (``PROFILER.enabled``) per
  dispatch — no allocation, no locking;
* the enabled path reads only shape-derived metadata (``padded_rows``,
  ``device_bytes()``, ``nbytes``) — never ``block_until_ready`` /
  ``np.asarray`` or anything else that would force a host sync.  A
  batch's logical ``num_rows`` is counted only when it is a plain
  Python int (kernel *outputs* can carry traced/device scalars there).

``wall_ns`` is the host's wall around the enqueue (first-shape
dispatches include compile): a host cost, never divided into a rate.
"""
from __future__ import annotations

import hashlib
import threading
from typing import Callable, Dict, List, Optional, Tuple


def kernel_fingerprint(key, fn: Callable) -> str:
    """Stable human-readable fingerprint for a kernel-cache entry.

    ``<head>#<md5-6>`` where head is the operator kind from the cache
    key (or the function's qualname for anonymous kernels) and the
    suffix is a deterministic content hash of the full key — stable
    across processes (unlike ``hash()``) so profiles from different
    runs can be diffed kernel-by-kernel.
    """
    if key is None:
        head = fn.__qualname__.replace("<locals>.", "")
        return f"{head}#anon"
    head = key[0] if (isinstance(key, tuple) and key
                      and isinstance(key[0], str)) else \
        fn.__qualname__.replace("<locals>.", "")
    digest = hashlib.md5(repr(key).encode()).hexdigest()[:6]
    return f"{head}#{digest}"


class KernelStat:
    """Accumulated counters for one kernel fingerprint."""

    __slots__ = ("dispatches", "wall_ns", "in_rows", "in_padded",
                 "in_padded_known", "in_bytes", "out_padded", "out_bytes")

    def __init__(self):
        self.dispatches = 0
        self.wall_ns = 0
        self.in_rows = 0          # logical rows (only when known host-side)
        self.in_padded = 0        # padded rows over ALL dispatches
        self.in_padded_known = 0  # padded rows over rows-known dispatches
        self.in_bytes = 0
        self.out_padded = 0
        self.out_bytes = 0

    def as_tuple(self) -> Tuple[int, ...]:
        return (self.dispatches, self.wall_ns, self.in_rows,
                self.in_padded, self.in_padded_known, self.in_bytes,
                self.out_padded, self.out_bytes)

    @classmethod
    def from_delta(cls, cur: Tuple[int, ...],
                   base: Optional[Tuple[int, ...]]) -> "KernelStat":
        st = cls()
        vals = (cur if base is None
                else tuple(c - b for c, b in zip(cur, base)))
        (st.dispatches, st.wall_ns, st.in_rows, st.in_padded,
         st.in_padded_known, st.in_bytes, st.out_padded,
         st.out_bytes) = vals
        return st

    @property
    def padding_waste(self) -> float:
        """Fraction of padded input rows that carry no logical row
        (over dispatches whose logical row count was known)."""
        if self.in_padded_known <= 0:
            return 0.0
        return max(0.0, 1.0 - self.in_rows / float(self.in_padded_known))


def _measure(values) -> Tuple[int, int, int, int]:
    """(logical_rows, padded_rows, padded_rows_known, bytes) over a
    flat sequence of kernel args/outputs.  Shape-metadata only."""
    rows = padded = padded_known = nbytes = 0
    for v in values:
        pr = getattr(v, "padded_rows", None)
        if pr is not None:                       # DeviceBatch-like
            db = v.device_bytes()
            nbytes += int(db)
            padded += int(pr)
            nr = v.num_rows
            if type(nr) is int:                  # traced scalars excluded
                rows += nr
                padded_known += int(pr)
            continue
        nb = getattr(v, "nbytes", None)
        if nb is not None and not isinstance(v, (int, float, bool)):
            try:
                nbytes += int(nb)
                shape = v.shape
                if shape:
                    padded += int(shape[0])
            except Exception:  # noqa: BLE001 - abstract/deleted arrays
                pass
    return rows, padded, padded_known, nbytes


class KernelProfiler:
    """Process-global per-kernel dispatch accumulator (see module doc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, KernelStat] = {}
        self.enabled = False

    # ---------------- configuration / lifecycle -----------------------
    def configure(self, conf) -> None:
        from ..config import TELEMETRY_PROFILER_ENABLED

        self.enabled = bool(conf.get(TELEMETRY_PROFILER_ENABLED))

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self.enabled = False

    # ---------------- hot-path recorders ------------------------------
    def record_dispatch(self, fingerprint: str, wall_ns: int,
                        args, out) -> None:
        """Account one jit dispatch.  Exception-safe; shape-metadata
        reads only (never forces a host sync)."""
        try:
            in_rows, in_padded, in_known, in_bytes = _measure(args)
            out_vals = out if isinstance(out, (tuple, list)) else (out,)
            _, out_padded, _, out_bytes = _measure(out_vals)
            with self._lock:
                st = self._stats.get(fingerprint)
                if st is None:
                    st = self._stats[fingerprint] = KernelStat()
                st.dispatches += 1
                st.wall_ns += wall_ns
                st.in_rows += in_rows
                st.in_padded += in_padded
                st.in_padded_known += in_known
                st.in_bytes += in_bytes
                st.out_padded += out_padded
                st.out_bytes += out_bytes
        except Exception:  # noqa: BLE001 - profiling must never fail a query
            pass

    # ---------------- snapshots / per-query deltas ---------------------
    def mark(self) -> Dict[str, Tuple[int, ...]]:
        """Counter snapshot for a later :meth:`since` delta (taken at
        query start, like KernelCache.counters())."""
        if not self.enabled:
            return {}
        with self._lock:
            return {fp: st.as_tuple() for fp, st in self._stats.items()}

    def since(self, mark: Optional[Dict[str, Tuple[int, ...]]]
              ) -> Dict[str, KernelStat]:
        """Per-kernel deltas since ``mark`` (kernels with no new
        dispatches are dropped)."""
        with self._lock:
            cur = {fp: st.as_tuple() for fp, st in self._stats.items()}
        out: Dict[str, KernelStat] = {}
        for fp, tup in cur.items():
            st = KernelStat.from_delta(tup, (mark or {}).get(fp))
            if st.dispatches > 0:
                out[fp] = st
        return out

    def snapshot(self) -> Dict[str, KernelStat]:
        return self.since(None)


def dispatch_rows(stats: Dict[str, KernelStat],
                  top_n: Optional[int] = None) -> List[dict]:
    """One dict per kernel, most bytes first — the rows
    ``Session.profile_report()`` renders."""
    rows = [{
        "kernel": fp,
        "dispatches": st.dispatches,
        "enqueue_s": round(st.wall_ns / 1e9, 6),
        "rows": st.in_rows,
        "padded_rows": st.in_padded,
        "bytes": st.in_bytes + st.out_bytes,
        "padding_waste": round(st.padding_waste, 4),
    } for fp, st in stats.items()]
    rows.sort(key=lambda r: (-r["bytes"], r["kernel"]))
    return rows[:top_n] if top_n else rows


def _fmt_count(v: float) -> str:
    if v >= 1e9:
        return f"{v / 1e9:.2f}G"
    if v >= 1e6:
        return f"{v / 1e6:.2f}M"
    if v >= 1e3:
        return f"{v / 1e3:.2f}K"
    return f"{v:.0f}"


def render_dispatches(stats: Dict[str, KernelStat],
                      top_n: int = 10) -> List[str]:
    """Text table for Session.profile_report()."""
    lines = ["-- Kernel dispatches --"]
    rows = dispatch_rows(stats, top_n=top_n)
    if not rows:
        lines.append("  (no kernel dispatches recorded)")
        return lines
    lines.append(f"  {'kernel':<34} {'disp':>5} {'enqueue':>9} {'rows':>9} "
                 f"{'padded':>9} {'bytes':>9} {'waste':>6}")
    for r in rows:
        lines.append(
            f"  {r['kernel'][:34]:<34} {r['dispatches']:>5} "
            f"{r['enqueue_s'] * 1e3:>7.1f}ms {_fmt_count(r['rows']):>9} "
            f"{_fmt_count(r['padded_rows']):>9} "
            f"{_fmt_count(r['bytes']):>8}B "
            f"{r['padding_waste'] * 100:>5.1f}%")
    return lines


#: THE process-wide profiler instance (analogue: KernelCache.GLOBAL)
PROFILER = KernelProfiler()
