"""What ran on the device, by the names the program gave it.

``jax.profiler`` writes an ``.xplane.pb``; on a TPU every event of a
device plane's ``XLA Ops`` line points at a metadata entry that carries
the op's ``tf_op`` (JAX's op path with the program's
``utils/tracing.device_phase`` scopes in it:
``jit(agg_batch)/TpuHashAggregate/lexsort/while/body/gather:``),
``source`` (file:line), ``bytes_accessed``, ``hlo_category`` and
``program_id`` (the fingerprint in the ``XLA Modules`` event's name),
and the plane itself ``peak_hbm_bw_gigabytes_per_second``.
``jax.profiler.ProfileData`` hands out none of the metadata's stats, so
this module reads the file's wire format itself (six message types of
``xplane.proto``: XSpace, XPlane, XLine, XEvent, XStat and the two
metadata entries), with nothing but the standard library.

The reduction: for each device, program (``jit_agg_batch``: the module
name without its fingerprint) and key — the op's **phase** (innermost
``device_phase`` scope of :data:`~spark_rapids_tpu.utils.tracing.DEVICE_PHASES`),
**operator** (outermost ``Tpu...`` scope), JAX **primitive** (last
component of ``tf_op``), **source** line or **tier** (``segment.reduce_sorted``'s
``readTier.<rows>`` scope: which read width a group-by's segment count
chose on the device, ``--by tier --within segments``; ``gather.take_rows``'
``readWords.<k>`` and ``readOwn``: a stacked read of ``k`` 32-bit words or
a part read alone, ``--by tier --within reorder``) — seconds, op count,
``bytes_accessed``, GB/s and the share of the plane's peak bandwidth.
Seconds are leaf seconds: an event that wraps others (a ``while`` around
its body) adds only the time in which none of them ran, so the keys of
one program sum to its ops' busy time and nothing counts twice; ops and
bytes count leaf events alone.  Optionally clipped to a window and
divided by requests (the benchmark's ``bench.query`` markers).

Two surfaces: ``Session.profile_report(device_trace=<path>)`` renders
:func:`render` as the report's ``-- Device phases --`` section, and

    python -m spark_rapids_tpu.telemetry.device_trace <xplane> \\
        [--program jit_agg_batch] \\
        [--by phase|operator|primitive|source|tier] [--within segments]

A persistent compile cache that holds executables compiled by a tree
without the scopes hands them back with that tree's metadata: the scopes
are not part of the cache's key (docs/profiling.md).
"""
from __future__ import annotations

import functools
import gzip
import re
import struct
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from ..utils.tracing import DEVICE_PHASES, READ_TAGS

#: the key of ops that stand under no scope of the asked kind
UNSCOPED = "(unscoped)"
#: the key of the compiler's own ops, which carry no op path at all (an
#: output fusion, a layout copy): no scope of the program's can reach them
NO_PATH = "(no op path)"
#: the benchmark's span around one traced request (host plane)
MARKER = "bench.query"
BY = ("phase", "operator", "primitive", "source", "tier")

_DEVICE = re.compile(r"/device:TPU:(\d+)")
_FINGERPRINT = re.compile(r"\((\d+)\)$")
_OPERATOR = re.compile(r"Tpu[A-Z]\w*")


# --------------------------------------------------------------------------
# the wire format
# --------------------------------------------------------------------------
def _varint(buf, at):
    out = shift = 0
    while True:
        byte = buf[at]
        at += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, at
        shift += 7


def _encoded(value):
    out = bytearray()
    while value >= 0x80:
        out.append(value & 0x7F | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def _fields(buf, at, end):
    """(field number, wire type, value) of one message: a varint's
    value, or the (start, end) of a length-delimited or fixed field."""
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = (at, at + size), at + size
        elif wire == 1:
            value, at = (at, at + 8), at + 8
        elif wire == 5:
            value, at = (at, at + 4), at + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {at}")
        yield key >> 3, wire, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(value):
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span):
    """(stat metadata id, value) of one XStat; a ``ref_value`` comes as
    ``("ref", id)`` for the plane's stat names to resolve."""
    key = value = None
    for field, wire, v in _fields(buf, *span):
        if field == 1:
            key = v
        elif field == 2:
            value = struct.unpack_from("<d", buf, v[0])[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = _text(buf, v)
        elif field == 7:
            value = ("ref", v)
    return key, value


def _map_entry(buf, span):
    """The value message's span of one ``map<int64, Message>`` entry."""
    for field, _, v in _fields(buf, *span):
        if field == 2:
            return v
    return None


def _event(buf, span):
    """(metadata id, offset ps, duration ps) of one XEvent."""
    meta = offset = duration = 0
    for field, wire, v in _fields(buf, *span):
        if wire:
            continue
        if field == 1:
            meta = v
        elif field == 2:
            offset = v
        elif field == 3:
            duration = v
    return meta, offset, duration


class _Plane:
    """One XPlane, split into its parts and parsed no further."""

    def __init__(self, buf, span):
        self.buf = buf
        self.name = ""
        self.lines, self._events, self._stat_names, self._stats = \
            [], [], [], []
        for field, _, v in _fields(buf, *span):
            if field == 2:
                self.name = _text(buf, v)
            elif field == 3:
                self.lines.append(v)
            elif field == 4:
                self._events.append(v)
            elif field == 5:
                self._stat_names.append(v)
            elif field == 6:
                self._stats.append(v)

    def stat_names(self) -> Dict[int, str]:
        out = {}
        for entry in self._stat_names:
            ident, name = 0, ""
            for field, _, v in _fields(self.buf, *_map_entry(self.buf,
                                                             entry)):
                if field == 1:
                    ident = v
                elif field == 2:
                    name = _text(self.buf, v)
            out[ident] = name
        return out

    def stats(self, names) -> Dict[str, object]:
        return {names.get(k, k): v
                for k, v in (_stat(self.buf, s) for s in self._stats)}

    def event_metadata(self, names) -> Dict[int, dict]:
        """{id: {"name", "display", <stat name>: value ...}}."""
        out = {}
        for entry in self._events:
            meta = {"name": "", "display": ""}
            ident = 0
            for field, _, v in _fields(self.buf, *_map_entry(self.buf,
                                                             entry)):
                if field == 1:
                    ident = v
                elif field == 2:
                    meta["name"] = _text(self.buf, v)
                elif field == 4:
                    meta["display"] = _text(self.buf, v)
                elif field == 5:
                    key, value = _stat(self.buf, v)
                    if isinstance(value, tuple):
                        value = names.get(value[1], "")
                    meta[names.get(key, key)] = value
            out[ident] = meta
        return out

    def line(self, span, only=None):
        """(name, [(metadata id, start ps, end ps)]) of one XLine;
        ``only``: the metadata ids wanted (a host line holds a million
        events of the Python tracer's: an event is written id first, so
        the others are told by their leading bytes and not parsed)."""
        buf = self.buf
        name, stamp_ns, events = "", 0, []
        lead = None if only is None else tuple(
            b"\x08" + _encoded(i) for i in only)
        for field, _, v in _fields(buf, *span):
            if field == 2:
                name = _text(buf, v)
            elif field == 3:
                stamp_ns = v
            elif field == 4 and (lead is None or bytes(
                    buf[v[0]:v[0] + 11]).startswith(lead)):
                events.append(v)
        base = stamp_ns * 1000
        out = []
        for ev in events:
            meta, offset, duration = _event(buf, ev)
            out.append((meta, base + offset, base + offset + duration))
        return name, out


# --------------------------------------------------------------------------
# the trace
# --------------------------------------------------------------------------
class Op(NamedTuple):
    """One event of a device's ``XLA Ops`` line.  Times in picoseconds."""
    start: int
    end: int
    program: str        # ``jit_agg_batch``; "" where the op names none
    tf_op: str          # ``jit(agg_batch)/lexsort/while/body/gather:``
    source: str         # ``.../ops/kernels/segment.py:416``
    bytes_accessed: int
    category: str       # ``hlo_category``


class Device(NamedTuple):
    peak_hbm_gbps: float
    ops: List[Op]
    modules: List[Tuple[int, int, str]]     # (start ps, end ps, program)


class DeviceTrace(NamedTuple):
    devices: Dict[int, Device]
    #: the host's marker spans, (start ps, end ps), in order
    markers: List[Tuple[int, int]]

    @property
    def window(self) -> Optional[Tuple[int, int]]:
        if not self.markers:
            return None
        return (min(m[0] for m in self.markers),
                max(m[1] for m in self.markers))


def module_name(event_name: str) -> str:
    """``jit_agg_batch(10167472635018975354)`` -> ``jit_agg_batch``."""
    return _FINGERPRINT.sub("", event_name)


def _device(plane: _Plane) -> Device:
    names = plane.stat_names()
    meta = plane.event_metadata(names)
    peak = plane.stats(names).get("peak_hbm_bw_gigabytes_per_second", 0.0)
    lines = dict(plane.line(span) for span in plane.lines)
    programs = {}       # fingerprint -> program
    for m in meta.values():
        found = _FINGERPRINT.search(m["name"])
        if found:
            programs[int(found.group(1))] = module_name(m["name"])
    modules = [(s, e, module_name(meta[i]["name"]))
               for i, s, e in lines.get("XLA Modules", ()) if i in meta]
    ops = []
    for i, s, e in lines.get("XLA Ops", ()):
        m = meta.get(i, {})
        ops.append(Op(s, e, programs.get(m.get("program_id"), ""),
                      m.get("tf_op") or "", m.get("source") or "",
                      int(m.get("bytes_accessed") or 0),
                      m.get("hlo_category") or ""))
    return Device(float(peak), ops, modules)


def _markers(plane: _Plane, marker: str) -> List[Tuple[int, int]]:
    wanted = {i for i, m in plane.event_metadata({}).items()
              if m["name"] == marker}
    if not wanted:
        return []
    return sorted((s, e) for span in plane.lines
                  for _, s, e in plane.line(span, only=wanted)[1])


def load(path: str, marker: Optional[str] = MARKER) -> DeviceTrace:
    """Read an ``.xplane.pb`` or ``.xplane.pb.gz``.  ``marker=None``
    leaves the host plane unread (the caller brings its own window)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = memoryview(f.read())
    devices, markers = {}, []
    for field, _, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        plane = _Plane(buf, span)
        found = _DEVICE.fullmatch(plane.name)
        if found:
            devices[int(found.group(1))] = _device(plane)
        elif marker and plane.name == "/host:CPU":
            markers = _markers(plane, marker)
    return DeviceTrace(devices, markers)


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------
def scopes(tf_op: str) -> List[str]:
    """The components of an op path, outermost first, without the
    trailing primitive: ``jit(f)/TpuFilter/reorder/gather:`` ->
    ``["jit(f)", "TpuFilter", "reorder"]``."""
    return tf_op.rstrip(":").split("/")[:-1]


def phase_of(tf_op: str, phases: Iterable[str] = DEVICE_PHASES) -> str:
    return next((c for c in reversed(scopes(tf_op)) if c in phases),
                UNSCOPED if tf_op else NO_PATH)


def operator_of(tf_op: str) -> str:
    return next((c for c in scopes(tf_op) if _OPERATOR.fullmatch(c)),
                UNSCOPED if tf_op else NO_PATH)


def tier_of(tf_op: str) -> str:
    """``.../segments/cond/branch_0_fun/readTier.65536/gather:`` ->
    ``readTier.65536``: the width of the read the op belongs to;
    ``.../reorder/readWords.6/gather:`` -> ``readWords.6`` (one stacked
    gather of six 32-bit words), ``readOwn`` (a part read alone)."""
    return next((c for c in scopes(tf_op) if c.startswith(READ_TAGS)),
                UNSCOPED if tf_op else NO_PATH)


def primitive_of(tf_op: str) -> str:
    return tf_op.rstrip(":").rsplit("/", 1)[-1] or NO_PATH


def _key_of(by: str, phases):
    """op -> its key; a path's key is worked out once (a loop's body
    repeats its few paths thousands of times)."""
    if by == "source":
        return lambda op: op.source or NO_PATH
    of_path = {"phase": lambda path: phase_of(path, phases),
               "operator": operator_of, "primitive": primitive_of,
               "tier": tier_of}.get(by)
    if of_path is None:
        raise ValueError(f"by={by!r}: one of {', '.join(BY)}")
    of_path = functools.lru_cache(maxsize=None)(of_path)
    return lambda op: of_path(op.tf_op)


class Row(NamedTuple):
    seconds: float      # leaf seconds (÷ queries)
    ops: float          # leaf events (÷ queries)
    bytes_accessed: float
    gbps: float         # bytes_accessed ÷ seconds ÷ 1e9
    peak_share: float   # gbps ÷ the plane's peak, in percent


def _leaf_times(ops: List[Op], window):
    """(op, picoseconds in which it ran and nothing inside it did, is a
    leaf) for every op that touches the window, clipped to it."""
    lo, hi = window if window else (-(1 << 62), 1 << 62)
    clipped = sorted(((max(op.start, lo), min(op.end, hi), op)
                      for op in ops if op.end > lo and op.start < hi),
                     key=lambda t: (t[0], -t[1]))
    out, stack = [], []     # stack of indices into ``out``
    for s, e, op in clipped:
        while stack and out[stack[-1]][0] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            e = min(e, parent[0])       # a child never outlives its parent
            parent[2] -= e - s
            parent[3] = False
            if not parent[1].tf_op and "/while/" in op.tf_op:
                # a loop the compiler left without a path stands where
                # its body does
                parent[1] = parent[1]._replace(
                    tf_op=op.tf_op.rsplit("/while/", 1)[0] + "/while:")
        out.append([e, op, e - s, True])
        stack.append(len(out) - 1)
    return [(op, max(own, 0), leaf) for _, op, own, leaf in out]


def reduce(trace: DeviceTrace, device: int, by: str = "phase",
           program: Optional[str] = None, window=None, queries: int = 1,
           phases: Iterable[str] = DEVICE_PHASES,
           within: Optional[str] = None) -> Dict[str, Dict[str, Row]]:
    """{program: {key: Row}} of one device.  ``window`` is (start,
    end) in picoseconds (``trace.window``: the markers'); ``program``
    keeps one program's ops, ``within`` one phase's (to ask what a
    phase is made of: ``by="primitive", within="segments"``)."""
    dev = trace.devices[device]
    phases = frozenset(phases)
    key_of, phase = _key_of(by, phases), _key_of("phase", phases)
    queries = max(1, queries)
    sums: Dict[str, Dict[str, List[float]]] = {}
    for op, own, leaf in _leaf_times(dev.ops, window):
        if program is not None and op.program != program:
            continue
        if within is not None and phase(op) != within:
            continue
        acc = sums.setdefault(op.program, {}).setdefault(
            key_of(op), [0, 0, 0])
        acc[0] += own
        if leaf:
            acc[1] += 1
            acc[2] += op.bytes_accessed
    out = {}
    for prog, keys in sums.items():
        out[prog] = {}
        for key, (ps, count, nbytes) in keys.items():
            secs = ps / 1e12
            gbps = nbytes / secs / 1e9 if secs > 0 else 0.0
            out[prog][key] = Row(
                secs / queries, count / queries, nbytes / queries, gbps,
                100.0 * gbps / dev.peak_hbm_gbps
                if dev.peak_hbm_gbps else 0.0)
    return out


def module_seconds(trace: DeviceTrace, device: int, window=None,
                   queries: int = 1) -> Dict[str, float]:
    """Seconds of each program's ``XLA Modules`` events (÷ queries): what
    a program's keys are compared with."""
    lo, hi = window if window else (-(1 << 62), 1 << 62)
    out: Dict[str, float] = {}
    for s, e, name in trace.devices[device].modules:
        part = min(e, hi) - max(s, lo)
        if part > 0:
            out[name] = out.get(name, 0.0) + part / 1e12 / max(1, queries)
    return out


def seconds_by_key(trace: DeviceTrace, device: int, by: str,
                   window=None, queries: int = 1) -> Dict[str, float]:
    """{key: leaf seconds (÷ queries)} over every program of the device."""
    out: Dict[str, float] = {}
    for rows in reduce(trace, device, by, window=window,
                       queries=queries).values():
        for key, row in rows.items():
            out[key] = out.get(key, 0.0) + row.seconds
    return out


def seconds_where(trace: DeviceTrace, device: int, by: str, key: str,
                  window=None, queries: int = 1) -> float:
    """Leaf seconds (÷ queries) of the ops whose ``by`` is ``key``, over
    every program of the device."""
    return seconds_by_key(trace, device, by, window, queries).get(key, 0.0)


def busiest(trace: DeviceTrace, window=None) -> Optional[int]:
    """The device whose ops ran longest (None: no device plane)."""
    def busy(d):
        return sum(own for _, own, _ in _leaf_times(trace.devices[d].ops,
                                                    window))

    return max(trace.devices, key=busy, default=None)


# --------------------------------------------------------------------------
# the table
# --------------------------------------------------------------------------
def render(trace: DeviceTrace, by: str = "phase",
           program: Optional[str] = None, device: Optional[int] = None,
           windowed: bool = True, top_n: int = 12,
           within: Optional[str] = None) -> List[str]:
    """The ``-- Device phases --`` lines: a table a program, largest
    program first, each with the seconds its ``XLA Modules`` events
    took beside the sum of its keys."""
    window = trace.window if windowed else None
    queries = len(trace.markers) if window else 1
    if device is None:
        device = busiest(trace, window)
    head = f"-- Device phases (by {by}" + (
        f" within {within}" if within else "")
    if device is None:
        return [head + ") --", "  (no device plane in the trace)"]
    dev = trace.devices[device]
    head += f"; device {device}, peak {dev.peak_hbm_gbps:.2f} GB/s"
    if window:
        head += (f"; {queries} request(s), a request's share of "
                 f"{(window[1] - window[0]) / 1e12:.3f} s")
    lines = [head + ") --"]
    tables = reduce(trace, device, by, program, window, queries,
                    within=within)
    whole = module_seconds(trace, device, window, queries)
    if not tables:
        lines.append("  (no op of "
                     + (program or "any program") + " in the window)")
    for prog in sorted(tables, key=lambda p: -sum(
            r.seconds for r in tables[p].values())):
        rows = tables[prog]
        total = sum(r.seconds for r in rows.values())
        lines.append(f"  {prog or '(no program)'}: {total:.6f} s in ops, "
                     f"{whole.get(prog, 0.0):.6f} s as a module")
        lines.append(f"    {by:<44} {'seconds':>10} {'share':>6} "
                     f"{'ops':>8} {'bytes':>14} {'GB/s':>8} {'%peak':>6}")
        ranked = sorted(rows.items(), key=lambda kv: -kv[1].seconds)
        for key, r in ranked[:top_n]:
            share = 100.0 * r.seconds / total if total else 0.0
            lines.append(
                f"    {key[-44:]:<44} {r.seconds:>10.6f} {share:>5.1f}% "
                f"{r.ops:>8.1f} {r.bytes_accessed:>14.0f} {r.gbps:>8.2f} "
                f"{r.peak_share:>5.1f}%")
        rest = ranked[top_n:]
        if rest:
            lines.append(f"    ({len(rest)} more: "
                         f"{sum(r.seconds for _, r in rest):.6f} s)")
    return lines


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.telemetry.device_trace",
        description="Device seconds of an xplane by phase, operator, JAX "
                    "primitive, source line or read tier, a table a "
                    "program.")
    ap.add_argument("xplane", help="an .xplane.pb or .xplane.pb.gz")
    ap.add_argument("--program", help="one program, e.g. jit_agg_batch")
    ap.add_argument("--by", choices=BY, default="phase")
    ap.add_argument("--within", metavar="PHASE",
                    help="only the ops of one phase, e.g. segments")
    ap.add_argument("--device", type=int,
                    help="a device id (default: the busiest)")
    ap.add_argument("--whole", action="store_true",
                    help="the whole trace, not the window of the "
                         f"{MARKER!r} markers divided by their number")
    ap.add_argument("--top", type=int, default=12,
                    help="rows a program (default 12)")
    args = ap.parse_args(argv)
    trace = load(args.xplane, marker=None if args.whole else MARKER)
    print("\n".join(render(trace, args.by, args.program, args.device,
                           windowed=not args.whole, top_n=args.top,
                           within=args.within)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
