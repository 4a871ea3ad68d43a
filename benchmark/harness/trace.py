"""From the profiler's xplane to numbers: the one reduction every PR uses.

``jax.profiler`` writes ``*.xplane.pb``; ``ProfileData`` reads it with
nothing but JAX.  What is in it (looked at by hand on a v5e, PERF.md):

* a plane ``/device:TPU:<n>`` for each chip, with a line ``XLA Ops``
  (every HLO operation that ran, nested ones inside their ``while``)
  and a line ``XLA Modules`` (every jitted program that ran, named
  ``jit_<function>(<fingerprint>)``);
* a plane ``/host:CPU`` with a line for each host thread.  The
  program's ``jax.profiler.TraceAnnotation`` ranges (``HostToDevice``,
  ``TpuShuffleWrite`` ...) and JAX's own (``np.asarray(jax.Array)``)
  are on the line of the thread that opened them, on the same clock as
  the device lines.

The benchmark opens one ``TraceAnnotation(MARKER)`` around each traced
request.  The traced window is from the first marker's start to the
last one's end, and everything below is clipped to it.  Times inside
are nanoseconds; what is handed out is seconds.
"""
import glob
import gzip
import os
import re
import shutil

#: the benchmark's own span around one traced request
MARKER = "bench.query"

_FINGERPRINT = re.compile(r"\(\d+\)$")


def module_name(event_name):
    """``jit_compute_batch(10167472635018975354)`` -> ``jit_compute_batch``:
    the fingerprint changes with every shape and compile, the name is
    what the program's source calls the function."""
    return _FINGERPRINT.sub("", event_name)


def union(intervals, lo, hi):
    """Merge ``(start, end, ...)`` intervals clipped to [lo, hi] into a
    sorted list of disjoint ``(start, end)``."""
    out = []
    for iv in sorted((max(i[0], lo), min(i[1], hi)) for i in intervals):
        if iv[1] <= iv[0]:
            continue
        if out and iv[0] <= out[-1][1]:
            if iv[1] > out[-1][1]:
                out[-1] = (out[-1][0], iv[1])
        else:
            out.append(iv)
    return out


def gaps(busy, lo, hi):
    """The complement of a disjoint sorted ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def innermost_segments(events):
    """Flatten one thread's properly nested ``(start, end, name)`` spans
    into disjoint ``(start, end, name)`` segments, each named by the
    innermost span open there."""
    out, stack = [], []   # stack of [end, name]; ``at`` = time reached

    def emit(lo, hi, name):
        if hi > lo:
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))

    at = None
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(at, end, top)
            at = max(at, end)
        if stack:
            emit(at, s, stack[-1][1])
            e = min(e, stack[-1][0])    # a child never outlives its parent
        at = s if at is None else max(at, s)
        stack.append([e, name])
    while stack:
        end, top = stack.pop()
        emit(at, end, top)
        at = max(at, end)
    return out


def overlap_by_name(intervals, segments):
    """Seconds of ``intervals`` (disjoint, sorted) under each segment
    name; what no segment covers goes to ``(no host span)``."""
    total, j = {}, 0
    for lo, hi in intervals:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < hi:
            s, e, name = segments[k]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                total[name] = total.get(name, 0.0) + part
                covered += part
            k += 1
        if hi - lo - covered > 0:
            total["(no host span)"] = \
                total.get("(no host span)", 0.0) + hi - lo - covered
    return {k: v / 1e9 for k, v in total.items()}


class Trace:
    """One xplane, reduced as far as the per-layer readers need."""

    def __init__(self, devices, host, marker=MARKER):
        #: {device id: {"ops": [(s, e, name)], "modules": [(s, e, name)]}}
        self.devices = devices
        #: {host line name: [(s, e, name)]}
        self.host = host
        marks = [ev for line in host.values() for ev in line
                 if ev[2] == marker]
        self.queries = len(marks)
        self.window = (min(m[0] for m in marks), max(m[1] for m in marks)) \
            if marks else None
        self.client_line = next((name for name, line in host.items()
                                 if any(ev[2] == marker for ev in line)),
                                None)

    # -- the window ---------------------------------------------------
    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def _clip(self, events):
        lo, hi = self.window
        return [ev for ev in events if ev[1] > lo and ev[0] < hi]

    # -- the device ---------------------------------------------------
    def busy(self, device):
        """Disjoint intervals in which an operation ran on ``device``."""
        return union(self.devices[device]["ops"], *self.window)

    def busy_s(self, device):
        return sum(e - s for s, e in self.busy(device)) / 1e9

    def idle_by_host_span(self, device):
        """The device's idle seconds inside the window, by the innermost
        span open on the client's thread."""
        line = self._clip(self.host[self.client_line])
        return overlap_by_name(gaps(self.busy(device), *self.window),
                               innermost_segments(line))

    def module_seconds(self, device):
        """Device seconds of each jitted program, summed over the
        fingerprints of one name."""
        lo, hi = self.window
        out = {}
        for s, e, name in self._clip(self.devices[device]["modules"]):
            key = module_name(name)
            out[key] = out.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        return out

    def op_seconds(self, device, wanted):
        """Seconds in which an operation that ``wanted(name)`` accepts
        ran on ``device`` (a union: nested operations count once)."""
        ops = [ev for ev in self.devices[device]["ops"] if wanted(ev[2])]
        return sum(e - s for s, e in union(ops, *self.window)) / 1e9

    # -- the host -----------------------------------------------------
    def span_seconds(self, name):
        """Seconds inside spans called ``name``, over all host threads
        (a span that re-enters itself counts its outer extent once a
        thread)."""
        lo, hi = self.window
        return sum(e - s for line in self.host.values()
                   for s, e in union([ev for ev in line if ev[2] == name],
                                     lo, hi)) / 1e9

    @property
    def active_devices(self):
        """Ids of the devices on which an operation ran, in order."""
        return sorted(d for d, lines in self.devices.items() if lines["ops"])

    @property
    def has_device(self):
        return bool(self.window) and bool(self.active_devices)


def find_xplane(directory):
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb*")))
    if not found:
        raise FileNotFoundError(f"the profiler left no xplane in {directory}")
    return found[-1]


def load(path, marker=MARKER):
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) into a Trace."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if dev:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(dev.group(1))] = {
                key: [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in lines[line].events] if line in lines else []
                for key, line in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name == "/host:CPU":
            # names that start with "$" are the profiler's Python frames
            # ("$column.py:352 host_to_device"), there or not with the
            # tracer's level; spans are what the program and JAX annotate
            for ln in plane.lines:
                host.setdefault(ln.name, []).extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in ln.events if not ev.name.startswith("$"))
    return Trace(devices, host, marker)


def keep(path, destination):
    """Copy an xplane out, gzipped, before the run's directory goes."""
    os.makedirs(os.path.dirname(destination) or ".", exist_ok=True)
    with open(path, "rb") as src, gzip.open(destination, "wb") as dst:
        shutil.copyfileobj(src, dst)
