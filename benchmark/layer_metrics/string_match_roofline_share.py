"""The phase ``strings.match``'s share of the chip's HBM bandwidth: the
bytes its ops touch, as the compiler counts them (each leaf op's
``bytes_accessed`` in the op metadata, the one count of this phase's
bytes a reader can have: it gets the trace and ``notes``, not the
cell's rows), over its leaf seconds, over ``hbm_bytes_per_s`` of
``harness/peaks.json``.  Any program, the busiest device, the traced
window.  0.0 where the program names no such scope or says nothing of
its trace."""
import json

from benchmark.harness import phases
from benchmark.harness import trace as harness_trace

UNIT, LAYER, MOVES = "%", "kernels", "query_s_p50"

PHASE = "strings.match"


def _rows(trace):
    """The phase's ``device_trace.Row`` of every program; [] where there
    is nothing to read."""
    if not trace.has_device or not trace.queries:
        return []
    try:
        from spark_rapids_tpu.telemetry import device_trace
        from spark_rapids_tpu.utils import tracing

        directory = tracing.last_profile_dir()
    except (ImportError, AttributeError):
        return []
    if not directory:
        return []
    try:
        path = harness_trace.find_xplane(directory)
    except FileNotFoundError:
        return []
    loaded = phases._loaded(path)
    busiest = max(trace.active_devices, key=trace.busy_s)
    if busiest not in loaded.devices:
        return []
    lo, hi = trace.window           # nanoseconds; the program's are ps
    tables = device_trace.reduce(loaded, busiest, "phase",
                                 window=(int(lo * 1000), int(hi * 1000)),
                                 queries=trace.queries)
    return [rows[PHASE] for rows in tables.values() if PHASE in rows]


def reduce(trace, notes):
    rows = _rows(trace)
    seconds = sum(r.seconds for r in rows)
    if seconds <= 0:
        return 0.0
    with open(notes["peaks_file"]) as f:
        peak = json.load(f)[notes["device_kind"]]["hbm_bytes_per_s"]
    return 100.0 * sum(r.bytes_accessed for r in rows) / seconds / peak
