"""How near the query's device time is to what HBM bandwidth allows:
the least bytes the query must read and write (``min_bytes`` in the
query's own file, times nothing) over the chip's peak bytes a second,
over the busy seconds a query.  Memory-bound by construction: these
queries do a few operations a byte."""
import json

UNIT, LAYER, MOVES = "%", "kernels", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    with open(notes["peaks_file"]) as f:
        peaks = json.load(f)
    if notes["device_kind"] not in peaks:
        raise KeyError(f"no peaks for device kind {notes['device_kind']!r} "
                       f"in {notes['peaks_file']}")
    peak = peaks[notes["device_kind"]]["hbm_bytes_per_s"]
    busy = max(trace.busy_s(d) for d in trace.active_devices) / trace.queries
    least = notes["min_bytes_per_query"] / notes["chips"] / peak
    return 100.0 * least / busy
