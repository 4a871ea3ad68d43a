"""Seconds a query in which the device sat idle while the client's
thread was inside ``PrefetchWait`` (``exec/transitions.py``) and in no
deeper span: blocked on the queue the decode thread fills, so the
device waiting for Parquet decode.  On the most idle device.  A trace
with no such span gives nothing; one whose waits cost the device no
idle time gives 0."""
UNIT, LAYER, MOVES = "s/query", "scan + h2d upload", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device or not trace.span_seconds("PrefetchWait"):
        return None
    return max(trace.idle_by_host_span(d).get("PrefetchWait", 0.0)
               for d in trace.active_devices) / trace.queries
