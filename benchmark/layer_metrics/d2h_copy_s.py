"""Seconds a query inside the program's ``DeviceToHost.copy`` spans
(``data/column.py``): the arrays' ``device_get`` and the host-side trim
and decode, apart from ``DeviceToHost.wait``, the row-count readback
that is the wait for the device."""
UNIT, LAYER, MOVES = "s/query", "result download", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return None
    secs = trace.span_seconds("DeviceToHost.copy")
    return secs / trace.queries if secs > 0 else None
