"""Seconds a query inside the program's ``HostToDevice`` span: the
upload of decoded batches (``data/column.py``), on the host's clock
as the xplane has it."""
UNIT, LAYER, MOVES = "s/query", "scan + h2d upload", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return None
    secs = trace.span_seconds("HostToDevice")
    return secs / trace.queries if secs > 0 else None
