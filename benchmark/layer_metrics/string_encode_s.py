"""Seconds a query inside the program's ``HostToDevice.strings`` spans
(``data/column.py``): the string columns' encoding into the byte
matrices that are uploaded, a child of ``HostToDevice``.  0.0 where no
such span was recorded (a program without the span, a query that
uploads no string)."""
UNIT, LAYER, MOVES = "s/query", "scan + h2d upload", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return 0.0
    return trace.span_seconds("HostToDevice.strings") / trace.queries
