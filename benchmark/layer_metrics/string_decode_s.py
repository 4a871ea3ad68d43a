"""Seconds a query inside the program's ``ScanDecode.strings`` spans
(``io/arrow_convert.py``): the string columns' conversion from Arrow to
host columns, a child of ``ScanDecode``, summed over the threads that
decode.  0.0 where no such span was recorded (a program without the
span, a query that reads no string)."""
UNIT, LAYER, MOVES = "s/query", "scan + h2d upload", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return 0.0
    return trace.span_seconds("ScanDecode.strings") / trace.queries
