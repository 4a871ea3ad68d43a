"""Seconds a query inside the program's ``ScanDecode`` spans
(``io/scans.py:_read_file``): the host decoding Parquet into host
batches, summed over the threads that do it (the ``h2d-prefetch-*``
producers, which run beside the client's thread, not in its way)."""
UNIT, LAYER, MOVES = "s/query", "scan + h2d upload", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return None
    secs = trace.span_seconds("ScanDecode")
    return secs / trace.queries if secs > 0 else None
