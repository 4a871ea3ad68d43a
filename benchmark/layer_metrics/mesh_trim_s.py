"""Seconds a query inside the mesh runner's ``MeshTrim`` spans
(``parallel/runner.py:_retile``): between two stages the host reads the
shards' row counts and cuts the stacked output to their bucket, column
by column.  0 where no stage ran."""
UNIT, LAYER, MOVES = "s/query", "mesh exchange", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    return trace.span_seconds("MeshTrim") / trace.queries
