"""Seconds a query inside the mesh runner's ``MeshStage`` spans
(``parallel/runner.py``): a stage program from its key to the readback
of its overflow verdict — trace and compile where the program is new,
dispatch, and the wait for the stage — every attempt, the broadcast
precompute programs among them.  0 where no stage ran."""
UNIT, LAYER, MOVES = "s/query", "mesh exchange", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    return trace.span_seconds("MeshStage") / trace.queries
