"""Device seconds a query in the semi/anti join's own program, the one
the kernel cache names ``jit_join_semi`` (``exec/joins.py:_semi_anti``:
the probe, the keep mask and the compaction of the left rows kept).  On
the busiest device.  0.0 where no such program ran."""
UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"

PROGRAM = "jit_join_semi"


def reduce(trace, notes):
    if not trace.has_device:
        return 0.0
    busiest = max(trace.active_devices, key=trace.busy_s)
    return trace.module_seconds(busiest).get(PROGRAM, 0.0) / trace.queries
