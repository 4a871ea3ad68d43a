"""Device seconds a query in the aggregate's own programs: those the
kernel cache names ``jit_agg_*`` (``exec/aggregate.py``: the batch's
group-by, the merges of partial results, the final projection).  On the
busiest device.  0.0 where no such program ran."""
UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"

PREFIX = "jit_agg_"


def reduce(trace, notes):
    if not trace.has_device or not trace.queries:
        return 0.0
    busiest = max(trace.active_devices, key=trace.busy_s)
    return sum(s for name, s in trace.module_seconds(busiest).items()
               if name.startswith(PREFIX)) / trace.queries
