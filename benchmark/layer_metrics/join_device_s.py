"""Device seconds a query in the join's own programs: those the kernel
cache names ``jit_join_*`` (``exec/joins.py``: the probe's count, the
expand, the semi/anti mask).  On the busiest device.  0 where no such
program ran: on the mesh the join bodies run inside the stage program
and carry no name of their own."""
UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"

PREFIX = "jit_join_"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    busiest = max(trace.active_devices, key=trace.busy_s)
    return sum(s for name, s in trace.module_seconds(busiest).items()
               if name.startswith(PREFIX)) / trace.queries
