"""Seconds a query in which an operation ran on the device: the union
of the ``XLA Ops`` intervals inside the traced window, on the busiest
device."""
UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    return max(trace.busy_s(d) for d in trace.active_devices) / trace.queries
