"""Device seconds a query in the exchange's own programs: those the
kernel cache names ``jit_shuffle_*`` (the packed build and slice, the
partition-id and range kernels, and whatever is keyed ``shuffle.*``
later).  On the busiest device."""
UNIT, LAYER, MOVES = "s/query", "exchange", "query_s_p50"

PREFIX = "jit_shuffle_"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    busiest = max(trace.active_devices, key=trace.busy_s)
    secs = sum(s for name, s in trace.module_seconds(busiest).items()
               if name.startswith(PREFIX))
    return secs / trace.queries if secs > 0 else None
