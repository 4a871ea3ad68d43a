"""Seconds a query in which a collective operation ran on a device
(all-to-all and its kin, start and done halves alike; a union, so
overlapping ones count once): the largest over the devices."""
UNIT, LAYER, MOVES = "s/query", "mesh exchange", "query_s_p50"

COLLECTIVES = ("%all-to-all", "%all-gather", "%all-reduce",
               "%reduce-scatter", "%collective-permute")


def reduce(trace, notes):
    if not trace.has_device:
        return None
    secs = max(trace.op_seconds(d, lambda n: n.startswith(COLLECTIVES))
               for d in trace.active_devices)
    return secs / trace.queries if secs > 0 else None
