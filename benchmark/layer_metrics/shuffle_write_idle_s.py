"""Seconds a query in which the device sat idle while the client's
thread was inside ``TpuShuffleWrite`` and in no deeper span: the
exchange's host side pulling, partitioning and dispatching (the top
idle gap of every one-chip trace so far).  On the most idle device."""
UNIT, LAYER, MOVES = "s/query", "exchange", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    idle = max(trace.idle_by_host_span(d).get("TpuShuffleWrite", 0.0)
               for d in trace.active_devices)
    return idle / trace.queries if idle > 0 else None
