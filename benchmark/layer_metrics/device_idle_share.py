"""1 - busy / traced window, in percent, on the most idle device."""
UNIT, LAYER, MOVES = "%", "device", "query_s_p50"


def reduce(trace, notes):
    if not trace.has_device:
        return None
    busy = min(trace.busy_s(d) for d in trace.active_devices)
    return 100.0 * (1.0 - busy / trace.window_s)
