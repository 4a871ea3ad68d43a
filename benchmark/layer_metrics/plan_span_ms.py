"""Milliseconds a query inside the program's ``Plan`` span
(``session.py:prepare_execution``): what the request itself pays for
planning, which on a plan-cache hit is the lookup, the context and the
recovery stamp, not the planner ``plan_ms`` times outside the request."""
UNIT, LAYER, MOVES = "ms/query", "plan / rewrite / fusion", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return None
    secs = trace.span_seconds("Plan")
    return 1e3 * secs / trace.queries if secs > 0 else None
