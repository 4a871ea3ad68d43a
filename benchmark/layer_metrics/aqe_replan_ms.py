"""Milliseconds a query inside the program's ``AqeReplan`` spans
(``adaptive/executor.py``): the adaptive planner's three rewrites over
the unexecuted rest of the plan, once after every stage: what adaptivity
costs a request on the host between stages.  0.0 where no such span was
recorded (a program without the span, a plan with no exchange)."""
UNIT, LAYER, MOVES = "ms/query", "plan / rewrite / fusion", "query_s_p50"


def reduce(trace, notes):
    if not trace.queries:
        return 0.0
    return 1e3 * trace.span_seconds("AqeReplan") / trace.queries
