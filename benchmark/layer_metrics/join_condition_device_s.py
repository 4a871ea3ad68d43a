"""Device seconds a query in the phase ``join.condition``
(``exec/joins.py:_semi_pairs``, ``ops/kernels/join.py:any_pair``: a
conditional semi/anti join's reads of the right columns at its pairs,
the condition's evaluation on every pair, and the reduction to one bit a
left row), in any program.  Leaf seconds on the busiest device, read
from the ops' metadata by the program's own ``telemetry/device_trace.py``
(``harness/phases.py``).  0.0 where the program names no such scope or
says nothing of its trace."""
from benchmark.harness import phases

UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    return phases.seconds(trace, "phase", "join.condition")
