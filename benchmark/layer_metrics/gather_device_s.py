"""Device seconds a query in the ops whose JAX primitive is ``gather`` (the
last component of the op's path), in any program: rows, words or
operands read through an index, whichever phase asks for them.  A gather
inside a named phase counts here and in that phase's metric: the four
are views, not a partition.  Leaf seconds on the busiest device, read
from the ops' metadata by the program's own
``telemetry/device_trace.py`` (``harness/phases.py``).  0.0 where the
program names no such scope or says nothing of its trace."""
from benchmark.harness import phases

UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    return phases.seconds(trace, "primitive", "gather")
