"""Seconds a query in which a collective operation ran on a device
(all-to-all and its kin, start and done halves alike; a union, so
overlapping ones count once): the largest over the devices.  0 where
none ran.

An ``XLA Ops`` event on the v5e is named by its whole HLO text,
``%all_to_all.20 = pred[4,4,1024]{...} all-to-all(pred[...] %reshape.364),
channel_id=1, ...``: the instruction's own name follows the JAX
primitive that made it (``%all_to_all``, ``%pmax``), so a collective is
told by its opcode, the word before the operands' bracket; a short name
(``%all-to-all.2``) is taken by its prefix."""
import re

UNIT, LAYER, MOVES = "s/query", "mesh exchange", "query_s_p50"

_OPCODE = (r"(?:all-to-all|all-gather|all-reduce|reduce-scatter|"
           r"collective-permute)(?:-start|-done)?")
COLLECTIVE = re.compile(rf"^%{_OPCODE}[.\s]|\s{_OPCODE}\(")


def reduce(trace, notes):
    if not trace.has_device:
        return None
    return max(trace.op_seconds(d, COLLECTIVE.search)
               for d in trace.active_devices) / trace.queries
