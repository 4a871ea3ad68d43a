"""Seconds of the first execution of the run, compiles and persistent
cache reads inside: what a new process pays before its first answer."""
UNIT, LAYER, MOVES = "s", "compile cache", "setup_s"


def reduce(trace, notes):
    return notes["first_query_s"]
