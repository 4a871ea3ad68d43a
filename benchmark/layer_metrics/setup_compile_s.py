"""Seconds JAX's compile events add up to during the warm-up (backend
compiles only; a persistent-cache hit is not a compile)."""
UNIT, LAYER, MOVES = "s", "compile cache", "setup_s"


def reduce(trace, notes):
    secs = notes["setup_compiles"]["xla_compile_s"]
    return secs if secs > 0 else None
