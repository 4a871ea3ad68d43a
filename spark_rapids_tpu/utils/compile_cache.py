"""Where JAX's persistent compilation cache lives.

A cold TPC-H query is dozens of XLA programs and the TPU compiler takes
seconds to minutes over each, so compiled programs are kept on disk
across processes.  The directory is part of the cache's key — one that
moves never hits — so there is ONE rule for it, applied by
:func:`enable` before the first compile (``DeviceManager`` and the
launchers call it):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it; no other
  directory is set in code.
* otherwise ``<checkout>/.jax_cache``, resolved from this package's own
  path (never a temp dir, a pid or a timestamp).

The CPU backend is left out, here and nowhere else: XLA:CPU logs a 4 KB
machine-feature warning ("+prefer-no-scatter is not supported on the
host machine ... could lead to SIGILL") for every entry it loads, even
on the machine that wrote it, and CPU compiles of this engine take
fractions of a second.
"""
from __future__ import annotations

import os
from typing import Optional

#: compiles faster than this are not worth a file
MIN_COMPILE_SECS = 0.5

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the rule above gives (no JAX needed)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> Optional[str]:
    """Point JAX's persistent cache at :func:`cache_dir` (idempotent)
    and return the directory in use — None on the CPU backend, where
    the cache is switched off."""
    import jax

    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not jax.config.jax_compilation_cache_dir:
        path = cache_dir()
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return jax.config.jax_compilation_cache_dir
