"""What the benchmark reads off the system besides its answers: what
JAX compiled, what the devices held, what ``explain()`` marks for the
host.  Copied from ``chip_smoke.py`` (PR 21), where they were proven
on the chip; the benchmark keeps its own so that no later PR can move
the yardstick."""
import os


class CompileWatch:
    """Counts XLA compiles and persistent-cache answers from JAX's own
    monitoring events; ``since(mark)`` is what happened after
    ``mark = snapshot()``."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"xla_compiles": self.compiles,
                "xla_compile_s": self.compile_s,
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses}

    def since(self, mark):
        now = self.snapshot()
        return {k: now[k] - mark[k] for k in now}


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


def memory_peaks(devices):
    """``peak_bytes_in_use`` of each device (None where the backend
    does not say, as on the CPU)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def host_operators(explain_text, allowed):
    """Operators that ``explain()`` marks for the host (``!``) or as
    partly so (``@``) and that the configuration does not allow."""
    marked = [ln.split()[1] for ln in map(str.strip,
                                          explain_text.splitlines())
              if ln.startswith(("!", "@")) and len(ln.split()) > 1]
    return sorted(set(m for m in marked if m not in allowed))
