"""Process-wide kernel-compilation cache.

Every device exec routes its jit compilation through here instead of
calling ``jax.jit`` directly (enforced by the ``jit-direct`` analysis
rule), which buys three things the scattered
per-exec ``_jit`` helpers could not:

* **Sharing** — entries are keyed by a kernel *fingerprint* (operator
  kind + bound-expression signatures) plus the input/output *schema
  signatures*; two exec instances computing the same thing over the
  same layout hand out ONE wrapped callable and with it one underlying
  jax executable cache.  The third key dimension of the design — the
  row bucket — rides the jax shape cache inside each entry: batches
  are padded to power-of-two buckets (``bucketMinRows``), so jax's own
  per-shape cache keys exactly on the bucket.
* **Telemetry** — per-dispatch hit/miss detection (via the jit
  wrapper's cache-size delta), compile-inclusive wall of first-shape
  dispatches, dispatch and eviction counters.  ``Session`` merges the
  per-query delta into ``last_metrics`` under ``kernelCache.*``; the
  per-exec ``compileTime`` metric attributes compile wall to the
  dispatching operator in EXPLAIN ANALYZE.
* **Donation** — ``donate_argnums`` buffer donation for call sites
  whose input batch is provably single-consumer (fused segments over
  fresh file-scan uploads), applied only on backends that honor it
  (the CPU backend ignores donation, so tests exercise the plumbing
  but never the aliasing).

* **Names** — every program is jitted under a name that starts with
  its operator's kind (:func:`program_name`), so the profiler's
  ``XLA Modules`` line — the only place a device trace names what ran
  — reads ``jit_filter__compute``, ``jit_agg_batch``,
  ``jit_shuffle_packedBuild``: device seconds by operator.

Conf-gated by ``spark.rapids.tpu.sql.kernelCache.{enabled,maxEntries,
donation.enabled}``; the cache is process-global like the
DeviceManager, (re)configured by each device Session.
"""
from __future__ import annotations

import re
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional, Tuple

from ..telemetry.profiler import PROFILER, kernel_fingerprint
from ..utils import metrics as M


def schema_signature(schema) -> Tuple:
    """Hashable fingerprint of a schema: (name, dtype, nullable) per
    field.  Names matter — the output schema is static aux data baked
    into the compiled closure's DeviceBatch pytree."""
    return tuple((f.name, str(f.dtype), bool(f.nullable))
                 for f in schema)


def expr_signature(exprs) -> Tuple:
    """Hashable fingerprint of bound expressions: canonical SQL plus
    result dtype (sql() prints the full bound tree, so equal
    signatures imply equal computations for deterministic exprs)."""
    return tuple((e.sql(), str(e.dtype)) for e in exprs)


def sort_key_signature(keys, bound: bool = True) -> Tuple:
    """Hashable fingerprint of sort keys: each key's SQL, result dtype
    and direction.  An unbound key (a range partitioning keeps its
    plan's) has no dtype to say."""
    return tuple((k.expr.sql(), str(k.expr.dtype) if bound else None,
                  bool(k.ascending), bool(k.nulls_first))
                 for k in keys or ())


def _identifier(text: str) -> str:
    return re.sub(r"[^0-9A-Za-z]", "_", text)


def program_name(key, fn: Callable, kind: Optional[str] = None) -> str:
    """The name a kernel is jitted under: ``<kind>_<phase>`` where the
    key ends in a phase string (``agg_batch``, ``join_count``), else
    ``<kind>_<function>`` (``filter__compute``), the function left out
    where the kind already says it (``shuffle_packedBuild``).
    ``kind`` defaults to the key's leading string, non-alphanumerics
    turned to ``_``.

    The name becomes part of the HLO module and so of the persistent
    compile cache's key: it is built from strings in the source alone
    — no id, address or counter — and is the same in every process."""
    if kind is None and isinstance(key, tuple) and key \
            and isinstance(key[0], str):
        kind = key[0]
    own = getattr(fn, "__name__", "")
    if not kind:
        return own  # unkeyed and unnamed: JAX's own naming
    kind = _identifier(kind)
    if isinstance(key, tuple) and len(key) > 1 \
            and isinstance(key[-1], str):
        return f"{kind}_{_identifier(key[-1])}"
    said = kind.replace("_", "").lower()
    if own.isidentifier() and \
            own.replace("_", "").lower() not in said:
        return f"{kind}_{own}"
    return kind


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` behind a function called ``name`` — what ``jax.jit``
    names the program after."""
    if not name or name == getattr(fn, "__name__", None):
        return fn

    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


class _CachedKernel:
    """A jitted kernel wrapped with dispatch accounting.

    ``__call__(*args, metrics=None)``: dispatches the underlying jax
    executable; when the dispatch triggered a compile (first call for
    this arg-shape bucket), the compile-inclusive wall is recorded
    globally and — when ``metrics`` (an exec's metric dict) is given —
    attributed to the dispatching exec's ``compileTime`` metric.
    """

    __slots__ = ("_cache", "fn", "_jfn", "donated", "fingerprint", "name",
                 "static_argnums")

    def __init__(self, cache: "KernelCache", fn: Callable,
                 static_argnums: Tuple[int, ...],
                 donate_argnums: Tuple[int, ...],
                 fingerprint: Optional[str] = None,
                 name: Optional[str] = None):
        import jax

        self._cache = cache
        self.fn = fn  # the raw traceable body (runner/fusion reuse it)
        self.static_argnums = tuple(static_argnums or ())
        self.fingerprint = fingerprint or kernel_fingerprint(None, fn)
        self.donated = bool(donate_argnums) and cache.donation_active()
        kwargs = {}
        if static_argnums:
            kwargs["static_argnums"] = tuple(static_argnums)
        if self.donated:
            kwargs["donate_argnums"] = tuple(donate_argnums)
        program = _named(fn, name)
        #: the program's name in a device trace, less jit's ``jit_``
        self.name = program.__name__
        self._jfn = jax.jit(program, **kwargs)

    def __call__(self, *args, metrics=None):
        # the disabled-profiler cost is this ONE attribute read — no
        # allocation, no lock (the profiler-guard analysis rule pins
        # both)
        prof = PROFILER if PROFILER.enabled else None
        # the jit wrapper's per-shape executable count (jax 0.9 offers
        # no public reading of it): a dispatch that grew it compiled
        before = self._jfn._cache_size()
        t0 = time.perf_counter_ns()
        out = self._jfn(*args)
        if prof is not None:
            prof.record_dispatch(self.fingerprint,
                                 time.perf_counter_ns() - t0, args, out)
        if self._jfn._cache_size() > before:
            dt = time.perf_counter_ns() - t0
            self._cache._count(dispatches=1, misses=1, compileTimeNs=dt)
            if metrics is not None:
                m = metrics.get(M.COMPILE_TIME)
                if m is not None:
                    m.add(dt)
        else:
            self._cache._count(dispatches=1, hits=1)
        return out


class KernelCache:
    """LRU registry of :class:`_CachedKernel` entries keyed by kernel
    fingerprint (see module doc).  Thread-safe; counters monotonic
    until :meth:`reset`."""

    _DEFAULT_MAX_ENTRIES = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict" = OrderedDict()
        self.enabled = True
        self.max_entries = self._DEFAULT_MAX_ENTRIES
        self.donation_enabled = True
        self._counters = self._zero_counters()

    @staticmethod
    def _zero_counters():
        return {"hits": 0, "misses": 0, "dispatches": 0,
                "compileTimeNs": 0, "evictions": 0, "sharedKernels": 0}

    # ---------------- configuration / lifecycle -----------------------
    def configure(self, conf) -> None:
        """Adopt a Session's kernelCache.* settings (process-global,
        like the DeviceManager: the most recent device Session wins)."""
        from ..config import (KERNEL_CACHE_DONATION, KERNEL_CACHE_ENABLED,
                              KERNEL_CACHE_MAX_ENTRIES)

        # read the conf outside the lock (conf getters can run user
        # checkers), publish every field inside it: a concurrent get()
        # must never observe a half-applied configuration
        enabled = bool(conf.get(KERNEL_CACHE_ENABLED))
        max_entries = max(1, int(conf.get(KERNEL_CACHE_MAX_ENTRIES)))
        donation = bool(conf.get(KERNEL_CACHE_DONATION))
        with self._lock:
            self.enabled = enabled
            self.max_entries = max_entries
            self.donation_enabled = donation
            self._evict_locked()

    def reset(self) -> None:
        """Drop every entry and zero every counter (test isolation —
        wired as an autouse fixture in tests/conftest.py).  Kernels
        already handed out keep working; they just stop being shared."""
        with self._lock:
            self._entries.clear()
            self._counters = self._zero_counters()
            self.enabled = True
            self.max_entries = self._DEFAULT_MAX_ENTRIES
            self.donation_enabled = True

    def donation_active(self) -> bool:
        """Donation applies only where the backend honors it — the CPU
        backend silently ignores donated buffers (and warns)."""
        import jax

        return self.donation_enabled and jax.default_backend() != "cpu"

    # ---------------- counters ----------------------------------------
    def _count(self, **kv) -> None:
        with self._lock:
            for k, v in kv.items():
                self._counters[k] += v

    def counters(self):
        with self._lock:
            return dict(self._counters)

    @property
    def num_entries(self) -> int:
        with self._lock:
            return len(self._entries)

    def metrics_since(self, mark) -> dict:
        """Per-query ``kernelCache.*`` metric section: counter deltas
        since ``mark`` (a :meth:`counters` snapshot taken at query
        start by ExecContext) plus the absolute entry count."""
        cur = self.counters()
        out = {}
        for k, v in cur.items():
            base = mark.get(k, 0) if mark else 0
            out[f"kernelCache.{k}"] = v - base
        out["kernelCache.numEntries"] = self.num_entries
        return out

    # ---------------- the entry point ----------------------------------
    def get(self, fn: Callable, *, key=None, kind: Optional[str] = None,
            static_argnums: Tuple[int, ...] = (),
            donate_argnums: Tuple[int, ...] = ()) -> _CachedKernel:
        """Wrap ``fn`` for jit dispatch through the cache.

        ``key=None`` (or cache disabled) compiles privately per call
        site — no sharing, but dispatches still count; such a site
        passes ``kind``, its operator's name for :func:`program_name`
        (a key would share the first caller's closure).  A non-None key
        MUST capture everything the closure reads (an operator's kind
        and its ``TpuExec.signature``): the first caller's closure
        serves every later caller.

        Lifetime discipline: a registered entry outlives the query, so
        an exec-bound body must be registered through
        ``TpuExec.kernel_twin()``, which holds no child and no host plan
        node — a kernel bound to the live exec would pin its plan
        subtree (and whatever the subtree's GC finalizers free, e.g.
        HostToDeviceExec's cached upload buffers) for the life of the
        process."""
        use_key = None
        if key is not None:
            # donation_active() probes the jax backend — keep it out of
            # the lock; the enabled/donation pair is then re-read and
            # applied atomically so a concurrent configure()/reset()
            # never yields a key built from a half-applied config
            donation = self.donation_active()
            with self._lock:
                if self.enabled:
                    use_key = (key, tuple(static_argnums),
                               tuple(donate_argnums),
                               donation and self.donation_enabled)
                    hit = self._entries.get(use_key)
                    if hit is not None:
                        self._entries.move_to_end(use_key)
                        self._counters["sharedKernels"] += 1
                        return hit
        kern = _CachedKernel(self, fn, static_argnums, donate_argnums,
                             fingerprint=kernel_fingerprint(key, fn),
                             name=program_name(key, fn, kind))
        if use_key is not None:
            with self._lock:
                # a concurrent thread may have registered the same key
                # between our miss and here — the first registration
                # wins and every caller shares it
                kern = self._entries.setdefault(use_key, kern)
                self._entries.move_to_end(use_key)
                self._evict_locked()
        return kern

    def _evict_locked(self) -> None:
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self._counters["evictions"] += 1


#: THE process-wide cache instance (analogue: DeviceManager singleton)
GLOBAL = KernelCache()


def jit_kernel(fn: Callable, *, key=None, kind: Optional[str] = None,
               static_argnums: Tuple[int, ...] = (),
               donate_argnums: Tuple[int, ...] = ()) -> _CachedKernel:
    """Module-level sugar over ``GLOBAL.get`` — the one way execs
    compile kernels (replaces the per-module ``_jit`` helpers)."""
    return GLOBAL.get(fn, key=key, kind=kind,
                      static_argnums=static_argnums,
                      donate_argnums=donate_argnums)
