"""KernelCache unit tests: sharing, counters, eviction, config gates.

The cache is process-wide (exec/kernel_cache.py GLOBAL) and reset
between tests by the autouse ``_reset_kernel_cache`` fixture, so every
test starts from zero counters and an empty registry.
"""
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec.kernel_cache import (GLOBAL, KernelCache,
                                                jit_kernel)


def _conf(**kv):
    base = {f"spark.rapids.tpu.sql.kernelCache.{k}": v
            for k, v in kv.items()}
    return TpuConf(base)


def _add_one(x):
    return x + 1


def _mul_two(x):
    return x * 2


# ==========================================================================
# sharing
# ==========================================================================
def test_same_key_shares_one_kernel():
    k1 = jit_kernel(_add_one, key=("unit", "add_one"))
    k2 = jit_kernel(_add_one, key=("unit", "add_one"))
    assert k1 is k2
    assert GLOBAL.counters()["sharedKernels"] == 1
    assert GLOBAL.num_entries == 1


def test_different_keys_do_not_share():
    k1 = jit_kernel(_add_one, key=("unit", "a"))
    k2 = jit_kernel(_mul_two, key=("unit", "b"))
    assert k1 is not k2
    assert GLOBAL.num_entries == 2
    assert GLOBAL.counters()["sharedKernels"] == 0


def test_key_none_compiles_privately():
    k1 = jit_kernel(_add_one)
    k2 = jit_kernel(_add_one)
    assert k1 is not k2
    assert GLOBAL.num_entries == 0  # private kernels are unregistered


# ==========================================================================
# hit/miss/compile counters
#
# NOTE: these use fresh LOCAL functions — jax shares its executable
# cache across jit wrappers of the same function object, so a
# module-level body compiled by an earlier test would (correctly, but
# inconveniently for counting) turn this test's first dispatch into a
# hit.
# ==========================================================================
def test_dispatch_counts_miss_then_hit():
    def body(x):
        return x + 3

    k = jit_kernel(body, key=("unit", "counts"))
    x = jnp.arange(8)
    assert int(k(x)[3]) == 6
    c = GLOBAL.counters()
    assert c["dispatches"] == 1 and c["misses"] == 1 and c["hits"] == 0
    assert c["compileTimeNs"] > 0
    k(x)
    c = GLOBAL.counters()
    assert c["dispatches"] == 2 and c["misses"] == 1 and c["hits"] == 1


def test_new_shape_is_a_new_miss():
    def body(x):
        return x + 5

    k = jit_kernel(body, key=("unit", "shapes"))
    k(jnp.arange(8))
    k(jnp.arange(16))  # different bucket -> jax shape-cache miss
    c = GLOBAL.counters()
    assert c["misses"] == 2 and c["hits"] == 0


def test_compile_time_attributed_to_exec_metrics():
    class _M:
        def __init__(self):
            self.v = 0

        def add(self, n):
            self.v += n

    from spark_rapids_tpu.utils import metrics as M

    def body(x):
        return x * 7

    m = {M.COMPILE_TIME: _M()}
    k = jit_kernel(body, key=("unit", "attr"))
    k(jnp.arange(4), metrics=m)
    assert m[M.COMPILE_TIME].v > 0
    warm = m[M.COMPILE_TIME].v
    k(jnp.arange(4), metrics=m)  # hit: no additional compile wall
    assert m[M.COMPILE_TIME].v == warm


def test_metrics_since_returns_deltas():
    def body(x):
        return x - 9

    mark = GLOBAL.counters()
    k = jit_kernel(body, key=("unit", "delta"))
    k(jnp.arange(4))
    out = GLOBAL.metrics_since(mark)
    assert out["kernelCache.dispatches"] == 1
    assert out["kernelCache.misses"] == 1
    assert out["kernelCache.numEntries"] == GLOBAL.num_entries


# ==========================================================================
# configuration gates
# ==========================================================================
def test_disabled_cache_stops_sharing_but_still_counts():
    GLOBAL.configure(_conf(enabled=False))
    k1 = jit_kernel(_add_one, key=("unit", "off"))
    k2 = jit_kernel(_add_one, key=("unit", "off"))
    assert k1 is not k2
    assert GLOBAL.num_entries == 0
    k1(jnp.arange(4))
    assert GLOBAL.counters()["dispatches"] == 1


def test_max_entries_evicts_lru():
    GLOBAL.configure(_conf(maxEntries=2))
    jit_kernel(_add_one, key=("unit", 1))
    jit_kernel(_add_one, key=("unit", 2))
    jit_kernel(_add_one, key=("unit", 1))  # touch 1 -> 2 becomes LRU
    jit_kernel(_add_one, key=("unit", 3))  # evicts 2
    assert GLOBAL.num_entries == 2
    assert GLOBAL.counters()["evictions"] == 1
    jit_kernel(_add_one, key=("unit", 1))  # still resident
    assert GLOBAL.counters()["sharedKernels"] == 2


def test_reset_restores_defaults():
    GLOBAL.configure(_conf(enabled=False, maxEntries=1))
    jit_kernel(_add_one, key=("unit", "x"))
    GLOBAL.reset()
    assert GLOBAL.enabled and GLOBAL.num_entries == 0
    assert GLOBAL.max_entries == KernelCache._DEFAULT_MAX_ENTRIES
    assert all(v == 0 for v in GLOBAL.counters().values())


def test_donation_inactive_on_cpu_backend():
    """The CPU backend ignores buffer donation — the cache must not
    request it (jax would warn per dispatch), but the plumbing still
    accepts donate_argnums so device runs exercise the same path."""
    assert GLOBAL.donation_active() is False  # tests run on CPU
    k = jit_kernel(_add_one, key=("unit", "donate"),
                   donate_argnums=(0,))
    assert k.donated is False
    assert int(k(jnp.arange(4))[0]) == 1


def test_donation_key_dimension_prevents_cross_config_sharing():
    """A kernel compiled with donation must not serve a caller that
    compiled without (and vice versa) — the donation flag is part of
    the entry key."""
    k1 = jit_kernel(_add_one, key=("unit", "dk"))
    k2 = jit_kernel(_add_one, key=("unit", "dk"), donate_argnums=(0,))
    assert k1 is not k2


# ==========================================================================
# engine integration
# ==========================================================================
def test_session_reports_kernel_cache_metrics():
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.plan import functions as F

    sess = srt.Session()
    df = sess.create_dataframe({"a": [1, 2, 3, 4]}, n_partitions=1)
    df.filter(F.col("a") > 1).select(
        (F.col("a") * 2).alias("d")).collect()
    m = sess.last_metrics
    assert m["kernelCache.dispatches"] >= 1
    assert m["kernelCache.misses"] >= 1
    assert "kernelCache.numEntries" in m
    # second run of the same logical plan rides the cache
    df.filter(F.col("a") > 1).select(
        (F.col("a") * 2).alias("d")).collect()
    m2 = sess.last_metrics
    assert m2["kernelCache.hits"] >= 1
    assert m2["kernelCache.compileTimeNs"] == 0


def test_registered_kernels_do_not_pin_plan_trees():
    """Keyed entries outlive the query, so execs register kernels on a
    children-detached twin (TpuExec.kernel_twin).  A kernel bound to
    the live exec would pin the plan subtree — including the
    HostToDeviceExec whose GC finalizer frees cached upload buffers —
    for the life of the process (regression: abandoned-reader cleanup
    in tests/test_exchange.py leaked upload.cache buffers)."""
    import gc
    import weakref

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.exec.transitions import HostToDeviceExec
    from spark_rapids_tpu.plan import functions as F

    sess = srt.Session()
    df = sess.create_dataframe({"a": [1, 2, 3, 4]}, n_partitions=1)
    # weakrefs via an execute spy — plan capture would itself retain
    # the tree on the (process-registered) session
    refs = []
    orig = HostToDeviceExec.execute_columnar

    def spy(self, ctx):
        refs.append(weakref.ref(self))
        return orig(self, ctx)

    HostToDeviceExec.execute_columnar = spy
    try:
        df.select((F.col("a") * 2).alias("b"), F.col("a")) \
            .filter(F.col("b") > 2).select(F.col("b")).collect()
    finally:
        HostToDeviceExec.execute_columnar = orig
    assert refs, "query ran without an upload transition"
    assert GLOBAL.num_entries >= 1  # the chain registered keyed kernels
    del df, sess
    gc.collect()
    alive = [r for r in refs if r() is not None]
    assert not alive, \
        "a registered kernel retains the plan tree past query end"


@pytest.mark.parametrize("kind", ["join", "aggregate"])
def test_a_cached_kernel_holds_no_host_plan_node(kind):
    """A join's and an aggregate's kernel twins hold no host plan node:
    once the query is dropped, its host HashJoinExec / HashAggregateExec
    is collected while the kernel cache still holds their programs."""
    import gc
    import weakref

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.joins import TpuHashJoinExec
    from spark_rapids_tpu.plan import functions as F
    from spark_rapids_tpu.plan import physical as P

    cls, host, name = {
        "join": (TpuHashJoinExec, P.HashJoinExec, "join_count"),
        "aggregate": (TpuHashAggregateExec, P.HashAggregateExec,
                      "agg_batch")}[kind]
    # weakrefs via a constructor spy: plan capture would itself retain
    # the tree on the session
    refs = []
    orig = cls.__init__

    def spy(self, *args, **kw):
        refs.extend(weakref.ref(a) for a in args if isinstance(a, host))
        orig(self, *args, **kw)

    cls.__init__ = spy
    try:
        sess = srt.Session(
            {"spark.rapids.tpu.sql.broadcastSizeThreshold": 0})
        df = sess.create_dataframe({"k": [1, 2, 2, 3], "v": [1, 2, 3, 4]})
        other = sess.create_dataframe({"rk": [2, 3], "w": [5, 6]})
        q = df.join(other, on=(["k"], ["rk"]), how="inner") \
            if kind == "join" else \
            df.group_by("k").agg(F.sum("v").alias("s"))
        assert q.collect()
    finally:
        cls.__init__ = orig
    assert refs, f"no {host.__name__} was converted"
    del df, other, q, sess
    gc.collect()
    with GLOBAL._lock:
        names = {k.name for k in GLOBAL._entries.values()}
    assert name in names
    assert [r for r in refs if r() is not None] == []


def test_identical_execs_across_sessions_share_kernels():
    """Two sessions building the same Project over the same schema
    hand out one cached kernel (the fingerprint keys on schema+exprs,
    not on instance identity)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.plan import functions as F

    def run():
        sess = srt.Session()
        df = sess.create_dataframe({"a": [1, 2, 3]}, n_partitions=1)
        return df.filter(F.col("a") > 0).select(
            (F.col("a") + 1).alias("b")).collect()

    assert run() == run()
    assert GLOBAL.counters()["sharedKernels"] >= 1


# ==========================================================================
# thread-safety (concurrent scheduler workers share the process cache)
# ==========================================================================
def test_concurrent_get_configure_reset_hammer():
    """Many threads racing get()/configure()/reset()/counters() must
    never corrupt the registry: every caller of a shared key in a
    stable window gets a working kernel, entry count respects
    maxEntries, counters stay non-negative, and no thread raises.
    This is the regression test for the scheduler's worker threads all
    dispatching through GLOBAL at once."""
    import threading

    errors = []
    stop = threading.Event()
    barrier = threading.Barrier(12)
    x = jnp.arange(8)

    def dispatcher(tid):
        barrier.wait()
        i = 0
        while not stop.is_set():
            # a small rotating key set forces constant hit/miss/evict
            # traffic through the same buckets
            key = ("hammer", i % 5)
            k = jit_kernel(_add_one, key=key)
            out = k(x)
            assert int(out[0]) == 1
            i += 1

    def configurer():
        barrier.wait()
        flip = False
        while not stop.is_set():
            GLOBAL.configure(_conf(enabled=True,
                                   maxEntries=2 if flip else 64))
            flip = not flip

    def resetter():
        barrier.wait()
        while not stop.is_set():
            GLOBAL.reset()
            GLOBAL.counters()
            _ = GLOBAL.num_entries

    def run(fn, *args):
        try:
            fn(*args)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)
            stop.set()

    threads = ([threading.Thread(target=run, args=(dispatcher, t))
                for t in range(10)]
               + [threading.Thread(target=run, args=(configurer,)),
                  threading.Thread(target=run, args=(resetter,))])
    for t in threads:
        t.start()
    import time

    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive(), "hammer thread wedged"
    assert not errors, errors[0]
    # post-race invariants: a coherent registry and sane counters
    c = GLOBAL.counters()
    assert all(v >= 0 for v in c.values()), c
    GLOBAL.configure(_conf(enabled=True, maxEntries=2))
    assert GLOBAL.num_entries <= 2
