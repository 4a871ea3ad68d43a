"""Test harness configuration.

Reference analogue: integration_tests conftest.py + SparkQueryCompareTest-
Suite — dual-session equality testing with a virtual device mesh:
tests run on CPU with 8 virtual XLA devices (multi-chip sharding testable
without a pod, the gap the reference never filled for UCX — SURVEY §4).
"""
import os

# Tests run on the CPU backend with 8 virtual devices and x64; set
# before jax initializes a backend.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import faulthandler  # noqa: E402

import pytest  # noqa: E402

# A hang must fail, not eat CI (r3 shipped with the full suite unable to
# complete).  Two layers: (1) the device-semaphore watchdog raises after
# a short wait in tests, so permit leaks become tracebacks; (2) a
# per-test faulthandler deadline dumps all thread stacks and hard-exits
# if anything else hangs.
from spark_rapids_tpu.memory.semaphore import DeviceSemaphore  # noqa: E402

DeviceSemaphore.ACQUIRE_TIMEOUT_SECONDS = 60.0

_PER_TEST_TIMEOUT = float(os.environ.get("SRT_TEST_TIMEOUT", "600"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
        "budget (-m 'not slow')")
    config.addinivalue_line(
        "markers", "oom_injection: drives operators through their "
        "OOM-recovery paths via the deterministic fault injector "
        "(spark.rapids.tpu.memory.oomInjection.*)")
    config.addinivalue_line(
        "markers", "fault_injection: drives the distributed "
        "fault-tolerance layer (corruption/delay/crash recovery, "
        "watchdogs, degradation ladder) via the generalized "
        "deterministic injector (spark.rapids.tpu.fault.injection.*)")


@pytest.fixture(autouse=True)
def _hang_watchdog():
    faulthandler.dump_traceback_later(_PER_TEST_TIMEOUT, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def _disarm_oom_injector():
    """An armed injector (legacy OOM slot OR the generalized fault
    slot) must never outlive its test — a later test's ExecContext
    normally re-installs from its own conf, but a test that fails
    before executing a query would otherwise inherit injected faults.
    Also asserts no in-flight recovery state (shield/recovering
    thread-local scopes) leaked across the test boundary."""
    yield
    from spark_rapids_tpu.fault.injector import (install_fault_injector,
                                                 recovery_in_flight)
    from spark_rapids_tpu.memory.retry import install_injector

    leaked = recovery_in_flight()
    install_injector(None)
    install_fault_injector(None)
    assert not leaked, \
        "recovery/shield scope leaked across the test boundary — a " \
        "combinator exited without unwinding its thread-local depth"


@pytest.fixture(autouse=True)
def _shutdown_query_schedulers():
    """Mirror of the injector-disarm fixture for the concurrent query
    scheduler: every scheduler created during a test is shut down
    (cancelling its queued/running queries) and its threads joined, so
    no scheduler/worker thread — and no thread-local cancel-token or
    scoped-injector binding on the main thread — outlives its test."""
    yield
    import threading

    from spark_rapids_tpu.fault.injector import \
        bind_scoped_fault_injector
    from spark_rapids_tpu.memory.retry import bind_scoped_injector
    from spark_rapids_tpu.scheduler import cancel as _cancel
    from spark_rapids_tpu.scheduler import query_scheduler as _qs

    _qs.shutdown_all()
    _cancel.deactivate()
    bind_scoped_injector(None)
    bind_scoped_fault_injector(None)
    leaked = [t.name for t in threading.enumerate()
              if t.is_alive() and t is not threading.current_thread()
              and (t.name.startswith("query-scheduler")
                   or t.name.startswith("query-worker"))]
    assert not leaked, \
        f"scheduler threads leaked across the test boundary: {leaked}"
    # stage-watchdog attempt threads may legitimately outlive a
    # tripped watchdog briefly (they drain with the abandoned
    # attempt); give them a bounded join so they cannot pile up
    # across tests, then assert they actually drained
    stragglers = [t for t in threading.enumerate()
                  if t.is_alive() and t.name == "stage-watchdog"]
    deadline = 10.0
    for t in stragglers:
        import time as _time

        t0 = _time.monotonic()
        t.join(deadline)
        deadline = max(0.1, deadline - (_time.monotonic() - t0))
    leaked_wd = [t.name for t in stragglers if t.is_alive()]
    assert not leaked_wd, \
        "stage-watchdog threads still running after the test " \
        f"boundary grace period: {len(leaked_wd)} thread(s)"


@pytest.fixture(autouse=True)
def _reset_kernel_cache():
    """The kernel cache is process-wide (like the device manager): a
    test that shrinks maxEntries or disables it must not starve every
    later test of kernel sharing, and counter assertions must start
    from a clean slate."""
    from spark_rapids_tpu.exec.kernel_cache import GLOBAL
    from spark_rapids_tpu.telemetry.profiler import PROFILER

    GLOBAL.reset()
    PROFILER.reset()
    yield


@pytest.fixture(autouse=True)
def _clear_telemetry_binding():
    """A query-telemetry binding (thread-local) must never outlive its
    test: a finished query's ring would silently collect the next
    test's late events."""
    yield
    from spark_rapids_tpu.telemetry import spans

    spans.deactivate()


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_worker_env() -> dict:
    """Environment for a child process that a test launches.  A device
    belongs to one process at a time and the pytest process has already
    initialised jax, so every worker script a test starts is held to the
    CPU backend — said here once, for all the multi-process launchers."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture()
def cpu_session():
    from spark_rapids_tpu import Session

    return Session(tpu_enabled=False)


@pytest.fixture()
def tpu_session():
    from spark_rapids_tpu import Session

    return Session(tpu_enabled=True)


@pytest.fixture()
def strict_tpu_session():
    """TPU session in test mode: any unexpected host fallback fails the
    test (reference: spark.rapids.sql.test.enabled wiring in conftest)."""
    from spark_rapids_tpu import Session

    return Session({"spark.rapids.tpu.sql.test.enabled": True})


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from jaxpr_eqns(inner)
