"""The graceful-degradation ladder.

Reference analogue: the plugin's core promise — transparent fallback
with bit-identical results (SURVEY §L0).  PR-1 made single-device OOMs
recoverable; this module makes *query-level* fault exhaustion
recoverable: when a distributed execution exhausts its bounded stage
retries, the query walks DOWN the ladder instead of failing —

    rung 0:  distributed SPMD execution (the native plan)
    rung 0.5: SHRUNKEN-MESH re-execution — a peer process died or
            stopped heartbeating (``TpuPeerLost``): re-form the mesh
            on the surviving devices and re-execute, resuming
            completed stages from the recovery substrate's
            checkpoints (``parallel/elastic.py``)
    rung 1: single-process device execution (``Session.execute``)
    rung 2: the CPU-exec plan (``plan.overrides.cpu_exec_plan`` — no
            TPU overrides at all; the oracle engine)

Every rung produces the same results by construction (the host engine
is the oracle the device plan is tested against; a float SUM/AVG of a
device rung equals the CPU rung's to rounding, everything else to the
bit: docs/fault_tolerance.md), so degradation trades throughput for
availability, never correctness.

The final rung is surfaced as ``fault.degradeLevel`` in
``Session.last_metrics`` next to the retry counters, and a DEGRADED
warning rides the trace log — a degraded query must be visibly
degraded.  Rung 1 -> 2 lives inside ``Session.execute`` itself (the
single-process path has its own fault exposure); this module drives
rung 0 -> 1.
"""
from __future__ import annotations

import logging

from .errors import TpuFaultError
from .stats import DEGRADE_SINGLE_PROCESS, GLOBAL as _stats
from .stats import fault_summary

log = logging.getLogger(__name__)


def run_with_fault_tolerance(session, df, mesh=None, n_devices: int = 8):
    """Execute ``df`` distributed with the full fault-tolerance
    protocol: bounded stage re-execution inside the runner, then the
    degradation ladder on exhaustion.  Returns the collected HostBatch;
    ``session.last_metrics`` carries the ``fault.*`` counters and the
    final ``degradeLevel``."""
    from ..config import FAULT_MAX_TOTAL_ATTEMPTS, RECOVERY_ENABLED
    from .budget import GLOBAL as _budget

    # ONE recovery manager spanning every rung: checkpoints the
    # distributed attempt writes are what the shrunken-mesh rung
    # resumes from after a peer loss
    recovery = None
    if session.conf.get(RECOVERY_ENABLED):
        from ..recovery.manager import RecoveryManager

        recovery = RecoveryManager(session.conf)
        recovery.attach_query(df.plan)
    # arm the unified attempt budget at THIS outermost entry; the
    # nested Session.execute on rung 1 sees it armed and leaves the
    # ledger alone, so charges accumulate across all rungs
    owned = _budget.begin(session.conf.get(FAULT_MAX_TOTAL_ATTEMPTS))
    try:
        out = _run_ladder(session, df, mesh, n_devices, recovery)
        # surface the cross-rung attempt ledger before it is disarmed
        # (Session.execute does the same merge for single-process runs)
        session.last_metrics = dict(
            getattr(session, "last_metrics", None) or {})
        session.last_metrics.update(_budget.snapshot())
        return out
    finally:
        _budget.end(owned)


def _run_ladder(session, df, mesh, n_devices: int, recovery=None):
    from ..config import FAULT_DEGRADE_ENABLED
    from ..parallel.runner import run_distributed
    from .errors import TpuPeerLost

    try:
        out = run_distributed(session, df, mesh=mesh,
                              n_devices=n_devices, recovery=recovery)
        session.last_metrics = dict(
            getattr(session, "last_metrics", None) or {})
        session.last_metrics.update(_stats.snapshot())
        return out
    except TpuPeerLost as e:
        # rung 0.5: a peer died — re-form the mesh on the survivors
        # and re-execute from checkpoints before giving up on
        # distributed execution entirely
        if not session.conf.get(FAULT_DEGRADE_ENABLED):
            raise
        from ..parallel.elastic import reexecute_on_shrunken_mesh
        from ..parallel.mesh import make_mesh

        try:
            return reexecute_on_shrunken_mesh(
                session, df, mesh or make_mesh(n_devices),
                f"{type(e).__name__}: {e}", recovery=recovery)
        except TpuFaultError as e2:
            return _degrade_single_process(session, df, e2)
    except TpuFaultError as e:
        if not session.conf.get(FAULT_DEGRADE_ENABLED):
            raise
        return _degrade_single_process(session, df, e)


def _degrade_single_process(session, df, e):
    """Rung 1: the whole query on the single-process engine (rung 2 —
    the CPU-exec oracle plan — lives inside ``Session.execute``)."""
    from .budget import GLOBAL as _budget

    _budget.charge("ladder_single_process", site="fault.ladder")
    # carry the distributed attempt's counters across the rung —
    # Session.execute re-arms the per-query stats
    pre = _stats.snapshot()
    log.warning(
        "distributed execution exhausted fault recovery (%s: %s) — "
        "DEGRADED to the single-process rung", type(e).__name__, e)
    out = session.execute(df.plan)  # rung 1 (rung 2 lives inside)
    merged = dict(session.last_metrics or {})
    for k, v in pre.items():
        if k != "fault.degradeLevel":
            merged[k] = merged.get(k, 0) + v
    merged["fault.degradeLevel"] = max(
        merged.get("fault.degradeLevel", 0), DEGRADE_SINGLE_PROCESS)
    _stats.set_max("degradeLevel", merged["fault.degradeLevel"])
    session.last_metrics = merged
    # the degrade decision must be visible in the profile the user
    # will actually read: session.execute installed the rung-1
    # query's telemetry as last_profile, so emit AFTER it (the
    # event log stays live for late events) and refresh its
    # metrics with the cross-rung merge
    from ..config import TELEMETRY_ENABLED
    from ..telemetry.events import emit_event

    emit_event("degrade", level=DEGRADE_SINGLE_PROCESS,
               rung="single-process", cause=type(e).__name__)
    if getattr(session, "last_profile", None) is not None \
            and session.conf.get(TELEMETRY_ENABLED):
        # telemetry was on for the rung-1 execute, so last_profile
        # is THIS query's — refresh with the cross-rung merge
        session.last_profile.metrics = dict(merged)
    summary = fault_summary(merged)
    if summary:
        log.warning("query completed DEGRADED: %s", summary)
    return out
