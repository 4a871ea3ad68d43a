"""Tests for the whole-program static-analysis engine (tpulint).

Three layers:

1. **Inventory meta-test** — every test function of the nine retired
   ``tests/test_lint_*.py`` modules is mapped to the rule id that now
   enforces the same invariant; the registry must cover the full
   inventory, so no invariant was silently dropped in the migration.
2. **Synthetic positive/negative mini-projects** — each detector is
   proven to *fire* on a tiny hand-written violation and to stay quiet
   on the fixed shape.  The live tree being clean must mean the tree
   is clean, not that a rule went inert.
3. **Baseline add/expire semantics and CLI exit codes** (the latter
   via subprocess, the supported entry point).

Beside them, a check the engine has no part in: the documents a reader
starts from cite only files the tree holds.
"""
import json
import glob
import os
import re
import subprocess
import sys
import textwrap

import pytest

from spark_rapids_tpu.analysis import (AnalysisContext, all_rules,
                                       run_rules, Finding)
from spark_rapids_tpu.analysis.baseline import (DEFAULT_BASELINE,
                                                Baseline)
from spark_rapids_tpu.analysis.project import Project

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


# ==========================================================================
# 1. Migration inventory: every retired lint assertion -> covering rule
# ==========================================================================
#: old test function (tests/test_lint_*.py, deleted in the tpulint
#: migration) -> the rule id that now enforces that invariant
OLD_LINT_INVENTORY = {
    # test_lint_adaptive.py
    "test_adaptive_package_never_imports_jax": "jax-import",
    "test_adaptive_package_has_no_host_sync_calls": "host-sync",
    "test_planner_and_executor_never_touch_device_arrays": "host-sync",
    "test_exchange_stats_recording_adds_no_syncs": "host-sync",
    "test_every_rewrite_decision_site_emits_event": "decision-event",
    "test_all_three_rewrite_events_exist": "decision-event",
    "test_executor_emits_stage_stats_and_final_plan": "decision-event",
    # test_lint_kernel_cache.py
    "test_no_exec_calls_jit_directly": "jit-direct",
    "test_kernel_cache_is_the_compile_path": "jit-direct",
    # test_lint_profiler.py
    "test_no_ad_hoc_stopwatch_around_dispatches": "stopwatch",
    "test_profiler_path_never_syncs_the_device": "host-sync",
    "test_dispatch_guard_is_one_attribute_read": "profiler-guard",
    "test_lint_watches_real_sites": "profiler-guard",
    # test_lint_qos.py
    "test_every_shed_or_preempt_decision_site_emits_telemetry":
        "decision-event",
    "test_no_tpu_overloaded_without_retry_after_ms": "overloaded-hint",
    "test_overload_monitor_thread_captures_binding": "thread-capture",
    # test_lint_recovery.py
    "test_no_direct_file_writes_in_recovery_or_spill": "atomic-write",
    "test_durable_writes_use_the_shared_fsio_helpers": "atomic-write",
    "test_frame_reads_verify_crc_in_same_function": "crc-verify",
    "test_recovery_never_deserializes_frames": "no-deserialize",
    "test_manifest_reader_checks_plan_fingerprint":
        "manifest-fingerprint",
    "test_recovery_package_never_imports_jax": "jax-import",
    # test_lint_scheduler.py
    "test_every_drain_loop_polls_a_cancellation_checkpoint":
        "cancel-poll",
    "test_scheduler_thread_spawns_capture_telemetry_binding":
        "thread-capture",
    "test_worker_binds_and_unbinds_the_cancel_token": "worker-unbind",
    # test_lint_shuffle.py
    "test_no_host_materialization_on_the_device_shuffle_hot_path":
        "host-sync",
    "test_exchange_step_dispatcher_polls_cancellation":
        "collective-cancel",
    "test_collective_dispatch_sites_poll_cancellation":
        "collective-cancel",
    # test_lint_streaming.py
    "test_every_while_loop_polls_cancellation_or_stop": "cancel-poll",
    "test_no_direct_file_writes_in_streaming": "atomic-write",
    "test_ledger_commit_uses_the_shared_fsio_helpers": "atomic-write",
    "test_skip_cap_shed_decisions_emit_stream_events":
        "decision-event",
    "test_streaming_events_use_the_stream_namespace_and_cover_catalog":
        "event-drift",
    "test_streaming_package_never_imports_jax": "jax-import",
    # test_lint_telemetry.py
    "test_no_bare_emit_outside_telemetry": "bare-emit",
    "test_emit_event_is_exception_safe_by_construction": "emit-safe",
    "test_every_thread_spawn_site_captures_telemetry_context":
        "thread-capture",
}

#: rules with no retired-lint ancestor (net-new whole-program checks)
NEW_RULE_IDS = {"lock-order", "race-global", "resource-pair",
                "conf-drift"}


def test_rule_registry_covers_retired_lint_inventory():
    ids = {cls.id for cls in all_rules()}
    needed = set(OLD_LINT_INVENTORY.values())
    missing = needed - ids
    assert not missing, (
        f"retired lint invariants with no covering rule: {missing}")
    # the net-new whole-program rules exist too
    assert NEW_RULE_IDS <= ids
    assert len(OLD_LINT_INVENTORY) == 37  # the full retired inventory


def test_retired_lint_modules_are_gone():
    leftovers = glob.glob(os.path.join(TESTS_DIR, "test_lint_*.py"))
    assert not leftovers, (
        f"retired ad-hoc lint modules still present: {leftovers} — "
        f"their invariants live in spark_rapids_tpu/analysis now")


# ==========================================================================
# Live tree: the committed baseline keeps the gate green
# ==========================================================================
def test_live_tree_is_clean_under_committed_baseline():
    findings = run_rules(AnalysisContext(Project(REPO_ROOT)))
    bl = Baseline.load(DEFAULT_BASELINE)
    new, _suppressed, stale = bl.split(findings)
    assert not new, "new findings on the committed tree:\n" + \
        "\n".join(f.render() for f in new)
    assert not stale, f"stale baseline entries: {stale}"


def test_committed_baseline_entries_are_all_justified():
    bl = Baseline.load(DEFAULT_BASELINE)
    assert bl.entries, "baseline unexpectedly empty"
    for fp, e in bl.entries.items():
        assert e["justification"] and \
            not e["justification"].startswith("TODO"), (
                f"baseline entry {fp} ({e['detail']}) lacks an "
                f"audit justification")


# ==========================================================================
# 2. Synthetic mini-projects: each detector demonstrably fires
# ==========================================================================
def _mini(tmp_path, files):
    """Materialize a mini-project and return its AnalysisContext."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return AnalysisContext(Project(str(tmp_path)))


def _findings(tmp_path, files, rule, *kinds):
    """Run one rule on a mini-project, filtered to real (non-health)
    findings, optionally to specific kinds."""
    out = run_rules(_mini(tmp_path, files), [rule])
    out = [f for f in out if f.kind != "health"]
    if kinds:
        out = [f for f in out if f.kind in kinds]
    return out


def test_host_sync_fires_on_synthetic_positive(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/exec/demo.py": """\
            import jax.numpy as jnp

            def gather(x):
                return x.tolist()

            def coerce(x):
                return float(jnp.sum(x))
            """,
    }, "host-sync", "sync-call", "scalar-coerce")
    details = {f.detail for f in hits}
    assert "gather:tolist" in details
    assert "coerce:float" in details


def test_host_sync_quiet_on_gated_and_host_paths(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/exec/demo.py": """\
            def fetch_counts(pending):
                return [int(n) for n in pending.tolist()]

            def lexsort_np(cols):
                return cols[0].item()
            """,
    }, "host-sync", "sync-call", "scalar-coerce")
    assert not hits, [f.render() for f in hits]


def test_lock_order_detects_synthetic_cycle(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/scheduler/demo.py": """\
            import threading

            A_LOCK = threading.Lock()
            B_LOCK = threading.Lock()

            def ab():
                with A_LOCK:
                    with B_LOCK:
                        return 1

            def ba():
                with B_LOCK:
                    with A_LOCK:
                        return 2
            """,
    }, "lock-order", "cycle")
    assert len(hits) == 1
    assert "A_LOCK" in hits[0].detail and "B_LOCK" in hits[0].detail


def test_lock_order_quiet_on_consistent_order(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/scheduler/demo.py": """\
            import threading

            A_LOCK = threading.Lock()
            B_LOCK = threading.Lock()

            def ab():
                with A_LOCK:
                    with B_LOCK:
                        return 1

            def ab_again():
                with A_LOCK:
                    with B_LOCK:
                        return 2
            """,
    }, "lock-order", "cycle")
    assert not hits


def test_race_global_flags_unlocked_thread_mutation(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/scheduler/demo.py": """\
            _PINS = {}

            def _watch_loop():
                _PINS["k"] = 1
            """,
    }, "race-global", "unlocked-mutation")
    assert len(hits) == 1
    assert hits[0].detail.startswith("_watch_loop:_PINS")


def test_race_global_quiet_when_lock_held(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/scheduler/demo.py": """\
            import threading

            _PINS = {}
            _LOCK = threading.Lock()

            def _watch_loop():
                with _LOCK:
                    _PINS["k"] = 1
            """,
    }, "race-global", "unlocked-mutation")
    assert not hits


def test_resource_pair_flags_unreleased_acquire(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/memory/demo.py": """\
            def leak(pool, batch, use):
                buf = pool.acquire_batch(batch)
                use(buf)
                return None
            """,
    }, "resource-pair", "leak")
    assert len(hits) == 1
    assert hits[0].detail == "leak:acquire_batch"


def test_resource_pair_accepts_unwind_safe_shapes(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/memory/demo.py": """\
            def ok_finally(pool, b, use):
                buf = pool.acquire_batch(b)
                try:
                    use(buf)
                finally:
                    pool.release_batch(buf)

            def ok_adjacent(pool, b):
                buf = pool.acquire_batch(b)
                pool.release_batch(buf)
                return buf

            def ok_with(pool, b, use):
                with pool.acquire_batch(b) as buf:
                    use(buf)
            """,
    }, "resource-pair", "leak")
    assert not hits, [f.render() for f in hits]


def test_cancel_poll_flags_unpolled_drain_loop(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/exec/demo.py": """\
            def drain(q, handle):
                while True:
                    handle(q.get())
            """,
    }, "cancel-poll", "drain-loop")
    assert len(hits) == 1


def test_cancel_poll_quiet_when_loop_polls(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/exec/demo.py": """\
            def drain(q, tok, handle):
                while True:
                    tok.check_cancel()
                    handle(q.get())
            """,
    }, "cancel-poll", "drain-loop")
    assert not hits


def test_jit_direct_flags_raw_jit_in_exec(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/exec/demo.py": """\
            import jax

            def compile_it(fn):
                return jax.jit(fn)
            """,
    }, "jit-direct", "direct-jit")
    assert len(hits) == 1
    assert hits[0].detail == "compile_it:jit"


def test_atomic_write_flags_direct_open(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/recovery/demo.py": """\
            def save(path, data):
                with open(path, "w") as f:
                    f.write(data)
            """,
    }, "atomic-write", "direct-write")
    assert len(hits) == 1


def test_atomic_write_quiet_on_fsio_helper(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/recovery/demo.py": """\
            from spark_rapids_tpu.utils.fsio import atomic_write_bytes

            def save(path, data):
                atomic_write_bytes(path, data)
            """,
    }, "atomic-write", "direct-write")
    assert not hits


def test_jax_import_flags_device_import_in_host_layer(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/adaptive/demo.py": """\
            import jax

            def plan(stats):
                return stats
            """,
    }, "jax-import", "device-import")
    assert len(hits) == 1
    assert hits[0].detail == "import:jax"


def test_thread_capture_flags_unbound_spawn(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/scheduler/demo.py": """\
            import threading

            def spawn(fn):
                t = threading.Thread(target=fn)
                t.start()
                return t
            """,
    }, "thread-capture", "unbound-spawn")
    assert len(hits) == 1


def test_thread_capture_quiet_when_target_is_bound(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/scheduler/demo.py": """\
            import threading
            from spark_rapids_tpu.telemetry import spans

            def spawn(fn):
                t = threading.Thread(
                    target=spans.bound(spans.capture(), fn))
                t.start()
                return t
            """,
    }, "thread-capture", "unbound-spawn")
    assert not hits


def test_bare_emit_flags_direct_emit_outside_telemetry(tmp_path):
    hits = _findings(tmp_path, {
        "spark_rapids_tpu/exec/demo.py": """\
            def note(log):
                log.emit("spill", nbytes=1)
            """,
    }, "bare-emit", "bare-emit")
    assert len(hits) == 1


def test_overloaded_hint_requires_retry_after_ms(tmp_path):
    files = {
        "spark_rapids_tpu/scheduler/demo.py": """\
            def shed(TpuOverloaded):
                raise TpuOverloaded("busy")
            """,
    }
    hits = _findings(tmp_path, files, "overloaded-hint",
                     "missing-hint")
    assert len(hits) == 1
    files_ok = {
        "spark_rapids_tpu/scheduler/demo.py": """\
            def shed(TpuOverloaded):
                raise TpuOverloaded("busy", retry_after_ms=50)
            """,
    }
    hits = _findings(tmp_path / "ok", files_ok, "overloaded-hint",
                     "missing-hint")
    assert not hits


def test_parse_error_surfaces_as_engine_finding(tmp_path):
    ctx = _mini(tmp_path, {
        "spark_rapids_tpu/exec/broken.py": "def oops(:\n",
    })
    findings = run_rules(ctx, ["jit-direct"])
    parse = [f for f in findings
             if f.rule == "engine" and f.kind == "parse-error"]
    assert len(parse) == 1
    assert parse[0].file == "spark_rapids_tpu/exec/broken.py"


# ==========================================================================
# 3a. Baseline semantics: add, line-move tolerance, expire, versioning
# ==========================================================================
def _finding(detail="gather:tolist", line=10):
    return Finding(rule="host-sync", kind="sync-call",
                   file="spark_rapids_tpu/exec/x.py", line=line,
                   message="m", detail=detail)


def test_baseline_add_suppress_and_expire(tmp_path):
    f1 = _finding()
    f2 = _finding(detail="other:item")
    path = str(tmp_path / "baseline.json")

    # empty baseline: everything is new
    new, supp, stale = Baseline([]).split([f1, f2])
    assert (len(new), len(supp), len(stale)) == (2, 0, 0)

    # add f1, reload: f1 suppressed, f2 still new
    Baseline.write(path, Baseline([]).updated([f1]))
    bl = Baseline.load(path)
    new, supp, stale = bl.split([f1, f2])
    assert [f.detail for f in new] == ["other:item"]
    assert [f.detail for f in supp] == ["gather:tolist"]
    assert not stale

    # fingerprints are line-number-free: a moved finding stays matched
    new, supp, stale = bl.split([_finding(line=999), f2])
    assert [f.detail for f in supp] == ["gather:tolist"]

    # expire: when the finding disappears the entry goes stale
    new, supp, stale = bl.split([f2])
    assert [f.detail for f in new] == ["other:item"]
    assert not supp
    assert len(stale) == 1 and stale[0]["detail"] == "gather:tolist"

    # --update-baseline semantics drop the stale entry...
    Baseline.write(path, bl.updated([f2]))
    bl2 = Baseline.load(path)
    assert len(bl2.entries) == 1
    # ...and fresh entries carry the fill-me-in marker
    entry = next(iter(bl2.entries.values()))
    assert entry["justification"].startswith("TODO")


def test_baseline_update_preserves_justifications(tmp_path):
    f1 = _finding()
    path = str(tmp_path / "baseline.json")
    data = Baseline([]).updated([f1])
    data["entries"][0]["justification"] = "audited: intentional"
    Baseline.write(path, data)
    data2 = Baseline.load(path).updated([f1])
    assert data2["entries"][0]["justification"] == \
        "audited: intentional"


def test_baseline_rejects_unknown_version(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError, match="unsupported version"):
        Baseline.load(str(path))


# ==========================================================================
# 3b. CLI exit codes (subprocess — the supported entry point)
# ==========================================================================
def _cli(tmp_path, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.analysis",
         "--root", str(tmp_path), *argv],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=120)


def _write_conf_tree(tmp_path, documented):
    """A mini-project the conf-drift rule reads whole: ten registered
    keys (its health floor), the first ``documented`` of them listed in
    docs/configs.md."""
    pkg = tmp_path / "spark_rapids_tpu"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "__init__.py").write_text("")
    keys = [f"spark.rapids.tpu.demo.k{i}" for i in range(10)]
    (pkg / "config.py").write_text(
        "".join(f'K{i} = conf("{k}")\n' for i, k in enumerate(keys)))
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "configs.md").write_text(
        "".join(f"| `{k}` | demo |\n" for k in keys[:documented]))


def test_cli_exit_codes_clean_dirty_and_baselined(tmp_path):
    baseline = str(tmp_path / "bl.json")

    # clean tree -> 0
    _write_conf_tree(tmp_path, documented=10)
    r = _cli(tmp_path, "--rule", "conf-drift", "--no-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s)" in r.stdout

    # a key the docs lack -> 1, finding rendered
    _write_conf_tree(tmp_path, documented=9)
    r = _cli(tmp_path, "--rule", "conf-drift", "--no-baseline")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "[conf-drift/undocumented-key]" in r.stdout

    # --update-baseline writes the suppression and exits 0...
    r = _cli(tmp_path, "--rule", "conf-drift",
             "--baseline", baseline, "--update-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    # ...after which the same finding is baselined -> 0
    r = _cli(tmp_path, "--rule", "conf-drift",
             "--baseline", baseline)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 baselined" in r.stdout


def test_cli_unknown_rule_is_usage_error(tmp_path):
    _write_conf_tree(tmp_path, documented=10)
    r = _cli(tmp_path, "--rule", "no-such-rule")
    assert r.returncode == 2
    assert "unknown rule" in r.stderr


# ==========================================================================
# 4. The documents a reader starts from name only files that exist
# ==========================================================================
#: README.md and the guides under docs/.  The histories (CHANGES.md,
#: PERF.md, ROADMAP.md, SURVEY.md) are left out: they name files of
#: their day.
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO_ROOT).replace(os.sep, "/")
    for p in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md")))

#: a back-ticked path ending in .py, .md, .json or / (a ``:line`` or
#: ``:function`` after it is allowed); patterns and placeholders
#: (``*``, ``<...>``) are not paths and do not match
_CITED_PATH_RE = re.compile(
    r"`([\w./-]+(?:\.py|\.md|\.json|/))(?::[\w.,:-]+)?`")


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_that_exist(doc):
    """A cited path resolves from the repo root, from the package (the
    guides write ``exec/joins.py`` for ``spark_rapids_tpu/exec/joins.py``)
    or from the document's own directory."""
    with open(os.path.join(REPO_ROOT, doc), encoding="utf-8") as f:
        cited = sorted(set(_CITED_PATH_RE.findall(f.read())))
    bases = ("", "spark_rapids_tpu", os.path.dirname(doc))
    missing = [p for p in cited if not any(
        os.path.exists(os.path.join(REPO_ROOT, b, p)) for b in bases)]
    assert not missing, f"{doc} cites files the tree lacks: {missing}"
