"""tpulint — the unified whole-program static-analysis engine.

One engine, one rule API, one baseline — replacing the nine ad-hoc
per-subsystem AST lints that used to live in ``tests/test_lint_*.py``
(~1.4k lines of copy-pasted walkers, each blind to the others' scope).

Why whole-program: the engine's correctness invariants are
cross-cutting — *no host syncs in dispatch paths*, *every permit/
reservation/pin released on unwind*, *telemetry bindings captured at
every thread spawn*, *no lock-order inversions between the
process-global singletons* — and each of them spans subsystems that
used to be linted in isolation.  The reference plugin's promise of
bit-identical results under fallback only holds if these invariants
hold *everywhere*, including the hot paths future PRs add.

Layout::

    analysis/
        project.py    file discovery + cached AST parse
        resolver.py   per-module symbol/call/function index
        findings.py   typed Finding (rule id, kind, file:line, severity)
        engine.py     Rule API, registry, run()
        baseline.py   suppression file load/match/update
        cli.py        python -m spark_rapids_tpu.analysis
        rules/        the rule catalog (docs/static_analysis.md)
        baseline.json audited intentional findings (one justification
                      string each)

Run it::

    python -m spark_rapids_tpu.analysis            # exit 1 on NEW findings
    python -m spark_rapids_tpu.analysis --list-rules
    python -m spark_rapids_tpu.analysis --rule host-sync --no-baseline
    python -m spark_rapids_tpu.analysis --update-baseline

The engine is pure stdlib ``ast`` over the source tree — no jax, no
imports of the analyzed modules — so it runs in well under the 10s
budget and is the fast-fail first step of the tier-1 flow (ROADMAP.md).
"""
from .engine import AnalysisContext, Rule, all_rules, get_rule, run_rules
from .findings import Finding, Severity

__all__ = ["AnalysisContext", "Finding", "Rule", "Severity",
           "all_rules", "get_rule", "run_rules"]
