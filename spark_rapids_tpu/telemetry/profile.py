"""EXPLAIN-ANALYZE profiles: the physical plan annotated with per-exec
metrics, the span tree, a hot-operator summary and the event digest.

Reference analogue: the per-exec SQLMetrics panel of the Spark SQL UI
(GpuExec's standard metric set rendered on the plan graph) plus the
"Rethinking Analytical Processing in the GPU Era" argument that
data-movement-aware profiles must precede any perf work — upload,
readback and device-sync wall are first-class columns here.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

#: metric suffixes excluded from the "is this exec interesting" test
_STD = ("numOutputRows", "numOutputBatches", "totalTime",
        "deviceSyncTime")


def _fmt_ms(ns) -> str:
    return f"{ns / 1e6:.2f}ms"


def _exec_prefixes(metrics: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """Group a flat metric snapshot by its ``<ExecName>.`` prefixes
    (counter families like ``retry.``/``fault.`` are not execs)."""
    out: Dict[str, Dict[str, int]] = {}
    for key, val in metrics.items():
        if "." not in key:
            continue
        name, metric = key.split(".", 1)
        if not name or not name[0].isupper():
            continue  # retry./fault./telemetry. counter families
        out.setdefault(name, {})[metric] = val
    return out


def explain_analyze(plan, metrics: Dict[str, int]) -> str:
    """Render ``plan``'s tree annotated with each exec's measured
    metrics (wall, device-sync, rows, batches) — the EXPLAIN ANALYZE
    surface.  Execs that never initialized metrics annotate empty."""
    per_exec = _exec_prefixes(metrics)

    def annotate(node) -> str:
        m = per_exec.get(node.name)
        if not m:
            return ""
        parts = []
        if "totalTime" in m:
            parts.append(f"wall={_fmt_ms(m['totalTime'])}")
        if m.get("deviceSyncTime"):
            parts.append(f"sync={_fmt_ms(m['deviceSyncTime'])}")
        if "numOutputRows" in m:
            parts.append(f"rows={m['numOutputRows']}")
        if "numOutputBatches" in m:
            parts.append(f"batches={m['numOutputBatches']}")
        extras = {k: v for k, v in m.items() if k not in _STD and v}
        for k in sorted(extras)[:3]:
            parts.append(f"{k}={extras[k]}")
        return "[" + " ".join(parts) + "] " if parts else ""

    return plan.tree_string(annotate=annotate)


def hot_operators(metrics: Dict[str, int],
                  top_n: int = 5) -> List[Tuple[str, int, int]]:
    """Top-N execs by measured wall: (name, wall_ns, rows)."""
    per_exec = _exec_prefixes(metrics)
    ranked = sorted(
        ((name, m.get("totalTime", 0), m.get("numOutputRows", 0))
         for name, m in per_exec.items()),
        key=lambda t: t[1], reverse=True)
    return [r for r in ranked if r[1] > 0][:top_n]


class QueryProfile:
    """The finished profile of one query: span tree, event log (a LIVE
    reference — late events like a degrade decision taken above the
    finalize layer still appear), metric snapshot, plan, HBM timeline."""

    def __init__(self, tele, metrics: Dict[str, int],
                 plan=None):
        self.query_id = tele.query_id
        self.root = tele.root
        self.events = tele.events
        self.metrics = dict(metrics)
        # the annotated plan is rendered NOW, not at report time:
        # retaining the live exec tree would pin everything its GC
        # finalizers release (HostToDeviceExec's cached uploads,
        # spill-registered buffers) for as long as the session's
        # profile ring holds this profile — a finished query must not
        # hold device memory
        self.plan_text = (explain_analyze(plan, self.metrics)
                          if plan is not None else None)
        self.hbm_timeline = list(tele.hbm_timeline)
        #: per-query kernel-profiler deltas ({fingerprint ->
        #: profiler.KernelStat}) — back-filled by
        #: Session._finalize_metrics when the profiler conf is on
        self.kernel_stats = None

    # ------------------------------------------------------------------
    @property
    def wall_ns(self) -> int:
        return self.root.wall_ns

    def span_tree(self) -> Dict:
        """Nested plain-dict form of the span tree."""
        return self.root.to_dict()

    def exec_spans(self) -> Dict[str, Dict]:
        """Flat exec-name -> span-dict view (test/assertion surface)."""
        out = {}

        def walk(sp):
            if sp["kind"] == "exec":
                out[sp["name"]] = sp
            for c in sp["children"]:
                walk(c)

        walk(self.span_tree())
        return out

    # ------------------------------------------------------------------
    def _render_span(self, sp: Dict, indent: int,
                     lines: List[str]) -> None:
        pad = "  " * indent
        parts = [f"{pad}{sp['kind']}:{sp['name']}",
                 f"wall={_fmt_ms(sp['wall_ns'])}"]
        if sp["device_sync_ns"]:
            parts.append(f"sync={_fmt_ms(sp['device_sync_ns'])}")
        if sp["rows"]:
            parts.append(f"rows={sp['rows']}")
        if sp["batches"]:
            parts.append(f"batches={sp['batches']}")
        if sp["attrs"]:
            parts.append(str(sp["attrs"]))
        lines.append(" ".join(parts))
        for c in sp["children"]:
            self._render_span(c, indent + 1, lines)

    def render(self, top_n: int = 5,
               device_trace: Optional[str] = None) -> str:
        """The full EXPLAIN-ANALYZE report; with ``device_trace`` (the
        path of a profiler trace's xplane) it ends in the device's
        seconds by program and phase."""
        lines = [f"== Query profile {self.query_id} "
                 f"(wall={_fmt_ms(self.wall_ns)}) =="]
        if self.plan_text is not None:
            lines.append("")
            lines.append("-- Physical plan (annotated) --")
            if any(k.startswith("aqe.") for k in self.metrics):
                # the rendered tree IS the final re-optimized plan (the
                # session profiles ctx.aqe_final_phys) — mark it the
                # way Spark's UI marks an AdaptiveSparkPlanExec
                lines.append(
                    "AdaptiveSparkPlan isFinalPlan=true (stages="
                    f"{self.metrics.get('aqe.numStages', 0)})")
            lines.append(self.plan_text)
        hot = hot_operators(self.metrics, top_n)
        if hot:
            lines.append("")
            lines.append(f"-- Top {len(hot)} operators by wall --")
            for name, wall, rows in hot:
                lines.append(f"  {name}: {_fmt_ms(wall)} "
                             f"(rows={rows})")
        kc = {k.split(".", 1)[1]: v for k, v in self.metrics.items()
              if k.startswith("kernelCache.")}
        if kc:
            # kernelCache. is a counter family (lowercase prefix), so
            # the per-exec grouping above skips it — render explicitly
            disp = kc.get("dispatches", 0)
            rate = f"{kc.get('hits', 0) / disp:.1%}" if disp else "n/a"
            lines.append("")
            lines.append(f"-- Kernel cache (hitRate={rate}) --")
            for k in sorted(kc):
                v = kc[k]
                lines.append(f"  {k}: "
                             + (_fmt_ms(v) if k.endswith("Ns") else str(v)))
        if self.kernel_stats:
            from .profiler import render_dispatches

            lines.append("")
            lines.extend(render_dispatches(self.kernel_stats,
                                           top_n=max(top_n, 10)))
        aqe = {k.split(".", 1)[1]: v for k, v in self.metrics.items()
               if k.startswith("aqe.")}
        if aqe:
            # aqe. is a counter family (lowercase prefix) like
            # kernelCache. — render its decisions explicitly
            lines.append("")
            lines.append("-- Adaptive execution --")
            for k in sorted(aqe):
                lines.append(f"  {k}: {aqe[k]}")
        rec = {k.split(".", 1)[1]: v for k, v in self.metrics.items()
               if k.startswith("recovery.")}
        if rec:
            # recovery. is a counter family too; a resumed query must
            # be visibly resumed — the header carries how many stages
            # were served from checkpoints instead of re-executed
            resumed = rec.get("numStagesResumed", 0)
            lines.append("")
            lines.append("-- Stage recovery "
                         f"(resumedFromStage={resumed}) --")
            for k in sorted(rec):
                lines.append(f"  {k}: {rec[k]}")
        ex: Dict[str, Dict[str, int]] = {}
        for k, v in self.metrics.items():
            if k.startswith("shuffle.exchange") and k.count(".") >= 2:
                head, metric = k.rsplit(".", 1)
                ex.setdefault(head, {})[metric] = v
        if ex:
            # per-exchange partition row histograms (StageStats) —
            # present whether or not adaptive execution ran
            lines.append("")
            lines.append("-- Exchange partition histograms --")

            def _eid(head: str) -> int:
                try:
                    return int(head[len("shuffle.exchange"):])
                except ValueError:
                    return 0

            for head in sorted(ex, key=_eid):
                m = ex[head]
                parts = [f"partitions={m.get('partitions', 0)}",
                         f"rows={m.get('rowsTotal', 0)}",
                         f"bytes={m.get('bytesTotal', 0)}"]
                if "partRowsP50" in m:
                    parts.append(
                        f"rows/part min={m.get('partRowsMin', 0)} "
                        f"p50={m.get('partRowsP50', 0)} "
                        f"max={m.get('partRowsMax', 0)} "
                        f"skew={m.get('skewPct', 0)}%")
                lines.append(f"  {head}: " + " ".join(parts))
        lines.append("")
        lines.append("-- Span tree --")
        self._render_span(self.span_tree(), 0, lines)
        from .events import replay_summary

        summary = replay_summary(self.events.snapshot())
        lines.append("")
        lines.append(f"-- Events ({summary['num_events']}"
                     + (f", {self.events.dropped} dropped"
                        if self.events.dropped else "") + ") --")
        for etype in sorted(summary["counts"]):
            lines.append(f"  {etype}: {summary['counts'][etype]}")
        if self.hbm_timeline:
            # (ts, allocated, peak): the peak column catches spikes
            # freed between samples
            peak = max(t[2] for t in self.hbm_timeline)
            lines.append("")
            lines.append(f"-- HBM watermark ({len(self.hbm_timeline)} "
                         f"samples, peak={peak}B) --")
        if device_trace is not None:
            from . import device_trace as _device_trace

            lines.append("")
            lines.extend(_device_trace.render(
                _device_trace.load(device_trace), top_n=max(top_n, 10)))
        return "\n".join(lines)

    def __repr__(self):  # pragma: no cover
        return (f"QueryProfile({self.query_id}, "
                f"wall={_fmt_ms(self.wall_ns)})")
