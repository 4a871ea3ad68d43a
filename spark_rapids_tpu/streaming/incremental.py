"""Incremental micro-batch planning over the recovery substrate.

The core trick: a streaming query's cumulative plan at tick N and at
tick N+1 differ ONLY in the file lists of their scan leaves.  The
recovery substrate already fingerprints every exchange from its host
subtree + leaf data identity, so the tick-over-tick delta is visible as
a fingerprint delta per exchange occurrence.  This module

1. normalizes exchange keys so the same occurrence matches across
   ticks despite differing file counts (``FileScan[parquet](N files)``
   → ``FileScan[parquet](* files)``),
2. derives a :class:`StreamRecoveryManager` whose query fingerprint is
   the STREAM fingerprint (stable across ticks — checkpoints of every
   tick share one pinned query directory), and
3. merges growing exchanges: for an exchange whose inputs only GREW,
   executes the delta subtree over just the new files on the host path
   and appends its frames to the previous tick's committed frames,
   writing the result under the new exchange fingerprint.  The
   cumulative query then resumes that exchange from the merged
   checkpoint instead of rescanning history.

Correctness of the merge (why append == recompute): merges
are attempted only for HashPartitioning exchanges over per-row
content-addressed partition ids, with nothing between scan and exchange
except row-local operators (filter/project/expand/generate) and at most
a PARTIAL hash aggregate.  Per output partition, old frames hold
exactly the rows (or ≤1 partial-agg row per group per file) of the
committed file prefix, delta frames those of the new suffix, in file
order — which is exactly the order the cold cumulative execution
produces, because discovery is sorted and the prefix is
fingerprint-stable.  The FINAL aggregate above the exchange merges
partials with order-insensitive buffers per group, so the cumulative
query over the merged checkpoint sees the frames a cold full recompute
would, in its order.  One thing differs between them: the delta's
partial aggregates are the HOST engine's, a cold recompute's the
engine's that runs it.  Both give the same keys, counts, integer sums,
minima, maxima and picks, to the bit.  A float64 SUM the host engine
adds row by row; the device engine adds a group's sorted rows block by
block (``ops/kernels/segment.segmented_scan``) and, on a TPU, in
float32 pairs: the same sum in another order, equal to rounding (1e-12
relative is what tests/test_streaming.py holds it to; 7e-16 is what it
reads) and not to the bit.  ``sql.variableFloatAgg.enabled=false``
keeps float aggregates on the host engine everywhere, and the identity
is bit for bit again.  Anything outside this shape (range/round-robin
partitioning, final/complete aggregates below the exchange, joins in
the subtree) is skipped with a ``stream_incremental_skip`` event and
recomputes from scratch — correct, just not incremental.

No jax here: delta subtrees run on the HOST operator path (the frames
are mode-independent; the cumulative query resumes them on any rung).
"""
from __future__ import annotations

import logging
import re
from typing import Dict, List, Optional, Tuple

from ..recovery.manager import (RecoveryManager, _digest, _exchange_key,
                                _leaf_material, schema_signature,
                                RESULT_CONF_KEYS)
from ..scheduler.cancel import TpuQueryCancelled, check_cancel
from ..telemetry.events import emit_event

log = logging.getLogger(__name__)

#: host execs that are row-local (each output row is a function of one
#: input row of one file) — safe between a scan and a merged exchange
_INCREMENTAL_SAFE_EXECS = frozenset({
    "FileScanExec", "ProjectExec", "FilterExec", "ExpandExec",
    "GenerateExec",
})

_FILE_COUNT_RE = re.compile(r"(FileScan\[\w+\])\(\d+ files\)")
#: ``HashPartitioning([k1, k2], 8)`` / ``RangePartitioning(8)`` — the
#: trailing fan-out tracks the input partition count, which grows with
#: the file set; occurrence matching must see through it
_PART_N_RE = re.compile(r"(\w+Partitioning\()((?:\[[^\]]*\], )?)\d+\)")


def normalize_plan_text(text: str) -> str:
    """Erase scan file counts AND partitioning fan-outs so the same
    plan shape matches across ticks with different cumulative file
    sets (the planner scales both with the input partition count)."""
    return _PART_N_RE.sub(r"\1\2*)",
                          _FILE_COUNT_RE.sub(r"\1(* files)", text))


def occurrence_key(norm_key: str, idx: int) -> str:
    """Stable ledger key of one exchange occurrence: digest of the
    normalized subtree string + preorder occurrence index."""
    return f"{_digest(norm_key)}#{idx}"


def stream_fingerprint(conf, plan) -> str:
    """Identity of a continuous query: normalized logical template tree
    + result-affecting conf.  Deliberately EXCLUDES leaf data — the
    whole point is that every tick, over a growing file set, shares one
    checkpoint namespace (one pinned query dir, one ledger)."""
    conf_part = "\n".join(
        f"{k}={conf.get_key(k)!r}" for k in RESULT_CONF_KEYS)
    return _digest("stream\n" + normalize_plan_text(plan.tree_string())
                   + "\n" + conf_part)


def _exchange_occurrences(phys) -> Dict[Tuple[str, int], object]:
    """Preorder map of ``(normalized key, occurrence idx) -> node`` for
    every exchange in a host physical tree."""
    out: Dict[Tuple[str, int], object] = {}
    seen: Dict[str, int] = {}

    def visit(node):
        key = _exchange_key(node)
        if key is not None:
            norm = normalize_plan_text(key)
            idx = seen.get(norm, 0)
            seen[norm] = idx + 1
            out[(norm, idx)] = node
        for c in getattr(node, "children", ()):
            visit(c)

    visit(phys)
    return out


def compute_exchange_fingerprints(host_phys) -> Dict[Tuple[str, int], str]:
    """Per-occurrence exchange fingerprints for one tick's cumulative
    plan: normalized subtree shape + occurrence index + the subtree's
    leaf DATA identity (file fingerprints).  Two ticks agree on an
    occurrence's fingerprint exactly when its input files are
    unchanged — that is what lets untouched exchanges resume."""
    fps: Dict[Tuple[str, int], str] = {}
    for (norm, idx), node in _exchange_occurrences(host_phys).items():
        material: List[str] = []
        _leaf_material(node, material)
        fps[(norm, idx)] = _digest(
            f"{norm}#{idx}@{_digest(chr(10).join(material))}")
    return fps


class StreamRecoveryManager(RecoveryManager):
    """RecoveryManager variant for one micro-batch of a stream.

    Differs from the per-query base in exactly two ways: the query
    fingerprint is the STREAM fingerprint (all ticks share one pinned
    checkpoint namespace), and exchange stamps fold in per-occurrence
    leaf data identity (so a grown scan changes the stamp and a merged
    checkpoint written under the new stamp is picked up by resume).
    Resume is forced on — a stream that checkpoints but never resumes
    would be pure overhead."""

    def __init__(self, conf, stream_fp: str):
        super().__init__(conf, force_resume=True)
        self.stream_fp = stream_fp
        #: (normalized key, occurrence idx) -> exchange fingerprint
        self.occ_fps: Dict[Tuple[str, int], str] = {}
        #: ledger form of the same map (occurrence_key -> fingerprint)
        self.exchange_fps: Dict[str, str] = {}
        self.host_phys = None
        #: exchanges stamped on the widest rung — the denominator of
        #: the batch's recompute fraction
        self.stamped_total = 0

    def attach_query(self, plan) -> None:
        if not (self.write_enabled or self.resume_enabled):
            return
        try:
            from ..recovery.manager import plan_fingerprints

            host_phys, _, query_fp, _ = plan_fingerprints(self.conf, plan)
            if query_fp is None:
                log.debug("stream recovery declined: nondeterministic "
                          "plan")
                self.write_enabled = self.resume_enabled = False
                return
            self.query_fp = self.stream_fp
            self.host_phys = host_phys
            self.occ_fps = compute_exchange_fingerprints(host_phys)
            self.exchange_fps = {
                occurrence_key(norm, idx): fp
                for (norm, idx), fp in self.occ_fps.items()}
        except Exception:  # noqa: BLE001 - recovery must never fail a query
            log.warning("stream recovery disabled: fingerprint failed",
                        exc_info=True)
            self.write_enabled = self.resume_enabled = False

    def stamp_plan(self, phys) -> int:
        """Stamp every exchange with its data-aware occurrence
        fingerprint.  Falls back to the base shape-only stamp for an
        occurrence the attach pass did not see (defensive: a rung that
        planned extra exchanges simply won't resume them)."""
        if self.query_fp is None:
            return 0
        seen: Dict[str, int] = {}
        stamped = 0

        def visit(node):
            nonlocal stamped
            key = _exchange_key(node)
            if key is not None:
                norm = normalize_plan_text(key)
                idx = seen.get(norm, 0)
                seen[norm] = idx + 1
                node._recovery_fp = self.occ_fps.get(
                    (norm, idx), _digest(f"{key}#{idx}"))
                stamped += 1
            for c in getattr(node, "children", ()):
                visit(c)

        visit(phys)
        self.stamped_total = max(self.stamped_total, stamped)
        return stamped


def incremental_safe(exchange_node) -> Optional[str]:
    """None when a host exchange's subtree is merge-eligible, else the
    human-readable reason it is not (emitted on the skip event)."""
    from ..shuffle.partitioning import HashPartitioning

    if not isinstance(exchange_node.partitioning, HashPartitioning):
        return ("partitioning "
                f"{type(exchange_node.partitioning).__name__} is not "
                "content-addressed")
    scans = 0
    stack = [exchange_node.children[0]]
    while stack:
        check_cancel("streaming.plan")
        node = stack.pop()
        name = type(node).__name__
        if name == "HashAggregateExec":
            if node.mode != "partial":
                return f"{node.mode} aggregate below exchange"
        elif name == "FileScanExec":
            scans += 1
        elif name not in _INCREMENTAL_SAFE_EXECS:
            return f"{name} below exchange is not row-local"
        stack.extend(getattr(node, "children", ()))
    if scans != 1:
        return f"subtree has {scans} file scans (need exactly 1)"
    return None


def _clone_with_delta_scan(node, new_by_cum: Dict[tuple, List[str]]):
    """Shallow-clone a cumulative exchange's child subtree with its
    (single, row-local) scan leaf swapped to the DELTA files — the
    delta executes under the cumulative plan's exact shape and
    partitioning, so its frames drop straight into the merged
    checkpoint.  ``new_by_cum`` maps a source's cumulative file tuple
    (how the tick pinned it) to that source's new-file suffix."""
    import copy

    from ..io.scans import FileScanExec, file_fingerprint

    if isinstance(node, FileScanExec):
        delta = new_by_cum.get(tuple(node.files))
        if delta is None:
            raise ValueError(
                "scan file list does not match a stream source")
        clone = copy.copy(node)
        clone.files = list(delta)
        clone.file_fingerprints = [file_fingerprint(p) for p in delta]
        clone.n_partitions = max(1, len(delta))
        clone.part_values = [{} for _ in delta]
        return clone
    clone = copy.copy(node)
    clone.children = [_clone_with_delta_scan(c, new_by_cum)
                      for c in node.children]
    return clone


def execute_delta_frames(conf, exchange_node,
                         new_by_cum: Dict[tuple, List[str]]):
    """Run a merge-eligible exchange subtree over the DELTA files on
    the host operator path and return its serialized partition frames
    ``frames[p] = [(uint8 frame, rows)]`` — the exact shape
    ``CheckpointStore.write_exchange`` persists.  Mirrors the host
    ``ShuffleExchangeExec`` store loop (and uses the CUMULATIVE plan's
    bound partitioning) so merged and cold checkpoints are
    indistinguishable."""
    import numpy as np

    from ..native import serializer
    from ..plan.physical import ExecContext

    ctx = ExecContext(conf, None)
    child = _clone_with_delta_scan(exchange_node.children[0], new_by_cum)
    data = child.execute(ctx)
    part = exchange_node.partitioning  # bound at planning time
    part.prepare(data, child.schema)
    n_out = exchange_node.n_out
    store: List[List[object]] = [[] for _ in range(n_out)]
    for pid in range(data.n_partitions):
        check_cancel("streaming.delta")
        for batch in data.iterator(pid):
            if batch.num_rows == 0:
                continue
            pids = part.partition_ids(batch)
            for out_pid in range(n_out):
                sel = np.nonzero(pids == out_pid)[0]
                if len(sel):
                    store[out_pid].append(batch.take(sel))
    frames = [[(serializer.serialize(b), b.num_rows) for b in plist]
              for plist in store]
    return frames


def _repartition_frames(base, schema, partitioning, new_n: int):
    """Re-split a committed base's frames across a GROWN fan-out using
    the cumulative plan's (content-addressed) partitioning.  Only
    called for partial-aggregate exchanges: there every group's rows —
    ≤1 per input file — live in exactly one old partition (hashed by
    group key) and stay in file order through the stable re-split, so
    per-group merge order is a cold recompute's."""
    import numpy as np

    from ..native import serializer

    out: List[List[object]] = [[] for _ in range(new_n)]
    for plist in base:
        check_cancel("streaming.repartition")
        for frame, _rows in plist:
            batch = serializer.deserialize(frame, schema)
            pids = partitioning.partition_ids(batch)
            for p in range(new_n):
                sel = np.nonzero(pids == p)[0]
                if len(sel):
                    out[p].append(batch.take(sel))
    return [[(serializer.serialize(b), b.num_rows) for b in plist]
            for plist in out]


def load_committed_frames(store, stream_fp: str, old_fp: str, *,
                          schema_sig: List[str],
                          conf_snapshot: Dict[str, str]):
    """Load the previous tick's committed frames for one exchange with
    the SAME paranoid validation as ``RecoveryManager.try_resume``
    (fingerprints, schema, conf snapshot, every frame CRC) — a merge
    built on a doubtful base would poison every later tick.  Raises on
    any invalidity (the caller skips the merge).  Returns
    ``(frames, old_n)`` with ``frames[p] = [(frame, rows)]`` ready to
    append delta frames to."""
    d = store.exchange_dir(stream_fp, old_fp)
    m = store.read_manifest(d)
    if m.get("plan_fingerprint") != old_fp:
        raise ValueError("stale plan fingerprint on committed base")
    if m.get("query_fingerprint") != stream_fp:
        raise ValueError("stream fingerprint mismatch on committed base")
    if m.get("schema") != list(schema_sig):
        raise ValueError("schema signature changed since last tick")
    if m.get("conf") != conf_snapshot:
        raise ValueError("result-affecting conf changed since last tick")
    old_n = int(m.get("n_out", -1))
    if old_n <= 0:
        raise ValueError(f"bad committed fan-out: {old_n}")
    frames = store.load_frames(d, m, old_n)  # CRC-verified eagerly
    rows: List[List[int]] = [[] for _ in range(old_n)]
    for rec in m["frames"]:  # same order load_frames appended in
        rows[int(rec["partition"])].append(int(rec["rows"]))
    return [list(zip(frames[p], rows[p])) for p in range(old_n)], old_n


def merge_growing_exchanges(mgr: StreamRecoveryManager,
                            new_by_cum: Dict[tuple, List[str]],
                            prev_exchanges: Dict[str, str]) -> int:
    """The incremental core of one tick: for every exchange occurrence
    whose fingerprint moved since the last committed batch, append the
    delta subtree's frames to the committed base and checkpoint the
    merge under the NEW fingerprint — the cumulative query then resumes
    it instead of recomputing history.  Returns how many exchanges were
    merged; every non-merge emits ``stream_incremental_skip`` with its
    reason.  Never fails the tick: a skipped merge just recomputes."""
    if mgr.query_fp is None or not (mgr.write_enabled
                                    and mgr.resume_enabled):
        return 0
    cum_occ = _exchange_occurrences(mgr.host_phys)
    merged = 0
    for (norm, idx), node in cum_occ.items():
        check_cancel("streaming.merge")
        cur_fp = mgr.occ_fps.get((norm, idx))
        old_fp = prev_exchanges.get(occurrence_key(norm, idx))
        if cur_fp is None or old_fp is None or cur_fp == old_fp:
            continue  # unseen / brand new / untouched — nothing to merge
        if mgr.store.has_manifest(mgr.query_fp, cur_fp):
            continue  # a crashed tick already merged this — idempotent
        reason = incremental_safe(node)
        if reason is not None:
            emit_event("stream_incremental_skip",
                       exchange=occurrence_key(norm, idx), reason=reason)
            continue
        try:
            sig = schema_signature(node.schema)
            n_out = node.partitioning.num_partitions
            base, old_n = load_committed_frames(
                mgr.store, mgr.query_fp, old_fp, schema_sig=sig,
                conf_snapshot=mgr._conf_snapshot)
            if old_n != n_out:
                # the planner grew the fan-out with the file count; a
                # re-split preserves per-group order only when groups
                # are file-unique — i.e. under a partial aggregate
                if type(node.children[0]).__name__ \
                        != "HashAggregateExec":
                    raise ValueError(
                        f"fan-out grew {old_n} -> {n_out} on a "
                        "non-aggregate exchange")
                base = _repartition_frames(
                    base, node.schema, node.partitioning, n_out)
            delta = execute_delta_frames(mgr.conf, node, new_by_cum)
            frames = [base[p] + delta[p] for p in range(n_out)]
            written = mgr.checkpoint_exchange(
                cur_fp, schema_sig=sig, n_out=n_out,
                part_rows=[sum(r for _f, r in plist)
                           for plist in frames],
                total_bytes=sum(int(f.nbytes)
                                for plist in frames for f, _r in plist),
                partitioning=type(node.partitioning).__name__,
                frames=frames)
            if written > 0:
                merged += 1
                emit_event(
                    "stream_incremental_merge",
                    exchange=occurrence_key(norm, idx),
                    partitions=n_out,
                    delta_rows=int(sum(r for plist in delta
                                       for _f, r in plist)),
                    bytes=int(written))
            else:
                emit_event("stream_incremental_skip",
                           exchange=occurrence_key(norm, idx),
                           reason="checkpoint write declined")
        except TpuQueryCancelled:
            raise
        except Exception as e:  # noqa: BLE001 - recompute, never fail
            emit_event("stream_incremental_skip",
                       exchange=occurrence_key(norm, idx),
                       reason=f"{type(e).__name__}: {e}")
            log.warning("incremental merge of exchange %s#%d skipped "
                        "(%s: %s) — recomputing", norm.splitlines()[0],
                        idx, type(e).__name__, e)
    return merged
