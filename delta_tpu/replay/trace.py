"""Trace reconstruction — journal segments → an ordered, replayable
:class:`WorkloadTrace`.

The journal persists scans as normalized predicate fingerprints
(``eq(v,?)``) plus a bounded literal-sample reservoir
(``delta.tpu.journal.literalSamples``, `obs/journal._stamp_sample`). This
module turns those segments back into something executable, rehydrating
each scan's concrete predicate in priority order:

1. the entry's own ``sample`` (reservoir hit — exact SQL),
2. the legacy un-redacted ``report["predicate"]`` (pre-reservoir segments),
3. a sibling sample recorded under the SAME fingerprint key (the workload
   shape is identical; only the literal differs),
4. stats-guided literal synthesis from the table's file-level min/max
   stats — flagged ``synthesized`` so shadow scores discount the event by
   ``delta.tpu.replay.literalDiscount`` (counter
   ``replay.literals.synthesized``).

Traces serialize to plain JSON (:meth:`WorkloadTrace.save` /
:meth:`WorkloadTrace.load`) — the synthetic scenario library
(`replay/scenarios`) emits the same format, so shadow runs, capacity
replays and torture all draw from one source.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["TraceEvent", "WorkloadTrace", "build_trace"]

#: trace serialization format version (bump on incompatible change)
TRACE_FORMAT = 1

#: journal entry kinds that become trace events
_EVENT_KINDS = ("scan", "commit", "dml", "router")


@dataclass
class TraceEvent:
    """One replayable workload event (ordered by journal timestamp)."""

    ts: int
    kind: str  # scan | commit | dml | router
    #: concrete predicate SQL for scans (None = full-table scan)
    predicate: Optional[str] = None
    columns: Optional[List[str]] = None
    #: normalized fingerprint key (``eq(v,?)&lt(a,?)``-style) — the shape
    #: identity shadow candidates are matched on
    fingerprint: str = ""
    #: True when the literal came from stats-guided synthesis, not a
    #: recorded sample — scores discount these events
    synthesized: bool = False
    #: measured planning phase duration (capacity replay feeds this into
    #: the live ``delta.scan.planning.duration_ms`` histogram)
    planning_ms: float = 0.0
    #: kind-specific extras (commit outcome, dml op, router audit, scan
    #: skipping numbers) — carried for scoring context, not re-executed
    payload: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ts": self.ts, "kind": self.kind, "predicate": self.predicate,
            "columns": list(self.columns) if self.columns is not None else None,
            "fingerprint": self.fingerprint, "synthesized": self.synthesized,
            "planningMs": self.planning_ms, "payload": dict(self.payload),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TraceEvent":
        return cls(
            ts=int(d.get("ts", 0)), kind=str(d.get("kind", "scan")),
            predicate=d.get("predicate"),
            columns=(list(d["columns"]) if d.get("columns") is not None
                     else None),
            fingerprint=str(d.get("fingerprint") or ""),
            synthesized=bool(d.get("synthesized", False)),
            planning_ms=float(d.get("planningMs", 0.0)),
            payload=dict(d.get("payload") or {}),
        )


@dataclass
class WorkloadTrace:
    """An ordered sequence of workload events for one table."""

    path: str
    built_at_ms: int
    events: List[TraceEvent] = field(default_factory=list)
    #: ``journal`` or ``synthetic:<scenario>``
    source: str = "journal"

    def scans(self) -> List[TraceEvent]:
        return [e for e in self.events if e.kind == "scan"]

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out

    @property
    def synthesized_literals(self) -> int:
        return sum(1 for e in self.events if e.synthesized)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "format": TRACE_FORMAT, "path": self.path,
            "builtAtMs": self.built_at_ms, "source": self.source,
            "counts": self.counts(),
            "synthesizedLiterals": self.synthesized_literals,
            "events": [e.to_dict() for e in self.events],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "WorkloadTrace":
        return cls(
            path=str(d.get("path") or ""),
            built_at_ms=int(d.get("builtAtMs", 0)),
            events=[TraceEvent.from_dict(e) for e in d.get("events") or ()],
            source=str(d.get("source") or "journal"),
        )

    def save(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "WorkloadTrace":
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_dict(json.load(f))


# ---------------------------------------------------------------------------
# Literal synthesis — stats-guided fallback for abstract fingerprints
# ---------------------------------------------------------------------------


def _column_ranges(snapshot) -> Dict[str, Tuple[Any, Any]]:
    """Per-column (min, max) over every live file's protocol stats —
    the raw material for synthesizing plausible literals."""
    ranges: Dict[str, Tuple[Any, Any]] = {}
    for add in snapshot.all_files:
        stats = add.stats_dict()
        if not stats:
            continue
        mins = stats.get("minValues") or {}
        maxs = stats.get("maxValues") or {}
        for col, lo in mins.items():
            hi = maxs.get(col)
            if lo is None or hi is None:
                continue
            key = col.lower()
            cur = ranges.get(key)
            if cur is None:
                ranges[key] = (lo, hi)
            else:
                try:
                    ranges[key] = (min(cur[0], lo), max(cur[1], hi))
                except TypeError:
                    pass  # mixed-type stats: keep the first sighting
    return ranges


def _sql_literal(value: Any) -> str:
    if isinstance(value, bool):
        return "TRUE" if value else "FALSE"
    if isinstance(value, (int, float)):
        return repr(value)
    return "'" + str(value).replace("'", "''") + "'"


def _synthesize_predicate(fingerprint: Dict[str, Any],
                          ranges: Dict[str, Tuple[Any, Any]]
                          ) -> Optional[str]:
    """Build an executable stand-in predicate for an abstracted fingerprint:
    one ``col <= <midpoint>`` conjunct per prunable column with known stats
    (numeric midpoint halves the range; strings fall back to ``<= min``,
    the most selective sound choice). Returns None when no referenced
    column has usable stats — the event replays as a full-table scan."""
    conjuncts: List[str] = []
    cols = (fingerprint.get("prunableColumns")
            or fingerprint.get("columns") or [])
    for col in cols:
        rng = ranges.get(col.lower())
        if rng is None:
            continue
        lo, hi = rng
        if isinstance(lo, bool) or isinstance(hi, bool):
            target: Any = lo
        elif isinstance(lo, (int, float)) and isinstance(hi, (int, float)):
            target = (lo + hi) / 2.0
            if isinstance(lo, int) and isinstance(hi, int):
                target = int(target)
        else:
            target = lo
        conjuncts.append(f"{col} <= {_sql_literal(target)}")
    return " AND ".join(conjuncts) if conjuncts else None


# ---------------------------------------------------------------------------
# build_trace
# ---------------------------------------------------------------------------


def _resolve_log(table: Any):
    """Accept a path, a DeltaTable, or a DeltaLog."""
    from delta_tpu.log.deltalog import DeltaLog

    if isinstance(table, DeltaLog):
        return table
    log = getattr(table, "delta_log", None)
    if log is not None:
        return log
    return DeltaLog.for_table(os.fspath(table))


def build_trace(table: Any, limit: Optional[int] = None,
                before_ts: Optional[int] = None) -> WorkloadTrace:
    """Reconstruct a table's :class:`WorkloadTrace` from its journal.

    ``limit`` bounds the number of SCAN events kept (newest win; default
    ``delta.tpu.replay.maxScans``); non-scan events are always kept — they
    cost nothing to carry and capacity replay wants the full timeline.
    ``before_ts`` drops events at/after that journal timestamp — the
    realized-audit path uses it to replay exactly the workload a shadow
    scorecard was scored on."""
    import time as _time

    from delta_tpu.obs import journal

    delta_log = _resolve_log(table)
    journal.flush(delta_log.log_path)
    entries = journal.read_entries(delta_log.log_path, kinds=_EVENT_KINDS)
    if before_ts is not None:
        entries = [e for e in entries if int(e.get("ts", 0)) < before_ts]

    # pass 1: collect reservoir samples per fingerprint key so sampled
    # entries can donate literals to same-shape entries past the bound
    samples_by_key: Dict[str, str] = {}
    for e in entries:
        if e.get("kind") != "scan":
            continue
        key = (e.get("fingerprint") or {}).get("key")
        sample = e.get("sample")
        if key and sample and key not in samples_by_key:
            samples_by_key[key] = sample

    ranges: Optional[Dict[str, Tuple[Any, Any]]] = None  # built lazily
    events: List[TraceEvent] = []
    synthesized = 0
    for e in entries:
        ts = int(e.get("ts", 0))
        kind = e.get("kind")
        if kind != "scan":
            payload = {k: v for k, v in e.items()
                       if k not in ("kind", "ts") and not k.startswith("_")}
            events.append(TraceEvent(ts=ts, kind=str(kind), payload=payload))
            continue
        report = e.get("report") or {}
        fp = e.get("fingerprint") or {}
        key = str(fp.get("key") or "")
        predicate: Optional[str] = None
        synth = False
        had_predicate = bool(key) or report.get("predicate") is not None
        if had_predicate:
            predicate = (e.get("sample") or report.get("predicate")
                         or samples_by_key.get(key))
            if predicate is None:
                if ranges is None:
                    ranges = _column_ranges(delta_log.update())
                predicate = _synthesize_predicate(fp, ranges)
                if predicate is not None:
                    synth = True
                    synthesized += 1
        phase = report.get("phaseMs") or {}
        events.append(TraceEvent(
            ts=ts, kind="scan", predicate=predicate,
            columns=report.get("columns"), fingerprint=key,
            synthesized=synth,
            planning_ms=float(phase.get("planning", 0) or 0),
            payload={
                "bytesRead": report.get("bytesRead", 0),
                "bytesSkipped": report.get("bytesSkipped", 0),
                "rowsOut": report.get("rowsOut", 0),
            },
        ))

    max_scans = limit if limit is not None else conf.get_int(
        "delta.tpu.replay.maxScans", 256)
    scan_idx = [i for i, ev in enumerate(events) if ev.kind == "scan"]
    if max_scans is not None and len(scan_idx) > max_scans:
        drop = set(scan_idx[:len(scan_idx) - max_scans])
        events = [ev for i, ev in enumerate(events) if i not in drop]

    telemetry.bump_counter("replay.traces.built")
    if synthesized:
        telemetry.bump_counter("replay.literals.synthesized", by=synthesized)
    return WorkloadTrace(
        path=delta_log.data_path, built_at_ms=int(_time.time() * 1000),
        events=events, source="journal",
    )
