"""Process-wide table registry — the fleet half of the observability plane.

Every observability surface before this module is single-table: ``doctor()``
and ``advise()`` take one path, and the hot gauges/histograms were process
-global, so an operator running one engine over many tables could not ask
"which of my tables is the problem". This module closes both gaps:

* **Registry** — every :class:`~delta_tpu.log.deltalog.DeltaLog`
  auto-registers on construction (weakref'd: the registry never extends a
  table's lifetime; dead handles are pruned on the next read). Strictly
  blackout-inert: with ``delta.tpu.telemetry.enabled=false`` (or
  ``delta.tpu.obs.fleet.enabled=false``) nothing registers.
* **Per-table labels** — :func:`table_label` hashes a table path into a
  short stable label (``table=<sha1[:12]>``) that the hot metric sites
  (commit latency, scan planning, journal flushes, key-cache residency)
  attach to their gauges/histograms, keeping series cardinality and label
  bytes bounded while making cross-table aggregation possible. The
  registry keeps the reverse map so ``/fleet``, ``/slo`` and the autopilot
  can resolve a label back to its path.
* **Fleet sweeps** — :func:`fleet_doctor` / :func:`fleet_advise` run the
  per-table doctor/advisor over every live table and rank the fleet by
  worst dimension (severity, then breadth of debt), so "which table first"
  is one call — the input the autopilot needs to schedule across a fleet
  instead of reacting per table.

Served by ``GET /fleet`` (`obs/server`) and ``tools/fleet_dump.py``.
"""
from __future__ import annotations

import functools
import hashlib
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["enabled", "register", "unregister", "live_tables", "table_label",
           "label_path", "fleet_doctor", "fleet_advise", "fleet_status",
           "FleetEntry", "FleetReport", "reset"]

_LOCK = threading.Lock()
# data_path -> (weakref to the DeltaLog, registered_at_ms)
_TABLES: Dict[str, tuple] = {}
# short hash label -> data path (populated by table_label; labels are
# kept across blackouts — they are pure derived names, not state)
_LABEL_PATHS: Dict[str, str] = {}


def enabled() -> bool:
    """The registry is live: telemetry on AND the fleet switch on."""
    return (conf.get_bool("delta.tpu.telemetry.enabled", True)
            and conf.get_bool("delta.tpu.obs.fleet.enabled", True))


@functools.lru_cache(maxsize=8192)
def table_label(path: str) -> str:
    """Stable short label for a table path (``sha1(path)[:12]``) — the
    value of the ``table=`` metric label. Hashed, not the raw path: label
    cardinality stays bounded-width and scrape lines don't leak full
    filesystem layout. The reverse map is kept for operators
    (:func:`label_path`). lru_cached — the per-commit hot path pays a dict
    probe, not a hash + lock."""
    label = hashlib.sha1(path.encode("utf-8")).hexdigest()[:12]
    with _LOCK:
        _LABEL_PATHS.setdefault(label, path)
        if len(_LABEL_PATHS) > 16384:
            # bounded like the lru_cache above it: under extreme table
            # churn the reverse map must not outgrow the process; dropping
            # the oldest only un-resolves labels of long-dead tables —
            # and the lru_cache must drop too, or a still-hot table whose
            # mapping was evicted would never re-prime it (its calls keep
            # hitting the cache and skipping the setdefault above)
            for k in list(_LABEL_PATHS)[:len(_LABEL_PATHS) - 8192]:
                _LABEL_PATHS.pop(k, None)
            evicted_labels = True
        else:
            evicted_labels = False
    if evicted_labels:
        table_label.cache_clear()
    return label


def label_path(label: str) -> Optional[str]:
    """The table path a ``table=`` label resolves to, if this process has
    seen it."""
    with _LOCK:
        return _LABEL_PATHS.get(label)


def register(delta_log) -> bool:
    """Weakref-register a constructed DeltaLog (called from
    ``DeltaLog.__init__``). Returns False (and stores nothing) under a
    telemetry blackout or with the fleet registry disabled."""
    if not enabled():
        return False
    path = delta_log.data_path
    prev = _TABLES.get(path)  # GIL-atomic probe: the common re-offer from
    if prev is not None and prev[0]() is delta_log:
        return True           # DeltaLog.update stays lock-free
    with _LOCK:
        prev = _TABLES.get(path)
        # re-registration (DeltaLog.update re-offers its handle, covering
        # tables constructed during a blackout that later lifted) keeps
        # the original registration time
        _TABLES[path] = (weakref.ref(delta_log),
                         prev[1] if prev else int(time.time() * 1000))
        if prev is None:
            # published under the lock: racing register/unregister calls
            # must not land their gauge writes out of order
            telemetry.set_gauge("fleet.tables", len(_TABLES))
    table_label(path)  # prime the reverse map outside the registry lock
    return True


def unregister(path: str) -> None:
    with _LOCK:
        _TABLES.pop(path.rstrip("/"), None)
        telemetry.set_gauge("fleet.tables", len(_TABLES))


def live_tables() -> Dict[str, Any]:
    """``{path: DeltaLog}`` for every registered table whose handle is
    still alive; dead weakrefs are pruned as a side effect."""
    out: Dict[str, Any] = {}
    with _LOCK:
        dead = []
        for path, (ref, _at) in _TABLES.items():
            dl = ref()
            if dl is None:
                dead.append(path)
            else:
                out[path] = dl
        for path in dead:
            _TABLES.pop(path, None)
        if dead:
            telemetry.set_gauge("fleet.tables", len(_TABLES))
    for path in dead:
        # the registry never forgets labeled series on its own: drop the
        # dead table's per-table gauges/histograms so scrape work and
        # registry memory track the LIVE fleet, not every table ever seen
        telemetry.drop_labeled_series(table=table_label(path))
        telemetry.drop_labeled_series(path=path)
    return out


# ---------------------------------------------------------------------------
# Fleet sweeps
# ---------------------------------------------------------------------------


@dataclass
class FleetEntry:
    """One table's row in a ranked fleet sweep."""

    path: str
    table: str                      # hashed label (the metric label value)
    severity: str = "ok"            # worst doctor dimension severity
    worst_dimension: str = ""       # name of the worst dimension
    critical_dims: int = 0
    warn_dims: int = 0
    remedies: List[str] = field(default_factory=list)
    top_score: float = 0.0          # advisor sweeps: best recommendation
    detail: Dict[str, Any] = field(default_factory=dict)
    error: Optional[str] = None     # sweep kept going; this table failed

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path,
            "table": self.table,
            "severity": self.severity,
            "worstDimension": self.worst_dimension,
            "criticalDims": self.critical_dims,
            "warnDims": self.warn_dims,
            "remedies": list(self.remedies),
            "topScore": round(self.top_score, 3),
            "detail": dict(self.detail),
            "error": self.error,
        }


@dataclass
class FleetReport:
    """A ranked sweep over every live table (worst first)."""

    kind: str                       # "doctor" | "advisor"
    generated_at_ms: int
    entries: List[FleetEntry]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "generatedAt": self.generated_at_ms,
            "tables": len(self.entries),
            "entries": [e.to_dict() for e in self.entries],
        }


def _severity_key(e: FleetEntry):
    from delta_tpu.obs.doctor import SEVERITY_RANK

    # worst severity first, then breadth of debt, then advisor score;
    # path last for a deterministic order
    return (-SEVERITY_RANK.get(e.severity, 0), -e.critical_dims,
            -e.warn_dims, -e.top_score, e.path)


def fleet_doctor() -> FleetReport:
    """Run :func:`~delta_tpu.obs.doctor.doctor` over every live table and
    rank the fleet by worst dimension. One failing table never aborts the
    sweep — its entry carries the error instead."""
    from delta_tpu.obs.doctor import SEVERITY_RANK, doctor

    telemetry.bump_counter("fleet.sweeps")
    entries: List[FleetEntry] = []
    for path, dl in sorted(live_tables().items()):
        entry = FleetEntry(path=path, table=table_label(path))
        try:
            rep = doctor(dl)
            worst = max(rep.dimensions,
                        key=lambda d: SEVERITY_RANK[d.severity])
            entry.severity = rep.severity
            entry.worst_dimension = (worst.name
                                     if worst.severity != "ok" else "")
            entry.critical_dims = sum(
                1 for d in rep.dimensions if d.severity == "critical")
            entry.warn_dims = sum(
                1 for d in rep.dimensions if d.severity == "warn")
            entry.remedies = rep.remedies()
            entry.detail = {"version": rep.version,
                            "numFiles": rep.num_files,
                            "sizeInBytes": rep.size_in_bytes}
        except Exception as e:  # noqa: BLE001 — sweep the rest of the fleet
            entry.error = f"{type(e).__name__}: {e}"
        entries.append(entry)
    entries.sort(key=_severity_key)
    return FleetReport("doctor", int(time.time() * 1000), entries)


def fleet_advise() -> FleetReport:
    """Run :func:`~delta_tpu.obs.advisor.advise` over every live table and
    rank by the strongest recommendation score."""
    from delta_tpu.obs.advisor import advise

    telemetry.bump_counter("fleet.sweeps")
    entries: List[FleetEntry] = []
    for path, dl in sorted(live_tables().items()):
        entry = FleetEntry(path=path, table=table_label(path))
        try:
            rep = advise(dl)
            recs = rep.recommendations if rep.status == "ok" else []
            entry.top_score = max((float(r.score) for r in recs), default=0.0)
            entry.remedies = [r.remedy for r in recs]
            entry.detail = {"status": rep.status, "entries": rep.entries,
                            "recommendations": len(recs)}
        except Exception as e:  # noqa: BLE001 — sweep the rest of the fleet
            entry.error = f"{type(e).__name__}: {e}"
        entries.append(entry)
    entries.sort(key=lambda e: (-e.top_score, e.path))
    return FleetReport("advisor", int(time.time() * 1000), entries)


def fleet_status() -> Dict[str, Any]:
    """Registry introspection for ``/fleet``: every registered table with
    its label, liveness, and registration time. Deliberately does NOT
    prune first (unlike :func:`live_tables`): a registered-but-collected
    table must be able to report ``alive=false`` once before the next
    sweep removes it."""
    with _LOCK:
        rows = [
            {"path": path, "table": _label_of(path),
             "registeredAt": at, "alive": ref() is not None}
            for path, (ref, at) in sorted(_TABLES.items())
        ]
    return {"enabled": enabled(), "tables": len(rows), "entries": rows}


def _label_of(path: str) -> str:
    """Label computation without touching the registry lock (callers hold
    ``_LOCK``); does not prime the reverse map."""
    return hashlib.sha1(path.encode("utf-8")).hexdigest()[:12]


def reset() -> None:
    """Drop the registry and label map (tests)."""
    with _LOCK:
        _TABLES.clear()
        _LABEL_PATHS.clear()
    table_label.cache_clear()
