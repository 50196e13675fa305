"""Declarative SLO objectives with multi-window burn-rate alerts.

The scraped series (`obs/timeseries`) answer "what happened"; this module
answers "is it acceptable" continuously: each :class:`SloObjective` states a
target over a series (a per-table latency quantile or a process-wide failure
ratio), and :func:`evaluate` — driven after every scrape — computes its
**burn rate** (observed / objective) over two trailing windows:

* **fast** (``delta.tpu.obs.slo.fastWindowMs``, default 5m) — is the
  problem happening *now*;
* **slow** (``delta.tpu.obs.slo.slowWindowMs``, default 1h) — is it
  *sustained* enough to matter.

An alert **fires** only when BOTH windows burn ≥ 1.0 (the classic
multi-window rule: a short blip inside budget never pages, and an already-
recovered incident doesn't either), and **clears with hysteresis** once the
fast window drops below ``clearRatio`` (default 0.8) — a series flapping
around the threshold stays firing instead of strobing.

A firing alert is attributed: per-table objectives carry the ``table=``
label (`obs/fleet.table_label`) and the resolved path. Three consumers see
it: ``GET /slo`` (live state), the flight recorder (one incident JSON per
fire, when ``incidentDir`` is set), and the autopilot planner
(`autopilot/planner.plan` boosts the offending table's actions by
``delta.tpu.obs.slo.priorityBoost`` and cites the alert in their evidence).

Default objectives (thresholds conf-overridable):

==================  ========================================================
commitLatencyP99    p99 of ``delta.commit.duration_ms`` per table ≤
                    ``commitLatencyP99Ms`` (2s)
scanPlanningP99     p99 of ``delta.scan.planning.duration_ms`` per table ≤
                    ``scanPlanningP99Ms`` (500ms)
commitConflictRate  ``commit.conflicts`` / ``commit.total`` ≤
                    ``commitConflictRate`` (5%)
retryExhaustion     ``storage.retry.exhausted`` / ``storage.retry.attempts``
                    ≤ ``retryExhaustionRate`` (2%)
journalDropRate     ``journal.entriesDropped`` / ``journal.entries`` ≤
                    ``journalDropRate`` (1%)
==================  ========================================================

Blackout-inert by construction: evaluation is only ever driven from
:func:`~delta_tpu.obs.timeseries.scrape_once`, which returns before any
work under ``delta.tpu.telemetry.enabled=false`` — and :func:`evaluate`
re-checks the gate for direct callers.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["SloObjective", "SloAlert", "SloBreach", "objectives", "evaluate",
           "active_alerts", "priority_boost", "firing_count", "status",
           "reset"]


class SloBreach(Exception):
    """The exception a firing alert records through the flight recorder —
    an SLO breach is an operational failure even when no operation raised."""


@dataclass(frozen=True)
class SloObjective:
    """One declarative objective over the scraped series."""

    name: str
    kind: str                 # "latencyQuantile" | "ratio"
    description: str
    #: latencyQuantile: histogram name + quantile
    series: str = ""
    q: float = 0.99
    #: ratio: bad-event counter / total-event counter
    bad: str = ""
    total: str = ""
    #: the objective value (latency ms / bad fraction), conf-resolved at
    #: construction — :func:`objectives` rebuilds per evaluation, so a
    #: conf change applies on the next pass
    threshold: float = 0.0
    threshold_conf: str = ""
    #: evaluated once per ``table=`` label (vs once process-wide)
    per_table: bool = False

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "kind": self.kind,
            "description": self.description,
            "series": self.series or f"{self.bad} / {self.total}",
            "q": self.q if self.kind == "latencyQuantile" else None,
            "threshold": self.threshold,
            "thresholdConf": self.threshold_conf,
            "perTable": self.per_table,
        }


def _thr(value, default: float) -> float:
    try:
        return float(value)
    except (TypeError, ValueError):
        return default


def objectives() -> List[SloObjective]:
    """The engine's default objectives (thresholds read live from conf)."""
    return [
        SloObjective(
            "commitLatencyP99", "latencyQuantile",
            "p99 commit pipeline latency per table",
            series="delta.commit.duration_ms", q=0.99,
            threshold=_thr(conf.get(
                "delta.tpu.obs.slo.commitLatencyP99Ms", 2_000.0), 2_000.0),
            threshold_conf="delta.tpu.obs.slo.commitLatencyP99Ms",
            per_table=True),
        SloObjective(
            "scanPlanningP99", "latencyQuantile",
            "p99 scan-planning latency per table",
            series="delta.scan.planning.duration_ms", q=0.99,
            threshold=_thr(conf.get(
                "delta.tpu.obs.slo.scanPlanningP99Ms", 500.0), 500.0),
            threshold_conf="delta.tpu.obs.slo.scanPlanningP99Ms",
            per_table=True),
        SloObjective(
            "commitConflictRate", "ratio",
            "fraction of commits aborted on logical conflicts",
            bad="commit.conflicts", total="commit.total",
            threshold=_thr(conf.get(
                "delta.tpu.obs.slo.commitConflictRate", 0.05), 0.05),
            threshold_conf="delta.tpu.obs.slo.commitConflictRate"),
        SloObjective(
            "retryExhaustion", "ratio",
            "fraction of storage retries that gave up",
            bad="storage.retry.exhausted", total="storage.retry.attempts",
            threshold=_thr(conf.get(
                "delta.tpu.obs.slo.retryExhaustionRate", 0.02), 0.02),
            threshold_conf="delta.tpu.obs.slo.retryExhaustionRate"),
        SloObjective(
            "journalDropRate", "ratio",
            "fraction of journal entries dropped before landing",
            bad="journal.entriesDropped", total="journal.entries",
            threshold=_thr(conf.get(
                "delta.tpu.obs.slo.journalDropRate", 0.01), 0.01),
            threshold_conf="delta.tpu.obs.slo.journalDropRate"),
    ]


@dataclass
class SloAlert:
    """One firing (or recently cleared) alert instance."""

    objective: str
    table: str                      # hashed label; "" = process-wide
    path: Optional[str]             # resolved table path, when known
    fired_at_ms: int
    burn_fast: float
    burn_slow: float
    threshold: float
    observed: float                 # the fast-window observation that fired
    firing: bool = True
    cleared_at_ms: Optional[int] = None
    #: exemplar: the last sampled trace id at fire time — the stitched
    #: /traces/<id> view an operator jumps to from the alert
    trace_id: Optional[str] = None

    @property
    def key(self) -> Tuple[str, str]:
        return (self.objective, self.table)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "objective": self.objective,
            "table": self.table or None,
            "path": self.path,
            "firedAt": self.fired_at_ms,
            "clearedAt": self.cleared_at_ms,
            "firing": self.firing,
            "burnFast": round(self.burn_fast, 3),
            "burnSlow": round(self.burn_slow, 3),
            "threshold": self.threshold,
            "observed": round(self.observed, 3),
            "traceId": self.trace_id,
        }


_LOCK = threading.Lock()
_ALERTS: Dict[Tuple[str, str], SloAlert] = {}
_LAST_EVAL: List[Dict[str, Any]] = []
_LAST_EVAL_MS = 0


def _windows() -> Tuple[int, int]:
    fast = conf.get_int("delta.tpu.obs.slo.fastWindowMs", 300_000)
    slow = conf.get_int("delta.tpu.obs.slo.slowWindowMs", 3_600_000)
    return max(fast, 1), max(slow, fast, 1)


def _clear_ratio() -> float:
    try:
        r = float(conf.get("delta.tpu.obs.slo.clearRatio", 0.8))
    except (TypeError, ValueError):
        r = 0.8
    return min(max(r, 0.0), 1.0)


def _min_observations() -> int:
    return max(conf.get_int("delta.tpu.obs.slo.minObservations", 10), 1)


def _quantile_burns(obj: SloObjective, fast_ms: int, slow_ms: int,
                    now_ms: int) -> List[Dict[str, Any]]:
    from delta_tpu.obs import fleet, timeseries

    rows: List[Dict[str, Any]] = []
    threshold = obj.threshold
    for labels in timeseries.histogram_labels(obj.series):
        label_map = dict(labels)
        table = label_map.get("table", "")
        if obj.per_table and not table:
            continue  # unlabeled series can't be attributed to a table
        fast_v, fast_n = timeseries.quantile_window(
            obj.series, labels, obj.q, fast_ms, now_ms)
        slow_v, slow_n = timeseries.quantile_window(
            obj.series, labels, obj.q, slow_ms, now_ms)
        rows.append({
            "objective": obj.name, "table": table,
            "path": fleet.label_path(table) if table else None,
            "threshold": threshold,
            "fast": {"value": fast_v, "observations": fast_n},
            "slow": {"value": slow_v, "observations": slow_n},
            "burnFast": (fast_v / threshold
                         if fast_v is not None and threshold > 0 else 0.0),
            "burnSlow": (slow_v / threshold
                         if slow_v is not None and threshold > 0 else 0.0),
        })
    return rows


def _ratio_burns(obj: SloObjective, fast_ms: int, slow_ms: int,
                 now_ms: int) -> List[Dict[str, Any]]:
    from delta_tpu.obs import timeseries

    threshold = obj.threshold

    def _ratio(window_ms: int) -> Tuple[float, float]:
        bad = timeseries.counter_window(obj.bad, window_ms, now_ms)
        tot = timeseries.counter_window(obj.total, window_ms, now_ms)
        if tot["delta"] <= 0:
            return 0.0, 0.0
        ratio = bad["delta"] / tot["delta"]
        return ratio, tot["delta"]

    fast_r, fast_n = _ratio(fast_ms)
    slow_r, slow_n = _ratio(slow_ms)
    return [{
        "objective": obj.name, "table": "", "path": None,
        "threshold": threshold,
        "fast": {"value": fast_r, "observations": fast_n},
        "slow": {"value": slow_r, "observations": slow_n},
        "burnFast": fast_r / threshold if threshold > 0 else 0.0,
        "burnSlow": slow_r / threshold if threshold > 0 else 0.0,
    }]


def _record_incident(alert: SloAlert) -> None:
    """One flight-recorder incident per fire (inert without incidentDir)."""
    from delta_tpu.obs import flight_recorder

    ev = telemetry.UsageEvent(
        "delta.slo.alert", alert.fired_at_ms,
        tags={"objective": alert.objective, "table": alert.table or ""},
        data=alert.to_dict(), trace_id=alert.trace_id or "")
    try:
        flight_recorder.record_incident(ev, SloBreach(
            f"SLO {alert.objective} burning: fast {alert.burn_fast:.2f}x / "
            f"slow {alert.burn_slow:.2f}x budget "
            f"(table {alert.path or alert.table or 'process'})"))
    except Exception:  # noqa: BLE001 — alerting must never raise
        telemetry.logger.warning("slo incident write failed", exc_info=True)


def evaluate(now_ms: Optional[int] = None) -> List[Dict[str, Any]]:
    """One evaluation pass over every objective: compute fast/slow burns,
    publish ``slo.burnRate``/``slo.alerts`` metrics, and advance the alert
    state machine (fire on both-window burn ≥ 1, clear below the hysteresis
    ratio). Returns the evaluation rows. No-op (empty list) under a
    telemetry blackout."""
    global _LAST_EVAL, _LAST_EVAL_MS
    if not conf.get_bool("delta.tpu.telemetry.enabled", True):
        return []
    now = int(now_ms if now_ms is not None else time.time() * 1000)
    fast_ms, slow_ms = _windows()
    clear_ratio = _clear_ratio()
    min_obs = _min_observations()
    telemetry.bump_counter("slo.evaluations")
    rows: List[Dict[str, Any]] = []
    for obj in objectives():
        if obj.kind == "latencyQuantile":
            rows.extend(_quantile_burns(obj, fast_ms, slow_ms, now))
        else:
            rows.extend(_ratio_burns(obj, fast_ms, slow_ms, now))
    fired: List[SloAlert] = []
    with _LOCK:
        for row in rows:
            key = (row["objective"], row["table"])
            telemetry.set_gauge(
                "slo.burnRate", row["burnFast"],
                objective=row["objective"], table=row["table"] or "-",
                window="fast")
            telemetry.set_gauge(
                "slo.burnRate", row["burnSlow"],
                objective=row["objective"], table=row["table"] or "-",
                window="slow")
            alert = _ALERTS.get(key)
            if alert is not None and alert.firing:
                alert.burn_fast = row["burnFast"]
                alert.burn_slow = row["burnSlow"]
                if row["burnFast"] < clear_ratio:
                    alert.firing = False
                    alert.cleared_at_ms = now
                    telemetry.bump_counter("slo.alerts.cleared")
                row["alert"] = alert.to_dict()
            elif (row["burnFast"] >= 1.0 and row["burnSlow"] >= 1.0
                  and row["fast"]["observations"] >= min_obs
                  and row["slow"]["observations"] >= min_obs):
                # the observation floor keeps thin windows honest: a
                # young series' fast and slow windows can hold the SAME
                # handful of samples (both baseline at the first scrape),
                # so without it a few outliers would defeat the
                # multi-window "a short blip never pages" rule
                alert = SloAlert(
                    objective=row["objective"], table=row["table"],
                    path=row["path"], fired_at_ms=now,
                    burn_fast=row["burnFast"], burn_slow=row["burnSlow"],
                    threshold=row["threshold"],
                    observed=float(row["fast"]["value"] or 0.0),
                    trace_id=telemetry.last_sampled_trace_id())
                _ALERTS[key] = alert
                fired.append(alert)
                telemetry.bump_counter("slo.alerts.fired")
                row["alert"] = alert.to_dict()
        # an alert whose series vanished from the rings (table died and its
        # series aged out past scrape.maxSeries) produces no burn row — it
        # must clear, not burn as a phantom forever
        visited = {(r["objective"], r["table"]) for r in rows}
        for key, alert in _ALERTS.items():
            if alert.firing and key not in visited:
                alert.burn_fast = 0.0
                alert.firing = False
                alert.cleared_at_ms = now
                telemetry.bump_counter("slo.alerts.cleared")
        firing = sum(1 for a in _ALERTS.values() if a.firing)
        # cleared alerts are history, not state: keep a bounded tail for
        # /slo (newest first), like every other capped structure in the
        # plane — the alert map must not grow for the process lifetime
        cleared = sorted(
            (k for k, a in _ALERTS.items() if not a.firing),
            key=lambda k: _ALERTS[k].cleared_at_ms or 0, reverse=True)
        for k in cleared[64:]:
            del _ALERTS[k]
        _LAST_EVAL = rows
        _LAST_EVAL_MS = now
    telemetry.set_gauge("slo.alerts", firing)
    for alert in fired:  # incidents outside the lock: file IO
        _record_incident(alert)
    return rows


def firing_count() -> int:
    """Currently-firing alerts as one lock-guarded sum — cheap enough for
    the trace sampler's forced-sampling probe on every new root span
    (`telemetry._slo_burning`), which must not read conf or build dicts."""
    with _LOCK:
        return sum(1 for a in _ALERTS.values() if a.firing)


def active_alerts(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Currently-firing alerts, optionally only those attributed to
    ``path`` (per-table objectives resolve their hashed label through the
    fleet registry)."""
    with _LOCK:
        alerts = [a for a in _ALERTS.values() if a.firing]
    if path is not None:
        want = path.rstrip("/")
        alerts = [a for a in alerts if a.path == want]
    return [a.to_dict() for a in sorted(
        alerts, key=lambda a: (-max(a.burn_fast, a.burn_slow), a.objective))]


def priority_boost(path: str) -> Tuple[float, List[Dict[str, Any]]]:
    """(priority boost, citing alerts) for a table: the autopilot planner
    adds the boost to every action planned for a table whose per-table SLO
    is firing, so fleet scheduling puts burning tables first."""
    alerts = active_alerts(path)
    if not alerts:
        return 0.0, []
    try:
        boost = float(conf.get("delta.tpu.obs.slo.priorityBoost", 25.0))
    except (TypeError, ValueError):
        boost = 25.0
    return boost, alerts


def status() -> Dict[str, Any]:
    """The ``/slo`` payload: objectives, windows, the last evaluation's
    burn rows, and every alert (firing first)."""
    fast_ms, slow_ms = _windows()
    with _LOCK:
        rows = list(_LAST_EVAL)
        eval_ms = _LAST_EVAL_MS
        alerts = sorted(_ALERTS.values(),
                        key=lambda a: (not a.firing, -a.fired_at_ms))
    return {
        "enabled": (conf.get_bool("delta.tpu.telemetry.enabled", True)
                    and conf.get_bool("delta.tpu.obs.slo.enabled", True)),
        "windows": {"fastMs": fast_ms, "slowMs": slow_ms,
                    "clearRatio": _clear_ratio(),
                    "minObservations": _min_observations()},
        "objectives": [o.to_dict() for o in objectives()],
        "lastEvaluationAt": eval_ms or None,
        "burns": rows,
        "alerts": [a.to_dict() for a in alerts],
        "firing": sum(1 for a in alerts if a.firing),
    }


def reset() -> None:
    """Drop alert state and the last evaluation (tests)."""
    global _LAST_EVAL, _LAST_EVAL_MS
    with _LOCK:
        _ALERTS.clear()
        _LAST_EVAL = []
        _LAST_EVAL_MS = 0
