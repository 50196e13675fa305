"""Per-query scan reports — the EXPLAIN-style counterpart of the reference's
``DataSkippingReader`` metrics.

The process-wide ``scan.*`` counters aggregate across every query; a
:class:`ScanReport` answers "what did THIS query cost": files considered vs
pruned at the file tier, row groups total/pruned/late-skipped at the Parquet
tier, bytes read vs skipped, per-phase durations, and the residual predicate
IR. ``exec/scan.scan_to_table`` opens a report (contextvar-scoped, so
concurrent scans on different threads never cross), ``read_files_as_table``
contributes the row-group numbers from the same sums that feed the
``scan.rowgroups.*`` counters — the report and the counters can never
disagree — and the finished report is retrievable via
:func:`last_scan_report` and attached to the ``delta.scan`` span.

Zero-overhead when ``delta.tpu.telemetry.enabled=false``: no report is
opened, and :func:`contribute` is a single contextvar probe.
"""
from __future__ import annotations

import contextvars
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

__all__ = ["ScanReport", "last_scan_report", "clear_last_report",
           "start_report", "current_report", "contribute",
           "record_phase", "record_rewrite_fired", "finish_report"]


@dataclass
class ScanReport:
    """One query's skipping ledger. Row-group and byte numbers are the exact
    per-scan deltas of the ``scan.rowgroups.*`` / ``scan.bytes.*`` counters."""

    path: str = ""
    version: int = -1
    predicate: Optional[str] = None  # residual predicate IR (SQL repr)
    columns: Optional[List[str]] = None
    files_total: int = 0            # snapshot files considered
    files_after_partition: int = 0  # survivors of partition pruning
    files_scanned: int = 0          # survivors of file-tier stats skipping
    row_groups_total: int = 0
    row_groups_pruned: int = 0        # footer-stats tier
    row_groups_late_skipped: int = 0  # late-materialization tier
    #: row groups whose device residual mask came back all-False — skipped
    #: without the host ever decoding them (ops/column_cache path)
    row_groups_device_skipped: int = 0
    bytes_read: int = 0
    bytes_skipped: int = 0
    #: the slice of ``bytes_skipped`` the footer-stats PLANNER avoided
    #: (row groups never opened); the remainder is late materialization
    bytes_skipped_planned: int = 0
    #: the slice of ``bytes_skipped`` the DEVICE mask avoided (all-False
    #: row groups) — disjoint from the host late-materialization slice
    bytes_device_skipped: int = 0
    #: row-group bytes decoded on host because the device mask kept at
    #: least one of their rows — the device path's survivor fetch, counted
    #: separately from plain host-decoded bytes
    bytes_device_survivor: int = 0
    #: ``"device"`` when the jitted residual path served this scan; None on
    #: the pure host path (declined / fallback / not attempted)
    device_residual: Optional[str] = None
    rows_out: int = 0
    #: milliseconds in each phase's span (:func:`record_phase`): planning,
    #: mask (device residual; absent on the host path), read, filter
    phase_ms: Dict[str, float] = field(default_factory=dict)
    #: synthesized predicate rewrites (expr/synthesis) that excluded at
    #: least one file or row group this scan: {family, conjunct, rewrite}
    #: with shape fingerprints; one entry per (family, conjunct), matching
    #: the ``scan.rewrites.fired`` counter delta by construction
    rewrites_fired: List[Dict[str, str]] = field(default_factory=list)

    @property
    def files_pruned(self) -> int:
        return max(0, self.files_total - self.files_scanned)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path,
            "version": self.version,
            "predicate": self.predicate,
            "columns": list(self.columns) if self.columns is not None else None,
            "filesTotal": self.files_total,
            "filesAfterPartition": self.files_after_partition,
            "filesScanned": self.files_scanned,
            "filesPruned": self.files_pruned,
            "rowGroupsTotal": self.row_groups_total,
            "rowGroupsPruned": self.row_groups_pruned,
            "rowGroupsLateSkipped": self.row_groups_late_skipped,
            "rowGroupsDeviceSkipped": self.row_groups_device_skipped,
            "bytesRead": self.bytes_read,
            "bytesSkipped": self.bytes_skipped,
            "bytesSkippedPlanned": self.bytes_skipped_planned,
            "bytesDeviceSkipped": self.bytes_device_skipped,
            "bytesDeviceSurvivor": self.bytes_device_survivor,
            "deviceResidual": self.device_residual,
            "rowsOut": self.rows_out,
            "phaseMs": dict(self.phase_ms),
            "rewritesFired": [dict(f) for f in self.rewrites_fired],
        }


# the report being filled by the scan running in THIS context
_CURRENT: "contextvars.ContextVar[Optional[ScanReport]]" = contextvars.ContextVar(
    "delta_obs_scan_report", default=None
)
# last finished report, process-wide (operator pull surface)
_LAST_LOCK = threading.Lock()
_LAST: Optional[ScanReport] = None


def start_report(path: str, version: int) -> "contextvars.Token":
    """Open a report for the scan running in this context; returns the
    contextvar token for :func:`finish_report`."""
    return _CURRENT.set(ScanReport(path=path, version=version))


def current_report() -> Optional[ScanReport]:
    """The report being filled by the scan in THIS context, if any."""
    return _CURRENT.get()


def contribute(**deltas: int) -> None:
    """Add row-group / byte tallies into the in-flight report, if any —
    called from ``read_files_as_table`` with the same sums that bump the
    process counters. Field names are ``ScanReport`` attributes."""
    rep = _CURRENT.get()
    if rep is None:
        return
    for k, v in deltas.items():
        setattr(rep, k, getattr(rep, k) + v)


def record_phase(key: str, span) -> None:
    """One phase of the in-flight scan, timed by the span that covered it
    (``delta.scan.planning``, ``.deviceMask``, ``.read``, ``.filter``):
    milliseconds from the span's own ``duration_us``, so the report and the
    trace cannot disagree. No report in flight (DML reads, blackout): no-op."""
    rep = _CURRENT.get()
    if rep is not None and span.duration_us is not None:
        rep.phase_ms[key] = round(
            rep.phase_ms.get(key, 0) + span.duration_us / 1000.0, 3)


def record_rewrite_fired(family: str, conjunct: str, rewrite: str) -> None:
    """Attribute one fired synthesized rewrite (both pruning tiers call
    this with shape fingerprints). Deduped per (family, conjunct) within
    the in-flight report — a conjunct that fires at the file tier AND the
    row-group tier is one workload fact, not two — and the
    ``scan.rewrites.fired`` counter bumps exactly once per appended entry,
    so ``last_scan_report().rewritesFired`` matches the counter delta by
    construction. Without an in-flight report (DML reads, blackout) the
    counter still counts the event."""
    from delta_tpu.utils.telemetry import bump_counter

    rep = _CURRENT.get()
    if rep is not None:
        if any(f.get("family") == family and f.get("conjunct") == conjunct
               for f in rep.rewrites_fired):
            return
        rep.rewrites_fired.append(
            {"family": family, "conjunct": conjunct, "rewrite": rewrite})
    bump_counter("scan.rewrites.fired")


def finish_report(token: "contextvars.Token",
                  completed: bool = True) -> Optional[ScanReport]:
    """Close the in-flight report. ``completed=True`` publishes it as
    :func:`last_scan_report`; a failed scan passes ``False`` so a
    half-filled report never overwrites the last genuinely completed one."""
    global _LAST
    rep = _CURRENT.get()
    _CURRENT.reset(token)
    if rep is not None and completed:
        with _LAST_LOCK:
            _LAST = rep
    return rep


def last_scan_report() -> Optional[ScanReport]:
    """The most recently completed scan's report (None before any scan, or
    while telemetry is disabled)."""
    with _LAST_LOCK:
        return _LAST


def clear_last_report() -> None:
    global _LAST
    with _LAST_LOCK:
        _LAST = None
