"""Table-health doctor — interpret the raw state into severities + remedies.

The reference surfaces raw numbers (``DESCRIBE DETAIL``, per-file stats,
checkpoint metadata) and leaves interpretation to the operator; small-file
and layout debt is the dominant silent performance killer in file-based
tables ("Only Aggressive Elephants are Fast Elephants", PAPERS.md), so this
module computes it: :func:`doctor` walks the current snapshot and
``_delta_log`` segment and yields one :class:`HealthDimension` per axis of
debt, each with a severity (``ok``/``warn``/``critical``), the numbers that
justified it, and the remedy command (OPTIMIZE / CHECKPOINT / VACUUM / PURGE
/ REPARTITION). Every numeric metric is also published as a
``table.health.*`` gauge (labeled by table path, names validated against
``obs/metric_names.py``) so the report flows into ``/metrics`` scrapes
without a second pipeline.

Thresholds are module constants, deliberately simple and visible — the
doctor's job is to rank debt, not to model it precisely.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from delta_tpu.obs import actions as actions_mod
from delta_tpu.obs.metric_names import health_gauge
from delta_tpu.utils import telemetry

__all__ = ["HealthDimension", "TableHealthReport", "doctor", "SEVERITY_RANK"]

SEVERITY_RANK = {"ok": 0, "warn": 1, "critical": 2}

# checkpoint staleness: commits replayed on every cold snapshot build
CHECKPOINT_WARN_COMMITS = 20
CHECKPOINT_CRIT_COMMITS = 100
# log tail bytes re-read per snapshot update
CHECKPOINT_WARN_TAIL_BYTES = 16 << 20
CHECKPOINT_CRIT_TAIL_BYTES = 256 << 20
# small-file debt: files below the OPTIMIZE compaction floor
SMALL_FILE_BYTES = 256 << 20  # OptimizeCommand.DEFAULT_MIN_FILE_SIZE
SMALL_WARN_COUNT = 16
SMALL_CRIT_COUNT = 128
# deletion-vector debt
DV_PURGE_FILE_PCT = 0.30  # per-file soft-deleted fraction past which PURGE
DV_WARN_PCT = 0.05
DV_CRIT_PCT = 0.20
# stats coverage
STATS_WARN_PCT = 0.90
# partition skew (Gini over per-partition bytes)
SKEW_WARN_GINI, SKEW_WARN_PARTS = 0.50, 4
SKEW_CRIT_GINI, SKEW_CRIT_PARTS = 0.80, 8


@dataclass
class HealthDimension:
    """One axis of table debt: the numbers, the verdict, and the fix."""

    name: str
    severity: str  # ok | warn | critical
    metrics: Dict[str, Any] = field(default_factory=dict)
    remedy: Optional[str] = None  # suggested command; None when ok
    detail: str = ""

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "severity": self.severity,
            "metrics": dict(self.metrics),
            "remedy": self.remedy,
            "detail": self.detail,
        }


@dataclass
class TableHealthReport:
    path: str
    version: int
    generated_at_ms: int
    severity: str
    dimensions: List[HealthDimension]
    num_files: int
    size_in_bytes: int

    def dimension(self, name: str) -> HealthDimension:
        for d in self.dimensions:
            if d.name == name:
                return d
        raise KeyError(name)

    def remedies(self) -> List[str]:
        """Distinct suggested remedies, worst dimension first."""
        out: List[str] = []
        for d in sorted(self.dimensions,
                        key=lambda d: -SEVERITY_RANK[d.severity]):
            if d.remedy and d.remedy not in out:
                out.append(d.remedy)
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path,
            "version": self.version,
            "generatedAt": self.generated_at_ms,
            "severity": self.severity,
            "remedies": self.remedies(),
            "numFiles": self.num_files,
            "sizeInBytes": self.size_in_bytes,
            "dimensions": [d.to_dict() for d in self.dimensions],
            # every remedy string above is a key of the shared maintenance
            # Action catalog — the autopilot consumes it without string
            # matching, and so can any external consumer
            "remedyCatalog": actions_mod.CATALOG_REF,
            # the doctor is point-in-time; the workload journal's advisor
            # answers the longitudinal question (what layout do the queries
            # this table ACTUALLY serves need) — see obs/advisor.py
            "advisor": "longitudinal layout advice: DeltaTable.advise() / "
                       "GET /advisor?path=<table>",
        }


def _gini(values: Sequence[float]) -> float:
    """Gini coefficient of a non-negative distribution (0 = equal,
    → 1 = one partition holds everything)."""
    n = len(values)
    total = float(sum(values))
    if n <= 1 or total <= 0:
        return 0.0
    xs = sorted(float(v) for v in values)
    weighted = sum(i * x for i, x in enumerate(xs, 1))
    return max(0.0, (2.0 * weighted) / (n * total) - (n + 1.0) / n)


def _dim_checkpoint(snapshot) -> HealthDimension:
    seg = snapshot.segment
    # no checkpoint yet: every commit since version 0 replays on cold start
    commits_since = (
        snapshot.version - seg.checkpoint_version
        if seg.checkpoint_version is not None
        else snapshot.version + 1
    )
    tail_bytes = sum(f.size for f in seg.deltas)
    sev = "ok"
    if (commits_since > CHECKPOINT_CRIT_COMMITS
            or tail_bytes > CHECKPOINT_CRIT_TAIL_BYTES):
        sev = "critical"
    elif (commits_since > CHECKPOINT_WARN_COMMITS
          or tail_bytes > CHECKPOINT_WARN_TAIL_BYTES):
        sev = "warn"
    detail = (f"{commits_since} commits replay after the last checkpoint "
              f"({tail_bytes} tail bytes)")
    if sev != "ok":
        from delta_tpu.utils.config import conf as _conf

        if not _conf.get_bool("delta.tpu.checkpoint.async", False):
            # a long tail under sustained write traffic usually means the
            # synchronous interval checkpoint can't keep up with (or is
            # being skipped by) the writers — the async builder keeps the
            # tail short without stalling commits
            detail += ("; consider delta.tpu.checkpoint.async=true "
                       "(+ .incremental) under sustained write traffic")
    return HealthDimension(
        "checkpoint", sev,
        {"commitsSince": commits_since, "tailBytes": tail_bytes,
         "tailFiles": len(seg.deltas)},
        remedy=actions_mod.remedy_name("CHECKPOINT") if sev != "ok" else None,
        detail=detail,
    )


def _dim_small_files(files) -> HealthDimension:
    small = [f for f in files if (f.size or 0) < SMALL_FILE_BYTES]
    small_bytes = sum(f.size or 0 for f in small)
    # OPTIMIZE bin-packs per partition: estimate the post-compaction file
    # count as ceil(bytes/target) per partition
    by_part: Dict[tuple, int] = {}
    for f in small:
        key = tuple(sorted((f.partition_values or {}).items()))
        by_part[key] = by_part.get(key, 0) + (f.size or 0)
    est_after = sum(max(1, math.ceil(b / SMALL_FILE_BYTES))
                    for b in by_part.values())
    reduction = max(0, len(small) - est_after)
    sev = "ok"
    if reduction >= len(small) / 2 and len(small) >= SMALL_CRIT_COUNT:
        sev = "critical"
    elif reduction >= len(small) / 2 and len(small) >= SMALL_WARN_COUNT:
        sev = "warn"
    return HealthDimension(
        "smallFiles", sev,
        {"count": len(small), "bytes": small_bytes,
         "estReduction": reduction},
        remedy=actions_mod.remedy_name("OPTIMIZE") if sev != "ok" else None,
        detail=f"{len(small)} files below the {SMALL_FILE_BYTES >> 20} MiB "
               f"compaction floor; OPTIMIZE would remove ~{reduction}",
    )


def _dim_dv(files) -> HealthDimension:
    dv_files = [f for f in files if f.deletion_vector is not None]
    deleted = sum(int((f.deletion_vector or {}).get("cardinality") or 0)
                  for f in dv_files)
    physical = 0
    past_purge = 0
    for f in files:
        n = f.num_logical_records  # stats numRecords: rows as written
        physical += n or 0
    for f in dv_files:
        n = f.num_logical_records
        card = int((f.deletion_vector or {}).get("cardinality") or 0)
        if n and card / n >= DV_PURGE_FILE_PCT:
            past_purge += 1
    pct = deleted / physical if physical else 0.0
    sev = "ok"
    if dv_files:
        if pct >= DV_CRIT_PCT:
            sev = "critical"
        elif pct >= DV_WARN_PCT or past_purge:
            sev = "warn"
    return HealthDimension(
        "dv", sev,
        {"files": len(dv_files), "deletedRows": deleted,
         "deletedPct": round(pct, 4), "filesPastPurge": past_purge},
        remedy=actions_mod.remedy_name("PURGE") if sev != "ok" else None,
        detail=f"{deleted} rows soft-deleted across {len(dv_files)} files "
               f"({pct:.1%} of the table); {past_purge} files past the "
               f"{DV_PURGE_FILE_PCT:.0%} purge threshold",
    )


def _dim_stats(files) -> HealthDimension:
    n = len(files)
    with_stats = sum(1 for f in files if f.stats is not None)
    parsed = sum(1 for f in files if f.stats_dict() is not None)
    cov = with_stats / n if n else 1.0
    parsed_pct = parsed / n if n else 1.0
    sev = "ok"
    if n and with_stats == 0:
        sev = "critical"
    elif cov < STATS_WARN_PCT or parsed_pct < STATS_WARN_PCT:
        sev = "warn"
    return HealthDimension(
        "stats", sev,
        {"coveragePct": round(cov, 4), "parsedPct": round(parsed_pct, 4)},
        remedy=actions_mod.remedy_name("OPTIMIZE") if sev != "ok" else None,
        detail=f"{with_stats}/{n} files carry stats ({parsed} parseable); "
               "files without stats are never skipped",
    )


def _dim_partition(files, partition_columns) -> HealthDimension:
    if not partition_columns:
        return HealthDimension(
            "partition", "ok", {"count": 1, "gini": 0.0},
            detail="unpartitioned table",
        )
    bytes_per: Dict[tuple, int] = {}
    for f in files:
        key = tuple(sorted((f.partition_values or {}).items()))
        bytes_per[key] = bytes_per.get(key, 0) + (f.size or 0)
    gini = _gini(list(bytes_per.values()))
    n_parts = len(bytes_per)
    sev = "ok"
    if gini >= SKEW_CRIT_GINI and n_parts >= SKEW_CRIT_PARTS:
        sev = "critical"
    elif gini >= SKEW_WARN_GINI and n_parts >= SKEW_WARN_PARTS:
        sev = "warn"
    return HealthDimension(
        "partition", sev,
        {"count": n_parts, "gini": round(gini, 4)},
        remedy=actions_mod.remedy_name("REPARTITION") if sev != "ok" else None,
        detail=f"{n_parts} partitions, byte-skew Gini {gini:.2f}",
    )


def _dim_tombstones(snapshot, live_bytes: int) -> HealthDimension:
    tombs = snapshot.tombstones
    tomb_bytes = sum(int(t.size or 0) for t in tombs)
    sev = "ok"
    if tombs and tomb_bytes > max(live_bytes, 0):
        sev = "warn"
        if live_bytes and tomb_bytes > 4 * live_bytes:
            sev = "critical"
    return HealthDimension(
        "tombstones", sev,
        {"count": len(tombs), "bytes": tomb_bytes},
        remedy=actions_mod.remedy_name("VACUUM") if sev != "ok" else None,
        detail=f"{len(tombs)} removed files ({tomb_bytes} bytes) await "
               "retention expiry",
    )


def _dim_device() -> HealthDimension:
    """Device residency pressure (8th dimension): the process-wide HBM
    ledger (`obs/hbm_ledger`) against the ``delta.tpu.device.hbmBudgetBytes``
    soft budget. Process-wide by nature — the caches are shared across
    tables — but reported per doctor call so the operator diagnosing THIS
    table sees what device memory its merges/scans compete with. Remedy
    EVICT: shrink the budgets (``delta.tpu.keyCache.maxBytes`` /
    ``delta.tpu.stateCache.maxBytes``) or disable the key cache
    (``delta.tpu.merge.keyCache.enabled=false``); `hbm_ledger.maybe_relieve`
    applies the LRU pressure immediately."""
    from delta_tpu.obs import hbm_ledger

    t = hbm_ledger.totals()
    budget = hbm_ledger.budget_bytes()
    used = t["total"]
    pressure = (used / budget) if budget else 0.0
    # per-device breakdown (sharded residency attributes slices): severity
    # follows the WORST device, not the mesh-wide mean — under an even
    # budget split, one device at 5x its fair share is the OOM candidate
    # even when the aggregate looks healthy
    per_device = hbm_ledger.device_totals()
    worst = hbm_ledger.worst_device()
    worst_pressure = 0.0
    if worst is not None and budget and per_device:
        fair = budget / max(len(per_device), 1)
        worst_pressure = worst[1] / fair if fair else 0.0
    sev = "ok"
    if budget:
        eff = max(pressure, worst_pressure)
        if eff > 1.0:
            sev = "critical"
        elif eff >= 0.8:
            sev = "warn"
    metrics = {"hbmBytes": used, "keyCacheBytes": t["keyCache"],
               "stateCacheBytes": t["stateCache"], "scratchBytes": t["scratch"],
               "budgetBytes": budget or 0, "pressure": round(pressure, 4)}
    if worst is not None:
        metrics["worstDevice"] = worst[0]
        metrics["worstDeviceBytes"] = worst[1]
        metrics["worstDevicePressure"] = round(worst_pressure, 4)
    return HealthDimension(
        "device", sev,
        metrics,
        remedy=actions_mod.remedy_name("EVICT") if sev != "ok" else None,
        detail=f"{used} device bytes resident "
               f"(keyCache {t['keyCache']}, stateCache {t['stateCache']}, "
               f"scratch {t['scratch']})"
               + (f"; worst device {worst[0]} holds {worst[1]} bytes"
                  if worst is not None else "")
               + (f" against a {budget}-byte soft budget" if budget
                  else "; no delta.tpu.device.hbmBudgetBytes budget set"),
    )


def _dim_distributed() -> HealthDimension:
    """Distributed-execution supervision health (9th dimension):
    process-wide evidence from the sharded executor's fault handling —
    retries are routine (transient IO happens), but quarantined items mean
    committed work is INCOMPLETE (an OPTIMIZE skipped a group's rewrite)
    and degradations mean a structural capability (device plan, worker
    pool, merge probe, lease coverage) silently fell back to a slower or
    more conservative path. Process-wide by nature, like the device
    dimension — the executor is shared across tables — but surfaced per
    doctor call so the operator sees WHY a job's output differs from its
    plan."""
    c = telemetry.counters("dist")
    retried = c.get("dist.items.retried", 0)
    quarantined = c.get("dist.items.quarantined", 0)
    speculated = c.get("dist.items.speculated", 0)
    wins = c.get("dist.speculation.wins", 0)
    recovered = c.get("dist.slice.recovered", 0)
    degraded = sum(v for k, v in c.items() if k.startswith("dist.degraded."))
    sev = "ok"
    if quarantined > 0 or degraded > 0:
        sev = "warn"
    return HealthDimension(
        "distributed", sev,
        {"itemsRetried": retried, "itemsQuarantined": quarantined,
         "itemsSpeculated": speculated, "speculationWins": wins,
         "slicesRecovered": recovered, "degraded": degraded},
        detail=f"{retried} item retries, {quarantined} quarantined, "
               f"{speculated} speculative re-dispatches ({wins} won), "
               f"{recovered} orphaned slices recovered, "
               f"{degraded} degradations (plan/pool/probe/lease rungs)",
    )


def _dim_protocol(snapshot) -> HealthDimension:
    p = snapshot.protocol
    features = sorted(set(p.reader_features or ()) | set(p.writer_features or ()))
    return HealthDimension(
        "protocol", "ok",
        {"minReader": p.min_reader_version, "minWriter": p.min_writer_version,
         "features": features},
        detail=f"protocol ({p.min_reader_version}, {p.min_writer_version})"
               + (f", features: {', '.join(features)}" if features else ""),
    )


def _publish(report: TableHealthReport) -> None:
    telemetry.set_gauge("table.health.severity",
                        SEVERITY_RANK[report.severity], path=report.path)
    telemetry.set_gauge("table.health.files.count", report.num_files,
                        path=report.path)
    telemetry.set_gauge("table.health.files.bytes", report.size_in_bytes,
                        path=report.path)
    for d in report.dimensions:
        for k, v in d.metrics.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue  # lists/strings stay report-only
            telemetry.set_gauge(health_gauge(d.name, k), v, path=report.path)


def doctor(table, snapshot=None, publish_gauges: bool = True) -> TableHealthReport:
    """Compute a :class:`TableHealthReport` for ``table`` (a
    :class:`~delta_tpu.api.tables.DeltaTable`, a ``DeltaLog``, or a path).

    Reads the current snapshot (or the one given) and the log segment; never
    writes. ``publish_gauges=False`` skips the ``table.health.*`` gauge
    publication (DESCRIBE DETAIL uses the numbers inline)."""
    from delta_tpu.log.deltalog import DeltaLog

    if isinstance(table, str):
        delta_log = DeltaLog.for_table(table)
    else:
        delta_log = getattr(table, "delta_log", table)
    with telemetry.record_operation("delta.utility.doctor",
                                    path=delta_log.data_path):
        snap = snapshot if snapshot is not None else delta_log.update()
        files = snap.all_files
        live_bytes = sum(f.size or 0 for f in files)
        dims = [
            _dim_checkpoint(snap),
            _dim_small_files(files),
            _dim_dv(files),
            _dim_stats(files),
            _dim_partition(files, snap.metadata.partition_columns),
            _dim_tombstones(snap, live_bytes),
            _dim_protocol(snap),
            _dim_device(),
            _dim_distributed(),
        ]
        severity = max((d.severity for d in dims), key=SEVERITY_RANK.get)
        report = TableHealthReport(
            path=delta_log.data_path,
            version=snap.version,
            generated_at_ms=delta_log.clock(),
            severity=severity,
            dimensions=dims,
            num_files=len(files),
            size_in_bytes=live_bytes,
        )
        if publish_gauges:
            _publish(report)
        telemetry.add_span_data(severity=severity,
                                remedies=report.remedies())
        return report
