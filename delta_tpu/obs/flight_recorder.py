"""Failure flight recorder — post-mortems that survive the ring buffer.

When an instrumented operation raises (commit conflict, scan/DML error), the
spans that explain it sit in a 4096-event ring buffer and are overwritten
within seconds on a busy table. This module registers a telemetry failure
hook (``utils/telemetry.add_failure_hook``) that — while
``delta.tpu.obs.incidentDir`` is set — snapshots the moment of failure into
one bounded incident JSON file:

* the open span stack at the instant of the raise (innermost span included,
  with its payload and elapsed time),
* the last N ring-buffer events (``delta.tpu.obs.incidentEvents``, def. 64),
* every counter, and the error itself.

Files are named ``incident-<epoch_ms>-<seq>-<opType>.json`` and pruned
oldest-first to ``delta.tpu.obs.incidentKeep`` (default 20). Off by default:
with ``incidentDir`` unset the hook exits on one conf probe, and hooks only
run on the error path at all. An exception unwinding through nested spans
fires the hook once per span — incidents dedupe on exception identity, so
one failure is one file (with the innermost, fullest stack).
"""
from __future__ import annotations

import json
import os
import threading
from collections import deque
from typing import Any, Dict, List, Optional

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["install", "uninstall", "record_incident", "incident_files"]

_LOCK = threading.Lock()
_SEQ = 0
# id()s of exceptions already recorded: the same exception unwinding through
# every enclosing span must not write one incident per span
_SEEN_EXC: "deque[int]" = deque(maxlen=64)
_installed = False


def _incident_dir() -> Optional[str]:
    d = conf.get("delta.tpu.obs.incidentDir")
    return str(d) if d else None


def incident_files(directory: Optional[str] = None) -> List[str]:
    """Incident file paths in ``directory`` (default: the configured dir),
    oldest first (the name embeds the timestamp and a monotonic sequence)."""
    d = directory or _incident_dir()
    if not d or not os.path.isdir(d):
        return []
    return sorted(
        os.path.join(d, f) for f in os.listdir(d)
        if f.startswith("incident-") and f.endswith(".json")
    )


def _sanitize(op_type: str) -> str:
    return "".join(c if c.isalnum() or c in "._-" else "_" for c in op_type)


def record_incident(ev, exc: BaseException) -> Optional[str]:
    """The failure hook body: write one incident file for ``exc`` (deduped)
    and prune the directory. Returns the path written, or None."""
    directory = _incident_dir()
    if directory is None:
        return None
    # one exception unwinding through N nested spans = one incident: mark
    # the exception object itself (id() alone can be recycled after gc)
    if getattr(exc, "_delta_incident_recorded", False):
        return None
    try:
        exc._delta_incident_recorded = True  # type: ignore[attr-defined]
    except Exception:  # noqa: BLE001 — slotted exceptions: fall back to id()
        with _LOCK:
            if id(exc) in _SEEN_EXC:
                return None
            _SEEN_EXC.append(id(exc))
    with _LOCK:
        global _SEQ
        _SEQ += 1
        seq = _SEQ
    try:
        keep = int(conf.get("delta.tpu.obs.incidentKeep", 20))
    except (TypeError, ValueError):
        keep = 20
    try:
        n_events = int(conf.get("delta.tpu.obs.incidentEvents", 64))
    except (TypeError, ValueError):
        n_events = 64
    events = telemetry.recent_events()[-max(n_events, 0):]
    incident: Dict[str, Any] = {
        "timestamp": ev.timestamp_ms,
        "opType": ev.op_type,
        # the failing span's trace: errors force-sample, so this links to
        # a spooled, stitchable /traces/<id> view of the incident
        "traceId": (getattr(ev, "trace_id", "")
                    or telemetry.current_trace_id()),
        "error": f"{type(exc).__name__}: {exc}",
        "tags": dict(ev.tags),
        "data": _jsonable(ev.data),
        "spanStack": _jsonable(telemetry.span_stack_snapshot()),
        "recentEvents": [json.loads(e.to_json()) for e in events],
        "counters": telemetry.counters(),
        "pid": os.getpid(),
        "thread": threading.current_thread().name,
    }
    # what the query was DOING, not just the span stack: the scan report
    # in flight on this context (e.g. a deadline breach mid-scan) and
    # the last router-audit record, when they exist
    try:
        from delta_tpu.obs import router_audit, scan_report

        rep = scan_report.current_report()
        if rep is not None:
            incident["scanReport"] = rep.to_dict()
        audit = router_audit.last_audit()
        if audit is not None:
            incident["routerAudit"] = audit.to_dict()
    except Exception:  # noqa: BLE001 — the recorder must never raise
        pass
    os.makedirs(directory, exist_ok=True)
    name = f"incident-{ev.timestamp_ms:013d}-{seq:06d}-{_sanitize(ev.op_type)}.json"
    path = os.path.join(directory, name)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(incident, f, indent=1, default=str)
    telemetry.bump_counter("obs.incidents.written")
    if keep > 0:
        for old in incident_files(directory)[:-keep]:
            try:
                os.remove(old)
            except OSError:
                pass
    return path


def _jsonable(obj):
    return json.loads(json.dumps(obj, default=str))


def install() -> None:
    """Register the recorder hook (idempotent). Inert until
    ``delta.tpu.obs.incidentDir`` is set; importing ``delta_tpu.obs``
    installs it."""
    global _installed
    if not _installed:
        telemetry.add_failure_hook(record_incident)
        _installed = True


def uninstall() -> None:
    global _installed
    telemetry.remove_failure_hook(record_incident)
    _installed = False
