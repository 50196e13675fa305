"""Distributed-trace spool and collector.

The span side of the distributed trace plane: ``utils/telemetry`` streams
every completed span (and point event) of a SAMPLED trace to the sink this
module installs, which appends one JSON line per span to a per-process
spool file under ``delta.tpu.trace.dir``. Each process in a sharded job —
the coordinator and every spawned worker — writes its own spool; nothing
coordinates at write time, so the hot path stays an append + flush.

The collector side stitches the spools back into ONE trace: spans share the
coordinator's 128-bit ``trace_id`` (threaded across process boundaries via
the traceparent-shaped wire carrier), span ids are namespaced per process,
and every span carries its start on the EPOCH clock — so
:func:`stitch_trace` can lay both hosts' spans on a single Perfetto-loadable
Chrome-trace timeline, and :func:`analyze_trace` can walk the stitched DAG
to name the critical path, the straggler shard (per-worker makespan vs the
LPT-predicted byte share), the slowest item, and how much the work-stealing
deques rescued.

Inert by default and under blackout: with ``delta.tpu.trace.dir`` unset the
sink returns before touching the filesystem, and with telemetry disabled or
the trace unsampled the sink is never called at all. The spool is bounded:
past ``delta.tpu.trace.maxBytes`` per process, spans drop (counted in
``trace.spansDropped``) instead of filling the disk.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["install", "uninstall", "read_spools", "recent_traces",
           "stitch_trace", "analyze_trace", "reset"]

_LOCK = threading.Lock()
# the open spool: directory it was opened under, file handle, bytes written
_STATE: Dict[str, Any] = {"dir": None, "fh": None, "bytes": 0, "nonce": 0}
_installed = False


# (conf generation, resolved dir) — the sink runs per sampled span, so the
# "is a spool even configured?" probe is cached until conf mutates
_DIR_CACHE = (-1, None)


def _spool_dir() -> Optional[str]:
    global _DIR_CACHE
    cached = _DIR_CACHE
    gen = conf.generation()
    if cached[0] == gen:
        return cached[1]
    d = conf.get("delta.tpu.trace.dir")
    resolved = str(d) if d else None
    _DIR_CACHE = (gen, resolved)
    return resolved


def _max_bytes() -> int:
    try:
        mb = int(conf.get("delta.tpu.trace.maxBytes", 32 * 1024 * 1024))
    except (TypeError, ValueError):
        mb = 32 * 1024 * 1024
    return mb if mb > 0 else 32 * 1024 * 1024


def _ensure_spool(directory: str):
    """The open spool handle for ``directory`` (callers hold ``_LOCK``).
    Reopens when the configured directory changes (tests, re-pointed conf)."""
    if _STATE["dir"] != directory or _STATE["fh"] is None:
        if _STATE["fh"] is not None:
            try:
                _STATE["fh"].close()
            except OSError:
                pass
        os.makedirs(directory, exist_ok=True)
        _STATE["nonce"] += 1
        path = os.path.join(
            directory, f"spool-{os.getpid()}-{_STATE['nonce']}.jsonl")
        _STATE["fh"] = open(path, "a", encoding="utf-8")  # delta-lint: ignore[lock-blocking] -- once per (re)configured spool, not per span; serialising the open IS the point
        _STATE["dir"] = directory
        _STATE["bytes"] = 0
    return _STATE["fh"]


def _sink(ev: "telemetry.UsageEvent") -> None:
    """Span sink: one JSONL line per completed span of a sampled trace.
    Conf probes happen before taking ``_LOCK`` (the conf lock must never
    nest inside a telemetry-adjacent lock)."""
    directory = _spool_dir()
    if directory is None or not ev.trace_id:
        return
    max_bytes = _max_bytes()
    line = json.dumps({
        "traceId": ev.trace_id,
        "spanId": ev.span_id or None,
        "parentId": ev.parent_id,
        "op": ev.op_type,
        "tsUs": ev.wall_us,
        "durUs": ev.duration_us,
        "pid": os.getpid(),
        "tid": ev.thread_id,
        "thread": ev.thread_name,
        "tags": ev.tags,
        "data": ev.data,
        "error": ev.error,
    }, separators=(",", ":"), default=str) + "\n"
    payload = line.encode("utf-8")
    dropped = False
    try:
        with _LOCK:
            fh = _ensure_spool(directory)
            if _STATE["bytes"] + len(payload) > max_bytes:
                dropped = True
            else:
                fh.write(line)
                fh.flush()
                _STATE["bytes"] += len(payload)
    except OSError:
        dropped = True
    if dropped:
        telemetry.bump_counter("trace.spansDropped")
    else:
        telemetry.bump_counter("trace.spansSpooled")


def install() -> None:
    """Register the spool sink with telemetry (idempotent)."""
    global _installed
    if not _installed:
        telemetry.add_span_sink(_sink)
        _installed = True


def uninstall() -> None:
    global _installed
    telemetry.remove_span_sink(_sink)
    _installed = False


def reset() -> None:
    """Close the open spool (tests); the next
    sampled span reopens a fresh spool file."""
    with _LOCK:
        if _STATE["fh"] is not None:
            try:
                _STATE["fh"].close()
            except OSError:
                pass
        _STATE.update(dir=None, fh=None, bytes=0)


# -- collector ---------------------------------------------------------------


def read_spools(directory: str,
                trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every span row across all spool files in ``directory`` (optionally
    only one trace), in spool order. Corrupt lines — a process killed
    mid-append — are skipped, not fatal: the collector reads what landed."""
    rows: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return rows
    for name in names:
        if not (name.startswith("spool-") and name.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(directory, name), encoding="utf-8") as f:
                for line in f:
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue
                    if trace_id is None or row.get("traceId") == trace_id:
                        rows.append(row)
        except OSError:
            continue
    return rows


def _roots(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    # instants carry spanId None — keep None out of the id set or a root
    # whose parentId is None would never be recognised as a root
    ids = {s.get("spanId") for s in spans if s.get("spanId")}
    return [s for s in spans
            if s.get("spanId") and s.get("parentId") not in ids]


def recent_traces(directory: str, limit: int = 20) -> List[Dict[str, Any]]:
    """Index of the most recent traces in the spool directory: one row per
    trace id with its root op, start, duration, span/process/error counts —
    the ``/traces`` payload, newest first."""
    by_trace: Dict[str, List[Dict[str, Any]]] = {}
    for row in read_spools(directory):
        by_trace.setdefault(row.get("traceId") or "?", []).append(row)
    out: List[Dict[str, Any]] = []
    for tid, spans in by_trace.items():
        starts = [int(s.get("tsUs") or 0) for s in spans]
        ends = [int(s.get("tsUs") or 0) + int(s.get("durUs") or 0)
                for s in spans]
        roots = _roots(spans)
        root = min(roots, key=lambda s: int(s.get("tsUs") or 0)) if roots \
            else None
        out.append({
            "traceId": tid,
            "rootOp": root.get("op") if root else None,
            "startUs": min(starts) if starts else 0,
            "durationMs": ((max(ends) - min(starts)) // 1000
                           if starts else 0),
            "spans": len(spans),
            "processes": len({s.get("pid") for s in spans}),
            "errors": sum(1 for s in spans if s.get("error")),
        })
    out.sort(key=lambda r: -r["startUs"])
    return out[:max(int(limit), 0)] if limit is not None else out


def stitch_trace(directory: str, trace_id: str) -> Optional[Dict[str, Any]]:
    """Stitch every process's spooled spans of ``trace_id`` into one
    Chrome-trace JSON (Perfetto-loadable): spans lie on the shared epoch
    timeline, each process renders as its own labeled lane, and every
    complete-span row carries ``traceId``/``spanId``/``parentId`` args so
    the hierarchy survives. None when the trace has no spooled spans."""
    spans = read_spools(directory, trace_id)
    if not spans:
        return None
    rows: List[Dict[str, Any]] = []
    threads: Dict[Any, str] = {}
    for s in spans:
        pid, tid = s.get("pid") or 0, s.get("tid") or 0
        threads.setdefault((pid, tid), s.get("thread") or str(tid))
        args: Dict[str, Any] = dict(s.get("tags") or {})
        args.update(s.get("data") or {})
        if s.get("error"):
            args["error"] = s["error"]
        args["traceId"] = trace_id
        args["spanId"] = s.get("spanId")
        if s.get("parentId"):
            args["parentId"] = s["parentId"]
        row: Dict[str, Any] = {
            "name": s.get("op"), "cat": "delta", "pid": pid, "tid": tid,
            "ts": int(s.get("tsUs") or 0), "args": args,
        }
        if s.get("durUs") is not None:
            row["ph"] = "X"
            row["dur"] = int(s["durUs"])
        else:
            row["ph"] = "i"
            row["s"] = "t"
        rows.append(row)
    for pid in sorted({p for p, _ in threads}):
        rows.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": f"delta-tpu-{pid}"}})
    for (pid, tid), name in threads.items():
        rows.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": name}})
    return {"traceEvents": rows, "displayTimeUnit": "ms",
            "otherData": {"traceId": trace_id}}


def _critical_path(spans: List[Dict[str, Any]],
                   root: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Walk from the root into the child whose END is latest at each level —
    the chain that determined the trace's makespan."""
    children: Dict[Any, List[Dict[str, Any]]] = {}
    for s in spans:
        if s.get("parentId") and s.get("durUs") is not None:
            children.setdefault(s["parentId"], []).append(s)
    path: List[Dict[str, Any]] = []
    node: Optional[Dict[str, Any]] = root
    while node is not None:
        kids = children.get(node.get("spanId"), [])
        kid = max(kids, key=lambda s: int(s.get("tsUs") or 0)
                  + int(s.get("durUs") or 0)) if kids else None
        # self time: the node's duration not covered by its own slowest child
        self_us = int(node.get("durUs") or 0) - (
            int(kid.get("durUs") or 0) if kid is not None else 0)
        path.append({
            "op": node.get("op"), "spanId": node.get("spanId"),
            "pid": node.get("pid"), "durUs": int(node.get("durUs") or 0),
            "selfUs": max(self_us, 0),
        })
        node = kid
    return path


def _job_analysis(job: Dict[str, Any],
                  spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-shard makespan vs the LPT-predicted byte share for one
    ``delta.dist.job`` span, plus slowest-item and steal-rescue rows."""
    data = job.get("data") or {}
    lpt_bytes = [int(b) for b in (data.get("lptBytes") or [])]
    total_bytes = sum(lpt_bytes)
    workers = [s for s in spans
               if s.get("op") == "delta.dist.worker"
               and s.get("parentId") == job.get("spanId")]
    # items parent under their worker span, or (inline path) under the job
    wids = {w.get("spanId") for w in workers}
    items = [s for s in spans
             if s.get("op") == "delta.dist.item"
             and (s.get("parentId") in wids
                  or s.get("parentId") == job.get("spanId"))]
    busy_total = sum(int(w.get("durUs") or 0) for w in workers)
    shards: List[Dict[str, Any]] = []
    for w in workers:
        ix = int((w.get("tags") or {}).get("worker", -1))
        share = (lpt_bytes[ix] / total_bytes
                 if 0 <= ix < len(lpt_bytes) and total_bytes else 0.0)
        predicted = int(busy_total * share)
        busy = int(w.get("durUs") or 0)
        w_items = [s for s in items if s.get("parentId") == w.get("spanId")]
        shards.append({
            "worker": ix, "pid": w.get("pid"), "busyUs": busy,
            "predictedUs": predicted, "deltaUs": busy - predicted,
            "bytes": lpt_bytes[ix] if 0 <= ix < len(lpt_bytes) else None,
            "items": len(w_items),
            "stolen": sum(1 for s in w_items
                          if (s.get("data") or {}).get("stolen")),
        })
    shards.sort(key=lambda s: -s["busyUs"])
    slowest = max(items, key=lambda s: int(s.get("durUs") or 0), default=None)
    stolen = [s for s in items if (s.get("data") or {}).get("stolen")]
    return {
        "label": (job.get("tags") or {}).get("job"),
        "spanId": job.get("spanId"),
        "pid": job.get("pid"),
        "durUs": int(job.get("durUs") or 0),
        "workers": len(workers),
        "items": len(items),
        "skew": data.get("skew"),
        "lptBytes": lpt_bytes or None,
        "shards": shards,
        "straggler": shards[0] if shards else None,
        "slowestItem": ({
            "index": (slowest.get("data") or {}).get("index"),
            "bytes": (slowest.get("data") or {}).get("bytes"),
            "durUs": int(slowest.get("durUs") or 0),
            "stolen": bool((slowest.get("data") or {}).get("stolen")),
            "pid": slowest.get("pid"),
        } if slowest is not None else None),
        "stealRescue": {
            "items": len(stolen),
            "bytes": sum(int((s.get("data") or {}).get("bytes") or 0)
                         for s in stolen),
            "busyUs": sum(int(s.get("durUs") or 0) for s in stolen),
        },
        # supervision attribution: retries/speculation racing outcomes per
        # item span — a speculative attempt that is NOT discarded beat the
        # original (the win the dist.speculation.wins counter records,
        # here attributed to its item and worker)
        "supervision": {
            "retriedAttempts": sum(
                max(int((s.get("data") or {}).get("attempt") or 1) - 1, 0)
                for s in items),
            "speculative": sum(1 for s in items
                               if (s.get("data") or {}).get("speculative")),
            "speculationWins": sum(
                1 for s in items
                if (s.get("data") or {}).get("speculative")
                and not (s.get("data") or {}).get("discarded")),
            "discarded": sum(1 for s in items
                             if (s.get("data") or {}).get("discarded")),
            "quarantined": data.get("quarantined") or 0,
        },
    }


def analyze_trace(directory: str,
                  trace_id: str) -> Optional[Dict[str, Any]]:
    """Walk the stitched span DAG of ``trace_id``: the critical path from
    the root, and — for every ``delta.dist.job`` span — each shard's
    makespan against its LPT-predicted byte share (naming the straggler),
    the slowest item, and what the work-stealing deques rescued. The answer
    to "which shard was the straggler and why" as a JSON document."""
    spans = read_spools(directory, trace_id)
    if not spans:
        return None
    closed = [s for s in spans if s.get("durUs") is not None]
    roots = _roots(closed)
    root = max(roots, key=lambda s: int(s.get("durUs") or 0)) if roots \
        else None
    starts = [int(s.get("tsUs") or 0) for s in spans]
    ends = [int(s.get("tsUs") or 0) + int(s.get("durUs") or 0)
            for s in spans]
    jobs = sorted(
        (_job_analysis(j, closed) for j in closed
         if j.get("op") == "delta.dist.job"),
        key=lambda j: -j["durUs"])
    shards = [s for j in jobs for s in j["shards"]]
    # fault-tolerance spans: orphaned-slice recoveries stitched into the
    # job trace (the coordinator re-executing a dead host's slice) — the
    # "why does this trace have an extra commit" answer
    recoveries = [{
        "spanId": s.get("spanId"), "pid": s.get("pid"),
        "durUs": int(s.get("durUs") or 0),
        "proc": (s.get("data") or {}).get("proc"),
        "outcome": (s.get("data") or {}).get("outcome"),
        "groups": (s.get("data") or {}).get("groups"),
    } for s in closed if s.get("op") == "delta.dist.sliceRecovery"]
    return {
        "traceId": trace_id,
        "rootOp": root.get("op") if root else None,
        "spans": len(spans),
        "processes": sorted({s.get("pid") for s in spans}),
        "errors": [{"op": s.get("op"), "spanId": s.get("spanId"),
                    "pid": s.get("pid"), "error": s.get("error")}
                   for s in spans if s.get("error")],
        "durationUs": max(ends) - min(starts) if starts else 0,
        "criticalPath": _critical_path(closed, root) if root else [],
        "jobs": jobs,
        "recoveries": recoveries,
        "straggler": max(shards, key=lambda s: s["busyUs"]) if shards
        else None,
    }
