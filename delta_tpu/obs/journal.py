"""Persistent per-table workload journal — longitudinal observability.

Doctor (`obs/doctor`) and the router audit ledger (`obs/router_audit`) are
point-in-time and per-process: when the process exits, every scan report,
commit stat, and routing decision is gone, and nothing can answer "what
layout does this table need for the queries it *actually* serves". This
module persists that evidence: one compact JSONL entry per operation,
batched into size/age-bounded segment files under
``<table>/_delta_log/_journal/`` and LRU-swept like the tmp-orphan sweep
(`log/cleanup.sweep_tmp_orphans`).

Entry kinds
===========

``scan``
    The per-query :class:`~delta_tpu.obs.scan_report.ScanReport` plus a
    normalized **predicate fingerprint** — columns referenced, per-conjunct
    op shapes with literals abstracted (``eq(v,?)``), and the
    prunable-vs-residual split (which conjuncts the shared skipping rewrite
    used by ``exec/rowgroups`` can lower to min/max stats, and which can
    only run as residual filters).
``commit``
    CommitStats (`txn/transaction`) plus the conflict/reconcile outcome and
    retry count — the raw material for contention-window analysis.
``dml``
    One entry per routed DML command (MERGE/UPDATE/DELETE): the router
    decision and the audit verdict when one was recorded.
``router``
    Every `obs/router_audit` record (merge joins AND scan-planning picks),
    so predicted-vs-actual routing history survives the audit ring.
``autopilot``
    The maintenance scheduler's **action ledger** (`delta_tpu/autopilot`):
    one entry per planned/started/executed/skipped/deferred action with the
    shared :mod:`~delta_tpu.obs.actions` model, its cited evidence, and —
    for executed actions — the predicted-vs-realized audit. Written through
    a synchronous flush (the autopilot's cooldowns survive a crash only if
    the "started" entry is on disk before the action runs).
``shadow``
    One :class:`~delta_tpu.replay.shadow.ShadowScorecard` per shadow-
    optimizer run (`delta_tpu/replay`): candidate layouts ranked by their
    MEASURED replay deltas against the baseline clone. The advisor
    attaches these verdicts to matching recommendations, and the
    autopilot's ``requireShadow`` guardrail gates rewrites on them.

Scan entries additionally carry a bounded **literal-sample reservoir**:
the first ``delta.tpu.journal.literalSamples`` (default 3) scans per
fingerprint key persist their concrete predicate SQL as ``sample`` —
deterministic first-K, so replays are stable — and every scan past the
bound has its report ``predicate`` redacted, making the reservoir the only
place concrete literals persist (size-bounded via :data:`SAMPLE_MAX_SQL`,
blackout-inert like every other journal write).

Hooks live in ``exec/scan.py``, ``txn/transaction.py``, ``commands/*`` and
``obs/router_audit.py``; each hook is a dict append under a lock — the IO
runs on a dedicated ``delta-journal-writer`` daemon thread (or inline in
:func:`flush`), never on the operation's thread. Fully inert under a
telemetry blackout (``delta.tpu.telemetry.enabled=false``) or with
``delta.tpu.journal.enabled=false``: zero bytes are written. Object-store
tables (``scheme://`` paths) skip journaling like `obs/calibration` skips
state files — the journal is plain local-file IO by design.

`obs/advisor` aggregates the journal into workload facts and ranked layout
recommendations; ``tools/journal_dump.py`` prints it offline.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["enabled", "journal_dir", "predicate_fingerprint", "record_scan",
           "record_commit", "record_dml", "record_router",
           "record_autopilot", "record_shadow", "record_dist",
           "attempt_state", "record_attempt", "flush", "read_entries",
           "sweep", "live_writer_spared", "reset"]

SEGMENT_PREFIX = "journal-"
SEGMENT_SUFFIX = ".jsonl"

#: Sweep-proof sidecar mirroring the autopilot's LAST attempt per action
#: key. Ledger entries live in journal segments the size/age sweep may
#: legitimately delete well inside a cooldown on a busy table; this one
#: small JSON file (not SEGMENT_PREFIX-named, so never swept) keeps the
#: cooldown/backoff guardrail durable for both the planner and the
#: advisor's suppression regardless of sweep pressure.
STATE_FILE = "_autopilot_state.json"

# per-table buffers keyed by journal dir; entries are ready-to-write dicts
_LOCK = threading.Lock()
_BUFFERS: Dict[str, List[Dict[str, Any]]] = {}
_OLDEST: Dict[str, float] = {}  # monotonic time of each buffer's oldest entry
# active segment per journal dir: (path, bytes_written) — files are opened
# in append mode per batch, never held open
_ACTIVE: Dict[str, Tuple[str, int]] = {}
_SWEPT: set = set()  # dirs swept at least once this process
_SEQ = 0
# IO serialization: the writer thread and synchronous flush() never
# interleave lines within a segment
_IO_LOCK = threading.Lock()
_WRITER: Optional[threading.Thread] = None
_WAKE = threading.Event()
_ATEXIT = False  # final synchronous drain registered (once per process)

#: hard cap per table buffer — a stalled writer degrades to dropped entries
#: (counted), never to unbounded memory
MAX_BUFFERED = 4096

#: longest predicate SQL a literal-sample reservoir slot accepts — one
#: pathological megabyte predicate must not blow the segment size bound
#: just to preserve a replay literal (truncated SQL would not parse back)
SAMPLE_MAX_SQL = 2048

#: literal-sample reservoir bookkeeping: journal dir → fingerprint key →
#: samples stamped so far this process. Deterministic first-K (not random
#: reservoir sampling): the same workload replayed over a fresh journal
#: yields the same sampled literals, which keeps shadow replays stable
_SAMPLE_COUNTS: Dict[str, Dict[str, int]] = {}


def enabled(log_path: Optional[str] = None) -> bool:
    """Journaling is on: the journal conf AND telemetry are enabled, and the
    table's log lives on a local filesystem (``scheme://`` paths skip it)."""
    if not conf.get_bool("delta.tpu.journal.enabled", True):
        return False
    if not conf.get_bool("delta.tpu.telemetry.enabled", True):
        return False
    if log_path is not None and "://" in log_path:
        return False
    return True


def journal_dir(log_path: str) -> str:
    """The segment directory for a table's ``_delta_log`` path."""
    return os.path.join(log_path, "_journal")


def _segment_bytes() -> int:
    try:
        n = int(conf.get("delta.tpu.journal.segmentBytes", 1 << 20))
    except (TypeError, ValueError):
        n = 1 << 20
    return n if n > 0 else 1 << 20


def _max_bytes() -> int:
    try:
        n = int(conf.get("delta.tpu.journal.maxBytes", 16 << 20))
    except (TypeError, ValueError):
        n = 16 << 20
    return n if n > 0 else 16 << 20


def _retention_ms() -> int:
    try:
        n = int(conf.get("delta.tpu.journal.retentionMs", 7 * 86_400_000))
    except (TypeError, ValueError):
        n = 7 * 86_400_000
    return n


def _flush_entries() -> int:
    try:
        n = int(conf.get("delta.tpu.journal.flushEntries", 64))
    except (TypeError, ValueError):
        n = 64
    return n if n > 0 else 64


def _flush_interval_s() -> float:
    try:
        ms = float(conf.get("delta.tpu.journal.flushIntervalMs", 2000))
    except (TypeError, ValueError):
        ms = 2000.0
    return max(ms, 100.0) / 1000.0


# ---------------------------------------------------------------------------
# Predicate fingerprint
# ---------------------------------------------------------------------------


def predicate_fingerprint(predicate, partition_cols: Iterable[str] = (),
                          types: Optional[Dict[str, Any]] = None
                          ) -> Optional[Dict[str, Any]]:
    """Normalize a predicate into its workload fingerprint: referenced
    columns, per-conjunct op shapes, and the prunable-vs-residual split —
    a conjunct is *prunable* when the shared skipping rewrite
    (``ops.pruning.skipping_predicate``, the same one ``exec/rowgroups``
    evaluates against footer stats) lowers it to something min/max-evaluable;
    otherwise it can only run as a residual filter and no amount of
    clustering will ever let it skip data. With ``types`` (lowercased
    column name → schema DataType) the rewrite includes the synthesis
    fallback, and each conjunct carries ``synthesizable``: prunable ONLY
    thanks to a synthesized rewrite — the advisor splits never-pruned
    evidence into layout vs shape vs synthesized-but-layout-bound with it."""
    if predicate is None:
        return None
    from delta_tpu.expr import ir, synthesis
    from delta_tpu.ops.pruning import skipping_predicate

    pcols = frozenset(c.lower() for c in partition_cols)
    conjuncts = []
    prunable_cols: set = set()
    residual_cols: set = set()
    for c in ir.split_conjuncts(predicate):
        cols = sorted({r.lower() for r in ir.references(c)})
        try:
            # typed but synthesis-free baseline: the NOT pushdown is a
            # base-rule fix, so it must read prunable, not synthesizable
            base_prunable = synthesis.can_exclude(
                skipping_predicate(c, pcols, types, synthesize=False))
            # synthesize=True: this runs DEFERRED on the writer thread —
            # the conf decision was resolved at scan time (record_scan
            # passes types=None when synthesis was off), so the process
            # conf's state at flush time must not re-decide it
            prunable = base_prunable or (
                types is not None
                and synthesis.can_exclude(
                    skipping_predicate(c, pcols, types, synthesize=True)))
        except Exception:  # noqa: BLE001 — fingerprinting must not fail a scan
            base_prunable = prunable = False
        (prunable_cols if prunable else residual_cols).update(cols)
        conjuncts.append({
            "shape": synthesis.shape(c),
            "columns": cols,
            "prunable": prunable,
            "synthesizable": prunable and not base_prunable,
            "partition": bool(cols) and all(col in pcols for col in cols),
        })
    return {
        "columns": sorted({col for c in conjuncts for col in c["columns"]}),
        "conjuncts": conjuncts,
        "prunableColumns": sorted(prunable_cols),
        "residualColumns": sorted(residual_cols - prunable_cols),
        "key": "&".join(sorted(c["shape"] for c in conjuncts)),
    }


# ---------------------------------------------------------------------------
# Recording hooks
# ---------------------------------------------------------------------------


def _record(log_path: str, entry: Dict[str, Any]) -> bool:
    """Buffer one entry for ``log_path``'s journal; the write happens on the
    writer thread (or a synchronous :func:`flush`). Returns False when the
    journal is inert for this table. Never raises: the commit hook runs
    AFTER version N is durably on disk and the conflict hook sits on the
    exception path — a journaling failure (e.g. ``Thread.start`` at
    interpreter shutdown) must not misreport a landed commit as failed or
    mask the conflict being raised."""
    if not enabled(log_path):
        return False
    try:
        entry.setdefault("ts", int(time.time() * 1000))
        jdir = journal_dir(log_path)
        wake = False
        with _LOCK:
            buf = _BUFFERS.setdefault(jdir, [])
            if len(buf) >= MAX_BUFFERED:
                telemetry.bump_counter("journal.entriesDropped")
                return False
            if not buf:
                _OLDEST[jdir] = time.monotonic()
            buf.append(entry)
            if len(buf) >= _flush_entries():
                wake = True
        _ensure_writer()
        if wake:
            _WAKE.set()
        return True
    except Exception:  # noqa: BLE001 — best-effort, never fail the caller
        telemetry.logger.debug("journal record failed", exc_info=True)
        return False


def record_scan(log_path: str, report=None, predicate=None,
                partition_cols: Iterable[str] = (),
                report_dict: Optional[Dict[str, Any]] = None,
                types: Optional[Dict[str, Any]] = None) -> None:
    """Journal one completed scan: the ScanReport plus the normalized
    predicate fingerprint (hook: ``exec/scan.scan_to_table``). The hot path
    pays only a dict append: callers pass the ``report_dict`` they already
    serialized for the span, and the fingerprint (an IR walk + the skipping
    rewrite per conjunct) is deferred to the writer thread — predicate IR
    expressions and the schema ``types`` map are immutable, so walking them
    off-thread is safe."""
    if not enabled(log_path):
        return
    # the reservoir bound is resolved NOW, like the synthesis decision in
    # the fingerprint input: the writer thread must not re-read a conf the
    # caller's set_temporarily scope may have exited by flush time
    _record(log_path, {
        "kind": "scan",
        "report": (report_dict if report_dict is not None
                   else report.to_dict()),
        "_fingerprint_input": (predicate, tuple(partition_cols), types),
        "_sample_limit": (conf.get_int("delta.tpu.journal.literalSamples", 3)
                          if predicate is not None else 0),
    })


def record_commit(log_path: str, stats: Dict[str, Any],
                  outcome: str = "committed") -> None:
    """Journal one commit attempt's CommitStats + outcome (``committed``,
    ``reconciledWin``, or ``conflict`` for a genuine logical conflict) —
    hook: ``txn/transaction.OptimisticTransaction``."""
    if not enabled(log_path):
        return
    _record(log_path, {"kind": "commit", "outcome": outcome,
                       "stats": dict(stats)})


def record_dml(log_path: str, op: str, **payload: Any) -> None:
    """Journal one DML command: the router decision + audit verdict for
    MERGE, mode + metrics for UPDATE/DELETE (hooks: ``commands/*``)."""
    if not enabled(log_path):
        return
    _record(log_path, {"kind": "dml", "op": op, **payload})


def record_router(log_path: str, audit: Dict[str, Any]) -> None:
    """Journal one router audit record (hook: ``obs/router_audit``)."""
    if not enabled(log_path):
        return
    _record(log_path, {"kind": "router", "audit": dict(audit)})


def record_autopilot(log_path: str, phase: str, action: Dict[str, Any],
                     durable: bool = True, **payload: Any) -> bool:
    """Journal one autopilot action-ledger entry (hook:
    ``delta_tpu/autopilot``). ``phase`` is the lifecycle stage (``planned``
    / ``started`` / ``executed`` / ``skipped`` / ``deferred`` / ``failed``
    / ``interrupted`` / ``abortedContention``); ``action`` is a
    :meth:`~delta_tpu.obs.actions.MaintenanceAction.to_dict` payload.
    ``durable=True`` (the default) bypasses the write-behind buffer and
    appends synchronously under the IO lock: the cooldown guardrail only
    works if attempt entries hit disk BEFORE the action executes — a
    crash mid-maintenance must leave the attempt visible to the restarted
    process. Returns False when the journal is inert OR (durable) when
    the write did not land — an unwritable journal directory drops the
    batch, and the caller must treat "not on disk" as "do not act"
    rather than execute with an unarmed cooldown."""
    if not enabled(log_path):
        return False
    entry = {"kind": "autopilot", "phase": phase, "action": dict(action),
             **payload}
    if not durable:
        return _record(log_path, entry)
    entry.setdefault("ts", int(time.time() * 1000))
    try:
        with _IO_LOCK:
            return _write_batch(journal_dir(log_path), [entry]) > 0
    except Exception:  # noqa: BLE001 — report failure, never raise into
        # the maintenance loop; the caller skips the action instead
        telemetry.logger.debug("durable autopilot journal write failed",
                               exc_info=True)
        return False


def record_shadow(log_path: str, scorecard: Dict[str, Any]) -> bool:
    """Journal one shadow-optimizer scorecard (hook:
    ``delta_tpu/replay/shadow.shadow_run``): the ranked candidate verdicts
    with their measured replay deltas. Buffered like scans — the shadow
    runner calls :func:`flush` itself so the NEXT ``advise()`` sees the
    verdicts read-after-write."""
    if not enabled(log_path):
        return False
    return _record(log_path, {"kind": "shadow", "scorecard": dict(scorecard)})


def record_dist(log_path: str, event: Dict[str, Any]) -> bool:
    """Journal one distributed-execution supervision event (hooks:
    ``parallel/leases`` orphan recovery, ``commands/optimize`` quarantine
    reports) — e.g. ``{"event": "dist.sliceRecovered", "groups": 3}``. The
    postmortem record of WHY a job's topology differs from its plan."""
    if not enabled(log_path):
        return False
    return _record(log_path, {"kind": "dist", **dict(event)})


def _state_path(log_path: str) -> str:
    return os.path.join(journal_dir(log_path), STATE_FILE)


def attempt_state(log_path: str) -> Dict[str, Dict[str, Any]]:
    """The autopilot sidecar's last-attempt map: action key →
    ``{"phase", "ts"}`` (see :data:`STATE_FILE`); empty when absent."""
    try:
        with open(_state_path(log_path), encoding="utf-8") as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except (OSError, ValueError):
        return {}


def record_attempt(log_path: str, key: str, phase: str, ts_ms: int) -> bool:
    """Durably mirror one autopilot attempt into the sidecar (atomic
    replace). Returns False when the write failed — the autopilot treats
    an un-persistable attempt as "do not act": without it on disk, a
    crash mid-action would leave the restarted process free to
    crash-loop."""
    import contextlib
    import uuid

    path = _state_path(log_path)
    state = attempt_state(log_path)
    state[key] = {"phase": phase, "ts": int(ts_ms)}
    tmp = f"{path}.{uuid.uuid4().hex}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(state, f, separators=(",", ":"))
            os.replace(tmp, path)
        finally:
            with contextlib.suppress(OSError):
                os.unlink(tmp)  # replace won: gone already; crash: no orphan
    except OSError:
        return False
    return True


# ---------------------------------------------------------------------------
# Literal-sample reservoir
# ---------------------------------------------------------------------------


def _stamp_sample(jdir: str, e: Dict[str, Any], predicate,
                  limit: int) -> None:
    """Persist the first ``limit`` concrete predicate SQLs per fingerprint
    key as ``e["sample"]`` — the bounded literal store that lets the replay
    layer (`delta_tpu/replay/trace`) rehydrate abstract fingerprints
    (``eq(v,?)``) back into executable scans. Entries past the bound get
    their report ``predicate`` redacted instead: the reservoir is then the
    ONLY place concrete literals persist, so the bound is a real bound.
    Runs on the writer thread; callers hold ``_IO_LOCK``."""
    fp = e.get("fingerprint") or {}
    key = fp.get("key")
    if key and limit > 0:
        counts = _SAMPLE_COUNTS.setdefault(jdir, {})
        if counts.get(key, 0) < limit:
            try:
                sql = predicate.sql()
            except Exception:  # noqa: BLE001 — sampling must not drop entries
                sql = None
            if sql and len(sql) <= SAMPLE_MAX_SQL:
                e["sample"] = sql
                counts[key] = counts.get(key, 0) + 1
                telemetry.bump_counter("journal.literalSamples")
                return
    report = e.get("report")
    if isinstance(report, dict) and report.get("predicate") is not None:
        # COPY before redacting — the caller's report dict is the SAME
        # object attached to the scan span's ``scanReport`` payload
        e["report"] = {**report, "predicate": None}


# ---------------------------------------------------------------------------
# Writer thread + segment IO
# ---------------------------------------------------------------------------


def _ensure_writer() -> None:
    global _WRITER, _ATEXIT
    if _WRITER is not None and _WRITER.is_alive():
        return
    with _LOCK:
        if _WRITER is not None and _WRITER.is_alive():
            return
        if not _ATEXIT:
            # a short-lived process (scan + commit + exit inside the flush
            # interval) must not lose its buffered entries with the daemon
            # writer: drain synchronously at interpreter exit
            atexit.register(_final_flush)
            _ATEXIT = True
        _WRITER = threading.Thread(target=_writer_loop, daemon=True,
                                   name="delta-journal-writer")
        _WRITER.start()


def _final_flush() -> None:  # pragma: no cover — exercised via subprocess test
    try:
        _drain(aged_only=False)
    except Exception:  # noqa: BLE001 — exiting anyway
        pass


def _writer_loop() -> None:  # pragma: no cover — exercised via flush() too
    while True:
        _WAKE.wait(timeout=_flush_interval_s())
        _WAKE.clear()
        try:
            _drain(aged_only=True)
        except Exception:  # noqa: BLE001 — journaling must never kill the thread
            telemetry.logger.debug("journal writer flush failed", exc_info=True)


def _take_batches(aged_only: bool,
                  only_dir: Optional[str]) -> List[Tuple[str, List[dict]]]:
    now = time.monotonic()
    interval = _flush_interval_s()
    limit = _flush_entries()
    out = []
    with _LOCK:
        for jdir in list(_BUFFERS):
            if only_dir is not None and jdir != only_dir:
                continue
            buf = _BUFFERS[jdir]
            if not buf:
                continue
            aged = now - _OLDEST.get(jdir, now) >= interval
            if aged_only and not (aged or len(buf) >= limit):
                continue
            out.append((jdir, buf))
            _BUFFERS[jdir] = []
            _OLDEST.pop(jdir, None)
    return out


def _drain(aged_only: bool = False, only_dir: Optional[str] = None) -> int:
    """Take buffered batches and write them. The WHOLE cycle (take + write)
    runs under ``_IO_LOCK``: a concurrent :func:`flush` blocks until any
    in-flight writer batch is on disk before taking its own, so
    read-after-flush sees every entry recorded before the call and batches
    land in take order (``read_entries``'s oldest-first contract)."""
    written = 0
    with _IO_LOCK:
        for jdir, entries in _take_batches(aged_only, only_dir):
            written += _write_batch(jdir, entries)
    return written


def _next_segment(jdir: str) -> str:
    global _SEQ
    with _LOCK:
        _SEQ += 1
        seq = _SEQ
    name = f"{SEGMENT_PREFIX}{int(time.time() * 1000):013d}-" \
           f"{os.getpid()}-{seq:06d}{SEGMENT_SUFFIX}"
    return os.path.join(jdir, name)


def _write_batch(jdir: str, entries: List[dict]) -> int:
    """Append one batch as JSONL, rotating the active segment at the size
    bound and sweeping the directory on rotation. Deferred work entries
    carry (the scan fingerprint) happens HERE, on the writer thread, not on
    the operation's thread. Callers hold ``_IO_LOCK`` (via :func:`_drain`).
    Best-effort: an unwritable directory drops the batch (counted), never
    fails the caller."""
    lines = []
    for e in entries:
        fp_in = e.pop("_fingerprint_input", None)
        sample_limit = e.pop("_sample_limit", 0)
        if fp_in is not None:
            try:
                e["fingerprint"] = predicate_fingerprint(
                    fp_in[0], fp_in[1], fp_in[2] if len(fp_in) > 2 else None)
            except Exception:  # noqa: BLE001 — never lose the report over it
                e["fingerprint"] = None
            if fp_in[0] is not None:
                _stamp_sample(jdir, e, fp_in[0], sample_limit)
        try:
            lines.append(json.dumps(e, separators=(",", ":"), default=str))
        except (TypeError, ValueError):
            continue
    if not lines:
        return 0
    # byte accounting must match what lands on disk (non-ASCII escapes via
    # default=str can still multi-byte), or rotation and the sweep disagree
    data = ("\n".join(lines) + "\n").encode("utf-8")
    seg_limit = _segment_bytes()
    rotated = False
    try:
        os.makedirs(jdir, exist_ok=True)
        active = _ACTIVE.get(jdir)
        if active is None or active[1] >= seg_limit \
                or not os.path.exists(active[0]):
            if jdir not in _SWEPT or active is not None:
                sweep(jdir)
            active = (_next_segment(jdir), 0)
            rotated = True
        # delta-lint: ignore[lock-blocking] -- _IO_LOCK is the journal's IO
        # serialization lock; appending under it is its entire purpose
        with open(active[0], "ab") as f:
            f.write(data)
        _ACTIVE[jdir] = (active[0], active[1] + len(data))
    except OSError:
        telemetry.bump_counter("journal.entriesDropped", len(lines))
        return 0
    if rotated:
        # counted only once the file actually exists — an unwritable dir
        # re-enters the rotation branch every batch and must not inflate it
        telemetry.bump_counter("journal.segments.written")
    telemetry.bump_counter("journal.entries", len(lines))
    telemetry.bump_counter("journal.bytes.written", len(data))
    # per-table write volume for the fleet plane (label: hashed table path
    # — jdir is <table>/_delta_log/_journal). KiB, not bytes: the shared
    # log2 histogram buckets top out at 65536, so byte-valued flushes over
    # 64 KiB would all collapse into +Inf
    from delta_tpu.obs.fleet import table_label

    table_path = os.path.dirname(os.path.dirname(jdir))
    telemetry.observe("journal.flushKb", len(data) / 1024.0,
                      table=table_label(table_path))
    return len(lines)


def flush(log_path: Optional[str] = None) -> int:
    """Synchronously write every buffered entry (for one table's log path,
    or all); returns entries written. The advisor and tests call this —
    steady-state writes stay on the writer thread."""
    only = journal_dir(log_path) if log_path is not None else None
    return _drain(aged_only=False, only_dir=only)


def live_writer_spared(stats: List[Tuple[str, int, float]],
                       grace_s: float) -> set:
    """The possibly-live subset of per-process files in a shared directory:
    among ``(path, size, mtime)`` stats whose basenames embed the creating
    pid at dash-field 2 (``<prefix>-<ts>-<pid>-...``), the newest file per
    pid, while touched within ``grace_s`` seconds. A process writes only to
    ITS newest file (journal segments rotate forward; dist leases heartbeat
    in place), so anything else — or anything grace-stale, since a live
    writer touches its file at least every flush/heartbeat interval — is
    guaranteed dead and fair game for the caller's sweep. One immune file
    per CI/cron run would make size caps and lease expiry unenforceable.
    Shared by the journal sweep and ``parallel/leases.sweep_leases`` so the
    two sweeps cannot drift on what "live" means."""
    newest_per_pid: Dict[str, str] = {}
    mtimes: Dict[str, float] = {}
    for p, _size, mtime in sorted(stats):  # name-sorted oldest → newest
        parts = os.path.basename(p).split("-")
        newest_per_pid[parts[2] if len(parts) >= 4 else ""] = p
        mtimes[p] = mtime
    now = time.time()
    return {p for p in newest_per_pid.values()
            if now - mtimes[p] <= grace_s}


def sweep(jdir: str) -> int:
    """Bound the journal directory: segments older than
    ``delta.tpu.journal.retentionMs`` are deleted, then oldest-first until
    the total is within ``delta.tpu.journal.maxBytes`` — the same
    aged-orphan discipline as ``log/cleanup.sweep_tmp_orphans``."""
    # _SWEPT is shared with the writer daemon (_write_batch's rotation
    # check) and sweep() is public API — mutate under the buffer lock
    with _LOCK:
        _SWEPT.add(jdir)
    try:
        names = sorted(n for n in os.listdir(jdir)
                       if n.startswith(SEGMENT_PREFIX)
                       and n.endswith(SEGMENT_SUFFIX))
    except OSError:
        return 0
    cutoff = time.time() - _retention_ms() / 1000.0
    max_total = _max_bytes()
    stats = []
    for n in names:
        p = os.path.join(jdir, n)
        try:
            st = os.stat(p)
        except OSError:
            continue
        stats.append((p, st.st_size, st.st_mtime))
    total = sum(s[1] for s in stats)
    deleted = 0
    active = _ACTIVE.get(jdir)
    active_path = active[0] if active is not None else None
    # Age expiry spares nothing: a table that stopped journaling must shed
    # its final segment too — except this process's own active file (tests
    # run with tiny retention windows while entries are still buffered for
    # it). Size pressure additionally spares possibly-live concurrent
    # writers' newest segments (see live_writer_spared).
    spared_set = live_writer_spared(stats,
                                    max(60.0, 10 * _flush_interval_s()))
    for p, size, mtime in stats:
        if p == active_path:
            continue
        if mtime <= cutoff or (total > max_total and p not in spared_set):
            try:
                os.remove(p)
                deleted += 1
                total -= size
            except OSError:
                continue
    if deleted:
        telemetry.bump_counter("journal.segments.swept", deleted)
    return deleted


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------


def read_entries(log_path: str, kinds: Optional[Iterable[str]] = None,
                 limit: Optional[int] = None) -> List[Dict[str, Any]]:
    """Parse every journal segment for a table, oldest entry first.
    Segment-name order (names embed the creation epoch) is only a first
    pass — two processes journaling the same table interleave in time while
    each appends to its OWN active segment, so entries are stable-sorted by
    their recorded ``ts`` (within-segment order kept on ties). Malformed
    lines are skipped — a torn tail write must never poison the history.
    ``kinds`` filters entry kinds; ``limit`` keeps the LAST N entries (a
    genuine recent window, thanks to the sort)."""
    jdir = journal_dir(log_path)
    try:
        names = sorted(n for n in os.listdir(jdir)
                       if n.startswith(SEGMENT_PREFIX)
                       and n.endswith(SEGMENT_SUFFIX))
    except OSError:
        return []
    want = frozenset(kinds) if kinds is not None else None
    out: List[Dict[str, Any]] = []
    for n in names:
        try:
            with open(os.path.join(jdir, n), encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        e = json.loads(line)
                    except ValueError:
                        continue
                    if not isinstance(e, dict):
                        continue
                    if want is None or e.get("kind") in want:
                        out.append(e)
        except OSError:
            continue
    out.sort(key=lambda e: e.get("ts") or 0)  # stable: ties keep file order
    if limit is not None and limit >= 0:
        # out[-0:] would be the WHOLE list — limit=0 means "no entries"
        out = out[-limit:] if limit > 0 else []
    return out


def reset() -> None:
    """Drop in-memory buffers and active-segment bookkeeping (tests).
    On-disk segments are left alone — delete the
    ``_journal`` directory to forget a table's history."""
    with _LOCK:
        _BUFFERS.clear()
        _OLDEST.clear()
        _SWEPT.clear()
    with _IO_LOCK:
        _ACTIVE.clear()
        _SAMPLE_COUNTS.clear()
