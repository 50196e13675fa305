"""Autopilot daemon — the closed observe→decide→act→audit loop.

One :func:`run_once` pass over a table is the whole loop:

1. **observe** — run the doctor and the advisor (both already feed the
   journal/gauges);
2. **decide** — `planner.plan` merges their remedies through the shared
   action catalog, then the persistent action ledger filters cooldowns and
   contention backoff;
3. **act** — with dry-run OFF, a quiet window, and the one-table-at-a-time
   lock held, `executor.execute` runs each action under the cost caps;
4. **audit** — a fresh doctor report brackets every executed action and
   the predicted-vs-realized delta lands in the action ledger (journal
   kind ``autopilot``), which the NEXT `advise()` cites instead of
   re-recommending the executed action — the same closed-loop idiom as the
   router calibrator (`obs/calibration`).

The :class:`Autopilot` daemon (thread ``delta-autopilot``) just ticks
:func:`run_once` over registered tables every
``delta.tpu.autopilot.intervalMs``. Strictly opt-in
(``delta.tpu.autopilot.enabled``), and dry-run by default
(``delta.tpu.autopilot.dryRun``) — until an operator flips both, nothing
executes, and the journaled plans show exactly what WOULD have run.

Crash semantics match the rest of the engine: every action's ``started``
ledger entry is flushed to disk BEFORE execution, so a process death
mid-maintenance leaves the attempt visible and the cooldown armed — a
crash-looping autopilot cannot re-execute the same action on every
restart (torture-tested via ``TortureHarness(autopilot=True)``).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from delta_tpu.autopilot import executor, planner
from delta_tpu.obs import journal as journal_mod
from delta_tpu.obs.actions import MaintenanceAction
from delta_tpu.obs.actions import spec as actions_spec
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

__all__ = ["Autopilot", "RunReport", "run_once", "status", "enabled",
           "dry_run", "last_runs", "reset"]

#: one-table-at-a-time: ONE maintenance action executes per process at any
#: moment, whichever thread (daemon or explicit run_once) got here first
_EXEC_LOCK = threading.Lock()

_STATE_LOCK = threading.Lock()
_LAST_RUNS: Dict[str, Dict[str, Any]] = {}  # path -> last RunReport dict
_DAEMON: Optional["Autopilot"] = None


def enabled() -> bool:
    return conf.get_bool("delta.tpu.autopilot.enabled", False)


def dry_run() -> bool:
    return conf.get_bool("delta.tpu.autopilot.dryRun", True)


@dataclass
class RunReport:
    """What one autopilot pass over one table observed and did."""

    path: str
    started_at_ms: int
    status: str = "ok"             # ok | journal disabled | deferred | busy
    dry_run: bool = True
    quiet: Dict[str, Any] = field(default_factory=dict)
    planned: List[Dict[str, Any]] = field(default_factory=list)
    planned_keys: List[str] = field(default_factory=list)
    cooled: List[str] = field(default_factory=list)   # keys inside cooldown
    #: rewrite-class actions the ``requireShadow`` guardrail held back,
    #: with the verdict + shadow evidence cited (`planner.shadow_gate`)
    shadow_filtered: List[Dict[str, Any]] = field(default_factory=list)
    backoff_until_ms: Optional[int] = None
    outcomes: List[Dict[str, Any]] = field(default_factory=list)
    duration_ms: float = 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path,
            "startedAt": self.started_at_ms,
            "status": self.status,
            "dryRun": self.dry_run,
            "quiet": dict(self.quiet),
            "planned": list(self.planned),
            "plannedKeys": list(self.planned_keys),
            "cooldownFiltered": list(self.cooled),
            "shadowFiltered": list(self.shadow_filtered),
            "backoffUntil": self.backoff_until_ms,
            "outcomes": list(self.outcomes),
            "durationMs": round(self.duration_ms, 3),
        }


def _resolve_log(table):
    from delta_tpu.log.deltalog import DeltaLog

    if isinstance(table, str):
        return DeltaLog.for_table(table)
    return getattr(table, "delta_log", table)


def _finish(report: RunReport, t0: float) -> RunReport:
    report.duration_ms = (time.monotonic() - t0) * 1000.0
    with _STATE_LOCK:
        _LAST_RUNS[report.path] = report.to_dict()
    return report


def run_once(table, force: bool = False) -> RunReport:
    """One full autopilot pass over ``table`` (DeltaTable, DeltaLog, or
    path). ``force=True`` skips the quiet-window check (operator-invoked
    "run it NOW"); every other guardrail still applies. Safe to call with
    the daemon running — execution is serialized process-wide."""
    t0 = time.monotonic()
    delta_log = _resolve_log(table)
    log_path = delta_log.log_path
    now = delta_log.clock()
    report = RunReport(path=delta_log.data_path, started_at_ms=now,
                       dry_run=dry_run())
    with telemetry.record_operation("delta.utility.autopilot",
                                    path=delta_log.data_path):
        telemetry.bump_counter("autopilot.runs")
        telemetry.set_gauge("autopilot.lastRunTimestamp", now,
                            path=delta_log.data_path)
        if not journal_mod.enabled(log_path):
            # no journal = no durable ledger = no cooldowns: refusing to
            # act is the only safe posture
            report.status = "journal disabled"
            return _finish(report, t0)

        # -- observe ----------------------------------------------------
        from delta_tpu.obs.advisor import advise
        from delta_tpu.obs.doctor import doctor

        doc = doctor(delta_log)
        adv = advise(delta_log)

        # -- decide -----------------------------------------------------
        # one journal read per pass: advise() just flushed, so a single
        # parse serves the ledger, the backoff scan, and the quiet window.
        # Ledger/window math runs on WALL time — journal entries stamp
        # ts from time.time(), and delta_log.clock() is injectable (tests
        # pin it), so mixing the domains would freeze every cooldown
        entries = journal_mod.read_entries(log_path)
        ledger = [e for e in entries if e.get("kind") == "autopilot"]
        commits = [e for e in entries if e.get("kind") == "commit"]
        wall_now = int(time.time() * 1000)
        blocked = planner.cooldown_blocked(ledger, wall_now,
                                           log_path=log_path)
        backoff = planner.contention_backoff_until(ledger, wall_now,
                                                   log_path=log_path)
        actions = planner.plan(doc, adv)
        # requireShadow guardrail BEFORE the cooldown filter and the
        # dry-run return: a dry-run plan must show the suppression too —
        # that's the whole point of rehearsing
        actions, shadow_deferred = planner.shadow_gate(
            actions, log_path,
            entries=[e for e in entries if e.get("kind") == "shadow"])
        if shadow_deferred:
            report.shadow_filtered = shadow_deferred
            telemetry.bump_counter("autopilot.actions.deferred",
                                   len(shadow_deferred))
            for d in shadow_deferred:
                journal_mod.record_autopilot(
                    log_path, "deferred",
                    {"kind": d["kind"], "target": d["target"],
                     "shadow": d.get("shadow")},
                    durable=False,
                    reason=f"requireShadow: {d['reason']}")
        runnable: List[MaintenanceAction] = []
        for a in actions:
            if a.key in blocked:
                report.cooled.append(a.key)
            else:
                runnable.append(a)
        max_actions = conf.get_int("delta.tpu.autopilot.maxActionsPerRun", 4)
        runnable = runnable[:max_actions]
        if runnable:
            telemetry.bump_counter("autopilot.actions.planned",
                                   len(runnable))
        planned_keys = sorted(a.key for a in runnable)
        with _STATE_LOCK:
            prev_planned = (_LAST_RUNS.get(delta_log.data_path) or {}).get(
                "plannedKeys")
        if planned_keys != prev_planned:
            # journal the plan only when it CHANGED — a dry-run daemon
            # ticking over stable debt must not flood the journal with
            # identical entries every interval. Buffered write: "planned"
            # never arms a cooldown, so it needs no durable sync write.
            for a in runnable:
                journal_mod.record_autopilot(log_path, "planned",
                                             a.to_dict(), durable=False,
                                             dryRun=report.dry_run)
        report.planned = [a.to_dict() for a in runnable]
        report.planned_keys = planned_keys
        if not runnable:
            return _finish(report, t0)

        # -- guardrails before acting ------------------------------------
        if report.dry_run:
            # the journaled "planned" entries ARE the dry run's output
            report.status = "dry-run"
            return _finish(report, t0)
        if backoff is not None:
            report.status = "deferred"
            report.backoff_until_ms = backoff
            telemetry.bump_counter("autopilot.actions.deferred",
                                   len(runnable))
            for a in runnable:
                journal_mod.record_autopilot(
                    log_path, "deferred", a.to_dict(), durable=False,
                    reason=f"contention backoff until {backoff}")
            return _finish(report, t0)
        report.quiet = planner.quiet_window(log_path, wall_now,
                                            commits=commits)
        if not force and not report.quiet["quiet"]:
            report.status = "deferred"
            telemetry.bump_counter("autopilot.actions.deferred",
                                   len(runnable))
            for a in runnable:
                journal_mod.record_autopilot(
                    log_path, "deferred", a.to_dict(), durable=False,
                    reason="window not quiet",
                    window=report.quiet)
            return _finish(report, t0)
        if not _EXEC_LOCK.acquire(blocking=False):
            # another table's maintenance is mid-flight in this process
            report.status = "busy"
            telemetry.bump_counter("autopilot.actions.deferred",
                                   len(runnable))
            for a in runnable:
                journal_mod.record_autopilot(
                    log_path, "deferred", a.to_dict(), durable=False,
                    reason="maintenance executor busy (one table at a time)")
            return _finish(report, t0)

        # -- act + audit -------------------------------------------------
        try:
            _execute_plan(delta_log, runnable, doc, report, t0)
        finally:
            _EXEC_LOCK.release()
        return _finish(report, t0)


def _execute_plan(delta_log, runnable: List[MaintenanceAction],
                  doc, report: RunReport, t0: float) -> None:
    """Run the plan under the wall-clock budget, journaling each action's
    lifecycle durably and auditing predicted-vs-realized per action."""
    from delta_tpu.obs.doctor import doctor

    log_path = delta_log.log_path
    budget_ms = conf.get_int("delta.tpu.autopilot.budgetMs", 300_000)
    # maxBytesPerRun is a PER-RUN pool: each rewrite action draws from it
    # and the remainder caps the next one, so a run can never rewrite more
    # than the cap no matter how many actions the plan holds
    bytes_left = conf.get_int("delta.tpu.autopilot.maxBytesPerRun", 2 << 30)
    attempts_cap = conf.get_int("delta.tpu.autopilot.maxCommitAttempts", 3)
    # re-check cooldowns now that the exec lock is held: a concurrent
    # run_once (daemon tick + manual call) may have attempted an action
    # between our plan and our turn at the lock (wall time: ledger ts
    # stamps come from time.time())
    blocked_now = planner.cooldown_blocked(
        planner.ledger_entries(log_path), int(time.time() * 1000),
        log_path=log_path)
    before = doc
    for a in runnable:
        if a.key in blocked_now:
            report.cooled.append(a.key)
            report.outcomes.append({"action": a.key, "status": "skipped",
                                    "reason": "cooldown"})
            continue
        elapsed_ms = (time.monotonic() - t0) * 1000.0
        if elapsed_ms > budget_ms:
            telemetry.bump_counter("autopilot.actions.skipped")
            journal_mod.record_autopilot(
                log_path, "skipped", a.to_dict(), durable=False,
                reason=f"run budget {budget_ms}ms exhausted "
                       f"({elapsed_ms:.0f}ms elapsed)")
            report.outcomes.append({"action": a.key, "status": "skipped",
                                    "reason": "runBudget"})
            continue
        # durable BEFORE acting: a crash mid-action must leave the attempt
        # on disk so the restarted process's cooldown check sees it. BOTH
        # the ledger entry and the sweep-proof sidecar must land — a
        # degraded journal directory (disk full, perms) cannot arm the
        # cooldown, and executing without one invites a crash loop
        journaled = journal_mod.record_autopilot(log_path, "started",
                                                 a.to_dict(), durable=True)
        mirrored = journal_mod.record_attempt(log_path, a.key, "started",
                                          int(time.time() * 1000))
        if not (journaled and mirrored):
            telemetry.bump_counter("autopilot.actions.skipped")
            report.outcomes.append({"action": a.key, "status": "skipped",
                                    "reason": "ledgerUnwritable"})
            continue
        try:
            result = executor.execute(delta_log, a,
                                      max_bytes=max(bytes_left, 0),
                                      attempts_cap=attempts_cap)
        except BaseException:
            # process-death class (SimulatedCrash in the harness): journal
            # the interruption best-effort and let it pierce — the started
            # entry above already armed the cooldown either way
            journal_mod.record_autopilot(log_path, "interrupted",
                                         a.to_dict())
            journal_mod.record_attempt(log_path, a.key, "interrupted",
                                   int(time.time() * 1000))
            raise
        if result.status == "executed":
            bytes_left -= int(result.metrics.get("numRemovedBytes") or 0)
        after = None
        if result.status == "executed" and (
                executor.audit_metrics(a.kind) is not None
                or actions_spec(a.kind).mutates_table):
            # re-measure after ANY executed mutating action — a ZORDER has
            # no audited doctor dimension of its own but still rewrites
            # files, and the NEXT action's audit must not credit that
            try:
                after = doctor(delta_log)
            except Exception:  # noqa: BLE001 — audit is best-effort
                after = None
        audit = executor.build_audit(a, before, after)
        journal_mod.record_autopilot(
            log_path, result.status, a.to_dict(),
            result=result.to_dict(), audit=audit)
        journal_mod.record_attempt(log_path, a.key, result.status,
                                   int(time.time() * 1000))
        report.outcomes.append({"action": a.key, "status": result.status,
                                "result": result.to_dict(),
                                "audit": audit})
        if result.status == "abortedContention":
            # one lost maintenance commit backs the WHOLE table off — the
            # remaining actions must not keep racing the same foreground
            # writers inside this very run; they defer to a later pass
            rest = runnable[runnable.index(a) + 1:]
            if rest:
                telemetry.bump_counter("autopilot.actions.deferred",
                                       len(rest))
            for b in rest:
                journal_mod.record_autopilot(
                    log_path, "deferred", b.to_dict(), durable=False,
                    reason="contention backoff (earlier action in this "
                           "run lost to a foreground writer)")
                report.outcomes.append({"action": b.key,
                                        "status": "deferred",
                                        "reason": "contentionBackoff"})
            break
        if after is not None:
            before = after  # the next action audits against fresh state


# ---------------------------------------------------------------------------
# Daemon
# ---------------------------------------------------------------------------


class Autopilot:
    """Per-process maintenance daemon: ticks :func:`run_once` over the
    registered tables every ``delta.tpu.autopilot.intervalMs`` on a
    ``delta-autopilot`` thread. Opt-in twice over — construction requires
    ``delta.tpu.autopilot.enabled=true``, and execution additionally
    requires ``delta.tpu.autopilot.dryRun=false``."""

    def __init__(self, tables: Optional[List[str]] = None):
        if not enabled():
            from delta_tpu.utils import errors

            raise errors.DeltaIllegalStateError(
                "the autopilot is opt-in: set delta.tpu.autopilot.enabled"
                "=true before starting it")
        self._tables: List[str] = list(tables or [])
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def register(self, path: str) -> None:
        with self._lock:
            if path not in self._tables:
                self._tables.append(path)

    def unregister(self, path: str) -> None:
        with self._lock:
            if path in self._tables:
                self._tables.remove(path)

    @property
    def tables(self) -> List[str]:
        with self._lock:
            return list(self._tables)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "Autopilot":
        global _DAEMON
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="delta-autopilot")
        self._thread.start()
        with _STATE_LOCK:
            _DAEMON = self
        return self

    def stop(self, timeout: float = 5.0) -> None:
        global _DAEMON
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        with _STATE_LOCK:
            if _DAEMON is self:
                _DAEMON = None

    def tick(self) -> None:
        """Wake the daemon for an immediate pass (tests, operators)."""
        self._wake.set()

    def _loop(self) -> None:
        while not self._stop.is_set():
            for path in self.tables:
                if self._stop.is_set():
                    break
                try:
                    run_once(path)
                except Exception:  # noqa: BLE001 — one table's failure must
                    # not starve the others; the ledger has the detail
                    telemetry.logger.warning(
                        "autopilot pass failed for %s", path, exc_info=True)
                # non-Exception BaseExceptions propagate and kill the
                # daemon thread — a simulated process death must not leave
                # a "dead" process's scheduler running (same narrowing as
                # log/checkpointer)
            interval = conf.get_int("delta.tpu.autopilot.intervalMs", 60_000)
            self._wake.wait(timeout=interval / 1000.0)
            self._wake.clear()


# ---------------------------------------------------------------------------
# Introspection (the /autopilot HTTP route serves this)
# ---------------------------------------------------------------------------


def last_runs() -> Dict[str, Dict[str, Any]]:
    with _STATE_LOCK:
        return {k: dict(v) for k, v in _LAST_RUNS.items()}


def status() -> Dict[str, Any]:
    """Process-wide autopilot status: conf posture, daemon state, and the
    last run report per table."""
    with _STATE_LOCK:
        daemon = _DAEMON
    return {
        "enabled": enabled(),
        "dryRun": dry_run(),
        "daemonRunning": daemon.running if daemon is not None else False,
        "tables": daemon.tables if daemon is not None else [],
        "intervalMs": conf.get_int("delta.tpu.autopilot.intervalMs", 60_000),
        "guardrails": {
            "maxBytesPerRun": conf.get_int("delta.tpu.autopilot.maxBytesPerRun", 2 << 30),
            "budgetMs": conf.get_int("delta.tpu.autopilot.budgetMs", 300_000),
            "maxActionsPerRun": conf.get_int("delta.tpu.autopilot.maxActionsPerRun", 4),
            "cooldownMs": conf.get_int("delta.tpu.autopilot.cooldownMs", 6 * 3_600_000),
            "contentionBackoffMs": conf.get_int("delta.tpu.autopilot.contentionBackoffMs", 300_000),
            "quietWindowMs": conf.get_int("delta.tpu.autopilot.quietWindowMs", 60_000),
            "quietMaxCommits": conf.get_int("delta.tpu.autopilot.quietMaxCommits", 0),
            "maxCommitAttempts": conf.get_int("delta.tpu.autopilot.maxCommitAttempts", 3),
        },
        "lastRuns": last_runs(),
    }


def reset() -> None:
    """Drop per-process autopilot state (tests). The
    on-disk action ledger is untouched — it lives in the journal."""
    global _DAEMON
    with _STATE_LOCK:
        daemon = _DAEMON
    if daemon is not None:
        daemon.stop(timeout=1.0)
    with _STATE_LOCK:
        _LAST_RUNS.clear()
        _DAEMON = None
