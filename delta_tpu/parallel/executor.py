"""Sharded work-item executor — LPT assignment, work stealing, supervision.

The DCN partitioner (`parallel/distributed`) decides which *host* owns each
work item; this module is the per-host engine that actually runs a host's
items: scan decode groups, OPTIMIZE bin-pack rewrites, fused-MERGE probe
batches, checkpoint part writes. The reference delegates the same role to
Spark's task scheduler (TaskSchedulerImpl: per-executor queues + speculative
execution); ours is deliberately smaller:

* **deterministic LPT seed** — items are pre-assigned to worker deques by
  size-weighted LPT (`distributed.lpt_assign`), so the steady state does no
  coordination at all;
* **work stealing** — a worker whose deque drains steals the *tail* item of
  the worker with the most remaining bytes (the zipf hot-shard case: one
  deque inherits the head of the distribution and everyone else finishes
  early). Stealing is conf-gated (`delta.tpu.distributed.workStealing.enabled`)
  and counted (`dist.steals`);
* **measured, not asserted** — every item's wall clock is recorded
  (`dist.item.duration_ms`), and the report carries per-worker totals +
  the max/mean byte skew so a caller (`chip_smoke.py`, tests) can print
  per-shard timings instead of an "ok" string.

Supervision (fault tolerance — the MapReduce task re-execution model the
column-storage paper assumes of its runtime):

* **per-item retry** — a *transient* ``Exception`` from an item (classified
  by `utils/retries.is_transient` — the convention that transient errors
  fire before an operation's side effects land) retries in place under the
  shared :class:`~delta_tpu.utils.retries.RetryPolicy` read from the
  ``delta.tpu.distributed.retry.*`` confs: bounded attempts AND a total
  deadline. Permanent errors and ``BaseException``s (`SimulatedCrash` is a
  process death) are never retried.
* **poison quarantine** — ``on_failure="quarantine"`` turns an exhausted or
  permanent item failure into a :class:`QuarantinedItem` on the report
  (``dist.items.quarantined``; the failing attempt raised through its item
  span, so the flight recorder holds an incident with the trace id) and the
  job completes with a structured partial result — ``results[j] is None``
  for quarantined ``j`` and the caller decides (OPTIMIZE skips the group,
  MERGE's probe keeps the file). The default ``"raise"`` aborts like the
  pre-supervision executor — but always with finalized per-worker stats
  (the raised error carries the partial report as ``exc.shard_report``).
* **heartbeats + speculation** — each worker stamps a monotonic heartbeat
  when it starts an item; a ``delta-dist-supervisor`` thread marks items
  whose heartbeat age exceeds their *priced* timeout — ``max(``
  ``delta.tpu.distributed.itemTimeoutMs``, measured ms/byte × the item's
  LPT byte estimate × ``speculation.slackFactor)``, not a flat constant —
  and re-dispatches them to an idle worker (``dist.items.speculated``).
  First completion wins; the loser's result is discarded idempotently
  (``dist.speculation.wins`` counts rescues, and the loser's item span
  carries ``discarded=true`` so `analyze_trace` attributes the race).
* **degradation** — if the pool dies under it (worker-spawn faults, pool
  construction failure), the caller's thread finishes every unresolved item
  inline (``dist.degraded.pool``): a sharded job degrades to the sequential
  loop instead of stranding work.

Fault points (`storage/faults.fire`): ``dist.itemExec`` fires per attempt
inside the item span (so injected faults exercise retry/quarantine/crash
paths), ``dist.workerSpawn`` per pool worker at startup (a transient spawn
failure abandons the worker and the job survives on the rest),
``dist.heartbeat`` around heartbeat stamps and supervisor sweeps (a lost
stamp may cost a spurious speculation, never correctness).

Threads come from one pool named ``delta-dist-exec`` plus the
``delta-dist-supervisor`` watchdog (pool-naming lint). Results preserve
item order.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from delta_tpu.parallel.distributed import bytes_skew, lpt_assign, lpt_loads

__all__ = ["ShardReport", "WorkerStats", "QuarantinedItem", "run_sharded",
           "default_workers"]


@dataclass
class WorkerStats:
    items: int = 0
    bytes: int = 0
    busy_s: float = 0.0  # includes FAILED attempts' elapsed time
    stolen: int = 0  # items this worker STOLE from another deque


@dataclass
class QuarantinedItem:
    """One poison item the job completed *around*: its index, the final
    error, how many attempts the retry policy spent, and the trace id the
    flight-recorder incident (when configured) filed under."""

    index: int
    error: str
    attempts: int
    trace_id: str = ""

    def to_dict(self) -> Dict[str, Any]:
        return {"index": self.index, "error": self.error,
                "attempts": self.attempts, "traceId": self.trace_id}


@dataclass
class ShardReport:
    """What a sharded job actually did: per-worker timings, steals, skew."""

    results: List[Any]
    wall_s: float
    workers: int
    steals: int
    skew: float  # max/mean per-worker bytes of the LPT seed assignment
    per_worker: Dict[int, WorkerStats] = field(default_factory=dict)
    retried: int = 0      # transient item attempts that were retried
    speculated: int = 0   # stuck items the supervisor re-dispatched
    rescued: int = 0      # speculative attempts that won the race
    degraded_inline: int = 0  # items finished inline after the pool died
    quarantined: List[QuarantinedItem] = field(default_factory=list)

    def quarantined_indices(self) -> set:
        return {q.index for q in self.quarantined}

    def timings(self) -> List[Dict[str, Any]]:
        """Per-shard timing rows for artifacts (sorted by worker id)."""
        return [
            {
                "worker": w,
                "items": s.items,
                "bytes": s.bytes,
                "busy_s": round(s.busy_s, 6),
                "stolen": s.stolen,
            }
            for w, s in sorted(self.per_worker.items())
        ]


def default_workers() -> int:
    """Worker count for sharded jobs: ``delta.tpu.distributed.workers``
    when set, else min(8, cpu count) — sized like the 8-way state mesh."""
    import os

    from delta_tpu.utils.config import conf

    w = conf.get("delta.tpu.distributed.workers", None)
    if w is not None:
        return max(int(w), 1)
    return max(min(8, os.cpu_count() or 1), 1)


def _retry_policy():
    """The shared item-retry policy from the distributed confs: bounded
    attempts AND a total per-item deadline (`utils/retries.RetryPolicy`)."""
    from delta_tpu.utils.config import conf
    from delta_tpu.utils.retries import RetryPolicy

    return RetryPolicy(
        max_attempts=max(conf.get_int(
            "delta.tpu.distributed.retry.maxAttempts", 3), 1),
        base_delay_s=conf.get_int(
            "delta.tpu.distributed.retry.baseDelayMs", 10) / 1000.0,
        max_delay_s=conf.get_int(
            "delta.tpu.distributed.retry.maxDelayMs", 200) / 1000.0,
        deadline_s=conf.get_int(
            "delta.tpu.distributed.retry.deadlineMs", 10_000) / 1000.0,
    )


class _JobState:
    """Shared mutable state of one pooled job: deques, claims, the
    speculation queue, and the first fatal error. Every mutation happens
    under ``cond``'s lock; completion/quarantine/speculation notify it so
    idle workers wake instead of polling."""

    def __init__(self, n: int, weights: Sequence[int],
                 deques: List[List[int]], stealing: bool,
                 per_worker: Dict[int, WorkerStats]):
        self.n = n
        self.weights = weights
        self.deques = deques
        self.remaining = [sum(weights[j] for j in b) for b in deques]
        self.stealing = stealing
        self.per_worker = per_worker
        self.cond = threading.Condition()
        self.results: List[Any] = [None] * n
        self.done = [False] * n
        self.quarantined: Dict[int, QuarantinedItem] = {}
        self.resolved = 0  # done + quarantined
        self.spec_queue: List[int] = []
        self.spec_marked: set = set()
        self.running: Dict[int, Tuple[int, float]] = {}  # worker -> (item, t0)
        self.stop = False
        self.fatal: List[BaseException] = []
        self.steals = 0
        self.retried = 0
        self.speculated = 0
        self.rescued = 0

    # -- scheduling -------------------------------------------------------

    def take(self, w: int):
        """Next item for worker ``w``: own deque head, else a speculative
        re-dispatch, else the tail of the most-loaded victim. Blocks while
        the job is unfinished but nothing is claimable (a sibling may still
        fail or get speculated); returns None when the job is over."""
        from delta_tpu.utils import telemetry

        with self.cond:
            while True:
                if self.stop or self.resolved >= self.n:
                    return None
                if self.deques[w]:
                    j = self.deques[w].pop(0)
                    self.remaining[w] -= self.weights[j]
                    return j, False, False
                while self.spec_queue:
                    j = self.spec_queue.pop(0)
                    if not self.done[j] and j not in self.quarantined:
                        return j, False, True
                if self.stealing:
                    # steal the tail of the most-loaded deque: the tail
                    # holds that worker's smallest seeded items, so the
                    # victim keeps the head it is already streaming through
                    victim = max(
                        (v for v in range(len(self.deques)) if self.deques[v]),
                        key=lambda v: (self.remaining[v], -v),
                        default=None,
                    )
                    if victim is not None:
                        j = self.deques[victim].pop()
                        self.remaining[victim] -= self.weights[j]
                        self.steals += 1
                        self.per_worker[w].stolen += 1
                        telemetry.bump_counter("dist.steals")
                        return j, True, False
                # job unfinished but nothing claimable: wait for a
                # completion, a speculation mark, or the stop flag (timeout
                # is belt-and-braces against a missed notify)
                self.cond.wait(0.05)

    def abandon_worker(self, w: int) -> None:
        """Worker ``w`` died at spawn: its seeded deque re-dispatches
        through the speculation queue so siblings (or the inline fallback)
        finish the items even with stealing disabled."""
        with self.cond:
            if self.deques[w]:
                self.spec_queue.extend(self.deques[w])
                self.deques[w] = []
                self.remaining[w] = 0
            self.running.pop(w, None)
            self.cond.notify_all()

    # -- outcomes ---------------------------------------------------------

    def commit(self, w: Optional[int], j: int, value: Any,
               speculative: bool) -> bool:
        """First-completion-wins: land ``value`` for item ``j`` unless a
        rival attempt already did. Returns whether this attempt won."""
        from delta_tpu.utils import telemetry

        with self.cond:
            if self.done[j] or j in self.quarantined:
                return False  # the loser's result is discarded idempotently
            self.done[j] = True
            self.results[j] = value
            self.resolved += 1
            if speculative:
                self.rescued += 1
                telemetry.bump_counter("dist.speculation.wins")
            self.cond.notify_all()
            return True

    def quarantine(self, j: int, exc: BaseException, attempts: int) -> None:
        from delta_tpu.utils import telemetry

        with self.cond:
            if self.done[j] or j in self.quarantined:
                return
            self.quarantined[j] = QuarantinedItem(
                index=j, error=f"{type(exc).__name__}: {exc}",
                attempts=attempts,
                trace_id=telemetry.current_trace_id() or "")
            self.resolved += 1
            telemetry.bump_counter("dist.items.quarantined")
            self.cond.notify_all()

    def record_fatal(self, exc: BaseException) -> None:
        with self.cond:
            if not self.fatal:
                self.fatal.append(exc)
            self.stop = True
            self.cond.notify_all()

    def unresolved(self) -> List[int]:
        with self.cond:
            return [j for j in range(self.n)
                    if not self.done[j] and j not in self.quarantined]


def run_sharded(
    items: Sequence,
    fn: Callable[[Any], Any],
    *,
    sizes: Optional[Sequence[int]] = None,
    workers: Optional[int] = None,
    label: str = "job",
    on_failure: str = "raise",
) -> ShardReport:
    """Run ``fn(item)`` for every item over a worker pool with LPT seeding,
    work stealing, and supervision; returns an order-preserving
    :class:`ShardReport`.

    ``sizes`` are per-item byte weights (defaults to uniform). ``workers``
    defaults to :func:`default_workers`; 1 worker runs inline with no pool,
    so a single-shard run measures the job, not the
    machinery (retry + quarantine still apply inline).

    ``on_failure`` decides what an item that exhausts its transient
    retries (or fails permanently) does to the job: ``"raise"`` aborts —
    after every worker drained and finalized its stats, with the partial
    report attached to the raised error as ``shard_report`` — while
    ``"quarantine"`` records the poison item on ``report.quarantined``
    (its ``results`` slot stays None) and the job completes. A
    ``BaseException`` that is not an ``Exception`` (e.g.
    :class:`~delta_tpu.storage.faults.SimulatedCrash` — a process death)
    always aborts: no recovery path may swallow a crash.

    The whole job runs inside a ``delta.dist.job`` span; each pool worker
    opens a ``delta.dist.worker`` span (adopting the job's span context —
    pool threads do not inherit contextvars) and each item attempt a
    ``delta.dist.item`` span carrying its index/bytes/stolen/attempt/
    speculative flags, so a distributed trace can attribute the makespan —
    and every retry, speculation race, and quarantine — to a specific
    shard and item (`obs/trace_store.analyze_trace`).
    """
    from delta_tpu.storage import faults
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf
    from delta_tpu.utils.retries import is_transient

    if on_failure not in ("raise", "quarantine"):
        raise ValueError(f"on_failure must be 'raise' or 'quarantine', "
                         f"got {on_failure!r}")

    n = len(items)
    if workers is None:
        workers = default_workers()
    workers = max(1, min(int(workers), max(n, 1)))
    weights = [int(s or 0) for s in sizes] if sizes is not None else [1] * n
    policy = _retry_policy()
    # pin the fault plan ONCE at job start: a lazily spawned pool thread can
    # dequeue its worker task after the job already resolved (the main thread
    # returns at resolved == n without awaiting never-started tasks), and a
    # live conf read from that stale task would consume script entries from
    # whatever plan the NEXT job installed — cross-job fault leakage
    fault_plan = faults.plan_from_conf()
    telemetry.bump_counter("dist.jobs")
    telemetry.bump_counter("dist.items", n)

    with telemetry.record_operation(
        "delta.dist.job", {"items": n, "workers": workers}, job=label
    ) as job_ev:
        t0 = time.perf_counter()

        state = _JobState(
            n, weights,
            deques=[[] for _ in range(workers)],
            stealing=conf.get_bool(
                "delta.tpu.distributed.workStealing.enabled", True),
            per_worker={w: WorkerStats() for w in range(workers)})

        def _attempt_item(j: int, stolen: bool, speculative: bool,
                          stats: WorkerStats) -> Tuple[str, Any, int]:
            """One item to a terminal outcome: retry transient Exceptions
            under ``policy``, then return ``("ok", won, attempts)`` or
            ``("fail", exc, attempts)``. Fatal BaseExceptions propagate.
            Elapsed time lands on ``stats.busy_s`` even for failed
            attempts, so an abort never leaves torn timings."""
            attempt = 0
            started = time.monotonic()
            while True:
                it0 = time.perf_counter()
                try:
                    try:
                        with telemetry.record_operation(
                            "delta.dist.item",
                            {"index": j, "bytes": weights[j],
                             "stolen": stolen, "attempt": attempt,
                             "speculative": speculative},
                            job=label,
                        ) as item_ev:
                            faults.fire("dist.itemExec", f"{label}#{j}",
                                        plan=fault_plan)
                            value = fn(items[j])
                            won = state.commit(None, j, value, speculative)
                            if speculative or not won:
                                item_ev.data["discarded"] = not won
                    finally:
                        d = time.perf_counter() - it0
                        stats.busy_s += d
                except Exception as exc:  # noqa: BLE001 — classified below;
                    # SimulatedCrash is a BaseException and falls through
                    if not is_transient(exc) \
                            or policy.give_up(attempt, started):
                        return "fail", exc, attempt + 1
                    with state.cond:
                        state.retried += 1
                    telemetry.bump_counter("dist.items.retried")
                    time.sleep(policy.delay(attempt))
                    attempt += 1
                    continue
                if won:
                    stats.items += 1
                    stats.bytes += weights[j]
                    telemetry.observe("dist.item.duration_ms", d * 1e3,
                                      job=label)
                return "ok", won, attempt + 1

        def _settle_failure(j: int, exc: BaseException,
                            attempts: int) -> None:
            """Terminal item failure: quarantine or abort per the policy."""
            if on_failure == "quarantine":
                state.quarantine(j, exc, attempts)
            else:
                raise exc

        # ---- inline path: 1 worker or 1 item — no pool, no supervisor ----
        if workers <= 1 or n <= 1:
            job_ev.data.update(skew=1.0, lptBytes=[sum(weights)])
            stats = state.per_worker.setdefault(0, WorkerStats())
            for j in range(n):
                status, out, attempts = _attempt_item(
                    j, stolen=False, speculative=False, stats=stats)
                if status == "fail":
                    _settle_failure(j, out, attempts)
            report = ShardReport(
                results=state.results,
                wall_s=time.perf_counter() - t0,
                workers=1,
                steals=0,
                skew=1.0,
                per_worker=state.per_worker,
                retried=state.retried,
                quarantined=sorted(state.quarantined.values(),
                                   key=lambda q: q.index),
            )
            if report.quarantined:
                job_ev.data.update(quarantined=len(report.quarantined))
            return report

        # ---- pool path ---------------------------------------------------
        seed = lpt_assign(weights, workers)
        skew = bytes_skew(weights, seed)
        for w, bucket in enumerate(seed):
            state.deques[w] = list(bucket)
        state.remaining = [sum(weights[j] for j in b) for b in state.deques]
        # the per-worker LPT byte shares: what each shard SHOULD cost if
        # bytes predicted time perfectly — analyze_trace diffs the worker
        # spans' measured busy time against exactly these
        job_ev.data.update(
            skew=round(skew, 4), lptBytes=lpt_loads(weights, seed))
        carrier = telemetry.span_context()

        def _stamp_heartbeat(w: int, j: int) -> None:
            # dist.heartbeat fault point: a lost stamp leaves the previous
            # (already-done) entry in place — the supervisor skips done
            # items, so the worst outcome is one spurious speculation
            try:
                faults.fire("dist.heartbeat", f"{label}:{w}",
                            plan=fault_plan)
            except Exception:  # noqa: BLE001 — heartbeat loss is benign
                return
            with state.cond:
                state.running[w] = (j, time.monotonic())

        def _drive(w: int) -> None:
            stats = state.per_worker[w]
            while True:
                taken = state.take(w)
                if taken is None:
                    return
                j, stolen, speculative = taken
                _stamp_heartbeat(w, j)
                try:
                    status, out, attempts = _attempt_item(
                        j, stolen=stolen, speculative=speculative,
                        stats=stats)
                finally:
                    with state.cond:
                        state.running.pop(w, None)
                if status == "fail":
                    _settle_failure(j, out, attempts)

        def _worker(w: int) -> None:
            with telemetry.adopt_span_context(carrier), \
                    telemetry.record_operation(
                        "delta.dist.worker", job=label, worker=str(w)):
                try:
                    faults.fire("dist.workerSpawn", f"{label}:{w}",
                                plan=fault_plan)
                except Exception:  # noqa: BLE001 — transient spawn failure:
                    # this worker is lost, its deque re-dispatches and the
                    # job survives on the remaining workers (or inline)
                    state.abandon_worker(w)
                    return
                try:
                    _drive(w)
                except BaseException as exc:  # propagate the FIRST failure
                    # (re-raised on the caller thread below — including
                    # SimulatedCrash, which must pierce like process death)
                    state.record_fatal(exc)
                    return

        # supervisor: watch heartbeats, speculatively re-dispatch stragglers
        spec_enabled = conf.get_bool(
            "delta.tpu.distributed.speculation.enabled", True)
        floor_ms = conf.get_int("delta.tpu.distributed.itemTimeoutMs",
                                120_000)
        slack = float(conf.get("delta.tpu.distributed.speculation.slackFactor",
                               4.0) or 4.0)
        interval_s = max(conf.get_int(
            "delta.tpu.distributed.supervisor.intervalMs", 25), 1) / 1000.0
        done_evt = threading.Event()

        def _supervise() -> None:
            while not done_evt.wait(interval_s):
                try:
                    faults.fire("dist.heartbeat", f"{label}:supervisor",
                                plan=fault_plan)
                except Exception:  # noqa: BLE001 — a flapping probe skips
                    continue       # one sweep, never kills supervision
                now = time.monotonic()
                # measured throughput prices each item's timeout: bytes
                # predict time, the slack factor absorbs honest variance
                done_bytes = sum(s.bytes for s in state.per_worker.values())
                busy_s = sum(s.busy_s for s in state.per_worker.values())
                ms_per_byte = (busy_s * 1e3 / done_bytes) if done_bytes > 0 \
                    else None
                with state.cond:
                    for w, (j, hb) in list(state.running.items()):
                        if state.done[j] or j in state.quarantined \
                                or j in state.spec_marked:
                            continue
                        timeout_ms = float(floor_ms)
                        if ms_per_byte is not None:
                            timeout_ms = max(
                                timeout_ms,
                                slack * weights[j] * ms_per_byte)
                        if (now - hb) * 1e3 > timeout_ms:
                            state.spec_marked.add(j)
                            state.spec_queue.append(j)
                            state.speculated += 1
                            telemetry.bump_counter("dist.items.speculated")
                            state.cond.notify_all()

        supervisor = None
        if spec_enabled and floor_ms > 0:
            supervisor = threading.Thread(
                target=_supervise, name="delta-dist-supervisor", daemon=True)
            supervisor.start()

        degraded_inline = 0
        try:
            try:
                pool = ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="delta-dist-exec")
            except Exception:  # noqa: BLE001 — pool machinery failure (not
                # an item failure: those land in state.fatal): degrade below
                pool = None
            if pool is not None:
                try:
                    futures = [pool.submit(_worker, w)
                               for w in range(workers)]
                    # wait for RESOLUTION, not thread exit: once every item
                    # is done/quarantined the job returns — a speculation
                    # race's loser thread may still be running its doomed
                    # attempt, and waiting for it would forfeit exactly the
                    # wall clock the rescue won (its late result is
                    # discarded idempotently by first-completion-wins)
                    with state.cond:
                        while state.resolved < n and not state.stop:
                            if all(f.done() for f in futures):
                                break  # every worker died: degrade below
                            state.cond.wait(0.05)
                    if state.fatal:
                        # abort path: drain in-flight siblings so every
                        # worker's stats are finalized before the re-raise
                        for f in futures:
                            f.result()
                    else:
                        # normal completion: join every worker that is NOT
                        # mid-item — post-resolution take() returns None, so
                        # they exit promptly. This makes worker spans and
                        # stats deterministic for observers and leaves no
                        # stale worker task behind the return. A worker
                        # still inside its fn is a speculation race's
                        # (possibly wedged) loser: waiting for it would
                        # forfeit exactly the wall clock the rescue won.
                        with state.cond:
                            busy = set(state.running)
                        for w, f in enumerate(futures):
                            if w in busy:
                                continue
                            try:
                                f.result(timeout=1.0)
                            except Exception:  # noqa: BLE001 — join is
                                pass  # best-effort; never fail a done job
                finally:
                    pool.shutdown(wait=False)
            # degradation rung: the pool died under the job (every worker
            # lost at spawn, or the executor itself failed) — finish the
            # unresolved items inline on the caller's thread
            if not state.fatal and state.resolved < n:
                telemetry.bump_counter("dist.degraded.pool")
                stats = state.per_worker[0]
                for j in state.unresolved():
                    degraded_inline += 1
                    status, out, attempts = _attempt_item(
                        j, stolen=False, speculative=False, stats=stats)
                    if status == "fail":
                        _settle_failure(j, out, attempts)
        finally:
            done_evt.set()
            if supervisor is not None:
                supervisor.join(timeout=5)

        report = ShardReport(
            results=state.results,
            wall_s=time.perf_counter() - t0,
            workers=workers,
            steals=state.steals,
            skew=skew,
            per_worker=state.per_worker,
            retried=state.retried,
            speculated=state.speculated,
            rescued=state.rescued,
            degraded_inline=degraded_inline,
            quarantined=sorted(state.quarantined.values(),
                               key=lambda q: q.index),
        )
        job_ev.data.update(
            steals=state.steals, wallMs=int(report.wall_s * 1e3),
            retried=state.retried, speculated=state.speculated,
            rescued=state.rescued, quarantined=len(report.quarantined))
        if state.fatal:
            # abort — but never with torn evidence: every worker drained
            # above, failed-attempt time is on busy_s, and the caller gets
            # the finalized partial report on the exception itself
            exc = state.fatal[0]
            try:
                exc.shard_report = report  # type: ignore[attr-defined]
            except Exception:  # noqa: BLE001 — slotted exceptions: raise bare
                pass
            raise exc
        return report
