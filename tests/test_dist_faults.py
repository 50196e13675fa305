"""Fault-tolerant distributed execution (ISSUE 20).

Unit and regression coverage for the supervision layer around
``parallel/executor.run_sharded`` and the multihost lease protocol
(``parallel/leases``): per-item retry + poison quarantine, heartbeat-driven
speculative re-dispatch (first completion wins), the degradation ladder,
the four ``dist.*`` fault points, and coordinator-side orphaned-slice
recovery / txnId reconciliation. The end-to-end subprocess version of the
crash-recovery scenario lives in ``test_multihost.py``; the seeded
whole-workload version in ``test_torture.py``.
"""
import json
import os
import threading
import time

import pyarrow as pa
import pytest

from delta_tpu.parallel import leases
from delta_tpu.parallel.executor import run_sharded
from delta_tpu.storage.faults import FaultPlan, SimulatedCrash
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf
from delta_tpu.utils.retries import TransientIOError


@pytest.fixture(autouse=True)
def _fresh_metrics():
    telemetry.reset_all()
    yield
    telemetry.reset_all()


def _fast_retries(**over):
    kw = {
        "delta__tpu__distributed__retry__baseDelayMs": 1,
        "delta__tpu__distributed__retry__maxDelayMs": 5,
        "delta__tpu__distributed__retry__deadlineMs": 5_000,
    }
    kw.update(over)
    return conf.set_temporarily(**kw)


# -- retry + quarantine ------------------------------------------------------


def test_transient_failures_are_retried_to_success():
    calls = {}

    def fn(x):
        calls[x] = calls.get(x, 0) + 1
        if x == 2 and calls[x] == 1:
            raise TransientIOError("flaky once")
        return x * 10

    with _fast_retries():
        report = run_sharded([0, 1, 2, 3], fn, workers=2, label="t")
    assert report.results == [0, 10, 20, 30]
    assert report.retried == 1
    assert calls[2] == 2
    assert telemetry.counters("dist")["dist.items.retried"] == 1
    assert not report.quarantined


def test_exhausted_retries_quarantine_and_job_completes():
    def fn(x):
        if x == 1:
            raise TransientIOError("always down")
        return x

    with _fast_retries(delta__tpu__distributed__retry__maxAttempts=2):
        report = run_sharded([0, 1, 2], fn, workers=2, label="t",
                             on_failure="quarantine")
    assert report.results[0] == 0 and report.results[2] == 2
    assert report.results[1] is None
    [q] = report.quarantined
    assert q.index == 1 and q.attempts == 2
    assert "always down" in q.error
    assert report.quarantined_indices() == {1}
    assert telemetry.counters("dist")["dist.items.quarantined"] == 1


def test_permanent_error_never_retried():
    calls = {"n": 0}

    def fn(x):
        if x == 0:
            calls["n"] += 1
            raise ValueError("poison")
        return x

    with _fast_retries():
        report = run_sharded([0, 1], fn, workers=2, label="t",
                             on_failure="quarantine")
    assert calls["n"] == 1  # non-transient: a single attempt
    [q] = report.quarantined
    assert q.index == 0 and q.attempts == 1
    assert report.retried == 0


def test_on_failure_raise_aborts_with_partial_report():
    def fn(x):
        if x == 1:
            raise ValueError("poison")
        time.sleep(0.01)
        return x

    with _fast_retries():
        with pytest.raises(ValueError, match="poison") as ei:
            run_sharded([0, 1, 2, 3], fn, workers=2, label="t")
    report = ei.value.shard_report
    assert report is not None
    assert report.workers == 2


def test_invalid_on_failure_rejected():
    with pytest.raises(ValueError, match="on_failure"):
        run_sharded([1], lambda x: x, on_failure="retry")


def test_inline_path_retries_and_quarantines():
    """1 worker / 1 item runs with no pool — the retry and quarantine
    policies must apply identically."""
    calls = {"n": 0}

    def fn(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise TransientIOError("once")
        raise ValueError("then poison")

    with _fast_retries():
        report = run_sharded(["only"], fn, workers=1, label="t",
                             on_failure="quarantine")
    assert report.retried == 1
    assert report.quarantined[0].attempts == 2


# -- crash semantics (satellite 1) -------------------------------------------


def test_simulated_crash_pierces_quarantine():
    """A BaseException that is not an Exception is process death: never
    retried, never quarantined, always fatal."""
    def fn(x):
        if x == 1:
            raise SimulatedCrash("dist.itemExec")
        return x

    with _fast_retries():
        with pytest.raises(SimulatedCrash):
            run_sharded([0, 1, 2], fn, workers=2, label="t",
                        on_failure="quarantine")
    assert "dist.items.quarantined" not in telemetry.counters("dist")


def test_abort_drains_sibling_workers_before_reraise():
    """Regression (ISSUE 20 satellite): a mid-item crash re-raises only
    after every in-flight sibling drained, so the attached report carries
    every worker's finalized stats — including the sibling that was still
    busy when the crash hit."""
    sibling_done = threading.Event()

    def fn(x):
        if x == "slow":
            time.sleep(0.25)
            sibling_done.set()
            return "slow-done"
        time.sleep(0.02)
        raise SimulatedCrash("dist.itemExec")

    with _fast_retries():
        with pytest.raises(SimulatedCrash) as ei:
            run_sharded(["slow", "crash"], fn,
                        sizes=[100, 1], workers=2, label="t")
    assert sibling_done.is_set(), "sibling must have finished before re-raise"
    report = ei.value.shard_report
    busy = sum(s.busy_s for s in report.per_worker.values())
    assert busy >= 0.25, f"sibling's elapsed time missing from stats: {busy}"


# -- speculation -------------------------------------------------------------


def test_straggler_speculatively_redispatched_first_completion_wins():
    """A wedged first attempt is re-dispatched once its heartbeat age
    clears the priced timeout; the fresh attempt's completion resolves the
    item and the job does NOT wait for the wedged thread."""
    attempts = {}
    lock = threading.Lock()

    def fn(x):
        with lock:
            attempts[x] = attempts.get(x, 0) + 1
            mine = attempts[x]
        if x == 0 and mine == 1:
            time.sleep(2.0)  # the straggler: wedged well past the timeout
            return "late"
        return f"ok-{x}"

    with _fast_retries(
        delta__tpu__distributed__itemTimeoutMs=60,
        delta__tpu__distributed__supervisor__intervalMs=5,
        delta__tpu__distributed__speculation__slackFactor=1.0,
    ):
        t0 = time.perf_counter()
        report = run_sharded([0, 1, 2, 3], fn, workers=4, label="t")
        wall = time.perf_counter() - t0
    assert report.results[0] == "ok-0"  # the rescue's result, not "late"
    assert report.speculated >= 1
    assert report.rescued >= 1
    assert attempts[0] == 2
    assert wall < 1.5, f"job must not wait for the wedged attempt ({wall:.2f}s)"
    c = telemetry.counters("dist")
    assert c["dist.items.speculated"] >= 1
    assert c["dist.speculation.wins"] >= 1


def test_no_speculation_when_disabled():
    def fn(x):
        if x == 0:
            time.sleep(0.2)
        return x

    with _fast_retries(
        delta__tpu__distributed__itemTimeoutMs=20,
        delta__tpu__distributed__supervisor__intervalMs=5,
        delta__tpu__distributed__speculation__enabled=False,
    ):
        report = run_sharded([0, 1, 2], fn, workers=3, label="t")
    assert report.speculated == 0
    assert report.results == [0, 1, 2]


@pytest.mark.parametrize("speculation", [False, True],
                         ids=["waits-it-out", "rescues-it"])
def test_scripted_slow_fault_is_the_straggler(speculation):
    """A `slow` fault scripted at `dist.itemExec` stalls whichever attempt
    fires first; the script is consumed once, so the re-dispatched attempt
    runs clean. With or without speculation the results are the same, in
    order; only with it is the stalled item rescued."""
    plan = FaultPlan(script=[("dist.itemExec", "slow")], slow_ms=400)
    with _fast_retries(
        delta__tpu__faults__plan=plan,
        delta__tpu__distributed__itemTimeoutMs=60,
        delta__tpu__distributed__supervisor__intervalMs=5,
        delta__tpu__distributed__speculation__slackFactor=1.0,
        delta__tpu__distributed__speculation__enabled=speculation,
    ):
        report = run_sharded(list(range(8)), lambda i: i * 10, workers=4,
                             label="t")
    assert not plan.script, "the scripted straggler never fired"
    assert report.results == [i * 10 for i in range(8)]
    if speculation:
        assert report.speculated >= 1 and report.rescued >= 1
    else:
        assert report.speculated == 0 and report.rescued == 0


# -- fault points + degradation ladder ---------------------------------------


def test_item_exec_fault_point_drives_retry():
    plan = FaultPlan(script=[("dist.itemExec", "transient")])
    with _fast_retries(delta__tpu__faults__plan=plan):
        report = run_sharded([0, 1, 2, 3], lambda x: x, workers=2, label="t")
    assert not plan.script
    assert report.results == [0, 1, 2, 3]
    assert report.retried == 1


def test_worker_spawn_fault_survived_by_siblings():
    plan = FaultPlan(script=[("dist.workerSpawn", "transient")])
    with _fast_retries(delta__tpu__faults__plan=plan):
        report = run_sharded(list(range(8)), lambda x: x, workers=4,
                             label="t")
    assert not plan.script
    assert report.results == list(range(8))


def test_all_workers_lost_degrades_to_inline():
    plan = FaultPlan(
        script=[("dist.workerSpawn", "transient")] * 4)
    with _fast_retries(delta__tpu__faults__plan=plan):
        report = run_sharded(list(range(6)), lambda x: x, workers=4,
                             label="t")
    assert not plan.script
    assert report.results == list(range(6))
    assert report.degraded_inline == 6
    assert telemetry.counters("dist")["dist.degraded.pool"] == 1


def test_stale_worker_task_cannot_consume_next_jobs_fault_plan():
    # a lazily spawned pool thread can dequeue a worker task AFTER its job
    # already resolved (the main thread returns at resolved == n without
    # awaiting never-started tasks); run_sharded pins the fault plan at job
    # start, so a stale task's `dist.workerSpawn` fire draws from ITS job's
    # plan and can never consume script entries from the plan a LATER job
    # installed (cross-job fault leakage)
    from concurrent.futures import Future

    import delta_tpu.parallel.executor as ex

    captured = []

    class HoldLastPool(ex.ThreadPoolExecutor):
        def submit(self, fn, *args, **kwargs):
            if args and args[0] == 3:
                # withhold the last worker task: its items are rescued by
                # stealing, and the task body runs only when we say so
                captured.append(lambda: fn(*args, **kwargs))
                f = Future()
                f.set_result(None)
                return f
            return super().submit(fn, *args, **kwargs)

    orig_pool = ex.ThreadPoolExecutor
    ex.ThreadPoolExecutor = HoldLastPool
    try:
        with _fast_retries():
            report = run_sharded(list(range(6)), lambda x: x, workers=4,
                                 label="t")
    finally:
        ex.ThreadPoolExecutor = orig_pool
    assert report.results == list(range(6))
    assert len(captured) == 1

    plan = FaultPlan(script=[("dist.workerSpawn", "transient")] * 4)
    with _fast_retries(delta__tpu__faults__plan=plan):
        captured[0]()  # the stale task executes under the NEW job's plan
        assert len(plan.script) == 4, "stale worker consumed a script entry"
        report2 = run_sharded(list(range(6)), lambda x: x, workers=4,
                              label="t2")
    assert not plan.script
    assert report2.results == list(range(6))
    assert report2.degraded_inline == 6


def test_heartbeat_fault_is_benign():
    plan = FaultPlan(script=[("dist.heartbeat", "transient")])
    with _fast_retries(delta__tpu__faults__plan=plan):
        report = run_sharded(list(range(4)), lambda x: x, workers=2,
                             label="t")
    assert report.results == list(range(4))
    assert not report.quarantined


# -- leases ------------------------------------------------------------------


def _log_path(tmp_path) -> str:
    p = str(tmp_path / "_delta_log")
    os.makedirs(p, exist_ok=True)
    return p


def test_lease_write_heartbeat_clear_roundtrip(tmp_path):
    log_path = _log_path(tmp_path)
    path = leases.write_lease(log_path, "optimize@3", 1, {
        "txnId": "tok123", "groupKeys": [[["p", "1"]]], "readVersion": 3})
    assert path is not None and os.path.exists(path)
    [(got_path, body, mtime)] = leases.read_leases(log_path)
    assert got_path == path
    assert body["job"] == "optimize@3" and body["proc"] == 1
    assert body["txnId"] == "tok123" and body["pid"] == os.getpid()
    past = time.time() - 30
    os.utime(path, (past, past))
    leases.heartbeat_lease(path)
    assert os.stat(path).st_mtime > past + 25  # heartbeat refreshed mtime
    leases.clear_lease(path)
    assert not os.path.exists(path)
    assert leases.read_leases(log_path) == []


def test_lease_disabled_for_remote_paths_and_by_conf(tmp_path):
    assert not leases.enabled("s3://bucket/tbl/_delta_log")
    with conf.set_temporarily(delta__tpu__distributed__lease__enabled=False):
        assert leases.write_lease(_log_path(tmp_path), "j", 0, {}) is None


def test_lease_write_fault_degrades_uncovered(tmp_path):
    plan = FaultPlan(script=[("dist.leaseWrite", "transient")])
    with conf.set_temporarily(delta__tpu__faults__plan=plan):
        path = leases.write_lease(_log_path(tmp_path), "j", 0, {})
    assert path is None  # slice proceeds uncovered, not failed
    assert telemetry.counters("dist")["dist.degraded.lease"] == 1


def test_lease_write_crash_pierces(tmp_path):
    plan = FaultPlan(script=[("dist.leaseWrite", "crash_before_publish")])
    with conf.set_temporarily(delta__tpu__faults__plan=plan):
        with pytest.raises(SimulatedCrash):
            leases.write_lease(_log_path(tmp_path), "j", 0, {})


def test_torn_lease_file_skipped(tmp_path):
    log_path = _log_path(tmp_path)
    leases.write_lease(log_path, "j", 0, {"txnId": "t"})
    torn = os.path.join(leases.dist_dir(log_path),
                        f"lease-{int(time.time() * 1000):013d}-99999-1.json")
    with open(torn, "w", encoding="utf-8") as f:
        f.write('{"job": "j", "pro')  # half-written by a dying host
    bodies = leases.read_leases(log_path)
    assert len(bodies) == 1
    assert bodies[0][1]["proc"] == 0


def test_sweep_spares_own_live_lease_expires_dead_pids(tmp_path):
    """Satellite: the ``_dist/`` sweep shares the journal's liveness rule —
    this process's fresh lease is spared exactly like the journal's active
    segment, while a dead CI pid's stale lease expires (one immune lease
    per crashed run would grow the directory forever)."""
    log_path = _log_path(tmp_path)
    with conf.set_temporarily(delta__tpu__distributed__lease__ttlMs=1_000):
        own = leases.write_lease(log_path, "j", 0, {"txnId": "a"})
        ddir = leases.dist_dir(log_path)
        dead = os.path.join(ddir, "lease-0000000000001-999999-1.json")
        with open(dead, "w", encoding="utf-8") as f:
            json.dump({"job": "old", "pid": 999999}, f)
        past = time.time() - 10  # heartbeat 10s stale vs a 1s ttl
        os.utime(dead, (past, past))
        deleted = leases.sweep_leases(log_path)
    assert deleted == 1
    assert os.path.exists(own)
    assert not os.path.exists(dead)
    assert telemetry.counters("dist")["dist.lease.swept"] == 1


def test_sweep_spares_fresh_foreign_lease(tmp_path):
    """A foreign pid's lease with a LIVE heartbeat is not swept — the
    grace rule is heartbeat age, not pid ownership."""
    log_path = _log_path(tmp_path)
    ddir = leases.dist_dir(log_path)
    os.makedirs(ddir, exist_ok=True)
    fresh = os.path.join(ddir, "lease-0000000000002-999999-0.json")
    with open(fresh, "w", encoding="utf-8") as f:
        json.dump({"job": "peer", "pid": 999999}, f)
    assert leases.sweep_leases(log_path) == 0
    assert os.path.exists(fresh)


def test_live_writer_spared_shared_rule():
    """Unit test for the rule itself (obs/journal): newest file per
    embedded pid, only while touched within the grace window."""
    from delta_tpu.obs.journal import live_writer_spared

    now = time.time()
    stats = [
        ("j-0000000000001-111-a.log", 10, now),        # old file, pid 111
        ("j-0000000000002-111-b.log", 10, now),        # newest for pid 111
        ("j-0000000000003-222-a.log", 10, now - 500),  # newest but stale
    ]
    spared = live_writer_spared(stats, grace_s=60.0)
    assert spared == {"j-0000000000002-111-b.log"}


# -- end-to-end: quarantined OPTIMIZE + orphaned-slice recovery --------------


def _mk_partitioned_table(path: str, parts: int = 4, files_per_part: int = 3,
                          rows_per_file: int = 16):
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.log.deltalog import DeltaLog

    def batch(base):
        n = parts * rows_per_file
        return pa.table({
            "id": pa.array(range(base, base + n), pa.int64()),
            "part": pa.array([str(i % parts) for i in range(n)]),
        })

    DeltaTable.create(path, data=batch(0), partition_columns=["part"])
    log = DeltaLog.for_table(path)
    for i in range(1, files_per_part):
        WriteIntoDelta(log, "append", batch(i * parts * rows_per_file),
                       partition_columns=["part"]).run()
    return log


def _table_rows(log):
    from delta_tpu.exec.scan import scan_to_table

    return sorted(scan_to_table(log.update(), [], ["id"])
                  .column("id").to_pylist())


def test_optimize_quarantine_completes_commit_without_poison_group(tmp_path):
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.obs import journal

    path = str(tmp_path / "t")
    log = _mk_partitioned_table(path)
    before = _table_rows(log)
    plan = FaultPlan(script=[("dist.itemExec", "transient")])
    with _fast_retries(delta__tpu__faults__plan=plan,
                       delta__tpu__distributed__retry__maxAttempts=1):
        cmd = OptimizeCommand(log, workers=4, on_failure="quarantine")
        cmd.run()
    assert cmd.metrics["numQuarantinedGroups"] == 1
    assert len(cmd.shard_report.quarantined) == 1
    assert _table_rows(log) == before  # no committed row touched
    # the skipped group's files survive untouched: 4 partitions planned,
    # 3 rewritten, one left exactly as planned-around
    snap = log.update()
    per_part = {}
    for f in snap.all_files:
        key = tuple(sorted((f.partition_values or {}).items()))
        per_part[key] = per_part.get(key, 0) + 1
    assert sorted(per_part.values()) == [1, 1, 1, 3]
    journal.flush(log.log_path)
    ev = [e for e in journal.read_entries(log.log_path, kinds=("dist",))
          if e.get("event") == "dist.quarantine"]
    assert len(ev) == 1 and ev[0]["op"] == "optimize"
    assert ev[0]["items"][0]["attempts"] == 1


def test_optimize_transient_faults_retry_to_the_clean_runs_table(tmp_path):
    """Four scripted transient `dist.itemExec` faults under a sharded
    OPTIMIZE with `on_failure="quarantine"`: every one retries to success,
    nothing is quarantined, and rows and file count equal a clean run's."""
    from delta_tpu.commands.optimize import OptimizeCommand

    clean = _mk_partitioned_table(str(tmp_path / "clean"))
    faulted = _mk_partitioned_table(str(tmp_path / "faulted"))
    OptimizeCommand(clean, workers=4).run()
    plan = FaultPlan(script=[("dist.itemExec", "transient")] * 4)
    # more attempts than faults: whichever items the four land on, none of
    # them can run out
    with _fast_retries(delta__tpu__faults__plan=plan,
                       delta__tpu__distributed__retry__maxAttempts=6):
        cmd = OptimizeCommand(faulted, workers=4, on_failure="quarantine")
        cmd.run()
    assert not plan.script, "scripted faults never fired"
    assert cmd.metrics["numQuarantinedGroups"] == 0
    assert cmd.shard_report.retried >= 4
    assert cmd.shard_report.quarantined == []
    assert _table_rows(faulted) == _table_rows(clean)
    assert (len(faulted.update().all_files)
            == len(clean.update().all_files) == 4)


def _posed_optimize(log, proc: int, n_procs: int = 2, **kw):
    """Run a distributed OPTIMIZE posing as host ``proc`` of ``n_procs``."""
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.parallel import distributed as dist_mod

    cmd = OptimizeCommand(log, workers=2, distribute=True, **kw)
    orig = dist_mod.process_info
    dist_mod.process_info = lambda: (proc, n_procs)
    try:
        cmd.run()
    finally:
        dist_mod.process_info = orig
    return cmd


def _age_leases(log_path: str, by_s: float = 120.0):
    past = time.time() - by_s
    for p, _b, _m in leases.read_leases(log_path):
        os.utime(p, (past, past))


def test_orphaned_slice_recovered_by_coordinator(tmp_path):
    """Host 1 dies mid-rewrite (SimulatedCrash at dist.itemExec) leaving
    its lease behind; the coordinator's post-commit reconciliation re-plans
    the orphan's recorded group keys from a fresh snapshot and re-executes.
    End state: rows AND file topology identical to a single-process run."""
    from delta_tpu.log.deltalog import DeltaLog
    from delta_tpu.obs import journal

    path = str(tmp_path / "t")
    ref_path = str(tmp_path / "ref")
    log = _mk_partitioned_table(path)
    ref_log = _mk_partitioned_table(ref_path)

    # reference: the same table optimized by one healthy process
    from delta_tpu.commands.optimize import OptimizeCommand

    OptimizeCommand(ref_log, workers=2).run()
    ref_rows = _table_rows(ref_log)
    ref_files = len(ref_log.update().all_files)

    # host 1 crashes mid-slice; its lease survives with a stale heartbeat
    plan = FaultPlan(script=[("dist.itemExec", "crash_before_publish")])
    with _fast_retries(delta__tpu__faults__plan=plan):
        with pytest.raises(SimulatedCrash):
            _posed_optimize(log, proc=1)
    assert len(leases.read_leases(log.log_path)) == 1
    _age_leases(log.log_path)

    # coordinator: commits its own slice, then recovers the orphan
    DeltaLog.invalidate_cache(path)
    log = DeltaLog(path)
    with conf.set_temporarily(
            delta__tpu__distributed__lease__settleMs=20):
        _posed_optimize(log, proc=0)

    assert _table_rows(log) == ref_rows
    assert len(log.update().all_files) == ref_files
    assert leases.read_leases(log.log_path) == []  # orphan cleared
    assert telemetry.counters("dist")["dist.slice.recovered"] == 1
    journal.flush(log.log_path)
    events = {e.get("event")
              for e in journal.read_entries(log.log_path, kinds=("dist",))}
    assert "dist.sliceRecovered" in events


def test_landed_commit_reconciled_not_reexecuted(tmp_path):
    """Host 1 commits but dies before clearing its lease: the coordinator
    finds the recorded txnId in the log tail and only clears the lease —
    a recovered slice is never double-committed."""
    from unittest import mock

    from delta_tpu.log.deltalog import DeltaLog
    from delta_tpu.obs import journal

    path = str(tmp_path / "t")
    log = _mk_partitioned_table(path)

    with mock.patch.object(leases, "clear_lease"):  # the lost clear
        _posed_optimize(log, proc=1)
    assert len(leases.read_leases(log.log_path)) == 1
    v_after_host1 = log.update().version
    _age_leases(log.log_path)

    DeltaLog.invalidate_cache(path)
    log = DeltaLog(path)
    with conf.set_temporarily(
            delta__tpu__distributed__lease__settleMs=20):
        _posed_optimize(log, proc=0)

    # exactly one commit per slice: host 1's + the coordinator's own
    assert log.update().version == v_after_host1 + 1
    assert leases.read_leases(log.log_path) == []
    assert "dist.slice.recovered" not in telemetry.counters("dist")
    journal.flush(log.log_path)
    events = {e.get("event")
              for e in journal.read_entries(log.log_path, kinds=("dist",))}
    assert "dist.sliceReconciled" in events
    assert "dist.sliceRecovered" not in events


def test_recovery_is_idempotent_when_nothing_replannable(tmp_path):
    """An orphan whose partitions were already compacted re-plans to zero
    groups: recovery commits NOTHING (no empty commit, no counter)."""
    from delta_tpu.log.deltalog import DeltaLog

    path = str(tmp_path / "t")
    log = _mk_partitioned_table(path)

    plan = FaultPlan(script=[("dist.itemExec", "crash_before_publish")])
    with _fast_retries(delta__tpu__faults__plan=plan):
        with pytest.raises(SimulatedCrash):
            _posed_optimize(log, proc=1)
    _age_leases(log.log_path)

    # a full single-process OPTIMIZE compacts everything first
    from delta_tpu.commands.optimize import OptimizeCommand

    DeltaLog.invalidate_cache(path)
    log = DeltaLog(path)
    OptimizeCommand(log, workers=2).run()
    v = log.update().version

    files_before = len(log.update().all_files)
    with conf.set_temporarily(
            delta__tpu__distributed__lease__settleMs=20):
        cmd = _posed_optimize(log, proc=0)
    # the coordinator's own (empty-plan) OPTIMIZE may land its usual
    # metrics-only commit, but the RECOVERY adds no commit, rewrites no
    # file, and counts nothing recovered
    assert log.update().version <= v + 1
    assert cmd.metrics["numAddedFiles"] == 0
    assert len(log.update().all_files) == files_before
    assert leases.read_leases(log.log_path) == []
    assert "dist.slice.recovered" not in telemetry.counters("dist")


# -- doctor dimension --------------------------------------------------------


def test_doctor_distributed_dimension(tmp_path):
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.obs.doctor import doctor

    path = str(tmp_path / "t")
    DeltaTable.create(path, data=pa.table({"id": pa.array([1], pa.int64())}))
    from delta_tpu.log.deltalog import DeltaLog

    rep = doctor(DeltaLog.for_table(path))
    dim = {d.name: d for d in rep.dimensions}["distributed"]
    assert dim.severity == "ok"

    telemetry.bump_counter("dist.items.quarantined")
    telemetry.bump_counter("dist.degraded.pool")
    rep = doctor(DeltaLog.for_table(path))
    dim = {d.name: d for d in rep.dimensions}["distributed"]
    assert dim.severity == "warn"
    assert dim.metrics["itemsQuarantined"] == 1
    assert dim.metrics["degraded"] == 1
