"""OPTIMIZE — compaction and Z-ORDER clustering.

The reference ships no OPTIMIZE command in this version (Z-order tags exist
in the format only, `actions/actions.scala:270-291`); the rebuild provides
both modes because the perf baseline measures them:

* **compaction**: bin-pack small files per partition up to a target size and
  rewrite them as one file;
* **Z-ORDER BY (cols)**: re-sort the selected partitions by the on-device
  Morton key (`ops/zorder.py`) and re-split, giving compact per-file min/max
  boxes for data skipping.

Both commit as rearrange-only transactions (`dataChange=False`), so
concurrent appends don't conflict and streams ignore the rewrite — the same
reason `WriteIntoDelta.scala:129-131` flips dataChange for rearrangeOnly.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import pyarrow as pa

from delta_tpu.commands import operations as ops
from delta_tpu.commands.dml_common import Timer
from delta_tpu.exec import write as write_exec
from delta_tpu.exec.scan import read_files_as_table
from delta_tpu.expr import ir
from delta_tpu.expr import partition as partition_expr
from delta_tpu.expr.parser import parse_predicate
from delta_tpu.ops.zorder import morton_order
from delta_tpu.protocol.actions import Action, AddFile
from delta_tpu.utils.errors import DeltaAnalysisError
from delta_tpu.utils import errors

__all__ = ["OptimizeCommand", "OptimizeBudgetExceeded"]

DEFAULT_MIN_FILE_SIZE = 256 * 1024 * 1024  # files below this are compactable
DEFAULT_TARGET_ROWS = 1 << 22


class OptimizeBudgetExceeded(errors.DeltaError):
    """The selected rewrite set exceeds ``max_rewrite_bytes``. Raised
    BEFORE any data is read or written — the cost-capped invocation path
    (`delta_tpu/autopilot`) turns this into a journaled SKIPPED outcome
    instead of an over-budget background rewrite."""

    def __init__(self, est_bytes: int, cap_bytes: int, files: int):
        super().__init__(
            f"OPTIMIZE would rewrite {est_bytes} bytes across {files} "
            f"files, over the {cap_bytes}-byte budget")
        self.est_bytes = est_bytes
        self.cap_bytes = cap_bytes
        self.files = files


class OptimizeCommand:
    def __init__(
        self,
        delta_log,
        predicate: Optional[Union[str, ir.Expression]] = None,
        z_order_by: Sequence[str] = (),
        min_file_size: int = DEFAULT_MIN_FILE_SIZE,
        target_rows: int = DEFAULT_TARGET_ROWS,
        purge: bool = False,
        max_rewrite_bytes: Optional[int] = None,
        workers: Optional[int] = None,
        distribute: bool = False,
        on_failure: str = "raise",
    ):
        if on_failure not in ("raise", "quarantine"):
            raise ValueError(
                f"on_failure must be 'raise' or 'quarantine', got {on_failure!r}")
        self.delta_log = delta_log
        self.predicate = (
            parse_predicate(predicate) if isinstance(predicate, str) else predicate
        )
        self.z_order_by = list(z_order_by)
        self.min_file_size = min_file_size
        self.target_rows = target_rows
        # purge mode (modern Delta's REORG TABLE ... APPLY (PURGE)): rewrite
        # exactly the files carrying deletion vectors, materializing the
        # deletes and dropping the DVs — size-based selection is bypassed
        self.purge = purge
        # cost cap (programmatic maintenance path): the total size of the
        # files selected for rewrite is bounded up front — an over-budget
        # job raises OptimizeBudgetExceeded before any IO
        self.max_rewrite_bytes = max_rewrite_bytes
        # sharded execution (parallel/executor): bin-pack groups rewrite on
        # `workers` LPT-seeded work-stealing workers (None = the
        # delta.tpu.distributed.optimize.workers conf, default 1 —
        # sequential, byte-identical to the classic loop). `distribute`
        # additionally splits the groups across jax.distributed hosts
        # (byte-weighted LPT); each host commits its disjoint rearrange-only
        # slice, funneled through the group-commit coordinator.
        self.workers = workers
        self.distribute = distribute
        # item-failure policy for the sharded executor: "raise" aborts the
        # job on the first exhausted group (classic semantics); "quarantine"
        # completes the commit WITHOUT the failed groups' rewrites — their
        # files stay exactly as planned-around, reported in shard_report
        self.on_failure = on_failure
        # the last run's executor evidence (per-worker timings, steals,
        # skew) — tests and `chip_smoke.py`'s mesh step read it
        self.shard_report = None
        # multihost crash evidence: this host's lease (heartbeated during
        # the rewrite, cleared after commit) and, on the coordinator, the
        # post-commit orphan-recovery context (parallel/leases.py)
        self._lease_path: Optional[str] = None
        self._recover_info: Optional[Dict] = None
        self.metrics: Dict[str, int] = {}

    def _resolve_workers(self) -> int:
        if self.workers is not None:
            return max(int(self.workers), 1)
        from delta_tpu.utils.config import conf

        got = conf.get("delta.tpu.distributed.optimize.workers")
        return max(int(got), 1) if got is not None else 1

    def run(self) -> int:
        from delta_tpu.utils.telemetry import record_operation

        with record_operation("delta.dml.optimize", path=self.delta_log.data_path):
            version = self.delta_log.with_new_transaction(self._body)
            if self._recover_info is not None:
                # coordinator fan-in: after our own slice committed, wait
                # for peer hosts' leases to clear and recover any orphans
                # (needs fresh transactions — cannot run inside _body's)
                self._recover_orphan_slices()
            return version

    def _recover_orphan_slices(self) -> int:
        """Coordinator-side orphaned-slice recovery: poll peer leases for
        this job until each clears (host committed and released) or its
        heartbeat expires past the ttl (host died). An expired lease is
        reconciled against the log by its recorded ``commitInfo.txnId`` —
        present means only the *clear* was lost; absent means the slice's
        work is re-planned from a fresh snapshot restricted to its recorded
        group keys and re-executed locally. Returns recovered slice count.

        The wait is bounded: with no peer lease in sight the coordinator
        only lingers ``delta.tpu.distributed.lease.settleMs`` (a peer that
        died before even publishing its lease lost no committed data — its
        partitions are merely left uncompacted for the next OPTIMIZE), and
        a wedged-but-heartbeating peer stops blocking fan-in after 10×ttl.
        """
        import time as _time

        from delta_tpu.parallel import leases
        from delta_tpu.utils.config import conf

        info = self._recover_info
        self._recover_info = None
        log_path = self.delta_log.log_path
        if info is None or not leases.enabled(log_path):
            return 0
        ttl_s = leases.lease_ttl_s()
        try:
            settle_s = max(float(conf.get(
                "delta.tpu.distributed.lease.settleMs", 250)), 0.0) / 1000.0
        except (TypeError, ValueError):
            settle_s = 0.25
        poll_s = max(min(ttl_s / 4.0, 0.25), 0.005)
        start = _time.monotonic()
        hard_deadline = start + max(10.0 * ttl_s, settle_s)
        recovered = 0
        own = self._lease_path
        while True:
            now = _time.time()
            all_leases = [(p, body, mtime)
                          for p, body, mtime in leases.read_leases(log_path)
                          if p != own]
            # an EXPIRED lease is an orphan whatever job wrote it — the
            # lease is self-describing (txnId + group keys + readVersion),
            # and hosts that planned across an interleaving commit carry
            # different job ids for the same fan-out. Only same-job live
            # peers gate the fan-in wait, though: another job's live lease
            # is that job's coordinator's problem.
            orphans = [(p, body) for p, body, mtime in all_leases
                       if now - mtime > ttl_s]
            live = [p for p, body, mtime in all_leases
                    if now - mtime <= ttl_s
                    and body.get("job") == info["job"]]
            seen_peer = any(body.get("job") == info["job"]
                            for _p, body, _m in all_leases)
            for path, body in orphans:
                recovered += self._recover_one_slice(path, body, info)
            if not live and (seen_peer or orphans or
                             _time.monotonic() - start >= settle_s):
                break
            if _time.monotonic() >= hard_deadline:
                break
            _time.sleep(poll_s)
        return recovered

    def _recover_one_slice(self, lease_path: str, body: Dict,
                           info: Dict) -> int:
        """Reconcile or re-execute one orphaned slice; returns 1 when its
        work had to be (and was) re-executed. Exactly-once per group:
        either the dead host's commit is found by token, or the restricted
        replan sees its partitions' current files — never both rewrites."""
        from delta_tpu.obs import journal
        from delta_tpu.parallel import leases
        from delta_tpu.utils import telemetry

        log_path = self.delta_log.log_path
        token = body.get("txnId")
        with telemetry.record_operation("delta.dist.sliceRecovery", {
            "job": str(body.get("job")), "proc": body.get("proc"),
        }) as ev:
            try:
                since = int(body.get("readVersion", info["readVersion"]))
            except (TypeError, ValueError):
                since = int(info["readVersion"])
            if token and self._txn_landed(str(token), since):
                # the host committed; only its lease clear was lost
                ev.data["outcome"] = "reconciled"
                leases.clear_lease(lease_path)
                journal.record_dist(log_path, {
                    "event": "dist.sliceReconciled",
                    "proc": body.get("proc"), "job": body.get("job"),
                })
                return 0
            keys = {tuple(tuple(kv) for kv in key)
                    for key in (body.get("groupKeys") or [])}

            def _recover_body(txn):
                groups = self._plan_groups(txn, restrict_keys=keys)
                if not groups:
                    return 0  # nothing re-plannable: no commit at all
                removes: List[Action] = []
                adds: List[Action] = []
                for _key, group in groups:
                    new_adds, new_removes = self._rewrite_group(
                        group, txn.metadata)
                    adds.extend(new_adds)
                    removes.extend(new_removes)
                op = (ops.Reorg(predicate=[]) if self.purge else
                      ops.Optimize(predicate=[],
                                   z_order_by=self.z_order_by or None))
                txn.commit(removes + adds, op)
                return len(groups)

            self.delta_log.update()  # replan from the freshest snapshot
            n_groups = self.delta_log.with_new_transaction(_recover_body)
            ev.data["outcome"] = "recovered" if n_groups else "noop"
            ev.data["groups"] = n_groups
            leases.clear_lease(lease_path)
            journal.record_dist(log_path, {
                "event": "dist.sliceRecovered",
                "proc": body.get("proc"), "job": body.get("job"),
                "groups": n_groups,
            })
            if n_groups:
                telemetry.bump_counter("dist.slice.recovered")
            return 1 if n_groups else 0

    def _txn_landed(self, token: str, since_version: int) -> bool:
        """Did a commit carrying ``commitInfo.txnId == token`` land after
        ``since_version``? Scans the log tail file-by-file — the same
        token comparison ``_reconcile_ambiguous_commit`` does for one
        version, widened to the window a dead peer could have written."""
        import json as _json

        from delta_tpu.protocol import filenames

        self.delta_log.update()
        current = self.delta_log.snapshot.version
        for v in range(since_version + 1, current + 1):
            path = f"{self.delta_log.log_path}/{filenames.delta_file(v)}"
            try:
                lines = self.delta_log.store.read(path)
            except FileNotFoundError:
                continue
            if not lines:
                continue
            try:
                got = (_json.loads(lines[0]).get("commitInfo")
                       or {}).get("txnId")
            except (ValueError, AttributeError):
                continue
            if got == token:
                return True
        return False

    def _plan_groups(self, txn, restrict_keys=None
                     ) -> List[Tuple[Tuple, List[AddFile]]]:
        """Metadata-only rewrite planning: the selected files per partition
        key, in deterministic key order. ``restrict_keys`` (a set of
        partition-key tuples) replans only those partitions — the orphan
        slice recovery path, where it makes re-execution idempotent: a
        partition the dead host already compacted yields fewer than two
        small files and drops out of the plan."""
        # filter_files evaluates the partition predicate exactly
        candidates = txn.filter_files(
            [self.predicate] if self.predicate is not None else None
        )

        by_partition: Dict[Tuple, List[AddFile]] = defaultdict(list)
        for f in candidates:
            key = tuple(sorted((f.partition_values or {}).items()))
            if restrict_keys is not None and key not in restrict_keys:
                continue
            by_partition[key].append(f)

        groups: List[Tuple[Tuple, List[AddFile]]] = []
        # None-safe ordering: null partition values sort first
        for key, files in sorted(
            by_partition.items(),
            key=lambda kv: [(c, v is not None, v or "") for c, v in kv[0]],
        ):
            if self.z_order_by:
                group = files  # Z-order rewrites every selected file
            elif self.purge:
                group = [f for f in files if f.deletion_vector is not None]
                if not group:
                    continue
            else:
                group = [f for f in files if (f.size or 0) < self.min_file_size]
                if len(group) < 2:
                    continue  # nothing to compact
            groups.append((key, group))
        return groups

    def _rewrite_group(self, group: List[AddFile], metadata):
        """Read, (optionally) re-sort, and rewrite one bin-packed group;
        returns ``(new_adds, removes)``. Runs on executor worker threads —
        each call heartbeats this host's lease so the coordinator sees the
        slice as live for as long as it is making progress."""
        from delta_tpu.parallel import leases

        leases.heartbeat_lease(self._lease_path)
        table = read_files_as_table(
            self.delta_log.data_path, group, metadata
        )
        if self.z_order_by:
            cols = [
                np_col(table, c) for c in self.z_order_by
            ]
            perm = morton_order(cols)
            table = table.take(pa.array(perm))
        new_adds = write_exec.write_files(
            self.delta_log.data_path,
            table,
            metadata,
            data_change=False,
            target_file_rows=self.target_rows,
        )
        return new_adds, [f.remove(data_change=False) for f in group]

    def _body(self, txn) -> int:
        metadata = txn.metadata
        pcols = metadata.partition_columns
        if self.predicate is not None:
            conjuncts = ir.split_conjuncts(self.predicate)
            if not all(partition_expr.is_partition_predicate(c, pcols) for c in conjuncts):
                raise DeltaAnalysisError(
                    "OPTIMIZE predicate must reference only partition columns"
                )
        for c in self.z_order_by:
            names = [f.name.lower() for f in metadata.schema.fields]
            if c.lower() not in names:
                raise errors.zorder_column_not_in_schema(c)
            if c.lower() in [p.lower() for p in pcols]:
                raise errors.zorder_on_partition_column(c)

        timer = Timer()
        # plan first (selection is metadata-only), so the cost cap can
        # abort an over-budget job before ANY file is read or written
        groups = self._plan_groups(txn)
        if self.max_rewrite_bytes is not None:
            est = sum(f.size or 0 for _, g in groups for f in g)
            if est > self.max_rewrite_bytes:
                raise OptimizeBudgetExceeded(
                    est, self.max_rewrite_bytes,
                    sum(len(g) for _, g in groups))

        # multi-host mode: every host plans the SAME group list from the
        # same snapshot, then takes its disjoint byte-weighted LPT slice —
        # deterministic, no scheduler RPC. Each host commits only its own
        # rearranged files, so the per-host transactions are disjoint
        # rearrange-only commits that cannot conflict.
        fan_in = False
        slice_info = None
        if self.distribute:
            from delta_tpu.parallel.distributed import (
                host_shard_indices, process_info)

            proc, n_procs = process_info()
            if n_procs > 1:
                gsizes = [sum(f.size or 0 for f in g) for _k, g in groups]
                mine = host_shard_indices(
                    len(groups), proc, n_procs, sizes=gsizes)
                groups = [groups[i] for i in mine]
                # this host's slice of the groups, as a span: the stitched
                # trace shows one delta.dist.hostSlice lane per process
                slice_info = {
                    "proc": proc, "nProcs": n_procs, "groups": len(groups),
                    "sliceBytes": sum(
                        f.size or 0 for _k, g in groups for f in g),
                }
                # narrow the recorded read set to THIS host's slice: the
                # commit's validity depends only on its own files surviving
                # (the reference's OPTIMIZE pins its read files the same
                # way), so a peer host's rearrange-only removes must not
                # fail us with a delete-read conflict
                keep = {f.path for _k, g in groups for f in g}
                for p in [p for p in txn.read_files if p not in keep]:
                    del txn.read_files[p]
                from delta_tpu.utils.config import conf

                fan_in = conf.get_bool(
                    "delta.tpu.distributed.singleWriterFanIn", True)

                # publish this host's lease BEFORE executing: the slice id,
                # its bin-packed group keys, and the txnId its commit will
                # carry — everything the coordinator needs to reconcile or
                # re-execute the slice if this host dies past this point
                from delta_tpu.parallel import leases

                job_id = f"optimize@{txn.read_version}"
                token = leases.new_token()
                txn.preset_txn_id = token
                self._lease_path = leases.write_lease(
                    self.delta_log.log_path, job_id, proc, {
                        "txnId": token,
                        "nProcs": n_procs,
                        "readVersion": txn.read_version,
                        "groupKeys": [[list(kv) for kv in key]
                                      for key, _g in groups],
                    })
                if proc == 0:
                    # the coordinator owns post-commit orphan recovery
                    # (run() — it needs its own transaction)
                    self._recover_info = {
                        "job": job_id, "proc": proc,
                        "readVersion": txn.read_version,
                    }

        removes: List[Action] = []
        adds: List[Action] = []
        rewritten_bytes = 0
        quarantined_groups = 0

        if groups:
            import contextlib

            from delta_tpu.parallel.executor import run_sharded
            from delta_tpu.utils import telemetry

            telemetry.bump_counter("dist.optimize.groups", len(groups))
            slice_span = (
                telemetry.record_operation("delta.dist.hostSlice", slice_info)
                if slice_info is not None else contextlib.nullcontext())
            with slice_span:
                report = run_sharded(
                    [g for _k, g in groups],
                    lambda g: self._rewrite_group(g, metadata),
                    sizes=[sum(f.size or 0 for f in g) for _k, g in groups],
                    workers=self._resolve_workers(),
                    label="optimize",
                    on_failure=self.on_failure,
                )
            self.shard_report = report
            # results are index-ordered, so adds/removes land in the exact
            # order the classic sequential loop produced them; a quarantined
            # group's slot is None — its files are simply not rewritten
            # this run (left exactly as planned-around, reported below)
            for (_key, group), pair in zip(groups, report.results):
                if pair is None:
                    quarantined_groups += 1
                    continue
                new_adds, new_removes = pair
                adds.extend(new_adds)
                removes.extend(new_removes)
                rewritten_bytes += sum(f.size or 0 for f in group)
            if report.quarantined:
                from delta_tpu.obs import journal

                journal.record_dist(self.delta_log.log_path, {
                    "event": "dist.quarantine", "op": "optimize",
                    "items": [q.to_dict() for q in report.quarantined],
                })

        self.metrics.update(
            numRemovedFiles=len(removes),
            numAddedFiles=len(adds),
            numRemovedBytes=rewritten_bytes,
            numAddedBytes=sum(a.size or 0 for a in adds
                              if isinstance(a, AddFile)),
            numQuarantinedGroups=quarantined_groups,
            timeMs=timer.lap_ms(),
        )
        txn.report_metrics(**self.metrics)
        pred_sql = [self.predicate.sql()] if self.predicate is not None else []
        if self.purge:
            op = ops.Reorg(predicate=pred_sql)
        else:
            op = ops.Optimize(
                predicate=pred_sql, z_order_by=self.z_order_by or None,
            )
        if fan_in:
            # single-writer fan-in: every host's commit funnels through the
            # group-commit coordinator (PR 9), so the log sees one ordered
            # writer instead of n_procs racing _do_commit_retry loops
            from delta_tpu.utils.config import conf
            from delta_tpu.utils import telemetry

            telemetry.bump_counter("dist.commit.fanin")
            with telemetry.record_operation(
                "delta.dist.commit.fanIn",
                {"adds": len(adds), "removes": len(removes)},
            ):
                with conf.set_temporarily(
                    **{"delta.tpu.commit.group.enabled": True}
                ):
                    version = txn.commit(removes + adds, op)
        else:
            version = txn.commit(removes + adds, op)
        # commit is durable: release this host's lease — a crash between
        # the commit and here leaves an orphan whose txnId reconciles to
        # already-committed (cleanup, not re-execution)
        if self._lease_path is not None:
            from delta_tpu.parallel import leases

            leases.clear_lease(self._lease_path)
            self._lease_path = None
        # file rewrite: bump the resident key-cache epoch so a stale HBM
        # slab can never serve a post-OPTIMIZE MERGE (ops/key_cache.py)
        if removes or adds:
            from delta_tpu.ops.column_cache import ColumnCache
            from delta_tpu.ops.key_cache import KeyCache

            KeyCache.instance().bump_epoch(self.delta_log.log_path)
            ColumnCache.instance().bump_epoch(self.delta_log.log_path)
        # feed the table-health doctor: maintenance recency as gauges, work
        # done as counters (obs/metric_names.py catalog)
        from delta_tpu.utils import telemetry

        telemetry.set_gauge("table.maintenance.lastOptimizeVersion", version,
                            path=self.delta_log.data_path)
        if removes:
            telemetry.bump_counter("maintenance.optimize.filesCompacted",
                                   len(removes))
        if adds:
            telemetry.bump_counter("maintenance.optimize.filesWritten",
                                   len(adds))
        return version


def np_col(table: pa.Table, name: str):
    """Column as numpy for ranking; NULLs substitute the column minimum so
    rank_u16's argsort stays total (NULLs cluster with the smallest value)."""
    import pyarrow.compute as pc

    col = None
    for c in table.column_names:
        if c.lower() == name.lower():
            col = table.column(c)
            break
    if col.null_count == len(col):
        # all-null: every rank is equal, contribute a constant dimension
        import numpy as np

        return np.zeros(len(col), np.int64)
    if col.null_count:
        col = pc.fill_null(col, pc.min(col))
    return col.to_numpy(zero_copy_only=False)
