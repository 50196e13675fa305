"""Worker for the 2-process DCN integration test (`test_multihost.py`).

Each process joins a real `jax.distributed` CPU cluster, then drives the
engine's multi-host paths against a SHARED table directory — the
coordination model is the store, not RPC (SURVEY §2.8):

  scan        — each host decodes its strided partition of the file list
  checkpoint  — each host writes its slice of the parts; proc 0 publishes
                `_last_checkpoint` after all parts are visible
  convert     — each host footers/stats its slice; proc 0 gathers the
                fragments from the store and commits
  vacuum      — each host deletes its slice of the expired files

``dist`` mode drives the sharded-execution plane instead: each host takes
its byte-weighted LPT slice of the OPTIMIZE bin-pack groups and commits its
own rearrange-only transaction, then proc 0 runs a probe-restricted MERGE.
``dist-crash`` kills proc 1 with a SimulatedCrash mid-OPTIMIZE (no cluster
join — the store is the coordination model, and a dead peer must not hang
the survivor's jax.distributed teardown; leases are disabled so the
survivor-only semantics stay isolated from the recovery path below).
``dist-recover`` is the lease-recovery flavor (ISSUE 20): proc 1 crashes
mid-slice AFTER publishing its lease; proc 0 — launched by the parent once
the lease's heartbeat has been aged past the ttl — commits its own slice,
then reconciles the orphan via the coordinator recovery path and reports
the recovered end state.

Results land in <out>/result-<proc>.json for the parent to assert.
"""
import json
import os
import sys
import time


def _barrier(out_dir: str, name: str, proc: int, n_procs: int) -> None:
    """Store-based barrier: marker files on the shared directory."""
    open(os.path.join(out_dir, f"{name}-{proc}"), "w").close()
    deadline = time.time() + 60
    while not all(
        os.path.exists(os.path.join(out_dir, f"{name}-{i}"))
        for i in range(n_procs)
    ):
        if time.time() > deadline:
            raise TimeoutError(f"barrier {name} timed out on proc {proc}")
        time.sleep(0.05)


def dist_body(proc: int, n_procs: int, table: str, out_dir: str,
              crash: bool) -> None:
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.exec.scan import scan_to_table

    # distributed tracing: the parent exports DELTA_TPU_TRACEPARENT (adopted
    # lazily by telemetry itself) and the spool directory; with the dir set,
    # every span this worker runs lands in its own JSONL spool for the
    # parent's collector to stitch
    trace_dir = os.environ.get("DELTA_TPU_TRACE_DIR")
    if trace_dir:
        from delta_tpu.utils.config import conf as _conf

        _conf.set("delta.tpu.trace.dir", trace_dir)

    result = {"proc": proc}
    log = DeltaLog.for_table(table)
    snap = log.update()

    # sharded scan: the byte-weighted LPT partitions tile the table
    part = scan_to_table(snap, distribute=True)
    result["scan_ids"] = sorted(part.column("id").to_pylist())

    if crash:
        # the crash flavor isolates SURVIVOR semantics: a dead peer commits
        # nothing and must not hang the survivor. Leases stay off so the
        # coordinator does not block on (and then recover) the orphaned
        # slice — that path is the `dist-recover` mode's subject.
        from delta_tpu.utils.config import conf as _cconf

        _cconf.set("delta.tpu.distributed.lease.enabled", False)

    if crash and proc == 1:
        # SimulatedCrash (a BaseException) mid-job: fires on this host's
        # SECOND group rewrite, after real work started but before commit
        from delta_tpu.exec import write as write_exec
        from delta_tpu.storage.faults import SimulatedCrash

        orig = write_exec.write_files
        state = {"n": 0}

        def crashing(*a, **k):
            state["n"] += 1
            if state["n"] >= 2:
                raise SimulatedCrash("dist.optimize.rewrite")
            return orig(*a, **k)

        write_exec.write_files = crashing

    cmd = OptimizeCommand(log, min_file_size=1 << 30, workers=2,
                          distribute=True)
    version = cmd.run()
    result["optimize_version"] = version
    result["optimize_groups"] = (
        len(cmd.shard_report.results) if cmd.shard_report else 0)
    result["shard_timings"] = (
        cmd.shard_report.timings() if cmd.shard_report else [])

    if not crash:
        _barrier(out_dir, "opt", proc, n_procs)
        if proc == 0:
            from delta_tpu.commands.merge import MergeClause, MergeIntoCommand

            DeltaLog.clear_cache()
            mlog = DeltaLog.for_table(table)
            src = pa.table({
                "id": pa.array([3, 75, 1000], pa.int64()),
                "part": pa.array(["p0", "p3", "p0"]),
                "v": pa.array([-1.0, -2.0, -3.0]),
            })
            m = MergeIntoCommand(
                mlog, src, "t.id = s.id",
                [MergeClause("update", assignments=None)],
                [MergeClause("insert", assignments=None)],
                source_alias="s", target_alias="t")
            m.run()
            result["merge_updated"] = m.metrics["numTargetRowsUpdated"]
            result["merge_inserted"] = m.metrics["numTargetRowsInserted"]
        _barrier(out_dir, "merge", proc, n_procs)

    DeltaLog.clear_cache()
    fsnap = DeltaLog.for_table(table).update()
    final = scan_to_table(fsnap)
    result["final_ids"] = sorted(final.column("id").to_pylist())
    result["final_files"] = fsnap.num_of_files
    result["final_version"] = fsnap.version

    with open(os.path.join(out_dir, f"result-{proc}.json"), "w") as f:
        json.dump(result, f)


def dist_recover_body(proc: int, n_procs: int, table: str,
                      out_dir: str) -> None:
    trace_dir = os.environ.get("DELTA_TPU_TRACE_DIR")
    if trace_dir:
        from delta_tpu.utils.config import conf as _conf

        _conf.set("delta.tpu.trace.dir", trace_dir)

    from delta_tpu import DeltaLog
    from delta_tpu.commands.optimize import OptimizeCommand

    log = DeltaLog.for_table(table)

    if proc == 1:
        # die on the SECOND group rewrite: the lease is already published
        # (written before slice execution) and real work has started — the
        # classic orphaned-slice shape. The SimulatedCrash (a BaseException)
        # pierces the executor and kills this process with a traceback.
        from delta_tpu.exec import write as write_exec
        from delta_tpu.storage.faults import SimulatedCrash

        orig = write_exec.write_files
        state = {"n": 0}

        def crashing(*a, **k):
            state["n"] += 1
            if state["n"] >= 2:
                raise SimulatedCrash("dist.itemExec")
            return orig(*a, **k)

        write_exec.write_files = crashing
        OptimizeCommand(log, min_file_size=1 << 30, workers=2,
                        distribute=True).run()
        raise AssertionError("proc 1 must have crashed mid-slice")

    # proc 0 — the coordinator: commit our slice, then recover the orphan
    from delta_tpu.obs import journal
    from delta_tpu.parallel import leases
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf
    from delta_tpu.exec.scan import scan_to_table

    with conf.set_temporarily(
            **{"delta.tpu.distributed.lease.settleMs": 20}):
        cmd = OptimizeCommand(log, min_file_size=1 << 30, workers=2,
                              distribute=True)
        version = cmd.run()

    journal.flush(log.log_path)
    DeltaLog.clear_cache()
    fsnap = DeltaLog.for_table(table).update()
    final = scan_to_table(fsnap)
    result = {
        "proc": proc,
        "optimize_version": version,
        "final_ids": sorted(final.column("id").to_pylist()),
        "final_files": fsnap.num_of_files,
        "final_version": fsnap.version,
        "recovered": telemetry.counters("dist").get(
            "dist.slice.recovered", 0),
        "leases_left": len(leases.read_leases(log.log_path)),
        "dist_events": [e.get("event") for e in journal.read_entries(
            log.log_path, kinds=("dist",))],
    }
    with open(os.path.join(out_dir, f"result-{proc}.json"), "w") as f:
        json.dump(result, f)


def main() -> None:
    proc = int(sys.argv[1])
    n_procs = int(sys.argv[2])
    port = sys.argv[3]
    table = sys.argv[4]
    convert_dir = sys.argv[5]
    out_dir = sys.argv[6]
    mode = sys.argv[7] if len(sys.argv) > 7 else "classic"

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from delta_tpu.parallel import distributed as dist

    if mode == "dist-crash":
        # no cluster join: a peer that dies mid-job must not hang the
        # survivor's jax.distributed teardown; slicing reads process_info
        dist.process_info = lambda: (proc, n_procs)
        dist_body(proc, n_procs, table, out_dir, crash=True)
        return

    if mode == "dist-recover":
        # no cluster join either: the two phases run sequentially (the
        # parent ages the dead host's lease between them), so there is no
        # live cluster to coordinate with
        dist.process_info = lambda: (proc, n_procs)
        dist_recover_body(proc, n_procs, table, out_dir)
        return

    pid, count = dist.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=n_procs,
        process_id=proc,
    )
    assert (pid, count) == (proc, n_procs), (pid, count)

    if mode == "dist":
        dist_body(proc, n_procs, table, out_dir, crash=False)
        return

    from delta_tpu import DeltaLog
    from delta_tpu.exec.scan import scan_to_table
    from delta_tpu.log import checkpoints as ckpt_mod

    result = {"proc": proc, "count": count}

    # -- scan: this host's partition of the pruned file list --------------
    log = DeltaLog.for_table(table)
    snap = log.update()
    part = scan_to_table(snap, distribute=True)
    full = scan_to_table(snap)
    result["scan_rows"] = part.num_rows
    result["scan_ids"] = sorted(part.column("id").to_pylist())
    result["full_rows"] = full.num_rows

    # -- checkpoint: each host writes its slice of the parts --------------
    md = ckpt_mod.write_checkpoint(
        log.store, log.log_path, snap.version, snap.checkpoint_actions(),
        parts=4, distribute=True,
    )
    result["ckpt_parts"] = md.parts

    # -- convert: fragment exchange through the store ---------------------
    from delta_tpu.commands.convert import ConvertToDeltaCommand

    clog = DeltaLog.for_table(convert_dir)
    version = ConvertToDeltaCommand(
        clog, collect_stats=True, distribute=True
    ).run()
    result["convert_version"] = version
    DeltaLog.clear_cache()
    csnap = DeltaLog.for_table(convert_dir).update()
    result["convert_files"] = csnap.num_of_files

    with open(os.path.join(out_dir, f"result-{proc}.json"), "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
