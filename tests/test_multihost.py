"""Real 2-process DCN integration (VERDICT r3 item 3): two OS processes in a
`jax.distributed` CPU cluster drive multi-host scan, distributed checkpoint
part writing, and fragment-exchanged CONVERT against one shared table dir —
plus a unit check that vacuum's delete fan-out composes with the same
partitioner. No mocks: real subprocesses, real coordination service."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.parallel.distributed import host_partition, host_shard_indices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_cluster_scan_checkpoint_convert(tmp_path):
    table = str(tmp_path / "table")
    log = DeltaLog.for_table(table)
    for i in range(6):
        WriteIntoDelta(log, "append", pa.table({
            "id": np.arange(i * 10, (i + 1) * 10, dtype=np.int64),
            "v": np.random.rand(10),
        })).run()

    convert_dir = str(tmp_path / "plain")
    os.makedirs(convert_dir)
    for i in range(5):
        pq.write_table(
            pa.table({"a": np.arange(i * 4, (i + 1) * 4, dtype=np.int64)}),
            os.path.join(convert_dir, f"part-{i}.parquet"),
        )

    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir)
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)  # the virtual 8-device mesh is for in-proc tests
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "multihost_worker.py"),
             str(i), "2", str(port), table, convert_dir, out_dir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=150) for p in procs]
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, se.decode()[-3000:]

    results = []
    for i in range(2):
        with open(os.path.join(out_dir, f"result-{i}.json")) as f:
            results.append(json.load(f))

    # scan: the two hosts' partitions tile the table exactly
    assert all(r["count"] == 2 for r in results)
    assert results[0]["full_rows"] == 60
    assert results[0]["scan_rows"] + results[1]["scan_rows"] == 60
    ids = sorted(results[0]["scan_ids"] + results[1]["scan_ids"])
    assert ids == list(range(60))

    # checkpoint: all 4 parts exist, _last_checkpoint published once,
    # and a cold reader reconstructs from it
    from delta_tpu.log import checkpoints as ckpt_mod

    last = ckpt_mod.read_last_checkpoint(log.store, log.log_path)
    assert last is not None and last.parts == 4
    DeltaLog.clear_cache()
    snap = DeltaLog.for_table(table).update()
    assert snap.num_of_files == 6
    assert snap.segment.checkpoint_version == last.version

    # convert: both processes agree on the committed version; all files in
    assert results[0]["convert_version"] == results[1]["convert_version"]
    assert all(r["convert_files"] == 5 for r in results)
    DeltaLog.clear_cache()
    csnap = DeltaLog.for_table(convert_dir).update()
    t = sorted(
        __import__("delta_tpu.exec.scan", fromlist=["scan_to_table"])
        .scan_to_table(csnap).column("a").to_pylist()
    )
    assert t == list(range(20))


def _mk_dist_table(path: str, parts: int = 4, files_per: int = 3,
                   rows: int = 16) -> None:
    log = DeltaLog.for_table(path)
    for p in range(parts):
        for f in range(files_per):
            base = (p * files_per + f) * rows
            WriteIntoDelta(log, "append", pa.table({
                "id": np.arange(base, base + rows, dtype=np.int64),
                "part": pa.array([f"p{p}"] * rows),
                "v": np.arange(base, base + rows, dtype=np.float64),
            }), partition_columns=["part"]).run()


def _run_workers(tmp_path, table: str, mode: str, out_name: str,
                 extra_env=None):
    out_dir = str(tmp_path / out_name)
    os.makedirs(out_dir)
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               **(extra_env or {}))
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "multihost_worker.py"),
             str(i), "2", str(port), table, "-", out_dir, mode],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=150) for p in procs]
    results = {}
    for i in range(2):
        f = os.path.join(out_dir, f"result-{i}.json")
        if os.path.exists(f):
            with open(f) as fh:
                results[i] = json.load(fh)
    return procs, outs, results


def test_two_process_sharded_optimize_merge_identity(tmp_path):
    """2-process sharded execution over a shared table: each host commits
    its byte-weighted LPT slice of the OPTIMIZE groups, proc 0 runs a
    MERGE — and the end state is row-identical to the same
    OPTIMIZE+MERGE run single-process on a clone."""
    table = str(tmp_path / "table")
    solo = str(tmp_path / "solo")
    _mk_dist_table(table)
    _mk_dist_table(solo)

    procs, outs, results = _run_workers(tmp_path, table, "dist", "out")
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, se.decode()[-3000:]
    assert sorted(results) == [0, 1]

    # sharded scan: the two hosts' LPT slices tile the table exactly
    ids = sorted(results[0]["scan_ids"] + results[1]["scan_ids"])
    assert ids == list(range(192))

    # each host committed a disjoint slice of the 4 partition groups
    assert results[0]["optimize_groups"] + results[1]["optimize_groups"] == 4
    assert all(r["optimize_groups"] >= 1 for r in results.values())
    assert results[0]["optimize_version"] != results[1]["optimize_version"]
    assert all(r["shard_timings"] for r in results.values())

    # proc 0's MERGE updated/inserted
    assert results[0]["merge_updated"] == 2
    assert results[0]["merge_inserted"] == 1

    # single-process reference on the clone: identical final rows
    from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.exec.scan import scan_to_table

    slog = DeltaLog.for_table(solo)
    OptimizeCommand(slog, min_file_size=1 << 30).run()
    src = pa.table({
        "id": pa.array([3, 75, 1000], pa.int64()),
        "part": pa.array(["p0", "p3", "p0"]),
        "v": pa.array([-1.0, -2.0, -3.0]),
    })
    MergeIntoCommand(
        slog, src, "t.id = s.id",
        [MergeClause("update", assignments=None)],
        [MergeClause("insert", assignments=None)],
        source_alias="s", target_alias="t").run()
    DeltaLog.clear_cache()
    want = scan_to_table(DeltaLog.for_table(solo).update()).sort_by("id")
    got = scan_to_table(DeltaLog.for_table(table).update()).sort_by("id")
    assert got.select(["id", "part", "v"]).to_pylist() == \
        want.select(["id", "part", "v"]).to_pylist()
    # both workers read back the same converged state, and the file
    # topology matches the single-process reference exactly
    assert results[0]["final_ids"] == results[1]["final_ids"]
    solo_files = DeltaLog.for_table(solo).update().num_of_files
    assert results[0]["final_files"] == results[1]["final_files"] == solo_files


def _mk_zipf_table(path: str, parts: int = 4, files_per: int = 2) -> int:
    """Partitioned table with zipf-skewed partition bytes (partition p holds
    ~1/(p+1) of the head's rows) — the workload where per-shard skew
    dominates makespan and the straggler analysis has something to name."""
    log = DeltaLog.for_table(path)
    base = 0
    for p in range(parts):
        rows = max(256 // (p + 1), 16)
        for _f in range(files_per):
            WriteIntoDelta(log, "append", pa.table({
                "id": np.arange(base, base + rows, dtype=np.int64),
                "part": pa.array([f"p{p}"] * rows),
                "v": np.arange(base, base + rows, dtype=np.float64),
            }), partition_columns=["part"]).run()
            base += rows
    return base


def test_two_process_distributed_optimize_stitches_one_trace(tmp_path):
    """The tentpole acceptance: a 2-process distributed OPTIMIZE under a
    coordinator root span produces ONE stitched trace — every span in every
    process's spool carries the coordinator's trace_id, parents resolve into
    a single tree, the stitched Chrome-trace span count equals the sum of
    all spools, and analyze_trace names the straggler shard and its makespan
    delta vs the LPT byte-share prediction on a zipf-skewed table."""
    from delta_tpu.obs import trace_store
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf

    table = str(tmp_path / "table")
    _mk_zipf_table(table)
    trace_dir = str(tmp_path / "spool")
    os.makedirs(trace_dir)

    with conf.set_temporarily(**{"delta.tpu.trace.dir": trace_dir,
                                 "delta.tpu.trace.sampleRate": 1.0}):
        with telemetry.record_operation("delta.test.coordinator") as root:
            wire = telemetry.span_context(wire=True)
            assert wire is not None and wire.split("-")[1] == root.trace_id
            procs, outs, results = _run_workers(
                tmp_path, table, "dist", "out",
                extra_env={"DELTA_TPU_TRACEPARENT": wire,
                           "DELTA_TPU_TRACE_DIR": trace_dir})
    trace_store.reset()  # release the coordinator's spool handle
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, se.decode()[-3000:]
    assert sorted(results) == [0, 1]

    trace_id = root.trace_id
    assert len(trace_id) == 32

    # ONE trace: every spooled span in every process carries the
    # coordinator's trace id, and parents resolve into a single tree
    all_rows = trace_store.read_spools(trace_dir)
    assert {r["traceId"] for r in all_rows} == {trace_id}
    ids = {r["spanId"] for r in all_rows}
    orphans = [r for r in all_rows
               if r["parentId"] is not None and r["parentId"] not in ids]
    assert orphans == []
    roots = [r for r in all_rows if r["parentId"] is None]
    assert [r["op"] for r in roots] == ["delta.test.coordinator"]

    # stitched Chrome trace: span count == sum of both hosts' spools (plus
    # the coordinator's), three distinct process lanes
    trace = trace_store.stitch_trace(trace_dir, trace_id)
    rows = [r for r in trace["traceEvents"] if r.get("cat") == "delta"]
    assert len(rows) == len(all_rows)
    assert all(r["args"]["traceId"] == trace_id for r in rows)
    assert len({r["pid"] for r in rows}) == 3  # coordinator + 2 workers

    # straggler analysis: the sharded OPTIMIZE jobs name their slowest
    # shard and its delta vs the LPT-predicted byte share
    analysis = trace_store.analyze_trace(trace_dir, trace_id)
    assert analysis["rootOp"] == "delta.test.coordinator"
    assert analysis["spans"] == len(all_rows)
    assert analysis["criticalPath"][0]["op"] == "delta.test.coordinator"
    assert len(analysis["criticalPath"]) >= 2
    jobs = [j for j in analysis["jobs"] if j["label"] == "optimize"]
    assert len(jobs) == 2  # one sharded job per worker process
    assert {j["pid"] for j in jobs} == {r["pid"] for r in rows} - \
        {roots[0]["pid"]}
    sharded = [j for j in jobs if j["shards"]]
    assert sharded, "no pool-path OPTIMIZE job produced worker shards"
    for j in sharded:
        s = j["straggler"]
        assert s["busyUs"] == max(x["busyUs"] for x in j["shards"])
        assert s["busyUs"] - s["predictedUs"] == s["deltaUs"]
        assert j["lptBytes"] and j["skew"] >= 1.0
    assert analysis["straggler"] is not None


def test_two_process_optimize_survives_worker_crash(tmp_path):
    """SimulatedCrash of worker 1 mid-OPTIMIZE: the surviving host completes
    and commits its slice; the crashed host commits NOTHING (its half-done
    rewrite leaves only uncommitted orphan data files), and the log replays
    to a consistent snapshot with every original row intact."""
    table = str(tmp_path / "table")
    _mk_dist_table(table)
    snap0 = DeltaLog.for_table(table).update()
    v0, files0 = snap0.version, snap0.num_of_files

    procs, outs, results = _run_workers(tmp_path, table, "dist-crash", "out")
    assert procs[0].returncode == 0, outs[0][1].decode()[-3000:]
    assert procs[1].returncode != 0
    assert b"SimulatedCrash" in outs[1][1]
    assert 0 in results and 1 not in results  # proc 1 died before reporting

    # ledger reconciles: exactly the survivor's commit landed, all rows live
    DeltaLog.clear_cache()
    snap = DeltaLog.for_table(table).update()
    assert snap.version == v0 + 1 == results[0]["final_version"]
    from delta_tpu.exec.scan import scan_to_table

    t = scan_to_table(snap)
    assert sorted(t.column("id").to_pylist()) == list(range(192))
    # survivor compacted its slice: fewer files than before, more than the
    # fully-compacted 4 (the crashed host's slice is still un-compacted)
    assert 4 < snap.num_of_files < files0
    assert results[0]["final_files"] == snap.num_of_files


def test_two_process_crash_recovery_acceptance(tmp_path):
    """ISSUE 20 acceptance: a worker host is killed mid-OPTIMIZE after
    publishing its lease; the coordinator recovers the orphaned slice. The
    end state is row- AND topology-identical to a single-process run, every
    group was committed exactly once (disjoint remove sets across exactly
    two commits), and the stitched trace shows the recovery span."""
    import time

    from delta_tpu.obs import trace_store
    from delta_tpu.parallel import leases
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf

    table = str(tmp_path / "table")
    solo = str(tmp_path / "solo")
    _mk_dist_table(table)
    _mk_dist_table(solo)
    log_path = DeltaLog.for_table(table).log_path
    snap0 = DeltaLog.for_table(table).update()
    v0, files0 = snap0.version, snap0.num_of_files

    trace_dir = str(tmp_path / "spool")
    out_dir = str(tmp_path / "out")
    os.makedirs(trace_dir)
    os.makedirs(out_dir)

    def run_worker(i, extra_env=None):
        # only the traced phase (the coordinator) gets the spool dir —
        # phase 1 has no traceparent and would spool under its own trace id
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                   **(extra_env or {}))
        env.pop("XLA_FLAGS", None)
        p = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "tests", "multihost_worker.py"),
             str(i), "2", "0", table, "-", out_dir, "dist-recover"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        return p, p.communicate(timeout=150)

    # phase 1: host 1 dies mid-slice, leaving its lease orphaned
    p1, out1 = run_worker(1)
    assert p1.returncode != 0
    assert b"SimulatedCrash" in out1[1]
    orphans = leases.read_leases(log_path)
    assert len(orphans) == 1
    assert orphans[0][1]["proc"] == 1 and orphans[0][1]["txnId"]
    assert DeltaLog.for_table(table).update().version == v0  # no commit
    past = time.time() - 120  # the dead host's heartbeat goes stale
    os.utime(orphans[0][0], (past, past))

    # phase 2: the coordinator, under a traced root span
    with conf.set_temporarily(**{"delta.tpu.trace.dir": trace_dir,
                                 "delta.tpu.trace.sampleRate": 1.0}):
        with telemetry.record_operation("delta.test.recovery") as root:
            wire = telemetry.span_context(wire=True)
            p0, out0 = run_worker(
                0, extra_env={"DELTA_TPU_TRACEPARENT": wire,
                              "DELTA_TPU_TRACE_DIR": trace_dir})
    trace_store.reset()
    assert p0.returncode == 0, out0[1].decode()[-3000:]
    with open(os.path.join(out_dir, "result-0.json")) as f:
        result = json.load(f)

    # end state: row- and topology-identical to a single-process run
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.exec.scan import scan_to_table

    OptimizeCommand(DeltaLog.for_table(solo), min_file_size=1 << 30).run()
    DeltaLog.clear_cache()
    ssnap = DeltaLog.for_table(solo).update()
    want = sorted(scan_to_table(ssnap).column("id").to_pylist())
    assert result["final_ids"] == want == list(range(192))
    assert result["final_files"] == ssnap.num_of_files < files0

    # the worker recovered exactly one slice and cleared its lease
    assert result["recovered"] == 1
    assert result["leases_left"] == 0
    assert leases.read_leases(log_path) == []
    assert "dist.sliceRecovered" in result["dist_events"]
    assert "dist.sliceReconciled" not in result["dist_events"]

    # exactly one commit per group: two commits (coordinator's slice + the
    # recovery), whose remove sets are disjoint and tile the original files
    snap = DeltaLog.for_table(table).update()
    assert snap.version == v0 + 2 == result["final_version"]
    removed = []
    for v in (v0 + 1, v0 + 2):
        with open(os.path.join(log_path, f"{v:020d}.json")) as f:
            removed.append({json.loads(line)["remove"]["path"]
                            for line in f if '"remove"' in line})
    assert removed[0] & removed[1] == set()
    assert len(removed[0] | removed[1]) == files0

    # the stitched trace carries the recovery span under the one trace id
    rows = trace_store.read_spools(trace_dir)
    assert {r["traceId"] for r in rows} == {root.trace_id}
    recovery_spans = [r for r in rows if r["op"] == "delta.dist.sliceRecovery"]
    assert len(recovery_spans) == 1
    analysis = trace_store.analyze_trace(trace_dir, root.trace_id)
    [rec] = analysis["recoveries"]
    assert rec["outcome"] == "recovered"
    assert rec["proc"] == 1 and rec["groups"] >= 1


def test_vacuum_composes_with_scan_partitioning():
    """The same strided partitioner drives vacuum's delete fan-out and the
    distributed scan: for any (index, count) the slices tile the work list
    without overlap — the composition property the multi-host paths rely on."""
    items = [f"f{i}" for i in range(13)]
    for count in (1, 2, 3, 5):
        seen = []
        for index in range(count):
            seen += host_partition(items, index, count)
        assert sorted(seen) == sorted(items)
        # disjointness
        assert len(seen) == len(set(seen))
        for index in range(count):
            idx = host_shard_indices(len(items), index, count)
            assert idx == list(range(index, len(items), count))


def test_convert_fragment_exchange_empty_slice_and_token(tmp_path):
    """A host with an empty file slice publishes a schema-less fragment
    (fewer files than processes must not crash), and fragments are
    namespaced by a listing hash so a retry after the data changed cannot
    consume stale ones."""
    from delta_tpu.commands.convert import ConvertToDeltaCommand

    d = str(tmp_path / "plain")
    os.makedirs(d)
    pq.write_table(pa.table({"a": np.arange(3, dtype=np.int64)}),
                   os.path.join(d, "only.parquet"))
    log = DeltaLog.for_table(d)
    cmd = ConvertToDeltaCommand(log, collect_stats=True, distribute=True)
    files = cmd._list_parquet_files()
    assert len(files) == 1
    # "proc 1" has the empty slice: publish its (schema-less) fragment
    m1, f1 = cmd._exchange_fragments(1, 2, None, [], files)
    assert m1 is None and f1 == []
    # "proc 0" computed the file and gathers both fragments
    abs_p = os.path.join(d, files[0][0])
    schema = pq.ParquetFile(abs_p).schema_arrow
    adds0 = [{"i": 0, "rel": files[0][0], "size": files[0][1],
              "mtime": files[0][2], "stats": None}]
    merged, all_adds = cmd._exchange_fragments(0, 2, schema, adds0, files)
    assert merged is not None and len(all_adds) == 1
    # token changes when the listing changes (stale fragments unreachable)
    t1 = cmd._listing_token(files)
    pq.write_table(pa.table({"a": np.arange(2, dtype=np.int64)}),
                   os.path.join(d, "second.parquet"))
    t2 = cmd._listing_token(cmd._list_parquet_files())
    assert t1 != t2
