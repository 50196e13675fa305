"""Workload journal + layout advisor (`delta_tpu/obs/journal.py`,
`delta_tpu/obs/advisor.py`): persistent per-table JSONL segments recording
scans/commits/DML routing, the predicate fingerprint, segment
rotation/sweep bounds, blackout inertness, the advisor's evidence-backed
recommendations (and their survival across a process "restart"), the HTTP
``/advisor`` route, the flight-recorder embeds, and the offline dump tool.
"""
import json
import os
import threading

import pyarrow as pa
import pytest

from delta_tpu.api.tables import DeltaTable
from delta_tpu.log.deltalog import DeltaLog
from delta_tpu.obs import journal
from delta_tpu.obs.advisor import advise
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_journal():
    journal.reset()
    telemetry.reset_all()
    yield
    journal.reset()
    telemetry.clear_events()


def _ids(n, extra_col=True):
    cols = {"id": pa.array(range(n), pa.int64())}
    if extra_col:
        cols["v"] = pa.array(range(n), pa.int64())
    return pa.table(cols)


def _dir_bytes(jdir):
    return sum(os.path.getsize(os.path.join(jdir, f))
               for f in os.listdir(jdir))


# -- recording hooks ---------------------------------------------------------


def test_scan_entries_carry_report_and_fingerprint(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    t.to_arrow(filters=["v = 7"])
    t.to_arrow(filters=["v > 3", "id = 1"])
    journal.flush()
    scans = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    assert len(scans) == 2
    first = scans[0]
    assert first["report"]["filesTotal"] == 1
    assert first["report"]["rowsOut"] == 1
    assert first["fingerprint"]["columns"] == ["v"]
    assert first["fingerprint"]["key"] == "eq(v,?)"
    [c] = first["fingerprint"]["conjuncts"]
    assert c["prunable"] is True and c["partition"] is False
    second = scans[1]
    assert second["fingerprint"]["columns"] == ["id", "v"]
    assert set(second["fingerprint"]["prunableColumns"]) == {"id", "v"}
    assert first.get("ts")


def test_fingerprint_normalizes_literals_and_splits_residual():
    from delta_tpu.expr.parser import parse_predicate

    fp1 = journal.predicate_fingerprint(parse_predicate("v = 5"))
    fp2 = journal.predicate_fingerprint(parse_predicate("v = 900"))
    assert fp1["key"] == fp2["key"] == "eq(v,?)"
    # arithmetic over columns is NOT min/max-evaluable without rewrite
    # synthesis: it lands in the residual split with its shape preserved
    fp3 = journal.predicate_fingerprint(
        parse_predicate("price * qty > 1000 AND id = 3"))
    assert fp3["prunableColumns"] == ["id"]
    assert set(fp3["residualColumns"]) == {"price", "qty"}
    shapes = {c["shape"] for c in fp3["conjuncts"]}
    assert "gt(mul(price,qty),?)" in shapes and "eq(id,?)" in shapes
    # partition-only conjuncts are flagged
    fp4 = journal.predicate_fingerprint(
        parse_predicate("p = 'x'"), partition_cols=["p"])
    assert fp4["conjuncts"][0]["partition"] is True
    assert journal.predicate_fingerprint(None) is None


def test_fingerprint_or_of_residual_shapes_is_not_prunable():
    """skipping_predicate recurses through OR, so an unsupported
    disjunction rewrites to Or(NULL, NULL) — NOT a bare Literal(None) root.
    Three-valued logic: an OR with an unknowable branch can never exclude a
    row group, so the conjunct must land in the residual split (else the
    advisor blames layout for a shape problem and recommends a Z-ORDER
    that cannot help)."""
    from delta_tpu.expr.parser import parse_predicate

    fp = journal.predicate_fingerprint(
        parse_predicate("a + b = 1 OR c + d = 2"))
    assert fp["conjuncts"][0]["prunable"] is False
    assert fp["prunableColumns"] == []
    assert set(fp["residualColumns"]) == {"a", "b", "c", "d"}
    # an OR of two genuinely evaluable comparisons CAN exclude
    fp2 = journal.predicate_fingerprint(parse_predicate("v = 1 OR v = 2"))
    assert fp2["conjuncts"][0]["prunable"] is True
    # ...but one unknowable branch poisons the whole OR
    fp3 = journal.predicate_fingerprint(
        parse_predicate("v = 1 OR a + b = 2"))
    assert fp3["conjuncts"][0]["prunable"] is False
    # AND excludes through either side, even nested inside the conjunct
    fp4 = journal.predicate_fingerprint(
        parse_predicate("(v = 1 AND a + b = 2) OR v = 3"))
    assert fp4["conjuncts"][0]["prunable"] is True


def test_commit_and_dml_entries(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    t.update({"v": "v + 1"}, "id = 3")
    t.delete("id = 7")
    journal.flush()
    entries = journal.read_entries(t.delta_log.log_path)
    commits = [e for e in entries if e["kind"] == "commit"]
    assert len(commits) == 3  # create + update + delete
    assert all(e["outcome"] == "committed" for e in commits)
    assert commits[1]["stats"]["operation"] == "UPDATE"
    assert commits[1]["stats"]["attempts"] == 1
    dmls = [e for e in entries if e["kind"] == "dml"]
    assert [e["op"] for e in dmls] == ["update", "delete"]
    assert dmls[0]["mode"] == "rewrite"
    assert dmls[0]["metrics"]["numUpdatedRows"] == 1
    assert dmls[0]["version"] == 1


def test_conflict_commits_journaled(tmp_table):
    """An aborted commit (genuine logical conflict) still leaves a journal
    entry — contention analysis needs the failures."""
    from delta_tpu.commands import operations as ops
    from delta_tpu.utils import errors

    t = DeltaTable.create(tmp_table, data=_ids(20))
    log = t.delta_log
    txn = log.start_transaction()
    txn.read_whole_table()
    removes = [f.remove() for f in txn.snapshot.all_files]
    # interleaving writer deletes the same files first -> our delete hits
    # a concurrent-delete-delete conflict on retry
    t.delete()
    with pytest.raises(errors.DeltaConcurrentModificationException):
        txn.commit(removes, ops.Delete(predicate=[]))
    journal.flush()
    commits = journal.read_entries(log.log_path, kinds=["commit"])
    conflicted = [e for e in commits if e["outcome"] == "conflict"]
    assert len(conflicted) == 1
    assert conflicted[0]["stats"]["attempts"] >= 1


def test_merge_dml_entry_carries_decision_and_audit(tmp_table):
    t = DeltaTable.create(tmp_table, data=pa.table({
        "id": pa.array(range(100), pa.int64()),
        "x": pa.array(range(100), pa.int64()),
    }))
    src = pa.table({"id": pa.array([3, 500], pa.int64()),
                    "x": pa.array([-1, -2], pa.int64())})
    (t.alias("t").merge(src, "t.id = s.id", source_alias="s")
     .when_matched_update_all().when_not_matched_insert_all().execute())
    journal.flush()
    entries = journal.read_entries(t.delta_log.log_path)
    [merge] = [e for e in entries if e["kind"] == "dml" and e["op"] == "merge"]
    assert merge["decision"]  # host / resident / device-cold / ...
    if merge["audit"] is not None:
        assert isinstance(merge["audit"]["miss"], bool)
        assert merge["audit"]["actualMs"] >= 0
    # the router audit itself is journaled too (hook in obs/router_audit)
    routers = [e for e in entries if e["kind"] == "router"]
    assert any(e["audit"]["op"] == "merge.join" for e in routers)


# -- blackout + enablement ---------------------------------------------------


def test_blackout_writes_zero_bytes_and_advise_reports_no_history(tmp_table):
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False):
        t = DeltaTable.create(tmp_table, data=_ids(50))
        t.to_arrow(filters=["v = 1"])
        t.update({"v": "v + 1"}, "id = 3")
        journal.flush()
        jdir = journal.journal_dir(t.delta_log.log_path)
        assert not os.path.isdir(jdir), "blackout must write ZERO journal bytes"
        rep = t.advise()
        assert rep.status == "no history"
        assert rep.recommendations == []
        assert "blackout" in rep.facts["reason"] or "disabled" in rep.facts["reason"]
    # journal.enabled=false behaves identically with telemetry on
    with conf.set_temporarily(delta__tpu__journal__enabled=False):
        t.to_arrow(filters=["v = 2"])
        journal.flush()
        assert not os.path.isdir(jdir)
        assert t.advise().status == "no history"


def test_object_store_paths_never_journal():
    assert journal.enabled("s3://bucket/tbl/_delta_log") is False
    assert journal.enabled("/local/tbl/_delta_log") is True
    # record_* are no-ops, not errors, for remote tables
    journal.record_dml("s3://bucket/tbl/_delta_log", "merge", decision="host")
    assert journal.flush() == 0


# -- segment rotation + sweep ------------------------------------------------


def test_segment_rotation_and_sweep_bounds(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    log_path = t.delta_log.log_path
    jdir = journal.journal_dir(log_path)
    with conf.set_temporarily(**{
        "delta.tpu.journal.segmentBytes": 400,
        "delta.tpu.journal.maxBytes": 2000,
    }):
        for i in range(60):
            journal.record_dml(log_path, "update", mode="dv",
                               metrics={"numUpdatedRows": i})
            journal.flush(log_path)  # one write per entry -> forced rotations
        segs = sorted(os.listdir(jdir))
        assert len(segs) > 1, "segmentBytes bound must rotate segments"
        # every closed segment respects the size bound (+ one entry slop)
        for s in segs[:-1]:
            assert os.path.getsize(os.path.join(jdir, s)) <= 600
        assert _dir_bytes(jdir) <= 2000 + 600, "maxBytes sweep must bound the dir"
        assert telemetry.counters("journal.segments.swept")[
            "journal.segments.swept"] >= 1
    # entries survive in the retained tail, oldest swept first
    entries = journal.read_entries(log_path, kinds=["dml"])
    assert entries, "sweep must never empty the journal"
    assert entries[-1]["metrics"]["numUpdatedRows"] == 59


def test_sweep_drops_aged_segments(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.record_dml(log_path, "update", mode="dv", metrics={})
    journal.flush(log_path)
    jdir = journal.journal_dir(log_path)
    [seg] = [n for n in os.listdir(jdir) if n.endswith(".jsonl")]
    old = os.path.join(jdir, "journal-0000000000001-1-000001.jsonl")
    with open(old, "w", encoding="utf-8") as f:
        f.write('{"kind":"dml","op":"old"}\n')
    past = 1_000_000  # epoch 1970: far past any retention window
    os.utime(old, (past, past))
    assert journal.sweep(jdir) == 1
    assert not os.path.exists(old)
    assert os.path.exists(os.path.join(jdir, seg))


def test_read_entries_limit_zero_returns_nothing(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    journal.flush()
    log_path = t.delta_log.log_path
    assert journal.read_entries(log_path, limit=0) == []
    assert len(journal.read_entries(log_path, limit=1)) == 1
    assert journal.read_entries(log_path, limit=None)


def test_partition_survival_counts_perfect_pruning(tmp_table):
    """filesAfterPartition=0 is perfect pruning (survival 0.0), not missing
    data — the falsy-zero regression."""
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    journal._record(log_path, {
        "kind": "scan",
        "report": {"filesTotal": 100, "filesAfterPartition": 0},
    })
    journal.flush()
    rep = advise(tmp_table)
    assert rep.facts["partition"]["meanPartitionSurvival"] == 0.0


def test_retry_fraction_counts_each_commit_once(tmp_table):
    """A conflict entry that also retried must not double-count toward the
    contention fraction."""
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    for i in range(10):
        if i < 3:  # conflicted AND retried: one contended commit, not two
            journal.record_commit(log_path, {"attempts": 2}, outcome="conflict")
        else:
            journal.record_commit(log_path, {"attempts": 1})
    rep = advise(tmp_table)
    cf = rep.facts["commits"]
    # 3 contended of 10 synthetic + 1 real create commit
    assert cf["retryFraction"] == pytest.approx(3 / 11, abs=1e-4)


def test_cleanup_sweeps_journal_even_when_disabled(tmp_table):
    """A table that STOPPED journaling still sheds its history through
    metadata cleanup."""
    from delta_tpu.log.cleanup import cleanup_expired_logs

    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    jdir = journal.journal_dir(log_path)
    old = os.path.join(jdir, "journal-0000000000001-1-000001.jsonl")
    with open(old, "w", encoding="utf-8") as f:
        f.write('{"kind":"dml","op":"ancient"}\n')
    os.utime(old, (1_000_000, 1_000_000))
    journal.reset()
    with conf.set_temporarily(delta__tpu__journal__enabled=False):
        cleanup_expired_logs(t.delta_log, t.delta_log.update())
    assert not os.path.exists(old)


def test_read_entries_skips_torn_lines(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.record_dml(log_path, "update", mode="dv", metrics={})
    journal.flush(log_path)
    jdir = journal.journal_dir(log_path)
    [seg] = [n for n in os.listdir(jdir) if n.endswith(".jsonl")]
    before = len(journal.read_entries(log_path))
    with open(os.path.join(jdir, seg), "a", encoding="utf-8") as f:
        f.write('{"kind":"dml","truncated')  # torn tail write
    entries = journal.read_entries(log_path)
    assert len(entries) == before  # the torn line is skipped, not fatal


def test_buffer_cap_drops_not_grows(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    # fill past the cap without flushing: drops are counted, memory bounded
    with conf.set_temporarily(**{"delta.tpu.journal.flushEntries": 10 ** 9,
                                 "delta.tpu.journal.flushIntervalMs": 10 ** 9}):
        for i in range(journal.MAX_BUFFERED + 50):
            journal.record_dml(log_path, "update", mode="dv", metrics={})
    assert telemetry.counters("journal.entriesDropped")[
        "journal.entriesDropped"] == 50
    assert journal.flush(log_path) == journal.MAX_BUFFERED


def test_concurrent_recording_loses_nothing(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    N, K = 8, 40

    def worker(w):
        for i in range(K):
            journal.record_dml(log_path, "update", mode="dv",
                               metrics={"w": w, "i": i})

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(N)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    journal.flush()
    dmls = journal.read_entries(log_path, kinds=["dml"])
    assert len(dmls) == N * K
    seen = {(e["metrics"]["w"], e["metrics"]["i"]) for e in dmls}
    assert len(seen) == N * K


# -- advisor -----------------------------------------------------------------


def _skewed_workload(path, scans=6):
    """The acceptance shape: a table whose queries repeatedly filter on a
    non-layout column where pruning never fires (wide-range values in every
    file — min/max stats exclude nothing)."""
    import numpy as np

    rng = np.random.RandomState(3)
    t = DeltaTable.create(path, data=pa.table({
        "id": pa.array(range(2000), pa.int64()),
        # every file spans the whole value domain -> stats never exclude
        "v": pa.array(rng.permutation(2000).astype("int64")),
    }))
    t.write(pa.table({
        "id": pa.array(range(2000, 4000), pa.int64()),
        "v": pa.array(rng.permutation(2000).astype("int64")),
    }), mode="append")
    for i in range(scans):
        t.to_arrow(filters=[f"v = {i * 7}"])
    return t


def test_advisor_recommends_zorder_with_cited_evidence(tmp_table):
    t = _skewed_workload(tmp_table)
    rep = t.advise()
    assert rep.status == "ok"
    assert rep.entries > 0
    zorder = [r for r in rep.recommendations if r.kind == "ZORDER"]
    assert zorder, f"expected a ZORDER rec, got {rep.recommendations}"
    top = zorder[0]
    assert top.target == "v"
    assert top.evidence["filterCount"] == 6
    assert top.evidence["pruningMissRate"] == 1.0
    assert "execute_z_order_by('v')" in top.action
    # ranked first: the strongest evidence leads
    assert rep.recommendations[0].kind == "ZORDER"
    # facts cite the never-pruned fingerprint with the layout reason
    [nv] = [g for g in rep.facts["neverPruned"] if g["columns"] == ["v"]]
    assert nv["scans"] == 6 and nv["prunable"] is True
    assert "layout" in nv["reason"]
    json.dumps(rep.to_dict())  # JSON-able end to end


def test_advisor_recommendation_survives_process_restart(tmp_table):
    """Acceptance: the journal re-reads from disk by a fresh DeltaLog —
    in-memory state dropped, caches cleared, same recommendation."""
    _skewed_workload(tmp_table)
    journal.flush()
    journal.reset()          # forget every in-memory buffer/segment handle
    DeltaLog.clear_cache()   # fresh DeltaLog on next resolution
    rep = advise(tmp_table)
    assert rep.status == "ok"
    top = [r for r in rep.recommendations if r.kind == "ZORDER"][0]
    assert top.target == "v"
    assert top.evidence["filterCount"] == 6
    assert top.evidence["pruningMissRate"] == 1.0


def test_advisor_no_zorder_when_pruning_works(tmp_table):
    """Sorted data prunes (files exclude by min/max): no ZORDER rec — the
    advisor must not recommend re-layout for a layout that works."""
    t = DeltaTable.create(tmp_table, data=pa.table({
        "id": pa.array(range(2000), pa.int64()),
        "v": pa.array(range(2000), pa.int64()),   # sorted: tight per-file stats
    }))
    t.write(pa.table({
        "id": pa.array(range(2000, 4000), pa.int64()),
        "v": pa.array(range(2000, 4000), pa.int64()),
    }), mode="append")
    for i in range(6):
        t.to_arrow(filters=[f"v = {i * 7}"])  # hits file 1, file 2 pruned
    rep = t.advise()
    assert rep.status == "ok"
    assert not [r for r in rep.recommendations if r.kind == "ZORDER"]
    assert rep.facts["columns"]["v"]["missRate"] == 0.0


def test_advisor_flags_residual_only_shapes(tmp_table):
    """neverPruned splits by reason: a shape predicate synthesis can lower
    but that never excluded anything is 'synthesizedLayout' (clustering
    WOULD help it now); one synthesis has no sound rewrite for (division
    by a zero-crossing column interval) stays 'shape'."""
    t = DeltaTable.create(tmp_table, data=pa.table({
        "price": pa.array([float(i) for i in range(100)], pa.float64()),
        "qty": pa.array(range(100), pa.int64()),
    }))
    for _ in range(3):
        t.to_arrow(filters=["price * qty > 1000"])
        t.to_arrow(filters=["qty / price > 2"])
    rep = t.advise()
    [g] = [g for g in rep.facts["neverPruned"]
           if g["fingerprint"].startswith("gt(mul")]
    assert g["prunable"] is True
    assert g["reason"].startswith("synthesizedLayout")
    [g2] = [g2 for g2 in rep.facts["neverPruned"]
            if g2["fingerprint"].startswith("gt(div")]
    assert g2["prunable"] is False
    assert g2["reason"].startswith("shape")


def test_row_group_facts_ignore_unpredicated_scans(tmp_table):
    """``rowGroupsTotal`` is populated only for predicated scans (footers
    are consulted only under a predicate/position hint) — unfiltered
    full-table scans must not dilute rowGroupsPerScannedFile toward 0 and
    fabricate a ROW_GROUP_SIZE recommendation."""
    from delta_tpu.expr.parser import parse_predicate

    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    for _ in range(10):  # full scans: footers untouched
        journal.record_scan(log_path, report_dict={
            "filesScanned": 10, "rowGroupsTotal": 0})
    for _ in range(4):   # predicated, 2 row groups per file, never pruned
        journal.record_scan(log_path, report_dict={
            "filesScanned": 10, "rowGroupsTotal": 20,
            "filesPruned": 0, "rowGroupsPruned": 0},
            predicate=parse_predicate("v = 1"))
    rep = advise(tmp_table)
    rgf = rep.facts["rowGroups"]
    assert rgf["rowGroupsPerScannedFile"] == 2.0
    assert rgf["filesScanned"] == 40
    assert not [r for r in rep.recommendations if r.kind == "ROW_GROUP_SIZE"]


def test_sweep_ages_out_the_newest_segment(tmp_table):
    """Age expiry reaches the NEWEST segment too — a table that stopped
    journaling must shed its final segment through the cleanup sweep —
    while this process's own active segment stays exempt."""
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    jdir = journal.journal_dir(log_path)
    [seg] = [n for n in os.listdir(jdir) if n.endswith(".jsonl")]
    past = (1_000_000, 1_000_000)
    os.utime(os.path.join(jdir, seg), past)
    # the segment is this process's active file: exempt even when stale
    assert journal.sweep(jdir) == 0
    assert os.path.exists(os.path.join(jdir, seg))
    # a fresh process (no active handle) sweeps it
    journal.reset()
    assert journal.sweep(jdir) == 1
    assert not os.path.exists(os.path.join(jdir, seg))


def test_read_entries_sorts_by_timestamp_across_segments(tmp_table):
    """Two processes journaling the same table interleave in time while
    each appends to its own active segment — segment-name order alone
    would time-scramble the advisor's 'recent window' (limit / recent-half
    trends). Entries stable-sort by their recorded ts."""
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    jdir = journal.journal_dir(log_path)
    # simulate process A's long-lived segment (name sorts FIRST) holding
    # entries written both before and after process B's whole segment
    with open(os.path.join(jdir, "journal-0000000000001-1-000001.jsonl"),
              "w", encoding="utf-8") as f:
        f.write('{"kind":"dml","op":"a-early","ts":1000}\n')
        f.write('{"kind":"dml","op":"a-late","ts":4000}\n')
    with open(os.path.join(jdir, "journal-0000000000002-2-000001.jsonl"),
              "w", encoding="utf-8") as f:
        f.write('{"kind":"dml","op":"b-mid","ts":2000}\n')
    entries = journal.read_entries(log_path, kinds=["dml"])
    assert [e["op"] for e in entries] == ["a-early", "b-mid", "a-late"]
    # the recent window is genuinely recent
    assert [e["op"] for e in journal.read_entries(
        log_path, kinds=["dml"], limit=1)] == ["a-late"]


def test_advisor_zorder_not_masked_by_partition_pruning(tmp_table):
    """``filesPruned`` counts BOTH pruning tiers — on a partitioned table
    every scan partition-prunes something, which must not mask a data
    column whose min/max stats never exclude anything (the headline
    acceptance scenario on a partitioned table)."""
    from delta_tpu.expr.parser import parse_predicate

    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    for _ in range(4):  # partition tier halves the files; stats tier: nothing
        journal.record_scan(log_path, report_dict={
            "filesTotal": 10, "filesAfterPartition": 5, "filesScanned": 5,
            "rowGroupsTotal": 5, "rowGroupsPruned": 0,
            "rowGroupsLateSkipped": 0},
            predicate=parse_predicate("date = 1 AND v = 2"),
            partition_cols=["date"])
    rep = advise(tmp_table)
    assert rep.facts["columns"]["v"]["missRate"] == 1.0
    assert [r for r in rep.recommendations
            if r.kind == "ZORDER" and r.target == "v"]
    # ...but the stats tier firing DOES count as pruned
    journal.record_scan(log_path, report_dict={
        "filesTotal": 10, "filesAfterPartition": 5, "filesScanned": 2,
        "rowGroupsTotal": 2},
        predicate=parse_predicate("date = 1 AND v = 2"),
        partition_cols=["date"])
    rep = advise(tmp_table)
    assert rep.facts["columns"]["v"]["missRate"] < 1.0


def test_never_pruned_partition_filter_gets_partition_reason(tmp_table):
    """A pure partition filter that never excludes a partition IS pushed
    down — the reason must point at value distribution, not clustering or
    rewrite synthesis."""
    from delta_tpu.expr.parser import parse_predicate

    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    for _ in range(3):
        journal.record_scan(log_path, report_dict={
            "filesTotal": 4, "filesAfterPartition": 4, "filesScanned": 4},
            predicate=parse_predicate("region = 'eu'"),
            partition_cols=["region"])
    rep = advise(tmp_table)
    [g] = [g for g in rep.facts["neverPruned"] if g["columns"] == ["region"]]
    assert g["partition"] is True
    assert g["reason"].startswith("partition:")
    # and no ZORDER rec for a column that's already the partition layout
    assert not [r for r in rep.recommendations if r.kind == "ZORDER"]


def test_sweep_size_pressure_spares_each_pids_newest_segment(tmp_table):
    """Segment names embed the creating pid and a process appends only to
    its newest segment — size pressure must never delete a concurrent
    writer's possibly-active file, only settled (non-newest-per-pid)
    segments."""
    t = DeltaTable.create(tmp_table, data=_ids(10))
    jdir = journal.journal_dir(t.delta_log.log_path)
    journal.reset()  # no in-process active handle
    os.makedirs(jdir, exist_ok=True)
    line = json.dumps({"kind": "dml", "op": "x", "ts": 1}) + "\n"
    segs = ["journal-0000000000001-111-000001.jsonl",
            "journal-0000000000002-111-000002.jsonl",
            "journal-0000000000003-222-000001.jsonl"]
    for n in segs:
        with open(os.path.join(jdir, n), "w", encoding="utf-8") as f:
            f.write(line * 10)
    with conf.set_temporarily(**{"delta.tpu.journal.maxBytes": 1}):
        assert journal.sweep(jdir) == 1
    left = sorted(n for n in os.listdir(jdir) if n.endswith(".jsonl"))
    # pid 111's older segment swept; each pid's newest survives
    assert left == [segs[1], segs[2]]


def test_advisor_commit_contention_recommendation(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    ts0 = 1_700_000_000_000
    for i in range(12):
        journal.record_commit(log_path, {
            "operation": "WRITE", "attempts": 3 if i % 2 else 1,
            "commitVersion": i,
        })
    # pin timestamps into two 60s windows for the window detector
    journal.flush()
    entries = journal.read_entries(log_path, kinds=["commit"])
    assert len(entries) >= 12
    rep = advise(tmp_table)
    cf = rep.facts["commits"]
    assert cf["retried"] == 6
    assert cf["retryFraction"] >= 0.2
    [rec] = [r for r in rep.recommendations if r.kind == "COMMIT_CONTENTION"]
    assert rec.evidence["commits"] == cf["commits"]
    assert "group commit" in rec.action


def test_advisor_calibration_and_hbm_recommendations(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    for i in range(6):
        journal.record_router(log_path, {
            "op": "merge.join", "decision": "host", "miss": i % 2 == 0,
            "predictedMs": {"host": 1.0}, "actualMs": 2.0,
        })
        journal.record_dml(log_path, "merge", decision="device-cold",
                           router={}, audit=None)
    rep = advise(tmp_table)
    kinds = {r.kind: r for r in rep.recommendations}
    assert "CALIBRATION" in kinds
    assert kinds["CALIBRATION"].evidence["missRate"] == 0.5
    assert "HBM_BUDGET" in kinds
    assert kinds["HBM_BUDGET"].evidence["coldDeviceMerges"] == 6
    assert rep.facts["keyCache"]["hitRate"] == 0.0


def test_advisor_empty_table_no_history(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(5))
    # nothing journaled for a DIFFERENT table path
    other = tmp_table + "_other"
    DeltaTable.create(other, data=_ids(5))
    journal.reset()
    import shutil

    shutil.rmtree(journal.journal_dir(
        DeltaTable.for_path(other).delta_log.log_path), ignore_errors=True)
    rep = advise(other)
    assert rep.status == "no history"
    assert rep.entries == 0
    assert rep.recommendations == []


# -- surfaces: doctor cross-link, HTTP route, dump tool, flight recorder -----


def test_doctor_report_cross_links_advisor(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(10))
    d = t.doctor().to_dict()
    assert "advise" in d["advisor"] and "/advisor" in d["advisor"]
    ad = t.advise().to_dict()
    assert "doctor" in ad["doctor"].lower()


def test_advisor_http_route(tmp_table):
    import urllib.request

    from delta_tpu.obs.server import ObsServer

    t = _skewed_workload(tmp_table, scans=4)
    journal.flush()
    server = ObsServer(0)
    try:
        host, port = server.address
        url = f"http://{host}:{port}/advisor?path={urllib.request.quote(tmp_table)}"
        with urllib.request.urlopen(url) as resp:
            assert resp.status == 200
            served = json.loads(resp.read())
        assert served["status"] == "ok"
        assert any(r["kind"] == "ZORDER" and r["target"] == "v"
                   for r in served["recommendations"])
        # missing ?path= is a 400, and the route is advertised on 404s
        req = urllib.request.Request(f"http://{host}:{port}/advisor")
        try:
            urllib.request.urlopen(req)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
        try:
            urllib.request.urlopen(f"http://{host}:{port}/nope")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert "/advisor" in json.loads(e.read())["routes"]
    finally:
        server.stop()


def test_journal_dump_tool(tmp_table, capsys):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.journal_dump import main

    t = DeltaTable.create(tmp_table, data=_ids(20))
    t.to_arrow(filters=["v = 3"])
    journal.flush()
    assert main([tmp_table, "--kind", "scan"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1 and lines[0]["kind"] == "scan"
    assert main([tmp_table, "--summary"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["segments"] >= 1 and summary["byKind"]["scan"] == 1
    assert main([tmp_table, "--advise"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["status"] == "ok"


def test_flight_recorder_embeds_scan_report_and_last_audit(tmp_path):
    """Satellite: incidents show WHAT the query was doing — the in-flight
    ScanReport and the last router-audit record ride into the file."""
    from delta_tpu.obs import flight_recorder, router_audit, scan_report

    router_audit.clear_audits()
    router_audit.record_audit("merge.join", "/t", "host",
                              {"host": 0.1, "device": 0.5}, 0.2,
                              units={"targetRows": 10})
    flight_recorder.install()
    inc_dir = str(tmp_path / "incidents")
    with conf.set_temporarily(**{"delta.tpu.obs.incidentDir": inc_dir}):
        token = scan_report.start_report("/t", 3)
        scan_report.contribute(bytes_read=123)
        try:
            with pytest.raises(ValueError):
                with telemetry.record_operation("delta.scan", path="/t"):
                    raise ValueError("mid-scan failure")
        finally:
            scan_report.finish_report(token, completed=False)
    [f] = flight_recorder.incident_files(inc_dir)
    incident = json.loads(open(f).read())
    assert incident["scanReport"]["bytesRead"] == 123
    assert incident["scanReport"]["version"] == 3
    assert incident["routerAudit"]["op"] == "merge.join"
    assert incident["routerAudit"]["decision"] == "host"
    router_audit.clear_audits()


def test_metrics_snapshot_carries_journal_counters(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(30))
    t.to_arrow(filters=["v = 1"])
    journal.flush()
    snap = telemetry.metrics_snapshot()
    assert snap["counters"].get("journal.entries", 0) >= 1
    advise(tmp_table)
    snap = telemetry.metrics_snapshot()
    assert snap["counters"].get("advisor.runs", 0) >= 1


# -- review-fix regressions --------------------------------------------------


def test_advisor_empty_table_scans_do_not_fabricate_zorder(tmp_table):
    """Scans over a zero-file table carry no pruning evidence: pruning
    could not possibly have fired, so repeated filters against an empty
    table must not manufacture a 100%-miss ZORDER/PARTITION case."""
    from delta_tpu.schema.types import LongType, StructType

    t = DeltaTable.create(tmp_table, StructType().add("v", LongType()))
    for i in range(4):
        t.to_arrow(filters=[f"v = {i}"])
    journal.flush()
    scans = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    assert scans and all(
        (s["report"].get("filesTotal") or 0) == 0 for s in scans)
    rep = t.advise()
    assert not [r for r in rep.recommendations
                if r.kind in ("ZORDER", "PARTITION")], rep.recommendations
    assert not rep.facts.get("neverPruned")


def test_record_hooks_never_raise_when_writer_cannot_start(
        tmp_table, monkeypatch):
    """The commit hook runs after version N is durably on disk and the
    conflict hook sits on the exception path — a journaling failure (e.g.
    Thread.start at interpreter shutdown) must stay invisible to the
    caller; the buffered entry still lands on the next flush."""
    t = DeltaTable.create(tmp_table, data=_ids(5))
    journal.flush()

    def boom():
        raise RuntimeError("can't start new thread")

    monkeypatch.setattr(journal, "_ensure_writer", boom)
    journal.record_commit(t.delta_log.log_path, {"attempts": 1},
                          outcome="committed")  # must not raise
    monkeypatch.undo()
    journal.flush()
    commits = journal.read_entries(t.delta_log.log_path, kinds=["commit"])
    assert any(c["stats"].get("attempts") == 1 for c in commits)


def test_buffered_entries_flush_at_interpreter_exit(tmp_table):
    """A short-lived process (scan + exit inside the flush interval) must
    not lose its buffered entries with the daemon writer thread — the
    atexit drain writes them synchronously."""
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {repo!r})
        import pyarrow as pa
        from delta_tpu.api.tables import DeltaTable
        from delta_tpu.utils.config import conf

        conf.set("delta.tpu.journal.flushIntervalMs", 60000)
        conf.set("delta.tpu.journal.flushEntries", 1000)
        t = DeltaTable.create({tmp_table!r}, data=pa.table(
            {{"id": pa.array(range(10), pa.int64())}}))
        t.to_arrow(filters=["id = 3"])
        # exit WITHOUT flushing: nothing aged, nothing hit the count
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300)
    entries = journal.read_entries(os.path.join(tmp_table, "_delta_log"),
                                   kinds=["scan"])
    assert entries, "atexit drain lost the buffered scan entry"
    assert entries[0]["fingerprint"]["key"] == "eq(id,?)"


def test_sweep_size_pressure_reclaims_grace_stale_pid_segments(tmp_table):
    """The newest-per-pid exemption only holds while a segment is recently
    written (a live writer touches its file at least every flush interval)
    — one immune segment per dead CI/cron pid would make the maxBytes cap
    unenforceable. Grace-stale segments yield to size pressure."""
    import time as time_mod

    t = DeltaTable.create(tmp_table, data=_ids(10))
    jdir = journal.journal_dir(t.delta_log.log_path)
    journal.reset()  # no in-process active handle
    os.makedirs(jdir, exist_ok=True)
    line = json.dumps({"kind": "dml", "op": "x", "ts": 1}) + "\n"
    stale = time_mod.time() - 3600  # long past any grace window
    segs = ["journal-0000000000001-111-000001.jsonl",
            "journal-0000000000002-222-000001.jsonl",
            "journal-0000000000003-333-000001.jsonl"]
    for n in segs:
        p = os.path.join(jdir, n)
        with open(p, "w", encoding="utf-8") as f:
            f.write(line * 10)
        os.utime(p, (stale, stale))
    # freshly-written newest-per-pid segment: spared even under pressure
    fresh = os.path.join(jdir, "journal-0000000000004-444-000001.jsonl")
    with open(fresh, "w", encoding="utf-8") as f:
        f.write(line * 10)
    with conf.set_temporarily(**{"delta.tpu.journal.maxBytes": 1}):
        assert journal.sweep(jdir) == 3
    left = sorted(n for n in os.listdir(jdir) if n.endswith(".jsonl"))
    assert left == [os.path.basename(fresh)]


def test_unwritable_journal_dir_drops_without_inflating_segment_counter(
        tmp_table):
    """Every failed batch re-enters the rotation branch; segments.written
    must count files that actually landed, not attempts."""
    t = DeltaTable.create(tmp_table, data=_ids(10))
    log_path = t.delta_log.log_path
    journal.flush()
    journal.reset()
    jdir = journal.journal_dir(log_path)
    import shutil

    shutil.rmtree(jdir, ignore_errors=True)
    with open(jdir, "w", encoding="utf-8") as f:
        f.write("not a directory")  # makedirs(jdir) now raises
    try:
        before = telemetry.counters("journal.segments.written").get(
            "journal.segments.written", 0)
        for _ in range(3):
            journal.record_dml(log_path, "update", mode="dv", metrics={})
            journal.flush(log_path)
        after = telemetry.counters("journal.segments.written").get(
            "journal.segments.written", 0)
        assert after == before
        assert telemetry.counters("journal.entriesDropped").get(
            "journal.entriesDropped", 0) >= 3
    finally:
        os.remove(jdir)
