"""Shadow optimizer (`delta_tpu/replay/`): journal→trace reconstruction with
the literal-sample reservoir, sandboxed what-if candidate scoring, the
advisor/autopilot closed loop (``shadowVerdict`` attachment, the
``requireShadow`` gate, the shadow-replay realized audit), time-compressed
SLO capacity replay, the ``/replay`` HTTP route, and the dump tool's
``--shadow`` view.
"""
import json
import os
import time
import urllib.parse

import pyarrow as pa
import pytest

from delta_tpu import autopilot
from delta_tpu.api.tables import DeltaTable
from delta_tpu.obs import journal
from delta_tpu.obs.advisor import advise
from delta_tpu.replay import shadow as shadow_mod
from delta_tpu.replay import (Candidate, TraceEvent, WorkloadTrace,
                              build_trace, capacity_replay, shadow_run,
                              zipf_hot_key_storm)
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_state():
    from delta_tpu.obs import slo, timeseries

    journal.reset()
    telemetry.reset_all()
    autopilot.reset()
    slo.reset()
    timeseries.reset()
    yield
    journal.reset()
    telemetry.clear_events()
    autopilot.reset()
    slo.reset()
    timeseries.reset()


def _ids(n):
    return pa.table({"id": pa.array(range(n), pa.int64()),
                     "v": pa.array(range(n), pa.int64())})


def _shadow_workload(path, v_scans=6, noise_scans=3, a_scans=4):
    """The acceptance layout: files clustered on ``id``/``a`` (file-level
    stats prune range scans), while ``v`` and ``noise`` span the full value
    domain in EVERY file — point scans on them never prune under the
    default coarse row groups, so the advisor recommends ZORDER for both.
    The ``v`` scans are selective (a ZORDER v rewrite genuinely wins); the
    ``noise`` scans match every row (a ZORDER noise rewrite gains nothing
    and destroys the ``a`` clustering — the deliberately-bad candidate)."""
    import numpy as np

    rng = np.random.RandomState(5)

    def _part(base, n=2000):
        return pa.table({
            "id": pa.array(range(base, base + n), pa.int64()),
            "a": pa.array(range(base, base + n), pa.int64()),
            "v": pa.array(rng.permutation(n).astype("int64")),
            "noise": pa.array(rng.permutation(n).astype("int64")),
        })

    # every scan keeps its own literal: the default 3-sample reservoir
    # would collapse later same-shape scans onto the first literal
    with conf.set_temporarily(**{"delta.tpu.journal.literalSamples": 16}):
        t = DeltaTable.create(path, data=_part(0))
        t.write(_part(2000), mode="append")
        t.write(_part(4000), mode="append")
        for i in range(v_scans):
            t.to_arrow(filters=[f"v = {i * 7}"])
        for _ in range(noise_scans):
            t.to_arrow(filters=["noise <= 1999"])  # matches every row
        for _ in range(a_scans):
            t.to_arrow(filters=["a < 100"])  # file-clustered range scan
    journal.flush()
    return t


# -- trace reconstruction ----------------------------------------------------


def test_trace_round_trip_rehydrates_reservoir_literals(tmp_table, tmp_path):
    t = _shadow_workload(tmp_table)
    trace = build_trace(t.delta_log)
    assert trace.source == "journal"
    assert trace.counts()["scan"] == 13
    assert trace.counts()["commit"] == 3
    scans = trace.scans()
    # every scan rehydrated to its EXACT concrete literal — no synthesis
    assert trace.synthesized_literals == 0
    assert [e.predicate for e in scans[:3]] == [
        "(v = 0)", "(v = 7)", "(v = 14)"]
    assert scans[0].fingerprint == "eq(v,?)"
    assert scans[0].payload["rowsOut"] == 3  # one hit per 2000-row file
    assert all(e.planning_ms >= 0 for e in scans)
    # serialize → load → identical trace
    p = str(tmp_path / "trace.json")
    trace.save(p)
    assert WorkloadTrace.load(p).to_dict() == trace.to_dict()
    assert telemetry.counters("replay.traces.built")["replay.traces.built"] == 1


def test_trace_sibling_samples_and_scan_limit(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    for i in range(5):
        t.to_arrow(filters=[f"v = {i}"])
    journal.flush()
    trace = build_trace(t.delta_log)
    scans = trace.scans()
    assert len(scans) == 5
    # scans past the 3-sample reservoir borrow a sibling literal recorded
    # under the SAME fingerprint key — executable, and NOT flagged synthetic
    assert scans[3].predicate == scans[0].predicate == "(v = 0)"
    assert trace.synthesized_literals == 0
    # limit keeps the NEWEST scans; non-scan events always survive
    bounded = build_trace(t.delta_log, limit=2)
    assert len(bounded.scans()) == 2
    assert bounded.counts()["commit"] == 1


def test_trace_synthesizes_literals_when_reservoir_disabled(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(100))
    with conf.set_temporarily(**{"delta.tpu.journal.literalSamples": 0}):
        t.to_arrow(filters=["v = 42"])
        t.to_arrow(filters=["v = 7"])
    journal.flush()
    trace = build_trace(t.delta_log)
    scans = trace.scans()
    # no literal survived anywhere: stats-guided synthesis fills in a
    # midpoint range predicate, flagged so scores discount the events
    assert trace.synthesized_literals == 2
    assert all(e.synthesized for e in scans)
    assert scans[0].predicate == "v <= 49"  # midpoint of [0, 99]
    c = telemetry.counters("replay.literals")
    assert c["replay.literals.synthesized"] == 2


# -- literal-sample reservoir (journal side) ---------------------------------


def test_literal_reservoir_first_k_then_redacts(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    for i in range(5):
        t.to_arrow(filters=[f"v = {i}"])
    journal.flush()
    scans = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    assert len(scans) == 5
    # first K=3 per fingerprint key carry the exact SQL
    assert [e.get("sample") for e in scans[:3]] == [
        "(v = 0)", "(v = 1)", "(v = 2)"]
    # past the bound: no sample AND the report predicate is redacted — the
    # reservoir is the ONLY place concrete literals persist
    for e in scans[3:]:
        assert "sample" not in e
        assert e["report"]["predicate"] is None
    c = telemetry.counters("journal.literalSamples")
    assert c["journal.literalSamples"] == 3


def test_literal_reservoir_is_per_fingerprint_key(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    for i in range(4):
        t.to_arrow(filters=[f"v = {i}"])
    for i in range(4):
        t.to_arrow(filters=[f"id > {i}"])
    journal.flush()
    scans = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    by_key = {}
    for e in scans:
        by_key.setdefault(e["fingerprint"]["key"], []).append(e)
    # each shape gets its own 3-sample budget
    for key in ("eq(v,?)", "gt(id,?)"):
        sampled = [e for e in by_key[key] if "sample" in e]
        assert len(sampled) == 3, key


def test_literal_reservoir_zero_redacts_everything(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    with conf.set_temporarily(**{"delta.tpu.journal.literalSamples": 0}):
        t.to_arrow(filters=["v = 9"])
    journal.flush()
    [e] = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    assert "sample" not in e
    assert e["report"]["predicate"] is None
    # the fingerprint (the abstract shape) still persists
    assert e["fingerprint"]["key"] == "eq(v,?)"


def test_literal_reservoir_size_bound_skips_oversized_sql(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    # >SAMPLE_MAX_SQL chars of conjuncts: too big to persist
    t.to_arrow(filters=[f"id < {10_000_000 + i}" for i in range(200)])
    t.to_arrow(filters=["v = 3"])
    journal.flush()
    scans = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    assert "sample" not in scans[0]
    assert scans[0]["report"]["predicate"] is None
    # the oversized predicate did not consume any key's budget
    assert scans[1]["sample"] == "(v = 3)"


def test_literal_reservoir_blackout_inert(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    with conf.set_temporarily(**{"delta.tpu.telemetry.enabled": False}):
        t.to_arrow(filters=["v = 99"])
    t.to_arrow(filters=["v = 1"])
    journal.flush()
    # the blackout scan journaled nothing; sampling resumes untouched after
    [e] = journal.read_entries(t.delta_log.log_path, kinds=["scan"])
    assert e["sample"] == "(v = 1)"


# -- shadow run: ranked measured scorecard -----------------------------------


def test_shadow_scorecard_ranks_zorder_candidate_first(tmp_table, tmp_path):
    t = _shadow_workload(tmp_table)
    sandbox_root = str(tmp_path / "sandboxes")
    os.makedirs(sandbox_root)
    # the deliberately-bad candidate: recoarsen the row groups — the
    # rewrite compacts everything into one giant group, losing the file
    # clustering the ``a < 100`` scan prunes on and gaining nothing
    cands = [Candidate("ROW_GROUP_ROWS", {"rows": 4_194_304}),
             Candidate("ZORDER", {"columns": ["v"]})]
    # the ZORDER rewrite gets fine-grained row groups; the baseline clone
    # keeps the table's coarse one-group-per-file layout
    with conf.set_temporarily(**{
            "delta.tpu.write.rowGroupRows": 64,
            "delta.tpu.replay.sandboxDir": sandbox_root}):
        card = shadow_run(t.delta_log, candidates=cands)
    # ranked: the genuinely-winning candidate first, with MEASURED deltas
    top = card.top
    assert top["candidate"]["label"] == "ZORDER:v"
    assert top["verdict"] == "confirmed"
    assert top["score"] > 0
    assert top["deltas"]["bytesSkipped"] > 0
    assert top["deltas"]["rowGroupsPruned"] > 0
    assert top["resultMismatch"] is False
    # replays returned identical results (rowsOut identity check held)
    assert top["metrics"]["rowsOut"] == card.baseline["rowsOut"]
    # the deliberately-bad candidate measures a LOSS and is refuted
    [bad] = [r for r in card.candidates
             if r["candidate"]["label"] == "ROW_GROUP_ROWS:4194304"]
    assert bad["verdict"] == "refuted"
    assert bad["score"] < 0
    # the loss is measured on the read side: the recoarsened table reads
    # bytes the baseline's file-tier pruning never touched
    assert bad["deltas"]["bytesRead"] > 0
    # journaled as a shadow entry, sandbox fully removed
    [e] = journal.read_entries(t.delta_log.log_path, kinds=["shadow"])
    assert e["scorecard"]["topCandidate"] == "ZORDER:v"
    assert os.listdir(sandbox_root) == []
    json.dumps(card.to_dict())  # JSON-able end to end
    c = telemetry.counters("shadow")
    assert c["shadow.runs"] == 1 and c["shadow.candidates"] == 2


def test_sandbox_cleanup_on_base_exception(tmp_table, tmp_path, monkeypatch):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    sandbox_root = str(tmp_path / "sandboxes")
    os.makedirs(sandbox_root)

    def _boom(*a, **k):
        raise KeyboardInterrupt()

    monkeypatch.setattr(shadow_mod, "_replay_scans", _boom)
    trace = WorkloadTrace(path=tmp_table, built_at_ms=0, events=[
        TraceEvent(ts=1, kind="scan", predicate="v = 1")])
    with conf.set_temporarily(
            **{"delta.tpu.replay.sandboxDir": sandbox_root}):
        with pytest.raises(KeyboardInterrupt):
            shadow_run(t.delta_log, trace=trace, candidates=[])
    # BaseException mid-replay: no leaked clones
    assert os.listdir(sandbox_root) == []


# -- the closed loop: advise → gate → execute → realized audit ---------------


def test_shadow_closed_loop(tmp_table):
    t = _shadow_workload(tmp_table)
    # the advisor recommends ZORDER for BOTH never-pruned filter columns —
    # it cannot tell selective v from useless noise from stats alone
    pre = advise(tmp_table)
    pre_kinds = {(r.kind, r.target) for r in pre.recommendations}
    assert ("ZORDER", "v") in pre_kinds and ("ZORDER", "noise") in pre_kinds
    assert all(r.to_dict()["shadowVerdict"] == "untested"
               for r in pre.recommendations)

    # run 1: ZORDER v under fine row groups — the rewrite that wins
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 64}):
        card = shadow_run(t.delta_log, candidates=[
            Candidate("ZORDER", {"columns": ["v"]})])
    assert card.top["candidate"]["label"] == "ZORDER:v"
    assert card.top["verdict"] == "confirmed"
    # run 2: ZORDER noise under the table's own coarse layout — clustering
    # on the non-selective column sacrifices the ``a`` file clustering for
    # zero gain; the measured verdict refutes the advisor's guess
    card2 = shadow_run(t.delta_log, candidates=[
        Candidate("ZORDER", {"columns": ["noise"]})])
    assert card2.top["verdict"] == "refuted"

    # 1) advise(): matching recs carry the measured verdicts
    rep = advise(tmp_table)
    recs = {(r.kind, r.target): r.to_dict() for r in rep.recommendations}
    zv = recs[("ZORDER", "v")]
    assert zv["shadowVerdict"] == "confirmed"
    assert zv["shadow"]["deltas"] == card.top["deltas"]
    assert zv["shadow"]["score"] == card.top["score"]
    zn = recs[("ZORDER", "noise")]
    assert zn["shadowVerdict"] == "refuted"
    assert rep.facts["shadow"]["runs"] == 2

    # 2) dry-run plan under requireShadow: the refuted action is suppressed
    # with the shadow evidence cited; the confirmed one passes the gate
    with conf.set_temporarily(**{
            "delta.tpu.autopilot.requireShadow": True,
            "delta.tpu.autopilot.maxActionsPerRun": 8}):
        dry = autopilot.run_once(tmp_table, force=True)
    assert "ZORDER:v" in dry.planned_keys
    assert "ZORDER:noise" not in dry.planned_keys
    filtered = {d["action"]: d for d in dry.shadow_filtered}
    assert filtered["ZORDER:noise"]["verdict"] == "refuted"
    assert "refuted by shadow run" in filtered["ZORDER:noise"]["reason"]
    assert filtered["ZORDER:noise"]["shadow"]["score"] == zn["shadow"]["score"]
    [planned_zv] = [a for a in dry.planned
                    if a["kind"] == "ZORDER" and a["target"] == "v"]
    assert planned_zv["evidence"]["shadow"]["verdict"] == "confirmed"

    # 3) execute: the realized rewrite improves with the SAME sign the
    # scorecard predicted, measured by replaying the scored trace against
    # the now-rewritten live table (auditSource=shadowReplay)
    with conf.set_temporarily(**{
            "delta.tpu.autopilot.dryRun": False,
            "delta.tpu.autopilot.requireShadow": True,
            "delta.tpu.autopilot.maxActionsPerRun": 8,
            "delta.tpu.autopilot.quietWindowMs": 50,
            "delta.tpu.write.rowGroupRows": 64}):
        time.sleep(0.1)
        run = autopilot.run_once(tmp_table, force=True)
    by_action = {o["action"]: o for o in run.outcomes}
    out = by_action["ZORDER:v"]
    assert out["status"] == "executed"
    audit = out["audit"]
    assert audit["auditSource"] == "shadowReplay"
    assert audit["verdict"] == "improved"
    assert audit["bytesSkippedDelta"] > 0
    assert (audit["realized"]["bytesSkipped"]
            > audit["shadowBaseline"]["bytesSkipped"])
    assert audit["shadowScore"] == card.top["score"]


def test_shadow_gate_defers_untested_rewrites(tmp_table):
    t = _shadow_workload(tmp_table)
    with conf.set_temporarily(**{
            "delta.tpu.autopilot.requireShadow": True,
            "delta.tpu.autopilot.maxActionsPerRun": 8}):
        dry = autopilot.run_once(tmp_table, force=True)
    # no shadow run exists: every rewrite-class action defers, with the
    # no-confirming-run reason cited in the report AND the journal ledger
    deferred = {d["action"]: d for d in dry.shadow_filtered}
    assert "ZORDER:v" in deferred
    assert deferred["ZORDER:v"]["verdict"] == "untested"
    assert "no confirming shadow run" in deferred["ZORDER:v"]["reason"]
    assert not any(k.startswith("ZORDER") for k in dry.planned_keys)
    journal.flush()
    ledger = journal.read_entries(t.delta_log.log_path, kinds=["autopilot"])
    assert any(e.get("phase") == "deferred"
               and (e.get("action") or {}).get("target") == "v"
               for e in ledger)


# -- capacity replay ---------------------------------------------------------


@pytest.mark.parametrize("speed", [10, 100], ids=["10x", "100x"])
def test_capacity_replay_compressed_fires_same_slo_objective(tmp_table, speed):
    from delta_tpu.obs import slo, timeseries

    trace = zipf_hot_key_storm(path=tmp_table)
    overrides = {"delta.tpu.obs.slo.minObservations": 4}
    with conf.set_temporarily(**overrides):
        full = capacity_replay(trace, speed=1.0, now_ms=1_000_000_000_000)
    assert full["objectives"] == ["scanPlanningP99"]
    assert full["events"] == 120

    slo.reset()
    timeseries.reset()
    with conf.set_temporarily(**overrides):
        fast = capacity_replay(trace, speed=float(speed),
                               now_ms=2_000_000_000_000)
    # the compressed burn pre-fires the SAME objective in 1/speed the time
    assert fast["objectives"] == full["objectives"]
    assert fast["simulatedMs"] == full["simulatedMs"] // speed
    assert fast["alerts"] and fast["alerts"][0]["firing"] is True
    assert fast["alerts"][0]["objective"] == "scanPlanningP99"
    c = telemetry.counters("replay.capacity")
    assert c["replay.capacity.runs"] == 2


def test_synthetic_scenarios_are_deterministic_and_serializable(tmp_path):
    from delta_tpu.replay import SCENARIOS

    for name, gen in SCENARIOS.items():
        a, b = gen(), gen()
        assert a.to_dict() == b.to_dict(), name
        assert a.source == f"synthetic:{name}"
        p = str(tmp_path / f"{name}.json")
        a.save(p)
        assert WorkloadTrace.load(p).to_dict() == a.to_dict()
    storm = SCENARIOS["zipfHotKeyStorm"]()
    assert any(e.payload.get("hotKey") for e in storm.scans())


# -- HTTP route + dump tool --------------------------------------------------


def test_replay_route_serves_scorecards_and_degrades_params(tmp_table):
    import http.client

    from delta_tpu.obs.server import ObsServer

    t = DeltaTable.create(tmp_table, data=_ids(20))
    journal.record_shadow(t.delta_log.log_path, {
        "ts": 123, "path": tmp_table, "trace": {}, "baseline": {},
        "candidates": [], "topCandidate": "ZORDER:v"})
    journal.flush()

    def _get(srv, route):
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        try:
            c.request("GET", route)
            r = c.getresponse()
            return r.status, json.loads(r.read())
        finally:
            c.close()

    srv = ObsServer(port=0)
    try:
        q = urllib.parse.quote(tmp_table)
        status, doc = _get(srv, f"/replay?path={q}")
        assert status == 200
        assert len(doc["shadowRuns"]) == 1
        assert doc["latest"]["topCandidate"] == "ZORDER:v"
        # malformed numeric params degrade to the default view, never 500
        status, doc2 = _get(srv, f"/replay?path={q}&limit=abc")
        assert status == 200 and doc2["latest"]["ts"] == 123
        status, err = _get(srv, "/replay")
        assert status == 400 and "path" in err["error"]
        status, err = _get(srv, "/nope")
        assert status == 404 and "/replay" in err["routes"]
    finally:
        srv.stop()


def test_journal_dump_shadow_views(tmp_table, capsys):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.journal_dump import main

    t = DeltaTable.create(tmp_table, data=_ids(20))
    journal.record_shadow(t.delta_log.log_path, {
        "ts": 5, "path": tmp_table, "trace": {"events": 3},
        "baseline": {"bytesSkipped": 0.0},
        "candidates": [
            {"candidate": {"kind": "ZORDER", "label": "ZORDER:v",
                           "params": {"columns": ["v"]}},
             "verdict": "confirmed", "score": 0.3,
             "deltas": {"bytesSkipped": 4096.0}}],
        "topCandidate": "ZORDER:v"})
    journal.flush()
    assert main([tmp_table, "--shadow"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["shadowRuns"] == 1
    assert doc["candidateVerdicts"] == {"confirmed": 1}
    [run] = doc["runs"]
    assert run["topCandidate"] == "ZORDER:v"
    assert run["candidates"][0]["deltas"]["bytesSkipped"] == 4096.0
    assert main([tmp_table, "--kind", "shadow"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1 and lines[0]["kind"] == "shadow"
