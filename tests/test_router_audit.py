"""Self-calibrating cost model (ISSUE 7 tentpole): the router audit ledger
(predicted vs actual per routed decision), the EWMA calibrator feeding the
`parallel/link.py` constants (persisted state round-trip), the device-memory
ledger + doctor pressure dimension, cross-thread trace propagation of the
staged MERGE pipeline, and the blackout guarantee over all of it.
"""
import json

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.obs import calibration, hbm_ledger, router_audit
from delta_tpu.ops.key_cache import KeyCache
from delta_tpu.parallel import link
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

UP = MergeClause("update", assignments=None)
INS = MergeClause("insert", assignments=None)
ALIAS = dict(source_alias="s", target_alias="t")


@pytest.fixture(autouse=True)
def _fresh_state():
    import gc

    def fresh():
        telemetry.reset_all()
        router_audit.clear_audits()
        calibration.reset()
        KeyCache.reset()
        # run dropped entries' hbm finalizers NOW, then zero the ledger, so
        # stale finalizers can't fire mid-test and skew equality asserts
        gc.collect()
        hbm_ledger.reset()

    fresh()
    yield
    fresh()


def _seed(path, files=2, per=50):
    log = DeltaLog.for_table(str(path))
    rng = np.random.RandomState(5)
    for i in range(files):
        keys = np.arange(i * per, (i + 1) * per, dtype=np.int64)
        WriteIntoDelta(log, "append", pa.table({
            "k": pa.array(keys),
            "v": pa.array(rng.rand(per)),
        })).run()
    return log


def _source(n=30, hit_lo=10):
    rng = np.random.RandomState(9)
    keys = np.concatenate([
        np.arange(hit_lo, hit_lo + n // 2, dtype=np.int64),
        np.arange(10_000, 10_000 + n - n // 2, dtype=np.int64),
    ])
    return pa.table({"k": pa.array(keys), "v": pa.array(rng.rand(len(keys)))})


def _merge(log, mode, source=None):
    with conf.set_temporarily(**{
        "delta.tpu.merge.devicePath.mode": mode,
        "delta.tpu.deletionVectors.enabled": True,
        "delta.tpu.merge.keyCache.enabled": mode != "off",
    }):
        cmd = MergeIntoCommand(log, source if source is not None
                               else _source(), "t.k = s.k", [UP], [INS],
                               **ALIAS)
        cmd.run()
    return cmd


# -- ledger unit behavior ----------------------------------------------------


def test_record_audit_miss_logic_and_stats():
    a = router_audit.record_audit(
        "merge.join", "/t", "host", {"host": 0.010, "device": 0.002}, 0.005,
        units={"targetRows": 10},
    )
    assert a is not None and a.miss  # device predicted 2ms, host ran 5ms
    b = router_audit.record_audit(
        "merge.join", "/t", "host", {"host": 0.010, "device": 0.050}, 0.005,
    )
    assert not b.miss
    stats = router_audit.audit_stats()
    assert stats == {"audits": 2, "misses": 1, "missRate": 0.5}
    g = telemetry.gauges("router.missRate")
    assert g[("router.missRate", ())] == 0.5
    assert telemetry.counters("router.audits") == {"router.audits": 2}
    assert telemetry.counters("router.misses") == {"router.misses": 1}
    recent = router_audit.recent_audits()
    assert [r["miss"] for r in recent] == [True, False]
    json.dumps(recent)
    # predicted/actual histograms populated under catalog-registered names
    h = telemetry.histograms("router.predicted_ms")
    assert sum(v.count for v in h.values()) == 2
    h = telemetry.histograms("router.actual_ms")
    assert sum(v.count for v in h.values()) == 2


def test_record_audit_no_alternative_never_misses():
    a = router_audit.record_audit(
        "merge.join", "/t", "host", {"host": 0.001}, 99.0)
    assert a is not None and not a.miss


def test_audit_ring_bounded_by_conf():
    with conf.set_temporarily(**{"delta.tpu.router.auditKeep": 4}):
        for i in range(10):
            router_audit.record_audit("merge.join", "/t", "host",
                                      {"host": 1.0}, 0.5, seq=i)
        recent = router_audit.recent_audits(limit=100)
    assert len(recent) == 4
    assert [r["extra"]["seq"] for r in recent] == [6, 7, 8, 9]
    assert router_audit.audit_stats()["audits"] == 10  # counts keep totals


# -- merge audits: predicted vs actual on both forced routes -----------------


def test_host_forced_merge_produces_populated_audit(tmp_path):
    log = _seed(tmp_path / "thost")
    cmd = _merge(log, "off")
    assert cmd._join_path == "host"
    [rec] = [r for r in router_audit.recent_audits() if r["op"] == "merge.join"]
    assert rec["decision"] == "host"
    assert rec["predictedMs"]["host"] > 0
    assert rec["actualMs"] > 0
    assert rec["units"]["targetRows"] == 100
    assert rec["units"]["sourceRows"] == 30
    assert "join_ms" in rec["extra"]["phases"]
    # host-only (device structurally off): no hindsight miss possible
    assert "device" not in rec["predictedMs"] or rec["predictedMs"]["device"] > 0


def test_device_forced_merge_produces_populated_audit(tmp_path):
    log = _seed(tmp_path / "tdev")
    cmd = _merge(log, "force")
    assert cmd._device_join is not None
    assert cmd._join_path in ("device-cold", "resident")
    [rec] = [r for r in router_audit.recent_audits() if r["op"] == "merge.join"]
    assert rec["decision"] == cmd._join_path
    assert rec["predictedMs"]["host"] > 0
    assert rec["predictedMs"][cmd._join_path] > 0
    assert rec["actualMs"] > 0
    h = telemetry.histograms("router.actual_ms")
    assert sum(v.count for v in h.values()) == 1


def test_scan_plan_batch_produces_audit(tmp_path):
    from delta_tpu.exec.scan import plan_scans

    log = _seed(tmp_path / "tplan", files=3)
    snap = log.update()
    with conf.set_temporarily(**{
        "delta.tpu.link.uploadMBps": 100, "delta.tpu.link.downloadMBps": 100,
    }):
        # AUTO mode (the default): the router made a priceable decision
        plans = plan_scans(snap, [["k >= 0 AND k <= 10"]], k=16)
    assert plans[0].count >= 1
    recs = [r for r in router_audit.recent_audits() if r["op"] == "scan.plan"]
    assert recs, "scan planning must audit its device/host pick"
    assert recs[-1]["decision"] in ("device", "host-resident")
    assert set(recs[-1]["predictedMs"]) == {"device", "host-resident"}
    assert recs[-1]["units"]["cells"] > 0
    # pinned modes made no priceable decision: no audit, no link probe
    router_audit.clear_audits()
    with conf.set_temporarily(**{
        "delta.tpu.stateCache.devicePlan.mode": "off",
    }):
        plan_scans(snap, [["k >= 0 AND k <= 10"]], k=16)
    assert [r for r in router_audit.recent_audits()
            if r["op"] == "scan.plan"] == []


# -- calibration: synthetic convergence + persistence ------------------------


def test_calibrator_ewma_converges_from_synthetic_samples(tmp_path):
    state_file = str(tmp_path / "cal.json")
    default = link.HOST_JOIN_S_PER_ROW
    target_rate = default * 10  # this hardware is 10x slower than the bench
    with conf.set_temporarily(**{
        "delta.tpu.router.calibration.enabled": True,
        "delta.tpu.router.calibration.statePath": state_file,
        "delta.tpu.router.calibration.alpha": 0.5,
        "delta.tpu.router.calibration.minSamples": 3,
    }):
        # below minSamples: no override installed yet
        for _ in range(2):
            calibration.ingest([("HOST_JOIN_S_PER_ROW", 1_000_000,
                                 target_rate * 1_000_000)])
        assert link.calibrated_constants() == {}
        assert link.constant("HOST_JOIN_S_PER_ROW") == default
        for _ in range(8):
            calibration.ingest([("HOST_JOIN_S_PER_ROW", 1_000_000,
                                 target_rate * 1_000_000)])
        got = link.constant("HOST_JOIN_S_PER_ROW")
        # EWMA over identical samples converges onto the sample rate
        assert got == pytest.approx(target_rate, rel=0.01)
        assert telemetry.counters("router.calibration.updates")[
            "router.calibration.updates"] == 10
        # gauge published under the catalog name, labeled by constant
        g = telemetry.gauges("router.calibration")
        assert g[("router.calibration",
                  (("constant", "HOST_JOIN_S_PER_ROW"),))] == got

        # state file round-trips into a fresh process (reset = fresh state)
        calibration.reset()
        assert link.constant("HOST_JOIN_S_PER_ROW") == default
        state = calibration.apply_state()
        assert state["HOST_JOIN_S_PER_ROW"]["samples"] == 10
        assert link.constant("HOST_JOIN_S_PER_ROW") == pytest.approx(
            target_rate, rel=0.01)


def test_calibrator_rejects_garbage_samples(tmp_path):
    with conf.set_temporarily(**{
        "delta.tpu.router.calibration.enabled": True,
        "delta.tpu.router.calibration.statePath": str(tmp_path / "c.json"),
    }):
        assert calibration.ingest([("NOT_A_CONSTANT", 10, 1.0)]) is None
        assert calibration.ingest([("HOST_JOIN_S_PER_ROW", 0, 1.0)]) is None
        assert calibration.ingest([("HOST_JOIN_S_PER_ROW", 10, -1.0)]) is None
    assert link.calibrated_constants() == {}


def test_calibration_hot_path_flush_is_throttled(tmp_path):
    """flush=False (the per-query scan-planner path) defers the state-file
    write to the flush interval; merge-path ingests and apply_state flush
    deferred state, so nothing is ever lost across a routed merge."""
    state_file = str(tmp_path / "hot.json")
    key = "HOST_PRUNE_S_PER_CELL"
    with conf.set_temporarily(**{
        "delta.tpu.router.calibration.enabled": True,
        "delta.tpu.router.calibration.statePath": state_file,
        "delta.tpu.router.calibration.flushIntervalMs": 60_000,
    }):
        # first hot-path ingest persists (nothing saved yet this process)
        calibration.ingest([(key, 100, 1.0)], flush=False)
        assert calibration.load_state(state_file)[key]["samples"] == 1
        # within the interval: deferred — file unchanged, memory advances
        for _ in range(5):
            calibration.ingest([(key, 100, 1.0)], flush=False)
        assert calibration.load_state(state_file)[key]["samples"] == 1
        assert calibration.current_state()[key]["samples"] == 6
        # a flushing ingest (the merge path) writes the deferred state
        calibration.ingest([(key, 100, 1.0)])
        assert calibration.load_state(state_file)[key]["samples"] == 7
        # apply_state (merge start) also flushes dirty deferred state
        calibration.ingest([(key, 100, 1.0)], flush=False)
        assert calibration.load_state(state_file)[key]["samples"] == 7
        calibration.apply_state()
        assert calibration.load_state(state_file)[key]["samples"] == 8


def test_calibration_disabled_is_inert(tmp_path):
    state_file = tmp_path / "never.json"
    with conf.set_temporarily(**{
        "delta.tpu.router.calibration.statePath": str(state_file),
    }):
        assert calibration.ingest(
            [("HOST_JOIN_S_PER_ROW", 100, 1.0)]) is None
    assert not state_file.exists()
    assert link.calibrated_constants() == {}


def test_host_merge_calibrates_and_round_trips_across_fresh_deltalog(tmp_path):
    """Acceptance: with calibration enabled, a real MERGE's measured samples
    move a link constant, the state persists under the table's log dir, and
    a FRESH DeltaLog (new process simulation: caches cleared, calibration
    state reset) re-applies it before routing."""
    log = _seed(tmp_path / "tcal")
    default = link.HOST_JOIN_S_PER_ROW
    with conf.set_temporarily(**{
        "delta.tpu.router.calibration.enabled": True,
        "delta.tpu.router.calibration.minSamples": 1,
    }):
        _merge(log, "off")
        moved = link.calibrated_constants()
        assert "HOST_JOIN_S_PER_ROW" in moved
        assert moved["HOST_JOIN_S_PER_ROW"] != default
        state_file = calibration.state_path(log.log_path)
        assert state_file is not None
        persisted = calibration.load_state(state_file)
        assert persisted["HOST_JOIN_S_PER_ROW"]["value"] == pytest.approx(
            moved["HOST_JOIN_S_PER_ROW"])

        # fresh process: no in-memory state, no installed overrides
        calibration.reset()
        DeltaLog.clear_cache()
        assert link.calibrated_constants() == {}
        fresh = DeltaLog.for_table(str(tmp_path / "tcal"))
        _merge(fresh, "off", source=_source(20))
        # the merge loaded the persisted state before routing
        assert "HOST_JOIN_S_PER_ROW" in link.calibrated_constants()


# -- cross-thread trace propagation (acceptance) -----------------------------


def test_cold_device_merge_trace_has_no_orphan_worker_spans(tmp_path):
    """export_chrome_trace of a cold fused MERGE shows decode, upload, and
    probe spans parented (transitively) under `delta.dml.merge`, on thread
    lanes other than the command's, with zero orphan roots from pooled
    workers."""
    log = _seed(tmp_path / "ttrace", files=3)
    telemetry.reset_all()
    cmd = _merge(log, "force")
    assert cmd._join_path == "device-cold"
    trace = telemetry.export_chrome_trace()
    rows = [r for r in trace["traceEvents"] if r.get("ph") == "X"]
    by_id = {r["args"]["spanId"]: r for r in rows if "spanId" in r["args"]}
    [merge_row] = [r for r in rows if r["name"] == "delta.dml.merge"]

    def under_merge(row):
        seen = set()
        while True:
            pid = row["args"].get("parentId")
            if pid is None or pid in seen or pid not in by_id:
                return False
            if pid == merge_row["args"]["spanId"]:
                return True
            seen.add(pid)
            row = by_id[pid]

    for name in ("delta.scan.decode", "delta.merge.slabUpload",
                 "delta.merge.deviceProbe"):
        spans = [r for r in rows if r["name"] == name]
        assert spans, f"{name} spans missing from the cold-merge trace"
        assert all(under_merge(r) for r in spans), f"{name} span orphaned"
    # decode + upload + probe ran on worker lanes, not the command thread
    worker_tids = {r["tid"] for r in rows
                   if r["name"] in ("delta.scan.decode",
                                    "delta.merge.slabUpload",
                                    "delta.merge.deviceProbe")}
    assert worker_tids - {merge_row["tid"]}, "no worker thread lanes in trace"
    # zero orphan roots from pooled workers: every span on a non-command
    # thread has a parent chain
    for r in rows:
        if r["tid"] != merge_row["tid"] and "spanId" in r["args"]:
            assert r["args"].get("parentId") is not None, (
                f"orphan worker span {r['name']}")


# -- device-memory ledger + doctor pressure ----------------------------------


def test_hbm_ledger_tracks_key_cache_residency(tmp_path):
    hbm_ledger.reset()
    log = _seed(tmp_path / "thbm")
    cmd = _merge(log, "force")  # cold slab pipeline registers in KeyCache
    assert cmd._device_join is not None
    t = hbm_ledger.totals()
    assert t["keyCache"] > 0
    g = telemetry.gauges("device.hbm.keyCacheBytes")
    assert g[("device.hbm.keyCacheBytes", ())] == t["keyCache"]
    # scratch is transient: released once the probe thread finished
    assert t["scratch"] == 0
    # dropping the entries returns every byte
    KeyCache.instance().bump_epoch(log.log_path)
    assert hbm_ledger.totals()["keyCache"] == 0


def test_hbm_ledger_tracks_state_cache(tmp_path):
    from delta_tpu.ops.state_cache import DeviceStateCache

    hbm_ledger.reset()
    DeviceStateCache.reset()
    log = _seed(tmp_path / "tsc")
    entry = DeviceStateCache.instance().get(log.update())
    assert entry is not None
    entry.ensure_resident()
    assert hbm_ledger.totals()["stateCache"] == entry.device_bytes
    entry.drop_device()
    assert hbm_ledger.totals()["stateCache"] == 0
    DeviceStateCache.reset()


def test_doctor_device_dimension_reports_pressure(tmp_path):
    from delta_tpu.obs.doctor import doctor

    hbm_ledger.reset()
    log = _seed(tmp_path / "tdoc")
    dim = doctor(log).dimension("device")
    assert dim.severity == "ok"  # no budget set
    hbm_ledger.adjust("keyCache", 900)
    with conf.set_temporarily(**{"delta.tpu.device.hbmBudgetBytes": 1000}):
        dim = doctor(log).dimension("device")
        assert dim.severity == "warn" and dim.remedy == "EVICT"
        assert dim.metrics["pressure"] == 0.9
        hbm_ledger.adjust("scratch", 200)
        dim = doctor(log).dimension("device")
        assert dim.severity == "critical" and dim.remedy == "EVICT"
    g = telemetry.gauges("table.health.device.pressure")
    assert g, "doctor must publish the device pressure gauge"
    hbm_ledger.reset()


# -- /router HTTP route + /metrics exposition --------------------------------


def test_router_route_and_metrics_exposition(tmp_path):
    import http.client

    from delta_tpu.obs.server import ObsServer

    log = _seed(tmp_path / "tsrv")
    _merge(log, "off")
    srv = ObsServer(port=0)
    try:
        host, port = srv.address

        def get(path):
            c = http.client.HTTPConnection(host, port, timeout=10)
            c.request("GET", path)
            r = c.getresponse()
            body = r.read().decode()
            c.close()
            return r.status, body

        status, body = get("/router?limit=8")
        assert status == 200
        payload = json.loads(body)
        assert payload["stats"]["audits"] >= 1
        assert payload["audits"][-1]["op"] == "merge.join"
        assert "calibration" in payload
        status, text = get("/metrics")
        assert status == 200
        assert "router_missRate" in text
        assert "router_actual_ms" in text
        # the doctor's device gauges flow into the same exposition
        from delta_tpu.obs.doctor import doctor

        doctor(log)
        _, text = get("/metrics")
        assert "table_health_device_hbmBytes" in text
    finally:
        srv.stop()


def test_metrics_snapshot_carries_router_and_hbm_gauges(tmp_path):
    log = _seed(tmp_path / "tsnap")
    _merge(log, "off")
    snap = telemetry.metrics_snapshot()
    assert "router.audits" in snap["counters"]
    assert any(k.startswith("router.missRate") for k in snap["gauges"])
    assert any(k.startswith("router.actual_ms")
               for k in snap["histograms"])


# -- blackout: zero overhead end to end --------------------------------------


def test_blackout_no_audits_no_calibration_no_hbm_gauges(tmp_path):
    state_file = tmp_path / "dark.json"
    hbm_ledger.reset()
    with conf.set_temporarily(**{
        "delta.tpu.telemetry.enabled": False,
        "delta.tpu.router.calibration.enabled": True,
        "delta.tpu.router.calibration.statePath": str(state_file),
    }):
        log = _seed(tmp_path / "tdark")
        _merge(log, "off")
        assert router_audit.recent_audits() == []
        assert router_audit.audit_stats()["audits"] == 0
        assert not state_file.exists()
        assert link.calibrated_constants() == {}
        assert telemetry.gauges("router") == {}
        assert telemetry.gauges("device.hbm") == {}
        assert telemetry.histograms("router") == {}
