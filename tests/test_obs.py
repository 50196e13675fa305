"""Operator observability layer (`delta_tpu/obs/`): the table-health doctor,
the per-query scan reports, the HTTP endpoint, and the failure flight
recorder — plus the blackout guarantee (everything off or zero-overhead when
``delta.tpu.telemetry.enabled=false``).
"""
import http.client
import json

import pyarrow as pa
import pytest

from tests.conftest import init_metadata

from delta_tpu.api.tables import DeltaTable
from delta_tpu.commands import operations as ops
from delta_tpu.log.deltalog import DeltaLog
from delta_tpu.obs import flight_recorder, metric_names
from delta_tpu.obs import scan_report as scan_report_mod
from delta_tpu.obs.doctor import SEVERITY_RANK, doctor
from delta_tpu.obs.scan_report import last_scan_report
from delta_tpu.obs.server import ObsServer
from delta_tpu.protocol.actions import AddFile, Metadata, RemoveFile
from delta_tpu.schema.types import IntegerType, StringType, StructType
from delta_tpu.utils import errors, telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_all()
    scan_report_mod.clear_last_report()
    yield
    telemetry.reset_all()


def _ids(n, start=0):
    import numpy as np

    return pa.table({"id": np.arange(start, start + n).astype("int64")})


# -- doctor ------------------------------------------------------------------


def test_doctor_on_known_debt_table(tmp_table):
    """Acceptance: a table with 200 tiny files, ~30% DV-deleted rows, and a
    stale checkpoint gets the expected severities and remedies."""
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 10}):
        t = DeltaTable.create(
            tmp_table, data=_ids(2000),
            configuration={"delta.tpu.enableDeletionVectors": "true",
                           "delta.checkpointInterval": "1000"},
        )
    # every 10-row file soft-deletes 3 rows -> each file past the 30% purge
    # threshold, table 30% deleted
    t.delete("id % 10 < 3")
    # stale checkpoint: > 20 commits, none checkpointed (interval 1000)
    for i in range(21):
        t.write(_ids(10, start=10_000 + 10 * i))

    report = t.doctor()
    assert report.severity == "critical"

    ckpt = report.dimension("checkpoint")
    assert ckpt.severity == "warn" and ckpt.remedy == "CHECKPOINT"
    assert ckpt.metrics["commitsSince"] == report.version + 1  # never ckpted
    assert ckpt.metrics["tailBytes"] > 0

    small = report.dimension("smallFiles")
    assert small.severity == "critical" and small.remedy == "OPTIMIZE"
    assert small.metrics["count"] >= 200
    assert small.metrics["estReduction"] >= 200

    dv = report.dimension("dv")
    assert dv.severity == "critical" and dv.remedy == "PURGE"
    assert dv.metrics["deletedRows"] == 600
    # 600 of 2000 + 210 staleness-commit rows
    assert dv.metrics["deletedPct"] == pytest.approx(600 / 2210, abs=0.01)
    assert dv.metrics["filesPastPurge"] >= 200

    assert report.dimension("stats").severity == "ok"
    assert report.dimension("partition").severity == "ok"
    assert report.remedies()[0] in ("OPTIMIZE", "PURGE")
    assert set(report.remedies()) == {"OPTIMIZE", "PURGE", "CHECKPOINT"}

    # every number doubled as a catalog-registered table.health gauge
    gauges = telemetry.gauges("table.health")
    assert gauges, "doctor must publish gauges"
    for (name, labels) in gauges:
        assert name in metric_names.GAUGES, name
        assert ("path", tmp_table) in labels
    key = ("table.health.severity", (("path", tmp_table),))
    assert gauges[key] == SEVERITY_RANK["critical"]

    # the report is JSON-able end to end
    json.dumps(report.to_dict())


def test_doctor_empty_table(tmp_table):
    schema = StructType().add("id", IntegerType())
    t = DeltaTable.create(tmp_table, schema=schema)
    report = t.doctor()
    assert report.severity == "ok"
    assert report.num_files == 0
    assert all(d.severity == "ok" for d in report.dimensions)
    assert report.remedies() == []


def test_doctor_fully_removed_table_suggests_vacuum(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(100))
    t.delete()  # 100% of files removed
    report = t.doctor()
    assert report.num_files == 0
    tomb = report.dimension("tombstones")
    assert tomb.severity == "warn" and tomb.remedy == "VACUUM"
    assert tomb.metrics["count"] >= 1
    # no live files: the file-shape dimensions stay vacuous-ok
    assert report.dimension("smallFiles").severity == "ok"
    assert report.dimension("stats").severity == "ok"
    assert report.severity == "warn"


def test_doctor_zero_stats_coverage(tmp_table):
    log = DeltaLog.for_table(tmp_table)
    txn = log.start_transaction()
    txn.update_metadata(init_metadata())
    txn.commit([], ops.ManualUpdate())
    txn = log.start_transaction()
    txn.commit(
        [AddFile(f"f{i}", {}, size=1, modification_time=1, stats=None)
         for i in range(3)],
        ops.Write(mode="Append"),
    )
    report = doctor(log)
    stats = report.dimension("stats")
    assert stats.severity == "critical" and stats.remedy == "OPTIMIZE"
    assert stats.metrics["coveragePct"] == 0.0


PART_SCHEMA = StructType().add("id", IntegerType()).add("p", StringType())


def _partitioned_log(tmp_table, sizes):
    log = DeltaLog.for_table(tmp_table)
    txn = log.start_transaction()
    txn.update_metadata(Metadata(schema_string=PART_SCHEMA.to_json(),
                                 partition_columns=["p"]))
    txn.commit([], ops.ManualUpdate())
    txn = log.start_transaction()
    txn.commit(
        [AddFile(f"p{i}/f{i}", {"p": f"p{i}"}, size=s, modification_time=1)
         for i, s in enumerate(sizes)],
        ops.Write(mode="Append"),
    )
    return log


def test_doctor_partition_skew(tmp_table):
    # one partition holds ~all bytes across 8 partitions
    log = _partitioned_log(tmp_table, [1 << 30] + [1] * 7)
    dim = doctor(log).dimension("partition")
    assert dim.severity == "critical" and dim.remedy == "REPARTITION"
    assert dim.metrics["count"] == 8
    assert dim.metrics["gini"] > 0.8


def test_doctor_balanced_partitions_ok(tmp_table):
    log = _partitioned_log(tmp_table, [1000] * 8)
    dim = doctor(log).dimension("partition")
    assert dim.severity == "ok" and dim.metrics["gini"] == 0.0


def test_describe_detail_gains_health_columns(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(50))
    d = t.detail()
    assert d["healthSeverity"] in ("ok", "warn", "critical")
    assert set(d["health"]) == {
        "checkpoint", "smallFiles", "dv", "stats", "partition",
        "tombstones", "protocol", "device", "distributed",
    }
    assert d["numCommitsSinceCheckpoint"] >= 1
    assert d["statsCoveragePct"] == 1.0
    assert d["numDeletionVectorFiles"] == 0
    assert d["numTombstones"] == 0


def test_maintenance_feeds_doctor_gauges(tmp_table):
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 10}):
        t = DeltaTable.create(tmp_table, data=_ids(100))
    version = t.delta_log.update().version
    t.optimize().execute_compaction()
    g = telemetry.gauges("table.maintenance.lastOptimizeVersion")
    assert g[("table.maintenance.lastOptimizeVersion",
              (("path", tmp_table),))] == version + 1
    c = telemetry.counters("maintenance.optimize")
    assert c["maintenance.optimize.filesCompacted"] == 10
    assert c["maintenance.optimize.filesWritten"] >= 1

    t.vacuum(retention_hours=0, retention_check_enabled=False)
    g = telemetry.gauges("table.maintenance.lastVacuumTimestamp")
    assert g[("table.maintenance.lastVacuumTimestamp",
              (("path", tmp_table),))] > 0
    c = telemetry.counters("maintenance.vacuum")
    assert c["maintenance.vacuum.filesDeleted"] == 10
    assert c["maintenance.vacuum.bytesReclaimed"] > 0


# -- scan reports ------------------------------------------------------------


def test_scan_report_matches_rowgroup_counters_exactly(tmp_table):
    """Acceptance: last_scan_report() for a pruned query equals the
    scan.rowgroups.* / scan.bytes.* counter deltas."""
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 1000}):
        t = DeltaTable.create(tmp_table, data=_ids(20_000))
    telemetry.reset_all()
    out = t.to_arrow(filters=["id < 1500"])
    assert out.num_rows == 1500
    rep = last_scan_report()
    assert rep is not None
    c = telemetry.counters("scan")
    assert rep.row_groups_total == c.get("scan.rowgroups.total", 0) > 0
    assert rep.row_groups_pruned == c.get("scan.rowgroups.pruned", 0) > 0
    assert rep.row_groups_late_skipped == c.get("scan.rowgroups.lateSkipped", 0)
    assert rep.bytes_skipped == c.get("scan.bytes.skipped", 0) > 0
    assert rep.bytes_read == c.get("scan.bytes.read", 0) > 0
    assert rep.files_scanned == c.get("scan.files.read", 0) == 1
    assert rep.rows_out == 1500
    assert rep.predicate == "(id < 1500)"
    # "mask" is there too whenever the device residual route was priced
    assert set(rep.phase_ms) - {"mask"} == {"planning", "read", "filter"}
    assert rep.version == t.delta_log.update().version
    json.dumps(rep.to_dict())


def test_scan_report_file_tier_pruning(tmp_table):
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 1000}):
        t = DeltaTable.create(tmp_table, data=_ids(10_000))
    telemetry.reset_all()
    t.to_arrow(filters=["id < 500"])
    rep = last_scan_report()
    assert rep.files_total == 10
    assert rep.files_scanned == 1
    assert rep.files_pruned == 9


def test_scan_report_attached_to_scan_span(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(100))
    telemetry.clear_events()
    t.to_arrow()
    [scan] = [e for e in telemetry.recent_events("delta.scan")
              if e.op_type == "delta.scan"]
    assert scan.data["scanReport"] == last_scan_report().to_dict()


def test_failed_scan_does_not_overwrite_last_report(tmp_table, tmp_path):
    import os

    t = DeltaTable.create(tmp_table, data=_ids(100))
    t.to_arrow()
    good = last_scan_report()
    assert good is not None
    # corrupt the data file: the next scan raises mid-read
    snap = t.delta_log.update()
    data_file = os.path.join(tmp_table, snap.all_files[0].path)
    with open(data_file, "wb") as f:
        f.write(b"garbage")
    DeltaLog.clear_cache()
    with pytest.raises(Exception):
        DeltaTable.for_path(tmp_table).to_arrow()
    assert last_scan_report() is good  # half-filled report never published


def test_server_events_limit_zero(tmp_table):
    srv = ObsServer(port=0)
    try:
        DeltaTable.create(tmp_table, data=_ids(5))
        status, _, body = _get(srv, "/events?limit=0")
        assert status == 200 and json.loads(body) == []
    finally:
        srv.stop()


def test_streaming_backlog_capped(tmp_table):
    from delta_tpu.streaming.source import DeltaSource

    t = DeltaTable.create(tmp_table, data=_ids(10))
    source = DeltaSource(t.delta_log, max_files_per_trigger=1)
    start = source.initial_offset()
    end = source.latest_offset(start)
    for i in range(3):
        t.write(_ids(10, start=100 * (i + 1)))
    with conf.set_temporarily(delta__tpu__obs__streamingBacklogMaxFiles=2):
        source.get_batch(start, end)
    g = telemetry.gauges("streaming.source.backlogFiles")
    # the walk stops at the cap: the count is a floor, not the full tail
    assert g[("streaming.source.backlogFiles", (("path", tmp_table),))] == 2


def test_scan_report_blackout(tmp_table):
    t = DeltaTable.create(tmp_table, data=_ids(100))
    scan_report_mod.clear_last_report()
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False):
        out = t.to_arrow(filters=["id < 10"])
    assert out.num_rows == 10
    assert last_scan_report() is None


# -- streaming consumer lag --------------------------------------------------


def test_streaming_source_publishes_backlog_gauges(tmp_table):
    from delta_tpu.streaming.source import DeltaSource

    t = DeltaTable.create(tmp_table, data=_ids(10))
    source = DeltaSource(t.delta_log, max_files_per_trigger=1)
    # plan the snapshot batch at version 0...
    start = source.initial_offset()
    end = source.latest_offset(start)
    # ...then three single-file commits land before it is served
    for i in range(3):
        t.write(_ids(10, start=100 * (i + 1)))
    source.get_batch(start, end)

    g = telemetry.gauges("streaming.source")
    key = lambda name: (name, (("path", tmp_table),))  # noqa: E731
    # batch 0 served the snapshot (1 file admitted); 3 tail files pending
    assert g[key("streaming.source.backlogFiles")] == 3
    assert g[key("streaming.source.backlogBytes")] > 0
    assert g[key("streaming.source.lastBatchVersionLag")] == 3

    # drain fully: backlog falls to zero
    cur = end
    while True:
        nxt = source.latest_offset(cur)
        if nxt is None:
            break
        source.get_batch(cur, nxt)
        cur = nxt
    g = telemetry.gauges("streaming.source")
    assert g[key("streaming.source.backlogFiles")] == 0
    assert g[key("streaming.source.lastBatchVersionLag")] == 0


def test_streaming_backlog_not_tracked_in_blackout(tmp_table):
    from delta_tpu.streaming.source import DeltaSource

    t = DeltaTable.create(tmp_table, data=_ids(10))
    source = DeltaSource(t.delta_log)
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False):
        start = source.initial_offset()
        end = source.latest_offset(start)
        batch = source.get_batch(start, end)
    assert batch.num_rows == 10
    assert telemetry.gauges("streaming.source") == {}


# -- HTTP endpoint -----------------------------------------------------------


@pytest.fixture
def obs_server():
    srv = ObsServer(port=0)
    yield srv
    srv.stop()


def _get(srv, route):
    c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        c.request("GET", route)
        r = c.getresponse()
        return r.status, r.getheader("Content-Type", ""), r.read()
    finally:
        c.close()


def test_server_healthz_and_metrics(tmp_table, obs_server):
    DeltaTable.create(tmp_table, data=_ids(10))
    status, ctype, body = _get(obs_server, "/healthz")
    assert status == 200 and ctype.startswith("application/json")
    health = json.loads(body)
    assert health["status"] == "ok"
    assert "footerCache" in health

    status, ctype, body = _get(obs_server, "/metrics")
    assert status == 200 and ctype.startswith("text/plain")
    assert body.decode() == telemetry.prometheus_text()
    assert b"commit_total_total" in body


def test_server_events_prefix_and_trace(tmp_table, obs_server):
    DeltaTable.create(tmp_table, data=_ids(10))
    status, _, body = _get(obs_server, "/events?prefix=delta.commit")
    assert status == 200
    events = json.loads(body)
    assert events and all(e["opType"].startswith("delta.commit")
                          for e in events)
    status, _, body = _get(obs_server, "/events?prefix=delta.commit&limit=1")
    assert len(json.loads(body)) == 1

    status, _, body = _get(obs_server, "/trace")
    trace = json.loads(body)
    assert {"delta.commit"} <= {r["name"] for r in trace["traceEvents"]}


def test_server_doctor_route_matches_in_process_report(tmp_table, obs_server):
    """Acceptance: GET /doctor?path= returns the same report as doctor()."""
    import urllib.parse

    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 10}):
        t = DeltaTable.create(tmp_table, data=_ids(300))
    status, _, body = _get(
        obs_server, f"/doctor?path={urllib.parse.quote(tmp_table)}"
    )
    assert status == 200
    served = json.loads(body)
    local = doctor(t).to_dict()
    served.pop("generatedAt"), local.pop("generatedAt")
    assert served == json.loads(json.dumps(local))
    assert served["severity"] == "warn"  # 30 tiny files -> small-file debt
    assert "OPTIMIZE" in served["remedies"]


def test_server_error_routes(obs_server):
    status, _, body = _get(obs_server, "/doctor")
    assert status == 400
    status, _, body = _get(obs_server, "/doctor?path=/nowhere/nothing")
    assert status in (200, 500)  # nonexistent table -> empty report or error
    status, _, body = _get(obs_server, "/nope")
    assert status == 404
    assert "routes" in json.loads(body)


def test_server_garbage_query_params_never_500(tmp_table, obs_server):
    """Regression (ISSUE 15 satellite): `/events?limit=abc` 500'd through
    the bare int() while /router and /advisor degraded — every route's
    numeric params now share one degrading parser (`server._q_int`)."""
    import urllib.parse

    DeltaTable.create(tmp_table, data=_ids(10))
    quoted = urllib.parse.quote(tmp_table)
    routes = [
        "/events?limit=abc", "/events?limit=", "/events?limit=%20",
        "/events?prefix=delta.commit&limit=abc",
        "/router?limit=abc", "/router?limit=1e3",
        f"/advisor?path={quoted}&limit=abc",
        f"/autopilot?path={quoted}&limit=abc",
        f"/doctor?path={quoted}&limit=abc",   # ignored param: still fine
        "/autopilot?limit=abc",
        "/fleet?limit=abc&sweep=bogus&samples=xyz",
        "/fleet?series=&samples=abc",
        "/slo?limit=abc",
        "/metrics?limit=abc", "/healthz?limit=abc", "/trace?limit=abc",
    ]
    for route in routes:
        status, _, body = _get(obs_server, route)
        assert status == 200, (route, body)
    # a malformed limit behaves exactly like an absent one
    _, _, with_garbage = _get(obs_server, "/events?limit=abc")
    _, _, without = _get(obs_server, "/events")
    assert json.loads(with_garbage) == json.loads(without)
    # negative limits clamp to "none" rather than erroring
    status, _, body = _get(obs_server, "/events?limit=-3")
    assert status == 200 and json.loads(body) == []


def test_reply_swallows_client_abort():
    """A client hanging up mid-response must be counted, not logged as a
    500-on-a-dead-socket cascade."""
    from delta_tpu.obs.server import _Handler

    class _DeadWfile:
        def write(self, data):
            raise BrokenPipeError("client went away")

    class _FakeHandler:
        close_connection = False
        wfile = _DeadWfile()

        def send_response(self, status):
            pass

        def send_header(self, k, v):
            pass

        def end_headers(self):
            pass

    before = telemetry.counters("obs.server.clientAborts").get(
        "obs.server.clientAborts", 0)
    fake = _FakeHandler()
    _Handler._reply(fake, 200, b"payload", "application/json")  # no raise
    assert fake.close_connection
    after = telemetry.counters("obs.server.clientAborts")
    assert after["obs.server.clientAborts"] == before + 1

    class _ResetWfile:
        def write(self, data):
            raise ConnectionResetError("reset")

    fake = _FakeHandler()
    fake.wfile = _ResetWfile()
    _Handler._reply(fake, 200, b"payload", "application/json")
    assert telemetry.counters("obs.server.clientAborts")[
        "obs.server.clientAborts"] == before + 2


def test_server_fleet_and_slo_routes(tmp_table, obs_server):
    from delta_tpu.obs import fleet

    t = DeltaTable.create(tmp_table, data=_ids(10))
    status, _, body = _get(obs_server, "/fleet")
    assert status == 200
    doc = json.loads(body)
    assert doc["tables"] >= 1
    assert any(e["path"] == tmp_table for e in doc["entries"])
    assert doc["sweep"]["kind"] == "doctor"
    status, _, body = _get(obs_server, "/fleet?sweep=advisor&limit=1")
    doc = json.loads(body)
    assert doc["sweep"]["kind"] == "advisor"
    assert len(doc["sweep"]["entries"]) <= 1
    status, _, body = _get(obs_server, "/fleet?sweep=none&series=fleet")
    doc = json.loads(body)
    assert "sweep" not in doc and "series" in doc

    status, _, body = _get(obs_server, "/slo")
    assert status == 200
    doc = json.loads(body)
    assert {o["name"] for o in doc["objectives"]} == {
        "commitLatencyP99", "scanPlanningP99", "commitConflictRate",
        "retryExhaustion", "journalDropRate"}
    fleet.unregister(tmp_table)
    del t


def test_start_server_requires_opt_in():
    from delta_tpu.obs.server import start_server

    assert conf.get("delta.tpu.obs.port") is None
    with pytest.raises(ValueError):
        start_server()


def test_start_server_reads_conf_port():
    from delta_tpu.obs.server import start_server, stop_server

    with conf.set_temporarily(delta__tpu__obs__port=0):
        srv = start_server()
        try:
            status, _, _ = _get(srv, "/healthz")
            assert status == 200
            # idempotent: second call returns the same server
            assert start_server() is srv
        finally:
            stop_server()


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_off_by_default(tmp_path):
    assert conf.get("delta.tpu.obs.incidentDir") is None
    with pytest.raises(ValueError):
        with telemetry.record_operation("delta.test.noincident"):
            raise ValueError("boom")
    assert flight_recorder.incident_files(str(tmp_path)) == []


def test_commit_conflict_writes_one_incident_with_span_stack(tmp_table, tmp_path):
    """Acceptance: a forced commit conflict leaves exactly one incident JSON
    containing the failing span stack (commit -> write -> conflictCheck)."""
    inc_dir = str(tmp_path / "incidents")
    log = DeltaLog.for_table(tmp_table)
    txn = log.start_transaction()
    txn.update_metadata(init_metadata())
    txn.commit([], ops.ManualUpdate())
    log.start_transaction().commit(
        [AddFile("f0", {}, 1, 1)], ops.Write(mode="Append"))

    a = log.start_transaction()
    a.filter_files()
    b = log.start_transaction()
    b.filter_files()
    b.commit([RemoveFile("f0", deletion_timestamp=1)], ops.Delete())

    with conf.set_temporarily(delta__tpu__obs__incidentDir=inc_dir):
        with pytest.raises(errors.ConcurrentDeleteReadException):
            a.commit([AddFile("a1", {}, 1, 1)], ops.Write(mode="Append"))

    files = flight_recorder.incident_files(inc_dir)
    assert len(files) == 1, "one failure = one incident file"
    with open(files[0], encoding="utf-8") as f:
        incident = json.load(f)
    assert "ConcurrentDeleteReadException" in incident["error"]
    stack = [s["opType"] for s in incident["spanStack"]]
    assert stack == ["delta.commit", "delta.commit.write",
                     "delta.commit.retry.conflictCheck"]
    assert incident["opType"] == "delta.commit.retry.conflictCheck"
    assert incident["recentEvents"]  # ring-buffer tail rides along
    assert incident["counters"].get("commit.conflicts", 0) == 1
    assert telemetry.counters("obs.incidents") == {"obs.incidents.written": 1}


def test_flight_recorder_keep_bound(tmp_path):
    inc_dir = str(tmp_path / "incidents")
    with conf.set_temporarily(delta__tpu__obs__incidentDir=inc_dir,
                              delta__tpu__obs__incidentKeep=3):
        for i in range(5):
            with pytest.raises(ValueError):
                with telemetry.record_operation("delta.test.boom"):
                    raise ValueError(f"boom {i}")
    files = flight_recorder.incident_files(inc_dir)
    assert len(files) == 3
    kept = [json.load(open(f, encoding="utf-8"))["error"] for f in files]
    assert kept == ["ValueError: boom 2", "ValueError: boom 3",
                    "ValueError: boom 4"]  # oldest pruned first


def test_flight_recorder_nested_spans_single_incident(tmp_path):
    inc_dir = str(tmp_path / "incidents")
    with conf.set_temporarily(delta__tpu__obs__incidentDir=inc_dir):
        with pytest.raises(RuntimeError):
            with telemetry.record_operation("delta.test.outer"):
                with telemetry.record_operation("delta.test.outer.inner"):
                    raise RuntimeError("deep")
    files = flight_recorder.incident_files(inc_dir)
    assert len(files) == 1
    incident = json.load(open(files[0], encoding="utf-8"))
    # recorded at the innermost span: fullest stack
    assert [s["opType"] for s in incident["spanStack"]] == [
        "delta.test.outer", "delta.test.outer.inner"]


# -- blackout: obs layer is off or zero-overhead when telemetry is off -------


def test_obs_blackout_smoke(tmp_table, tmp_path):
    inc_dir = str(tmp_path / "incidents")
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False,
                              delta__tpu__obs__incidentDir=inc_dir):
        t = DeltaTable.create(tmp_table, data=_ids(100))
        # doctor still computes (pull-by-call is the operator asking) but
        # records no events
        report = t.doctor()
        assert report.severity in ("ok", "warn", "critical")
        assert telemetry.recent_events() == []
        # scans produce no reports
        scan_report_mod.clear_last_report()
        t.to_arrow(filters=["id < 5"])
        assert last_scan_report() is None
        # failing spans never reach the recorder: no incidents
        with pytest.raises(ValueError):
            with telemetry.record_operation("delta.test.dark"):
                raise ValueError("unseen")
    assert flight_recorder.incident_files(inc_dir) == []
