"""The distributed-trace plane: trace identity (128-bit trace ids, namespaced
span ids, the traceparent-shaped wire carrier), head sampling and its forced
paths (errors, SLO burn windows), the JSONL span spool + cross-process
collector (``obs/trace_store``), straggler/critical-path analysis, the
``/traces`` routes, and the flight-recorder exemplar link. The end-to-end
2-process stitch lives in ``test_multihost.py``; these are the unit
contracts it stands on.
"""
import http.client
import json
import os
import threading

import pytest

from delta_tpu.obs import trace_store
from delta_tpu.obs.server import ObsServer
from delta_tpu.parallel.executor import run_sharded
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.clear_events()
    yield
    telemetry.clear_events()
    trace_store.reset()


def _get(srv, route):
    c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        c.request("GET", route)
        r = c.getresponse()
        return r.status, r.read()
    finally:
        c.close()


# -- trace identity ----------------------------------------------------------


def test_root_span_mints_trace_id_children_inherit():
    with telemetry.record_operation("delta.test.root") as root:
        assert telemetry.current_trace_id() == root.trace_id
        with telemetry.record_operation("delta.test.child") as child:
            pass
        telemetry.record_event("delta.test.mark")
    [mark] = telemetry.recent_events("delta.test.mark")
    assert len(root.trace_id) == 32
    int(root.trace_id, 16)  # hex
    assert child.trace_id == root.trace_id
    assert mark.trace_id == root.trace_id
    # the trace ends with its root: sequential roots are distinct traces
    assert telemetry.current_trace_id() is None
    with telemetry.record_operation("delta.test.root2") as root2:
        pass
    assert root2.trace_id != root.trace_id


def test_span_ids_share_the_process_namespace():
    with telemetry.record_operation("delta.test.a") as a:
        pass
    with telemetry.record_operation("delta.test.b") as b:
        pass
    assert a.span_id != b.span_id
    # high word = the per-process random namespace, low word = the counter —
    # two hosts' spools cannot collide when stitched
    assert a.span_id >> 32 == b.span_id >> 32 == telemetry._SPAN_NS >> 32


def test_wire_carrier_round_trip():
    with telemetry.record_operation("delta.test.coord") as root:
        wire = telemetry.span_context(wire=True)
    assert wire == "00-%s-%016x-01" % (root.trace_id, root.span_id)
    with telemetry.adopt_span_context(wire):
        assert telemetry.current_trace_id() == root.trace_id
        with telemetry.record_operation("delta.test.remote") as child:
            pass
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert telemetry.current_trace_id() is None
    # no active trace → nothing to put on the wire
    assert telemetry.span_context(wire=True) is None
    with pytest.raises(ValueError):
        with telemetry.adopt_span_context("not-a-traceparent"):
            pass


def test_thread_carrier_keeps_trace_and_parent():
    # pool threads do not inherit contextvars: the carrier must hand over
    # both the span chain (legacy tuple contract) and the trace state
    assert telemetry.span_context() == ()
    out = {}

    def work(carrier):
        with telemetry.adopt_span_context(carrier):
            with telemetry.record_operation("delta.test.pooled") as ev:
                out["ev"] = ev

    with telemetry.record_operation("delta.test.submit") as root:
        carrier = telemetry.span_context()
        assert carrier == (root.span_id,)
        t = threading.Thread(target=work, args=(carrier,))
        t.start()
        t.join()
    assert out["ev"].parent_id == root.span_id
    assert out["ev"].trace_id == root.trace_id


# -- sampling + spool --------------------------------------------------------


def test_spool_stitch_and_index_round_trip(tmp_path):
    spool = str(tmp_path / "spool")
    before = telemetry.counters().get("trace.spansSpooled", 0)
    with conf.set_temporarily(**{"delta.tpu.trace.dir": spool,
                                 "delta.tpu.trace.sampleRate": 1.0}):
        with telemetry.record_operation("delta.test.parent", path="/t") as root:
            telemetry.record_event("delta.test.mark", {"n": 1})
            with telemetry.record_operation("delta.test.child"):
                pass
    trace_store.reset()
    assert telemetry.counters()["trace.spansSpooled"] - before >= 3

    rows = trace_store.read_spools(spool, root.trace_id)
    by_op = {r["op"]: r for r in rows}
    assert set(by_op) == {"delta.test.parent", "delta.test.mark",
                          "delta.test.child"}
    assert {r["traceId"] for r in rows} == {root.trace_id}
    assert by_op["delta.test.parent"]["parentId"] is None
    assert by_op["delta.test.child"]["parentId"] == root.span_id
    # instants spool too (no span id, no duration), parented in place
    assert by_op["delta.test.mark"]["spanId"] is None
    assert by_op["delta.test.mark"]["durUs"] is None
    assert by_op["delta.test.mark"]["parentId"] == root.span_id

    trace = trace_store.stitch_trace(spool, root.trace_id)
    spans = [r for r in trace["traceEvents"] if r.get("cat") == "delta"]
    assert len(spans) == len(rows) == 3
    phases = {r["name"]: r["ph"] for r in spans}
    assert phases["delta.test.parent"] == "X"
    assert phases["delta.test.mark"] == "i"
    assert all(r["args"]["traceId"] == root.trace_id for r in spans)
    meta = {r["name"] for r in trace["traceEvents"]} - {s["name"] for s in spans}
    assert {"process_name", "thread_name"} <= meta
    assert trace_store.stitch_trace(spool, "f" * 32) is None

    [row] = trace_store.recent_traces(spool)
    assert row["traceId"] == root.trace_id
    assert row["rootOp"] == "delta.test.parent"
    assert row["spans"] == 3 and row["processes"] == 1 and row["errors"] == 0


def test_sample_rate_zero_is_inert_and_errors_force_sample(tmp_path):
    spool = str(tmp_path / "spool")
    with conf.set_temporarily(**{"delta.tpu.trace.dir": spool,
                                 "delta.tpu.trace.sampleRate": 0.0}):
        with telemetry.record_operation("delta.test.quiet"):
            telemetry.record_event("delta.test.quiet.mark")
        # unsampled: the sink never ran, the spool dir was never created
        assert not os.path.exists(spool)
        with pytest.raises(ValueError):
            with telemetry.record_operation("delta.test.outer"):
                with telemetry.record_operation("delta.test.boom") as boom:
                    raise ValueError("kapow")
        rows = trace_store.read_spools(spool)
    trace_store.reset()
    # the error force-sampled the WHOLE trace: both spans spooled
    assert {r["op"] for r in rows} == {"delta.test.boom", "delta.test.outer"}
    assert {r["traceId"] for r in rows} == {boom.trace_id}
    [err_row] = [r for r in rows if r["op"] == "delta.test.boom"]
    assert "kapow" in err_row["error"]
    assert telemetry.last_sampled_trace_id() == boom.trace_id


def test_slo_burn_window_forces_sampling(tmp_path):
    from delta_tpu.obs import slo

    spool = str(tmp_path / "spool")
    alert = slo.SloAlert(objective="test.burn", table="", path=None,
                         fired_at_ms=0, burn_fast=2.0, burn_slow=2.0,
                         threshold=1.0, observed=2.0)
    with slo._LOCK:
        slo._ALERTS[alert.key] = alert
    try:
        assert slo.firing_count() == 1
        with conf.set_temporarily(**{"delta.tpu.trace.dir": spool,
                                     "delta.tpu.trace.sampleRate": 0.0}):
            with telemetry.record_operation("delta.test.burning") as ev:
                pass
            rows = trace_store.read_spools(spool)
    finally:
        with slo._LOCK:
            slo._ALERTS.pop(alert.key, None)
        trace_store.reset()
    # rate 0, no error — but the burn window forced an exemplar trace
    assert [r["op"] for r in rows] == ["delta.test.burning"]
    assert rows[0]["traceId"] == ev.trace_id


def test_spool_byte_cap_drops_instead_of_filling_disk(tmp_path):
    spool = str(tmp_path / "spool")
    before = telemetry.counters().get("trace.spansDropped", 0)
    with conf.set_temporarily(**{"delta.tpu.trace.dir": spool,
                                 "delta.tpu.trace.sampleRate": 1.0,
                                 "delta.tpu.trace.maxBytes": 400}):
        for i in range(8):
            with telemetry.record_operation("delta.test.capped",
                                            {"i": i, "pad": "x" * 64}):
                pass
        rows = trace_store.read_spools(spool)
    trace_store.reset()
    assert 0 < len(rows) < 8
    assert telemetry.counters()["trace.spansDropped"] > before


def test_disabled_telemetry_spools_nothing_and_allocates_nothing(tmp_path):
    import tracemalloc

    spool = str(tmp_path / "spool")
    with conf.set_temporarily(**{"delta.tpu.trace.dir": spool,
                                 "delta.tpu.telemetry.enabled": False}):
        with telemetry.record_operation("delta.test.dark"):
            telemetry.record_event("delta.test.dark.mark")
        assert not os.path.exists(spool)
        assert telemetry.current_trace_id() is None
        # the hot counter path must stay allocation-free under blackout:
        # steady-state increments of an existing key retain no memory
        telemetry.bump_counter("delta.test.hot")
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            for _ in range(1000):
                telemetry.bump_counter("delta.test.hot")
            grown = tracemalloc.get_traced_memory()[0] - base
        finally:
            tracemalloc.stop()
    assert grown < 512, f"hot counter path retained {grown} bytes"


# -- sharded-executor span topology ------------------------------------------


def test_run_sharded_pool_spans_parent_under_job():
    sizes = [10, 20, 30, 40, 50, 60]
    with telemetry.record_operation("delta.test.harness") as root:
        rep = run_sharded(list(range(6)), lambda x: x * 2, sizes=sizes,
                          workers=2, label="unit")
    assert rep.results == [0, 2, 4, 6, 8, 10]
    evs = telemetry.recent_events("delta.dist")
    [job] = [e for e in evs if e.op_type == "delta.dist.job"]
    assert job.parent_id == root.span_id
    assert job.tags["job"] == "unit"
    assert sum(job.data["lptBytes"]) == sum(sizes)
    assert len(job.data["lptBytes"]) == 2
    workers = [e for e in evs if e.op_type == "delta.dist.worker"]
    assert len(workers) == 2
    assert all(w.parent_id == job.span_id for w in workers)
    assert {w.tags["worker"] for w in workers} == {"0", "1"}
    items = [e for e in evs if e.op_type == "delta.dist.item"]
    assert len(items) == 6
    wids = {w.span_id for w in workers}
    assert all(i.parent_id in wids for i in items)
    assert {i.data["index"] for i in items} == set(range(6))
    assert {i.data["bytes"] for i in items} == set(sizes)
    assert all(isinstance(i.data["stolen"], bool) for i in items)
    # one trace covers the harness, the job, every worker and every item
    assert {e.trace_id for e in evs} == {root.trace_id}


@pytest.mark.parametrize("posture, knob", [
    pytest.param(p, k, id=p) for p, k in [
        ("sampled", {"delta.tpu.trace.sampleRate": 1.0}),
        ("unsampled", {"delta.tpu.trace.sampleRate": 0.0}),
        ("disabled", {"delta.tpu.telemetry.enabled": False})]])
def test_sharded_optimize_under_each_tracing_posture(tmp_path, posture, knob):
    """The pool path of a real command (job, worker and item spans across
    threads) under the three postures an operator can set: the compaction
    is the same, `sampleRate=1` spools its spans, and `sampleRate=0` or
    telemetry off never creates the spool directory."""
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.commands.write import WriteIntoDelta

    log = DeltaLog.for_table(str(tmp_path / "t"))
    for i in range(12):  # 4 partitions x 3 files
        WriteIntoDelta(log, "append", pa.table({
            "id": pa.array(range(i * 8, i * 8 + 8), pa.int64()),
            "part": pa.array([f"p{i % 4}"] * 8),
        }), partition_columns=["part"]).run()
    spool = str(tmp_path / "spool")
    cmd = OptimizeCommand(log, min_file_size=1 << 30, workers=4)
    with conf.set_temporarily(**{"delta.tpu.trace.dir": spool}, **knob):
        cmd.run()
    trace_store.reset()
    assert cmd.metrics["numRemovedFiles"] == 12
    assert cmd.metrics["numAddedFiles"] == 4
    if posture == "sampled":
        ops = {r["op"] for r in trace_store.read_spools(spool)}
        assert {"delta.dist.job", "delta.dist.worker",
                "delta.dist.item"} <= ops
    else:
        assert not os.path.exists(spool), f"{posture} touched the spool"


def test_run_sharded_inline_path_spans_items_under_job():
    rep = run_sharded([3, 4], lambda x: x + 1, sizes=[5, 7], workers=1,
                      label="inline")
    assert rep.results == [4, 5]
    evs = telemetry.recent_events("delta.dist")
    [job] = [e for e in evs if e.op_type == "delta.dist.job"]
    assert job.data["lptBytes"] == [12]  # one bin: the whole byte weight
    assert not [e for e in evs if e.op_type == "delta.dist.worker"]
    items = [e for e in evs if e.op_type == "delta.dist.item"]
    assert [i.parent_id for i in items] == [job.span_id] * 2


# -- analysis ----------------------------------------------------------------


def _synthetic_spool(tmp_path) -> str:
    """A hand-built two-worker OPTIMIZE trace with known makespans: worker 0
    holds 100 of 150 bytes and runs 30ms, worker 1 holds 50 and runs 10ms
    (one of its items stolen), under a 40ms root."""
    tid = "ab" * 16
    rows = [
        {"spanId": 1, "parentId": None, "op": "delta.cmd.optimize",
         "tsUs": 0, "durUs": 40000, "tags": {}, "data": {}},
        {"spanId": 2, "parentId": 1, "op": "delta.dist.job",
         "tsUs": 1000, "durUs": 35000, "tags": {"job": "optimize"},
         "data": {"skew": 2.0, "lptBytes": [100, 50], "steals": 1}},
        {"spanId": 3, "parentId": 2, "op": "delta.dist.worker",
         "tsUs": 1000, "durUs": 30000,
         "tags": {"job": "optimize", "worker": "0"}, "data": {}},
        {"spanId": 4, "parentId": 2, "op": "delta.dist.worker",
         "tsUs": 1000, "durUs": 10000,
         "tags": {"job": "optimize", "worker": "1"}, "data": {}},
        {"spanId": 5, "parentId": 3, "op": "delta.dist.item",
         "tsUs": 1000, "durUs": 30000, "tags": {},
         "data": {"index": 0, "bytes": 100, "stolen": False}},
        {"spanId": 6, "parentId": 4, "op": "delta.dist.item",
         "tsUs": 1000, "durUs": 6000, "tags": {},
         "data": {"index": 1, "bytes": 40, "stolen": False}},
        {"spanId": 7, "parentId": 4, "op": "delta.dist.item",
         "tsUs": 8000, "durUs": 3000, "tags": {},
         "data": {"index": 2, "bytes": 10, "stolen": True}},
    ]
    spool = tmp_path / "spool"
    spool.mkdir()
    with open(spool / "spool-7-1.jsonl", "w") as f:
        for r in rows:
            r.update(traceId=tid, pid=7, tid=1, thread="main", error=None)
            f.write(json.dumps(r) + "\n")
    return str(spool), tid


def test_analyze_trace_names_straggler_and_critical_path(tmp_path):
    spool, tid = _synthetic_spool(tmp_path)
    a = trace_store.analyze_trace(spool, tid)
    assert a["traceId"] == tid
    assert a["rootOp"] == "delta.cmd.optimize"
    assert a["spans"] == 7 and a["processes"] == [7] and a["errors"] == []
    assert a["durationUs"] == 40000

    # critical path: root → job → the 30ms worker → its 30ms item
    assert [p["op"] for p in a["criticalPath"]] == [
        "delta.cmd.optimize", "delta.dist.job", "delta.dist.worker",
        "delta.dist.item"]
    assert a["criticalPath"][0]["selfUs"] == 5000  # 40ms minus the 35ms job

    [job] = a["jobs"]
    assert job["label"] == "optimize"
    assert job["workers"] == 2 and job["items"] == 3
    assert job["skew"] == 2.0 and job["lptBytes"] == [100, 50]
    # busy total 40ms; LPT shares 100/150 and 50/150 predict 26.6ms / 13.3ms
    w0, w1 = job["shards"]
    assert (w0["worker"], w0["busyUs"], w0["predictedUs"], w0["deltaUs"]) == \
        (0, 30000, 26666, 3334)
    assert (w1["worker"], w1["busyUs"], w1["deltaUs"]) == (1, 10000, -3333)
    assert (w0["bytes"], w1["bytes"]) == (100, 50)
    assert (w1["items"], w1["stolen"]) == (2, 1)
    assert job["straggler"] == w0 == a["straggler"]
    assert job["slowestItem"] == {"index": 0, "bytes": 100, "durUs": 30000,
                                  "stolen": False, "pid": 7}
    assert job["stealRescue"] == {"items": 1, "bytes": 10, "busyUs": 3000}
    assert trace_store.analyze_trace(spool, "0" * 32) is None


def test_read_spools_skips_corrupt_lines(tmp_path):
    spool, tid = _synthetic_spool(tmp_path)
    # a process killed mid-append leaves a torn tail line
    with open(os.path.join(spool, "spool-7-1.jsonl"), "a") as f:
        f.write('{"traceId": "' + tid + '", "spanId": 8, "op": "torn')
    rows = trace_store.read_spools(spool, tid)
    assert len(rows) == 7
    assert trace_store.analyze_trace(spool, tid)["spans"] == 7


# -- HTTP routes -------------------------------------------------------------


@pytest.fixture
def obs_server():
    srv = ObsServer(port=0)
    yield srv
    srv.stop()


def test_trace_route_op_prefix_and_limit(obs_server):
    with telemetry.record_operation("delta.test.alpha"):
        pass
    with telemetry.record_operation("delta.test.beta"):
        pass
    with telemetry.record_operation("other.gamma"):
        pass
    status, body = _get(obs_server, "/trace?op=delta.test")
    assert status == 200
    names = [r["name"] for r in json.loads(body)["traceEvents"]
             if r.get("cat") == "delta"]
    assert set(names) == {"delta.test.alpha", "delta.test.beta"}
    status, body = _get(obs_server, "/trace?op=delta.test&limit=1")
    names = [r["name"] for r in json.loads(body)["traceEvents"]
             if r.get("cat") == "delta"]
    assert names == ["delta.test.beta"]
    # malformed limit degrades to the default view, never 500s
    status, body = _get(obs_server, "/trace?op=delta.test&limit=abc")
    assert status == 200
    assert len([r for r in json.loads(body)["traceEvents"]
                if r.get("cat") == "delta"]) == 2


def test_traces_routes_serve_index_stitch_and_analysis(tmp_path, obs_server):
    status, body = _get(obs_server, "/traces")
    assert status == 400 and b"delta.tpu.trace.dir" in body

    spool = str(tmp_path / "spool")
    with conf.set_temporarily(**{"delta.tpu.trace.dir": spool,
                                 "delta.tpu.trace.sampleRate": 1.0}):
        with telemetry.record_operation("delta.test.served") as root:
            with telemetry.record_operation("delta.test.served.child"):
                pass
        status, body = _get(obs_server, "/traces")
        assert status == 200
        [row] = json.loads(body)
        assert row["traceId"] == root.trace_id and row["spans"] == 2

        status, body = _get(obs_server, f"/traces/{root.trace_id}")
        assert status == 200
        trace = json.loads(body)
        assert trace["otherData"]["traceId"] == root.trace_id
        assert len([r for r in trace["traceEvents"]
                    if r.get("cat") == "delta"]) == 2

        status, body = _get(obs_server,
                            f"/traces/{root.trace_id}?analyze=1")
        assert status == 200
        assert json.loads(body)["rootOp"] == "delta.test.served"

        status, body = _get(obs_server, "/traces/" + "0" * 32)
        assert status == 404 and b"no spooled spans" in body
    trace_store.reset()


# -- flight-recorder exemplar ------------------------------------------------


def test_incident_carries_trace_id_once_per_exception(tmp_path):
    from delta_tpu.obs import flight_recorder

    inc_dir = str(tmp_path / "incidents")
    spool = str(tmp_path / "spool")
    flight_recorder.install()
    with conf.set_temporarily(**{"delta.tpu.obs.incidentDir": inc_dir,
                                 "delta.tpu.trace.dir": spool,
                                 "delta.tpu.trace.sampleRate": 0.0}):
        with pytest.raises(RuntimeError):
            with telemetry.record_operation("delta.test.outer") as outer:
                with telemetry.record_operation("delta.test.mid"):
                    with telemetry.record_operation("delta.test.inner"):
                        raise RuntimeError("boom")
        rows = trace_store.read_spools(spool)
    trace_store.reset()
    # one exception through three nested spans = ONE incident ...
    [path] = flight_recorder.incident_files(inc_dir)
    with open(path) as f:
        incident = json.load(f)
    assert incident["opType"] == "delta.test.inner"
    assert "boom" in incident["error"]
    # ... whose traceId links to a force-sampled, stitchable trace
    assert incident["traceId"] == outer.trace_id
    assert {r["traceId"] for r in rows} == {outer.trace_id}
    assert len(rows) == 3


# -- one clock with the device trace ------------------------------------------


def test_spans_are_host_events_of_an_open_profiler_session(tmp_path):
    """With a ``jax.profiler`` session open, every span of the program is an
    event of the ``.xplane.pb`` itself (``TraceAnnotation``), on the
    profiler's clock; the operator who traces a slow MERGE sees the engine's
    phases beside the device's planes."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with telemetry.record_operation("delta.test.beforeSession"):
        pass  # no session open: the annotation is a flag test, nothing kept
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with telemetry.record_operation("delta.test.profiled") as outer:
            with telemetry.record_operation("delta.test.profiled.phase"):
                jax.block_until_ready(jax.numpy.arange(8) + 1)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("delta.test."):
                    found[e.name] = (e.start_ns, e.duration_ns)
    assert set(found) == {"delta.test.profiled", "delta.test.profiled.phase"}
    (a, da), (b, db) = found["delta.test.profiled"], found["delta.test.profiled.phase"]
    assert a <= b and b + db <= a + da  # nested on the profiler's clock too
    # and the two clocks agree on how long the span took, to well under the span of a phase
    assert abs(da / 1000 - outer.duration_us) < 20_000
