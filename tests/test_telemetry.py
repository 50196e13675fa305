"""Usage-logging telemetry (SURVEY §5; ``metering/DeltaLogging.scala:50-109``):
hierarchical spans (contextvar nesting, Chrome-trace export), the metrics
registry (counters/gauges/log-bucket histograms, Prometheus exposition),
CommitStats parity events, and the engine wiring. (The AST lints that used
to live here — command-entry-point instrumentation, the metric catalog and
its DESCRIPTIONS — are now passes in the ``delta_tpu/analysis`` engine,
exercised by ``tests/test_analysis.py`` and ``tools/analyze.py``.)
"""
import json
import os
import threading

import pyarrow as pa
import pytest

from delta_tpu.api.tables import DeltaTable
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_buffer():
    telemetry.clear_events()
    yield
    telemetry.clear_events()


def test_record_event_and_query_by_prefix():
    telemetry.record_event("delta.test.alpha", {"n": 1}, path="/t")
    telemetry.record_event("delta.test.beta", {"n": 2})
    telemetry.record_event("other.op")
    got = telemetry.recent_events("delta.test")
    assert [e.op_type for e in got] == ["delta.test.alpha", "delta.test.beta"]
    assert got[0].tags == {"path": "/t"}
    assert got[0].data == {"n": 1}


def test_record_operation_captures_duration():
    with telemetry.record_operation("delta.test.op") as ev:
        pass
    [got] = telemetry.recent_events("delta.test.op")
    assert got is ev
    assert got.duration_ms is not None and got.duration_ms >= 0
    assert got.error is None


def test_record_operation_captures_error_and_reraises():
    with pytest.raises(ValueError):
        with telemetry.record_operation("delta.test.boom"):
            raise ValueError("kapow")
    [got] = telemetry.recent_events("delta.test.boom")
    assert got.error and "kapow" in got.error


def test_event_json_round_trips():
    telemetry.record_event("delta.test.json", {"k": [1, 2]}, table="x")
    [ev] = telemetry.recent_events("delta.test.json")
    d = json.loads(ev.to_json())
    assert d["opType"] == "delta.test.json"
    assert d["data"] == {"k": [1, 2]}


def test_prefix_matching_respects_dotted_boundaries():
    """`recent_events("delta.commit")` must not match `delta.commitFoo.*`."""
    telemetry.record_event("delta.commit")
    telemetry.record_event("delta.commit.stats")
    telemetry.record_event("delta.commitFoo")
    telemetry.record_event("delta.commitFoo.bar")
    got = [e.op_type for e in telemetry.recent_events("delta.commit")]
    assert got == ["delta.commit", "delta.commit.stats"]

    telemetry.clear_counters()
    telemetry.bump_counter("scan.files", 1)
    telemetry.bump_counter("scan.files.read", 2)
    telemetry.bump_counter("scan.filesFoo", 3)
    assert telemetry.counters("scan.files") == {
        "scan.files": 1, "scan.files.read": 2,
    }


def test_ring_buffer_bounded():
    for _ in range(5000):
        telemetry.record_event("delta.test.flood")
    # deque(maxlen=4096): exactly full — also catches silent non-recording
    assert len(telemetry.recent_events()) == 4096


def test_ring_buffer_size_configurable():
    with conf.set_temporarily(delta__tpu__telemetry__bufferSize=16):
        for _ in range(100):
            telemetry.record_event("delta.test.small")
        assert len(telemetry.recent_events()) == 16
    # back to the default on the next record
    telemetry.record_event("delta.test.restored")
    assert len(telemetry.recent_events()) == 17  # resize preserves contents


# -- hierarchical spans ------------------------------------------------------


def test_span_nesting_parent_child_ordering():
    with telemetry.record_operation("delta.test.outer") as outer:
        telemetry.record_event("delta.test.point")
        with telemetry.record_operation("delta.test.outer.mid") as mid:
            with telemetry.record_operation("delta.test.outer.mid.leaf") as leaf:
                pass
    assert outer.parent_id is None and outer.depth == 0
    assert mid.parent_id == outer.span_id and mid.depth == 1
    assert leaf.parent_id == mid.span_id and leaf.depth == 2
    # point events parent to the enclosing span
    [pt] = telemetry.recent_events("delta.test.point")
    assert pt.parent_id == outer.span_id
    # children close (and land in the buffer) before their parent
    order = [e.op_type for e in telemetry.recent_events("delta.test")]
    assert order.index("delta.test.outer.mid.leaf") < order.index("delta.test.outer.mid")
    assert order.index("delta.test.outer.mid") < order.index("delta.test.outer")


def test_span_data_attaches_to_innermost_open_span():
    with telemetry.record_operation("delta.test.host") as ev:
        telemetry.add_span_data(rows=7)
    assert ev.data == {"rows": 7}
    # no open span: silently a no-op
    telemetry.add_span_data(ignored=True)


def test_span_nesting_isolated_across_threads():
    """Each thread gets its own contextvar stack: concurrent spans never
    parent across threads, and nesting inside each thread stays intact."""
    results = {}
    barrier = threading.Barrier(2)

    def worker(name):
        barrier.wait()
        with telemetry.record_operation(f"delta.test.{name}") as root:
            barrier.wait()  # both roots open simultaneously
            with telemetry.record_operation(f"delta.test.{name}.child") as child:
                pass
        results[name] = (root, child)

    ts = [threading.Thread(target=worker, args=(n,)) for n in ("t1", "t2")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    r1, c1 = results["t1"]
    r2, c2 = results["t2"]
    assert r1.parent_id is None and r2.parent_id is None
    assert c1.parent_id == r1.span_id
    assert c2.parent_id == r2.span_id
    assert r1.span_id != r2.span_id
    assert c1.thread_id != c2.thread_id


def test_span_stack_snapshot_reports_open_chain():
    assert telemetry.span_stack_snapshot() == []
    with telemetry.record_operation("delta.test.a", path="/t") as a:
        with telemetry.record_operation("delta.test.a.b") as b:
            telemetry.add_span_data(rows=3)
            snap = telemetry.span_stack_snapshot()
    assert [s["opType"] for s in snap] == ["delta.test.a", "delta.test.a.b"]
    assert snap[0]["spanId"] == a.span_id and snap[1]["parentId"] == a.span_id
    assert snap[1]["data"] == {"rows": 3}
    assert snap[0]["tags"] == {"path": "/t"}
    assert all(s["elapsedMs"] >= 0 for s in snap)
    assert b.span_id  # snapshot is JSON-able copies, not the live events
    json.dumps(snap)


def test_failure_hooks_fire_once_per_span_with_stack():
    calls = []

    def hook(ev, exc):
        calls.append((ev.op_type, str(exc),
                      [s["opType"] for s in telemetry.span_stack_snapshot()]))

    telemetry.add_failure_hook(hook)
    try:
        with pytest.raises(ValueError):
            with telemetry.record_operation("delta.test.outer"):
                with telemetry.record_operation("delta.test.outer.leaf"):
                    raise ValueError("pow")
    finally:
        telemetry.remove_failure_hook(hook)
    # innermost fires first, with the full open stack; the same exception
    # then fires again as it unwinds the outer span
    assert calls[0] == ("delta.test.outer.leaf", "pow",
                        ["delta.test.outer", "delta.test.outer.leaf"])
    assert calls[1] == ("delta.test.outer", "pow", ["delta.test.outer"])
    # a broken hook never masks the real error
    broken = lambda ev, exc: 1 / 0  # noqa: E731
    telemetry.add_failure_hook(broken)
    try:
        with pytest.raises(ValueError):
            with telemetry.record_operation("delta.test.brokenhook"):
                raise ValueError("real")
    finally:
        telemetry.remove_failure_hook(broken)


def test_chrome_trace_includes_open_spans_with_clamped_duration():
    """Regression: spans still open at export time used to be dropped (they
    live in _ACTIVE, not the ring buffer) — they must export as clamped
    complete events flagged incomplete."""
    telemetry.clear_events()
    with telemetry.record_operation("delta.test.live") as live:
        with telemetry.record_operation("delta.test.live.closedchild"):
            pass
        trace = telemetry.export_chrome_trace()
        rows = [r for r in trace["traceEvents"]
                if r.get("name") == "delta.test.live"]
        assert len(rows) == 1, "open span must appear exactly once"
        [row] = rows
        assert row["ph"] == "X" and row["dur"] >= 0
        assert row["args"]["incomplete"] is True
        assert row["args"]["spanId"] == live.span_id
        # the closed child exported normally alongside it
        assert any(r.get("name") == "delta.test.live.closedchild"
                   and "incomplete" not in r["args"]
                   for r in trace["traceEvents"])
    # after the span closes, a fresh export has the real (final) row only
    trace = telemetry.export_chrome_trace()
    rows = [r for r in trace["traceEvents"]
            if r.get("name") == "delta.test.live"]
    assert len(rows) == 1 and "incomplete" not in rows[0]["args"]


# -- metrics registry --------------------------------------------------------


def test_histogram_bucket_boundaries():
    telemetry.reset_all()
    telemetry.observe("delta.test.hist", 1.0)     # == first bound -> le=1
    telemetry.observe("delta.test.hist", 1.5)     # -> le=2
    telemetry.observe("delta.test.hist", 2.0)     # == bound -> le=2
    telemetry.observe("delta.test.hist", 65536.0)  # == last bound
    telemetry.observe("delta.test.hist", 1e9)     # -> +Inf
    [(key, h)] = telemetry.histograms("delta.test.hist").items()
    assert key == ("delta.test.hist", ())
    bounds = telemetry.HISTOGRAM_BUCKETS
    assert h.counts[bounds.index(1.0)] == 1
    assert h.counts[bounds.index(2.0)] == 2
    assert h.counts[bounds.index(65536.0)] == 1
    assert h.counts[-1] == 1  # +Inf
    assert h.count == 5
    assert h.sum == pytest.approx(1.0 + 1.5 + 2.0 + 65536.0 + 1e9)


def test_gauges_with_labels():
    telemetry.reset_all()
    telemetry.set_gauge("delta.test.gauge", 3, path="/a")
    telemetry.set_gauge("delta.test.gauge", 5, path="/a")  # overwrite
    telemetry.set_gauge("delta.test.gauge", 7, path="/b")
    g = telemetry.gauges("delta.test.gauge")
    assert g[("delta.test.gauge", (("path", "/a"),))] == 5.0
    assert g[("delta.test.gauge", (("path", "/b"),))] == 7.0


def test_prometheus_text_golden():
    telemetry.reset_all()
    telemetry.bump_counter("commit.total", 3)
    telemetry.set_gauge("delta.cache.bytes", 128, path="/t")
    telemetry.observe("delta.op.ms", 3.0, path="/t")
    telemetry.observe("delta.op.ms", 5.0, path="/t")
    text = telemetry.prometheus_text()
    bucket_lines = "".join(
        f'delta_op_ms_bucket{{path="/t",le="{b}"}} '
        f"{0 if b < 4 else (1 if b < 8 else 2)}\n"
        for b in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                  2048, 4096, 8192, 16384, 32768, 65536)
    )
    expected = (
        # cataloged metrics carry a # HELP line from metric_names.DESCRIPTIONS;
        # ad-hoc names (delta.cache.bytes, delta.op.ms) get TYPE only
        "# HELP commit_total_total "
        "Commits attempted through the transaction pipeline.\n"
        "# TYPE commit_total_total counter\n"
        "commit_total_total 3\n"
        "# TYPE delta_cache_bytes gauge\n"
        'delta_cache_bytes{path="/t"} 128\n'
        "# TYPE delta_op_ms histogram\n"
        + bucket_lines
        + 'delta_op_ms_bucket{path="/t",le="+Inf"} 2\n'
        'delta_op_ms_sum{path="/t"} 8\n'
        'delta_op_ms_count{path="/t"} 2\n'
    )
    assert text == expected


def test_prometheus_type_emitted_once_per_metric_name():
    """Label sets of one gauge share a single # HELP/# TYPE header —
    Prometheus parsers reject duplicate TYPE lines for a name."""
    telemetry.reset_all()
    telemetry.set_gauge("router.missRate", 0.25)
    telemetry.set_gauge("table.health.severity", 1, path="/a")
    telemetry.set_gauge("table.health.severity", 2, path="/b")
    text = telemetry.prometheus_text()
    assert text.count("# TYPE table_health_severity gauge") == 1
    assert text.count("# HELP table_health_severity ") == 1
    assert 'table_health_severity{path="/a"} 1' in text
    assert 'table_health_severity{path="/b"} 2' in text
    assert "# HELP router_missRate " in text


def test_prometheus_escapes_label_values():
    telemetry.reset_all()
    telemetry.set_gauge("delta.test.esc", 1, path='C:\\data\\"t"\ntbl')
    text = telemetry.prometheus_text()
    assert 'path="C:\\\\data\\\\\\"t\\"\\ntbl"' in text
    assert "\n\n" not in text  # raw newline never leaks into the exposition


def test_metrics_snapshot_is_json_serializable():
    telemetry.reset_all()
    telemetry.bump_counter("a.b", 2)
    telemetry.set_gauge("g", 1.5)
    telemetry.observe("h.ms", 10, path="/t")
    snap = json.loads(json.dumps(telemetry.metrics_snapshot()))
    assert snap["counters"] == {"a.b": 2}
    assert snap["gauges"] == {"g": 1.5}
    assert snap["histograms"]["h.ms{path=/t}"]["count"] == 1
    # the one observation of 10 sits in the bucket whose upper bound is 16
    assert snap["histograms"]["h.ms{path=/t}"]["buckets"] == {"16": 1}


def _quantile(values, q):
    """`bucket_quantile` over the registry's own bucket counts (+Inf last)
    of a histogram that observed ``values``."""
    telemetry.reset_all()
    for v in values:
        telemetry.observe("q.ms", v)
    rows = telemetry.histogram_rows("q.ms")
    if not rows:
        return telemetry.bucket_quantile(
            [0] * (len(telemetry.HISTOGRAM_BUCKETS) + 1), 0, q)
    [(_name, _labels, counts, _sum, count)] = rows
    return telemetry.bucket_quantile(counts, count, q)


def _empty_is_none():
    assert _quantile([], 0.5) is None


def _one_observation_of_10_reads_16():
    assert _quantile([10.0], 0.5) == 16.0


def _edge_takes_the_lower_bucket():
    # q x count = 1.0 is met exactly at the end of the first bucket, which
    # wins over the bucket above it
    assert _quantile([10.0, 100.0], 0.5) == 16.0


def _crossing_in_inf_is_none():
    past = telemetry.HISTOGRAM_BUCKETS[-1] * 2
    assert _quantile([10.0, past, past], 0.95) is None
    assert _quantile([10.0, past, past], 0.25) == 16.0


def _p50_not_above_p95():
    values = [10.0] * 9 + [100.0]
    p50, p95 = _quantile(values, 0.5), _quantile(values, 0.95)
    assert (p50, p95) == (16.0, 128.0) and p50 <= p95


def _quantile_window_agrees():
    """Over the delta of two scrapes `timeseries.quantile_window` (what
    `/slo` reads) gives what `bucket_quantile` gives on the observations
    made between them, and leaves out what came before."""
    from delta_tpu.obs import timeseries

    between = [10.0, 10.0, 100.0, 700.0]
    want = _quantile(between, 0.75)
    timeseries.reset()
    telemetry.reset_all()
    try:
        telemetry.observe("q.ms", 3.0)
        timeseries.scrape_once(now_ms=1_000, evaluate_slo=False)
        for v in between:
            telemetry.observe("q.ms", v)
        timeseries.scrape_once(now_ms=2_000, evaluate_slo=False)
        got = timeseries.quantile_window("q.ms", (), 0.75, 5_000,
                                         now_ms=2_000)
    finally:
        timeseries.reset()
    assert got == (want, len(between)) and want == 128.0


@pytest.mark.parametrize("case", [
    _empty_is_none, _one_observation_of_10_reads_16,
    _edge_takes_the_lower_bucket, _crossing_in_inf_is_none,
    _p50_not_above_p95, _quantile_window_agrees,
], ids=lambda f: f.__name__.strip("_"))
def test_bucket_quantile(case):
    """The one quantile rule that `/slo` (through
    `timeseries.quantile_window`) and every histogram summary share: the
    upper bound of the bucket in which the cumulative count first reaches
    q x count; None for an empty histogram or a crossing in +Inf."""
    case()


# -- zero-overhead disable ---------------------------------------------------


def test_telemetry_disabled_records_nothing_counters_still_work():
    telemetry.reset_all()
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False):
        telemetry.record_event("delta.test.blackout")
        with telemetry.record_operation("delta.test.blackout.op") as ev:
            telemetry.add_span_data(x=1)
        telemetry.bump_counter("hot.counter")
    assert telemetry.recent_events() == []
    assert ev.duration_ms is None  # span never timed or buffered
    assert telemetry.counters("hot.counter") == {"hot.counter": 1}
    # no fabricated 0-ms samples leak into the latency histograms
    assert telemetry.histograms("delta.streaming") == {}
    # re-enabled: recording resumes
    telemetry.record_event("delta.test.back")
    assert len(telemetry.recent_events()) == 1


# -- engine wiring -----------------------------------------------------------


def test_commits_emit_usage_events(tmp_table):
    t = DeltaTable.create(
        tmp_table, data=pa.table({"id": pa.array([1], pa.int64())})
    )
    t.delete("id = 1")
    commits = [e for e in telemetry.recent_events("delta.commit")
               if e.op_type == "delta.commit"]
    assert len(commits) >= 2  # create + delete
    assert all(e.duration_ms is not None for e in commits)
    assert all(e.tags.get("path") == tmp_table for e in commits)


def test_commit_stats_on_clean_commit(tmp_table):
    DeltaTable.create(
        tmp_table, data=pa.table({"id": pa.array([1, 2], pa.int64())})
    )
    [stats] = [e.data for e in telemetry.recent_events("delta.commit.stats")]
    assert stats["readVersion"] == -1 and stats["commitVersion"] == 0
    assert stats["attempts"] == 1
    assert stats["numAdd"] >= 1 and stats["numRemove"] == 0
    assert stats["bytesNew"] > 0
    assert stats["isolationLevel"] == "WriteSerializable"
    for phase in ("prepare", "write", "postCommit"):
        assert phase in stats["phaseDurationsMs"]
    # phase spans nest under the commit span
    [commit] = [e for e in telemetry.recent_events("delta.commit")
                if e.op_type == "delta.commit"]
    kids = {e.op_type for e in telemetry.recent_events()
            if e.parent_id == commit.span_id}
    assert {"delta.commit.prepare", "delta.commit.write",
            "delta.commit.postCommit"} <= kids


def test_commit_stats_on_conflict_retry(tmp_table):
    """A commit that loses the race retries through the conflict checker and
    reports attempts/conflictCheck duration in its CommitStats."""
    from delta_tpu.commands import operations as ops
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.exec import write as write_exec

    t = DeltaTable.create(
        tmp_table, data=pa.table({"id": pa.array([0], pa.int64())})
    )
    log = t.delta_log
    txn = log.start_transaction()
    # interleaving writer wins version 1 before our txn commits
    WriteIntoDelta(log, "append", pa.table({"id": pa.array([1], pa.int64())})).run()
    telemetry.clear_events()
    actions = write_exec.write_files(
        log.data_path, pa.table({"id": pa.array([2], pa.int64())}),
        txn.metadata, data_change=True,
    )
    version = txn.commit(actions, ops.Write(mode="Append"))
    assert version == 2
    assert txn.stats.attempts == 2
    [stats] = [e.data for e in telemetry.recent_events("delta.commit.stats")]
    assert stats["attempts"] == 2
    assert "conflictCheck" in stats["phaseDurationsMs"]
    checks = [e for e in telemetry.recent_events("delta.commit.retry.conflictCheck")]
    assert checks and checks[0].data["winningCommits"] == 1
    assert telemetry.counters("commit.retries") == {"commit.retries": 1}


def test_concurrent_commits_each_emit_stats(tmp_table):
    """Chaos-harness shape: racing writers all emit CommitStats, spans stay
    thread-local (no cross-thread parenting)."""
    from delta_tpu.commands.write import WriteIntoDelta

    t = DeltaTable.create(
        tmp_table, data=pa.table({"id": pa.array([0], pa.int64())})
    )
    telemetry.clear_events()
    N = 6
    errs = []

    def appender(i):
        try:
            WriteIntoDelta(t.delta_log, "append", pa.table({
                "id": pa.array([100 + i], pa.int64()),
            })).run()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=appender, args=(i,)) for i in range(N)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert errs == []
    stats = telemetry.recent_events("delta.commit.stats")
    assert len(stats) == N
    assert sorted(e.data["commitVersion"] for e in stats) == list(range(1, N + 1))
    # every commit span is parented by a dml span from ITS OWN thread
    by_id = {e.span_id: e for e in telemetry.recent_events() if e.span_id}
    for c in (e for e in telemetry.recent_events("delta.commit")
              if e.op_type == "delta.commit"):
        parent = by_id[c.parent_id]
        assert parent.thread_id == c.thread_id


def test_history_metrics_disabled_suppresses_stats_op_metrics(tmp_table):
    t = DeltaTable.create(
        tmp_table, data=pa.table({"id": pa.array(range(5), pa.int64())})
    )
    telemetry.clear_events()
    with conf.set_temporarily(delta__tpu__history__metricsEnabled=False):
        t.delete("id = 1")
    [stats] = [e.data for e in telemetry.recent_events("delta.commit.stats")]
    assert "opMetrics" not in stats


# -- acceptance: MERGE observability end to end ------------------------------


def test_merge_produces_span_tree_stats_prometheus_and_trace(tmp_table, tmp_path):
    from delta_tpu.protocol import filenames
    from delta_tpu.protocol.actions import AddFile, RemoveFile, actions_from_lines

    telemetry.reset_all()
    t = DeltaTable.create(
        tmp_table,
        data=pa.table({"id": pa.array(range(10), pa.int64()),
                       "v": pa.array(["x"] * 10)}),
    )
    src = pa.table({"id": pa.array([3, 100], pa.int64()),
                    "v": pa.array(["u", "i"])})
    (t.alias("t").merge(src, "t.id = s.id", source_alias="s")
     .when_matched_update_all().when_not_matched_insert_all().execute())

    # 1. nested span tree: merge -> commit -> {prepare, write, postCommit}
    [merge] = [e for e in telemetry.recent_events("delta.dml.merge")
               if e.op_type == "delta.dml.merge"]  # its phases match the prefix too
    commits = [e for e in telemetry.recent_events("delta.commit")
               if e.op_type == "delta.commit" and e.parent_id == merge.span_id]
    assert commits, "delta.commit span must nest under delta.dml.merge"
    commit = commits[-1]
    kids = {e.op_type for e in telemetry.recent_events()
            if e.parent_id == commit.span_id}
    assert {"delta.commit.prepare", "delta.commit.write"} <= kids
    # DML rewrite metrics attached to the merge span via report_metrics
    assert "numTargetRowsUpdated" in merge.data

    # 2. stats event matches the actions actually committed
    stats = telemetry.recent_events("delta.commit.stats")[-1].data
    version = stats["commitVersion"]
    committed = actions_from_lines(t.delta_log.store.read_iter(
        f"{t.delta_log.log_path}/{filenames.delta_file(version)}"))
    num_add = sum(isinstance(a, AddFile) for a in committed)
    num_remove = sum(isinstance(a, RemoveFile) for a in committed)
    assert stats["numAdd"] == num_add >= 1
    assert stats["numRemove"] == num_remove >= 1

    # 3. prometheus exposition includes at least one histogram
    text = telemetry.prometheus_text()
    assert "# TYPE delta_commit_duration_ms histogram" in text
    assert "_bucket{" in text and "_count{" in text

    # 4. Perfetto-loadable Chrome trace JSON
    out = tmp_path / "trace.json"
    trace = telemetry.export_chrome_trace(str(out))
    loaded = json.loads(out.read_text())
    assert loaded["traceEvents"] == json.loads(json.dumps(
        trace["traceEvents"], default=str))
    complete = [r for r in loaded["traceEvents"] if r.get("ph") == "X"]
    names = {r["name"] for r in complete}
    assert {"delta.dml.merge", "delta.commit"} <= names
    mrow = next(r for r in complete if r["name"] == "delta.dml.merge")
    crow = next(r for r in complete
                if r["name"] == "delta.commit"
                and r["args"].get("parentId") == mrow["args"]["spanId"])
    # child timeline contained within the parent's
    assert mrow["ts"] <= crow["ts"]
    assert crow["ts"] + crow["dur"] <= mrow["ts"] + mrow["dur"] + 1000


# -- engine status events (pre-existing behavior) ----------------------------


def test_with_status_records_event_and_duration(tmp_table):
    import numpy as np

    from delta_tpu import DeltaLog
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.exec.scan import scan_files

    telemetry.clear_events()
    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", pa.table({"a": np.arange(5)})).run()
    scan_files(log.update(), ["a > 1"])
    # a scan's planning is its own span and opens no status inside it: the
    # status spans are for the long steps (a checkpoint, VACUUM's listing)
    assert telemetry.recent_events("delta.scan.planning")
    assert telemetry.recent_events("delta.status") == []

    telemetry.clear_events()
    from delta_tpu.commands.vacuum import VacuumCommand

    VacuumCommand(log, retention_hours=1000, dry_run=True).run()
    evs = telemetry.recent_events("delta.status")
    assert any("VACUUM" in e.data.get("message", "") for e in evs)
    # and the whole command ran under its utility span
    assert telemetry.recent_events("delta.utility.vacuum")


def test_logstore_io_counters(tmp_table):
    telemetry.reset_all()
    DeltaTable.create(
        tmp_table, data=pa.table({"id": pa.array([1], pa.int64())})
    )
    io = telemetry.counters("logstore")
    assert io.get("logstore.write.calls", 0) >= 1
    assert io.get("logstore.write.bytes", 0) > 0
    assert io.get("logstore.list.calls", 0) >= 1


# -- cross-thread span propagation -------------------------------------------


def test_span_context_propagates_into_pool_workers():
    """propagated() captures the submitter's open span chain: worker-thread
    spans parent under it (on their own thread lanes) instead of starting
    orphan roots."""
    from concurrent.futures import ThreadPoolExecutor

    def work(i):
        with telemetry.record_operation("delta.test.prop.child") as w:
            pass
        return w

    with telemetry.record_operation("delta.test.prop") as parent:
        with ThreadPoolExecutor(max_workers=2) as pool:
            children = list(pool.map(telemetry.propagated(work), range(4)))
    assert all(c.parent_id == parent.span_id for c in children)
    assert any(c.thread_id != parent.thread_id for c in children)
    # the submitter's own stack is untouched by the workers
    assert telemetry.span_context() == ()


def test_chrome_trace_emits_process_and_pool_thread_metadata():
    """Named worker-pool lanes render labeled in Perfetto: the export
    carries a process_name metadata row, and a tid whose first event came
    from a generic Thread-N later adopts the engine pool's name."""
    import os as _os
    from concurrent.futures import ThreadPoolExecutor

    telemetry.clear_events()

    def work(i):
        with telemetry.record_operation("delta.test.pool.child"):
            pass

    with telemetry.record_operation("delta.test.pool"):
        with ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="delta-scan-decode"
        ) as pool:
            list(pool.map(telemetry.propagated(work), range(4)))
    trace = telemetry.export_chrome_trace()
    meta = [r for r in trace["traceEvents"] if r.get("ph") == "M"]
    procs = [r for r in meta if r["name"] == "process_name"]
    assert procs and procs[0]["args"]["name"] == "delta-tpu"
    assert procs[0]["pid"] == _os.getpid()
    tnames = {r["tid"]: r["args"]["name"] for r in meta
              if r["name"] == "thread_name"}
    assert any(n.startswith("delta-scan-decode") for n in tnames.values())
    # every span row's tid has a thread_name metadata row
    for r in trace["traceEvents"]:
        if r.get("ph") == "X":
            assert r["tid"] in tnames


def test_adopt_span_context_restores_on_exit():
    with telemetry.record_operation("delta.test.adopt") as parent:
        carrier = telemetry.span_context()
    assert carrier == (parent.span_id,)
    with telemetry.adopt_span_context(carrier):
        telemetry.record_event("delta.test.adopt.point")
    assert telemetry.span_context() == ()
    [pt] = telemetry.recent_events("delta.test.adopt.point")
    assert pt.parent_id == parent.span_id


def test_propagated_is_identity_with_no_span_or_blackout():
    def f(x):
        return x

    assert telemetry.propagated(f) is f  # no open span: nothing to carry
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False):
        with telemetry.record_operation("delta.test.dark"):
            assert telemetry.propagated(f) is f  # blackout: zero overhead


def test_obs_public_api_matches_catalog():
    """Each obs module's ``__all__`` must equal its PUBLIC_API entry — a new
    entry point (or a rename) has to land in the catalog too. (A runtime
    import check, not an AST lint — the AST lints moved to the
    delta_tpu/analysis engine; see tests/test_analysis.py.)"""
    import importlib

    from delta_tpu.obs import metric_names

    obs_dir = os.path.join(
        os.path.dirname(__file__), "..", "delta_tpu", "obs")
    modules = sorted(
        f[:-3] for f in os.listdir(obs_dir)
        if f.endswith(".py") and f != "__init__.py"
    )
    assert set(modules) == set(metric_names.PUBLIC_API), (
        "obs modules and PUBLIC_API catalog diverge"
    )
    for mod in modules:
        m = importlib.import_module(f"delta_tpu.obs.{mod}")
        assert tuple(sorted(m.__all__)) == tuple(
            sorted(metric_names.PUBLIC_API[mod])
        ), f"obs/{mod}.py __all__ out of sync with PUBLIC_API"


# -- phases as spans, link and compile counters (ISSUE 25) --------------------

MERGE_PHASES = {  # span -> the phase_ms key it fills
    "delta.dml.merge.analyze": "analyze_ms",
    "delta.dml.merge.keyDecode": "key_decode_ms",
    "delta.dml.merge.rowDecode": "decode_ms",
    "delta.dml.merge.join": "join_ms",
    "delta.dml.merge.apply": "apply_ms",
    "delta.dml.merge.deletionVectors": "dv_ms",
    "delta.dml.merge.write": "write_ms",
    "delta.dml.merge.residentKeys": "resident_ms",
}
DEVICE = {"delta.tpu.merge.devicePath.mode": "force",
          "delta.tpu.read.deviceResidual.mode": "force"}


def _covered_us(intervals):
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _cover(events, root):
    lo, hi = root.start_us, root.start_us + root.duration_us
    inside = [(e.start_us, min(e.start_us + e.duration_us, hi))
              for e in events if e is not root and e.duration_us is not None
              and lo <= e.start_us < hi]
    return _covered_us(inside) / (hi - lo)


def _keyed_table(path, rows=8000, files=8):
    import numpy as np

    data = pa.table({
        "k": pa.array(np.arange(rows, dtype=np.int64)),
        "d": pa.array((np.arange(rows) % 500).astype(np.int32)),
        "q": pa.array((np.arange(rows) % 97).astype(np.int32)),
    })
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": rows // files}):
        return DeltaTable.create(
            str(path), data=data,
            configuration={"delta.tpu.enableDeletionVectors": "true"})


def _upsert(table, lo, hi, step=1, assignments=None):
    """MERGE every ``step``-th ``k`` in [lo, hi) into the table through the
    command the public builder runs; returns the command (for ``phase_ms``).
    A star update, or ``assignments``."""
    import numpy as np

    from delta_tpu.commands.merge import MergeClause, MergeIntoCommand

    keys = np.arange(lo, hi, step, dtype=np.int64)
    n = len(keys)
    src = pa.table({"k": pa.array(keys),
                    "d": pa.array(np.zeros(n, np.int32)),
                    "q": pa.array(np.zeros(n, np.int32))})
    cmd = MergeIntoCommand(
        table.delta_log, src, "t.k = s.k",
        [MergeClause("update", assignments=assignments)],
        [MergeClause("insert", assignments=None)],
        source_alias="s", target_alias="t")
    cmd.run()
    return cmd


@pytest.fixture
def _fresh_device_caches():
    from delta_tpu.ops.column_cache import ColumnCache
    from delta_tpu.ops.key_cache import KeyCache

    KeyCache.reset()
    ColumnCache.reset()
    yield
    KeyCache.reset()
    ColumnCache.reset()


@pytest.mark.parametrize("case", ["cold", "resident", "assigned"])
def test_merge_phases_are_child_spans_that_fill_phase_ms(
        tmp_path, _fresh_device_caches, case):
    resident = case == "resident"
    t = _keyed_table(tmp_path / "t")
    with conf.set_temporarily(**DEVICE):
        if resident:
            _upsert(t, 5, 8010, step=500)  # builds and registers the slab
        telemetry.clear_events()
        # half existing keys, half fresh; explicit assignments read a target
        # column, so that statement is not pairs-only in shape
        cmd = _upsert(t, 7900, 8100, assignments=(
            {"d": "s.d", "q": "t.q + s.q"} if case == "assigned" else None))
    assert cmd.metrics["numTargetRowsUpdated"] > 0
    events = telemetry.recent_events()
    [root] = [e for e in events if e.op_type == "delta.dml.merge"]
    # a star upsert over deletion vectors is pairs-only in shape: resident,
    # the probe's pairs are the join and the row decode reads nothing (its
    # span still fills decode_ms); cold, it builds the table's slab over every
    # file. A statement that reads a target column decodes: every route
    # opens each phase once
    for name, key in MERGE_PHASES.items():
        [ev] = [e for e in events if e.op_type == name]  # exactly once
        assert ev.parent_id == root.span_id, name
        assert cmd.phase_ms[key] == ev.duration_us / 1000.0, name
    assert set(cmd.phase_ms) == set(MERGE_PHASES.values())
    by_name = {e.op_type: e for e in events}
    if resident:
        assert not any(e.op_type.startswith("delta.scan") for e in events)
        assert by_name["delta.dml.merge.join"].data["route"] == "pairs-only"
    else:
        assert by_name["delta.dml.merge.join"].data["route"] == "decode"
    [router] = [e for e in events if e.op_type == "delta.merge.router"]
    assert router.data["route"] == by_name["delta.dml.merge.join"].data["route"]
    [commit] = [e for e in events if e.op_type == "delta.commit"]
    assert commit.parent_id == root.span_id
    # the phases tile the command: in order, not overlapping on its thread.
    # An upsert makes vectors and rows, so its vectors are written beside
    # its data file (ISSUE 39): that phase alone is on another thread,
    # starts before `.write` does and ends before the commit is built
    phases = sorted((e for e in events
                     if e.op_type in MERGE_PHASES or e is commit),
                    key=lambda e: e.start_us)
    [dv] = [e for e in phases if e.op_type == "delta.dml.merge.deletionVectors"]
    assert dv.data["overlapped"] is True and dv.thread_id != root.thread_id
    assert dv.thread_name.startswith("delta-merge-dv")
    assert dv.start_us + dv.duration_us <= commit.start_us + 1
    assert [e.op_type for e in phases][:len(MERGE_PHASES) - 4] == \
        list(MERGE_PHASES)[:-4]
    phases.remove(dv)
    assert all(e.thread_id == root.thread_id for e in phases)
    for a, b in zip(phases, phases[1:]):
        assert a.start_us + a.duration_us <= b.start_us + 1
    # on a device route the wait for the device and the host's pair work
    # tile the join
    join = by_name["delta.dml.merge.join"]
    stages = sorted((e for e in events if e.parent_id == join.span_id),
                    key=lambda e: e.start_us)
    assert [e.op_type for e in stages] == ["delta.dml.merge.join.wait",
                                           "delta.dml.merge.join.pairs"]
    assert stages[0].start_us + stages[0].duration_us <= stages[1].start_us + 1
    assert sum(e.duration_us for e in stages) >= 0.9 * join.duration_us
    assert _cover(events, root) >= 0.9
    [dv] = [e for e in events if e.op_type == "delta.dml.merge.deletionVectors"]
    assert dv.data["rows"] == cmd.metrics["numTargetRowsUpdated"]
    assert dv.data["files"] >= 1
    # the key cache's own spans lie under the phase that waited for them
    names = {e.op_type for e in events}
    assert "delta.merge.deviceProbe" in names and "delta.keyCache.sort" in names
    assert ("delta.keyCache.advance" in names) == resident
    # the command's own metrics keep their meaning: whole milliseconds. The
    # vectors run beside the write, so the two are no longer additive: the
    # rewrite lasts as long as the apply and the longer of them
    assert cmd.metrics["rewriteTimeMs"] >= int(
        cmd.phase_ms["apply_ms"]
        + max(cmd.phase_ms["dv_ms"], cmd.phase_ms["write_ms"])) - 1


def test_scan_phases_are_spans_and_fill_the_report(tmp_path,
                                                   _fresh_device_caches):
    from delta_tpu import obs

    t = _keyed_table(tmp_path / "t", files=2)
    filters = ["d >= 17", "d < 29", "q < 60"]
    with conf.set_temporarily(**DEVICE):
        t.to_arrow(filters=filters, columns=["k"])  # lanes up, mask compiled
        telemetry.clear_events()
        got = t.to_arrow(filters=filters, columns=["k"])
    events = telemetry.recent_events()
    [root] = [e for e in events if e.op_type == "delta.scan"]
    for name in ("delta.scan.planning", "delta.scan.deviceMask",
                 "delta.scan.read", "delta.scan.filter", "delta.scan.report"):
        [ev] = [e for e in events if e.op_type == name]
        assert ev.parent_id == root.span_id, name
    by_name = {e.op_type: e for e in events}
    mask = by_name["delta.scan.deviceMask"]
    assert (mask.data["files"], mask.data["hits"], mask.data["misses"]) == (2, 4, 0)
    assert mask.data["coldBytes"] == 0
    masks = [e for e in events if e.op_type == "delta.columnCache.mask"]
    assert len(masks) == 2 and all(e.parent_id == mask.span_id for e in masks)
    # one decode a file, and inside it the three stages once each, in order
    decodes = [e for e in events if e.op_type == "delta.scan.decode"]
    assert len(decodes) == 2
    for dec in decodes:
        stages = sorted((e for e in events if e.parent_id == dec.span_id),
                        key=lambda e: e.start_us)
        assert [e.op_type for e in stages] == [
            "delta.scan.decode.open", "delta.scan.decode.rowGroups",
            "delta.scan.decode.assemble"]
        for a, b in zip(stages, stages[1:]):
            assert a.start_us + a.duration_us <= b.start_us + 1
        assert stages[1].data["groups"] >= 1 and stages[1].data["bytes"] > 0
    assert _cover(events, root) >= 0.9
    rep = obs.last_scan_report()
    assert rep.rows_out == got.num_rows
    assert rep.phase_ms == {
        key: round(by_name[name].duration_us / 1000.0, 3)
        for key, name in (("planning", "delta.scan.planning"),
                          ("mask", "delta.scan.deviceMask"),
                          ("read", "delta.scan.read"),
                          ("filter", "delta.scan.filter"))}


def test_link_counters_are_the_nbytes_of_what_moved():
    import numpy as np

    from delta_tpu.parallel import link

    before = telemetry.counters("link")
    with telemetry.record_operation("delta.test.link") as ev:
        up = link.to_device(np.arange(1000, dtype=np.int32))      # 4,000 B
        link.to_device(np.zeros(64, bool))                        # 64 B
        link.to_device(np.int32(7))                               # 4 B
        down = link.to_host(up)                                   # 4,000 B
    assert down.tolist() == list(range(1000))
    after = telemetry.counters("link")
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved["link.h2d.bytes"] == 4000 + 64 + 4
    assert moved["link.h2d.count"] == 3
    assert moved["link.d2h.bytes"] == 4000
    assert moved["link.d2h.count"] == 1
    assert moved["link.d2h.waitUs"] >= 0
    assert ev.data == {"h2dBytes": 4068, "d2hBytes": 4000}


def test_scan_link_bytes_are_the_lanes_up_and_the_masks_down(
        tmp_path, _fresh_device_caches):
    """A cold scan ships two int64 lanes and their validity a file, padded to
    the next power of two, and fetches one boolean mask a file; a warm scan
    ships nothing."""
    t = _keyed_table(tmp_path / "t", rows=6000, files=2)  # 3,000 -> 4,096 rows
    filters = ["d >= 3", "d < 9", "q < 50"]
    with conf.set_temporarily(**DEVICE):
        c0 = telemetry.counters("link")
        t.to_arrow(filters=filters, columns=["k"])
        c1 = telemetry.counters("link")
        t.to_arrow(filters=filters, columns=["k"])
        c2 = telemetry.counters("link")
    cold = {k: c1[k] - c0.get(k, 0) for k in c1}
    warm = {k: c2[k] - c1.get(k, 0) for k in c2}
    assert cold["link.h2d.bytes"] == 2 * 2 * 4096 * (8 + 1)
    assert cold["link.d2h.bytes"] == warm["link.d2h.bytes"] == 2 * 4096
    assert warm["link.h2d.bytes"] == 0 and warm["link.d2h.count"] == 2


def test_compile_inside_a_scan_is_counted_where_it_happens(
        tmp_path, _fresh_device_caches):
    t = _keyed_table(tmp_path / "t", rows=2000, files=1)
    # literals no other test uses: the mask's program is keyed on them
    filters = ["d >= 123", "d < 457", "q < 71"]

    def scan():
        before = telemetry.counters()
        telemetry.clear_events()
        with conf.set_temporarily(**DEVICE):
            t.to_arrow(filters=filters, columns=["k"])
        after = telemetry.counters()
        moved = {k: after[k] - before.get(k, 0) for k in after
                 if k.endswith((".compiles", ".compileUs", ".cacheFetches"))}
        spans = [e for e in telemetry.recent_events() if "compiles" in e.data]
        return moved, spans

    moved, spans = scan()
    assert moved["scan.device.compiles"] >= 1
    assert moved["device.compiles"] == moved["scan.device.compiles"]
    assert moved["device.compileUs"] > 0
    assert moved.get("merge.device.compiles", 0) == 0
    assert {e.op_type for e in spans} == {"delta.columnCache.mask"}
    assert sum(e.data["compiles"] for e in spans) == moved["device.compiles"]
    assert all(e.data["compileMs"] > 0 for e in spans)
    # the same literals and lane shape again: nothing compiles, nothing counts
    moved, spans = scan()
    assert not any(moved.values()) and not spans


def test_blackout_records_no_span_but_counts_and_times_the_phases(
        tmp_path, _fresh_device_caches):
    t = _keyed_table(tmp_path / "t")
    before = telemetry.counters("link")
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False, **DEVICE):
        telemetry.clear_events()
        cmd = _upsert(t, 7900, 8100)
        t.to_arrow(filters=["d >= 40", "d < 44"], columns=["k"])
        assert telemetry.recent_events() == []
    after = telemetry.counters("link")
    assert after["link.h2d.bytes"] > before.get("link.h2d.bytes", 0)
    assert after["link.d2h.bytes"] > before.get("link.d2h.bytes", 0)
    assert set(MERGE_PHASES.values()) <= set(cmd.phase_ms)
    assert all(v >= 0 for v in cmd.phase_ms.values())
    assert cmd.phase_ms["join_ms"] > 0


def test_span_stages_tile_their_parent():
    with telemetry.record_operation("delta.test.decode") as parent, \
            telemetry.span_stages() as stage:
        a = stage("delta.test.decode.open")
        assert stage("delta.test.decode.open") is a  # already open: no new span
        b = stage("delta.test.decode.read", {"groups": 2})
        assert a.duration_us is not None and b.duration_us is None
        stage("delta.test.decode.assemble")
    stages = [e for e in telemetry.recent_events("delta.test.decode")
              if e is not parent]
    assert [e.op_type.rsplit(".", 1)[1] for e in stages] == [
        "open", "read", "assemble"]
    assert all(e.parent_id == parent.span_id and e.duration_us is not None
               for e in stages)
    assert b.data == {"groups": 2}
    assert telemetry.current_span() is None


def test_span_stages_close_the_open_stage_with_the_error():
    with pytest.raises(ValueError):
        with telemetry.span_stages() as stage:
            stage("delta.test.stage.one")
            stage("delta.test.stage.two")
            raise ValueError("kapow")
    one, two = telemetry.recent_events("delta.test.stage")
    assert one.error is None and "kapow" in two.error
    assert telemetry.current_span() is None


def test_open_spans_and_span_counts():
    assert telemetry.open_spans() == []
    telemetry.add_span_counts(h2dBytes=5)  # no span open: nothing to add to
    with telemetry.record_operation("delta.test.outer") as outer:
        with telemetry.record_operation("delta.test.inner", {"compiles": 1}) as inner:
            assert telemetry.open_spans() == [outer, inner]
            telemetry.add_span_counts(compiles=1, compileMs=2.5)
            telemetry.add_span_counts(compileMs=0.5)
    assert inner.data == {"compiles": 2, "compileMs": 3.0}
    assert outer.data == {}


def test_chrome_trace_carries_both_clocks():
    import time

    with telemetry.record_operation("delta.test.clock"):
        pass
    lo = time.perf_counter_ns(), time.time_ns()
    clock = telemetry.export_chrome_trace()["metadata"]["clock"]
    hi = time.perf_counter_ns(), time.time_ns()
    assert lo[0] <= clock["perf_counter_ns"] <= hi[0]
    assert lo[1] <= clock["time_ns"] <= hi[1]


# -- the inside of the largest leaf spans (ISSUE 36) ---------------------------


def _children(events, parent):
    return sorted((e for e in events if e.parent_id == parent.span_id),
                  key=lambda e: e.start_us)


def _only(events, name):
    [ev] = [e for e in events if e.op_type == name]
    return ev


def _assert_tiled(parent, stages, share=0.3):
    """``stages`` lie inside ``parent`` in order without overlapping, and
    take a good part of it (the chip's traced runs read 98-99.98%: a loose
    bound here, where five other workers share the cores)."""
    for a, b in zip(stages, stages[1:]):
        assert a.start_us + a.duration_us <= b.start_us + 1
    assert parent.start_us <= stages[0].start_us
    assert stages[-1].start_us + stages[-1].duration_us \
        <= parent.start_us + parent.duration_us + 1
    assert sum(e.duration_us for e in stages) >= share * parent.duration_us


def _flagged_table(path, rows=6000, files=3):
    """``g`` takes three values: few enough groups for the device route."""
    import numpy as np

    data = pa.table({
        "k": pa.array(np.arange(rows, dtype=np.int64)),
        "g": pa.array((np.arange(rows) % 3).astype(np.int32)),
        "q": pa.array((np.arange(rows) % 97).astype(np.int32)),
    })
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": rows // files}):
        return DeltaTable.create(str(path), data=data)


def _aggregate_events(path, text):
    from delta_tpu.sql.parser import execute_sql

    sql = text.format(t=f"delta.`{path}`")
    with conf.set_temporarily(**DEVICE):
        execute_sql(sql)  # lanes up, the program compiled
        telemetry.clear_events()
        out = execute_sql(sql)
    return out, telemetry.recent_events()


@pytest.mark.parametrize("family", ["write", "apply", "open", "launch",
                                    "launch-grouped", "lanes", "select"])
def test_stages_tile_the_leaf_span_they_were_opened_in(
        tmp_path, _fresh_device_caches, family):
    """Each of the host's largest leaf spans holds stages that are its
    children, tile it, and carry the data a reader of the trace needs."""
    if family in ("write", "apply"):
        t = _keyed_table(tmp_path / "t")
        with conf.set_temporarily(**DEVICE):
            telemetry.clear_events()
            cmd = _upsert(t, 7900, 8100)
        events = telemetry.recent_events()
        updated = cmd.metrics["numTargetRowsUpdated"]
        inserted = cmd.metrics["numTargetRowsInserted"]
        assert (updated, inserted) == (100, 100)
    if family == "write":
        parent = _only(events, "delta.dml.merge.write")
        stages = _children(events, parent)
        assert [e.op_type for e in stages] == [
            "delta.dml.merge.write.concat", "delta.write.prepare",
            "delta.write.encode", "delta.write.stats"]
        concat, prepare, encode, stats = stages
        assert concat.data == {"blocks": 2, "rows": updated + inserted}
        assert prepare.data == {"rows": 200, "columns": 3, "chunksIn": 2,
                                "files": 1}
        assert encode.data["rows"] == 200 and encode.data["bytes"] > 0
        assert stats.data == {"columns": 3, "source": "footer"}
        _assert_tiled(parent, stages)
    elif family == "apply":
        parent = _only(events, "delta.dml.merge.apply")
        stages = _children(events, parent)
        assert [e.op_type for e in stages] == [
            "delta.dml.merge.apply.multiMatch",
            "delta.dml.merge.apply.matched",
            "delta.dml.merge.apply.notMatched"]
        multi, matched, not_matched = stages
        assert multi.data == {"pairs": updated}
        assert matched.data == {"pairs": updated, "updated": updated,
                                "deleted": 0, "copied": 0}
        assert not_matched.data == {"inserted": inserted}
        _assert_tiled(parent, stages)
    elif family == "open":
        t = _keyed_table(tmp_path / "t", files=2)
        filters = ["d >= 17", "d < 29", "q < 60"]
        with conf.set_temporarily(**DEVICE, **{
                "delta.tpu.write.rowGroupRows": 250}):
            t.optimize().execute_compaction()  # files of many row groups
            t.to_arrow(filters=filters, columns=["k"])
            telemetry.clear_events()
            t.to_arrow(filters=filters, columns=["k"])
        events = telemetry.recent_events()
        opens = [e for e in events if e.op_type == "delta.scan.decode.open"]
        assert opens
        for parent in opens:
            stages = _children(events, parent)
            assert [e.op_type for e in stages] == [
                "delta.scan.decode.open.plan",
                "delta.scan.decode.open.survivors",
                "delta.scan.decode.open.file"]
            plan, survivors, _file = stages
            assert plan.data["rowGroups"] >= plan.data["kept"] >= 1
            assert plan.data["footerCached"] is True  # the second scan
            assert 1 <= survivors.data["survivors"] <= plan.data["kept"]
            _assert_tiled(parent, stages)
    elif family in ("launch", "launch-grouped"):
        _flagged_table(tmp_path / "t")
        out, events = _aggregate_events(tmp_path / "t", (
            "select g, sum(q) as s, count(*) as c from {t} where q < 60 "
            "group by g order by g") if family == "launch-grouped" else
            "select sum(q) as s, count(*) as c from {t} where q < 60")
        query = _only(events, "delta.scan.deviceAggregate")
        assert query.data["route"] == "device" and query.data["files"] == 3
        assert out.num_rows == (3 if family == "launch-grouped" else 1)
        parent = _only(events, "delta.columnCache.aggregate")
        stages = _children(events, parent)
        assert [e.op_type for e in stages] == [
            "delta.columnCache.aggregate.launch",
            "delta.columnCache.aggregate.fetch"]
        launch, fetch = stages
        assert launch.data["launches"] == query.data["files"]
        assert 0 < launch.data["dispatchUs"] <= launch.duration_us
        assert fetch.data["d2hBytes"] > 0  # the one download is the fetch's
        _assert_tiled(parent, stages)
    elif family == "lanes":
        _flagged_table(tmp_path / "t")
        _out, events = _aggregate_events(
            tmp_path / "t", "select g, sum(q) as s from {t} group by g")
        parent = _only(events, "delta.scan.deviceAggregate")
        stages = _children(events, parent)
        assert [e.op_type for e in stages] == [
            "delta.scan.deviceAggregate.resolve", "delta.scan.planning",
            "delta.scan.deviceAggregate.lanes", "delta.columnCache.aggregate",
            "delta.scan.deviceAggregate.groups"]
        assert stages[2].data == {"files": 3, "lanes": 6}  # g and q a file
        _assert_tiled(parent, stages)
    else:
        _flagged_table(tmp_path / "t")
        _out, events = _aggregate_events(
            tmp_path / "t", "select g, sum(q) as s from {t} where q < 60 "
                            "group by g order by g")
        parent = _only(events, "delta.sql.select")
        stages = _children(events, parent)
        assert [e.op_type for e in stages] == [
            "delta.sql.select.resolve", "delta.scan.deviceAggregate",
            "delta.sql.select.order"]  # the last only where there is a sort
        update = _only(events, "delta.log.update")
        assert update.parent_id == stages[0].span_id
        assert stages[2].data == {"rows": 3}
        _assert_tiled(parent, stages)
    assert not any(e.op_type == "delta.status" for e in events)


def test_a_decline_passes_through_a_stage_without_marking_it(tmp_path):
    """`host:budget` is raised inside ``.lanes``: the route's answer, on the
    query's span; the stage it was reached in records no error."""
    from delta_tpu.sql.parser import execute_sql

    _flagged_table(tmp_path / "t")
    with conf.set_temporarily(**{
            "delta.tpu.read.deviceResidual.mode": "auto",
            "delta.tpu.columnCache.maxBytes": 1}):
        telemetry.clear_events()
        execute_sql(f"select sum(q) as s from delta.`{tmp_path / 't'}`")
    events = telemetry.recent_events()
    query = _only(events, "delta.scan.deviceAggregate")
    assert query.data["route"] == "host:budget" and query.error is None
    lanes = _only(events, "delta.scan.deviceAggregate.lanes")
    assert lanes.parent_id == query.span_id and lanes.error is None


def test_write_files_parents_its_pool_threads_under_the_callers_span(tmp_path):
    import numpy as np

    from delta_tpu.exec.write import write_files

    t = _keyed_table(tmp_path / "t", rows=80, files=1)
    metadata = t.delta_log.update().metadata
    rows = pa.table({"k": pa.array(np.arange(4000, dtype=np.int64)),
                     "d": pa.array(np.zeros(4000, np.int32)),
                     "q": pa.array(np.zeros(4000, np.int32))})
    telemetry.clear_events()
    with telemetry.record_operation("delta.test.caller") as caller:
        adds = write_files(str(tmp_path / "t"), rows, metadata,
                           target_file_rows=1000)
    assert len(adds) == 4
    events = telemetry.recent_events()
    prepare = _only(events, "delta.write.prepare")
    assert prepare.data["files"] == 4 and prepare.parent_id == caller.span_id
    assert prepare.thread_id == caller.thread_id
    encodes = [e for e in events if e.op_type == "delta.write.encode"]
    stats = [e for e in events if e.op_type == "delta.write.stats"]
    assert len(encodes) == len(stats) == 4
    assert sum(e.data["rows"] for e in encodes) == 4000
    assert sorted(e.data["bytes"] for e in encodes) == sorted(a.size for a in adds)
    for e in encodes + stats:
        assert e.parent_id == caller.span_id
        assert e.thread_id != caller.thread_id
        assert e.thread_name.startswith("delta-parquet-write")
        assert e.start_us >= prepare.start_us + prepare.duration_us


@pytest.mark.parametrize("case", ["counted", "event", "below", "off"])
def test_a_collection_is_counted_and_a_long_one_is_an_event(monkeypatch, case):
    """The interpreter's pauses: every collection bumps two counters, and one
    at least ``GC_EVENT_US`` long is an event with a start and a length on
    the spans' clock; with telemetry off only the counters move."""
    import gc
    import time

    with telemetry.record_operation("delta.test.warm"):
        pass  # telemetry was found enabled: the callback is installed
    assert gc.callbacks.count(telemetry._on_gc) == 1
    monkeypatch.setattr(telemetry, "GC_EVENT_US",
                        10 ** 12 if case in ("counted", "below") else 0)
    before = telemetry.counters("host.gc")
    telemetry.clear_events()
    lo = time.perf_counter_ns() // 1000
    with conf.set_temporarily(delta__tpu__telemetry__enabled=case != "off"):
        gc.disable()  # so that the one collection below finds them all
        try:
            for _ in range(10_000):
                cycle = []
                cycle.append(cycle)
            del cycle
            gc.collect()
        finally:
            gc.enable()
    hi = time.perf_counter_ns() // 1000
    after = telemetry.counters("host.gc")
    assert after["host.gc.collections"] > before.get("host.gc.collections", 0)
    assert after["host.gc.pauseUs"] > before.get("host.gc.pauseUs", 0)
    events = telemetry.recent_events("host.gc")
    if case != "event":
        assert events == []
        return
    full = [e for e in events if e.data["generation"] == 2]
    assert full and full[-1].data["collected"] >= 10_000
    assert all(e.parent_id is None and e.span_id == 0 for e in events)
    assert all(lo <= e.start_us and e.start_us + e.duration_us <= hi
               for e in events)
    assert sum(e.duration_us for e in events) <= (
        after["host.gc.pauseUs"] - before.get("host.gc.pauseUs", 0))


def test_blackout_records_none_of_the_stages(tmp_path, _fresh_device_caches):
    import numpy as np

    from delta_tpu.exec.write import write_files
    from delta_tpu.sql.parser import execute_sql

    t = _flagged_table(tmp_path / "t")
    metadata = t.delta_log.update().metadata
    rows = pa.table({"k": pa.array(np.arange(200, dtype=np.int64)),
                     "g": pa.array(np.zeros(200, np.int32)),
                     "q": pa.array(np.zeros(200, np.int32))})
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False, **DEVICE):
        telemetry.clear_events()
        out = execute_sql(f"select g, sum(q) as s from delta.`{tmp_path / 't'}` "
                          "group by g order by g")
        t.to_arrow(filters=["q < 5"], columns=["k"])
        assert len(write_files(str(tmp_path / "t"), rows, metadata,
                               target_file_rows=50)) == 4
        assert telemetry.recent_events() == []
    assert out.num_rows == 3


def test_bench_spans_prints_the_stages_the_launches_and_the_collections(
        tmp_path, _fresh_device_caches, monkeypatch, capsys):
    """`tools/bench_spans.py` on requests made of a query's real spans: the
    stages under each leaf span add up to it, a launch's host time splits
    into arguments and dispatch, and a collection is listed with the
    request it fell in."""
    import gc
    import importlib.util
    import time

    from benchmark.harness.runner import Request, Run
    from delta_tpu.sql.parser import execute_sql

    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_spans.py"))
    bench_spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_spans)
    _flagged_table(tmp_path / "t")
    sql = (f"select g, sum(q) as s from delta.`{tmp_path / 't'}` "
           "where q < 60 group by g")
    monkeypatch.setattr(telemetry, "GC_EVENT_US", 0)
    requests = []
    with conf.set_temporarily(**DEVICE):
        execute_sql(sql)
        before = telemetry.counters("host.gc")
        for i in range(3):
            telemetry.clear_events()
            t0 = time.perf_counter()
            execute_sql(sql)
            if i == 1:
                gc.collect()  # a pause inside the second request
            requests.append(Request(i, t0, time.perf_counter(), True, spans=[
                {"name": e.op_type, "start_us": e.start_us,
                 "duration_us": e.duration_us, "thread": e.thread_id,
                 "data": e.data} for e in telemetry.recent_events()]))
    after = telemetry.counters("host.gc")
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, requests=requests,
              trace=object(), counters={k: v - before.get(k, 0)
                                        for k, v in after.items()})
    got = bench_spans.insides(run)
    assert set(got) == {"delta.columnCache.aggregate",
                        "delta.scan.deviceAggregate", "delta.sql.select"}
    for parent, (mean, each, total, cover) in got.items():
        assert set(each) == set(bench_spans.STAGES[parent])
        assert 0.3 * mean <= total <= mean and cover >= 30, parent
    assert got["delta.sql.select"][1]["delta.scan"] == 0.0  # the device's route
    split = bench_spans.launch_split(run.done)
    assert split["launches a query"] == 3
    assert split["host us a launch"] == pytest.approx(
        split["of it dispatch"] + split["of it arguments"], abs=0.02)
    assert split["of it arguments"] > 0 and split["fetch ms a query"] > 0
    seen = bench_spans.gc_pauses(run)
    assert seen["collections"] >= 1 and seen["a request"] > 0
    assert seen["events"] >= 1 and 1 in [e["request"] for e in seen["longest"]]
    assert seen["longest"][0]["ms"] <= seen["pause_ms"]
    assert {r["request"] for r in seen["slowest_requests"]} == {0, 1, 2}
    assert next(r for r in seen["slowest_requests"]
                if r["request"] == 1)["gc_ms"] > 0
    run.trace = None  # the whole report, but for the device's modules
    bench_spans.report(run)
    printed = capsys.readouterr().err
    for line in ("stages inside the leaf spans", "an aggregate query's launches",
                 "the interpreter's collections", "aggregate routes"):
        assert line in printed
