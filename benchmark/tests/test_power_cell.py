"""`lineitem_sf10_power.power_stream` end to end on the CPU at a fiftieth of
its size, as its entries in `BENCHMARK.json`: the line passes
`lastline.check`, every limit reads 0; its control,
`aggregates_skip_vectors`, comes out as not correct by `aggregates_wrong`
alone; a program without the device route ends set-up at the first query;
and the cell's per-layer metrics read a real stream's spans. Run by hand, as
this directory's conftest says.

At a fiftieth a function is 300 orders and an RF1 about 1,200 rows (its lanes
pad to 2,048 beside the loaded files' 131,072): as at full size every stream
of the window has the second warm-up stream's shapes and nothing compiles in
it."""
import json
import os
import shutil
import tempfile

import pytest

from benchmark.controls_power import (FAILS_BY, WORKLOAD, SkipVectorsTable,
                                      failed_by, run_control)
from benchmark.harness import cell as cell_mod
from benchmark.harness import lastline, runner
from benchmark.harness.cell import load_cell

NEW = ["power_queries_ms", "power_refresh_ms", "agg_keep_mask_ms",
       "power_lane_load_ms", "power_version_install_ms"]
LISTED = ["merge_key_join_ms", "merge_apply_write_ms", "merge_commit_ms",
          "device_idle_pct.merge", "merge_resort_ms"]
COMPARED = {"aggregates_wrong", "queries_off_device", "route_declined",
            "rows_missing", "rows_extra", "cells_wrong", "merge_counts_wrong",
            "commits_wrong", "statements_off_route", "compiles_in_window",
            "requests_failed"}


def test_cell_runs_at_a_fiftieth_and_is_correct():
    line = runner.run_cell(WORKLOAD, 2**31 + 38, 600.0, False, scale=0.02,
                           need_tpu=False)
    cell = load_cell(WORKLOAD)
    lastline.check(line, runner.expected_metrics(cell, False), False, 1)
    assert line["correct"] is True, line["compared"]
    # 13 sets, 2 streams in set-up: the window ends with the last of them
    assert line["attempted"] == 11 and line["failed"] == 0
    assert line["notes"]["window_s"] < 600
    assert set(line["compared"]) == COMPARED
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["compared"].values())
    assert set(line["metrics"]) == {"merge_rows_per_s", "setup_s"}


def test_control_is_not_correct_by_aggregates_wrong_alone():
    line = run_control(9, 600.0, scale=0.02, need_tpu=False)
    assert line["correct"] is False
    assert failed_by(line) == FAILS_BY == {"aggregates_wrong"}
    # every query of the window met at least the first stream's deletes
    assert line["compared"]["aggregates_wrong"]["value"] == 22 * line["attempted"]


def test_a_program_without_the_device_route_stops_at_the_first_query():
    from benchmark.traffic.kinds.power_stream import RouteMissing

    class HostOnly(SkipVectorsTable):
        def counters(self):
            return {"merge.resident.pairsOnly": self.statements}

    cell = load_cell(WORKLOAD)
    with pytest.raises(RouteMissing, match="q1 40000"):
        runner.run_cell(WORKLOAD, 5, 0.2, False, scale=0.001, need_tpu=False,
                        sut_factory=lambda path, config: HostOnly(
                            path, config, cell.table_module(),
                            "aggregates_skip_vectors"))


def test_a_program_that_compiles_in_the_first_stream_ends_the_run():
    """A program keyed on what a stream changes compiles at every stream:
    the window's first stream shows it, the other requests fail at once and
    the run ends with an error and no result, as a missing route does."""
    from benchmark.traffic.kinds.power_stream import ProgramsCold

    class Recompiles(SkipVectorsTable):
        def counters(self):
            return dict(super().counters(), **{"device.compiles": self.answered})

    cell = load_cell(WORKLOAD)
    with pytest.raises(ProgramsCold, match="first stream compiled or fetched 22"):
        runner.run_cell(WORKLOAD, 5, 600.0, False, scale=0.001, need_tpu=False,
                        sut_factory=lambda path, config: Recompiles(
                            path, config, cell.table_module(),
                            "aggregates_skip_vectors"))


def test_cell_is_added_at_the_end_and_edits_nothing():
    real = cell_mod._load
    bench = real(cell_mod.ROOT, "BENCHMARK.json")
    assert [w["name"] for w in bench["workloads"]][-1] == WORKLOAD
    assert len(bench["workloads"]) == 6
    mine = bench["workloads"][-1]
    assert mine["chips"] == 1 and mine["traffic"] == "power_stream"
    assert bench["configs"][-1]["name"] == mine["config"] == "lineitem_sf10_power"
    assert bench["configs"][-1]["reduced"] == [
        "scale_factor", "orders_table", "query_templates"]
    assert [m["name"] for m in bench["per_layer"]][-5:] == NEW
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if WORKLOAD in m.get("workloads", ()):
                assert m["workloads"][-1] == WORKLOAD
                # a metric lists the cell only if the cell reports what it moves
                assert m.get("moves", "merge_rows_per_s") == "merge_rows_per_s"
    cell = load_cell(WORKLOAD)
    assert [m.name for m in cell.end_to_end] == ["merge_rows_per_s", "setup_s"]
    assert sorted(m.name for m in cell.per_layer) == sorted(LISTED + NEW)
    refresh = real(cell_mod.HERE, "configs", "lineitem_sf10_refresh.json")
    pricing = real(cell_mod.HERE, "configs", "lineitem_sf10_pricing.json")
    for key in ("table", "layout", "table_properties"):
        assert cell.config[key] == refresh[key]
    assert cell.config["engine_confs"] == pricing["engine_confs"]
    assert cell.config["guarantees"][:4] == refresh["guarantees"]
    assert len(cell.config["guarantees"]) == 5
    assert set(cell.config["reduced"]) == set(bench["configs"][-1]["reduced"])
    assert len(bench["configs"][-1]["source"]) <= 200
    assert len(json.dumps(bench)) < 64 << 10
    # the two queries' texts are the accepted mixes' own
    assert cell.traffic["q6"]["query"] == real(
        cell_mod.HERE, "traffic", "q6.json")["query"]
    assert cell.traffic["q1"]["query"] == real(
        cell_mod.HERE, "traffic", "q1.json")["query"]
    assert cell.traffic["queries"] == ["q6", "q1"] * 11


def test_parameters_follow_the_seed_and_the_stream():
    from types import SimpleNamespace

    from benchmark.traffic.kinds import power_stream

    cell = load_cell(WORKLOAD)
    draws = [power_stream.parameters(SimpleNamespace(cell=cell, seed=seed), k)
             for seed, k in ((1, 0), (1, 1), (2**31 + 9, 0), (1, 0))]
    assert draws[0] == draws[3] and len({str(d) for d in draws}) == 3
    for name, what in draws[0]:
        if name == "q6":
            assert what[0] in range(1993, 1998) and what[2] in (24, 25)
            assert what[1] in cell.traffic["q6"]["discounts"]
        else:
            assert 60 <= what <= 120
    assert [name for name, _ in draws[0]] == ["q6", "q1"] * 11


def test_per_layer_metrics_read_a_streams_spans():
    """Three streams of the kind through the engine at a fiftieth of the
    size, the spans drained a request as the harness does in a traced run:
    every span-read metric of the cell finds something, the stream's queries
    and MERGEs are apart, and a keep mask costs the stream's first query."""
    from benchmark.harness.engine import EngineTable
    import time

    cell = load_cell(WORKLOAD)
    table, kind = cell.table_module(), cell.traffic_kind()
    cfg = dict(cell.config, layout=runner._scaled_layout(cell.config["layout"],
                                                         0.02))
    workdir = tempfile.mkdtemp(prefix="bench_power_")
    try:
        sut = EngineTable(os.path.join(workdir, "table"), cfg)
        gen = table.Generator(runner._scaled(cfg["table"], 0.02), 11)
        base = gen.base()
        sut.load(table.to_arrow(base))
        ctx = runner.Context(cell, 11, 0.02, sut, table, gen, base)
        state = kind.prepare(ctx)
        kind.warm_up(ctx, state)
        sut.drain_spans()
        run = runner.Run(cell, 11, 1.0, True)
        c0 = sut.counters()
        for i in range(3):
            t0 = time.perf_counter()
            out = kind.request(ctx, state, i)
            req = runner.Request(i, t0, time.perf_counter(), True, **out)
            req.spans = sut.drain_spans()
            run.requests.append(req)
        run.counters = {k: v - c0.get(k, 0) for k, v in sut.counters().items()}
        compared = kind.check(ctx, state, run.requests)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    assert all(c["value"] == 0 for c in compared.values()), compared
    values = {m.name: m.read(run) for m in cell.per_layer
              if m.name not in ("device_idle_pct.merge", "merge_resort_ms")}
    assert all(v is not None for v in values.values()), values
    assert values["power_queries_ms"] > 0 and values["power_refresh_ms"] > 0
    assert values["power_lane_load_ms"] > 0  # RF1's file, the first query
    assert values["power_version_install_ms"] > 0
    assert 0 < values["agg_keep_mask_ms"] < values["power_queries_ms"]
    assert values["merge_commit_ms"] < values["power_refresh_ms"]
    # 22 queries and two MERGEs a stream, one keep mask built a stream
    names = [s["name"] for s in run.requests[0].spans]
    assert names.count("delta.sql.select") == 22
    assert names.count("delta.dml.merge") == 2
    masks = [s["data"]["cached"] for r in run.requests for s in r.spans
             if s["name"] == "delta.columnCache.keepMask"]
    assert masks.count(False) == 3 and masks.count(True) == 3 * 21
    assert run.counters["columnCache.keep.misses"] == 3
    # a program without the span: 0.0 and true
    for r in run.requests:
        r.spans = [s for s in r.spans
                   if s["name"] != "delta.columnCache.keepMask"]
    [keep] = [m for m in cell.per_layer if m.name == "agg_keep_mask_ms"]
    assert keep.read(run) == 0.0
