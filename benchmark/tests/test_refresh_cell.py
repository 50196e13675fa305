"""`lineitem_sf10_refresh.rf_pairs` end to end on the CPU at a thousandth and
at a fiftieth of its size, as its entries in
`BENCHMARK.json`, its control, `delete_first_line_only`, coming out as not
correct by its two counts alone, and the reader its two device metrics use. Run by hand, as this directory's
conftest says.

At a thousandth a function is 15 orders and an RF1 about 60 rows, which lie
on both sides of 64: the program's buckets (a power of two of the rows
appended, of the rows flipped) change from pair to pair and it compiles in
the window, rightly counted. At a fiftieth (300 orders, ~1,200 rows, bucket
2,048) as at full size (15,000 orders, ~60,000 rows, bucket 65,536) every
pair has the second warm-up pair's shapes."""
import json

import pytest

from benchmark.controls_refresh import FAILS_BY, WORKLOAD, failed_by, run_control
from benchmark.harness import cell as cell_mod
from benchmark.harness import lastline, runner
from benchmark.harness.cell import load_cell


def test_cell_runs_at_a_fiftieth_and_is_correct():
    line = runner.run_cell(WORKLOAD, 2**31 + 34, 600.0, False, scale=0.02,
                           need_tpu=False)
    cell = load_cell(WORKLOAD)
    lastline.check(line, runner.expected_metrics(cell, False), False, 1)
    assert line["correct"] is True, line["compared"]
    # 13 sets, 2 sent in set-up: the window ends with the last of them
    assert line["attempted"] == 11 and line["failed"] == 0
    assert line["notes"]["window_s"] < 600
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert set(line["compared"]) == {
        "rows_missing", "rows_extra", "cells_wrong", "merge_counts_wrong",
        "commits_wrong", "statements_off_route", "compiles_in_window",
        "requests_failed"}


def test_cell_runs_at_a_thousandth():
    line = runner.run_cell(WORKLOAD, 7, 2.0, False, scale=0.001,
                           need_tpu=False)
    wrong = failed_by(line)
    assert wrong <= {"compiles_in_window"}, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_control_is_not_correct_by_its_two_counts_alone():
    line = run_control(9, 1.0, scale=0.02, need_tpu=False)
    assert line["correct"] is False
    assert failed_by(line) == FAILS_BY
    # every RF2 of the run reported one row an order, not every line
    assert line["compared"]["merge_counts_wrong"]["value"] \
        == line["attempted"] + 2


def test_cell_is_added_at_the_end_and_edits_nothing():
    """The cell as the PR adds it: a configuration file, a mix, a kind, a
    table module, one reader, metric files, and entries at the end of
    `BENCHMARK.json`'s lists; an accepted entry only gains the cell's name
    at the end of its `workloads`."""
    real = cell_mod._load
    bench = real(cell_mod.ROOT, "BENCHMARK.json")
    mine = [w for w in bench["workloads"] if w["name"] == WORKLOAD]
    assert len(mine) == 1 and mine[0]["chips"] == 1
    assert len(bench["workloads"]) == 5
    assert bench["workloads"][-1]["name"] == WORKLOAD
    assert bench["configs"][-1]["name"] == mine[0]["config"]
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if WORKLOAD in m.get("workloads", ()):
                assert m["workloads"][-1] == WORKLOAD
    cell = load_cell(WORKLOAD)
    # not `merge_written_B_per_row`: six seeds spread by 0.52%, over half
    # of its 1% bound (PERF.md §6)
    assert [m.name for m in cell.end_to_end] == ["merge_rows_per_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == [
        "merge_key_join_ms", "merge_apply_write_ms", "merge_commit_ms",
        "probe_roofline", "device_idle_pct.merge", "merge_span_cover_pct",
        "merge_idle_unattributed_pct", "merge_resort_ms", "merge_inverse_ms",
        "merge_dv_ms", "merge_write_ms"]
    assert cell.config["table"] == real(
        cell_mod.HERE, "configs", "lineitem_sf10.json")["table"]
    assert "delta.tpu.columnCache.maxBytes" not in cell.config["engine_confs"]
    assert len(json.dumps(bench)) < 64 << 10


def test_a_program_that_keeps_no_slab_stops_after_one_pair():
    """The route check: a system that answers, but never from the resident
    route, ends set-up with an error after the first pair."""
    from benchmark.controls_refresh import FirstLineOnlyTable
    from benchmark.traffic.kinds.merge_refresh import RouteMissing

    class HostOnly(FirstLineOnlyTable):
        def counters(self):
            return {}

    cell = load_cell(WORKLOAD)
    with pytest.raises(RouteMissing, match="warm-up pair 0"):
        runner.run_cell(WORKLOAD, 5, 0.2, False, scale=0.001, need_tpu=False,
                        sut_factory=lambda path, config: HostOnly(
                            path, config, cell.table_module(),
                            "delete_first_line_only"))


def test_sets_follow_the_seed():
    table = load_cell(WORKLOAD).table_module()
    params = dict(load_cell(WORKLOAD).config["table"], rows=5000)
    firsts = []
    for seed in (1, 2**31 + 9):
        gen = table.Generator(params, seed)
        firsts.append(gen.refresh_set(gen.base(), 0, 15).rf1.lanes[
            "l_partkey"][:8].tolist())
    assert firsts[0] != firsts[1]


def test_module_ms_reads_a_modules_device_time_a_request():
    """`merge_resort_ms` and `merge_inverse_ms` read the device's time in
    an XLA module from the trace (the program's spans around a jitted call
    close at the dispatch): over the recorded trace, the re-sort's module is
    told from the probe's by its operations' argument names, and a module
    that never ran reads nothing."""
    import os
    from types import SimpleNamespace

    from benchmark.harness import trace
    from benchmark.metrics.readers import module_ms

    recorded = trace.read(os.path.join(cell_mod.HERE, "data",
                                       "recorded.xplane.pb"))
    run = SimpleNamespace(trace=recorded, done=[object()] * 3)
    cell = load_cell(WORKLOAD)
    resort, inverse = (next(m for m in cell.per_layer if m.name == n)
                       for n in ("merge_resort_ms", "merge_inverse_ms"))
    assert resort.reader == inverse.reader == "module_ms"
    every = module_ms.read(run, {"module": "^jit_kernel"})
    sort_ms = resort.read(run)
    probe_ms = module_ms.read(run, {"module": "^jit_kernel",
                                    "operand": r"%s_keys\b"})
    assert 0 < sort_ms < every and 0 < probe_ms < every
    assert sort_ms + probe_ms <= every + 1e-9
    events = recorded.module_events("^jit_kernel", r"%(keys|valid)\b")
    assert sort_ms == pytest.approx(
        sum(e.end - e.start for e in events) / 1e6 / 3)
    assert inverse.read(run) is None  # recorded before PR 31: no such module
    assert module_ms.read(SimpleNamespace(trace=None, done=[1]),
                          resort.params) is None
