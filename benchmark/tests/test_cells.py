"""Each cell runs end to end on the CPU at a thousandth of its size, and is
refused at its real size without a TPU."""
import os
import subprocess
import sys

import pytest

from benchmark.harness import lastline, runner
from benchmark.harness.cell import load_cell
from benchmark.harness.preflight import NoChip
from benchmark.tests.conftest import CELLS, ROOT


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_small_and_is_correct(workload):
    line = runner.run_cell(workload, 2**31 + 5, 1.0, False, scale=0.001,
                           need_tpu=False)
    cell = load_cell(workload)
    lastline.check(line, runner.expected_metrics(cell, False), False, 1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["compared"].values())


def test_refused_at_real_size_without_a_tpu():
    with pytest.raises(NoChip):
        runner.run_cell(CELLS[0], 1, 1.0, False)


def test_command_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "refusing to run" in p.stderr


def test_traced_window_without_device_work_prints_nothing():
    """On the CPU no operation runs on a TPU's plane: the run ends in the
    error that names the counters, not in a line with busy_s 0."""
    with pytest.raises(lastline.LastLineError, match="scan.device.declined"):
        runner.run_cell(CELLS[1], 3, 0.5, True, scale=0.001, need_tpu=False)


def test_window_ends_with_the_last_source():
    """`upsert_1m` makes 9 sources and merges 3 in set-up: a window of any
    length holds 6 MERGEs at the most, and none is made inside it."""
    line = runner.run_cell(CELLS[0], 7, 600.0, False, scale=0.001,
                           need_tpu=False)
    assert line["attempted"] == 6 and line["correct"] is True
    assert line["notes"]["window_s"] < 600


def test_scan_order_follows_the_seed_and_the_pool_does_not():
    from types import SimpleNamespace

    from benchmark.traffic.kinds import scan_filter as kind

    cell = load_cell(CELLS[1])
    ctxs = [SimpleNamespace(cell=cell, seed=s) for s in (1, 2**31 + 9)]
    assert kind._pool(ctxs[0]) == kind._pool(ctxs[1])
    first, count = cell.config["table"]["domains"]["date"]
    for q in kind._pool(ctxs[0]):
        (_, _, lo), (_, _, hi), (_, _, q_lo), _ = q["terms"]
        assert first <= lo and hi == lo + 29 and hi <= first + count - 1
        assert q_lo in cell.traffic["quantity_buckets"]
    n = cell.traffic["windows"]
    orders = []
    for ctx in ctxs:
        state = {"pool": [None] * n, "rounds": {}}
        rounds = [kind._round(ctx, state, r).tolist() for r in range(3)]
        assert all(sorted(r) == list(range(n)) for r in rounds)
        assert rounds[0] != rounds[1]
        orders.append(rounds)
    assert orders[0] != orders[1]


def test_cell_added_as_data_only(monkeypatch, tmp_path):
    """README's walk-through: `store_sales_sf10_smallfiles.upsert_1m` is one
    new configuration file, the mix that is there, and entries in
    BENCHMARK.json; no file under benchmark/ changes."""
    import json

    from benchmark.harness import cell as cell_mod

    new = "store_sales_sf10_smallfiles.upsert_1m"
    config = cell_mod._load(cell_mod.HERE, "configs", "store_sales_sf10.json")
    config["name"] = "store_sales_sf10_smallfiles"
    config["layout"] = {"write_confs": {
        "delta.tpu.write.targetFileRows": 125000}, "files": 231}
    path = tmp_path / "store_sales_sf10_smallfiles.json"
    path.write_text(json.dumps(config))
    real = cell_mod._load

    def load(*parts):
        obj = real(*parts)
        if parts[-1] != "BENCHMARK.json":
            return obj
        obj["configs"].append({"name": config["name"], "file": str(path)})
        obj["workloads"].append({
            "name": new, "config": config["name"],
            "traffic": "upsert_1m", "chips": 1, "why": "every file touched"})
        for m in obj["end_to_end"] + obj["per_layer"]:
            if CELLS[0] in m.get("workloads", ()):
                m["workloads"].append(new)
        return obj

    monkeypatch.setattr(cell_mod, "_load", load)
    cell = load_cell(new)
    assert [m.name for m in cell.end_to_end] == [
        "merge_rows_per_s", "merge_written_B_per_row", "setup_s"]
    assert "probe_roofline" in [m.name for m in cell.per_layer]
    line = runner.run_cell(new, 4, 0.5, False, scale=0.002, need_tpu=False)
    lastline.check(line, runner.expected_metrics(cell, False), False, 1)
    assert line["correct"] is True, line["compared"]
