"""Each cell's control comes out as not correct, and so does a run whose
timed path is broken underneath."""
import numpy as np
import pyarrow as pa
import pytest

from benchmark.controls import run_control
from benchmark.harness import engine, runner
from benchmark.tests.conftest import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    line = run_control(workload, 9, 0.5, scale=0.001, need_tpu=False)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


def _run(workload):
    return runner.run_cell(workload, 21, 0.5, False, scale=0.001,
                           need_tpu=False)


def test_merge_that_leaves_the_state_unchanged(monkeypatch):
    def merge(self, source, condition):
        half = source.num_rows // 2
        return {"numTargetRowsUpdated": half,
                "numTargetRowsInserted": source.num_rows - half}

    monkeypatch.setattr(engine.EngineTable, "merge", merge)
    line = _run(CELLS[0])
    assert line["correct"] is False
    assert line["compared"]["rows_missing"]["value"] > 0
    assert line["compared"]["commits_wrong"]["value"] > 0


def test_merge_that_leaves_half_of_the_batch_out(monkeypatch):
    real = engine.EngineTable.merge

    def merge(self, source, condition):
        return real(self, source.slice(0, source.num_rows // 2), condition)

    monkeypatch.setattr(engine.EngineTable, "merge", merge)
    line = _run(CELLS[0])
    assert line["correct"] is False
    c = line["compared"]
    assert c["rows_missing"]["value"] > 0 and c["cells_wrong"]["value"] > 0
    assert c["merge_counts_wrong"]["value"] > 0


def test_merge_that_alters_a_cell_as_it_writes(monkeypatch):
    real = engine.EngineTable.merge

    def merge(self, source, condition):
        i = source.schema.get_field_index("ss_quantity")
        q = source.column(i).to_pylist()
        q[0] = 1 if q[0] != 1 else 2
        altered = source.set_column(i, source.schema.field(i),
                                    pa.array(q, pa.int32()))
        return real(self, altered, condition)

    monkeypatch.setattr(engine.EngineTable, "merge", merge)
    line = _run(CELLS[0])
    assert line["correct"] is False
    assert line["compared"]["cells_wrong"]["value"] >= 1


@pytest.mark.parametrize("fault", ["drop_a_row", "alter_a_cell", "extra_row"])
def test_scan_whose_answer_is_altered(monkeypatch, fault):
    real = engine.EngineTable.scan

    def scan(self, filters, columns):
        got = real(self, filters, columns)
        if got.num_rows < 2:
            return got
        if fault == "drop_a_row":
            return got.slice(1)
        if fault == "extra_row":
            return pa.concat_tables([got, got.slice(0, 1)])
        i = got.schema.get_field_index("ss_quantity")
        q = np.array(got.column(i).to_numpy(zero_copy_only=False))
        q[0] += 1
        return got.set_column(i, got.schema.field(i), pa.array(q, pa.int32()))

    monkeypatch.setattr(engine.EngineTable, "scan", scan)
    line = _run(CELLS[1])
    assert line["correct"] is False
    assert line["compared"]["scans_wrong"]["value"] >= 1
