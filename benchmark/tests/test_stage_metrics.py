"""PR 36's reader and its fifteen metrics: `child_span_mean_ms` on hand-made
runs (children inside and outside the parent, on another thread, two parents
a request, a parent with no child, no parent), and every new entry of
`BENCHMARK.json` with its file, its reader and cells that exist. Run by hand,
as this directory's conftest says. Beside `test_span_readers.py` and
`test_cells.py`, whose helpers it borrows: a PR that is not a benchmark PR
adds files here and edits none."""
import pytest

from benchmark.harness.cell import load_cell
from benchmark.metrics.readers import child_span_mean_ms, span_cover_pct
from benchmark.tests.test_span_readers import request, run_of, span

# -- child_span_mean_ms: the stages of one of the program's spans -------------

WRITE = "delta.dml.merge.write"
ENCODE = {"parent": WRITE, "spans": ["delta.write.encode"]}
PREPARE = {"parent": WRITE,
           "spans": ["delta.dml.merge.write.concat", "delta.write.prepare"]}


def _merge_request(i, start, children):
    """A MERGE whose ``.write`` lies 100..400 us after ``start``."""
    return request(i, start, start + 1000, [
        span("delta.dml.merge", start, 1000),
        span(WRITE, start + 100, 300)] + children)


@pytest.mark.parametrize("case, params, spans, want", [
    ("a child inside the parent counts, one outside it does not", ENCODE,
     [span("delta.write.encode", 150, 200),
      span("delta.write.encode", 500, 100)], 0.2),        # an OPTIMIZE's, say
    ("a child on another thread counts", ENCODE,
     [span("delta.write.encode", 150, 100, thread=2),
      span("delta.write.encode", 160, 120, thread=3)], 0.22),
    ("several names add up", PREPARE,
     [span("delta.dml.merge.write.concat", 100, 30),
      span("delta.write.prepare", 130, 50),
      span("delta.write.encode", 180, 200)], 0.08),
    ("a parent with no child is 0.0, and true", ENCODE,
     [span("delta.write.prepare", 130, 50)], 0.0),
    ("a point event of the name is no span", ENCODE,
     [span("delta.write.encode", 150, None)], 0.0),
])
def test_child_span_mean_ms(case, params, spans, want):
    run = run_of([_merge_request(0, 0, spans)], busy_us=[])
    assert child_span_mean_ms.read(run, params) == pytest.approx(want), case


def test_child_span_mean_ms_over_two_parents_and_over_requests():
    """A refresh pair holds two MERGEs, each with its ``.write``: a child of
    either counts once. The mean is over the window's requests, also those
    without the parent; a failed request is not of the window's."""
    pair = request(0, 0, 3000, [
        span(WRITE, 100, 300), span("delta.write.encode", 150, 200),
        span(WRITE, 1100, 50),                                  # RF2: no file
        span(WRITE, 2100, 300), span("delta.write.encode", 2150, 100),
        span("delta.write.encode", 2600, 70)])                  # outside all
    bare = request(1, 3000, 4000, [span("delta.scan", 3000, 900)])
    failed = request(2, 4000, 5000, [span(WRITE, 4100, 300),
                                     span("delta.write.encode", 4150, 200)])
    failed.ok = False
    run = run_of([pair, bare, failed], busy_us=[])
    assert child_span_mean_ms.read(run, ENCODE) == pytest.approx(0.3 / 2)


def test_child_span_mean_ms_is_silent_without_the_parent():
    """No request has the parent span: nothing, and no error. The parent's
    program with this PR's metric files has the parent and no child: 0.0."""
    run = run_of([request(0, 0, 100, [span("delta.scan", 0, 100),
                                      span("delta.write.encode", 10, 20)])],
                 busy_us=[])
    assert child_span_mean_ms.read(run, ENCODE) is None
    run = run_of([], busy_us=[])
    assert child_span_mean_ms.read(run, ENCODE) is None
    older = run_of([request(0, 0, 1000, [span("delta.dml.merge", 0, 1000),
                                         span(WRITE, 100, 300)])], busy_us=[])
    assert child_span_mean_ms.read(older, ENCODE) == 0.0
    # the cover of a leaf span that holds nothing reads 0.0 there, a number
    assert span_cover_pct.read(older, {"root": WRITE}) == 0.0


PR36 = ["merge_write_cover_pct", "merge_apply_cover_pct", "scan_open_cover_pct",
        "agg_launch_cover_pct", "merge_write_prepare_ms",
        "merge_write_encode_ms", "merge_write_stats_ms",
        "merge_apply_multimatch_ms", "merge_apply_matched_ms",
        "merge_apply_insert_ms", "scan_open_plan_ms", "scan_open_file_ms",
        "agg_launch_ms", "agg_fetch_wait_ms", "agg_lanes_ms"]


@pytest.mark.parametrize("name", PR36)
def test_stage_metric_has_its_file_its_reader_and_cells_that_exist(name):
    """PR 36's fifteen: each entry names cells the benchmark has, each of
    which reports the end-to-end metric it moves; its file names a reader
    that is there and, for a stage metric, the span whose stages it reads."""
    import importlib

    from benchmark.harness import cell as cell_mod

    bench = cell_mod._load(cell_mod.ROOT, "BENCHMARK.json")
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span"
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert entry["better"] == ("higher" if name.endswith("_pct") else "lower")
    cells = {w["name"] for w in bench["workloads"]}
    [moved] = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert set(entry["workloads"]) <= set(moved["workloads"])
    spec = cell_mod._load(cell_mod.HERE, "metrics", name + ".json")
    reader = importlib.import_module(
        f"benchmark.metrics.readers.{spec['reader']}")
    assert callable(reader.read)
    if name.endswith("_pct"):
        assert spec == {"reader": "span_cover_pct",
                        "params": {"root": spec["params"]["root"]}}
    else:
        assert spec["reader"] == "child_span_mean_ms"
        parent, spans = spec["params"]["parent"], spec["params"]["spans"]
        assert spans and parent not in spans
    for workload in entry["workloads"]:
        assert name in [m.name for m in load_cell(workload).per_layer]
