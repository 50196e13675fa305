"""`lineitem_sf10.q6` end to end on the CPU at a thousandth of its size, and
its control, `float_bounds`, coming out as not correct by the revenue alone.
Run by hand, as this directory's conftest says."""
from benchmark.controls_q6 import WORKLOAD, run_control
from benchmark.harness import lastline, runner
from benchmark.harness.cell import load_cell


def test_cell_runs_small_and_is_correct():
    line = runner.run_cell(WORKLOAD, 2**31 + 27, 1.0, False, scale=0.001,
                           need_tpu=False)
    cell = load_cell(WORKLOAD)
    lastline.check(line, runner.expected_metrics(cell, False), False, 1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 10 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["compared"].values())


def test_control_is_not_correct_by_the_revenue_alone():
    line = run_control(9, 0.5, scale=0.001, need_tpu=False)
    assert line["correct"] is False
    wrong = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert wrong == {"revenue_wrong"}


def test_parameters_follow_the_seed_request_by_request():
    from types import SimpleNamespace

    from benchmark.traffic.kinds import sql_aggregate as kind

    cell = load_cell(WORKLOAD)
    draws = []
    for seed in (1, 2**31 + 9):
        ctx, state = SimpleNamespace(cell=cell, seed=seed), {"blocks": {}}
        draws.append([kind._triple(ctx, state, i) for i in range(5000)])
    assert draws[0] != draws[1]
    assert len(set(draws[0])) == 80  # every triple of 5 x 8 x 2
    p = cell.traffic
    assert all(y in p["years"] and d in p["discounts"] and q in p["quantities"]
               for y, d, q in draws[0])
