"""`lineitem_sf10_pricing.q1` end to end on the CPU at a thousandth of its
size, and its control, `float_sums`, coming out as not correct by the
aggregates alone. Run by hand, as this directory's conftest says.

The control runs at a fiftieth of the size, not a thousandth: a float64
holds every integer below 2^53 = 9.0e15, and at 60,000 rows the largest sum
(a group's `sum_charge`, 3.7e10 millionths a row) is 1e15, so adding in
float64 there is exact and the control is, rightly, correct."""
from benchmark.controls_q1 import WORKLOAD, run_control
from benchmark.harness import lastline, runner
from benchmark.harness.cell import load_cell


def test_cell_runs_small_and_is_correct():
    line = runner.run_cell(WORKLOAD, 2**31 + 32, 1.0, False, scale=0.001,
                           need_tpu=False)
    cell = load_cell(WORKLOAD)
    lastline.check(line, runner.expected_metrics(cell, False), False, 1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 10 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["compared"].values())


def test_control_is_not_correct_by_the_aggregates_alone():
    line = run_control(9, 0.5, scale=0.02, need_tpu=False)
    assert line["correct"] is False
    wrong = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert wrong == {"aggregates_wrong"}


def test_control_at_a_thousandth_adds_exactly():
    """Why the test above is not run at a thousandth."""
    line = run_control(9, 0.5, scale=0.001, need_tpu=False)
    assert line["correct"] is True


def test_parameter_follows_the_seed_request_by_request():
    from types import SimpleNamespace

    from benchmark.traffic.kinds import sql_grouped_aggregate as kind

    cell = load_cell(WORKLOAD)
    draws = []
    for seed in (1, 2**31 + 9):
        ctx, state = SimpleNamespace(cell=cell, seed=seed), {"blocks": {}}
        draws.append([kind._value(ctx, state, i) for i in range(5000)])
    assert draws[0] != draws[1]
    assert sorted(set(draws[0])) == list(range(60, 121))  # all 61 values


def test_a_program_without_the_route_stops_in_set_up():
    """The route probe: a system that answers, but not from the device
    route, ends set-up with an error before any full-size query."""
    import pytest

    from benchmark.controls_q1 import FloatSumsTable
    from benchmark.traffic.kinds.sql_grouped_aggregate import RouteMissing

    class HostOnly(FloatSumsTable):
        def counters(self):
            return {}

    cell = load_cell(WORKLOAD)
    with pytest.raises(RouteMissing):
        runner.run_cell(WORKLOAD, 5, 0.2, False, scale=0.001, need_tpu=False,
                        sut_factory=lambda path, config: HostOnly(
                            path, config, cell.table_module(), "float_sums"))
