"""The control of ``lineitem_sf10_power.power_stream``,
``aggregates_skip_vectors``: the plain reference in the program's place,
with "every query's answer is that of the newest committed version" broken
the way an aggregate route that reads a file's resident lanes and not its
deletion vector breaks it: the refresh functions are applied as they should
be (the table read back is right, every count is right), and every query
counts every loaded row, the lines an acknowledged RF2 deleted among them.
The cell's comparison has to come out as not correct on it, by
``aggregates_wrong`` alone.

    python3 benchmark/controls_power.py --seeds 1,2,3 --seconds 5

runs it at the cell's own size, through the same window and the same
comparison, and prints one line for each seed; the exit code is 0 when
every seed came out as not correct by that count and no other.
"""
from __future__ import annotations

import json
import os
import re
import sys
from decimal import Decimal
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.controls_refresh import FirstLineOnlyTable, failed_by  # noqa: E402,F401

WORKLOAD = "lineitem_sf10_power.power_stream"
FAILS_BY = {"aggregates_wrong"}
_Q1 = re.compile(r"interval '(\d+)' day group by")
_Q6 = re.compile(r"l_shipdate >= date '(\d{4})-01-01'.* between ([\d.]+) - "
                 r"0\.01 .*l_quantity < (\d+)")


class SkipVectorsTable(FirstLineOnlyTable):
    """What the traffic kind asks of a system under test, over the
    reference's rows: the refresh control's table (its ``refresh``,
    ``read_all`` and ``versions``) with every function applied as it should
    be, and ``sql`` over a state whose queries do not see the deletes."""

    def __init__(self, path: str, config: Dict[str, Any], module, broken: str):
        if broken != "aggregates_skip_vectors":
            raise ValueError(f"no control named {broken!r}")
        self.path = path
        self.config = config
        self.m = module
        self.ref = None  # a StreamRef: a Refresher's rf1, rf2 and state
        self.statements = 0
        self.answered = 0
        self._state = None

    def load(self, data) -> None:
        part = self.m.part_from_arrow(data)
        self.ref = self.m.StreamRef(self.m.Rows(part.lanes), part,
                                    honours_deletes=False)

    def sql(self, text: str):
        import pyarrow as pa

        self.answered += 1
        q1 = _Q1.search(text)
        if q1:
            return self.ref.q1(int(q1.group(1)))
        year, discount, quantity = _Q6.search(text).groups()
        revenue = self.ref.q6(int(year), Decimal(discount), int(quantity))
        return pa.table({"revenue": pa.array([revenue], pa.decimal128(38, 4))})

    # it answers every statement on its one route and compiles nothing
    def counters(self) -> Dict[str, int]:
        return {"merge.resident.pairsOnly": self.statements,
                "scan.aggregate.device": self.answered}


def run_control(seed: int, seconds: float, scale: float = 1.0,
                need_tpu: bool = True, workload: str = WORKLOAD):
    """One run of the cell with its control in the program's place."""
    from benchmark.harness import runner
    from benchmark.harness.cell import load_cell

    cell = load_cell(workload)

    def factory(path, config):
        return SkipVectorsTable(path, config, cell.table_module(),
                                cell.traffic["control"])

    return runner.run_cell(workload, seed, seconds, False, scale=scale,
                           need_tpu=need_tpu, sut_factory=factory)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run lineitem_sf10_power.power_stream's control.")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        line = run_control(seed, args.seconds)
        print(json.dumps({"control_of": WORKLOAD, "seed": seed,
                          "correct": line["correct"],
                          "compared": line["compared"]}), flush=True)
        caught = caught and not line["correct"] and failed_by(line) == FAILS_BY
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
