"""The control of ``lineitem_sf10_refresh.rf_pairs``,
``delete_first_line_only``: the plain reference in the program's place, with
"the table holds the loaded rows less every line of every order an
acknowledged RF2 named" broken the way a join that stops at a key's first
match breaks it over a key that is not unique: an RF2 deletes one line of
each order, the first, and leaves the others. The cell's comparison has to
come out as not correct on it, by ``rows_extra`` (the lines left behind) and
``merge_counts_wrong`` (each RF2 reports fewer rows deleted) alone.

    python3 benchmark/controls_refresh.py --seeds 1,2,3 --seconds 5

runs it at the cell's own size, through the same window and the same
comparison, and prints one line for each seed; the exit code is 0 when
every seed came out as not correct by those two counts and no other.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "lineitem_sf10_refresh.rf_pairs"
FAILS_BY = {"rows_extra", "merge_counts_wrong"}


class FirstLineOnlyTable:
    """What the traffic kind asks of a system under test, over the
    reference's rows."""

    def __init__(self, path: str, config: Dict[str, Any], module, broken: str):
        if broken != "delete_first_line_only":
            raise ValueError(f"no control named {broken!r}")
        self.m = module
        self.ref = None
        self.statements = 0
        self._state = None

    def load(self, data) -> None:
        self.ref = self.m.Refresher(self.m.part_from_arrow(data),
                                    rf2_deletes=self.m.first_line_only)

    def refresh(self, function: str, source) -> Dict[str, int]:
        self.statements += 1
        self._state = None
        if function == "rf1":
            return {"numTargetRowsInserted": self.ref.rf1(
                self.m.part_from_arrow(source)), "numTargetRowsDeleted": 0}
        keys = source.column(self.m.RF2_KEY).to_numpy()
        return {"numTargetRowsInserted": 0,
                "numTargetRowsDeleted": self.ref.rf2(keys)}

    def read_all(self, columns=None):
        if self._state is None:
            self._state = self.ref.state()
        return self._state.to_arrow(list(columns or self.m.NAMES))

    def versions(self) -> List[Dict[str, Any]]:
        return [{"version": 0, "operation": "CREATE"}] + [
            {"version": v, "operation": "MERGE"}
            for v in range(1, self.statements + 1)]

    # it answers every statement on its one route and compiles nothing
    def counters(self) -> Dict[str, int]:
        return {"merge.resident.pairsOnly": self.statements}

    def drain_spans(self) -> List[Dict[str, Any]]:
        return []


def run_control(seed: int, seconds: float, scale: float = 1.0,
                need_tpu: bool = True, workload: str = WORKLOAD):
    """One run of the cell with its control in the program's place."""
    from benchmark.harness import runner
    from benchmark.harness.cell import load_cell

    cell = load_cell(workload)

    def factory(path, config):
        return FirstLineOnlyTable(path, config, cell.table_module(),
                                  cell.traffic["control"])

    return runner.run_cell(workload, seed, seconds, False, scale=scale,
                           need_tpu=need_tpu, sut_factory=factory)


def failed_by(line) -> set:
    return {k for k, c in line["compared"].items() if c["value"] > c["limit"]}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run lineitem_sf10_refresh.rf_pairs's control.")
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
