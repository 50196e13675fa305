"""Controls: the plain reference put in the program's place, with one
guarantee of the configuration broken. A cell's comparison has to come out
as not correct on its control, or it compares nothing.

The system states no precision, so a control breaks a guarantee
(``guarantees`` in the configuration's file) instead of computing in a lower
one. Each mix's file names its control:

``lost_update``
    breaks "every MERGE is one committed version: all of its rows or none":
    a MERGE inserts its new rows and loses the update of every row the table
    already held, as a writer does that commits over a stale snapshot.
``half_open_window``
    breaks "results are exact": the upper bound of each range is taken as
    exclusive, the off-by-one of a range lowered to ``[lo, hi)``. (Rounding
    the lanes to a float32 pair, the guard of ``tests/test_chip_smoke.py``,
    changes no result here: every integer of ``store_sales`` at SF10 is
    below 2^24.)

    python3 benchmark/controls.py --workload <name> --seeds 1,2,3 --seconds 5

runs a cell's control at the cell's own size, through the same window and
the same comparison, and prints one line for each seed: the numbers compared,
each beside its limit. The exit code is 0 when every seed came out as not
correct. The benchmark's own runs never run this; ``benchmark/tests`` keeps
it at a small size.
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class ControlTable:
    """The same calls as ``harness.engine.EngineTable`` over the reference's
    rows."""

    def __init__(self, path: str, config: Dict[str, Any], module, broken: str):
        if broken not in ("lost_update", "half_open_window"):
            raise ValueError(f"no control named {broken!r}")
        self.config = config
        self.m = module
        self.broken = broken
        self.rows = None
        self.merges = 0

    def _rows(self, table):
        kinds = {n: k for n, k, _ in self.m.COLUMNS}
        return self.m.Rows({n: self.m.lane_from_arrow(table.column(n), kinds[n])
                            for n in table.column_names})

    def load(self, data) -> None:
        self.rows = self._rows(data)

    def merge(self, source, condition: str) -> Dict[str, Any]:
        src = self._rows(source)
        held = np.isin(src.packed_key(), self.rows.packed_key())
        if self.broken == "lost_update":
            new = src.take(np.flatnonzero(~held))
            self.rows = self.m.concat([self.rows, new])
        else:
            self.rows, _ = self.m.ref_upsert([self.rows, src])
        self.merges += 1
        return {"numTargetRowsUpdated": int(held.sum()),
                "numTargetRowsInserted": int((~held).sum())}

    def scan(self, filters: Sequence[str], columns: Sequence[str]):
        terms = [(c, op, int(v)) for c, op, v in re.findall(
            r"(\w+) (>=|<=|>|<|=) (-?\d+)", " AND ".join(filters))]
        if self.broken == "half_open_window":
            terms = [(c, "<" if op == "<=" else op, v) for c, op, v in terms]
        got = self.m.ref_filter(self.rows, terms, columns)
        full = self.m.to_arrow(self.m.Rows({
            n: got.lanes.get(n, np.zeros(len(got), np.int32))
            for n in self.m.NAMES}))
        return full.select(list(columns))

    def read_all(self, columns=None):
        full = self.m.to_arrow(self.rows)
        return full if columns is None else full.select(list(columns))

    def versions(self) -> List[Dict[str, Any]]:
        return [{"version": 0, "operation": "CREATE"}] + [
            {"version": v, "operation": "MERGE"}
            for v in range(1, self.merges + 1)]

    # nothing of the program runs, so there is nothing of it to read
    def counters(self) -> Dict[str, int]:
        return {}

    def drain_spans(self) -> List[Dict[str, Any]]:
        return []

    def merge_phases(self) -> Dict[str, float]:
        return {}

    def merge_decision(self):
        return "control"


def run_control(workload: str, seed: int, seconds: float, scale: float = 1.0,
                need_tpu: bool = True):
    """One run of the cell with its control in the program's place."""
    from benchmark.harness import runner
    from benchmark.harness.cell import load_cell

    cell = load_cell(workload)

    def factory(path, config):
        return ControlTable(path, config, cell.table_module(),
                            cell.traffic["control"])

    return runner.run_cell(workload, seed, seconds, False, scale=scale,
                           need_tpu=need_tpu, sut_factory=factory)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run a cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        line = run_control(args.workload, seed, args.seconds)
        print(json.dumps({"control_of": args.workload, "seed": seed,
                          "correct": line["correct"],
                          "compared": line["compared"]}), flush=True)
        caught = caught and not line["correct"]
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
