"""The control of ``lineitem_sf10_pricing.q1``, ``float_sums``: the plain
reference in the program's place, with "every column is exact" broken the
way a program that adds in a lower precision than decimal breaks it: every
sum accumulated in float64 and converted back. A float64 holds integers up
to 2^53 = 9.0e15, and a group's ``sum_charge`` at SF10 is about 5e17
millionths. The cell's comparison has to come out as not correct on it, by
``aggregates_wrong`` alone.

    python3 benchmark/controls_q1.py --seeds 1,2,3 --seconds 5

runs it at the cell's own size, through the same window and the same
comparison, and prints one line for each seed; the exit code is 0 when
every seed came out as not correct. At a thousandth of the size every sum
is below 2^53 and the control is, rightly, correct: ``benchmark/tests/
test_q1_cell.py`` runs it at a fiftieth.
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "lineitem_sf10_pricing.q1"
_DELTA = re.compile(r"interval '(\d+)' day")


class FloatSumsTable:
    """What the traffic kind asks of a system under test, over the
    reference's rows."""

    def __init__(self, path: str, config: Dict[str, Any], module, broken: str):
        if broken != "float_sums":
            raise ValueError(f"no control named {broken!r}")
        self.path = path
        self.config = config
        self.m = module
        self.rows = None
        self.answered = 0

    def load(self, data) -> None:
        self.rows = self.m.Rows({
            name: self._lane(name, data.column(name).combine_chunks())
            for name in ("l_shipdate", "l_quantity", "l_extendedprice",
                         "l_discount", "l_tax", "l_returnflag", "l_linestatus")})

    def _lane(self, name: str, arr):
        if pa.types.is_date(arr.type):
            return arr.cast(pa.int32()).to_numpy()
        if pa.types.is_string(arr.type):
            values = {"l_returnflag": self.m.RETURN_FLAGS,
                      "l_linestatus": self.m.LINE_STATUS}[name]
            return pc.index_in(arr, value_set=pa.array(values)).to_numpy(
                ).astype(np.int8)
        # decimal(15,2), no NULLs: the low word of each 16-byte value is its
        # hundredths, and every value of the table is below 2^31
        words = np.frombuffer(arr.buffers()[1], np.int64)
        return words[2 * arr.offset:2 * (arr.offset + len(arr)):2].astype(np.int32)

    def sql(self, text: str):
        self.answered += 1
        return self.m.ref_q1_float_sums(self.rows,
                                        int(_DELTA.search(text).group(1)))

    # it answers every request on its one route and compiles nothing
    def counters(self) -> Dict[str, int]:
        return {"scan.aggregate.device": self.answered}

    def drain_spans(self) -> List[Dict[str, Any]]:
        return []


def run_control(seed: int, seconds: float, scale: float = 1.0,
                need_tpu: bool = True, workload: str = WORKLOAD):
    """One run of the cell with its control in the program's place."""
    from benchmark.harness import runner
    from benchmark.harness.cell import load_cell

    cell = load_cell(workload)

    def factory(path, config):
        return FloatSumsTable(path, config, cell.table_module(),
                              cell.traffic["control"])

    return runner.run_cell(workload, seed, seconds, False, scale=scale,
                           need_tpu=need_tpu, sut_factory=factory)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run lineitem_sf10_pricing.q1's control.")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        line = run_control(seed, args.seconds)
        print(json.dumps({"control_of": WORKLOAD, "seed": seed,
                          "correct": line["correct"],
                          "compared": line["compared"]}), flush=True)
        caught = caught and not line["correct"]
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
