"""The control of ``lineitem_sf10.q6``, ``float_bounds``: the plain reference
in the program's place, with "the revenue is exact" broken the way a
program that computes in a lower precision than decimal breaks it: the
bounds ``DISCOUNT -+ 0.01`` folded in float64 and compared as floats
(``0.06 + 0.01`` is below 0.07 there, so a whole discount falls out). The
cell's comparison has to come out as not correct on it, by ``revenue_wrong``
alone.

    python3 benchmark/controls_q6.py --seeds 1,2,3 --seconds 5

runs it at the cell's own size, through the same window and the same
comparison, and prints one line for each seed; the exit code is 0 when
every seed came out as not correct. A file of its own, beside
``controls.py``: that one's table answers ``merge`` and ``scan``, this one
SQL text.
"""
from __future__ import annotations

import json
import os
import re
import sys
from decimal import Decimal
from typing import Any, Dict, List

import numpy as np
import pyarrow as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "lineitem_sf10.q6"
_Q6 = re.compile(r"date '(\d{4})-01-01' and .* between ([\d.]+) - 0\.01 and "
                 r"[\d.]+ \+ 0\.01 and l_quantity < (\d+)")


class FloatBoundsTable:
    """What the traffic kind asks of a system under test, over the
    reference's rows."""

    def __init__(self, path: str, config: Dict[str, Any], module, broken: str):
        if broken != "float_bounds":
            raise ValueError(f"no control named {broken!r}")
        self.path = path
        self.config = config
        self.m = module
        self.rows = None
        self.answered = 0

    def load(self, data) -> None:
        self.rows = self.m.Rows({
            name: self._lane(data.column(name).combine_chunks())
            for name in ("l_shipdate", "l_discount", "l_quantity",
                         "l_extendedprice")})

    @staticmethod
    def _lane(arr):
        if pa.types.is_date(arr.type):
            return arr.cast(pa.int32()).to_numpy()
        # decimal(15,2), no NULLs: the low word of each 16-byte value is its
        # hundredths, and every value of the table is below 2^31
        words = np.frombuffer(arr.buffers()[1], np.int64)
        return words[2 * arr.offset:2 * (arr.offset + len(arr)):2].astype(np.int32)

    def sql(self, text: str):
        year, discount, quantity = _Q6.search(text).groups()
        revenue = self.m.ref_q6_float_bounds(self.rows, int(year),
                                             Decimal(discount), int(quantity))
        self.answered += 1
        return pa.table({"revenue": pa.array([revenue], pa.decimal128(38, 4))})

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
        return FloatBoundsTable(path, config, cell.table_module(),
                                cell.traffic["control"])

    return runner.run_cell(workload, seed, seconds, False, scale=scale,
                           need_tpu=need_tpu, sut_factory=factory)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run lineitem_sf10.q6's control.")
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
