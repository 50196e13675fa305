#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; everything
else goes to standard error. Without the chips the cell asks for, or when
the result does not meet the driver's contract (a traced window in which no
operation ran on the device, a metric missing), the exit code is not 0 and
no result is printed. See ``benchmark/README.md``.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()
# Nothing but the result may reach standard output: libraries, the profiler
# and the engine's threads write where they like. So descriptor 1 becomes
# standard error for the whole run, and the result goes to a copy of the
# real standard output that nothing else knows.
RESULT_FD = os.dup(1)
os.dup2(2, 1)
sys.stdout = sys.stderr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import argparse
    import traceback

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import lastline, runner
    from benchmark.harness.cell import load_cell
    from benchmark.harness.preflight import NoChip, log

    try:
        cell = load_cell(args.workload)
        line = runner.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS)
        lastline.emit(line, runner.expected_metrics(cell, bool(args.trace)),
                      bool(args.trace), cell.chips, RESULT_FD)
    except NoChip as e:
        log(f"benchmark: {e}; refusing to run")
        return 3
    except lastline.LastLineError as e:
        log(f"benchmark: the result is not fit to print: {e}")
        return 4
    except Exception:  # noqa: BLE001 — exit boundary: report and fail
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
