#!/usr/bin/env python3
"""Run one traced cell of the benchmark and say where a request's time went.

    python3 tools/bench_spans.py [--decode-route] --workload <cell> --seed <n> --seconds <s> --trace 1

The arguments and the result line are `benchmark/run.py`'s. Besides, on
standard error: the mean milliseconds a request of every span of the program
(and how many of them a request opened), and each MERGE's route. A builder's
instrument for PERF.md; nothing of the benchmark reads it.

``--decode-route`` makes `MergeIntoCommand._pairs_only_shape` read false, so
that a resident MERGE decodes the target as before PR 26: the same program
with one observable turned off, for the before-and-after of that route.
"""
import collections
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def report(run) -> None:
    done = run.done
    total, count = collections.Counter(), collections.Counter()
    routes = []
    for r in done:
        for s in r.spans:
            total[s["name"]] += s["duration_us"] or 0
            count[s["name"]] += 1
            if s["name"] == "delta.merge.router":
                routes.append(s["data"].get("route"))
    means = {n: [round(us / 1e3 / len(done), 3), round(count[n] / len(done), 2)]
             for n, us in total.most_common()}
    print("span means a request [ms, spans]:", json.dumps(means),
          file=sys.stderr)
    print("merge routes:", json.dumps(routes), file=sys.stderr)


def main() -> int:
    decode_route = "--decode-route" in sys.argv
    argv = [a for a in sys.argv[1:] if a != "--decode-route"]
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    bench_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_run)  # standard output is the result's now

    from benchmark.harness import runner

    if decode_route:
        from delta_tpu.commands.merge import MergeIntoCommand

        MergeIntoCommand._pairs_only_shape = lambda self, *a: False

    breakdown = runner._breakdown

    def with_span_means(run):
        report(run)
        return breakdown(run)

    runner._breakdown = with_span_means
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
