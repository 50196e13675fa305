#!/usr/bin/env python3
"""What one span of the program costs, in microseconds, on this host.

    python3 tools/span_cost.py [--spans 20000]

Times ``telemetry.record_operation`` as a child span under one root: with
``delta.tpu.telemetry.enabled`` off, on with no profiler session open (the
production state: the ``TraceAnnotation`` each span opens is a flag test),
and on under an open ``jax.profiler`` session (each span is then also a host
event of the trace); and one ``bump_counter``. Prints one JSON line. The
numbers in PERF.md's Tracing section come from this script on the chip's
host (``chiprun -- python3 tools/span_cost.py``).
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def per_call_us(fn, n: int) -> float:
    fn(max(n // 10, 1))  # warm
    t0 = time.perf_counter_ns()
    fn(n)
    return (time.perf_counter_ns() - t0) / n / 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=20000)
    n = ap.parse_args().spans

    import jax

    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf

    def spans(k: int) -> None:
        with telemetry.record_operation("delta.test.root"):
            for _ in range(k):
                with telemetry.record_operation("delta.test.span"):
                    pass

    def counters(k: int) -> None:
        for _ in range(k):
            telemetry.bump_counter("link.h2d.count")

    out = {"spans": n, "platform": jax.devices()[0].platform}
    with conf.set_temporarily(**{"delta.tpu.telemetry.enabled": False}):
        out["span_off_us"] = per_call_us(spans, n)
    out["span_on_us"] = per_call_us(spans, n)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["span_profiled_us"] = per_call_us(spans, n)
        finally:
            jax.profiler.stop_trace()
    out["bump_counter_us"] = per_call_us(counters, n)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
