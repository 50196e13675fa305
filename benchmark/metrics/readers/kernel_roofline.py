"""A kernel's share of the memory roofline: the least bytes its calls had
to move (each request's ``least_bytes``, from ``benchmark/metrics/bytes.py``)
over the chip's bandwidth, over the device time of its executions in the
traced window.

``module`` is a pattern for the XLA module's name and ``operand`` one for the
HLO text of the operations inside it (see ``harness/trace.py``): the program's
kernels share the name ``jit_kernel`` and differ in their arguments' names.
Which roofline: the memory one, of the least work. ``bytes.py`` counts what
any algorithm for the job has to move (every key read once, the answer
written once); ``bytes / bandwidth`` is the least time any kernel can take,
so the share cannot honestly pass 100%. It is a distance from that floor and
says nothing of what holds the program's kernel: the resident probe as
written compares each slab block with a window of the source
(``ops/key_cache.py``, about capacity x W int64 compares) and is held by the
vector unit, not by memory, which is why its share reads hundredths of a
percent: the gap is the algorithm's. Finds nothing, returns nothing.
"""
from benchmark.harness.preflight import peak_table


def read(run, params):
    if run.trace is None:
        return None
    events = run.trace.module_events(params["module"], params.get("operand"))
    busy_ns = sum(e.end - e.start for e in events)
    least = sum(r.info.get("least_bytes", 0) for r in run.done)
    if not busy_ns or not least:
        return None
    peak = peak_table()[run.device_kind]["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / (busy_ns / 1e9)
