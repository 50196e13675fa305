"""Milliseconds of device time a request spent in the XLA modules that
``module`` names (a pattern for the module's name) and, where ``operand`` is
given, one of whose operations matches it in its HLO text (see
``harness/trace.py``: the program's kernels share the name ``jit_kernel`` and
differ in their arguments' names): the sum of those modules' executions that
start in the traced window, over the requests that succeeded.

For work the program only enqueues: the span around a jitted call closes
when the program is dispatched, so ``span_mean_ms`` of it reads the dispatch
and not the device's time. Finds nothing, returns nothing.
"""


def read(run, params):
    if run.trace is None or not run.done:
        return None
    events = run.trace.module_events(params["module"], params.get("operand"))
    if not events:
        return None
    return sum(e.end - e.start for e in events) / 1e6 / len(run.done)
