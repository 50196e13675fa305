"""Pool-naming pass: every thread and pool carries a registered lane name.

The Chrome-trace export labels Perfetto lanes from thread names
(``telemetry.export_chrome_trace`` thread_name metadata), and
``adopt_span_context`` propagation audits assume worker provenance is
readable from the thread name. An anonymous ``Thread()`` or
``ThreadPoolExecutor()`` shows up as ``Thread-N`` — an unattributable
lane. Rule:

``pool-name``
    Every ``threading.Thread(...)`` construction passes ``name=`` and every
    ``ThreadPoolExecutor(...)`` passes ``thread_name_prefix=``, as a string
    constant present in :data:`REGISTERED_POOLS` below. The registry IS
    this module — adding a pool means adding its name here, which is
    exactly the reviewable event the pass exists to force.
"""
from __future__ import annotations

import ast
from typing import List, Optional

from delta_tpu.analysis.core import AnalysisContext, AnalysisPass, Finding
from delta_tpu.analysis.modgraph import terminal_name

__all__ = ["PoolNamingPass", "REGISTERED_POOLS"]

#: Every engine thread/pool lane name. Perfetto lanes and the thread-name
#: metadata rows in export_chrome_trace render these verbatim.
REGISTERED_POOLS = frozenset({
    # pools (ThreadPoolExecutor thread_name_prefix)
    "delta-parquet-read",         # exec/parquet.py decode pool
    "delta-parquet-write",        # exec/write.py write pool
    "delta-scan-decode",          # exec/scan.py scan decode pool
    "delta-ckpt-part",            # log/checkpoints.py part writers
    "delta-ckpt-decode",          # log/columnar.py part decoders
    "delta-vacuum-list",          # commands/vacuum.py partition listing
    "delta-vacuum-delete",        # commands/vacuum.py parallel delete
    "delta-replay-prep",          # replay/shadow.py candidate clone prep
    "delta-dist-exec",            # parallel/executor.py sharded work items
    "delta-merge-dv",             # commands/merge.py vectors beside the write
    # dedicated threads (threading.Thread name)
    "delta-dist-supervisor",      # parallel/executor.py heartbeat watchdog
    "delta-ckpt-async",           # log/checkpointer.py coalescing daemon
    "delta-journal-writer",       # obs/journal.py writer daemon
    "delta-state-update",         # log/deltalog.py async snapshot refresh
    "delta-obs-server",           # obs/server.py HTTP endpoint
    "delta-merge-slab-upload",    # commands/merge.py slab uploader
    "delta-merge-device-probe",   # ops/key_cache.py probe staging thread
    "delta-merge-keys-build",     # commands/merge.py background key build
    "delta-join-upload",          # ops/join_kernel.py async kernel launch
    "delta-object-store-http",    # storage/object_store_emulator.py server
    "delta-autopilot",            # autopilot/daemon.py maintenance daemon
    "delta-obs-scraper",          # obs/timeseries.py metrics scraper daemon
})

_CTOR_KW = {
    "Thread": "name",
    "ThreadPoolExecutor": "thread_name_prefix",
}


def _name_kwarg(call: ast.Call, kwarg: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == kwarg:
            return kw.value
    return None


class PoolNamingPass(AnalysisPass):
    name = "pool-naming"
    description = ("Thread/ThreadPoolExecutor constructions carry a "
                   "registered delta-* lane name")
    rules = ("pool-name",)

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        out: List[Finding] = []
        for sf in ctx.files:
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                ctor = terminal_name(node.func)
                kwarg = _CTOR_KW.get(ctor or "")
                if kwarg is None:
                    continue
                value = _name_kwarg(node, kwarg)
                if value is None:
                    out.append(Finding(
                        "pool-name", sf.rel, node.lineno,
                        f"{ctor} constructed without {kwarg}= — the lane "
                        f"is unattributable in Perfetto; pass a name "
                        f"registered in analysis/passes/pool_naming.py"))
                    continue
                if not (isinstance(value, ast.Constant)
                        and isinstance(value.value, str)):
                    out.append(Finding(
                        "pool-name", sf.rel, node.lineno,
                        f"{ctor} {kwarg}= must be a string constant so the "
                        f"lane registry stays statically checkable"))
                    continue
                if value.value not in REGISTERED_POOLS:
                    out.append(Finding(
                        "pool-name", sf.rel, node.lineno,
                        f"{ctor} lane name '{value.value}' is not in the "
                        f"registered pool registry "
                        f"(analysis/passes/pool_naming.py)"))
        return out
