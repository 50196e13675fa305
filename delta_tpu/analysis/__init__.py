"""Engine-wide static analysis — concurrency & invariant lints as a subsystem.

PRs 6-9 made the engine deeply concurrent: the group-commit leader, the
coalescing checkpoint daemon (``delta-ckpt-async``), the journal writer
(``delta-journal-writer``), the MERGE slab uploader and the device-probe
staging thread all share state with foreground commits — and PR 9's worst
bugs (blocking tail reads under the commit lock, stranded drained members
on BaseException) were found only by hand-profiling. This package makes
that checking structural: an AST engine over the whole ``delta_tpu``
package with pluggable passes, a shared finding/suppression model and a
checked-in baseline, run as one tier-1 test and by ``tools/analyze.py``.

Passes (see ``delta_tpu/analysis/passes/``):

================  ===========================================================
``lock-discipline``  per-class/module lock→state map from ``with <lock>:``
                     regions; cross-thread unguarded mutation, blocking calls
                     (LogStore IO, ``time.sleep``, ``Thread.join``,
                     ``Future.result``) inside held-lock regions, and
                     lock-acquisition-order cycles
``crash-safety``     ``except Exception`` handlers on paths reachable from
                     named fault points (``SimulatedCrash`` must pierce),
                     swallowed ``BaseException``/bare ``except``, tmp-file
                     writes without try/finally cleanup (the PR 5 orphan
                     class)
``config-registry``  every constant ``delta.tpu.*`` conf read must resolve to
                     the ``utils/config.py`` registry (typo'd keys silently
                     return defaults otherwise); registered keys never read
                     are dead
``pool-naming``      every ``ThreadPoolExecutor``/``Thread`` construction
                     carries a registered ``delta-*`` pool name so Perfetto
                     lanes and ``adopt_span_context`` propagation stay total
``telemetry-spans``  every command entry point opens a ``delta.dml.*``/
                     ``delta.utility.*`` span (migrated from
                     ``tests/test_telemetry.py``)
``metric-catalog``   every constant-name metric call site resolves to
                     ``obs/metric_names.py`` (migrated)
``metric-descriptions``  every cataloged metric carries a one-line # HELP
                     description, none stale (migrated)
================  ===========================================================

Suppression: ``# delta-lint: ignore[rule]`` on the flagged line (or a
standalone comment line directly above it), with an optional justification
after ``--``. Repo-wide accepted debt lives in ``tools/analyze_baseline.json``
(``tools/analyze.py --update-baseline``). Pure stdlib — no runtime imports
of the engine modules it inspects.
"""
from __future__ import annotations

from delta_tpu.analysis.core import (AnalysisContext, AnalysisPass,
                                     AnalysisReport, Finding, analyze_repo,
                                     apply_suppressions, default_baseline_path,
                                     load_baseline, repo_root, run_passes)
from delta_tpu.analysis.passes import all_passes

__all__ = [
    "AnalysisContext", "AnalysisPass", "AnalysisReport", "Finding",
    "all_passes", "analyze_repo", "apply_suppressions",
    "default_baseline_path", "load_baseline", "publish_metrics",
    "repo_root", "run_passes",
]


def publish_metrics(report: AnalysisReport) -> None:
    """Publish per-rule finding counts as the cataloged ``analysis.findings``
    gauge (label: rule) so ``/metrics`` scrapes carry them."""
    from delta_tpu.utils import telemetry

    counts = report.counts()
    telemetry.set_gauge("analysis.findings", sum(counts.values()),
                        rule="total")
    for rule, n in sorted(counts.items()):
        telemetry.set_gauge("analysis.findings", n, rule=rule)
