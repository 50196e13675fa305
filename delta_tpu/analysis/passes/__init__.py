"""Pass registry. ``all_passes()`` is the one list the CLI and the tier-1
test share — a new pass registers here and nowhere
else."""
from __future__ import annotations

from typing import List

from delta_tpu.analysis.core import AnalysisPass
from delta_tpu.analysis.passes.config_registry import ConfigRegistryPass
from delta_tpu.analysis.passes.crash_safety import CrashSafetyPass
from delta_tpu.analysis.passes.lock_discipline import LockDisciplinePass
from delta_tpu.analysis.passes.metric_catalog import MetricCatalogPass
from delta_tpu.analysis.passes.metric_descriptions import \
    MetricDescriptionsPass
from delta_tpu.analysis.passes.pool_naming import PoolNamingPass
from delta_tpu.analysis.passes.telemetry_spans import TelemetrySpansPass

__all__ = ["all_passes"]


def all_passes() -> List[AnalysisPass]:
    return [
        LockDisciplinePass(),
        CrashSafetyPass(),
        ConfigRegistryPass(),
        PoolNamingPass(),
        TelemetrySpansPass(),
        MetricCatalogPass(),
        MetricDescriptionsPass(),
    ]
