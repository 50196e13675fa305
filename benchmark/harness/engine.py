"""The system under test, as the traffic sees it: one table of the engine
behind ``load``, ``merge``, ``scan`` and ``read_all``. Everything goes through
the engine's public API; the only other things taken from the program are its
spans, its counters and the MERGE's own phase times.

A control (``benchmark/controls.py``) offers the same four calls over the
plain reference, with one guarantee broken.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence


class EngineTable:
    def __init__(self, path: str, config: Dict[str, Any]):
        from delta_tpu.utils.config import conf

        self.path = path
        self.config = config
        self.table = None
        # the configuration's engine settings hold for the whole process
        for key, value in config["engine_confs"].items():
            conf.set(key, value)

    # -- the four calls ----------------------------------------------------

    def load(self, data) -> None:
        from delta_tpu import DeltaTable
        from delta_tpu.utils.config import conf

        with conf.set_temporarily(**self.config["layout"]["write_confs"]):
            self.table = DeltaTable.create(
                self.path, data=data,
                configuration=dict(self.config["table_properties"]))

    def merge(self, source, condition: str) -> Dict[str, Any]:
        """``MERGE INTO t USING s ON condition WHEN MATCHED UPDATE *
        WHEN NOT MATCHED INSERT *``; returns the command's metrics."""
        return (self.table.alias("t")
                .merge(source, condition, source_alias="s")
                .when_matched_update_all()
                .when_not_matched_insert_all()
                .execute())

    def scan(self, filters: Sequence[str], columns: Sequence[str]):
        return self.table.to_arrow(filters=list(filters), columns=list(columns))

    def read_all(self, columns: Optional[Sequence[str]] = None):
        """Every row of the table through a handle that has seen nothing:
        the log is replayed and every file decoded anew."""
        from delta_tpu import DeltaLog, DeltaTable

        DeltaLog.clear_cache()
        return DeltaTable.for_path(self.path).to_arrow(
            columns=None if columns is None else list(columns))

    def versions(self) -> List[Dict[str, Any]]:
        """The history, oldest first: ``version`` and ``operation``."""
        from delta_tpu import DeltaLog, DeltaTable

        DeltaLog.clear_cache()
        hist = DeltaTable.for_path(self.path).history()
        return [{"version": h["version"], "operation": h.get("operation")}
                for h in reversed(hist)]

    # -- what the per-layer metrics read -------------------------------------

    def counters(self) -> Dict[str, int]:
        from delta_tpu.utils import telemetry

        return dict(telemetry.counters())

    def drain_spans(self) -> List[Dict[str, Any]]:
        """The spans and events recorded since the last drain: name, start
        and length on the ``perf_counter`` clock in microseconds, thread,
        data."""
        from delta_tpu.utils import telemetry

        events = telemetry.recent_events()
        telemetry.clear_events()
        return [{"name": e.op_type, "start_us": e.start_us,
                 "duration_us": e.duration_us, "thread": e.thread_id,
                 "data": e.data}
                for e in events]

    def merge_phases(self) -> Dict[str, float]:
        """``phase_ms`` of the MERGE that just ran, as its router audit
        keeps them (key_decode_ms, decode_ms, join_ms)."""
        from delta_tpu.obs import router_audit

        audit = router_audit.last_audit()
        if audit is None or audit.op != "merge.join":
            return {}
        return dict(audit.extra.get("phases", {}))

    def merge_decision(self) -> Optional[str]:
        from delta_tpu.utils import telemetry

        events = telemetry.recent_events("delta.merge.router")
        return events[-1].data.get("decision") if events else None


def table_bytes(path: str) -> int:
    """Bytes under the table's directory: data files, deletion vectors and
    the log."""
    total = 0
    for root, _dirs, names in os.walk(path):
        for name in names:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except FileNotFoundError:  # a temporary file of a commit
                pass
    return total
