"""A cell and its files, found by the names in ``BENCHMARK.json``.

``BENCHMARK.json`` says which configuration and traffic a cell pairs and
which metrics it reports; each of those is a file of its own under
``benchmark/``, so a later PR adds a cell by adding files and entries and
edits nothing that is here.
"""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")


class CellError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


def _load(*parts: str) -> Dict[str, Any]:
    path = os.path.join(*parts)
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"{os.path.relpath(path, ROOT)} is missing") from None


@dataclass
class Metric:
    """One metric: its ``BENCHMARK.json`` entry and its file under
    ``benchmark/metrics/``, which names the reader and its parameters."""

    name: str
    unit: str
    reader: str
    params: Dict[str, Any]

    def read(self, run) -> Any:
        mod = importlib.import_module(f"benchmark.metrics.readers.{self.reader}")
        return mod.read(run, self.params)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    def table_module(self):
        """The configuration's generator and plain reference."""
        return importlib.import_module(
            f"benchmark.tables.{self.config['table_module']}")

    def traffic_kind(self):
        return importlib.import_module(
            f"benchmark.traffic.kinds.{self.traffic['kind']}")


def _metrics(bench: Dict[str, Any], section: str, cell: str) -> List[Metric]:
    """The section's metrics that this cell reports: those that list it, and
    ``setup_s``, which has no list and is every cell's. A per-layer entry
    always lists its cells (``benchmark/README.md``)."""
    out = []
    for m in bench[section]:
        if cell not in m.get("workloads", (cell,)):
            continue
        spec = _load(HERE, "metrics", m["name"] + ".json")
        out.append(Metric(m["name"], m["unit"], spec["reader"],
                          spec.get("params", {})))
    return out


def load_cell(name: str) -> Cell:
    bench = _load(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise CellError(f"BENCHMARK.json has no workload {name!r}; it has "
                        f"{[w['name'] for w in bench['workloads']]}")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_load(ROOT, cfg["file"]),
        traffic=_load(HERE, "traffic", entry["traffic"] + ".json"),
        end_to_end=_metrics(bench, "end_to_end", name),
        per_layer=_metrics(bench, "per_layer", name),
    )
