"""Finds what a cell needs by the names in BENCHMARK.json: its configuration
file, its traffic file (`traffic/<mix>.json`) and the reader of each
per-layer metric (`metrics/<metric>.py`). A later PR adds a cell, a
configuration, a mix or a metric by adding files and entries only."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Optional

#: The directory that holds run.py, configs/, traffic/ and metrics/.
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The checkout's root: BENCHMARK.json and the paths in it are relative to it.
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[list] = None
    read: Optional[Callable] = None  # per-layer metrics: reader(Reading)

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: list = field(default_factory=list)  # Metric, this cell's
    per_layer: list = field(default_factory=list)  # Metric, this cell's


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from None


def load_reader(path: str) -> Callable:
    """The `read` function of a per-layer metric's reader file."""
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load metric reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"{path} defines no read(reading)")
    return module.read


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its configuration,
    traffic and metrics (per-layer readers loaded from
    `<root>/benchmark/metrics/<metric>.py`)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(
        os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json")
    )

    def metrics(key: str, reader: bool) -> list:
        out = []
        for m in bench.get(key, []):
            metric = Metric(m["name"], m["unit"], m["better"], m["source"],
                            m.get("workloads"))
            if not metric.applies_to(name):
                continue
            if reader:
                metric.read = load_reader(os.path.join(
                    root, "benchmark", "metrics", m["name"] + ".py"))
            out.append(metric)
        return out

    return Cell(name, int(w["chips"]), config, traffic,
                metrics("end_to_end", False), metrics("per_layer", True))
