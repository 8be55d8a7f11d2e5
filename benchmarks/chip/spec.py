"""Finds a cell's files by name.

``BENCHMARK.json`` at the root of the checkout lists the cells
(``workloads``), the model configurations and the metrics. Everything that
belongs to one of them is a file of its own under this directory, found by
its name alone, so that a new cell, configuration, traffic mix or metric is
added as a file and an entry, with no edit to an existing file:

- configuration ``<c>``: the JSON file named by its ``file`` entry;
- traffic mix ``<t>``: ``traffic/<t>.json``;
- metric ``<m>``: ``metrics/<m>.py``, or, where no such file exists,
  ``metrics/<base>.py`` with ``<base>`` the part of ``<m>`` before its
  first ``.`` (``idle_share.chat`` and ``idle_share.batch`` share a reader).
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass
class Cell:
    name: str
    config: dict           # the configuration file's contents
    config_name: str
    traffic: dict          # the traffic file's contents
    traffic_name: str
    chips: int
    end_to_end: list       # metric entries that this cell reports
    per_layer: list


class Spec:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench_dir = self.root / self.bench["paths"][0]

    def config_path(self, name: str) -> Path:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return self.root / c["file"]
        raise KeyError(f"no configuration named {name!r}")

    def traffic_path(self, name: str) -> Path:
        return self.bench_dir / "traffic" / f"{name}.json"

    def metric_path(self, name: str) -> Path:
        exact = self.bench_dir / "metrics" / f"{name}.py"
        if exact.exists():
            return exact
        return self.bench_dir / "metrics" / f"{name.split('.')[0]}.py"

    def cell(self, workload: str) -> Cell:
        for w in self.bench["workloads"]:
            if w["name"] == workload:
                break
        else:
            raise KeyError(f"no workload named {workload!r}")

        def mine(metrics):
            return [m for m in metrics
                    if workload in m.get("workloads", [workload])]

        return Cell(
            name=workload,
            config=json.loads(self.config_path(w["config"]).read_text()),
            config_name=w["config"],
            traffic=json.loads(self.traffic_path(w["traffic"]).read_text()),
            traffic_name=w["traffic"], chips=int(w["chips"]),
            end_to_end=mine(self.bench["end_to_end"]),
            per_layer=mine(self.bench["per_layer"]))

    def reader(self, metric: str):
        """The ``read(rec)`` function of a metric's reader file."""
        path = self.metric_path(metric)
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
