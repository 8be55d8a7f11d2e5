"""Finds a cell's files by name.

``BENCHMARK.json`` at the root of the checkout lists the cells
(``workloads``), the model configurations and the metrics. Everything that
belongs to one of them is a file of its own under this directory, found by
its name alone, so that a new cell, configuration, traffic mix or metric is
added as a file and an entry, with no edit to an existing file:

- configuration ``<c>``: the JSON file named by its ``file`` entry;
- its architecture ``<a>``, the configuration's ``"arch"``:
  ``arch/<a>.py``, which gives the ``Dims`` the cost functions read (``L``,
  ``H``, ``KH``, ``D``, ``V``, ``matmul_params()``), the program's
  ``model_config(config, dims)`` and ``program_params(dims, seed)``, and
  the plain reference's parts that ``reference.compare`` runs:
  ``layer_keys(words, dims)``, ``layer_weights(key, m=)``, ``layer(h, w,
  m=, quant=)``, ``embed(words, toks, m=)`` and ``head(words, h, m=,
  quant=)``;
- traffic mix ``<t>``: ``traffic/<t>.json``, and its generator, the mix's
  ``arrivals.kind`` ``<k>``: ``traffic/kinds/<k>.py``, which gives
  ``generate(mix, seed, seconds, vocab)`` and ``max_context(mix)``;
- metric ``<m>``: ``metrics/<m>.py``, or, where no such file exists,
  ``metrics/<base>.py`` with ``<base>`` the part of ``<m>`` before its
  first ``.`` (``idle_share.chat`` and ``idle_share.batch`` share a reader).
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path: Path, kind: str):
    """The module of ``path``, executed once per process and path."""
    if not path.is_file():
        raise ValueError(f"unknown {kind} {path.stem!r}: no file {path}")
    digest = hashlib.sha1(str(path.resolve()).encode()).hexdigest()[:12]
    name = re.sub(r"\W", "_", f"chipbench_{kind}_{path.stem}_{digest}")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod     # a dataclass looks its module up
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


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
    arch: object           # the configuration's architecture module
    kind: object           # the traffic mix's generator module


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

    def arch(self, config: dict):
        """The architecture module that a configuration names."""
        return load(self.bench_dir / "arch" / f"{config['arch']}.py",
                    "arch")

    def traffic_kind(self, mix: dict):
        """The generator module of a traffic mix's arrivals."""
        return load(self.bench_dir / "traffic" / "kinds"
                    / f"{mix['arrivals']['kind']}.py", "arrivals")

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

        config = json.loads(self.config_path(w["config"]).read_text())
        mix = json.loads(self.traffic_path(w["traffic"]).read_text())
        return Cell(
            name=workload, config=config, config_name=w["config"],
            traffic=mix, traffic_name=w["traffic"], chips=int(w["chips"]),
            end_to_end=mine(self.bench["end_to_end"]),
            per_layer=mine(self.bench["per_layer"]),
            arch=self.arch(config), kind=self.traffic_kind(mix))

    def reader(self, metric: str):
        """The ``read(rec)`` function of a metric's reader file."""
        return load(self.metric_path(metric), "metric").read
