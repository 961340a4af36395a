"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration, whose file is
given in ``configs``, and a traffic mix, read from ``traffic/<mix>.json``.
Every metric ``<name>`` is read by ``metrics/<name>.py``, whose
``read(run)`` returns the number or None.  A new cell, mix or metric is a
new file and a new entry: nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_manifest(path: Optional[Path] = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


def load_reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict  # the configuration file
    traffic: dict  # the traffic mix, with the cell's server settings
    end_to_end: List[dict]
    per_layer: List[dict]

    def metrics(self, traced: bool) -> List[Metric]:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer
        ones; a metric with a ``workloads`` list applies to those cells."""
        entries = self.per_layer if traced else self.end_to_end
        return [Metric(m["name"], m["unit"], load_reader(m["name"])) for m in entries
                if self.name in m.get("workloads", [self.name])]


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_manifest()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name=name, chips=int(w["chips"]), spec=_json(ROOT / conf["file"]),
                traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
