"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the ``file`` of its ``configs``
entry; the traffic mix is ``benchmark/traffic/<traffic>.json``, whose
``generator`` names ``benchmark/generators/<generator>.py``; each
metric is read by ``benchmark/metrics/<metric>.py``.  Adding a cell, a
mix or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]       # the metrics this cell reports
    per_layer: List[Dict]


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: pathlib.Path = ROOT /
              "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((spec_path.parent /
                         configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" /
                          f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def generator_class(traffic: Dict):
    name = traffic["generator"]
    mod = _load_module(BENCH / "generators" / f"{name}.py",
                       f"benchmark_generator_{name}")
    return mod.Generator


def metric_reader(name: str):
    mod = _load_module(BENCH / "metrics" / f"{name}.py",
                       f"benchmark_metric_{name.replace('.', '_')}")
    return mod.read
