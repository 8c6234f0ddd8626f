"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name that ``BENCHMARK.json``
gives it:

  configs/<config>.json      sizes as run, with the driver that serves them
  traffic/<traffic>.json     parameters of the one arrival generator
  metrics/<metric>.py        a reader: ``read(run) -> float | None``
  drivers/<driver>.py        ``run(ctx)`` for one entry point
  limits/<config>.json       the limits of the output check
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one Python file by path (metric names hold dots, so these
    files are not importable as modules by name)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]  # the configuration file's content
    traffic_name: str
    traffic: Dict[str, Any]  # the traffic file's content
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports, trace 0
    per_layer: List[Dict[str, Any]]  # metrics this cell reports, trace 1

    def driver(self):
        return load_module(
            os.path.join(BENCH_DIR, "drivers", f"{self.config['driver']}.py"),
            f"bench_driver_{self.config['driver']}")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = cfgs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(os.path.join(ROOT, cfg_entry["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       f"{w['traffic']}.json")),
        limits=load_json(os.path.join(BENCH_DIR, "limits",
                                      f"{w['config']}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str):
    """The reader of per-layer metric ``name``."""
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))
