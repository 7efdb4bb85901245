"""Resolve a cell by name: `BENCHMARK.json` -> configuration file ->
traffic mix -> generator kind, plus the metrics declared for it.

Everything that belongs to one configuration, one mix or one per-layer
metric lives in a file of its own and is found here by the name
`BENCHMARK.json` gives it, so a later PR adds files and entries and
edits nothing that is there. No JAX import: resolving a cell must work
(and fail loudly) before any backend is touched.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict           # benchmarks/configs/<config>.json
    traffic: dict          # benchmarks/traffic/<traffic>.json
    end_to_end: tuple      # BENCHMARK.json entries reported in this cell
    per_layer: tuple


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def applies(metric: dict, cell_name: str) -> bool:
    """A metric with no `workloads` key is reported in every cell."""
    return cell_name in metric.get("workloads", (cell_name,))


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(
        root, "benchmarks", "traffic", w["traffic"] + ".json"))
    end_to_end = tuple(m for m in bench["end_to_end"]
                       if applies(m, name))
    e2e_names = {m["name"] for m in end_to_end}
    # a per-layer metric is reported only where the metric it moves is
    per_layer = tuple(m for m in bench["per_layer"]
                      if applies(m, name) and m["moves"] in e2e_names)
    return Cell(name=name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic=traffic,
                end_to_end=end_to_end, per_layer=per_layer)


def _load_file(kind: str, directory: str, stem: str) -> ModuleType:
    path = os.path.join(BENCH_DIR, directory, stem + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} {stem!r}: expected {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{directory}.{stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def traffic_kind(cell: Cell) -> ModuleType:
    """The generator module a mix's `kind` names; it exposes
    `run(rt) -> facts`."""
    return _load_file("traffic kind", "traffic_kinds",
                      cell.traffic["kind"])


def layer_metric_reader(metric_name: str) -> ModuleType:
    """The reader of one per-layer metric: the file named after it,
    exposing `read(facts) -> float | None`."""
    return _load_file("per-layer metric reader", "layer_metrics",
                      metric_name)
