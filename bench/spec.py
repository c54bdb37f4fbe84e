"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them.

* a configuration: the JSON file its entry names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric, end-to-end or per-layer: ``bench/metrics/<name>.py``, a
  module with ``read(run) -> float | None``.

A cell, traffic mix or metric that is added as files and entries is
found with no edit here.  An unknown name is refused.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

__all__ = ["ROOT", "Cell", "Metric", "load_cell", "load_benchmark"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """A name in the request or in ``BENCHMARK.json`` resolves to nothing."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    read: object  # callable(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple
    per_layer: tuple


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(entries, cell: str) -> tuple:
    return tuple(Metric(m["name"], m["unit"], m["better"], _reader(m["name"]))
                 for m in entries if cell in m.get("workloads", (cell,)))


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {w['config']!r}")
    cfg_path = root / configs[w["config"]]["file"]
    traffic_path = BENCH / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, traffic_path):
        if not p.is_file():
            raise SpecError(f"workload {name!r}: no {p}")
    return Cell(name=name, chips=int(w["chips"]),
                config=json.loads(cfg_path.read_text()),
                traffic=json.loads(traffic_path.read_text()),
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))
