"""Resolve a cell of ``BENCHMARK.json`` into its files, by name.

A cell names a configuration and a traffic mix; each is one JSON file
(``configs/<file>``, ``traffic/<mix>.json``). Each per-layer metric is
one module ``metrics/<metric>.py`` with a ``read(run)`` function. Adding
a cell, a configuration, a mix or a metric is adding files and entries:
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: str = ""
    layer: str = ""
    workloads: List[str] = field(default_factory=list)
    read: Callable[[Any], Any] = None


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_benchmark(path: Path = BENCHMARK) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, metrics_dir: Path = HERE / "metrics"):
    """The ``read`` function of ``metrics/<name>.py`` (names may hold dots,
    so the module is loaded from its file, not imported by name)."""
    path = metrics_dir / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict[str, Any], cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def resolve(cell_name: str, bench: Dict[str, Any] = None,
            root: Path = ROOT, bench_dir: Path = HERE) -> Cell:
    """``root`` is the checkout (configuration files are named relative to
    it); ``bench_dir`` holds ``traffic/`` and ``metrics/``."""
    bench = bench if bench is not None else load_benchmark(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"unknown workload {cell_name!r}; known: "
                       f"{sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = load_json(root / centry["file"])
    config.setdefault("name", centry["name"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    traffic.setdefault("name", w["traffic"])
    e2e = [Metric(**{k: m[k] for k in ("name", "unit", "better", "source")},
                  workloads=m.get("workloads", []))
           for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    names = {m.name for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if not _applies(m, cell_name, names):
            continue
        per_layer.append(Metric(
            name=m["name"], unit=m["unit"], better=m["better"],
            source=m["source"], moves=m["moves"], layer=m["layer"],
            workloads=m.get("workloads", []),
            read=load_reader(m["name"], bench_dir / "metrics")))
    return Cell(name=cell_name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)
