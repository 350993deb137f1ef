"""Finds what a cell needs by name, so that a configuration, a traffic mix,
a cell, a driver or a metric is added by adding a file:

- ``BENCHMARK.json`` at the root: the cell's entry (its config and traffic
  names, chips) and the metrics it reports, with their units;
- ``chipbench/configs/<config>.json``: sizes and deployment, and in
  ``"family"`` the name of its architecture's module;
- ``chipbench/families/<family>.py``: sizes, the program's configuration,
  weights, reference, operation counts and kernels of one architecture;
- ``chipbench/traffic/<traffic>.json``: the generator's parameters;
- ``chipbench/workloads/<cell>.json``: driver, engine settings, check limits;
- ``chipbench/drivers/<driver>.py``: a ``Driver`` class;
- ``chipbench/metrics/<metric>.py``: a ``read(run)`` function.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]
PACKAGE = "chipbench"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    driver: type
    metrics: List[Metric]
    family: ModuleType


def load_family(config_file: Path) -> ModuleType:
    """The module in ``families/`` beside ``configs/`` that the configuration
    file names in ``"family"``; a file without the key, or naming no module,
    is refused."""
    name = _json(config_file).get("family")
    path = Path(config_file).parents[1] / "families" / f"{name}.py"
    if not isinstance(name, str) or not path.is_file():
        raise ValueError(f"{config_file}: 'family' must name a module in "
                         f"{PACKAGE}/families/, not {name!r}")
    return load_module(path, f"{PACKAGE}_family_{name}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, trace: bool, root: Path = ROOT) -> Cell:
    """The cell ``name`` with the metrics that a run of it reports: its
    end-to-end metrics, or with ``trace`` its per-layer metrics."""
    root = Path(root)
    bench = _json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    pkg = root / PACKAGE
    workload = _json(pkg / "workloads" / f"{name}.json")
    driver = load_module(pkg / "drivers" / f"{workload['driver']}.py",
                         f"{PACKAGE}_driver_{workload['driver']}").Driver
    specs = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [Metric(m["name"], m["unit"],
                      load_module(pkg / "metrics" / f"{m['name']}.py",
                                  f"{PACKAGE}_metric_{m['name']}").read)
               for m in specs if _reports(m, name)]
    config_file = pkg / "configs" / f"{entry['config']}.json"
    return Cell(name=name, chips=int(entry["chips"]), config=_json(config_file),
                traffic=_json(pkg / "traffic" / f"{entry['traffic']}.json"),
                workload=workload, driver=driver, metrics=metrics,
                family=load_family(config_file))


def peaks_for(kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The published peaks of a device kind; an unknown kind is an error."""
    peaks = _json(Path(root) / PACKAGE / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in {PACKAGE}/peaks.json")
    return peaks[kind]
