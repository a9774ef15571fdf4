"""Finds what a cell names, each in a file of its own, by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness reads them from ``configs/<config>.json`` and
``traffic/<traffic>.json``, the limits of its output check from
``checks/<cell>.json`` and every metric from ``metrics/<metric>.py``. The
configuration's ``step_kind`` names the rest: the program's configuration
built from the file's published keys, with the check of the structure the
step needs (``steps/<step_kind>.py``), the step's operation and byte
counts (``counts/<step_kind>.py``) and its plain reference
(``reference/<step_kind>.py``). Adding a configuration, a mix, a cell, a
metric or a step kind adds files and entries; it edits none of the files
that are there.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class CatalogError(LookupError):
    """A name that no file answers, or a file that breaks its schema."""


def _path(bench_dir: Path, kind: str, name: str, suffix: str) -> Path:
    if not NAME.match(name):
        raise CatalogError(f"{kind}: {name!r} is not a valid name")
    path = bench_dir / kind / f"{name}{suffix}"
    if not path.is_file():
        raise CatalogError(f"{kind}: no file {path} for {name!r}")
    return path


def load_json(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    with open(_path(bench_dir, kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str,
                bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = _path(bench_dir, kind, name, ".py")
    mod_name = "chipbench_" + kind + "_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One cell and everything it names, loaded from its files."""

    name: str
    chips: int
    config: Dict
    traffic: Dict
    #: Limits of the output check, or None where none is set yet.
    checks: Optional[Dict]
    end_to_end: List[Dict] = field(default_factory=list)
    per_layer: List[Dict] = field(default_factory=list)
    bench_dir: Path = BENCH_DIR

    def step(self) -> ModuleType:
        return load_module("steps", self.config["step_kind"], self.bench_dir)

    def counts(self) -> ModuleType:
        return load_module("counts", self.config["step_kind"], self.bench_dir)

    def reference(self) -> ModuleType:
        return load_module("reference", self.config["step_kind"],
                           self.bench_dir)

    def reader(self, metric: str) -> ModuleType:
        return load_module("metrics", metric, self.bench_dir)


def _metrics_of(entries: List[Dict], cell: str) -> List[Dict]:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = load_benchmark(root)
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise CatalogError(
            f"no workload {name!r}; choose from "
            f"{[w['name'] for w in bench['workloads']]}")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json("configs", w["config"], bench_dir),
        traffic=load_json("traffic", w["traffic"], bench_dir),
        checks=_optional_checks(name, bench_dir),
        end_to_end=_metrics_of(bench["end_to_end"], name),
        per_layer=_metrics_of(bench["per_layer"], name),
        bench_dir=bench_dir)


def _optional_checks(cell: str, bench_dir: Path) -> Optional[Dict]:
    try:
        return load_json("checks", cell, bench_dir)
    except CatalogError:
        return None
