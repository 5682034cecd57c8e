"""Discovery: everything a cell needs, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of its
own under this directory (``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json``), and each metric has a reader of its own
(``metrics/<metric>.py``). Adding a cell, a configuration, a mix or a metric
therefore means adding files and entries, never editing one that exists.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it refers to."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell named ``workload``, resolved against ``root``'s files."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    here = root / HERE.relative_to(ROOT)
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(here / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_reader(metric: str, root: Path = ROOT) -> Callable[[Any], Any]:
    """``read(run)`` of ``metrics/<metric>.py``: it returns the metric's value
    for a finished run, or None where the run holds nothing to read."""
    path = root / HERE.relative_to(ROOT) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
