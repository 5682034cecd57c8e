"""Discovery: everything a cell needs, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of its
own under this directory (``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json``), each metric has a reader of its own
(``metrics/<metric>.py``), and each model family named by a configuration's
``model.family`` has a file of its own (``families/<family>.py``). Adding a
cell, a configuration, a mix, a metric or an architecture therefore means
adding files and entries, never editing one that exists.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
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
    root: Path = ROOT        # the checkout whose files the cell resolves to

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def family(self) -> ModuleType:
        """The file of the model family that the configuration names."""
        return load_family(self.config["model"]["family"], self.root)


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
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)


def _load_module(kind: str, name: str, root: Path) -> ModuleType:
    path = root / HERE.relative_to(ROOT) / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT) -> Callable[[Any], Any]:
    """``read(run)`` of ``metrics/<metric>.py``: it returns the metric's value
    for a finished run, or None where the run holds nothing to read."""
    return _load_module("metrics", metric, root).read


def load_family(family: str, root: Path = ROOT) -> ModuleType:
    """``families/<family>.py``: everything the harness knows of one model
    family's block. It holds

    - ``PROGRAM_FAMILY``: the program's ``ModelConfig.family`` that builds it;
    - ``layout(m)``: {parameter path: (shape, dtype name, init kind)} and
      ``init_stds(m, init)``: {init kind: std} of the kinds drawn from a
      normal (``weights.make_init``; the kind ``ones`` is ones);
    - ``forward(params, tokens, m, mode)``: the plain reference's logits,
      built from ``reference.mm`` (whose ``mode`` is the control's switch),
      and ``row_logits(params, tokens, rows, m, mode)``: the serving
      reference's logits at the compared rows, a layer at a time;
    - ``train_work(m, batch, seq)``: a training step's ``attn_call_flops``,
      ``attn_call_bytes`` and ``model_flops_per_token``;
      ``prefill_flops(m, n)`` and ``decode_work(m, n, first, end)``: what
      serving counts for a prompt and for a run of decode rows.
    """
    return _load_module("families", family, root)
