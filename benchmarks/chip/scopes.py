"""Device time by program scope, and idle gaps by host span, from a profile.

The program names its own work (``repro.launch.tracing``): each device
operation carries the ``jax.named_scope`` names around it in its ``op_name``
metadata (``jit(train_step)/jvp(layers)/while/body/attn/dot_general``; a
backward operation's names sit inside ``transpose(...)``), and the host's
work runs under ``repro.*`` annotations carrying request ids. This module
reads both next to what ``trace.py`` reads, on the same clock, and adds to
what ``trace.reduce`` gives:

- ``scope_in_module_s``: device self seconds per program (jit name), per
  scope (the innermost of the program's scopes around the operation, or
  ``unscoped``), per direction: ``fwd``, ``bwd`` (inside JAX's
  ``transpose(...)``), or ``remat`` (a forward operation rerun in the
  backward pass, under ``rematted_computation``);
- ``device_scopes``: the same as ``[module, scope, direction, seconds]``
  rows, longest first;
- ``op_in_scope_s``: the same per operation kind (``trace.op_name``), per
  program and ``<scope>/<direction>``;
- ``idle_gaps_by_span``: each idle gap of the window named by the innermost
  host annotation of either prefix (``bench.`` or ``repro.``) around its
  middle, longest first.

The trace events of TPU operations hold no name stack (on a TPU v5 lite
under JAX 0.9 their stats are device offsets and durations only), so an
operation's stack is found by its name in the optimized HLO text that XLA
writes for each program with ``--xla_dump_to`` (:func:`hlo_names`). An
operation that no dumped program names, or whose program has no scopes,
reads as ``unscoped``, and the readers (:func:`ms_per_execution`) then
return None.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.chip import trace as trace_lib

try:
    from repro.launch.tracing import SCOPES, SPAN_PREFIX
except ImportError:                 # a program that names nothing
    SCOPES, SPAN_PREFIX = (), "repro."

Event = trace_lib.Event
UNSCOPED = "unscoped"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"')


def instruction(text: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def hlo_names(hlo_dir: str) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: op_name}} from the optimized HLO text of each
    program that XLA compiled (``--xla_dump_to=<hlo_dir>``). A program name
    compiled twice keeps the names of both."""
    out: Dict[str, Dict[str, str]] = defaultdict(dict)
    for path in sorted(glob.glob(os.path.join(
            hlo_dir, "*after_optimizations.txt"))):
        with open(path) as f:
            head = f.readline()
            module = head.split()[1].rstrip(",") if head.startswith(
                "HloModule") else ""
            for line in f:
                m = _INSTR.match(line)
                if m:
                    out[module][m.group(1)] = m.group(2)
    return dict(out)


def scope_of(stack: str) -> Tuple[str, str]:
    """(scope, direction) of a name stack: the innermost of the program's
    scopes in it (``unscoped`` where none is), and ``remat`` where it lies
    under ``rematted_computation``, else ``bwd`` where any part lies inside
    ``transpose(...)``, else ``fwd``. A fused operation's stack may join
    several with ``;``; the first names it."""
    first = stack.split(";", 1)[0]
    scope = UNSCOPED
    for part in first.split("/"):
        name = part
        while "(" in name:                       # transpose(jvp(attn)) -> attn
            name = name[name.index("(") + 1:].rstrip(")")
        if name in SCOPES:
            scope = name
    way = ("remat" if "rematted_computation" in first
           else "bwd" if "transpose(" in first else "fwd")
    return scope, way


def program_spans(path: str) -> List[Event]:
    """The program's ``repro.*`` host annotations in one .xplane.pb, which
    ``trace.load_xplane`` leaves out."""
    from jax.profiler import ProfileData  # noqa: PLC0415 (chip-side only)

    return [(ev.name, int(ev.start_ns), int(ev.end_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events if ev.name.startswith(SPAN_PREFIX)]


def _window(devices, host: Sequence[Event],
            window: Optional[Tuple[int, int]]) -> Tuple[int, int]:
    """``trace.reduce``'s window: the ``bench.window`` annotation, else the
    span of every operation."""
    if window is not None:
        return window
    spans = [(s, e) for n, s, e in host
             if n == trace_lib.HOST_PREFIX + "window"]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    allev = [ev for d in devices.values() for ev in d["ops"]]
    return min(ev[1] for ev in allev), max(ev[2] for ev in allev)


def _innermost(host: Sequence[Event], s: int, e: int) -> str:
    mid = (s + e) // 2
    inner = [h for h in host if h[1] <= mid < h[2]]
    if not inner:
        return "host"
    return min(inner, key=lambda h: h[2] - h[1])[0]


def reduce(devices: Dict[str, Dict[str, List[Event]]],
           host: Iterable[Event], names: Dict[str, Dict[str, str]],
           window: Optional[Tuple[int, int]] = None,
           top: int = 16) -> Optional[Dict[str, Any]]:
    """Scope times and named idle gaps of one traced window, averaged over
    devices as ``trace.reduce`` averages; None where no operation ran.
    ``devices`` is what ``trace.load_xplane`` reads, ``host`` its
    annotations and :func:`program_spans`, ``names`` :func:`hlo_names`;
    ``named_ops`` counts the operations ``names`` holds a stack for, and
    ``unnamed_ops`` the others."""
    if not any(d["ops"] for d in devices.values()):
        return None
    host = sorted(host, key=lambda h: h[1])
    lo, hi = _window(devices, host, window)
    n_dev = max(len(devices), 1)
    acc: Dict[str, Dict[str, Dict[str, float]]] = defaultdict(
        lambda: defaultdict(lambda: defaultdict(float)))
    by_op: Dict[str, Dict[str, Dict[str, float]]] = defaultdict(
        lambda: defaultdict(lambda: defaultdict(float)))
    module_n: Dict[str, float] = defaultdict(float)
    named = [0, 0]
    gaps: List[Tuple[int, int]] = []
    for i, (plane, d) in enumerate(sorted(devices.items())):
        ops = trace_lib.clip(d["ops"], lo, hi)
        mods = trace_lib.clip(d["modules"], lo, hi)
        for name, _, _ in mods:
            module_n[trace_lib.module_base(name)] += 1.0 / n_dev
        own = trace_lib.self_times(ops)
        for (text, mod, _, _), (_, _, _, t) in zip(
                trace_lib.attribute(ops, mods), own):
            stack = names.get(mod, {}).get(instruction(text), "")
            named[not stack] += 1
            scope, way = scope_of(stack)
            acc[mod][scope][way] += t / 1e9 / n_dev
            by_op[mod][f"{scope}/{way}"][trace_lib.op_name(text)] += (
                t / 1e9 / n_dev)
        if i == 0:
            merged = trace_lib.merge((s, e) for _, s, e in ops)
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges) - 1, 2)
                    if edges[j + 1] > edges[j]]
    gaps.sort(key=lambda g: g[0] - g[1])
    scope_s = {m: {sc: dict(w) for sc, w in v.items()}
               for m, v in acc.items()}
    rows = sorted(([m, sc, w, t] for m, v in scope_s.items()
                   for sc, ws in v.items() for w, t in ws.items()),
                  key=lambda r: -r[3])
    return {
        "scope_in_module_s": scope_s,
        "module_n": dict(module_n),
        "named_ops": named[0],
        "unnamed_ops": named[1],
        "device_scopes": rows[:top],
        "op_in_scope_s": {m: {k: dict(v) for k, v in sv.items()}
                          for m, sv in by_op.items()},
        "idle_gaps_by_span": [[_innermost(host, s, e), (e - s) / 1e9]
                              for s, e in gaps[:top]],
    }


def scope_time(summary: Dict[str, Any], module: str,
               scopes: Sequence[str]) -> float:
    """Device self seconds under ``scopes`` (both directions) in programs
    whose jit name contains ``module``."""
    return sum(t for m, v in summary["scope_in_module_s"].items()
               if module in m for sc, ws in v.items() if sc in scopes
               for t in ws.values())


def scoped_share(summary: Dict[str, Any], module: str) -> Optional[float]:
    """The share of ``module``'s device self time under a named scope."""
    total = scope_time(summary, module, tuple(SCOPES) + (UNSCOPED,))
    if total <= 0:
        return None
    return 1.0 - scope_time(summary, module, (UNSCOPED,)) / total


def ms_per_execution(summary: Optional[Dict[str, Any]], module: str,
                     scopes: Sequence[str]) -> Optional[float]:
    """Milliseconds of device self time under ``scopes`` per execution of
    ``module``'s programs in the traced window; None where the trace holds
    no such execution or no time under those scopes."""
    if not summary:
        return None
    n = sum(v for k, v in summary["module_n"].items() if module in k)
    t = scope_time(summary, module, scopes)
    if n <= 0 or t <= 0:
        return None
    return 1e3 * t / n


# the per-step metrics this reduction feeds, by the scopes each sums
TRAIN_STEP_METRICS = {
    "train.attn_bwd_ms_per_step": ("attn_bwd",),
    "train.head_loss_ms_per_step": ("head_loss", "embed"),
    "train.optimizer_ms_per_step": ("optimizer", "guard"),
}


def train_step_metrics(summary: Optional[Dict[str, Any]]
                       ) -> Dict[str, float]:
    """Each of :data:`TRAIN_STEP_METRICS` that the trace holds, in ms per
    execution of ``jit_train_step``."""
    out = {}
    for name, scopes in TRAIN_STEP_METRICS.items():
        v = ms_per_execution(summary, "train_step", scopes)
        if v is not None:
            out[name] = v
    return out
