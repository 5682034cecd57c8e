"""From a profiler trace to the numbers the per-layer readers use.

A device plane carries two lines that matter: "XLA Modules" (one event per
execution of a compiled program, named after its jit, e.g.
``jit_generate_step(123)``) and "XLA Ops" (one event per operation, named by
its HLO text, e.g. ``%closed_call.11 = bf16[...] custom-call(...),
custom_call_target="tpu_custom_call", ...``). Ops nest: a ``while`` (a scan)
encloses the ops of its body on the same line, so time per op is self time,
its duration less that of the ops it directly encloses. Every Pallas kernel
is a ``tpu_custom_call`` with a generic name, so a kernel is attributed to
the program whose module event encloses it. Busy time is the union of the
operation intervals inside the window; the benchmark's own host annotations
(``bench.*``) name what the host was doing in each idle gap.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]             # (name, start_ns, end_ns)

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel


def op_name(text: str) -> str:
    """``%copy.160 = bf16[...] copy(...)`` -> ``copy``; a Pallas kernel ->
    ``pallas:closed_call``."""
    short = text.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = short.rpartition(".")
    if head and tail.isdigit():
        short = head
    return ("pallas:" + short) if KERNEL_MARK in text else short


def self_times(ops: Sequence[Event]) -> List[Tuple[str, int, int, int]]:
    """(text, start, end, self ns) per op: its duration less that of the ops
    it directly encloses."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0] * len(ops)
    stack: List[int] = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(n, s, e, (e - s) - child[i]) for i, (n, s, e) in enumerate(ops)]


def module_base(name: str) -> str:
    """``jit_generate_step(123)`` -> ``jit_generate_step``."""
    return name.split("(", 1)[0].strip()


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def attribute(ops: Sequence[Event],
              modules: Sequence[Event]) -> List[Tuple[str, str, int, int]]:
    """(op, enclosing module base name or "", start, end) for each op; an op
    belongs to the module event whose interval holds its start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = module_base(mods[i][0]) if i >= 0 and mods[i][2] > s else ""
        out.append((name, mod, s, e))
    return out


def reduce(devices: Dict[str, Dict[str, List[Event]]], host: List[Event],
           window: Optional[Tuple[int, int]] = None,
           top: int = 10) -> Optional[Dict[str, Any]]:
    """Summary of one traced window over every device plane.

    ``devices`` maps a plane name to ``{"modules": [...], "ops": [...]}``;
    ``host`` holds the benchmark's annotations. The window defaults to the
    host annotation ``bench.window``. Times come back in seconds, averaged
    over devices where a device total is meant. None where no operation
    ran on a device."""
    if not any(d["ops"] for d in devices.values()):
        return None
    if window is None:
        spans = [(s, e) for n, s, e in host if n == HOST_PREFIX + "window"]
        if spans:
            window = (min(s for s, _ in spans), max(e for _, e in spans))
        else:
            allev = [ev for d in devices.values() for ev in d["ops"]]
            window = (min(ev[1] for ev in allev), max(ev[2] for ev in allev))
    lo, hi = window
    n_dev = max(len(devices), 1)
    busy = 0
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    op_in_module: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    op_n_in_module: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    gaps: List[Tuple[int, int]] = []
    for i, (plane, d) in enumerate(sorted(devices.items())):
        ops = clip(d["ops"], lo, hi)
        mods = clip(d["modules"], lo, hi)
        merged = merge((s, e) for _, s, e in ops)
        busy += sum(e - s for s, e in merged)
        for name, s, e in mods:
            module_s[module_base(name)] += (e - s) / 1e9 / n_dev
            module_n[module_base(name)] += 1.0 / n_dev
        own = {(n, s, e): t for n, s, e, t in self_times(ops)}
        for text, mod, s, e in attribute(ops, mods):
            name = op_name(text)
            t = own[(text, s, e)] / 1e9 / n_dev
            op_s[name] += t
            op_in_module[mod][name] += t
            op_n_in_module[mod][name] += 1.0 / n_dev
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges) - 1, 2)
                    if edges[j + 1] > edges[j]]
    host_sorted = sorted(host, key=lambda h: h[1])

    def doing(s: int, e: int) -> str:
        mid = (s + e) // 2
        inner = [h for h in host_sorted if h[1] <= mid < h[2]]
        if not inner:
            return "host"
        return min(inner, key=lambda h: h[2] - h[1])[0]

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n_dev,
        "devices": n_dev,
        "module_s": dict(module_s),
        "module_n": dict(module_n),
        "op_s": dict(op_s),
        "op_in_module_s": {m: dict(v) for m, v in op_in_module.items()},
        "op_in_module_n": {m: dict(v) for m, v in op_n_in_module.items()},
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[doing(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    }


def module_time(summary: Dict[str, Any], module: str) -> float:
    """Seconds per device in executions of programs whose jit name contains
    ``module``."""
    return sum(v for k, v in summary["module_s"].items() if module in k)


def module_count(summary: Dict[str, Any], module: str) -> float:
    """Executions per device of programs whose jit name contains ``module``."""
    return sum(v for k, v in summary["module_n"].items() if module in k)


def ops_in(summary: Dict[str, Any], module: str, prefix: str,
           table: str = "op_in_module_s") -> Dict[str, float]:
    """{op name: seconds per device} of the ops whose name starts with
    ``prefix`` inside programs whose jit name contains ``module``, summed
    over those programs; with ``table="op_in_module_n"``, executions per
    device."""
    out: Dict[str, float] = {}
    for mod, ops in summary[table].items():
        if module in mod:
            for name, v in ops.items():
                if name.startswith(prefix):
                    out[name] = out.get(name, 0.0) + v
    return out


def kernel_time(summary: Dict[str, Any], module: str,
                prefix: str = "pallas:") -> float:
    """Seconds per device of the Pallas kernels (those named ``prefix...``)
    inside ``module``'s programs."""
    return sum(ops_in(summary, module, prefix).values())


def kernel_count(summary: Dict[str, Any], module: str,
                 prefix: str = "pallas:") -> float:
    """Executions per device of the Pallas kernels (those named
    ``prefix...``) inside ``module``'s programs."""
    return sum(ops_in(summary, module, prefix, "op_in_module_n").values())


def load_xplane(path: str) -> Tuple[Dict[str, Dict[str, List[Event]]],
                                    List[Event]]:
    """Device planes and ``bench.*`` host annotations of one .xplane.pb."""
    from jax.profiler import ProfileData  # noqa: PLC0415 (chip-side only)

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            d: Dict[str, List[Event]] = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is not None:
                    d[key] = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                              for ev in line.events]
            if d["ops"]:
                devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.end_ns))
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return devices, host


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
