"""One run of one cell: drive it, read its metrics, check it, and report.

The last line of standard output is the result; the lines before it on
standard error say how set-up split, what the window held, and, last, each
number compared beside its limit.
"""
from __future__ import annotations

import json
import math
import sys
from typing import Any, Callable, Dict, Optional, Tuple

from benchmarks.chip import check, spec
from benchmarks.chip.record import RunRecord
from benchmarks.chip.runtime import Runtime


def _drive(cell, **kw) -> Tuple[RunRecord, Dict[str, Any]]:
    if cell.kind == "serve":
        from benchmarks.chip import serve_cell as mod  # noqa: PLC0415
    elif cell.kind == "train":
        from benchmarks.chip import train_cell as mod  # noqa: PLC0415
    else:
        raise ValueError(f"unknown traffic kind {cell.kind!r}")
    return mod.run(cell, **kw)


def _outcome(rec: RunRecord) -> Tuple[int, int]:
    """(attempted, failed): requests due in the window (an offline queue:
    those admitted), or train steps (failed: skipped by the guard)."""
    if rec.kind == "train":
        return rec.steps, int(rec.counters.get("skipped_steps", 0))
    reqs = [r for r in rec.requests if r.due < rec.seconds
            and (r.admitted is not None or r.failed)]
    return len(reqs), sum(r.failed for r in reqs)


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, fault: Optional[Callable] = None,
             control: Optional[str] = None, peaks: Optional[Dict] = None,
             log=sys.stderr) -> Dict[str, Any]:
    """Run ``cell`` once and return the result line's object."""
    from benchmarks.chip import roofline  # noqa: PLC0415

    dev0 = devices[0]
    rt = Runtime(t_start, list(devices), trace)
    rec, numbers = _drive(cell, seed=seed, seconds=seconds, rt=rt,
                          devices=list(devices), fault=fault, control=control)
    rec.peaks = peaks if peaks is not None else roofline.peaks_for(
        dev0.device_kind)
    attempted, failed = _outcome(rec)
    limits = dict(cell.limits["limits"])
    if rec.kind == "serve":
        numbers["failed_requests"] = failed
        limits["failed_requests"] = 0
    correct, checks = check.verdict(numbers, limits)

    metrics: Dict[str, Any] = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.load_reader(m["name"], cell.root)(rec)
        if value is None or not math.isfinite(value):
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device: Dict[str, Any] = {"platform": dev0.platform,
                              "kind": dev0.device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": rt.memory_peak}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           rec.trace["device_ops"]],
                            "idle_gaps": rec.trace["idle_gaps"]}

    w = rec.work
    print(f"setup_s {rec.setup_s:.3f}: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in rt.phases.items() if k != "check")
        + f", of which compile or cache load {rt.setup_compile_s:.3f} s "
        f"({rt.setup_compiles} programs; persistent cache hits "
        f"{rt.cache['hits']}, misses {rt.cache['misses']})", file=log)
    print(f"window: {rec.window_s:.3f} s, compilations inside it "
          f"{rt.window_compiles}; the check after it took "
          f"{rt.phases.get('check', 0.0):.3f} s", file=log)
    if rec.step_times:
        durs = sorted(((e - s, s) for s, e in rec.step_times), reverse=True)
        print(f"steps: median {1e3 * durs[len(durs) // 2][0]:.1f} ms; "
              "longest " + ", ".join(f"{1e3 * d:.1f} ms at {s:.2f} s"
                                     for d, s in durs[:3]), file=log)
    if rec.kind == "serve":
        done = sum(r.done and not r.failed for r in rec.requests
                   if r.due < seconds)
        late_n = max(w.get("late_n", 0), 1)
        print(f"requests: due in window {len([r for r in rec.requests if r.due < seconds])}, "
              f"completed {done}, failed {failed}; tokens held by the close "
              f"{rec.tokens_in_window}; engine steps {int(w['steps'])}, "
              f"straggler_events {rec.counters['straggler_events']}, "
              f"shed level {rec.counters['shed_events']}; generator "
              f"lateness mean {1e3 * w['late_s_sum'] / late_n:.3f} ms, max "
              f"{1e3 * w['late_s_max']:.3f} ms over {int(w['late_n'])} "
              f"idle waits", file=log)
    else:
        print(f"train: {rec.steps} steps of {rec.tokens_per_step} tokens, "
              f"skipped {failed}", file=log)
    info = {k: v for k, v in numbers.items() if k not in limits}
    if info:
        print("also read: " + json.dumps(info, sort_keys=True), file=log)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=log)
    out["checks"] = checks
    return out
