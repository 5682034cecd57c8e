"""Run one cell traced, as run.py does, and print where its device time went
by program scope and what the host was doing in each idle gap.

    python3 benchmarks/chip/scoped_run.py --workload \
        internlm2-1.8b-6l.train-seq1k --seed 7 --seconds 30

The lines run.py prints come first, its result line included; the last line
of standard output is one more JSON object: what ``scopes.reduce`` gives
(``device_scopes``, ``scope_in_module_s``, ``op_in_scope_s``,
``idle_gaps_by_span``, and how many operations were named), ``scoped_share``
(the share of each program's device self time under a named scope), and
the per-step scope metrics of a training cell
(``scopes.TRAIN_STEP_METRICS``). It reads the profile that the traced run
records, once the window has closed, so its numbers come from the same
traced steps as the result line's. The trace events of TPU operations hold
no name stacks, so the run compiles every program afresh (the persistent
compilation cache off) with XLA writing each optimized program to ``.hlo/``
beside this file, where the stacks are read by operation name; set-up is
therefore longer than run.py's.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
HLO_DIR = HERE / ".hlo"
sys.path.insert(0, str(HERE.parents[1]))

from benchmarks.chip import run as run_lib  # noqa: E402


def main(argv=None) -> int:
    args = run_lib.parse(argv)
    run_lib.prepare()
    shutil.rmtree(HLO_DIR, ignore_errors=True)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_dump_to={HLO_DIR}"
                               " --xla_dump_hlo_as_text").strip()
    from benchmarks.chip import scopes, spec  # noqa: PLC0415
    from benchmarks.chip import trace as trace_lib  # noqa: PLC0415

    cell = spec.load_cell(args.workload)
    import jax  # noqa: PLC0415

    jax.config.update("jax_enable_compilation_cache", False)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"error: {cell.name} needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    from benchmarks.chip import harness  # noqa: PLC0415

    read: dict = {}
    load_xplane = trace_lib.load_xplane

    def load_both(path: str):
        # the runtime reads the traced window's profile through this name
        # before it deletes the file: keep what it reads, and the program's
        # spans beside it
        devices, host = load_xplane(path)
        read["devices"] = devices
        read["host"] = host + scopes.program_spans(path)
        return devices, host

    trace_lib.load_xplane = load_both
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=True, devices=devices[:cell.chips],
                           t_start=T_START)
    print(json.dumps(out), flush=True)
    summary = None
    names = scopes.hlo_names(str(HLO_DIR))
    if read:
        summary = scopes.reduce(read["devices"], read["host"], names)
    shutil.rmtree(HLO_DIR, ignore_errors=True)
    extra = {"device_scopes": None, "idle_gaps_by_span": None}
    if summary:
        extra = dict(summary)
        extra["scoped_share"] = {m: scopes.scoped_share(summary, m)
                                 for m in summary["scope_in_module_s"]}
        extra["metrics"] = scopes.train_step_metrics(summary)
        # a few of the operations that no dumped program names
        known = {n for v in names.values() for n in v}
        extra["unnamed_examples"] = sorted({
            scopes.instruction(t) for d in read["devices"].values()
            for t, _, _ in d["ops"]} - known)[:8]
    print(json.dumps(extra), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
