"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload internlm2-1.8b.offline-conv \
        --seed 7 --seconds 30 --trace 0

Everything the cell needs is found by name from BENCHMARK.json at the root
of the checkout. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last the numbers compared for
``correct``, each with its limit. Without the program beside it, or without
the accelerator and chip count the cell asks for, it prints no result and
exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# knobs that would change what the program runs; every cell measures the
# program's defaults (no speculation, no quantised cache, no fault
# injection, Pallas attention on the TPU), except that a serving cell's
# traffic file sets the KV layout
PROGRAM_KNOBS = ("REPRO_KV_PAGES", "REPRO_KV_QUANT", "REPRO_SPEC_DECODE",
                 "REPRO_SPEC_K", "REPRO_SPEC_DRAFT_LAYERS", "REPRO_CHAOS",
                 "REPRO_ATTN_IMPL", "REPRO_PALLAS_INTERPRET")


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def prepare() -> None:
    """Import paths, the program's knobs and the compile cache, before JAX
    is imported."""
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    for k in PROGRAM_KNOBS:
        os.environ.pop(k, None)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    prepare()
    from benchmarks.chip import spec  # noqa: PLC0415

    cell = spec.load_cell(args.workload)
    import jax  # noqa: PLC0415

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"error: {cell.name} needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    from benchmarks.chip import harness  # noqa: PLC0415

    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace),
                           devices=devices[:cell.chips], t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
