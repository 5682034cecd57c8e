"""Readings that set the limits of ``correct``, on the chip at a cell's size.

    python3 benchmarks/chip/control.py --workload internlm2-1.8b.offline-conv \
        --seeds 11,12,13 --seconds 15 [--kernels]

For each seed, in one process: one run of the cell with a short window, the
numbers its run compares (the program's readings), the same numbers for the
control (the reference computed with float8 matrix products in the
program's place) and, for training, for half of each batch left out. One
JSON line per seed. ``--kernels`` first compares the Pallas attention
kernels of the serving path with plain attention, at the cell's widths.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip.run import prepare  # noqa: E402


def rel_l2(a, b) -> float:
    import numpy as np  # noqa: PLC0415

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def plain_attention(q, k, v, q_pos, kv_pos):
    """Softmax attention in float32 at the highest precision; a query row
    sees the kv positions p with 0 <= p <= its own position."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / q.shape[-1] ** 0.5
    ok = (kv_pos[:, None, None, :] >= 0) & (
        kv_pos[:, None, None, :] <= q_pos[:, None, :, None])
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def kernel_check(m, e, seed: int):
    """Relative L2 of the compiled Pallas prefill and decode attention
    against plain attention on the same bfloat16 values: a causal
    full-bucket prefill of two prompts, and one decode row per slot at mixed
    depths of a contiguous cache."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415
    import numpy as np  # noqa: PLC0415
    from repro.models.layers import chunked_attention  # noqa: PLC0415

    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    S, B = e["prompt_bucket"], e["slots"]
    T = S + e["max_new"]
    rng = np.random.default_rng(seed)

    def bf16(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q, k, v = bf16(2, S, H, D), bf16(2, S, Hkv, D), bf16(2, S, Hkv, D)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (2, 1))
    got = jax.jit(lambda *a: chunked_attention(*a, impl="pallas"))(q, k, v)
    out = {"prefill": rel_l2(got, plain_attention(q, k, v, pos, pos))}
    qd, kc, vc = bf16(B, 1, H, D), bf16(B, T, Hkv, D), bf16(B, T, Hkv, D)
    depth = rng.integers(1, T, B)
    ar = np.arange(T)
    q_pos = jnp.asarray(depth, jnp.int32)
    kv_pos = jnp.asarray(np.where(ar[None] <= depth[:, None], ar, -1),
                         jnp.int32)
    got = jax.jit(lambda *a: chunked_attention(
        a[0], a[1], a[2], q_offset=a[3], kv_positions=a[4],
        impl="pallas"))(qd, kc, vc, q_pos, kv_pos)
    out["decode"] = rel_l2(got, plain_attention(qd, kc, vc, q_pos[:, None],
                                                kv_pos))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--control", default="fp8")
    p.add_argument("--kernels", action="store_true")
    args = p.parse_args(argv)
    prepare()
    import jax  # noqa: PLC0415

    from benchmarks.chip import harness, spec  # noqa: PLC0415
    from benchmarks.chip.runtime import Runtime  # noqa: PLC0415

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("error: needs the chip", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    if args.kernels:
        print(json.dumps({"kernels": kernel_check(
            cell.config["model"], cell.traffic["engine"], 0)}), flush=True)
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        rt = Runtime(t, devices, False)
        rec, numbers = harness._drive(cell, seed=seed, seconds=args.seconds,
                                      rt=rt, devices=devices,
                                      control=args.control)
        line = {"workload": cell.name, "seed": seed, "numbers": numbers,
                "setup_s": rec.setup_s, "window_s": rec.window_s,
                "memory_peak_bytes": rt.memory_peak}
        for m in cell.end_to_end:
            line[m["name"]] = spec.load_reader(m["name"])(rec)
        if rec.kind == "serve":
            due = rec.due_in_window()
            line["completed"] = sum(r.done and not r.failed for r in due)
            line["slot_waits"] = sum(rec.waited_for_slot(r) for r in due)
        print(json.dumps(line), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
