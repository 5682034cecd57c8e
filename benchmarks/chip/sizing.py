"""How much of one chip a cell's programs take, at the cell's size and above.

    python3 benchmarks/chip/sizing.py --workload <train cell> --size 4,6,8
    python3 benchmarks/chip/sizing.py --workload <train cell> --size 4 --aot
    python3 benchmarks/chip/sizing.py --workload <serving cell> --size 16,24

For a training cell, each size is a batch: it compiles the cell's guarded
train step and prints the compiler's ``memory_analysis()``; on the chip it
then builds the state, runs one step and prints the device's
``peak_bytes_in_use``, or the error that the step ran out of memory.
``--aot`` compiles for a described v5e instead, without a chip (nothing
runs). For a serving cell, each size is a slot count: on the chip it builds
the cell's engine with its weights and KV pool and warms it up, as a run's
set-up does, and prints the peak or the error. Give sizes in ascending
order: the peak a process reports never falls. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip.run import prepare  # noqa: E402


def memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(ma, k, 0) or 0) for k in keys}
    # what the program holds at once: its arguments (outputs that alias a
    # donated argument live in its buffer), new outputs, and temporaries
    out["live_bytes"] = (out["argument_size_in_bytes"]
                         + out["output_size_in_bytes"]
                         - out["alias_size_in_bytes"]
                         + out["temp_size_in_bytes"])
    return out


def train_sizes(cell, batch: int, devices, run: bool) -> dict:
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from benchmarks.chip import data, train_cell, weights  # noqa: PLC0415
    from benchmarks.chip.runtime import model_config, program_model  # noqa: PLC0415
    from repro.configs.base import ShapeConfig  # noqa: PLC0415
    from repro.core.mimdram import plan_sharding  # noqa: PLC0415
    from repro.launch import mesh as mesh_lib  # noqa: PLC0415
    from repro.launch import specs as specs_lib  # noqa: PLC0415
    from repro.launch.steps import make_train_step, named  # noqa: PLC0415
    from repro.models.module import param_pspecs  # noqa: PLC0415
    from repro.optim import make_optimizer  # noqa: PLC0415

    m, t = cell.config["model"], dict(cell.traffic)
    fam = cell.family()
    t["batch"] = batch
    S = t["seq_len"]
    cfg = model_config(cell.config, fam)
    mesh = mesh_lib.make_local_mesh(("data",), devices)
    shape = ShapeConfig("train", seq_len=S, global_batch=batch, mode="train")
    plan = plan_sharding(cfg, shape, mesh)
    model = program_model(cfg)
    specs = model.param_specs()
    rcfg = train_cell.run_config(t)
    opt = make_optimizer("adamw", rcfg)
    p_sh = named(plan, param_pspecs(specs, plan))
    o_sh = named(plan, param_pspecs(opt.state_specs(specs), plan))
    b_sh = named(plan, specs_lib.batch_pspecs(cfg, shape, plan))["tokens"]
    init = weights.make_init(fam, m, cell.config["init"], p_sh)
    key = weights.seed_key(0)
    p_sds = jax.eval_shape(init, key)
    p_sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        p_sds, p_sh)
    o_sds = jax.eval_shape(opt.init, p_sds)
    o_sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        o_sds, o_sh)
    tok = jax.ShapeDtypeStruct((batch, S), jnp.int32, sharding=b_sh)
    step = jax.jit(make_train_step(model, opt, plan, rcfg, guard=True),
                   donate_argnums=(0, 1))
    compiled = step.lower(p_sds, o_sds, {"tokens": tok, "labels": tok}) \
        .compile()
    out = {"batch": batch, "tokens_per_step": batch * S, **memory(compiled)}
    if run:
        params = init(key)
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
        b = jax.device_put(data.make_pool(t, m["vocab_size"], 0, 1)[0], b_sh)
        try:
            params, opt_state, met = compiled(params, opt_state,
                                              {"tokens": b, "labels": b})
            out["loss"] = float(met["loss"])
            out["ran"] = True
        except Exception as e:  # noqa: BLE001 — the out-of-memory error
            out["ran"] = False
            out["error"] = str(e).splitlines()[0][:300]
        del params, opt_state, b
        gc.collect()
        stats = devices[0].memory_stats() or {}
        out["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", -1))
        out["bytes_limit"] = int(stats.get("bytes_limit", -1))
    return out


def serve_sizes(cell, slots: int, devices) -> dict:
    import copy  # noqa: PLC0415
    import time  # noqa: PLC0415

    from benchmarks.chip import serve_cell  # noqa: PLC0415
    from benchmarks.chip.runtime import Runtime  # noqa: PLC0415

    c = copy.deepcopy(cell)
    c.traffic["engine"]["slots"] = slots
    out = {"slots": slots}
    server = None
    try:
        server = serve_cell.Server(c, 0, devices,
                                   Runtime(time.perf_counter(), devices,
                                           False))
        out["ran"] = True
        out["kv_pool_pages"] = int(getattr(server.engine, "n_phys_pages", 0))
    except Exception as e:  # noqa: BLE001 — the out-of-memory error
        out["ran"] = False
        out["error"] = str(e).splitlines()[0][:300]
    if server is not None:
        server.release()
    gc.collect()
    stats = devices[0].memory_stats() or {}
    out["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", -1))
    out["bytes_limit"] = int(stats.get("bytes_limit", -1))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--size", required=True,
                   help="comma-separated batch sizes or slot counts, "
                        "ascending")
    p.add_argument("--aot", action="store_true")
    args = p.parse_args(argv)
    prepare()
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["REPRO_ATTN_IMPL"] = "pallas"
        os.environ["REPRO_PALLAS_INTERPRET"] = "0"
    import jax  # noqa: PLC0415

    from benchmarks.chip import spec  # noqa: PLC0415

    cell = spec.load_cell(args.workload)
    if args.aot:
        from jax.experimental import topologies  # noqa: PLC0415

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[:cell.chips]
    else:
        devices = jax.devices()[:cell.chips]
        if devices[0].platform != "tpu":
            print("error: needs the chip, or --aot", file=sys.stderr)
            return 3
    if cell.kind == "serve" and args.aot:
        print("error: serving cells are sized on the chip", file=sys.stderr)
        return 2
    for b in (int(x) for x in args.size.split(",")):
        if cell.kind == "serve":
            line = serve_sizes(cell, b, devices)
        else:
            try:
                line = train_sizes(cell, b, devices, run=not args.aot)
            except Exception as e:  # noqa: BLE001 — a compile that does not fit
                line = {"batch": b, "compiled": False,
                        "error": str(e).splitlines()[0][:300]}
        print(json.dumps(line), flush=True)
        if not line.get("ran", True):
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
