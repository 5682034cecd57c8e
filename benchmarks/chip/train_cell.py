"""Training cells: the guarded train step, driven as the trainer drives it.

Set-up builds one compiled step with its state and runs its first three
steps through the same call and feed as the window, on batches whose rows
all differ; the program's numbers for the check are read there (each step's
loss, the first clipped gradient as AdamW holds it after step 1, and each
leaf's change after step 3, before step 4 consumes the buffers). The window
then keeps stepping the same object. The reference follows the first three
steps afterwards, from the same seed.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, data, reference, weights
from benchmarks.chip.record import RunRecord
from benchmarks.chip.runtime import Runtime, model_config, program_model

from repro.configs.base import RunConfig, ShapeConfig
from repro.core.mimdram import plan_sharding
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib
from repro.launch.steps import make_train_step, named
from repro.models.module import param_pspecs
from repro.optim import make_optimizer

CHECKED_STEPS = 3


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in weights.flatten(tree).items()}


@jax.jit
def _change_norms(new, old):
    return _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


def _host(d: Dict[str, jax.Array]) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(d).items()}


def run_config(t: Dict[str, Any]) -> RunConfig:
    r = t["run"]
    return RunConfig(learning_rate=r["lr"], warmup_steps=r["warmup_steps"],
                     total_steps=r["total_steps"],
                     weight_decay=r["weight_decay"], grad_clip=r["grad_clip"],
                     b1=r["b1"], b2=r["b2"], microbatches=1)


def run(cell, *, seed: int, seconds: float, rt: Runtime, devices,
        fault: Optional[Callable] = None, control: Optional[str] = None
        ) -> Tuple[RunRecord, Dict[str, Any]]:
    m, t = cell.config["model"], cell.traffic
    fam = cell.family()
    B, S = t["batch"], t["seq_len"]
    rec = RunRecord(kind="train", seconds=seconds, chips=len(devices),
                    tokens_per_step=B * S)
    rec.work.update(fam.train_work(m, B, S))
    cfg = model_config(cell.config, fam)
    mesh = mesh_lib.make_local_mesh(("data",), devices)
    shape = ShapeConfig("train", seq_len=S, global_batch=B, mode="train")
    plan = plan_sharding(cfg, shape, mesh)
    model = program_model(cfg)
    specs = model.param_specs()
    weights.check_layout(fam, m, specs)
    rcfg = run_config(t)
    opt = make_optimizer("adamw", rcfg)
    p_sh = named(plan, param_pspecs(specs, plan))
    o_sh = named(plan, param_pspecs(opt.state_specs(specs), plan))
    b_sh = named(plan, specs_lib.batch_pspecs(cfg, shape, plan))["tokens"]
    init = weights.make_init(fam, m, cell.config["init"], p_sh)
    with rt.phase("weights"):
        params = init(weights.seed_key(seed))
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
        pool = data.make_pool(t, m["vocab_size"], seed, t["pool_batches"])
        pool = [jax.device_put(pool[i], b_sh)
                for i in range(t["pool_batches"])]
        jax.block_until_ready((params, opt_state, pool))
    step_fn = jax.jit(make_train_step(model, opt, plan, rcfg, guard=True),
                      donate_argnums=(0, 1))
    if fault is not None:
        step_fn = fault(step_fn)
    state = {"params": params, "opt": opt_state}
    del params, opt_state

    def one_step(k: int) -> Dict[str, Any]:
        b = pool[k % len(pool)]
        state["params"], state["opt"], met = step_fn(
            state["params"], state["opt"], {"tokens": b, "labels": b})
        return jax.device_get(met)            # one host sync per step

    prog: Dict[str, Any] = {"losses": []}
    with rt.phase("warmup"):
        for k in range(CHECKED_STEPS):
            prog["losses"].append(float(one_step(k)["loss"]))
            if k == 0:
                prog["grad"] = {n: v / (1.0 - rcfg.b1) for n, v in
                                _host(_leaf_norms(state["opt"]["mu"])).items()}
        # the initial weights are made again from the seed rather than kept
        # beside the step's state and temporaries, which fill the chip
        p0 = init(weights.seed_key(seed))
        prog["change"] = _host(_change_norms(state["params"], p0))
        del p0
    checked = [np.asarray(pool[k]) for k in range(CHECKED_STEPS)]
    rec.setup_s = rt.setup_done()
    skipped = 0
    k = CHECKED_STEPS
    with rt.window() as win:
        clock = time.perf_counter
        t0 = clock()
        while clock() - t0 < seconds:
            ts = clock() - t0
            with rt.annotate("bench.step"):
                met = one_step(k)
            rec.step_times.append((ts, clock() - t0))
            rec.work["steps"] = k + 1 - CHECKED_STEPS
            rt.step_done(rec.work)
            skipped += int(bool(met["skipped"]))
            k += 1
        rec.window_s = clock() - t0
    rec.trace, rec.traced_work = win.summary, rt.traced_work
    rec.traced_until = rt.traced_until
    rec.steps = k - CHECKED_STEPS
    rec.counters = {"skipped_steps": skipped}
    rt.read_memory()
    state.clear()
    pool.clear()
    gc.collect()
    with rt.phase("check"):
        numbers = compare(fam.forward, init, seed, checked, prog, m, t,
                          control)
    return rec, numbers


def compare(forward: Callable, init, seed: int, batches,
            prog: Dict[str, Any], m, t,
            control: Optional[str]) -> Dict[str, Any]:
    """The program's first steps against the reference's (the family's
    ``forward``), from the same seed; with ``control``, the reference
    computed that way in the program's place, against the float32
    reference."""
    p0 = init(weights.seed_key(seed))
    rows = t["check"]["rows_per_block"]
    ref = reference.train_steps(forward, p0, batches, m, t["run"], "f32",
                                rows)
    ref_grad, ref_change = ref["grad"], ref["change"]
    leaves = check.counted_leaves(ref_grad)

    def numbers(got: Dict[str, Any], tag: str) -> Dict[str, Any]:
        g, gl = check.worst_leaf_gap(got["grad"], ref_grad, ref_grad)
        u, ul = check.worst_leaf_gap(got["change"], ref_change, leaves)
        return {f"{tag}loss_gap": max(check.rel_gap(a, b) for a, b in
                                      zip(got["losses"], ref["losses"])),
                f"{tag}grad_gap": g, f"{tag}grad_gap_leaf": gl,
                f"{tag}update_gap": u, f"{tag}update_gap_leaf": ul}

    out = numbers(prog, "")
    out["uncompared_leaves"] = sorted(set(ref_grad) - set(leaves))
    if control:
        # the control in the program's place, and the fault of half of each
        # batch left out (the mean taken over the other half)
        half = [b[:len(b) // 2] for b in batches]
        for tag, mode, bs in (("control_", control, batches),
                              ("half_batch_", "f32", half)):
            out.update(numbers(reference.train_steps(
                forward, p0, bs, m, t["run"], mode, rows), tag))
    return out
