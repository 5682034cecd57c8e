"""Training driver: guarded end-to-end loop with verified checkpoints,
bitwise-identical resume, chaos injection, and a bounded-restart supervisor.

Runs real training on whatever devices exist (CPU smoke configs, TPU slices)
using the same planner/step machinery the dry-run proves out at 512 chips.
The loop mirrors the serving stack's failure model (PR 8/9) on the training
side:

* every jitted step carries an on-device non-finite guard — a NaN/Inf loss
  or gradient skips the optimizer update (params pass through unchanged,
  donation preserved) and ``max_bad_steps`` consecutive skips abort with a
  typed :class:`TrainDivergedError`;
* a host-side loss-spike detector (EWMA + factor threshold) rolls back to
  the last good checkpoint and re-seeds the data window (``salt``), so a
  poisonous batch window is not replayed verbatim;
* checkpoints capture the full loop state (RNG key, data cursor/salt,
  skip/rollback counters, loss EWMA), so an interrupted+resumed run's losses
  and final params are *byte-identical* to an uninterrupted run — gated by
  :func:`verify_resume_identity`;
* :class:`TrainSupervisor` wraps :func:`train` in a bounded auto-restart
  loop resuming from the last *verified* checkpoint (restore walks back past
  torn/corrupt checkpoints).

  PYTHONPATH=src python -m repro.launch.train --arch pimref-100m --steps 200
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 50 --checkpoint-dir /tmp/ck --resume
  PYTHONPATH=src python -m repro.launch.train --arch pimref-100m --steps 12 \
      --chaos-seed 7                      # REPRO_CHAOS="nan=2,slow=1" ...
  PYTHONPATH=src python -m repro.launch.train --arch pimref-100m --steps 10 \
      --checkpoint-dir /tmp/ck --checkpoint-every 3 --preempt-after 5 \
      --max-restarts 2 --resume-verify    # byte-identity gate
  PYTHONPATH=src python -m repro.launch.train --arch pimref-100m --steps 8 \
      --profile-dir /tmp/profile          # device time by program scope
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.checkpoint import (CheckpointManager, CheckpointWriteError)
from repro.configs import ALL_IDS, RunConfig, ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.data import make_batch_fn
from repro.distributed import (PreemptionHandler, RestartManifest,
                               StragglerMonitor, TrainChaosConfig,
                               TrainChaosMonkey)
from repro.distributed.chaos import nan_grad_hook
from repro.launch import mesh as mesh_lib
from repro.launch import tracing
from repro.launch.steps import make_train_step
from repro.models import build_model, init_params
from repro.optim import make_optimizer


class TrainDivergedError(RuntimeError):
    """``max_bad_steps`` consecutive steps were skipped by the non-finite
    guard: the run has genuinely diverged, and an auto-restart would replay
    the same divergence — so the supervisor never retries this."""


def _tree_host(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)), tree)


def _salted_seed(seed: int, salt: int) -> int:
    """Data-pipeline seed for rollback window ``salt`` (0 = original run).

    ``batch(step)`` is a pure function of (seed, step), so bumping the salt
    after a rollback re-seeds the replayed step window deterministically —
    the same salt always yields the same token stream."""
    return seed if salt == 0 else (seed + 0x9E3779B1 * salt) & 0x7FFFFFFF


def train(arch: str, *, smoke: bool = True, steps: int = 100,
          batch: int = 8, seq: int = 128, run: Optional[RunConfig] = None,
          checkpoint_dir: str = "", resume: bool = False,
          log_every: int = 10, use_mesh: bool = True,
          proteus: bool = False, chaos: Any = None,
          max_bad_steps: int = 8, spike_factor: float = 3.0,
          spike_warmup: int = 10,
          preempt_after: Optional[int] = None,
          devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """One training attempt. ``chaos`` is a :class:`TrainChaosConfig` (a
    fresh monkey is built) or a :class:`TrainChaosMonkey` (shared across a
    supervisor's attempts, so fire-once faults stay fired). ``preempt_after``
    requests a clean preemption once the run first crosses that absolute
    step; a resumed run past it never re-fires. ``devices`` (default: every
    local device) is the data-parallel mesh."""
    print(compat.describe_support())
    cfg = get_config(arch, smoke=smoke)
    run = run or RunConfig(total_steps=steps, microbatches=1)
    shape = ShapeConfig("custom", seq_len=seq, global_batch=batch,
                        mode="train")

    mesh = mesh_lib.make_local_mesh(("data",), devices) if use_mesh else None
    plan = plan_sharding(cfg, shape, mesh)
    model = build_model(cfg)
    optimizer = make_optimizer(cfg.optimizer, run)

    key = jax.random.PRNGKey(run.seed)
    with use_plan(plan):
        params = init_params(model.param_specs(), key)
        opt_state = optimizer.init(params)

    monkey: Optional[TrainChaosMonkey] = None
    if isinstance(chaos, TrainChaosMonkey):
        monkey = chaos
    elif chaos is not None:
        monkey = TrainChaosMonkey(chaos, total_steps=steps)
    hook = nan_grad_hook if (monkey and monkey.nan_steps) else None
    step_fn = jax.jit(make_train_step(model, optimizer, plan, run,
                                      guard=True, grad_hook=hook),
                      donate_argnums=(0, 1))

    # -- loop state: checkpointed, restored bit-for-bit on resume -----------
    start = 0
    salt = 0                        # rollback window counter (data reseed)
    ewma: Optional[float] = None    # loss EWMA for the spike detector
    ewma_n = 0
    consec_skips = 0
    skipped_total = 0
    rollbacks = 0
    anomalies = 0
    ckpt_failures = 0
    rng_key = np.asarray(jax.device_get(key)).tolist()

    ckpt = CheckpointManager(
        checkpoint_dir, keep=run.keep_checkpoints,
        fault_hook=monkey.ckpt_fault if monkey else None) \
        if checkpoint_dir else None
    resumed_at = None
    if ckpt and resume and ckpt.latest_step() is not None:
        start, state = ckpt.restore({"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        loop = ckpt.load_extra(start).get("loop", {})
        salt = int(loop.get("data_salt", 0))
        ewma = loop.get("loss_ewma")
        ewma_n = int(loop.get("ewma_n", 0))
        consec_skips = int(loop.get("consec_skips", 0))
        skipped_total = int(loop.get("skipped_steps", 0))
        rollbacks = int(loop.get("rollbacks", 0))
        anomalies = int(loop.get("anomalies", 0))
        rng_key = loop.get("rng_key", rng_key)
        resumed_at = start
        print(f"resumed from step {start} (salt={salt})")

    batch_fn = make_batch_fn(cfg, shape, seed=_salted_seed(run.seed, salt))
    preempt = PreemptionHandler().install()
    straggler = StragglerMonitor()
    losses: List[float] = []
    grad_norms: List[float] = []
    first_step = start
    preempted = False
    t_begin = time.time()

    def loop_state(step_next: int) -> Dict[str, Any]:
        return {"step": step_next, "data_salt": salt, "loss_ewma": ewma,
                "ewma_n": ewma_n, "consec_skips": consec_skips,
                "skipped_steps": skipped_total, "rollbacks": rollbacks,
                "anomalies": anomalies, "rng_key": rng_key,
                "straggler_flags": len(straggler.flagged)}

    def save_boundary(step_next: int, loss: float) -> bool:
        nonlocal ckpt_failures
        try:
            ckpt.save(step_next, {"params": params, "opt": opt_state},
                      extra={"loss": loss, "loop": loop_state(step_next)})
            RestartManifest(
                step=step_next, checkpoint_dir=checkpoint_dir,
                mesh_shape=list(mesh.shape.values()) if mesh else [1],
                mesh_axes=list(mesh.shape.keys()) if mesh else ["data"],
                data_seed=run.seed, arch=arch, shape=shape.name,
                straggler_events=straggler.flagged,
                train=loop_state(step_next),
            ).save(os.path.join(checkpoint_dir, "manifest.json"))
            if monkey:
                monkey.maybe_tear(ckpt, step_next)
        except CheckpointWriteError as e:
            ckpt_failures += 1
            warnings.warn(f"checkpoint write failed at step {step_next} "
                          f"({e}); training continues — the previous "
                          "checkpoint still restores")
            return False
        return True

    def drain_writer() -> None:
        nonlocal ckpt_failures
        if not ckpt:
            return
        try:
            ckpt.wait()
        except CheckpointWriteError as e:
            ckpt_failures += 1
            warnings.warn(str(e))

    step = start
    while step < steps:
        straggler.step_start()
        if monkey:
            try:
                monkey.on_step(step)    # injected sleep / hard host crash
            except Exception:
                preempt.uninstall()
                drain_writer()
                raise
        with tracing.span("train.step", step=step):
            b = {k: jnp.asarray(v) for k, v in batch_fn(step).items()}
            if hook is not None:
                arm = jnp.asarray(1 if monkey.nan_armed(step) else 0,
                                  jnp.int32)
                params, opt_state, metrics = step_fn(params, opt_state, b,
                                                     arm)
            else:
                params, opt_state, metrics = step_fn(params, opt_state, b)
            loss = float(metrics["loss"])            # the step's host sync
            gnorm = float(metrics["grad_norm"])
        losses.append(loss)
        grad_norms.append(gnorm)

        if bool(metrics["skipped"]):
            skipped_total += 1
            consec_skips += 1
            print(f"step {step:5d} SKIPPED non-finite loss/grads "
                  f"(grad_norm={gnorm:.3g}, {consec_skips} consecutive)")
            if consec_skips >= max_bad_steps:
                preempt.uninstall()
                drain_writer()
                raise TrainDivergedError(
                    f"{consec_skips} consecutive non-finite steps ending at "
                    f"step {step} (max_bad_steps={max_bad_steps})")
        else:
            consec_skips = 0
            observed = loss * (monkey.loss_scale(step, salt) if monkey
                               else 1.0)
            if (spike_factor > 0 and ewma is not None
                    and ewma_n >= spike_warmup
                    and observed > spike_factor * max(ewma, 1e-9)):
                anomalies += 1
                drain_writer()  # pending async saves must be visible, so
                                # the rollback target is deterministic
                if ckpt and ckpt.latest_step() is not None:
                    rb, state = ckpt.restore({"params": params,
                                              "opt": opt_state})
                    params, opt_state = state["params"], state["opt"]
                    loop = ckpt.load_extra(rb).get("loop", {})
                    rollbacks += 1
                    salt = int(loop.get("data_salt", 0)) + 1
                    ewma = loop.get("loss_ewma")
                    ewma_n = int(loop.get("ewma_n", 0))
                    consec_skips = int(loop.get("consec_skips", 0))
                    skipped_total = int(loop.get("skipped_steps", 0))
                    batch_fn = make_batch_fn(
                        cfg, shape, seed=_salted_seed(run.seed, salt))
                    if rb < first_step:
                        first_step = rb
                        del losses[:], grad_norms[:]
                    else:
                        del losses[rb - first_step:]
                        del grad_norms[rb - first_step:]
                    print(f"loss spike at step {step} ({observed:.3f} > "
                          f"{spike_factor:.1f}x EWMA): rolled back to step "
                          f"{rb}, data window re-seeded (salt={salt})")
                    step = rb
                    continue
                warnings.warn(f"loss spike at step {step} with no "
                              "checkpoint to roll back to; continuing")
            ewma = observed if ewma is None else 0.9 * ewma + 0.1 * observed
            ewma_n += 1

        flag = straggler.step_end(step)
        if flag:
            print(f"  straggler flag: {flag}")
        if step % log_every == 0 or step == steps - 1:
            dt = time.time() - t_begin
            # a model with held experts also counts the pairs routed to
            # them and the busiest expert's load over the mean
            moe = "".join(f" {k} {float(metrics[k]):.4g}"
                          for k in ("moe_rows", "moe_load_max")
                          if k in metrics)
            print(f"step {step:5d} loss {loss:8.4f}{moe} "
                  f"({dt / max(step - start + 1, 1):.2f}s/step)")
        if preempt_after is not None and start < preempt_after <= step + 1:
            preempt.requested = True
        if monkey and monkey.preempt(step):
            preempt.requested = True
        if ckpt and ((step + 1) % run.checkpoint_every == 0
                     or preempt.requested or step == steps - 1):
            save_boundary(step + 1, loss)
        if preempt.requested:
            print(f"preemption requested: checkpointed at {step + 1}, "
                  "exiting cleanly")
            preempted = True
            break
        step += 1
    preempt.uninstall()
    drain_writer()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "grad_norms": grad_norms, "params": params, "opt_state": opt_state,
            "first_step": first_step, "resumed_at": resumed_at,
            "preempted": preempted, "skipped_steps": skipped_total,
            "rollbacks": rollbacks, "anomalies": anomalies,
            "ckpt_failures": ckpt_failures,
            "chaos_events": list(monkey.events) if monkey else []}


class TrainSupervisor:
    """Bounded auto-restart loop around :func:`train`.

    Each attempt resumes from the last *verified* checkpoint
    (``CheckpointManager.restore`` walks back past torn/corrupt steps). An
    injected preemption or hard step crash consumes one restart;
    :class:`TrainDivergedError` is never retried — a divergence replays
    deterministically, so a restart would only burn the budget. One chaos
    monkey is shared across attempts: operational faults (preempt, crash,
    checkpoint failures/tears) fire once per supervised run, per-step data
    faults (NaN grads, spikes) replay by absolute step — together that makes
    the supervised run byte-identical to an uninterrupted one
    (:func:`verify_resume_identity`).
    """

    def __init__(self, arch: str, *, checkpoint_dir: str, steps: int,
                 max_restarts: int = 2, chaos: Any = None,
                 preempt_after: Optional[int] = None, **train_kw):
        assert checkpoint_dir, "TrainSupervisor needs a checkpoint_dir"
        self.arch = arch
        self.checkpoint_dir = checkpoint_dir
        self.steps = steps
        self.max_restarts = max_restarts
        if chaos is not None and not isinstance(chaos, TrainChaosMonkey):
            chaos = TrainChaosMonkey(chaos, total_steps=steps)
        self.monkey: Optional[TrainChaosMonkey] = chaos
        self.preempt_after = preempt_after
        self.train_kw = train_kw
        self.restarts = 0
        self.attempts: List[Dict[str, Any]] = []

    def run(self) -> Dict[str, Any]:
        while True:
            try:
                out = train(self.arch, checkpoint_dir=self.checkpoint_dir,
                            steps=self.steps, resume=True, chaos=self.monkey,
                            preempt_after=(self.preempt_after
                                           if self.restarts == 0 else None),
                            **self.train_kw)
            except TrainDivergedError:
                raise
            except Exception as e:  # noqa: BLE001 — supervisor absorbs
                self.attempts.append({"error": repr(e)})
                if self.restarts >= self.max_restarts:
                    raise
                self.restarts += 1
                print(f"supervisor: attempt {self.restarts} died ({e!r}); "
                      "restarting from the last verified checkpoint")
                continue
            self.attempts.append({"first_step": out["first_step"],
                                  "losses": list(out["losses"]),
                                  "preempted": out["preempted"]})
            if out["preempted"] and self.restarts < self.max_restarts:
                self.restarts += 1
                print(f"supervisor: preempted; restart "
                      f"{self.restarts}/{self.max_restarts}")
                continue
            out["restarts"] = self.restarts
            out["losses_full"] = self.stitched_losses()
            return out

    def stitched_losses(self) -> List[float]:
        """Per-attempt loss curves merged by absolute step (later attempts
        win — they replayed those steps from a verified checkpoint)."""
        by_step: Dict[int, float] = {}
        for a in self.attempts:
            if "losses" not in a:
                continue
            for i, loss in enumerate(a["losses"]):
                by_step[a["first_step"] + i] = loss
        return [by_step[s] for s in sorted(by_step)]


def _strip_operational(cfg: TrainChaosConfig) -> TrainChaosConfig:
    """The reference run keeps per-step data faults (NaN/slow/spike — they
    must replay identically) but drops operational faults (preemption,
    crashes, checkpoint failures/tears — the interruptions under test)."""
    return dataclasses.replace(
        cfg, preempt=-1, crash=0, ckpt_fail=0, torn=0,
        crash_steps=None, ckpt_fail_steps=None, torn_steps=None)


def verify_resume_identity(arch: str, *, steps: int, work_dir: str,
                           chaos: Optional[TrainChaosConfig] = None,
                           preempt_after: Optional[int] = None,
                           max_restarts: int = 2,
                           **train_kw) -> Dict[str, Any]:
    """The resume-identity gate: a run interrupted by preemption/crashes and
    auto-restarted by :class:`TrainSupervisor` must produce byte-identical
    losses and final params vs an uninterrupted reference run."""
    sup = TrainSupervisor(arch, checkpoint_dir=os.path.join(work_dir, "sup"),
                          steps=steps, max_restarts=max_restarts,
                          chaos=chaos, preempt_after=preempt_after,
                          **train_kw)
    out = sup.run()
    ref_chaos = _strip_operational(chaos) if chaos is not None else None
    ref = train(arch, steps=steps,
                checkpoint_dir=os.path.join(work_dir, "ref"),
                chaos=ref_chaos, **train_kw)
    losses_ok = (len(out["losses_full"]) == len(ref["losses"])
                 and np.array_equal(np.asarray(out["losses_full"]),
                                    np.asarray(ref["losses"]),
                                    equal_nan=True))
    pa = jax.tree_util.tree_leaves(_tree_host(out["params"]))
    pb = jax.tree_util.tree_leaves(_tree_host(ref["params"]))
    params_ok = len(pa) == len(pb) and all(
        a.tobytes() == b.tobytes() for a, b in zip(pa, pb))
    return {"identical": losses_ok and params_ok,
            "losses_match": losses_ok, "params_match": params_ok,
            "restarts": out["restarts"],
            "skipped_steps": out["skipped_steps"],
            "rollbacks": out["rollbacks"],
            "ckpt_failures": out["ckpt_failures"],
            "out": out, "ref": ref}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="pimref-100m", choices=list(ALL_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="checkpoint interval in steps (default: RunConfig)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="seed for the REPRO_CHAOS train fault plan (arms "
                    "a default nan+slow plan when the env var is unset)")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap the run in TrainSupervisor auto-restart")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--max-bad-steps", type=int, default=8)
    ap.add_argument("--spike-factor", type=float, default=3.0)
    ap.add_argument("--preempt-after", type=int, default=None,
                    help="request a clean preemption once this absolute "
                    "step is crossed (with --supervise / --resume-verify "
                    "the run auto-restarts)")
    ap.add_argument("--resume-verify", action="store_true",
                    help="run the interrupted+resumed vs uninterrupted "
                    "byte-identity gate and exit")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "pallas", "jnp"],
                    help="attention backend (sets REPRO_ATTN_IMPL before "
                    "the train step is traced)")
    ap.add_argument("--kv-quant", default=None,
                    choices=["off", "int8", "int4", "auto"],
                    help="Proteus-quantized KV cache for any eval/serve "
                    "prefill+decode launched from this process (sets "
                    "REPRO_KV_QUANT; the train step itself has no KV cache)")
    ap.add_argument("--profile-dir", default=None,
                    help="record a profile of the run into this directory: "
                    "device ops named by program scope, host spans "
                    "repro.train.step")
    args = ap.parse_args()
    compat.enable_compilation_cache()
    with tracing.profile(args.profile_dir):
        _run(args)


def _run(args) -> None:
    if args.attn_impl:
        os.environ["REPRO_ATTN_IMPL"] = args.attn_impl
    if args.kv_quant:
        os.environ["REPRO_KV_QUANT"] = args.kv_quant
    run = RunConfig(total_steps=args.steps, learning_rate=args.lr,
                    microbatches=1,
                    checkpoint_every=args.checkpoint_every or 200)
    chaos = TrainChaosConfig.from_env(args.chaos_seed)
    if chaos is None and args.chaos_seed is not None:
        chaos = TrainChaosConfig.parse("nan=1,slow=1", seed=args.chaos_seed)
    common: Dict[str, Any] = dict(
        smoke=args.smoke, batch=args.batch, seq=args.seq, run=run,
        max_bad_steps=args.max_bad_steps, spike_factor=args.spike_factor)
    if args.resume_verify:
        work = args.checkpoint_dir or tempfile.mkdtemp(prefix="train_verify_")
        res = verify_resume_identity(
            args.arch, steps=args.steps, work_dir=work, chaos=chaos,
            preempt_after=args.preempt_after or max(args.steps // 2, 1),
            max_restarts=args.max_restarts, **common)
        assert res["identical"], (
            f"resume-verify FAILED: losses_match={res['losses_match']} "
            f"params_match={res['params_match']}")
        print(f"resume-verify: byte-identical across {res['restarts']} "
              f"restart(s) ({res['skipped_steps']} skipped, "
              f"{res['rollbacks']} rollback(s))")
        return
    if args.supervise or (args.preempt_after is not None):
        assert args.checkpoint_dir, "--supervise needs --checkpoint-dir"
        sup = TrainSupervisor(
            args.arch, checkpoint_dir=args.checkpoint_dir, steps=args.steps,
            max_restarts=args.max_restarts, chaos=chaos,
            preempt_after=args.preempt_after, **common)
        out = sup.run()
        print(f"supervisor: {out['restarts']} restart(s), "
              f"{out['skipped_steps']} skipped step(s), "
              f"{out['rollbacks']} rollback(s)")
    else:
        out = train(args.arch, steps=args.steps,
                    checkpoint_dir=args.checkpoint_dir, resume=args.resume,
                    chaos=chaos, **common)
    if chaos is not None and (chaos.nan or chaos.nan_steps):
        assert out["skipped_steps"] >= 1, \
            "chaos armed NaN grads but no step was skipped"
        print(f"chaos: survived {out['skipped_steps']} skipped step(s), "
              f"{out['rollbacks']} rollback(s), "
              f"{len(out['chaos_events'])} injected event(s)")
    if out["final_loss"] is None:
        print(f"nothing to do: resumed at step {out['resumed_at']}, "
              f"already past --steps {args.steps}")
    else:
        print(f"final loss: {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
