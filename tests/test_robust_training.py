"""Fault-tolerant training gates: guarded steps, verified checkpoints,
bitwise-identical resume, chaos determinism, supervisor restarts.

Mirrors tests/test_robust_serving.py on the training side. The central
invariants:

* the non-finite guard is *free* on clean steps (bitwise parity with the
  unguarded step) and a poisoned step passes params through unchanged;
* a torn/corrupt latest checkpoint restores from the previous one;
* an interrupted+resumed run is byte-identical to an uninterrupted one.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointCorruptError, CheckpointManager,
                              CheckpointMismatchError, CheckpointWriteError)
from repro.configs import RunConfig, ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.data import make_batch_fn
from repro.distributed import (TrainChaosConfig, TrainChaosMonkey,
                               TrainStepCrashError)
from repro.distributed.chaos import nan_grad_hook
from repro.launch import mesh as mesh_lib
from repro.launch import train as train_mod
from repro.launch.steps import make_train_step
from repro.launch.train import (TrainDivergedError, TrainSupervisor, train,
                                verify_resume_identity)
from repro.models import build_model, init_params
from repro.optim import make_optimizer

ARCH = "pimref-100m"
B, S = 4, 32


def _bytes_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(jax.device_get(x)).tobytes()
        == np.asarray(jax.device_get(y)).tobytes() for x, y in zip(la, lb))


@pytest.fixture(scope="module")
def step_env():
    cfg = get_config(ARCH, smoke=True)
    shape = ShapeConfig("t", seq_len=S, global_batch=B, mode="train")
    mesh = mesh_lib.make_local_mesh(("data",))
    plan = plan_sharding(cfg, shape, mesh)
    model = build_model(cfg)
    run = RunConfig(total_steps=10, microbatches=1)
    opt = make_optimizer(cfg.optimizer, run)
    with use_plan(plan):
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        opt_state = opt.init(params)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch_fn(cfg, shape, seed=0)(0).items()}
    return dict(model=model, opt=opt, plan=plan, run=run, params=params,
                opt_state=opt_state, batch=batch)


# ---------------------------------------------------------------------------
# Guarded step
# ---------------------------------------------------------------------------
def test_guard_disarmed_is_bitwise_identity(step_env):
    """Clean step through guard+hook == plain step, byte for byte — the
    guard and the compiled-in chaos hook cost nothing when disarmed."""
    e = step_env
    plain = jax.jit(make_train_step(e["model"], e["opt"], e["plan"],
                                    e["run"]))
    guarded = jax.jit(make_train_step(e["model"], e["opt"], e["plan"],
                                      e["run"], guard=True,
                                      grad_hook=nan_grad_hook))
    p0, s0, m0 = plain(e["params"], e["opt_state"], e["batch"])
    arm = jnp.asarray(0, jnp.int32)
    p1, s1, m1 = guarded(e["params"], e["opt_state"], e["batch"], arm)
    assert not bool(m1["skipped"])
    assert float(m0["loss"]) == float(m1["loss"])
    assert bool(jnp.isfinite(m1["grad_norm"]))
    assert _bytes_equal(p0, p1) and _bytes_equal(s0, s1)


def test_guard_armed_skips_update(step_env):
    """NaN-poisoned grads: the update is skipped — params and opt_state
    pass through byte-identical, and the metrics say so."""
    e = step_env
    guarded = jax.jit(make_train_step(e["model"], e["opt"], e["plan"],
                                      e["run"], guard=True,
                                      grad_hook=nan_grad_hook))
    arm = jnp.asarray(1, jnp.int32)
    p1, s1, m1 = guarded(e["params"], e["opt_state"], e["batch"], arm)
    assert bool(m1["skipped"])
    assert not bool(jnp.isfinite(m1["grad_norm"]))
    assert _bytes_equal(e["params"], p1)
    assert _bytes_equal(e["opt_state"], s1)


def test_divergence_raises_typed_error(tmp_path):
    run = RunConfig(total_steps=8, learning_rate=1e-3, microbatches=1,
                    checkpoint_every=100)
    chaos = TrainChaosConfig(seed=1, nan_steps=list(range(8)))
    with pytest.raises(TrainDivergedError, match="consecutive non-finite"):
        train(ARCH, steps=8, batch=B, seq=S, run=run, chaos=chaos,
              max_bad_steps=3, log_every=100)


# ---------------------------------------------------------------------------
# Hardened checkpoints
# ---------------------------------------------------------------------------
def _tree():
    return {"w": np.arange(16, dtype=np.float32).reshape(4, 4),
            "n": np.asarray(7, dtype=np.int32)}


def test_dtype_mismatch_is_typed_and_names_leaf(tmp_path):
    ck = CheckpointManager(str(tmp_path), async_save=False)
    ck.save(1, _tree())
    bad = {"w": np.zeros((4, 4), np.float32), "n": np.asarray(0, np.float32)}
    with pytest.raises(CheckpointMismatchError, match="dtype mismatch.*n"):
        ck.restore(bad)
    # typed error is still a ValueError for pre-existing handlers
    with pytest.raises(ValueError):
        ck.restore(bad)


def test_corrupt_latest_falls_back_to_previous(tmp_path):
    ck = CheckpointManager(str(tmp_path), async_save=False)
    ck.save(1, _tree())
    ck.save(2, _tree())
    leaf = os.path.join(str(tmp_path), "step_00000002", "__w__.npy")
    assert os.path.exists(leaf)
    with open(leaf, "r+b") as f:       # flip payload bytes: CRC must catch
        f.seek(os.path.getsize(leaf) - 4)
        f.write(b"\xff\xff\xff\xff")
    with pytest.warns(UserWarning, match="torn/corrupt"):
        step, tree = ck.restore(_tree())
    assert step == 1
    assert (tree["w"] == _tree()["w"]).all()
    # an explicitly requested corrupt step never silently falls back
    with pytest.raises(CheckpointCorruptError):
        ck.restore(_tree(), step=2)


def test_torn_write_falls_back(tmp_path):
    ck = CheckpointManager(str(tmp_path), async_save=False)
    ck.save(3, _tree())
    ck.save(5, _tree())
    leaf = os.path.join(str(tmp_path), "step_00000005", "__w__.npy")
    with open(leaf, "r+b") as f:       # truncated leaf = torn write
        f.truncate(os.path.getsize(leaf) // 2)
    with pytest.warns(UserWarning, match="falling back"):
        step, _ = ck.restore(_tree())
    assert step == 3
    # with every checkpoint corrupt, the failure is typed
    leaf3 = os.path.join(str(tmp_path), "step_00000003", "__w__.npy")
    with open(leaf3, "r+b") as f:
        f.truncate(1)
    with pytest.warns(UserWarning):
        with pytest.raises(CheckpointCorruptError, match="no intact"):
            ck.restore(_tree())


def test_async_write_error_reraised_at_next_wait(tmp_path):
    fired = []

    def hook(step, key):
        if not fired:
            fired.append(step)
            raise OSError("disk on fire")

    ck = CheckpointManager(str(tmp_path), fault_hook=hook)
    ck.save(1, _tree())                # async write dies in the thread
    with pytest.raises(CheckpointWriteError, match="disk on fire"):
        ck.wait()
    ck.save(2, _tree())                # error was drained; next save works
    ck.wait()
    assert ck.latest_step() == 2


def test_overwrite_and_gc_never_expose_partial_steps(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    ck.save(1, _tree())
    ck.save(1, _tree())                # overwrite goes through the .old swap
    ck.save(2, _tree())
    ck.save(3, _tree())                # gc drops step 1 via .trash rename
    assert ck.all_steps() == [2, 3]
    # stray swap/trash/tmp dirs are never mistaken for checkpoints
    for suffix in (".tmp", ".old", ".trash"):
        os.makedirs(os.path.join(str(tmp_path), "step_00000009" + suffix),
                    exist_ok=True)
    assert ck.all_steps() == [2, 3]
    step, tree = ck.restore(_tree())
    assert step == 3 and int(tree["n"]) == 7


# ---------------------------------------------------------------------------
# Bitwise resume identity + supervisor
# ---------------------------------------------------------------------------
def test_resume_identity_contiguous(tmp_path):
    """Preempt at step 3 of 6, auto-restart, and the stitched run is
    byte-identical (losses and final params) to an uninterrupted one."""
    run = RunConfig(total_steps=6, learning_rate=1e-3, microbatches=1,
                    checkpoint_every=2)
    res = verify_resume_identity(ARCH, steps=6, work_dir=str(tmp_path),
                                 preempt_after=3, max_restarts=2,
                                 batch=B, seq=S, run=run, log_every=100)
    assert res["restarts"] == 1
    assert res["losses_match"] and res["params_match"] and res["identical"]


def test_resume_identity_chaos_armed(tmp_path):
    """Same gate with the chaos plan armed: NaN-skip + slow step + injected
    preemption all replay deterministically across the restart."""
    run = RunConfig(total_steps=8, learning_rate=1e-3, microbatches=1,
                    checkpoint_every=3)
    chaos = TrainChaosConfig(seed=5, nan_steps=[2], slow_steps=[1],
                             slow_ms=2.0, preempt=4)
    res = verify_resume_identity(ARCH, steps=8, work_dir=str(tmp_path),
                                 chaos=chaos, max_restarts=2,
                                 batch=B, seq=S, run=run, log_every=100)
    assert res["restarts"] == 1
    assert res["skipped_steps"] >= 1
    assert res["identical"], (res["losses_match"], res["params_match"])


def test_spike_rollback_reseeds_window(tmp_path):
    run = RunConfig(total_steps=7, learning_rate=1e-3, microbatches=1,
                    checkpoint_every=2)
    chaos = TrainChaosConfig(seed=2, spike_steps=[4], spike_x=100.0)
    out = train(ARCH, steps=7, batch=B, seq=S, run=run,
                checkpoint_dir=str(tmp_path), chaos=chaos,
                spike_warmup=2, log_every=100)
    assert out["anomalies"] == 1 and out["rollbacks"] == 1
    assert len(out["losses"]) == 7          # rolled back, then completed
    assert np.isfinite(out["final_loss"])
    # the replayed window really was re-seeded and re-checkpointed
    mf = os.path.join(str(tmp_path), "manifest.json")
    with open(mf) as f:
        assert json.load(f)["train"]["data_salt"] == 1


def test_supervisor_bounded_restarts(tmp_path):
    """Hard crashes burn the restart budget; the supervisor re-raises once
    it is exhausted instead of looping forever."""
    run = RunConfig(total_steps=6, learning_rate=1e-3, microbatches=1,
                    checkpoint_every=100)    # no checkpoint: restart from 0
    chaos = TrainChaosConfig(seed=3, crash_steps=[1, 2, 3])
    sup = TrainSupervisor(ARCH, checkpoint_dir=str(tmp_path), steps=6,
                          max_restarts=1, chaos=chaos, batch=B, seq=S,
                          run=run, log_every=100)
    with pytest.raises(TrainStepCrashError):
        sup.run()
    assert sup.restarts == 1
    assert len(sup.attempts) == 2
    assert all("error" in a for a in sup.attempts)


def test_chaos_plan_is_deterministic():
    cfg = TrainChaosConfig.parse("nan=2,slow=1,spike=1,preempt=9,seed=13",
                                 seed=7)
    assert cfg.seed == 13 and cfg.preempt == 9    # inline seed wins
    a = TrainChaosMonkey(cfg, total_steps=16)
    b = TrainChaosMonkey(cfg, total_steps=16)
    assert a.nan_steps == b.nan_steps and len(a.nan_steps) == 2
    assert a.slow_steps == b.slow_steps and a.spike_steps == b.spike_steps
    # fire-once: operational faults fire exactly once per monkey
    step = next(iter(a.slow_steps))
    a.cfg.slow_ms = 0.0
    a.on_step(step)
    a.on_step(step)
    assert sum(e["kind"] == "slow" for e in a.events) == 1
    with pytest.raises(ValueError, match="unknown train chaos knob"):
        TrainChaosConfig.parse("explode=1")


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------
def test_main_resume_past_end_prints_nothing_to_do(tmp_path, monkeypatch,
                                                   capsys):
    """--resume with start >= --steps used to crash formatting a None
    final_loss; now it reports cleanly."""
    run = RunConfig(total_steps=3, learning_rate=3e-4, microbatches=1,
                    checkpoint_every=2)
    train(ARCH, steps=3, batch=B, seq=S, run=run,
          checkpoint_dir=str(tmp_path), log_every=100)
    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", ARCH, "--steps", "3", "--batch", str(B),
        "--seq", str(S), "--checkpoint-dir", str(tmp_path), "--resume"])
    # main() turns on the persistent compilation cache for its process;
    # turn it off again, so that later tests in this process compile afresh
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        train_mod.main()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    outp = capsys.readouterr().out
    assert "nothing to do: resumed at step 3" in outp
    assert "final loss" not in outp
