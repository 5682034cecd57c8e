"""A training run with its step broken underneath comes out not correct, and
so does the float8 control in the program's place.

Smoke-size limits, set on the CPU between the sound runs' readings on these
seeds (loss 1.8e-5, gradient norm 1.9e-3, update norm 4.6e-4 at most) and
the control's (7.6e-5 and 7.2e-3) and the faults' (gradient 0.23 and more).
"""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import TRAIN, run, tiny_cell
from benchmarks.chip import check, harness
from benchmarks.chip.runtime import Runtime

LIMIT = {"loss_gap": 4e-5, "grad_gap": 4e-3, "update_gap": 5e-2}


def cell():
    c = tiny_cell(TRAIN)
    c.limits = {"limits": dict(LIMIT)}
    return c


def unchanged(step):
    """The step returns its state unchanged."""
    def broken(params, opt, batch):
        keep = jax.tree_util.tree_map(jnp.copy, (params, opt))
        met = step(params, opt, batch)[2]
        return keep[0], keep[1], met
    return broken


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def broken(params, opt, batch):
        return step(params, opt, {k: v[:v.shape[0] // 2]
                                  for k, v in batch.items()})
    return broken


def altered_loss(step):
    """The step's answer, its loss, altered where it is produced."""
    def broken(params, opt, batch):
        p, o, met = step(params, opt, batch)
        return p, o, dict(met, loss=met["loss"] * 1.001)
    return broken


def test_sound_run_is_correct():
    out = run(cell(), seed=3)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered_loss])
def test_broken_step_is_not_correct(fault):
    out = run(cell(), seed=3, fault=fault)
    assert not out["correct"], out["checks"]


def test_float8_control_is_not_correct():
    for seed in (1, 2, 3):
        rt = Runtime(time.perf_counter(), jax.devices()[:1], False)
        _, nums = harness._drive(cell(), seed=seed, seconds=1.0, rt=rt,
                                 devices=jax.devices()[:1], control="fp8")
        assert check.verdict({k: nums[k] for k in LIMIT}, LIMIT)[0], nums
        assert not check.verdict({k: nums["control_" + k] for k in LIMIT},
                                 LIMIT)[0], nums
