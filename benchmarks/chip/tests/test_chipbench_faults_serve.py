"""A serving run with its timed path broken underneath comes out not
correct, and so does the float8 control in the program's place.

Smoke widths spread the logits far less than the cell's widths do, so the
limit here is the smoke-size one: above the sound runs' widest gap on these
seeds (at most 1.5e-3 on the CPU) and below the control's (5.2e-3 at least).
"""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import CHAT, OFFLINE, run, tiny_cell
from benchmarks.chip import check, harness
from benchmarks.chip.runtime import Runtime

LIMIT = {"max_logit_gap": 3e-3}


@pytest.fixture(autouse=True)
def _kv_layout(monkeypatch):
    """A serving run sets the program's KV layout knob from its traffic
    file; it is put back after each test."""
    monkeypatch.setenv("REPRO_KV_PAGES", "0")


def cell(workload=CHAT):
    c = tiny_cell(workload)
    c.limits = {"limits": dict(LIMIT)}
    return c


def alter_token(eng):
    """A served token altered where it is produced."""
    gen, vocab = eng._generate, eng.model.cfg.vocab_size

    def broken(*args):
        out = list(gen(*args))
        out[5] = out[5].at[:, 1].set((out[5][:, 1] + 1) % vocab)
        return tuple(out)

    eng._generate = broken


def stale_cache(eng):
    """The decode chunk returns its state unchanged: the cache it was
    given, not the one it wrote."""
    gen = eng._generate

    def broken(params, cache, *rest):
        keep = jax.tree_util.tree_map(jnp.copy, cache)
        return (keep,) + tuple(gen(params, cache, *rest))[1:]

    eng._generate = broken


def half_batch(eng):
    """Half of the decode batch left out: the second half of the slots
    emits its fed token again instead of decoding."""
    gen = eng._generate

    def broken(*args):
        out = list(gen(*args))
        half = out[5].shape[0] // 2
        out[5] = out[5].at[half:].set(out[5][half:, :1])
        return tuple(out)

    eng._generate = broken


@pytest.mark.parametrize("workload", [CHAT, OFFLINE])
def test_sound_run_is_correct(workload):
    out = run(cell(workload), seed=3)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["metrics"]["setup_s"]["unit"] == "s"
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("workload,fault", [
    (CHAT, alter_token), (CHAT, stale_cache),
    (OFFLINE, half_batch)])             # a full batch: every slot in use
def test_broken_path_is_not_correct(workload, fault):
    out = run(cell(workload), seed=3, fault=fault)
    assert not out["correct"], out["checks"]


def test_float8_control_is_not_correct():
    for seed in (1, 2, 3):
        rt = Runtime(time.perf_counter(), jax.devices()[:1], False)
        _, nums = harness._drive(cell(), seed=seed, seconds=2.0, rt=rt,
                                 devices=jax.devices()[:1], control="fp8")
        assert check.verdict({"max_logit_gap": nums["max_logit_gap"]},
                             LIMIT)[0]
        assert not check.verdict(
            {"max_logit_gap": nums["control_max_logit_gap"]}, LIMIT)[0], nums
