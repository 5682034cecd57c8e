"""The sparse-expert family (``families/moe.py``) and the Mellum2 train cell:
the tiny cell trains correct against the family's float32 reference and
broken steps do not; the family's layout is the program's at the published
widths, its YaRN table the program's, its work counts the hand-counted
ones and the flash readers' calls; and the dense step is the one the train
cell ran before the layer pattern existed."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import PEAKS, TRAIN, run, tiny_cell
from chipbench_tiny_moe import MOE_TRAIN, tiny_moe_cell
from test_chipbench_faults_train import half_batch, unchanged
from benchmarks.chip import check, data, harness, spec, weights
from benchmarks.chip.record import RunRecord
from benchmarks.chip.roofline import share
from benchmarks.chip.runtime import Runtime, model_config, program_model

MOE = spec.load_family("moe")
# smoke-size limits, set on the CPU above the sound runs' readings on
# seeds 1-6 (loss 1.4e-5, gradient norm 3.5e-3, update norm 1.2e-3 at
# most); the float8 control's read 3.0e-5, 5.7e-3, 2.2e-3 at least
LIMIT = {"loss_gap": 2e-5, "grad_gap": 5e-3, "update_gap": 2e-3}


def cell(**model):
    c = tiny_moe_cell(**model)
    c.limits = {"limits": dict(LIMIT)}
    return c


def test_tiny_cell_trains_correct_against_the_family_reference():
    out = run(cell(), seed=2 ** 31 + 7)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_broken_step_is_not_correct(fault):
    out = run(cell(), seed=2 ** 31 + 7, fault=fault)
    assert not out["correct"], out["checks"]


def test_float8_control_is_not_correct():
    rt = Runtime(time.perf_counter(), jax.devices()[:1], False)
    _, nums = harness._drive(cell(), seed=2, seconds=0.5, rt=rt,
                             devices=jax.devices()[:1], control="fp8")
    assert check.verdict({k: nums[k] for k in LIMIT}, LIMIT)[0], nums
    assert not check.verdict({k: nums["control_" + k] for k in LIMIT},
                             LIMIT)[0], nums


def test_layout_is_the_programs_at_the_published_widths():
    c = spec.load_cell(MOE_TRAIN)
    m = c.config["model"]
    model = program_model(model_config(c.config, MOE))
    weights.check_layout(MOE, m, model.param_specs())
    lay = MOE.layout(m)
    assert lay["blocks/moe/router"][0] == (4, 2304, 64)       # all experts
    assert lay["blocks/moe/wi_gate"][0] == (4, 8, 2304, 896)  # held ones
    assert lay["blocks/attn/w_q"][0] == (4, 2304, 32, 128)    # 4096 wide
    n = sum(int(np.prod(s)) for s, _, _ in lay.values())
    assert n == 340_349_184          # 340.3M, norms included


def test_yarn_table_is_the_programs():
    from repro.models.layers import yarn_rotary

    m = spec.load_cell(MOE_TRAIN).config["model"]
    inv, scale = MOE.yarn(m)
    want = yarn_rotary(m["head_dim"], m["rope_theta"], m["yarn_factor"],
                       m["yarn_original_max_positions"], m["yarn_beta_fast"],
                       m["yarn_beta_slow"], m["yarn_attention_factor"])
    np.testing.assert_allclose(inv, want[0], rtol=1e-6)
    assert scale == want[1] == 1.2772588722239782


def test_training_counts():
    """Mellum2-4l at 4 rows of 4096 tokens: six per weight of every matrix
    product per token (4 layers of attention projections 2304 x 128 x 72,
    a 64-wide router and one expert of 3 x 2304 x 896 at the expected load,
    and a 2304 x 12288 head) and three times the attention forward (three
    window layers at 896.125 keys a query, one full layer at 2048.5)."""
    m = spec.load_cell(MOE_TRAIN).config["model"]
    w = MOE.train_work(m, 4, 4096)
    matmul = 4 * (2304 * 128 * 72 + 2304 * 64 + 3 * 2304 * 896) \
        + 2304 * 12288
    attn = 4 * 32 * 128 * (3 * 896.125 + 2048.5)
    assert w["model_flops_per_token"] == pytest.approx(6 * matmul + 3 * attn)
    assert w["model_flops_per_token"] == pytest.approx(1.0645e9, rel=1e-4)
    # serving counts: a window layer's decode row attends at most 1024 keys
    d = MOE.decode_work(m, 3000, 1, 2)
    assert d["decode_attn_flops"] == 4 * 32 * 128 * (3 * 1024 + 3001)


def test_attention_call_counts_the_mean_layer():
    """One flash call is one layer's forward over the 4 rows: its
    operations the mean over the period's three window layers (896.125
    keys a query) and one full layer (2048.5), its bytes q, k, v and the
    output once with the log-sum-exp, whatever the layer's window."""
    m = spec.load_cell(MOE_TRAIN).config["model"]
    w = MOE.train_work(m, 4, 4096)
    keys = (3 * 896.125 + 2048.5) / 4
    assert w["attn_call_flops"] == pytest.approx(
        4 * 4 * 32 * 128 * 4096 * keys)
    assert w["attn_call_bytes"] == 4 * ((2 * 32 + 2 * 4) * 4096 * 128 * 2
                                        + 4 * 32 * 4096)
    # a stack of full layers alone counts the causal call
    full = dict(m, layer_pattern=["full"])
    assert MOE.train_work(full, 4, 4096)["attn_call_flops"] == \
        pytest.approx(4 * 4 * 32 * 128 * 4096 * 4097 / 2)


def test_flash_roofline_readers_read_the_cell():
    """The flash readers on two traced Mellum2 steps: each kernel's
    executions in the trace times the cell's per-call counts, over the
    kernel's time."""
    c = spec.load_cell(MOE_TRAIN)
    work = MOE.train_work(c.config["model"], 4, 4096)
    kernels = {"pallas:flash_fwd": 0.05, "pallas:flash_bwd": 0.04}
    summary = {"op_in_module_s": {"jit_train_step": kernels},
               "op_in_module_n": {"jit_train_step": {
                   "pallas:flash_fwd": 16.0, "pallas:flash_bwd": 8.0}}}
    rec = RunRecord(kind="train", seconds=30, trace=summary,
                    traced_work={"steps": 2}, work=work, peaks=PEAKS)
    fwd = spec.load_reader("kernel.flash_fwd_roofline")(rec)
    bwd = spec.load_reader("kernel.flash_bwd_roofline")(rec)
    assert fwd == pytest.approx(share(16 * work["attn_call_flops"],
                                      16 * work["attn_call_bytes"], 0.05,
                                      PEAKS)[0])
    assert bwd == pytest.approx(share(2.5 * 8 * work["attn_call_flops"],
                                      2.0 * 8 * work["attn_call_bytes"], 0.04,
                                      PEAKS)[0])


# loss and gradient leaf norms of the tiny dense train cell's step from
# seed 2**31 + 5, as the program gave them before layer patterns and held
# experts (the same optimized program, metadata aside)
DENSE_LOSS = 6.2419657707214355
DENSE_GRADS = {
    "blocks/attn/w_k": 0.002783907577395439,
    "blocks/attn/w_o": 0.25013548135757446,
    "blocks/attn/w_q": 0.0028510757256299257,
    "blocks/attn/w_v": 0.13424056768417358,
    "blocks/ln1": 0.002632205607369542,
    "blocks/ln2": 0.0016908076358959079,
    "blocks/mlp/wi_gate": 0.05201226472854614,
    "blocks/mlp/wi_up": 0.050752297043800354,
    "blocks/mlp/wo": 0.10070335119962692,
    "embed": 0.5224297642707825,
    "final_norm": 0.010873500257730484,
    "head": 0.5125136375427246,
}


def test_dense_step_is_unchanged():
    from repro.configs.base import ShapeConfig
    from repro.core.mimdram import plan_sharding, use_plan
    from repro.launch import mesh as mesh_lib
    from repro.launch.steps import _loss_and_grads

    c = tiny_cell(TRAIN)
    fam, m, t = c.family(), c.config["model"], c.traffic
    model = program_model(model_config(c.config, fam))
    plan = plan_sharding(model.cfg, ShapeConfig(
        "train", seq_len=t["seq_len"], global_batch=t["batch"],
        mode="train"), mesh_lib.make_local_mesh(("data",),
                                                jax.devices()[:1]))
    params = weights.make_init(fam, m, c.config["init"])(
        weights.seed_key(2 ** 31 + 5))
    b = data.make_pool(t, m["vocab_size"], 2 ** 31 + 5, 1)[0]

    def step(params, batch):
        with use_plan(plan):
            return _loss_and_grads(model, params, batch, 1)

    loss, grads, stats = jax.jit(step)(params, {"tokens": b, "labels": b})
    assert stats == {}
    assert float(loss) == pytest.approx(DENSE_LOSS, rel=1e-6)
    got = {k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
           for k, v in weights.flatten(grads).items()}
    assert got.keys() == DENSE_GRADS.keys()
    for k, v in DENSE_GRADS.items():
        assert got[k] == pytest.approx(v, rel=1e-6), k
