"""The flash kernels compile for a TPU v5e, at the widths chip_smoke.py runs.

Interpret mode accepts block shapes the TPU compiler refuses, so each kernel
of the serving and training paths is compiled ahead of time here for one
chip of a described (not attached) ``v5e:2x2`` topology: stablelm-1.6b's
attention (32 kv heads, head_dim 64, bf16) with 8 slots of a 576-token cache
and a 512-token prefill; and the training attention's forward and backward
kernels at internlm2-1.8b-6l's per-row shapes (1024 tokens, 16 query heads,
8 kv heads, head_dim 128, bf16); and the held experts' grouped matmul
(``gmm``, ``tgmm``) at Mellum2's expert widths (2304 by 896, 8 experts).
Nothing runs; a refused block shape, a kernel that does not lower, or
scratch that overflows VMEM fails the compile.

The topology is described inside a fixture only: one process at a time may
hold the TPU library, so no module-level code here may touch it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.compat import make_mesh
from repro.configs import ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.kernels.flash_attention import ops
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.layers import chunked_attention, flash_attention_pallas

H, D = 32, 64                  # stablelm-1.6b kv heads and head_dim
B, T, S = 8, 576, 512          # slots, cache length, prefill bucket
PS = 128                       # chip_smoke.py's page size
NP = -(-T // PS)
P = B * NP + 1                 # engine pool: every slot's pages + trash page
TS, TQ, TKV, TD = 1024, 16, 8, 128   # the train cell's row: internlm2-1.8b
# Mellum2's expert layer: d_model, expert width, experts held; and a buffer
# of 4096 routed rows (eight row tiles)
ED, EF, EH, EM = 2304, 896, 8, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # entries compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sd(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _train_attention_grads(q, k, v):
    """dq, dk, dv of the train step's Pallas attention on one row: the
    forward kernel with its chunks, the backward kernel with its own."""
    def loss(q, k, v):
        qg = q.reshape(1, TS, TKV, TQ // TKV, TD)
        return flash_attention_pallas(qg, k, v, True, 0, 0.0, 512, 1024, 0,
                                      False).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _cases(sh):
    bf, i8, i32, f32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
    q1 = _sd(sh, (B, 1, H, D), bf)
    qp, kp = _sd(sh, (B, 1), i32), _sd(sh, (B, T), i32)
    return {
        "prefill_bwd": (
            _train_attention_grads,
            [_sd(sh, (1, TS, TQ, TD), bf), _sd(sh, (1, TS, TKV, TD), bf),
             _sd(sh, (1, TS, TKV, TD), bf)]),
        "prefill": (
            lambda q, k, v: ops.flash_attention_gqa_fwd(
                q, k, v, block_q=512, block_k=1024, interpret=False),
            [_sd(sh, (1, S, H, D), bf)] * 3),
        "decode": (
            lambda q, k, v, a, b: ops.flash_decode(
                q, k, v, a, b, block_k=1024, interpret=False),
            [q1, _sd(sh, (B, T, H, D), bf), _sd(sh, (B, T, H, D), bf),
             qp, kp]),
        "decode_int8": (
            lambda q, kc, ks, vc, vs, a, b: ops.flash_decode_quant(
                q, kc, ks, vc, vs, a, b, block_k=1024, interpret=False),
            [q1, _sd(sh, (B, T, H, D), i8), _sd(sh, (B, T, H), f32),
             _sd(sh, (B, T, H, D), i8), _sd(sh, (B, T, H), f32), qp, kp]),
        "paged": (
            lambda q, k, v, t, a, b: ops.flash_decode_paged(
                q, k, v, t, a, b, interpret=False),
            [q1, _sd(sh, (P, H, PS, D), bf), _sd(sh, (P, H, PS, D), bf),
             _sd(sh, (B, NP), i32), qp, _sd(sh, (B, NP * PS), i32)]),
        "paged_int8": (
            lambda q, kc, ks, vc, vs, t, a, b: ops.flash_decode_paged_quant(
                q, kc, ks, vc, vs, t, a, b, interpret=False),
            [q1, _sd(sh, (P, H, PS, D), i8), _sd(sh, (P, H, PS), f32),
             _sd(sh, (P, H, PS, D), i8), _sd(sh, (P, H, PS), f32),
             _sd(sh, (B, NP), i32), qp, _sd(sh, (B, NP * PS), i32)]),
    }


@pytest.mark.parametrize(
    "name", ["prefill", "decode", "decode_int8", "paged", "paged_int8",
             "prefill_bwd"])
def test_flash_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the kernel streams its operands: no HBM temporaries beyond the
    # layout copies of the wrapper, and its VMEM scratch fitted (or the
    # compile above had failed)
    assert mem.temp_size_in_bytes <= mem.argument_size_in_bytes


def test_attention_compiles_per_batch_shard_on_4_chips(topo, monkeypatch):
    """XLA cannot partition a compiled Pallas kernel: under a 4-chip
    data-parallel plan (what training does on a 4-chip host) the prefill
    kernel, its backward kernel (dq, dk and dv) and the decode kernel must
    compile per batch shard."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    plan = plan_sharding(get_config("pimref-100m", smoke=True),
                         ShapeConfig("t", S, B, "train"), mesh)
    x = _sd(NamedSharding(mesh, PartitionSpec("data")), (B, S, H, D),
            jnp.bfloat16)
    off = jnp.full((B,), S - 1, jnp.int32)

    def step(q, k, v):
        with use_plan(plan):
            grad = jax.grad(lambda q, k, v: chunked_attention(
                q, k, v, impl="pallas").astype(jnp.float32).sum(),
                argnums=(0, 1, 2))
            return grad(q, k, v), chunked_attention(
                q[:, -1:], k, v, q_offset=off, impl="pallas")

    text = jax.jit(step).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "flash_bwd" in text


def test_grouped_matmul_compiles_for_v5e(one_chip, monkeypatch):
    """The held experts' up and down projections with their gradients:
    the forward and input-gradient kernel (``gmm``, both orientations) and
    the weight-gradient kernel (``tgmm``) at Mellum2's widths."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    bf, f32 = jnp.bfloat16, jnp.float32

    def grads(x, w_in, w_out, sizes):
        def loss(x, w_in, w_out):
            h = grouped_matmul(x, w_in, sizes)
            return grouped_matmul(h, w_out, sizes).astype(f32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(x, w_in, w_out)

    args = [_sd(one_chip, (EM, ED), bf), _sd(one_chip, (EH, ED, EF), f32),
            _sd(one_chip, (EH, EF, ED), f32),
            _sd(one_chip, (EH,), jnp.int32)]
    text = jax.jit(grads).lower(*args).compile().as_text()
    # the first forward, both input gradients and both weight gradients
    # (the second forward's value is not needed)
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert "%gmm" in text and "%tgmm" in text


def test_window_attention_with_eight_heads_a_group_compiles_for_v5e(
        one_chip, monkeypatch):
    """Mellum2's training attention on one 4096-token row: 32 query heads
    on 4 kv heads of 128, a 1024-key window; forward and backward through
    ``chunked_attention``, whose forward q block shrinks with the group
    (``fwd_block_q``) so that its score tiles fit the kernel's VMEM."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    bf = jnp.bfloat16
    q = _sd(one_chip, (1, 4096, 32, 128), bf)
    kv = _sd(one_chip, (1, 4096, 4, 128), bf)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: chunked_attention(
            q, k, v, causal=True, window=1024, impl="pallas").astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd" in text
