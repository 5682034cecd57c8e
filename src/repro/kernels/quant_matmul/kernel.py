"""Block-scaled int8 matmul Pallas kernel (Proteus arithmetic engine).

The TPU-native form of Proteus' adaptive-representation arithmetic: weights
are stored as int8 codes with per-(K-block, N-column) fp32 scales — the
block-scaled representation that replaces RBR (DESIGN.md §6). The kernel
dequantizes in VMEM registers (scales applied to the fp32 accumulator), so
HBM traffic for weights is 2x(int8) / 4x(int4-packed) lower than bf16/fp32.

Grid: (m_blocks, n_blocks, k_blocks), k minor (sequential); fp32 accumulator
in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import import_pallas, import_pallas_tpu

pl = import_pallas()
pltpu = import_pallas_tpu()


def _qmm_kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *, n_k_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, bk)
    wq = wq_ref[...].astype(jnp.float32)          # (bk, bn) int8 codes
    scale = scale_ref[...]                        # (1, bn) fp32, this k-block
    part = jax.lax.dot_general(x, wq, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    acc_ref[...] += part * scale

    @pl.when(ki == n_k_blocks - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul_kernel(x: jax.Array, wq: jax.Array, scales: jax.Array, *,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 128, out_dtype=jnp.float32,
                        interpret: bool = True) -> jax.Array:
    """x: (M, K) float; wq: (K, N) int8; scales: (K//block_k, N) fp32."""
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    assert scales.shape == (K // bk, N), (scales.shape, K // bk, N)
    nm, nn, nk = M // bm, N // bn, K // bk

    kernel = functools.partial(_qmm_kernel, n_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(x, wq, scales)
