"""Blocked RG-LRU linear-recurrence Pallas kernel (recurrentgemma hot loop).

h_t = a_t * h_{t-1} + b_t, with (a, b) precomputed by the gate projections.
Grid: (batch, time_blocks) — time minor (sequential); the hidden state is
carried across time blocks in VMEM scratch, so the recurrence's working set
never leaves VMEM within a block (the PIM-style locality win; the jnp
associative_scan materializes log-depth intermediates in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import import_pallas, import_pallas_tpu

pl = import_pallas()
pltpu = import_pallas_tpu()


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                                  # (bt, d) fp32
    b = b_ref[0]
    h = h_ref[...]                                # (d,)

    def step(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = jax.lax.dynamic_update_index_in_dim(out, h, t, 0)
        return h, out

    out0 = jnp.zeros_like(b)
    h, out = jax.lax.fori_loop(0, block_t, step, (h, out0))
    h_ref[...] = h
    o_ref[0, ...] = out


def rglru_scan_kernel(a: jax.Array, b: jax.Array, *, block_t: int = 128,
                      interpret: bool = True) -> jax.Array:
    """a, b: (B, T, D) fp32 -> h sequence (B, T, D)."""
    B, T, D = a.shape
    bt = min(block_t, T)
    assert T % bt == 0
    nt = T // bt
    kernel = functools.partial(_rglru_kernel, block_t=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, bt, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bt, D), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, D), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((D,), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, b)
