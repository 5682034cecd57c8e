"""Narrow-value detection + int4 packing Pallas kernels (Proteus DBPE).

The thesis' Dynamic Bit-Precision Engine scans operand rows for leading
zeros/ones to find the narrowest safe width. TPU form: a per-block maximum-
magnitude scan (``required_bits``) feeding the representation selector, and
an exact nibble-packing kernel for the int4 wire format used by quantized
collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import import_pallas

pl = import_pallas()


def _bits_kernel(x_ref, o_ref):
    """Per-block required two's-complement width for int32 data."""
    x = x_ref[...]
    m = jnp.abs(x.astype(jnp.float32)).max()
    # bits = ceil(log2(m+1)) + 1 (sign); m=0 -> 1
    bits = jnp.where(
        m == 0, 1.0, jnp.ceil(jnp.log2(m + 1.0)) + 1.0)
    o_ref[0] = bits.astype(jnp.int32)


def required_bits_kernel(x: jax.Array, block: int = 256, *,
                         interpret: bool = True) -> jax.Array:
    """x: int32 flat (N,), N % block == 0 -> per-block widths (N//block,)."""
    n = x.shape[0]
    assert n % block == 0
    nb = n // block
    return pl.pallas_call(
        _bits_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nb,), jnp.int32),
        interpret=interpret,
        name="narrow_required_bits",
    )(x)


def _pack4_kernel(v_ref, o_ref):
    v = v_ref[...]
    lo = (v[0::2] & 0x0F).astype(jnp.uint8)
    hi = (v[1::2] & 0x0F).astype(jnp.uint8)
    o_ref[...] = (lo | (hi << 4)).astype(jnp.int8)


def _unpack4_kernel(p_ref, o_ref):
    pu = p_ref[...].astype(jnp.uint8)
    lo = (pu & 0x0F).astype(jnp.int8)
    hi = ((pu >> 4) & 0x0F).astype(jnp.int8)
    sx = lambda t: jnp.where(t >= 8, t - 16, t).astype(jnp.int8)
    out = jnp.stack([sx(lo), sx(hi)], axis=-1).reshape(-1)
    o_ref[...] = out


def pack_int4_kernel(v: jax.Array, block: int = 512, *,
                     interpret: bool = True) -> jax.Array:
    """v: int8 codes in [-8, 7], flat (N,), N even -> packed (N//2,) int8."""
    n = v.shape[0]
    assert n % 2 == 0
    b = min(block, n)
    assert n % b == 0
    return pl.pallas_call(
        _pack4_kernel,
        grid=(n // b,),
        in_specs=[pl.BlockSpec((b,), lambda i: (i,))],
        out_specs=pl.BlockSpec((b // 2,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n // 2,), jnp.int8),
        interpret=interpret,
        name="narrow_pack_int4",
    )(v)


def unpack_int4_kernel(p: jax.Array, block: int = 256, *,
                       interpret: bool = True) -> jax.Array:
    n = p.shape[0]
    b = min(block, n)
    assert n % b == 0
    return pl.pallas_call(
        _unpack4_kernel,
        grid=(n // b,),
        in_specs=[pl.BlockSpec((b,), lambda i: (i,))],
        out_specs=pl.BlockSpec((2 * b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((2 * n,), jnp.int8),
        interpret=interpret,
        name="narrow_unpack_int4",
    )(p)
