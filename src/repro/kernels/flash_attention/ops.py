"""Public wrappers for the flash-attention Pallas kernels.

Model-layout in, model-layout out: q ``(B, S, Hq, D)``, k/v ``(B, T, Hkv, D)``
with ``Hq % Hkv == 0`` (query head ``h`` belongs to kv head ``h // G``). The
wrappers handle the GQA layout transform (no ``jnp.repeat`` of k/v — kv tiles
are shared across the G query heads inside the kernel), default positions,
and pad-to-block-multiple + slice for odd sequence lengths.

The decode wrappers are shape-generic in ``S``: speculative decoding's
draft-verify blocks (``S = k+1`` rows scored in one dispatch) reuse these
exact kernels — position-based causal masking already gives every drafted
row its correct visibility, so verification adds no new kernel variants.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_axis, pad_positions, use_interpret
from repro.kernels.flash_attention.kernel import (bwd_blocks,
                                                 flash_attention_bh,
                                                 flash_attention_bwd,
                                                 flash_attention_fwd,
                                                 flash_decode_fwd,
                                                 flash_decode_paged_fwd,
                                                 flash_decode_paged_quant_fwd,
                                                 flash_decode_quant_fwd)

__all__ = ["flash_attention", "flash_attention_gqa_fwd",
           "flash_attention_gqa_bwd", "flash_decode",
           "flash_decode_quant", "flash_decode_paged",
           "flash_decode_paged_quant", "flash_attention_bh"]


def _default_positions(B: int, n: int) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))


def _to_kernel(q: jax.Array, Hkv: int) -> jax.Array:
    """(B, S, Hq, D) -> the kernels' (B, Hkv, S, G, D) grouped layout."""
    B, S, Hq, D = q.shape
    return q.reshape(B, S, Hkv, Hq // Hkv, D).transpose(0, 2, 1, 3, 4)


def _from_kernel(out: jax.Array) -> jax.Array:
    """(B, Hkv, S, G, D) -> (B, S, Hq, D)."""
    B, Hkv, S, G, D = out.shape
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, Hkv * G, D)


def flash_attention_gqa_fwd(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, softcap: float = 0.0,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    block_q: int = 128, block_k: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pallas forward, any S/T. q: (B, S, Hq, D), k/v: (B, T, Hkv, D).

    Returns (out (B, S, Hq, D), lse (B, Hkv, G, S) f32) — lse is what
    :func:`flash_attention_gqa_bwd` needs instead of saved score tiles.
    """
    if interpret is None:
        interpret = use_interpret()
    B, S, _, _ = q.shape
    _, T, Hkv, _ = k.shape
    bq = min(block_q, S)
    bk = min(block_k, T)
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    q_pos = _default_positions(B, S) if q_positions is None else q_positions
    kv_pos = _default_positions(B, T) if kv_positions is None else kv_positions
    out5, lse = flash_attention_fwd(
        _to_kernel(pad_axis(q, 1, Sp), Hkv),
        pad_axis(k, 1, Tp).transpose(0, 2, 1, 3),
        pad_axis(v, 1, Tp).transpose(0, 2, 1, 3),
        pad_positions(q_pos.astype(jnp.int32), Sp),
        pad_positions(kv_pos.astype(jnp.int32), Tp),
        causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, interpret=interpret)
    return _from_kernel(out5)[:, :S], lse.transpose(0, 1, 3, 2)[..., :S]


def flash_attention_gqa_bwd(
    q: jax.Array, k: jax.Array, v: jax.Array, out: jax.Array,
    lse: jax.Array, do: jax.Array, *, causal: bool = True, window: int = 0,
    softcap: float = 0.0, kv_len: int = 0,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas backward of :func:`flash_attention_gqa_fwd` at default
    positions, any S/T; ``kv_len`` > 0 masks kv positions >= kv_len.

    q/out/do: (B, S, Hq, D)   k/v: (B, T, Hkv, D)   lse: (B, Hkv, G, S) f32,
    as the forward returned it. Returns (dq, dk, dv) in the layouts of q, k
    and v. Block sizes come from the shapes (``bwd_blocks``); S and T are
    padded to them, and padded rows carry zero ``do`` and ``delta``, so
    they add nothing.
    """
    if interpret is None:
        interpret = use_interpret()
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    bq, bk, split = bwd_blocks(S, T, G, D, jnp.dtype(k.dtype).itemsize)
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta.reshape(B, S, Hkv, G).transpose(0, 2, 1, 3)
    dq5, dk, dv = flash_attention_bwd(
        _to_kernel(pad_axis(q, 1, Sp), Hkv),
        pad_axis(k, 1, Tp).transpose(0, 2, 1, 3),
        pad_axis(v, 1, Tp).transpose(0, 2, 1, 3),
        _to_kernel(pad_axis(do, 1, Sp), Hkv),
        pad_axis(lse.transpose(0, 1, 3, 2), 2, Sp), pad_axis(delta, 2, Sp),
        causal=causal, window=window, softcap=softcap,
        kv_len=kv_len or (T if Tp != T else 0), block_q=bq, block_k=bk,
        split=split, interpret=interpret)
    return (_from_kernel(dq5)[:, :S], dk.transpose(0, 2, 1, 3)[:, :T],
            dv.transpose(0, 2, 1, 3)[:, :T])


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                                   "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, S, Hq, D); k/v: (B, T, Hkv, D) with Hq % Hkv == 0."""
    out, _ = flash_attention_gqa_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 q_positions: jax.Array, kv_positions: jax.Array, *,
                 causal: bool = True, window: int = 0, softcap: float = 0.0,
                 block_k: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Decode-step attention against a (ring) KV cache.

    q: (B, S, Hq, D) with small S — 1 for plain decode, or k+1 when the
    serving layer verifies a speculative draft block in one dispatch (each
    drafted row attends causally via its own q_position; no kernel change
    is needed for speculation). k/v: (B, T, Hkv, D) cache, q_positions:
    (B, S) per-sequence absolute positions, kv_positions: (B, T) per-slot
    positions (-1 = empty slot — ring layout and valid-length masking are
    both expressed here).
    """
    if interpret is None:
        interpret = use_interpret()
    _, T, Hkv, _ = k.shape
    bk = min(block_k, T)
    Tp = -(-T // bk) * bk
    out5 = flash_decode_fwd(
        _to_kernel(q, Hkv), pad_axis(k, 1, Tp).transpose(0, 2, 1, 3),
        pad_axis(v, 1, Tp).transpose(0, 2, 1, 3),
        q_positions.astype(jnp.int32),
        pad_positions(kv_positions.astype(jnp.int32), Tp),
        causal=causal, window=window, softcap=softcap, block_k=bk,
        interpret=interpret)
    return _from_kernel(out5)


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       table: jax.Array, q_positions: jax.Array,
                       kv_positions: jax.Array, *, causal: bool = True,
                       window: int = 0, softcap: float = 0.0,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Decode-step attention against a paged (block-table) KV cache.

    q: (B, S, Hq, D) with small S; k/v_pages: (P, Hkv, ps, D) shared page
    pool in storage layout (physical page 0 is the trash page); table:
    (B, NP) int32 mapping each slot's logical pages to pool rows;
    q_positions: (B, S); kv_positions: (B, NP * ps) per-slot positions
    (-1 = empty — ring layout, valid length, and dead pages all live here).
    The kernel streams only the pages each slot owns; no dense gather.
    """
    if interpret is None:
        interpret = use_interpret()
    out5 = flash_decode_paged_fwd(
        _to_kernel(q, k_pages.shape[1]), k_pages, v_pages,
        table.astype(jnp.int32), q_positions.astype(jnp.int32),
        kv_positions.astype(jnp.int32),
        causal=causal, window=window, softcap=softcap, interpret=interpret)
    return _from_kernel(out5)


def flash_decode_paged_quant(q: jax.Array, k_codes: jax.Array,
                             k_scale: jax.Array, v_codes: jax.Array,
                             v_scale: jax.Array, table: jax.Array,
                             q_positions: jax.Array,
                             kv_positions: jax.Array, *, causal: bool = True,
                             window: int = 0, softcap: float = 0.0,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Decode-step attention against a Proteus-quantized paged KV cache.

    q: (B, S, Hq, D); code pools: (P, Hkv, ps, Dc) int8 (Dc = D, or D//2
    when nibble-packed int4); scale pools: (P, Hkv, ps) fp32; table /
    positions as in :func:`flash_decode_paged`. Dequantization happens per
    page in VMEM, so the quantized-HBM and paged-allocation savings compose.
    """
    if interpret is None:
        interpret = use_interpret()
    out5 = flash_decode_paged_quant_fwd(
        _to_kernel(q, k_codes.shape[1]), k_codes, k_scale, v_codes, v_scale,
        table.astype(jnp.int32), q_positions.astype(jnp.int32),
        kv_positions.astype(jnp.int32),
        causal=causal, window=window, softcap=softcap, interpret=interpret)
    return _from_kernel(out5)


def flash_decode_quant(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                       v_codes: jax.Array, v_scale: jax.Array,
                       q_positions: jax.Array, kv_positions: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       softcap: float = 0.0, block_k: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Decode-step attention against a Proteus-quantized (ring) KV cache.

    q: (B, S, Hq, D); codes: (B, T, Hkv, Dc) int8 (Dc = D, or D//2 when
    nibble-packed int4); scales: (B, T, Hkv) fp32 per (slot, kv head) row;
    positions as in :func:`flash_decode`. Dequantization happens inside the
    kernel, per tile in VMEM — HBM reads only the narrow codes + scales.
    """
    if interpret is None:
        interpret = use_interpret()
    _, T, Hkv, _ = k_codes.shape
    bk = min(block_k, T)
    Tp = -(-T // bk) * bk
    out5 = flash_decode_quant_fwd(
        _to_kernel(q, Hkv),
        pad_axis(k_codes, 1, Tp).transpose(0, 2, 1, 3),
        pad_axis(k_scale, 1, Tp).transpose(0, 2, 1),
        pad_axis(v_codes, 1, Tp).transpose(0, 2, 1, 3),
        pad_axis(v_scale, 1, Tp).transpose(0, 2, 1),
        q_positions.astype(jnp.int32),
        pad_positions(kv_positions.astype(jnp.int32), Tp),
        causal=causal, window=window, softcap=softcap, block_k=bk,
        interpret=interpret)
    return _from_kernel(out5)
