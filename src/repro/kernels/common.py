"""Shared kernel utilities: interpret-mode / attention-backend / kv-quant
selection, pad-to-block-multiple helpers (one sentinel convention for every
caller), and the canonical int4 nibble pack/unpack pair."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

ATTN_IMPLS = ("auto", "pallas", "jnp")
KV_QUANT_MODES = ("off", "int8", "int4", "auto")
SPEC_DECODE_MODES = ("off", "ngram", "draft")


def pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to length ``to`` (no-op if already)."""
    if x.shape[axis] == to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pads)


def pad_positions(pos: jax.Array, to: int) -> jax.Array:
    """Pad the last axis of an int position array ((N,) or (B, N)) up to
    ``to`` with the -1 sentinel every mask treats as invalid/empty."""
    if pos.shape[-1] == to:
        return pos
    pads = [(0, 0)] * (pos.ndim - 1) + [(0, to - pos.shape[-1])]
    return jnp.pad(pos, pads, constant_values=-1)


def on_tpu() -> bool:
    """Whether JAX's default backend is the TPU."""
    return jax.default_backend() == "tpu"


def use_interpret() -> bool:
    """Pallas interpret mode: never on the TPU, whatever the environment
    says; elsewhere on unless ``REPRO_PALLAS_INTERPRET=0`` (a switch for
    CPU tests only)."""
    if on_tpu():
        return False
    return os.environ.get("REPRO_PALLAS_INTERPRET", "1") != "0"


def kv_quant_mode() -> str:
    """KV-cache representation for the serving hot path (Proteus runtime).

    ``REPRO_KV_QUANT=off|int8|int4|auto``: ``off`` (default) keeps the bf16
    cache; ``int8``/``int4`` store block-scaled codes + per-row fp32 scales
    (int4 nibble-packed); ``auto`` keeps int8 storage but picks the
    quantization grid per tensor data-aware (narrow-value detection). Read at
    trace time, like ``REPRO_ATTN_IMPL``: set the knob before building jitted
    programs (the launchers plumb ``--kv-quant`` here).
    """
    v = os.environ.get("REPRO_KV_QUANT", "off").lower()
    if v not in KV_QUANT_MODES:
        raise ValueError(
            f"REPRO_KV_QUANT={v!r}: expected one of {KV_QUANT_MODES}")
    return v


# ---------------------------------------------------------------------------
# int4 nibble packing — the one shared implementation (re-exported by
# repro.core.proteus and repro.kernels.narrow_value.ref; the Pallas kernels
# in kernels/narrow_value are the hardware lowering tested against these).
# Pure jnp, so no new version-sensitive Pallas entry point is needed.
# ---------------------------------------------------------------------------
def pack_int4(v: jax.Array) -> jax.Array:
    """Pack int8-held int4 codes (pairs along the last axis) into one int8
    byte each; exact roundtrip with :func:`unpack_int4`."""
    assert v.shape[-1] % 2 == 0, v.shape
    lo = (v[..., 0::2] & 0x0F).astype(jnp.uint8)
    hi = (v[..., 1::2] & 0x0F).astype(jnp.uint8)
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: sign-extended int8 codes in [-8, 7]."""
    pu = p.astype(jnp.uint8)
    lo = (pu & 0x0F).astype(jnp.int8)
    hi = ((pu >> 4) & 0x0F).astype(jnp.int8)
    sx = lambda t: jnp.where(t >= 8, t - 16, t).astype(jnp.int8)
    out = jnp.stack([sx(lo), sx(hi)], axis=-1)
    return out.reshape(p.shape[:-1] + (p.shape[-1] * 2,))


def kv_page_size() -> int:
    """KV-cache page size for the paged (block-table) serving cache.

    ``REPRO_KV_PAGES=<tokens-per-page>``: 0 (default) keeps the contiguous
    per-slot ring cache; a positive value switches ``kv_cache_init`` & co.
    to the :class:`~repro.models.layers.PagedKVCache` layout — one pooled
    page array plus per-slot int32 page tables — which the serving engine
    pairs with a free-list allocator and hash-consed prefix sharing. Read at
    trace time, like ``REPRO_KV_QUANT``: set the knob before building jitted
    programs (the launchers plumb ``--kv-page-size`` here).
    """
    v = os.environ.get("REPRO_KV_PAGES", "0")
    try:
        ps = int(v)
    except ValueError:
        raise ValueError(f"REPRO_KV_PAGES={v!r}: expected a non-negative int")
    if ps < 0:
        raise ValueError(f"REPRO_KV_PAGES={v!r}: expected a non-negative int")
    return ps


def spec_decode_mode() -> str:
    """Speculative-decoding drafter for the fused decode scan.

    ``REPRO_SPEC_DECODE=off|ngram|draft``: ``off`` (default) decodes one token
    per scan iteration; ``ngram`` drafts ``spec_draft_len()`` tokens per
    iteration by device-side bigram suffix lookup over the slot's
    prompt+emitted history and verifies them in one multi-query decode pass;
    ``draft`` drafts with a layer-skip pass through the target model's own
    first layers (self-speculative — the draft shares the engine's cache
    machinery literally: same params, same KV cache). Read at trace time, like
    ``REPRO_ATTN_IMPL``: set the knob before building jitted programs (the
    launchers plumb ``--spec-decode`` here). Greedy output is byte-identical
    with speculation on or off.
    """
    v = os.environ.get("REPRO_SPEC_DECODE", "off").lower()
    if v not in SPEC_DECODE_MODES:
        raise ValueError(
            f"REPRO_SPEC_DECODE={v!r}: expected one of {SPEC_DECODE_MODES}")
    return v


def spec_draft_len() -> int:
    """Static draft length k for speculative decoding (``REPRO_SPEC_K``,
    default 3): each fused-scan iteration verifies a (k+1)-token block —
    the fed token plus k drafts — and commits 1..k+1 tokens."""
    v = os.environ.get("REPRO_SPEC_K", "3")
    try:
        k = int(v)
    except ValueError:
        raise ValueError(f"REPRO_SPEC_K={v!r}: expected a positive int")
    if k < 1:
        raise ValueError(f"REPRO_SPEC_K={v!r}: expected a positive int")
    return k


def spec_draft_layers() -> int:
    """Layer budget for the ``draft`` (layer-skip self-drafting) mode:
    ``REPRO_SPEC_DRAFT_LAYERS`` (default 0 = half the target's layers,
    at least one)."""
    v = os.environ.get("REPRO_SPEC_DRAFT_LAYERS", "0")
    try:
        n = int(v)
    except ValueError:
        raise ValueError(
            f"REPRO_SPEC_DRAFT_LAYERS={v!r}: expected a non-negative int")
    if n < 0:
        raise ValueError(
            f"REPRO_SPEC_DRAFT_LAYERS={v!r}: expected a non-negative int")
    return n


def attn_impl() -> str:
    """Attention backend for ``chunked_attention``: 'pallas' or 'jnp'.

    ``REPRO_ATTN_IMPL=pallas|jnp|auto`` (default auto = compiled Pallas on
    TPU, jnp elsewhere). ``pallas`` off-TPU runs in interpret mode. Read at
    trace time: set the knob before building jitted programs (the launchers
    plumb ``--attn-impl`` here).
    """
    v = os.environ.get("REPRO_ATTN_IMPL", "auto").lower()
    if v not in ATTN_IMPLS:
        raise ValueError(
            f"REPRO_ATTN_IMPL={v!r}: expected one of {ATTN_IMPLS}")
    if v == "auto":
        return "pallas" if on_tpu() else "jnp"
    return v
