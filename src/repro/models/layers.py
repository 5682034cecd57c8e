"""Shared layers: RMSNorm, RoPE, gated MLP, and the attention dispatch layer.

``chunked_attention`` is the single attention entry point for every model
family and the serving engine. It dispatches between two backends
(``REPRO_ATTN_IMPL=pallas|jnp|auto``; auto = compiled Pallas on TPU, jnp
elsewhere):

* **pallas** — the ``repro.kernels.flash_attention`` TPU kernels: GQA-native
  prefill/train forward and its backward kernel under a custom VJP, and a
  decode-specialized kernel streaming the ring KV cache.
* **jnp** — the chunked online-softmax implementation below (same flash
  structure in pure jnp); the oracle the Pallas path is tested against.

Both keep the working set per step at one (q-chunk x kv-chunk) tile — the
HBM->VMEM data-movement-minimization analogue of processing-using-memory.

The decode hot path additionally supports a Proteus-quantized KV cache
(``REPRO_KV_QUANT``, :class:`QKVCache` below): k/v may arrive as block-scaled
int8 / packed-int4 codes + per-row scales, consumed directly by the Pallas
decode kernel (in-kernel dequant) and dequantized up front on the jnp path.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.compat import in_manual_context, shard_map
from repro.core.mimdram import constrain, current_plan
from repro.core.proteus import required_bits_float
from repro.kernels.common import (attn_impl, kv_page_size, kv_quant_mode,
                                  on_tpu, pack_int4, pad_axis, pad_positions,
                                  unpack_int4)
from repro.kernels.flash_attention.kernel import fwd_block_q
from repro.kernels.flash_attention.ops import (flash_attention_gqa_bwd,
                                               flash_attention_gqa_fwd,
                                               flash_decode,
                                               flash_decode_paged,
                                               flash_decode_paged_quant,
                                               flash_decode_quant)
from repro.launch import tracing

# Pallas decode kernel: the whole (G, S) query block stays VMEM-resident
# across the kv stream, so the positional path only routes to it while the
# q-block row count is small; beyond this, forced-pallas calls fall back to
# the jnp path (with a trace-time warning) off the TPU and raise on it.
PALLAS_DECODE_MAX_Q_ROWS = 1024

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Basic ops
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(dt)


def dense(x: jax.Array, w: jax.Array, subscripts: str) -> jax.Array:
    """einsum in compute dtype with fp32 accumulation."""
    y = jnp.einsum(subscripts, x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def gated_mlp(x: jax.Array, wi_gate: jax.Array, wi_up: jax.Array,
              wo: jax.Array) -> jax.Array:
    """SwiGLU MLP: (silu(x W_g) * x W_u) W_o; activations TP-sharded on d_ff."""
    g = dense(x, wi_gate, "bsd,df->bsf")
    u = dense(x, wi_up, "bsd,df->bsf")
    h = jax.nn.silu(g) * u
    h = constrain(h, "act_batch", "act_seq", "act_ff")
    return dense(h, wo, "bsf,fd->bsd")


def yarn_rotary(head_dim: int, theta: float, factor: float,
                original_max_positions: int, beta_fast: float,
                beta_slow: float, attention_factor: float = 0.0
                ) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim // 2,), attention factor) of YaRN, as
    transformers' ``_compute_yarn_parameters`` gives them (``truncate``
    rounding): the interpolated and extrapolated frequencies blended by a
    linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context. The attention factor
    scales cos and sin; 0 takes 0.1 ln(factor) + 1."""
    half = head_dim // 2
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                          / head_dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)

    def dim_of(rotations):
        return (head_dim * math.log(original_max_positions
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1)
    keep = 1 - ramp                         # share of the extrapolation
    inv_freq = interpolation * (1 - keep) + extrapolation * keep
    scale = attention_factor or 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(scale)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
         inv_freq: Optional[np.ndarray] = None,
         scale: float = 1.0) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D); positions: (S,) or (B, S).
    ``inv_freq`` replaces theta's frequencies and ``scale`` multiplies cos
    and sin (YaRN: :func:`yarn_rotary`)."""
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freq = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    # broadcast to (..., S, 1, half) over heads
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


def slot_isfinite(logits: jax.Array) -> jax.Array:
    """Per-slot finite guard for the fused decode scan: ``(B, ..., V)``
    logits -> ``(B,)`` bool, True iff every logit the slot produced this
    step is finite. Slots are independent through the whole decode stack
    (per-sequence positions, per-slot cache rows), so a non-finite row
    indicts exactly one slot and the engine can quarantine it without
    touching the rest of the batch."""
    B = logits.shape[0]
    return jnp.all(jnp.isfinite(logits.reshape(B, -1)), axis=-1)


def ring_cache_update(cache: jax.Array, new: jax.Array,
                      slot: jax.Array) -> jax.Array:
    """Write ``new`` (B, S, ...) into ``cache`` (B, T, ...) at per-row slots.

    Each sequence in the batch carries its own write position (continuous
    batching: slots are refilled independently). ``slot`` is (B,) for the
    per-token decode path (S == 1 — a per-row dynamic_update_slice, the
    original fused-decode write) or (B, S) explicit slots for a speculative
    verify block (a scatter; block positions may wrap mod T, and the S
    consecutive slots are distinct as long as S <= T).
    """
    s = slot.astype(jnp.int32)
    if new.shape[1] == 1 and s.ndim == 1:
        zeros = (jnp.int32(0),) * (cache.ndim - 2)

        def row(c, x, si):
            return jax.lax.dynamic_update_slice(
                c, x.astype(c.dtype), (si,) + zeros)

        return jax.vmap(row)(cache, new, s)
    if s.ndim == 1:
        s = s[:, None]
    b = jnp.arange(cache.shape[0], dtype=jnp.int32)[:, None]
    return cache.at[b, s].set(new.astype(cache.dtype))


def ring_cache_store(k: jax.Array, total: int, cache_len: int) -> jax.Array:
    """Place the last min(total, cache_len) positions of ``k`` (B, S, ...)
    into a cache_len-slot ring buffer so that slot ``p % cache_len`` holds
    position ``p`` — the invariant decode's ring write (``ring_cache_update``
    at ``pos % T``) relies on. Unused slots are zero-filled."""
    S, T = total, cache_len
    keep = min(S, T)
    kk = k[:, S - keep:]
    if T > keep:
        kk = jnp.pad(kk, ((0, 0), (0, T - keep)) + ((0, 0),) * (k.ndim - 2))
    shift = (S - keep) % T
    return jnp.roll(kk, shift, axis=1) if shift else kk


def ring_position_ids(batch: int, total: int, cache_len: int) -> jax.Array:
    """(batch, cache_len) absolute positions matching ``ring_cache_store``'s
    layout after a ``total``-token prefill; empty slots hold -1 (masked)."""
    keep = min(total, cache_len)
    ids = jnp.concatenate([
        jnp.arange(total - keep, total, dtype=jnp.int32),
        jnp.full((cache_len - keep,), -1, jnp.int32)])
    shift = (total - keep) % cache_len
    if shift:
        ids = jnp.roll(ids, shift)
    return jnp.tile(ids[None], (batch, 1))


# ---------------------------------------------------------------------------
# Proteus-quantized KV cache (REPRO_KV_QUANT=off|int8|int4|auto)
#
# Decode is memory-bandwidth-bound: every generated token streams the whole
# ring KV cache through the decode kernel, so kv bytes/token — not FLOPs —
# sets tokens/s. The Proteus runtime's narrow-value machinery applied to that
# stream: K/V rows are stored as block-scaled int8 (or nibble-packed int4)
# codes with one fp32 scale per (slot, kv head) row (block = head_dim), and
# the Pallas decode kernel dequantizes per tile in VMEM — HBM reads only the
# narrow codes. ``auto`` keeps int8 storage but picks the quantization grid
# per tensor data-aware via ``required_bits_float`` (uniform-magnitude
# tensors take the int4 grid; spiky ones the int8 grid) — the DBPE analogue,
# transparent to every call site.
# ---------------------------------------------------------------------------
# auto-mode error target (per-element quant error vs block mean |x|): the
# narrowest crest (uniform magnitudes, crest = 1) needs ceil(log2(1/(2r)+1))+1
# bits, so r = 0.1 is the loosest target at which the int4 grid (4 bits,
# crest <= 1.4) is ever feasible while gaussian-crest (~3.5) rows still
# escalate to the int8 grid.
KV_QUANT_RTOL = 0.1

# Documented worst |output| deviation vs the bf16 cache for unit-normal
# q/k/v — the single source of truth for the pytest gate, the bench/CI gate
# (benchmarks/bench_kernels.py), and the README error-budget table. ``auto``
# stores int8-width codes, so it inherits the int8 budget.
KV_ERROR_BUDGET = {"int8": 0.05, "int4": 0.25, "auto": 0.05}


@jax.tree_util.register_pytree_node_class
@dataclass
class QKVCache:
    """Quantized KV-cache leaf: ``codes`` int8 ``(..., T, H, Dc)`` with
    ``Dc = D`` (int8/auto) or ``D // 2`` (nibble-packed int4), and ``scale``
    fp32 ``(..., T, H)``. Static shapes and a flat two-leaf pytree, so the
    fused ``lax.scan`` decode loop, donation, and the engine's slot swaps
    work unchanged."""

    codes: jax.Array
    scale: jax.Array

    def tree_flatten(self):
        return (self.codes, self.scale), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def kv_len(self) -> int:
        return self.codes.shape[-3]

    @property
    def num_heads(self) -> int:
        return self.codes.shape[-2]


def _kv_qmax(x: jax.Array, mode: str):
    if mode == "int8":
        return 127.0
    if mode == "int4":
        return 7.0
    # auto: data-aware narrow-value detection over head_dim rows (the quant
    # blocks); <= 4 consequential bits -> the int4 grid is safe.
    bits = required_bits_float(x, block=x.shape[-1], rtol=KV_QUANT_RTOL)
    return jnp.where(bits <= 4, 7.0, 127.0)


def kv_quantize(x: jax.Array, mode: str) -> QKVCache:
    """Symmetric per-row quantization of ``x`` (..., T, H, D)."""
    xf = x.astype(jnp.float32)
    qmax = _kv_qmax(xf, mode)
    maxabs = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(maxabs == 0, 1.0, maxabs / qmax)
    codes = jnp.clip(jnp.round(xf / scale[..., None]),
                     -qmax - 1, qmax).astype(jnp.int8)
    if mode == "int4":
        codes = pack_int4(codes)
    return QKVCache(codes, scale.astype(jnp.float32))


def kv_dequantize(qkv: QKVCache, head_dim: int, dtype) -> jax.Array:
    """jnp fallback dequant (non-TPU / forced-jnp backends): the Pallas
    decode kernel dequantizes in VMEM instead and never calls this."""
    codes = qkv.codes
    if codes.shape[-1] != head_dim:
        codes = unpack_int4(codes)
    return (codes.astype(jnp.float32)
            * qkv.scale[..., None]).astype(dtype)


def maybe_kv_quantize(x: jax.Array, mode: Optional[str] = None):
    """Quantize a cache-layout tensor unless the mode is ``off``."""
    mode = kv_quant_mode() if mode is None else mode
    return x if mode == "off" else kv_quantize(x, mode)


# ---------------------------------------------------------------------------
# Paged KV cache (REPRO_KV_PAGES=<tokens-per-page>, block-table layout)
#
# The contiguous per-slot ring cache statically over-allocates HBM: every
# slot owns cache_len rows whether its prompt filled them or not — the
# "statically over-allocated resources" problem the paper's MIMDRAM line
# solves in DRAM by allocating per-kernel. The paged layout splits the cache
# into fixed-size pages in ONE pool array plus a per-slot int32 page table
# (static shapes, so the fused lax.scan decode, donation and the engine's
# slot swaps are unchanged); the serving engine pairs it with a free-list
# allocator and hash-consed prefix sharing so only pages actually holding
# tokens occupy distinct HBM. Physical page 0 is a reserved trash page:
# retired/unused table rows point at it, so stale slots keep decoding
# harmlessly and shared pages are never overwritten by a redirected write.
# ---------------------------------------------------------------------------
TRASH_PAGE = 0


@jax.tree_util.register_pytree_node_class
@dataclass
class PagedKVCache:
    """Paged KV-cache leaf: ``pages`` is the pool — a plain array
    ``(..., P, H, ps, D)`` or a :class:`QKVCache` of pooled codes
    ``(..., P, H, ps, Dc)`` + scales ``(..., P, H, ps)`` — head-major, so a
    page of one kv head is a contiguous ``(ps, D)`` tile for the kernel —
    and ``table`` int32 ``(..., B, NP)`` maps each slot's logical page to a
    physical pool index (0 = trash page). Leading ``...`` dims (layers /
    groups) are shared between pool and table so ``lax.scan`` over layers
    unstacks both together."""

    pages: Any
    table: jax.Array

    def tree_flatten(self):
        return (self.pages, self.table), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def _pool(self) -> jax.Array:
        return (self.pages.codes if isinstance(self.pages, QKVCache)
                else self.pages)

    @property
    def page_size(self) -> int:
        return self._pool.shape[-2]

    @property
    def num_pages(self) -> int:
        """Physical pool capacity P (including the trash page)."""
        return self._pool.shape[-4]

    @property
    def kv_len(self) -> int:
        """Logical per-slot capacity T = NP * page_size."""
        return self.table.shape[-1] * self.page_size

    @property
    def num_heads(self) -> int:
        return self._pool.shape[-3]


def aligned_cache_len(n: int, page_size: Optional[int] = None) -> int:
    """Round a cache length up to the page multiple in paged mode (identity
    otherwise) so per-slot capacity is a whole number of pages and the ring
    invariant ``slot = pos % T`` maps cleanly onto logical pages."""
    ps = kv_page_size() if page_size is None else page_size
    return n if ps <= 0 else -(-n // ps) * ps


def _identity_table(batch: int, n_pages: int, lead: Tuple[int, ...] = ()):
    """Default table: slot b's logical page i -> physical page 1 + b*NP + i
    (page 0 stays the trash page) — standalone decode and spec dryruns work
    without an allocator."""
    t = (1 + jnp.arange(batch * n_pages, dtype=jnp.int32)).reshape(
        batch, n_pages)
    return jnp.broadcast_to(t, lead + (batch, n_pages))


def paged_from_ring(ring, page_size: Optional[int] = None,
                    mode: Optional[str] = None) -> "PagedKVCache":
    """Re-layout a ring cache ``(B, T, H, D)`` as a :class:`PagedKVCache`:
    slot b's pages land at pool rows 1 + b*NP .. with the identity table."""
    ps = kv_page_size() if page_size is None else page_size
    mode = kv_quant_mode() if mode is None else mode
    B, T = ring.shape[:2]
    npg = T // ps
    q = ring if mode == "off" else kv_quantize(ring, mode)

    def to_pool(x):              # (B, T, H, ...) -> (1 + B*NP, H, ps, ...)
        px = jnp.swapaxes(x.reshape((B * npg, ps) + x.shape[2:]), 1, 2)
        return jnp.concatenate([jnp.zeros_like(px[:1]), px])

    pages = (QKVCache(to_pool(q.codes), to_pool(q.scale))
             if isinstance(q, QKVCache) else to_pool(q))
    return PagedKVCache(pages, _identity_table(B, npg))


def paged_gather(cache: "PagedKVCache"):
    """Dense ``(B, T, H, D)`` view (plain or :class:`QKVCache`) of a paged
    cache: one pool gather per call — the jnp fallback path; the Pallas
    paged kernel streams pages via the table instead and never calls this."""
    table = cache.table                              # (B, NP)
    B = table.shape[0]

    def g(pool):
        x = jnp.swapaxes(pool[table], 2, 3)          # (B, NP, ps, H, ...)
        return x.reshape((B, -1) + x.shape[3:])

    if isinstance(cache.pages, QKVCache):
        return QKVCache(g(cache.pages.codes), g(cache.pages.scale))
    return g(cache.pages)


def kv_cache_init(shape: Tuple[int, ...], dtype,
                  mode: Optional[str] = None,
                  page_size: Optional[int] = None):
    """Zeros KV-cache leaf for logical shape ``(..., B, T, H, D)``: a plain
    array when quantization is off, else a :class:`QKVCache`; either is
    wrapped in a :class:`PagedKVCache` (identity table, +1 trash page) when
    paged mode is on."""
    mode = kv_quant_mode() if mode is None else mode
    ps = kv_page_size() if page_size is None else page_size
    if ps > 0:
        lead, (B, T, H, D) = shape[:-4], shape[-4:]
        assert T % ps == 0, (
            f"paged cache_len {T} not a multiple of page size {ps}; "
            "size caches via aligned_cache_len")
        npg = T // ps
        pages = _kv_zeros(lead + (B * npg + 1, H, ps, D), dtype, mode)
        return PagedKVCache(pages, _identity_table(B, npg, lead))
    return _kv_zeros(shape, dtype, mode)


def _kv_zeros(shape: Tuple[int, ...], dtype, mode: str):
    if mode == "off":
        return jnp.zeros(shape, dtype)
    dc = shape[-1] // 2 if mode == "int4" else shape[-1]
    return QKVCache(jnp.zeros(shape[:-1] + (dc,), jnp.int8),
                    jnp.zeros(shape[:-1], jnp.float32))


def kv_cache_axes(axes: Tuple, mode: Optional[str] = None,
                  page_size: Optional[int] = None):
    """Logical-axis tree matching :func:`kv_cache_init`'s structure."""
    mode = kv_quant_mode() if mode is None else mode
    ps = kv_page_size() if page_size is None else page_size
    if ps > 0:
        lead = tuple(axes[:-4])
        # pool has no batch axis (pages are shared across slots): replicate
        # it; the table keeps the slot axis.
        pool = lead + ("cache_pages", axes[-2], "cache_page_seq", axes[-1])
        pages = pool if mode == "off" else QKVCache(pool, pool[:-1])
        return PagedKVCache(pages, lead + (axes[-4], "cache_pages"))
    if mode == "off":
        return axes
    return QKVCache(tuple(axes), tuple(axes[:-1]))


def kv_cache_store(k: jax.Array, total: int, cache_len: int,
                   mode: Optional[str] = None,
                   page_size: Optional[int] = None):
    """Prefill store: ring-place, (maybe) quantize, (maybe) page."""
    mode = kv_quant_mode() if mode is None else mode
    ps = kv_page_size() if page_size is None else page_size
    ring = ring_cache_store(k, total, cache_len)
    if ps > 0:
        return paged_from_ring(ring, ps, mode)
    return ring if mode == "off" else kv_quantize(ring, mode)


def kv_cache_update(cache, new: jax.Array, slot: jax.Array,
                    mode: Optional[str] = None):
    """Ring/paged cache write: quantizes ``new`` (B, S, H, D) row-wise before
    the write when the cache is quantized; paged caches scatter each row into
    ``pool[table[b, slot // ps], :, slot % ps]`` (rows whose table entry is the
    trash page collide there harmlessly). ``slot`` is (B,) for the per-token
    decode path (S == 1) or (B, S) for a speculative verify block."""
    if isinstance(cache, PagedKVCache):
        ps = cache.page_size
        s = slot.astype(jnp.int32)
        b = jnp.arange(cache.table.shape[-2], dtype=jnp.int32)
        if new.shape[1] == 1 and s.ndim == 1:
            phys = cache.table[b, s // ps]           # (B,)
            off = s % ps

            def wr(pool, x):                         # x: (B, 1, H, ...)
                return pool.at[phys, :, off].set(x[:, 0].astype(pool.dtype))
        else:
            if s.ndim == 1:
                s = s[:, None]
            phys = cache.table[b[:, None], s // ps]  # (B, S)
            off = s % ps

            def wr(pool, x):                         # x: (B, S, H, ...)
                return pool.at[phys, :, off].set(x.astype(pool.dtype))

        if isinstance(cache.pages, QKVCache):
            mode = kv_quant_mode() if mode is None else mode
            q = kv_quantize(new, mode)
            return PagedKVCache(QKVCache(wr(cache.pages.codes, q.codes),
                                         wr(cache.pages.scale, q.scale)),
                                cache.table)
        return PagedKVCache(wr(cache.pages, new), cache.table)
    if not isinstance(cache, QKVCache):
        return ring_cache_update(cache, new, slot)
    mode = kv_quant_mode() if mode is None else mode
    q = kv_quantize(new, mode)
    return QKVCache(ring_cache_update(cache.codes, q.codes, slot),
                    ring_cache_update(cache.scale, q.scale, slot))


def kv_cache_len(cache) -> int:
    """Logical cache capacity T of a (stacked / quantized / paged) leaf."""
    if isinstance(cache, PagedKVCache):
        return cache.kv_len
    return (cache.codes if isinstance(cache, QKVCache) else cache).shape[-3]


def kv_cast(cache, dtype):
    """``cache.astype(dtype)`` for plain arrays; identity for QKVCache (the
    attention dispatch consumes codes+scales directly); recurses into the
    pool for paged caches."""
    if isinstance(cache, PagedKVCache):
        return PagedKVCache(kv_cast(cache.pages, dtype), cache.table)
    return cache if isinstance(cache, QKVCache) else cache.astype(dtype)


def stack_trees(xs):
    """Stack a list of identically-structured pytrees leaf-wise (the
    unrolled-layers analogue of ``lax.scan`` ys stacking)."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *xs)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (GQA, causal / sliding-window / cross)
# ---------------------------------------------------------------------------
def _attn_tile(qc, kc, vc, mask, m, l, acc, scale, cap):
    """One (q-tile, kv-tile) online-softmax update.

    qc: (B, Cq, K, G, D)   kc/vc: (B, Ck, K, D)
    mask: (Cq, Ck) bool, or (B, Cq, Ck) for per-sequence positions
    m, l: (B, K, G, Cq)    acc: (B, Cq, K, G, D)
    """
    mb = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc,
                   preferred_element_type=jnp.float32) * scale
    if cap > 0:
        s = softcap(s, cap)
    s = jnp.where(mb, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked tiles: exp(NEG_INF - NEG_INF) would be 1
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(mb, p, 0.0)
    alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - safe_m))
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vc.dtype), vc,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
    return m_new, l_new, acc_new


def _require_kernel(what: str) -> None:
    """On the TPU the Pallas kernels are the attention path: a call they
    cannot take is an error there, never a silent swap to the jnp path."""
    if on_tpu():
        raise NotImplementedError(
            f"chunked_attention: {what} exceeds PALLAS_DECODE_MAX_Q_ROWS="
            f"{PALLAS_DECODE_MAX_Q_ROWS}; no Pallas kernel takes it on the TPU")


def _per_batch_shard(kernel, *args, shared: Tuple[int, ...] = ()):
    """``kernel(*args)``, run once per batch shard of the plan's mesh.

    XLA cannot partition a compiled Pallas (Mosaic) kernel, so under a plan
    whose mesh has several devices the call goes inside a ``shard_map``:
    the leading (batch) axis of every arg and output is split over the mesh
    axes the plan gives ``act_batch``, and the args at the ``shared``
    indices (the paged pools, which have no batch axis) are replicated.
    Every row of attention depends only on its own batch row, so the result
    is the unsharded call's.
    """
    plan = current_plan()
    if (plan is None or plan.mesh is None or plan.mesh.size == 1
            or in_manual_context()):
        return kernel(*args)
    B = args[0].shape[0]
    batch = P(plan.spec("act_batch", dims=(B,))[0])
    specs = tuple(P() if i in shared else batch for i in range(len(args)))
    return shard_map(kernel, mesh=plan.mesh, in_specs=specs,
                     out_specs=batch, check_vma=False)(*args)


def _decode_positions(q_offset, kv_positions, kv_valid_len, B, S, T):
    """Per-sequence (B, S) q positions and (B, T) kv positions for the
    decode kernels; kv_valid_len folds into the -1 (masked) sentinel."""
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    q_pos = q_off[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    if kv_positions is None:
        kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    else:
        kv_pos = jnp.broadcast_to(kv_positions.astype(jnp.int32), (B, T))
    if kv_valid_len is not None:
        valid = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (B,))
        kv_pos = jnp.where(kv_pos < valid[:, None], kv_pos, -1)
    return q_pos, kv_pos


def chunked_attention(
    q: jax.Array,                 # (B, S, Hq, D)
    k: Any,                       # (B, T, Hkv, D) array, or QKVCache
    v: Any,                       # (B, T, Hkv, D) array, or QKVCache
    *,
    causal: bool = True,
    window: int = 0,              # >0: sliding-window attention
    q_offset: Any = 0,            # position of q[0]: int, traced scalar, or (B,)
    kv_positions: Optional[jax.Array] = None,  # (T,) or (B, T) abs positions
    kv_valid_len: Any = None,     # mask kv positions >= this: scalar or (B,)
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    attn_softcap: float = 0.0,
    block_skip: bool = False,     # beyond-paper: skip fully-masked kv tiles
    impl: Optional[str] = None,   # 'pallas' | 'jnp' | None = REPRO_ATTN_IMPL
) -> jax.Array:
    """Tiled attention with online softmax; O(Cq*Ck) live scores memory.

    Backend dispatch: see the module docstring. Non-block-multiple S/T are
    padded to the chunk multiple (padded kv carries -1 positions / a static
    valid length, so it is masked) and the output sliced back — odd prompt
    lengths are legal on every path.
    """
    paged = isinstance(k, PagedKVCache)
    B, S, Hq, D = q.shape
    if paged:
        assert isinstance(v, PagedKVCache), "k paged but v is not"
        quant = isinstance(k.pages, QKVCache)
        T, Hkv = k.kv_len, k.num_heads
    else:
        quant = isinstance(k, QKVCache)
        if quant:
            assert isinstance(v, QKVCache), "k quantized but v is not"
            T, Hkv = k.kv_len, k.num_heads
        else:
            _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    cq = min(chunk_q, S)
    ck = min(chunk_kv, T)
    backend = attn_impl() if impl is None else impl

    # paged KV (block tables): the Pallas paged kernel streams pages straight
    # from the pool via scalar-prefetch page-table lookups; every other path
    # first gathers the slot's pages into the dense (B, T, H, D) layout.
    if paged:
        if backend == "pallas" and S * G <= PALLAS_DECODE_MAX_Q_ROWS:
            q_pos, kv_pos = _decode_positions(q_offset, kv_positions,
                                              kv_valid_len, B, S, T)
            kw = dict(causal=causal, window=window, softcap=attn_softcap)
            if quant:
                return _per_batch_shard(
                    partial(flash_decode_paged_quant, **kw),
                    q, k.pages.codes, k.pages.scale, v.pages.codes,
                    v.pages.scale, k.table, q_pos, kv_pos,
                    shared=(1, 2, 3, 4))
            return _per_batch_shard(partial(flash_decode_paged, **kw), q,
                                    k.pages, v.pages, k.table, q_pos, kv_pos,
                                    shared=(1, 2))
        if backend == "pallas":
            _require_kernel(f"paged attention with {S * G} q-block rows")
        k = paged_gather(k)
        v = paged_gather(v)

    # training/prefill path: flash custom-VJP (O(S) activation memory)
    if (not quant and kv_positions is None and kv_valid_len is None and S > 1
            and isinstance(q_offset, int) and q_offset == 0):
        if backend == "pallas":
            cq = fwd_block_q(cq, G)
        Sp = -(-S // cq) * cq
        Tp = -(-T // ck) * ck
        qp = pad_axis(q, 1, Sp)
        kp = pad_axis(k, 1, Tp)
        vp = pad_axis(v, 1, Tp)
        kv_len = 0 if Tp == T else T
        qg = qp.reshape(B, Sp, Hkv, G, D)
        if backend == "pallas":
            out = flash_attention_pallas(qg, kp, vp, causal, window,
                                         attn_softcap, cq, ck, kv_len, None)
        else:
            out = flash_attention_jnp(qg, kp, vp, causal, window, attn_softcap,
                                      cq, ck, block_skip, kv_len)
        out = out.reshape(B, Sp, Hq, D)
        return out[:, :S] if Sp != S else out

    # decode path (small q against a possibly-ring KV cache): the Pallas
    # decode kernel takes per-sequence q positions + per-slot kv positions
    # (-1 = empty slot; kv_valid_len folds into the same sentinel).
    if backend == "pallas":
        if S * G <= PALLAS_DECODE_MAX_Q_ROWS:
            q_pos, kv_pos = _decode_positions(q_offset, kv_positions,
                                              kv_valid_len, B, S, T)
            kw = dict(causal=causal, window=window, softcap=attn_softcap,
                      block_k=ck)
            if quant:
                # in-kernel dequant: HBM reads only codes + scales
                return _per_batch_shard(
                    partial(flash_decode_quant, **kw), q, k.codes, k.scale,
                    v.codes, v.scale, q_pos, kv_pos)
            return _per_batch_shard(partial(flash_decode, **kw), q, k, v,
                                    q_pos, kv_pos)
        _require_kernel(f"positional attention with {S * G} q-block rows")
        warnings.warn(
            f"chunked_attention: positional call with {S * G} q-block rows "
            f"exceeds PALLAS_DECODE_MAX_Q_ROWS={PALLAS_DECODE_MAX_Q_ROWS}; "
            "falling back to the jnp path", stacklevel=2)

    # generic jnp fallback (batched positions, any q length); quantized kv
    # is dequantized up front here — only the Pallas decode kernel reads the
    # narrow codes directly.
    if quant:
        k = kv_dequantize(k, D, q.dtype)
        v = kv_dequantize(v, D, q.dtype)
    S0 = S
    Sp = -(-S // cq) * cq
    Tp = -(-T // ck) * ck
    if Tp != T:
        if kv_positions is None:
            kv_positions = jnp.arange(T, dtype=jnp.int32)
        kv_positions = pad_positions(kv_positions, Tp)
        k = pad_axis(k, 1, Tp)
        v = pad_axis(v, 1, Tp)
        T = Tp
    if Sp != S:
        q = pad_axis(q, 1, Sp)
        S = Sp
    nq, nk = S // cq, T // ck

    qg = q.reshape(B, nq, cq, Hkv, G, D)
    kg = k.reshape(B, nk, ck, Hkv, D)
    vg = v.reshape(B, nk, ck, Hkv, D)
    # per-sequence positions (continuous batching: every slot has its own pos)
    batched = (getattr(q_offset, "ndim", 0) >= 1
               or (kv_positions is not None and kv_positions.ndim == 2)
               or getattr(kv_valid_len, "ndim", 0) >= 1)
    if batched:
        q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
        if kv_positions is None:
            kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        else:
            kv_pos = jnp.broadcast_to(kv_positions.astype(jnp.int32), (B, T))
        kv_pos = kv_pos.reshape(B, nk, ck)
        kv_valid = (None if kv_valid_len is None
                    else jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32),
                                          (B,)))
    elif kv_positions is None:
        kv_pos = jnp.arange(T, dtype=jnp.int32).reshape(nk, ck)
    else:
        kv_pos = kv_positions.astype(jnp.int32).reshape(nk, ck)

    def q_chunk(i):
        qc = qg[:, i].astype(jnp.float32)  # fp32 q tile for stable softmax
        if batched:
            q_pos = (q_off[:, None] + i * cq
                     + jnp.arange(cq, dtype=jnp.int32)[None, :])   # (B, cq)
        else:
            q_pos = q_offset + i * cq + jnp.arange(cq, dtype=jnp.int32)

        def kv_step(carry, j):
            m, l, acc = carry
            kc = kg[:, j]
            vc = vg[:, j]
            if batched:
                kp = kv_pos[:, j]                                  # (B, ck)
                mask = kp[:, None, :] >= 0                         # (B, cq, ck)
                if causal:
                    mask &= kp[:, None, :] <= q_pos[:, :, None]
                if window > 0:
                    mask &= kp[:, None, :] > q_pos[:, :, None] - window
                if kv_valid is not None:
                    mask &= kp[:, None, :] < kv_valid[:, None, None]
            else:
                kp = kv_pos[j]
                mask = jnp.ones((cq, ck), dtype=bool)
                mask &= kp[None, :] >= 0
                if causal:
                    mask &= kp[None, :] <= q_pos[:, None]
                if window > 0:
                    mask &= kp[None, :] > q_pos[:, None] - window
                if kv_valid_len is not None:
                    mask &= kp[None, :] < kv_valid_len
            m, l, acc = _attn_tile(qc.astype(k.dtype), kc, vc, mask, m, l, acc,
                                   scale, attn_softcap)
            return (m, l, acc), None

        m0 = jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, Hkv, G, D), jnp.float32)

        if block_skip and causal and kv_positions is None and kv_valid_len is None:
            # beyond-paper optimization: statically bound the kv range per
            # q-tile; tiles wholly above the causal diagonal are never built.
            hi = 0
            if isinstance(q_offset, int):
                hi = (q_offset + (i + 1) * cq + ck - 1) // ck
                lo = 0
                if window > 0:
                    lo = max(0, (q_offset + i * cq - window) // ck)
                idx = jnp.arange(lo, max(hi, lo + 1), dtype=jnp.int32)
                (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), idx)
            else:
                (m, l, acc), _ = jax.lax.scan(
                    kv_step, (m0, l0, a0), jnp.arange(nk, dtype=jnp.int32))
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), jnp.arange(nk, dtype=jnp.int32))
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l.transpose(0, 3, 1, 2)[..., None]
        return out.astype(q.dtype)  # (B, cq, Hkv, G, D)

    if nq == 1:
        out = q_chunk(0).reshape(B, S, Hq, D)
    else:
        outs = jax.lax.map(q_chunk, jnp.arange(nq, dtype=jnp.int32))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, D)  # (nq,B,cq,...)
    return out[:, :S0] if S != S0 else out


# ---------------------------------------------------------------------------
# Flash attention with custom VJP (recompute-from-lse backward).
#
# The default autodiff of the chunked forward saves the fp32 (m, l, acc)
# carries of every kv step — O(S*T/ck) live fp32 — which DAMOV flagged as the
# dominant train-time memory term. The flash backward stores only (out, lse)
# and rebuilds p per tile: activation memory drops to O(S) per layer.
# ---------------------------------------------------------------------------
def _kv_range(i, cq, ck, T, causal, window, block_skip):
    """Static kv-chunk range [lo, hi) that q-chunk i can attend to."""
    nk = T // ck
    if not block_skip:
        return 0, nk
    hi = min(nk, (i * cq + cq + ck - 1) // ck) if causal else nk
    lo = max(0, (i * cq - window) // ck) if window > 0 else 0
    return lo, max(hi, lo + 1)


def _flash_fwd_impl(q, k, v, causal, window, attn_softcap, cq, ck,
                    block_skip=False, kv_len=0):
    """Returns (out, lse). q:(B,S,Hkv,G,D) k/v:(B,T,Hkv,D).
    kv_len > 0 masks kv positions >= kv_len (pad-to-block-multiple support).

    block_skip=True (beyond-paper): q-chunks are Python-unrolled so each
    scans only its statically-reachable kv chunks — causal attention does
    ~(nq+1)/2nq of the full-pair work in both FLOPs and tile traffic.
    """
    B, S, Hkv, G, D = q.shape
    T = k.shape[1]
    nq, nk = S // cq, T // ck
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, nq, cq, Hkv, G, D)
    kg = k.reshape(B, nk, ck, Hkv, D)
    vg = v.reshape(B, nk, ck, Hkv, D)

    def q_chunk(i, lo=0, hi=nk):
        qc = qg[:, i]
        q_pos = i * cq + jnp.arange(cq, dtype=jnp.int32)

        def kv_step(carry, j):
            m, l, acc = carry
            mask = _flash_mask(q_pos, j, ck, causal, window, kv_len)
            m, l, acc = _attn_tile(qc, kg[:, j], vg[:, j], mask, m, l, acc,
                                   scale, attn_softcap)
            return (m, l, acc), None

        m0 = jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, Hkv, G, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(lo, hi, dtype=jnp.int32))
        lsafe = jnp.where(l == 0.0, 1.0, l)
        out = (acc / lsafe.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
        lse = jnp.where(m <= NEG_INF / 2, NEG_INF, m + jnp.log(lsafe))
        return out, lse                          # (B,cq,K,G,D), (B,K,G,cq)

    if nq == 1:
        out, lse = q_chunk(0, *_kv_range(0, cq, ck, T, causal, window,
                                         block_skip))
        return out.reshape(B, S, Hkv, G, D), lse[..., None, :]
    if block_skip:
        outs, lses = [], []
        for i in range(nq):
            lo, hi = _kv_range(i, cq, ck, T, causal, window, True)
            o, l = q_chunk(i, lo, hi)
            outs.append(o)
            lses.append(l)
        out = jnp.stack(outs, 1).reshape(B, S, Hkv, G, D)
        lse = jnp.stack(lses, 3)
        return out, lse
    outs, lses = jax.lax.map(q_chunk, jnp.arange(nq, dtype=jnp.int32))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, Hkv, G, D)
    lse = jnp.moveaxis(lses, 0, 3)               # (B,K,G,nq,cq)
    return out, lse


def _flash_mask(q_pos, j, ck, causal, window, kv_len=0):
    k_pos = j * ck + jnp.arange(ck, dtype=jnp.int32)
    mask = jnp.ones((q_pos.shape[0], ck), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if kv_len > 0:
        mask &= (k_pos < kv_len)[None, :]
    return mask


def _flash_tile_scores(qc, kc, scale, cap):
    s_raw = jnp.einsum("bqkgd,bskd->bkgqs", qc.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
    if cap > 0:
        return cap * jnp.tanh(s_raw / cap), s_raw
    return s_raw, s_raw


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_jnp(q, k, v, causal=True, window=0, attn_softcap=0.0,
                        cq=512, ck=1024, block_skip=False, kv_len=0):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, attn_softcap, cq, ck,
                             block_skip, kv_len)
    return out


def _flash_vjp_fwd(q, k, v, causal, window, attn_softcap, cq, ck, block_skip,
                   kv_len):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, attn_softcap, cq, ck,
                               block_skip, kv_len)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, window, attn_softcap, cq, ck, block_skip, kv_len,
                   res, do):
    with tracing.scope("attn_bwd"):
        return _flash_bwd_impl(causal, window, attn_softcap, cq, ck,
                               block_skip, kv_len, res, do)


def _flash_bwd_impl(causal, window, attn_softcap, cq, ck, block_skip, kv_len,
                    res, do):
    q, k, v, out, lse = res
    B, S, Hkv, G, D = q.shape
    T = k.shape[1]
    nq, nk = S // cq, T // ck
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, nq, cq, Hkv, G, D)
    kg = k.reshape(B, nk, ck, Hkv, D)
    vg = v.reshape(B, nk, ck, Hkv, D)
    og = out.reshape(B, nq, cq, Hkv, G, D)
    dog = do.reshape(B, nq, cq, Hkv, G, D)
    # delta = rowsum(do * o): (B,nq,cq,K,G) -> align to scores (B,K,G,cq)
    delta = (dog.astype(jnp.float32) * og.astype(jnp.float32)).sum(-1)

    def q_chunk(i, carry, lo=0, hi=nk):
        dk_acc, dv_acc = carry
        qc = qg[:, i]
        doc = dog[:, i].astype(jnp.float32)
        lse_i = lse[:, :, :, i]                                # (B,K,G,cq)
        dlt_i = delta[:, i].transpose(0, 2, 3, 1)              # (B,K,G,cq)
        q_pos = i * cq + jnp.arange(cq, dtype=jnp.int32)

        def kv_step(carry2, j):
            dq_c, dk_a, dv_a = carry2
            kc, vc = kg[:, j], vg[:, j]
            mask = _flash_mask(q_pos, j, ck, causal, window, kv_len)
            s, s_raw = _flash_tile_scores(qc, kc, scale, attn_softcap)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lse_i[..., None])                  # (B,K,G,q,s)
            p = jnp.where(mask[None, None, None], p, 0.0)
            dv_t = jnp.einsum("bkgqs,bqkgd->bskd", p, doc)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", doc,
                            vc.astype(jnp.float32))
            ds = p * (dp - dlt_i[..., None])
            if attn_softcap > 0:
                t = jnp.tanh(s_raw / attn_softcap)
                ds = ds * (1.0 - t * t)
            dq_t = jnp.einsum("bkgqs,bskd->bqkgd", ds,
                              kc.astype(jnp.float32)) * scale
            dk_t = jnp.einsum("bkgqs,bqkgd->bskd", ds,
                              qc.astype(jnp.float32)) * scale
            dk_a = jax.lax.dynamic_update_slice(
                dk_a, (jax.lax.dynamic_slice(
                    dk_a, (0, j * ck, 0, 0), (B, ck, Hkv, D)) + dk_t),
                (0, j * ck, 0, 0))
            dv_a = jax.lax.dynamic_update_slice(
                dv_a, (jax.lax.dynamic_slice(
                    dv_a, (0, j * ck, 0, 0), (B, ck, Hkv, D)) + dv_t),
                (0, j * ck, 0, 0))
            return (dq_c + dq_t, dk_a, dv_a), None

        dq0 = jnp.zeros((B, cq, Hkv, G, D), jnp.float32)
        (dq_c, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc),
            jnp.arange(lo, hi, dtype=jnp.int32))
        return dq_c, (dk_acc, dv_acc)

    dkv0 = (jnp.zeros((B, T, Hkv, D), jnp.float32),
            jnp.zeros((B, T, Hkv, D), jnp.float32))

    if block_skip:
        carry = dkv0
        dq_chunks = []
        for i in range(nq):
            lo, hi = _kv_range(i, cq, ck, T, causal, window, True)
            dq_c, carry = q_chunk(i, carry, lo, hi)
            dq_chunks.append(dq_c)
        dk, dv = carry
        dq = jnp.stack(dq_chunks, 1).reshape(B, S, Hkv, G, D)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))

    def scan_q(carry, i):
        dq_c, carry = q_chunk(i, carry)
        return carry, dq_c

    (dk, dv), dqs = jax.lax.scan(scan_q, dkv0,
                                 jnp.arange(nq, dtype=jnp.int32))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, S, Hkv, G, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention_jnp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Pallas-backed flash attention (train/prefill): the Pallas forward kernel
# saves (out, lse), and the Pallas backward kernel recomputes each score tile
# from them in VMEM and writes dq, dk and dv. Training runs both on the TPU
# with O(S) activation memory and no score tiles in HBM. The residuals are
# private to this pair; flash_attention_jnp keeps its own.
# ---------------------------------------------------------------------------
def _flash_pallas_fwd_impl(q, k, v, causal, window, attn_softcap, cq, ck,
                           kv_len, interpret):
    """q:(B,S,Hkv,G,D) k/v:(B,T,Hkv,D); S % cq == 0, T % ck == 0 (caller
    pads); kv_len > 0 masks kv positions >= kv_len. Returns (out, lse) with
    lse (B,Hkv,G,S) f32, as the kernel wrapper gives it."""
    B, S, Hkv, G, D = q.shape
    T = k.shape[1]
    ar = jnp.arange(T, dtype=jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.where(ar < kv_len, ar, -1) if kv_len
                              else ar, (B, T))
    kw = dict(causal=causal, window=window, softcap=attn_softcap,
              block_q=cq, block_k=ck, interpret=interpret)
    out, lse = _per_batch_shard(
        lambda q, k, v, kv_pos: flash_attention_gqa_fwd(
            q, k, v, kv_positions=kv_pos, **kw),
        q.reshape(B, S, Hkv * G, D), k, v, kv_pos)
    return out.reshape(B, S, Hkv, G, D), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_pallas(q, k, v, causal=True, window=0, attn_softcap=0.0,
                           cq=512, ck=1024, kv_len=0, interpret=None):
    out, _ = _flash_pallas_fwd_impl(q, k, v, causal, window, attn_softcap,
                                    cq, ck, kv_len, interpret)
    return out


def _flash_pallas_vjp_fwd(q, k, v, causal, window, attn_softcap, cq, ck,
                          kv_len, interpret):
    out, lse = _flash_pallas_fwd_impl(q, k, v, causal, window, attn_softcap,
                                      cq, ck, kv_len, interpret)
    return out, (q, k, v, out, lse)


def _flash_pallas_vjp_bwd(causal, window, attn_softcap, cq, ck, kv_len,
                          interpret, res, do):
    q, k, v, out, lse = res
    B, S, Hkv, G, D = q.shape
    kw = dict(causal=causal, window=window, softcap=attn_softcap,
              kv_len=kv_len, interpret=interpret)
    rows = (B, S, Hkv * G, D)
    with tracing.scope("attn_bwd"):
        dq, dk, dv = _per_batch_shard(
            partial(flash_attention_gqa_bwd, **kw), q.reshape(rows), k, v,
            out.reshape(rows), lse, do.reshape(rows))
    return dq.reshape(q.shape), dk, dv


flash_attention_pallas.defvjp(_flash_pallas_vjp_fwd, _flash_pallas_vjp_bwd)


def attention_ref(q, k, v, *, causal=True, window=0, q_offset=0,
                  kv_valid_len=None, attn_softcap=0.0):
    """Naive quadratic oracle (tests only)."""
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    qf = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    s = s / math.sqrt(D)
    if attn_softcap > 0:
        s = softcap(s, attn_softcap)
    qp = q_offset + jnp.arange(S)
    kp = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window > 0:
        mask &= kp[None, :] > qp[:, None] - window
    if kv_valid_len is not None:
        mask &= kp[None, :] < kv_valid_len
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Cross-entropy
# ---------------------------------------------------------------------------
def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross-entropy. logits (B,S,V) fp-any; labels (B,S)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
