"""Flash attention Pallas TPU kernels: tiled online softmax, GQA-native.

Processing-using-memory principle on the HBM->VMEM hierarchy: the score tile
lives only in VMEM; scores never round-trip to HBM (the jnp chunked path
materializes them — this kernel removes the dominant memory-term contribution
found by DAMOV for train/prefill cells, and the KV-stream term for decode).

The online-softmax kernel body serves five forward entry points, and a
second body serves the training backward:

* ``flash_attention_fwd`` — prefill/train. Grid ``(B, Hkv, nq, nk)``, kv
  minor => sequential on TPU; the ``G = Hq // Hkv`` grouped query heads of
  one kv head ride in the q block, so each (k, v) tile is fetched from HBM
  once per kv head, not once per query head (GQA without materializing
  ``jnp.repeat`` copies). Emits ``(out, lse)`` so the backward can run
  without saved score tiles.
* ``flash_attention_bwd`` — train. One kernel (``flash_bwd``) recomputes
  each score tile from ``lse`` and writes dq, dk and dv, with the tiles the
  causal, window and padding masks empty skipped; see its docstring.
* ``flash_decode_fwd`` — serving. Small q (the fused-decode chunk step)
  against the ring KV cache; one q block, the kv stream on the grid.
* ``flash_decode_quant_fwd`` — serving over a Proteus-quantized KV cache:
  the kv BlockSpecs carry int8 (or nibble-packed int4) codes + per-row fp32
  scales, dequantized per tile in VMEM, cutting the dominant decode HBM
  stream ~2x/~4x.
* ``flash_decode_paged_fwd`` / ``flash_decode_paged_quant_fwd`` — the same
  over a paged pool: a scalar-prefetched page table resolves logical page ->
  pool row inside the kv index maps, so the kernel streams exactly the pages
  a slot owns, with no dense gather in HBM.

Tiles are 2-D inside the kernel: a q block is ``R = block_q * G`` rows
(position-major, the G heads of one position adjacent), a kv block is
``bk`` rows. Every block's last two dims are either the array's own or
(8, 128)-divisible, which is what the TPU compiler accepts:

* q positions travel as a ``(B, S*G, 1)`` column, kv positions as
  ``(..., 1, bk)`` rows, so the mask is a plain ``(R, 1) x (1, bk)``
  broadcast;
* quant scales travel as ``(..., 1, bk)`` rows and scale the score and
  probability columns (``q.(c*s) = (q.c)*s``), never needing a transpose.

Masking is position-based everywhere: per-row absolute q positions and
per-slot kv positions (-1 = empty/invalid slot) subsume causal/window/
ring-cache/valid-length and pad-to-block masking in one rule. Fully-masked
kv tiles (max position == -1: dead ring slots, pad tiles, trash pages) are
skipped inside the kernel.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.compat import (import_pallas, pallas_prefetch_grid_spec,
                          pallas_vmem_scratch)
from repro.kernels.common import pad_axis, unpack_int4

pl = import_pallas()

NEG_INF = -1e30


def _tile(ref) -> jax.Array:
    """A block's contents with its leading unit dims dropped: 2-D tile."""
    x = ref[...]
    return x.reshape(x.shape[-2:])


def _tile_update(q, k, v, qp, kp, m_ref, l_ref, acc_ref, *, scale: float,
                 causal: bool, window: int, softcap: float, k_scale=None,
                 v_scale=None):
    """One (R q rows) x (bk kv rows) online-softmax update.

    q: (R, D) f32   k/v: (bk, D) f32 (dequantized codes when scaled)
    qp: (R, 1) int32 absolute q positions (-1 = padded row)
    kp: (1, bk) int32 absolute kv positions (-1 = empty/padded/invalid slot)
    k_scale/v_scale: (1, bk) f32 per-kv-row dequant scales, or None
    m/l: (R, 1) f32 scratch   acc: (R, D) f32 scratch
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if k_scale is not None:
        s = s * k_scale
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    mask = (kp >= 0) & (qp >= 0)                        # (R, bk)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - safe_m))
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _codes(ref, head_dim: int) -> jax.Array:
    """A code tile as f32: int8 codes, or nibble-packed int4 (half width)
    unpacked in registers — HBM only ever saw the packed bytes."""
    c = _tile(ref)
    if c.shape[-1] != head_dim:
        c = unpack_int4(c)
    return c.astype(jnp.float32)


def _flash_kernel(*refs, scale: float, causal: bool, window: int,
                  softcap: float, n_kv: int, head_dim: int, quant: bool,
                  paged: bool, with_lse: bool):
    refs = list(refs[1:] if paged else refs)  # table: read by the index maps
    qp_ref, kp_ref, q_ref = refs[:3]
    if quant:
        kc_ref, ks_ref, vc_ref, vs_ref = refs[3:7]
        refs = refs[7:]
    else:
        k_ref, v_ref = refs[3:5]
        refs = refs[5:]
    o_ref = refs[0]
    lse_ref = refs[1] if with_lse else None
    m_ref, l_ref, acc_ref = refs[-3:]
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kp = _tile(kp_ref)

    # block-sparse kv-tile skip: a tile whose max position is -1 is fully
    # masked (dead ring slots, pad-to-block tiles, trash pages)
    @pl.when(jnp.max(kp) >= 0)
    def _update():
        if quant:
            k, v = _codes(kc_ref, head_dim), _codes(vc_ref, head_dim)
            ks, vs = _tile(ks_ref), _tile(vs_ref)
        else:
            k = _tile(k_ref).astype(jnp.float32)
            v = _tile(v_ref).astype(jnp.float32)
            ks = vs = None
        _tile_update(_tile(q_ref).astype(jnp.float32), k, v, _tile(qp_ref),
                     kp, m_ref, l_ref, acc_ref, scale=scale, causal=causal,
                     window=window, softcap=softcap, k_scale=ks, v_scale=vs)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / lsafe).astype(o_ref.dtype).reshape(
            o_ref.shape)
        if lse_ref is not None:
            m = m_ref[...]
            lse_ref[...] = jnp.where(m <= NEG_INF / 2, NEG_INF,
                                     m + jnp.log(lsafe)).reshape(lse_ref.shape)


def _flash_call(q: jax.Array, k: jax.Array, v: jax.Array,
                q_positions: jax.Array, kv_positions: jax.Array, *,
                k_scale: Optional[jax.Array] = None,
                v_scale: Optional[jax.Array] = None,
                table: Optional[jax.Array] = None, block_q: int,
                block_k: int, with_lse: bool, causal: bool, window: int,
                softcap: float, interpret: bool):
    """Shared launcher. q: (B, Hkv, S, G, D); q_positions: (B, S).

    Contiguous kv: k/v (B, Hkv, T, Dc), scales (B, Hkv, T), kv_positions
    (B, T), ``block_k | T``. Paged kv (``table`` (B, NP)): k/v pools
    (P, Hkv, ps, Dc), scale pools (P, Hkv, ps), kv_positions (B, NP * ps);
    one page per kv step. Returns out (B, Hkv, S, G, D) and, with
    ``with_lse``, lse (B, Hkv, S, G) f32.
    """
    B, Hkv, S, G, D = q.shape
    quant = k_scale is not None
    paged = table is not None
    Dc = k.shape[-1]
    if Dc != D and not interpret:
        # the in-register nibble unpack interleaves lanes, which Mosaic
        # refuses ("unsupported shape cast"); int8 and auto compile
        raise NotImplementedError(
            "the int4 KV-cache decode kernel does not compile for the TPU; "
            "use REPRO_KV_QUANT=int8 or auto there")
    if paged:
        bk = k.shape[2]
        nk = table.shape[1]
    else:
        bk = block_k
        assert k.shape[2] % bk == 0, (k.shape, bk)
        nk = k.shape[2] // bk
    assert S % block_q == 0, (S, block_q)
    nq, R = S // block_q, block_q * G

    # index maps see (b, h, i, j) plus the prefetched table when paged
    def rows(b, h, i, j, *_):
        return (b, h, i, 0)

    if paged:
        def kv_blk(b, h, i, j, tab):
            return (tab[b, j], h, 0, 0)
        sc_blk, sc_shape = kv_blk, (1, 1, 1, bk)
        scales = [s[:, :, None, :] for s in (k_scale, v_scale)] \
            if quant else []
    else:
        def kv_blk(b, h, i, j, *_):
            return (b, h, j, 0)

        def sc_blk(b, h, i, j, *_):
            return (b, h, j, 0, 0)
        sc_shape = (1, 1, 1, 1, bk)
        scales = [s.reshape(B, Hkv, nk, 1, bk) for s in (k_scale, v_scale)] \
            if quant else []

    in_specs = [
        pl.BlockSpec((1, R, 1), lambda b, h, i, j, *_: (b, i, 0)),
        pl.BlockSpec((1, 1, 1, bk), lambda b, h, i, j, *_: (b, j, 0, 0)),
        pl.BlockSpec((1, 1, R, D), rows),
    ]
    kv_spec = pl.BlockSpec((1, 1, bk, Dc), kv_blk)
    operands = [jnp.repeat(q_positions, G, axis=1)[..., None],
                kv_positions.reshape(B, nk, 1, bk),
                q.reshape(B, Hkv, S * G, D)]
    if quant:
        sc_spec = pl.BlockSpec(sc_shape, sc_blk)
        in_specs += [kv_spec, sc_spec, kv_spec, sc_spec]
        operands += [k, scales[0], v, scales[1]]
    else:
        in_specs += [kv_spec, kv_spec]
        operands += [k, v]
    out_specs = [pl.BlockSpec((1, 1, R, D), rows)]
    out_shape = [jax.ShapeDtypeStruct((B, Hkv, S * G, D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, R, 1), rows))
        out_shape.append(jax.ShapeDtypeStruct((B, Hkv, S * G, 1),
                                              jnp.float32))
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(D), causal=causal,
        window=window, softcap=softcap, n_kv=nk, head_dim=D, quant=quant,
        paged=paged, with_lse=with_lse)
    grid_spec = pallas_prefetch_grid_spec()(
        num_scalar_prefetch=1 if paged else 0,
        grid=(B, Hkv, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pallas_vmem_scratch((R, 1), jnp.float32),
            pallas_vmem_scratch((R, 1), jnp.float32),
            pallas_vmem_scratch((R, D), jnp.float32),
        ],
    )
    # the kernel's name in a profile: flash_fwd, flash_decode[_paged][_quant]
    name = ("flash_fwd" if with_lse else "flash_decode"
            + ("_paged" if paged else "") + ("_quant" if quant else ""))
    outs = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret, name=name)(
        *([table] if paged else []), *operands)
    out = outs[0].reshape(B, Hkv, S, G, D)
    if with_lse:
        return out, outs[1].reshape(B, Hkv, S, G)
    return out


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        q_positions: jax.Array, kv_positions: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 128,
                        block_k: int = 128, interpret: bool = True):
    """Prefill/train kernel. Shapes (already padded to block multiples):

    q: (B, Hkv, S, G, D)   k/v: (B, Hkv, T, D)
    q_positions: (B, S) int32   kv_positions: (B, T) int32 (-1 = masked)
    Returns (out (B, Hkv, S, G, D), lse (B, Hkv, S, G) f32).
    """
    S, T = q.shape[2], k.shape[2]
    return _flash_call(q, k, v, q_positions, kv_positions,
                       block_q=min(block_q, S), block_k=min(block_k, T),
                       with_lse=True, causal=causal, window=window,
                       softcap=softcap, interpret=interpret)


# ---------------------------------------------------------------------------
# Backward: one kernel writes dq, dk and dv
# ---------------------------------------------------------------------------
# A TPU v5e core's default scoped VMEM: the backward's tiles and its resident
# dk/dv accumulators are sized to stay under it.
VMEM_BUDGET = 16 * 1024 * 1024
BWD_Q_ROWS = 1024            # q rows (positions x G heads) per block
BWD_KV_ROWS = (512, 256, 128)  # kv rows per block, largest that fits first
FWD_Q_ROWS = 1024            # the forward's q rows per block, at most


def fwd_block_q(block_q: int, G: int) -> int:
    """The training forward's q block: ``block_q`` positions, fewer where
    their ``G`` grouped heads would pass ``FWD_Q_ROWS`` rows (a multiple of
    128 lanes), so that a block's float32 score tiles stay in VMEM."""
    unit = 128 // math.gcd(G, 128)             # positions per 128 lanes
    return min(block_q, max(unit, FWD_Q_ROWS // G // unit * unit))

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _kv_block_range(i, block_q: int, block_k: int, n_kv: int, causal: bool,
                    window: int, kv_len: int):
    """[lo, hi]: the kv blocks that q block ``i`` (positions ``i * block_q``
    on) attends to; kv position t >= ``kv_len`` is padding. Python ints or
    traced scalars (an index map's) alike; the same bounds as
    :func:`_pair_state`'s ``live`` on whole blocks."""
    hi = n_kv - 1
    if causal:
        hi = jnp.minimum(hi, (i * block_q + block_q - 1) // block_k)
    if kv_len:
        hi = jnp.minimum(hi, (kv_len - 1) // block_k)
    lo = 0
    if window > 0:
        lo = jnp.maximum(i * block_q - window + 1, 0) // block_k
    return lo, hi


def _bwd_vmem_bytes(rows: int, block_k: int, T: int, D: int,
                    itemsize: int) -> int:
    """VMEM the backward kernel holds: its (block_k, rows) float32 tiles
    (scores or probabilities, dP, dS, the mask; the compiler reuses the
    rest), double-buffered input blocks, the dq block and the resident dk/dv
    accumulators with their output blocks."""
    tiles = 4 * block_k * rows * 4
    blocks = 2 * ((2 * rows + 2 * block_k) * D * itemsize + 3 * 8 * rows * 4)
    dq = D * rows * (4 + 2 * itemsize)
    dkv = 2 * T * D * (4 + 2 * itemsize)
    return tiles + blocks + dq + dkv


def bwd_blocks(S: int, T: int, G: int, D: int,
               itemsize: int = 2) -> tuple:
    """(block_q, block_k, split) for the backward, from shapes: about
    ``BWD_Q_ROWS`` q rows a block (a multiple of 128 lanes, or all of S);
    the largest kv block of ``BWD_KV_ROWS`` (or all of T) whose tiles and
    resident dk/dv fit ``VMEM_BUDGET``; and tiles the masks cut split 2 x 2
    where the halves stay multiples of 128. Blocks smaller than the
    sequence let the causal and window skips engage, and the split skips
    the empty quarter of a diagonal tile."""
    unit = 128 // math.gcd(G, 128)             # positions per 128 lanes
    bq = max(unit, BWD_Q_ROWS // G // unit * unit)
    bq = S if S <= bq else bq
    for bk in BWD_KV_ROWS:
        bk = T if T <= bk else bk
        Tp = -(-T // bk) * bk
        if _bwd_vmem_bytes(bq * G, bk, Tp, D, itemsize) <= VMEM_BUDGET:
            split = 2 if (bq * G) % 256 == 0 and bk % 256 == 0 else 1
            return bq, bk, split
    raise NotImplementedError(
        f"flash attention backward: {T} kv positions of head_dim {D} do not "
        f"fit the kernel's resident dk/dv accumulators in "
        f"{VMEM_BUDGET >> 20} MiB of VMEM")


def _pair_state(q0, q_len: int, k0, k_len: int, causal: bool, window: int,
                kv_len: int):
    """(live, whole) of the pairs between q positions [q0, q0 + q_len) and
    kv positions [k0, k0 + k_len): whether any survives the masks, and
    whether all do. Python bools where no mask applies."""
    live, whole = True, True
    if causal:
        live = live & (k0 <= q0 + q_len - 1)
        whole = whole & (k0 + k_len - 1 <= q0)
    if window > 0:
        live = live & (k0 + k_len - 1 > q0 - window)
        whole = whole & (k0 > q0 + q_len - 1 - window)
    if kv_len:
        live = live & (k0 < kv_len)
        whole = whole & (k0 + k_len <= kv_len)
    return live, whole


def _when(cond, fn):
    """``fn()`` where ``cond`` holds: at trace time for a Python bool."""
    if cond is True:
        fn()
    elif cond is not False:
        pl.when(cond)(fn)


def _flash_bwd_kernel(qp_ref, q_ref, do_ref, lse_ref, dlt_ref, k_ref, v_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      scale: float, causal: bool, window: int,
                      softcap: float, kv_len: int, block_q: int,
                      block_k: int, G: int, n_q: int, n_kv: int,
                      split: int):
    """Grid (B, Hkv, nq, nk), kv innermost. Scores are laid out transposed,
    (block_k, R), so lse, delta and q positions are lane-dense rows; dV and
    dK are plain products, and dQ takes dS transposed."""
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when((i == 0) & (j == 0))
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def update(masked: bool, q_off: int, q_len: int, k_off: int, k_len: int):
        rows = pl.ds(q_off * G, q_len * G)
        kvr = pl.ds(k_off, k_len)
        q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]    # (r, D)
        k, v = k_ref[0, 0, kvr, :], v_ref[0, 0, kvr, :]        # (bk, D)
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            t = jnp.tanh(s / softcap)
            s = softcap * t
        p = jnp.exp(s - lse_ref[0, 0, :, rows])               # (bk, r)
        if masked:
            kp = j * block_k + k_off + jax.lax.broadcasted_iota(
                jnp.int32, (k_len, 1), 0)
            qp = qp_ref[:, rows]                               # (1, r)
            cuts = ([kp <= qp] if causal else []) + (
                [kp > qp - window] if window > 0 else []) + (
                [kp < kv_len] if kv_len else [])
            p = jnp.where(functools.reduce(jnp.logical_and, cuts), p, 0.0)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt_ref[0, 0, :, rows])
        if softcap > 0:
            ds = ds * (1.0 - t * t)
        # P and dS meet the MXU in the operands' precision
        p = p.astype(do.dtype)
        ds = ds.astype(q.dtype)
        dv_acc[j, kvr, :] += jax.lax.dot_general(
            p, do, _NN, preferred_element_type=jnp.float32)
        dk_acc[j, kvr, :] += jax.lax.dot_general(
            ds, q, _NN, preferred_element_type=jnp.float32)
        dq_acc[rows, :] += jax.lax.dot_general(
            ds, k, _TN, preferred_element_type=jnp.float32)

    # skip every pair the masks empty; apply the mask only where it cuts:
    # a tile it cuts runs as split x split sub-tiles, each skipped, masked
    # or run whole on its own
    q0, k0 = i * block_q, j * block_k
    live, whole = _pair_state(q0, block_q, k0, block_k, causal, window,
                              kv_len)
    _when(live & whole, lambda: update(False, 0, block_q, 0, block_k))
    sq, sk = block_q // split, block_k // split

    def cut_tile():
        for a in range(split):
            for c in range(split):
                lv, wh = _pair_state(q0 + a * sq, sq, k0 + c * sk, sk,
                                     causal, window, kv_len)
                _when(lv & wh, functools.partial(update, False, a * sq, sq,
                                                 c * sk, sk))
                _when(lv & jnp.logical_not(wh), functools.partial(
                    update, True, a * sq, sq, c * sk, sk))

    if whole is not True:
        _when(live & jnp.logical_not(whole), cut_tile)

    @pl.when(j == n_kv - 1)
    def _write_dq():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype).reshape(
            dq_ref.shape)

    @pl.when(i == n_q - 1)
    def _write_dkv():
        dk_ref[0, 0, j] = (dk_acc[j] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, j] = dv_acc[j].astype(dv_ref.dtype)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        do: jax.Array, lse: jax.Array, delta: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, kv_len: int = 0,
                        block_q: int, block_k: int, split: int = 1,
                        interpret: bool = True):
    """Backward of :func:`flash_attention_fwd` at default positions: q row
    s at position s, kv row t at position t, and t >= ``kv_len`` masked
    when ``kv_len`` > 0. Shapes (already padded to block multiples):

    q/do: (B, Hkv, S, G, D)   k/v: (B, Hkv, T, D)
    lse: (B, Hkv, S, G) f32 from the forward; delta: rowsum(do * out), the
    same shape, f32. Returns (dq, dk, dv) in the shapes of q, k, v.

    One kernel, grid ``(B, Hkv, nq, nk)``: a q block's G heads ride in its
    rows as in the forward, so each k/v tile is read once per kv head; dq
    accumulates over the kv blocks in VMEM, and dk/dv over the q blocks in
    VMEM accumulators that stay resident for the whole (b, kv head) and are
    written once. Tiles the masks empty are skipped, and their k/v index
    maps repeat a live block, so a skipped step fetches nothing; a tile the
    masks cut runs as ``split`` x ``split`` sub-tiles, each skipped, masked
    or run whole.
    """
    B, Hkv, S, G, D = q.shape
    T = k.shape[2]
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    nq, nk, R = S // block_q, T // block_k, block_q * G

    def kv_row(b, h, i, j):
        lo, hi = _kv_block_range(i, block_q, block_k, nk, causal, window,
                                 kv_len)
        return jnp.minimum(jnp.maximum(j, lo), hi)

    q_rows = pl.BlockSpec((1, 1, R, D), lambda b, h, i, j: (b, h, i, 0))
    row = pl.BlockSpec((1, 1, 1, R), lambda b, h, i, j: (b, h, 0, i))
    kv = pl.BlockSpec((1, 1, block_k, D),
                      lambda b, h, i, j: (b, h, kv_row(b, h, i, j), 0))
    dkv = pl.BlockSpec((1, 1, nk, block_k, D),
                       lambda b, h, i, j: (b, h, 0, 0, 0))
    kernel = functools.partial(
        _flash_bwd_kernel, scale=1.0 / math.sqrt(D), causal=causal,
        window=window, softcap=softcap, kv_len=kv_len, block_q=block_q,
        block_k=block_k, G=G, n_q=nq, n_kv=nk, split=split)
    grid_spec = pallas_prefetch_grid_spec()(
        num_scalar_prefetch=0,
        grid=(B, Hkv, nq, nk),
        in_specs=[pl.BlockSpec((1, R), lambda b, h, i, j: (0, i)),
                  q_rows, q_rows, row, row, kv, kv],
        out_specs=[q_rows, dkv, dkv],
        scratch_shapes=[
            pallas_vmem_scratch((R, D), jnp.float32),
            pallas_vmem_scratch((nk, block_k, D), jnp.float32),
            pallas_vmem_scratch((nk, block_k, D), jnp.float32),
        ],
    )
    dkv_shape = jax.ShapeDtypeStruct((B, Hkv, nk, block_k, D), k.dtype)
    dq, dk, dv = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, S * G, D), q.dtype),
                   dkv_shape, dkv_shape],
        interpret=interpret, name="flash_bwd")(
        jnp.repeat(jnp.arange(S, dtype=jnp.int32), G)[None],
        q.reshape(B, Hkv, S * G, D), do.reshape(B, Hkv, S * G, D),
        lse.reshape(B, Hkv, 1, S * G), delta.reshape(B, Hkv, 1, S * G), k, v)
    return (dq.reshape(q.shape), dk.reshape(B, Hkv, T, D),
            dv.reshape(B, Hkv, T, D))


def flash_decode_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_positions: jax.Array, kv_positions: jax.Array, *,
                     causal: bool = True, window: int = 0,
                     softcap: float = 0.0, block_k: int = 128,
                     interpret: bool = True) -> jax.Array:
    """Decode kernel: the serving engine's per-chunk inner loop.

    The whole small-q block (one scan step of the fused decode loop) stays in
    VMEM while the ring KV cache streams through.

    q: (B, Hkv, S, G, D) with small S   k/v: (B, Hkv, T, D), T % block_k == 0
    q_positions: (B, S) per-sequence positions (continuous batching)
    kv_positions: (B, T) per-slot ring-cache positions (-1 = empty slot)
    Returns out (B, Hkv, S, G, D).
    """
    return _flash_call(q, k, v, q_positions, kv_positions,
                       block_q=q.shape[2], block_k=min(block_k, k.shape[2]),
                       with_lse=False, causal=causal, window=window,
                       softcap=softcap, interpret=interpret)


def flash_decode_quant_fwd(q: jax.Array, k_codes: jax.Array,
                           k_scale: jax.Array, v_codes: jax.Array,
                           v_scale: jax.Array, q_positions: jax.Array,
                           kv_positions: jax.Array, *, causal: bool = True,
                           window: int = 0, softcap: float = 0.0,
                           block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """Decode kernel over a Proteus-quantized ring KV cache.

    The KV stream — the dominant HBM term of decode — is read as int8 codes
    (optionally nibble-packed int4, ``Dc == D//2``) plus per-(slot, head)
    fp32 scales, and dequantized per tile **in VMEM**: HBM traffic drops
    ~2x (int8) / ~4x (int4) vs the bf16 cache while the math runs fp32.

    q: (B, Hkv, S, G, D)   k/v codes: (B, Hkv, T, Dc) int8
    k/v scale: (B, Hkv, T) fp32      q_positions: (B, S) int32
    kv_positions: (B, T) int32 (-1 = empty slot)  ->  out (B, Hkv, S, G, D).
    """
    return _flash_call(q, k_codes, v_codes, q_positions, kv_positions,
                       k_scale=k_scale, v_scale=v_scale, block_q=q.shape[2],
                       block_k=min(block_k, k_codes.shape[2]),
                       with_lse=False, causal=causal, window=window,
                       softcap=softcap, interpret=interpret)


def flash_decode_paged_fwd(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           q_positions: jax.Array, kv_positions: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           softcap: float = 0.0,
                           interpret: bool = True) -> jax.Array:
    """Paged decode kernel: flash-decoding split-KV over *pages*.

    q: (B, Hkv, S, G, D) with small S   k/v pool: (P, Hkv, ps, D)
    table: (B, NP) int32 physical pool rows (page 0 = trash page)
    q_positions: (B, S)   kv_positions: (B, NP * ps) (-1 = empty/invalid)
    Returns out (B, Hkv, S, G, D).
    """
    return _flash_call(q, k_pages, v_pages, q_positions, kv_positions,
                       table=table, block_q=q.shape[2], block_k=0,
                       with_lse=False, causal=causal, window=window,
                       softcap=softcap, interpret=interpret)


def flash_decode_paged_quant_fwd(q: jax.Array, k_codes: jax.Array,
                                 k_scale: jax.Array, v_codes: jax.Array,
                                 v_scale: jax.Array, table: jax.Array,
                                 q_positions: jax.Array,
                                 kv_positions: jax.Array, *,
                                 causal: bool = True, window: int = 0,
                                 softcap: float = 0.0,
                                 interpret: bool = True) -> jax.Array:
    """Paged decode kernel over Proteus-quantized pages: int8 / nibble-packed
    int4 code pages + per-row fp32 scale pages, dequantized per page in VMEM
    — the narrow-code HBM saving and the paged allocation saving compose.

    q: (B, Hkv, S, G, D)   code pools: (P, Hkv, ps, Dc) int8
    scale pools: (P, Hkv, ps) fp32   table: (B, NP) int32
    q_positions: (B, S)   kv_positions: (B, NP * ps)
    Returns out (B, Hkv, S, G, D).
    """
    return _flash_call(q, k_codes, v_codes, q_positions, kv_positions,
                       k_scale=k_scale, v_scale=v_scale, table=table,
                       block_q=q.shape[2], block_k=0, with_lse=False,
                       causal=causal, window=window, softcap=softcap,
                       interpret=interpret)


def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool = True) -> jax.Array:
    """q: (BH, S, D), k/v: (BH, T, D) -> (BH, S, D). MHA-layout adapter.

    Non-block-multiple S/T are padded to the block multiple (padded kv slots
    carry position -1 and are masked) and the output sliced back.
    """
    BH, S, D = q.shape
    T = k.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, T)
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    q_pos = jnp.where(jnp.arange(Sp) < S, jnp.arange(Sp), -1).astype(jnp.int32)
    kv_pos = jnp.where(jnp.arange(Tp) < T, jnp.arange(Tp), -1).astype(jnp.int32)
    out, _ = flash_attention_fwd(
        pad_axis(q, 1, Sp)[:, None, :, None], pad_axis(k, 1, Tp)[:, None],
        pad_axis(v, 1, Tp)[:, None],
        jnp.tile(q_pos[None], (BH, 1)), jnp.tile(kv_pos[None], (BH, 1)),
        causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=interpret)
    return out[:, 0, :S, 0]
