"""Pallas flash attention (interpret) vs the jnp oracle: full parity grid.

Covers the production cells the dispatch layer routes to the kernels —
causal x window x GQA groups x softcap x decode-mask (ring cache, per-slot
positions, valid length) x odd lengths — forward and gradient, plus the
``REPRO_ATTN_IMPL`` dispatch itself end to end through a model decode step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import attn_impl, kv_quant_mode
from repro.kernels.flash_attention import flash_attention, flash_attention_bh
from repro.models.layers import (KV_ERROR_BUDGET, attention_ref,
                                 chunked_attention, kv_dequantize,
                                 kv_quantize, ring_cache_store,
                                 ring_position_ids)


def _qkv(rng, B, S, T, Hq, Hkv, D, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return (jax.random.normal(ks[0], (B, S, Hq, D)).astype(dtype),
            jax.random.normal(ks[1], (B, T, Hkv, D)).astype(dtype),
            jax.random.normal(ks[2], (B, T, Hkv, D)).astype(dtype))


# ---------------------------------------------------------------------------
# Forward parity grid: Pallas (interpret) vs the quadratic oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("causal,window,cap", [
    (True, 0, 0.0), (True, 48, 0.0), (True, 0, 20.0), (False, 0, 0.0),
    (True, 48, 20.0),
])
def test_flash_forward_grid(G, causal, window, cap, rng):
    B, S, Hkv, D = 2, 128, 2, 32
    q, k, v = _qkv(rng, B, S, S, G * Hkv, Hkv, D)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        attn_softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("S,T", [(100, 100), (130, 70), (1, 96)])
def test_flash_forward_odd_lengths(S, T, rng):
    """Non-block-multiple S/T: pad + slice, padded kv masked."""
    q, k, v = _qkv(rng, 1, S, T, 4, 2, 32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_bh_odd_length_no_crash(rng):
    """flash_attention_bh: odd S/T pad+slice (was a hard assert) and the
    compat scratch helper (was a None deref without TPU pallas)."""
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 100, 32))
    k = jax.random.normal(ks[1], (2, 100, 32))
    v = jax.random.normal(ks[2], (2, 100, 32))
    out = flash_attention_bh(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True)
    ref = attention_ref(q[:, :, None], k[:, :, None], v[:, :, None],
                        causal=True)[:, :, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_bf16(rng):
    q, k, v = _qkv(rng, 1, 128, 128, 4, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


# ---------------------------------------------------------------------------
# Gradients: the Pallas forward and backward kernels vs the jnp flash path
# and the oracle
# ---------------------------------------------------------------------------
F32, BF16 = jnp.float32, jnp.bfloat16


def _rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("win,cap,G,S,dtype", [
    pytest.param(0, 0.0, 2, 128, F32, id="0-0.0-2"),
    pytest.param(48, 0.0, 1, 128, F32, id="48-0.0-1"),
    pytest.param(0, 20.0, 4, 128, F32, id="0-20.0-4"),
    pytest.param(0, 0.0, 1, 128, F32, id="causal-G1"),
    pytest.param(48, 20.0, 2, 128, F32, id="window-softcap-G2"),
    pytest.param(0, 0.0, 2, 100, F32, id="odd-kv_len-G2"),
    pytest.param(48, 0.0, 1, 100, F32, id="odd-kv_len-window-G1"),
    pytest.param(0, 0.0, 2, 600, F32, id="tile-skip-G2"),
    pytest.param(0, 0.0, 2, 128, BF16, id="bf16-causal-G2"),
    pytest.param(48, 20.0, 1, 128, BF16, id="bf16-window-softcap-G1"),
])
def test_flash_pallas_grads(win, cap, G, S, dtype, rng):
    """dq/dk/dv of the Pallas path (forward and backward kernels, through
    the dispatch: odd S pads to the chunk and masks kv past it by kv_len;
    S = 600 gives the backward 3 q blocks and 2 kv blocks, so it skips the
    tiles above the diagonal) against the jnp flash backward and the
    oracle's autodiff."""
    B, Hkv, D = 2, 2, 32
    q, k, v = _qkv(rng, B, S, S, G * Hkv, Hkv, D, dtype)
    do = jax.random.normal(jax.random.split(rng, 4)[3], q.shape)

    def grads(attn):
        def f(q, k, v):
            return (attn(q, k, v).astype(F32) * do).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def dispatch(impl):
        return grads(lambda q, k, v: chunked_attention(
            q, k, v, causal=True, window=win, attn_softcap=cap, chunk_q=64,
            chunk_kv=64, impl=impl))

    gp, gj = dispatch("pallas"), dispatch("jnp")
    gr = grads(lambda q, k, v: attention_ref(
        q.astype(F32), k.astype(F32), v.astype(F32), causal=True,
        window=win, attn_softcap=cap))
    for a, j, r in zip(gp, gj, gr):
        if dtype == F32:
            # float32 products on both paths: only the order of the sums
            # differs (largest seen 1e-5 on gradients of order 1)
            np.testing.assert_allclose(np.asarray(a), np.asarray(j),
                                       atol=5e-5)
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=5e-5)
        else:
            # bf16 operands, and P and dS rounded to bf16 for the MXU where
            # the jnp path keeps them float32, then bf16 gradients: a few
            # bf16 roundings (2^-9 each) of relative error: seen 3.5e-3,
            # and 2.9e-3 on the jnp path
            assert _rel_gap(a, r) < 1e-2
            assert _rel_gap(a, r) < 2 * _rel_gap(j, r) + 2e-3


@pytest.mark.parametrize("split", [1, 2])
@pytest.mark.parametrize("causal,window,kv_len", [
    (True, 0, 0), (True, 80, 0), (True, 0, 250), (False, 0, 250),
])
def test_flash_bwd_tile_skip_matches_one_tile(causal, window, kv_len, split,
                                              rng):
    """The backward kernel with small blocks, where whole tiles are
    skipped (above the diagonal, behind the window, past kv_len), the tiles
    the masks cut run masked (split into 2 x 2 sub-tiles, each skipped,
    masked or run whole, with ``split`` 2), equals one unsplit tile over
    everything, where nothing is skipped."""
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.flash_attention.kernel import (_kv_block_range,
                                                      flash_attention_bwd)
    B, Hkv, G, S, D, bq, bk = 1, 2, 2, 512, 32, 128, 256
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, Hkv, S, G, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    do = jax.random.normal(ks[3], q.shape)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kv_pos = jnp.where(pos < kv_len, pos, -1) if kv_len else pos
    kw = dict(causal=causal, window=window)
    out, lse = flash_attention_fwd(q, k, v, pos, kv_pos, block_q=S,
                                   block_k=S, **kw)
    delta = (do * out).sum(-1)
    ranges = [_kv_block_range(i, bq, bk, S // bk, causal, window, kv_len)
              for i in range(S // bq)]
    assert any(lo > 0 or hi < S // bk - 1 for lo, hi in ranges)
    one = flash_attention_bwd(q, k, v, do, lse, delta, kv_len=kv_len,
                              block_q=S, block_k=S, **kw)
    tiled = flash_attention_bwd(q, k, v, do, lse, delta, kv_len=kv_len,
                                block_q=bq, block_k=bk, split=split, **kw)
    for a, b in zip(tiled, one):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flash_pallas_grads_odd_length_via_dispatch(rng):
    """Odd S through chunked_attention(impl=pallas): padded-row grads zero."""
    B, S, Hq, Hkv, D = 1, 100, 4, 2, 32
    q, k, v = _qkv(rng, B, S, S, Hq, Hkv, D)
    do = jax.random.normal(jax.random.split(rng, 4)[3], q.shape)

    def make(impl):
        def f(q, k, v):
            o = chunked_attention(q, k, v, causal=True, chunk_q=64,
                                  chunk_kv=64, impl=impl)
            return (o * do).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def f_ref(q, k, v):
        return (attention_ref(q, k, v, causal=True) * do).sum()

    gp = make("pallas")
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ---------------------------------------------------------------------------
# Decode cells: ring cache, per-sequence positions, valid length, window
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window,valid", [(0, False), (40, False), (0, True),
                                          (40, True)])
def test_flash_decode_ring_cache(window, valid, rng):
    """The serving engine's masks: ring kv layout (-1 empty slots), per-seq
    q positions, optional kv_valid_len — Pallas decode kernel vs jnp path."""
    B, Hq, Hkv, D, cache_len, total = 2, 4, 2, 32, 64, 80
    q, k, v = _qkv(rng, B, 1, total, Hq, Hkv, D)
    kc = ring_cache_store(k, total, cache_len)
    vc = ring_cache_store(v, total, cache_len)
    pos_ids = ring_position_ids(B, total, cache_len)
    pos = jnp.full((B,), total, jnp.int32)
    kw = dict(causal=True, window=window, q_offset=pos, kv_positions=pos_ids,
              chunk_kv=48)                 # 48 also exercises T % ck != 0
    if valid:
        kw["kv_valid_len"] = pos + 1
    oj = chunked_attention(q, kc, vc, impl="jnp", **kw)
    op = chunked_attention(q, kc, vc, impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5)


def test_flash_decode_cross_attention(rng):
    """Enc-dec cross-attention decode: S=1, non-causal, odd source length."""
    q, k, v = _qkv(rng, 2, 1, 48, 4, 2, 32)
    oj = chunked_attention(q, k, v, causal=False, chunk_kv=32, impl="jnp")
    op = chunked_attention(q, k, v, causal=False, chunk_kv=32, impl="pallas")
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5)


def test_flash_decode_mixed_depth_slots(rng):
    """Continuous batching: every slot at a different depth in one cache."""
    B, Hq, Hkv, D, T = 3, 4, 1, 32, 64
    q, k, v = _qkv(rng, B, 1, T, Hq, Hkv, D)
    pos = jnp.asarray([5, 33, 61], jnp.int32)
    pos_ids = jnp.where(jnp.arange(T)[None, :] <= pos[:, None],
                        jnp.arange(T, dtype=jnp.int32)[None, :], -1)
    kw = dict(causal=True, q_offset=pos, kv_positions=pos_ids, chunk_kv=32)
    oj = chunked_attention(q, k, v, impl="jnp", **kw)
    op = chunked_attention(q, k, v, impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5)


# ---------------------------------------------------------------------------
# Proteus-quantized KV cache: in-kernel dequant decode vs the bf16 oracle
# ---------------------------------------------------------------------------
# per-bits error budgets: the shared KV_ERROR_BUDGET from models/layers.py
# (also gated in benchmarks/bench_kernels.py and tabled in the README)
KV_BUDGET = KV_ERROR_BUDGET


@pytest.mark.parametrize("mode", ["int8", "int4", "auto"])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("cell", ["ring", "valid", "odd"])
def test_flash_decode_quant_grid(mode, G, cell, rng):
    """Quant parity grid: the Pallas in-kernel-dequant decode kernel must
    match the jnp dequant fallback exactly (same dequantized operands), and
    both must track the bf16 oracle within the per-bits error budget."""
    B, Hkv, D = 2, 2, 32
    Hq = G * Hkv
    cache_len, total = (48, 60) if cell == "odd" else (64, 80)
    q, k, v = _qkv(rng, B, 1, total, Hq, Hkv, D)
    kc = ring_cache_store(k, total, cache_len)
    vc = ring_cache_store(v, total, cache_len)
    pos = jnp.full((B,), total, jnp.int32)
    kw = dict(causal=True, q_offset=pos,
              kv_positions=ring_position_ids(B, total, cache_len),
              chunk_kv=32 if cell == "odd" else 48)
    if cell == "valid":
        kw["kv_valid_len"] = pos + 1
    qk, qv = kv_quantize(kc, mode), kv_quantize(vc, mode)
    ref = chunked_attention(q, kc, vc, impl="jnp", **kw)
    oj = chunked_attention(q, qk, qv, impl="jnp", **kw)
    op = chunked_attention(q, qk, qv, impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5)
    assert float(np.abs(np.asarray(oj) - np.asarray(ref)).max()) \
        <= KV_BUDGET[mode]


def test_kv_quant_auto_narrow_value_detection(rng):
    """auto mode is data-aware: uniform-magnitude rows (crest ~ 1) take the
    int4 grid (codes within [-8, 7]); spiky gaussian rows need the int8
    grid — the Proteus narrow-value / DBPE behaviour."""
    flat = jnp.sign(jax.random.normal(rng, (2, 16, 2, 32)))   # |x| == 1
    qt = kv_quantize(flat, "auto")
    assert int(jnp.abs(qt.codes).max()) <= 7
    spiky = jax.random.normal(jax.random.split(rng)[0], (2, 16, 2, 32))
    qt2 = kv_quantize(spiky, "auto")
    assert int(jnp.abs(qt2.codes).max()) > 7
    # the grid choice is transparent: dequant error still tracks the input
    rt = kv_dequantize(qt, 32, jnp.float32)
    np.testing.assert_allclose(np.asarray(rt), np.asarray(flat), atol=1e-6)


def test_kv_quant_int4_roundtrip_packing(rng):
    """int4 codes are nibble-packed: half the code bytes, exact pack/unpack
    roundtrip through the shared repro.kernels.common helpers."""
    x = jax.random.normal(rng, (2, 8, 2, 32))
    qt = kv_quantize(x, "int4")
    assert qt.codes.shape == (2, 8, 2, 16) and qt.codes.dtype == jnp.int8
    rt = kv_dequantize(qt, 32, jnp.float32)
    # per-row scale bound: |err| <= scale/2 per element
    bound = np.asarray(qt.scale)[..., None] * 0.5 + 1e-6
    assert (np.abs(np.asarray(rt - x)) <= bound).all()


def test_kv_quant_mode_knob(monkeypatch):
    monkeypatch.setenv("REPRO_KV_QUANT", "int8")
    assert kv_quant_mode() == "int8"
    monkeypatch.delenv("REPRO_KV_QUANT")
    assert kv_quant_mode() == "off"
    monkeypatch.setenv("REPRO_KV_QUANT", "nope")
    with pytest.raises(ValueError):
        kv_quant_mode()


def test_kv_quant_end_to_end_decode_step(monkeypatch, rng):
    """TransformerLM prefill + decode with REPRO_KV_QUANT=int8: the decode
    logits stay close to the bf16-cache run, with zero call-site changes,
    and the off mode is bit-identical to the pre-quant path."""
    from repro.configs.base import ModelConfig
    from repro.models import init_params
    from repro.models.model import TransformerLM

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      param_dtype="float32", compute_dtype="float32")
    model = TransformerLM(cfg)
    params = init_params(model.param_specs(), rng)
    tokens = jax.random.randint(jax.random.split(rng)[0], (2, 9), 0, 64)
    outs = {}
    for mode in ("off", "int8"):
        monkeypatch.setenv("REPRO_KV_QUANT", mode)
        logits, cache = model.prefill(params, {"tokens": tokens}, max_len=16)
        step, cache = model.decode_step(
            params, cache, jnp.argmax(logits, -1).astype(jnp.int32))
        outs[mode] = np.asarray(step)
    np.testing.assert_allclose(outs["int8"], outs["off"], atol=0.1)


# ---------------------------------------------------------------------------
# Dispatch layer: REPRO_ATTN_IMPL routes every family's attention
# ---------------------------------------------------------------------------
def test_attn_impl_knob(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    assert attn_impl() == "pallas"
    monkeypatch.setenv("REPRO_ATTN_IMPL", "jnp")
    assert attn_impl() == "jnp"
    monkeypatch.setenv("REPRO_ATTN_IMPL", "auto")
    expect = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert attn_impl() == expect
    monkeypatch.setenv("REPRO_ATTN_IMPL", "nope")
    with pytest.raises(ValueError):
        attn_impl()


def test_dispatch_env_end_to_end_decode_step(monkeypatch, rng):
    """A TransformerLM prefill + decode step is bit-compatible between the
    jnp and Pallas backends, selected purely via REPRO_ATTN_IMPL — the
    serving engine's hot path with zero call-site changes."""
    from repro.configs.base import ModelConfig
    from repro.models import init_params
    from repro.models.model import TransformerLM

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      param_dtype="float32", compute_dtype="float32")
    model = TransformerLM(cfg)
    params = init_params(model.param_specs(), rng)
    tokens = jax.random.randint(jax.random.split(rng)[0], (2, 9), 0, 64)
    outs = {}
    for impl in ("jnp", "pallas"):
        monkeypatch.setenv("REPRO_ATTN_IMPL", impl)
        logits, cache = model.prefill(params, {"tokens": tokens}, max_len=16)
        step, cache = model.decode_step(
            params, cache, jnp.argmax(logits, -1).astype(jnp.int32))
        outs[impl] = (np.asarray(logits), np.asarray(step))
    np.testing.assert_allclose(outs["pallas"][0], outs["jnp"][0], atol=2e-4)
    np.testing.assert_allclose(outs["pallas"][1], outs["jnp"][1], atol=2e-4)


# ---------------------------------------------------------------------------
# On the TPU no path may hide the device: interpret mode, the paged gather and
# the jnp path are CPU-only, and a call no kernel takes raises there
# ---------------------------------------------------------------------------
@pytest.fixture()
def on_tpu_backend(monkeypatch):
    """Make repro see a TPU default backend (no kernel runs in these
    tests: each raises, or returns, before anything is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_interpret_mode_never_on_tpu(on_tpu_backend, monkeypatch):
    from repro.kernels.common import use_interpret
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert use_interpret() is False
    assert attn_impl() == "pallas"


def test_interpret_mode_env_switch_off_tpu(monkeypatch):
    from repro.kernels.common import use_interpret
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert use_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert use_interpret() is False


def test_paged_gather_fallback_raises_on_tpu(on_tpu_backend, rng):
    from repro.models.layers import PALLAS_DECODE_MAX_Q_ROWS, paged_from_ring
    S = PALLAS_DECODE_MAX_Q_ROWS + 1
    q, k, v = _qkv(rng, 1, S, 8, 1, 1, 8)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(NotImplementedError, match="paged attention"):
        chunked_attention(q, paged_from_ring(k, 4, "off"),
                          paged_from_ring(v, 4, "off"), q_offset=pos,
                          impl="pallas")


def test_positional_jnp_fallback_raises_on_tpu(on_tpu_backend, rng):
    from repro.models.layers import PALLAS_DECODE_MAX_Q_ROWS
    S = PALLAS_DECODE_MAX_Q_ROWS + 1
    q, k, v = _qkv(rng, 1, S, 8, 1, 1, 8)
    with pytest.raises(NotImplementedError, match="positional attention"):
        chunked_attention(q, k, v, q_offset=jnp.zeros((1,), jnp.int32),
                          impl="pallas")


def test_positional_jnp_fallback_warns_off_tpu(rng):
    from repro.models.layers import PALLAS_DECODE_MAX_Q_ROWS
    S = PALLAS_DECODE_MAX_Q_ROWS + 1
    q, k, v = _qkv(rng, 1, S, 8, 1, 1, 8)
    kw = dict(q_offset=jnp.zeros((1,), jnp.int32), chunk_kv=8)
    with pytest.warns(UserWarning, match="falling back to the jnp path"):
        out = chunked_attention(q, k, v, impl="pallas", **kw)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(chunked_attention(q, k, v, impl="jnp",
                                                      **kw)), atol=1e-6)


def test_int4_decode_kernel_refuses_compiled_mode(rng):
    """The nibble unpack does not lower for the TPU: compiled mode raises a
    clear error instead of a compiler failure deep in the program."""
    q, k, v = _qkv(rng, 1, 1, 16, 2, 2, 32)
    qk, qv = kv_quantize(k, "int4"), kv_quantize(v, "int4")
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    from repro.kernels.flash_attention import ops
    with pytest.raises(NotImplementedError, match="int4"):
        ops.flash_decode_quant(q, qk.codes, qk.scale, qv.codes, qv.scale,
                               pos[:, -1:], pos, interpret=False)


def test_dispatch_impl_arg_overrides_env(monkeypatch, rng):
    monkeypatch.setenv("REPRO_ATTN_IMPL", "jnp")
    q, k, v = _qkv(rng, 1, 64, 64, 2, 2, 16)
    a = chunked_attention(q, k, v, causal=True, chunk_q=32, chunk_kv=32,
                          impl="pallas")
    b = chunked_attention(q, k, v, causal=True, chunk_q=32, chunk_kv=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
