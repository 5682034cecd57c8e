"""Paged (block-table) KV cache correctness: layout round-trips, the paged
Pallas decode kernels, and the serving engine's allocator / prefix sharing.

The paged cache must be a pure layout transform: greedy decode through paged
pools + page tables is byte-identical to the contiguous ring cache, and the
engine's mixed-length queue drain is byte-identical to per-request
references — with no silent prompt truncation.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.kernels.flash_attention import ops as fa_ops
from repro.launch import mesh as mesh_lib
from repro.launch.engine import Request, ServeEngine
from repro.launch.steps import make_serving_jits
from repro.models import build_model, init_params
from repro.models import layers as L

PS = 4  # page size used throughout


@pytest.fixture()
def paged_env(monkeypatch):
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))


def _build(arch, batch, prompt_len, max_len):
    cfg = get_config(arch, smoke=True)
    mesh = mesh_lib.make_local_mesh(("data",))
    plan = plan_sharding(cfg, ShapeConfig("serve", max_len, batch, "decode"),
                         mesh)
    model = build_model(cfg)
    with use_plan(plan):
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    return cfg, model, params, plan


# ---------------------------------------------------------------------------
# Layout unit tests
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["off", "int8", "int4"])
def test_paged_round_trip(mode):
    """ring -> pages -> gather is the identity (exactly, or through the
    quantizer for quantized pools)."""
    rng = np.random.default_rng(0)
    ring = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    paged = L.paged_from_ring(ring, page_size=PS, mode=mode)
    assert paged.page_size == PS and paged.kv_len == 16
    got = L.paged_gather(paged)
    want = ring if mode == "off" else L.maybe_kv_quantize(ring, mode)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        got, want)


def test_paged_update_writes_through_table(paged_env):
    """kv_cache_update lands the row in the pool page the table points at,
    and trash-page rows absorb writes without touching live pages."""
    cache = L.kv_cache_init((2, 8, 2, 4), jnp.float32, mode="off",
                            page_size=PS)
    new = jnp.ones((2, 1, 2, 4), jnp.float32)
    slot = jnp.asarray([1, 5], jnp.int32)         # page 0 off 1 / page 1 off 1
    upd = L.kv_cache_update(cache, new, slot)
    dense = L.paged_gather(upd)
    assert float(dense[0, 1].sum()) == 8.0 and float(dense[1, 5].sum()) == 8.0
    assert float(jnp.abs(dense).sum()) == 16.0    # nothing else written
    # retired slot 0: its table row points at the trash page, so a stale
    # write is absorbed there and its original pool pages stay intact
    trashed = L.PagedKVCache(upd.pages, upd.table.at[0].set(L.TRASH_PAGE))
    upd2 = L.kv_cache_update(trashed, 3 * new, slot)
    dense2 = L.paged_gather(L.PagedKVCache(upd2.pages, upd.table))
    np.testing.assert_array_equal(np.asarray(dense2[0]), np.asarray(dense[0]))
    assert float(dense2[1, 5].sum()) == 24.0      # live slot write landed


def test_aligned_cache_len(paged_env):
    assert L.aligned_cache_len(13) == 16
    assert L.aligned_cache_len(16) == 16
    assert L.aligned_cache_len(13, page_size=0) == 13


# ---------------------------------------------------------------------------
# Paged Pallas decode kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["off", "int8", "int4"])
def test_paged_kernel_matches_dense(mode):
    """The paged kernel streaming pool pages through the table is bitwise
    equal to the dense decode kernel at the same tile size."""
    B, T, Hkv, D, Hq = 2, 32, 2, 16, 4
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    valid = jnp.asarray([20, 7], jnp.int32)
    kv_pos = jnp.where(jnp.arange(T)[None, :] < valid[:, None],
                       jnp.arange(T, dtype=jnp.int32)[None, :], -1)
    q_pos = (valid - 1)[:, None]
    pk = L.paged_from_ring(k, page_size=8, mode=mode)
    pv = L.paged_from_ring(v, page_size=8, mode=mode)
    if mode == "off":
        ref = fa_ops.flash_decode(q, k, v, q_pos, kv_pos, block_k=8,
                                  interpret=True)
        out = fa_ops.flash_decode_paged(q, pk.pages, pv.pages, pk.table,
                                        q_pos, kv_pos, interpret=True)
    else:
        qk = L.maybe_kv_quantize(k, mode)
        qv = L.maybe_kv_quantize(v, mode)
        ref = fa_ops.flash_decode_quant(q, qk.codes, qk.scale, qv.codes,
                                        qv.scale, q_pos, kv_pos, block_k=8,
                                        interpret=True)
        out = fa_ops.flash_decode_paged_quant(
            q, pk.pages.codes, pk.pages.scale, pv.pages.codes, pv.pages.scale,
            pk.table, q_pos, kv_pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_chunked_attention_backends_agree(paged_env):
    """chunked_attention's paged pallas dispatch vs its paged jnp gather
    fallback on the same PagedKVCache."""
    B, T, Hkv, D, Hq = 2, 16, 2, 8, 4
    rng = np.random.default_rng(1)
    k = L.paged_from_ring(
        jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32))
    v = L.paged_from_ring(
        jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32))
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    pos = jnp.asarray([9, 5], jnp.int32)
    kv_pos = jnp.where(jnp.arange(T)[None, :] <= pos[:, None],
                       jnp.arange(T, dtype=jnp.int32)[None, :], -1)
    a = L.chunked_attention(q, k, v, q_offset=pos, kv_positions=kv_pos,
                            impl="pallas")
    b = L.chunked_attention(q, k, v, q_offset=pos, kv_positions=kv_pos,
                            impl="jnp")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


# ---------------------------------------------------------------------------
# Model decode: paged == contiguous
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["pimref-100m", "recurrentgemma-2b"])
def test_model_decode_paged_matches_contiguous(arch, monkeypatch):
    """Greedy prefill+decode with the paged cache reproduces the contiguous
    ring cache token-for-token (same model, same prompt)."""
    def greedy(pages):
        if pages:
            monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
        else:
            monkeypatch.delenv("REPRO_KV_PAGES", raising=False)
        cfg, model, params, plan = _build(arch, 1, 8, 16)
        toks = jnp.asarray(
            np.random.default_rng(2).integers(1, cfg.vocab_size, (1, 8)),
            jnp.int32)
        with use_plan(plan):
            logits, cache = jax.jit(
                lambda p, b: model.prefill(p, b, max_len=16))(
                    params, {"tokens": toks})
        step = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
        out = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(7):
            logits, cache = step(params, cache,
                                 jnp.asarray([[out[-1]]], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
        return out

    assert greedy(pages=True) == greedy(pages=False)


# ---------------------------------------------------------------------------
# Serving engine: allocator, sharing, COW, error paths
# ---------------------------------------------------------------------------
def _reference_paged(model, params, plan, prompt, prompt_len, max_len, n_new):
    """Per-request mirror of the paged engine: right-pad to the bucket,
    full-logits prefill, greedy decode from the true prompt end."""
    n = len(prompt)
    toks = np.zeros((1, prompt_len), np.int32)
    toks[0, :n] = np.asarray(prompt, np.int32)
    prefill, _, _, _ = make_serving_jits(model, plan, max_len=max_len,
                                         chunk=4, full_logits=True)
    logits, cache = prefill(params, {"tokens": jnp.asarray(toks)})
    cache["pos"] = jnp.full((1,), n, jnp.int32)
    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
    out = [int(jnp.argmax(logits[0, n - 1]))]
    for _ in range(n_new - 1):
        lg, cache = decode(params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(lg[0, -1])))
    return out


@pytest.mark.parametrize("kv_quant", ["off", "int8"])
def test_engine_paged_mixed_queue_byte_identical(kv_quant, monkeypatch):
    """Mixed-length queue (4x prompt-length spread) through the paged engine
    drains byte-identical to per-request references, including with
    int8-quantized pages, and the HBM accounting moves."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    if kv_quant != "off":
        monkeypatch.setenv("REPRO_KV_QUANT", kv_quant)
    prompt_len, max_new, chunk, slots = 8, 10, 4, 2
    max_len = prompt_len + max_new
    cfg, model, params, plan = _build("pimref-100m", slots, prompt_len,
                                      max_len)
    rng = np.random.default_rng(3)
    lengths = [8, 8, 2, 3, 2, 5]                  # 4x spread
    prompts = [rng.integers(1, cfg.vocab_size, size=l).astype(np.int32)
               for l in lengths]
    prompts[1][:PS] = prompts[0][:PS]             # concurrent shared prefix

    eng = ServeEngine(model, params, plan, slots=slots, prompt_len=prompt_len,
                      max_new=max_new, chunk=chunk)
    assert eng.paged
    comps = {c.uid: c for c in eng.run(
        [Request(uid=i, tokens=p, max_new_tokens=max_new)
         for i, p in enumerate(prompts)])}
    for i, p in enumerate(prompts):
        ref = _reference_paged(model, params, plan, p, prompt_len, max_len,
                               min(max_new, max_len - len(p)))
        assert comps[i].tokens.tolist() == ref, f"request {i} diverged"
    assert eng.stats["prefix_hits"] > 0
    assert eng.stats["prefills"] == len(prompts)
    # HBM accounting: peak pages strictly under the contiguous worst case
    assert 0 < eng.stats["kv_pages_peak"] < slots * eng.n_logical_pages
    assert eng.stats["kv_bytes_per_token"] > 0
    assert eng.stats["kv_pages_in_use"] == 0      # fully drained
    sz = eng.compile_cache_size()
    assert sz in (None, 1)


def test_engine_paged_cow_on_ring_wrap(monkeypatch):
    """Two slots share prefix pages, then one ring-wraps into the shared
    page inside its final over-run chunk: copy-on-write must fork the page
    so the other slot's output still matches its reference."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    prompt_len, max_new, chunk, slots = 8, 4, 8, 2
    max_len = prompt_len + max_new                # T == 12: wrap in chunk 1
    cfg, model, params, plan = _build("pimref-100m", slots, prompt_len,
                                      max_len)
    rng = np.random.default_rng(4)
    base = rng.integers(1, cfg.vocab_size, size=prompt_len).astype(np.int32)
    other = base.copy()
    other[PS:] = rng.integers(1, cfg.vocab_size, size=prompt_len - PS)

    eng = ServeEngine(model, params, plan, slots=slots, prompt_len=prompt_len,
                      max_new=max_new, chunk=chunk)
    comps = {c.uid: c for c in eng.run(
        [Request(uid=0, tokens=base, max_new_tokens=max_new),
         Request(uid=1, tokens=other, max_new_tokens=max_new)])}
    assert eng.stats["prefix_hits"] > 0           # page 0 was shared
    for uid, p in ((0, base), (1, other)):
        ref = _reference_paged(model, params, plan, p, prompt_len, max_len,
                               max_new)
        assert comps[uid].tokens.tolist() == ref, f"request {uid} diverged"


@pytest.mark.parametrize("pages", [0, PS])
def test_engine_rejects_over_long_prompt(pages, monkeypatch):
    """Over-long prompts retire with an explicit error completion in BOTH
    cache layouts — never a silent truncation — and draining continues."""
    if pages:
        monkeypatch.setenv("REPRO_KV_PAGES", str(pages))
    prompt_len, max_new = 8, 4
    cfg, model, params, plan = _build("pimref-100m", 2, prompt_len,
                                      prompt_len + max_new)
    eng = ServeEngine(model, params, plan, slots=2, prompt_len=prompt_len,
                      max_new=max_new, chunk=4)
    good = np.arange(1, 5, dtype=np.int32)
    comps = {c.uid: c for c in eng.run(
        [Request(uid=0, tokens=np.arange(1, prompt_len + 2, dtype=np.int32),
                 max_new_tokens=max_new),
         Request(uid=1, tokens=good, max_new_tokens=max_new)])}
    assert comps[0].finish_reason == "error"
    assert len(comps[0].tokens) == 0
    assert "prompt" in (comps[0].error or "")
    assert comps[1].finish_reason in ("length", "eos")
    assert len(comps[1].tokens) > 0


def test_engine_paged_pages_freed_and_reused(monkeypatch):
    """Retired slots release their pages to the free list (tables point at
    trash) and the allocator reuses them for later admissions."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    prompt_len, max_new = 8, 6
    cfg, model, params, plan = _build("pimref-100m", 1, prompt_len,
                                      prompt_len + max_new)
    eng = ServeEngine(model, params, plan, slots=1, prompt_len=prompt_len,
                      max_new=max_new, chunk=3)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i,
                    tokens=rng.integers(1, cfg.vocab_size, 6).astype(np.int32),
                    max_new_tokens=max_new) for i in range(3)]
    eng.run(reqs)
    assert len(eng.completions) == 3
    assert eng.stats["kv_pages_in_use"] == 0
    assert eng._alloc.used == 0
    assert not eng._alloc.registry                # no leaked registrations
    n_phys = eng.slots * eng.n_logical_pages
    assert sorted(eng._alloc.free) == list(range(1, n_phys + 1))
    np.testing.assert_array_equal(eng._host_table, 0)
