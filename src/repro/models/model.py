"""Decoder-only transformer LM (dense / MoE / VLM families) + model dispatch.

One block stack, scanned (``jax.lax.scan``) over stacked params so HLO size
and compile time are O(1) in depth; KV-cache decode path for serving. A
``layer_pattern`` (windowed and full layers, e.g. three of one to one of the
other) makes the scan walk whole periods, the period's layers unrolled in
its body so that each keeps a static window and rotary table. Hybrid
(RG-LRU), SSM (xLSTM) and enc-dec (audio) families live in sibling modules
and share the same Model protocol:

    param_specs() -> spec pytree
    loss(params, batch) -> scalar
    prefill(params, batch) -> (last_logits, cache)
    decode_step(params, cache, tokens) -> (logits, cache)
    init_cache(batch, max_len) -> cache pytree
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import optimization_barrier
from repro.configs.base import ModelConfig
from repro.core.mimdram import constrain
from repro.launch import tracing
from repro.models import module as mod
from repro.models.layers import (aligned_cache_len, chunked_attention, dense,
                                 gated_mlp, kv_cache_axes, kv_cache_init,
                                 kv_cache_len, kv_cache_store,
                                 kv_cache_update, kv_cast, ring_cache_update,
                                 ring_position_ids, rms_norm, rope,
                                 softmax_xent, stack_trees, yarn_rotary)
from repro.models.moe import held_moe_ffn, moe_ffn, moe_param_specs


def _dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# ---------------------------------------------------------------------------
# Shared block pieces
# ---------------------------------------------------------------------------
def attn_param_specs(cfg: ModelConfig, dtype) -> Dict[str, mod.ParamSpec]:
    d, hq, hkv, dh = (cfg.d_model, cfg.tp_pad_heads or cfg.num_heads,
                      cfg.num_kv_heads, cfg.resolved_head_dim)
    return {
        "w_q": mod.spec((d, hq, dh), ("embed", "heads", "head_dim"), dtype),
        "w_k": mod.spec((d, hkv, dh), ("embed", "kv", "head_dim"), dtype),
        "w_v": mod.spec((d, hkv, dh), ("embed", "kv", "head_dim"), dtype),
        "w_o": mod.spec((hq, dh, d), ("heads", "head_dim", "embed"), dtype,
                        ("normal", 0)),
    }


def mlp_param_specs(cfg: ModelConfig, dtype) -> Dict[str, mod.ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wi_gate": mod.spec((d, f), ("embed", "mlp"), dtype),
        "wi_up": mod.spec((d, f), ("embed", "mlp"), dtype),
        "wo": mod.spec((f, d), ("mlp", "embed"), dtype),
    }


def qkv(cfg: ModelConfig, p, xn: jax.Array, positions,
        rot: Optional[Tuple[Any, float]] = None) -> Tuple[jax.Array, ...]:
    """q, k, v of one layer; ``rot`` (inverse frequencies, scale) replaces
    the plain rotary embedding at ``rope_theta``."""
    q = dense(xn, p["w_q"], "bsd,dhe->bshe")
    k = dense(xn, p["w_k"], "bsd,dhe->bshe")
    v = dense(xn, p["w_v"], "bsd,dhe->bshe")
    rot = () if rot is None else rot
    q = rope(q, positions, cfg.rope_theta, *rot)
    k = rope(k, positions, cfg.rope_theta, *rot)
    q = constrain(q, "act_batch", "act_seq", "act_heads", "act_hd")
    k = constrain(k, "act_batch", "act_seq", "act_kv", "act_hd")
    return q, k, v


def attn_out(p, o: jax.Array) -> jax.Array:
    return dense(o, p["w_o"], "bshe,hed->bsd")


# how each expert-layer counter goes from layers to the stack
STAT_OVER_LAYERS = {"moe_rows": jnp.sum, "moe_load_max": jnp.max}


def _stack(stats):
    """A list of equal stat dicts -> one dict of stacked arrays."""
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats) \
        if stats and stats[0] else {}


# ---------------------------------------------------------------------------
# TransformerLM
# ---------------------------------------------------------------------------
class TransformerLM:
    """dense / moe / vlm families."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = _dt(cfg.param_dtype)
        self.cdtype = _dt(cfg.compute_dtype)

    # -- specs ---------------------------------------------------------------
    def block_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        s: Dict[str, Any] = {
            "ln1": mod.spec((cfg.d_model,), (None,), jnp.float32, ("ones",)),
            "ln2": mod.spec((cfg.d_model,), (None,), jnp.float32, ("ones",)),
            "attn": attn_param_specs(cfg, self.dtype),
        }
        if cfg.num_experts > 0:
            s["moe"] = moe_param_specs(cfg, self.dtype)
        else:
            s["mlp"] = mlp_param_specs(cfg, self.dtype)
        return s

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "embed": mod.spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                              self.dtype),
            "final_norm": mod.spec((cfg.d_model,), (None,), jnp.float32, ("ones",)),
            "blocks": mod.stack_tree(self.block_specs(), cfg.num_layers),
        }
        if not cfg.tie_embeddings:
            specs["head"] = mod.spec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"), self.dtype)
        return specs

    def layer_kinds(self) -> Tuple[Tuple[int, Any], ...]:
        """(window, rotary) of each layer of one period of the stack: one
        layer as ``attention_kind`` says, or ``layer_pattern``'s; a full
        layer takes YaRN's rotary where ``yarn_factor`` is set (else
        None, the plain table)."""
        cfg = self.cfg
        if not cfg.layer_pattern:
            return ((cfg.sliding_window if cfg.attention_kind == "sliding"
                     else 0, None),)
        assert cfg.num_layers % len(cfg.layer_pattern) == 0, (
            f"{cfg.num_layers} layers are not whole periods of "
            f"{cfg.layer_pattern}")
        yarn = None
        if cfg.yarn_factor:
            yarn = yarn_rotary(cfg.resolved_head_dim, cfg.rope_theta,
                               cfg.yarn_factor,
                               cfg.yarn_original_max_positions,
                               cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                               cfg.yarn_attention_factor)
        kinds = {"sliding": (cfg.sliding_window, None), "full": (0, yarn)}
        return tuple(kinds[k] for k in cfg.layer_pattern)

    # -- one block -----------------------------------------------------------
    def _block(self, p, x, positions, *, window, rot=None, block_skip=False):
        """One layer -> (x, stats): stats are the expert layer's counters
        (empty elsewhere)."""
        cfg = self.cfg
        with tracing.scope("attn"):
            # barrier: stops XLA promoting the scan-saved bf16 residual stack
            # to f32 via convert motion (observed 2x activation memory
            # otherwise)
            x = optimization_barrier(x)
            p = mod.constrain_tree(p, self.block_specs())
            xn = rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = qkv(cfg, p["attn"], xn, positions, rot)
            o = chunked_attention(q, k, v, causal=True, window=window,
                                  q_offset=0, chunk_q=cfg.attn_chunk_q,
                                  chunk_kv=cfg.attn_chunk_kv,
                                  block_skip=cfg.attn_block_skip or block_skip)
            x = x + dense(o, p["attn"]["w_o"], "bshe,hed->bsd")
            x = constrain(x, "act_batch", "act_seq", "act_embed")
        with tracing.scope("mlp"):
            y, stats = self._ffn(p, rms_norm(x, p["ln2"], cfg.norm_eps))
            x = x + y
            x = constrain(x, "act_batch", "act_seq", "act_embed")
        return x, stats

    def _ffn(self, p, xn2: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
        cfg = self.cfg
        if cfg.num_experts > 0 and cfg.experts_held > 0:
            return held_moe_ffn(cfg, p["moe"], xn2)
        if cfg.num_experts > 0:
            return moe_ffn(cfg, p["moe"], xn2), {}
        return gated_mlp(xn2, p["mlp"]["wi_gate"], p["mlp"]["wi_up"],
                         p["mlp"]["wo"]), {}

    def _serving_window(self) -> int:
        """The one window of every layer, for the serving paths, which do
        not take a stack of mixed layer kinds."""
        kinds = self.layer_kinds()
        if len({w for w, _ in kinds}) > 1 or any(r is not None
                                                  for _, r in kinds):
            raise NotImplementedError(
                f"{self.cfg.name}: serving a stack of layer kinds "
                f"{self.cfg.layer_pattern} is not built; it trains")
        return kinds[0][0]

    def _embed(self, params, tokens: jax.Array) -> jax.Array:
        with tracing.scope("embed"):
            return params["embed"].astype(self.cdtype)[tokens]

    def _head(self, params, x: jax.Array, last: bool = False) -> jax.Array:
        """Final norm and vocabulary projection, of the last position alone
        with ``last``."""
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["head"])
        return dense(x[:, -1:] if last else x, head, "bsd,dv->bsv")

    # -- full-sequence forward (train / prefill) ------------------------------
    def forward(self, params, tokens: jax.Array,
                patch_embeds: Optional[jax.Array] = None):
        return self.forward_and_stats(params, tokens, patch_embeds)[0]

    def forward_and_stats(self, params, tokens: jax.Array,
                          patch_embeds: Optional[jax.Array] = None):
        """(logits, stats): stats are the expert layers' counters over the
        stack (``moe_rows`` summed, ``moe_load_max`` the largest), empty
        for a model without held experts."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if patch_embeds is not None:
            x = jnp.concatenate([patch_embeds.astype(self.cdtype), x], axis=1)
        B, S, _ = x.shape
        x = constrain(x, "act_batch", "act_seq", "act_embed")
        positions = jnp.arange(S, dtype=jnp.int32)
        kinds = self.layer_kinds()
        period = len(kinds)

        def layer_fn(window, rot):
            def body(carry, layer_p):
                return self._block(layer_p, carry, positions, window=window,
                                   rot=rot)
            if cfg.remat != "none":
                return jax.checkpoint(body, prevent_cse=False)
            return body

        fns = [layer_fn(*k) for k in kinds]
        blocks = params["blocks"]
        if period == 1:
            block_fn = fns[0]
        else:
            # one scan step is one period, its layers unrolled
            blocks = jax.tree_util.tree_map(
                lambda a: a.reshape((-1, period) + a.shape[1:]), blocks)

            def block_fn(carry, period_p):
                stats = []
                for i, fn in enumerate(fns):
                    carry, st = fn(carry, jax.tree_util.tree_map(
                        lambda a: a[i], period_p))
                    stats.append(st)
                return carry, _stack(stats)
        with tracing.scope("layers"):
            if cfg.scan_layers:
                x, stats = jax.lax.scan(block_fn, x, blocks)
            else:
                stats = []
                for i in range(cfg.num_layers // period):
                    x, st = block_fn(x, jax.tree_util.tree_map(
                        lambda a: a[i], blocks))
                    stats.append(st)
                stats = _stack(stats)
        with tracing.scope("head_loss"):
            logits = self._head(params, x)
            logits = constrain(logits, "act_batch", "act_seq", "act_vocab")
        return logits, {k: STAT_OVER_LAYERS[k](v) for k, v in stats.items()}

    def loss(self, params, batch) -> jax.Array:
        return self.loss_and_stats(params, batch)[0]

    def loss_and_stats(self, params, batch):
        """(loss, stats of :meth:`forward_and_stats`)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        logits, stats = self.forward_and_stats(params, tokens,
                                               batch.get("patch_embeds"))
        if "patch_embeds" in batch and batch["patch_embeds"] is not None:
            # loss only over text region (after the patch prefix)
            P = batch["patch_embeds"].shape[1]
            logits = logits[:, P:]
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        with tracing.scope("head_loss"):
            return softmax_xent(logits[:, :-1], labels[:, 1:],
                                None if mask is None else mask[:, 1:]), stats

    # -- serving ---------------------------------------------------------------
    def cache_len(self, max_len: int) -> int:
        cfg = self.cfg
        if cfg.attention_kind == "sliding" and cfg.sliding_window > 0:
            return aligned_cache_len(min(max_len, cfg.sliding_window))
        return aligned_cache_len(max_len)

    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        T = self.cache_len(max_len)
        kv = (batch, T, cfg.num_kv_heads, cfg.resolved_head_dim)
        L = cfg.num_layers
        return {
            "k": kv_cache_init((L,) + kv, self.cdtype),
            "v": kv_cache_init((L,) + kv, self.cdtype),
            "pos_ids": jnp.full((batch, T), -1, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }

    def cache_logical_axes(self) -> Dict[str, Any]:
        kv = kv_cache_axes(
            ("layers", "act_batch", "cache_seq", "cache_kv", "cache_hd"))
        return {"k": kv, "v": kv, "pos_ids": ("act_batch", "cache_seq"),
                "pos": ("act_batch",)}

    def prefill(self, params, batch, max_len: Optional[int] = None,
                full_logits: bool = False) -> Tuple[jax.Array, Dict[str, Any]]:
        """Run the full prompt, return last-token logits + filled cache.

        With ``max_len`` the cache is pre-sized for ``max_len`` total positions
        (ring-aligned so decode's ``pos % T`` writes land on the right slots)
        — prefill -> decode involves zero cache copies or repads.
        ``full_logits=True`` returns logits for every position instead of the
        last one (the paged engine right-pads prompts to a bucket length and
        reads the logits at the true prompt end).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        patch = batch.get("patch_embeds")
        x = self._embed(params, tokens)
        if patch is not None:
            x = jnp.concatenate([patch.astype(self.cdtype), x], axis=1)
        B, S, _ = x.shape
        T = self.cache_len(max(max_len or S, S))
        positions = jnp.arange(S, dtype=jnp.int32)
        window = self._serving_window()

        def store(k):
            return kv_cache_store(k.astype(self.cdtype), S, T)

        def body(carry, layer_p):
            h = carry
            with tracing.scope("attn"):
                layer_p = mod.constrain_tree(layer_p, self.block_specs())
                xn = rms_norm(h, layer_p["ln1"], cfg.norm_eps)
                q, k, v = qkv(cfg, layer_p["attn"], xn, positions)
                o = chunked_attention(q, k, v, causal=True, window=window,
                                      q_offset=0, chunk_q=cfg.attn_chunk_q,
                                      chunk_kv=cfg.attn_chunk_kv,
                                      block_skip=cfg.attn_block_skip)
                h = h + dense(o, layer_p["attn"]["w_o"], "bshe,hed->bsd")
            with tracing.scope("mlp"):
                h = h + self._ffn(layer_p,
                                  rms_norm(h, layer_p["ln2"], cfg.norm_eps))[0]
            with tracing.scope("attn"):
                return h, (store(k), store(v))

        with tracing.scope("layers"):
            if cfg.scan_layers:
                x, (ck, cv) = jax.lax.scan(body, x, params["blocks"])
            else:
                ks, vs = [], []
                for i in range(cfg.num_layers):
                    layer_p = jax.tree_util.tree_map(lambda a: a[i],
                                                     params["blocks"])
                    x, (k1, v1) = body(x, layer_p)
                    ks.append(k1)
                    vs.append(v1)
                ck, cv = stack_trees(ks), stack_trees(vs)
        with tracing.scope("head_loss"):
            logits = self._head(params, x, last=not full_logits)
        cache = {
            "k": ck, "v": cv,
            "pos_ids": ring_position_ids(B, S, T),
            "pos": jnp.full((B,), S, jnp.int32),
        }
        return logits, cache

    def decode_step(self, params, cache, tokens: jax.Array,
                    layers: Optional[int] = None):
        """tokens: (B, S). Appends S tokens per row; returns their logits.

        Positions are per-sequence (``pos``: (B,)) so continuous batching can
        host sequences at different depths in one cache. S == 1 is the
        per-token decode step; S > 1 is a speculative verify block (the fed
        token plus k drafts), written at consecutive slots and attended
        causally within the block via the position masks. ``layers`` (static)
        truncates the forward to the first N transformer blocks — the
        layer-skip self-drafting pass of speculative decoding — updating only
        those layers' cache entries (the verify pass overwrites them with
        identical values, so partial-layer writes never leak).
        """
        cfg = self.cfg
        S = tokens.shape[1]
        x = self._embed(params, tokens)                          # (B,S,D)
        pos = cache["pos"]                                       # (B,)
        T = kv_cache_len(cache["k"])
        window = self._serving_window()
        if S == 1:
            slot = (pos % T).astype(jnp.int32)                   # (B,)
            positions = pos[:, None].astype(jnp.int32)           # (B, 1)
            pos_ids = ring_cache_update(cache["pos_ids"], pos[:, None], slot)
        else:
            block_pos = pos[:, None] + jnp.arange(S, dtype=pos.dtype)
            slot = (block_pos % T).astype(jnp.int32)             # (B, S)
            positions = block_pos.astype(jnp.int32)
            pos_ids = ring_cache_update(cache["pos_ids"], block_pos, slot)

        def body(carry, xs):
            h = carry
            layer_p, ck, cv = xs
            with tracing.scope("attn"):
                layer_p = mod.constrain_tree(layer_p, self.block_specs())
                xn = rms_norm(h, layer_p["ln1"], cfg.norm_eps)
                q, k, v = qkv(cfg, layer_p["attn"], xn, positions)
                ck = kv_cache_update(ck, k, slot)
                cv = kv_cache_update(cv, v, slot)
                o = chunked_attention(
                    q, kv_cast(ck, h.dtype), kv_cast(cv, h.dtype),
                    causal=True, window=window, q_offset=pos,
                    kv_positions=pos_ids, chunk_kv=min(1024, T))
                h = h + dense(o, layer_p["attn"]["w_o"], "bshe,hed->bsd")
            with tracing.scope("mlp"):
                h = h + self._ffn(layer_p,
                                  rms_norm(h, layer_p["ln2"], cfg.norm_eps))[0]
            return h, (ck, cv)

        tm = jax.tree_util.tree_map
        blocks, ck0, cv0 = params["blocks"], cache["k"], cache["v"]
        if layers is not None:
            blocks = tm(lambda a: a[:layers], blocks)
            ck0 = tm(lambda a: a[:layers], ck0)
            cv0 = tm(lambda a: a[:layers], cv0)
        with tracing.scope("layers"):
            if cfg.scan_layers:
                x, (ck, cv) = jax.lax.scan(body, x, (blocks, ck0, cv0))
            else:
                n_layers = cfg.num_layers if layers is None else layers
                ks, vs = [], []
                for i in range(n_layers):
                    xs = tm(lambda a: a[i], (blocks, ck0, cv0))
                    x, (k1, v1) = body(x, xs)
                    ks.append(k1)
                    vs.append(v1)
                ck, cv = stack_trees(ks), stack_trees(vs)
            if layers is not None:
                ck = tm(lambda f, p: f.at[:layers].set(p), cache["k"], ck)
                cv = tm(lambda f, p: f.at[:layers].set(p), cache["v"], cv)
        with tracing.scope("head_loss"):
            logits = self._head(params, x)
        new_cache = {"k": ck, "v": cv, "pos_ids": pos_ids,
                     "pos": pos + jnp.asarray(S, pos.dtype)}
        return logits, new_cache


def build_model(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        return TransformerLM(cfg)
    if cfg.family == "hybrid":
        from repro.models.recurrent_lm import GriffinLM
        return GriffinLM(cfg)
    if cfg.family == "ssm":
        from repro.models.recurrent_lm import XLSTMLM
        return XLSTMLM(cfg)
    if cfg.family == "audio":
        from repro.models.encdec import EncDecLM
        return EncDecLM(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")
