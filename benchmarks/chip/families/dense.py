"""The dense decoder family: the block that InternLM2 publishes and the
repository's dense model runs. Pre-norm RMSNorm; grouped-query attention
with rotary embedding over every head dim; RMSNorm; SwiGLU MLP; no biases;
an untied output head unless ``tie_embeddings``. The blocks' parameters are
stacked over layers.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import mm, rms_norm
from benchmarks.chip.roofline import causal_attention, decode_attention

# the program's ModelConfig.family that builds this block
PROGRAM_FAMILY = "dense"


# -- parameters ---------------------------------------------------------------
def layout(m: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, dtype name, init kind)} of the parameters; the
    projections whose output feeds the residual stream are ``normal_out``."""
    L, d, f, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    w = m["param_dtype"]
    out = {
        "embed": ((V, d), w, "normal"),
        "final_norm": ((d,), "float32", "ones"),
        "blocks/ln1": ((L, d), "float32", "ones"),
        "blocks/ln2": ((L, d), "float32", "ones"),
        "blocks/attn/w_q": ((L, d, H, D), w, "normal"),
        "blocks/attn/w_k": ((L, d, Hkv, D), w, "normal"),
        "blocks/attn/w_v": ((L, d, Hkv, D), w, "normal"),
        "blocks/attn/w_o": ((L, H, D, d), w, "normal_out"),
        "blocks/mlp/wi_gate": ((L, d, f), w, "normal"),
        "blocks/mlp/wi_up": ((L, d, f), w, "normal"),
        "blocks/mlp/wo": ((L, f, d), w, "normal_out"),
    }
    if not m.get("tie_embeddings", False):
        out["head"] = ((d, V), w, "normal")
    return out


def init_stds(m: Dict[str, Any], init: Dict[str, Any]) -> Dict[str, float]:
    """{init kind: std} of the kinds drawn from a normal: the output
    projections' std is the configuration's over sqrt(2 * layers)."""
    std = float(init["std"])
    return {"normal": std, "normal_out": std / math.sqrt(2 * m["num_layers"])}


# -- the float32 reference -----------------------------------------------------
def rotary(x, pos, theta):
    """Rotate-half rotary embedding over every head dim; x (B,S,H,D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(p: Dict[str, jax.Array], x: jax.Array, m: Dict[str, Any],
          mode: str) -> jax.Array:
    """One decoder block over x (B,S,d), causal, positions 0..S-1."""
    S = x.shape[1]
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(S)
    h = rms_norm(x, f32(p["ln1"]), m["norm_eps"])
    q = rotary(mm("bsd,dhe->bshe", h, p["w_q"], mode), pos, m["rope_theta"])
    k = rotary(mm("bsd,dhe->bshe", h, p["w_k"], mode), pos, m["rope_theta"])
    v = mm("bsd,dhe->bshe", h, p["w_v"], mode)
    g = m["num_heads"] // m["num_kv_heads"]
    if g > 1:
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", a, v, mode)
    x = x + mm("bshe,hed->bsd", o, p["w_o"], mode)
    h = rms_norm(x, f32(p["ln2"]), m["norm_eps"])
    u = jax.nn.silu(mm("bsd,df->bsf", h, p["wi_gate"], mode)) \
        * mm("bsd,df->bsf", h, p["wi_up"], mode)
    return x + mm("bsf,fd->bsd", u, p["wo"], mode)


def _layer_params(params: Dict[str, Any], i) -> Dict[str, jax.Array]:
    b = params["blocks"]
    return {"ln1": b["ln1"][i], "ln2": b["ln2"][i],
            **{k: v[i] for k, v in b["attn"].items()},
            **{k: v[i] for k, v in b["mlp"].items()}}


def _head(params):
    return params["embed"].T if "head" not in params else params["head"]


def forward(params: Dict[str, Any], tokens: jax.Array, m: Dict[str, Any],
            mode: str = "f32") -> jax.Array:
    """Logits (B,S,V) in float32: the whole model in one function."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(m["num_layers"]):
        x = block(_layer_params(params, i), x, m, mode)
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), m["norm_eps"])
    return mm("bsd,dv->bsv", x, _head(params), mode)


# -- serving: layer by layer, logits only at the rows that are compared ------
@partial(jax.jit, static_argnames=("mode", "mkey"))
def _block_jit(p, x, mode, mkey):
    return block(p, x, dict(mkey), mode)


@partial(jax.jit, static_argnames=("mode", "eps"))
def _rows_logits(x, norm, head, rows, mode, eps):
    x = rms_norm(x, norm.astype(jnp.float32), eps)
    xr = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return mm("bsd,dv->bsv", xr, head, mode)


def row_logits(params: Dict[str, Any], tokens: np.ndarray, rows: np.ndarray,
               m: Dict[str, Any], mode: str = "f32") -> jax.Array:
    """Logits (B,R,V) at positions ``rows`` (B,R) of ``tokens`` (B,S), one
    layer at a time so that only one layer's float32 copy is ever live."""
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["num_layers"]):
        x = _block_jit(_layer_params(params, i), x, mode, mkey)
    return _rows_logits(x, params["final_norm"], _head(params),
                        jnp.asarray(rows, jnp.int32), mode, m["norm_eps"])


# -- work counts -------------------------------------------------------------
def matmul_params(m: Dict[str, Any]) -> float:
    """Weights that take part in a matrix product per token: every block's
    projections and the output head (the embedding is a lookup)."""
    d, f, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    hd = m["head_dim"]
    attn = d * hd * (2 * m["num_heads"] + 2 * m["num_kv_heads"])
    return float(L * (attn + 3 * d * f) + d * v)


def train_work(m: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """What a training step of ``batch`` rows of ``seq`` tokens counts: one
    attention call (one layer's causal forward over the batch) in
    operations and bytes, and the step's operations per token: six per
    weight of every matrix product and three times the causal attention
    forward of every layer (rematerialised work is not counted)."""
    f_att, b_att = causal_attention(seq, m["num_heads"], m["num_kv_heads"],
                                    m["head_dim"])
    return {"attn_call_flops": batch * f_att,
            "attn_call_bytes": batch * b_att,
            "model_flops_per_token": 6.0 * matmul_params(m)
            + 3.0 * m["num_layers"] * f_att / seq}


def prefill_flops(m: Dict[str, Any], n: int) -> float:
    """Operations of one prompt's prefill over its ``n`` valid positions:
    two per weight of every matrix product per token, and the causal
    attention of every layer."""
    per_tok = 2.0 * matmul_params(m)
    return per_tok * n + m["num_layers"] * causal_attention(
        n, m["num_heads"], m["num_kv_heads"], m["head_dim"])[0]


def decode_work(m: Dict[str, Any], n: int, first: int,
                end: int) -> Dict[str, float]:
    """The decode rows that make tokens ``first`` .. ``end - 1`` (first >= 1:
    token 0 comes from the prefill) of a request with an ``n``-token prompt,
    where the row that makes token t attends the n + t positions before it:
    their attention's operations and least bytes, and their model
    operations (two per weight of every matrix product, and the
    attention)."""
    L, H, Hkv, D = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                    m["head_dim"])
    rows = end - first
    kv = rows * n + (first + end - 1) * rows / 2
    attn = L * decode_attention(1, H, Hkv, D)[0] * kv
    return {"decode_attn_flops": attn,
            "decode_attn_bytes": L * (2.0 * Hkv * kv + 2.0 * H * rows) * D * 2,
            "decode_model_flops": 2.0 * matmul_params(m) * rows + attn}
