"""The sparse-expert decoder family with windowed and full layers, as
Mellum2 publishes it, cut to one device's share of each expert layer.

Pre-norm RMSNorm; grouped-query attention whose heads need not span the
hidden size; the layers follow ``layer_pattern``: a ``sliding`` layer
attends the ``sliding_window`` keys up to its query (k > q - window) with
plain rotary embedding at ``rope_theta``, a ``full`` layer attends every
earlier key with YaRN's rotary (inverse frequencies blended between the
interpolated and extrapolated ones, cos and sin scaled by the attention
factor). RMSNorm; a router over all ``num_experts`` that keeps each token's
top ``experts_per_token`` with gates renormalised over them; of those, the
experts ``[expert_offset, expert_offset + experts_held)`` held here, each a
SwiGLU of width ``d_ff``. What the absent experts would add is left out. No
biases, no shared expert; an untied output head. Parameters are stacked
over layers.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

# the dense family's init kinds and stds are this family's too
from benchmarks.chip.families.dense import init_stds, rotary  # noqa: F401
from benchmarks.chip.reference import mm, rms_norm
from benchmarks.chip.roofline import causal_attention, decode_attention

# the program's ModelConfig.family that builds this block
PROGRAM_FAMILY = "moe"


# -- parameters ---------------------------------------------------------------
def layout(m: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, dtype name, init kind)} of the parameters, drawn as
    the dense family draws them (its ``init_stds``): the projections whose
    output feeds the residual stream are ``normal_out``."""
    L, d, f, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, Eh = m["num_experts"], m["experts_held"]
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
        "blocks/moe/router": ((L, d, E), w, "normal"),
        "blocks/moe/wi_gate": ((L, Eh, d, f), w, "normal"),
        "blocks/moe/wi_up": ((L, Eh, d, f), w, "normal"),
        "blocks/moe/wo": ((L, Eh, f, d), w, "normal_out"),
    }
    if not m.get("tie_embeddings", False):
        out["head"] = ((d, V), w, "normal")
    return out


# -- the float32 reference -----------------------------------------------------
def yarn(m: Dict[str, Any]):
    """(inverse frequencies, attention factor) of the full layers' rotary,
    written out from YaRN's definition (transformers'
    ``_compute_yarn_parameters``, ``truncate`` rounding)."""
    D, base = m["head_dim"], m["rope_theta"]
    factor, orig = m["yarn_factor"], m["yarn_original_max_positions"]

    def dim_of(rotations):
        return D * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim_of(m["yarn_beta_slow"])), D - 1)
    pos = base ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ramp = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0, 1)
    extrapolated = 1 - ramp
    inv = (1 / (factor * pos)) * ramp + (1 / pos) * extrapolated
    scale = m["yarn_attention_factor"] or 0.1 * math.log(factor) + 1
    return inv.astype(np.float32), float(scale)


def yarn_rotary(x, pos, m):
    """Rotate-half rotary over every head dim with YaRN's frequencies and
    cos and sin scaled by its attention factor; x (B,S,H,D)."""
    inv, scale = yarn(m)
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos = scale * jnp.cos(ang)[None, :, None]
    sin = scale * jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int, mode: str):
    """Causal softmax attention (window > 0: keys k > q - window), one kv
    head's query group at a time under rematerialisation, so that one
    group's scores are live at once; q (B,S,H,D), k and v (B,S,Hkv,D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    allowed = ki <= qi
    if window > 0:
        allowed &= ki > qi - window

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args                       # (B,S,G,D), (B,S,D) x2
        s = mm("bqgd,bkd->bgqk", qg, kg, mode) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(allowed[None, None], s, -jnp.inf), -1)
        return mm("bgqk,bkd->bqgd", a, vg, mode)

    qg = jnp.moveaxis(q.reshape(B, S, Hkv, G, D), 2, 0)
    o = jax.lax.map(group, (qg, jnp.moveaxis(k, 2, 0),
                            jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(o, 0, 2).reshape(B, S, H, D)


def experts(p: Dict[str, jax.Array], h: jax.Array, m: Dict[str, Any],
            mode: str) -> jax.Array:
    """The held experts' part of the layer over h (B,S,d): the router over
    every expert, the top-k renormalised, and each held expert run over
    every token, weighted by the gate it has for that token (0 where the
    token did not choose it)."""
    K, Eh, off = m["experts_per_token"], m["experts_held"], m["expert_offset"]
    probs = jax.nn.softmax(mm("bsd,de->bse", h, p["router"], mode), -1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / gate.sum(-1, keepdims=True)
    y = jnp.zeros(h.shape, jnp.float32)
    for e in range(Eh):
        w = jnp.sum(jnp.where(idx == off + e, gate, 0.0), -1)   # (B,S)
        u = jax.nn.silu(mm("bsd,df->bsf", h, p["wi_gate"][e], mode)) \
            * mm("bsd,df->bsf", h, p["wi_up"][e], mode)
        y = y + w[..., None] * mm("bsf,fd->bsd", u, p["wo"][e], mode)
    return y


def block(p: Dict[str, jax.Array], x: jax.Array, m: Dict[str, Any],
          mode: str, kind: str) -> jax.Array:
    """One decoder layer of ``kind`` over x (B,S,d), positions 0..S-1."""
    S = x.shape[1]
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(S)
    rot = (partial(yarn_rotary, m=m) if kind == "full" and m["yarn_factor"]
           else partial(rotary, theta=m["rope_theta"]))
    h = rms_norm(x, f32(p["ln1"]), m["norm_eps"])
    q = rot(mm("bsd,dhe->bshe", h, p["w_q"], mode), pos)
    k = rot(mm("bsd,dhe->bshe", h, p["w_k"], mode), pos)
    v = mm("bsd,dhe->bshe", h, p["w_v"], mode)
    window = m["sliding_window"] if kind == "sliding" else 0
    o = attention(q, k, v, window, mode)
    x = x + mm("bshe,hed->bsd", o, p["w_o"], mode)
    return x + experts(p, rms_norm(x, f32(p["ln2"]), m["norm_eps"]), m, mode)


def _layer_params(params: Dict[str, Any], i) -> Dict[str, jax.Array]:
    b = params["blocks"]
    return {"ln1": b["ln1"][i], "ln2": b["ln2"][i],
            **{k: v[i] for k, v in b["attn"].items()},
            **{k: v[i] for k, v in b["moe"].items()}}


def _kind(m: Dict[str, Any], i: int) -> str:
    pattern = m["layer_pattern"]
    return pattern[i % len(pattern)]


def _head(params):
    return params["embed"].T if "head" not in params else params["head"]


def forward(params: Dict[str, Any], tokens: jax.Array, m: Dict[str, Any],
            mode: str = "f32") -> jax.Array:
    """Logits (B,S,V) in float32: the whole model in one function."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(m["num_layers"]):
            x = block(_layer_params(params, i), x, m, mode, _kind(m, i))
        x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                     m["norm_eps"])
        return mm("bsd,dv->bsv", x, _head(params), mode)


# -- serving: layer by layer, logits only at the rows that are compared ------
@partial(jax.jit, static_argnames=("mode", "mkey", "kind"))
def _block_jit(p, x, mode, mkey, kind):
    with jax.default_matmul_precision("highest"):
        return block(p, x, dict(mkey), mode, kind)


@partial(jax.jit, static_argnames=("mode", "eps"))
def _rows_logits(x, norm, head, rows, mode, eps):
    x = rms_norm(x, norm.astype(jnp.float32), eps)
    xr = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return mm("bsd,dv->bsv", xr, head, mode)


def row_logits(params: Dict[str, Any], tokens: np.ndarray, rows: np.ndarray,
               m: Dict[str, Any], mode: str = "f32") -> jax.Array:
    """Logits (B,R,V) at positions ``rows`` (B,R) of ``tokens`` (B,S), one
    layer at a time so that only one layer's float32 copy is ever live."""
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()
                        if isinstance(v, (int, float, str, bool, list))))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["num_layers"]):
        x = _block_jit(_layer_params(params, i), x, mode, mkey, _kind(m, i))
    return _rows_logits(x, params["final_norm"], _head(params),
                        jnp.asarray(rows, jnp.int32), mode, m["norm_eps"])


# -- work counts -------------------------------------------------------------
def matmul_params(m: Dict[str, Any]) -> float:
    """Weights that take part in a matrix product per token, at the
    expected load: every layer's attention projections and router, the
    held experts' share of a token's experts (experts_per_token x
    experts_held / num_experts of them), and the output head."""
    d, f, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    hd = m["head_dim"]
    attn = d * hd * (2 * m["num_heads"] + 2 * m["num_kv_heads"])
    experts = (m["experts_per_token"] * m["experts_held"]
               / m["num_experts"]) * 3 * d * f
    return float(L * (attn + d * m["num_experts"] + experts) + d * v)


def _keys_per_query(m: Dict[str, Any], n: int, i: int) -> float:
    """Mean keys a query of an n-position sequence attends in layer i."""
    w = m["sliding_window"] if _kind(m, i) == "sliding" else 0
    if w <= 0 or w >= n:
        return (n + 1) / 2.0
    return (w * (w + 1) / 2.0 + (n - w) * w) / n


def _attention_flops(m: Dict[str, Any], n: int) -> float:
    """Causal attention forward over an n-position sequence, every layer,
    window layers at their windowed key counts."""
    per_key = 4.0 * m["num_heads"] * m["head_dim"]
    return sum(per_key * n * _keys_per_query(m, n, i)
               for i in range(m["num_layers"]))


def train_work(m: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """What a training step of ``batch`` rows of ``seq`` tokens counts: one
    attention call (one layer's forward over the batch) in operations and
    bytes, its operations the mean over the layers, window layers at their
    windowed key counts, as each layer runs the same number of calls; and
    the step's operations per token: six per weight of every matrix product
    at the expected expert load and three times the attention forward
    (rematerialised work is not counted)."""
    _, b_att = causal_attention(seq, m["num_heads"], m["num_kv_heads"],
                                m["head_dim"])
    f_att = _attention_flops(m, seq) / m["num_layers"]
    return {"attn_call_flops": batch * f_att,
            "attn_call_bytes": batch * b_att,
            "model_flops_per_token": 6.0 * matmul_params(m)
            + 3.0 * _attention_flops(m, seq) / seq}


def prefill_flops(m: Dict[str, Any], n: int) -> float:
    """Operations of one prompt's prefill over its ``n`` valid positions:
    two per weight of every matrix product per token at the expected
    expert load, and every layer's attention (window layers windowed)."""
    return 2.0 * matmul_params(m) * n + _attention_flops(m, n)


def decode_work(m: Dict[str, Any], n: int, first: int,
                end: int) -> Dict[str, float]:
    """The decode rows that make tokens ``first`` .. ``end - 1`` (first >= 1)
    of a request with an ``n``-token prompt, where the row that makes token
    t attends the n + t positions before it (a window layer at most
    ``sliding_window`` of them): their attention's operations and least
    bytes, and their model operations (two per weight of every matrix
    product at the expected expert load, and the attention)."""
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    rows = end - first
    kv = 0.0
    for i in range(m["num_layers"]):
        w = m["sliding_window"] if _kind(m, i) == "sliding" else 0
        kv += sum(min(n + t, w) if w > 0 else n + t
                  for t in range(first, end))
    attn = decode_attention(1, H, Hkv, D)[0] * kv
    return {"decode_attn_flops": attn,
            "decode_attn_bytes": (2.0 * Hkv * kv + 2.0 * H * rows
                                  * m["num_layers"]) * D * 2,
            "decode_model_flops": 2.0 * matmul_params(m) * rows + attn}
