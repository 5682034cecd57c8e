"""Mixture-of-Experts FFN — the MIMDRAM MIMD-segment showcase.

Two paths. :func:`held_moe_ffn` (``experts_held > 0``) is one device's share
of an expert-parallel layer: it routes over every expert, computes only the
experts it holds, dropless, through the grouped matmul kernel. :func:`moe_ffn`
holds every expert, sharded over the 'model' axis as independent programs in
different mesh segments, with the capacity-bounded scatter/gather dispatch:
O(T*k) routing work plus O(E*C*d*ff) expert compute — no O(T*E*C) one-hot
tensors; tokens past an expert's capacity are dropped.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ModelConfig
from repro.core.mimdram import constrain
from repro.kernels.grouped_matmul import grouped_matmul
from repro.launch import tracing
from repro.models import module as mod
from repro.models.layers import dense


def moe_param_specs(cfg: ModelConfig, dtype: Any) -> Dict[str, mod.ParamSpec]:
    """The router over all ``num_experts``, and the experts this layer
    holds (``experts_held``, or all of them)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    h = cfg.experts_held or e
    return {
        "router": mod.spec((d, e), ("embed", "expert"), dtype),
        "wi_gate": mod.spec((h, d, f), ("expert", "embed", "mlp"), dtype, ("normal", 1)),
        "wi_up": mod.spec((h, d, f), ("expert", "embed", "mlp"), dtype, ("normal", 1)),
        "wo": mod.spec((h, f, d), ("expert", "mlp", "embed"), dtype, ("normal", 1)),
    }


def held_moe_ffn(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> ((B, S, D), stats): the part of a dropless top-k MoE
    that the held experts ``[expert_offset, expert_offset + experts_held)``
    give.

    The router (float32, at the highest matmul precision) scores all
    ``num_experts``; each token keeps its top ``experts_per_token``, with
    gates renormalised over them. Of those (token, expert) pairs the held
    ones are sorted by expert into a buffer of ``T * min(K, E_held)`` rows,
    as many as there can be, so no pair is ever dropped and a token's
    result never depends on its batch mates. The held experts run as a
    SwiGLU through the grouped matmul, and each pair's row is gathered back
    and weighted by its gate. The absent experts' part is left out (the
    other devices of the layer give it). ``stats``: ``moe_rows``, the pairs
    routed here, and ``moe_load_max``, the busiest held expert's rows over
    the mean.
    """
    B, S, D = x.shape
    T = B * S
    K, Eh, off = cfg.experts_per_token, cfg.experts_held, cfg.expert_offset
    M = T * min(K, Eh)
    xt = x.reshape(T, D)
    with tracing.scope("moe_route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        gate = gate / gate.sum(-1, keepdims=True)
        local = idx - off
        held = (local >= 0) & (local < Eh)                      # (T, K)
        key = jnp.where(held, local, Eh).reshape(T * K)
        # pairs in expert order (a stable sort keeps token order within an
        # expert); the held ones fill the buffer's first rows
        order = jnp.argsort(key, stable=True)[:M]
        one_hot = (key[:, None] == jnp.arange(Eh)[None, :]).astype(jnp.int32)
        sizes = one_hot.sum(0)                                  # (Eh,)
        # each held pair's row: its expert's first row plus its rank there
        rank = ((jnp.cumsum(one_hot, 0) - one_hot) * one_hot).sum(-1)
        first = jnp.cumsum(sizes) - sizes
        # a pair that is not held points past the buffer
        row = jnp.where(held.reshape(-1),
                        first[jnp.minimum(key, Eh - 1)] + rank, M)
        total = sizes.sum()
        filled = (jnp.arange(M) < total)[:, None]
        xs = jnp.where(filled, xt[order // K], 0)
    with tracing.scope("moe_experts"):
        g = grouped_matmul(xs, p["wi_gate"], sizes)
        u = grouped_matmul(xs, p["wi_up"], sizes)
        ys = grouped_matmul(jax.nn.silu(g) * u, p["wo"], sizes)
    with tracing.scope("moe_combine"):
        # only written rows are read: the gather fills the pairs that point
        # past the buffer with zero
        y = ys.at[row].get(mode="fill", fill_value=0).reshape(T, K, D)
        y = (y.astype(jnp.float32) * gate[..., None]).sum(1).astype(x.dtype)
    stats = {"moe_rows": total,
             "moe_load_max": sizes.max() * Eh / jnp.maximum(total, 1)}
    return y.reshape(B, S, D), stats


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(tokens * cfg.experts_per_token * cfg.capacity_factor // cfg.num_experts)
    return max(c, cfg.experts_per_token)


def _dispatch_plan(T: int):
    """(n_groups, manual_axes, mesh): one dispatch group per data shard so
    routing (cumsum, scatter, gather) never crosses devices — the
    MIMDRAM-style 'keep work inside the mat' rule. Off-mesh: (1, (), None).
    """
    from repro.core.mimdram import _axis_size, current_plan  # noqa: PLC0415
    plan = current_plan()
    if plan is None or plan.mesh is None:
        return 1, (), None
    axes = plan.rules.get("act_batch") or ()
    g = _axis_size(plan.mesh, axes)
    if g <= 1 or T % g != 0:
        return 1, (), None
    # when already inside a shard_map (e.g. the Proteus cross-pod step), the
    # nested shard_map must carry the context mesh's axis types
    ctx = compat.context_mesh()
    mesh = ctx if (ctx is not None
                   and set(plan.mesh.axis_names) <= set(ctx.axis_names)) \
        else compat.abstract_mesh(plan.mesh)
    return g, tuple(axes), mesh


def _scatter_to_buffers(xt, idx, slot, keep, E: int, C: int, axes, mesh):
    """(G,Tl,D),(G,Tl,K)x3 -> (E,G,C,D). Manual over the data axes so the
    scatter is provably device-local (GSPMD would otherwise all-reduce the
    whole buffer); expert/model axes stay auto."""

    def local(xt1, idx1, slot1, keep1):
        # shapes (1, Tl, ...) per shard
        buf = jnp.zeros((E, 1, C, xt1.shape[-1]), xt1.dtype)
        scat = xt1[0, :, None, :] * keep1[0, ..., None]
        return buf.at[idx1[0], 0, slot1[0]].add(scat, mode="drop")

    if mesh is None:
        return local(xt, idx, slot, keep)
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415
    sm = compat.shard_map(
        local, mesh=mesh,                 # abstract; composes in context
        in_specs=(P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(None, axes),
        axis_names=frozenset(axes), check_vma=False)
    return sm(xt, idx, slot, keep)


def _gather_from_buffers(y_buf, idx, slot, axes, mesh):
    """(E,G,C,D),(G,Tl,K)x2 -> (G,Tl,K,D), group-local."""

    def local(yb1, idx1, slot1):
        return yb1[:, 0][idx1[0], slot1[0]][None]            # (1,Tl,K,D)

    if mesh is None:
        return local(y_buf, idx, slot)
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415
    sm = compat.shard_map(
        local, mesh=mesh,                 # abstract; composes in context
        in_specs=(P(None, axes), P(axes), P(axes)),
        out_specs=P(axes),
        axis_names=frozenset(axes), check_vma=False)
    return sm(y_buf, idx, slot)


def moe_ffn(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """x: (B, S, D) -> (B, S, D). Dropped-token, capacity-bounded top-k MoE.

    Dispatch is *group-local* (GShard/Switch-style): tokens are routed within
    their data shard's group; per-group capacity buffers keep scatter/gather
    and the position cumsum device-local, and only the expert einsum crosses
    the mesh (expert/capacity dims on the model axis).
    """
    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.experts_per_token
    G, dax, mesh = _dispatch_plan(T)
    Tl = T // G
    C = _capacity(cfg, Tl)                                   # per-group
    xt = x.reshape(G, Tl, D)
    xt = constrain(xt, "act_batch", None, None)

    logits = dense(xt, p["router"], "gtd,de->gte").astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                      # (G, Tl, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position within the group's expert buffer: group-local cumsum
    oh = jax.nn.one_hot(idx.reshape(G, Tl * K), E, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=1) - oh                        # slots before mine
    pos = (pos * oh).sum(-1).reshape(G, Tl, K)
    keep = (pos < C).astype(x.dtype)
    slot = jnp.minimum(pos, C - 1)

    # scatter tokens into (E, G, C, D) buffers (gates applied at combine);
    # device-local by construction (manual over the data axes).
    buf = _scatter_to_buffers(xt, idx, slot, keep, E, C, dax, mesh)
    buf = constrain(buf, "act_expert", "act_batch", "act_cap", None)

    # expert FFN: independent per-segment programs (MIMD over 'model' axis)
    g = jnp.einsum("egcd,edf->egcf", buf, p["wi_gate"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.einsum("egcd,edf->egcf", buf, p["wi_up"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = jax.nn.silu(g) * u
    h = constrain(h, "act_expert", "act_batch", "act_cap", "act_ff")
    y_buf = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    y_buf = constrain(y_buf, "act_expert", "act_batch", "act_cap", None)

    # gather back and combine with gates (group-local)
    y = _gather_from_buffers(y_buf, idx, slot, dax, mesh)    # (G, Tl, K, D)
    y = (y * (gate[..., None].astype(x.dtype)) * keep[..., None]).sum(axis=2)
    return y.reshape(B, S, D)


def moe_ffn_ref(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
                respect_capacity: bool = True) -> jax.Array:
    """Dense oracle: every token through every expert, masked combine (tests)."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.experts_per_token
    C = _capacity(cfg, T)
    xt = x.reshape(T, D).astype(jnp.float32)
    logits = xt @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_idx = idx.reshape(-1)
    oh = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)
    pos = ((jnp.cumsum(oh, axis=0) - oh) * oh).sum(-1).reshape(T, K)
    keep = (pos < C) if respect_capacity else jnp.ones_like(pos, bool)

    y = jnp.zeros((T, D), jnp.float32)
    for e in range(E):
        g = jax.nn.silu(xt @ p["wi_gate"][e].astype(jnp.float32))
        u = xt @ p["wi_up"][e].astype(jnp.float32)
        ye = (g * u) @ p["wo"][e].astype(jnp.float32)
        w = ((idx == e) * keep * gate).sum(axis=-1)          # (T,)
        y = y + ye * w[:, None]
    return y.reshape(B, S, D).astype(x.dtype)


def load_balance_loss(router_probs: jax.Array, idx: jax.Array, E: int) -> jax.Array:
    """Switch-style auxiliary loss (fraction-routed * mean-prob)."""
    me = router_probs.mean(axis=0)                           # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(idx.size, 1)
    return E * jnp.sum(me * ce)
