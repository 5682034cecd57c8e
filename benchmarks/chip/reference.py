"""The plain reference, in jax.numpy, in float32 at the highest matrix
precision, with no kernels, cache or batching tricks: the parts every model
family shares. Each family's forward pass lives in ``families/<family>.py``
and is built from ``mm`` and ``rms_norm``; here are the loss, the gradients
summed over blocks of rows, and AdamW with global-norm clipping, linear
warm-up and cosine decay, as the training traffic file states them.

It imports nothing of the program.

``mode="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (one scale per tensor), in the forward and, for
training, in the backward too. It is the step below the bfloat16 the
configurations state.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return _ein(spec, _fp8(a), _fp8(b))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _ein(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(partial(_ein, spec), qa, qb)
    return vjp(_fp8(g))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(spec: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return _mm_fp8(spec, a, b) if mode == "fp8" else _ein(spec, a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# -- training: loss, gradients in row blocks, and AdamW ----------------------
def xent_sum(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy summed over every position of every row."""
    lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    tgt = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - tgt)


def make_loss_and_grad(forward: Callable, m: Dict[str, Any],
                       mode: str = "f32") -> Callable:
    """jit((params, tokens block) -> (summed loss, summed gradients)) of a
    family's ``forward``."""
    def f(params, tokens):
        return xent_sum(forward(params, tokens, m, mode), tokens)
    return jax.jit(jax.value_and_grad(f))


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, g):
    return jax.tree_util.tree_map(jnp.add, total, g)


def loss_and_grad(vg: Callable, params: Any, tokens: np.ndarray,
                  rows_per_block: int) -> Tuple[float, Any]:
    """Mean loss and mean gradient over every row, a block of rows at a
    time."""
    B, S = tokens.shape
    tot, grads = 0.0, None
    for r in range(0, B, rows_per_block):
        l, g = vg(params, jnp.asarray(tokens[r:r + rows_per_block]))
        tot += float(l)
        grads = g if grads is None else _accumulate(grads, g)
    n = B * (S - 1)
    return tot / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def lr_at(step: int, run: Dict[str, Any]) -> float:
    """Linear warm-up to ``lr``, then cosine decay to ``final_frac`` of it."""
    base, warm, total = run["lr"], run["warmup_steps"], run["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (run["final_frac"]
                   + (1 - run["final_frac"]) * 0.5 * (1 + math.cos(math.pi
                                                                   * prog)))


def leaf_norms(tree: Any) -> Dict[str, float]:
    """{"a/b/c": L2 norm} over the leaves of a nested dict, in float32."""
    out: Dict[str, float] = {}

    def walk(node, prefix):
        for k, v in node.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                walk(v, p)
            else:
                out[p] = v
    walk(tree, "")
    norms = jax.jit(lambda d: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in d.items()})(out)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"),
         donate_argnums=(0, 2, 3))
def adamw_step(params, grads, mu, nu, t, lr, *, b1, b2, eps, wd, clip):
    """One AdamW update after clipping the gradients to a global norm; also
    returns the clipped gradients' leaf norms. The parameters and moments
    are donated, so an update holds one copy of each."""
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    gnorm = jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
    mu = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m_, v: p - lr * ((m_ / bc1) / (jnp.sqrt(v / bc2) + eps)
                                   + wd * p), params, mu, nu)
    return params, gnorm, mu, nu


def train_steps(forward: Callable, params0: Any,
                batches: Sequence[np.ndarray], m: Dict[str, Any],
                run: Dict[str, Any], mode: str = "f32",
                rows_per_block: int = 2) -> Dict[str, Any]:
    """A family's ``forward`` through its first steps from ``params0`` (left
    as it is): each
    step's loss, the leaf norms of the first step's clipped gradient, and the
    leaf norms of the parameters' change over all the steps."""
    vg = make_loss_and_grad(forward, m, mode)
    params = jax.tree_util.tree_map(
        lambda p: jnp.array(p, jnp.float32, copy=True), params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses: List[float] = []
    first_grad = None
    for t, tokens in enumerate(batches, start=1):
        loss, g = loss_and_grad(vg, params, tokens, rows_per_block)
        losses.append(loss)
        params, gnorm, mu, nu = adamw_step(
            params, g, mu, nu, jnp.float32(t), jnp.float32(lr_at(t, run)),
            b1=run["b1"], b2=run["b2"], eps=run["eps"],
            wd=run["weight_decay"], clip=run["grad_clip"])
        del g
        if first_grad is None:
            first_grad = leaf_norms(gnorm)
    del mu, nu
    change = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(jnp.float32), params, params0))
    return {"losses": losses, "grad": first_grad, "change": change}
