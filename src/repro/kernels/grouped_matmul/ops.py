"""The grouped matmul with its gradients, and its tiles from shapes.

``grouped_matmul(lhs, rhs, group_sizes)`` is differentiable in ``lhs`` and
``rhs``: the input gradient is :func:`kernel.gmm` against the transposed
groups, the weight gradient :func:`kernel.tgmm`. Every product takes its
operands in ``lhs``'s dtype and accumulates in float32.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import use_interpret
from repro.kernels.grouped_matmul.kernel import gmm, tgmm

ROWS_PER_TILE = 512          # row tile: a few tiles per group at a time
LANES_PER_TILE = 1024        # cap of the k and n tiles


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is at most ``cap`` or has none."""
    if dim <= cap:
        return dim
    for t in range(cap // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def tiles(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of a product of ``m`` rows, ``k`` deep, ``n`` wide:
    2304 deep takes 768, 896 wide takes all of it."""
    return (_tile(m, ROWS_PER_TILE), _tile(k, LANES_PER_TILE),
            _tile(n, LANES_PER_TILE))


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, out_dtype=lhs.dtype,
               tiling=tiles(m, k, n), transpose_rhs=transpose_rhs,
               interpret=use_interpret())


@jax.custom_vjp
def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """lhs (m, k) with its rows sorted by group, rhs (num_groups, k, n),
    group_sizes (num_groups,) int32 -> (m, n). Rows past
    ``group_sizes.sum()`` are not written; the gradient of ``lhs`` is not
    written there either."""
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, grad):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(grad, rhs.astype(grad.dtype), group_sizes,
                 transpose_rhs=True)
    m, k = lhs.shape
    n = grad.shape[1]
    d_rhs = tgmm(lhs, grad.astype(lhs.dtype), group_sizes,
                 out_dtype=jnp.float32, tiling=tiles(m, k, n),
                 interpret=use_interpret())
    return d_lhs, d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_fwd, _bwd)
