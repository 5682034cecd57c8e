"""Pure-jnp oracle for the grouped matmul: one matmul per group."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array,
                       group_sizes) -> jax.Array:
    """lhs (m, k), rhs (num_groups, k, n) -> (m, n) in float32; rows past
    the groups' total are zero."""
    sizes = np.asarray(group_sizes)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    start = 0
    for g, size in enumerate(sizes):
        rows = lhs[start:start + size].astype(jnp.float32)
        out = out.at[start:start + size].set(
            rows @ rhs[g].astype(jnp.float32))
        start += size
    return out
