"""The grouped matmul kernels (``gmm``, ``tgmm``) in interpret mode against
one matmul per group, forward and gradients, with empty groups and fewer
routed rows than the buffer holds; and the tiles they take from shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul import (gmm, grouped_matmul,
                                          grouped_matmul_ref, tgmm, tiles)

# rows of a 1024-row buffer in five groups: two empty, groups that start
# inside a 512-row tile, and 124 rows past the last group
SIZES = (0, 300, 0, 500, 100)
M, K, N = 1024, 256, 384


def _operands(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((M, K)), dtype)
    rhs = jnp.asarray(rng.standard_normal((len(SIZES), K, N)), dtype)
    return lhs, rhs, jnp.asarray(SIZES, jnp.int32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / np.abs(b).max()


def test_tiles_come_from_shapes():
    # the expert layer's widths: 2304 = 3 x 768 deep, 896 wide whole
    assert tiles(163840, 2304, 896) == (512, 768, 896)
    assert tiles(163840, 896, 2304) == (512, 896, 768)
    assert tiles(512, 64, 32) == (512, 64, 32)      # smoke widths: whole


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_gmm_matches_per_group_matmuls(transpose_rhs):
    lhs, rhs, sizes = _operands()
    total = sum(SIZES)
    w = jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs
    out = gmm(lhs, w, sizes, out_dtype=jnp.float32, tiling=(512, 128, 128),
              transpose_rhs=transpose_rhs, interpret=True)
    ref = grouped_matmul_ref(lhs, rhs, SIZES)
    assert _rel(out[:total], ref[:total]) < 1e-6
    # rows past the routed total are not written: interpret mode leaves
    # them NaN, so a reader of them would show
    assert np.isnan(np.asarray(out[total:])).all()


def test_tgmm_matches_per_group_products_and_zeroes_empty_groups():
    lhs, _, sizes = _operands()
    rng = np.random.default_rng(1)
    rhs = np.asarray(rng.standard_normal((M, N)), np.float32)
    total = sum(SIZES)
    rhs[total:] = np.nan                # rows past the total never count
    out = tgmm(lhs, jnp.asarray(rhs), sizes, out_dtype=jnp.float32,
               tiling=(512, 128, 128), interpret=True)
    start = 0
    for g, size in enumerate(SIZES):
        want = np.asarray(lhs[start:start + size]).T @ rhs[start:start + size]
        if size == 0:
            assert (np.asarray(out[g]) == 0).all()
        else:
            assert _rel(out[g], want) < 1e-6
        start += size


def test_gradients_match_per_group_matmuls():
    lhs, rhs, sizes = _operands()
    total = sum(SIZES)
    rng = np.random.default_rng(2)
    cot = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    written = (jnp.arange(M) < total)[:, None]

    def loss(l, r):       # what a caller does: never reads unwritten rows
        return jnp.sum(jnp.where(written, grouped_matmul(l, r, sizes), 0)
                       * cot)

    def loss_ref(l, r):
        return jnp.sum(grouped_matmul_ref(l, r, SIZES) * cot)

    got = jax.jit(jax.grad(loss, argnums=(0, 1)))(lhs, rhs)
    want = jax.grad(loss_ref, argnums=(0, 1))(lhs, rhs)
    assert _rel(got[0][:total], want[0][:total]) < 1e-6
    assert _rel(got[1], want[1]) < 1e-6
    assert got[1].dtype == rhs.dtype


def test_bfloat16_operands_accumulate_in_float32():
    lhs, rhs, sizes = _operands(dtype=jnp.bfloat16)
    total = sum(SIZES)
    out = jax.jit(grouped_matmul)(lhs, rhs.astype(jnp.float32), sizes)
    assert out.dtype == jnp.bfloat16
    ref = grouped_matmul_ref(lhs, rhs, SIZES)
    assert _rel(out[:total], ref[:total]) < 1e-2
