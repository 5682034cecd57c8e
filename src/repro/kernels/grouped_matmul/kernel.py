# Adapted from JAX's megablox grouped matmul
# (jax/experimental/pallas/ops/tpu/megablox/gmm.py, JAX 0.9.0),
# Copyright 2024 The JAX Authors, licensed under the Apache License,
# Version 2.0 (https://www.apache.org/licenses/LICENSE-2.0).
# Changes: the kernels carry names (``gmm``, ``tgmm``) so that a profile
# tells them apart; the Pallas entry points come through ``repro.compat``;
# the group offset, the existing-output alias, tile look-up tables and cost
# estimates are dropped (every group is local, tiles come from ``ops.py``).
"""Grouped matrix multiplication Pallas TPU kernels.

Rows of ``lhs`` are sorted by group; group ``i`` owns rows
``[offsets[i], offsets[i + 1])`` where ``offsets`` is the running sum of
``group_sizes``. Rows past the last group's end are neither read into a
product that is stored nor written: their output holds whatever the buffer
held, so a caller must never read them.

* :func:`gmm` — ``out[rows of i] = lhs[rows of i] @ rhs[i]``, the forward
  of an expert layer and its input gradient (``transpose_rhs``). The grid
  walks only the row tiles that hold groups, so its length follows the
  routed rows, not the buffer's.
* :func:`tgmm` — ``out[i] = lhs[:, rows of i] @ rhs[rows of i]``, the
  weight gradient; an empty group's output is zero.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.compat import (import_pallas, pallas_prefetch_grid_spec,
                          pallas_vmem_scratch)

pl = import_pallas()

GroupMetadata = Tuple[jax.Array, jax.Array, jax.Array]


def make_group_metadata(group_sizes: jax.Array, m: int, tm: int,
                        visit_empty_groups: bool
                        ) -> Tuple[GroupMetadata, jax.Array]:
    """((group_offsets, group_ids, m_tile_ids), num_tiles) for a grid over
    ``m`` rows in tiles of ``tm``.

    ``group_offsets`` (num_groups + 1) are the groups' first rows;
    grid step ``g`` works on row tile ``m_tile_ids[g]`` for group
    ``group_ids[g]``. A tile that two groups share is visited once per
    group, consecutively; ``num_tiles`` is how many steps hold a group.
    With ``visit_empty_groups`` an empty group gets one step too (``tgmm``
    must zero its output).
    """
    num_groups = group_sizes.shape[0]
    group_ends = jnp.cumsum(group_sizes)
    group_offsets = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), group_ends])
    # a group covers every tile from the one holding its first row to the
    # one holding its last
    rounded_ends = ((group_ends + tm - 1) // tm * tm).astype(jnp.int32)
    group_starts = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), group_ends[:-1]])
    rounded_starts = group_starts // tm * tm
    rounded_sizes = jnp.where(group_sizes == 0, 0,
                              rounded_ends - rounded_starts)
    group_tiles = rounded_sizes // tm
    if visit_empty_groups:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    tiles_m = m // tm
    group_ids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32),
                           group_tiles,
                           total_repeat_length=tiles_m + num_groups - 1)
    # each tile is visited once by the group owning its first row, and
    # once more by each group that starts inside it
    partial_tile = jnp.logical_or((group_offsets[:-1] % tm) == 0,
                                  group_sizes == 0)
    if visit_empty_groups:
        partial_tile = jnp.where(group_sizes == 0, 0, partial_tile)
    partial_tile_ids = jnp.where(partial_tile, tiles_m,
                                 group_offsets[:-1] // tm)
    tile_visits = jnp.histogram(partial_tile_ids, bins=tiles_m,
                                range=(0, tiles_m - 1))[0] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32),
                            tile_visits.astype(jnp.int32),
                            total_repeat_length=tiles_m + num_groups - 1)
    return (group_offsets, group_ids, m_tile_ids), group_tiles.sum()


def _rows_of_group(grid_id, meta: GroupMetadata, tm: int, tn: int):
    """(tm, tn) mask of the current tile's rows that belong to the current
    group."""
    group_offsets, group_ids, m_tile_ids = meta
    group = group_ids[grid_id]
    first = m_tile_ids[grid_id] * tm
    iota = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + first
    return jnp.logical_and(iota >= group_offsets[group],
                           iota < group_offsets[group + 1])


def _check_tiles(dims: Tuple[int, ...], tiles: Tuple[int, ...]) -> None:
    for d, t in zip(dims, tiles):
        if d % t:
            raise ValueError(f"dimension {d} is not a multiple of tile {t}")


@functools.partial(jax.jit, static_argnames=("out_dtype", "tiling",
                                             "transpose_rhs", "interpret"))
def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
        out_dtype, tiling: Tuple[int, int, int], transpose_rhs: bool = False,
        interpret: bool = False) -> jax.Array:
    """lhs (m, k), rhs (num_groups, k, n) (or (num_groups, n, k) with
    ``transpose_rhs``), group_sizes (num_groups,) int32 -> (m, n)."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    _check_tiles((m, k, n), (tm, tk, tn))
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_tiles = make_group_metadata(group_sizes, m, tm,
                                          visit_empty_groups=False)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # a tile shared by two groups is written by each in turn, each
            # keeping the other's rows
            mask = _rows_of_group(grid_id, meta, tm, tn)
            out_ref[...] = jax.lax.select(
                mask, acc_ref[...],
                out_ref[...].astype(jnp.float32)).astype(out_dtype)

    def lhs_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, meta):
        group = meta[1][grid_id]
        return (group, n_i, k_i) if transpose_rhs else (group, k_i, n_i)

    def out_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    grid_spec = pallas_prefetch_grid_spec()(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                  pl.BlockSpec(rhs_block, rhs_map)],
        out_specs=pl.BlockSpec((tm, tn), out_map),
        grid=(tiles_n, num_tiles, tiles_k),
        scratch_shapes=[pallas_vmem_scratch((tm, tn), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret, name="gmm")(meta, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tiling",
                                             "interpret"))
def tgmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
         out_dtype, tiling: Tuple[int, int, int],
         interpret: bool = False) -> jax.Array:
    """lhs (m, k) (the transpose of the product's left operand), rhs (m, n),
    group_sizes (num_groups,) int32 -> (num_groups, k, n): each group's
    ``lhs[rows].T @ rhs[rows]``. Rows outside every group are masked out
    by a select, so whatever they hold never reaches the output."""
    m, k = lhs.shape
    n = rhs.shape[1]
    num_groups = group_sizes.shape[0]
    tm, tk, tn = tiling
    _check_tiles((m, k, n), (tm, tk, tn))
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_tiles = make_group_metadata(group_sizes, m, tm,
                                          visit_empty_groups=True)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        group_offsets, group_ids, _ = meta
        grid_id = pl.program_id(2)
        group = group_ids[grid_id]
        prev = group_ids[jnp.where(grid_id > 0, grid_id - 1, 0)]

        @pl.when(jnp.logical_or(grid_id == 0, prev != group))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():
            lhs_rows = _rows_of_group(grid_id, meta, tm, tk)
            rhs_rows = _rows_of_group(grid_id, meta, tm, tn)
            a = jax.lax.select(lhs_rows, lhs_ref[...].astype(jnp.float32),
                               jnp.zeros((tm, tk), jnp.float32))
            b = jax.lax.select(rhs_rows, rhs_ref[...].astype(jnp.float32),
                               jnp.zeros((tm, tn), jnp.float32))
            acc_ref[...] += jax.lax.dot(
                a.swapaxes(0, 1).astype(lhs_ref.dtype),
                b.astype(rhs_ref.dtype), preferred_element_type=jnp.float32)

        last = grid_id == pl.num_programs(2) - 1
        nxt = group_ids[jnp.where(last, grid_id, grid_id + 1)]

        @pl.when(jnp.logical_or(last, group != nxt))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_dtype)

    def lhs_map(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], n_i

    def out_map(n_i, k_i, grid_id, meta):
        return meta[1][grid_id], k_i, n_i

    grid_spec = pallas_prefetch_grid_spec()(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                  pl.BlockSpec((tm, tn), rhs_map)],
        out_specs=pl.BlockSpec((None, tk, tn), out_map),
        grid=(tiles_n, tiles_k, num_tiles),
        scratch_shapes=[pallas_vmem_scratch((tk, tn), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        interpret=interpret, name="tgmm")(meta, lhs, rhs)
