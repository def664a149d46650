"""Grouped matrix products over an expert share's sorted rows, Pallas TPU.

The rows of ``lhs`` are sorted by group (a held expert): group g owns
rows ``offsets[g] .. offsets[g+1]``, ``offsets`` the running sum of
``group_sizes``; rows after the last group belong to none.

  gmm   out[rows of g] = lhs[rows of g] @ rhs[g]       (name "moe_gmm")
  tgmm  out[g] = lhs[rows of g]^T @ rhs[rows of g]     (name "moe_tgmm")

The grid walks (n tile, row tile visit, k tile). A row tile that two
groups share is visited once for each, and each visit stores only its
own group's rows. The number of visits is known only on the device, so
it is a dynamic grid dimension: rows that belong to no group cost
nothing, and gmm leaves them unwritten (the caller masks them); tgmm
never reads them and writes zeros for an empty group.

Adapted from ``jax.experimental.pallas.ops.tpu.megablox`` (Apache-2.0),
without its sharded-group offset and in-place output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a whole expert matrix of a 2048-wide model fits as one (k, n) tile,
# double-buffered beside the row tiles: more than the default scoped
# VMEM, well under the v5e core's 128 MiB
VMEM_LIMIT = 64 * 2 ** 20
MAX_TILE = 2048


def _tile(dim: int) -> int:
    """The block along a k or n dimension: the whole dimension up to
    ``MAX_TILE``, else the largest multiple of 128 under it that divides
    the dimension."""
    if dim <= MAX_TILE:
        return dim
    for t in range(MAX_TILE, 0, -128):
        if dim % t == 0:
            return t
    raise ValueError(f"no tile of a multiple of 128 divides {dim}")


def group_metadata(group_sizes, m: int, tm: int, visit_empty: bool):
    """((offsets, group_ids, m_tile_ids), n_visits). Visit i computes
    row tile ``m_tile_ids[i]`` for group ``group_ids[i]``; an empty
    group gets one visit when ``visit_empty`` (tgmm zeroes its output
    there)."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    tiles = ((ends + tm - 1) // tm - starts // tm).astype(jnp.int32)
    tiles = jnp.where(group_sizes == 0, 1 if visit_empty else 0, tiles)
    tiles_m = m // tm
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), tiles,
                           total_repeat_length=tiles_m + n_groups - 1)
    # a tile is visited once by the group that owns its first row, and
    # once more by each group that starts inside it
    starts_inside = (starts % tm != 0) & (group_sizes > 0)
    if visit_empty:
        starts_inside = starts_inside | (group_sizes == 0)
    partial = jnp.where(starts_inside, starts // tm, tiles_m)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[partial].add(1)[:-1] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=tiles_m + n_groups - 1)
    return (offsets, group_ids, m_tile_ids), jnp.sum(tiles)


def _rows_of_group(meta, visit, tm: int, width: int):
    """(tm, width) mask of the visited tile's rows that belong to the
    visit's group."""
    offsets, group_ids, m_tile_ids = meta
    g = group_ids[visit]
    row = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + (
        m_tile_ids[visit] * tm)
    return (row >= offsets[g]) & (row < offsets[g + 1])


@functools.partial(jax.jit, static_argnames=("tm", "transpose_rhs",
                                             "interpret"))
def gmm_fwd(lhs, rhs, group_sizes, *, tm: int, transpose_rhs: bool = False,
            interpret: bool = False):
    """lhs (m, k) sorted by group; rhs (G, k, n), or (G, n, k) with
    ``transpose_rhs``; -> (m, n) in lhs's dtype, f32 accumulation."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tk, tn = _tile(k), _tile(n)
    meta, n_visits = group_metadata(group_sizes, m, tm, visit_empty=False)
    tiles_k = k // tk

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ()))
        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            mask = _rows_of_group(meta, visit, tm, tn)
            out_ref[...] = lax.select(
                mask, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_map(n_i, visit, k_i, meta):
        return meta[2][visit], k_i

    def rhs_map(n_i, visit, k_i, meta):
        g = meta[1][visit]
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, visit, k_i, meta):
        return meta[2][visit], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, n_visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.itemsize * (n // tn)
                            + rhs.size * rhs.itemsize
                            + m * n * lhs.itemsize)),
        name="moe_gmm",
    )(meta, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("tm", "n_groups", "out_dtype",
                                             "interpret"))
def tgmm_fwd(lhs, rhs, group_sizes, *, tm: int, n_groups: int, out_dtype,
             interpret: bool = False):
    """lhs (m, k), rhs (m, n), rows sorted by group -> (G, k, n):
    each group's lhs rows transposed times its rhs rows, f32
    accumulation; zeros for an empty group."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tk, tn = _tile(k), _tile(n)
    meta, n_visits = group_metadata(group_sizes, m, tm, visit_empty=True)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        visit = pl.program_id(2)
        offsets, group_ids, _ = meta
        g = group_ids[visit]
        prev = group_ids[jnp.maximum(visit - 1, 0)]
        last = pl.num_programs(2) - 1
        nxt = group_ids[jnp.minimum(visit + 1, last)]

        @pl.when((visit == 0) | (prev != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[g + 1] > offsets[g])
        def _():
            x = lax.select(_rows_of_group(meta, visit, tm, tk),
                           lhs_ref[...].astype(jnp.float32),
                           jnp.zeros((tm, tk), jnp.float32))
            y = lax.select(_rows_of_group(meta, visit, tm, tn),
                           rhs_ref[...].astype(jnp.float32),
                           jnp.zeros((tm, tn), jnp.float32))
            acc_ref[...] += lax.dot(
                x.swapaxes(0, 1).astype(lhs_ref.dtype),
                y.astype(rhs_ref.dtype), preferred_element_type=jnp.float32)

        @pl.when((visit == last) | (nxt != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, visit, meta):
        return meta[2][visit], k_i

    def rhs_map(n_i, k_i, visit, meta):
        return meta[2][visit], n_i

    def out_map(n_i, k_i, visit, meta):
        return meta[1][visit], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, n_visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.itemsize * (n // tn)
                            + rhs.size * rhs.itemsize * (k // tk)
                            + n_groups * k * n
                            * jnp.dtype(out_dtype).itemsize)),
        name="moe_tgmm",
    )(meta, lhs, rhs)
