"""Pure-jnp oracle for the grouped matrix products: each group's rows
multiplied densely and kept by a row mask. Rows in no group read 0."""
from __future__ import annotations

import jax.numpy as jnp


def _row_masks(group_sizes, m: int):
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(m)
    return (rows[None, :] >= starts[:, None]) & (rows[None, :] < ends[:, None])


def gmm_ref(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    """lhs (m, k) sorted by group; rhs (G, k, n) or (G, n, k) ->
    (m, n) in lhs's dtype."""
    masks = _row_masks(group_sizes, lhs.shape[0])
    spec = "mk,gnk->gmn" if transpose_rhs else "mk,gkn->gmn"
    y = jnp.einsum(spec, lhs, rhs, preferred_element_type=jnp.float32)
    out = jnp.sum(jnp.where(masks[:, :, None], y, 0.0), axis=0)
    return out.astype(lhs.dtype)


def tgmm_ref(lhs, rhs, group_sizes, out_dtype=None):
    """lhs (m, k), rhs (m, n) -> (G, k, n): each group's rows of lhs
    transposed times its rows of rhs."""
    masks = _row_masks(group_sizes, lhs.shape[0])
    x = jnp.where(masks[:, :, None], lhs[None], 0).astype(lhs.dtype)
    out = jnp.einsum("gmk,mn->gkn", x, rhs,
                     preferred_element_type=jnp.float32)
    return out.astype(out_dtype or rhs.dtype)
