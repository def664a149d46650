"""Chunked SSD / gated-linear-attention scan, Pallas TPU.

Computes, per head, the linear recurrence

    h_t = exp(g_t) h_{t-1} + k_t v_t^T          (state: ds x hd)
    y_t = q_t^T h_t

in chunk-parallel form: within a chunk the contribution is an
attention-like masked matmul (MXU work); across chunks a small (ds, hd)
f32 state is carried in VMEM scratch over the sequential chunk grid
dimension. This is the inner loop of Mamba2 (q=C, k=B, v=dt*x,
g=dt*A) — the wrapper in ops.py does that mapping.

Grid: (B*nh, S/chunk), chunk axis innermost/sequential.
Blocks: q,k: (1, chunk, ds); v: (1, chunk, hd); g: (1, chunk).
The B/C group->head broadcast is folded into the k/q index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(g_ref, q_ref, k_ref, v_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    g = g_ref[...].astype(jnp.float32)               # (1, chunk)
    cum = jnp.cumsum(g, axis=1)[0]                   # (chunk,)
    seg = cum[-1]

    q = q_ref[0].astype(jnp.float32)                 # (chunk, ds)
    k = k_ref[0].astype(jnp.float32)                 # (chunk, ds)
    v = v_ref[0].astype(jnp.float32)                 # (chunk, hd)

    # inter-chunk: y_off = (q * exp(cum)) @ h_in
    h_in = h_scr[...]                                # (ds, hd)
    q_dec = q * jnp.exp(cum)[:, None]
    y = jax.lax.dot_general(q_dec, h_in, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # intra-chunk: y += (q k^T ∘ L) v, L_ij = exp(cum_i - cum_j), i >= j
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    y = y + jax.lax.dot_general(qk * L, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: h = exp(seg) h_in + (k * exp(seg - cum))^T v
    k_dec = k * jnp.exp(seg - cum)[:, None]
    h_new = h_in * jnp.exp(seg) + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h_scr[...] = h_new

    y_ref[0] = y.astype(y_ref.dtype)


def linear_scan_fwd(g, q, k, v, *, chunk: int = 128,
                    interpret: bool = False):
    """g: (BH, S) log-decays; q,k: (BHG, S, ds) (group-shared);
    v: (BH, S, hd). BH = BHG * rep. Returns y (BH, S, hd)."""
    BH, S = g.shape
    BHG, _, ds = q.shape
    hd = v.shape[-1]
    rep = BH // BHG
    assert S % chunk == 0

    grid = (BH, S // chunk)

    def qk_map(bh, ci):
        return (bh // rep, ci, 0)

    out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk), lambda bh, ci: (bh, ci)),
            pl.BlockSpec((1, chunk, ds), qk_map),
            pl.BlockSpec((1, chunk, ds), qk_map),
            pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), v.dtype),
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],
        interpret=interpret,
        name="linear_scan",
    )(g, q, k, v)
    return out
