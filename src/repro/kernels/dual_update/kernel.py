"""Fused dual-averaging update, Pallas TPU.

The master's hot loop (paper eq. (3)-(4), psi = 0.5||w||^2):

    z <- z + g ;  w <- -alpha * z

Memory-bound: 2 reads + 2 writes per element. Fusing keeps z and w in
VMEM for one pass instead of XLA's two elementwise kernels, and donates
z (input_output_aliases) so no extra HBM allocation appears. Operates on
a flattened (rows, 128) lane-aligned view provided by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _update_kernel(alpha_ref, z_ref, g_ref, z_out_ref, w_out_ref):
    a = alpha_ref[0, 0]
    z = z_ref[...].astype(jnp.float32) + g_ref[...].astype(jnp.float32)
    z_out_ref[...] = z.astype(z_out_ref.dtype)
    w_out_ref[...] = (-a * z).astype(w_out_ref.dtype)


def _fused_kernel(scal_ref, z_ref, g_ref, z_out_ref, w_out_ref):
    a = scal_ref[0, 0]
    d = scal_ref[0, 1]
    g = g_ref[...].astype(jnp.float32) / d
    z = z_ref[...].astype(jnp.float32) + g
    z_out_ref[...] = z.astype(z_out_ref.dtype)
    w_out_ref[...] = (-a * z).astype(w_out_ref.dtype)


def dual_update_fused_fwd(z, g_sum, denom, alpha, *, block_rows: int = 256,
                          interpret: bool = False):
    """Arena entry point: consumes the *popped, un-normalized* gradient
    sum and fuses the anytime count-normalization into the same pass:

        w <- -alpha * (z + g_sum / denom) ;  z <- z + g_sum / denom

    z, g_sum: (rows, 128) f32; denom, alpha: scalars. Returns
    (z_new, w_new); z is donated."""
    rows, lanes = z.shape
    assert lanes == 128 and rows % block_rows == 0, (rows, lanes)
    scal = jnp.stack([jnp.float32(alpha), jnp.float32(denom)]).reshape(1, 2)
    grid = (rows // block_rows,)
    z_new, w_new = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 128), z.dtype),
            jax.ShapeDtypeStruct((rows, 128), z.dtype),
        ],
        input_output_aliases={1: 0},   # donate z -> z_new
        interpret=interpret,
        name="dual_update_fused",
    )(scal, z, g_sum)
    return z_new, w_new


def dual_update_fwd(z, g, alpha, *, block_rows: int = 256,
                    interpret: bool = False):
    """z, g: (rows, 128) f32; alpha: scalar f32.
    Returns (z_new, w_new) both (rows, 128)."""
    rows, lanes = z.shape
    assert lanes == 128 and rows % block_rows == 0, (rows, lanes)
    alpha2d = jnp.reshape(alpha.astype(jnp.float32), (1, 1))
    grid = (rows // block_rows,)
    z_new, w_new = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, 128), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 128), z.dtype),
            jax.ShapeDtypeStruct((rows, 128), z.dtype),
        ],
        input_output_aliases={1: 0},   # donate z -> z_new
        interpret=interpret,
        name="dual_update",
    )(alpha2d, z, g)
    return z_new, w_new
