"""Public wrapper: Mamba2/SSD over the generalized linear-scan kernel.

Mapping (see models/ssm.py): q=C, k=B (group-shared), v=dt*x, g=dt*A.
The D-skip, gating and projections stay in the model; this is only the
sequence-mixing hot loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.linear_scan.kernel import linear_scan_fwd
from repro.kernels.linear_scan.ref import linear_scan_ref


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_scan(g, q, k, v, *, chunk: int = 128,
                interpret: Optional[bool] = None):
    interp = resolve_interpret(interpret)
    return linear_scan_fwd(g, q, k, v, chunk=chunk, interpret=interp)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_mamba2(x, dt, A, B, C, *, chunk: int = 128,
               interpret: Optional[bool] = None):
    """x: (Bt,S,nh,hd); dt: (Bt,S,nh) post-softplus; A: (nh,) negative;
    B,C: (Bt,S,g,ds). Returns y (Bt,S,nh,hd) — the SSD sequence mix
    (without the D-skip, added by the caller)."""
    Bt, S, nh, hd = x.shape
    g_grp = B.shape[2]
    ds = B.shape[-1]
    # fold dt into v; build log-decays
    v = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(Bt * nh, S, hd)
    gdec = (dt * A[None, None, :]).transpose(0, 2, 1).reshape(Bt * nh, S)
    q = C.transpose(0, 2, 1, 3).reshape(Bt * g_grp, S, ds)
    k = B.transpose(0, 2, 1, 3).reshape(Bt * g_grp, S, ds)
    interp = resolve_interpret(interpret)
    y = linear_scan_fwd(gdec.astype(jnp.float32), q, k, v,
                        chunk=chunk, interpret=interp)
    return y.reshape(Bt, nh, S, hd).transpose(0, 2, 1, 3)


__all__ = ["linear_scan", "ssd_mamba2", "linear_scan_ref"]
