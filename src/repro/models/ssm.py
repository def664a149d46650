"""Mamba2 (SSD — state-space duality) block, chunked for TPU.

Training/prefill uses the chunked SSD algorithm: within a chunk the
output is a masked (decay-weighted) attention-like matmul; across chunks
a small recurrent state (nh, hd, d_state) is carried with ``lax.scan``.
Decode is the O(1) recurrence. The chunk kernel has a Pallas
implementation in ``repro.kernels.linear_scan`` (TPU target); this module
is the pure-XLA path used for dry-runs and CPU tests.

Shapes follow the Mamba2 paper: input (B, S, d_model), inner dim
d_in = expand*d, nh = d_in/head_dim heads, n_groups shared B/C groups.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamFactory


def mamba2_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    return d_in, nh


def mamba2_init(f: ParamFactory, cfg: ModelConfig, name: str = "mamba"):
    s = cfg.ssm
    d = cfg.d_model
    d_in, nh = mamba2_dims(cfg)
    m = f.child(name)
    # fused input projection -> [z, x, B, C, dt]
    d_proj = 2 * d_in + 2 * s.n_groups * s.d_state + nh
    m.param("w_in", (d, d_proj), ("embed", "mlp"))
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    m.param("w_conv", (s.d_conv, conv_dim), (None, "mlp"))
    m.param("b_conv", (conv_dim,), ("mlp",), init="zeros")
    m.param("a_log", (nh,), (None,), init="ones")
    m.param("dt_bias", (nh,), (None,), init="zeros")
    m.param("d_skip", (nh,), (None,), init="ones")
    m.param("norm_scale", (d_in,), ("mlp",), init="ones")
    m.param("w_out", (d_in, d), ("mlp", "embed"))


def _split_proj(p, cfg: ModelConfig, u):
    """u: (B,S,d) -> z,(B,S,d_in) xBC,(B,S,conv_dim) dt,(B,S,nh)."""
    s = cfg.ssm
    d_in, nh = mamba2_dims(cfg)
    proj = u @ p["w_in"].astype(u.dtype)
    z = proj[..., :d_in]
    xBC = proj[..., d_in:d_in + d_in + 2 * s.n_groups * s.d_state]
    dt = proj[..., -nh:]
    return z, xBC, dt


def _causal_conv(p, xBC, conv_state: Optional[jax.Array] = None):
    """Depthwise causal conv, width d_conv. xBC: (B,S,C). If conv_state
    (B, d_conv-1, C) is given (decode), prepend it and return new state."""
    w = p["w_conv"].astype(xBC.dtype)              # (W, C)
    W = w.shape[0]
    if conv_state is not None:
        xpad = jnp.concatenate([conv_state.astype(xBC.dtype), xBC], axis=1)
    else:
        xpad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xpad[:, i:i + xBC.shape[1], :] * w[i] for i in range(W))
    out = out + p["b_conv"].astype(xBC.dtype)
    new_state = xpad[:, -(W - 1):, :]
    return jax.nn.silu(out), new_state


def _gated_norm(p, y, z, eps):
    y = y * jax.nn.silu(z)
    dtype = y.dtype
    y32 = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    y32 = y32 * jax.lax.rsqrt(var + eps)
    return (y32 * p["norm_scale"].astype(jnp.float32)).astype(dtype)


def ssd_chunked(x, dt, A, B, C, chunk: int, unroll: bool = False):
    """Chunked SSD scan (pure XLA reference; Pallas version in kernels/).

    x: (Bt, S, nh, hd); dt: (Bt, S, nh) (post-softplus);
    A: (nh,) negative decay rates; B, C: (Bt, S, g, d_state).
    Returns y: (Bt, S, nh, hd) and final state (Bt, nh, hd, d_state).
    """
    Bt, S, nh, hd = x.shape
    g = B.shape[2]
    rep = nh // g
    nchunks = S // chunk
    assert S % chunk == 0

    xc = x.reshape(Bt, nchunks, chunk, nh, hd)
    dtc = dt.reshape(Bt, nchunks, chunk, nh)
    Bc = B.reshape(Bt, nchunks, chunk, g, -1)
    Cc = C.reshape(Bt, nchunks, chunk, g, -1)

    dA = dtc * A[None, None, None, :]                       # (Bt,nc,L,nh) <= 0
    cum = jnp.cumsum(dA, axis=2)                            # running log-decay
    seg_total = cum[:, :, -1, :]                            # (Bt,nc,nh)

    # --- intra-chunk (diagonal blocks): attention-like masked matmul ---
    # L_ij = exp(cum_i - cum_j + dA? ) for i >= j  (decay from j..i incl. i's dt·A? )
    # SSD convention: y_i += C_i . (sum_{j<=i} exp(cum_i - cum_j) dt_j B_j x_j)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (Bt,nc,L,L,nh)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE exp: the i<j entries have diff > 0 and would overflow,
    # poisoning gradients through the where
    diff = jnp.where(mask[None, None, :, :, None], diff, -jnp.inf)
    L = jnp.exp(diff)
    CB = jnp.einsum("bnigs,bnjgs->bnijg", Cc, Bc)           # (Bt,nc,L,L,g)
    CB = jnp.repeat(CB, rep, axis=-1)                       # (Bt,nc,L,L,nh)
    scores = CB * L * dtc[:, :, None, :, :]                 # weight by dt_j
    y_diag = jnp.einsum("bnijh,bnjhd->bnihd", scores.astype(x.dtype), xc)

    # --- chunk states: decay-weighted sum of B x within each chunk ---
    decay_to_end = jnp.exp(seg_total[:, :, None, :] - cum)  # (Bt,nc,L,nh)
    Bfull = jnp.repeat(Bc, rep, axis=3) if g != nh else Bc  # (Bt,nc,L,nh,s)
    Bx = jnp.einsum("bnlhs,bnlhd->bnhds",
                    Bfull, (xc * (dtc * decay_to_end)[..., None]).astype(Bfull.dtype))

    # --- inter-chunk recurrence over nchunks (small state) ---
    def step(h, inp):
        bx, seg = inp                                        # (Bt,nh,hd,s), (Bt,nh)
        h_new = h * jnp.exp(seg)[:, :, None, None] + bx
        return h_new, h                                      # emit state *entering* chunk

    h0 = jnp.zeros((Bt, nh, hd, Bc.shape[-1]), jnp.float32)
    h_last, h_in = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(Bx, 1, 0).astype(jnp.float32),
         jnp.moveaxis(seg_total, 1, 0).astype(jnp.float32)),
        unroll=unroll)
    h_in = jnp.moveaxis(h_in, 0, 1)                          # (Bt,nc,nh,hd,s)

    # --- inter-chunk contribution: y_i += exp(cum_i) C_i . h_in ---
    Cfull = jnp.repeat(Cc, rep, axis=3) if g != nh else Cc
    y_off = jnp.einsum("bnlhs,bnhds->bnlhd",
                       (Cfull * jnp.exp(cum)[..., None].astype(Cfull.dtype)),
                       h_in.astype(Cfull.dtype))

    y = (y_diag + y_off.astype(y_diag.dtype)).reshape(Bt, S, nh, hd)
    return y, h_last


def mamba2_apply(p, cfg: ModelConfig, u):
    """Full-sequence (train/prefill). u: (B,S,d) -> (B,S,d)."""
    s = cfg.ssm
    d_in, nh = mamba2_dims(cfg)
    z, xBC, dt = _split_proj(p, cfg, u)
    xBC, _ = _causal_conv(p, xBC)
    x = xBC[..., :d_in]
    Bmat = xBC[..., d_in:d_in + s.n_groups * s.d_state]
    Cmat = xBC[..., d_in + s.n_groups * s.d_state:]
    Bt, S, _ = u.shape
    chunk = min(s.chunk_size, S)
    pad = (-S) % chunk
    if pad:  # pad to a chunk multiple; padded steps have dt=0 => no effect
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)),
                     constant_values=-30.0)
    Sp = S + pad
    x = x.reshape(Bt, Sp, nh, s.head_dim)
    Bmat = Bmat.reshape(Bt, Sp, s.n_groups, s.d_state)
    Cmat = Cmat.reshape(Bt, Sp, s.n_groups, s.d_state)
    dt_act = jax.nn.softplus(dt.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    with jax.named_scope("model.mixer"):
        y, _ = ssd_chunked(x, dt_act, A, Bmat, Cmat, chunk,
                           unroll=cfg.scan_unroll)
    y = y + x * p["d_skip"].astype(x.dtype)[None, None, :, None]
    if pad:
        y = y[:, :S]
    y = y.reshape(Bt, S, d_in)
    y = _gated_norm(p, y, z, cfg.norm_eps)
    return y @ p["w_out"].astype(u.dtype)


# ---------------------------------------------------------------------------
# Decode (O(1) recurrence)
# ---------------------------------------------------------------------------
def mamba2_state_init(cfg: ModelConfig, n_layers: int, batch: int,
                      dtype=jnp.float32):
    s = cfg.ssm
    d_in, nh = mamba2_dims(cfg)
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return {
        "ssm": jnp.zeros((n_layers, batch, nh, s.head_dim, s.d_state), dtype),
        "conv": jnp.zeros((n_layers, batch, s.d_conv - 1, conv_dim), dtype),
    }


def mamba2_state_axes():
    return {"ssm": ("layers", "batch", "heads", None, None),
            "conv": ("layers", "batch", None, "mlp")}


def mamba2_decode(p, cfg: ModelConfig, u, ssm_state, conv_state):
    """One token. u: (B,1,d); ssm_state: (B,nh,hd,ds); conv_state:
    (B, d_conv-1, conv_dim). Returns (y, new_ssm, new_conv)."""
    s = cfg.ssm
    d_in, nh = mamba2_dims(cfg)
    z, xBC, dt = _split_proj(p, cfg, u)
    xBC, new_conv = _causal_conv(p, xBC, conv_state)
    x = xBC[..., :d_in].reshape(-1, nh, s.head_dim)
    Bmat = xBC[..., d_in:d_in + s.n_groups * s.d_state].reshape(-1, s.n_groups, s.d_state)
    Cmat = xBC[..., d_in + s.n_groups * s.d_state:].reshape(-1, s.n_groups, s.d_state)
    rep = nh // s.n_groups
    Bfull = jnp.repeat(Bmat, rep, axis=1)
    Cfull = jnp.repeat(Cmat, rep, axis=1)
    dt_act = jax.nn.softplus(dt[:, 0].astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))   # (B,nh)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt_act * A[None, :])                          # (B,nh)
    upd = jnp.einsum("bhd,bhs->bhds",
                     (x * dt_act[..., None].astype(x.dtype)).astype(jnp.float32),
                     Bfull.astype(jnp.float32))
    new_ssm = ssm_state * decay[:, :, None, None] + upd
    y = jnp.einsum("bhds,bhs->bhd", new_ssm.astype(x.dtype), Cfull.astype(x.dtype))
    y = y + x * p["d_skip"].astype(x.dtype)[None, :, None]
    y = y.reshape(-1, 1, d_in)
    y = _gated_norm(p, y, z, cfg.norm_eps)
    return y @ p["w_out"].astype(u.dtype), new_ssm, new_conv
