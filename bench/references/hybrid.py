"""Plain float32 reference of a Mamba2 hybrid (zamba2): token embedding
scaled by sqrt(d_model); groups of ``shared_attn_every`` Mamba2 layers
(h += Mamba2(RMSNorm(h))), each group followed by one call of a single
shared block (h += Attn(RMSNorm(h)); h += MLP(RMSNorm(h))); a final
RMSNorm and logits from the output table (the embedding table when tied).

The Mamba2 mixer: one input projection to [z, x, B, C, dt]; a causal
depthwise convolution with bias and SiLU over [x, B, C];
dt = softplus(dt + dt_bias), A = -exp(a_log); the selective scan
h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t + D x_t,
computed exactly in chunks (quadratic inside a chunk, the state
carried between chunks); y * SiLU(z), RMSNorm, output projection.
Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from references.common import (Dot, attention, mlp, next_token_nll,
                               output_table, rmsnorm)


def _dims(cfg):
    s = cfg["ssm"]
    d_in = s["expand"] * cfg["d_model"]
    nh = d_in // s["head_dim"]
    gs = s["n_groups"] * s["d_state"]
    return s, d_in, nh, gs


def ssd(x, dt, A, B, C, chunk: int, dot: Dot):
    """x: (S, nh, hd); dt: (S, nh); A: (nh,); B, C: (S, g, ds).
    Returns y: (S, nh, hd) with y_t = C_t h_t."""
    S, nh, hd = x.shape
    rep = nh // B.shape[1]
    B = jnp.repeat(B, rep, axis=1)
    C = jnp.repeat(C, rep, axis=1)
    nc, L = S // chunk, chunk
    xs = x.reshape(nc, L, nh, hd)
    dts = dt.reshape(nc, L, nh)
    Bs = B.reshape(nc, L, nh, -1)
    Cs = C.reshape(nc, L, nh, -1)
    cum = jnp.cumsum(dts * A, axis=1)                  # (nc, L, nh)
    tril = jnp.tril(jnp.ones((L, L), bool))[:, :, None]

    def one_chunk(h, inp):
        xc, dtc, Bc, Cc, cc = inp
        decay = jnp.where(tril, jnp.exp(jnp.where(
            tril, cc[:, None, :] - cc[None, :, :], 0.0)), 0.0)
        cb = dot("ihs,jhs->ijh", Cc, Bc)
        y = dot("ijh,jhd->ihd", cb * decay * dtc[None], xc)
        y = y + dot("ihs,hds->ihd", Cc * jnp.exp(cc)[..., None], h)
        to_end = jnp.exp(cc[-1][None, :] - cc)        # (L, nh)
        h = h * jnp.exp(cc[-1])[:, None, None] + dot(
            "jhs,jhd->hds", Bc, xc * (dtc * to_end)[..., None])
        return h, y

    h0 = jnp.zeros((nh, hd, Bs.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(jax.checkpoint(one_chunk), h0,
                        (xs, dts, Bs, Cs, cum))
    return y.reshape(S, nh, hd)


def mamba2(p, u, cfg, dot: Dot):
    s, d_in, nh, gs = _dims(cfg)
    S = u.shape[0]
    proj = dot("sd,dn->sn", u, p["w_in"])
    z = proj[:, :d_in]
    xbc = proj[:, d_in:2 * d_in + 2 * gs]
    dt = proj[:, 2 * d_in + 2 * gs:]
    W = s["d_conv"]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    conv = sum(pad[i:i + S] * p["w_conv"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["b_conv"])
    x = xbc[:, :d_in].reshape(S, nh, s["head_dim"])
    B = xbc[:, d_in:d_in + gs].reshape(S, s["n_groups"], s["d_state"])
    C = xbc[:, d_in + gs:].reshape(S, s["n_groups"], s["d_state"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    y = ssd(x, dt, A, B, C, min(s["chunk_size"], S), dot)
    y = y + x * p["d_skip"][None, :, None]
    y = y.reshape(S, d_in) * jax.nn.silu(z)
    y = rmsnorm(y, p["norm_scale"], cfg["norm_eps"])
    return dot("sn,nd->sd", y, p["w_out"])


def sample_loss(params, tokens, cfg, dot: Dot):
    """Summed next-token loss of one sequence. tokens: (S,) int32."""
    eps = cfg["norm_eps"]
    k = cfg["shared_attn_every"]
    n_groups = cfg["n_layers"] // k
    sh = params["shared_attn"]
    h = params["tok_emb"][tokens] * math.sqrt(cfg["d_model"])
    grouped = jax.tree.map(lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                           params["layers"])

    def layer(h, p):
        return h + mamba2(p["mamba"], rmsnorm(h, p["ln1"], eps), cfg,
                          dot), None

    def shared(h):
        h = h + attention(sh["attn"], rmsnorm(h, sh["ln1"], eps), cfg, dot)
        return h + mlp(sh["mlp"], rmsnorm(h, sh["ln2"], eps), cfg["act"],
                       dot)

    def group(h, gp):
        h, _ = jax.lax.scan(jax.checkpoint(layer), h, gp)
        return jax.checkpoint(shared)(h), None

    h, _ = jax.lax.scan(group, h, grouped)
    h = rmsnorm(h, params["ln_final"], eps)
    return next_token_nll(h, output_table(params, cfg), tokens, dot)


def flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs of training on one token: forward and backward
    (3x forward). Every weight product (2 FLOPs a multiply-add), the
    convolution, the selective scan at its recurrent cost (the state
    update B x and the read-out C h: 4 d_in d_state), and attention's
    two products over the causal half of the context in each call of
    the shared block. The count is that of the architecture, not of the
    chunked algorithm that computes it; recomputation is not counted."""
    s, d_in, nh, gs = _dims(cfg)
    d, ff = cfg["d_model"], cfg["d_ff"]
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    L = cfg["n_layers"]
    calls = L // cfg["shared_attn_every"]
    mamba_mm = d * (2 * d_in + 2 * gs + nh) + d_in * d
    shared_mm = 2 * d * H * hd + 2 * d * Hkv * hd + 3 * d * ff
    mm = L * mamba_mm + calls * shared_mm + d * cfg["vocab_size"]
    conv = 2 * s["d_conv"] * (d_in + 2 * gs)
    scan = 4 * d_in * s["d_state"]
    attn = calls * 2 * seq_len * H * hd
    return 3.0 * (2 * mm + L * (conv + scan) + attn)
