"""Pieces the plain references share: matrix products at a stated
precision, RMSNorm, rotary embedding, causal attention, the gated MLP
and the next-token loss. Plain ``jax.numpy`` in float32; nothing here
imports the program.

``Dot`` is where precision enters. ``Dot("float32")`` multiplies at
``Precision.HIGHEST`` (a float32 product on the TPU). ``Dot("fp8")`` is
the control, the precision below the configuration's bfloat16, as fp8
training computes: every operand of every product, forward and
backward, is scaled per tensor so that its largest entry sits at the
format's largest, rounded there and scaled back before a float32
product; the forward operands in float8_e4m3fn, the gradient flowing
back in float8_e5m2.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    """x rounded to ``dtype`` under one per-tensor scale."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / float(
        jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return _einsum(spec, _round(a, jnp.float8_e4m3fn),
                   _round(b, jnp.float8_e4m3fn))


def _fp8_fwd(spec, a, b):
    qa, qb = _round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn)
    return _einsum(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, saved, ct):
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *saved)
    return vjp(_round(ct, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


class Dot:
    def __init__(self, precision: str = "float32"):
        if precision not in ("float32", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision

    def __call__(self, spec: str, a, b):
        if self.precision == "fp8":
            return _fp8_einsum(spec, a, b)
        return _einsum(spec, a, b)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """Rotate-half rotary embedding over the whole head. x: (S, H, D)."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, cfg, dot: Dot, q_block: int = 512):
    """Causal multi-head attention with optional q/k/v bias and grouped
    keys. x: (S, d). Scores are formed one block of queries at a time
    (exact softmax over each whole row) so that long sequences fit."""
    S = x.shape[0]
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    D = cfg["head_dim"]
    q = dot("sd,dn->sn", x, p["wq"])
    k = dot("sd,dn->sn", x, p["wk"])
    v = dot("sd,dn->sn", x, p["wv"])
    if cfg.get("qkv_bias"):
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(S, H, D), cfg["rope_theta"])
    k = rope(k.reshape(S, Hkv, D), cfg["rope_theta"])
    v = v.reshape(S, Hkv, D)
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    qb = min(q_block, S)
    nb = S // qb
    kpos = jnp.arange(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        s = dot("qhd,khd->hqk", qi, k) / math.sqrt(D)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return dot("hqk,khd->qhd", w, v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))
    out = out.reshape(S, H * D)
    return dot("sn,nd->sd", out, p["wo"])


def mlp(p, x, act: str, dot: Dot):
    f = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    h = f(dot("sd,df->sf", x, p["w_gate"])) * dot("sd,df->sf", x,
                                                   p["w_up"])
    return dot("sf,fd->sd", h, p["w_down"])


def output_table(params, cfg):
    """(vocab, d) rows the logits are read from: the embedding table
    when tied, else the output head."""
    if cfg.get("tie_embeddings"):
        return params["tok_emb"]
    return params["out_head"].T


def next_token_nll(h, table, tokens, dot: Dot):
    """Sum over positions of -log p(token[t+1] | ..t), logits over the
    whole (padded) embedding table. h: (S, d) after the final norm."""
    logits = dot("sd,vd->sv", h[:-1], table)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
