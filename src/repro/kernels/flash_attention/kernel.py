"""Blocked (flash) attention forward, Pallas TPU.

Grid: (batch*q_heads, Sq/block_q, Skv/block_k); the kv dimension is the
innermost (sequential on TPU) axis, carrying the online-softmax state
(m, l, acc) in VMEM scratch. Q/K/V tiles are MXU-aligned (block sizes
multiples of 128 recommended; head_dim is the lane dim). GQA is handled
in the k/v index maps (q head h reads kv head h // group_size), so no
repeated kv materialization. Causal and sliding-window masks are fused
(positions from broadcasted iota; queries right-aligned when Sq < Skv,
which is what chunked prefill produces).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: Optional[int],
                 block_q: int, block_k: int, sq: int, skv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (bq, D)
    k = k_ref[0].astype(jnp.float32)                 # (bk, D)
    v = v_ref[0].astype(jnp.float32)                 # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # positions (right-aligned queries)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (skv - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...][:, 0]                        # (bq,)
    l_prev = l_scr[...][:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    correction = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_cur = l_prev * correction + jnp.sum(p, axis=-1)

    acc = acc_scr[...] * correction[:, None]
    acc = acc + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    m_scr[...] = m_cur[:, None]
    l_scr[...] = l_cur[:, None]
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...][:, 0]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv)
    scale = scale if scale is not None else D ** -0.5

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)

    def kv_head(bh):
        return (bh // Hq) * Hkv + (bh % Hq) // G

    grid = (B * Hq, Sq // block_q, Skv // block_k)
    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          sq=Sq, skv=Skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            # (m, l) carried as (block_q, 1) f32; acc (block_q, D) f32
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, D)
