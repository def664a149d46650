"""Flash-attention backward, Pallas TPU.

Two kernels over the recomputation trick (no S^2 residuals):
  * forward (kernel.py) extended to emit the row logsumexp (lse);
  * dq kernel: grid (B*Hq, nq, nk), kv innermost, dq accumulator in VMEM;
  * dkv kernel: grid (B*Hq, nk, nq), q innermost, dk/dv accumulators in
    VMEM — computed per q-head and group-summed outside (GQA).

delta = rowsum(do * o) is a cheap elementwise pass done in jnp.
``flash_attention_train`` wires these into a jax.custom_vjp so
``jax.grad`` through the kernel matches the reference exactly
(tests/test_kernels_bwd.py, interpret mode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import flash_attention_fwd

NEG_INF = -1e30


def _mask(block_q, block_k, qi, ki, *, causal, window, sq, skv):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (skv - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        m &= q_pos >= k_pos
    if window is not None:
        m &= (q_pos - k_pos) < window
    return m


# ---------------------------------------------------------------------------
# Forward with lse output (for the backward recomputation)
# ---------------------------------------------------------------------------
def _fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                    acc_scr, *, scale, causal, window, block_q, block_k,
                    sq, skv):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(block_q, block_k, qi, ki, causal=causal,
                        window=window, sq=sq, skv=skv), s, NEG_INF)
    m_prev, l_prev = m_scr[...][:, 0], l_scr[...][:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_cur = l_prev * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur[:, None]
    l_scr[...] = l_cur[:, None]

    @pl.when(ki == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...][:, 0] + jnp.log(l))[:, None].astype(
            lse_ref.dtype)


def flash_fwd_lse(q, k, v, *, causal, window, scale, block_q, block_k,
                  interpret):
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)

    def kv_head(bh):
        return (bh // Hq) * Hkv + (bh % Hq) // G

    out, lse = pl.pallas_call(
        functools.partial(_fwd_lse_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          sq=Sq, skv=Skv),
        grid=(B * Hq, Sq // block_q, Skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, D), lse.reshape(B, Hq, Sq)


# ---------------------------------------------------------------------------
# dq kernel
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, window, block_q, block_k, sq, skv):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0]
    delta = delta_ref[0][:, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(block_q, block_k, qi, ki, causal=causal,
                        window=window, sq=sq, skv=skv), s, NEG_INF)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# dk/dv kernel (per q-head; group-summed outside for GQA)
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                block_q, block_k, sq, skv):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0]
    delta = delta_ref[0][:, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(block_q, block_k, qi, ki, causal=causal,
                        window=window, sq=sq, skv=skv), s, NEG_INF)
    p = jnp.exp(s - lse[:, None])                        # (bq, bk)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale               # (bq, bk)
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal, window, scale,
                        block_q, block_k, interpret):
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                              # (B,Hq,Sq)

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)
    dof = do.reshape(B * Hq, Sq, D)
    lsef = lse.reshape(B * Hq, Sq, 1)
    deltaf = delta.reshape(B * Hq, Sq, 1)

    def kv_head(bh):
        return (bh // Hq) * Hkv + (bh % Hq) // G

    kw = dict(scale=scale, causal=causal, window=window,
              block_q=block_q, block_k=block_k, sq=Sq, skv=Skv)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B * Hq, Sq // block_q, Skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    # dk/dv per q-head, group-summed after (GQA)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B * Hq, Skv // block_k, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, ki, qi: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, ki, qi: (kv_head(bh), ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Skv, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Skv, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(qf, kf, vf, dof, lsef, deltaf)

    dk = dk_h.reshape(B, Hkv, G, Skv, D).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, G, Skv, D).sum(axis=2).astype(v.dtype)
    return dq.reshape(B, Hq, Sq, D), dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_train(q, k, v, causal=True, window=None,
                          block_q=128, block_k=128, interpret=False):
    D = q.shape[-1]
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def _train_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    D = q.shape[-1]
    o, lse = flash_fwd_lse(q, k, v, causal=causal, window=window,
                           scale=D ** -0.5, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _train_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    D = q.shape[-1]
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window,
        scale=D ** -0.5, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return dq, dk, dv


flash_attention_train.defvjp(_train_fwd, _train_bwd)
