"""Flash-attention backward, Pallas TPU, on the forward's
sequence-minor layout (kernel.py).

Two kernels over the recomputation trick (no S^2 residuals), on the
blocks and head groups of ``kernel.Plan``, scores held transposed as
in the forward:
  * dq kernel: grid (B, groups, nq, nk), kv innermost, dq accumulated
    in VMEM;
  * dkv kernel: grid (B, groups, nk, nq), q innermost, dk/dv
    accumulated in VMEM; the kv block, resident for the whole q loop,
    is transposed into VMEM once, so no product in the loop takes a
    transposed left operand; a kv head's q heads share its block
    (GQA), so their sum is taken in the kernel.

The forward (kernel.py) emits each query's logsumexp; delta =
rowsum(do * o) is a cheap elementwise pass done in jnp. The residuals
stay in the kernels' layout.
``flash_attention_train`` wires these into a jax.custom_vjp, so
``jax.grad`` through the kernel matches the reference
(tests/test_kernels_bwd.py, interpret mode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import (Plan, dot_nn, dot_nt,
                                                  dot_tn, flash_forward,
                                                  flash_forward_t, heads,
                                                  heads_t, masked, rows,
                                                  scores_t)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, plan: Plan):
    qi, ki = pl.program_id(2), pl.program_id(3)
    dk, dv = plan.dk, plan.dv

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body(mask):
        for j, kh in plan.pairs():
            k, v = rows(k_ref, kh, dk), rows(v_ref, kh, dv)
            p = jnp.exp(scores_t(plan, k, rows(q_ref, j, dk), mask)
                        - lse_ref[0, 0, j:j + 1, :])       # (bk, bq)
            dp = dot_tn(v, rows(do_ref, j, dv))
            ds = p * (dp - delta_ref[0, 0, j:j + 1, :]) * plan.scale
            dq_scr[j] += dot_nn(k, ds.astype(k.dtype))

    plan.run(qi, ki, ki, plan.kv_span(qi), body)

    @pl.when(ki == plan.nk - 1)
    def _fin():
        for j, _ in plan.pairs():
            dq_ref[0, j * dk:(j + 1) * dk, :] = dq_scr[j].astype(
                dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, kt_scr, vt_scr, dk_scr, dv_scr, *,
                plan: Plan):
    ki, qi = pl.program_id(2), pl.program_id(3)
    dk, dv = plan.dk, plan.dv

    @pl.when(qi == 0)
    def _init():
        # the kv block stays for the whole q loop: transpose it once
        for kh in range(plan.hkb):
            kt_scr[kh] = rows(k_ref, kh, dk).T
            vt_scr[kh] = rows(v_ref, kh, dv).T
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(mask):
        for j, kh in plan.pairs():
            q, do = rows(q_ref, j, dk), rows(do_ref, j, dv)
            s = masked(dot_nn(kt_scr[kh], q) * plan.scale, mask)
            p = jnp.exp(s - lse_ref[0, 0, j:j + 1, :])     # (bk, bq)
            dv_scr[kh] += dot_nt(do, p.astype(do.dtype))
            dp = dot_nn(vt_scr[kh], do)
            ds = p * (dp - delta_ref[0, 0, j:j + 1, :]) * plan.scale
            dk_scr[kh] += dot_nt(q, ds.astype(q.dtype))

    plan.run(qi, ki, qi, plan.q_span(ki), body)

    @pl.when(qi == plan.nq - 1)
    def _fin():
        for kh in range(plan.hkb):
            dk_ref[0, kh * dk:(kh + 1) * dk, :] = dk_scr[kh].astype(
                dk_ref.dtype)
            dv_ref[0, kh * dv:(kh + 1) * dv, :] = dv_scr[kh].astype(
                dv_ref.dtype)


def flash_backward_t(plan: Plan, q_t, k_t, v_t, o_t, lse, do_t, *,
                     interpret: bool):
    """dq^T, dk^T, dv^T of ``flash_forward_t``, on the kernels' layout."""
    delta = jnp.sum((do_t.astype(jnp.float32)
                     * o_t.astype(jnp.float32)).reshape(
                         plan.b, plan.groups, plan.hb, plan.dv, plan.sq),
                    axis=3)                               # as lse
    args = (q_t, k_t, v_t, do_t, lse, delta)

    def in_specs(rows_of):
        return [plan.q_spec(plan.dk, rows_of),
                plan.kv_spec(plan.dk, rows_of),
                plan.kv_spec(plan.dv, rows_of),
                plan.q_spec(plan.dv, rows_of),
                plan.stat_spec(rows_of), plan.stat_spec(rows_of)]

    dq_t = pl.pallas_call(
        functools.partial(_dq_kernel, plan=plan),
        grid=(plan.b, plan.groups, plan.nq, plan.nk),
        in_specs=in_specs(plan.q_rows),
        out_specs=plan.q_spec(plan.dk, plan.q_rows),
        out_shape=jax.ShapeDtypeStruct(q_t.shape, q_t.dtype),
        scratch_shapes=[pltpu.VMEM((plan.hb, plan.dk, plan.bq),
                                   jnp.float32)],
        compiler_params=plan.params(),
        interpret=interpret,
        name="flash_attention_dq",
    )(*args)

    dk_t, dv_t = pl.pallas_call(
        functools.partial(_dkv_kernel, plan=plan),
        grid=(plan.b, plan.groups, plan.nk, plan.nq),
        in_specs=in_specs(plan.kv_rows),
        out_specs=[plan.kv_spec(plan.dk, plan.kv_rows),
                   plan.kv_spec(plan.dv, plan.kv_rows)],
        out_shape=[jax.ShapeDtypeStruct(k_t.shape, k_t.dtype),
                   jax.ShapeDtypeStruct(v_t.shape, v_t.dtype)],
        scratch_shapes=[
            pltpu.VMEM((plan.hkb, plan.bk, plan.dk), k_t.dtype),
            pltpu.VMEM((plan.hkb, plan.bk, plan.dv), v_t.dtype),
            pltpu.VMEM((plan.hkb, plan.dk, plan.bk), jnp.float32),
            pltpu.VMEM((plan.hkb, plan.dv, plan.bk), jnp.float32),
        ],
        compiler_params=plan.params(),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*args)
    return dq_t, dk_t, dv_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_train(q, k, v, causal: bool = True,
                          window: Optional[int] = None,
                          interpret: bool = False):
    """Differentiable flash attention. q: (B, Sq, Hq, Dk); k: (B, Skv,
    Hkv, Dk); v: (B, Skv, Hkv, Dv) -> (B, Sq, Hq, Dv)."""
    return flash_forward(q, k, v, causal=causal, window=window,
                         interpret=interpret)


def _train_fwd(q, k, v, causal, window, interpret):
    plan = Plan(q.shape, k.shape, v.shape, causal=causal, window=window)
    args = (heads_t(q), heads_t(k), heads_t(v))
    o_t, lse = flash_forward_t(plan, *args, with_lse=True,
                               interpret=interpret)
    return heads(o_t, plan.hq), (*args, o_t, lse)


def _train_bwd(causal, window, interpret, res, do):
    q_t, k_t, v_t, o_t, lse = res
    b, sq, hq, dv = do.shape
    hkv, skv = v_t.shape[1] // dv, v_t.shape[2]
    plan = Plan((b, sq, hq, q_t.shape[1] // hq),
                (b, skv, hkv, k_t.shape[1] // hkv), (b, skv, hkv, dv),
                causal=causal, window=window)
    grads = flash_backward_t(plan, q_t, k_t, v_t, o_t, lse, heads_t(do),
                             interpret=interpret)
    return tuple(heads(g, n) for g, n in
                 zip(grads, (plan.hq, plan.hkv, plan.hkv)))


flash_attention_train.defvjp(_train_fwd, _train_bwd)
