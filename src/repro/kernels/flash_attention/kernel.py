"""Blocked (flash) attention forward, Pallas TPU, in the layout the
projections already have on the chip.

q: (B, Sq, Hq, Dk); k: (B, Skv, Hkv, Dk); v: (B, Skv, Hkv, Dv) in and
out (B, Sq, Hq, Dv), as the model holds them. On the TPU, XLA keeps
these activations sequence-minor, (B, H * D, S) in memory, and so do
the kernels: each reads ``heads_t(x)``, which is that layout as a
row-major array, and writes it back the same way, so the transposes
around the call are bitcasts and no relayout of q, k, v, o or their
gradients is made (docs/profiling.md).

Inside, sequence positions are lanes: scores are held transposed,
s^T (block_k, block_q) = k^T q, the softmax statistics are (1, block_q)
rows, and o^T (Dv, block_q) accumulates v p^T. Grid: (batch, head
group, Sq / block_q, Skv / block_k), the kv axis innermost (sequential
on TPU) with the online-softmax state in VMEM scratch. A block holds
``head_group`` kv heads and their q heads. Products take the operands'
own dtype into the MXU (bf16 in training) with f32 accumulation; the
softmax statistics are f32. Causal and sliding-window masks are fused
(queries right-aligned when Sq < Skv). A kv block that no query of the
q block may see is neither computed nor fetched: its index map re-uses
the resident block, and the mask is built only on blocks that cross
its edge.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCKS = (512, 256, 128)     # sequence blocks, largest first
HEAD_ROWS = 512              # q rows (heads x width) a block aims at
VMEM_LIMIT = 64 * 1024 * 1024


def seq_block(s: int) -> Optional[int]:
    """The largest of ``BLOCKS`` dividing ``s``, or None."""
    return next((b for b in BLOCKS if s % b == 0), None)


def head_group(hq: int, hkv: int, dk: int, dv: int) -> int:
    """Kv heads a block holds, dividing ``hkv``, whose q, k, v and o rows
    are whole 16-row tiles: the most whose q rows stay within
    ``HEAD_ROWS``, else the fewest; all of them (the whole dim) where
    no smaller group is whole tiles."""
    g = hq // hkv
    whole = [n for n in range(1, hkv + 1) if hkv % n == 0
             and all(n * w % 16 == 0 for w in (g * dk, dk, dv, g * dv))]
    small = [n for n in whole if n * g * max(dk, dv) <= HEAD_ROWS]
    return max(small) if small else min(whole, default=hkv)


def heads_t(x):
    """(B, S, H, D) -> (B, H * D, S), the kernels' layout."""
    return jnp.swapaxes(x.reshape(x.shape[0], x.shape[1], -1), 1, 2)


def heads(x_t, n_heads: int):
    """(B, H * D, S) -> (B, S, H, D)."""
    b, hd, s = x_t.shape
    return jnp.swapaxes(x_t, 1, 2).reshape(b, s, n_heads, hd // n_heads)


class Plan:
    """The static shape of one attention call: blocks, head groups,
    mask, and the span of blocks each block row or column needs."""

    def __init__(self, q_shape, k_shape, v_shape, *, causal, window):
        self.b, self.sq, self.hq, self.dk = q_shape
        _, self.skv, self.hkv, _ = k_shape
        self.dv = v_shape[-1]
        self.g = self.hq // self.hkv
        self.bq, self.bk = seq_block(self.sq), seq_block(self.skv)
        assert self.bq and self.bk, (self.sq, self.skv)
        self.nq, self.nk = self.sq // self.bq, self.skv // self.bk
        self.hkb = head_group(self.hq, self.hkv, self.dk, self.dv)
        self.hb = self.hkb * self.g              # q heads a block
        self.groups = self.hkv // self.hkb
        self.causal, self.window = causal, window
        self.off = self.skv - self.sq            # right-aligned queries
        self.scale = self.dk ** -0.5

    def kv_span(self, qi):
        """First and last kv block a q block sees."""
        first_q = qi * self.bq + self.off
        hi = self.nk - 1
        if self.causal:
            hi = jnp.minimum(hi, (first_q + self.bq - 1) // self.bk)
        lo = 0
        if self.window is not None:
            lo = jnp.maximum(first_q - self.window + 1, 0) // self.bk
        return lo, hi

    def q_span(self, ki):
        """First and last q block that sees a kv block."""
        first_k = ki * self.bk
        lo = 0
        if self.causal:
            lo = jnp.maximum(first_k - self.off, 0) // self.bq
        hi = self.nq - 1
        if self.window is not None:
            last = first_k + self.bk - 1 + self.window - 1 - self.off
            hi = jnp.minimum(hi, last // self.bq)
        return lo, hi

    def q_rows(self, qi, ki):
        """(q block, kv block) for a grid of kv innermost: a kv block
        out of the span maps to the nearest one in it."""
        lo, hi = self.kv_span(qi)
        return qi, jnp.clip(ki, lo, hi)

    def kv_rows(self, ki, qi):
        """(q block, kv block) for a grid of q innermost."""
        lo, hi = self.q_span(ki)
        return jnp.clip(qi, lo, hi), ki

    def mask_t(self, qi, ki):
        """The (block_k, block_q) mask of s^T."""
        k_pos = ki * self.bk + jax.lax.broadcasted_iota(
            jnp.int32, (self.bk, self.bq), 0)
        q_pos = qi * self.bq + self.off + jax.lax.broadcasted_iota(
            jnp.int32, (self.bk, self.bq), 1)
        m = jnp.ones((self.bk, self.bq), jnp.bool_)
        if self.causal:
            m &= q_pos >= k_pos
        if self.window is not None:
            m &= (q_pos - k_pos) < self.window
        return m

    def edge(self, qi, ki):
        """Whether the mask cuts the (qi, ki) block."""
        first_q, first_k = qi * self.bq + self.off, ki * self.bk
        cut = jnp.bool_(False)
        if self.causal:
            cut = first_k + self.bk - 1 > first_q
        if self.window is not None:
            cut = jnp.logical_or(
                cut, first_q + self.bq - 1 - first_k >= self.window)
        return cut

    def run(self, qi, ki, step, span, body):
        """Run ``body(mask)`` where the grid's innermost ``step`` lies
        in ``span``: with the mask on a block it cuts, without it on a
        whole one."""
        inside = jnp.logical_and(step >= span[0], step <= span[1])
        cut = self.edge(qi, ki)

        @pl.when(jnp.logical_and(inside, cut))
        def _edge():
            body(self.mask_t(qi, ki))

        @pl.when(jnp.logical_and(inside, jnp.logical_not(cut)))
        def _whole():
            body(None)

    # block specs over the (B, H * D, S) arrays and the (B, groups, hb,
    # S) statistics; ``rows_of`` maps the grid to the q and kv block
    def q_spec(self, width, rows_of):
        return pl.BlockSpec((1, self.hb * width, self.bq),
                            lambda b, h, i, j: (b, h, rows_of(i, j)[0]))

    def kv_spec(self, width, rows_of):
        return pl.BlockSpec((1, self.hkb * width, self.bk),
                            lambda b, h, i, j: (b, h, rows_of(i, j)[1]))

    def stat_spec(self, rows_of):
        return pl.BlockSpec((1, 1, self.hb, self.bq),
                            lambda b, h, i, j: (b, h, 0, rows_of(i, j)[0]))

    def stat_shape(self):
        return jax.ShapeDtypeStruct((self.b, self.groups, self.hb, self.sq),
                                    jnp.float32)

    def pairs(self):
        """(q head, kv head) of a block, by row offset."""
        return [(j, j // self.g) for j in range(self.hb)]

    def params(self):
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT)


def rows(ref, j, width):
    """Head ``j``'s (width, block) rows of a block."""
    return ref[0, j * width:(j + 1) * width, :]


def dot_tn(a, b):
    """a^T b in f32: (k, m), (k, n) -> (m, n)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def dot_nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def dot_nt(a, b):
    """a b^T in f32: (m, k), (n, k) -> (m, n)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def masked(s, mask):
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def scores_t(plan: Plan, k, q, mask):
    """s^T = k^T q / sqrt(Dk), masked: (block_k, block_q) f32."""
    return masked(dot_tn(k, q) * plan.scale, mask)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, plan: Plan,
                with_lse: bool):
    lse_ref = rest[0] if with_lse else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi, ki = pl.program_id(2), pl.program_id(3)
    dk, dv = plan.dk, plan.dv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(mask):
        for j, kh in plan.pairs():
            k, v = rows(k_ref, kh, dk), rows(v_ref, kh, dv)
            s = scores_t(plan, k, rows(q_ref, j, dk), mask)
            m_prev = m_scr[j:j + 1, :]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)                         # (bk, bq)
            l_scr[j:j + 1, :] = (l_scr[j:j + 1, :] * corr
                                 + jnp.sum(p, axis=0, keepdims=True))
            acc_scr[j] = acc_scr[j] * corr + dot_nn(v, p.astype(v.dtype))
            m_scr[j:j + 1, :] = m_cur

    plan.run(qi, ki, ki, plan.kv_span(qi), body)

    @pl.when(ki == plan.nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        for j, _ in plan.pairs():
            o_ref[0, j * dv:(j + 1) * dv, :] = (
                acc_scr[j] / l[j:j + 1, :]).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def flash_forward_t(plan: Plan, q_t, k_t, v_t, *, with_lse: bool,
                    interpret: bool):
    """The forward on the kernels' layout: o^T (B, Hq * Dv, Sq), and
    with ``with_lse`` the rows' logsumexp (B, groups, hb, Sq) f32."""
    rows_of = plan.q_rows
    out_specs = [plan.q_spec(plan.dv, rows_of)]
    out_shape = [jax.ShapeDtypeStruct(
        (plan.b, plan.hq * plan.dv, plan.sq), q_t.dtype)]
    if with_lse:
        out_specs.append(plan.stat_spec(rows_of))
        out_shape.append(plan.stat_shape())
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, with_lse=with_lse),
        grid=(plan.b, plan.groups, plan.nq, plan.nk),
        in_specs=[plan.q_spec(plan.dk, rows_of),
                  plan.kv_spec(plan.dk, rows_of),
                  plan.kv_spec(plan.dv, rows_of)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((plan.hb, plan.bq), jnp.float32),     # row max
            pltpu.VMEM((plan.hb, plan.bq), jnp.float32),     # row sum
            pltpu.VMEM((plan.hb, plan.dv, plan.bq), jnp.float32),
        ],
        compiler_params=plan.params(),
        interpret=interpret,
        name="flash_attention_fwd" if with_lse else "flash_attention",
    )(q_t, k_t, v_t)
    return tuple(res) if with_lse else res[0]


def flash_forward(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None, interpret: bool = False):
    """q: (B, Sq, Hq, Dk); k: (B, Skv, Hkv, Dk); v: (B, Skv, Hkv, Dv)
    -> (B, Sq, Hq, Dv)."""
    plan = Plan(q.shape, k.shape, v.shape, causal=causal, window=window)
    o_t = flash_forward_t(plan, heads_t(q), heads_t(k), heads_t(v),
                          with_lse=False, interpret=interpret)
    return heads(o_t, plan.hq)
