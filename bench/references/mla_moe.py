"""Plain float32 reference of a DeepSeek-V3 block decoder
(Moonlight-16B-A3B) as one chip's expert share runs it: token embedding
scaled by sqrt(d_model); ``first_k_dense`` layers
h += MLA(RMSNorm(h)); h += MLP(RMSNorm(h)); then MoE layers
h += MLA(RMSNorm(h)); h += MoE(RMSNorm(h)); a final RMSNorm and logits
from the output head. Written from the published equations and imports
nothing of the program. Parameter names follow the program's tree.

MLA (DeepSeek-V2 section 2.1, no q-LoRA): q = W_q h split into q_nope
and q_pe; [c_kv | k_pe] = W_kv_a h; [k_nope | v] = W_kv_b RMSNorm(c_kv)
per head; RoPE on q_pe and on the one k_pe all heads share; scores
(q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope), causal; W_o over the
value heads.

MoE (DeepSeek-V3 section 2.1.2): s = sigmoid(h W_g) over all routed
experts; the top k of s + b (the correction bias steers the choice
only); weights the chosen s over their sum times the routed scale. The
chip holds experts first_expert .. first_expert + n_experts - 1 and adds
only their part, each computed here densely over every token with its
weight (0 where the token did not choose it); the shared experts are
one gated MLP every token passes through.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from references.common import Dot, mlp, output_table, rmsnorm, rope


def mla(p, x, cfg, dot: Dot, q_block: int = 256):
    """x: (S, d) -> (S, d), the attention's queries in blocks so that
    long sequences fit."""
    S = x.shape[0]
    H, nope = cfg["n_heads"], cfg["qk_nope_head_dim"]
    rd, r, vd = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    q = dot("sd,dn->sn", x, p["wq"]).reshape(S, H, nope + rd)
    kv_a = dot("sd,dn->sn", x, p["w_kv_a"])
    c_kv = rmsnorm(kv_a[:, :r], p["kv_latent"]["norm_scale"], cfg["norm_eps"])
    kv = dot("sr,rn->sn", c_kv, p["w_kv_b"]).reshape(S, H, nope + vd)
    q_nope = q[..., :nope]
    q_pe = rope(q[..., nope:], cfg["rope_theta"])
    k_pe = rope(kv_a[:, None, r:], cfg["rope_theta"])[:, 0]      # (S, rd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    qb = min(q_block, S)
    kpos = jnp.arange(S)

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb, axis=0)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * qb, qb, axis=0)
        s = (dot("qhd,khd->hqk", qn, k_nope)
             + dot("qhd,kd->hqk", qp, k_pe)) / math.sqrt(nope + rd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s,
                      -jnp.inf)
        return dot("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(S // qb))
    return dot("sn,nd->sd", out.reshape(S, H * vd), p["wo"])


def held_weights(p, x, cfg, dot: Dot):
    """(S, n_experts): each held expert's weight for each token, 0
    where the token did not choose it."""
    m = cfg["moe"]
    s = jax.nn.sigmoid(dot("sd,de->se", x, p["w_router"]))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s + p["b_correction"]),
                           m["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * m["routed_scale"]
    held = m["first_expert"] + jnp.arange(m["n_experts"])
    return jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)


def moe(p, x, cfg, dot: Dot):
    act = cfg["act"]
    gate = held_weights(p, x, cfg, dot)
    experts = {k: p[k] for k in ("w_gate", "w_up", "w_down")}

    def one(y, inp):
        g, pe = inp
        return y + g[:, None] * mlp(pe, x, act, dot), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                        (gate.T, experts))
    return y + mlp(p["shared"], x, act, dot)


def nll_in_blocks(h, table, tokens, dot: Dot, block: int = 512):
    """Sum over positions of -log p(token[t+1] | ..t), logits over the
    whole table, formed ``block`` positions at a time (and formed again
    in the backward) so that 8k positions of a 20k vocabulary fit.
    h: (S, d) after the final norm."""
    S = h.shape[0]
    nb = -(-S // block)
    pad = nb * block - S
    hp = jnp.pad(h, ((0, pad), (0, 0)))
    target = jnp.pad(tokens[1:], (0, pad + 1))
    has_target = jnp.arange(nb * block) < S - 1

    def one(i):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * block, slice_size=block)
        logp = jax.nn.log_softmax(dot("sd,vd->sv", sl(hp), table), axis=-1)
        ll = jnp.take_along_axis(logp, sl(target)[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(sl(has_target), ll, 0.0))

    return jnp.sum(jax.lax.map(jax.checkpoint(one), jnp.arange(nb)))


def sample_loss(params, tokens, cfg, dot: Dot):
    """Summed next-token loss of one sequence. tokens: (S,) int32."""
    eps = cfg["norm_eps"]
    h = params["tok_emb"][tokens] * math.sqrt(cfg["d_model"])

    def dense_layer(h, p):
        h = h + mla(p["attn"], rmsnorm(h, p["ln1"], eps), cfg, dot)
        h = h + mlp(p["mlp"], rmsnorm(h, p["ln2"], eps), cfg["act"], dot)
        return h, None

    def moe_layer(h, p):
        h = h + mla(p["attn"], rmsnorm(h, p["ln1"], eps), cfg, dot)
        h = h + moe(p["moe"], rmsnorm(h, p["ln2"], eps), cfg, dot)
        return h, None

    h, _ = jax.lax.scan(jax.checkpoint(dense_layer), h,
                        params["dense_layers"])
    h, _ = jax.lax.scan(jax.checkpoint(moe_layer), h, params["layers"])
    h = rmsnorm(h, params["ln_final"], eps)
    return nll_in_blocks(h, output_table(params, cfg), tokens, dot)


def matmul_params(cfg) -> float:
    """Weights that enter a matrix product once per token, the routed
    experts at their balanced share on this chip: top_k x n_experts /
    n_routed of an expert a token."""
    d, H, m = cfg["d_model"], cfg["n_heads"], cfg["moe"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    attn = (d * H * (nope + rd) + d * (r + rd) + r * H * (nope + vd)
            + H * vd * d)
    expert = 3 * d * m["d_expert"]
    routed = m["top_k"] * m["n_experts"] / m["n_routed"] * expert
    moe_layer = attn + d * m["n_routed"] + routed + m["n_shared"] * expert
    dense_layer = attn + 3 * d * cfg["d_ff"]
    k = cfg["first_k_dense"]
    return (k * dense_layer + (cfg["n_layers"] - k) * moe_layer
            + d * cfg["vocab_size"])


def flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs of training on one token: forward and backward (3x
    forward), every weight product (2 FLOPs a multiply-add) and MLA's
    two attention products over the causal half of the context
    (S x H x (qk width + v width) a layer). Recomputation is not
    counted, nor norms, softmax, routing's sort and gather."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = cfg["n_layers"] * seq_len * cfg["n_heads"] * (
        qk + cfg["v_head_dim"])
    return 3.0 * (2 * matmul_params(cfg) + attn)
