"""Plain float32 reference of a dense decoder (qwen1.5): token
embedding scaled by sqrt(d_model), then per layer
h += Attn(RMSNorm(h)); h += MLP(RMSNorm(h)), a final RMSNorm and logits
from the tied embedding table. Written from the published architecture
with the departures the configuration file lists; imports nothing of
the program. Parameter names follow the program's tree, so the
reference reads the same weights the benchmark made.
"""
from __future__ import annotations

import math

import jax

from references.common import (Dot, attention, mlp, next_token_nll,
                               output_table, rmsnorm)


def sample_loss(params, tokens, cfg, dot: Dot):
    """Summed next-token loss of one sequence. tokens: (S,) int32."""
    eps = cfg["norm_eps"]
    h = params["tok_emb"][tokens] * math.sqrt(cfg["d_model"])

    def layer(h, p):
        h = h + attention(p["attn"], rmsnorm(h, p["ln1"], eps), cfg, dot)
        h = h + mlp(p["mlp"], rmsnorm(h, p["ln2"], eps), cfg["act"], dot)
        return h, None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h, params["layers"])
    h = rmsnorm(h, params["ln_final"], eps)
    return next_token_nll(h, output_table(params, cfg), tokens, dot)


def matmul_params(cfg) -> int:
    """Weights that enter a matrix product once per token."""
    d, hd, ff = cfg["d_model"], cfg["head_dim"], cfg["d_ff"]
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    layer = 2 * d * H * hd + 2 * d * Hkv * hd + 3 * d * ff
    return cfg["n_layers"] * layer + d * cfg["vocab_size"]


def flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs of training on one token: forward and backward
    (3x forward), every weight product (2 FLOPs a multiply-add) and
    attention's two products over the causal half of the context.
    Recomputation is not counted, nor norms, softmax or bias."""
    attn = cfg["n_layers"] * 2 * seq_len * cfg["n_heads"] * cfg["head_dim"]
    return 3.0 * (2 * matmul_params(cfg) + attn)
