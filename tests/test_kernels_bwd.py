"""Flash-attention backward kernel vs jax.grad of the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.bwd import flash_attention_train
from repro.kernels.flash_attention.kernel import (Plan, flash_forward_t,
                                                  heads, heads_t)
from repro.kernels.flash_attention.ref import attention_ref


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window", [
    (1, 2, 2, 128, 128, 64, True, None),
    (1, 4, 2, 128, 128, 64, True, None),       # GQA group-sum in dkv
    (1, 2, 1, 128, 256, 64, True, None),       # MQA, right-aligned q
    (1, 2, 2, 128, 128, 64, True, 64),         # sliding window
    (1, 2, 2, 128, 128, 64, False, None),      # bidirectional
    # (q/k width, v width, operand dtype): 512-blocks with kv blocks
    # above the diagonal skipped, in f32 and in bf16; latent
    # attention's 192/128; zamba2's 80; blocks skipped below a window
    (1, 2, 2, 1024, 1024, (64, 64, "float32"), True, None),
    (1, 2, 2, 1024, 1024, (64, 64, "bfloat16"), True, None),
    (1, 2, 2, 1024, 1024, (192, 128, "float32"), True, None),
    (1, 4, 4, 1024, 1024, (80, 80, "float32"), True, None),
    (1, 1, 1, 2048, 2048, (64, 64, "float32"), True, 300),
])
def test_bwd_matches_reference(B, Hq, Hkv, Sq, Skv, D, causal, window):
    dk, dv, dtype = D if isinstance(D, tuple) else (D, D, "float32")
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, Sq, Hq, dk), dtype)
    k = jax.random.normal(keys[1], (B, Skv, Hkv, dk), dtype)
    v = jax.random.normal(keys[2], (B, Skv, Hkv, dv), dtype)

    def loss_kernel(q, k, v):
        o = flash_attention_train(q, k, v, causal, window, True)
        o = o.astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))          # nontrivial cotangent

    def loss_ref(q, k, v):
        o = attention_ref(q, k, v, causal=causal, window=window)
        o = o.astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    # the reference in f32 from the same operands; bf16 products in the
    # kernel then differ by bf16 rounding, relative to each gradient
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(*f32)
    for a, b, name in zip(gk, gr, ("dq", "dk", "dv")):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3,
                                       err_msg=name)
        else:
            assert np.max(np.abs(a - b)) <= 2e-2 * np.max(np.abs(b)), name


def test_fwd_lse_matches_plain_fwd():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 128, 2, 64))
    k = jax.random.normal(keys[1], (1, 128, 2, 64))
    v = jax.random.normal(keys[2], (1, 128, 2, 64))
    plan = Plan(q.shape, k.shape, v.shape, causal=True, window=None)
    o_t, lse = flash_forward_t(plan, heads_t(q), heads_t(k), heads_t(v),
                               with_lse=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(heads(o_t, 2)), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    # lse sanity: softmax weights recomputed from lse sum to 1; lse is
    # (B, head groups, heads a group, Sq)
    lse = lse.reshape(1, 2, 128)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
    mask = np.tril(np.ones((128, 128), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    p = jnp.exp(s - lse[..., None])
    np.testing.assert_allclose(np.asarray(p.sum(-1)),
                               np.ones((1, 2, 128)), atol=1e-4, rtol=1e-4)
