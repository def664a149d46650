"""Pallas kernel allclose tests vs pure-jnp oracles (interpret=True on
CPU), with shape/dtype sweeps per the deliverable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.delay_ring.ops import ring_push_pop, ring_push_pop_ref
from repro.kernels.dual_update.ops import dual_update, dual_update_arena
from repro.kernels.dual_update.ref import dual_update_fused_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.linear_scan.ops import linear_scan, ssd_mamba2
from repro.kernels.linear_scan.ref import linear_scan_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal,window",
    [
        (2, 4, 2, 256, 256, 64, True, None),
        (1, 8, 8, 128, 128, 128, True, None),
        (2, 4, 1, 256, 512, 64, True, None),      # MQA, right-aligned q
        (1, 4, 2, 256, 256, 64, True, 128),       # sliding window
        (1, 2, 2, 128, 256, 64, False, None),     # bidirectional
        # a (q/k, v) width pair: 512-blocks, kv blocks above the
        # diagonal skipped; latent attention's 192/128; zamba2's 80
        (1, 2, 2, 1024, 1024, (64, 64), True, None),
        (1, 2, 2, 1024, 1024, (192, 128), True, None),
        (1, 4, 4, 1024, 1024, (80, 80), True, None),
        (1, 1, 1, 2048, 2048, (64, 64), True, 300),  # blocks skipped below
    ])
def test_flash_attention(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype):
    dk, dv = D if isinstance(D, tuple) else (D, D)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, Sq, Hq, dk), dtype)
    k = jax.random.normal(keys[1], (B, Skv, Hkv, dk), dtype)
    v = jax.random.normal(keys[2], (B, Skv, Hkv, dv), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("BH,BHG,S,ds,hd,chunk", [
    (4, 4, 256, 32, 64, 128),
    (6, 2, 256, 16, 32, 64),     # grouped B/C (GQA-style broadcast)
    (2, 2, 512, 64, 64, 128),
])
def test_linear_scan(BH, BHG, S, ds, hd, chunk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    g = (-jnp.abs(jax.random.normal(keys[0], (BH, S))) * 0.1).astype(
        jnp.float32)
    q = jax.random.normal(keys[1], (BHG, S, ds), dtype)
    k = (jax.random.normal(keys[2], (BHG, S, ds), dtype) * 0.1).astype(dtype)
    v = jax.random.normal(keys[3], (BH, S, hd), dtype)
    out = linear_scan(g, q, k, v, chunk=chunk, interpret=True)
    ref = linear_scan_ref(g, q, k, v)
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-6
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))) / scale
    assert err < tol


def test_ssd_mamba2_matches_model_path():
    """Kernel == the model's XLA ssd_chunked (the integration contract)."""
    from repro.models.ssm import ssd_chunked
    Bt, S, nh, hd, g, ds = 2, 256, 4, 32, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (Bt, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bt, S, nh)))
    A = -jnp.exp(jax.random.normal(keys[2], (nh,)))
    B = jax.random.normal(keys[3], (Bt, S, g, ds)) * 0.2
    Cm = jax.random.normal(keys[4], (Bt, S, g, ds)) * 0.2
    y_kernel = ssd_mamba2(x, dt, A, B, Cm, chunk=64, interpret=True)
    y_xla, _ = ssd_chunked(x, dt, A, B, Cm, 64)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_xla),
                               atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("shapes", [
    [(7,)], [(128,)], [(10, 100), (77,), (3, 5, 7)],
])
def test_dual_update(shapes):
    rng = np.random.default_rng(0)
    z = {f"p{i}": jnp.asarray(rng.standard_normal(s), jnp.float32)
         for i, s in enumerate(shapes)}
    g = {f"p{i}": jnp.asarray(rng.standard_normal(s), jnp.float32)
         for i, s in enumerate(shapes)}
    alpha = 0.37
    z_ref = jax.tree.map(lambda a, b: a + b, z, g)
    w_ref = jax.tree.map(lambda a: -alpha * a, z_ref)
    z2, w2 = dual_update(jax.tree.map(jnp.copy, z), g, alpha,
                         interpret=True)
    for kk in z:
        np.testing.assert_allclose(np.asarray(z2[kk]), np.asarray(z_ref[kk]),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(w2[kk]), np.asarray(w_ref[kk]),
                                   rtol=1e-6)


@pytest.mark.parametrize("head", [0, 1, 2])
@pytest.mark.parametrize("tau,n_pods,rows", [(3, 2, 256), (1, 1, 512)])
def test_delay_ring_kernel_f32(tau, n_pods, rows, head):
    """Pallas slot rotation == jnp oracle, untouched slots retained
    (the aliasing passthrough contract)."""
    if head >= tau:
        pytest.skip("head out of range")
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    ring = jax.random.normal(keys[0], (tau, n_pods, rows, 128), jnp.float32)
    g = jax.random.normal(keys[1], (n_pods, rows, 128), jnp.float32)
    h = jnp.int32(head)
    popped, ring_new, _, _ = ring_push_pop(ring, g, h, impl="pallas",
                                           interpret=True)
    popped_r, ring_r, _, _ = ring_push_pop_ref(ring, g, h)
    np.testing.assert_array_equal(np.asarray(popped), np.asarray(popped_r))
    np.testing.assert_array_equal(np.asarray(ring_new), np.asarray(ring_r))


@pytest.mark.parametrize("head", [0, 2])
def test_delay_ring_kernel_int8(head):
    tau, n_pods, rows = 3, 2, 256
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    ring = jax.random.randint(keys[0], (tau, n_pods, rows, 128), -127, 128,
                              jnp.int8)
    scales = jax.random.uniform(keys[1], (tau, n_pods, rows)) + 0.01
    # the int8 contract takes the already error-fed gradient
    fed = (jax.random.normal(keys[3], (n_pods, rows, 128), jnp.float32)
           + 0.1 * jax.random.normal(keys[2], (n_pods, rows, 128)))
    scale_new = jax.random.uniform(keys[4], (n_pods, rows)) + 0.01
    h = jnp.int32(head)
    outs = ring_push_pop(ring, fed, h, scales=scales,
                         scale_new=scale_new, impl="pallas", interpret=True)
    refs = ring_push_pop_ref(ring, fed, h, scales=scales,
                             scale_new=scale_new)
    # popped payload, int8 ring and scales must be identical
    for o, r in zip(outs[:3], refs[:3]):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
    # residual: fed - q*s may fuse into an FMA in one lowering and not
    # the other -> 1-ULP differences are allowed
    # (atol ~ 1 ULP of fed, not of the tiny residual remainder)
    np.testing.assert_allclose(np.asarray(outs[3]), np.asarray(refs[3]),
                               rtol=1e-6, atol=2.5e-7)


def test_dual_update_arena_fused():
    """Fused count-normalizing kernel == oracle, incl. count=0 guard."""
    rows = 512
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    z = jax.random.normal(keys[0], (rows, 128), jnp.float32)
    g = jax.random.normal(keys[1], (rows, 128), jnp.float32)
    for count in (7.0, 0.0):
        z_k, w_k = dual_update_arena(z, g, jnp.float32(count),
                                     jnp.float32(0.37),
                                     impl="pallas", interpret=True)
        z_r, w_r = dual_update_fused_ref(
            z, g, jnp.maximum(jnp.float32(count), 1e-12), jnp.float32(0.37))
        np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r),
                                   rtol=1e-6)
        assert bool(jnp.all(jnp.isfinite(w_k)))


def test_mlstm_chunked_matches_recurrence():
    """Chunk-parallel mLSTM == naive stabilized recurrence."""
    from repro.models.xlstm import mlstm_sequence
    B, S, nh, hd = 2, 64, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(keys[0], (B, S, nh, hd))
    k = jax.random.normal(keys[1], (B, S, nh, hd)) * 0.3
    v = jax.random.normal(keys[2], (B, S, nh, hd))
    logf = jax.nn.log_sigmoid(jax.random.normal(keys[3], (B, S, nh)) + 2)
    logi = jax.random.normal(keys[4], (B, S, nh)) * 0.5

    y_chunk = mlstm_sequence(q, k, v, logf, logi, chunk=16)

    # naive recurrence
    C = np.zeros((B, nh, hd, hd)); n = np.zeros((B, nh, hd))
    m = np.full((B, nh), -1e30)
    ys = np.zeros((B, S, nh, hd))
    qn, kn, vn = map(np.asarray, (q, k, v))
    lf, li = np.asarray(logf), np.asarray(logi)
    for t in range(S):
        m_new = np.maximum(lf[:, t] + m, li[:, t])
        fw = np.exp(lf[:, t] + m - m_new)
        iw = np.exp(li[:, t] - m_new)
        C = C * fw[..., None, None] + np.einsum(
            "bhd,bhe,bh->bhde", kn[:, t], vn[:, t], iw)
        n = n * fw[..., None] + kn[:, t] * iw[..., None]
        m = m_new
        qs = qn[:, t] / np.sqrt(hd)
        num = np.einsum("bhd,bhde->bhe", qs, C)
        den = np.maximum(np.abs(np.einsum("bhd,bhd->bh", qs, n)),
                         np.exp(-m))
        ys[:, t] = num / den[..., None]
    np.testing.assert_allclose(np.asarray(y_chunk), ys, atol=2e-4,
                               rtol=1e-3)


def test_resolve_impl_never_hides_the_device(monkeypatch):
    """On the TPU a multi-pod profile resolves to the shard_map'd
    kernels, which need an ambient mesh: without one, a caller that has
    the wrapper gets an error, not the XLA reference path."""
    import repro.kernels as K
    from repro.configs.base import MeshConfig
    from repro.dist.context import sharding_profile
    from repro.launch.mesh import make_mesh

    assert K.resolve_impl("auto", pod_shard_map=True) == "ref"   # CPU
    monkeypatch.setattr(K, "on_tpu", lambda: True)
    assert K.resolve_impl("auto", pod_shard_map=True) == "pallas"
    with sharding_profile(MeshConfig(n_pods=2, data=1, model=1)):
        assert K.resolve_impl("auto") == "ref"      # no wrapper
        with pytest.raises(ValueError, match="ambient mesh"):
            K.resolve_impl("auto", pod_shard_map=True)
        with jax.set_mesh(make_mesh((1,), ("pod",))):
            assert (K.resolve_impl("auto", pod_shard_map=True)
                    == "pallas_sharded")
    assert not K.resolve_interpret(None)
    assert K.resolve_interpret(True)
