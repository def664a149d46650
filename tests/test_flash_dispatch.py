"""Which attention core a full-sequence call takes: the Pallas flash
kernel only on the TPU, for the causal mask, with no softcap and no
prefix-LM, from ``layers._FLASH_THRESHOLD`` tokens up; and the two
cores agree on a qwen1.5-0.5b layer (kernel in interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
import repro.kernels
from repro.configs.base import MeshConfig
from repro.dist.context import sharding_profile
from repro.models import build_model
from repro.models import layers as L

QWEN = dataclasses.replace(C.get_config("qwen1.5-0.5b"), n_layers=1,
                           vocab_size=256)


@pytest.fixture
def tpu(monkeypatch):
    """Trace as for the TPU (the kernels still run interpreted only
    where a test asks for it)."""
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)


@pytest.mark.parametrize("seq_len,flash", [(512, False), (1024, True),
                                           (2048, True), (4096, True),
                                           (8192, True)])
def test_takes_flash_by_length_on_the_tpu(tpu, seq_len, flash):
    assert L.takes_flash(QWEN, seq_len, True) is flash


@pytest.mark.parametrize("why", ["cpu", "prefix_lm", "softcap",
                                 "not causal", "no block divides",
                                 "multi-device mesh"])
def test_takes_flash_refuses(monkeypatch, why):
    cfg, seq_len, causal, mesh = QWEN, 2048, True, None
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: why != "cpu")
    if why == "prefix_lm":
        # the caller's flag, as transformer.forward sets it
        cfg = dataclasses.replace(cfg, prefix_lm=True)
        causal = not cfg.prefix_lm
    elif why == "softcap":
        cfg = dataclasses.replace(cfg, logit_softcap=30.0)
    elif why == "not causal":
        causal = False
    elif why == "no block divides":
        seq_len = 2000
    elif why == "multi-device mesh":
        mesh = MeshConfig(n_pods=1, data=4, model=1)
    with sharding_profile(mesh):
        assert not L.takes_flash(cfg, seq_len, causal)


def test_takes_flash_on_a_one_device_mesh(tpu):
    with sharding_profile(MeshConfig(n_pods=1, data=1, model=1)):
        assert L.takes_flash(QWEN, 2048, True)


def _traced(fn, *args) -> str:
    return str(jax.make_jaxpr(fn)(*args))


def _params(model):
    return jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))


@pytest.mark.parametrize("seq_len,flash", [(512, False), (1024, True)])
def test_decoder_traces_the_kernel_by_length(tpu, seq_len, flash):
    """On the TPU, qwen's decoder at 512 tokens traces no pallas_call:
    its program is the jnp one."""
    model = build_model(QWEN)
    text = _traced(model.loss, _params(model),
                   model.input_specs(1, seq_len))
    assert ("pallas_call" in text) is flash


@pytest.mark.parametrize("arch", ["seamless-m4t-large-v2", "paligemma-3b"])
def test_encdec_and_prefix_lm_keep_jnp_on_the_tpu(tpu, arch):
    cfg = C.get_smoke_config(arch)
    model = build_model(cfg)
    text = _traced(model.loss, _params(model), model.input_specs(1, 1024))
    assert "pallas_call" not in text


def test_qwen_layer_same_through_both_cores(monkeypatch):
    """qwen1.5-0.5b's attention at 2,048 tokens (16 heads of 64, qkv
    bias, bf16 products): output and gradients through the flash kernel
    (interpret mode) match the jnp core within the kernels' bf16
    tolerance."""
    model = build_model(QWEN)
    params = model.init(jax.random.PRNGKey(0))[0]
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2048, QWEN.d_model),
                          jnp.bfloat16)
    pos = jnp.arange(2048)[None]
    mask = L.causal_mask(2048, 2048)
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)

    def loss(p, x):
        out = L.attention_apply(p, QWEN, x, pos, mask, causal=True)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    def run():
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(p, x)

    (_, ref), ref_g = run()
    # the predicate as on the TPU, the kernel interpreted on this host
    monkeypatch.setattr(L, "takes_flash", lambda cfg, seq_len, causal:
                        causal and seq_len >= L._FLASH_THRESHOLD)
    assert "pallas_call" in _traced(loss, p, x)
    (_, out), g = run()
    pairs = [(out, ref)] + list(zip(jax.tree.leaves(g),
                                    jax.tree.leaves(ref_g)))
    assert len(pairs) == 1 + len(p) + 1
    for a, b in pairs:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= 2e-2 * np.max(np.abs(b))
