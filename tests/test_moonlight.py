"""Moonlight-16B-A3B (DeepSeek-V3 block) at its smoke size on the CPU:
the program against the benchmark's plain reference
(``bench/references/mla_moe.py``), the expert shares adding up to the
uncut layer, the dropless layer keeping every token, and the
benchmark's configuration file building the program's config."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.configs.base import MoEConfig
from repro.models import build_model
from repro.models.common import ParamFactory
from repro.models.layers import gated_mlp
from repro.models.moe import moe_apply_dropless, moe_init

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import drive, weights  # noqa: E402
from references import mla_moe  # noqa: E402
from references.common import Dot  # noqa: E402

SMOKE = C.get_smoke_config("moonlight-16b-a3b")
F32 = dataclasses.replace(SMOKE, compute_dtype="float32")


def _cfg_dict(cfg):
    return dataclasses.asdict(cfg)


def _leaf_gaps(got, want):
    return jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)
                           / jnp.maximum(jnp.linalg.norm(b), 1e-30)),
        got, want)


def test_program_matches_reference():
    """Loss and every leaf's gradient of two sequences, float32, from
    the benchmark's seeded weights."""
    model = build_model(F32)
    struct = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    params = weights.Generator(struct)(2147483687)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0,
                                F32.vocab_size)
    batch = {"tokens": tokens, "weights": jnp.ones(2)}
    cfg = _cfg_dict(F32)
    lp, gp = jax.value_and_grad(lambda p: model.loss(p, batch)[0])(params)
    lr, gr = jax.value_and_grad(lambda p: sum(
        mla_moe.sample_loss(p, t, cfg, Dot()) for t in tokens))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    gaps = _leaf_gaps(gp, gr)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    # the correction bias steers the choice only: no gradient reaches it
    assert not np.any(np.asarray(gp["layers"]["moe"]["b_correction"]))


def _moe_params(cfg, key):
    f = ParamFactory(key)
    moe_init(f, cfg)
    p = f.params["moe"]
    p["b_correction"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1), p["b_correction"].shape)
    return p


def _share(cfg, p, first, n):
    """The layer as the chip holding experts first .. first + n - 1."""
    c = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=n, first_expert=first))
    held = {k: p[k][first:first + n] for k in ("w_gate", "w_up", "w_down")}
    return c, dict(p, **held)


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares of 4 experts, with the shared
    experts counted once, give the layer that holds all 32."""
    m = F32.moe
    full = dataclasses.replace(F32, moe=dataclasses.replace(
        m, n_experts=m.n_routed))
    p = _moe_params(full, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, F32.d_model))
    whole, _ = moe_apply_dropless(p, full, x)
    shared = gated_mlp(p["shared"], full, x.reshape(-1, F32.d_model))
    routed = 0.0
    for first in range(0, m.n_routed, 4):
        cfg, held = _share(full, p, first, 4)
        routed = routed + moe_apply_dropless(held, cfg, x)[0]
    routed = routed.reshape(-1, F32.d_model) - 8 * shared
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(whole.reshape(-1, F32.d_model)),
                               rtol=1e-5, atol=1e-5)
    # and the uncut layer is the reference's layer holding every expert
    ref = mla_moe.moe(p, x.reshape(-1, F32.d_model),
                      _cfg_dict(full), Dot())
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, F32.d_model)),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_held_rows_count_each_choice_once():
    """Each share's held rows are the choices that land in its block,
    as the reference's gate has them, and the 8 shares' rows add up to
    every token's 6 choices."""
    m = F32.moe
    full = dataclasses.replace(F32, moe=dataclasses.replace(
        m, n_experts=m.n_routed))
    p = _moe_params(full, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, F32.d_model))
    flat = x.reshape(-1, F32.d_model)
    total = 0.0
    for first in range(0, m.n_routed, 4):
        cfg, held = _share(full, p, first, 4)
        rows = float(moe_apply_dropless(held, cfg, x)[1])
        gate = mla_moe.held_weights(held, flat, _cfg_dict(cfg), Dot())
        assert rows == np.count_nonzero(np.asarray(gate)), first
        total += rows
    assert total == 64 * m.top_k
    assert float(moe_apply_dropless(p, full, x)[1]) == 64 * m.top_k


@pytest.mark.parametrize("held", ["all", "share"])
def test_train_step_reports_the_held_rows(held):
    """The AMB-DG step's ``moe_held_rows``: the held rows summed over the
    MoE layers and the microbatches. Holding every expert, that is each
    token's 6 choices in every MoE layer; holding a share, fewer."""
    from repro import api
    from repro.configs.base import (AmbdgConfig, MeshConfig, RunConfig,
                                    TRAIN_4K)
    cfg = SMOKE
    if held == "all":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_experts=cfg.moe.n_routed))
    rc = RunConfig(model=cfg,
                   shape=dataclasses.replace(TRAIN_4K, seq_len=32,
                                             global_batch=4),
                   mesh=MeshConfig(n_pods=1, data=1, model=1),
                   ambdg=AmbdgConfig(tau=1, n_microbatches=2, b_bar=4.0))
    model = build_model(cfg)
    strategy = api.build(model, rc)
    state = strategy.init_state(jax.random.PRNGKey(0))
    _, metrics = jax.jit(strategy.train_step)(state,
                                              model.dummy_batch(4, 32))
    every = 4 * 32 * cfg.moe.top_k * (cfg.n_layers - cfg.first_k_dense)
    rows = float(metrics["moe_held_rows"])
    if held == "all":
        assert rows == every
    else:
        assert 0 < rows < every


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_dropless_keeps_every_token(impl):
    """A router forced to send every token to held expert 2 (and its
    other 5 choices to experts held elsewhere): all 128 tokens go
    through expert 2, as the reference has it; a capacity layer would
    have kept ceil(6 * 128 / 32 * 1.25) of them."""
    p = _moe_params(F32, jax.random.PRNGKey(2))
    bias = jnp.zeros((F32.moe.n_routed,)).at[2].set(10.0)
    p["b_correction"] = bias.at[10:15].set(5.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, F32.d_model))
    y, _ = moe_apply_dropless(p, F32, x, impl=impl)
    flat = x.reshape(-1, F32.d_model)
    gate = mla_moe.held_weights(p, flat, _cfg_dict(F32), Dot())
    assert np.all(np.asarray(gate[:, 2]) > 0)
    assert not np.any(np.asarray(gate[:, [0, 1, 3]]))
    ref = mla_moe.moe(p, flat, _cfg_dict(F32), Dot())
    np.testing.assert_allclose(np.asarray(y.reshape(-1, F32.d_model)),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_dropless_pallas_matches_ref_gradients():
    """The layer's gradients through the Pallas grouped products
    (interpret mode) equal those through the jnp oracle, with tokens
    spread unevenly over the held experts and rows beyond the held
    ones left unwritten by the kernel."""
    p = _moe_params(F32, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, F32.d_model))

    def loss(impl):
        def f(p, x):
            y, _ = moe_apply_dropless(p, F32, x, impl=impl)
            return jnp.sum(y * y)
        return jax.grad(f, argnums=(0, 1))(p, x)

    got, want = loss("pallas"), loss("ref")
    assert max(jax.tree.leaves(_leaf_gaps(got, want))) < 1e-5


def test_mixtral_tree_unchanged_by_the_new_fields():
    """The held-share fields default to Mixtral's layer: the same
    leaves, shapes and parameter count as before they existed."""
    cfg = C.get_smoke_config("mixtral-8x7b")
    f = ParamFactory(jax.random.PRNGKey(0))
    moe_init(f, cfg)
    shapes = jax.tree.map(lambda a: a.shape, f.params["moe"])
    e, d, ff = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    assert shapes == {"w_router": (d, e), "w_gate": (e, d, ff),
                      "w_up": (e, d, ff), "w_down": (e, ff, d)}
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, moe=MoEConfig(n_experts=8, n_routed=64))


def test_benchmark_config_builds_the_program_config():
    data = json.loads((BENCH / "configs"
                       / "moonlight-16b-a3b-ep8.json").read_text())
    cfg = drive.model_config(data)
    assert isinstance(cfg.moe, MoEConfig)
    assert (cfg.moe.n_experts, cfg.moe.n_routed, cfg.moe.top_k,
            cfg.moe.d_expert, cfg.moe.n_shared) == (8, 64, 6, 1408, 2)
    assert cfg.moe.routed_scale == data["routed_scaling_factor"]
    assert cfg.moe_impl == "dropless" and cfg.first_k_dense == 1
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    model = build_model(cfg)
    struct = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(struct))
    assert n == cfg.n_params()
    # every published width and count as the catalog gives it
    full = C.get_config("moonlight-16b-a3b")
    for key in ("d_model", "n_heads", "d_ff", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "rope_theta", "norm_eps", "first_k_dense"):
        assert getattr(cfg, key) == getattr(full, key), key
    assert cfg.moe.n_routed == full.moe.n_experts == data["n_routed_experts"]
    assert model.decode_step is None          # training only
