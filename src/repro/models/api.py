"""Unified model API.

``build_model(cfg)`` returns a ``Model`` with a common interface across
all families so the AMB-DG train-step factory, the serving engine and the
dry-run never special-case architectures:

    params, axes   = model.init(key)
    loss_sum, aux  = model.loss(params, batch)       # SUM + counts
    cache, caxes   = model.init_decode_state(batch, max_len)
    logits, cache  = model.decode_step(params, cache, tokens, pos)
    batch          = model.dummy_batch(batch_size, seq_len)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (CNN, DENSE, ENCDEC, HYBRID, LINREG, MOE, SSM,
                                VLM, ModelConfig)
from repro.models import cnn as cnn_mod
from repro.models import encdec as encdec_mod
from repro.models import linear as linear_mod
from repro.models import transformer as tf_mod


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Tuple[Dict, Dict]]
    loss: Callable[[Dict, Dict], Tuple[jax.Array, Dict]]
    init_decode_state: Optional[Callable] = None
    decode_step: Optional[Callable] = None
    dummy_batch: Optional[Callable] = None
    input_specs: Optional[Callable] = None


def _lm_dummy_batch(cfg: ModelConfig, batch: int, seq: int, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    n_text = seq - cfg.n_frontend_tokens if cfg.family == VLM else seq
    out = {
        "tokens": jax.random.randint(key, (batch, n_text), 0,
                                     cfg.vocab_size, jnp.int32),
        "weights": jnp.ones((batch,), jnp.float32),
    }
    if cfg.family == VLM:
        out["patches"] = jax.random.normal(
            key, (batch, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.family == ENCDEC:
        out["frames"] = jax.random.normal(
            key, (batch, seq, cfg.d_model), jnp.float32)
    return out


def _lm_input_specs(cfg: ModelConfig, batch: int, seq: int):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    n_text = seq - cfg.n_frontend_tokens if cfg.family == VLM else seq
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, n_text), jnp.int32),
        "weights": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    if cfg.family == VLM:
        specs["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.family == ENCDEC:
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch, seq, cfg.d_model), jnp.float32)
    return specs


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == LINREG:
        def dummy(batch, seq=0, key=None):
            key = key if key is not None else jax.random.PRNGKey(0)
            kx, kw, kn = jax.random.split(key, 3)
            w_star = jax.random.normal(kw, (cfg.linreg_dim,))
            x = jax.random.normal(kx, (batch, cfg.linreg_dim))
            y = x @ w_star + 0.001 ** 0.5 * jax.random.normal(kn, (batch,))
            return {"x": x, "y": y, "weights": jnp.ones((batch,), jnp.float32)}
        return Model(cfg, lambda k: linear_mod.init(k, cfg),
                     lambda p, b: linear_mod.loss(p, cfg, b),
                     dummy_batch=dummy)

    if cfg.family == CNN:
        def dummy(batch, seq=0, key=None):
            key = key if key is not None else jax.random.PRNGKey(0)
            ki, kl = jax.random.split(key)
            return {
                "images": jax.random.normal(
                    ki, (batch, cfg.image_size, cfg.image_size, 3)),
                "labels": jax.random.randint(kl, (batch,), 0, cfg.n_classes),
                "weights": jnp.ones((batch,), jnp.float32),
            }
        return Model(cfg, lambda k: cnn_mod.init(k, cfg),
                     lambda p, b: cnn_mod.loss(p, cfg, b),
                     dummy_batch=dummy)

    if cfg.family == ENCDEC:
        return Model(
            cfg,
            init=lambda k: encdec_mod.init(k, cfg),
            loss=lambda p, b: encdec_mod.loss(p, cfg, b),
            init_decode_state=lambda batch, max_len, dtype=jnp.bfloat16:
                encdec_mod.init_decode_state(cfg, batch, max_len, dtype),
            decode_step=lambda p, c, t, pos: encdec_mod.decode_step(
                p, cfg, c, t, pos),
            dummy_batch=lambda b, s, key=None: _lm_dummy_batch(cfg, b, s, key),
            input_specs=lambda b, s: _lm_input_specs(cfg, b, s),
        )

    if cfg.family in (DENSE, MOE, SSM, HYBRID, VLM):
        # a latent-attention model trains only: no latent cache here
        decoder = cfg.has_decoder
        return Model(
            cfg,
            init=lambda k: tf_mod.init(k, cfg),
            loss=lambda p, b: tf_mod.lm_loss(p, cfg, b),
            init_decode_state=(
                lambda batch, max_len, dtype=jnp.bfloat16:
                tf_mod.init_decode_state(cfg, batch, max_len, dtype)
            ) if decoder else None,
            decode_step=(lambda p, c, t, pos: tf_mod.decode_step(
                p, cfg, c, t, pos)) if decoder else None,
            dummy_batch=lambda b, s, key=None: _lm_dummy_batch(cfg, b, s, key),
            input_specs=lambda b, s: _lm_input_specs(cfg, b, s),
        )

    raise ValueError(f"unknown family {cfg.family}")
