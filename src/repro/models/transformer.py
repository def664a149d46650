"""Decoder-only LM covering the dense / MoE / xLSTM / hybrid families,
with scan-over-layers (stacked params), KV-cache decode, and logical
sharding axes throughout. Families:

  dense  : [attn -> mlp] x L
  moe    : [attn -> moe_ffn] x L            (mixtral); moonlight: first
           [attn -> mlp] x first_k_dense, latent attention (MLA)
  ssm    : [mLSTM | sLSTM] x L              (xlstm; slstm_every-th is sLSTM)
  hybrid : [mamba2] x L + shared attn block every k layers (zamba2)
  vlm    : dense backbone + patch-embedding stub, prefix-LM mask
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (DENSE, HYBRID, MOE, SSM, VLM, ModelConfig)
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import ParamFactory, split_factory
from repro.models.layers import (attention_apply, attention_init, cache_axes,
                                 causal_mask, decode_attention, embed_tokens,
                                 embedding_init, init_kv_cache, mlp_apply,
                                 mlp_init, output_logits, prefix_lm_mask,
                                 rmsnorm, rmsnorm_init)
from repro.models.moe import moe_apply, moe_init


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _sp(h, cfg: ModelConfig):
    """Sequence-parallel residual boundary (no-op without a mesh)."""
    if not cfg.seq_parallel or h.shape[1] == 1:
        return h
    from repro.dist.context import constrain
    return constrain(h, ("batch", "seq_sp", None))


def _remat(fn, cfg: ModelConfig):
    """Wrap a scanned block in jax.checkpoint per cfg.block_remat."""
    if cfg.block_remat == "full":
        return jax.checkpoint(fn)
    if cfg.block_remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return fn


def _layer_init(f: ParamFactory, cfg: ModelConfig, dense: bool = False):
    """One scanned layer's params (dense/moe/hybrid backbone); ``dense``:
    an MoE model's leading dense-MLP layer."""
    rmsnorm_init(f, "ln1", cfg.d_model)
    if cfg.family in (DENSE, VLM) or dense:
        attention_init(f, cfg)
        rmsnorm_init(f, "ln2", cfg.d_model)
        mlp_init(f, cfg)
    elif cfg.family == MOE:
        attention_init(f, cfg)
        rmsnorm_init(f, "ln2", cfg.d_model)
        moe_init(f, cfg)
    elif cfg.family == HYBRID:
        ssm_mod.mamba2_init(f, cfg)
    else:
        raise ValueError(cfg.family)


def init(key, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    """Returns (params, logical_axes) with matching pytree structure."""
    import numpy as np
    dtype = jnp.dtype(cfg.param_dtype)

    def build(f: ParamFactory):
        embedding_init(f, cfg)
        rmsnorm_init(f, "ln_final", cfg.d_model)
        if cfg.family == SSM:
            # xLSTM: alternating block types -> two stacks
            x = cfg.xlstm
            n_sl = cfg.n_layers // x.slstm_every
            n_ml = cfg.n_layers - n_sl
            f.vmapped_children("mlstm_layers", n_ml, lambda g: (
                rmsnorm_init(g, "ln1", cfg.d_model),
                xlstm_mod.mlstm_init(g, cfg)))
            f.vmapped_children("slstm_layers", n_sl, lambda g: (
                rmsnorm_init(g, "ln1", cfg.d_model),
                xlstm_mod.slstm_init(g, cfg)))
        elif cfg.family == HYBRID:
            f.vmapped_children("layers", cfg.n_layers,
                               lambda g: _layer_init(g, cfg))
            sh = f.child("shared_attn")
            rmsnorm_init(sh, "ln1", cfg.d_model)
            attention_init(sh, cfg)
            rmsnorm_init(sh, "ln2", cfg.d_model)
            mlp_init(sh, cfg)
        else:
            if cfg.first_k_dense:
                f.vmapped_children("dense_layers", cfg.first_k_dense,
                                   lambda g: _layer_init(g, cfg, dense=True))
            f.vmapped_children("layers", cfg.n_layers - cfg.first_k_dense,
                               lambda g: _layer_init(g, cfg))
        if cfg.family == VLM and cfg.frontend:
            # stub frontend: a learned projection applied to precomputed
            # patch embeddings + positional table
            f.param("patch_proj", (cfg.d_model, cfg.d_model),
                    ("embed", "embed2"))
            f.param("patch_pos", (cfg.n_frontend_tokens, cfg.d_model),
                    (None, "embed"))

    return split_factory(build, key, dtype)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def _layer_sums(cfg: ModelConfig) -> Dict[str, jax.Array]:
    """Zeros of what the decoder's layers add up beside their output:
    ``aux``, the MoE balance loss, and for the dropless layer
    ``held_rows``, the rows its held experts ran."""
    sums = {"aux": jnp.float32(0.0)}
    if cfg.family == MOE and cfg.moe_impl == "dropless":
        sums["held_rows"] = jnp.float32(0.0)
    return sums


def _block_apply(layer_p, cfg: ModelConfig, h, positions, mask,
                 mask_fn=None, dense: bool = False):
    h = h + attention_apply(
        layer_p["attn"], cfg, rmsnorm(h, layer_p["ln1"], cfg.norm_eps),
        positions, mask, mask_fn=mask_fn, causal=not cfg.prefix_lm)
    sums = _layer_sums(cfg)
    if cfg.family == MOE and not dense:
        y, sums = moe_apply(layer_p["moe"], cfg,
                            rmsnorm(h, layer_p["ln2"], cfg.norm_eps))
        h = h + y
    else:
        h = h + mlp_apply(layer_p["mlp"], cfg,
                          rmsnorm(h, layer_p["ln2"], cfg.norm_eps))
    return h, sums


def _mamba_block_apply(layer_p, cfg: ModelConfig, h):
    return h + ssm_mod.mamba2_apply(
        layer_p["mamba"], cfg, rmsnorm(h, layer_p["ln1"], cfg.norm_eps))


def forward(params, cfg: ModelConfig, tokens, *, extra: Optional[Dict] = None,
            prefix_len=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens: (B, S_text) int32. Returns (logits over full sequence,
    the layers' sums: ``aux``, the MoE balance loss, and ``held_rows``
    for the dropless MoE layer). For VLM, ``extra['patches']`` (B, P, d)
    is prepended."""
    dtype = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("model.embed"):
        h = embed_tokens(params, tokens, dtype) * math.sqrt(cfg.d_model)
    if cfg.family == VLM and extra is not None and "patches" in extra:
        patches = extra["patches"].astype(dtype)
        patches = patches @ params["patch_proj"].astype(dtype)
        patches = patches + params["patch_pos"].astype(dtype)[None]
        h = jnp.concatenate([patches, h], axis=1)
    B, S, _ = h.shape
    positions = jnp.arange(S)[None, :].astype(jnp.int32)

    if cfg.prefix_lm:
        plen = prefix_len if prefix_len is not None else (
            cfg.n_frontend_tokens if cfg.family == VLM else 0)
        mask_fn = lambda off, qn: prefix_lm_mask(qn, S, plen, q_offset=off)
    else:
        mask_fn = lambda off, qn: causal_mask(
            qn, S, window=cfg.sliding_window, q_offset=off)
    mask = mask_fn(0, S)

    sums = _layer_sums(cfg)

    if cfg.family == SSM:
        h, sums["aux"] = _xlstm_forward(params, cfg, h)
    elif cfg.family == HYBRID:
        h = _hybrid_forward(params, cfg, h, positions, mask, mask_fn)
    else:
        def stack(carry, layers, dense):
            block = _remat(
                lambda layer_p, hh: _block_apply(layer_p, cfg, hh,
                                                 positions, mask, mask_fn,
                                                 dense=dense), cfg)

            def scan_body(carry, layer_p):
                hh, total = carry
                hh, s = block(layer_p, _sp(hh, cfg))
                return (hh, jax.tree.map(jnp.add, total, s)), None
            return jax.lax.scan(scan_body, carry, layers,
                                unroll=cfg.scan_unroll)[0]

        carry = (h, sums)
        if cfg.first_k_dense:
            carry = stack(carry, params["dense_layers"], True)
        h, sums = stack(carry, params["layers"], False)

    with jax.named_scope("model.head"):
        h = rmsnorm(h, params["ln_final"], cfg.norm_eps)
        logits = output_logits(params, cfg, h)
    return logits, sums


def _xlstm_forward(params, cfg: ModelConfig, h):
    """Interleave mLSTM / sLSTM blocks in layer order; the two stacks are
    scanned separately but applied in their true order via index map."""
    x = cfg.xlstm
    # layer i is sLSTM iff (i+1) % slstm_every == 0
    sl_block = _remat(lambda lp, hh: xlstm_mod.slstm_block_apply(
        lp["slstm"], cfg, rmsnorm(hh, lp["ln1"], cfg.norm_eps))[0], cfg)
    ml_block = _remat(lambda lp, hh: xlstm_mod.mlstm_block_apply(
        lp["mlstm"], cfg, rmsnorm(hh, lp["ln1"], cfg.norm_eps)), cfg)
    ml_i, sl_i = 0, 0
    for i in range(cfg.n_layers):
        if (i + 1) % x.slstm_every == 0:
            lp = jax.tree.map(lambda a: a[sl_i], params["slstm_layers"])
            h = h + sl_block(lp, h)
            sl_i += 1
        else:
            lp = jax.tree.map(lambda a: a[ml_i], params["mlstm_layers"])
            h = h + ml_block(lp, h)
            ml_i += 1
    return h, jnp.float32(0.0)


def _hybrid_forward(params, cfg: ModelConfig, h, positions, mask,
                    mask_fn=None):
    """zamba2: groups of ``shared_attn_every`` mamba layers, a single
    *shared* attention+mlp block applied after each group."""
    k = cfg.shared_attn_every
    n_groups = cfg.n_layers // k
    sh = params["shared_attn"]

    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, k) + a.shape[1:]), params["layers"])

    mamba_block = _remat(
        lambda layer_p, hhh: _mamba_block_apply(layer_p, cfg, hhh), cfg)
    shared_block = _remat(
        lambda _p, hh: hh
        + attention_apply(sh["attn"], cfg,
                          rmsnorm(hh, sh["ln1"], cfg.norm_eps),
                          positions, mask, mask_fn=mask_fn,
                          causal=not cfg.prefix_lm), cfg)

    def group_body(hh, group_p):
        def layer_body(hhh, layer_p):
            return mamba_block(layer_p, _sp(hhh, cfg)), None
        hh, _ = jax.lax.scan(layer_body, hh, group_p,
                             unroll=cfg.scan_unroll)
        # shared attention block (same params every group)
        hh = shared_block(None, hh)
        hh = hh + mlp_apply(sh["mlp"], cfg,
                            rmsnorm(hh, sh["ln2"], cfg.norm_eps))
        return hh, None

    h, _ = jax.lax.scan(group_body, h, grouped, unroll=cfg.scan_unroll)
    return h


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, Dict]:
    """Per-sample-weighted next-token cross entropy.

    batch: {"tokens": (B,S) int32, "weights": (B,) f32, optional extras}.
    Returns (sum_weighted_loss, {"count": weighted token count,
    "loss_sum", and ``moe_held_rows`` for the dropless MoE layer: the
    rows its held experts ran, over every MoE layer}) so the AMB-DG
    aggregation can normalize by the *global* count (paper eq. (5)).
    """
    tokens = batch["tokens"]
    weights = batch.get("weights")
    if weights is None:
        weights = jnp.ones((tokens.shape[0],), jnp.float32)
    extra = {k: v for k, v in batch.items()
             if k not in ("tokens", "weights", "targets")}
    # run the forward at the full (power-of-two) sequence length and
    # slice the logits — slicing the *inputs* would make every internal
    # shape odd (S-1) and break sharding divisibility throughout
    logits, sums = forward(params, cfg, tokens, extra=extra or None)
    # VLM prepends patches: logits cover [patches, text]; loss on text only
    if cfg.family == VLM and "patches" in batch:
        logits = logits[:, batch["patches"].shape[1]:, :]
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    with jax.named_scope("model.head"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    per_sample = -jnp.sum(ll, axis=-1)                     # (B,)
    n_tok_per_sample = targets.shape[1]
    loss_sum = jnp.sum(per_sample * weights)
    count = jnp.sum(weights) * n_tok_per_sample
    out = {"count": count, "loss_sum": loss_sum}
    if "held_rows" in sums:
        out["moe_held_rows"] = sums["held_rows"]
    return loss_sum + sums["aux"] * count, out


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> Tuple[Dict, Dict]:
    """Returns (cache pytree, logical axes pytree)."""
    if cfg.family == SSM:
        x = cfg.xlstm
        n_sl = cfg.n_layers // x.slstm_every
        n_ml = cfg.n_layers - n_sl
        nh, hd, _ = xlstm_mod.slstm_dims(cfg)
        cache = {
            "mlstm": xlstm_mod.mlstm_state_init(cfg, n_ml, batch),
            "slstm": {
                "h": jnp.zeros((n_sl, batch, nh, hd), jnp.float32),
                "c": jnp.zeros((n_sl, batch, nh, hd), jnp.float32),
                "n": jnp.zeros((n_sl, batch, nh, hd), jnp.float32),
                "m": jnp.full((n_sl, batch, nh, hd), -30.0, jnp.float32),
            },
        }
        laxes = ("layers", "batch", "heads", None)
        axes = {
            "mlstm": {"C": ("layers", "batch", "heads", None, None),
                      "n": laxes, "m": ("layers", "batch", "heads")},
            "slstm": {k: laxes for k in ("h", "c", "n", "m")},
        }
        return cache, axes
    if cfg.family == HYBRID:
        cache = {
            "mamba": ssm_mod.mamba2_state_init(cfg, cfg.n_layers, batch),
            "shared": init_kv_cache(cfg, cfg.n_layers // cfg.shared_attn_every,
                                    batch, max_len, dtype),
        }
        axes = {"mamba": ssm_mod.mamba2_state_axes(),
                "shared": cache_axes(cfg)}
        return cache, axes
    cache = init_kv_cache(cfg, cfg.n_layers, batch, max_len, dtype)
    return cache, cache_axes(cfg)


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    """One-token decode. tokens: (B,1) int32; pos: scalar int32 (one
    absolute position shared by the batch — lockstep decode) or (B,)
    int32 per-slot positions (continuous batching; see
    ``layers.decode_attention``). Returns (logits (B,1,V), new_cache)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    h = embed_tokens(params, tokens, dtype) * math.sqrt(cfg.d_model)

    if cfg.family == SSM:
        h, cache = _xlstm_decode(params, cfg, h, cache)
    elif cfg.family == HYBRID:
        h, cache = _hybrid_decode(params, cfg, h, cache, pos)
    else:
        def scan_body(carry, xs):
            hh = carry
            layer_p, k_c, v_c = xs
            hn = rmsnorm(hh, layer_p["ln1"], cfg.norm_eps)
            y, k_c, v_c = decode_attention(layer_p["attn"], cfg, hn, pos,
                                           k_c, v_c)
            hh = hh + y
            if cfg.family == MOE:
                y2, _ = moe_apply(layer_p["moe"], cfg,
                                  rmsnorm(hh, layer_p["ln2"], cfg.norm_eps))
            else:
                y2 = mlp_apply(layer_p["mlp"], cfg,
                               rmsnorm(hh, layer_p["ln2"], cfg.norm_eps))
            return hh + y2, (k_c, v_c)

        h, (new_k, new_v) = jax.lax.scan(
            scan_body, h, (params["layers"], cache["k"], cache["v"]))
        cache = {"k": new_k, "v": new_v}

    h = rmsnorm(h, params["ln_final"], cfg.norm_eps)
    return output_logits(params, cfg, h), cache


def _xlstm_decode(params, cfg: ModelConfig, h, cache):
    x = cfg.xlstm
    ml_i, sl_i = 0, 0
    m_st, s_st = cache["mlstm"], cache["slstm"]
    new_m = jax.tree.map(lambda a: a, m_st)
    new_s = jax.tree.map(lambda a: a, s_st)
    for i in range(cfg.n_layers):
        if (i + 1) % x.slstm_every == 0:
            lp = jax.tree.map(lambda a: a[sl_i], params["slstm_layers"])
            hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
            st = tuple(new_s[k][sl_i] for k in ("h", "c", "n", "m"))
            y, st_out = xlstm_mod.slstm_block_apply(lp["slstm"], cfg, hn, st)
            h = h + y
            for k, v in zip(("h", "c", "n", "m"), st_out):
                new_s[k] = new_s[k].at[sl_i].set(v)
            sl_i += 1
        else:
            lp = jax.tree.map(lambda a: a[ml_i], params["mlstm_layers"])
            hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
            st = {k: new_m[k][ml_i] for k in ("C", "n", "m")}
            y, st_out = xlstm_mod.mlstm_block_decode(lp["mlstm"], cfg, hn, st)
            h = h + y
            for k in ("C", "n", "m"):
                new_m[k] = new_m[k].at[ml_i].set(st_out[k])
            ml_i += 1
    return h, {"mlstm": new_m, "slstm": new_s}


def _hybrid_decode(params, cfg: ModelConfig, h, cache, pos):
    k = cfg.shared_attn_every
    n_groups = cfg.n_layers // k
    sh = params["shared_attn"]
    mamba_c = cache["mamba"]
    grouped_p = jax.tree.map(
        lambda a: a.reshape((n_groups, k) + a.shape[1:]), params["layers"])
    grouped_ssm = jax.tree.map(
        lambda a: a.reshape((n_groups, k) + a.shape[1:]), mamba_c)

    def group_body(hh, xs):
        group_p, group_state, k_c, v_c = xs

        def layer_body(hhh, ys):
            layer_p, ssm_st, conv_st = ys
            hn = rmsnorm(hhh, layer_p["ln1"], cfg.norm_eps)
            y, ssm_new, conv_new = ssm_mod.mamba2_decode(
                layer_p["mamba"], cfg, hn, ssm_st, conv_st)
            return hhh + y, (ssm_new, conv_new)

        hh, (ssm_new, conv_new) = jax.lax.scan(
            layer_body, hh, (group_p, group_state["ssm"], group_state["conv"]))
        hn = rmsnorm(hh, sh["ln1"], cfg.norm_eps)
        y, k_c, v_c = decode_attention(sh["attn"], cfg, hn, pos, k_c, v_c)
        hh = hh + y
        hh = hh + mlp_apply(sh["mlp"], cfg,
                            rmsnorm(hh, sh["ln2"], cfg.norm_eps))
        return hh, ({"ssm": ssm_new, "conv": conv_new}, k_c, v_c)

    h, (new_mamba, new_k, new_v) = jax.lax.scan(
        group_body, h,
        (grouped_p, grouped_ssm, cache["shared"]["k"], cache["shared"]["v"]))
    new_mamba = jax.tree.map(
        lambda a: a.reshape((n_groups * k,) + a.shape[2:]), new_mamba)
    return h, {"mamba": new_mamba, "shared": {"k": new_k, "v": new_v}}
