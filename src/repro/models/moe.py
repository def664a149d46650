"""Mixture-of-Experts FFN (Mixtral-style top-k routing), grouped dispatch.

Tokens are routed in independent groups of ``dispatch_group`` tokens
(the Mesh/Switch trick): capacity, dispatch tensors and gathers are all
per-group, so memory is O(group * E * C_g) instead of O(T * E * C) and
routing stays local to the batch shard (the group dim inherits the
batch sharding).

Two dispatch implementations, selectable via ``ModelConfig.moe_impl``
(a §Perf lever, see EXPERIMENTS.md):

* ``einsum`` — classic capacity dispatch: a dense (g, tokens, experts,
  capacity) one-hot dispatch tensor and two routing einsums. Simple and
  MXU-friendly, but the routing einsums are pure overhead FLOPs and the
  dispatch tensor is a real intermediate.
* ``gather`` — sort-free scatter/gather routing: rank tokens within
  their expert via a per-group cumsum, scatter token ids into (E*C_g)
  slots, gather activations. Zero routing matmul FLOPs, no dispatch
  tensor; only data movement.

Both drop tokens beyond capacity C_g = ceil(top_k * g / E * cf) with
identical drop order, so they are numerically equivalent.

A third, ``dropless`` (``moe_apply_dropless``), is the held-expert layer
of expert parallelism with DeepSeek-V3's router: sigmoid scores over
all ``router_width`` experts, the top-k chosen by the scores plus a
correction bias no gradient reaches, the chosen scores renormalised
and scaled. The layer holds ``n_experts`` experts from ``first_expert`` on,
sorts the assignments that land on its experts by expert, runs the
gated MLP as grouped products over their rows
(``kernels/grouped_matmul``) and scatter-adds the weighted rows back to
their tokens, dropping none. Shared experts, if any, are added once.
Holding every expert, it is the uncut layer; holding a share, it
computes that share's part, as one chip of an expert-parallel
deployment does before the exchange.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.grouped_matmul.ops import gmm
from repro.models.common import ParamFactory
from repro.models.layers import _act, gated_mlp, mlp_init


def moe_init(f: ParamFactory, cfg: ModelConfig, name: str = "moe"):
    mc = cfg.moe
    m = f.child(name)
    e, d, dff = mc.n_experts, cfg.d_model, mc.d_expert or cfg.d_ff
    m.param("w_router", (d, mc.router_width), ("embed", None))
    if cfg.moe_impl == "dropless":
        m.param("b_correction", (mc.router_width,), (None,), init="zeros")
    m.param("w_gate", (e, d, dff), ("expert", "embed", "mlp"))
    m.param("w_up", (e, d, dff), ("expert", "embed", "mlp"))
    m.param("w_down", (e, dff, d), ("expert", "mlp", "embed"))
    if mc.n_shared:
        mlp_init(m, cfg, "shared", d_ff=mc.n_shared * dff)


def _capacity(cfg: ModelConfig, group: int) -> int:
    mc = cfg.moe
    c = int(mc.top_k * group / mc.n_experts * mc.capacity_factor)
    return max(8, -(-c // 8) * 8)


def _group(cfg: ModelConfig, x):
    """(B, S, d) -> (G, g, d) with the group dim inheriting the batch
    sharding (groups never span samples unless g > S)."""
    B, S, d = x.shape
    T = B * S
    g = min(cfg.moe.dispatch_group, T)
    while T % g:
        g //= 2
    return x.reshape(T // g, g, d), g


def _router(p, cfg: ModelConfig, xg):
    """xg: (G, g, d) -> gates (G,g,k), idx (G,g,k), aux scalar."""
    mc = cfg.moe
    logits = (xg @ p["w_router"].astype(xg.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (G, g, E)
    gates, idx = jax.lax.top_k(probs, mc.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    density = jnp.mean(jax.nn.one_hot(idx[..., 0], mc.n_experts),
                       axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=(0, 1))
    aux = mc.n_experts * jnp.sum(density * density_proxy) * mc.aux_loss_weight
    return gates, idx, aux


def _expert_ffn(p, cfg: ModelConfig, xe):
    """xe: (..., E, C, d) -> same, batched expert MLP."""
    act = _act(cfg.act)
    h = act(jnp.einsum("...ecd,edf->...ecf", xe,
                       p["w_gate"].astype(xe.dtype)))
    h = h * jnp.einsum("...ecd,edf->...ecf", xe,
                       p["w_up"].astype(xe.dtype))
    return jnp.einsum("...ecf,efd->...ecd", h,
                      p["w_down"].astype(xe.dtype))


def moe_apply_einsum(p, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """Dense grouped capacity dispatch. x: (B, S, d)."""
    mc = cfg.moe
    B, S, d = x.shape
    xg, g = _group(cfg, x)
    G = xg.shape[0]
    C = _capacity(cfg, g)
    gates, idx, aux = _router(p, cfg, xg)

    onehot = jax.nn.one_hot(idx, mc.n_experts, dtype=jnp.float32)  # (G,g,k,E)
    flat = onehot.reshape(G, g * mc.top_k, mc.n_experts)
    pos = jnp.cumsum(flat, axis=1) - 1.0
    pos = pos.reshape(G, g, mc.top_k, mc.n_experts)
    pos = jnp.sum(pos * onehot, axis=-1)                           # (G,g,k)
    keep = (pos < C).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    dispatch = jnp.einsum("Gtke,Gtkc,Gtk->Gtec", onehot, pos_oh, keep)
    combine = jnp.einsum("Gtec,Gtk,Gtke->Gtec", dispatch,
                         gates.astype(jnp.float32), onehot)

    xe = jnp.einsum("Gtec,Gtd->Gecd", dispatch.astype(x.dtype), xg)
    ye = _expert_ffn(p, cfg, xe)
    y = jnp.einsum("Gtec,Gecd->Gtd", combine.astype(x.dtype), ye)
    return y.reshape(B, S, d), aux


def moe_apply_gather(p, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """Scatter/gather routing — no dispatch matmuls. x: (B, S, d)."""
    mc = cfg.moe
    B, S, d = x.shape
    xg, g = _group(cfg, x)
    G = xg.shape[0]
    C = _capacity(cfg, g)
    gates, idx, aux = _router(p, cfg, xg)
    E, k = mc.n_experts, mc.top_k

    flat_e = idx.reshape(G, g * k)                    # expert per assignment
    flat_g = gates.reshape(G, g * k)
    flat_t = jnp.tile(jnp.repeat(jnp.arange(g), k)[None], (G, 1))

    onehot = (flat_e[..., None] == jnp.arange(E)[None, None, :])
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=1) - 1
    rank = jnp.take_along_axis(rank, flat_e[..., None], axis=2)[..., 0]
    keep = rank < C
    slot = flat_e * C + jnp.minimum(rank, C - 1)      # (G, g*k)

    def per_group(xg_i, slot_i, keep_i, t_i, g_i):
        slot_token = jnp.zeros((E * C,), jnp.int32)
        slot_token = slot_token.at[
            jnp.where(keep_i, slot_i, E * C)].set(t_i.astype(jnp.int32),
                                                  mode="drop")
        xe = jnp.take(xg_i, slot_token, axis=0)       # (E*C, d)
        return xe, slot_token

    xe, _ = jax.vmap(per_group)(xg, slot, keep, flat_t, flat_g)
    xe = xe.reshape(G, E, C, d)
    ye = _expert_ffn(p, cfg, xe).reshape(G, E * C, d)

    def combine_group(ye_i, slot_i, keep_i, t_i, g_i):
        contrib = jnp.take(ye_i, slot_i, axis=0) * (
            g_i * keep_i)[:, None].astype(ye_i.dtype)
        return jnp.zeros((g, d), ye_i.dtype).at[t_i].add(contrib)

    y = jax.vmap(combine_group)(ye, slot, keep, flat_t, flat_g)
    return y.reshape(B, S, d), aux


def route(p, cfg: ModelConfig, x):
    """x: (T, d) -> (weights (T, k) f32, expert ids (T, k) over the
    router's whole width). In float32 at full precision, as the
    published router is, so that bf16 rounding flips no choice."""
    mc = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32),
                     p["w_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + p["b_correction"].astype(jnp.float32)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(choice), mc.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * mc.routed_scale, idx


def moe_apply_dropless(p, cfg: ModelConfig, x, impl: str = "auto"
                       ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer plus the shared experts, no
    token dropped. x: (B, S, d) -> (y, held rows): the assignments the
    held experts ran, the rows of each grouped product (f32; the layer
    has no balance loss). ``impl`` picks the grouped product's kernel
    (``kernels.grouped_matmul.ops.gmm``)."""
    mc = cfg.moe
    B, S, d = x.shape
    T, E, k = B * S, mc.n_experts, mc.top_k
    xt = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        w, idx = route(p, cfg, xt)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(T * k) - mc.first_expert
        expert = jnp.where((local >= 0) & (local < E), local, E)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
        # a token's k experts are distinct, so at most T * min(k, E)
        # assignments land here; sorted, they come first
        rows = T * min(k, E)
        order = jnp.argsort(expert, stable=True)[:rows]
        valid = jnp.arange(rows) < jnp.sum(sizes)
        tok = order // k
        xs = jnp.where(valid[:, None], jnp.take(xt, tok, axis=0), 0)
        ws = jnp.where(valid, jnp.take(w.reshape(T * k), order), 0.0)
    with jax.named_scope("moe.experts"):
        h = _act(cfg.act)(gmm(xs, p["w_gate"].astype(x.dtype), sizes,
                              impl=impl))
        h = h * gmm(xs, p["w_up"].astype(x.dtype), sizes, impl=impl)
        ys = gmm(h, p["w_down"].astype(x.dtype), sizes, impl=impl)
    with jax.named_scope("moe.combine"):
        ys = jnp.where(valid[:, None], ys.astype(jnp.float32)
                       * ws[:, None], 0.0)
        y = jax.ops.segment_sum(ys, tok, num_segments=T).astype(x.dtype)
    if mc.n_shared:
        with jax.named_scope("moe.shared"):
            y = y + gated_mlp(p["shared"], cfg, xt)
    return y.reshape(B, S, d), jnp.sum(sizes).astype(jnp.float32)


def moe_apply(p, cfg: ModelConfig, x) -> Tuple[jax.Array, Dict]:
    """(y, the layer's sums): ``aux``, the balance loss, and for the
    dropless layer ``held_rows``."""
    impl = getattr(cfg, "moe_impl", "einsum")
    with jax.named_scope("model.moe"):
        if impl == "dropless":
            y, rows = moe_apply_dropless(p, cfg, x)
            return y, {"aux": jnp.float32(0.0), "held_rows": rows}
        if impl == "gather":
            y, aux = moe_apply_gather(p, cfg, x)
        else:
            y, aux = moe_apply_einsum(p, cfg, x)
        return y, {"aux": aux}
