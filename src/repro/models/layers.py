"""Core transformer layers: norms, RoPE (incl. partial/"2d"), GQA/MQA
attention with sliding-window / prefix-LM masks and KV-cache decode,
gated MLPs. Everything is written against logical sharding axes (see
``models.common``) and is family-agnostic.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import kernels
from repro.configs.base import ModelConfig
from repro.dist.context import active_mesh
from repro.kernels.flash_attention.bwd import flash_attention_train
from repro.kernels.flash_attention.kernel import seq_block
from repro.models.common import ParamFactory

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(f: ParamFactory, name: str, dim: int):
    f.param(name, (dim,), ("embed",), init="ones")


def rmsnorm(x, scale, eps: float):
    # stats accumulate in f32 through the reduction only — materializing
    # x.astype(f32) as the first block op makes XLA hoist the convert
    # into the scan-saved residual (a full f32 copy of the carry per
    # layer => 2x remat memory; observed on the 8x22B dry-run)
    var = jnp.mean(jnp.square(x).astype(jnp.float32), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def head_rmsnorm(x, scale, eps: float):
    """Per-head qk-norm (qwen3): x (..., n_heads, head_dim)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, partial: float, theta: float):
    rot = int(head_dim * partial)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float, partial: float = 1.0):
    """x: (B, S, H, D); positions: (B, S) or (S,). Rotates the first
    ``partial * D`` dims (rotate-half convention); chatglm's "2d RoPE"
    corresponds to partial=0.5."""
    head_dim = x.shape[-1]
    inv, rot = rope_frequencies(head_dim, partial, theta)
    if rot == 0:
        return x
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * inv  # (B,S,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------
def causal_mask(q_len: int, kv_len: int, *, window: Optional[int] = None,
                q_offset=0):
    """(q_len, kv_len) bool mask, True = attend. ``q_offset`` shifts query
    positions (decode / chunked prefill)."""
    q_pos = jnp.arange(q_len) + q_offset
    kv_pos = jnp.arange(kv_len)
    m = q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        m &= (q_pos[:, None] - kv_pos[None, :]) < window
    return m


def prefix_lm_mask(q_len: int, kv_len: int, prefix_len, q_offset=0):
    """Bidirectional over the first ``prefix_len`` positions, causal after."""
    base = causal_mask(q_len, kv_len, q_offset=q_offset)
    kv_pos = jnp.arange(kv_len)
    in_prefix = kv_pos[None, :] < prefix_len
    return base | in_prefix


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def attention_init(f: ParamFactory, cfg: ModelConfig, name: str = "attn",
                   d_model: Optional[int] = None):
    if cfg.is_mla:
        return mla_init(f, cfg, name)
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    a = f.child(name)
    a.param("wq", (d, cfg.n_heads * hd), ("embed", "heads"))
    a.param("wk", (d, cfg.n_kv_heads * hd), ("embed", "heads"))
    a.param("wv", (d, cfg.n_kv_heads * hd), ("embed", "heads"))
    a.param("wo", (cfg.n_heads * hd, d), ("heads", "embed"))
    if cfg.qkv_bias:
        a.param("bq", (cfg.n_heads * hd,), ("heads",), init="zeros")
        a.param("bk", (cfg.n_kv_heads * hd,), ("heads",), init="zeros")
        a.param("bv", (cfg.n_kv_heads * hd,), ("heads",), init="zeros")
    if cfg.qk_norm:
        a.param("q_norm", (hd,), (None,), init="ones")
        a.param("k_norm", (hd,), (None,), init="ones")


def _project_qkv(p, cfg: ModelConfig, x, positions, rope: bool = True):
    hd = cfg.resolved_head_dim
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = head_rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_partial)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_partial)
    return q, k, v


def gqa_attend(q, k, v, mask, softcap: Optional[float] = None):
    """Grouped-query attention core. q, k: (B,S,H,D) with Hkv key
    heads; v: (B,Skv,Hkv,Dv), Dv may differ from D (latent attention);
    mask: broadcastable to (B,Hkv,G,Sq,Skv) or (Sq,Skv)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    q = q.reshape(B, Sq, Hkv, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhe->bqhge", w, v)
    return out.reshape(B, Sq, Hq, v.shape[-1])


_Q_CHUNK = 2048          # q-block size for long-sequence attention
_CHUNK_THRESHOLD = 8192  # chunk when S exceeds this
# the Pallas flash kernel from this length up. A call's device time on
# the v5e, fitted over the training cells' layer calls (jnp at 512,
# 2,048 and 8,192 tokens; the kernel with (B, H, S, D) relayouts):
# jnp ~ 0.076 ms x (S/512)^2, flash ~ 0.092 ms + 0.031 ms x (S/512)^2,
# so flash wins above ~730 tokens (1,024: 0.22 against 0.30 ms) and
# loses at 512 (0.123 against 0.076 ms), where the fixed cost of the
# kernel's grid outweighs the small causal square
_FLASH_THRESHOLD = 1024


def takes_flash(cfg: ModelConfig, seq_len: int, causal: bool) -> bool:
    """Whether a full-sequence attention core runs the Pallas flash
    kernel: on the TPU, outside any mesh of more than one device (the
    kernel has no shard_map wrapper, so GSPMD would gather q, k and v to
    every device, as ``kernels.resolve_impl`` says of the arena
    kernels), for the causal (optionally sliding-window) mask that
    ``transformer.forward`` builds (``causal``: the caller says so, and
    a prefix-LM caller says not), with no logit softcap, at ``seq_len``
    of at least ``_FLASH_THRESHOLD`` that the kernel's blocks divide.
    Every other call keeps the jnp core."""
    mesh = active_mesh()
    return (kernels.on_tpu() and (mesh is None or mesh.n_devices == 1)
            and causal and cfg.logit_softcap is None
            and seq_len >= _FLASH_THRESHOLD
            and seq_block(seq_len) is not None)


def flash_attend(cfg: ModelConfig, q, k, v):
    """The causal core through the flash kernel: q, k (B, S, H, D), v
    (B, S, Hkv, Dv) -> (B, S, H, Dv)."""
    return flash_attention_train(q, k, v, True, cfg.sliding_window,
                                 kernels.resolve_interpret(None))


def chunked_gqa_attend(q, k, v, mask_fn, softcap=None, chunk=_Q_CHUNK,
                       remat_chunks: bool = False):
    """Exact attention in q-blocks (lazy softmax over the full row per
    block): peak scores memory O(chunk * S) instead of O(S^2). mask_fn
    (q_offset, q_len) -> (q_len, Skv) bool. ``remat_chunks``: each
    block's scores are recomputed in the backward instead of saved,
    so that one block's scores are live at a time there too."""
    from repro.dist.context import constrain
    # pin batch-sharded activations: with few kv heads (MQA) GSPMD can
    # otherwise trade the batch sharding away and materialize unsharded
    # (B, H, chunk, S) score blocks
    q = constrain(q, ("batch", None, None, None))
    k = constrain(k, ("batch", None, None, None))
    v = constrain(v, ("batch", None, None, None))
    B, Sq, Hq, D = q.shape
    nc = Sq // chunk
    qc = q.reshape(B, nc, chunk, Hq, D)
    masks = jnp.stack([mask_fn(i * chunk, chunk) for i in range(nc)])

    def body(_, inp):
        q_i, m_i = inp
        return None, gqa_attend(q_i, k, v, m_i, softcap)

    if remat_chunks:
        body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None,
                          (jnp.moveaxis(qc, 1, 0), masks))
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hq, v.shape[-1])


def attention_apply(p, cfg: ModelConfig, x, positions, mask,
                    d_model: Optional[int] = None, mask_fn=None,
                    causal: bool = False):
    """Full-sequence (train / prefill) attention. ``causal``: ``mask``
    is the causal (or sliding-window) mask of ``transformer.forward``,
    which lets ``takes_flash`` choose the Pallas kernel. Otherwise, for
    S beyond the chunk threshold, pass ``mask_fn`` to enable exact
    q-block chunking."""
    if cfg.is_mla:
        return mla_apply(p, cfg, x, positions, mask, mask_fn, causal)
    q, k, v = _project_qkv(p, cfg, x, positions)
    B, S = x.shape[:2]
    with jax.named_scope("model.mixer"):
        if takes_flash(cfg, S, causal):
            out = flash_attend(cfg, q, k, v)
        elif (mask_fn is not None and S > _CHUNK_THRESHOLD
                and S % _Q_CHUNK == 0):
            out = chunked_gqa_attend(q, k, v, mask_fn, cfg.logit_softcap)
        else:
            out = gqa_attend(q, k, v, mask, cfg.logit_softcap)
    return out.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2 section 2.1, no q-LoRA)
# ---------------------------------------------------------------------------
_MLA_Q_CHUNK = 1024     # q-block of the latent attention core


def mla_init(f: ParamFactory, cfg: ModelConfig, name: str = "attn"):
    d, H = cfg.d_model, cfg.n_heads
    nope, rope, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank)
    a = f.child(name)
    a.param("wq", (d, H * (nope + rope)), ("embed", "heads"))
    # [c_kv | k_pe]: the kv latent and the rope key all heads share
    a.param("w_kv_a", (d, r + rope), ("embed", None))
    a.child("kv_latent").param("norm_scale", (r,), (None,), init="ones")
    a.param("w_kv_b", (r, H * (nope + cfg.v_head_dim)), (None, "heads"))
    a.param("wo", (H * cfg.v_head_dim, d), ("heads", "embed"))


def mla_apply(p, cfg: ModelConfig, x, positions, mask, mask_fn=None,
              causal: bool = False):
    """q = W_q h, split into q_nope and q_pe; [c_kv | k_pe] = W_kv_a h;
    [k_nope | v] = W_kv_b RMSNorm(c_kv) per head; RoPE on q_pe and on
    the one k_pe; scores (q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope),
    causal; out = W_o over the value heads. x: (B, S, d)."""
    B, S, _ = x.shape
    H, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r, vd = cfg.kv_lora_rank, cfg.v_head_dim
    with jax.named_scope("model.attn"):
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, nope + rope)
        kv_a = x @ p["w_kv_a"].astype(x.dtype)
        c_kv = rmsnorm(kv_a[..., :r], p["kv_latent"]["norm_scale"],
                       cfg.norm_eps)
        kv = (c_kv @ p["w_kv_b"].astype(x.dtype)).reshape(B, S, H,
                                                          nope + vd)
        q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta)
        k_pe = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))],
            axis=-1)
        v = kv[..., nope:]
    with jax.named_scope("model.mixer"):
        if takes_flash(cfg, S, causal):
            out = flash_attend(cfg, q, k, v)
        elif (mask_fn is not None and S > _MLA_Q_CHUNK
                and S % _MLA_Q_CHUNK == 0):
            out = chunked_gqa_attend(q, k, v, mask_fn, cfg.logit_softcap,
                                     chunk=_MLA_Q_CHUNK, remat_chunks=True)
        else:
            out = gqa_attend(q, k, v, mask, cfg.logit_softcap)
    with jax.named_scope("model.attn"):
        return out.reshape(B, S, H * vd) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    """Cache layout: SWA archs use a rolling buffer of ``window`` slots;
    full attention keeps ``max_len`` slots."""
    hd = cfg.resolved_head_dim
    slots = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
    shape = (n_layers, batch, slots, cfg.n_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def cache_axes(cfg: ModelConfig):
    ax = ("layers", "batch", "kv_seq", "heads", None)
    return {"k": ax, "v": ax}


def decode_attention(p, cfg: ModelConfig, x, pos, k_cache, v_cache,
                     cache_len: Optional[int] = None):
    """One-token decode step against a (possibly rolling) layer cache.

    x: (B, 1, d); k_cache/v_cache: (B, slots, Hkv, D). ``pos`` is
    either a scalar int32 absolute position (lockstep decode, same
    across the batch — the original path, kept byte-identical) or a
    (B,) int32 vector of PER-SLOT positions (continuous batching: each
    batch row is its own sequence at its own local position, so each
    writes its own cache column and masks only the columns it has
    itself written — a freshly admitted sequence at pos 0 can never
    attend to a previous occupant's stale entries). Returns
    (out, new_k, new_v).
    """
    B = x.shape[0]
    slots = k_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        q, k, v = _project_qkv(p, cfg, x, jnp.full((B, 1), pos), rope=True)
        slot = pos % slots if cfg.sliding_window else pos
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), slot, axis=1)
        kv_pos = jnp.arange(slots)
        if cfg.sliding_window:
            # rolling buffer: once full every slot is within the window;
            # before that only slots <= pos have been written.
            valid = jnp.where(pos + 1 >= slots,
                              jnp.ones((slots,), bool), kv_pos <= pos)
        else:
            valid = kv_pos <= pos
        mask = valid[None, None, None, None, :]  # (B,Hkv,G,Sq,Skv) bcast
    else:
        # per-slot positions: batched scatter into each row's own
        # column, per-row validity mask
        q, k, v = _project_qkv(p, cfg, x, pos[:, None], rope=True)
        col = pos % slots if cfg.sliding_window else pos
        bidx = jnp.arange(B)
        k_cache = k_cache.at[bidx, col].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, col].set(v[:, 0].astype(v_cache.dtype))
        kv_pos = jnp.arange(slots)
        written = kv_pos[None, :] <= pos[:, None]          # (B, slots)
        if cfg.sliding_window:
            valid = jnp.where(pos[:, None] + 1 >= slots,
                              jnp.ones((B, slots), bool), written)
        else:
            valid = written
        mask = valid[:, None, None, None, :]     # (B,Hkv,G,Sq,Skv) bcast
    out = gqa_attend(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                     mask, cfg.logit_softcap)
    out = out.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------
def mlp_init(f: ParamFactory, cfg: ModelConfig, name: str = "mlp",
             d_model: Optional[int] = None, d_ff: Optional[int] = None):
    d = d_model or cfg.d_model
    dff = d_ff or cfg.d_ff
    m = f.child(name)
    m.param("w_gate", (d, dff), ("embed", "mlp"))
    m.param("w_up", (d, dff), ("embed", "mlp"))
    m.param("w_down", (dff, d), ("mlp", "embed"))


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def gated_mlp(p, cfg: ModelConfig, x):
    act = _act(cfg.act)
    h = (act(x @ p["w_gate"].astype(x.dtype))
         * (x @ p["w_up"].astype(x.dtype)))
    return h @ p["w_down"].astype(x.dtype)


def mlp_apply(p, cfg: ModelConfig, x):
    with jax.named_scope("model.mlp"):
        return gated_mlp(p, cfg, x)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def embedding_init(f: ParamFactory, cfg: ModelConfig):
    # input table: vocab dim REPLICATED (None) so the token gather needs
    # no reshard; embed dim FSDP-sharded. The output projection is
    # vocab-sharded (TP); tied configs reshard the table at the use
    # site via the logits sharding constraint (see output_logits).
    f.param("tok_emb", (cfg.padded_vocab_size, cfg.d_model),
            (None, "embed"), scale=1.0 / math.sqrt(cfg.d_model))
    if not cfg.tie_embeddings:
        f.param("out_head", (cfg.d_model, cfg.padded_vocab_size),
                ("embed", "vocab"))


def embed_tokens(params, tokens, dtype):
    from repro.dist.context import constrain
    out = jnp.take(params["tok_emb"], tokens, axis=0).astype(dtype)
    # pin batch-sharded/embed-replicated output: the gather would
    # otherwise inherit the table's FSDP ("data") sharding on the embed
    # dim and silently drop the batch sharding for the whole network
    # (ZeRO-3 semantics: table sharded at rest, gathered at use).
    return constrain(out, ("batch",) + (None,) * (out.ndim - 1))


def output_logits(params, cfg: ModelConfig, h):
    from repro.dist.context import constrain
    if cfg.tie_embeddings:
        # reshard the (gather-layout, embed-FSDP) table into the
        # vocab-sharded TP layout BEFORE the matmul — otherwise GSPMD
        # resolves the data-axis conflict by replicating h's batch and
        # materializes unsharded (B, S, V) logits
        wt = constrain(params["tok_emb"].astype(h.dtype).T,
                       (None, "vocab"))
        logits = h @ wt
    else:
        logits = h @ params["out_head"].astype(h.dtype)
    # pin vocab-sharded logits (keeps the softmax/CE sharded over TP
    # instead of replicating a (B,S,V) tensor)
    axes = ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",)
    return constrain(logits, axes)
