"""Config system for the AMB-DG framework.

Plain frozen dataclasses (pytree-static, hashable) so they can be closed
over by jitted functions. One ``ModelConfig`` covers every assigned
architecture family; per-arch files in this package instantiate it and
register under the public ``--arch`` id.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model families
# ---------------------------------------------------------------------------
DENSE = "dense"
MOE = "moe"
SSM = "ssm"          # xLSTM-style (mLSTM/sLSTM blocks)
HYBRID = "hybrid"    # zamba2: mamba2 backbone + shared attention
ENCDEC = "encdec"    # seamless: encoder-decoder
VLM = "vlm"          # paligemma: patch-embedding stub + prefix-LM decoder
LINREG = "linreg"    # paper's own linear-regression experiment
CNN = "cnn"          # paper's own CIFAR-style CNN experiment

LM_FAMILIES = (DENSE, MOE, SSM, HYBRID, ENCDEC, VLM)


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # aux load-balancing loss weight (Switch/Mixtral style)
    aux_loss_weight: float = 0.01
    # tokens are routed in independent groups of this size (the Mesh/
    # Switch "group" trick): keeps dispatch tensors O(group^2) instead
    # of O(T^2) and keeps routing local to the batch shard.
    dispatch_group: int = 4096
    # --- the held-expert dropless layer (moe_impl "dropless") ---
    # experts the router scores (its published width); 0: n_experts,
    # every expert held here. The layer holds n_experts of them,
    # first_expert .. first_expert + n_experts - 1, and computes only
    # their part of the result (an expert-parallel share).
    n_routed: int = 0
    first_expert: int = 0
    d_expert: int = 0               # expert width; 0: ModelConfig.d_ff
    # shared experts every token passes through, run as one gated MLP
    # of width n_shared * d_expert
    n_shared: int = 0
    # the dropless layer's chosen weights (renormalised sigmoid scores,
    # DeepSeek-V3) are multiplied by this
    routed_scale: float = 1.0

    @property
    def router_width(self) -> int:
        return self.n_routed or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD parameters (used by hybrid + ssm families)."""
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256
    # number of mamba "groups" for B/C projections (mamba2 ngroups)
    n_groups: int = 1


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block layout: which layer indices are sLSTM (rest mLSTM)."""
    slstm_every: int = 2          # every k-th block is sLSTM (offset 1)
    proj_factor_mlstm: float = 2.0
    proj_factor_slstm: float = 1.3333333
    conv_width: int = 4


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # defaults to d_model // n_heads
    # --- attention flavour ---
    rope_theta: float = 10000.0
    rope_partial: float = 1.0               # fraction of head_dim rotated (chatglm: 0.5)
    qk_norm: bool = False                   # qwen3-style per-head RMSNorm
    qkv_bias: bool = False                  # qwen1.5-style
    sliding_window: Optional[int] = None    # SWA window (mixtral: 4096)
    prefix_lm: bool = False                 # paligemma: bidirectional prefix
    logit_softcap: Optional[float] = None
    # --- ffn / norms ---
    act: str = "silu"                       # silu | gelu
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- family-specific ---
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    shared_attn_every: int = 0              # zamba2: shared attn block period
    first_k_dense: int = 0                  # moe: leading dense-MLP layers
    # multi-head latent attention (DeepSeek-V2 MLA, no q-LoRA) when
    # kv_lora_rank > 0: keys and values come from a kv_lora_rank-wide
    # latent; each q/k head is qk_nope_head_dim + qk_rope_head_dim
    # wide, the rope part of the key shared by all heads; values are
    # v_head_dim wide
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_encoder_layers: int = 0               # encdec only
    cross_attention: bool = False           # encdec only
    frontend: Optional[str] = None          # "siglip_stub" | "w2vbert_stub"
    n_frontend_tokens: int = 0              # patches / frames fed by stub
    # --- paper's own problems ---
    linreg_dim: int = 0
    image_size: int = 0
    n_classes: int = 0
    # --- implementation knobs (perf levers, not architecture) ---
    moe_impl: str = "einsum"                # "einsum" | "gather" | "dropless" (see models/moe.py)
    # fully unroll layer/chunk scans (roofline measurement mode: makes
    # cost_analysis() and the HLO collective census count every
    # iteration instead of one while-loop body)
    scan_unroll: bool = False
    # sequence-parallel residual stream: shard the inter-block
    # activations' sequence dim over the TP axis (Megatron-SP).
    # MEASURED NEGATIVE on this GSPMD version (see EXPERIMENTS.md §Perf
    # iteration log): the per-layer reshard triggers involuntary
    # rematerialization and ~2x temp memory; default OFF.
    seq_parallel: bool = False
    # activation rematerialization at the scanned-block level:
    # "full" recomputes the block in backward (residuals = block inputs
    # only); "dots" saves matmul outputs; "none" saves everything.
    block_remat: str = "full"
    # --- numerics ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        # a configuration file gives the MoE group as a plain dict
        if isinstance(self.moe, dict):
            object.__setattr__(self, "moe", MoEConfig(**self.moe))
        m = self.moe
        if m is not None and self.moe_impl != "dropless" and (
                m.router_width != m.n_experts or m.n_shared):
            raise ValueError("a held expert share and shared experts need "
                             "moe_impl 'dropless'")

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def has_decoder(self) -> bool:
        """Can the model decode token by token? A latent-attention
        model has no latent cache here: training only."""
        return not self.is_mla

    @property
    def padded_vocab_size(self) -> int:
        """Vocab rounded up to a multiple of 256 so the vocab dim always
        divides the TP axis (seamless: 256206 -> 256256). Logits cover
        the padded range; real token ids never index the pad."""
        return -(-self.vocab_size // 256) * 256

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def is_subquadratic(self) -> bool:
        """Does this arch admit an O(1)/O(window) per-token decode state?"""
        if self.family in (SSM, HYBRID):
            return True
        if self.sliding_window is not None:
            return True
        return False

    def n_params(self) -> int:
        """Analytic parameter count (total, incl. all experts)."""
        return _count_params(self)

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        return _count_params(self, active_only=True)


def _count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    if cfg.family == LINREG:
        return cfg.linreg_dim
    if cfg.family == CNN:
        return _cnn_param_count(cfg)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    emb = cfg.vocab_size * d
    out_head = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    total = emb + out_head + d  # final norm

    def attn_params() -> int:
        if cfg.is_mla:
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            r = cfg.kv_lora_rank
            return (d * nq * qk + d * (r + cfg.qk_rope_head_dim) + r
                    + r * nq * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                    + nq * cfg.v_head_dim * d)
        p = d * (nq * hd) + 2 * d * (nkv * hd) + (nq * hd) * d
        if cfg.qkv_bias:
            p += (nq + 2 * nkv) * hd
        return p

    def dense_ffn(dff: int) -> int:
        return 3 * d * dff  # gated (SwiGLU/GeGLU): up, gate, down

    def moe_ffn() -> int:
        m = cfg.moe
        per = 3 * d * (m.d_expert or cfg.d_ff)
        router = d * m.router_width
        if cfg.moe_impl == "dropless":       # the correction bias
            router += m.router_width
        # active: a token's top_k experts, of which a held share holds
        # n_experts / router_width on average
        n_used = (m.top_k * m.n_experts / m.router_width if active_only
                  else m.n_experts)
        return router + int(n_used * per) + m.n_shared * per

    def mamba2_params() -> int:
        s = cfg.ssm
        d_in = s.expand * d
        nh = d_in // s.head_dim
        zxbcdt = d * (2 * d_in + 2 * s.n_groups * s.d_state + nh)
        conv = s.d_conv * (d_in + 2 * s.n_groups * s.d_state)
        out = d_in * d
        extra = 2 * nh + d_in  # A_log, D, norm
        return zxbcdt + conv + out + extra

    if cfg.family in (DENSE, VLM):
        per_layer = attn_params() + dense_ffn(cfg.d_ff) + 2 * d
        total += cfg.n_layers * per_layer
        if cfg.frontend:
            total += cfg.n_frontend_tokens  # stub: positional table only
    elif cfg.family == MOE:
        per_layer = attn_params() + moe_ffn() + 2 * d
        dense_layer = attn_params() + dense_ffn(cfg.d_ff) + 2 * d
        total += ((cfg.n_layers - cfg.first_k_dense) * per_layer
                  + cfg.first_k_dense * dense_layer)
    elif cfg.family == SSM:  # xlstm
        x = cfg.xlstm
        d_in_m = int(x.proj_factor_mlstm * d)
        n_sl = cfg.n_layers // x.slstm_every
        n_ml = cfg.n_layers - n_sl
        # mLSTM block: up(2x), q/k/v proj, gates, out
        mlstm = d * 2 * d_in_m + 3 * d_in_m * d_in_m + 2 * d_in_m + d_in_m * d + 2 * d
        # sLSTM block: 4 gates on (x,h) + ffn
        slstm = 8 * d * d + dense_ffn(int(x.proj_factor_slstm * d)) + 2 * d
        total += n_ml * mlstm + n_sl * slstm
    elif cfg.family == HYBRID:
        per_layer = mamba2_params() + 2 * d
        total += cfg.n_layers * per_layer
        # one shared attention+mlp block (counted once — it is shared)
        total += attn_params() + dense_ffn(cfg.d_ff) + 2 * d
    elif cfg.family == ENCDEC:
        enc_layer = attn_params() + dense_ffn(cfg.d_ff) + 2 * d
        dec_layer = 2 * attn_params() + dense_ffn(cfg.d_ff) + 3 * d
        total += cfg.n_encoder_layers * enc_layer + cfg.n_layers * dec_layer
        total += d  # encoder final norm
    else:
        raise ValueError(f"unknown family {cfg.family}")
    return total


def _cnn_param_count(cfg: ModelConfig) -> int:
    # mirrors models/cnn.py: 6 conv layers + 3 fc
    chans = [3, 64, 64, 128, 128, 256, 256]
    total = 0
    for cin, cout in zip(chans[:-1], chans[1:]):
        total += 3 * 3 * cin * cout + cout
    feat = 256 * (cfg.image_size // 8) * (cfg.image_size // 8)
    for fin, fout in [(feat, 512), (512, 128), (128, cfg.n_classes)]:
        total += fin * fout + fout
    return total


# ---------------------------------------------------------------------------
# Input shapes (assigned set)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# AMB-DG technique knobs (paper Sec. III)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class AmbdgConfig:
    # Timeline (paper): compute epoch T_p, round-trip T_c, staleness tau.
    t_p: float = 2.5
    t_c: float = 10.0
    # Staleness of the cross-pod (DCN) gradient exchange, in steps.
    # tau = ceil(T_c / T_p) in the paper; tau=0 degenerates to sync AMB.
    tau: int = 1
    # Max microbatches a shard may contribute per epoch (scan length);
    # the anytime mask activates b_i(t) <= n_microbatches of them.
    n_microbatches: int = 4
    # "scan_masked": fixed-trip scan, masked samples still cost FLOPs
    #   (deterministic roofline; the SPMD-friendly default).
    # "while_dynamic": lax.while_loop with a per-shard dynamic trip
    #   count from batch["n_active"] — zero wasted FLOPs on stragglers
    #   (devices genuinely run different iteration counts; no
    #   collectives inside the body). Deployment mode on real HW.
    anytime_impl: str = "scan_masked"
    # Dual averaging step-size constants: alpha(t)^-1 = L + sqrt((t+tau)/b_bar)
    smoothness_L: float = 1.0
    b_bar: float = 600.0
    # Proximal psi: "l2" (psi = 0.5||w||^2) or "l2_ball" (projection radius C)
    proximal: str = "l2"
    radius_C: float = 0.0
    # Cross-pod gradient compression: "none" | "int8"
    pod_compression: str = "none"
    # K-batch baseline (Dutta et al.): the master updates on every K-th
    # arriving fixed-size message (used by the "kbatch" strategy and
    # the event-driven simulator).
    kbatch_K: int = 10

    @property
    def staleness(self) -> int:
        import math
        return max(int(math.ceil(self.t_c / self.t_p)), 0)


@dataclass(frozen=True)
class DelayConfig:
    """Stochastic delay process driving a time-varying staleness
    ``tau_t`` (paper analyzes the fixed ``tau = ceil(T_c/T_p)``; real
    networks jitter, burst and heavy-tail — Agarwal & Duchi 2011,
    Attia et al. 2024). Resolved by ``core.delay_process``:

      "fixed"       tau_t = tau every step — the paper, and the exact
                    pre-existing static-phase master path (pinned
                    bit-identical by the regression suites).
      "jitter"      tau_t = clip(tau + U{-jitter..jitter}).
      "heavy_tail"  tau_t = clip(delay_min + floor(Pareto(tail_alpha))).
      "bursty"      2-state Gilbert-Elliott chain: base delay in the
                    normal state, tau_max inside a burst.

    All processes are seeded (``seed``) and emit integer delays in
    ``[delay_min, tau_max]``; the host loop draws one per step and
    ships it to the device step as ``batch["delay"]``. Non-fixed
    processes run the delay-tolerant arena ring (tau_max+1 slots; see
    docs/arena.md) and, with ``adaptive_alpha``, the Agarwal-Duchi
    style delay-adaptive dual-averaging step size (alpha(t)^-1 =
    L + sqrt((t + tau_obs(t)) / b_bar), tau_obs = observed staleness
    of the gradients applied at t)."""
    process: str = "fixed"      # fixed | jitter | heavy_tail | bursty
    # Hard staleness cap (ring depth = tau_max + 1). 0 resolves to
    # ambdg.tau for "fixed"; stochastic processes must set it.
    tau_max: int = 0
    delay_min: int = 1          # floor for stochastic draws
    jitter: int = 1             # "jitter": +- range around ambdg.tau
    tail_alpha: float = 1.1     # "heavy_tail": Pareto shape (smaller = fatter)
    p_burst: float = 0.1        # "bursty": P(normal -> burst) per step
    p_exit: float = 0.3         # "bursty": P(burst -> normal) per step
    seed: int = 0
    # Scale the dual-averaging step by the OBSERVED staleness of each
    # update (Agarwal-Duchi) instead of the static worst case.
    adaptive_alpha: bool = True


@dataclass(frozen=True)
class ElasticConfig:
    """Elastic-worker process driving a time-varying active set and
    per-worker speed skew (the straggler premise of the AMB line —
    Ferdinand et al. — promoted to a simulable, seeded, checkpointable
    scenario). Resolved by ``core.worker_process``:

      "static"         every worker alive at speed 1.0 every epoch —
                       the degenerate process: the host loop and every
                       strategy route it to the exact pre-existing
                       no-churn path (pinned bit-identical by the
                       regression suites).
      "heterogeneous"  persistent per-worker speed skew: speeds drawn
                       ONCE from lognormal(-speed_sigma^2/2,
                       speed_sigma) (mean 1.0), floored at speed_min;
                       everyone stays alive.
      "churn"          per-worker Gilbert-Elliott up/down chain:
                       up -> down with ``p_fail`` per epoch, down ->
                       up with ``p_recover`` (geometric dwell times —
                       the join/leave membership model).
      "crash_restart"  exponential MTTF/MTTR in epoch units: each
                       worker alternates Exp(mttf)-long lives and
                       Exp(mttr)-long outages (fail-stop + restart).

    All processes are seeded (``seed``) and emit one per-epoch
    ``(active_mask, speeds)`` pair; the host loop folds the pair into
    ``batch["weights"]`` (a dead worker contributes b_i = 0 and the
    eq. (5) normalization stays exact — paper Sec. IV-C), and
    ``api.simulate`` wires the same seeded sequence into both cluster-
    simulator engines. ``state_dict``/``load_state_dict`` keep the
    restart-exactness contract of the data pipeline and the delay
    processes."""
    process: str = "static"   # static | heterogeneous | churn | crash_restart
    speed_sigma: float = 0.5    # "heterogeneous": lognormal shape
    speed_min: float = 0.05     # "heterogeneous": floor on drawn speeds
    p_fail: float = 0.05        # "churn": P(up -> down) per epoch
    p_recover: float = 0.5      # "churn": P(down -> up) per epoch
    mttf: float = 50.0          # "crash_restart": mean epochs between failures
    mttr: float = 5.0           # "crash_restart": mean epochs to restart
    seed: int = 0


@dataclass(frozen=True)
class BatchScheduleConfig:
    """Adaptive minibatch schedule b(t): a seeded batch-size controller
    that replaces the static anytime target (and the static ``b_bar``
    inside the dual-averaging step size) with a per-step schedule.
    Resolved by ``core.batch_schedule``:

      "fixed"       b(t) = b0 every step — the degenerate schedule: the
                    host loop, both simulator engines and every strategy
                    route it to the exact pre-existing timing-driven
                    path (pinned bit-identical by the regression
                    suites).
      "linear"      b(t) = b0 + floor(growth_rate * (t-1)) — a
                    deterministic warmup ramp.
      "adadamp"     grow b(t) to damp gradient noise as the loss drops
                    (AdaDamp principle): b(t) = b0 * loss(1)/loss(t),
                    monotone non-decreasing, per-step growth capped at
                    ``growth_factor``x; the loss signal is an EMA
                    (weight ``ema``) of the feedback fed through
                    ``BatchSchedule.observe(loss=...)``.
      "delay_aware" scale b(t) by the observed staleness of applied
                    gradients (Attia-Gaash-Koren: larger accumulated
                    minibatches amortize larger delays):
                    b(t) = b0 * (1 + ema_tau(t)) / (1 + tau_ref), fed
                    through ``observe(tau_obs=...)`` and composing with
                    the Agarwal-Duchi ``rc.delay.adaptive_alpha``.

    All schedules are seeded (``seed``), emit integer targets in
    ``[b_min, b_cap]``, and checkpoint/restore exactly
    (``state_dict``/``load_state_dict``, matching the delay/worker
    processes). The drawn b(t) is injected as the anytime target (the
    per-worker shares of b(t) cap the timing-driven draw) and shipped
    to the device step as ``batch["b_sched"]``, where it replaces
    ``b_bar`` inside alpha(t)^-1 = L + sqrt((t + tau) / b(t))."""
    schedule: str = "fixed"   # fixed | linear | adadamp | delay_aware
    # Base target b(1); 0 resolves to round(ambdg.b_bar).
    b0: int = 0
    b_min: int = 1            # floor on emitted targets
    # Cap on emitted targets; 0 resolves to 16 * b0.
    b_cap: int = 0
    growth_rate: float = 1.0    # "linear": +samples per step
    growth_factor: float = 2.0  # "adadamp": max per-step growth multiplier
    ema: float = 0.3            # feedback EMA weight in (0, 1]
    seed: int = 0


@dataclass(frozen=True)
class ServeConfig:
    """Train-while-serve: the continuous-batching inference engine fed
    by staleness-bounded async weight publication (the Agarwal-Duchi
    delayed-consumer argument applied to *serving*: an inference server
    reading asynchronously published master snapshots is exactly a
    consumer of delayed parameters, so bounded staleness preserves the
    quality guarantees the training analysis already needs).

    Resolved by ``repro.serve``:

      * ``slots``/``max_len``/``max_new`` size the engine: ``slots``
        concurrent sequences share one fixed-slot KV/recurrent cache of
        depth ``max_len``; finished sequences are evicted and new
        requests admitted every decode step (continuous batching, per-
        slot positions — see ``serve/engine.py``).
      * ``arrival`` names the seeded request arrival process
        (``serve/request_queue.py``, mirroring ``core/delay_process``):
        "poisson" draws Poisson(``arrival_rate``) new requests per
        decode step; "bursty" is a 2-state Gilbert-Elliott chain
        emitting Poisson(``arrival_rate``) in the normal state and
        Poisson(``burst_rate``) inside a burst. Synthesized prompts
        have seeded lengths in [prompt_len_min, prompt_len_max].
      * ``publish_period``/``staleness_bound`` drive the weight-
        publication channel (``serve/publisher.py``): every
        ``publish_period`` master steps the train loop pushes a
        ``w = -alpha z`` snapshot into a bounded-staleness publish ring
        (arena (rows, 128) layout, int8 + bf16-scales wire format —
        the gossip path's quantizer, bit-identical); servers pop the
        freshest snapshot whose age is <= ``staleness_bound`` master
        steps. ``publish_period = 0`` (the default) disables the
        channel entirely — the pre-existing paths are untouched.
    """
    slots: int = 4
    max_len: int = 128
    max_new: int = 16
    # master steps between published snapshots; 0 = channel disabled
    publish_period: int = 0
    # max age (master steps) of a servable snapshot; ring depth =
    # staleness_bound // publish_period + 1 slots
    staleness_bound: int = 4
    arrival: str = "poisson"    # poisson | bursty
    arrival_rate: float = 0.5   # mean new requests per decode step
    burst_rate: float = 4.0     # "bursty": rate inside a burst
    p_burst: float = 0.1        # "bursty": P(normal -> burst) per step
    p_exit: float = 0.3         # "bursty": P(burst -> normal) per step
    prompt_len_min: int = 4
    prompt_len_max: int = 12
    seed: int = 0


@dataclass(frozen=True)
class ConsensusConfig:
    """Decentralized AMB-DG (paper Sec. V): gossip-consensus knobs.

    Workers exchange messages through a doubly-stochastic matrix Q for
    ``rounds`` gossip rounds per epoch; ``rounds=0`` derives the count
    from the paper's eq. (24) lower bound with (delta, msg_norm_J) and
    lambda_2(Q) of the configured topology.
    """
    topology: str = "ring"        # "ring" | "torus" | "complete"
    n_workers: int = 8
    delta: float = 0.05           # consensus-error target of eq. (24)
    msg_norm_J: float = 1.0       # message-norm bound J in eq. (24)
    rounds: int = 0               # 0 = derive from eq. (24)
    # "auto" runs the gossip under shard_map (one mesh index = one
    # worker, lax.ppermute neighbour exchange) exactly when the local
    # device count equals n_workers — the deployment shape where the
    # strategy's private ('worker',) mesh owns the same devices any
    # surrounding jit lowers for — and on the dense per-round fold
    # (one program, bit-identical arithmetic) otherwise;
    # "dense"/"shard_map" force one path.
    gossip_impl: str = "auto"
    # Gossip message compression: "none" exchanges full f32 messages;
    # "int8" quantizes each outgoing message per round to int8 with
    # per-row scales (the delay-ring scheme) and carries the
    # quantization error in a per-worker error-feedback residual
    # (DecentralizedState.residual), so the compression error
    # telescopes across rounds instead of accumulating. ~3.9x less
    # wire payload per round; dense and shard_map executions stay
    # bit-identical on the same (messages, residual).
    compression: str = "none"
    # Debug/validation: also return the pre-gossip messages m^(0) in
    # the step metrics ("gossip_m0"), so a harness can re-apply the
    # dense gossip-matrix fold oracle to the EXACT in-program messages
    # and bit-compare with the step's consensus output. Keep False in
    # training loops (metrics are assumed scalar there).
    debug_messages: bool = False


@dataclass(frozen=True)
class MeshConfig:
    n_pods: int = 1
    data: int = 16
    model: int = 16

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.n_pods > 1 else ("data", "model")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.n_pods, self.data, self.model) if self.n_pods > 1 else (self.data, self.model)

    @property
    def n_devices(self) -> int:
        return self.n_pods * self.data * self.model


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = field(default_factory=MeshConfig)
    ambdg: AmbdgConfig = field(default_factory=AmbdgConfig)
    # Algorithm variant, resolved through the Strategy registry by
    # ``repro.api.build``: "ambdg" (the paper), "amb" (synchronous
    # baseline), "kbatch" (fixed-minibatch baseline), "decentralized"
    # (Sec.-V gossip consensus). See docs/strategies.md.
    strategy: str = "ambdg"
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    # Staleness process of the cross-pod exchange: the default "fixed"
    # keeps the paper's constant tau (and the exact pre-existing master
    # path); stochastic processes drive the delay-tolerant ring. See
    # DelayConfig / core/delay_process.py / docs/arena.md.
    delay: DelayConfig = field(default_factory=DelayConfig)
    # Elastic-worker process: the default "static" keeps every worker
    # alive at speed 1.0 (and the exact pre-existing no-churn path);
    # stochastic processes drive a seeded per-epoch (active_mask,
    # speeds) sequence through the host loop and both simulator
    # engines. See ElasticConfig / core/worker_process.py /
    # docs/strategies.md.
    elastic: ElasticConfig = field(default_factory=ElasticConfig)
    # Train-while-serve: continuous-batching engine + bounded-staleness
    # weight publication. The default (publish_period=0) keeps the
    # publish channel off and the train loop byte-identical to the
    # serve-less path. See ServeConfig / repro.serve / docs/serve.md.
    serve: ServeConfig = field(default_factory=ServeConfig)
    # Adaptive minibatch schedule b(t): the default "fixed" keeps the
    # timing-driven anytime target (and the static b_bar inside alpha)
    # and the exact pre-existing step/sim paths; adaptive schedules
    # drive a seeded batch-size controller through the host loop, both
    # simulator engines and the dual-averaging step size. See
    # BatchScheduleConfig / core/batch_schedule.py / docs/strategies.md.
    batch_schedule: BatchScheduleConfig = field(default_factory=BatchScheduleConfig)
    optimizer: str = "dual_averaging"   # paper-faithful default
    remat: str = "none"                 # "none" | "full" | "dots"
    # Master-pipeline implementation: "arena" runs the delay ring +
    # dual update on the persistent flat gradient arena (fused Pallas
    # kernels on TPU; see core/arena.py + docs/arena.md); "pytree" is
    # the per-leaf reference path kept for ablations/verification.
    master_impl: str = "arena"
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)
