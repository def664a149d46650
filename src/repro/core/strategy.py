"""The Strategy protocol: ONE surface for every algorithm variant.

The paper presents four schemes — AMB-DG (Sec. III), the synchronous
AMB baseline, the fixed-minibatch K-batch baseline (Dutta et al.) and
the fully-decentralized gossip extension (Sec. V). Each used to live
behind its own incompatible entry point; they are now classes
implementing one contract, registered by name and constructed through
``repro.api.build(model, rc)`` from ``rc.strategy``:

    strategy = repro.api.build(model, rc)
    state    = strategy.init_state(rng)
    state, metrics = strategy.train_step(state, batch)   # jit/donate-safe
    strategy.staleness_schedule()   # how stale applied gradients are
    Strategy.timeline_model()       # wall-clock algebra for sim/benchmarks

All master-ful strategies share the persistent-arena master pipeline
(``core.ambdg.build_step_fns``); ``DecentralizedStrategy`` is the
on-device promotion of the Sec.-V scheme — per-worker dual variables
held in arena layout, r gossip rounds as ``lax.ppermute`` under
``shard_map`` (bit-identical to the dense gossip-matrix fold oracle;
see ``core.consensus``), with r derived from the paper's eq. (24).

Adding a scenario = one new subclass + ``@register``. See
docs/strategies.md.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.core import ambdg, anytime, consensus
from repro.core import arena as arena_mod
from repro.core import dual_averaging as da
from repro.models.api import Model


class StalenessSchedule(NamedTuple):
    """How stale the gradients applied by each master update are."""
    kind: str          # "delayed" | "sync" | "random" | "gossip"
    tau: int           # deterministic delay in epochs (0 = fresh)
    description: str


class TimelineModel(NamedTuple):
    """Wall-clock algebra of a scheme (paper Sec. III / Fig. 1), used
    by the cluster simulator and the benchmarks. The closed-form
    fields are the EXACT float expressions the golden traces pin —
    refactors must keep them literally.

    ``event_driven`` schemes (k-batch) have no closed form: update
    times come out of the simulator's arrival heap.
    """
    scheme: str
    event_driven: bool
    epoch_duration: Optional[Callable[[float, float], float]] = None
    # (t, t_p, t_c) -> wall time of the master's t-th update
    update_time: Optional[Callable[[int, float, float], float]] = None
    # (total_time, t_p, t_c) -> number of updates fitting the budget
    n_updates: Optional[Callable[[float, float, float], int]] = None


class Strategy:
    """Base class: subclasses assign ``init_state`` / ``train_step``
    as plain closures in ``__init__`` (so ``jax.jit(s.train_step,
    donate_argnums=(0,))`` behaves exactly like the pre-Strategy
    factory functions) and implement the two schedule probes."""

    name: str = "?"
    # one-line schedule summary for registry tables (benchmarks/report)
    schedule_summary: str = "?"
    # which simulator engine runs this scheme, if any: "anytime"
    # (epoch-timeline master), "kbatch" (event-driven arrival heap) or
    # None (on-device only) — dispatched by ``repro.api.simulate``
    sim_engine: Optional[str] = None
    # does this strategy's device step consume the per-epoch elastic
    # active mask as batch["active"]? Master-ful strategies don't (the
    # mask rides the anytime weights — a dead worker's samples carry
    # weight 0 and eq. (5) stays exact); the decentralized gossip does
    # (its stencil renormalizes around dead neighbours). The host loop
    # ships the mask exactly when this is True.
    consumes_active_mask: bool = False

    init_state: Callable[[jax.Array], Any]
    train_step: Callable[[Any, Any], Tuple[Any, Dict]]

    def __init__(self, model: Model, rc: RunConfig):
        from repro.core.batch_schedule import resolve_targets
        from repro.core.worker_process import validate_elastic
        validate_elastic(rc.elastic)   # every strategy reads rc.elastic
        # every strategy reads rc.batch_schedule (raise at build time,
        # not at the first drawn target)
        resolve_targets(rc.batch_schedule, rc.ambdg.b_bar)
        self.model = model
        self.rc = rc

    def staleness_schedule(self) -> StalenessSchedule:
        raise NotImplementedError

    def delay_process(self):
        """The seeded ``core.delay_process`` instance this strategy's
        ``rc.delay`` configures, or None under the fixed process. This
        is what makes the knob live outside the device step:
        ``api.simulate(strategy_instance, ...)`` feeds it to the
        simulator engine (per-epoch staleness for anytime schemes,
        per-message uplink jitter for k-batch)."""
        if self.rc.delay.process == "fixed":
            return None
        from repro.core.delay_process import make_delay_process
        return make_delay_process(self.rc.delay, self.rc.ambdg.tau)

    def worker_process(self, n_workers: int):
        """The seeded ``core.worker_process`` instance this strategy's
        ``rc.elastic`` configures for an ``n_workers``-strong fleet,
        or None under the static process. The elastic twin of
        ``delay_process``: ``api.simulate(strategy_instance, ...)``
        feeds it to the simulator engine (per-epoch active/speed draws
        for anytime schemes, epoch-indexed churn on the k-batch
        arrival heap)."""
        if self.rc.elastic.process == "static":
            return None
        from repro.core.worker_process import make_worker_process
        return make_worker_process(self.rc.elastic, n_workers)

    def batch_schedule(self):
        """The seeded ``core.batch_schedule`` controller this
        strategy's ``rc.batch_schedule`` configures, or None under the
        fixed schedule. The minibatch twin of ``delay_process``: the
        host loop draws one target per step (shipping it to the device
        step as ``batch["b_sched"]``), and
        ``api.simulate(strategy_instance, ...)`` feeds the same seeded
        sequence to the simulator engine (per-epoch anytime targets,
        per-job sizes for k-batch)."""
        if self.rc.batch_schedule.schedule == "fixed":
            return None
        from repro.core.batch_schedule import make_batch_schedule
        return make_batch_schedule(self.rc.batch_schedule,
                                   self.rc.ambdg.b_bar,
                                   self.rc.ambdg.tau)

    @classmethod
    def timeline_model(cls) -> TimelineModel:
        raise NotImplementedError


_REGISTRY: Dict[str, Type[Strategy]] = {}


def register(cls: Type[Strategy]) -> Type[Strategy]:
    """Class decorator: make ``cls`` constructible by name through
    ``repro.api.build`` / ``get_strategy``."""
    if cls.name in _REGISTRY:
        raise ValueError(f"strategy {cls.name!r} already registered")
    _REGISTRY[cls.name] = cls
    return cls


def get_strategy(name: str) -> Type[Strategy]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown strategy {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _require_fixed_delay(rc: RunConfig, name: str, why: str):
    """Strategies without a master delay ring reject stochastic
    ``rc.delay`` processes up front (a silently-ignored knob is worse
    than an error). The knob is still *read* by every strategy:
    ``staleness_schedule`` reports it, and the kbatch SIMULATOR
    consumes it for per-message network delays."""
    if rc.delay.process != "fixed":
        raise ValueError(
            f"strategy {name!r} does not support the stochastic delay "
            f"process {rc.delay.process!r}: {why}")


# ---------------------------------------------------------------------------
# AMB-DG (the paper) and its synchronous AMB degenerate
# ---------------------------------------------------------------------------
@register
class AmbdgStrategy(Strategy):
    """Anytime minibatch with delayed gradients: anytime accumulation
    -> tau-deep delay ring -> dual averaging, on the persistent arena
    master pipeline (or the pytree reference path)."""

    name = "ambdg"
    schedule_summary = "deterministic tau"
    sim_engine = "anytime"

    def __init__(self, model: Model, rc: RunConfig):
        super().__init__(model, rc)
        self.init_state, self.train_step = ambdg.build_step_fns(model, rc)

    def staleness_schedule(self) -> StalenessSchedule:
        from repro.core.delay_process import resolve_bounds
        dc = self.rc.delay
        if dc.process != "fixed":
            lo, hi = resolve_bounds(dc, self.rc.ambdg.tau)
            adaptive = ("delay-adaptive alpha" if dc.adaptive_alpha
                        else "worst-case alpha")
            return StalenessSchedule(
                "random", hi,
                f"stochastic tau_t in [{lo}, {hi}] from the seeded "
                f"{dc.process!r} delay process (delay-tolerant ring, "
                f"{adaptive})")
        tau = self.rc.ambdg.tau
        return StalenessSchedule(
            "delayed" if tau else "sync", tau,
            "deterministic tau = ceil(T_c / T_p) after pipeline fill")

    @classmethod
    def timeline_model(cls) -> TimelineModel:
        # workers never idle: epochs tile at T_p; the t-th update lands
        # half a round trip after epoch t ends (paper Fig. 1)
        return TimelineModel(
            scheme=cls.name, event_driven=False,
            epoch_duration=lambda t_p, t_c: t_p,
            update_time=lambda t, t_p, t_c: t * t_p + 0.5 * t_c,
            n_updates=lambda total, t_p, t_c:
                max(int((total - 0.5 * t_c) // t_p), 0))


@register
class AmbStrategy(Strategy):
    """Synchronous AMB (Ferdinand et al.): the AMB-DG step with tau=0
    on device; the wall-clock penalty (workers idle through the round
    trip) lives entirely in the timeline model."""

    name = "amb"
    schedule_summary = "none (sync)"
    sim_engine = "anytime"

    def __init__(self, model: Model, rc: RunConfig):
        _require_fixed_delay(rc, self.name,
                             "the synchronous baseline blocks on every "
                             "round trip — a stochastic tau_t belongs "
                             "to 'ambdg'")
        rc = rc.replace(ambdg=dataclasses.replace(rc.ambdg, tau=0))
        super().__init__(model, rc)
        self.init_state, self.train_step = ambdg.build_step_fns(model, rc)

    def staleness_schedule(self) -> StalenessSchedule:
        return StalenessSchedule("sync", 0, "fresh gradients every epoch")

    @classmethod
    def timeline_model(cls) -> TimelineModel:
        return TimelineModel(
            scheme=cls.name, event_driven=False,
            epoch_duration=lambda t_p, t_c: t_p + t_c,
            update_time=lambda t, t_p, t_c: t * t_p + (t - 0.5) * t_c,
            n_updates=lambda total, t_p, t_c:
                max(int((total - t_p - 0.5 * t_c) // (t_p + t_c)) + 1, 0))


# ---------------------------------------------------------------------------
# K-batch async (Dutta et al., AISTATS'18)
# ---------------------------------------------------------------------------
class KBatchState(NamedTuple):
    """The synchronous on-device realization's state: the shared
    master-pipeline state plus the parameter-version counter
    ``ref_epoch`` threaded through so staleness bookkeeping (and the
    simulator's Fig.-4 histogram) is derived from *state*, never from
    event-arrival order."""
    base: ambdg.TrainState
    ref_epoch: jax.Array    # i32: version the NEXT gradients refer to


@register
class KBatchStrategy(Strategy):
    """Fixed-per-message minibatch. The interesting behaviour — K
    arrivals per update, random staleness — is event-driven and lives
    in the simulator (``core.kbatch.KBatchMaster``, constructed by
    ``sim.simulate_kbatch`` with K defaulting to
    ``AmbdgConfig.kbatch_K``); the on-device SPMD realization is its
    synchronous degenerate (every worker's message arrives together,
    so staleness is 0 and the step is the tau=0 master pipeline on
    fixed-size minibatches)."""

    name = "kbatch"
    schedule_summary = "random (per message)"
    sim_engine = "kbatch"

    def __init__(self, model: Model, rc: RunConfig):
        from repro.core.delay_process import resolve_bounds
        # validated here, CONSUMED by the event-driven simulator: a
        # stochastic rc.delay jitters the per-message uplink times
        # (sim.simulate_kbatch's delay_process); the on-device SPMD
        # realization stays the synchronous degenerate either way
        resolve_bounds(rc.delay, rc.ambdg.tau)
        delay_cfg = rc.delay
        self._nominal_tau = rc.ambdg.tau
        rc = rc.replace(ambdg=dataclasses.replace(rc.ambdg, tau=0),
                        delay=dataclasses.replace(delay_cfg,
                                                  process="fixed",
                                                  tau_max=0))
        self.delay_cfg = delay_cfg
        super().__init__(model, rc)
        init_base, step_base = ambdg.build_step_fns(model, rc)

        def init_state(key) -> KBatchState:
            return KBatchState(base=init_base(key),
                               ref_epoch=jnp.ones((), jnp.int32))

        def train_step(state: KBatchState, batch):
            base, metrics = step_base(state.base, batch)
            metrics["staleness"] = metrics["step"] - state.ref_epoch
            return KBatchState(base=base,
                               ref_epoch=state.ref_epoch + 1), metrics

        self.init_state = init_state
        self.train_step = train_step

    def delay_process(self):
        # the on-device step stripped rc.delay to fixed; the simulator
        # hook reconstructs the configured process from the original
        if self.delay_cfg.process == "fixed":
            return None
        from repro.core.delay_process import make_delay_process
        return make_delay_process(self.delay_cfg, self._nominal_tau)

    def batch_schedule(self):
        # the on-device step runs the tau=0 synchronous degenerate,
        # but the delay-aware schedule still references the ORIGINAL
        # nominal staleness (the event-driven simulator's regime)
        if self.rc.batch_schedule.schedule == "fixed":
            return None
        from repro.core.batch_schedule import make_batch_schedule
        return make_batch_schedule(self.rc.batch_schedule,
                                   self.rc.ambdg.b_bar,
                                   self._nominal_tau)

    def staleness_schedule(self) -> StalenessSchedule:
        extra = ""
        if self.delay_cfg.process != "fixed":
            extra = (f"; uplink times jittered by the seeded "
                     f"{self.delay_cfg.process!r} delay process in the "
                     f"event-driven simulator")
        return StalenessSchedule(
            "random", 0,
            "random per-message staleness (update t applies messages "
            "with ref_epoch <= t; distribution from the arrival heap)"
            + extra)

    @classmethod
    def timeline_model(cls) -> TimelineModel:
        return TimelineModel(scheme=cls.name, event_driven=True)


# ---------------------------------------------------------------------------
# Decentralized AMB-DG (paper Sec. V): gossip consensus, no master
# ---------------------------------------------------------------------------
class DecentralizedState(NamedTuple):
    params: Any        # per-worker stacked pytree: leaves (n, *shape) f32
    z: jax.Array       # (n, rows, 128) f32 — per-worker duals, arena layout
    # (n, rows, 128) f32 — per-worker error-feedback residual of the
    # int8-compressed gossip (arena layout, donated alongside z; stays
    # zero under compression="none")
    residual: jax.Array
    t: jax.Array       # i32: dual-averaging epoch counter
    step: jax.Array    # i32: steps taken (mirrors TrainState.step)


@register
class DecentralizedStrategy(Strategy):
    """No master: each of ``rc.consensus.n_workers`` workers holds its
    own dual variable z_i in arena layout ((n, rows, 128), built once
    from the model's abstract shapes) and its own parameters w_i. Per
    epoch every worker computes an anytime gradient at w_i, forms the
    message m_i = n (b_i z_i + g_i) / b(t), and the messages run r
    gossip rounds through the topology's doubly-stochastic stencil;
    the consensus result is the new z_i and w_i = prox(z_i) applies
    per worker. r comes from the paper's eq. (24) bound computed from
    ``rc.consensus`` (or its explicit ``rounds`` override).

    Two gossip executions (``rc.consensus.gossip_impl``):

      "shard_map"  one mesh index per worker on a 1-D ('worker',)
                   device mesh; each round's neighbour exchange is a
                   ``lax.ppermute`` (specs from
                   ``dist.sharding.gossip_specs``) — the on-device
                   deployment path;
      "dense"      the same ordered stencil fold on the stacked (n,
                   rows, 128) array in one program — the gossip-matrix
                   power oracle, and the fallback when n_workers
                   doesn't map onto the local device count ("auto"
                   picks per availability).

    The two are bit-identical ON THE SAME MESSAGES (same fold, same
    barriers; validated every step by the conformance suite via
    ``ConsensusConfig.debug_messages``). Whole-run agreement across
    the two program variants is at tolerance only: GSPMD partitions
    the surrounding per-worker gradient matmuls differently in the
    multi-device program, which reorders their reductions.

    ``rc.consensus.compression="int8"`` quantizes each round's
    outgoing message to int8 with per-row scales (the delay-ring
    scheme) and carries the quantization error in the per-worker
    ``DecentralizedState.residual`` (arena layout, donated), so the
    compression error telescopes across rounds and train steps; the
    wire payload per round drops ~3.9x and the dense/shard_map
    bit-identity holds per compression mode (compressed sharded vs
    the compressed dense oracle). See docs/strategies.md.
    """

    name = "decentralized"
    schedule_summary = "none (gossip consensus)"
    sim_engine = None      # on-device only (api.build + the example)

    def __init__(self, model: Model, rc: RunConfig):
        _require_fixed_delay(rc, self.name,
                             "gossip consensus exchanges fresh local "
                             "duals every epoch (no master delay ring "
                             "to jitter)")
        super().__init__(model, rc)
        cc = rc.consensus
        n = cc.n_workers
        # elastic worker set: the host ships the per-epoch active mask
        # as batch["active"]; the gossip stencil renormalizes around
        # dead neighbours and dead workers' state freezes
        self._elastic = rc.elastic.process != "static"
        self.consumes_active_mask = self._elastic
        if self._elastic and cc.compression == "int8":
            raise ValueError(
                "decentralized elastic churn does not compose with "
                "int8 gossip compression: a dead worker cannot "
                "quantize its message or carry error feedback for "
                "rounds it never ran (the telescoping identity would "
                "break); use compression='none' with a non-static "
                "rc.elastic")
        self.Q = consensus.gossip_matrix(cc.topology, n)
        self.lam2 = consensus.lambda2(self.Q)
        self.rounds = cc.rounds if cc.rounds > 0 else consensus.min_rounds(
            cc.delta, n, cc.msg_norm_J, self.lam2)
        params_shapes = jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.PRNGKey(0))
        self.layout = arena_mod.make_layout(params_shapes)
        self.gossip_impl = self._resolve_gossip_impl(cc)
        self._mesh = None
        if self.gossip_impl == "shard_map":
            from repro.launch.mesh import make_mesh
            self._mesh = make_mesh((n,), ("worker",))
        self.init_state, self.train_step = self._build()

    @staticmethod
    def _resolve_gossip_impl(cc) -> str:
        if cc.gossip_impl != "auto":
            return cc.gossip_impl
        # only the literal deployment shape — one local device per
        # worker — auto-selects the shard_map path: its private 1-D
        # worker mesh must own the same device set a surrounding jit
        # lowers for (a pod-mesh dryrun over MORE devices would
        # conflict), and device_count == n_workers is the one case
        # where that holds by construction
        return ("shard_map" if jax.device_count() == cc.n_workers
                else "dense")

    def _gossip_fn(self):
        """The consensus exchange as one closure (m0, residual) ->
        (z_new, residual_new): four variants over
        {dense, shard_map} x {none, int8}. Under "none" the residual
        is donated straight through (aliased, no copy); under "int8"
        each round quantizes/dequantizes through the shared
        error-feedback body in ``core.consensus``, so the dense and
        shard_map executions stay bit-identical on the same inputs."""
        cc = self.rc.consensus
        topology, rounds = cc.topology, self.rounds
        compression = cc.compression
        elastic = self._elastic
        if compression not in consensus.COMPRESSION_MODES:
            raise ValueError(f"unknown gossip compression "
                             f"{compression!r}")
        if self.gossip_impl == "dense":
            if compression == "int8":
                return lambda m0, res: consensus.run_consensus_fold_int8(
                    m0, res, topology, rounds)
            if elastic:
                # the masked fold: dead neighbours contribute identity
                # weight, the stencil renormalizes per receiver
                return lambda m0, res, active: (
                    consensus.run_consensus_fold_masked(
                        m0, topology, rounds, active), res)
            return lambda m0, res: (consensus.run_consensus_fold(
                m0, topology, rounds), res)
        if self.gossip_impl != "shard_map":
            raise ValueError(f"unknown gossip_impl "
                             f"{self.gossip_impl!r}")
        from jax.sharding import PartitionSpec

        from repro.dist.sharding import gossip_specs
        msg_spec = gossip_specs().msg

        n = self.rc.consensus.n_workers

        if elastic:
            # the (n,) active mask is replicated to every worker
            # (spec P()): each shard resolves its own per-term source
            # activity from the full mask + its axis index
            def local_masked(x, res, active):
                return consensus.gossip_rounds_shard_masked(
                    x, "worker", topology, n, rounds, active), res

            return jax.shard_map(local_masked, mesh=self._mesh,
                                 in_specs=(msg_spec, msg_spec,
                                           PartitionSpec()),
                                 out_specs=(msg_spec, msg_spec),
                                 check_vma=False)

        def local(x, res):   # x, res: (1, rows, 128) — this worker's
            if compression == "int8":
                return consensus.gossip_rounds_shard_int8(
                    x, res, "worker", topology, n, rounds)
            return consensus.gossip_rounds_shard(
                x, "worker", topology, n, rounds), res

        return jax.shard_map(local, mesh=self._mesh,
                             in_specs=(msg_spec, msg_spec),
                             out_specs=(msg_spec, msg_spec), check_vma=False)

    def _build(self):
        model, rc = self.model, self.rc
        cfg = rc.ambdg
        n = rc.consensus.n_workers
        n_mb = cfg.n_microbatches
        layout = self.layout
        loss_fn = ambdg._loss_with_remat(model, rc)
        gossip = self._gossip_fn()

        def init_state(key) -> DecentralizedState:
            params0, _ = model.init(key)
            # every worker starts at the same point, f32 (dual
            # averaging overwrites w with -alpha z from step 1 on, so
            # params stay f32 exactly like the arena master path)
            stacked = jax.tree.map(
                lambda p: jnp.tile(p.astype(jnp.float32)[None],
                                   (n,) + (1,) * p.ndim), params0)
            return DecentralizedState(
                params=stacked,
                z=jnp.zeros((n, layout.rows, arena_mod.LANES),
                            jnp.float32),
                residual=jnp.zeros((n, layout.rows, arena_mod.LANES),
                                   jnp.float32),
                t=jnp.zeros((), jnp.int32),
                step=jnp.zeros((), jnp.int32))

        def per_worker_grads(params, batch):
            def one_worker(p, chunk):
                n_active = chunk.get("n_active", jnp.int32(n_mb))
                chunk = {k: v for k, v in chunk.items()
                         if k != "n_active"}
                if cfg.anytime_impl == "while_dynamic":
                    return anytime.accumulate_while(
                        loss_fn, p, chunk, n_mb, n_active)
                return anytime.accumulate_scan(loss_fn, p, chunk, n_mb)

            chunked = jax.tree.map(
                lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]),
                batch)
            g, c, m = jax.vmap(one_worker, in_axes=(0, 0))(params, chunked)
            return g, c, m["loss_sum"]

        elastic = self._elastic
        variable_batch = rc.batch_schedule.schedule != "fixed"

        def messages(state, batch, scale):
            """(m0, per-worker counts, loss sums, flat grads): the
            pre-gossip consensus inputs. The oracle harness reads m0
            through the ``debug_messages`` metrics capture below, so
            what it validates is exactly what this program gossiped.
            ``scale`` is the effective fleet size the Sec.-V messages
            scale by: the static ``n``, or the traced alive count
            under churn (so the alive consensus still targets
            z-bar + sum(g)/b(t) over the workers that exist)."""
            g, b, loss = per_worker_grads(state.params, batch)
            g_flat = arena_mod.flatten_tree(layout, g, leading=1)
            denom = jnp.maximum(jnp.sum(b), 1e-12)
            # m_i^(0) = n * b_i * (z_i + g_i / b_i) / b(t)
            #         = n * (b_i z_i + g_i) / b(t)  (paper Sec. V)
            m0 = (scale * (state.z * b[:, None, None] + g_flat)) / denom
            return m0, b, loss, g_flat

        def train_step(state: DecentralizedState, batch):
            if elastic:
                if "active" not in batch:
                    raise ValueError(
                        "decentralized elastic step needs the per-"
                        "epoch active mask as batch['active'] (the "
                        "host loop / harness ships the (n_workers,) "
                        "0/1 vector the worker process drew)")
                active = jnp.asarray(batch["active"],
                                     jnp.float32).reshape(n)
                batch = {k: v for k, v in batch.items()
                         if k != "active"}
                scale = jnp.sum(active)
            else:
                active, scale = None, n
            b_sched = None
            if variable_batch:
                if "b_sched" not in batch:
                    raise ValueError(
                        f"rc.batch_schedule.schedule="
                        f"{rc.batch_schedule.schedule!r} needs a per-"
                        "step batch['b_sched'] scalar (the host loop "
                        "draws it from core.batch_schedule)")
                b_sched = jnp.asarray(batch["b_sched"], jnp.float32)
                batch = {k: v for k, v in batch.items()
                         if k != "b_sched"}
            m0, b, loss, g_flat = messages(state, batch, scale)
            total_b = jnp.sum(b)
            denom = jnp.maximum(total_b, 1e-12)
            if elastic:
                z_g, res_new = gossip(m0, state.residual, active)
                # dead workers are frozen spectators: their dual (and
                # params, below) carry over bit-identically until the
                # process brings them back
                z_new = jnp.where(active[:, None, None] > 0, z_g,
                                  state.z)
            else:
                z_new, res_new = gossip(m0, state.residual)
            t_next = state.t + 1
            a = da.alpha(t_next.astype(jnp.float32) + 1.0, cfg,
                         b=b_sched)
            w = -a * z_new
            if cfg.proximal == "l2_ball":
                # per-worker projection (each worker owns its prox)
                norms = jnp.sqrt(jnp.sum(jnp.square(w), axis=(1, 2)))
                proj = jnp.minimum(
                    1.0, cfg.radius_C / jnp.maximum(norms, 1e-12))
                w = w * proj[:, None, None]
            params = arena_mod.unflatten_tree(layout, w, cast=False)
            if elastic:
                params = jax.tree.map(
                    lambda new, old: jnp.where(
                        (active > 0).reshape(
                            (n,) + (1,) * (new.ndim - 1)),
                        new, old),
                    params, state.params)
            grad_sum = jnp.sum(g_flat, axis=0)
            metrics = {
                "loss": jnp.sum(loss) / denom,
                "applied_count": total_b,
                "local_count": total_b,
                "grad_norm": (jnp.sqrt(jnp.sum(jnp.square(grad_sum)))
                              / denom),
                "consensus_error": (
                    consensus.consensus_error_masked(
                        z_new.reshape(n, -1), active) if elastic
                    else consensus.consensus_error(
                        z_new.reshape(n, -1))),
                "step": state.step + 1,
            }
            if elastic:
                metrics["active_workers"] = scale
            if rc.consensus.debug_messages:
                # the exact messages this program's gossip consumed:
                # the oracle harness re-applies the dense fold to them
                # (with the same incoming residual under compression,
                # and the same mask under churn)
                metrics["gossip_m0"] = m0
                metrics["gossip_r0"] = state.residual
                if elastic:
                    metrics["gossip_active"] = active
            return DecentralizedState(params=params, z=z_new,
                                      residual=res_new, t=t_next,
                                      step=state.step + 1), metrics

        return init_state, train_step

    def staleness_schedule(self) -> StalenessSchedule:
        return StalenessSchedule(
            "gossip", 0,
            f"fresh local gradients; r={self.rounds} gossip rounds "
            f"(eq. 24: delta={self.rc.consensus.delta}, "
            f"lambda2={self.lam2:.4f}) bound the consensus error")

    @classmethod
    def timeline_model(cls) -> TimelineModel:
        # synchronous epochs like AMB: the gossip exchange rides the
        # round trip T_c between compute epochs
        return TimelineModel(
            scheme=cls.name, event_driven=False,
            epoch_duration=lambda t_p, t_c: t_p + t_c,
            update_time=lambda t, t_p, t_c: t * t_p + (t - 0.5) * t_c,
            n_updates=lambda total, t_p, t_c:
                max(int((total - t_p - 0.5 * t_c) // (t_p + t_c)) + 1, 0))
