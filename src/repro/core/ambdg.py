"""AMB-DG composed train step: anytime accumulation -> (delayed) pod
exchange -> dual-averaging update.

``make_train_step(model, rc)`` returns ``(init_state, train_step)``:

    state = init_state(rng)
    state, metrics = train_step(state, batch)

Semantics (paper Sec. III, adapted per DESIGN.md §2):
  * batch leaves are globally-shaped, sharded (pod, data) on dim 0;
    per-sample ``weights`` carry the anytime mask (b_i(t)).
  * gradients are summed per pod chunk (vmap over a pod-stacked view,
    so no cross-pod communication happens in the backward pass), then
    pushed into the tau-deep delay buffer; the popped tau-old entry is
    reduced across pods and fed to dual averaging — the master's
    z(t+1) = z(t) + g(t - tau) pipeline with deterministic staleness.
  * tau = 0 (or a single pod) collapses to the synchronous AMB update.

Two master-pipeline implementations, selected by ``rc.master_impl``:

  "arena"   (default) the delay ring, dual variable, int8 residual and
            popped gradient all live in one persistent lane-aligned
            (rows, 128) arena (see ``core.arena`` / docs/arena.md).
            Parameters are flattened ONCE at init to build the static
            layout; per step the pod gradients are scattered into the
            arena (no tree concatenate) and the ring rotation + dual
            update run as two fused passes (Pallas on TPU).
  "pytree"  the per-leaf reference path (``core.delayed`` +
            tree-mapped optimizers) — kept as the bit-exact oracle and
            for ablations.

The optimizer is pluggable (``rc.optimizer``): "dual_averaging" is the
paper; "sgd"/"adam" compose the same delayed anytime gradients with
standard optimizers (beyond-paper comparisons).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.core import anytime, delayed
from repro.core import arena as arena_mod
from repro.core import dual_averaging as da
from repro.models.api import Model


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    buffer: Optional[delayed.DelayBuffer]    # pytree master path
    arena: Optional[arena_mod.GradArena]     # arena master path
    step: jax.Array


def _loss_with_remat(model: Model, rc: RunConfig):
    # Remat lives at the scanned-block level (ModelConfig.block_remat);
    # a whole-loss checkpoint would still store per-layer scan residuals
    # during the recompute, so rc.remat is only kept for ablations.
    loss = lambda p, b: model.loss(p, b)
    if rc.remat == "whole_loss":
        loss = jax.checkpoint(loss)
    return loss


def arena_master_update(layout, opt, params, opt_state, arena_state,
                        pod_grads, pod_counts, compression: str = "none",
                        b_sched=None):
    """The fused master pipeline on the flat arena: scatter the
    pod-stacked gradient tree into arena form (static update-slices —
    never a full-tree concatenate; asserted by tests/test_arena.py),
    rotate the delay ring, and apply the optimizer to the popped row.
    ``b_sched`` threads an adaptive batch schedule's target b(t) into
    the optimizer (None = the static ``b_bar``).

    Returns (params, opt_state, arena_state, grad_sum_flat, count).
    """
    from repro.dist.context import constrain
    if arena_state is not None:
        grad_sum, count, arena_state = arena_mod.push_pop(
            layout, arena_state, pod_grads, pod_counts, compression)
    else:  # tau = 0: synchronous exchange, then one flat scatter
        summed = jax.tree.map(delayed.pod_sum, pod_grads)
        grad_sum = arena_mod.flatten_tree(layout, summed)
        count = jnp.sum(pod_counts)
    grad_sum = constrain(grad_sum, ("flat", None))
    params, opt_state = opt.update(opt_state, params, grad_sum, count,
                                   b_sched=b_sched)
    return params, opt_state, arena_state, grad_sum, count


def make_train_step(model: Model, rc: RunConfig):
    """Deprecated alias — construct through the Strategy registry
    (``repro.api.build(model, rc)``) instead. Kept so pre-Strategy
    call sites (and the golden traces they pinned) keep working."""
    from repro import api
    s = api.build(model, rc if rc.strategy == "ambdg"
                  else rc.replace(strategy="ambdg"))
    return s.init_state, s.train_step


def build_step_fns(model: Model, rc: RunConfig):
    """The AMB-DG step factory: returns ``(init_state, train_step)``.
    Internal to the Strategy layer — ``AmbdgStrategy`` (and the
    strategies composing it) wrap this; user code goes through
    ``repro.api.build``.

    ``rc.delay`` selects the staleness process: the default "fixed"
    runs the static-phase master path unchanged (bit-identical to the
    pre-delay-process code — pinned by the regression suites); a
    stochastic process runs the delay-tolerant arena ring
    (``arena.push_pop_variable``) on a per-step ``batch["delay"]``
    scalar the host loop draws from ``core.delay_process``, with the
    Agarwal-Duchi delay-adaptive dual-averaging step
    (``rc.delay.adaptive_alpha``).

    ``rc.batch_schedule`` selects the minibatch-target schedule: the
    default "fixed" keeps the timing-driven anytime target and the
    static ``b_bar`` inside alpha (bit-identical to the pre-schedule
    code); an adaptive schedule ships the controller's per-step target
    as a ``batch["b_sched"]`` scalar, which replaces ``b_bar`` in the
    dual-averaging step size (sgd/adam ignore it)."""
    from repro.core.batch_schedule import resolve_targets
    from repro.core.delay_process import resolve_bounds
    from repro.optim import make_arena_optimizer, make_optimizer
    n_pods = rc.mesh.n_pods
    tau = rc.ambdg.tau
    n_mb = rc.ambdg.n_microbatches
    compression = rc.ambdg.pod_compression
    if rc.master_impl not in ("arena", "pytree"):
        raise ValueError(f"unknown master_impl {rc.master_impl!r}; "
                         "expected 'arena' or 'pytree'")
    use_arena = rc.master_impl == "arena"
    variable_delay = rc.delay.process != "fixed"
    if variable_delay:
        if not use_arena:
            raise ValueError(
                "stochastic delay processes run on the arena master "
                "pipeline only (rc.master_impl='arena'); the pytree "
                "reference path keeps the paper's fixed tau")
        _, tau_max = resolve_bounds(rc.delay, tau)
        ring_tau = tau_max
    else:
        resolve_bounds(rc.delay, tau)       # validate tau_max vs tau
        ring_tau = tau
    variable_batch = rc.batch_schedule.schedule != "fixed"
    if variable_batch:
        resolve_targets(rc.batch_schedule, rc.ambdg.b_bar)  # raise early
        if not use_arena:
            raise ValueError(
                "adaptive batch schedules run on the arena master "
                "pipeline only (rc.master_impl='arena'); the pytree "
                "reference path keeps the paper's static b_bar")
    loss_fn = _loss_with_remat(model, rc)

    if use_arena:
        # flatten ONCE: the layout (treedef + row offsets) is static
        # metadata computed from abstract shapes at build time
        params_shapes = jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.PRNGKey(0))
        layout = arena_mod.make_layout(params_shapes)
        opt = make_arena_optimizer(rc, layout)
    else:
        layout = None
        opt = make_optimizer(rc)

    params_axes = None
    if compression == "int8" and not use_arena:
        from repro.dist import shapes_and_axes
        _, params_axes = shapes_and_axes(model.init, jax.random.PRNGKey(0))

    def init_state(key) -> TrainState:
        params, _ = model.init(key)
        if use_arena:
            return TrainState(
                params=params, opt_state=opt.init(), buffer=None,
                arena=arena_mod.init_arena(layout, ring_tau, n_pods,
                                           compression,
                                           variable=variable_delay),
                step=jnp.zeros((), jnp.int32))
        return TrainState(
            params=params,
            opt_state=opt.init(params),
            buffer=delayed.init_buffer(params, tau, n_pods, compression),
            arena=None,
            step=jnp.zeros((), jnp.int32),
        )

    anytime_impl = rc.ambdg.anytime_impl

    def _pod_chunk_grads(params, batch):
        """Returns pod-stacked (grads (n_pods, ...), counts (n_pods,),
        loss sums (n_pods,)). No cross-pod reduction."""
        def one_chunk(chunk):
            n_active = chunk.get("n_active", jnp.int32(n_mb))
            chunk = {k: v for k, v in chunk.items() if k != "n_active"}
            if anytime_impl == "while_dynamic":
                return anytime.accumulate_while(
                    loss_fn, params, chunk, n_mb, n_active)
            return anytime.accumulate_scan(loss_fn, params, chunk, n_mb)

        if n_pods == 1:
            g, c, m = one_chunk(batch)
            stack = lambda x: x[None]
            return (jax.tree.map(stack, g), c[None], m["loss_sum"][None])

        # reshape (B, ...) -> (n_pods, B/n_pods, ...); dim 0 is sharded
        # over the 'pod' mesh axis so each chunk computes on its own pod
        chunked = jax.tree.map(
            lambda x: x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:]),
            batch)
        g, c, m = jax.vmap(one_chunk, in_axes=(0,))(chunked)
        return g, c, m["loss_sum"]

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        from repro.dist.context import (active_mesh, ambient_mesh,
                                        profile_set, sharding_profile)
        # a caller's profile holds: under `sharding_profile(None)` a
        # multi-pod config runs as one program on one device (the pod
        # exchange a local fold), the reference a sharded run is
        # checked against
        mesh_cfg = (active_mesh() if profile_set()
                    else rc.mesh if rc.mesh.n_devices > 1 else None)
        if (mesh_cfg is not None and mesh_cfg.n_devices > 1
                and ambient_mesh() is None):
            raise ValueError(
                f"the {mesh_cfg.shape} mesh profile needs an ambient "
                "mesh: run the step under `with jax.set_mesh(mesh):`, "
                "or under `sharding_profile(None)` to run it as one "
                "program on one device")
        with sharding_profile(mesh_cfg):
            return _train_step_inner(state, batch)

    def _train_step_inner(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        from repro.dist.context import constrain
        tau_obs = None
        if variable_delay:
            if "delay" not in batch:
                raise ValueError(
                    f"rc.delay.process={rc.delay.process!r} needs a "
                    "per-step batch['delay'] scalar (the host loop "
                    "draws it from core.delay_process)")
            delay = batch["delay"]
            batch = {k: v for k, v in batch.items() if k != "delay"}
        b_sched = None
        if variable_batch:
            if "b_sched" not in batch:
                raise ValueError(
                    f"rc.batch_schedule.schedule="
                    f"{rc.batch_schedule.schedule!r} needs a per-step "
                    "batch['b_sched'] scalar (the host loop draws it "
                    "from core.batch_schedule)")
            b_sched = jnp.asarray(batch["b_sched"], jnp.float32)
            batch = {k: v for k, v in batch.items() if k != "b_sched"}
        pod_grads, pod_counts, pod_loss = _pod_chunk_grads(
            state.params, batch)

        if use_arena and variable_delay:
            grad_sum_flat, count, tau_obs, arena_state = \
                arena_mod.push_pop_variable(layout, state.arena,
                                            pod_grads, pod_counts,
                                            delay, compression)
            grad_sum_flat = constrain(grad_sum_flat, ("flat", None))
            # zero-arrival contract: the ring reports tau_obs = 0 when
            # nothing lands, but 0 would tell the Agarwal-Duchi
            # adaptive alpha the stall step was perfectly FRESH and
            # inflate the step size exactly when the network stalled —
            # fall back to the ring cap (the worst case the
            # non-adaptive schedule already uses)
            tau_obs = jnp.where(count > 0.0, tau_obs,
                                jnp.float32(ring_tau))
            # adaptive: observed staleness of THIS update; otherwise
            # the static worst case is the ring cap tau_max (ring_tau)
            # — NOT the nominal cfg.tau a stochastic process exceeds
            params, opt_state = opt.update(
                state.opt_state, state.params, grad_sum_flat, count,
                tau_obs=(tau_obs if rc.delay.adaptive_alpha
                         else float(ring_tau)),
                b_sched=b_sched)
            buffer = None
            g_norm = (jnp.sqrt(jnp.sum(jnp.square(grad_sum_flat)))
                      / jnp.maximum(count, 1e-12))
        elif use_arena:
            params, opt_state, arena_state, grad_sum_flat, count = \
                arena_master_update(layout, opt, state.params,
                                    state.opt_state, state.arena,
                                    pod_grads, pod_counts, compression,
                                    b_sched=b_sched)
            buffer = None
            # scalar divide after the reduce: same value as norm(g/c),
            # without a params-sized elementwise divide for a metric
            g_norm = (jnp.sqrt(jnp.sum(jnp.square(grad_sum_flat)))
                      / jnp.maximum(count, 1e-12))
        else:
            arena_state = None
            if state.buffer is not None:
                grad_sum, count, buffer = delayed.push_pop(
                    state.buffer, pod_grads, pod_counts, compression,
                    params_axes=params_axes)
            else:
                grad_sum = jax.tree.map(delayed.pod_sum, pod_grads)
                count = jnp.sum(pod_counts)
                buffer = None
            g = anytime.normalize(grad_sum, count)
            params, opt_state = opt.update(state.opt_state, state.params, g)
            g_norm = optax_global_norm(g)

        metrics = {
            "loss": jnp.sum(pod_loss) / jnp.maximum(jnp.sum(pod_counts), 1e-12),
            "applied_count": count,
            "local_count": jnp.sum(pod_counts),
            "grad_norm": g_norm,
            "step": state.step + 1,
        }
        if tau_obs is not None:
            # observed staleness of the gradients applied this step
            # (count-weighted). Zero-arrival steps report the ring-cap
            # FALLBACK staleness — the value the step size actually
            # used — never 0 (indistinguishable from genuinely-fresh
            # delivery); ``applied_count == 0`` is the stall signal.
            metrics["tau_applied"] = tau_obs
        return TrainState(params=params, opt_state=opt_state,
                          buffer=buffer, arena=arena_state,
                          step=state.step + 1), metrics

    return init_state, train_step


def optax_global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))
