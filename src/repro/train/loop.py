"""Host training loop: fixed-time (anytime) epochs, checkpoint/restart,
failure handling.

This is the deployment loop the launcher runs, for ANY registered
strategy (``rc.strategy`` -> ``repro.api.build``). Each iteration:
  1. the data pipeline draws per-worker anytime counts b_i(t) (real
     timer on hardware; shifted-exponential model in CI) and emits the
     masked global batch;
  2. under an elastic worker process, the health tracker zeroes
     contributions of dead workers (the aggregation stays exact —
     paper Sec. IV-C);
  3. the strategy's jitted step runs (e.g. AMB-DG: anytime accumulate
     -> delayed pod exchange -> dual-averaging update; decentralized:
     anytime accumulate -> r gossip rounds -> per-worker prox);
  4. periodic checkpoint (atomic, retention-managed) including the
     strategy state (delay buffers / per-worker duals), so staleness
     and consensus semantics survive restart.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.data.pipeline import AnytimePipeline
from repro.data.timing import ShiftedExponential
from repro.models.api import Model
from repro.train import checkpoint as ckpt
from repro.train.fault import WorkerHealth, fold_anytime_weights


@dataclasses.dataclass
class LoopConfig:
    n_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    n_workers: int = 8                  # logical anytime workers
    samples_per_worker: int = 8
    use_timing_model: bool = True
    # elastic mode: consecutive dead epochs before a worker is evicted
    # (eviction -> re-mesh plan -> immediate checkpoint; the worker is
    # readmitted when the elastic process brings it back)
    eviction_misses: int = 3


def _served_params(state, strategy_name: str):
    """The parameter tree (w = -alpha z) the publication channel
    snapshots, across strategy state layouts: ambdg/amb carry it as
    ``state.params``; kbatch wraps the base state; decentralized stacks
    per-worker copies — serve worker 0's view (post-gossip they agree
    up to consensus error, which the staleness-vs-quality column of
    BENCH_serve tracks anyway)."""
    if hasattr(state, "base"):
        state = state.base
    params = state.params
    if strategy_name == "decentralized":
        params = jax.tree.map(lambda a: a[0], params)
    return params


def train(model: Model, rc: RunConfig, loop: LoopConfig,
          log_fn: Callable[[Dict], None] = None) -> Dict:
    from repro import api
    strategy = api.build(model, rc)
    init_state, train_step = strategy.init_state, strategy.train_step
    step_fn = jax.jit(train_step, donate_argnums=(0,))

    timing = (ShiftedExponential() if loop.use_timing_model else None)
    pipeline = AnytimePipeline(
        cfg=rc.model, n_workers=loop.n_workers,
        samples_per_worker=loop.samples_per_worker,
        seq_len=rc.shape.seq_len if rc.model.family not in
        ("linreg", "cnn") else 0,
        seed=rc.seed, timing=timing, t_p=rc.ambdg.t_p)

    # stochastic staleness: the host owns the seeded delay process and
    # ships one draw per step to the device ring as batch["delay"]
    # (ambdg is the strategy with a master delay ring; the others
    # either reject or strip non-fixed processes at build time)
    delay_proc = None
    if rc.delay.process != "fixed" and rc.strategy == "ambdg":
        from repro.core.delay_process import make_delay_process
        delay_proc = make_delay_process(rc.delay, rc.ambdg.tau)

    # adaptive minibatch schedule: the host owns the seeded controller
    # (Strategy.batch_schedule(); None under the default "fixed"
    # schedule — the exact pre-existing path), draws one target per
    # step, caps the anytime weights with it, and ships it to the
    # device step as batch["b_sched"] (alpha swaps it for b_bar)
    batch_sched = strategy.batch_schedule()

    # elastic workers: the host owns the seeded worker process and
    # folds one (active_mask, speeds) draw per step into the anytime
    # weights; the "static" default keeps the exact pre-existing
    # no-churn path (no process object, no fold)
    elastic_proc = None
    if rc.elastic.process != "static":
        from repro.core.worker_process import make_worker_process
        elastic_proc = make_worker_process(rc.elastic, loop.n_workers)

    # train-while-serve: the master publishes w = -alpha z snapshots
    # into the bounded-staleness ring every publish_period master
    # updates; inference engines pop asynchronously (serve.publisher).
    # publish_period=0 (default) keeps the loop byte-identical.
    publisher = None
    if rc.serve.publish_period > 0:
        from repro.core.arena import make_layout
        from repro.serve.publisher import WeightPublisher
        params_struct = jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.PRNGKey(0))
        publisher = WeightPublisher(make_layout(params_struct), rc.serve)

    state = init_state(jax.random.PRNGKey(rc.seed))
    start_step = 0
    # heartbeats are driven by the elastic process on a virtual epoch
    # clock (at=step; a missed epoch is a missed heartbeat). The static
    # fleet has no liveness source, so it keeps no tracker: a
    # wall-clock one with no heartbeats would call every worker failed
    # once a run (its first compile included) outlasts the timeout
    health = (WorkerHealth(loop.n_workers, heartbeat_timeout=0.5,
                           eviction_misses=loop.eviction_misses, t0=0.0)
              if elastic_proc is not None else None)
    if loop.ckpt_dir and ckpt.latest_step(loop.ckpt_dir) is not None:
        state, extra = ckpt.restore(loop.ckpt_dir, state)
        pipeline.load_state_dict(extra["pipeline"])
        if delay_proc is not None and "delay_process" in extra:
            delay_proc.load_state_dict(extra["delay_process"])
        if elastic_proc is not None and "elastic_process" in extra:
            # restart exactness: the remaining churn sequence AND the
            # liveness bookkeeping survive the restart
            elastic_proc.load_state_dict(extra["elastic_process"])
            if "health" in extra:
                health.load_state_dict(extra["health"])
        if publisher is not None and "publisher" in extra:
            # the publish ring and its staleness metadata survive too —
            # servers keep popping due snapshots across the restart
            publisher.load_state_dict(extra["publisher"])
        if batch_sched is not None and "batch_schedule" in extra:
            # the controller's counters, EMA trackers and rng survive,
            # so the remaining b(t) sequence is restart-exact
            batch_sched.load_state_dict(extra["batch_schedule"])
        start_step = extra["step"]

    wants_active = bool(getattr(strategy, "consumes_active_mask", False))
    history = []
    remesh_events = []
    t_start = time.monotonic()

    def save_ckpt(next_step: int, plan=None):
        extra = {"step": next_step, "pipeline": pipeline.state_dict()}
        if delay_proc is not None:
            # same restart-exactness contract as the data pipeline:
            # the remaining delay sequence survives the restart
            extra["delay_process"] = delay_proc.state_dict()
        if elastic_proc is not None:
            extra["elastic_process"] = elastic_proc.state_dict()
            extra["health"] = health.state_dict()
        if publisher is not None:
            extra["publisher"] = publisher.state_dict()
        if batch_sched is not None:
            extra["batch_schedule"] = batch_sched.state_dict()
        if plan is not None:
            extra["remesh_plan"] = plan
        ckpt.save(loop.ckpt_dir, next_step, state, extra=extra)

    for step in range(start_step, loop.n_steps):
        batch = pipeline.next_global_batch()
        b_target = None
        if batch_sched is not None:
            from repro.data.pipeline import apply_batch_target
            b_target = batch_sched.target()
            batch["weights"] = apply_batch_target(
                batch["weights"], b_target, loop.n_workers,
                loop.samples_per_worker)
        remesh_plan = None
        if elastic_proc is not None:
            active, speeds = elastic_proc.step()
            at = float(step)
            for i in np.flatnonzero(active):
                if int(i) in health.evicted:
                    # elastic re-mesh, recovery half: the process
                    # brought the worker back -> readmit explicitly
                    health.readmit(int(i), at=at)
                    remesh_events.append({"step": step,
                                          "event": "readmit",
                                          "worker": int(i)})
                health.heartbeat(int(i), at=at)
            before = set(health.evicted)
            health.tick(at=at)
            newly_evicted = sorted(health.evicted - before)
            batch["weights"] = fold_anytime_weights(
                batch["weights"], active, speeds, loop.n_workers,
                loop.samples_per_worker)
            if wants_active:
                batch["active"] = active.astype(np.float32)
            if newly_evicted:
                # persistent failure -> elastic re-mesh plan + an
                # immediate checkpoint after this step commits (the
                # launcher would rebuild the mesh and restore it)
                remesh_plan = health.rescale_plan()
                remesh_plan["evicted"] = sorted(health.evicted)
                remesh_events.append({"step": step, "event": "evict",
                                      "workers": newly_evicted,
                                      "plan": remesh_plan})
        if delay_proc is not None:
            batch["delay"] = np.int32(delay_proc.next())
        if b_target is not None:
            batch["b_sched"] = np.float32(b_target)
        batch = jax.tree.map(jax.numpy.asarray, batch)
        state, metrics = step_fn(state, batch)
        if batch_sched is not None:
            # closed-loop feedback: the step's loss damps adadamp, the
            # observed staleness feeds the delay-aware scaling
            batch_sched.observe(
                loss=float(metrics["loss"]),
                tau_obs=(float(metrics["tau_applied"])
                         if "tau_applied" in metrics else None))
        if publisher is not None and \
                (step + 1) % rc.serve.publish_period == 0:
            publisher.publish(_served_params(state, rc.strategy),
                              step + 1)
        if (step + 1) % loop.log_every == 0 or step == loop.n_steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["wall_s"] = time.monotonic() - t_start
            if elastic_proc is not None:
                m["active_workers"] = float(active.sum())
            history.append(m)
            if log_fn:
                log_fn(m)
        if loop.ckpt_dir and ((step + 1) % loop.ckpt_every == 0
                              or remesh_plan is not None):
            save_ckpt(step + 1, plan=remesh_plan)
    return {"state": state, "history": history, "step_fn": step_fn,
            "b_history": pipeline.b_history,
            "remesh_events": remesh_events,
            "publisher": publisher}
