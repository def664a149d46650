"""Master-update microbenchmark: pytree vs arena pipeline.

Times ONLY the master side of the AMB-DG step — delayed pod exchange
(ring push/pop) + count-normalization + dual-averaging update — for a
>= 10M-parameter, many-leaf tree shaped like an LM config, on CPU
(interpret-mode environment: the arena path runs its pure-XLA
reference kernels, the same code the CPU fallback uses in production).

Emits ``name,metric,value`` CSV rows (run.py contract) and writes
``BENCH_master_update.json`` so the perf trajectory is tracked across
PRs: steps/sec for the pytree path and BOTH arena ring layouts (v2
per-slot/static-phase, v1 stacked), analytic bytes/step, and two
MEASURED bytes-moved/step columns from the compiled executable —
cost_analysis' bytes-accessed, and the bytes of ``copy`` instructions
XLA:CPU inserted (the whole-ring copy-protection v2 exists to remove:
v1 pays ~3 ring copies per step for the pop-read/push-write hazard +
lax.switch, v2 compiles copy-free on the uncompressed path).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.base import (AmbdgConfig, LINREG, MeshConfig, ModelConfig,
                                RunConfig, TRAIN_4K)
from repro.core import ambdg, anytime, arena, delayed
from repro.launch.hlo import copy_bytes
from repro.optim import make_arena_optimizer, make_optimizer


def _lm_like_tree(key, target_params: int):
    """A many-leaf tree with LM-config-like leaf statistics: a few big
    embedding/projection matrices and hundreds of small norms/biases."""
    leaves = {}
    big = [("emb", (target_params // 4 // 1024, 1024)),
           ("head", (target_params // 4 // 1024, 1024))]
    n_layers = 48
    d = int(np.sqrt(target_params // 2 // (4 * n_layers)))
    for i in range(n_layers):
        leaves[f"l{i:02d}"] = {
            "wq": (d, d), "wo": (d, d), "w_up": (d, 2 * d),
            "norm1": (d,), "norm2": (d,), "bias": (d,),
        }
    for name, shape in big:
        leaves[name] = shape
    flat, treedef = jax.tree.flatten(
        leaves, is_leaf=lambda x: isinstance(x, tuple))
    ks = jax.random.split(key, len(flat))
    return jax.tree.unflatten(
        treedef, [jax.random.normal(k, s, jnp.float32) * 0.02
                  for k, s in zip(ks, flat)])


class _Timed:
    """One benchmarked pipeline: an AOT-compiled step (so its measured
    cost/copy stats come from the exact executable being timed) with
    its (donated) state chained across timing rounds."""

    def __init__(self, step_fn, state, grads, counts):
        lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(
            state, grads, counts)
        self.compiled = lowered.compile()
        cost = self.compiled.cost_analysis()
        self.bytes_accessed = int(cost.get("bytes accessed", -1))
        self.copy_bytes = copy_bytes(self.compiled.as_text())
        self.state = state

    def warm(self, grads, counts):
        for _ in range(2):
            self.state = self.compiled(self.state, grads, counts)
        jax.block_until_ready(self.state)

    def round(self, grads, counts, iters: int) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            self.state = self.compiled(self.state, grads, counts)
        jax.block_until_ready(self.state)
        return iters / (time.perf_counter() - t0)


def _time_interleaved(pipelines, grads, counts, iters: int,
                      rounds: int = 5):
    """Alternate short rounds of all pipelines and keep each one's
    best — noise on a shared CI box hits all of them, alternation keeps
    it from biasing whichever ran later."""
    for p in pipelines:
        p.warm(grads, counts)
    best = [0.0] * len(pipelines)
    for _ in range(rounds):
        for i, p in enumerate(pipelines):
            best[i] = max(best[i], p.round(grads, counts, iters))
    return best


def bench_one(params, tau: int, n_pods: int, compression: str,
              iters: int):
    rc = RunConfig(
        model=ModelConfig(name="bench", family=LINREG, n_layers=0,
                          d_model=0, n_heads=0, n_kv_heads=0, d_ff=0,
                          vocab_size=0, linreg_dim=8),
        shape=TRAIN_4K, mesh=MeshConfig(n_pods=1, data=1, model=1),
        ambdg=AmbdgConfig(tau=tau, pod_compression=compression))
    key = jax.random.PRNGKey(0)
    grads = jax.tree.map(
        lambda p: jax.random.normal(
            jax.random.fold_in(key, p.size % 9973),
            (n_pods,) + p.shape, jnp.float32),
        params)
    counts = jnp.full((n_pods,), 7.0)

    # --- pytree reference path (donated, as in train.loop) ---
    opt_p = make_optimizer(rc)

    def step_pytree(state, grads, counts):
        p, o, b = state
        gs, c, b = delayed.push_pop(b, grads, counts, compression)
        g = anytime.normalize(gs, c)
        p, o = opt_p.update(o, p, g)
        return p, o, b

    pytree = _Timed(step_pytree,
                    (params, opt_p.init(params),
                     delayed.init_buffer(params, tau, n_pods, compression)),
                    grads, counts)

    # --- arena path, both ring layouts ---
    layout = arena.make_layout(params)
    opt_a = make_arena_optimizer(rc, layout)

    def step_arena(state, grads, counts):
        p, o, a = state
        p, o, a, _, _ = ambdg.arena_master_update(
            layout, opt_a, p, o, a, grads, counts, compression)
        return p, o, a

    def arena_state(ring_version):
        return (params, opt_a.init(),
                arena.init_arena(layout, tau, n_pods, compression,
                                 ring_version=ring_version))

    # NB: v2's phase advances per step, so steady-state timing would
    # cycle tau+1 executables; benchmarking the phase-0 program is
    # representative (every phase compiles the same static-slot code,
    # just with different slot numbers). The AOT-compiled step keeps
    # the donated output structure == input structure for re-feeding,
    # which phase advancement would break — so the timed v2 step runs
    # with the phase pinned (the per-step work is identical).
    arena_v2 = _Timed(_pin_phase(step_arena), arena_state(2),
                      grads, counts)
    arena_v1 = _Timed(step_arena, arena_state(1), grads, counts)

    pytree_sps, v2_sps, v1_sps = _time_interleaved(
        [pytree, arena_v2, arena_v1], grads, counts, iters)

    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    elem = 1 if compression == "int8" else 4
    # analytic HBM traffic per step (reads+writes of the big buffers)
    bytes_arena = n_pods * n_params * (
        4 +          # gradient scatter write
        2 * elem +   # ring slot: pop read + push write
        (12 if compression == "int8" else 0)   # residual r/w + fed read
    ) + n_params * (4 * 4 + 4)  # z r/w + w write + popped read (+unflatten)
    bytes_pytree = bytes_arena + 4 * 4 * n_params  # z/g re-flatten+unflatten

    return {
        "n_params": n_params,
        "n_leaves": len(jax.tree.leaves(params)),
        "tau": tau, "n_pods": n_pods, "compression": compression,
        "pytree_steps_per_s": round(pytree_sps, 3),
        "arena_steps_per_s": round(v2_sps, 3),
        "arena_v1_steps_per_s": round(v1_sps, 3),
        "speedup": round(v2_sps / pytree_sps, 3),
        "speedup_vs_ring_v1": round(v2_sps / v1_sps, 3),
        "approx_bytes_per_step_arena": int(bytes_arena),
        "approx_bytes_per_step_pytree": int(bytes_pytree),
        "measured_bytes_per_step": {
            "pytree": {"bytes_accessed": pytree.bytes_accessed,
                       "copy_bytes": pytree.copy_bytes},
            "arena": {"bytes_accessed": arena_v2.bytes_accessed,
                      "copy_bytes": arena_v2.copy_bytes},
            "arena_ring_v1": {"bytes_accessed": arena_v1.bytes_accessed,
                              "copy_bytes": arena_v1.copy_bytes},
        },
    }


def _pin_phase(step_fn):
    """Keep the v2 arena's static phase fixed across timed iterations
    so the donated AOT executable can be re-fed its own output (see
    the note at the call site)."""
    def step(state, grads, counts):
        p, o, a = state
        p, o, a = step_fn((p, o, a), grads, counts)
        return p, o, a._replace(phase=state[2].phase)
    return step


def run(full: bool = False) -> None:
    target = 40_000_000 if full else 12_000_000
    iters = 10 if full else 6
    params = _lm_like_tree(jax.random.PRNGKey(0), target)
    results = []
    for compression in ("none", "int8"):
        r = bench_one(params, tau=2, n_pods=2, compression=compression,
                      iters=iters)
        results.append(r)
        tag = f"master_update_{compression}"
        emit(tag, "params", r["n_params"])
        emit(tag, "pytree_steps_per_s", r["pytree_steps_per_s"])
        emit(tag, "arena_steps_per_s", r["arena_steps_per_s"])
        emit(tag, "arena_v1_steps_per_s", r["arena_v1_steps_per_s"])
        emit(tag, "speedup", r["speedup"])
        emit(tag, "speedup_vs_ring_v1", r["speedup_vs_ring_v1"])
        emit(tag, "copy_bytes_per_step_arena",
             r["measured_bytes_per_step"]["arena"]["copy_bytes"])
        emit(tag, "copy_bytes_per_step_ring_v1",
             r["measured_bytes_per_step"]["arena_ring_v1"]["copy_bytes"])
    with open("BENCH_master_update.json", "w") as f:
        json.dump({"results": results}, f, indent=1)


if __name__ == "__main__":
    run()
