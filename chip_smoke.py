"""Smoke run of AMB-DG training on a TPU: the proof that the system
starts on the chip.

    python chip_smoke.py                # one chip: qwen1.5-0.5b, full width
    python chip_smoke.py --four-chips   # the pod exchange and the gossip
                                        # on a 4-chip host, nothing else

One chip. The training launcher's own path (``repro.launch.train`` ->
``train.loop.train`` -> ``api.build`` -> the ambdg ``train_step`` ->
the arena master and its Pallas ``dual_update`` kernel) runs
qwen1.5-0.5b at its published widths and full depth, random weights
from a seed, tau = 1, dual averaging, fixed delay, no compression:
2 warm-up steps and 6 more. Checks: finite loss every step;
``applied_count`` 0 for the first tau steps and positive after; the
compiled step holds a Pallas kernel (``tpu_custom_call``); and one more
step's master update (params and z) matches the same update recomputed
with the master's reference implementation (``impl="ref"``) from the
same state, to ``MASTER_RTOL`` of the largest entry.

Four chips (``--four-chips``). One process on a ``pod=4`` mesh over
``jax.devices()[:4]``, qwen1.5-0.5b at full width with depth and
vocabulary cut:
  (a) ambdg with int8 pod compression, so the delay ring's int8 rotate
      and the dual update run as Pallas kernels under shard_map with
      one pod-axis collective. The last step also runs as one program
      on one device from the same state: the master's params and z
      must agree to ``EXCHANGE_RTOL``, and each pod's pushed gradient
      before int8 rounding to ``GRAD_RTOL``. The int8 ring rotation
      alone, on identical inputs, must leave the ring state bit-equal
      to its one-device run and pop the same sum to ``EXCHANGE_RTOL``;
      the ring slots must be spread over the 4 devices;
  (b) decentralized AMB-DG, 4 workers gossiping over ``ppermute``;
      every step's consensus state must equal the dense gossip fold
      applied to the step's own messages, bit for bit.

Every number printed before the last line is informal: wall-clock
figures are host-clock readings of one run, not metrics. The last line
is one JSON object, ``{"ok": true, "device": {...}}``; any failed check
exits non-zero without it. Exits non-zero, printing no result, when
JAX finds no TPU. Runs in one process: it starts no child.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import repro.configs as C  # noqa: E402  (src/ is on the path now)
from repro import api, dist  # noqa: E402
from repro.configs.base import (AmbdgConfig, ConsensusConfig,  # noqa: E402
                                MeshConfig, RunConfig, TRAIN_4K)
from repro.core import arena  # noqa: E402
from repro.core import consensus  # noqa: E402
from repro.core import dual_averaging as da  # noqa: E402
from repro.data.pipeline import AnytimePipeline  # noqa: E402
from repro.dist.context import sharding_profile  # noqa: E402
from repro.launch import train as launcher  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train.loop import train  # noqa: E402

ARCH = "qwen1.5-0.5b"
WARMUP, STEPS = 2, 6     # one chip: warm-up steps (both arena phases
                         # compile), then the steps reported
# one chip's shape: the step's compiled memory fits the v5e's 16 GB at
# all 24 layers (12.7 GB by the chip compiler's own analysis)
SEQ_LEN, N_WORKERS, SAMPLES_PER_WORKER, N_MICROBATCHES = 2048, 2, 1, 2
# |pallas - ref| <= MASTER_RTOL * max|ref| for the master update: the
# kernel and the XLA reference divide and scale in f32 on different
# compilers, so a few ULPs of the largest entry are allowed
MASTER_RTOL = 1e-6
# the master update on the mesh vs as one program on one device, from
# the same state: the same arithmetic in another partitioning
EXCHANGE_RTOL = 1e-6
# the pushed gradient before int8 rounding, on the mesh vs as one
# program on one device: the same forward/backward in another
# partitioning, computed in bf16, so a few bf16 ULPs (2^-8) of the
# largest entry are allowed; a pod that took the wrong part of the
# batch misses by the size of the gradient itself
GRAD_RTOL = 2.0 ** -6


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects named pass/fail results; a run is ok only if all pass."""
    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"check {name}: {'PASS' if ok else 'FAIL'}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)


def rel_max_err(got, want) -> float:
    """max |got - want| / max |want| (0 when both are all zero)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    err = float(np.max(np.abs(got - want))) if want.size else 0.0
    return err / scale if scale > 0 else err


def tree_rel_max_err(got, want) -> float:
    errs = jax.tree.leaves(jax.tree.map(rel_max_err, got, want))
    return max(errs) if errs else 0.0


class CompileTimer:
    """Sums XLA backend compile time reported by JAX's monitoring."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


# ---------------------------------------------------------------------------
# One chip: the training launcher's path at full width
# ---------------------------------------------------------------------------
def run_one_chip(checks: Checks) -> None:
    n_steps = WARMUP + STEPS
    flags = ["--arch", ARCH, "--strategy", "ambdg", "--tau", "1",
             "--optimizer", "dual_averaging", "--delay-process", "fixed",
             "--seq-len", str(SEQ_LEN), "--n-workers", str(N_WORKERS),
             "--samples-per-worker", str(SAMPLES_PER_WORKER),
             "--n-microbatches", str(N_MICROBATCHES),
             "--steps", str(n_steps)]
    model, rc, loop = launcher.build_run(
        launcher.build_parser().parse_args(flags))
    loop = dataclasses.replace(loop, log_every=1)
    cfg, tau = rc.model, rc.ambdg.tau
    log(f"model {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}x"
        f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"layers={cfg.n_layers} params={cfg.n_params()}")
    log("reduced: none (published widths, all layers)")
    log(f"shape: {N_WORKERS} workers x {SAMPLES_PER_WORKER} "
        f"samples x {SEQ_LEN} tokens, {N_MICROBATCHES} "
        f"microbatches, tau={tau}, pod_compression="
        f"{rc.ambdg.pod_compression}, delay={rc.delay.process}")

    timer = CompileTimer()
    history = []
    out = train(model, rc, loop, log_fn=history.append)
    log(f"compile: {timer.count} XLA compiles, {timer.seconds:.1f} s "
        "(informal)")
    prev = 0.0
    for i, m in enumerate(history):
        log(f"step {i + 1}: loss={m['loss']!r} "
            f"applied_count={m['applied_count']!r} "
            f"wall={m['wall_s'] - prev:.3f}s (informal, host clock)")
        prev = m["wall_s"]
    timed = sorted(b["wall_s"] - a["wall_s"] for a, b in
                   zip(history[WARMUP - 1:], history[WARMUP:]))
    if timed:
        log(f"timed steps: {len(timed)}, median "
            f"{timed[len(timed) // 2]:.3f} s/step, "
            f"{N_WORKERS * SAMPLES_PER_WORKER * SEQ_LEN} "
            "tokens/step (informal, host clock)")

    checks.check("steps_logged", len(history) == n_steps,
                 f"{len(history)} of {n_steps}")
    checks.check("loss_finite",
                 all(math.isfinite(m["loss"]) for m in history))
    counts = [m["applied_count"] for m in history]
    checks.check("applied_count",
                 all(c == 0 for c in counts[:tau])
                 and all(c > 0 for c in counts[tau:]),
                 f"{counts}")

    # the compiled step for the current state (jit's cache: no compile)
    state, step_fn = out["state"], out["step_fn"]
    pipeline = AnytimePipeline(
        cfg=cfg, n_workers=N_WORKERS,
        samples_per_worker=SAMPLES_PER_WORKER, seq_len=SEQ_LEN,
        seed=rc.seed + 1)
    batch = jax.tree.map(jnp.asarray, pipeline.next_global_batch())
    compiled = step_fn.lower(state, batch).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    checks.check("pallas_kernel_in_step", n_kernels > 0,
                 f"{n_kernels} tpu_custom_call")
    mem = compiled.memory_analysis()
    log(f"compiled step memory: arguments {mem.argument_size_in_bytes} B, "
        f"temporaries {mem.temp_size_in_bytes} B, aliased "
        f"{mem.alias_size_in_bytes} B")

    # the next step's master update, recomputed with impl="ref" from the
    # same state: at tau=1 it reads only the ring's pop slot, its count
    # and z, so the step's fresh gradient does not enter the comparison
    params_struct = jax.eval_shape(lambda k: model.init(k)[0],
                                   jax.random.PRNGKey(0))
    layout = arena.make_layout(params_struct)
    ar = state.arena
    pop_i = (ar.phase + 1) % len(ar.ring)

    @jax.jit
    def ref_master(slot, counts, opt_state):
        return da.update_arena(layout, opt_state, jnp.sum(slot, axis=0),
                               jnp.sum(counts), rc.ambdg, impl="ref")

    ref_params, ref_opt = jax.device_get(
        ref_master(ar.ring[pop_i], ar.counts[pop_i], state.opt_state))
    state, metrics = compiled(state, batch)
    got_params, got_opt = jax.device_get((state.params, state.opt_state))
    err_p = tree_rel_max_err(got_params, ref_params)
    err_z = rel_max_err(got_opt.z, ref_opt.z)
    exact = float(np.mean(np.asarray(got_opt.z) == np.asarray(ref_opt.z)))
    log(f"master pallas vs ref at step {n_steps + 1}: params rel max err "
        f"{err_p!r}, z rel max err {err_z!r}, z entries bit-equal "
        f"{exact!r}, applied_count {float(metrics['applied_count'])!r}")
    checks.check("master_pallas_matches_ref",
                 err_p <= MASTER_RTOL and err_z <= MASTER_RTOL
                 and float(metrics["applied_count"]) > 0,
                 f"tolerance {MASTER_RTOL} of the largest entry")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# Four chips: the pod-axis exchange and the gossip
# ---------------------------------------------------------------------------
FOUR_LAYERS, FOUR_VOCAB, FOUR_SEQ, FOUR_STEPS = 2, 8192, 512, 3


def four_chip_config():
    full = C.get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=FOUR_LAYERS,
                              vocab_size=FOUR_VOCAB)
    log(f"model {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}x"
        f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} params={cfg.n_params()}")
    log(f"reduced: n_layers {full.n_layers}->{FOUR_LAYERS}, vocab_size "
        f"{full.vocab_size}->{FOUR_VOCAB} (so that the one-device "
        "reference holds all 4 pods' state)")
    return cfg


def four_chip_batches(cfg, n: int):
    pipeline = AnytimePipeline(cfg=cfg, n_workers=n, samples_per_worker=1,
                               seq_len=FOUR_SEQ, seed=0)
    return [pipeline.next_global_batch() for _ in range(FOUR_STEPS)]


def pushed_f32(ar, k: int) -> np.ndarray:
    """The f32 gradient that ring slot ``k``'s int8 push quantized:
    q * scale plus the new error-feedback residual, which is the pod
    gradient plus the old residual (the same on both sides of a
    comparison from one state)."""
    q = np.asarray(ar.ring[k], np.float64)
    return (q * np.asarray(ar.scales[k], np.float64)[..., None]
            + np.asarray(ar.residual, np.float64))


def check_pushed_gradient(layout, params_struct, k: int, got, want,
                          checks: Checks) -> None:
    """Compares the gradient pushed into slot ``k`` pod by pod, then
    says where the int8 rounding of the two differs: per leaf, the
    differing entries and the leaf's largest |g| against the slot's."""
    g, w = pushed_f32(got, k), pushed_f32(want, k)
    errs = [rel_max_err(gp, wp) for gp, wp in zip(g, w)]
    checks.check("a_pushed_gradient_matches_one_device",
                 max(errs) <= GRAD_RTOL,
                 f"per-pod rel max err {errs!r}, tolerance {GRAD_RTOL}")
    differ = (np.asarray(got.ring[k]) != np.asarray(want.ring[k])).sum(
        axis=(0, 2))
    per_leaf = np.bincount(layout.row_to_leaf, weights=differ,
                           minlength=layout.n_leaves + 1)
    log(f"(a) pushed int8 entries bit-equal fraction "
        f"{float(1.0 - per_leaf.sum() / got.ring[k].size)!r}")
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params_struct)[0]]
    names.append("(padding)")
    top = float(np.max(np.abs(w)))
    for i in np.argsort(-per_leaf)[:5]:
        if per_leaf[i] == 0:
            break
        amax = float(np.max(np.abs(w[:, layout.row_to_leaf == i])))
        log(f"(a)   {names[i]}: {int(per_leaf[i])} int8 entries differ; "
            f"leaf max |g| {amax / top!r} of the slot's largest")


def run_pod_exchange(cfg, devices, checks: Checks) -> None:
    """(a) ambdg, int8 pod compression, on a pod=4 mesh."""
    n = len(devices)
    rc = RunConfig(
        model=cfg,
        shape=dataclasses.replace(TRAIN_4K, seq_len=FOUR_SEQ,
                                  global_batch=n),
        mesh=MeshConfig(n_pods=n, data=1, model=1),
        ambdg=AmbdgConfig(tau=1, n_microbatches=1, b_bar=float(n),
                          pod_compression="int8"))
    model = build_model(cfg)
    strategy = api.build(model, rc)
    mesh = make_mesh(rc.mesh.shape, rc.mesh.axis_names, devices=devices)
    st_specs = dist.state_specs(model, rc, strategy.init_state)
    b_specs = dist.batch_specs(model, rc)
    log(f"(a) ambdg int8, {n} pods x 1 sample x {FOUR_SEQ} tokens, "
        f"{FOUR_STEPS} steps on the pod={n} mesh")

    state = jax.device_put(strategy.init_state(jax.random.PRNGKey(rc.seed)),
                           dist.to_shardings(st_specs, mesh))
    compiled = {}

    def sharded_step(state, b):
        batch = jax.device_put(b, dist.to_shardings(b_specs, mesh))
        structure = jax.tree.structure(state)
        if structure not in compiled:
            t0 = time.perf_counter()
            jitted = dist.jit_train_step(strategy.train_step, st_specs,
                                         b_specs, state, batch, mesh)
            with jax.set_mesh(mesh):
                compiled[structure] = jitted.lower(state, batch).compile()
            text = compiled[structure].as_text()
            phase = state.arena.phase
            gathers = len(re.findall(r"all-gather(?:-start)?\(", text))
            log(f"(a) phase {phase}: compiled in "
                f"{time.perf_counter() - t0:.1f} s (informal), "
                f"{text.count('tpu_custom_call')} tpu_custom_call, "
                f"{gathers} all-gather ops")
            checks.check(f"a_kernels_and_collective_phase{phase}",
                         "tpu_custom_call" in text and gathers > 0)
        return compiled[structure](state, batch)

    batches = four_chip_batches(cfg, n)
    counts = []
    for i, b in enumerate(batches[:-1]):
        state, metrics = sharded_step(state, b)
        counts.append(float(metrics["applied_count"]))
        log(f"(a) step {i + 1}: loss={float(metrics['loss'])!r} "
            f"applied_count={counts[-1]!r}")
    checks.check("a_applied_after_tau", counts[0] == 0
                 and all(c > 0 for c in counts[1:]), f"{counts}")

    # the last step twice from the same state: on the mesh, and as one
    # program on one device (``sharding_profile(None)``: the pod
    # exchange is a local fold, the kernels run unsharded)
    params_struct = jax.eval_shape(lambda k: model.init(k)[0],
                                   jax.random.PRNGKey(0))
    layout = arena.make_layout(params_struct)
    push_i = state.arena.phase
    one_device = jax.device_put(jax.device_get(state), devices[0])
    with sharding_profile(None):
        want, _ = jax.jit(strategy.train_step)(
            one_device, jax.tree.map(jnp.asarray, batches[-1]))
    want = jax.device_get(want)
    state, _ = sharded_step(state, batches[-1])
    slots = state.arena.ring
    spread = [len(s.sharding.device_set) for s in slots]
    local = [sorted({tuple(x.data.shape) for x in s.addressable_shards})
             for s in slots]
    log(f"(a) ring slots: dtype {slots[0].dtype}, global "
        f"{tuple(slots[0].shape)}, devices per slot {spread}, "
        f"per-device blocks {local}")
    checks.check("a_ring_spread_over_devices",
                 all(d == n for d in spread)
                 and all(shapes == [(1,) + tuple(slots[0].shape[1:])]
                         for shapes in local))
    got = jax.device_get(state)
    # the master's outputs read only the popped slot and z: the same
    # inputs on both sides
    for name, g, w in (("params", got.params, want.params),
                       ("z", got.opt_state.z, want.opt_state.z)):
        err = tree_rel_max_err(g, w)
        checks.check(f"a_master_{name}_matches_one_device",
                     err <= EXCHANGE_RTOL, f"rel max err {err!r}")
    check_pushed_gradient(layout, params_struct, push_i, got.arena,
                          want.arena, checks)

    # the exchange alone on identical inputs: the int8 ring rotation
    # under shard_map on the mesh vs the unsharded kernel on one device,
    # bit for bit (the chip twin of the CPU interpret-mode test)
    leaves, treedef = jax.tree.flatten(params_struct)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    pod_counts = jnp.full((n,), 4.0)

    def grads_at(t):
        return treedef.unflatten([
            jax.random.normal(jax.random.fold_in(k, t), (n,) + l.shape)
            for k, l in zip(keys, leaves)])

    @functools.partial(jax.jit, static_argnums=2)
    def rotate(ar, g, impl):
        return arena.push_pop(layout, ar, g, pod_counts, "int8", impl=impl)

    ar_s = jax.device_put(arena.init_arena(layout, rc.ambdg.tau, n, "int8"),
                          dist.to_shardings(st_specs.arena, mesh))
    ar_r = arena.init_arena(layout, rc.ambdg.tau, n, "int8")
    for t in range(FOUR_STEPS):
        g = grads_at(t)
        with jax.set_mesh(mesh), sharding_profile(rc.mesh):
            gs_s, _, ar_s = rotate(ar_s, g, "pallas_sharded")
        gs_r, _, ar_r = rotate(ar_r, g, "pallas")
        # ring state bit for bit; the popped sum may differ by isolated
        # ULPs where one side contracts its dequantize into the pod fold
        # (docs/arena.md)
        same = all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
                   for x, y in zip(jax.tree.leaves(ar_s),
                                   jax.tree.leaves(ar_r)))
        err = rel_max_err(jax.device_get(gs_s), jax.device_get(gs_r))
        checks.check(f"a_exchange_rotation{t + 1}",
                     same and err <= EXCHANGE_RTOL,
                     f"ring state bit-equal {same}, popped sum rel max "
                     f"err {err!r}")


def run_gossip(cfg, devices, checks: Checks) -> None:
    """(b) decentralized, 4 workers over ppermute vs the dense fold."""
    n = len(devices)
    model = build_model(cfg)
    rc = RunConfig(
        model=cfg,
        shape=dataclasses.replace(TRAIN_4K, seq_len=FOUR_SEQ,
                                  global_batch=n),
        mesh=MeshConfig(n_pods=1, data=1, model=1),
        ambdg=AmbdgConfig(tau=1, n_microbatches=1, b_bar=float(n)),
        strategy="decentralized",
        consensus=ConsensusConfig(topology="ring", n_workers=n,
                                  gossip_impl="shard_map",
                                  debug_messages=True))
    s = api.build(model, rc)
    oracle = jax.jit(lambda m0: consensus.run_consensus_fold(
        m0, "ring", s.rounds))
    step = jax.jit(s.train_step)
    state = s.init_state(jax.random.PRNGKey(rc.seed))
    log(f"(b) decentralized ring, {n} workers, {s.rounds} gossip rounds")
    for i, b in enumerate(four_chip_batches(cfg, n)):
        state, m = step(state, jax.tree.map(jnp.asarray, b))
        same = bool(np.array_equal(np.asarray(state.z),
                                   np.asarray(oracle(m["gossip_m0"]))))
        log(f"(b) step {i + 1}: loss={float(m['loss'])!r} "
            f"z bit-equal to the dense fold {same}")
        checks.check(f"b_step{i + 1}_matches_dense_fold", same)
    spread = len(state.z.sharding.device_set)
    log(f"(b) z {tuple(state.z.shape)} on {spread} devices")
    checks.check("b_z_spread_over_devices", spread == n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip pod-exchange and gossip "
                         "phases")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r}); "
              "this smoke run needs a TPU and has no CPU fallback",
              file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:want]
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    checks = Checks()
    if args.four_chips:
        cfg = four_chip_config()
        run_pod_exchange(cfg, devices, checks)
        run_gossip(cfg, devices, checks)
    else:
        run_one_chip(checks)
    if checks.failed:
        print(f"chip_smoke: FAILED checks: {checks.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
