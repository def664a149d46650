# Virtual-device count for the dry-run compiles. No-clobber: a count
# already pinned in XLA_FLAGS (CI legs, the matrix harness, a caller)
# is respected; otherwise REPRO_HOST_DEVICES or the 512-chip default.
# Must run before the first jax backend touch, hence before imports.
from repro.launch.xla import ensure_host_platform_device_count
HOST_DEVICES = ensure_host_platform_device_count(default=512)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the AMB-DG train step (train shapes) or the
serve step (decode shapes) with full production shardings, lowers it
against ShapeDtypeStruct inputs (no allocation), compiles, and records:

  * memory_analysis()  — bytes per device (proves the cell fits HBM)
  * cost_analysis()    — FLOPs / bytes accessed (roofline compute+memory)
  * the collective byte count parsed from the optimized HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — roofline's collective term.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as C
from repro.configs.base import (AmbdgConfig, MeshConfig, RunConfig,
                                ShapeConfig, SHAPES)
from repro.dist import (batch_specs, jit_train_step, shapes_and_axes,
                        state_specs, to_shardings)
from repro.dist.sharding import spec_for
# the collective census lives in launch.hlo (no import side effects)
# so the benchmarks can use it without this module's forced device
# count; re-exported here for existing callers (benchmarks.roofline).
from repro.launch.hlo import collective_bytes  # noqa: F401
from repro.launch.mesh import make_mesh, mesh_config, mesh_label
from repro.models import build_model


# per-cell capacity overrides: deeper microbatching for the largest
# train cells (keeps activation residuals under the 16 GB v5e HBM)
CELL_OVERRIDES = {
    ("mixtral-8x22b", "train_4k"): {"n_microbatches": 16},
    ("paligemma-3b", "train_4k"): {"n_microbatches": 16},
    ("seamless-m4t-large-v2", "train_4k"): {"n_microbatches": 16},
}


def build_run_config(arch: str, shape_name: str, multi_pod: bool,
                     strategy: str = "ambdg", **overrides) -> RunConfig:
    for k, v in CELL_OVERRIDES.get((arch, shape_name), {}).items():
        overrides.setdefault(k, v)
    model_cfg = C.get_config(arch)
    if "model_cfg" in overrides:
        model_cfg = overrides.pop("model_cfg")
    shape = SHAPES[shape_name]
    ambdg = overrides.pop("ambdg", AmbdgConfig(
        tau=1, n_microbatches=overrides.pop("n_microbatches", 8)))
    mesh = overrides.pop("mesh", None) or mesh_config(multi_pod)
    return RunConfig(model=model_cfg, shape=shape,
                     mesh=mesh, ambdg=ambdg,
                     strategy=strategy,
                     remat=overrides.pop("remat", "dots"), **overrides)


def lower_train(rc: RunConfig, mesh):
    from repro import api
    model = build_model(rc.model)
    strategy = api.build(model, rc)
    init_state, train_step = strategy.init_state, strategy.train_step
    st_specs = state_specs(model, rc, init_state)
    b_specs = batch_specs(model, rc)
    state_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))

    def shard_struct(specs, shapes):
        return jax.tree.map(
            lambda sp, sh: jax.ShapeDtypeStruct(
                sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
            specs, shapes, is_leaf=lambda x: isinstance(x, P))

    state_in = shard_struct(st_specs, state_shapes)
    batch_shapes = model.input_specs(rc.shape.global_batch, rc.shape.seq_len)
    if rc.delay.process != "fixed":
        # stochastic staleness: the host loop ships one delay draw per
        # step; the lowered program takes it as a replicated scalar
        batch_shapes = dict(batch_shapes,
                            delay=jax.ShapeDtypeStruct((), jnp.int32))
        b_specs = dict(b_specs, delay=P())
    if rc.batch_schedule.schedule != "fixed":
        # adaptive minibatch schedule: the host loop ships one target
        # draw per step; alpha takes it as a replicated f32 scalar
        batch_shapes = dict(batch_shapes,
                            b_sched=jax.ShapeDtypeStruct((), jnp.float32))
        b_specs = dict(b_specs, b_sched=P())
    batch_in = shard_struct(b_specs, batch_shapes)

    jitted = jit_train_step(train_step, st_specs, b_specs, state_shapes,
                            batch_shapes, mesh)
    with jax.set_mesh(mesh):    # traced under the mesh: constrain()
        lowered = jitted.lower(state_in, batch_in)
    return lowered


def lower_serve(rc: RunConfig, mesh):
    """The continuous-batching decode step with a seq_len-deep cache:
    per-slot (B,) positions + the active-slot mask, exactly the
    program ``serve.engine`` jits at smoke scale."""
    from repro.serve.engine import continuous_decode_step
    model = build_model(rc.model)
    B, S = rc.shape.global_batch, rc.shape.seq_len

    cache_shapes, cache_axes = shapes_and_axes(
        lambda: model.init_decode_state(B, S))
    params_shapes, params_axes = shapes_and_axes(
        model.init, jax.random.PRNGKey(0))

    def resolve(ax, sh):
        return spec_for(tuple(ax), tuple(sh.shape), rc.mesh,
                        profile="serve")

    from repro.dist.sharding import _is_axes_leaf
    p_specs = jax.tree.map(resolve, params_axes, params_shapes,
                           is_leaf=_is_axes_leaf)
    c_specs = jax.tree.map(resolve, cache_axes, cache_shapes,
                           is_leaf=_is_axes_leaf)

    def shard_struct(specs, shapes):
        return jax.tree.map(
            lambda sp, sh: jax.ShapeDtypeStruct(
                sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
            specs, shapes, is_leaf=lambda x: isinstance(x, P))

    tok_spec = spec_for(("batch", None), (B, 1), rc.mesh,
                        profile="serve")
    row_spec = spec_for(("batch",), (B,), rc.mesh, profile="serve")
    serve_in = (
        shard_struct(p_specs, params_shapes),
        shard_struct(c_specs, cache_shapes),
        jax.ShapeDtypeStruct((B, 1), jnp.int32,
                             sharding=NamedSharding(mesh, tok_spec)),
        jax.ShapeDtypeStruct((B,), jnp.int32,
                             sharding=NamedSharding(mesh, row_spec)),
        jax.ShapeDtypeStruct((B,), jnp.bool_,
                             sharding=NamedSharding(mesh, row_spec)),
    )

    def serve_step(params, cache, tokens, pos, active):
        from repro.dist.context import sharding_profile
        with sharding_profile(rc.mesh, "serve"):
            return continuous_decode_step(model.decode_step, params,
                                          cache, tokens, pos, active)

    with jax.set_mesh(mesh):
        jitted = jax.jit(
            serve_step,
            in_shardings=tuple(jax.tree.map(
                lambda s: s.sharding, x) for x in serve_in),
            out_shardings=(NamedSharding(mesh, row_spec),
                           to_shardings(c_specs, mesh)),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(*serve_in)
    return lowered


def lower_publish_pop(rc: RunConfig, mesh):
    """The server side of the weight-publication channel at production
    shape: dequantize one popped int8 snapshot (per-row bf16 scales)
    and unflatten it back to the sharded serve-profile parameter tree
    — the program an inference pod runs on every ``refresh_weights``.
    Shape depends only on the arch (ring depth is host metadata)."""
    from repro.core import arena as arena_mod
    from repro.optim.compression import dequantize_int8_rows
    model = build_model(rc.model)
    params_shapes, params_axes = shapes_and_axes(
        model.init, jax.random.PRNGKey(0))
    layout = arena_mod.make_layout(params_shapes)
    rows = layout.rows

    from repro.dist.sharding import _is_axes_leaf
    p_specs = jax.tree.map(
        lambda ax, sh: spec_for(tuple(ax), tuple(sh.shape), rc.mesh,
                                profile="serve"),
        params_axes, params_shapes, is_leaf=_is_axes_leaf)
    q_spec = spec_for(("flat", None), (rows, 128), rc.mesh,
                      profile="serve")
    s_spec = spec_for(("flat",), (rows,), rc.mesh, profile="serve")

    def pop(q, s):
        from repro.dist.context import sharding_profile
        with sharding_profile(rc.mesh, "serve"):
            w = dequantize_int8_rows(q, s)
            return arena_mod.unflatten_tree(layout, w, cast=True)

    pop_in = (
        jax.ShapeDtypeStruct((rows, 128), jnp.int8,
                             sharding=NamedSharding(mesh, q_spec)),
        jax.ShapeDtypeStruct((rows,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, s_spec)),
    )
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            pop,
            in_shardings=tuple(x.sharding for x in pop_in),
            out_shardings=to_shardings(p_specs, mesh),
        )
        lowered = jitted.lower(*pop_in)
    return lowered


def resolve_cell_rc(arch: str, shape_name: str, multi_pod: bool,
                    rc: Optional[RunConfig] = None,
                    strategy: str = "ambdg",
                    gossip_compression: str = "none",
                    delay_process: str = "fixed",
                    tau_max: Optional[int] = None,
                    batch_schedule: str = "fixed",
                    mesh: Optional[MeshConfig] = None) -> RunConfig:
    """The cell's RunConfig from the CLI-style knobs (split out of
    ``run_cell`` so the override semantics are testable without a
    compile).

    ``tau_max`` is an EXPLICIT-ONLY override: ``None`` (the default)
    keeps an explicit ``rc``'s own ``rc.delay.tau_max`` (falling back
    to 4 only when that is itself unset), while any integer — zero
    included — is used verbatim.  The pre-PR-10 ``tau_max or
    rc.delay.tau_max or 4`` treated a caller's explicit 0 as "unset"
    and silently replaced a configured cap with the default.
    """
    if rc is None:
        overrides = {}
        if mesh is not None:
            overrides["mesh"] = mesh
        if gossip_compression != "none":
            from repro.configs.base import ConsensusConfig
            overrides["consensus"] = ConsensusConfig(
                compression=gossip_compression)
        if delay_process != "fixed":
            from repro.configs.base import DelayConfig
            overrides["delay"] = DelayConfig(
                process=delay_process,
                tau_max=4 if tau_max is None else tau_max)
        if batch_schedule != "fixed":
            from repro.configs.base import BatchScheduleConfig
            overrides["batch_schedule"] = BatchScheduleConfig(
                schedule=batch_schedule)
        return build_run_config(arch, shape_name, multi_pod,
                                strategy=strategy, **overrides)
    if mesh is not None:
        rc = rc.replace(mesh=mesh)
    if gossip_compression != "none":
        # an explicit rc must not silently shadow the knob
        rc = rc.replace(consensus=dataclasses.replace(
            rc.consensus, compression=gossip_compression))
    if delay_process != "fixed":
        # replace, not a fresh DelayConfig: the caller's other
        # delay fields (delay_min, seeding, adaptive_alpha) must
        # not silently reset to defaults
        resolved = (tau_max if tau_max is not None
                    else rc.delay.tau_max or 4)
        rc = rc.replace(delay=dataclasses.replace(
            rc.delay, process=delay_process, tau_max=resolved))
    if batch_schedule != "fixed":
        rc = rc.replace(batch_schedule=dataclasses.replace(
            rc.batch_schedule, schedule=batch_schedule))
    return rc


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rc: Optional[RunConfig] = None, verbose: bool = True,
             strategy: str = "ambdg",
             gossip_compression: str = "none",
             delay_process: str = "fixed",
             tau_max: Optional[int] = None,
             batch_schedule: str = "fixed",
             mesh_cfg: Optional[MeshConfig] = None,
             want_hlo: bool = False) -> Dict:
    rc = resolve_cell_rc(arch, shape_name, multi_pod, rc=rc,
                         strategy=strategy,
                         gossip_compression=gossip_compression,
                         delay_process=delay_process, tau_max=tau_max,
                         batch_schedule=batch_schedule, mesh=mesh_cfg)
    mesh = make_mesh(rc.mesh.shape, rc.mesh.axis_names)
    t0 = time.time()
    publish_pop = None
    if rc.shape.kind in ("train", "prefill"):
        # prefill cost ~ the forward of the train step; we lower the
        # train step for train_4k and a loss-less forward for prefill
        lowered = (lower_train(rc, mesh) if rc.shape.kind == "train"
                   else lower_prefill(rc, mesh))
    else:
        lowered = lower_serve(rc, mesh)
        # decode cells also compile the per-refresh publish pop
        # (dequantize + unflatten at the serve shardings) — the other
        # half of the train-while-serve channel on this mesh
        pp = lower_publish_pop(rc, mesh).compile()
        pp_cost = pp.cost_analysis()
        pp_text = pp.as_text()
        publish_pop = {
            "flops": float(pp_cost.get("flops", -1)),
            "bytes_accessed": float(pp_cost.get("bytes accessed", -1)),
            "collectives": collective_bytes(pp_text),
        }
        if want_hlo:
            publish_pop["hlo_text"] = pp_text
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    # which master delay-ring path this cell lowered with: v2 per-slot
    # ring everywhere; "pallas_sharded" = the shard_map'd fused kernel
    # (multi-pod TPU), "pallas" = single-pod TPU, "ref" = XLA (CPU)
    from repro.core import arena as arena_mod
    from repro.dist.context import sharding_profile
    from repro.kernels import resolve_impl
    with jax.set_mesh(mesh), \
            sharding_profile(rc.mesh if rc.mesh.n_devices > 1 else None):
        ring_impl = resolve_impl("auto", pod_shard_map=True)
    result = {
        "arch": arch, "shape": shape_name,
        # derived from the cell's ACTUAL mesh — an explicit rc with a
        # custom mesh used to be labeled 16x16/2x16x16 regardless
        "mesh": mesh_label(rc.mesh),
        "strategy": rc.strategy,
        "master": {"ring_version": arena_mod.RING_VERSION,
                   "ring_impl": ring_impl,
                   # delay-tolerant ring cells read all tau_max+1 slots
                   # per step (masked fold) instead of one static slot
                   "delay_process": rc.delay.process,
                   "tau_max": rc.delay.tau_max,
                   # adaptive b(t) cells take one extra replicated f32
                   # scalar (batch["b_sched"]) that alpha consumes
                   "batch_schedule": rc.batch_schedule.schedule},
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if publish_pop is not None:
        result["publish_pop"] = publish_pop
    if want_hlo:
        # the matrix runner's HLO invariants read the optimized text;
        # callers must pop this before serializing the row
        result["hlo_text"] = hlo_text
    if verbose:
        printable = {k: v for k, v in result.items() if k != "hlo_text"}
        if want_hlo and publish_pop is not None:
            printable["publish_pop"] = {
                k: v for k, v in publish_pop.items() if k != "hlo_text"}
        print(json.dumps(printable))
    return result


def lower_prefill(rc: RunConfig, mesh):
    """Prefill = full-sequence forward producing last-position logits +
    (implicitly) the cache; we lower the forward pass at the prefill
    shape — the compute/memory-dominant piece."""
    model = build_model(rc.model)
    B, S = rc.shape.global_batch, rc.shape.seq_len

    def fwd(params, batch):
        from repro.dist.context import sharding_profile
        with sharding_profile(rc.mesh):
            loss_sum, aux = model.loss(params, batch)
        return loss_sum  # forward dominates; keeps one program per cell

    # prefill has no labels/backward: lower loss forward only via
    # jax.eval_shape-compatible wrapper (no grad)
    params_shapes, params_axes = shapes_and_axes(
        model.init, jax.random.PRNGKey(0))
    from repro.dist.sharding import _is_axes_leaf
    p_specs = jax.tree.map(
        lambda ax, sh: spec_for(tuple(ax), tuple(sh.shape), rc.mesh),
        params_axes, params_shapes, is_leaf=_is_axes_leaf)
    b_specs = batch_specs(model, rc)
    batch_shapes = model.input_specs(B, S)

    def shard_struct(specs, shapes):
        return jax.tree.map(
            lambda sp, sh: jax.ShapeDtypeStruct(
                sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
            specs, shapes, is_leaf=lambda x: isinstance(x, P))

    with jax.set_mesh(mesh):
        jitted = jax.jit(
            fwd,
            in_shardings=(to_shardings(p_specs, mesh),
                          to_shardings(b_specs, mesh)),
            out_shardings=NamedSharding(mesh, P()),
        )
        lowered = jitted.lower(shard_struct(p_specs, params_shapes),
                               shard_struct(b_specs, batch_shapes))
    return lowered


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="ambdg",
                    help="algorithm variant to lower (Strategy registry)")
    ap.add_argument("--gossip-compression", default="none",
                    choices=("none", "int8"),
                    help="decentralized: gossip message compression")
    ap.add_argument("--delay-process", default="fixed",
                    choices=("fixed", "jitter", "heavy_tail", "bursty"),
                    help="lower the ambdg cells with the delay-tolerant "
                         "ring for this stochastic staleness process")
    ap.add_argument("--tau-max", type=int, default=None,
                    help="staleness cap for --delay-process (explicit "
                         "values — 0 included — are used verbatim; "
                         "default: the cell's configured cap, else 4)")
    ap.add_argument("--mesh", default=None,
                    help="mesh shape spec (DxM or PxDxM, e.g. 8x8 or "
                         "2x16x16); default: the production mesh "
                         "(16x16, or 2x16x16 with --multi-pod)")
    ap.add_argument("--batch-schedule", default="fixed",
                    choices=("fixed", "linear", "adadamp", "delay_aware"),
                    help="lower the train cells with the adaptive "
                         "minibatch schedule input (b_sched scalar)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in C.ARCH_IDS:
            for shape in C.applicable_shapes(arch):
                cells.append((arch, shape.name))
    else:
        cells.append((args.arch, args.shape))

    mesh_cfg = None
    if args.mesh is not None:
        from repro.launch.mesh import parse_mesh
        mesh_cfg = parse_mesh(args.mesh)

    results, failures = [], []
    for arch, shape in cells:
        try:
            results.append(run_cell(
                arch, shape, args.multi_pod, strategy=args.strategy,
                gossip_compression=args.gossip_compression,
                delay_process=args.delay_process, tau_max=args.tau_max,
                batch_schedule=args.batch_schedule, mesh_cfg=mesh_cfg))
        except Exception as e:  # noqa: BLE001
            failures.append({"arch": arch, "shape": shape,
                             "error": repr(e)[:500]})
            print(f"FAIL {arch} {shape}: {e!r}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"results": results, "failures": failures}, f,
                      indent=1)
    print(f"\n{len(results)} cells OK, {len(failures)} failed "
          f"({'multi-pod' if args.multi_pod else 'single-pod'})")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
