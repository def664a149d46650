"""Readings that set a cell's correctness limits (``limits/*.json``).

    python3 bench/control.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,...,12 --fault-seeds 1,2,3

For each seed, in one process: the program's first steps through the
training loop, exactly as ``bench/run.py`` drives them but with no
window, and the float32 reference on the same batches; the
numbers of ``harness/compare.py`` are the program's lower readings. For
each control seed also the reference computed in fp8 in the program's
place (the control); for each fault seed the reference with half of
each batch left out and the mean taken over the rest, and the reference
with every gradient popped one step late (faults). A step that returns
its state unchanged reads 1 on grad_gap by construction and needs no
run. One JSON line per reading on standard output, with param_gap step
by step.

Needs a TPU, like the benchmark itself; the benchmark's runs never run
this.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR.parent / "src"), str(BENCH_DIR)]
CACHE_DIR = BENCH_DIR.parent / ".jax_cache"
CACHE_DIR.mkdir(exist_ok=True)      # JAX writes no entry into a missing one
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

import numpy as np  # noqa: E402

from harness import compare, drive, spec  # noqa: E402


def program_readings(cell, seed: int):
    """The program's first steps for ``seed``: (struct, Readings)."""
    model, rc, loop, struct = drive.build(cell, seed)
    driver = drive.Driver(loop, drive.Probes(struct),
                          drive.n_ring_phases(cell.traffic), None,
                          drive.CompileCounter())
    return struct, drive.run_train(model, rc, loop, driver)


def worst_leaves(got, ref, names, n: int = 4):
    """The first gradient's median leaf gap, the worst leaf's among
    leaves the reference moves, and the ``n`` worst leaves by name."""
    d = compare.leaf_gaps(got.grad_norms, ref.grad_norms)
    rg = ref.grad_norms
    moving = rg >= compare.ZERO_GRAD * float(np.median(rg))
    worst = np.argsort(-d)[:n]
    return {"median": float(np.median(d)),
            "worst_moving": float(np.max(np.where(moving, d, 0.0))),
            "worst": [[names[i], float(d[i]), float(rg[i]),
                       bool(moving[i])] for i in worst]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    compare.check_traffic(cell.traffic)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        gc.collect()           # the last seed's program state is freed
        t0 = time.perf_counter()
        struct, r = program_readings(cell, seed)
        names = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(struct)[0]]
        follow = functools.partial(compare.follow, cell, struct, seed,
                                   r.batches, r.delays, drive.N_CHECK)
        ref = follow()
        sides = {"program": compare.Side(r.losses, r.grad_norms,
                                         r.param_norms)}
        if seed in controls:
            sides["fp8"] = follow(precision="fp8")
        if seed in faults:
            sides["half_batch"] = follow(half_batch=True)
            sides["late_pop"] = follow(late_pop=True)
        for side, got in sides.items():
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "side": side, **compare.gaps(got, ref),
                              "param_gap_steps":
                                  compare.param_step_gaps(got, ref),
                              "grad_leaves": worst_leaves(got, ref, names),
                              "delays": r.delays[:drive.N_CHECK],
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
