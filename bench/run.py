"""AMB-DG training benchmark on the TPU: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout. The cell (configuration, traffic,
limits, metrics) is found by name from ``BENCHMARK.json``. One process:
it makes the weights on the device from the seed, drives the training
loop (``repro.train.loop.train``) through the steps the correctness
check follows and a warm-up that compiles every ring phase, measures
``--seconds`` of steps, reads peak memory, frees the program's state
and runs the plain reference. With ``--trace 0`` the last line of
standard output holds the end-to-end metrics, with ``--trace 1`` the
per-layer ones, read from a profiler trace of a short window. The
numbers that decide ``correct`` are printed last on standard error and
last in the result line, each beside its limit.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR.parent / "src"), str(BENCH_DIR)]
# the persistent compile cache sits at a fixed path inside the checkout,
# whatever the environment names; JAX reads it when it is imported, and
# the program's own enable_compile_cache takes it from there
CACHE_DIR = BENCH_DIR.parent / ".jax_cache"
CACHE_DIR.mkdir(exist_ok=True)      # JAX writes no entry into a missing one
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

from harness import drive, runner, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax
    if jax.default_backend() != "tpu":
        runner.log(f"bench: no TPU found (JAX backend "
                   f"{jax.default_backend()!r}); this benchmark measures "
                   "the chip and has no CPU fallback")
        return 2
    devices = jax.devices()
    if len(devices) < cell.chips:
        runner.log(f"bench: {cell.name} needs {cell.chips} chips, found "
                   f"{len(devices)}")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()              # the checkout's, set above
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    drive.TIMES["backend_s"] = time.perf_counter() - T_START
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        devices[:cell.chips], T_START)
    for name, c in result["checks"].items():
        runner.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
