"""One run of one cell: set-up, the window, the readings, the
comparison and the result line (``bench/run.py`` is its command)."""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from harness import compare, drive, spec
from harness.peaks import peaks


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader (``metrics/<name>.py``) reads."""
    cell: spec.Cell
    events: Optional[list]              # trace.Event list of the window
    window: Optional[Tuple[float, float]]
    steps: int                          # steps in the traced window
    n_params: int
    tokens_per_step: int
    peaks: Dict[str, float]
    compile_s: float                    # XLA compile seconds in set-up
    setup: Dict[str, float]             # set-up seconds by part
    delays: Optional[List[int]]         # every step's delay draw, set-up
                                        # then window (variable rings)
    first_window_step: int


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup_split(r: drive.Readings, t_start: float) -> Dict[str, float]:
    """Set-up seconds by part: JAX and the chip up; the launcher's
    objects; the weights; the first step (state, weights, the compile or
    cache load of its program); the later steps before the window (the
    other ring phases' programs, the correctness readings, the timed
    warm-up); and, across them, XLA's compile seconds."""
    first, last = min(r.step_done), max(r.step_done)
    return {"backend_s": drive.TIMES.get("backend_s", 0.0),
            "build_s": r.call_start - t_start
            - drive.TIMES.get("backend_s", 0.0),
            "weights_s": drive.TIMES.get("weights_s", 0.0),
            "first_step_s": r.step_done[first] - r.call_start,
            f"steps_2_to_{last}_s": r.step_done[last] - r.step_done[first],
            "compile_s": r.setup_compile_s}


def tokens_per_step(t: Dict[str, Any]) -> int:
    return t["n_workers"] * t["samples_per_worker"] * t["seq_len"]


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        devices, t_start: float) -> Dict[str, Any]:
    """The result line's object. ``t_start``: the process's start on
    ``time.perf_counter``'s clock, where ``setup_s`` is counted from."""
    import jax
    dev = devices[0]
    pk = peaks(dev.device_kind)
    compare.check_traffic(cell.traffic)
    compiles = drive.CompileCounter()

    model, rc, loop, struct = drive.build(cell, seed)
    probes = drive.Probes(struct)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    driver = drive.Driver(loop, probes, drive.n_ring_phases(cell.traffic),
                          seconds, compiles, trace_dir)
    r = drive.run_train(model, rc, loop, driver)
    setup_s = r.window_start - t_start
    del model, driver
    gc.collect()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"setup {setup_s:.3f} s, warm step {r.warm_step_s:.4f} s, window "
        f"{r.window_steps} steps in {r.window_s:.3f} s, compiles in "
        f"window {r.compiles_in_window}, peak {peak} B")
    split = setup_split(r, t_start)
    log("setup split: " + json.dumps(split))

    events = win = None
    if trace_dir is not None:
        from harness import trace as tr
        events = tr.load_events(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = tr.window(events)

    ref = compare.follow(cell, struct, seed, r.batches, r.delays,
                         drive.N_CHECK)
    got = compare.Side(r.losses, r.grad_norms, r.param_norms)
    numbers = compare.gaps(got, ref)
    limits = {k: float(v) for k, v in cell.limits["limits"].items()}
    correct = (bool(compare.judge(numbers, limits))
               and r.compiles_in_window == 0
               and math.isfinite(r.final_loss))
    log(f"losses program {r.losses!r} reference {ref.losses!r}")
    log(f"param_gap by step {compare.param_step_gaps(got, ref)!r}")

    tps = tokens_per_step(cell.traffic)
    n_params = sum(int(math.prod(x.shape)) for x in jax.tree.leaves(struct))
    result: Dict[str, Any] = {
        "correct": correct, "attempted": r.window_steps,
        "failed": 0 if math.isfinite(r.final_loss) else 1}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace_dir is None:
        rate = r.window_steps * tps / r.window_s
        flops = cell.reference().flops_per_token(cell.config,
                                                 cell.traffic["seq_len"])
        values = {
            "train_tokens_per_s": (rate, "tokens/s"),
            "mfu": (100.0 * flops * rate / (len(devices)
                                            * pk["bf16_flops_per_s"]), "%"),
            "peak_hbm_gib": (peak / 2 ** 30, "GiB"),
            "setup_s": (setup_s, "s"),
        }
        result["metrics"] = {m["name"]: {"value": values[m["name"]][0],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from harness import trace as tr
        delays = (r.delays + r.window_delays if r.window_delays is not None
                  else None)
        ctx = Context(cell=cell, events=events, window=win,
                      steps=r.window_steps, n_params=n_params,
                      tokens_per_step=tps, peaks=pk,
                      compile_s=r.setup_compile_s, setup=split,
                      delays=delays,
                      first_window_step=len(r.delays) + 1)
        result["metrics"] = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = tr.mean_busy_s(events, win)
        device["window_s"] = (win[1] - win[0]) / 1e9
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.top_ops(events, win)],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps(events, win)]}
    result["device"] = device
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    result["checks"]["compiles_in_window"] = {
        "value": r.compiles_in_window, "limit": 0}
    return result
