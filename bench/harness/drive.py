"""Drives the training loop, ``repro.train.loop.train``, as the
launcher sets it up, through its first steps, a warm-up and the
measured window: one call of ``train`` with one compiled step and one
state.

``train`` offers no hook to read its state and no end on the clock, so
the benchmark passes a ``log_fn`` (``Driver``) and reads, at a log
point, the loop's own locals (the state, the batch it fed, the delay
process) from the calling frame without changing them. Per-step log
points (a host sync each) cover only the first steps and the warm-up;
then the driver moves the next log point to the window's last step, so
the window runs with no sync but the one that ends it, and ends the
call there by raising ``WindowClosed``.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import math
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from harness.spec import Cell

BIG = 10 ** 9          # a step count no run reaches: the window ends it
N_CHECK = 3            # steps the correctness comparison follows
N_TIMED_WARM = 3       # warm steps timed to size the window
MIN_WINDOW_STEPS = 3
TRACE_SECONDS = 2.0    # a traced window is this long, or 3 steps
# set-up seconds of this process by part (one run a process), for the
# log: JAX and the chip up, the weights made, the first step
TIMES: Dict[str, float] = {}


class WindowClosed(Exception):
    pass


class CompileCounter:
    """Counts XLA backend compiles and their seconds (JAX's own
    monitoring event)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def model_config(config: Dict[str, Any]):
    """The program's ModelConfig from the configuration file."""
    from repro.configs.base import ModelConfig, SSMConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    if "ssm" in kw:
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


def n_ring_phases(traffic: Dict[str, Any]) -> int:
    """Distinct step programs the ring schedule compiles: one per slot
    of the ring (its phase is a static part of the state)."""
    if traffic["delay_process"] == "fixed":
        return traffic["tau"] + 1
    return traffic["tau_max"] + 1


# launcher flags the harness sets itself, from the configuration and the
# seed; a traffic file may not name them
HARNESS_FLAGS = ("arch", "smoke", "delay_seed", "steps")


def launcher_args(traffic: Dict[str, Any], arch: str, seed31: int):
    """(flags, fields): every traffic key as the launcher's own flag
    (``key`` -> ``--key-with-dashes``) where its parser has one, else as
    a field of ``RunConfig`` named by a dotted path ("ambdg.smoothness_L",
    "delay.tail_alpha"). ``fields`` is checked by ``with_fields``."""
    from repro.launch import train as launcher
    actions = {a.dest: a for a in launcher.build_parser()._actions
               if a.option_strings}
    flags = ["--arch", arch, "--delay-seed", str(seed31),
             "--steps", str(BIG)]
    fields = {}
    for key, value in traffic.items():
        if key in HARNESS_FLAGS:
            raise ValueError(f"traffic key {key!r} is set by the harness")
        action = actions.get(key)
        if action is None:
            fields[key] = value
        elif isinstance(action, argparse._StoreTrueAction):
            if not isinstance(value, bool):
                raise ValueError(f"traffic key {key!r} takes true or false")
            flags += [action.option_strings[0]] if value else []
        else:
            flags += [action.option_strings[0], str(value)]
    return flags, fields


def _replace_path(obj, path: List[str], value, key: str):
    names = ({f.name for f in dataclasses.fields(obj)}
             if dataclasses.is_dataclass(obj) else set())
    if path[0] not in names:
        raise KeyError(f"traffic key {key!r} is neither a flag of the "
                       "training launcher nor a field of RunConfig")
    if len(path) > 1:
        value = _replace_path(getattr(obj, path[0]), path[1:], value, key)
    return dataclasses.replace(obj, **{path[0]: value})


def with_fields(rc, fields: Dict[str, Any]):
    """``rc`` with each dotted ``RunConfig`` field of ``fields`` set."""
    for key, value in fields.items():
        rc = _replace_path(rc, key.split("."), value, key)
    return rc


def build(cell: Cell, seed: int):
    """(model, rc, loop, struct): the launcher's objects for the cell,
    the model's init replaced by the benchmark's weights."""
    from repro.launch import train as launcher
    from repro.models import build_model
    s31 = weights.seed31(seed)
    flags, fields = launcher_args(cell.traffic, cell.config["arch"], s31)
    _, rc, loop = launcher.build_run(launcher.build_parser().parse_args(flags))
    mcfg = model_config(cell.config)
    rc = with_fields(rc, fields).replace(model=mcfg, seed=s31)
    model = build_model(mcfg)
    axes = {}

    def params_only(key):
        p, axes["tree"] = model.init(key)
        return p

    struct = jax.eval_shape(params_only, jax.random.PRNGKey(0))
    gen = weights.Generator(struct)

    def init(key):
        t0 = time.perf_counter()
        params = jax.block_until_ready(gen(seed))
        TIMES["weights_s"] = time.perf_counter() - t0
        return params, axes["tree"]

    return dataclasses.replace(model, init=init), rc, loop, struct


class Probes:
    """Small jitted readings of the program's state, taken between
    steps so that they hold nothing on the device across one."""

    def __init__(self, struct):
        from repro.core import arena
        layout = arena.make_layout(struct)
        n = layout.n_leaves
        r2l = jnp.asarray(layout.row_to_leaf)

        @jax.jit
        def slot_norms(slot, counts):
            g = jnp.sum(slot, axis=0) / jnp.maximum(jnp.sum(counts), 1e-12)
            sq = jnp.sum(g * g, axis=1)
            return jnp.sqrt(jax.ops.segment_sum(sq, r2l,
                                                num_segments=n + 1)[:n])

        @jax.jit
        def param_norms(params):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(p)))
                              for p in jax.tree.leaves(params)])

        self.slot_norms, self.param_norms = slot_norms, param_norms

    def pushed(self, state) -> np.ndarray:
        """Per-leaf norm of the gradient the first step pushed into the
        ring, over its count: what the optimizer will be given."""
        ar = state.arena
        return np.asarray(self.slot_norms(ar.ring[0], ar.counts[0]))

    def params(self, state) -> np.ndarray:
        """Per-leaf norm of the parameters."""
        return np.asarray(self.param_norms(state.params))


@dataclasses.dataclass
class Readings:
    losses: List[float] = dataclasses.field(default_factory=list)
    batches: List[Dict[str, np.ndarray]] = dataclasses.field(
        default_factory=list)
    delays: List[int] = dataclasses.field(default_factory=list)  # set-up
    grad_norms: Optional[np.ndarray] = None
    # per-leaf parameter norms after each of the first steps
    param_norms: List[np.ndarray] = dataclasses.field(default_factory=list)
    warm_step_s: float = 0.0
    window_steps: int = 0
    window_s: float = 0.0
    window_start: float = 0.0
    compiles_in_window: int = 0
    final_loss: float = float("nan")
    window_delays: Optional[List[int]] = None
    setup_compile_s: float = 0.0
    call_start: float = 0.0
    step_done: Dict[int, float] = dataclasses.field(default_factory=dict)


class Driver:
    """``log_fn`` for ``train``: readings at the first ``N_CHECK``
    steps, then a timed warm-up, then the window."""

    def __init__(self, loop, probes: Probes, n_phases: int,
                 seconds: Optional[float], compiles: CompileCounter,
                 trace_dir: Optional[str] = None):
        self.loop, self.probes = loop, probes
        self.loop.log_every = 1
        self.seconds, self.compiles = seconds, compiles
        self.trace_dir = trace_dir
        self.warm_start = max(N_CHECK, n_phases) + 1
        self.marks: List[float] = []
        self.r = Readings()
        self.t0: Optional[float] = None
        self._compiles0 = 0
        self._delay_proc = None

    def __call__(self, m: Dict[str, float]) -> None:
        now = time.perf_counter()
        loc = sys._getframe(1).f_locals      # train()'s locals, read only
        step = int(loc["step"]) + 1
        if self.t0 is not None:
            self._close(m, loc)
        self.r.step_done[step] = now
        if "delay" in loc["batch"]:
            self.r.delays.append(int(loc["batch"]["delay"]))
        if step <= N_CHECK:
            self._check_step(step, m, loc)
        if self.seconds is None:
            if step == N_CHECK:
                raise WindowClosed
            return
        if step >= self.warm_start:
            self.marks.append(now)
        if step == self.warm_start + N_TIMED_WARM:
            self._open(step, loc)

    def _check_step(self, step, m, loc):
        r = self.r
        r.losses.append(m["loss"])
        batch = loc["batch"]
        r.batches.append({"tokens": np.asarray(batch["tokens"]),
                          "weights": np.asarray(batch["weights"])})
        if step == 1:
            r.grad_norms = self.probes.pushed(loc["state"])
        r.param_norms.append(self.probes.params(loc["state"]))

    def _open(self, step, loc):
        r = self.r
        diffs = np.diff(self.marks)
        r.warm_step_s = float(statistics.median(diffs))
        secs = self.seconds
        if self.trace_dir is not None:
            secs = min(secs, TRACE_SECONDS)
        n = max(MIN_WINDOW_STEPS, math.ceil(secs / r.warm_step_s))
        r.window_steps = n
        if loc.get("delay_proc") is not None:
            self._delay_proc = copy.deepcopy(loc["delay_proc"])
        self.loop.log_every = step + n
        if self.trace_dir is not None:
            from harness import trace
            trace.capture_start(self.trace_dir)
        self._compiles0 = self.compiles.count
        r.setup_compile_s = self.compiles.seconds
        self.t0 = time.perf_counter()
        r.window_start = self.t0

    def _close(self, m, loc):
        r = self.r
        jax.block_until_ready(loc["state"])
        r.window_s = time.perf_counter() - self.t0
        r.compiles_in_window = self.compiles.count - self._compiles0
        r.final_loss = m["loss"]
        if self.trace_dir is not None:
            from harness import trace
            trace.capture_stop()
        if self._delay_proc is not None:
            r.window_delays = [int(self._delay_proc.next())
                               for _ in range(r.window_steps)]
        raise WindowClosed


def run_train(model, rc, loop, driver: Driver) -> Readings:
    """One call of the training loop; returns once the window closed.
    The loop's state is gone when this returns."""
    from repro.train.loop import train
    driver.r.call_start = time.perf_counter()
    try:
        train(model, rc, loop, log_fn=driver)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the training loop ended before the window")
    return driver.r
