"""The comparison that decides ``correct``.

The reference follows the program's first ``N_CHECK`` steps on the
same batches from the same weights: its own float32 forward and
backward (``references/<family>.py``), its own delay ring (a gradient
pushed at step t is applied at step t + tau_t, tau_t as the host drew
it) and its own dual averaging (z += g / count, w = -alpha z). Four
numbers are read; those that ``limits/<workload>.json`` gives a limit
are compared with it (a number no control or fault separates from
sound runs has none):

  loss_gap    the largest |loss - ref| / ref over the first steps
  grad_gap    the first gradient, over its count, as the ring hands it
              to the optimizer: the worst leaf's |norm - ref norm|
  grad_gap_median  the same gradient's median leaf gap: rounding of
              every product (the fp8 control) shows on most leaves,
              where one leaf's noise sets grad_gap
  param_gap   the parameters after each of the first steps, as the next
              step reads them: the worst leaf's |norm - ref norm| over
              the steps. Dual averaging sets w = -alpha z, and z is 0
              until the first gradient pops, so the reference's
              parameters are exactly 0 before that step and not after
              it: a ring that pops a step early or late reads 1 or more.

A leaf's gap is measured against the larger of the reference's norm of
that leaf and of the median leaf (where both are 0, a gap of 0 reads 0
and any other reads infinity). Leaves whose reference gradient is
under a thousandth of the median leaf's (nought but for rounding, as a
key bias is under softmax) are left out of param_gap.

Why the parameters and not their change from the start: the starting
weights are gone after step 1, and |w_3 - w_0| is |w_0| to a part in
a million on every seed: it would catch no fault of the master.

The reference follows only the traffic keys in ``FOLLOWED``; a cell
whose traffic names another is refused before its run
(``check_traffic``), since the reference would follow another job.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

ZERO_GRAD = 1e-3    # of the median leaf's reference gradient norm
# traffic keys the reference follows, with the value it requires (None:
# any); the sizes and draws of the rest reach it through the batches
FOLLOWED = {"strategy": "ambdg", "optimizer": "dual_averaging",
            "tau": None, "delay_process": None, "tau_max": None,
            "delay_min": None, "delay.tail_alpha": None,
            "n_workers": None, "samples_per_worker": None, "seq_len": None,
            "n_microbatches": None, "ambdg.smoothness_L": None}


def check_traffic(traffic) -> None:
    """Raises unless the reference follows every key of ``traffic``."""
    for key, value in traffic.items():
        if key not in FOLLOWED:
            raise ValueError(f"the reference does not follow traffic key "
                             f"{key!r}")
        if FOLLOWED[key] is not None and value != FOLLOWED[key]:
            raise ValueError(f"the reference follows {key}="
                             f"{FOLLOWED[key]!r} only, not {value!r}")


@dataclasses.dataclass
class Side:
    """What one side (the program, the reference, a control) reads."""
    losses: List[float]
    grad_norms: np.ndarray
    param_norms: List[np.ndarray]       # per leaf, after each step


def _tree_norms(tree) -> np.ndarray:
    return np.asarray(jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                                 for x in jax.tree.leaves(tree)]))


_SAMPLE_LOSS: Dict[tuple, tuple] = {}
axpy = jax.jit(lambda a, x, y: jax.tree.map(lambda u, v: u + a * v, x, y))
scale = jax.jit(lambda a, x: jax.tree.map(lambda u: a * u, x))


def _sample_loss(cell, precision: str):
    """(value and gradient, value) of the reference's loss of one
    sample, jitted once a process for each configuration and precision."""
    from references.common import Dot
    key = (json.dumps(cell.config, sort_keys=True), precision)
    if key not in _SAMPLE_LOSS:
        ref, cfg, dot = cell.reference(), cell.config, Dot(precision)

        def loss(p, tok):
            return ref.sample_loss(p, tok, cfg, dot)

        _SAMPLE_LOSS[key] = (jax.jit(jax.value_and_grad(loss)),
                             jax.jit(loss))
    return _SAMPLE_LOSS[key]


def follow(cell, struct, seed: int, batches, delays, n_steps: int,
           precision: str = "float32", half_batch: bool = False,
           late_pop: bool = False) -> Side:
    """The reference's first ``n_steps`` steps. ``precision`` "fp8" makes
    the control, ``half_batch`` and ``late_pop`` (every gradient popped
    one step after it is due) faults; each must fail the limits."""
    t = cell.traffic
    w0 = weights.Generator(struct)(seed)
    vg, fwd = _sample_loss(cell, precision)
    fixed = t["delay_process"] == "fixed"
    n_slots = (t["tau"] if fixed else t["tau_max"]) + 1
    b_bar = float(t["n_workers"] * t["samples_per_worker"])

    params, z = w0, None
    pending: Dict[int, list] = {}
    losses, grad_norms, param_norms = [], None, []
    for step in range(1, n_steps + 1):
        b = batches[step - 1]
        tok, wts = b["tokens"], np.asarray(b["weights"], np.float64)
        rows = list(range(len(wts)))
        if half_batch:
            rows = rows[:len(rows) // 2]
        d = t["tau"] if fixed else int(np.clip(delays[step - 1], 0,
                                                n_slots - 1))
        due = step + d + int(late_pop)
        count = float(sum(wts[i] for i in rows)) * (tok.shape[1] - 1)
        loss_sum, g = 0.0, None
        need_grad = due <= n_steps or step == 1
        for i in rows:
            if wts[i] == 0:
                continue
            if need_grad:
                li, gi = vg(params, jnp.asarray(tok[i]))
                g = scale(float(wts[i]), gi) if g is None else axpy(
                    float(wts[i]), g, gi)
            else:
                li = fwd(params, jnp.asarray(tok[i]))
            loss_sum += float(wts[i]) * float(li)
        losses.append(loss_sum / max(count, 1e-12))
        if step == 1:
            grad_norms = _tree_norms(g) / max(count, 1e-12)
        if due <= n_steps:
            pending.setdefault(due, []).append((g, count, d))
        arrived = pending.pop(step, [])
        c_sum = sum(c for _, c, _ in arrived)
        if arrived:
            g_sum = arrived[0][0]
            for gi, _, _ in arrived[1:]:
                g_sum = axpy(1.0, g_sum, gi)
            inc = scale(1.0 / max(c_sum, 1e-12), g_sum)
            z = inc if z is None else axpy(1.0, z, inc)
        if fixed:
            tau_used = float(t["tau"])
        elif c_sum > 0:      # the launcher's delay-adaptive step size
            tau_used = sum(c * dd for _, c, dd in arrived) / c_sum
        else:
            tau_used = float(t["tau_max"])
        # alpha(t)^-1 = L + sqrt((t + tau) / b_bar), the paper's Theorem
        # IV.1, counted from t = 2 at the first update as the program does
        a = 1.0 / (t["ambdg.smoothness_L"]
                   + np.sqrt((step + 1 + tau_used) / b_bar))
        params = (scale(0.0, w0) if z is None else scale(-a, z))
        param_norms.append(_tree_norms(params))
    return Side(losses=losses, grad_norms=grad_norms,
                param_norms=param_norms)


def leaf_gaps(got, ref) -> np.ndarray:
    """Each leaf's |got - ref| over the larger of its ``ref`` and the
    median ``ref``; 0 over 0 reads 0, anything else over 0 infinity."""
    den = np.maximum(ref, float(np.median(ref)))
    diff = np.abs(got - ref)
    return np.where(den > 0, diff / np.where(den > 0, den, 1.0),
                    np.where(diff > 0, np.inf, 0.0))


def _worst(got, ref, keep) -> float:
    """The worst kept leaf's gap (``leaf_gaps``)."""
    return float(np.max(np.where(keep, leaf_gaps(got, ref), 0.0)))


def param_step_gaps(got: Side, ref: Side) -> List[float]:
    """param_gap of each step: the parameters it leaves."""
    rg = ref.grad_norms
    moving = rg >= ZERO_GRAD * float(np.median(rg))
    return [_worst(g, r, moving) for g, r in zip(got.param_norms,
                                                  ref.param_norms)]


def gaps(got: Side, ref: Side) -> Dict[str, float]:
    """The four numbers of the module docstring."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got.losses,
                                                       ref.losses))
    return {"loss_gap": float(loss_gap),
            "grad_gap": _worst(got.grad_norms, ref.grad_norms, True),
            "grad_gap_median": float(np.median(leaf_gaps(
                got.grad_norms, ref.grad_norms))),
            "param_gap": max(param_step_gaps(got, ref))}


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Optional[bool]:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
