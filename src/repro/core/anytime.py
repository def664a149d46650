"""Anytime (variable-size) minibatch gradient accumulation.

The paper's workers compute gradients for a fixed time T_p and ship
(sum_of_gradients, count). On an SPMD TPU program we express this as
accumulation over a budget of ``n_microbatches`` scanned microbatches
with per-sample 0/1 ``weights`` carrying the anytime mask — a shard that
"finished" only b_i of its samples contributes exactly the paper's
(g_i(t), b_i(t)). Aggregation across shards then normalizes by the
*global* count (paper eq. (5)): g(t) = sum_i g_i / sum_i b_i.

Two implementations:
  * ``scan_masked``  — lax.scan over the full microbatch budget, masked.
    Deterministic FLOPs (used for dry-run/roofline); wasted compute on
    masked samples is the SPMD price of staying bulk-synchronous.
  * ``while_dynamic`` — lax.while_loop with a *per-shard dynamic trip
    count* (no collectives inside the body, so devices may genuinely run
    different iteration counts and re-sync only at the reduction). Zero
    wasted FLOPs on stragglers; the deployment mode on real hardware.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

LossFn = Callable[[Any, Dict], Tuple[jax.Array, Dict]]


def _split_batch(batch: Dict, n_mb: int) -> Dict:
    """Reshape every leaf (B, ...) -> (n_mb, B//n_mb, ...), keeping the
    *second* dim batch-sharded (GSPMD would otherwise try to shard the
    small n_mb dim and replicate the rest — see dist.context)."""
    from repro.dist.context import constrain

    def r(x):
        b = x.shape[0]
        assert b % n_mb == 0, f"batch {b} not divisible by {n_mb} microbatches"
        out = x.reshape((n_mb, b // n_mb) + x.shape[1:])
        return constrain(out, (None, "batch") + (None,) * (out.ndim - 2))
    return jax.tree.map(r, batch)


def _counters(aux: Dict) -> Dict:
    """The loss's sums other than the count and the loss: a model's
    counters (the dropless MoE layer's ``moe_held_rows``)."""
    return {k: v for k, v in aux.items() if k not in ("count", "loss_sum")}


def accumulate_scan(loss_fn: LossFn, params, batch: Dict, n_mb: int):
    """Masked scan accumulation.

    Returns (grad_sum, count, metrics) where grad_sum is the *sum* of
    per-sample gradients (weighted), count the weighted sample/token
    count — exactly the worker message m_i(t) = (g_i(t), b_i(t)) — and
    metrics the loss sum with the model's counters, each summed over
    the microbatches.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if n_mb == 1:  # no scan: keeps roofline measurement loop-free
        (loss_sum, aux), g = grad_fn(params, batch)
        with jax.named_scope("worker.accumulate"):
            g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        return g, aux["count"], {"loss_sum": aux["loss_sum"],
                                 **_counters(aux)}
    mbs = _split_batch(batch, n_mb)

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    carry0 = (zeros, jnp.float32(0.0), jnp.float32(0.0))

    def body(carry, mb):
        gsum, csum, lsum = carry
        (loss_sum, aux), g = grad_fn(params, mb)
        with jax.named_scope("worker.accumulate"):
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
        return ((gsum, csum + aux["count"], lsum + aux["loss_sum"]),
                _counters(aux))

    (gsum, count, loss_sum), counters = jax.lax.scan(body, carry0, mbs)
    return gsum, count, {"loss_sum": loss_sum,
                         **jax.tree.map(jnp.sum, counters)}


def accumulate_while(loss_fn: LossFn, params, batch: Dict, n_mb: int,
                     n_active):
    """Dynamic-trip-count accumulation: runs ``n_active`` (<= n_mb)
    microbatches. ``n_active`` may differ across shards — there are no
    collectives in the body, so each device runs its own count and the
    program re-synchronizes at the first cross-device reduction after.
    The model's counters are not carried on this path.
    """
    mbs = _split_batch(batch, n_mb)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def cond(state):
        i, *_ = state
        return i < n_active

    def body(state):
        i, gsum, csum, lsum = state
        mb = jax.tree.map(lambda x: x[i], mbs)
        (loss_sum, aux), g = grad_fn(params, mb)
        with jax.named_scope("worker.accumulate"):
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
        return (i + 1, gsum, csum + aux["count"], lsum + aux["loss_sum"])

    _, gsum, count, loss_sum = jax.lax.while_loop(
        cond, body, (jnp.int32(0), zeros, jnp.float32(0.0), jnp.float32(0.0)))
    return gsum, count, {"loss_sum": loss_sum}


def normalize(grad_sum, count):
    """g(t) = (sum of gradients) / (total count), guarding count=0 (a
    fully-failed epoch contributes a zero update, not NaNs). A plain
    division: the downstream dual add cannot FMA-contract with it, so
    no pinning is needed for pytree/arena bit-equality."""
    denom = jnp.maximum(count, 1e-12)
    return jax.tree.map(lambda g: g / denom, grad_sum)
