"""Weights made from the seed, by the benchmark, in one jitted call.

The program trains them and the reference reads them; neither makes
them. A leaf's values depend only on the seed, its position in the
parameter tree and its name and shape, so the same seed gives the same
weights in every run, and after the window the reference makes them
again instead of keeping a copy on the device.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SEED_MOD = 2 ** 31 - 1   # jax.random.PRNGKey takes a 32-bit seed


def seed31(seed: int) -> int:
    return int(seed) % SEED_MOD


def _last_key(path) -> str:
    k = path[-1]
    return str(getattr(k, "key", getattr(k, "name", k)))


def _leaf(key, path, shape, dtype):
    name = _last_key(path)
    if name in ("ln1", "ln2", "ln_final", "norm_scale", "q_norm",
                "k_norm", "d_skip"):
        return jnp.ones(shape, dtype)
    if name == "a_log":          # A = -exp(a_log), decay rates 1..16
        return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
    if name == "dt_bias":        # softplus(dt_bias) in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, dtype,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if len(shape) <= 1 or (len(shape) == 2 and name.startswith("b")):
        return 0.02 * jax.random.normal(key, shape, dtype)   # biases
    if name == "tok_emb":
        fan_in = shape[-1]
    else:
        fan_in = shape[-2]
    return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)


class Generator:
    """``gen(seed)`` -> the parameter tree of ``struct``'s shapes and
    dtypes, made on the default device in one jitted call. ``gen.fn``
    is that jitted function of a 32-bit seed, for use inside another
    jitted function."""

    def __init__(self, struct):
        flat, treedef = jax.tree_util.tree_flatten_with_path(struct)

        def make(s):
            base = jax.random.PRNGKey(s)
            return treedef.unflatten([
                _leaf(jax.random.fold_in(base, i), path, x.shape, x.dtype)
                for i, (path, x) in enumerate(flat)])

        self.fn = jax.jit(make)

    def __call__(self, seed: int):
        return self.fn(jnp.int32(seed31(seed)))
