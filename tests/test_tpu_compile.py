"""The arena kernels compiled for a TPU v5e by the chip's own compiler,
with no chip attached (``jax.experimental.topologies``).

Interpret mode cannot show what the chip refuses — block shapes that
are not whole tiles, for one — nor that a kernel really lowers to a
Mosaic custom call. These compiles can, at no chip time. The shapes are
the ones ``chip_smoke.py`` runs: qwen1.5-0.5b's arena rows on one chip,
and its ``--four-chips`` config (2 layers, vocabulary 8192), whose pod=4
exchange runs the int8 kernels on one pod per chip and whose one-device
reference runs them on all 4 pods at once.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.core import arena
from repro.kernels import fit_block_rows
from repro.kernels.delay_ring.kernel import (delay_ring_slot_fwd,
                                             variable_pop_fwd)
from repro.kernels.dual_update.kernel import dual_update_fused_fwd
from repro.models import build_model

BLOCK = 256
N_SLOTS = 3          # a delay-tolerant ring with tau_max = 2


def _arena_rows(cfg) -> int:
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    return arena.make_layout(shapes).rows


# (n_pods, config): one chip; one pod per chip of the pod=4 mesh; the
# four-chip run's one-device reference, all 4 pods in one kernel
RING_SHAPES = [(1, "qwen"), (1, "four_chip"), (4, "four_chip")]


@pytest.fixture(scope="module")
def rows():
    qwen = C.get_config("qwen1.5-0.5b")
    return {"qwen": _arena_rows(qwen),
            "four_chip": _arena_rows(dataclasses.replace(
                qwen, n_layers=2, vocab_size=8192))}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dual_update_compiles_at_qwen_rows(one_chip, rows):
    fn = functools.partial(dual_update_fused_fwd, block_rows=BLOCK,
                           interpret=False)
    r = rows["qwen"]
    text = _compile_text(fn, one_chip, ((r, 128), jnp.float32),
                         ((r, 128), jnp.float32),
                         ((), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_pods,config", RING_SHAPES)
def test_delay_ring_slot_int8_compiles(one_chip, rows, n_pods, config):
    r = rows[config]
    blk = fit_block_rows(r, BLOCK, int8=True)
    fn = functools.partial(delay_ring_slot_fwd, block_rows=blk,
                           interpret=False)
    slot = ((n_pods, r, 128), jnp.int8)
    scales = ((n_pods, r), jnp.float32)
    text = _compile_text(fn, one_chip, slot, scales, slot, scales,
                         ((n_pods, r, 128), jnp.float32), scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_pods,config", RING_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_variable_pop_compiles(one_chip, rows, dtype, n_pods, config):
    r = rows[config]
    int8 = dtype == "int8"
    blk = fit_block_rows(r, BLOCK, int8=int8)
    shapes = [((N_SLOTS, n_pods, r, 128), jnp.dtype(dtype)),
              ((N_SLOTS,), jnp.bool_)]
    if int8:
        shapes.append(((N_SLOTS, n_pods, r), jnp.float32))
    fn = functools.partial(variable_pop_fwd, block_rows=blk,
                           interpret=False)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes)


def test_int8_block_rows_follow_the_chip(one_chip):
    """An int8 block streams its per-row scales along the 128 lanes, so
    the chip refuses one that is not a multiple of 128 rows (or all of
    them); ``fit_block_rows`` raises for such a block before the
    compiler does, and accepts the f32 block the same rows allow."""
    r = 3 * 32         # no 128-row block divides it
    with pytest.raises(ValueError):
        fit_block_rows(r, BLOCK, int8=True)
    assert fit_block_rows(r, BLOCK) == 32
    assert fit_block_rows(r, BLOCK, int8=True, interpret=True) == 32
    slot = ((1, r, 128), jnp.int8)
    scales = ((1, r), jnp.float32)
    fn = functools.partial(delay_ring_slot_fwd, block_rows=32,
                           interpret=False)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile_text(fn, one_chip, slot, scales, slot, scales,
                      ((1, r, 128), jnp.float32), scales)
