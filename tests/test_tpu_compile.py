"""The Pallas kernels compiled for a TPU v5e by the chip's own compiler,
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
import re

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.core import arena
from repro.kernels import fit_block_rows
from repro.kernels.delay_ring.kernel import (delay_ring_fwd,
                                             delay_ring_slot_fwd,
                                             variable_pop_fwd)
from repro.kernels.dual_update.kernel import (dual_update_fused_fwd,
                                              dual_update_fwd)
from repro.kernels.flash_attention.bwd import flash_attention_train
from repro.kernels.flash_attention.kernel import flash_forward
from repro.kernels.grouped_matmul.ops import gmm
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


# instructions that move no data
_NO_DATA = ("bitcast", "get-tuple-element", "parameter", "constant", "tuple")
_DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "s8": 1, "pred": 1}


def _bytes(shape_text: str) -> int:
    """Bytes of every array in an HLO result shape (tuples summed)."""
    total = 0
    for dt, dims in re.findall(r"\b(f32|s32|u32|bf16|s8|pred)\[([\d,]*)\]",
                               shape_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += _DTYPE_BYTES[dt] * n
    return total


_INSTR = re.compile(
    r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(([^)]*)\)(.*)")


def _computations(text: str):
    """Computation name ("ENTRY" for the entry) -> its instructions, each
    (name, result shape, opcode, operands, rest of the line)."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            body = comps.setdefault(
                "ENTRY" if head.group(1) else head.group(2), [])
        elif body is not None and (m := _INSTR.match(line)):
            body.append(m.groups())
    return comps


def _read_bytes(comps, shapes, instr) -> int:
    """Bytes one instruction reads: a slice reads what it writes; a
    fusion reads what its slices take of an operand, or all of an
    operand that has another user; any other op reads its operands."""
    _, shape, code, args, rest = instr
    operands = re.findall(r"%([\w.\-]+)", args)
    if code == "slice":
        return _bytes(shape)
    if code != "fusion":
        return sum(_bytes(shapes[o]) for o in operands)
    body = comps[re.search(r"calls=%([\w.\-]+)", rest).group(1)]
    total = 0
    for name, _, op, index, _ in body:
        if op != "parameter":
            continue
        users = [u for u in body
                 if f"%{name}" in re.findall(r"%[\w.\-]+", u[3])]
        if users and all(u[2] == "slice" for u in users):
            total += sum(_bytes(u[1]) for u in users)
        else:
            total += _bytes(shapes[operands[int(index)]])
    return total


def test_master_update_moves_the_arena_once(one_chip, monkeypatch):
    """The arena master update at qwen1.5-0.5b widths, compiled for the
    v5e (tau=1, one pod, the dual-update kernel): the unflatten of
    ``w`` into the parameter tree is one row slice of each leaf's tiled
    image, so no op holds the whole arena as one 1-D array, and the
    ops under ``master.update`` other than the kernel read and write
    the arena's bytes once each."""
    import repro.kernels
    from repro.configs.base import RunConfig, TRAIN_4K
    from repro.core import ambdg
    from repro.optim import make_arena_optimizer
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    cfg = C.get_config("qwen1.5-0.5b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    layout = arena.make_layout(shapes)
    opt = make_arena_optimizer(RunConfig(model=cfg, shape=TRAIN_4K), layout)

    def master(state, grads, counts):
        p, o, a = state
        p, o, a, _, _ = ambdg.arena_master_update(layout, opt, p, o, a,
                                                  grads, counts)
        return p, o, a

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    state = jax.eval_shape(lambda: (shapes, opt.init(),
                                    arena.init_arena(layout, 1, 1)))
    grads = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (1,) + x.shape, x.dtype, sharding=one_chip), shapes)
    counts = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip)
    text = jax.jit(master, donate_argnums=(0,)).lower(
        on_chip(state), grads, counts).compile().as_text()
    arena_bytes = layout.rows * 128 * 4
    assert not re.search(rf"\[{layout.rows * 128}\]", text)
    comps = _computations(text)
    results = {instr[0]: instr[1] for instr in comps["ENTRY"]}
    moved, kernel = 0, 0
    for instr in comps["ENTRY"]:
        name, shape, op, _, rest = instr
        if op in _NO_DATA or "master.update" not in rest:
            continue
        if name.startswith("dual_update"):
            kernel += 1
            continue
        moved += _bytes(shape) + _read_bytes(comps, results, instr)
    assert kernel == 1
    assert moved <= 2 * arena_bytes, (moved, arena_bytes)


def _flash_attention_grads(q, k, v):
    def loss(q, k, v):
        return jnp.sum(flash_attention_train(q, k, v).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _qkv(b, s, hq, hkv, dk, dv):
    """q, k, v in the model's (B, S, H, D) layout, bf16."""
    return [((b, s, hq, dk), jnp.bfloat16), ((b, s, hkv, dk), jnp.bfloat16),
            ((b, s, hkv, dv), jnp.bfloat16)]


_QKV = _qkv(1, 256, 2, 1, 128, 128)


def _expert_mlp_grads(x, w_gate, w_up, w_down, sizes):
    """The held-expert layer's three grouped products, forward and
    backward (``models/moe.moe_apply_dropless``)."""
    def loss(x, w_gate, w_up, w_down):
        def f(w):
            return gmm(x, w, sizes, impl="pallas", interpret=False)
        h = jax.nn.silu(f(w_gate)) * f(w_up)
        y = gmm(h, w_down, sizes, impl="pallas", interpret=False)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))
    return jax.grad(loss, argnums=(0, 1, 2, 3))(x, w_gate, w_up, w_down)


def _gmm_shapes(rows, d, f, groups):
    return [((rows, d), jnp.bfloat16), ((groups, d, f), jnp.bfloat16),
            ((groups, d, f), jnp.bfloat16), ((groups, f, d), jnp.bfloat16),
            ((groups,), jnp.int32)]
_R = 1024            # arena rows of the small cases
# kernel name -> (function, argument shapes): every pallas_call in
# kernels/ at a small shape the chip takes, but linear_scan's, which the
# chip's Pallas lowering refuses (it has no cumsum)
NAMED_KERNELS = {
    "dual_update": (
        functools.partial(dual_update_fwd, block_rows=BLOCK),
        [((_R, 128), jnp.float32)] * 2 + [((), jnp.float32)]),
    "dual_update_fused": (
        functools.partial(dual_update_fused_fwd, block_rows=BLOCK),
        [((_R, 128), jnp.float32)] * 2 + [((), jnp.float32)] * 2),
    "delay_ring_f32": (
        lambda ring, g, head: delay_ring_fwd(ring, g, head,
                                             block_rows=BLOCK)[:2],
        [((2, 1, _R, 128), jnp.float32), ((1, _R, 128), jnp.float32),
         ((), jnp.int32)]),
    "delay_ring_int8": (
        lambda ring, sc, g, head, sn: delay_ring_fwd(
            ring, g, head, scales=sc, scale_new=sn, block_rows=BLOCK),
        [((2, 1, _R, 128), jnp.int8), ((2, 1, _R), jnp.float32),
         ((1, _R, 128), jnp.float32), ((), jnp.int32),
         ((1, _R), jnp.float32)]),
    "delay_ring_slot_int8": (
        functools.partial(delay_ring_slot_fwd, block_rows=BLOCK),
        [((1, _R, 128), jnp.int8), ((1, _R), jnp.float32)] * 2
        + [((1, _R, 128), jnp.float32), ((1, _R), jnp.float32)]),
    "variable_pop_f32": (
        functools.partial(variable_pop_fwd, block_rows=BLOCK),
        [((N_SLOTS, 1, _R, 128), jnp.float32), ((N_SLOTS,), jnp.bool_)]),
    "variable_pop_int8": (
        functools.partial(variable_pop_fwd, block_rows=BLOCK),
        [((N_SLOTS, 1, _R, 128), jnp.int8), ((N_SLOTS,), jnp.bool_),
         ((N_SLOTS, 1, _R), jnp.float32)]),
    "flash_attention": (flash_forward, _QKV),
    "flash_attention_fwd": (_flash_attention_grads, _QKV),
    "flash_attention_dq": (_flash_attention_grads, _QKV),
    "flash_attention_dkv": (_flash_attention_grads, _QKV),
    "moe_gmm": (_expert_mlp_grads, _gmm_shapes(1024, 256, 384, 4)),
    "moe_tgmm": (_expert_mlp_grads, _gmm_shapes(1024, 256, 384, 4)),
}


@pytest.mark.parametrize("name", sorted(NAMED_KERNELS))
def test_kernel_carries_its_name(one_chip, name):
    """Each Mosaic custom call is named after its kernel (``name=`` on
    the pallas_call), so a profile of the chip shows ``%<name>.N``
    where it would show the enclosing function's name (under autodiff
    with the transform around it: ``%transpose_jvp_<name>__.N``)."""
    fn, shapes = NAMED_KERNELS[name]
    text = _compile_text(fn, one_chip, *shapes)
    assert re.search(rf"%(?:\w+_)?{name}_*(?:\.\d+)? = .*custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"", text), name


# the training cells' attention cores, and a GQA group: (B, S, Hq, Hkv,
# q/k width, v width)
FLASH_WIDTHS = {
    "qwen1.5-0.5b": (1, 2048, 16, 16, 64, 64),
    "zamba2-2.7b": (1, 4096, 32, 32, 80, 80),
    "moonlight-16b-a3b": (1, 8192, 16, 16, 192, 128),
    "gqa-4": (1, 1024, 8, 2, 128, 128),
}


@pytest.mark.parametrize("model", sorted(FLASH_WIDTHS))
def test_flash_attention_compiles_at_cell_widths(one_chip, model):
    """The flash forward and backward at the widths the cells train
    (bf16 operands, 512-blocks, causal skipping): the chip takes every
    block, and each kernel appears once."""
    text = _compile_text(_flash_attention_grads, one_chip,
                         *_qkv(*FLASH_WIDTHS[model]))
    calls = re.findall(r"%\w*flash_attention_(fwd|dq|dkv)\w*(?:\.\d+)? = ",
                       text)
    assert sorted(calls) == ["dkv", "dq", "fwd"], calls


def _relayouts(text: str):
    """The copy and transpose ops of a compiled program, scalars aside."""
    return [line.split(" = ")[1].split("{")[0] for line in text.splitlines()
            if re.search(r" = \w+\[[\d,]+\]\S* (copy|transpose)\(", line)]


def test_flash_adds_no_relayout_to_the_dense_gradient(one_chip,
                                                      monkeypatch):
    """qwen1.5-0.5b's gradient at 2,048 tokens (2 layers), compiled for
    the chip with the jnp core and with the flash kernel: the kernels
    read and write the layout XLA gives the projections, so the kernel's
    program has no copy or transpose the jnp one lacks."""
    import repro.kernels
    cfg = dataclasses.replace(C.get_config("qwen1.5-0.5b"), n_layers=2,
                              vocab_size=8192)
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    shapes = [(x.shape, x.dtype) for x in jax.tree.leaves(
        (params, model.input_specs(1, 2048)))]
    treedef = jax.tree.structure((params, model.input_specs(1, 2048)))

    def grad(*leaves):
        p, batch = jax.tree.unflatten(treedef, leaves)
        return jax.grad(lambda p: model.loss(p, batch)[0])(p)

    texts = {}
    for platform in ("cpu", "tpu"):
        monkeypatch.setattr(repro.kernels, "on_tpu",
                            lambda: platform == "tpu")
        # a fresh function each time: jit would re-use the first trace
        texts[platform] = _compile_text(lambda *a: grad(*a), one_chip,
                                        *shapes)
    assert "flash_attention_dkv" in texts["tpu"]
    assert "flash_attention" not in texts["cpu"]
    jnp_ops, flash_ops = _relayouts(texts["cpu"]), _relayouts(texts["tpu"])
    extra = [op for op in set(flash_ops)
             if flash_ops.count(op) > jnp_ops.count(op)]
    assert not extra, extra


def test_grouped_matmul_compiles_at_moonlight_widths(one_chip):
    """The expert layer's grouped products at the moonlight cell's
    shapes: one microbatch's 8,192 tokens x 6 choices as rows, the 8
    held experts of width 1,408 over hidden 2,048, whole expert
    matrices as one VMEM tile each; the chip takes every block."""
    text = _compile_text(_expert_mlp_grads, one_chip,
                         *_gmm_shapes(8192 * 6, 2048, 1408, 8))
    calls = re.findall(r"%\w*moe_(t?)gmm\w*(?:\.\d+)? = ", text)
    # forward: gate, up, down; backward: each product's rows (gmm) and
    # matrices (tgmm)
    assert sorted(calls) == [""] * 6 + ["t"] * 3, calls
