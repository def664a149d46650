"""Fused delay-ring step, Pallas TPU.

One pass over the slot(s) being rotated: pop the tau-old entry,
dequantize it, quantize the incoming gradient with error feedback, and
write the push slot — where the pytree path lowers to hundreds of
per-leaf dynamic-update-slice kernels plus separate elementwise
chains, this is a single kernel launch whose grid touches exactly
``n_pods * rows/block`` blocks.

Two entry points for the two ring layouts:

  ``delay_ring_slot_fwd``  (v2, default) — the pop and push slots are
      two different per-slot buffers, statically selected by the
      caller's phase counter; only int8 needs a kernel (the f32 v2
      rotate is a read plus a scatter).
  ``delay_ring_fwd``       (v1) — one stacked (tau, ...) ring, head
      slot indexed by a scalar-prefetched index map.

State buffers are donated (input_output_aliases), so untouched slots
are never copied: blocks outside the grid keep their (aliased)
contents.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _ring_kernel_f32(head_ref, ring_ref, g_ref, popped_ref, ring_out_ref):
    del head_ref  # consumed by the index maps
    popped_ref[...] = ring_ref[0].astype(jnp.float32)
    ring_out_ref[...] = g_ref[...][None]


def _ring_kernel_int8(head_ref, ring_ref, scales_ref, fed_ref,
                      scale_new_ref, popped_ref, ring_out_ref,
                      scales_out_ref, residual_out_ref):
    # fed = g + residual is formed by the caller (the scale pass needs
    # it anyway); re-adding it here would cost an extra HBM read of
    # the residual per step. residual_out aliases fed's buffer.
    del head_ref
    q_old = ring_ref[0].astype(jnp.float32)            # (1, B, 128)
    s_old = scales_ref[0, 0][..., None]                # (1, B, 1)
    popped_ref[...] = q_old * s_old
    fed = fed_ref[...]
    s = scale_new_ref[0][..., None]                    # (1, B, 1)
    q = jnp.clip(jnp.round(fed / s), -127, 127)
    ring_out_ref[...] = q[None].astype(jnp.int8)
    scales_out_ref[...] = scale_new_ref[...][None]
    residual_out_ref[...] = fed - q * s


def _slot_kernel_int8(pop_ref, pop_scales_ref, push_ref, push_scales_ref,
                      fed_ref, scale_new_ref, popped_ref, slot_out_ref,
                      scales_out_ref, residual_out_ref):
    # Ring layout v2: the pop and push slots are DIFFERENT buffers,
    # both statically selected by the caller's phase counter — no
    # scalar-prefetched head. push_ref/push_scales_ref are consumed
    # only through input_output_aliases (the spare slot's old contents
    # are dead by construction); residual_out aliases fed's buffer.
    del push_ref, push_scales_ref
    q_old = pop_ref[...].astype(jnp.float32)           # (1, B, 128)
    s_old = pop_scales_ref[0][..., None]               # (1, B, 1)
    popped_ref[...] = q_old * s_old
    fed = fed_ref[...]
    s = scale_new_ref[0][..., None]                    # (1, B, 1)
    q = jnp.clip(jnp.round(fed / s), -127, 127)
    slot_out_ref[...] = q.astype(jnp.int8)
    scales_out_ref[...] = scale_new_ref[...]
    residual_out_ref[...] = fed - q * s


def delay_ring_slot_fwd(slot_pop, scales_pop, slot_push, scales_push,
                        fed, scale_new, *, block_rows: int = 256,
                        interpret: bool = False):
    """Ring layout v2 int8 rotate: pop one slot, overwrite another.

    slot_pop/slot_push: (n_pods, rows, 128) int8 — two *different*
    per-slot ring buffers, selected statically by the caller's phase
    (v2 keeps tau+1 slots so the push target is always the slot whose
    entry was consumed last step). fed: (n_pods, rows, 128) f32, the
    error-fed gradient; its buffer receives the new residual.
    scales_pop/scales_push/scale_new: (n_pods, rows) f32.

    One fused pass: dequantize the popped entry, quantize fed with
    error feedback, write the push slot — ring state donated end-to-end
    via input_output_aliases. (The f32 ring needs no kernel under v2:
    its pop is a plain read and its push a scatter into the spare
    slot.) Returns (popped f32, slot_new, scales_new, residual_new).

    The kernel sees every (n_pods, rows) scales array as (n_pods, 1,
    rows): the chip needs a block's last two dims to be whole (8, 128)
    tiles or the full dims, and a (1, block_rows) block of an
    (n_pods, rows) array is neither once n_pods > 1."""
    n_pods, rows, lanes = slot_pop.shape
    assert lanes == _LANES and rows % block_rows == 0, (slot_pop.shape,)
    grid = (n_pods, rows // block_rows)
    pods3 = pl.BlockSpec((1, block_rows, _LANES), lambda p, r: (p, r, 0))
    pods2 = pl.BlockSpec((1, 1, block_rows), lambda p, r: (p, 0, r))
    scales3 = lambda s: s.reshape((n_pods, 1, rows))

    popped, slot_new, scales_new, residual_new = pl.pallas_call(
        _slot_kernel_int8, grid=grid,
        in_specs=[pods3, pods2, pods3, pods2, pods3, pods2],
        out_specs=[pods3, pods3, pods2, pods3],
        out_shape=[
            jax.ShapeDtypeStruct((n_pods, rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct(slot_push.shape, jnp.int8),
            jax.ShapeDtypeStruct((n_pods, 1, rows), jnp.float32),
            jax.ShapeDtypeStruct(fed.shape, jnp.float32),
        ],
        # donate the push slot/scales; residual_new reuses fed's buffer
        input_output_aliases={2: 1, 3: 2, 4: 3},
        interpret=interpret,
        name="delay_ring_slot_int8",
    )(slot_pop, scales3(scales_pop), slot_push, scales3(scales_push), fed,
      scales3(scale_new))
    return popped, slot_new, scales_new.reshape((n_pods, rows)), \
        residual_new


def _variable_meta(mask_ref, cs_ref, n_slots, meta_ref):
    # fused scalar-metadata epilogue: count / staleness-sum fold over
    # the same scalar-prefetched masks, accumulated in the same
    # unrolled ascending-j loop order as the slot fold. cs_ref is
    # (2, n_slots) f32 in SMEM: row 0 the per-slot pod-summed example
    # counts, row 1 the per-slot tagged staleness. Every grid cell
    # writes the same two scalars (idempotent), so no separate
    # O(n_slots) metadata pass survives outside the kernel.
    count = jnp.float32(0.0)
    ssum = jnp.float32(0.0)
    for j in range(n_slots):
        mc = mask_ref[j].astype(jnp.float32) * cs_ref[0, j]
        count = count + mc
        ssum = ssum + mc * cs_ref[1, j]
    meta_ref[0, 0] = count
    meta_ref[0, 1] = ssum


def _variable_pop_kernel_f32(mask_ref, cs_ref, ring_ref, popped_ref,
                             meta_ref):
    # single pass over the stacked ring block: the (due[j]==t) masks
    # arrive as a scalar-prefetched i32 vector and the fold stays in
    # registers — one accumulator, n_slots multiply-adds, one write
    acc = jnp.zeros(popped_ref.shape, jnp.float32)
    for j in range(ring_ref.shape[0]):
        m = mask_ref[j].astype(jnp.float32)
        acc = acc + m * ring_ref[j].astype(jnp.float32)
    popped_ref[...] = acc
    _variable_meta(mask_ref, cs_ref, ring_ref.shape[0], meta_ref)


def _variable_pop_kernel_int8(mask_ref, cs_ref, ring_ref, scales_ref,
                              popped_ref, meta_ref):
    acc = jnp.zeros(popped_ref.shape, jnp.float32)
    for j in range(ring_ref.shape[0]):
        m = mask_ref[j].astype(jnp.float32)
        x = ring_ref[j].astype(jnp.float32) * scales_ref[j, 0][..., None]
        acc = acc + m * x
    popped_ref[...] = acc
    _variable_meta(mask_ref, cs_ref, ring_ref.shape[0], meta_ref)


def variable_pop_fwd(ring, mask, scales=None, counts_stale=None, *,
                     block_rows: int = 256, interpret: bool = False):
    """Single-pass masked pop of the STACKED delay-tolerant ring
    (layout v3, see ``core.arena``): stream the tau_max+1 slots once
    and fold ``mask[j] * slot_j`` in registers — where the slot-order
    XLA loop materializes tau_max+1 separate slot reads per step.

    ring: (n_slots, n_pods, rows, 128) f32 or int8; mask: (n_slots,)
    bool/i32, ``due == t``; scales: (n_slots, n_pods, rows) f32 under
    int8 (dequantized in the same pass); counts_stale: (2, n_slots)
    f32, row 0 the pod-summed per-slot example counts, row 1 the
    per-slot tagged staleness. Pure read — the ring is not rotated here
    (the push is a static-index update-slice the caller already fused).

    Returns the per-pod popped partial sums (n_pods, rows, 128) f32,
    the pod fold/reduce left to the caller (locally under shard_map, so
    one DCN reduce crosses pods). With ``counts_stale`` the scalar
    metadata epilogue is fused into the same pass (SMEM output) and a
    second value ``meta = (count, stale_sum)`` (2,) f32 is returned —
    so the per-step O(n_slots) slot-metadata pass disappears; tau_obs
    is the caller's one division.

    The fold order (ascending j, from a zero accumulator) is the
    canonical one shared with ``ring_variable_pop_ref`` /
    ``ring_variable_meta_ref`` — bit-identical against the oracles in
    interpret mode (exact regardless of order for the meta fold: counts
    and staleness are small-integer-valued floats)."""
    n_slots, n_pods, rows, lanes = ring.shape
    assert lanes == _LANES and rows % block_rows == 0, (ring.shape,)
    mask = jnp.asarray(mask).astype(jnp.int32).reshape((n_slots,))
    with_meta = counts_stale is not None
    if with_meta:
        cs = jnp.asarray(counts_stale, jnp.float32).reshape((2, n_slots))
    else:
        # the kernels always fold the meta epilogue (one compiled
        # shape); without caller metadata it folds zeros
        cs = jnp.zeros((2, n_slots), jnp.float32)
    grid = (n_pods, rows // block_rows)

    slots4 = pl.BlockSpec((n_slots, 1, block_rows, _LANES),
                          lambda p, r, mask, cs: (0, p, r, 0))
    pods3 = pl.BlockSpec((1, block_rows, _LANES),
                         lambda p, r, mask, cs: (p, r, 0))
    meta_spec = pl.BlockSpec((1, 2), lambda p, r, mask, cs: (0, 0),
                             memory_space=pltpu.SMEM)
    out_shape = [
        jax.ShapeDtypeStruct((n_pods, rows, _LANES), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), jnp.float32),
    ]

    if scales is None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[slots4], out_specs=[pods3, meta_spec])
        popped, meta = pl.pallas_call(
            _variable_pop_kernel_f32, grid_spec=grid_spec,
            out_shape=out_shape, interpret=interpret,
            name="variable_pop_f32",
        )(mask, cs, ring)
        return (popped, meta.reshape((2,))) if with_meta else popped

    # scales viewed as (n_slots, n_pods, 1, rows): see
    # delay_ring_slot_fwd for the chip's block-shape rule
    scales4 = pl.BlockSpec((n_slots, 1, 1, block_rows),
                           lambda p, r, mask, cs: (0, p, 0, r))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[slots4, scales4], out_specs=[pods3, meta_spec])
    popped, meta = pl.pallas_call(
        _variable_pop_kernel_int8, grid_spec=grid_spec,
        out_shape=out_shape, interpret=interpret,
        name="variable_pop_int8",
    )(mask, cs, ring, scales.reshape((n_slots, n_pods, 1, rows)))
    return (popped, meta.reshape((2,))) if with_meta else popped


def delay_ring_fwd(ring, g, head, scales=None, scale_new=None, *,
                   block_rows: int = 256, interpret: bool = False):
    """ring: (tau, n_pods, rows, 128); g: (n_pods, rows, 128) f32 —
    under int8 (``scales`` is not None) ``g`` is the error-fed
    gradient fed = g + residual, and the new residual is written into
    its (donated) buffer. head: () or (1,) i32.
    Returns (popped f32, ring_new, scales_new, residual_new)."""
    tau, n_pods, rows, lanes = ring.shape
    assert lanes == _LANES and rows % block_rows == 0, (ring.shape,)
    head = jnp.asarray(head, jnp.int32).reshape((1,))
    grid = (n_pods, rows // block_rows)

    slot3 = pl.BlockSpec((1, 1, block_rows, _LANES),
                         lambda p, r, head: (head[0], p, r, 0))
    pods3 = pl.BlockSpec((1, block_rows, _LANES), lambda p, r, head: (p, r, 0))

    if scales is None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[slot3, pods3], out_specs=[pods3, slot3])
        popped, ring_new = pl.pallas_call(
            _ring_kernel_f32, grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((n_pods, rows, _LANES), jnp.float32),
                jax.ShapeDtypeStruct(ring.shape, ring.dtype),
            ],
            input_output_aliases={1: 1},    # donate ring -> ring_new
            interpret=interpret,
            name="delay_ring_f32",
        )(head, ring, g)
        return popped, ring_new, None, None

    # scales viewed with a unit dim before rows: see
    # delay_ring_slot_fwd for the chip's block-shape rule
    slot2 = pl.BlockSpec((1, 1, 1, block_rows),
                         lambda p, r, head: (head[0], p, 0, r))
    pods2 = pl.BlockSpec((1, 1, block_rows), lambda p, r, head: (p, 0, r))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=[slot3, slot2, pods3, pods2],
        out_specs=[pods3, slot3, slot2, pods3])
    popped, ring_new, scales_new, residual_new = pl.pallas_call(
        _ring_kernel_int8, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_pods, rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct(ring.shape, jnp.int8),
            jax.ShapeDtypeStruct((tau, n_pods, 1, rows), jnp.float32),
            jax.ShapeDtypeStruct(g.shape, jnp.float32),
        ],
        # donate ring / scales in place; residual_new reuses fed's buffer
        input_output_aliases={1: 1, 2: 2, 3: 3},
        interpret=interpret,
        name="delay_ring_int8",
    )(head, ring, scales.reshape((tau, n_pods, 1, rows)), g,
      scale_new.reshape((n_pods, 1, rows)))
    return popped, ring_new, scales_new.reshape(scales.shape), residual_new
