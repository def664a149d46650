"""Public wrapper: fused delay-ring pop/push on arena buffers.

Dispatch contract (shared by dual_update's arena entry point):
  impl="auto"    Pallas on TPU, pure-XLA reference elsewhere (the ref
                 IS the CPU fast path — interpret-mode Pallas is an
                 emulator, only useful for correctness tests);
  impl="pallas"  force the kernel (interpret=True off-TPU);
  impl="ref"     force the reference.

Two ring layouts:

  v1  one (tau, n_pods, rows, 128) buffer; the kernel selects the head
      slot with a scalar-prefetched index (``ring_push_pop``).
  v2  per-slot buffers with a STATIC phase schedule (see
      ``core.arena.GradArena``): the pop and push slots arrive here as
      two separate, statically-chosen arrays, so the only kernel left
      is the int8 rotate (``ring_slot_rotate_int8`` — dequantize +
      quantize + error feedback in one pass; the f32 rotate is a plain
      read plus a scatter and needs no kernel at all). On a multi-pod
      mesh the kernel runs under ``ring_slot_rotate_int8_sharded``, a
      shard_map wrapper whose only cross-shard traffic is the pop: an
      all-gather of the COMPRESSED int8 payload + per-row scales, with
      dequantization and the deterministic pod fold local to each
      shard — the compressed bytes are what cross the DCN.

  v3  the delay-tolerant (variable per-step delay) ring: one STACKED
      (n_slots, ...) buffer so the masked pop can stream every slot in
      a single pass (``ring_variable_pop`` — fold ``(due[j]==t) *
      slot_j`` in registers; ``ring_variable_pop_sharded`` folds per
      pod shard and crosses the DCN with one reduce). The push stays a
      static-index update-slice in ``core.arena`` and needs no kernel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.delay_ring.kernel import (delay_ring_fwd,
                                             delay_ring_slot_fwd,
                                             variable_pop_fwd)
from repro.kernels.delay_ring.ref import (ring_push_pop_ref,
                                          ring_rotate_int8,
                                          ring_slot_rotate_int8_ref,
                                          ring_variable_meta_ref,
                                          ring_variable_pop_ref)


def ring_push_pop(ring, g, head, *, scales=None, scale_new=None,
                  impl: str = "auto", interpret: Optional[bool] = None,
                  block_rows: int = 256, constrain_axes=None):
    """v1 entry point: pop ring[head] (dequantized f32), push g
    (quantized) in its place. Under int8 (``scales`` given), ``g`` is
    the already error-fed gradient fed = g + residual — the caller
    forms it once (the scale pass needs it anyway) and the new
    residual is written into its donated buffer. Returns (popped,
    ring, scales, residual); state buffers are donated end-to-end.
    See ref.py for shapes."""
    from repro.kernels import resolve_impl, resolve_interpret
    impl = resolve_impl(impl)
    if impl == "ref":
        return ring_push_pop_ref(ring, g, head, scales=scales,
                                 scale_new=scale_new,
                                 constrain_axes=constrain_axes)
    interp = resolve_interpret(interpret)
    return delay_ring_fwd(ring, g, head, scales=scales,
                          scale_new=scale_new, block_rows=block_rows,
                          interpret=interp)


def ring_slot_rotate_int8(slot_pop, scales_pop, slot_push, scales_push,
                          fed, scale_new, *, impl: str = "pallas",
                          interpret: Optional[bool] = None,
                          block_rows: int = 256):
    """v2 int8 slot rotate: dequantize ``slot_pop``, quantize ``fed``
    with error feedback into ``slot_push``'s donated buffer — one
    fused pass (the two slots are different buffers, statically chosen
    by the caller's phase). Returns (popped f32, slot_new, scales_new,
    residual_new); residual_new reuses fed's buffer."""
    from repro.kernels import resolve_impl, resolve_interpret
    impl = resolve_impl(impl)
    if impl == "ref":
        return ring_slot_rotate_int8_ref(slot_pop, scales_pop, fed,
                                         scale_new)
    interp = resolve_interpret(interpret)
    return delay_ring_slot_fwd(slot_pop, scales_pop, slot_push,
                               scales_push, fed, scale_new,
                               block_rows=block_rows, interpret=interp)


def ring_variable_pop(ring, mask, *, scales=None, counts_stale=None,
                      impl: str = "auto",
                      interpret: Optional[bool] = None,
                      block_rows: int = 256):
    """Single-pass masked pop of the STACKED delay-tolerant ring
    (layout v3): fold ``mask[j] * slot_j`` over the tau_max+1 slots in
    one kernel launch instead of tau_max+1 separate slot reads.
    Pure read — the push is the caller's static-index update-slice.

    ring: (n_slots, n_pods, rows, 128) f32|int8; mask: (n_slots,)
    bool, ``due == t``; scales: (n_slots, n_pods, rows) f32 under int8;
    counts_stale: optional (2, n_slots) f32 [pod-summed counts;
    staleness tags] — when given, the scalar count/tau metadata fold is
    fused into the kernel epilogue (SMEM output) and the return value
    becomes ``(popped, meta)`` with ``meta = (count, stale_sum)`` (2,)
    f32, eliminating the separate per-step O(n_slots) metadata pass.

    Returns the per-pod popped partials (n_pods, rows, 128) f32; the
    pod fold is the caller's (``arena._pod_fold`` / the sharded
    wrapper's single DCN reduce). NOTE: unlike the rotate entry points,
    "ref" here is the expression-identical slot fold oracle used by the
    bit-identity tests — the production CPU path is the O(arrivals)
    gather inside ``arena.push_pop_variable``, which never reaches this
    wrapper."""
    from repro.kernels import (fit_block_rows, resolve_impl,
                               resolve_interpret)
    impl = resolve_impl(impl)
    if impl == "ref":
        popped = ring_variable_pop_ref(ring, mask, scales=scales)
        if counts_stale is None:
            return popped
        return popped, ring_variable_meta_ref(mask, counts_stale)
    interp = resolve_interpret(interpret)
    blk = fit_block_rows(ring.shape[2], block_rows,
                         int8=scales is not None, interpret=interp)
    return variable_pop_fwd(ring, mask, scales=scales,
                            counts_stale=counts_stale, block_rows=blk,
                            interpret=interp)


def ring_variable_pop_sharded(ring, mask, *, scales=None,
                              counts_stale=None, mesh_cfg,
                              interpret: Optional[bool] = None,
                              block_rows: int = 256):
    """``shard_map`` wrapper around the variable-pop kernel for
    multi-pod meshes (mirrors ``ring_slot_rotate_int8_sharded``): the
    kernel folds the due slots LOCALLY on each pod shard — the int8
    payload is dequantized in place, never gathered — and the pod
    reduction is ONE ``psum`` of the already-folded f32 rows, i.e. a
    single DCN reduce per step where the slot-order loop issued
    n_slots of them.

    Axis placement comes from ``arena_ring_specs`` (slot dim
    replicated, pods over 'pod', rows over the intra-pod slice); the
    (n_slots,) mask — and ``counts_stale``, when the fused metadata
    epilogue is requested — are replicated, so the kernel's (count,
    stale_sum) meta is already the GLOBAL value on every shard (the
    counts row is the pod-summed metadata the arena carries), no
    second collective needed. Returns grad_sum (rows, 128) f32 ALREADY
    summed over pods — like the sharded rotate, the pod reduction
    happens inside (it IS the DCN collective) — or (grad_sum, meta)
    with ``counts_stale``."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.context import ambient_mesh
    from repro.dist.sharding import arena_ring_specs
    from repro.kernels import dim_shard, fit_block_rows, resolve_interpret

    mesh = ambient_mesh()
    if mesh is None:
        raise ValueError("ring_variable_pop_sharded needs an ambient "
                         "mesh (`with jax.set_mesh(mesh):`)")
    interp = resolve_interpret(interpret)
    n_slots, n_pods, rows, _ = ring.shape
    ring_spec, scales_spec, row_spec = arena_ring_specs(mesh_cfg, rows)
    rows_local = rows // dim_shard(
        ring_spec[2] if len(ring_spec) > 2 else None, mesh)
    blk = fit_block_rows(rows_local, block_rows, int8=scales is not None,
                         interpret=interp)
    mask_spec = P()
    with_meta = counts_stale is not None

    def local_pop(ring, scales, mask, cs):
        out = variable_pop_fwd(ring, mask, scales=scales,
                               counts_stale=cs if with_meta else None,
                               block_rows=blk, interpret=interp)
        part, meta = out if with_meta else (out, None)
        acc = part[0]                     # local pods: deterministic
        for p in range(1, part.shape[0]):  # left fold, shard-local
            acc = acc + part[p]
        acc = jax.lax.psum(acc, "pod")    # THE one DCN reduce
        return (acc, meta) if with_meta else acc

    out_specs = (row_spec, mask_spec) if with_meta else row_spec
    if scales is None:
        fn = jax.shard_map(lambda r, m, cs: local_pop(r, None, m, cs),
                           mesh=mesh,
                           in_specs=(ring_spec, mask_spec, mask_spec),
                           out_specs=out_specs, check_vma=False)
        args = (ring, mask)
    else:
        fn = jax.shard_map(local_pop, mesh=mesh,
                           in_specs=(ring_spec, scales_spec, mask_spec,
                                     mask_spec),
                           out_specs=out_specs, check_vma=False)
        args = (ring, scales, mask)
    cs = (jnp.asarray(counts_stale, jnp.float32) if with_meta
          else jnp.zeros((2, n_slots), jnp.float32))
    return fn(*args, cs)


# ---------------------------------------------------------------------------
# Multi-pod shard_map wrapper (ring layout v2 only)
# ---------------------------------------------------------------------------
def ring_slot_rotate_int8_sharded(slot_pop, scales_pop, slot_push,
                                  scales_push, fed, scale_new, *,
                                  mesh_cfg,
                                  interpret: Optional[bool] = None,
                                  block_rows: int = 256):
    """``shard_map`` wrapper around the v2 int8 slot kernel for
    multi-pod meshes — the fused kernel runs per shard instead of
    falling back to the XLA ref path (a bare pallas_call on the
    pod-sharded slots would make GSPMD gather them whole per device).

    Axis placement comes from the ``repro.dist`` profiles
    (``arena_slot_specs``): slots shard ('pod', 'flat'-rows). The only
    cross-shard traffic is the pop — an all-gather of the COMPRESSED
    int8 payload + per-row scales across the pod axis (those are the
    actual DCN bytes, mirroring the pytree path's pop_leaf wire
    contract); dequantization and the deterministic left fold happen
    locally, in the same order on every shard. The kernel's own
    (local, already-dequantized) popped output is unused here — one
    spare slot-shard write, traded for keeping the fold order
    shard-count-independent.

    Returns (grad_sum (rows, 128) f32 ALREADY summed over pods,
    slot_new, scales_new, residual_new) — unlike the unsharded entry
    points, the pod reduction happens inside (it IS the DCN
    collective)."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.context import ambient_mesh
    from repro.dist.sharding import arena_slot_specs
    from repro.kernels import dim_shard, fit_block_rows, resolve_interpret

    mesh = ambient_mesh()
    if mesh is None:
        raise ValueError("ring_slot_rotate_int8_sharded needs an "
                         "ambient mesh (`with jax.set_mesh(mesh):`)")
    interp = resolve_interpret(interpret)
    n_pods, rows, _ = slot_pop.shape
    slot_spec, scales_spec, row_spec = arena_slot_specs(mesh_cfg, rows)
    rows_local = rows // dim_shard(
        slot_spec[1] if len(slot_spec) > 1 else None, mesh)
    blk = fit_block_rows(rows_local, block_rows, int8=True,
                         interpret=interp)

    def local_rotate(slot_pop, scales_pop, slot_push, scales_push,
                     fed, scale_new):
        # the wire transfer: gather the compressed payload over pods
        q_all = jax.lax.all_gather(slot_pop, "pod", axis=0, tiled=True)
        s_all = jax.lax.all_gather(scales_pop, "pod", axis=0, tiled=True)
        acc = None
        for p in range(q_all.shape[0]):
            x = jax.lax.optimization_barrier(
                q_all[p].astype(jnp.float32) * s_all[p][:, None])
            acc = x if acc is None else acc + x
        _, slot_new, scales_new, residual = delay_ring_slot_fwd(
            slot_pop, scales_pop, slot_push, scales_push, fed,
            scale_new, block_rows=blk, interpret=interp)
        return acc, slot_new, scales_new, residual

    fn = jax.shard_map(
        local_rotate, mesh=mesh,
        in_specs=(slot_spec, scales_spec, slot_spec, scales_spec,
                  slot_spec, scales_spec),
        out_specs=(row_spec, slot_spec, scales_spec, slot_spec),
        check_vma=False)
    return fn(slot_pop, scales_pop, slot_push, scales_push, fed,
              scale_new)


__all__ = ["ring_push_pop", "ring_push_pop_ref", "ring_rotate_int8",
           "ring_slot_rotate_int8", "ring_slot_rotate_int8_sharded",
           "ring_variable_meta_ref", "ring_variable_pop",
           "ring_variable_pop_ref", "ring_variable_pop_sharded"]
