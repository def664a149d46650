"""Public wrappers for the fused dual-averaging update.

``dual_update_arena`` is the production entry point: it operates
directly on the persistent (rows, 128) gradient arena (see
``repro.core.arena``) — no flattening happens here at all, and the
anytime count-normalization is fused into the same pass. On multi-pod
meshes ``dual_update_arena_sharded`` runs the same kernel per shard
under shard_map (the update is elementwise, so the wrapper carries no
collectives) instead of letting GSPMD gather the flat-sharded arena.

``dual_update`` is the legacy pytree wrapper kept for ablations and
kernel tests: it re-flattens the whole tree on every call (two
concatenate+pad copies in, two unflattens out), which is exactly the
overhead the arena was introduced to eliminate.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dual_update.kernel import (dual_update_fused_fwd,
                                              dual_update_fwd)
from repro.kernels.dual_update.ref import (dual_update_fused_ref,
                                           dual_update_ref)

_LANES = 128
_BLOCK_ROWS = 256


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [int(x.size) for x in leaves]
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])
    pad = (-flat.size) % (_LANES * _BLOCK_ROWS)
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANES), (treedef, sizes,
                                      [x.shape for x in leaves],
                                      [x.dtype for x in leaves])


def _unflatten(mat, meta):
    treedef, sizes, shapes, dtypes = meta
    flat = mat.reshape(-1)
    out, ofs = [], 0
    for size, shape, dtype in zip(sizes, shapes, dtypes):
        out.append(flat[ofs:ofs + size].reshape(shape).astype(dtype))
        ofs += size
    return jax.tree.unflatten(treedef, out)


def dual_update_arena(z, g_sum, count, alpha, *, impl: str = "auto",
                      interpret: Optional[bool] = None,
                      block_rows: int = _BLOCK_ROWS):
    """Fused arena update: g = g_sum / max(count, eps); z += g;
    w = -alpha z — one read/write pass over the donated (rows, 128)
    arena. impl dispatch as in kernels.delay_ring.ops ("auto" = Pallas
    on TPU, pure-XLA reference elsewhere). Returns (z_new, w)."""
    from repro.kernels import resolve_impl, resolve_interpret
    denom = jnp.maximum(count, 1e-12)
    impl = resolve_impl(impl)
    if impl == "ref":
        return dual_update_fused_ref(z, g_sum, denom, alpha)
    interp = resolve_interpret(interpret)
    return dual_update_fused_fwd(z, g_sum, denom, jnp.float32(alpha),
                                 block_rows=block_rows, interpret=interp)


def dual_update_arena_sharded(z, g_sum, count, alpha, *, mesh_cfg,
                              interpret: Optional[bool] = None,
                              block_rows: int = _BLOCK_ROWS):
    """``shard_map`` wrapper around the fused dual-update kernel for
    multi-pod meshes — mirrors ``ring_slot_rotate_int8_sharded``: a
    bare pallas_call on the flat-sharded z/g buffers would make GSPMD
    gather them whole per device, so the kernel runs per shard
    instead. The update is elementwise over rows (z and g_sum shard
    identically on the intra-pod "flat" slice via
    ``dist.sharding.arena_slot_specs``), so the wrapper needs NO
    cross-shard communication at all — count and alpha are replicated
    scalars. Returns (z_new, w) exactly like ``dual_update_arena``."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.context import ambient_mesh
    from repro.dist.sharding import arena_slot_specs
    from repro.kernels import dim_shard, fit_block_rows, resolve_interpret

    mesh = ambient_mesh()
    if mesh is None:
        raise ValueError("dual_update_arena_sharded needs an ambient "
                         "mesh (`with jax.set_mesh(mesh):`)")
    interp = resolve_interpret(interpret)
    rows, _ = z.shape
    _, _, row_spec = arena_slot_specs(mesh_cfg, rows)
    rows_local = rows // dim_shard(row_spec[0] if len(row_spec) else None,
                                   mesh)
    blk = fit_block_rows(rows_local, block_rows, interpret=interp)
    denom = jnp.maximum(count, 1e-12)

    def local_update(z, g, scal):
        return dual_update_fused_fwd(z, g, scal[0], scal[1],
                                     block_rows=blk, interpret=interp)

    fn = jax.shard_map(
        local_update, mesh=mesh,
        in_specs=(row_spec, row_spec, P()),
        out_specs=(row_spec, row_spec),
        check_vma=False)
    scal = jnp.stack([jnp.float32(denom), jnp.float32(alpha)])
    return fn(z, g_sum, scal)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def dual_update(z_tree, g_tree, alpha, *, interpret: Optional[bool] = None
                ) -> Tuple[Any, Any]:
    """(z_new_tree, w_new_tree) = fused [z+g ; -alpha(z+g)].

    Legacy pytree wrapper (per-call re-flatten); production runs on
    ``dual_update_arena``."""
    from repro.kernels import resolve_interpret
    interp = resolve_interpret(interpret)
    z_mat, meta = _flatten(z_tree)
    g_mat, _ = _flatten(g_tree)
    z_new, w_new = dual_update_fwd(z_mat, g_mat, jnp.float32(alpha),
                                   block_rows=_BLOCK_ROWS, interpret=interp)
    return _unflatten(z_new, meta), _unflatten(w_new, meta)


__all__ = ["dual_update", "dual_update_arena", "dual_update_arena_sharded",
           "dual_update_ref"]
