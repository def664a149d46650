"""Checkpointing: sharded-state save/restore with atomic renames and
retention. Fault-tolerance contract:

  * every array leaf of TrainState (params, z, delay buffers, counts,
    head pointer) plus the data-pipeline cursor and step are saved, so
    a restarted job reproduces the exact update sequence — including
    the in-flight delayed gradients (staleness semantics survive
    restart). This covers both master pipelines: the arena path's
    GradArena (int8 ring kept as int8 on disk, per-row scales,
    error-feedback residual, head) and the flat dual variable z in
    opt_state round-trip leaf-by-leaf like any other state;
  * writes go to ``<dir>/tmp.<step>`` then os.replace() into place, so
    a crash mid-save never corrupts the latest checkpoint;
  * ``keep`` most-recent checkpoints are retained;
  * the manifest records the arena's leaf layout (``leaf_layout``,
    ``core.arena.LEAF_LAYOUT``): arena-form state saved under an older
    leaf order is moved into the current one on restore, never read
    as if it were in it.

Format: one .npz per checkpoint (leaves flattened with path-keys) +
a small JSON manifest. Device arrays are fetched with device_get — on a
real pod each host writes its own shard set (addressable_shards); the
single-process path here is the degenerate case of that layout.
"""
from __future__ import annotations

import json
import os
import re
import shutil
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


def _flatten_with_paths(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def save(ckpt_dir: str, step: int, state, extra: Optional[Dict] = None,
         keep: int = 3) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    tmp = os.path.join(ckpt_dir, f"tmp.{step}")
    final = os.path.join(ckpt_dir, f"step_{step:09d}")
    os.makedirs(tmp, exist_ok=True)
    flat = _flatten_with_paths(state)
    np.savez(os.path.join(tmp, "state.npz"), **flat)
    from repro.core.arena import LEAF_LAYOUT
    manifest = {"step": int(step), "keys": sorted(flat),
                "leaf_layout": LEAF_LAYOUT, "extra": extra or {}}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)                     # atomic publish
    _apply_retention(ckpt_dir, keep)
    return final


def _apply_retention(ckpt_dir: str, keep: int):
    ckpts = sorted(d for d in os.listdir(ckpt_dir)
                   if re.fullmatch(r"step_\d+", d))
    for old in ckpts[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, old))


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    ckpts = sorted(d for d in os.listdir(ckpt_dir)
                   if re.fullmatch(r"step_\d+", d))
    return int(ckpts[-1].split("_")[1]) if ckpts else None


def _migrate_ring_v1(data, template_keys) -> Dict[str, np.ndarray]:
    """Delay-ring layout v1 -> v2 migration, at the numpy level.

    A v2 template asks for per-slot keys ``<p>.ring/<k>`` (tau+1 of
    them) where a v1 checkpoint holds one stacked ``<p>.ring`` array
    of tau slots plus a dynamic head. v2's schedule starts at phase 0
    (pop slot 1 first), so the i-th oldest v1 entry ``ring[(head+i) %
    tau]`` becomes v2 slot ``1+i``; slot 0 — the first push target —
    is dead and zeroed; per-slot counts permute the same way and the
    head resets to phase 0. Returns an overlay dict consulted before
    the raw file, so old checkpoints restore without a conversion
    pass. (Same permutation as ``arena.convert_ring``.)"""
    out: Dict[str, np.ndarray] = {}
    prefixes = {k[:-len("ring/0")] for k in template_keys
                if re.search(r"\.ring/\d+$", k)}
    for prefix in prefixes:
        if f"{prefix}ring" not in data:       # not a v1 checkpoint
            continue
        ring = data[f"{prefix}ring"]
        counts = data[f"{prefix}counts"]
        head = int(data[f"{prefix}head"])
        tau = ring.shape[0]
        perm = [(head + i) % tau for i in range(tau)]
        out[f"{prefix}ring/0"] = np.zeros_like(ring[0])
        new_counts = np.zeros((tau + 1,) + counts.shape[1:], counts.dtype)
        for i, k in enumerate(perm):
            out[f"{prefix}ring/{1 + i}"] = ring[k]
            new_counts[1 + i] = counts[k]
        if f"{prefix}scales" in data:
            scales = data[f"{prefix}scales"]
            out[f"{prefix}scales/0"] = np.ones_like(scales[0])
            for i, k in enumerate(perm):
                out[f"{prefix}scales/{1 + i}"] = scales[k]
        out[f"{prefix}counts"] = new_counts
        out[f"{prefix}head"] = np.zeros_like(data[f"{prefix}head"])
    return out


def _migrate_variable_ring_v2(data, keys, paths) -> Dict[str, np.ndarray]:
    """Delay-tolerant ring, per-slot tuple (v2-shaped) -> stacked v3.

    A v3 template asks for one stacked ``<p>.ring`` array where a
    pre-PR-7 delay-tolerant checkpoint holds per-slot keys
    ``<p>.ring/<k>`` (plus the ``.due`` metadata that marks the arena
    as variable — fixed v1 checkpoints also hold a stacked ``.ring``
    and must NOT match here). Slot k of the tuple IS row k of the
    stack (the variable schedule never permuted slots: the phase is
    ``head % n_slots``), so migration is a plain np.stack; scales
    stack the same way. Returns an overlay dict consulted before the
    raw file."""
    out: Dict[str, np.ndarray] = {}
    for key, (_, leaf) in zip(keys, paths):
        m = re.fullmatch(r"(.*\.)(ring|scales)", key)
        if not m or key in data:
            continue
        prefix = m.group(1)
        if f"{prefix}due" not in data or f"{m.group(0)}/0" not in data:
            continue                          # not a tuple-variable ckpt
        n_slots = data[f"{prefix}due"].shape[0]
        out[key] = np.stack([data[f"{key}/{k}"] for k in range(n_slots)])
    return out


def _migrate_decentralized_residual(data, keys, paths
                                    ) -> Dict[str, np.ndarray]:
    """DecentralizedState grew a gossip error-feedback ``residual``
    field (int8-compressed gossip); checkpoints saved before it lack
    the ``.residual`` key. A zero residual is exactly the state every
    run under ``compression="none"`` carries (and the correct cold
    start for error feedback), so old decentralized checkpoints
    restore with a zero overlay and continue bit-for-bit. Only the
    top-level ``.residual`` is synthesized — the arena's own
    ``.arena.residual`` predates this and is always present."""
    out: Dict[str, np.ndarray] = {}
    for key, (_, leaf) in zip(keys, paths):
        if key == ".residual" and key not in data and ".z" in data:
            out[key] = np.zeros(tuple(leaf.shape),
                                np.dtype(leaf.dtype))
    return out


def _arena_params(template):
    """(parameter tree, leading dims of its leaves) that the template's
    arena-form state lays out, or None: TrainState and KBatchState hold
    the tree itself, DecentralizedState one copy per worker."""
    from repro.core.strategy import DecentralizedState, KBatchState
    node = template.base if isinstance(template, KBatchState) else template
    params = (node.get("params") if isinstance(node, dict)
              else getattr(node, "params", None))
    if params is None:
        return None
    return params, int(isinstance(node, DecentralizedState))


def _migrate_leaf_layout(arrays, version: int, keys, paths, template
                         ) -> Dict[str, np.ndarray]:
    """Arena-form state in the flat leaf layout (version 1: every leaf
    C-order flattened into 128-lane rows, back to back) -> the
    tiled-image strips of ``core.arena``, at the numpy level: each leaf
    is cut out of its old rows and written back as its strip. The
    arena-form arrays are the template's ``(..., rows, 128)`` leaves
    outside the parameters (z, ring slots, residual, staging, optimizer
    moments); per-row ``scales`` hold one value per leaf and follow
    their leaf, pad rows 1. For qwen-like trees ``rows`` does not
    change, so only this keeps such state from loading silently in the
    wrong order. Returns an overlay dict."""
    from repro.core import arena
    if version == arena.LEAF_LAYOUT:
        return {}
    if version != 1:
        raise ValueError(f"checkpoint leaf layout {version} is newer "
                         f"than this program's ({arena.LEAF_LAYOUT})")
    found = _arena_params(template)
    if found is None:
        return {}
    params, lead = found
    leaves = jax.tree.leaves(params)
    layout = arena.make_layout([jax.ShapeDtypeStruct(l.shape[lead:],
                                                     l.dtype)
                                for l in leaves])
    if any(tuple(l.shape[-2:]) == (layout.rows, arena.LANES)
           for l in leaves):
        raise ValueError("a parameter has the arena's (rows, 128) shape: "
                         "the checkpoint's arena-form state cannot be "
                         "told from parameter-shaped state")
    # the flat layout: each leaf's C-order rows, back to back
    old = np.cumsum([0] + [arena._round_up(size, arena.LANES) // arena.LANES
                           for size in layout.sizes])
    old_rows = arena._round_up(int(old[-1]), arena.BLOCK_ROWS)
    strips = list(zip(old[:-1], layout.row_offsets, layout.row_counts))
    is_param = {id(l) for l in leaves}
    out: Dict[str, np.ndarray] = {}
    for key, (_, leaf) in zip(keys, paths):
        arr = arrays.get(key)
        if arr is None or id(leaf) in is_param:
            continue
        if (tuple(leaf.shape[-2:]) == (layout.rows, arena.LANES)
                and arr.shape[-2:] == (old_rows, arena.LANES)):
            lead_shape = arr.shape[:-2]
            flat = arr.reshape(lead_shape + (-1,))
            new = np.zeros(lead_shape + (layout.rows, arena.LANES),
                           arr.dtype)
            for i, (o, ofs, rc) in enumerate(strips):
                x = flat[..., o * arena.LANES:o * arena.LANES
                         + layout.sizes[i]]
                x = x.reshape(lead_shape + layout.shapes[i])
                new[..., ofs:ofs + rc, :] = np.asarray(arena._leaf_strip(
                    layout, i, x, len(lead_shape)))
            out[key] = new
        elif (re.search(r"scales(/\d+)?$", key)
              and tuple(leaf.shape[-1:]) == (layout.rows,)
              and arr.shape[-1:] == (old_rows,)):
            new = np.ones(arr.shape[:-1] + (layout.rows,), arr.dtype)
            for o, ofs, rc in strips:
                new[..., ofs:ofs + rc] = arr[..., o:o + 1]
            out[key] = new
    return out


def _migrate_publisher(extra: Dict, version: int) -> Dict:
    """The publisher's snapshot ring (``serve.publisher``, in ``extra``)
    holds per-row int8 arena rows. Saved under an older leaf layout its
    rows are in the old order, and where a leaf is padded one old row
    straddles leaves, so they cannot be reordered: the snapshots are
    dropped (every slot marked unwritten), and pops miss until the next
    publish, as before the first one."""
    from repro.core import arena
    if version == arena.LEAF_LAYOUT or "publisher" not in extra:
        return extra
    pub = dict(extra["publisher"])
    pub["pub_step"] = np.full(np.shape(pub["pub_step"]), -1, np.int64)
    return {**extra, "publisher": pub}


def restore(ckpt_dir: str, state_template, step: Optional[int] = None
            ) -> Tuple[Any, Dict]:
    """Restore into the structure of ``state_template`` (arrays are
    placed back leaf-by-leaf; shapes/dtypes validated). Checkpoints
    saved under delay-ring layout v1 load transparently into a v2
    template (``_migrate_ring_v1``), per-slot-tuple delay-tolerant
    checkpoints into the stacked v3 layout
    (``_migrate_variable_ring_v2``), pre-residual decentralized
    checkpoints into the current DecentralizedState
    (``_migrate_decentralized_residual``), arena-form state saved in
    the flat leaf layout into the tiled-image one
    (``_migrate_leaf_layout``, and the publisher's snapshots in
    ``extra`` are dropped: ``_migrate_publisher``); every restored v2
    arena gets its static slot phase re-derived from the saved head
    counter."""
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:09d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "state.npz"))

    paths, treedef = jax.tree_util.tree_flatten_with_path(state_template)
    keys = ["/".join(str(getattr(q, "key", getattr(q, "idx", q)))
                     for q in p) for p, _ in paths]
    arrays = dict(data)
    arrays.update(_migrate_ring_v1(data, keys))
    arrays.update(_migrate_variable_ring_v2(data, keys, paths))
    arrays.update(_migrate_decentralized_residual(data, keys, paths))
    version = manifest.get("leaf_layout", 1)
    arrays.update(_migrate_leaf_layout(arrays, version, keys, paths,
                                       state_template))
    leaves = []
    for key, (p, leaf) in zip(keys, paths):
        arr = arrays[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: "
                             f"{arr.shape} vs {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype))
    restored = jax.tree_util.tree_unflatten(treedef, leaves)
    from repro.core.arena import sync_ring_phase
    return (sync_ring_phase(restored),
            _migrate_publisher(manifest["extra"], version))
