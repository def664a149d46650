"""Persistent, tile-aligned flat gradient arena — the master pipeline's
memory layout.

Every leaf of the parameter pytree becomes a strip of 128-lane rows;
strips lie back to back in one ``(rows, 128)`` f32 buffer (rows padded
to a multiple of the kernel block). A leaf of rank 2 or more is stored
as the memory image of its (8,128)-tiled form — the way the TPU holds
it — so moving it in or out of the arena is a copy of whole tiles; a
rank 0 or 1 leaf is its flat C-order strip. Every strip starts on an
8-row boundary and fills whole 8-row tiles (``_leaf_strip`` and
``_strip_leaf`` are the only conversions). The layout is computed ONCE
at ``init_state`` and carried as a static closure constant
(``ArenaLayout``); per-step work never re-flattens the tree with
``jnp.concatenate`` — the gradient is scattered into a preallocated
buffer with static-offset update-slices, and the dual variable ``z``,
the delay ring, and the int8 error-feedback residual live in arena
form permanently.

Row alignment is what makes int8 compression cheap here: every row
belongs to exactly one leaf, so the pytree path's *per-tensor* scales
become *per-row* vectors through a static row->leaf map — elementwise
multiplies in the kernel, no gathers — while staying bit-identical to
the per-tensor reference (a max is a max regardless of reduction
order, and the zero pads never raise it).

The delay ring has three layouts (see ``GradArena``): the default v2
stores one buffer per slot (tau+1 of them) and selects slots with
STATIC indices from a phase counter carried as static pytree aux data,
which is what removes XLA:CPU's copy-protection entirely; v1 is the
single stacked (tau, ...) buffer, kept for migration and as a layout
oracle; v3 is the delay-tolerant (variable per-step delay) ring — one
STACKED (n_slots, ...) buffer like v1, but still pushed at the v2
phase schedule's static slot index (so the writes stay in-place), with
per-slot due/stale metadata driving a masked pop that can read the
whole ring in a single pass (gather the due slots on CPU; one Pallas
kernel launch + one cross-pod reduce on TPU meshes).

See docs/arena.md for the full memory-layout and donation contract.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
SUBLANES = 8      # rows of one (8,128) f32 tile; every strip fills whole tiles
BLOCK_ROWS = 256  # kernel grid block; total rows padded to a multiple
LEAF_LAYOUT = 2   # leaf order in a strip (1: flat C order for every rank)
# named scope of the staging fill (``flatten_tree`` / ``scatter_fed``):
# the matrix harness attributes the fill's layout copies by it
FILL_SCOPE = "arena.fill"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _strip_rows(shape) -> int:
    """Rows of a leaf's strip: its (8,128)-tiled image for rank >= 2,
    its flat C-order rows for rank 0/1, either padded to whole tiles."""
    if len(shape) >= 2:
        *lead, m, n = shape
        return (int(np.prod(lead)) * _round_up(m, SUBLANES)
                * _round_up(n, LANES) // LANES)
    size = int(np.prod(shape)) if shape else 1
    return _round_up(-(-size // LANES), SUBLANES)


class ArenaLayout:
    """Static flatten metadata (plain Python: safe to close over)."""

    def __init__(self, treedef, shapes, dtypes):
        self.treedef = treedef
        self.shapes = tuple(tuple(s) for s in shapes)
        self.dtypes = tuple(dtypes)
        self.sizes = tuple(int(np.prod(s)) if s else 1 for s in self.shapes)
        self.row_counts = tuple(_strip_rows(s) for s in self.shapes)
        self.row_offsets = tuple(
            int(o) for o in np.cumsum((0,) + self.row_counts[:-1]))
        self.n_leaves = len(self.sizes)
        self.rows = _round_up(sum(self.row_counts), BLOCK_ROWS)
        # static row -> leaf map; tail-pad rows get the sentinel segment
        # ``n_leaves`` (their scale is pinned to 1, their data to 0)
        r2l = np.full((self.rows,), self.n_leaves, np.int32)
        for i, (ro, rc) in enumerate(zip(self.row_offsets, self.row_counts)):
            r2l[ro:ro + rc] = i
        self.row_to_leaf = r2l
        # share of the parameters in whole-tile images: rank >= 2
        # leaves whose last two dims need no padding, so their strip is
        # a pure permutation of the flat layout's rows
        total = sum(self.sizes)
        whole = sum(s for s, sh in zip(self.sizes, self.shapes)
                    if len(sh) >= 2 and sh[-2] % SUBLANES == 0
                    and sh[-1] % LANES == 0)
        self.tiled_share = whole / total if total else 0.0


def make_layout(params) -> ArenaLayout:
    """Build the layout from a parameter pytree (arrays or
    ShapeDtypeStructs). Called once at init — never per step."""
    leaves, treedef = jax.tree.flatten(params)
    return ArenaLayout(treedef, [l.shape for l in leaves],
                       [l.dtype for l in leaves])


def _leaf_strip(layout: ArenaLayout, i: int, leaf, leading: int):
    """Leaf ``i`` (with ``leading`` extra leading dims) as its f32 strip
    ``(*lead, row_counts[i], 128)``, zero-padded. Rank >= 2: pad the
    last two dims to (8k, 128j) and lay the tiles out in row-major
    order, each as its 8 sublanes; rank 0/1: the flat row-major rows."""
    lead = tuple(leaf.shape[:leading])
    shape = layout.shapes[i]
    x = leaf.astype(np.float32)
    if len(shape) >= 2:
        m, n = shape[-2:]
        pm, pn = _round_up(m, SUBLANES) - m, _round_up(n, LANES) - n
        if pm or pn:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)])
        x = x.reshape(lead + (int(np.prod(shape[:-2])),
                              (m + pm) // SUBLANES, SUBLANES,
                              (n + pn) // LANES, LANES)).swapaxes(-3, -2)
    else:
        x = x.reshape(lead + (layout.sizes[i],))
        pad = layout.row_counts[i] * LANES - layout.sizes[i]
        if pad:
            x = jnp.pad(x, [(0, 0)] * leading + [(0, pad)])
    return x.reshape(lead + (layout.row_counts[i], LANES))


def _strip_leaf(layout: ArenaLayout, i: int, strip):
    """Inverse of ``_leaf_strip``: ``(*lead, row_counts[i], 128)`` ->
    ``(*lead, *shapes[i])``, pads dropped, dtype kept."""
    lead = tuple(strip.shape[:-2])
    shape = layout.shapes[i]
    if len(shape) >= 2:
        m, n = shape[-2:]
        m8, n128 = _round_up(m, SUBLANES), _round_up(n, LANES)
        x = strip.reshape(lead + (int(np.prod(shape[:-2])), m8 // SUBLANES,
                                  n128 // LANES, SUBLANES, LANES))
        x = x.swapaxes(-3, -2).reshape(lead + shape[:-2] + (m8, n128))
        return x[..., :m, :n]
    x = strip.reshape(lead + (layout.row_counts[i] * LANES,))
    return x[..., :layout.sizes[i]].reshape(lead + shape)


def _scatter(layout: ArenaLayout, tree, leading: int, out, slot=None,
             add=None):
    """Write every leaf's strip into ``out`` at its row offset — into
    ``out[slot]`` when a (static) slot index is given — plus the same
    rows of ``add`` when given. Static-offset update-slices only."""
    leaves = layout.treedef.flatten_up_to(tree)
    at = () if slot is None else (slot,)
    for i, leaf in enumerate(leaves):
        x = _leaf_strip(layout, i, leaf, leading)
        ofs = layout.row_offsets[i]
        if add is not None:
            x = x + jax.lax.slice_in_dim(add, ofs, ofs + layout.row_counts[i],
                                         axis=leading)
        if slot is not None:
            x = x[None]
        out = jax.lax.dynamic_update_slice(
            out, x.astype(out.dtype), at + (0,) * leading + (ofs, 0))
    return out


def flatten_tree(layout: ArenaLayout, tree, leading: int = 0, out=None):
    """Scatter a pytree into arena form: ``(*lead, rows, 128)`` f32.

    ``leading`` counts extra leading dims shared by every leaf (the
    pod-stacked gradient uses leading=1). Uses static-offset
    dynamic-update-slices — no ``concatenate`` (asserted by
    tests/test_arena.py). Pass a persistent donated buffer (the
    arena's ``staging``, or the ring slot being overwritten) as
    ``out`` to make the whole scatter in-place (an order of magnitude
    faster than materializing a fresh buffer: no zero-fill, no
    allocation, just the leaf writes).
    """
    # NB: never reshape ``out`` — reshaping the donated accumulator
    # breaks XLA's in-place update-slice chain (measured 10x on CPU);
    # scatter along the row axis instead.
    if out is None:
        leaves = layout.treedef.flatten_up_to(tree)
        lead = leaves[0].shape[:leading] if leaves else ()
        out = jnp.zeros(lead + (layout.rows, LANES), jnp.float32)
    with jax.named_scope(FILL_SCOPE):
        return _scatter(layout, tree, leading, out)


def scatter_fed(layout: ArenaLayout, tree, residual, out):
    """int8 error feedback: build fed = g + residual in arena form with
    one scatter pass (leaf read + residual-row read + in-place write
    into the staging buffer), instead of flatten-then-add."""
    with jax.named_scope(FILL_SCOPE):
        return _scatter(layout, tree, 1, out, add=residual)


def unflatten_tree(layout: ArenaLayout, mat, cast: bool = True, scale=None):
    """Gather arena rows back into the pytree: a static row slice per
    leaf, then its tiling undone — never a reshape of the whole arena
    (on the TPU, the (rows, 128) -> 1-D reshape is a relayout copy of
    every row). ``cast=False`` keeps every leaf f32 (the dual-averaging
    ``w`` convention); ``cast=True`` restores the layout dtypes.
    ``scale`` multiplies each slice on the way out — the dual-averaging
    prox (w = -alpha z) rides the gather for free instead of
    materializing a separate w buffer."""
    axis = mat.ndim - 2
    out = []
    for i, (ofs, rc) in enumerate(zip(layout.row_offsets, layout.row_counts)):
        x = jax.lax.slice_in_dim(mat, ofs, ofs + rc, axis=axis)
        if scale is not None:
            x = scale * x
        x = _strip_leaf(layout, i, x)
        out.append(x.astype(layout.dtypes[i]) if cast else x)
    return layout.treedef.unflatten(out)


def _scatter_slot(layout: ArenaLayout, ring, tree, head):
    """v1: per-leaf scatter straight into ring[head]. A ``lax.switch``
    over the (static, small) tau slots keeps every update-slice
    STATICALLY indexed — XLA:CPU then writes in place, where a dynamic
    head index degrades every chained update into a full ring copy."""
    tau = ring.shape[0]
    return jax.lax.switch(
        head, [lambda r, k=k: _scatter(layout, tree, 1, r, slot=k)
               for k in range(tau)], ring)


def _update_slot_int8(ring, scales, q, scale_new, head):
    """v1: write the quantized slot + its per-row scales with static
    slot indices (same lax.switch trick as _scatter_slot)."""
    tau = ring.shape[0]

    def branch(k):
        def push(r, s):
            r = jax.lax.dynamic_update_slice(r, q[None], (k, 0, 0, 0))
            s = jax.lax.dynamic_update_slice(s, scale_new[None], (k, 0, 0))
            return r, s
        return push

    return jax.lax.switch(head, [branch(k) for k in range(tau)],
                          ring, scales)


# ---------------------------------------------------------------------------
# Delay state in arena form
# ---------------------------------------------------------------------------
_ARENA_FIELDS = ("ring", "scales", "residual", "staging", "counts", "head",
                 "due", "stale")


@jax.tree_util.register_pytree_with_keys_class
class GradArena:
    """The delay ring + int8 error feedback, all contiguous. ``ring``
    is f32 (compression="none") or int8; per-row scales and the
    residual exist only under int8. The pod dim is preserved so GSPMD
    can keep the ring pod-sharded (the pop's pod-sum is the DCN
    all-reduce, exactly as in the pytree path).

    Three ring layouts:

      v2 (default)  ``ring`` is a TUPLE of tau+1 per-slot (n_pods,
                    rows, 128) buffers (``scales`` a tuple of (n_pods,
                    rows); ``counts`` (tau+1, n_pods)). The slot
                    schedule lives in ``phase`` — static pytree AUX
                    data, not a traced array — so each step pops slot
                    ``(phase+1) % (tau+1)`` and overwrites slot
                    ``phase`` with fully STATIC indices on two
                    *different* donated buffers. XLA:CPU then inserts
                    NO copy-protection at all (a same-buffer pop/push
                    costs 2 slot copies; any dynamic slot choice —
                    ``lax.switch`` or a dynamic index — costs 2-3
                    whole-ring copies per step, measured). The price is
                    one spare slot of memory and one retrace per phase
                    (jit sees tau+1 input structures, then cycles).
      v1            one (tau, n_pods, rows, 128) buffer; the slot is a
                    dynamic head index (lax.switch on CPU, scalar-
                    prefetched Pallas kernel on TPU); ``phase`` stays
                    0 and is unused. Kept constructible for the
                    bit-exactness matrix and checkpoint migration
                    (restore() splits a v1 ring into v2 slots).
      v3            the delay-tolerant (variable-delay) ring: STACKED
                    (n_slots, n_pods, rows, 128) like v1, but pushed at
                    the v2 phase schedule's STATIC slot index (a
                    static-index update-slice — in-place on the donated
                    buffer, no copy-protection), with per-slot ``due``/
                    ``stale`` metadata driving the masked pop. Stacking
                    is what makes the pop a SINGLE pass: a
                    data-dependent gather of the O(arrivals) due slots
                    on CPU, one Pallas kernel launch streaming all
                    slots on TPU (impossible on a tuple of slots).

    ``head`` stays an array leaf in BOTH layouts: under v2 it mirrors
    ``phase`` (a trace-time constant) so checkpoints record where the
    schedule stood — restore re-derives the static phase from it.

    ``staging`` is the persistent scratch the per-step gradient tree is
    scattered into (int8's fed buffer): because it lives in the
    (donated) train state, the scatter is a chain of in-place
    static-offset writes — no per-step allocation or zero-fill. The
    uncompressed path scatters straight into the ring's push slot and
    carries no staging at all (a params-sized x n_pods buffer of dead
    memory and checkpoint bytes otherwise). Staging contents are
    scratch (rewritten in full every step) but checkpointed when
    present: exactness of restore is easier to audit than to argue
    about.

    Delay-tolerant (variable-delay) rings additionally carry ``due``
    and ``stale`` — per-slot i32 vectors recording the absolute step a
    slot's entry is to be applied at and the delay it was pushed with
    (see ``push_pop_variable``). Both are None on fixed-tau rings, so
    the fixed-mode state structure (and its checkpoints) is unchanged;
    ``head`` doubles as the absolute step counter in variable mode
    (``phase`` still mirrors ``head % n_slots``, so
    ``sync_ring_phase`` restores the schedule unchanged)."""

    __slots__ = _ARENA_FIELDS + ("phase",)

    def __init__(self, ring, scales, residual, staging, counts, head,
                 due=None, stale=None, phase: int = 0):
        self.ring = ring            # v2: tuple of (n_pods, rows, 128);
                                    # v1/v3: stacked (n_slots, ...)
        self.scales = scales        # v2: tuple of (n_pods, rows) — int8
        self.residual = residual    # (n_pods, rows, 128) f32 — int8 only
        self.staging = staging      # (n_pods, rows, 128) f32 scratch
        self.counts = counts        # (tau+1, n_pods) f32 (v1: (tau, ...))
        self.head = head            # () i32: next slot to overwrite
        self.due = due              # (n_slots,) i32 — variable rings only
        self.stale = stale          # (n_slots,) i32 — variable rings only
        self.phase = int(phase)     # STATIC slot schedule position (v2)

    def _replace(self, **kw) -> "GradArena":
        vals = {f: getattr(self, f) for f in self.__slots__}
        vals.update(kw)
        return GradArena(**vals)

    def tree_flatten_with_keys(self):
        children = tuple((jax.tree_util.GetAttrKey(f), getattr(self, f))
                         for f in _ARENA_FIELDS)
        return children, self.phase

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, phase=aux)

    def __repr__(self):
        return (f"GradArena(phase={self.phase}, " +
                ", ".join(f"{f}={getattr(self, f)!r}"
                          for f in _ARENA_FIELDS) + ")")


RING_VERSION = 2  # layout written by init_arena (v1 kept for tests/migration)


def init_arena(layout: ArenaLayout, tau: int, n_pods: int,
               compression: str = "none",
               ring_version: int = RING_VERSION,
               variable: bool = False) -> Optional[GradArena]:
    """Allocate the delay state. ``tau`` is the staleness depth; with
    ``variable=True`` it is the CAP ``tau_max`` of a stochastic delay
    process and the ring becomes delay-tolerant: the v2 static-phase
    schedule over tau+1 slots plus per-slot ``due``/``stale`` metadata
    (``push_pop_variable`` consumes it), stored STACKED as one
    (tau+1, n_pods, rows, 128) buffer — layout v3 — so the pop can
    dynamically gather the due slots (CPU) or stream them through one
    Pallas kernel (TPU) instead of reading tau+1 separate buffers."""
    if tau == 0:
        return None
    if ring_version not in (1, 2):
        raise ValueError(f"unknown ring_version {ring_version!r}")
    if variable and ring_version != 2:
        raise ValueError("the delay-tolerant (variable-delay) ring "
                         "extends the default v2 schedule (stored "
                         "stacked as layout v3); ring_version=1 has no "
                         "delay-tolerant form")
    R = layout.rows
    v2 = ring_version == 2
    n_slots = tau + 1 if v2 else tau
    stacked = variable or not v2   # v1 and v3 share the stacked shape
    # staging presence depends only on the CONFIG (int8), never on the
    # backend: TrainState structure and the checkpoint key-set must be
    # identical across hosts (a CPU-saved checkpoint restores on TPU).
    staging = None
    if compression == "int8":
        if stacked:
            ring = jnp.zeros((n_slots, n_pods, R, LANES), jnp.int8)
            scales = jnp.ones((n_slots, n_pods, R), jnp.float32)
        else:
            ring = tuple(jnp.zeros((n_pods, R, LANES), jnp.int8)
                         for _ in range(n_slots))
            scales = tuple(jnp.ones((n_pods, R), jnp.float32)
                           for _ in range(n_slots))
        residual = jnp.zeros((n_pods, R, LANES), jnp.float32)
        staging = jnp.zeros((n_pods, R, LANES), jnp.float32)
    else:
        if stacked:
            ring = jnp.zeros((n_slots, n_pods, R, LANES), jnp.float32)
        else:
            ring = tuple(jnp.zeros((n_pods, R, LANES), jnp.float32)
                         for _ in range(n_slots))
        scales = residual = None
    due = stale = None
    if variable:
        # due = -1: never applied (matches no step counter, which
        # starts at 0); stale = 0 until a real push tags the slot
        due = jnp.full((n_slots,), -1, jnp.int32)
        stale = jnp.zeros((n_slots,), jnp.int32)
    return GradArena(ring=ring, scales=scales, residual=residual,
                     staging=staging,
                     counts=jnp.zeros((n_slots, n_pods), jnp.float32),
                     head=jnp.zeros((), jnp.int32), due=due, stale=stale,
                     phase=0)


def ring_version(arena: GradArena) -> int:
    """2 when the ring is the per-slot tuple layout (fixed rings, the
    default); 3 for the stacked delay-tolerant ring (one (n_slots, ...)
    buffer plus due/stale metadata); 1 for the legacy stacked fixed
    ring."""
    if isinstance(arena.ring, tuple):
        return 2
    return 3 if is_variable(arena) else 1


def is_variable(arena: GradArena) -> bool:
    """True for delay-tolerant rings (per-slot due/stale metadata)."""
    return arena.due is not None


def arena_tau(arena: GradArena) -> int:
    """The staleness depth tau this arena implements (v2/v3 carry one
    spare slot beyond tau)."""
    v = ring_version(arena)
    if v == 2:
        return len(arena.ring) - 1
    if v == 3:
        return int(arena.ring.shape[0]) - 1
    return int(arena.ring.shape[0])


def convert_ring(arena: GradArena, version: int) -> GradArena:
    """Convert between ring layouts. v1 slot ``(head+i) % tau`` (the
    i-th oldest entry) becomes v2 slot ``1+i`` with phase/head reset to
    0 (v2 pops slot phase+1 first, so slot 1 must hold the oldest
    entry; slot 0 — the first push target — is dead and zeroed).
    Requires a concrete (non-traced) head. Checkpoint restore performs
    the same permutation at the numpy level."""
    if ring_version(arena) == version:
        return arena
    if is_variable(arena):
        raise ValueError("variable-delay rings have no v1 layout and "
                         "no per-slot v2 form (they are always the "
                         "stacked v3 layout, which carries the "
                         "due/stale metadata)")
    if version == 2:
        tau = int(arena.ring.shape[0])
        h = int(arena.head)
        perm = [(h + i) % tau for i in range(tau)]
        ring = ((jnp.zeros_like(arena.ring[0]),)
                + tuple(arena.ring[k] for k in perm))
        scales = None
        if arena.scales is not None:
            scales = ((jnp.ones_like(arena.scales[0]),)
                      + tuple(arena.scales[k] for k in perm))
        counts = jnp.concatenate(
            [jnp.zeros_like(arena.counts[:1]), arena.counts[perm]])
        return arena._replace(ring=ring, scales=scales, counts=counts,
                              head=jnp.zeros((), jnp.int32), phase=0)
    if version == 1:
        tau = len(arena.ring) - 1
        p = arena.phase
        perm = [(p + 1 + i) % (tau + 1) for i in range(tau)]
        ring = jnp.stack([arena.ring[k] for k in perm])
        scales = None
        if arena.scales is not None:
            scales = jnp.stack([arena.scales[k] for k in perm])
        counts = jnp.stack([arena.counts[k] for k in perm])
        return arena._replace(ring=ring, scales=scales, counts=counts,
                              head=jnp.zeros((), jnp.int32), phase=0)
    raise ValueError(f"unknown ring_version {version!r}")


def sync_ring_phase(tree):
    """Re-derive every v2/v3 arena's static ``phase`` from its
    (restored) ``head`` leaf. Checkpoint restore rebuilds state with
    the template's phase; the saved schedule position lives in the head
    array, so this runs once after every restore (heads are concrete
    there)."""
    def fix(a):
        if isinstance(a, GradArena) and ring_version(a) in (2, 3):
            return a._replace(phase=int(a.head) % len(a.ring))
        return a
    return jax.tree_util.tree_map(
        fix, tree, is_leaf=lambda x: isinstance(x, GradArena))


def arena_logical_axes(arena: GradArena) -> GradArena:
    """Logical axes per arena field (None fields stay None). Rows shard
    over the intra-pod slice ("flat"); slots replicated; pods on 'pod'.
    v2 rings get one (pod, flat, None) entry per slot buffer; the
    stacked layouts (v1 fixed, v3 delay-tolerant) one entry with a
    replicated leading slot dim."""
    if ring_version(arena) == 2:
        ring_ax = tuple(("pod", "flat", None) for _ in arena.ring)
        scales_ax = (None if arena.scales is None
                     else tuple(("pod", "flat") for _ in arena.scales))
    else:
        ring_ax = (None, "pod", "flat", None)
        scales_ax = None if arena.scales is None else (None, "pod", "flat")
    return GradArena(
        ring=ring_ax,
        scales=scales_ax,
        residual=None if arena.residual is None else ("pod", "flat", None),
        staging=None if arena.staging is None else ("pod", "flat", None),
        counts=(None, "pod"),
        head=(),
        due=None if arena.due is None else (None,),      # replicated
        stale=None if arena.stale is None else (None,),  # replicated
        phase=arena.phase,   # aux must match for tree.maps over both
    )


def row_scales(layout: ArenaLayout, fed) -> jax.Array:
    """Per-row int8 scales reproducing the pytree path's per-(pod,leaf)
    symmetric scales bit-exactly. fed: (n_pods, rows, 128) f32 — the
    error-fed gradient. One elementwise pass + a segment-max over the
    static row->leaf map; no per-leaf kernel launches."""
    rowmax = jnp.max(jnp.abs(fed), axis=-1)                 # (n_pods, rows)
    amax = jax.ops.segment_max(rowmax.T, layout.row_to_leaf,
                               num_segments=layout.n_leaves + 1,
                               indices_are_sorted=True)     # (leaves+1, pods)
    # sentinel segment (tail pad rows / empty) -> scale 1: pads are zero
    amax = amax.at[layout.n_leaves].set(127.0)
    scales = jnp.maximum(amax, 1e-12) / 127.0               # pytree formula
    return scales[layout.row_to_leaf].T                     # (n_pods, rows)


def _pop_sum(ring, head, scales=None):
    """v1: pod-sum of ring[head] (dequantized), mesh-aware.

    Under an active multi-pod sharding profile: pop the whole slot,
    pin the *compressed* payload across the pod axis (int8 — those are
    the actual DCN bytes, mirroring the pytree path's pop_leaf),
    dequantize locally, and reduce with one pod-axis ``jnp.sum`` — the
    reduce GSPMD lowers to the DCN all-reduce.

    Off-mesh: unrolled per-pod slice adds WITHOUT materializing the
    (n_pods, rows, 128) popped buffer — XLA:CPU's axis-0 reduce of a
    dynamic slice is ~4x slower than chained adds."""
    from repro.dist.context import active_mesh, constrain
    _, n_pods, rows, _ = ring.shape
    head = jnp.asarray(head, jnp.int32)

    mesh = active_mesh()
    if mesh is not None and mesh.n_pods > 1:
        popped = jax.lax.dynamic_index_in_dim(ring, head, 0,
                                              keepdims=False)
        if scales is not None:
            # pod-REPLICATE the int8 payload (as the pytree pop_leaf
            # does): the gather of the compressed bytes is the actual
            # DCN transfer; dequantization happens after, locally
            popped = constrain(popped, (None, "flat", None))
            s = jax.lax.dynamic_index_in_dim(scales, head, 0,
                                             keepdims=False)
            s = constrain(s, (None, "flat"))
            popped = jax.lax.optimization_barrier(
                popped.astype(jnp.float32) * s[..., None])
        with jax.named_scope("master.exchange"):
            return jnp.sum(popped, axis=0)

    acc = None
    for p in range(n_pods):
        x = jax.lax.dynamic_slice(
            ring, (head, jnp.int32(p), jnp.int32(0), jnp.int32(0)),
            (1, 1, rows, LANES)).reshape(rows, LANES)
        if scales is not None:
            s = jax.lax.dynamic_slice(
                scales, (head, jnp.int32(p), jnp.int32(0)),
                (1, 1, rows)).reshape(rows)
            # barrier mirrors delayed._dequantize: without it the
            # accumulate contracts to fma(q, s, acc) and drifts a ULP
            # off the pytree reference
            x = jax.lax.optimization_barrier(
                x.astype(jnp.float32) * s[:, None])
        acc = x if acc is None else acc + x
    return acc


def _slot_pop_sum(slot, scales_slot=None):
    """Pod-sum of ONE v2 slot (dequantized), mesh-aware — the per-slot
    twin of ``_pop_sum``: the slot was selected by a static phase
    index, so no dynamic slicing remains at all.

    Under an active multi-pod sharding profile: pin the *compressed*
    payload across the pod axis (int8 — those are the actual DCN
    bytes), dequantize locally, reduce with one pod-axis ``jnp.sum``
    (GSPMD lowers the reduce to the DCN all-reduce). Off-mesh: the
    deterministic left fold shared with the pytree path."""
    from repro.dist.context import active_mesh, constrain
    n_pods = slot.shape[0]

    mesh = active_mesh()
    if mesh is not None and mesh.n_pods > 1:
        if scales_slot is not None:
            q = constrain(slot, (None, "flat", None))
            s = constrain(scales_slot, (None, "flat"))
            slot = jax.lax.optimization_barrier(
                q.astype(jnp.float32) * s[..., None])
        with jax.named_scope("master.exchange"):
            return jnp.sum(slot, axis=0)

    acc = None
    for p in range(n_pods):
        x = slot[p]
        if scales_slot is not None:
            # barrier mirrors delayed._dequantize (see _pop_sum)
            x = jax.lax.optimization_barrier(
                x.astype(jnp.float32) * scales_slot[p][:, None])
        acc = x if acc is None else acc + x
    return acc


def _replace_slot(slots: tuple, k: int, new):
    return slots[:k] + (new,) + slots[k + 1:]


def _int8_quantize(layout: ArenaLayout, arena: GradArena, pod_grads):
    """The int8 push arithmetic shared by every ring layout: scatter
    fed = g + residual into staging, per-row scales, quantize,
    error-feedback residual. ONE definition keeps the fixed and
    delay-tolerant schedules byte-for-byte by construction — the
    fixed/variable bit-exactness suites ride on this arithmetic being
    literally shared. Returns (q f32, scale_new, residual, fed)."""
    fed = scatter_fed(layout, pod_grads, arena.residual,
                      out=arena.staging)
    scale_new = row_scales(layout, fed)
    s = scale_new[..., None]
    q = jnp.clip(jnp.round(fed / s), -127, 127)
    # barrier mirrors delayed._dequantize: no FMA contraction, so the
    # residual stays bit-identical to the pytree path
    residual = fed - jax.lax.optimization_barrier(q * s)
    return q, scale_new, residual, fed


def _int8_slot_push(layout: ArenaLayout, arena: GradArena, k: int,
                    pod_grads):
    """v2 int8 push into per-slot buffer ``k``.
    Returns (slot_new, scales_new, residual, staging)."""
    q, scale_new, residual, fed = _int8_quantize(layout, arena, pod_grads)
    # write the quantized slot through a (full-shape) update-slice on
    # the donated slot: a plain value assignment makes XLA:CPU
    # materialize q in a fresh buffer and COPY it into the aliased
    # slot (2 slot copies, measured); the update-slice writes in place
    slot_new = jax.lax.dynamic_update_slice(
        arena.ring[k], q.astype(jnp.int8), (0, 0, 0))
    sc_new = jax.lax.dynamic_update_slice(
        arena.scales[k], scale_new, (0, 0))
    return slot_new, sc_new, residual, fed


def _push_pop_v2(layout: ArenaLayout, arena: GradArena, pod_grads,
                 pod_counts, compression: str, impl: str,
                 interpret: Optional[bool]):
    """One v2 rotation: pop slot (phase+1) % (tau+1), push slot phase —
    two different buffers, both statically indexed, so the pop read
    and the in-place push write can never alias (zero copy-protection;
    see GradArena). The spare slot is exactly the one whose entry was
    consumed LAST step, so its contents are dead by construction."""
    n_slots = len(arena.ring)
    push_i = arena.phase
    pop_i = (arena.phase + 1) % n_slots
    old_count = arena.counts[pop_i]       # static index

    if compression == "int8":
        if impl in ("pallas", "pallas_sharded"):
            # flatten into staging, form fed once: the scale pass needs
            # it, and the kernel consumes it directly (writing the new
            # residual into its buffer)
            with jax.named_scope("master.stage"):
                g_flat = flatten_tree(layout, pod_grads, leading=1,
                                      out=arena.staging)
                fed = g_flat + arena.residual
                scale_new = row_scales(layout, fed)
            # buffer swap: the old residual becomes next step's scratch
            staging = arena.residual
            if impl == "pallas_sharded":
                from repro.dist.context import active_mesh
                from repro.kernels.delay_ring.ops import \
                    ring_slot_rotate_int8_sharded
                with jax.named_scope("master.ring"):
                    grad_sum, slot_new, sc_new, residual = \
                        ring_slot_rotate_int8_sharded(
                            arena.ring[pop_i], arena.scales[pop_i],
                            arena.ring[push_i], arena.scales[push_i],
                            fed, scale_new, mesh_cfg=active_mesh(),
                            interpret=interpret)
            else:
                from repro.kernels.delay_ring.ops import \
                    ring_slot_rotate_int8
                with jax.named_scope("master.ring"):
                    popped, slot_new, sc_new, residual = \
                        ring_slot_rotate_int8(
                            arena.ring[pop_i], arena.scales[pop_i],
                            arena.ring[push_i], arena.scales[push_i],
                            fed, scale_new, interpret=interpret)
                grad_sum = _pod_fold(popped)  # pod sum = DCN all-reduce
        else:
            with jax.named_scope("master.ring"):
                grad_sum = _slot_pop_sum(arena.ring[pop_i],
                                         arena.scales[pop_i])
            with jax.named_scope("master.stage"):
                slot_new, sc_new, residual, staging = _int8_slot_push(
                    layout, arena, push_i, pod_grads)
        ring = _replace_slot(arena.ring, push_i, slot_new)
        scales = _replace_slot(arena.scales, push_i, sc_new)
    else:
        # No kernel at all: the pop is a read of one statically-chosen
        # slot, the push scatters the per-leaf strips straight into the
        # (donated) spare slot's buffer — under v2 the f32 ring
        # rotation IS just those two XLA ops, on every backend.
        with jax.named_scope("master.ring"):
            grad_sum = _slot_pop_sum(arena.ring[pop_i])
        with jax.named_scope("master.stage"):
            slot_new = flatten_tree(layout, pod_grads, leading=1,
                                    out=arena.ring[push_i])
        ring = _replace_slot(arena.ring, push_i, slot_new)
        scales, residual = None, None
        staging = arena.staging    # untouched pass-through (zero cost)

    count = jnp.sum(old_count)
    next_phase = (arena.phase + 1) % n_slots
    new_arena = GradArena(
        ring=ring, scales=scales, residual=residual, staging=staging,
        counts=arena.counts.at[push_i].set(pod_counts),
        head=jnp.full((), next_phase, jnp.int32),   # trace-time constant
        phase=next_phase)
    return grad_sum, count, new_arena


def _pod_fold(popped):
    """Deterministic left fold over the pod axis of an already-
    dequantized popped slot (the kernel path's pod reduction)."""
    from repro.core.delayed import pod_sum
    with jax.named_scope("master.exchange"):
        return pod_sum(popped)


def push_pop(layout: ArenaLayout, arena: GradArena, pod_grads, pod_counts,
             compression: str = "none", impl: str = "auto",
             interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array, GradArena]:
    """Arena twin of ``delayed.push_pop``: insert this step's
    pod-stacked gradient *tree*, return the tau-old entry summed over
    pods (the DCN collective) and the updated arena.

    pod_grads: pytree, leaves (n_pods, *shape). Returns
    (grad_sum (rows, 128) f32, count (), new_arena).

    v2 rings rotate with fully static slot indices (see
    ``_push_pop_v2``); the only kernel left is the int8 rotate —
    impl="auto" picks Pallas for it on TPU, the XLA elementwise chain
    elsewhere, and the shard_map-wrapped kernel on a multi-pod mesh
    (requires an ambient mesh, ``jax.set_mesh``; the pop's pod
    reduction then happens inside the wrapper, int8 payload crossing
    the DCN compressed). v1 rings keep the stacked-buffer paths:
    lax.switch scatter + dynamic pop on XLA, scalar-prefetched-head
    kernel on single-pod TPU.
    """
    from repro.kernels import resolve_impl
    from repro.kernels.delay_ring.ops import ring_push_pop

    if is_variable(arena):
        raise ValueError("delay-tolerant arenas rotate via "
                         "push_pop_variable (per-step tau_t), not the "
                         "fixed-tau push_pop")
    # only v2 has the shard_map wrapper: a v1 arena on a multi-pod
    # mesh must keep auto-resolving to the XLA ref path
    impl = resolve_impl(impl, pod_shard_map=ring_version(arena) == 2)
    if ring_version(arena) == 2:
        return _push_pop_v2(layout, arena, pod_grads, pod_counts,
                            compression, impl, interpret)
    if impl == "pallas_sharded":   # only reachable when forced explicitly
        raise ValueError("the shard_map'd delay-ring path needs ring "
                         "layout v2 (per-slot buffers); migrate the "
                         "arena with convert_ring(arena, 2)")
    head = arena.head
    old_count = arena.counts[head]

    if impl == "pallas":
        with jax.named_scope("master.stage"):
            g_flat = flatten_tree(layout, pod_grads, leading=1,
                                  out=arena.staging)
        if compression == "int8":
            with jax.named_scope("master.stage"):
                fed = g_flat + arena.residual
                scale_new = row_scales(layout, fed)
            with jax.named_scope("master.ring"):
                popped, ring, scales, residual = ring_push_pop(
                    arena.ring, fed, head, scales=arena.scales,
                    scale_new=scale_new, impl="pallas",
                    interpret=interpret)
            staging = arena.residual
        else:
            with jax.named_scope("master.ring"):
                popped, ring, scales, residual = ring_push_pop(
                    arena.ring, g_flat, head, impl="pallas",
                    interpret=interpret)
            # "none" carries no staging (g_flat was a fresh temp) —
            # keep the state structure identical to init_arena's
            staging = arena.staging
        grad_sum = _pod_fold(popped)        # pod sum = DCN all-reduce
    elif compression == "int8":
        with jax.named_scope("master.stage"):
            fed = scatter_fed(layout, pod_grads, arena.residual,
                              out=arena.staging)
            scale_new = row_scales(layout, fed)
        with jax.named_scope("master.ring"):
            grad_sum = _pop_sum(arena.ring, head, arena.scales)
        with jax.named_scope("master.stage"):
            s = scale_new[..., None]
            q = jnp.clip(jnp.round(fed / s), -127, 127)
        # XLA sequences the slot read above ahead of this in-place
        # overwrite itself (copy-protection where it must)
        with jax.named_scope("master.ring"):
            ring, scales = _update_slot_int8(arena.ring, arena.scales,
                                             q.astype(jnp.int8),
                                             scale_new, head)
        # barrier mirrors delayed._dequantize: no FMA contraction, so
        # the residual stays bit-identical to the pytree reference
        with jax.named_scope("master.stage"):
            residual = fed - jax.lax.optimization_barrier(q * s)
        staging = fed
    else:
        with jax.named_scope("master.ring"):
            grad_sum = _pop_sum(arena.ring, head)
        with jax.named_scope("master.stage"):
            ring = _scatter_slot(layout, arena.ring, pod_grads, head)
        staging = arena.staging    # untouched pass-through (zero cost)
        scales = residual = None

    count = jnp.sum(old_count)
    new_arena = GradArena(
        ring=ring, scales=scales, residual=residual, staging=staging,
        counts=arena.counts.at[head].set(pod_counts),
        head=(head + 1) % arena.counts.shape[0], phase=0)
    return grad_sum, count, new_arena


def _variable_pop_ref(ring, scales, mask):
    """Reference pop of the stacked delay-tolerant ring: fold the due
    slots, mesh-aware.

    Off-mesh (the CPU fast path): a data-dependent GATHER — sort the
    due slot indices to the front and branch on the arrival count H, so
    the step reads O(arrivals) slots instead of all tau_max+1 (the
    3-4x read amplification the old full masked fold paid; arrivals
    average ~1/step because the delay process conserves pushes). H = 1,
    by far the common case, is a single dynamic-slice read folded
    exactly like the static path's ``_slot_pop_sum`` — which is what
    keeps the constant-sequence degeneration bit-identical.

    Under an active multi-pod sharding profile: masks are elementwise,
    so each pod shard folds its own due slots LOCALLY (dequantizing in
    place) and ONE pod-axis ``jnp.sum`` — a single f32 DCN all-reduce —
    replaces the per-slot reduces the old fold issued n_slots times."""
    from repro.dist.context import active_mesh, constrain
    n_slots, n_pods, rows, _ = ring.shape

    mesh = active_mesh()
    if mesh is not None and mesh.n_pods > 1:
        x = constrain(ring, (None, "pod", "flat", None))
        if scales is not None:
            s = constrain(scales, (None, "pod", "flat"))
            # barrier mirrors delayed._dequantize (see _slot_pop_sum)
            x = jax.lax.optimization_barrier(
                x.astype(jnp.float32) * s[..., None])
        m = mask.astype(jnp.float32)[:, None, None, None]
        local = jnp.sum(m * x, axis=0)       # per-pod masked fold, local
        with jax.named_scope("master.exchange"):
            return jnp.sum(local, axis=0)    # ONE pod-axis DCN reduce

    def slot_pod_sum(j):
        q = jax.lax.dynamic_index_in_dim(ring, j, 0, keepdims=False)
        s = (None if scales is None else
             jax.lax.dynamic_index_in_dim(scales, j, 0, keepdims=False))
        acc = None
        for p in range(n_pods):
            x = q[p]
            if s is not None:
                # barrier mirrors delayed._dequantize (see _slot_pop_sum)
                x = jax.lax.optimization_barrier(
                    x.astype(jnp.float32) * s[p][:, None])
            acc = x if acc is None else acc + x
        return acc.astype(jnp.float32)

    # due slots sorted to the front (ascending j — the canonical fold
    # order), padded with n_slots
    order = jnp.sort(jnp.where(mask,
                               jnp.arange(n_slots, dtype=jnp.int32),
                               jnp.int32(n_slots)))
    H = jnp.sum(mask.astype(jnp.int32))
    zeros = jnp.zeros((rows, LANES), jnp.float32)
    return jax.lax.switch(
        jnp.minimum(H, 2),
        [lambda o: zeros,                    # H = 0: exact zero pop
         lambda o: slot_pod_sum(o[0]),       # H = 1: one slot, exactly
                                             #   the static single pop
         lambda o: jax.lax.fori_loop(        # H > 1: fold the H due
             0, H,                           #   slots in ascending j
             lambda i, acc: acc + slot_pod_sum(o[i]), zeros)],
        order)


def push_pop_variable(layout: ArenaLayout, arena: GradArena, pod_grads,
                      pod_counts, delay,
                      compression: str = "none", impl: str = "auto",
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 GradArena]:
    """Delay-tolerant rotation for a stochastic per-step delay process
    (``core.delay_process``): this step's gradient is pushed with a
    TRACED delay ``tau_t = delay`` (the host draws it; clipped to the
    ring cap) and applied ``tau_t`` steps later; the pop folds every
    slot whose entry is due exactly now.

    Generalizes the static v2 phase schedule, keeping every WRITE
    statically indexed (the copy-protection-free property the v2
    layout exists for):

      * ``head`` is the absolute step counter t; the push target is
        still slot ``phase = t % (tau_max+1)`` — a static index — whose
        previous entry was pushed at t - (tau_max+1) and therefore due
        at latest t-1: dead by construction, so no unread slot is ever
        overwritten (the property suite's first invariant);
      * the push tags its slot ``due[k] = t + tau_t`` and
        ``stale[k] = tau_t`` (the only delay-dependent state — i32
        metadata, not a dynamic slot index);
      * the pop folds ``(due[j] == t) * slot_j`` — late and
        out-of-order arrivals from different push epochs land in the
        one step they are due, zero-arrival steps pop an exact zero,
        and a constant sequence reduces to the static path's
        single-slot pop (pinned value-identical by
        tests/test_delay_process.py). The ring is STACKED (layout v3)
        so the fold can be a single pass: ``impl`` dispatches via
        ``resolve_impl`` — "ref" (auto off-TPU) is the gather fold of
        ``_variable_pop_ref`` (reads O(arrivals) slots, not tau_max+1),
        "pallas" streams all slots once through the
        ``ring_variable_pop`` kernel with the masked fold in registers,
        and "pallas_sharded" (auto on a multi-pod TPU mesh) runs the
        kernel per pod shard under shard_map and crosses the DCN with
        ONE reduce instead of n_slots of them.

    int8 compression keeps the fixed path's per-push quantization +
    error-feedback residual byte-for-byte (each slot still holds one
    compressed push and its per-row scales; the wire payload stays
    int8), only the pop-side fold widens.

    Also returns ``tau_obs`` — the count-weighted mean staleness of the
    gradients applied this step. On zero-arrival steps it is 0 by
    convention; consumers feeding a delay-ADAPTIVE step size must fall
    back to the ring cap on ``count == 0`` (see ``ambdg``) — 0 would
    claim a stall step is perfectly fresh.

    pod_grads: pytree, leaves (n_pods, *shape); delay: () i32.
    Returns (grad_sum (rows, 128) f32, count (), tau_obs () f32,
    new_arena).
    """
    from repro.kernels import resolve_impl

    if not is_variable(arena):
        raise ValueError("push_pop_variable needs a delay-tolerant "
                         "arena (init_arena(..., variable=True)); "
                         "fixed-tau rings rotate via push_pop")
    impl = resolve_impl(impl, pod_shard_map=True)
    n_slots = int(arena.ring.shape[0])
    k = arena.phase                      # static push slot: t % n_slots
    t = arena.head                       # traced absolute step counter
    delay = jnp.clip(jnp.asarray(delay, jnp.int32), 0, n_slots - 1)
    due = arena.due.at[k].set(t + delay)
    stale = arena.stale.at[k].set(delay)
    counts = arena.counts.at[k].set(pod_counts)

    if compression == "int8":
        # literally the fixed ref path's push arithmetic (shared
        # helper): per-push quantization + EF residual, byte-for-byte;
        # the slot index k is STATIC, so the stacked update-slices
        # write in place on the donated ring
        with jax.named_scope("master.stage"):
            q, scale_new, residual, staging = _int8_quantize(
                layout, arena, pod_grads)
        with jax.named_scope("master.ring"):
            ring = jax.lax.dynamic_update_slice(
                arena.ring, q.astype(jnp.int8)[None], (k, 0, 0, 0))
            scales = jax.lax.dynamic_update_slice(
                arena.scales, scale_new[None], (k, 0, 0))
    else:
        from repro.dist.context import active_mesh
        mesh = active_mesh()
        if mesh is not None and mesh.n_devices > 1:
            # GSPMD cannot keep the per-leaf row-offset update-slices
            # of the in-place slot scatter sharded — each one
            # rematerializes the WHOLE stacked ring through layout
            # copies (flagged by the matrix harness's ring-copy
            # invariant). Build the slot in a temp instead ("none"
            # carries no staging buffer — state structure is config-
            # determined) and land it with ONE row-aligned update,
            # exactly like the int8 branch above; the per-leaf traffic
            # stays on the temp.
            with jax.named_scope("master.stage"):
                fed = flatten_tree(layout, pod_grads, leading=1)
            with jax.named_scope("master.ring"):
                ring = jax.lax.dynamic_update_slice(
                    arena.ring, fed[None], (k, 0, 0, 0))
        else:
            # per-leaf scatter straight into stacked slot ring[k]: every
            # index (slot AND row offset) is static, so XLA:CPU chains
            # in-place update-slices on the donated buffer, like the v2
            # per-slot scatter (no temp slot, no copy-protection)
            with jax.named_scope("master.stage"):
                ring = _scatter(layout, pod_grads, 1, arena.ring, slot=k)
        staging = arena.staging       # untouched pass-through (zero cost)
        scales, residual = None, None

    # ---- single-pass pop: every slot due exactly at t ----
    # (reads the post-push ring, so a tau_t = 0 push delivers
    # synchronously through the same quantize/dequantize it would
    # cross the wire with)
    mask = due == t
    # per-slot metadata for the fused scalar epilogue: pod-summed
    # counts stacked over tagged staleness. Both rows are
    # small-integer-valued floats, so every fold order sums them
    # exactly — count/tau_obs stay bitwise impl-independent whether
    # the fold runs in the kernel epilogue (pallas impls, SMEM
    # output: no separate O(n_slots) metadata pass) or in the jnp
    # form below (ref impl — also the oracle pinned by
    # tests/test_delay_ring_interpret.py)
    cs = jnp.stack([jnp.sum(counts, axis=1),
                    stale.astype(jnp.float32)])
    if impl == "pallas_sharded":
        from repro.dist.context import active_mesh
        from repro.kernels.delay_ring.ops import ring_variable_pop_sharded
        with jax.named_scope("master.ring"):
            grad_sum, meta = ring_variable_pop_sharded(
                ring, mask, scales=scales, counts_stale=cs,
                mesh_cfg=active_mesh(), interpret=interpret)
        count, stale_sum = meta[0], meta[1]
    elif impl == "pallas":
        from repro.kernels.delay_ring.ops import ring_variable_pop
        with jax.named_scope("master.ring"):
            partial, meta = ring_variable_pop(
                ring, mask, scales=scales, counts_stale=cs,
                impl="pallas", interpret=interpret)
        grad_sum = _pod_fold(partial)   # pod sum = DCN all-reduce
        count, stale_sum = meta[0], meta[1]
    else:
        with jax.named_scope("master.ring"):
            grad_sum = _variable_pop_ref(ring, scales, mask)
        mf = mask.astype(jnp.float32)
        count = jnp.sum(mf * cs[0])
        stale_sum = jnp.sum(mf * cs[0] * cs[1])
    tau_obs = stale_sum / jnp.maximum(count, 1.0)

    new_arena = GradArena(
        ring=ring, scales=scales, residual=residual, staging=staging,
        counts=counts, head=t + 1, due=due, stale=stale,
        phase=(arena.phase + 1) % n_slots)
    return grad_sum, count, tau_obs, new_arena
