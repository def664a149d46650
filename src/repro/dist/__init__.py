"""Distribution layer: logical-axes sharding resolution and the spec
builders the launcher/dry-run uses to jit with full production
shardings.

    spec_for(axes, shape, mesh)     logical axes -> PartitionSpec
    shapes_and_axes(init_fn, *a)    abstract-eval an (arrays, axes) init
    batch_specs(model, rc)          specs for the global batch pytree
    state_specs(model, rc, init)    specs for the whole TrainState
    to_shardings(specs, mesh)       PartitionSpec tree -> NamedSharding
    jit_train_step(...)             the step jitted with those shardings
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.sharding import (_is_axes_leaf, shapes_and_axes,  # noqa: F401
                                 spec_for)

__all__ = ["batch_specs", "jit_train_step", "shapes_and_axes", "spec_for",
           "specs_for_state", "state_specs", "to_shardings"]


def batch_specs(model, rc):
    """Specs for the global batch: dim 0 is the batch dim (sharded over
    ('pod','data')), everything else replicated; scalars -> P()."""
    shapes = model.input_specs(rc.shape.global_batch, rc.shape.seq_len)
    return jax.tree.map(
        lambda sh: spec_for(
            (("batch",) + (None,) * (len(sh.shape) - 1)) if sh.shape else (),
            tuple(sh.shape), rc.mesh),
        shapes)


def state_specs(model, rc, init_state):
    """Specs for the full train state produced by ``init_state``. For
    the shared-master pipeline's TrainState:

      params      by their logical axes from ``model.init``
      opt_state   subtrees structurally matching params reuse the param
                  axes (dual z / momenta mirror params); (rows, 128)
                  leaves are arena buffers -> rows over the intra-pod
                  slice; scalars replicated
      buffer      pytree delay buffer via ``delayed.buffer_logical_axes``
      arena       flat delay ring via ``arena.arena_logical_axes``

    Strategy states wrap or replace TrainState and resolve through the
    same machinery: a wrapper with a ``base`` field (KBatchState)
    recurses into it with extra scalars replicated; the decentralized
    state's per-worker stacked leaves prepend a replicated worker dim
    (the worker axis lives on the strategy's own 1-D gossip mesh, not
    the pod mesh).
    """
    state_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    return specs_for_state(model, rc, state_shapes)


def specs_for_state(model, rc, state_shapes):
    """``state_specs`` on an already-abstract state tree."""
    from repro.core import arena as arena_mod
    from repro.core import delayed
    from repro.core.strategy import DecentralizedState, KBatchState

    if isinstance(state_shapes, KBatchState):
        return KBatchState(
            base=specs_for_state(model, rc, state_shapes.base),
            ref_epoch=P())

    _, params_axes = shapes_and_axes(model.init, jax.random.PRNGKey(0))

    if isinstance(state_shapes, DecentralizedState):
        p_specs = jax.tree.map(
            lambda ax, sh: spec_for((None,) + tuple(ax),
                                    tuple(sh.shape), rc.mesh),
            params_axes, state_shapes.params, is_leaf=_is_axes_leaf)
        return DecentralizedState(
            params=p_specs,
            z=spec_for((None, "flat", None),
                       tuple(state_shapes.z.shape), rc.mesh),
            residual=spec_for((None, "flat", None),
                              tuple(state_shapes.residual.shape),
                              rc.mesh),
            t=P(), step=P())

    def resolve(ax, sh):
        return spec_for(tuple(ax), tuple(sh.shape), rc.mesh)

    def resolve_tree(axes_tree, shapes_tree):
        return jax.tree.map(resolve, axes_tree, shapes_tree,
                            is_leaf=_is_axes_leaf)

    p_specs = resolve_tree(params_axes, state_shapes.params)
    params_structure = jax.tree.structure(state_shapes.params)

    def opt_specs(node):
        if isinstance(node, jax.ShapeDtypeStruct):
            if node.ndim == 2 and node.shape[-1] == 128:  # arena row buffer
                return resolve(("flat", None), node)
            return P()
        if jax.tree.structure(node) == params_structure:
            return resolve_tree(params_axes, node)
        return jax.tree.map(opt_specs, node, is_leaf=lambda c: c is not node)

    fields = {
        "params": p_specs,
        "opt_state": opt_specs(state_shapes.opt_state),
        "step": P(),
    }
    buffer_shapes = getattr(state_shapes, "buffer", None)
    if buffer_shapes is not None:
        buf_axes = delayed.buffer_logical_axes(
            params_axes, rc.ambdg.tau, rc.ambdg.pod_compression)
        fields["buffer"] = resolve_tree(buf_axes, buffer_shapes)
    arena_shapes = getattr(state_shapes, "arena", None)
    if arena_shapes is not None:
        fields["arena"] = resolve_tree(
            arena_mod.arena_logical_axes(arena_shapes), arena_shapes)
    return type(state_shapes)(**{
        f: fields.get(f) for f in state_shapes._fields})


def to_shardings(specs, mesh):
    """Map a PartitionSpec tree onto NamedShardings for one mesh."""
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


def retree_specs(specs, target):
    """Rebuild a spec tree onto ``target``'s (possibly different)
    structure — same array leaves, different static pytree metadata.

    Needed for jitted out_shardings of the train step: the arena's
    slot-schedule ``phase`` is static aux data that ADVANCES each step,
    so the output TrainState's structure differs from the input's in
    metadata only, and the input-derived spec tree would be rejected
    as an out_shardings prefix. Array-leaf count and order are
    identical, so the specs transplant 1:1."""
    leaves = jax.tree.flatten(specs,
                              is_leaf=lambda x: isinstance(x, P))[0]
    return jax.tree.unflatten(jax.tree.structure(target), leaves)


def jit_train_step(train_step, st_specs, b_specs, state, batch, mesh):
    """``train_step`` jitted on ``mesh`` with the state (donated) and
    batch placed by ``st_specs`` / ``b_specs``; metrics replicated.
    ``state`` and ``batch`` (arrays or ShapeDtypeStructs) fix the input
    structure: the arena's static slot phase advances every step, so a
    fixed-delay ring needs one jitted step per phase, and the output
    specs are the input specs moved onto the advanced structure."""
    st_specs = retree_specs(st_specs, state)
    with jax.set_mesh(mesh):
        out_state, out_metrics = jax.eval_shape(train_step, state, batch)
    metrics_spec = jax.tree.map(lambda _: P(), out_metrics)
    return jax.jit(
        train_step,
        in_shardings=(to_shardings(st_specs, mesh),
                      to_shardings(b_specs, mesh)),
        out_shardings=(to_shardings(retree_specs(st_specs, out_state),
                                    mesh),
                       to_shardings(metrics_spec, mesh)),
        donate_argnums=(0,))
