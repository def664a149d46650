"""Ambient sharding profile: lets model code pin intermediate
activations to logical axes without threading a mesh through every
call.

    with sharding_profile(rc.mesh):            # train profile
        ...
    with sharding_profile(rc.mesh, "serve"):   # serve profile
        ...

``constrain(x, axes)`` resolves the logical axes against the active
profile and applies ``with_sharding_constraint``; with no active
profile (unit tests, single-device runs — ``sharding_profile(None)``
also counts) it is the identity, so model code can call it
unconditionally.
"""
from __future__ import annotations

import contextlib
import threading

import jax

from repro.dist.sharding import spec_for

_state = threading.local()


def _active():
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


def active_mesh():
    """The MeshConfig of the active sharding profile, or None. Lets
    numeric code pick mesh-aware lowerings (e.g. a single pod-axis
    reduce -> DCN all-reduce) only when actually lowering for a mesh."""
    active = _active()
    return active[0] if active is not None else None


def profile_set() -> bool:
    """Whether a caller has entered ``sharding_profile`` (``None``
    counts: it is an explicit request for no mesh)."""
    return bool(getattr(_state, "stack", None))


def ambient_mesh():
    """The mesh set by ``with jax.set_mesh(mesh):`` as an
    ``AbstractMesh`` (readable inside and outside jit), or None. The
    shard_map wrappers around the arena kernels run over it — a
    MeshConfig names the axes but owns no devices."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


@contextlib.contextmanager
def sharding_profile(mesh_cfg, profile: str = "train"):
    """Activate (mesh, profile) for constrain(); ``mesh_cfg=None``
    deactivates (constrain becomes the identity inside the block)."""
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(None if mesh_cfg is None else (mesh_cfg, profile))
    try:
        yield
    finally:
        stack.pop()


def constrain(x, axes):
    """Pin ``x`` to the sharding its logical ``axes`` resolve to under
    the active profile (identity when none is active)."""
    active = _active()
    if active is None:
        return x
    mesh_cfg, profile = active
    spec = spec_for(tuple(axes), tuple(x.shape), mesh_cfg, profile=profile)
    if not isinstance(x, jax.core.Tracer):
        # outside jit the constraint is a transfer onto the concrete
        # devices; a bare spec names only the abstract mesh
        mesh = jax.sharding.get_mesh()
        if not mesh.empty:
            spec = jax.sharding.NamedSharding(mesh, spec)
    return jax.lax.with_sharding_constraint(x, spec)
