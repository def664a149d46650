"""Mesh construction: the one constructor every mesh of the repo goes
through.

``make_mesh`` builds a ``jax.sharding.Mesh`` whose axes are all
``AxisType.Auto``: the sharding rules here are written for GSPMD
(``with_sharding_constraint`` on bare PartitionSpecs, shard_map over
named axes), and ``with_sharding_constraint`` refuses Explicit axes —
which is what ``jax.make_mesh`` builds by default.

The production reference shapes:

Single pod : (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips, 'pod' crosses DCN

Mesh shape is a harness axis too: ``parse_mesh`` turns a ``"DxM"`` /
``"PxDxM"`` spec string into a ``MeshConfig`` (a leading pod factor
> 1 adds the DCN-crossing ``pod`` axis), and ``mesh_label`` is its
inverse — the canonical cell label the dry-run and the matrix runner
emit.

Importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over the first ``prod(shape)`` of ``devices``
    (default: ``jax.devices()``), every axis Auto. For a ``MeshConfig``
    pass ``cfg.shape, cfg.axis_names``."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def mesh_config(multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(n_pods=2 if multi_pod else 1, data=16, model=16)


def parse_mesh(spec: str) -> MeshConfig:
    """``"16x16" -> MeshConfig(1, 16, 16)``,
    ``"2x8x8" -> MeshConfig(2, 8, 8)``.  Two factors are (data, model);
    three are (pod, data, model).  A three-factor spec with pod=1
    collapses to the two-axis mesh (``MeshConfig.axis_names`` only
    grows the ``pod`` axis when ``n_pods > 1``, so "1x8x8" and "8x8"
    are the same mesh — and the same label, see ``mesh_label``)."""
    try:
        dims = [int(d) for d in spec.lower().split("x")]
    except ValueError:
        raise ValueError(f"unparsable mesh spec {spec!r} "
                         "(want DxM or PxDxM, e.g. 8x8 or 2x16x16)")
    if len(dims) == 2:
        dims = [1] + dims
    if len(dims) != 3 or any(d < 1 for d in dims):
        raise ValueError(f"mesh spec {spec!r} must have 2 or 3 "
                         "positive factors (DxM or PxDxM)")
    return MeshConfig(n_pods=dims[0], data=dims[1], model=dims[2])


def mesh_label(cfg: MeshConfig) -> str:
    """Canonical cell label; inverse of ``parse_mesh``."""
    return "x".join(str(d) for d in cfg.shape)
