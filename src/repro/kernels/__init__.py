"""Pallas TPU kernels for the framework's compute hot-spots.

The paper's contribution is an optimizer/communication scheme (no kernel
of its own), but the framework's hot loops get TPU-native kernels:

  flash_attention/  blocked causal/SWA attention fwd + custom-vjp bwd
                    (dq + dkv kernels, lse recomputation — no S^2
                    residuals) on the projections' sequence-minor
                    layout; the training attention core on the TPU
                    from 1,024 tokens (models.layers.takes_flash)
  linear_scan/      chunked SSD / gated-linear-attention scan
                    (Mamba2 + mLSTM inner loop)
  dual_update/      fused dual-averaging update z += g; w = -alpha z
                    (the paper's eq. (3)-(4) hot loop, memory-bound);
                    the arena entry point also folds in the anytime
                    count-normalization g/count
  delay_ring/       fused delay-ring rotation on the flat gradient
                    arena: pop-oldest + push-new + int8 quantize/
                    dequantize with error feedback, one pass over the
                    slot (ring donated; v2 per-slot layout selects the
                    slot statically, v1 scalar-prefetches the head)

Each kernel directory: kernel.py (pl.pallas_call + BlockSpec), ops.py
(jit'd public wrapper with an interpret fallback for CPU), ref.py
(pure-jnp oracle used by the allclose tests).
"""
from __future__ import annotations

import math
from typing import Optional

import jax


def dim_shard(entry, mesh) -> int:
    """Devices a PartitionSpec entry shards one dimension over."""
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(int(mesh.shape[n]) for n in names)


def fit_block_rows(rows: int, want: int, *, int8: bool = False,
                   interpret: bool = False) -> int:
    """Largest block <= ``want`` dividing ``rows`` (gcd keeps it a
    multiple of 8 whenever rows is, which the arena layout guarantees
    down to any power-of-two device count).

    The chip refuses a block whose last two dims are neither whole
    tiles nor the full dims. A 32-bit row block is whole at 8 rows. The
    int8 kernels also stream per-row scales whose row dim is the 128
    lanes, so their block is 128 rows (or all of them). Outside
    interpret mode a block the chip would refuse raises here."""
    blk = math.gcd(rows, want)
    align = 128 if int8 else 8
    if not interpret and blk % align and blk != rows:
        raise ValueError(f"{rows} rows admit no block <= {want} rows "
                         f"that is a multiple of {align}")
    return blk


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode unless asked otherwise: off on the TPU,
    on elsewhere (the CPU tests run the kernels through the
    interpreter)."""
    return (not on_tpu()) if interpret is None else interpret


def resolve_impl(impl: str = "auto", *, pod_shard_map: bool = False) -> str:
    """Shared impl dispatch for the arena kernels (delay_ring,
    dual_update): "auto" resolves to Pallas on TPU and to the pure-XLA
    reference everywhere else.

    Multi-pod meshes: a bare pallas_call on a pod-sharded arena buffer
    would make GSPMD gather the whole buffer per device, so "auto"
    resolves to "ref" — UNLESS the caller has a shard_map wrapper
    (``pod_shard_map=True``: the v2 delay ring, the variable pop and
    the dual_update arena entry point), in which case it resolves to
    "pallas_sharded" and the fused kernel runs per shard. That wrapper
    needs the ambient concrete mesh (``jax.set_mesh``); a caller that
    has the wrapper but set no mesh is an error on the TPU, not a
    silent fall back to the XLA path."""
    if impl != "auto":
        return impl
    from repro.dist.context import active_mesh, ambient_mesh
    mesh = active_mesh()
    multi_pod = mesh is not None and mesh.n_pods > 1
    if not on_tpu():
        return "ref"
    if not multi_pod:
        return "pallas"
    if not pod_shard_map:
        return "ref"
    if ambient_mesh() is None:
        raise ValueError(
            "multi-pod sharding profile without an ambient mesh: run "
            "the step under `with jax.set_mesh(mesh):` so the arena "
            "kernels can shard_map over the 'pod' axis")
    return "pallas_sharded"
