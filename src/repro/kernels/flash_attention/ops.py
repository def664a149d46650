"""Public jit'd wrapper for the flash-attention forward kernel.

On TPU this lowers to the Pallas kernel; elsewhere (or with
``interpret=True``) the kernel body is interpreted on CPU — used by the
allclose tests. The model layers take the differentiable
``bwd.flash_attention_train`` where ``models.layers.takes_flash``
says the shape wins.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_forward
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, Dk); k: (B, Skv, Hkv, Dk); v: (B, Skv, Hkv, Dv)
    -> (B, Sq, Hq, Dv)."""
    return flash_forward(q, k, v, causal=causal, window=window,
                         interpret=resolve_interpret(interpret))


__all__ = ["flash_attention", "attention_ref"]
