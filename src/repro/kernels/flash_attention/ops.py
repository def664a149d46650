"""Public jit'd wrapper for the flash-attention kernel.

On TPU this lowers to the Pallas kernel; elsewhere (or with
``interpret=True``) the kernel body is interpreted on CPU — used by the
allclose tests. The model layers call this through
``ModelConfig.attn_impl == "flash"``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    interp = resolve_interpret(interpret)
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interp)


__all__ = ["flash_attention", "attention_ref"]
