"""Public wrapper for the grouped matrix product of a held expert share.

``gmm(lhs, rhs, group_sizes)`` multiplies each group's rows of ``lhs``
(sorted by group) by that group's matrix ``rhs[g]`` and is
differentiable in ``lhs`` and ``rhs``. With the Pallas kernels the
forward is ``moe_gmm``, the backward ``moe_gmm`` again for the rows'
gradient (``rhs`` transposed inside the kernel) and ``moe_tgmm`` for the
matrices' gradient, tied by ``custom_vjp``. Rows in no group come out
unwritten there: the caller masks them, in and out.

``impl``: "auto" is the Pallas kernels on the TPU and the pure-jnp
oracle (``ref.py``) elsewhere; "pallas" off the chip runs the kernels in
interpret mode (the CPU tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu, resolve_interpret
from repro.kernels.grouped_matmul.kernel import gmm_fwd, tgmm_fwd
from repro.kernels.grouped_matmul.ref import gmm_ref, tgmm_ref

ROW_TILE = 512


def _row_tile(m: int) -> int:
    return ROW_TILE if m >= ROW_TILE else -(-m // 16) * 16


def _pad_rows(x, tm: int):
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _gmm_call(lhs, rhs, group_sizes, interpret: bool,
              transpose_rhs: bool = False):
    m = lhs.shape[0]
    tm = _row_tile(m)
    out = gmm_fwd(_pad_rows(lhs, tm), rhs, group_sizes, tm=tm,
                  transpose_rhs=transpose_rhs, interpret=interpret)
    return out[:m]


def _tgmm_call(lhs, rhs, group_sizes, n_groups: int, out_dtype,
               interpret: bool):
    tm = _row_tile(lhs.shape[0])
    return tgmm_fwd(_pad_rows(lhs, tm), _pad_rows(rhs, tm), group_sizes,
                    tm=tm, n_groups=n_groups, out_dtype=out_dtype,
                    interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, group_sizes, interpret: bool):
    return _gmm_call(lhs, rhs, group_sizes, interpret)


def _gmm_pallas_fwd(lhs, rhs, group_sizes, interpret):
    return (_gmm_call(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _gmm_pallas_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm_call(g, rhs, group_sizes, interpret, transpose_rhs=True)
    d_rhs = _tgmm_call(lhs, g, group_sizes, rhs.shape[0], rhs.dtype,
                       interpret)
    return d_lhs, d_rhs, None


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def gmm(lhs, rhs, group_sizes, *, impl: str = "auto", interpret=None):
    """lhs (m, k), rows sorted by group; rhs (G, k, n); group_sizes (G,)
    int32 with a sum of at most m -> (m, n) in lhs's dtype."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return gmm_ref(lhs, rhs, group_sizes)
    return _gmm_pallas(lhs, rhs, group_sizes.astype(jnp.int32),
                       resolve_interpret(interpret))


__all__ = ["gmm", "gmm_ref", "tgmm_ref"]
