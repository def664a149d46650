"""The grouped matrix products of the held-expert layer
(``kernels/grouped_matmul``) in Pallas interpret mode against the jnp
oracle: forward, the rows' and the matrices' gradients, with empty and
uneven groups, groups sharing a row tile, several row tiles, and rows
that belong to no group."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul.kernel import group_metadata
from repro.kernels.grouped_matmul.ops import _gmm_call, _tgmm_call, gmm
from repro.kernels.grouped_matmul.ref import gmm_ref, tgmm_ref

K, N = 64, 128
# (rows, group sizes): uneven with empty groups and rows past the last
# group; one group; nothing held; every row held; three row tiles of
# 512 (1100 rows padded to 1536) with groups crossing tile edges
CASES = {
    "uneven": (96, [10, 0, 37, 20]),
    "one_group": (64, [0, 64, 0]),
    "none_held": (48, [0, 0, 0, 0]),
    "full": (80, [16, 24, 40]),
    "tiles": (1100, [300, 0, 500, 1, 250]),
}


def _inputs(m, sizes, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(m))
    lhs = jax.random.normal(k1, (m, K), dtype)
    rhs = jax.random.normal(k2, (len(sizes), K, N), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(m) < int(sum(sizes)))[:, None]
    return jnp.where(valid, lhs, 0), rhs, gs, valid


@pytest.mark.parametrize("case", sorted(CASES))
def test_gmm_forward(case):
    lhs, rhs, gs, valid = _inputs(*CASES[case])
    got = jnp.where(valid, gmm(lhs, rhs, gs, impl="pallas"), 0)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gmm_ref(lhs, rhs, gs)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case", sorted(CASES))
def test_gmm_gradients(case):
    lhs, rhs, gs, valid = _inputs(*CASES[case])
    ct = jax.random.normal(jax.random.PRNGKey(9), (lhs.shape[0], N))

    def loss(impl):
        def f(x, w):
            y = gmm(jnp.where(valid, x, 0), w, gs, impl=impl)
            return jnp.sum(jnp.where(valid, y, 0) * ct)
        return jax.grad(f, argnums=(0, 1))(lhs, rhs)

    for got, want in zip(loss("pallas"), loss("ref")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("case", sorted(CASES))
def test_tgmm_zeroes_empty_groups(case):
    m, sizes = CASES[case]
    lhs, _, gs, _ = _inputs(m, sizes)
    rhs = jax.random.normal(jax.random.PRNGKey(3), (m, N))
    got = _tgmm_call(lhs, rhs, gs, len(sizes), jnp.float32, True)
    want = tgmm_ref(lhs, rhs, gs, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.any(np.asarray(got[g]))


@pytest.mark.parametrize("case", ["uneven", "tiles"])
def test_transposed_rhs_forward(case):
    """The rows' gradient product: rhs given as (G, n, k) and
    transposed inside the kernel."""
    lhs, rhs, gs, valid = _inputs(*CASES[case])
    got = _gmm_call(lhs, jnp.swapaxes(rhs, 1, 2), gs, True,
                    transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(jnp.where(valid, got, 0)),
                               np.asarray(gmm_ref(lhs, rhs, gs)),
                               rtol=1e-5, atol=1e-4)


def test_group_metadata_visits_each_group_tile():
    """Rows 0-299 group 0, 300-799 group 2, row 800 group 3, 801-1050
    group 4, with 512-row tiles: tile 0 holds groups 0 and 2, tile 1
    groups 2, 3 and 4, tile 2 group 4; the empty group 1 costs no
    visit in gmm and one in tgmm."""
    gs = jnp.asarray([300, 0, 500, 1, 250], jnp.int32)
    (offsets, group_ids, tiles), n = group_metadata(gs, 1536, 512, False)
    n = int(n)
    assert n == 6
    assert list(np.asarray(group_ids[:n])) == [0, 2, 2, 3, 4, 4]
    assert list(np.asarray(tiles[:n])) == [0, 0, 1, 1, 1, 2]
    _, n_empty = group_metadata(gs, 1536, 512, True)
    assert int(n_empty) == 7
