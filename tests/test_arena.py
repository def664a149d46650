"""Flat gradient arena: the fused master pipeline (flatten -> ring
push/pop -> dual update -> unflatten) must be bit-exact vs the per-leaf
pytree reference path across staleness, pod count, and compression —
including int8 error-feedback telescoping and head wrap-around — and
must never re-flatten the tree with a full concatenate per step.

Ring layout v2 (per-slot buffers, static phase schedule) additionally
must be bit-exact vs the stacked v1 layout across the same matrix, must
survive a v1-checkpoint -> v2 migration mid-run, and must compile on
XLA:CPU with NO ring-dtype copy instructions at all (the whole-ring
copy-protection v1 pays for the pop-read/push-write hazard)."""
import dataclasses
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (AmbdgConfig, LINREG, MeshConfig, ModelConfig,
                                RunConfig, TRAIN_4K)
from repro.core import ambdg, anytime, arena, delayed
from repro.launch.hlo import copy_shapes
from repro.optim import make_arena_optimizer, make_optimizer

# odd, row-misaligned leaf sizes exercise padding in every leaf
SHAPES = {"a": (7,), "b": {"c": (3, 5), "d": (130,)}, "e": (257,)}


def _rc(tau, compression, optimizer="dual_averaging"):
    cfg = ModelConfig(name="t", family=LINREG, n_layers=0, d_model=0,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab_size=0,
                      linreg_dim=8)
    return RunConfig(model=cfg, shape=TRAIN_4K,
                     mesh=MeshConfig(n_pods=1, data=1, model=1),
                     ambdg=AmbdgConfig(tau=tau, b_bar=8.0, smoothness_L=2.0,
                                       pod_compression=compression),
                     optimizer=optimizer)


def _params(key):
    leaves, treedef = jax.tree.flatten(
        SHAPES, is_leaf=lambda x: isinstance(x, tuple))
    ks = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [jax.random.normal(k, s, jnp.float32)
                  for k, s in zip(ks, leaves)])


def _pod_grads(key, n_pods):
    shapes, treedef = jax.tree.flatten(
        SHAPES, is_leaf=lambda x: isinstance(x, tuple))
    ks = jax.random.split(key, len(shapes))
    return jax.tree.unflatten(
        treedef, [jax.random.normal(k, (n_pods,) + s, jnp.float32)
                  for k, s in zip(ks, shapes)])


@pytest.mark.parametrize("compression", ["none", "int8"])
@pytest.mark.parametrize("n_pods", [1, 4])
@pytest.mark.parametrize("tau", [0, 1, 4])
def test_arena_bitexact_vs_pytree(tau, n_pods, compression):
    """10 steps (tau=4 wraps the ring twice): params and the dual
    variable z must match the pytree reference bit for bit.

    One documented exception: int8 with n_pods > 1. XLA:CPU duplicates
    the dequantize+pod-sum chain into multiple fusions and lowers the
    fold of array slices with different association per fusion, so the
    two jitted programs differ by a few ULP of the summands (the
    error-feedback residual keeps the drift bounded — it does not
    accumulate). There we assert ULP-level agreement instead; see
    docs/arena.md."""
    rc = _rc(tau, compression)
    params = _params(jax.random.PRNGKey(0))
    layout = arena.make_layout(params)

    opt_p = make_optimizer(rc)
    opt_a = make_arena_optimizer(rc, layout)

    p_ref, p_arena = params, params
    opt_ref = opt_p.init(params)
    opt_ar = opt_a.init()
    buf = delayed.init_buffer(params, tau, n_pods, compression)
    ar = arena.init_arena(layout, tau, n_pods, compression)

    @jax.jit
    def step_ref(p, o, b, grads, counts):
        if b is not None:
            gs, c, b = delayed.push_pop(b, grads, counts, compression)
        else:
            gs = jax.tree.map(delayed.pod_sum, grads)
            c = jnp.sum(counts)
        g = anytime.normalize(gs, c)
        p, o = opt_p.update(o, p, g)
        return p, o, b

    @jax.jit
    def step_arena(p, o, a, grads, counts):
        p, o, a, _, _ = ambdg.arena_master_update(
            layout, opt_a, p, o, a, grads, counts, compression)
        return p, o, a

    if compression == "int8" and n_pods > 1:
        def check(a, b):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-6, atol=5e-7)
    else:
        def check(a, b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    for t in range(10):
        grads = _pod_grads(jax.random.PRNGKey(100 + t), n_pods)
        counts = jnp.full((n_pods,), 3.0 + t)
        p_ref, opt_ref, buf = step_ref(p_ref, opt_ref, buf, grads, counts)
        p_arena, opt_ar, ar = step_arena(p_arena, opt_ar, ar, grads, counts)

        for a_leaf, b_leaf in zip(jax.tree.leaves(p_ref),
                                  jax.tree.leaves(p_arena)):
            check(a_leaf, b_leaf)
        z_arena = arena.unflatten_tree(layout, opt_ar.z, cast=False)
        for a_leaf, b_leaf in zip(jax.tree.leaves(opt_ref.z),
                                  jax.tree.leaves(z_arena)):
            check(a_leaf, b_leaf)


def test_arena_l2_ball_matches_pytree():
    """l2_ball prox: elementwise ops match the pytree path; only the
    ball-norm reduction order differs (flat vs per-leaf sums), so the
    paths agree at ULP tolerance with the projection active."""
    rc = _rc(1, "none")
    rc = rc.replace(ambdg=dataclasses.replace(rc.ambdg, proximal="l2_ball",
                                              radius_C=0.05))
    params = _params(jax.random.PRNGKey(0))
    layout = arena.make_layout(params)
    opt_p, opt_a = make_optimizer(rc), make_arena_optimizer(rc, layout)
    p_ref, p_arena = params, params
    o_ref, o_ar = opt_p.init(params), opt_a.init()
    buf = delayed.init_buffer(params, 1, 2)
    ar = arena.init_arena(layout, 1, 2)
    projected = False
    for t in range(6):
        grads = _pod_grads(jax.random.PRNGKey(t), 2)
        counts = jnp.full((2,), 4.0)
        gs, c, buf = delayed.push_pop(buf, grads, counts)
        p_ref, o_ref = opt_p.update(o_ref, p_ref,
                                    anytime.normalize(gs, c))
        p_arena, o_ar, ar, _, _ = ambdg.arena_master_update(
            layout, opt_a, p_arena, o_ar, ar, grads, counts, "none")
        norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                  for x in jax.tree.leaves(p_ref))))
        projected = projected or abs(norm - rc.ambdg.radius_C) < 1e-5
        for a_leaf, b_leaf in zip(jax.tree.leaves(p_ref),
                                  jax.tree.leaves(p_arena)):
            np.testing.assert_allclose(np.asarray(a_leaf),
                                       np.asarray(b_leaf),
                                       rtol=2e-6, atol=1e-8)
    assert projected, "radius_C too large: projection never activated"


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_arena_optimizers_match_pytree(optimizer):
    """The flat-state sgd/adam arena optimizers reproduce the per-leaf
    implementations (allclose: identical formulas, FP-identical ops)."""
    rc = _rc(2, "none", optimizer=optimizer)
    params = _params(jax.random.PRNGKey(1))
    layout = arena.make_layout(params)
    opt_p, opt_a = make_optimizer(rc), make_arena_optimizer(rc, layout)
    p_ref, p_arena = params, params
    o_ref, o_ar = opt_p.init(params), opt_a.init()
    for t in range(5):
        grads = _pod_grads(jax.random.PRNGKey(t), 2)
        gs = jax.tree.map(lambda g: jnp.sum(g, axis=0), grads)
        count = jnp.float32(6.0)
        p_ref, o_ref = opt_p.update(o_ref, p_ref,
                                    anytime.normalize(gs, count))
        g_flat = arena.flatten_tree(layout, grads, leading=1)
        p_arena, o_ar = opt_a.update(o_ar, p_arena,
                                     jnp.sum(g_flat, axis=0), count)
        for a_leaf, b_leaf in zip(jax.tree.leaves(p_ref),
                                  jax.tree.leaves(p_arena)):
            np.testing.assert_array_equal(np.asarray(a_leaf),
                                          np.asarray(b_leaf))


def test_flatten_roundtrip_exact():
    params = _params(jax.random.PRNGKey(2))
    layout = arena.make_layout(params)
    mat = arena.flatten_tree(layout, params)
    back = arena.unflatten_tree(layout, mat)
    for a_leaf, b_leaf in zip(jax.tree.leaves(params),
                              jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a_leaf), np.asarray(b_leaf))
    # pod-stacked round trip
    grads = _pod_grads(jax.random.PRNGKey(3), 4)
    g_flat = arena.flatten_tree(layout, grads, leading=1)
    assert g_flat.shape == (4, layout.rows, 128)
    back = arena.unflatten_tree(layout, g_flat)
    for a_leaf, b_leaf in zip(jax.tree.leaves(grads), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a_leaf), np.asarray(b_leaf))


def test_head_wraparound_semantics():
    """The entry applied at step t is the one pushed at t - tau, across
    several full ring rotations; the first tau pops are zero. Under
    ring v2 the schedule is the static ``phase`` (mirrored by the head
    leaf), cycling through the tau+1 per-slot buffers."""
    tau, n_pods = 2, 3
    params = {"w": jnp.zeros((5,))}
    layout = arena.make_layout(params)
    ar = arena.init_arena(layout, tau, n_pods)
    assert len(ar.ring) == tau + 1 and ar.phase == 0
    for t in range(1, 9):
        gs, c, ar = arena.push_pop(layout, ar,
                                   {"w": jnp.full((n_pods, 5), float(t))},
                                   jnp.full((n_pods,), float(t)))
        w = arena.unflatten_tree(layout, gs)["w"]
        if t <= tau:
            assert float(w[0]) == 0.0 and float(c) == 0.0
        else:
            assert float(w[0]) == (t - tau) * n_pods
            assert float(c) == (t - tau) * n_pods
        assert ar.phase == t % (tau + 1)
        assert int(ar.head) == ar.phase


@pytest.mark.parametrize("compression", ["none", "int8"])
def test_push_pop_pallas_branch_matches_ref(compression):
    """The Pallas branch (staging flatten + fused kernel, interpret on
    CPU) produces the same ring rotation as the scatter/XLA branch."""
    tau, n_pods = 2, 2
    params = _params(jax.random.PRNGKey(5))
    layout = arena.make_layout(params)
    ar_r = arena.init_arena(layout, tau, n_pods, compression)
    ar_p = arena.init_arena(layout, tau, n_pods, compression)
    for t in range(4):
        grads = _pod_grads(jax.random.PRNGKey(t), n_pods)
        counts = jnp.ones((n_pods,))
        gs_r, c_r, ar_r = arena.push_pop(layout, ar_r, grads, counts,
                                         compression, impl="ref")
        gs_p, c_p, ar_p = arena.push_pop(layout, ar_p, grads, counts,
                                         compression, impl="pallas",
                                         interpret=True)
        if compression == "none":
            np.testing.assert_allclose(np.asarray(gs_r), np.asarray(gs_p),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_array_equal(np.asarray(ar_r.ring),
                                          np.asarray(ar_p.ring))
        else:
            # a 1-ULP difference in the kernel's internal fed = g + r
            # can flip a round-half boundary: allow isolated single-step
            # quantization disagreements, nothing larger
            qd = np.abs(np.asarray(ar_r.ring, np.int32)
                        - np.asarray(ar_p.ring, np.int32))
            assert qd.max() <= 1 and (qd > 0).mean() < 1e-3
            step = float(np.asarray(ar_r.scales).max())
            gd = np.abs(np.asarray(gs_r) - np.asarray(gs_p))
            assert gd.max() <= 1.01 * n_pods * step + 1e-6
            assert (gd > 1e-6).mean() < 1e-3
        assert float(c_r) == float(c_p)


def test_int8_error_feedback_telescoping():
    """residual(t) = fed(t) - dequant(t) exactly, so over T steps:
    sum(applied) + sum(in-flight dequants) + residual_T = sum(true).
    The arena must preserve this telescoping invariant (no drift)."""
    tau, n_pods = 2, 1
    params = {"w": jnp.zeros((64,))}
    layout = arena.make_layout(params)
    ar = arena.init_arena(layout, tau, n_pods, "int8")
    rng = np.random.default_rng(0)
    true_total = np.zeros(64, np.float32)
    applied = np.zeros(64, np.float32)
    for t in range(20):
        g = 0.05 * rng.standard_normal((n_pods, 64)).astype(np.float32)
        true_total += g.sum(0)
        gs, _, ar = arena.push_pop(layout, ar, {"w": jnp.asarray(g)},
                                   jnp.ones((n_pods,)), compression="int8")
        applied += np.asarray(arena.unflatten_tree(layout, gs)["w"])
    # dequantize the tau entries still in flight + the residual; the
    # v1 view drops ring v2's spare slot (its entry is dead — already
    # popped and applied — so counting it would double-book)
    live = arena.convert_ring(ar, 1)
    in_flight = (np.asarray(live.ring, np.float32)
                 * np.asarray(live.scales)[..., None]).sum(axis=(0, 1))
    residual = np.asarray(ar.residual).sum(axis=0)
    total = applied + arena.unflatten_tree(
        layout, jnp.asarray(in_flight))["w"] + arena.unflatten_tree(
        layout, jnp.asarray(residual))["w"]
    np.testing.assert_allclose(np.asarray(total), true_total,
                               atol=1e-5, rtol=1e-5)


def _stack(x):
    """v2 slot tuples -> stacked numpy (v1 view helper for asserts)."""
    return np.stack([np.asarray(s) for s in x]) if isinstance(x, tuple) \
        else np.asarray(x)


@pytest.mark.parametrize("compression", ["none", "int8"])
@pytest.mark.parametrize("n_pods", [1, 4])
@pytest.mark.parametrize("tau", [1, 2, 4])
def test_ring_v2_matches_v1(tau, n_pods, compression):
    """Ring layout v2 (per-slot buffers, static phase) is bit-exact vs
    the stacked v1 layout across tau x pods x compression: same popped
    sums, same counts, and — through the v1 view, which undoes the
    phase permutation and drops the dead spare slot — the same ring
    contents, for 10 steps (tau=4 wraps the schedule twice)."""
    params = _params(jax.random.PRNGKey(0))
    layout = arena.make_layout(params)
    ar1 = arena.init_arena(layout, tau, n_pods, compression,
                           ring_version=1)
    ar2 = arena.init_arena(layout, tau, n_pods, compression,
                           ring_version=2)
    assert arena.ring_version(ar1) == 1 and arena.ring_version(ar2) == 2
    assert len(ar2.ring) == tau + 1

    step1 = jax.jit(functools.partial(arena.push_pop, layout,
                                      compression=compression))
    step2 = jax.jit(functools.partial(arena.push_pop, layout,
                                      compression=compression))
    for t in range(10):
        grads = _pod_grads(jax.random.PRNGKey(200 + t), n_pods)
        counts = jnp.full((n_pods,), 2.0 + t)
        gs1, c1, ar1 = step1(ar1, grads, counts)
        gs2, c2, ar2 = step2(ar2, grads, counts)
        np.testing.assert_array_equal(np.asarray(gs1), np.asarray(gs2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        view = arena.convert_ring(jax.device_get(ar2), 1)
        # compare in oldest-first order: v1 slots rotated to head
        order1 = [(int(ar1.head) + i) % tau for i in range(tau)]
        np.testing.assert_array_equal(_stack(ar1.ring)[order1],
                                      _stack(view.ring))
        if compression == "int8":
            np.testing.assert_array_equal(_stack(ar1.scales)[order1],
                                          _stack(view.scales))
            np.testing.assert_array_equal(np.asarray(ar1.residual),
                                          np.asarray(view.residual))
        np.testing.assert_array_equal(np.asarray(ar1.counts)[order1],
                                      np.asarray(view.counts))


@pytest.mark.parametrize("compression", ["none", "int8"])
@pytest.mark.parametrize("tau", [1, 2, 4])
def test_variable_ring_constant_delay_matches_static(tau, compression):
    """The delay-tolerant ring fed the CONSTANT sequence tau_t = tau is
    the static-phase v2 path: same popped sums, counts, ring slots,
    scales and residual — value-identical per step across three full
    wraps (the masked pop folds exact zeros around the one due slot,
    and the push schedule lands in the same slot indices). This is the
    degeneracy the fixed delay process rides."""
    n_pods = 2
    params = _params(jax.random.PRNGKey(0))
    layout = arena.make_layout(params)
    ar_s = arena.init_arena(layout, tau, n_pods, compression)
    ar_v = arena.init_arena(layout, tau, n_pods, compression,
                            variable=True)
    step_s = jax.jit(functools.partial(arena.push_pop, layout,
                                       compression=compression))
    step_v = jax.jit(functools.partial(arena.push_pop_variable, layout,
                                       compression=compression))
    for t in range(3 * (tau + 1) + 2):
        grads = _pod_grads(jax.random.PRNGKey(400 + t), n_pods)
        counts = jnp.full((n_pods,), 2.0 + t)
        gs_s, c_s, ar_s = step_s(ar_s, grads, counts)
        gs_v, c_v, tau_obs, ar_v = step_v(ar_v, grads, counts,
                                          jnp.int32(tau))
        np.testing.assert_array_equal(np.asarray(gs_s), np.asarray(gs_v))
        assert float(c_s) == float(c_v)
        # the fill phase pops nothing (tau_obs 0); afterwards exactly
        # the constant staleness
        assert float(tau_obs) == (float(tau) if t >= tau else 0.0)
        for s_slot, v_slot in zip(ar_s.ring, ar_v.ring):
            np.testing.assert_array_equal(np.asarray(s_slot),
                                          np.asarray(v_slot))
        np.testing.assert_array_equal(np.asarray(ar_s.counts),
                                      np.asarray(ar_v.counts))
        if compression == "int8":
            for s_sc, v_sc in zip(ar_s.scales, ar_v.scales):
                np.testing.assert_array_equal(np.asarray(s_sc),
                                              np.asarray(v_sc))
            np.testing.assert_array_equal(np.asarray(ar_s.residual),
                                          np.asarray(ar_v.residual))
        assert ar_v.phase == ar_s.phase


_VARIABLE_DELAY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import MeshConfig
    from repro.core import arena
    from repro.dist.context import sharding_profile
    from repro.launch.mesh import make_mesh

    mesh_cfg = MeshConfig(n_pods=2, data=2, model=2)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    params = {"a": jnp.zeros((7,)), "b": jnp.zeros((300, 5)),
              "c": jnp.zeros((257,))}
    layout = arena.make_layout(params)
    n_pods, tau = 2, 2

    def grads_at(t):
        ks = jax.random.split(jax.random.PRNGKey(t), 3)
        return {k: jax.random.normal(kk, (n_pods,) + params[k].shape)
                for k, kk in zip(sorted(params), ks)}

    ar_s = arena.init_arena(layout, tau, n_pods, "int8")
    ar_v = arena.init_arena(layout, tau, n_pods, "int8", variable=True)
    for t in range(8):
        g = grads_at(t)
        counts = jnp.full((n_pods,), 4.0)
        # both paths under the multi-pod GSPMD profile: the static
        # schedule vs the delay-tolerant masked fold fed tau_t = tau
        with jax.set_mesh(mesh), sharding_profile(mesh_cfg):
            gs_s, c_s, ar_s = arena.push_pop(
                layout, ar_s, g, counts, "int8", impl="ref")
            gs_v, c_v, tau_obs, ar_v = arena.push_pop_variable(
                layout, ar_v, g, counts, jnp.int32(tau), "int8")
        np.testing.assert_array_equal(np.asarray(gs_s), np.asarray(gs_v))
        assert float(c_s) == float(c_v)
        for s_slot, v_slot in zip(ar_s.ring, ar_v.ring):
            np.testing.assert_array_equal(np.asarray(s_slot),
                                          np.asarray(v_slot))
        for s_sc, v_sc in zip(ar_s.scales, ar_v.scales):
            np.testing.assert_array_equal(np.asarray(s_sc),
                                          np.asarray(v_sc))
        np.testing.assert_array_equal(np.asarray(ar_s.residual),
                                      np.asarray(ar_v.residual))
    print("VARIABLE_DELAY_OK")
""")


@pytest.mark.slow
def test_variable_ring_matches_static_8dev():
    """The fixed-delay degeneracy holds under the multi-pod GSPMD
    profile too (8 virtual CPU devices, pod=2 mesh): the delay-tolerant
    masked fold fed the constant sequence is bit-identical to the
    static-phase path — int8 payload, per-row scales and error-feedback
    residual included. Subprocess: the forced device count must not
    leak into this test process."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _VARIABLE_DELAY_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "VARIABLE_DELAY_OK" in out.stdout


def _arena_master_hlo(compression, ring_version, tau=2, n_pods=2):
    """Compile the donated arena master update on CPU; return (HLO
    text, layout)."""
    rc = _rc(tau, compression)
    params = _params(jax.random.PRNGKey(0))
    layout = arena.make_layout(params)
    opt_a = make_arena_optimizer(rc, layout)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, grads, counts):
        p, o, a = state
        p, o, a, _, _ = ambdg.arena_master_update(
            layout, opt_a, p, o, a, grads, counts, compression)
        return p, o, a

    state = jax.eval_shape(
        lambda: (params, opt_a.init(),
                 arena.init_arena(layout, tau, n_pods, compression,
                                  ring_version=ring_version)))
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((n_pods,) + p.shape, p.dtype),
        params)
    lowered = step.lower(state, grads,
                         jax.ShapeDtypeStruct((n_pods,), jnp.float32))
    return lowered.compile().as_text(), layout


@pytest.mark.parametrize("compression", ["none", "int8"])
def test_no_whole_ring_copy_protection(compression):
    """XLA:CPU inserts NO ring-dtype copy instructions for the v2
    master update: the pop reads and the push overwrites two different
    statically-indexed slot buffers, so the whole-ring copy-protection
    v1 pays for the pop-read/push-write hazard (plus the lax.switch
    operand/result copies) is structurally impossible. v1 is compiled
    too, as a positive control for the detector."""
    tau, n_pods = 2, 2
    hlo2, layout = _arena_master_hlo(compression, 2, tau, n_pods)
    hlo1, _ = _arena_master_hlo(compression, 1, tau, n_pods)
    dt = "s8" if compression == "int8" else "f32"
    slot = f"{dt}[{n_pods},{layout.rows},128]"
    ring = f"{dt}[{tau},{n_pods},{layout.rows},128]"

    copies1 = copy_shapes(hlo1)
    assert copies1.get(ring, 0) >= 1, (
        "detector sanity: v1 should pay whole-ring copy-protection; "
        f"saw {copies1}")
    copies2 = copy_shapes(hlo2)
    assert copies2.get(ring, 0) == 0 and copies2.get(slot, 0) == 0, (
        f"ring layout v2 must compile without ring-dtype copies; "
        f"saw {copies2}")
    if compression == "none":
        # no staging/fed scratch on this path: no big copies at all
        big = {k: v for k, v in copies2.items()
               if np.prod([int(d) for d in k.split("[")[1][:-1]
                           .split(",") if d]) >= layout.rows * 128}
        assert not big, big


def test_checkpoint_v1_ring_migration(tmp_path):
    """Mid-run migration: train under ring v2, convert the arena to the
    v1 layout (as a pre-migration checkpoint would hold), save, restore
    into a v2 template, continue — bit-for-bit identical to the
    uninterrupted v2 run, including the in-flight delayed gradients."""
    from repro.train import checkpoint as ckpt
    compression = "int8"
    tau, n_pods = 2, 2
    rc = _rc(tau, compression)
    params = _params(jax.random.PRNGKey(3))
    layout = arena.make_layout(params)
    opt_a = make_arena_optimizer(rc, layout)

    @jax.jit
    def step(p, o, a, grads, counts):
        p, o, a, _, _ = ambdg.arena_master_update(
            layout, opt_a, p, o, a, grads, counts, compression)
        return p, o, a

    def batches(t):
        return (_pod_grads(jax.random.PRNGKey(300 + t), n_pods),
                jnp.full((n_pods,), 3.0))

    p, o = params, opt_a.init()
    ar = arena.init_arena(layout, tau, n_pods, compression)
    for t in range(4):   # 4 steps: phase 4 % 3 == 1, mid-cycle
        p, o, ar = step(p, o, ar, *batches(t))
    assert ar.phase == 4 % (tau + 1) == 1

    # save in the v1 layout (what an old checkpoint holds)
    state_v1 = {"params": p, "opt": o, "arena": arena.convert_ring(
        jax.device_get(ar), 1)}
    assert int(state_v1["arena"].head) == 0
    ckpt.save(str(tmp_path), 3, state_v1, extra={"step": 3})

    # restore into a v2 template: migration splits + permutes the ring
    template = {"params": p, "opt": o,
                "arena": arena.init_arena(layout, tau, n_pods,
                                          compression)}
    restored, extra = ckpt.restore(str(tmp_path), template)
    assert extra["step"] == 3
    r_ar = restored["arena"]
    assert arena.ring_version(r_ar) == 2 and r_ar.phase == 0

    # continue both runs; they must agree bit for bit
    rp, ro = restored["params"], restored["opt"]
    for t in range(4, 9):
        p, o, ar = step(p, o, ar, *batches(t))
        rp, ro, r_ar = step(rp, ro, r_ar, *batches(t))
        for a_leaf, b_leaf in zip(jax.tree.leaves(p), jax.tree.leaves(rp)):
            np.testing.assert_array_equal(np.asarray(a_leaf),
                                          np.asarray(b_leaf))
        np.testing.assert_array_equal(np.asarray(o.z), np.asarray(ro.z))


_SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import MeshConfig
    from repro.core import arena
    from repro.dist.context import sharding_profile
    from repro.launch.mesh import make_mesh

    mesh_cfg = MeshConfig(n_pods=2, data=2, model=2)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    params = {"a": jnp.zeros((7,)), "b": jnp.zeros((300, 5)),
              "c": jnp.zeros((257,))}
    layout = arena.make_layout(params)
    n_pods, tau = 2, 2

    def grads_at(t):
        ks = jax.random.split(jax.random.PRNGKey(t), 3)
        return {k: jax.random.normal(kk, (n_pods,) + params[k].shape)
                for k, kk in zip(sorted(params), ks)}

    ar_s = arena.init_arena(layout, tau, n_pods, "int8")
    ar_r = arena.init_arena(layout, tau, n_pods, "int8")
    for t in range(5):
        g = grads_at(t)
        counts = jnp.full((n_pods,), 4.0)
        # shard_map'd Pallas kernel (interpret) on the multi-pod mesh
        with jax.set_mesh(mesh), sharding_profile(mesh_cfg):
            gs_s, c_s, ar_s = arena.push_pop(
                layout, ar_s, g, counts, "int8",
                impl="pallas_sharded", interpret=True)
        # off-mesh single-program kernel: identical quantize/dequantize
        # arithmetic, deterministic pod fold — only the reduction's
        # placement (all-gather + local fold vs materialized popped)
        # differs, so everything must agree BIT for bit. (kernel vs
        # XLA-ref drift is covered, with tolerances, by
        # test_push_pop_pallas_branch_matches_ref.)
        gs_r, c_r, ar_r = arena.push_pop(layout, ar_r, g, counts,
                                         "int8", impl="pallas",
                                         interpret=True)
        np.testing.assert_array_equal(np.asarray(gs_s), np.asarray(gs_r))
        assert float(c_s) == float(c_r)
        for s_slot, r_slot in zip(ar_s.ring, ar_r.ring):
            np.testing.assert_array_equal(np.asarray(s_slot),
                                          np.asarray(r_slot))
        for s_sc, r_sc in zip(ar_s.scales, ar_r.scales):
            np.testing.assert_array_equal(np.asarray(s_sc),
                                          np.asarray(r_sc))
        np.testing.assert_array_equal(np.asarray(ar_s.residual),
                                      np.asarray(ar_r.residual))
    print("SHARD_MAP_OK")
""")


@pytest.mark.slow
def test_shard_map_kernel_matches_off_mesh_fold():
    """The shard_map'd delay-ring kernel (8 virtual CPU devices, pod=2
    mesh, interpret-mode Pallas, int8 payload all-gathered compressed)
    produces bit-identical popped sums and ring state to the off-mesh
    deterministic fold. Subprocess: the forced device count must not
    leak into this test process."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARD_MAP_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "SHARD_MAP_OK" in out.stdout


def _collect_primitives(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for u in vs:
                inner = getattr(u, "jaxpr", None)
                if inner is not None:
                    _collect_primitives(inner, acc)
                elif hasattr(u, "eqns"):
                    _collect_primitives(u, acc)
    return acc


@pytest.mark.parametrize("compression", ["none", "int8"])
def test_no_per_step_concatenate(compression):
    """The fused arena master update never concatenates the tree: the
    one-time flatten happened at init (layout build), and the per-step
    gradient lands via static-offset update-slices."""
    tau, n_pods = 2, 2
    rc = _rc(tau, compression)
    params = _params(jax.random.PRNGKey(0))
    layout = arena.make_layout(params)
    opt_a = make_arena_optimizer(rc, layout)

    def master(p, o, a, grads, counts):
        return ambdg.arena_master_update(layout, opt_a, p, o, a, grads,
                                         counts, compression)

    jaxpr = jax.make_jaxpr(master)(
        params, opt_a.init(), arena.init_arena(layout, tau, n_pods,
                                               compression),
        _pod_grads(jax.random.PRNGKey(1), n_pods), jnp.ones((n_pods,)))
    prims = _collect_primitives(jaxpr.jaxpr, set())
    assert "concatenate" not in prims, sorted(prims)
    # the per-leaf pytree path, by contrast, IS allowed to concatenate;
    # sanity-check the detector catches one where we expect it
    probe = jax.make_jaxpr(
        lambda t: jnp.concatenate([x.reshape(-1) for x in
                                   jax.tree.leaves(t)]))(params)
    assert "concatenate" in _collect_primitives(probe.jaxpr, set())


@pytest.mark.parametrize("compression", ["none", "int8"])
def test_checkpoint_roundtrip_arena_state(tmp_path, compression):
    """GradArena (incl. int8 ring + per-row scales + residual) threads
    through save/restore bit-exactly."""
    from repro.train import checkpoint as ckpt
    import repro.configs as C
    from repro.core import make_train_step
    from repro.models import build_model

    cfg = C.get_smoke_config("qwen3-1.7b")
    model = build_model(cfg)
    rc = RunConfig(model=cfg,
                   shape=dataclasses.replace(TRAIN_4K, seq_len=32,
                                             global_batch=8),
                   mesh=MeshConfig(n_pods=1, data=1, model=1),
                   ambdg=AmbdgConfig(tau=2, n_microbatches=2, b_bar=8.0,
                                     smoothness_L=8.0,
                                     pod_compression=compression))
    init_state, train_step = make_train_step(model, rc)
    state = init_state(jax.random.PRNGKey(0))
    state, _ = jax.jit(train_step)(state, model.dummy_batch(8, 32))
    assert state.arena is not None and state.buffer is None
    if compression == "int8":
        assert all(s.dtype == jnp.int8 for s in state.arena.ring)
    ckpt.save(str(tmp_path), 1, state, extra={"step": 1})
    restored, _ = ckpt.restore(str(tmp_path), state)
    for a_leaf, b_leaf in zip(jax.tree.leaves(state),
                              jax.tree.leaves(restored)):
        assert a_leaf.dtype == b_leaf.dtype
        np.testing.assert_array_equal(np.asarray(a_leaf), np.asarray(b_leaf))
