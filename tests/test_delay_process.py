"""Property/fuzz suite for the stochastic delay subsystem.

Two layers:

  * the PROCESSES (``core.delay_process``): seeded reproducibility,
    bounds, checkpointable state, config validation;
  * the delay-tolerant RING (``arena.push_pop_variable``) replayed
    against a pure-numpy oracle over seeded random delay sequences —
    sweeping tau_max in {1, 4, 16} x all four processes — asserting
    the structural invariants the delay tolerance rests on:

      - no unread-slot overwrite: the statically-scheduled push target
        is always a slot whose entry was already applied;
      - per-slot count conservation: counts pushed == counts applied +
        counts still in flight, every step;
      - gradient mass telescoping: the same conservation for the
        gradient payload itself (exact under f32, since the masked
        fold adds exact zeros);
      - ``gradient_reference_epoch`` consistency: the popped sets and
        the observed staleness ``tau_obs`` match the
        ``staleness.delivery_schedule`` of the emitted sequence.

The ring layer parametrizes over the pop implementation: the CPU
gather reference AND the single-pass Pallas kernel in interpret mode
(``impl="pallas"`` — the oracle replay, the int8 conservation law and
the constant-sequence degeneration all hold through the kernel too).

``REPRO_TEST_DELAY`` (comma-separated process names) narrows the
process sweep and ``REPRO_TEST_TAU`` (comma-separated taus) the
tau_max sweep — the CI matrix legs compose the two, one cell per job.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DelayConfig
from repro.core import arena
from repro.core.delay_process import (DELAY_PROCESSES, make_delay_process,
                                      resolve_bounds)
from repro.core.staleness import delivery_schedule, observed_staleness

ALL_PROCESSES = ("fixed", "jitter", "heavy_tail", "bursty")
PROCESSES = tuple(
    p for p in os.environ.get("REPRO_TEST_DELAY",
                              ",".join(ALL_PROCESSES)).split(",") if p)
TAUS = [int(t) for t in
        os.environ.get("REPRO_TEST_TAU", "1,4,16").split(",") if t]
TAU = 3          # nominal staleness the processes wobble around

# pop implementations the ring tests replay through: the CPU gather
# reference and the single-pass kernel (Pallas interpret mode)
IMPLS = ("ref", "pallas")


def _impl_kw(impl: str) -> dict:
    return {"impl": impl,
            "interpret": True if impl == "pallas" else None}


def _cfg(process: str, tau_max: int, seed: int = 0, **kw) -> DelayConfig:
    return DelayConfig(process=process, tau_max=tau_max, seed=seed, **kw)


# ---------------------------------------------------------------------------
# the processes
# ---------------------------------------------------------------------------
def test_registry_and_validation():
    assert set(DELAY_PROCESSES) == set(ALL_PROCESSES)
    with pytest.raises(ValueError, match="unknown delay process"):
        make_delay_process(_cfg("lognormal", 4), TAU)
    with pytest.raises(ValueError, match="tau_max >= 1"):
        make_delay_process(_cfg("jitter", 0), TAU)
    with pytest.raises(ValueError, match="delay_min"):
        make_delay_process(_cfg("jitter", 2, delay_min=5), TAU)
    with pytest.raises(ValueError, match="delay_min"):
        make_delay_process(_cfg("jitter", 2, delay_min=-1), TAU)
    with pytest.raises(ValueError, match="tail_alpha"):
        make_delay_process(_cfg("heavy_tail", 4, tail_alpha=0.0), TAU)
    with pytest.raises(ValueError, match="probabilities"):
        make_delay_process(_cfg("bursty", 4, p_burst=1.5), TAU)
    with pytest.raises(ValueError, match="tau_max"):
        # fixed with an explicit cap below the nominal tau
        make_delay_process(_cfg("fixed", 1), TAU)
    # fixed resolves tau_max=0 to tau
    assert resolve_bounds(_cfg("fixed", 0), TAU)[1] == TAU


@pytest.mark.parametrize("tau_max", TAUS)
@pytest.mark.parametrize("process", PROCESSES)
def test_bounds_and_seeding(process, tau_max):
    if process == "fixed" and tau_max < TAU:
        pytest.skip("fixed caps at tau")
    n = 512
    a = make_delay_process(_cfg(process, tau_max, seed=1), TAU).sequence(n)
    b = make_delay_process(_cfg(process, tau_max, seed=1), TAU).sequence(n)
    lo, hi = resolve_bounds(_cfg(process, tau_max), TAU)
    assert (a >= lo).all() and (a <= hi).all()
    np.testing.assert_array_equal(a, b)          # seeded: reproducible
    if process == "fixed":
        assert (a == TAU).all()
    elif tau_max > 1:
        c = make_delay_process(_cfg(process, tau_max, seed=2),
                               TAU).sequence(n)
        assert not np.array_equal(a, c)          # seeds matter
        assert len(np.unique(a)) > 1             # genuinely stochastic


@pytest.mark.parametrize("process", PROCESSES)
def test_state_dict_resumes_mid_sequence(process):
    dp = make_delay_process(_cfg(process, 8, seed=5), TAU)
    dp.sequence(37)                               # advance
    saved = dp.state_dict()
    rest = dp.sequence(64)
    dp2 = make_delay_process(_cfg(process, 8, seed=999), TAU)
    dp2.load_state_dict(saved)
    np.testing.assert_array_equal(rest, dp2.sequence(64))


def test_heavy_tail_has_a_tail_and_bursty_bursts():
    seq = make_delay_process(_cfg("heavy_tail", 16, seed=0),
                             TAU).sequence(4096)
    # mostly delay_min, with genuine stragglers reaching the cap
    assert np.median(seq) == 1 and seq.max() == 16
    seq = make_delay_process(
        _cfg("bursty", 16, seed=0, p_burst=0.1, p_exit=0.3),
        TAU).sequence(4096)
    # geometric dwell: bursts of consecutive tau_max draws exist
    runs, cur = [], 0
    for d in seq:
        cur = cur + 1 if d == 16 else 0
        runs.append(cur)
    assert max(runs) >= 3
    assert (seq == TAU).any()                     # and normal periods


# ---------------------------------------------------------------------------
# the delay-tolerant ring vs a pure-numpy oracle
# ---------------------------------------------------------------------------
class _RingOracle:
    """Host-side model of the delay-tolerant ring: slot j holds the
    push from the last step s with s % n_slots == j, applied at
    s + tau_s. Checks the structural invariants each step."""

    def __init__(self, n_slots, n_pods, width):
        self.n_slots = n_slots
        self.slots = np.zeros((n_slots, n_pods, width), np.float32)
        self.due = np.full((n_slots,), -1, np.int64)
        self.counts = np.zeros((n_slots, n_pods), np.float32)
        self.stale = np.zeros((n_slots,), np.int64)
        self.pushed_mass = np.zeros((width,), np.float64)
        self.pushed_count = 0.0
        self.applied_mass = np.zeros((width,), np.float64)
        self.applied_count = 0.0

    def step(self, t, g, counts, d):
        k = t % self.n_slots
        # invariant 1: the overwritten slot's entry was already applied
        assert self.due[k] < t, (t, k, self.due[k])
        self.slots[k], self.counts[k] = g, counts
        self.due[k], self.stale[k] = t + d, d
        self.pushed_mass += g.sum(0)
        self.pushed_count += counts.sum()
        mask = self.due == t
        grad = self.slots[mask].sum(axis=(0, 1))
        count = float(self.counts[mask].sum())
        csums = self.counts.sum(1)
        tau_obs = (float((self.stale[mask] * csums[mask]).sum())
                   / max(count, 1.0))
        self.applied_mass += grad
        self.applied_count += count
        return grad, count, tau_obs

    def check_conservation(self, t):
        # invariants 2+3: pushed == applied + in-flight, every step
        live = self.due > t
        in_flight_count = float(self.counts[live].sum())
        assert self.pushed_count == self.applied_count + in_flight_count
        in_flight_mass = self.slots[live].sum(axis=(0, 1))
        np.testing.assert_allclose(
            self.pushed_mass, self.applied_mass + in_flight_mass,
            rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("tau_max", TAUS)
@pytest.mark.parametrize("process", PROCESSES)
def test_ring_invariants_under_random_delays(process, tau_max, impl):
    """Replay a seeded delay sequence through push_pop_variable and the
    numpy oracle: identical pops, conserved counts/mass, tau_obs
    consistent with the delivery schedule of the emitted sequence —
    through the CPU gather reference and the interpret-mode kernel."""
    if process == "fixed" and tau_max < TAU:
        pytest.skip("fixed caps at tau")
    n_pods = 2
    params = {"w": jnp.zeros((130,))}             # row-misaligned leaf
    layout = arena.make_layout(params)
    ar = arena.init_arena(layout, tau_max, n_pods, variable=True)
    oracle = _RingOracle(tau_max + 1, n_pods, 130)
    dp = make_delay_process(_cfg(process, tau_max, seed=11), TAU)
    n_steps = 3 * (tau_max + 1) + 4
    delays = dp.sequence(n_steps)
    rng = np.random.default_rng(0)

    step = jax.jit(
        lambda a, g, c, d: arena.push_pop_variable(layout, a, g, c, d,
                                                   **_impl_kw(impl)),
        donate_argnums=(0,))

    sched = delivery_schedule(delays.tolist())    # 1-indexed push steps
    for t in range(n_steps):
        g = rng.standard_normal((n_pods, 130)).astype(np.float32)
        counts = np.arange(1.0, n_pods + 1, dtype=np.float32) + t
        gs, c, tau_obs, ar = step(ar, {"w": jnp.asarray(g)},
                                  jnp.asarray(counts),
                                  jnp.int32(delays[t]))
        og, oc, otau = oracle.step(t, g, counts, int(delays[t]))
        got = np.asarray(arena.unflatten_tree(layout, gs)["w"])
        np.testing.assert_allclose(got, og, rtol=1e-6, atol=1e-5)
        assert float(c) == oc
        assert float(tau_obs) == pytest.approx(otau, rel=1e-6)
        oracle.check_conservation(t)
        # invariant 4: the popped set IS the delivery schedule of the
        # emitted sequence (1-indexed: push step s applied at
        # s + tau_s). Push s carried counts arange(1..n_pods) + (s-1),
        # so the applied count identifies exactly WHICH pushes arrived.
        due_pushes = sched.get(t + 1, [])
        expect_count = sum(n_pods * (n_pods + 1) / 2 + n_pods * (s - 1)
                           for s in due_pushes)
        assert oc == expect_count, (t, due_pushes)
        assert ar.phase == (t + 1) % (tau_max + 1)
        assert int(ar.head) == t + 1

    # the observed-staleness helper agrees with the emitted sequence
    # under equal per-push weights (constant counts): rebuild with
    # constant counts and compare tau_obs to observed_staleness
    ar = arena.init_arena(layout, tau_max, n_pods, variable=True)
    expect = observed_staleness(delays.tolist(), n_steps)
    for t in range(n_steps):
        g = jnp.ones((n_pods, 130), jnp.float32)
        gs, c, tau_obs, ar = step(ar, {"w": g},
                                  jnp.ones((n_pods,)),
                                  jnp.int32(delays[t]))
        assert float(tau_obs) == pytest.approx(expect[t], rel=1e-6)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("process", PROCESSES)
def test_ring_invariants_int8(process, impl):
    """The int8 ring keeps the same invariants: per-push quantization
    + error feedback means (applied + in-flight dequants + residual)
    telescopes to the true pushed mass."""
    tau_max, n_pods, width = 4, 1, 256
    params = {"w": jnp.zeros((width,))}
    layout = arena.make_layout(params)
    ar = arena.init_arena(layout, tau_max, n_pods, "int8", variable=True)
    dp = make_delay_process(_cfg(process, tau_max, seed=3), TAU)
    rng = np.random.default_rng(1)
    n_steps = 24
    true_mass = np.zeros((width,), np.float64)
    applied = np.zeros((width,), np.float64)
    step = jax.jit(
        lambda a, g, c, d: arena.push_pop_variable(layout, a, g, c, d,
                                                   "int8",
                                                   **_impl_kw(impl)),
        donate_argnums=(0,))
    for t in range(n_steps):
        g = 0.05 * rng.standard_normal((n_pods, width)).astype(np.float32)
        true_mass += g.sum(0)
        gs, c, tau_obs, ar = step(ar, {"w": jnp.asarray(g)},
                                  jnp.ones((n_pods,)),
                                  jnp.int32(dp.next()))
        applied += np.asarray(arena.unflatten_tree(layout, gs)["w"])
    due = np.asarray(ar.due)
    in_flight = np.zeros((width,), np.float64)
    for j in range(tau_max + 1):
        if due[j] >= n_steps:     # still undelivered
            deq = (np.asarray(ar.ring[j], np.float32)
                   * np.asarray(ar.scales[j])[..., None]).sum(0)
            in_flight += np.asarray(
                arena.unflatten_tree(layout, jnp.asarray(deq))["w"])
    residual = np.asarray(
        arena.unflatten_tree(
            layout, jnp.asarray(np.asarray(ar.residual).sum(0)))["w"])
    np.testing.assert_allclose(applied + in_flight + residual, true_mass,
                               rtol=1e-5, atol=1e-5)


def test_variable_ring_rejects_fixed_arena():
    params = {"w": jnp.zeros((8,))}
    layout = arena.make_layout(params)
    ar = arena.init_arena(layout, 2, 1)
    with pytest.raises(ValueError, match="delay-tolerant"):
        arena.push_pop_variable(layout, ar, {"w": jnp.zeros((1, 8))},
                                jnp.ones((1,)), jnp.int32(1))
    with pytest.raises(ValueError, match="v2"):
        arena.init_arena(layout, 2, 1, ring_version=1, variable=True)
    ar_v = arena.init_arena(layout, 2, 1, variable=True)
    with pytest.raises(ValueError, match="no v1 layout"):
        arena.convert_ring(ar_v, 1)
    with pytest.raises(ValueError, match="push_pop_variable"):
        arena.push_pop(layout, ar_v, {"w": jnp.zeros((1, 8))},
                       jnp.ones((1,)))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("compression", ["none", "int8"])
def test_constant_sequence_degenerates_to_static(compression, impl):
    """A constant delay sequence tau_t == tau reduces the variable
    ring — through the gather reference AND the single-pass kernel —
    to the static fixed-tau path BIT-identically: every step has
    exactly one due slot (H = 1), so the masked fold is the static
    single-slot pop. (One carve-out, matching the fixed-ring kernel
    contract: the int8 KERNEL's unprotected in-register dequantize may
    contract into an FMA where the XLA paths round the product — the
    popped sums then differ by isolated f32 ulps; ring/scales/residual
    state stays bit-identical.)"""
    import functools
    tau, n_pods = 2, 2
    params = {"a": jnp.zeros((9,)), "b": jnp.zeros((33, 7))}
    layout = arena.make_layout(params)
    ar_s = arena.init_arena(layout, tau, n_pods, compression)
    ar_v = arena.init_arena(layout, tau, n_pods, compression,
                            variable=True)
    step_s = jax.jit(functools.partial(arena.push_pop, layout,
                                       compression=compression))
    step_v = jax.jit(functools.partial(arena.push_pop_variable, layout,
                                       compression=compression,
                                       **_impl_kw(impl)))
    for t in range(3 * (tau + 1) + 2):
        ks = jax.random.split(jax.random.PRNGKey(t), len(params))
        g = {k: jax.random.normal(kk, (n_pods,) + params[k].shape)
             for k, kk in zip(sorted(params), ks)}
        counts = jnp.full((n_pods,), 2.0 + t)
        gs_s, c_s, ar_s = step_s(ar_s, g, counts)
        gs_v, c_v, tau_obs, ar_v = step_v(ar_v, g, counts,
                                          jnp.int32(tau))
        if compression == "int8" and impl == "pallas":
            np.testing.assert_allclose(np.asarray(gs_s),
                                       np.asarray(gs_v), rtol=1e-6,
                                       atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(gs_s),
                                          np.asarray(gs_v))
        assert float(c_s) == float(c_v)
        assert float(tau_obs) == (float(tau) if t >= tau else 0.0)
        for s_slot, v_slot in zip(ar_s.ring, ar_v.ring):
            np.testing.assert_array_equal(np.asarray(s_slot),
                                          np.asarray(v_slot))
        if compression == "int8":
            for s_sc, v_sc in zip(ar_s.scales, ar_v.scales):
                np.testing.assert_array_equal(np.asarray(s_sc),
                                              np.asarray(v_sc))
            np.testing.assert_array_equal(np.asarray(ar_s.residual),
                                          np.asarray(ar_v.residual))


_SHARDED_VARPOP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import MeshConfig
    from repro.dist.context import sharding_profile
    from repro.launch.mesh import make_mesh
    from repro.kernels.delay_ring.ops import (ring_variable_pop,
                                              ring_variable_pop_ref,
                                              ring_variable_pop_sharded)

    mesh_cfg = MeshConfig(n_pods=2, data=2, model=2)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    n_slots, n_pods, rows = 5, 2, 256
    rng = np.random.default_rng(7)

    for comp in ("none", "int8"):
        if comp == "int8":
            ring = jnp.asarray(rng.integers(
                -127, 128, size=(n_slots, n_pods, rows, 128)), jnp.int8)
            scales = jnp.asarray(rng.uniform(
                1e-3, 1.0, size=(n_slots, n_pods, rows)), jnp.float32)
        else:
            ring = jnp.asarray(rng.normal(
                size=(n_slots, n_pods, rows, 128)), jnp.float32)
            scales = None
        for trial in range(6):
            mask = jnp.asarray(rng.integers(0, 2, size=(n_slots,)) > 0)
            with jax.set_mesh(mesh), sharding_profile(mesh_cfg):
                got = ring_variable_pop_sharded(
                    ring, mask, scales=scales, mesh_cfg=mesh_cfg,
                    interpret=True)
            # dense oracle: same per-pod fold, pods left-folded
            part = ring_variable_pop_ref(ring, mask, scales=scales)
            want = np.asarray(part[0])
            for p in range(1, n_pods):
                want = want + np.asarray(part[p])
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=1e-6, atol=1e-6)
    print("SHARDED_VARPOP_OK")
""")


@pytest.mark.slow
def test_variable_pop_sharded_matches_dense_8dev():
    """The single-reduce shard_map wrapper around the variable-pop
    kernel agrees with the dense oracle fold under a pod=2 x data=2 x
    model=2 mesh of 8 virtual CPU devices (f32 and int8) — i.e. the
    local fold + one psum is the same sum the dense path computes.
    Subprocess: the forced device count must not leak."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED_VARPOP_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "SHARDED_VARPOP_OK" in out.stdout
