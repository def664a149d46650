"""``correct`` at the smoke size on the CPU: a sound run passes; the
control (the reference in fp8 in the program's place) and the faults a
training cell can have fail. The faults are planted under the timed
path, in the strategy step the training loop jits, and the rest of a
run (set-up, window, probes, reference, comparison) runs as it does on
the chip; only the look for a chip is skipped."""
import dataclasses
import time

import jax
import pytest

from bench_smoke import PEAKS, smoke_cell
from harness import compare, drive, runner

SEED = 2 ** 31 + 77          # larger than 32 signed bits
CELLS = ["qwen05b-dense-2k", "qwen05b-stale-512", "zamba2p1-ssm-4k"]


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """The CPU programs stay out of the checkout's compile cache, and
    the peaks table is not asked about the CPU."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(runner, "peaks", lambda kind: PEAKS)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _run(cell):
    return runner.run(cell, SEED, 0.3, False, jax.devices()[:1],
                      time.perf_counter())


def _plant(monkeypatch, fault):
    """``fault(build, model, rc)`` builds the strategy the training loop
    runs in place of ``api.build``."""
    from repro import api
    build = api.build
    monkeypatch.setattr(api, "build",
                        lambda model, rc: fault(build, model, rc))


def unchanged(build, model, rc):
    """A step that returns its state unchanged."""
    s = build(model, rc)
    step = s.train_step
    s.train_step = lambda state, batch: (state, step(state, batch)[1])
    return s


def half_batch(build, model, rc):
    """Half of the batch left out, the mean taken over the rest."""
    s = build(model, rc)
    step = s.train_step

    def f(state, batch):
        w = batch["weights"]
        return step(state, dict(batch, weights=w.at[w.shape[0] // 2:]
                                .set(0.0)))
    s.train_step = f
    return s


def late_pop(build, model, rc):
    """The ring one slot deeper: every gradient pops one step late."""
    if rc.delay.process == "fixed":
        return build(model, rc.replace(ambdg=dataclasses.replace(
            rc.ambdg, tau=rc.ambdg.tau + 1)))
    s = build(model, rc.replace(delay=dataclasses.replace(
        rc.delay, tau_max=rc.delay.tau_max + 1)))
    step = s.train_step
    s.train_step = lambda state, batch: step(
        state, dict(batch, delay=batch["delay"] + 1))
    return s


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = _run(smoke_cell(workload))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [unchanged, half_batch, late_pop],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(monkeypatch, workload, fault):
    _plant(monkeypatch, fault)
    res = _run(smoke_cell(workload))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_late_pop_reads_on_the_parameters(monkeypatch, workload):
    """The ring's timing shows on param_gap: the reference's parameters
    are 0 until the first gradient pops, the late ring's a step longer."""
    _plant(monkeypatch, late_pop)
    res = _run(smoke_cell(workload))
    assert res["checks"]["param_gap"]["value"] >= 1.0, res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_fp8_control_fails_the_limits(workload):
    cell = smoke_cell(workload)
    model, rc, loop, struct = drive.build(cell, SEED)
    driver = drive.Driver(loop, drive.Probes(struct),
                          drive.n_ring_phases(cell.traffic), None,
                          drive.CompileCounter())
    r = drive.run_train(model, rc, loop, driver)
    ref = compare.follow(cell, struct, SEED, r.batches, r.delays,
                         drive.N_CHECK)
    fp8 = compare.follow(cell, struct, SEED, r.batches, r.delays,
                         drive.N_CHECK, precision="fp8")
    limits = cell.limits["limits"]
    assert compare.judge(compare.gaps(compare.Side(
        r.losses, r.grad_norms, r.param_norms), ref), limits)
    assert not compare.judge(compare.gaps(fp8, ref), limits)
