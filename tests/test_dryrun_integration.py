"""Integration: the real dry-run entry point lowers+compiles one cell
per kind (train / prefill / decode) in a subprocess (the dry-run forces
512 virtual devices, so it must not share this test process)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(arch, shape, *extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", arch, "--shape", shape, *extra],
        capture_output=True, text=True, env=env, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][0]
    return json.loads(line)


def _tiled_share(arch):
    import jax
    import repro.configs as C
    from repro.core import arena
    from repro.models import build_model
    model = build_model(C.get_config(arch))
    return arena.make_layout(jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0))).tiled_share


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("qwen1.5-0.5b", "train_4k"),      # AMB-DG train step
    ("xlstm-125m", "long_500k"),       # sub-quadratic decode
])
def test_dryrun_cell(arch, shape):
    r = _run(arch, shape)
    assert r["flops"] > 0
    mem = r["memory"]
    assert (mem["argument_bytes"] + mem["temp_bytes"]) < 16e9
    assert r["collectives"]["count"] > 0


@pytest.mark.slow
def test_dryrun_cell_variable_delay():
    """The delay-tolerant ring lowers + compiles with full production
    shardings on the 16x16 mesh: the per-step delay scalar enters the
    batch specs, the per-slot due/stale metadata threads the state
    specs, and the cell reports the process it lowered with."""
    r = _run("qwen1.5-0.5b", "train_4k",
             "--delay-process", "heavy_tail", "--tau-max", "3")
    assert r["flops"] > 0
    assert r["master"]["delay_process"] == "heavy_tail"
    assert r["master"]["tau_max"] == 3
    # every qwen leaf but the final norm is a whole-tile image
    assert r["master"]["tiled_share"] == _tiled_share("qwen1.5-0.5b")
    mem = r["memory"]
    assert (mem["argument_bytes"] + mem["temp_bytes"]) < 16e9
    assert r["collectives"]["count"] > 0
