"""The scenario-matrix harness contracts (docs/matrix.md):

  * the no-clobber ``XLA_FLAGS`` device-count contract of
    ``repro.launch.xla`` — the PR-10 bugfix: importing the dry-run (or
    any benchmark) must never override a count the caller pinned;
  * ``resolve_cell_rc``'s explicit-only ``tau_max`` override (an
    explicit 0 is a value, not "unset");
  * ``parse_mesh`` / ``mesh_label`` roundtrips;
  * the closed-form wire models the matrix invariants compare the
    strict HLO census against (hand-computed expectations);
  * (slow) the end-to-end subprocess regressions: an import with the
    flag already pinned leaves the device count alone, and one real
    8-device matrix cell passes all three invariants.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from repro.launch import wire_model
from repro.launch.xla import (ENV_VAR, FLAG,
                              ensure_host_platform_device_count,
                              pinned_host_device_count,
                              without_host_device_flag)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# XLA_FLAGS no-clobber contract (the import-time bugfix)
# ---------------------------------------------------------------------------
class TestEnsureHostDeviceCount:
    @pytest.fixture(autouse=True)
    def clean_env(self):
        # explicit snapshot/restore: the tests (and the function under
        # test) write os.environ directly, which monkeypatch.delenv on
        # an ABSENT var would not roll back
        saved = {k: os.environ.get(k) for k in ("XLA_FLAGS", ENV_VAR)}
        for k in saved:
            os.environ.pop(k, None)
        yield
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def test_default_appended_once(self):
        assert ensure_host_platform_device_count(default=64) == 64
        assert os.environ["XLA_FLAGS"] == f"{FLAG}=64"
        # idempotent: a second call appends nothing
        assert ensure_host_platform_device_count(default=64) == 64
        assert os.environ["XLA_FLAGS"].count(FLAG) == 1

    def test_preexisting_flag_wins_and_is_never_rewritten(self):
        os.environ["XLA_FLAGS"] = f"--xla_cpu_foo=1 {FLAG}=48"
        # the pre-PR-10 clobber: this used to append =512 (and XLA
        # takes the LAST occurrence)
        assert ensure_host_platform_device_count(default=512) == 48
        assert os.environ["XLA_FLAGS"] == f"--xla_cpu_foo=1 {FLAG}=48"

    def test_env_var_injects_count(self):
        os.environ[ENV_VAR] = "128"
        assert ensure_host_platform_device_count(default=512) == 128
        assert pinned_host_device_count() == 128

    def test_explicit_count_beats_env_var(self):
        os.environ[ENV_VAR] = "128"
        assert ensure_host_platform_device_count(32, default=512) == 32

    def test_conflicting_request_raises(self):
        os.environ["XLA_FLAGS"] = f"{FLAG}=48"
        with pytest.raises(ValueError, match="already pinned"):
            ensure_host_platform_device_count(64)
        os.environ[ENV_VAR] = "64"
        with pytest.raises(ValueError, match=ENV_VAR):
            ensure_host_platform_device_count()
        # a MATCHING request is not a conflict
        os.environ[ENV_VAR] = "48"
        assert ensure_host_platform_device_count() == 48

    def test_last_occurrence_wins(self):
        # XLA's own precedence, mirrored by the probe
        assert pinned_host_device_count(f"{FLAG}=8 {FLAG}=64") == 64
        assert pinned_host_device_count("--xla_cpu_foo=1") is None

    def test_without_host_device_flag(self):
        flags = f"--xla_cpu_foo=1 {FLAG}=8 --bar=2 {FLAG}=64"
        assert without_host_device_flag(flags) == "--xla_cpu_foo=1 --bar=2"
        assert without_host_device_flag("") == ""
        # only the exact flag token is removed
        assert without_host_device_flag("--bar=2") == "--bar=2"


# ---------------------------------------------------------------------------
# resolve_cell_rc: explicit-only tau_max override
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dryrun():
    """Import ``launch.dryrun`` without leaking its import-time flag
    append into this test process's env (jax's backend reads XLA_FLAGS
    lazily, so restoring before any jax computation keeps the suite on
    the single real CPU device)."""
    saved = os.environ.get("XLA_FLAGS")
    try:
        import repro.launch.dryrun as dr
    finally:
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved
    return dr


class TestResolveCellRcTauMax:
    def _rc(self, dryrun, tau_max):
        import dataclasses
        rc = dryrun.build_run_config("qwen1.5-0.5b", "train_4k", False)
        return rc.replace(delay=dataclasses.replace(
            rc.delay, tau_max=tau_max))

    def test_none_keeps_rc_value(self, dryrun):
        rc = self._rc(dryrun, 2)
        out = dryrun.resolve_cell_rc("qwen1.5-0.5b", "train_4k", False,
                                     rc=rc, delay_process="jitter",
                                     tau_max=None)
        assert out.delay.tau_max == 2
        assert out.delay.process == "jitter"

    def test_explicit_zero_is_a_value(self, dryrun):
        # the pre-PR-10 `tau_max or rc.delay.tau_max or 4` turned an
        # explicit 0 into the default
        rc = self._rc(dryrun, 2)
        out = dryrun.resolve_cell_rc("qwen1.5-0.5b", "train_4k", False,
                                     rc=rc, delay_process="jitter",
                                     tau_max=0)
        assert out.delay.tau_max == 0

    def test_explicit_value_verbatim(self, dryrun):
        rc = self._rc(dryrun, 2)
        out = dryrun.resolve_cell_rc("qwen1.5-0.5b", "train_4k", False,
                                     rc=rc, delay_process="heavy_tail",
                                     tau_max=7)
        assert out.delay.tau_max == 7

    def test_unset_rc_falls_back_to_default(self, dryrun):
        rc = self._rc(dryrun, 0)   # 0 in the rc itself means unset
        out = dryrun.resolve_cell_rc("qwen1.5-0.5b", "train_4k", False,
                                     rc=rc, delay_process="jitter",
                                     tau_max=None)
        assert out.delay.tau_max == 4

    def test_fixed_delay_leaves_rc_alone(self, dryrun):
        rc = self._rc(dryrun, 2)
        out = dryrun.resolve_cell_rc("qwen1.5-0.5b", "train_4k", False,
                                     rc=rc)
        assert out.delay is rc.delay


# ---------------------------------------------------------------------------
# invariant A: a copy of the ring is reported, a staging-fill copy is not
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def matrix(dryrun):
    """``launch.matrix``, once ``dryrun`` (which it imports) is loaded
    without leaking its flag append."""
    import repro.launch.matrix as m
    return m


def _planted_copies(fill: bool, tag: bool) -> str:
    """Compiled HLO of a write into an f32 buffer that stays live beside
    its update, so XLA copies the buffer: the ring-slot scatter
    (``arena._scatter`` into slot 1 of a stacked (2, 1, rows, 128)
    ring) or, with ``fill``, the staging fill (``flatten_tree`` into a
    (1, rows, 128) buffer, the shape of a ring slot). With
    ``tag`` each copy gets the op metadata of the scatter's own
    update-slice (op_name, and a source line inside ``_scatter``), as
    GSPMD's layout copies carry it."""
    import inspect
    import jax
    import jax.numpy as jnp
    from repro.core import arena
    tree = {"a": jnp.zeros((1, 16, 256)), "b": jnp.zeros((1, 200))}
    layout = arena.make_layout(jax.tree.map(lambda x: x[0], tree))

    def plant(buf, tree):
        with jax.named_scope("master.stage"):
            if fill:
                return arena.flatten_tree(layout, tree, 1, out=buf), buf
            return arena._scatter(layout, tree, 1, buf, slot=1), buf
    buf = jnp.zeros((1, layout.rows, 128) if fill
                    else (2, 1, layout.rows, 128))
    text = jax.jit(plant).lower(buf, tree).compile().as_text()
    if tag:
        op = re.search(r'op_name="([^"]*dynamic_update_slice)"',
                       text).group(1)
        line = inspect.getsourcelines(arena._scatter)[1] + 1
        meta = (f'metadata={{op_name="{op}" source_file="src/repro/core/'
                f'arena.py" source_line={line}}}')
        text = re.sub(r"( copy\(%[\w.]+\))$", rf"\1, {meta}", text,
                      flags=re.M)
    return text


@pytest.mark.parametrize("fill,tag,reported", [
    (False, False, True),     # the ring's own copy
    (False, True, True),      # ... with the slot scatter's metadata
    (True, True, False),      # a staging-fill copy: attributed
], ids=["slot-scatter", "slot-scatter-tagged", "staging-fill"])
def test_ring_copy_in_the_slot_scatter_is_reported(matrix, fill, tag,
                                                   reported):
    """Invariant A on a planted copy. A copy of the f32 ring made by the
    ring-slot scatter is a violation even though the scatter shares
    ``arena._scatter`` with the staging fill; only copies under the
    fill's named scope (``arena.FILL_SCOPE``) are attributed to it."""
    import repro.configs as C
    from repro.configs.base import MeshConfig, RunConfig
    cell = matrix.MatrixCell("planted", "qwen1.5-0.5b", "1x1",
                             delay_process="heavy_tail", tau_max=1)
    rc = RunConfig(model=C.get_smoke_config("qwen1.5-0.5b"),
                   shape=matrix.MATRIX_TRAIN,
                   mesh=MeshConfig(n_pods=1, data=1, model=1))
    text = _planted_copies(fill, tag)
    out = matrix.check_ring_copies(cell, rc, 256, text, None)
    shape = "f32[1,256,128]" if fill else "f32[2,1,256,128]"
    assert re.search(rf"= {re.escape(shape)}\S* copy\(", text)
    if reported:
        assert not out["ok"]
        assert {v["key"] for v in out["violations"]} == {shape}
    else:
        assert out["ok"] and out["violations"] == []
        assert out["attributed_copies"]["staging_fill"]["count"] >= 1


# ---------------------------------------------------------------------------
# parse_mesh / mesh_label
# ---------------------------------------------------------------------------
class TestParseMesh:
    def test_roundtrip(self):
        from repro.launch.mesh import mesh_label, parse_mesh
        for spec in ("16x16", "8x8", "2x16x16", "2x4x8", "8x16"):
            assert mesh_label(parse_mesh(spec)) == spec

    def test_pod_one_collapses(self):
        from repro.launch.mesh import mesh_label, parse_mesh
        cfg = parse_mesh("1x8x8")
        assert cfg == parse_mesh("8x8")
        assert mesh_label(cfg) == "8x8"

    def test_factors(self):
        from repro.launch.mesh import parse_mesh
        cfg = parse_mesh("2x4x8")
        assert (cfg.n_pods, cfg.data, cfg.model) == (2, 4, 8)

    @pytest.mark.parametrize("bad", ["abc", "8", "2x2x2x2", "0x8", "8x-1"])
    def test_rejects(self, bad):
        from repro.launch.mesh import parse_mesh
        with pytest.raises(ValueError):
            parse_mesh(bad)


# ---------------------------------------------------------------------------
# closed-form wire models (hand-computed, integer floor division)
# ---------------------------------------------------------------------------
class TestWireModel:
    def test_master_uncompressed(self):
        # psum of the f32 (96,128) slot over 2 pods:
        # 2*(2-1)*49152//2 = 49152
        got = wire_model.master_pod_exchange_bytes(96, 2, "none")
        assert got == {"f32": 49152}

    def test_master_int8(self):
        # s8 all-gather of (2,96,128): (2-1)*24576//2 = 12288
        # f32 scales all-gather of (2,96): (2-1)*768//2 = 384
        got = wire_model.master_pod_exchange_bytes(96, 2, "int8")
        assert got == {"s8": 12288, "f32": 384}

    def test_variable_psum(self):
        # one f32 psum regardless of compression:
        # 2*(4-1)*(96*128*4)//4 = 73728
        got = wire_model.variable_pod_exchange_bytes(96, 4)
        assert got == {"f32": 73728}

    def test_publish_pop(self):
        # s8 snapshot: (8-1)*(96*128)//8 = 10752
        # u16 scale bits: (8-1)*(96*2)//8 = 168
        got = wire_model.publish_pop_bytes(96, 8)
        assert got == {"s8": 10752, "u16": 168}

    def test_single_pod_is_wire_free(self):
        assert wire_model.master_pod_exchange_bytes(96, 1, "int8") == {}
        assert wire_model.variable_pod_exchange_bytes(96, 1) == {}
        assert wire_model.publish_pop_bytes(96, 1) == {}

    def test_gossip_split_sums_to_consensus_total(self):
        from repro.core import consensus
        for comp in ("none", "int8"):
            split = wire_model.gossip_round_bytes("ring", 8, 96,
                                                  compression=comp)
            assert sum(split.values()) == consensus.payload_bytes_per_round(
                "ring", 8, 96, compression=comp)


# ---------------------------------------------------------------------------
# subprocess regressions (slow tier): the bug this PR fixes, end to end
# ---------------------------------------------------------------------------
def _sub_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    env.pop(ENV_VAR, None)
    env.update(extra)
    return env


@pytest.mark.slow
def test_import_with_pinned_flag_keeps_device_count():
    """The acceptance regression: importing ``repro.launch.dryrun``
    with the flag already pinned used to append ``=512`` (and XLA
    takes the last occurrence); the backend must now initialize with
    the caller's count."""
    code = ("import os, jax\n"
            "import repro.launch.dryrun as d\n"
            "print(d.HOST_DEVICES, jax.device_count(),"
            " os.environ['XLA_FLAGS'].count("
            "'--xla_force_host_platform_device_count'))\n")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=_sub_env(XLA_FLAGS=f"{FLAG}=4", JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["4", "4", "1"]


@pytest.mark.slow
def test_matrix_cell_invariants_8dev():
    """One real 8-device matrix cell end to end: ring-copy freedom,
    compressed DCN edges, census == analytic wire model."""
    out_json = os.path.join(REPO, "tests", ".m8_cell.json")
    try:
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.matrix",
             "--devices", "8", "--cells", "m8-ambdg-qwen15-2x2x2-int8",
             "--json", out_json],
            env=_sub_env(JAX_PLATFORMS="cpu", **{ENV_VAR: "8"}),
            capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        with open(out_json) as f:
            result = json.load(f)
        (row,) = result["results"]
        inv = row["invariants"]
        assert inv["ok"]
        assert inv["ring_copies"]["violations"] == []
        assert inv["exchange"]["census_matches_model"]
        assert inv["exchange"]["compressed_edges"] is True
        assert inv["exchange"]["census_by_dtype"] == \
            inv["exchange"]["analytic_by_dtype"]
    finally:
        if os.path.exists(out_json):
            os.unlink(out_json)
