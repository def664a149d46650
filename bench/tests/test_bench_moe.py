"""The moonlight cell's per-layer readers on a hand-built trace: the
grouped-product kernels found by name (wrapped by autodiff's name or
not), their time a step, and their work against the bf16 peak from the
expert-matrix shape in each call's op text and the held rows the
program names in its ``moe.held_rows=<n>`` spans."""
from types import SimpleNamespace

import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, trace

TPU = "/device:TPU:0"
CELL = "moonlight-ep8-moe-8k"
GMM = ("%moe_gmm.3 = bf16[49152,1408]{1,0:T(8,128)(2,1)} custom-call("
       "s32[9]{0} %a, s32[391]{0} %b, s32[391]{0} %c, "
       "bf16[49152,2048]{1,0:T(8,128)(2,1)} %x, "
       "bf16[8,2048,1408]{2,1,0:T(8,128)(2,1)} %w), "
       "custom_call_target=\"tpu_custom_call\"")
GMM_T = ("%transpose_jvp_moe_gmm__.7 = bf16[49152,1408]{1,0} custom-call("
         "s32[9]{0} %a, bf16[49152,2048]{1,0} %g, "
         "bf16[8,1408,2048]{2,1,0} %w), "
         "custom_call_target=\"tpu_custom_call\"")
TGMM = ("%moe_tgmm.1 = bf16[8,2048,1408]{2,1,0:T(8,128)(2,1)} custom-call("
        "s32[9]{0} %a, bf16[49152,2048]{1,0} %x, "
        "bf16[49152,1408]{1,0} %g), "
        "custom_call_target=\"tpu_custom_call\"")
OTHER = ("%fusion.9 = bf16[8,2048,1408]{2,1,0} fusion(f32[8,2048,1408] %p)")
PEAK = 197e12


def _events():
    ev = [trace.Event("host", "/host:CPU", trace.WINDOW_START, 0.0, 1.0),
          trace.Event("host", "/host:CPU", trace.WINDOW_END, 1e9, 1.0)]
    at = 1e6
    for name, dur in ((GMM, 2e6), (GMM_T, 3e6), (TGMM, 4e6),
                      (OTHER, 5e6)):
        ev.append(trace.Event("op", TPU, name, at, dur))
        at += dur + 1e3
    # a call after the window is not counted
    ev.append(trace.Event("op", TPU, GMM, 2e9, 7e6))
    # the held rows of two steps (4 MoE layers x 2 microbatches each),
    # and one after the window that is not counted
    for at, rows in ((2e6, 40000), (6e8, 56000), (3e9, 98304)):
        ev.append(trace.Event("host", "/host:CPU",
                              f"moe.held_rows={rows}", at, 1e3))
    return ev


def _ctx(events):
    return SimpleNamespace(events=events, window=trace.window(events),
                           steps=2, tokens_per_step=16384,
                           peaks={"bf16_flops_per_s": PEAK},
                           cell=spec.load_cell(CELL))


def test_moe_gmm_ms_sums_both_kernels_a_step():
    ms = spec.metric_reader("moe_gmm_ms")(_ctx(_events()))
    assert ms == pytest.approx((2e6 + 3e6 + 4e6) / 1e6 / 2)


def test_moe_gmm_roofline_counts_the_held_rows():
    share = spec.metric_reader("moe_gmm_roofline")(_ctx(_events()))
    # the window's mean step, 48,000 held rows, over 4 MoE layers x 2
    # microbatches: 6,000 rows a call
    rows = (40000 + 56000) / 2 / (4 * 2)
    assert rows == 6000
    flops = 3 * 2 * rows * 2048 * 1408
    assert share == pytest.approx(100 * flops / PEAK / 9e-3)


@pytest.mark.parametrize("rows", [6144, 49152])
def test_moe_gmm_roofline_scales_with_the_rows_really_run(rows):
    """The same calls and times read 8x the share when every choice is
    held (the tie of a near-zero router: 49,152 rows a call) as at
    balanced routing (6,144)."""
    ev = [e for e in _events() if not e.name.startswith("moe.held_rows")]
    ev.append(trace.Event("host", "/host:CPU",
                          f"moe.held_rows={rows * 8}", 2e6, 1e3))
    share = spec.metric_reader("moe_gmm_roofline")(_ctx(ev))
    assert share == pytest.approx(
        100 * 3 * 2 * rows * 2048 * 1408 / PEAK / 9e-3)


@pytest.mark.parametrize("drop", ["moe_", "moe.held_rows"])
def test_readers_find_nothing_without_the_kernels(drop):
    """No kernels: neither reader reads. No held-row spans (a program
    that does not name them): the roofline reads nothing."""
    events = [e for e in _events() if drop not in e.name]
    ctx = _ctx(events)
    if drop == "moe_":
        assert spec.metric_reader("moe_gmm_ms")(ctx) is None
    assert spec.metric_reader("moe_gmm_roofline")(ctx) is None


def test_mla_moe_flops_per_token_hand_count():
    cfg = {"d_model": 64, "n_heads": 4, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "kv_lora_rank": 32, "v_head_dim": 16,
           "d_ff": 128, "first_k_dense": 1, "n_layers": 3,
           "vocab_size": 256,
           "moe": {"n_experts": 4, "n_routed": 32, "top_k": 6,
                   "d_expert": 32, "n_shared": 2}}
    ref = spec.load_cell(CELL).reference()
    # MLA a layer: q 64*4*24 = 6144, kv_a 64*40 = 2560, kv_b 32*4*32 =
    # 4096, o 4*16*64 = 4096 -> 16896. Dense layer + MLP 3*64*128:
    # 41472. MoE layer + router 64*32, routed experts at 6 x 4/32 of
    # 3*64*32 = 4608, shared 2 * 6144: 35840. Head 64*256 = 16384 ->
    # 129536 weights; attention 3 layers * 128 * 4 * (24 + 16) = 61440;
    # forward 2*129536 + 61440 = 320512, training 3x
    assert ref.flops_per_token(cfg, 128) == 961536


MOE_SMOKE = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                 vocab_size=256, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16)


def _smoke_cell():
    cell = spec.load_cell(CELL)
    cell.config.update(MOE_SMOKE)
    cell.config["moe"] = dict(cell.config["moe"], n_experts=4, n_routed=32,
                              d_expert=32)
    cell.traffic["seq_len"] = 128
    return cell


def test_moonlight_cell_runs_correct_on_the_cpu(monkeypatch):
    """The cell through the whole run at its smoke size: the program's
    steps, the window, the plain reference and the comparison; the
    fp8 control fails the cell's limits on the same steps."""
    import time

    import jax
    from harness import compare, drive, runner
    from bench_smoke import PEAKS
    monkeypatch.setattr(runner, "peaks", lambda kind: PEAKS)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        cell = _smoke_cell()
        res = runner.run(cell, 2 ** 31 + 77, 0.3, False,
                         jax.devices()[:1], time.perf_counter())
        assert res["correct"], res["checks"]
        model, rc, loop, struct = drive.build(cell, 2 ** 31 + 77)
        driver = drive.Driver(loop, drive.Probes(struct),
                              drive.n_ring_phases(cell.traffic), None,
                              drive.CompileCounter())
        r = drive.run_train(model, rc, loop, driver)
        fp8 = compare.follow(cell, struct, 2 ** 31 + 77, r.batches,
                             r.delays, drive.N_CHECK, precision="fp8")
        ref = compare.follow(cell, struct, 2 ** 31 + 77, r.batches,
                             r.delays, drive.N_CHECK)
        assert not compare.judge(compare.gaps(fp8, ref),
                                 cell.limits["limits"])
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
