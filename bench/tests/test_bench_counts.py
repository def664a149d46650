"""Model FLOPs per token and kernel byte counts against hand counts at
the smoke sizes."""
from types import SimpleNamespace

import pytest

from bench_smoke import PEAKS, smoke_cell
from harness import spec, trace


def test_dense_flops_per_token_hand_count():
    cell = smoke_cell("qwen05b-dense-2k")
    # per layer: q,k,v,o 4*64*64 = 16384, gated MLP 3*64*96 = 18432;
    # 2 layers + tied head 64*256: 2*34816 + 16384 = 86016 weights;
    # attention over the causal half: 2 layers * 2 * 128 * 4 * 16
    # = 32768; forward 2*86016 + 32768 = 204800, training 3x
    assert cell.reference().flops_per_token(cell.config, 128) == 614400


def test_hybrid_flops_per_token_hand_count():
    cell = smoke_cell("zamba2p1-ssm-4k")
    # d_in 128, 8 heads, state 16: in-proj 64*(256+32+8) = 18944,
    # out-proj 128*64 = 8192, x2 layers = 54272; one shared call:
    # attention 4*64*64 = 16384 + MLP 3*64*128 = 24576; head 64*256 =
    # 16384 -> 111616 weights. Per layer conv 2*4*160 = 1280 and scan
    # 4*128*16 = 8192 (x2 = 18944); attention 2*128*4*16 = 16384.
    # forward 2*111616 + 18944 + 16384 = 258560, training 3x
    assert cell.reference().flops_per_token(cell.config, 128) == 775680


def _ctx(events, n_params, traffic=None, delays=None, first=1, steps=0):
    cell = SimpleNamespace(traffic=traffic or {}, config={})
    return SimpleNamespace(events=events, window=(0.0, 1e9),
                           n_params=n_params, peaks=PEAKS, cell=cell,
                           delays=delays, first_window_step=first,
                           steps=steps)


def _op(name, start_us, dur_us, dev="/device:TPU:0"):
    return trace.Event("op", dev, name, start_us * 1e3, dur_us * 1e3)


# the kernels as the trace prints them (HLO text, shortened operands)
DUAL_UPDATE = ('%train_step.1 = (f32[7936,128]{1,0:T(8,128)}, '
               'f32[7936,128]{1,0:T(8,128)}) custom-call(f32[1,2]{1,0} %a, '
               'f32[7936,128]{1,0} %z, f32[7936,128]{1,0} %g), '
               'custom_call_target="tpu_custom_call"')
VARIABLE_POP = ('%train_step.2 = (f32[1,7936,128]{2,1,0}, f32[1,2]{1,0}) '
                'custom-call(s32[3]{0} %m, f32[2,3]{1,0} %c, '
                'f32[3,1,7936,128]{3,2,1,0} %ring), '
                'custom_call_target="tpu_custom_call"')


def test_dual_update_bytes():
    # 3 executions of 100 us; 16 B a parameter each: 3 * 16 * 1e6 B at
    # 1e11 B/s = 480 us of 300 us measured -> 160 %
    ev = [_op(DUAL_UPDATE, 1000 * i, 100) for i in range(3)] + [
        _op(VARIABLE_POP, 3000, 100)]
    read = spec.metric_reader("dual_update_roofline")
    assert read(_ctx(ev, 1_000_000)) == pytest.approx(160.0)


def test_variable_pop_due_slots():
    due = spec.load_module(spec.BENCH_DIR / "metrics"
                           / "variable_pop_roofline.py", "vp").due_slots
    delays = [1, 2, 1, 1, 5]     # due at 2, 4, 4, 5 and (5 -> 2) 7
    assert [due(delays, 3, t) for t in range(1, 8)] == [0, 1, 0, 2, 1, 0,
                                                         1]
    # window steps 4 and 5: (2 + 1) + (1 + 1) = 5 slot reads and writes
    # of 4 B a parameter: 5 * 4 * 1e6 B / 1e11 B/s = 200 us of 400 us
    ev = [_op(VARIABLE_POP, 0, 200), _op(VARIABLE_POP, 500, 200),
          _op(DUAL_UPDATE, 800, 100)]
    read = spec.metric_reader("variable_pop_roofline")
    ctx = _ctx(ev, 1_000_000, {"tau_max": 2}, delays, first=4, steps=2)
    assert read(ctx) == pytest.approx(50.0)
