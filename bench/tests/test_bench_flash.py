"""The flash-attention reader on a hand-built trace: the three training
kernels found by name, wrapped by autodiff's name or not, their time a
window step; the inference forward, other ops and calls after the
window not counted; nothing read where no kernel ran."""
from types import SimpleNamespace

import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, trace

TPU = "/device:TPU:0"
CUSTOM = "custom_call_target=\"tpu_custom_call\""
FWD = ("%flash_attention_fwd.20 = (bf16[1,1024,2048]{2,1,0}, "
       "f32[1,2,8,2048]{3,2,1,0}) custom-call(bf16[1,1024,2048] %q, "
       f"bf16[1,1024,2048] %k, bf16[1,1024,2048] %v), {CUSTOM}")
FWD_JVP = ("%jvp_flash_attention_fwd_.3 = (bf16[1,1024,2048]{2,1,0}, "
           "f32[1,2,8,2048]{3,2,1,0}) custom-call(bf16[1,1024,2048] %q), "
           f"{CUSTOM}")
DQ = ("%transpose_jvp_flash_attention_dq__.13 = bf16[1,1024,2048]{2,1,0} "
      f"custom-call(bf16[1,1024,2048] %q, f32[1,2,8,2048] %l), {CUSTOM}")
DKV = ("%flash_attention_dkv.13 = (bf16[1,1024,2048]{2,1,0}, "
       "bf16[1,1024,2048]{2,1,0}) custom-call(bf16[1,1024,2048] %q), "
       f"{CUSTOM}")
INFER = ("%flash_attention.2 = bf16[1,1024,2048]{2,1,0} "
         f"custom-call(bf16[1,1024,2048] %q), {CUSTOM}")
OTHER = "%fusion.7 = bf16[1,1024,2048]{2,1,0} fusion(bf16[1,1024,2048] %p)"


def _events():
    ev = [trace.Event("host", "/host:CPU", trace.WINDOW_START, 0.0, 1.0),
          trace.Event("host", "/host:CPU", trace.WINDOW_END, 1e9, 1.0)]
    at = 1e6
    for name, dur in ((FWD, 2e6), (FWD_JVP, 3e6), (DQ, 4e6), (DKV, 5e6),
                      (INFER, 6e6), (OTHER, 7e6)):
        ev.append(trace.Event("op", TPU, name, at, dur))
        at += dur + 1e3
    # a call after the window is not counted
    ev.append(trace.Event("op", TPU, DKV, 2e9, 9e6))
    return ev


def _ctx(events, cell="qwen05b-dense-2k"):
    return SimpleNamespace(events=events, window=trace.window(events),
                           steps=2, cell=spec.load_cell(cell))


def test_flash_attention_ms_sums_the_training_kernels_a_step():
    ms = spec.metric_reader("flash_attention_ms")(_ctx(_events()))
    assert ms == pytest.approx((2e6 + 3e6 + 4e6 + 5e6) / 1e6 / 2)


@pytest.mark.parametrize("cell", ["qwen05b-stale-512",
                                  "moonlight-ep8-moe-8k"])
def test_flash_attention_ms_reads_nothing_without_the_kernels(cell):
    """A jnp core (the stale cell's 512 tokens, or a program without
    the kernel) leaves the metric out of the line."""
    events = [e for e in _events() if "flash_attention_" not in e.name]
    assert spec.metric_reader("flash_attention_ms")(_ctx(events, cell)) \
        is None
