"""The trace-to-metric reduction on a small recorded trace: two steps
of ``qwen05b-dense-2k`` on one TPU v5e, cut from a ``--trace 1`` run
(``data/qwen05b-dense-2k.steps.json.gz``: the device ops and modules
overlapping steps 2-3 of the traced window, the host events over 20 us,
and window markers moved to the cut)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, trace

DATA = Path(__file__).parent / "data" / "qwen05b-dense-2k.steps.json.gz"
TPU = "/device:TPU:0"
# the profiler's own durations of the two step modules and of the two
# dual-update kernel calls in the cut (ns)
MODULES = [288921048.0, 288927230.0]
KERNELS = [11580984.0, 11576885.0]
N_PARAMS = 464118784          # qwen1.5-0.5b with its table padded to 152064


@pytest.fixture(scope="module")
def events():
    return trace.read_events(str(DATA))


def _ctx(events, peaks=None):
    return SimpleNamespace(events=events, window=trace.window(events),
                           n_params=N_PARAMS, peaks=peaks or {
                               "hbm_bytes_per_s": 819e9,
                               "bf16_flops_per_s": 197e12},
                           steps=2, tokens_per_step=4096,
                           cell=spec.load_cell("qwen05b-dense-2k"))


def test_busy_union_and_idle_share(events):
    w = trace.window(events)
    busy = trace.busy_ns(events, TPU, w)
    # the union covers the two modules and never exceeds the window
    assert sum(MODULES) <= busy <= w[1] - w[0]
    assert busy == 578026234.0
    idle = spec.metric_reader("device_idle_share")(_ctx(events))
    assert idle == pytest.approx(100 * (1 - busy / (w[1] - w[0])))
    assert 0 <= idle < 0.01


def test_step_module_time(events):
    ms = spec.metric_reader("step_device_ms")(_ctx(events))
    assert ms == pytest.approx(sum(MODULES) / 2 / 1e6)


def test_kernel_time_by_name(events):
    w = trace.window(events)
    kernel = spec.load_module(spec.BENCH_DIR / "metrics"
                              / "dual_update_roofline.py", "du").KERNEL
    calls = trace.select(events, "op", kernel, w)
    assert [e.dur_ns for e in calls] == KERNELS
    share = spec.metric_reader("dual_update_roofline")(_ctx(events))
    # 2 calls x 16 B x params at 819 GB/s over the kernels' own time
    assert share == pytest.approx(
        100 * 2 * 16 * N_PARAMS / 819e9 / (sum(KERNELS) / 1e9))


def test_breakdown_names_leaf_ops(events):
    w = trace.window(events)
    top = trace.top_ops(events, w)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert all(" = " not in name for name, _ in top)
    assert not any("while" in name for name, _ in top)   # parents left out
    gaps = trace.idle_gaps(events, w)
    assert gaps and all(s > 0 for _, s in gaps)


def _op(name, start, end, dev=TPU):
    return trace.Event("op", dev, name, float(start), float(end - start))


def test_exposed_collective_time():
    # hand-built: an all-gather over [0, 10) with compute over [2, 5)
    # and [8, 12): exposed 10 - 3 - 2 = 5; a second all-reduce over
    # [20, 24) with nothing beside it: 4 more
    ev = [_op("%all-gather.1 = all-gather(...)", 0, 10),
          _op("%fusion.1 = fusion(...)", 2, 5),
          _op("%fusion.2 = fusion(...)", 8, 12),
          _op("%all-reduce.3 = all-reduce(...)", 20, 24)]
    assert trace.exposed_collective_ns(ev, TPU, (0, 30)) == 9
    assert trace.exposed_collective_ns(ev, TPU, (0, 10)) == 5
