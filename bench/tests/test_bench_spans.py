"""The readers on a small recorded trace of the program as it names its
work: two steps of ``qwen05b-stale-512`` on one TPU v5e, cut from a
``--trace 1`` run (``data/qwen05b-stale-512.steps.json.gz``: the device
ops and modules overlapping steps 3-4 of the traced window, the host
events over 20 us and every ``train`` and ``loop.*`` span, and window
markers moved to the cut: the first step module's start and the second's
end)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, trace

DATA = Path(__file__).parent / "data" / "qwen05b-stale-512.steps.json.gz"
# (start, end) ns of the batch, draws and put spans of the two steps in
# the cut, as the trace holds them: the host makes each step's batch
# while the device runs the step before
PREP = [(221529586.0, 221837796.0), (221839466.0, 221869156.0),
        (221870006.0, 223470816.0), (305035221.0, 305373841.0),
        (305375761.0, 305420961.0), (305421851.0, 306905921.0)]
MODULES = [83545340.0, 83542382.0]
# the two named kernels' calls in the cut (ns)
DUAL_UPDATE = [11552806.0, 11552140.0]
VARIABLE_POP = [11094664.0, 11091986.0]


@pytest.fixture(scope="module")
def events():
    return trace.read_events(str(DATA))


def _ctx(events):
    return SimpleNamespace(events=events, window=trace.window(events),
                           steps=2, cell=spec.load_cell("qwen05b-stale-512"))


def test_host_prep_is_the_union_of_the_prep_spans(events):
    ms = spec.metric_reader("host_prep_ms")(_ctx(events))
    assert ms == pytest.approx(sum(t - s for s, t in PREP) / 2 / 1e6)
    # a trace of a loop that writes no spans reads nothing
    bare = [e for e in events if not e.name.startswith("loop.")]
    assert spec.metric_reader("host_prep_ms")(_ctx(bare)) is None


def test_host_prep_clips_a_span_at_the_window_end(events):
    ctx = _ctx(events)
    end = PREP[-1][0] + 1000.0          # cuts the last put span short
    ctx.window = (ctx.window[0], end)
    kept = [(s, min(t, end)) for s, t in PREP if s < end]
    ms = spec.metric_reader("host_prep_ms")(ctx)
    assert ms == pytest.approx(sum(t - s for s, t in kept) / 2 / 1e6)


def test_readers_find_the_step_and_the_named_kernels(events):
    w = trace.window(events)
    ms = spec.metric_reader("step_device_ms")(_ctx(events))
    assert ms == pytest.approx(sum(MODULES) / 2 / 1e6)
    # the kernel readers anchor on shapes and the custom-call target;
    # the calls they select are the kernels named after themselves
    for metric, name, durs in (("dual_update_roofline", "dual_update_fused",
                                DUAL_UPDATE),
                               ("variable_pop_roofline", "variable_pop_f32",
                                VARIABLE_POP)):
        kernel = spec.load_module(spec.BENCH_DIR / "metrics"
                                  / f"{metric}.py", metric).KERNEL
        calls = trace.select(events, "op", kernel, w)
        assert [e.dur_ns for e in calls] == durs
        assert all(e.name.startswith(f"%{name}.") for e in calls)
