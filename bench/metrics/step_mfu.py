"""step_mfu: the model FLOPs of one step (the configuration's
``flops_per_token`` times the step's tokens) over the step's device
time (``step_device_ms``) and the chip's bf16 peak, in percent: the
whole step's share of the peak, which bounds every kernel's gain.
Layer: the strategy step."""
from harness import spec

_step = spec.load_module(spec.BENCH_DIR / "metrics" / "step_device_ms.py",
                         "bench_metric_step_device_ms").step_ms


def read(ctx):
    ms = _step(ctx)
    if ms is None:
        return None
    flops = ctx.cell.reference().flops_per_token(
        ctx.cell.config, ctx.cell.traffic["seq_len"]) * ctx.tokens_per_step
    return 100.0 * flops / (ms / 1e3) / ctx.peaks["bf16_flops_per_s"]
