"""step_device_ms: device time of one execution of the jitted training
step (``core/ambdg.py`` ``train_step``, jitted by the loop as
``jit(train_step)``), averaged over the executions that start in the
traced window. Layer: the strategy step."""
from harness import trace

MODULE = r"train_step"


def step_ms(ctx):
    steps = trace.select(ctx.events, "module", MODULE, ctx.window)
    if not steps:
        return None
    return trace.total_ns(steps) / len(steps) / 1e6


def read(ctx):
    return step_ms(ctx)
