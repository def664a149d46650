"""host_prep_ms: host time the training loop spends making each step's
batch before it dispatches the step, in ms a window step: the union
of its ``loop.batch`` (the data pipeline), ``loop.draws`` (the
batch-schedule, elastic and delay draws) and ``loop.put`` (the batch
to the device) spans in the traced window, over the window's steps.
The host makes step t+1's batch while the device runs step t, so this
costs the rate only as far as it outgrows the device's step. None for
a program whose loop writes no such spans. Layer: the host loop
(``train/loop.py``)."""
from harness import trace

SPANS = r"^loop\.(batch|draws|put)$"


def read(ctx):
    spans = trace.select(ctx.events, "host", SPANS, ctx.window)
    if not spans:
        return None
    hi = ctx.window[1]
    busy = trace.union((e.start_ns, min(e.end_ns, hi)) for e in spans)
    return sum(t - s for s, t in busy) / ctx.steps / 1e6
