"""device_idle_share: the share of the traced window in which no
operation runs on the device (1 - the union of op intervals over the
window, averaged over the chips used), in percent. Layer: the host
loop (``train/loop.py``)."""
from harness import trace


def read(ctx):
    span = (ctx.window[1] - ctx.window[0]) / 1e9
    if span <= 0 or not trace.devices(ctx.events):
        return None
    return 100.0 * (1.0 - trace.mean_busy_s(ctx.events, ctx.window) / span)
