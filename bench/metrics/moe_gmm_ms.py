"""moe_gmm_ms: device milliseconds a window step of the held-expert
layer's grouped matrix products (``kernels/grouped_matmul``): every
``moe_gmm`` call (the forward and recomputed products, and the rows'
gradient) and every ``moe_tgmm`` call (the expert matrices' gradient).
Layer: kernels."""
from harness import trace

# the kernels' own names (``name=`` on their pallas_call); under
# autodiff the transform's name may wrap them
KERNEL = (r"^%[\w.]*moe_t?gmm[\w.]* = .*custom-call\(.*"
          r"custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    calls = trace.select(ctx.events, "op", KERNEL, ctx.window)
    if not calls:
        return None
    return trace.total_ns(calls) / 1e6 / ctx.steps
