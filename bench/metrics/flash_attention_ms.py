"""flash_attention_ms: device milliseconds a window step of the training
attention core's Pallas kernels (``kernels/flash_attention``): every
``flash_attention_fwd`` call (the forward and its recomputation), and
every ``flash_attention_dq`` and ``flash_attention_dkv`` call (the
backward). Reads nothing where the core runs in jnp (below
``models.layers._FLASH_THRESHOLD`` tokens, or a program without the
kernel). Layer: kernels."""
from harness import trace

# the kernels' own names (``name=`` on their pallas_call); under
# autodiff the transform's name may wrap them
KERNEL = (r"^%[\w.]*flash_attention_(?:fwd|dq|dkv)[\w.]* = .*custom-call\(.*"
          r"custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    calls = trace.select(ctx.events, "op", KERNEL, ctx.window)
    if not calls:
        return None
    return trace.total_ns(calls) / 1e6 / ctx.steps
