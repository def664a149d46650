"""backend_start_s: seconds from the process's start until JAX has
started its TPU runtime and found the chip, on the host's clock. The
part of ``setup_s`` that neither the weights, the compile cache nor the
warm-up holds, and its noisiest: apart so that a change of ``setup_s``
can be told from a slow runtime start. Layer: launcher set-up."""


def read(ctx):
    return ctx.setup["backend_s"]
