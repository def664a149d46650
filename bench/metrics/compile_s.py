"""compile_s: XLA backend compile seconds during set-up, summed from
JAX's ``/jax/core/compile/backend_compile_duration`` events. Near zero
when every program comes from the persistent compile cache. Layer:
launcher set-up (``launch/train``, ``launch/compile_cache``)."""


def read(ctx):
    return ctx.compile_s
