"""dual_update_roofline: the fused dual-averaging update kernel
(``kernels/dual_update``) against its memory roofline, in percent.
The work, counted from the parameters and not from the kernel: read z
and the popped gradient sum, write z and the parameters, float32 over
every parameter, so 16 bytes a parameter a step. Least time = those
bytes / peak HBM bandwidth; share = least time / kernel device time.
Layer: kernels."""
from harness import trace

# the kernel has no name of its own in the trace (its pallas_call sets
# none): it is the Pallas custom call that returns two float32 arenas
# of the same rows, z and the parameters
KERNEL = (r"^\S+ = \(f32\[(\d+),128\]\{[^}]*\}, f32\[\1,128\]\{[^}]*\}\) "
          r"custom-call\(.*custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    calls = trace.select(ctx.events, "op", KERNEL, ctx.window)
    if not calls:
        return None
    least = len(calls) * 16.0 * ctx.n_params / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (trace.total_ns(calls) / 1e9)
