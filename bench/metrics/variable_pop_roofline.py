"""variable_pop_roofline: the delay-tolerant ring's pop kernel
(``kernels/delay_ring`` ``variable_pop_fwd``) against its memory
roofline, in percent. The work, counted from the delays and not from
the kernel: each step reads the ring slots due at that step (the
pushes s with s + tau_s = t, tau_s the host's draw clipped to the
ring) and writes the popped sum, float32 over every parameter. Least
time = those bytes / peak HBM bandwidth; share = least time / kernel
device time, summed over the traced window's steps. Layer: kernels."""
from harness import trace

# the kernel has no name of its own in the trace (its pallas_call sets
# none): it is the Pallas custom call that reads the stacked ring,
# a rank-4 float32 operand (slots, pods, rows, 128)
KERNEL = (r"custom-call\(.*f32\[\d+,\d+,\d+,128\].*"
          r"custom_call_target=\"tpu_custom_call\"")


def due_slots(delays, n_slots, step):
    """Pushes due at absolute step ``step`` (1-based)."""
    return sum(1 for s, d in enumerate(delays[:step], start=1)
               if s + min(max(d, 0), n_slots - 1) == step)


def read(ctx):
    if ctx.delays is None:
        return None
    calls = trace.select(ctx.events, "op", KERNEL, ctx.window)
    if not calls:
        return None
    n_slots = ctx.cell.traffic["tau_max"] + 1
    steps = range(ctx.first_window_step, ctx.first_window_step + ctx.steps)
    slots = sum(due_slots(ctx.delays, n_slots, t) + 1 for t in steps)
    t = trace.total_ns(calls) / 1e9
    least = slots * 4.0 * ctx.n_params / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
