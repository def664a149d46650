"""moe_gmm_roofline: the held-expert layer's grouped matrix products
against the bf16 compute roofline, in percent. A call multiplies R rows
by one K x N matrix an expert, 2 x R x K x N FLOPs, where K x N is read
from the call's expert-matrix shape in its op text (an operand of
``moe_gmm``, the result of ``moe_tgmm``) and R is the rows the held
experts really ran: the program names each step's held rows, summed
over its MoE layers and microbatches, in a host span
``moe.held_rows=<n>`` (``train/loop.py``), and every call of a layer
and microbatch sees that layer's rows, so R is the window's mean of
<n> over MoE layers x microbatches. The kernels skip rows in no group,
so this is the work they do. Recomputed products count, since the
kernel really runs them. Least time = that work / peak bf16 FLOP/s;
share = least time / the calls' device time. None for a program that
writes no such span. Layer: kernels."""
import re
import statistics

from harness import spec, trace

_ms = spec.load_module(spec.BENCH_DIR / "metrics" / "moe_gmm_ms.py",
                       "bench_metric_moe_gmm_ms")
EXPERTS = re.compile(r"\b(?:bf16|f32)\[(\d+),(\d+),(\d+)\]")
HELD_ROWS = re.compile(r"^moe\.held_rows=(\d+)$")


def call_rows(ctx):
    """The held rows a call of the grouped products sees, on average
    over the window's steps, MoE layers and microbatches; None without
    the program's spans."""
    spans = trace.select(ctx.events, "host", HELD_ROWS.pattern, ctx.window)
    if not spans:
        return None
    step = statistics.fmean(int(HELD_ROWS.match(e.name).group(1))
                            for e in spans)
    c = ctx.cell.config
    moe_layers = c["n_layers"] - c.get("first_k_dense", 0)
    return step / (moe_layers * ctx.cell.traffic["n_microbatches"])


def read(ctx):
    calls = trace.select(ctx.events, "op", _ms.KERNEL, ctx.window)
    rows = call_rows(ctx)
    if not calls or rows is None:
        return None
    flops = 0.0
    for e in calls:
        _, k, n = EXPERTS.search(e.name).groups()
        flops += 2.0 * rows * int(k) * int(n)
    least = flops / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least / (trace.total_ns(calls) / 1e9)
