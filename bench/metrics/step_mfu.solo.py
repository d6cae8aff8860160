"""The whole step's share of the chip's peak: the operations the
algorithm requires (Find Winners and Update, ``bench/work.py``) over
the traced window's length times the peak rate."""
from bench import work


def read(ctx):
    flops = 0.0
    for s in ctx.supersteps:
        m = work.m_schedule(s["n_active"], ctx.buffer, ctx.min_m)
        flops += s["iterations"] * work.find_winners(
            m, s["n_active"], ctx.dim)[0]
        flops += work.update(s["survivors"], s["degree"], ctx.dim)[0]
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["flops_per_s"])
