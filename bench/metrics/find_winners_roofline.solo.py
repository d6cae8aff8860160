"""Find Winners' share of its roofline: the least time the chip needs
for the work the algorithm requires (m_t valid signals against the
active units, ``bench/work.py``) over the kernel's device time."""
from bench import work

KERNELS = {"find_winners": ("find_winners_pallas_padded",)}


def read(ctx):
    t = ctx.trace.kernel_s["find_winners"]
    if t <= 0:
        return None
    least = 0.0
    for s in ctx.supersteps:
        m = work.m_schedule(s["n_active"], ctx.buffer, ctx.min_m)
        flops, nbytes = work.find_winners(m, s["n_active"], ctx.dim)
        least += s["iterations"] * max(flops / ctx.peak["flops_per_s"],
                                       nbytes / ctx.peak["bytes_per_s"])
    return 100.0 * least / t
