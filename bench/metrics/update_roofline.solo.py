"""The Update kernels' share of their roofline: the least time the chip
needs for the work the algorithm requires (the surviving signals'
winners and neighbours, ``bench/work.py``) over their device time."""
from bench import work

KERNELS = {"update": ("winner_lock_pallas_padded",
                      "update_accum_pallas_padded",
                      "edge_age_pallas_padded")}


def read(ctx):
    t = ctx.trace.kernel_s["update"]
    if t <= 0:
        return None
    least = 0.0
    for s in ctx.supersteps:
        flops, nbytes = work.update(s["survivors"], s["degree"], ctx.dim)
        least += max(flops / ctx.peak["flops_per_s"],
                     nbytes / ctx.peak["bytes_per_s"])
    return 100.0 * least / t
