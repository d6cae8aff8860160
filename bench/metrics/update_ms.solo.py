"""Device milliseconds per iteration in the Update kernels: winner
lock, accumulation and edge aging (profiler trace, events by name)."""

KERNELS = {"update": ("winner_lock_pallas_padded",
                      "update_accum_pallas_padded",
                      "edge_age_pallas_padded")}


def read(ctx):
    iters = sum(s["iterations"] for s in ctx.supersteps)
    t = ctx.trace.kernel_s["update"]
    return 1e3 * t / iters if iters and t > 0 else None
