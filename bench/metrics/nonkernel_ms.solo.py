"""Device milliseconds per iteration outside the two Pallas kernel
families: the structural tail, topology refresh, convergence check,
sampling and the glue around the kernels (profiler trace)."""

KERNELS = {
    "find_winners": ("find_winners_pallas_padded",),
    "update": ("winner_lock_pallas_padded", "update_accum_pallas_padded",
               "edge_age_pallas_padded"),
}


def read(ctx):
    iters = sum(s["iterations"] for s in ctx.supersteps)
    if not iters:
        return None
    kernel = sum(ctx.trace.kernel_s[g] for g in KERNELS)
    return 1e3 * (ctx.trace.busy_s - kernel) / iters
