"""Device milliseconds per iteration in the Find Winners kernel
(profiler trace, events by the kernel's name)."""

KERNELS = {"find_winners": ("find_winners_pallas_padded",)}


def read(ctx):
    iters = sum(s["iterations"] for s in ctx.supersteps)
    t = ctx.trace.kernel_s["find_winners"]
    return 1e3 * t / iters if iters and t > 0 else None
