"""Host milliseconds per finished job outside the superstep calls:
``Session`` construction, ``result()``, the Euler-characteristic check
and the loop between supersteps (the harness's own spans)."""


def read(ctx):
    return 1e3 * sum(r.wall_s - r.superstep_s for r in ctx.jobs) / len(ctx.jobs)
