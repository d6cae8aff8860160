"""Share of the traced window in which no operation ran on the device
(profiler trace: one minus the union of operation intervals)."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
