"""Share of the traced window in which the device is idle and the host
is not in ``gson.step.wait``: the idle the host's own work causes (the
rest of ``idle_share.solo`` is idle while the host waits on the device)."""
from bench import phases


def read(ctx):
    got = phases.of(ctx)
    if got is None or not got.supersteps:
        return None
    return 100.0 * got.host_idle_s / got.window_s
