"""Device-to-host scalar reads of the session loop per superstep:
``gson.readback`` spans per ``gson.superstep`` span in the trace."""
from bench import phases


def read(ctx):
    got = phases.of(ctx)
    if got is None or not got.supersteps:
        return None
    return got.readbacks / got.supersteps
