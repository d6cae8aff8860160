"""Host milliseconds per superstep on the job's critical path: the time
in each ``gson.superstep`` span in which the host is not in
``gson.step.wait`` and the device is idle, from the program's spans in
the trace. Device time is left out as well as the wait span because the
host can block on the device elsewhere in the step (in
``gson.step.unwrap``, where slicing the result waits for the superstep)."""
from bench import phases


def read(ctx):
    got = phases.of(ctx)
    if got is None or not got.supersteps:
        return None
    return 1e3 * got.session_host_s / got.supersteps
