"""Device milliseconds per iteration under the named scope ``gson.check``:
the convergence check on its cadence (fleet_check_impl in
core/gson/fleet.py). Self time of the operations whose innermost
``gson.*`` scope it is (profiler trace, ``bench/phases.py``)."""
from bench import phases

SCOPES = {"check": ("gson.check",)}


def read(ctx):
    return phases.scope_ms_per_iteration(ctx, SCOPES)
