"""Device milliseconds per iteration under the named scope ``gson.sample``:
the key split and the signal buffer drawn from the surface
(core/gson/fleet.py, sampling.py). Self time of the operations whose
innermost ``gson.*`` scope it is (profiler trace, ``bench/phases.py``)."""
from bench import phases

SCOPES = {"sample": ("gson.sample",)}


def read(ctx):
    return phases.scope_ms_per_iteration(ctx, SCOPES)
