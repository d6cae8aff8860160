"""Device milliseconds per iteration under the named scope ``gson.tail``:
the structural tail, unit and edge insertion, expiry and pruning
(core/gson/multi.py steps 3f-3h). Self time of the operations whose
innermost ``gson.*`` scope it is (profiler trace, ``bench/phases.py``)."""
from bench import phases

SCOPES = {"tail": ("gson.tail",)}


def read(ctx):
    return phases.scope_ms_per_iteration(ctx, SCOPES)
