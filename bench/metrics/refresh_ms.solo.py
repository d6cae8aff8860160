"""Device milliseconds per iteration under the named scope
``gson.refresh``: the topology refresh on its cadence
(core/gson/fleet.py, topology.py). Self time of the operations whose
innermost ``gson.*`` scope it is (profiler trace, ``bench/phases.py``)."""
from bench import phases

SCOPES = {"refresh": ("gson.refresh",)}


def read(ctx):
    return phases.scope_ms_per_iteration(ctx, SCOPES)
