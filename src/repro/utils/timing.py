"""Host timing: the program's one tracing entry point, and a
block-until-ready micro-timer.

:func:`span` marks a stretch of host work as ``gson.<name>`` in the
profiler's trace (a ``jax.profiler.TraceAnnotation``, on the same clock
as the device operations) and, given a ``timings`` dict, adds its
wall-clock seconds there too, so stats and trace share one boundary.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "gson."
_recording = TraceAnnotation.is_enabled


class _Off:
    """What :func:`span` returns with the profiler off and no timings."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("_name", "_timings", "_me", "_t0")

    def __init__(self, name: str, timings: dict | None, ids: dict):
        self._name, self._timings = name, timings
        # the profiler formats ``ids`` only while it records
        self._me = (TraceAnnotation(PREFIX + name, **ids) if _recording()
                    else None)
        self._t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self._timings is not None:
            t = self._timings
            t[self._name] = (t.get(self._name, 0.0)
                             + time.perf_counter() - self._t0)
        if self._me is not None:
            self._me.__exit__(*exc)
        return False


def span(name: str, timings: dict | None = None, **ids):
    """``with span(name, timings=None, **ids): ...``

    Opens the profiler annotation ``gson.<name>`` carrying ``ids`` as
    event stats, and adds the elapsed ``perf_counter`` seconds to
    ``timings[name]`` when a dict is given. With the profiler off and
    no dict it does nothing."""
    if timings is None and not _recording():
        return _OFF
    return _Span(name, timings, ids)


def timed(fn, *args, n: int = 5, warmup: int = 1, **kwargs):
    """Return (result, seconds_per_call) with block_until_ready."""
    import jax

    result = None
    for _ in range(warmup):
        result = fn(*args, **kwargs)
        jax.block_until_ready(result)
    t0 = time.perf_counter()
    for _ in range(n):
        result = fn(*args, **kwargs)
        jax.block_until_ready(result)
    return result, (time.perf_counter() - t0) / n
