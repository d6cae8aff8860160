"""The plain reference that decides ``correct``, and its surfaces.

A surface is one file, ``surfaces/<name>.py``, found by name as a
configuration, a traffic mix or a metric reader is. It defines

  GENUS     the genus g of the surface: a converged mesh of it has the
            Euler characteristic 2 - 2g
  sample    ``sample(rng, n) -> (n, 3) float32`` in plain ``jax.numpy``,
            a copy of the program's sampler of the same name, operation
            for operation, so that both draw the same points bit for bit

and imports nothing of the program. A new surface is a new file.
"""
from __future__ import annotations

import functools
import importlib.util
import os

SURFACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "surfaces")


def surface_file(name: str) -> str:
    return os.path.join(SURFACE_DIR, f"{name}.py")


@functools.cache
def surface(name: str):
    """The module ``surfaces/<name>.py`` (``GENUS``, ``sample``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_surface_" + name.replace(".", "_").replace("-", "_"),
        surface_file(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
