"""Pallas kernel packages for the paper's profiled hot spots.

One package per kernel, each with the same layout: ``kernel.py`` (the
Pallas TPU kernels), ``ops.py`` (jit'd padding/masking wrapper + engine
adapter), ``ref.py`` (an independent pure-jnp oracle for the tests).

  find_winners — the paper's parallelized phase (Sec. 2.5): batched
      top-2 nearest-unit search as a streaming MXU matmul reduction.
  update_phase — the phase the paper leaves as future work once Find
      Winners is parallel: winner lock + dense adaptation as tiled
      one-hot contractions (lock scatter-min, accumulators, edge aging).

Kernels are selected per-``RunSpec`` through the BACKENDS registry
(``repro.gson.registry``); every kernel keeps a reference fallback for
lowering failures, so this package is an optional acceleration layer,
never a dependency of correctness.
"""


class PlatformMismatchError(RuntimeError):
    """The process runs on a device a kernel backend was not built or
    measured for. A configuration error, not a lowering failure: the
    session and fleet drivers re-raise it instead of falling back to
    the reference backend."""


def interpret_mode(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` flag from the default backend.

    ``None`` compiles through Mosaic on a TPU and interprets on the CPU
    (where the tests run). Any other backend raises
    :class:`PlatformMismatchError`: the accelerator the kernels were
    written for is absent, and running them interpreted there would
    hide that behind a slow, silent run.
    """
    import jax

    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise PlatformMismatchError(
        f"Pallas TPU kernels cannot run on backend {backend!r}: use a "
        "TPU, the CPU (kernels interpreted), or pass interpret=")
