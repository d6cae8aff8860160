"""Named registries for the composable GSON run API.

Four orthogonal axes, mirroring the paper's experimental matrix:

  VARIANTS  — how the iterate-sample-converge loop is parallelized
              (the paper's contribution axis: single / indexed / multi /
              multi-fused)
  MODELS    — the growing-network rule set (GNG / GWR / SOAM)
  SAMPLERS  — the signal distribution P(xi) (benchmark surfaces +
              point-cloud streams from ``repro.data.pointclouds``)
  BACKENDS  — device implementations of the step's two hot phases
              (paper Sec. 2.5): Find Winners and the dense Update
              phase (pure-jnp references, Pallas kernel suites)

Every axis accepts either a registered name or a concrete object, so
``RunSpec(variant="multi", sampler="sphere")`` and
``RunSpec(variant=MultiVariant(), sampler=my_sampler)`` resolve to the
same run. Registries raise on duplicates and list their options on a
miss; registering a new entry makes it visible to every enumerating
caller (``benchmarks/run.py`` builds its variant matrix from
``VARIANTS.names()``).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from typing import Any, Callable, Generic, Iterator, TypeVar

from repro.core.gson.multi import find_winners_reference
from repro.core.gson.sampling import SURFACES, make_sampler
from repro.core.gson.state import GSONParams
from repro.kernels import PlatformMismatchError

T = TypeVar("T")


class Registry(Generic[T]):
    """A write-once name -> object table with helpful misses."""

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: dict[str, T] = {}

    def register(self, name: str, obj: T | None = None):
        """``register(name, obj)`` directly, or ``@register(name)`` as a
        decorator. Duplicate names are an error (use a new name; the
        registries are flat namespaces shared by benchmarks and CLIs)."""
        if obj is None:
            return functools.partial(self.register, name)
        if name in self._entries:
            raise ValueError(
                f"duplicate {self.kind} registration {name!r}")
        self._entries[name] = obj
        return obj

    def get(self, name: str) -> T:
        try:
            return self._entries[name]
        except KeyError:
            raise KeyError(
                f"unknown {self.kind} {name!r}; registered: "
                f"{', '.join(self.names())}") from None

    def names(self) -> tuple[str, ...]:
        """Registered names, sorted (the order misses are reported in)."""
        return tuple(sorted(self._entries))

    def items(self):
        return tuple(sorted(self._entries.items()))

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __iter__(self) -> Iterator[str]:
        return iter(self.names())

    def __len__(self) -> int:
        return len(self._entries)

    def __repr__(self) -> str:
        return f"Registry({self.kind!r}: {', '.join(self.names())})"


# ---------------------------------------------------------------------------
# Models: the growing-network rule sets.

@dataclasses.dataclass(frozen=True)
class ModelDef:
    """A registered rule set: published defaults + how runs terminate.

    ``convergence`` drives the host-side predicate
    (``variants.check_convergence``): "topology" = SOAM's all-units-
    disk/patch criterion, "qe" = quantization-error threshold. The
    fused superstep's on-device check follows the compiled rule set
    (``params.model``), which agrees for all built-in models.
    """

    name: str
    params: GSONParams
    convergence: str        # "topology" (SOAM) | "qe" (GNG/GWR)
    description: str = ""


MODELS: Registry[ModelDef] = Registry("model")

MODELS.register("gng", ModelDef(
    "gng", GSONParams(model="gng"), "qe",
    "Growing Neural Gas (Fritzke 95): error-driven periodic insertion"))
MODELS.register("gwr", ModelDef(
    "gwr", GSONParams(model="gwr"), "qe",
    "Grow When Required (Marsland 02): threshold + habituation insertion"))
MODELS.register("soam", ModelDef(
    "soam", GSONParams(model="soam"), "topology",
    "Self-Organizing Adaptive Map (Piastra 12): terminates when every "
    "unit neighborhood is a disk/patch"))


def resolve_model(model: str | GSONParams) -> GSONParams:
    """Name -> published defaults; a GSONParams instance passes through
    (validated against the registry so typos in ``model=`` fail early)."""
    if isinstance(model, GSONParams):
        MODELS.get(model.model)
        return model
    return MODELS.get(model).params


# ---------------------------------------------------------------------------
# Samplers: P(xi). Entries are zero-arg factories returning an engine
# sampler ``f(rng, n) -> (n, dim) f32``; surface samplers hash by name so
# they are stable jit keys for the fused superstep.

SAMPLERS: Registry[Callable[[], Any]] = Registry("sampler")

for _surface in SURFACES:
    SAMPLERS.register(_surface, functools.partial(make_sampler, _surface))


def resolve_sampler(sampler: str | Any):
    """Name, engine sampler, or a ``repro.data.pointclouds`` stream."""
    if isinstance(sampler, str):
        return SAMPLERS.get(sampler)()
    as_sampler = getattr(sampler, "as_sampler", None)
    if as_sampler is not None:        # PointCloudStream and friends
        return as_sampler()
    if not callable(sampler):
        raise TypeError(
            f"sampler must be a registered name, a callable (rng, n) -> "
            f"points, or a point-cloud stream; got {type(sampler)!r}")
    return sampler


# ---------------------------------------------------------------------------
# Backends: the device implementations of the step's two hot phases.
# Entries are zero-arg factories returning a :class:`Backend`; a ``None``
# phase field means "the engine's pure-jnp reference for that phase".


@dataclasses.dataclass(frozen=True)
class Backend:
    """One entry on the BACKENDS axis: per-phase device implementations.

    The paper's profile (Sec. 2.5) has two hot phases — Find Winners
    and Update — and each is independently pluggable:
    ``find_winners`` is a ``FindWinnersFn`` (top-2 nearest-unit
    search), ``update_phase`` an ``UpdatePhaseFn`` (winner lock +
    dense adaptation; see ``repro.core.gson.multi``). The callables
    are jit cache keys for every program that threads them (step /
    superstep / fleet), so factories must return shared instances —
    the registrations below memoize theirs.
    """

    name: str
    find_winners: Any = None      # FindWinnersFn | None (= reference)
    update_phase: Any = None      # UpdatePhaseFn | None (= reference)
    description: str = ""


@functools.lru_cache(maxsize=None)
def _pallas_find_winners():
    # one shared adapter instance: the fused superstep keys its jit cache
    # on the (identity-hashed) find_winners callable
    from repro.kernels.find_winners.ops import make_pallas_find_winners
    return make_pallas_find_winners()


@functools.lru_cache(maxsize=None)
def _pallas_update_phase():
    from repro.kernels.update_phase.ops import make_pallas_update_phase
    return make_pallas_update_phase()


@functools.lru_cache(maxsize=None)
def _sparse_update_phase():
    from repro.kernels.update_phase.sparse import make_sparse_update_phase
    return make_sparse_update_phase()


@functools.lru_cache(maxsize=None)
def _autotuned_update_phase(table_env: str | None):
    # memoized per $REPRO_AUTOTUNE_TABLE value: the resolved adapter is
    # the jit cache key, and an operator override must not silently
    # reuse a closure that already latched a different table
    from repro.gson.autotune import make_autotuned_update_phase
    return make_autotuned_update_phase(table_env)


# The ANN backends hash by VALUE (frozen dataclasses), so equal configs
# are already identical jit keys; the lru_cache just keeps one instance
# per config like the Pallas adapters above.

@functools.lru_cache(maxsize=None)
def _ann_windowed(recall_target: float = 0.95):
    from repro.ann import windowed_find_winners
    return windowed_find_winners(recall_target)


@functools.lru_cache(maxsize=None)
def _ann_grid(recall_target: float = 0.95):
    from repro.ann import grid_find_winners
    return grid_find_winners(recall_target)


@functools.lru_cache(maxsize=None)
def _indexed_find_winners():
    from repro.ann import indexed_find_winners
    return indexed_find_winners()


BACKENDS: Registry[Callable[[], Backend]] = Registry("backend")

BACKENDS.register("reference", lambda: Backend(
    "reference", find_winners_reference, None,
    "pure-jnp scatter reference for both phases"))
BACKENDS.register("pallas", lambda: Backend(
    "pallas", _pallas_find_winners(), None,
    "Pallas MXU Find Winners kernel, reference Update"))
BACKENDS.register("pallas-update", lambda: Backend(
    "pallas-update", find_winners_reference, _pallas_update_phase(),
    "reference Find Winners, Pallas Update-phase kernel suite"))
BACKENDS.register("pallas-full", lambda: Backend(
    "pallas-full", _pallas_find_winners(), _pallas_update_phase(),
    "Pallas kernels for both hot phases"))
BACKENDS.register("pallas-sparse", lambda: Backend(
    "pallas-sparse", find_winners_reference, _sparse_update_phase(),
    "reference Find Winners, winner-neighborhood slab Update: the "
    "Pallas kernels run on just the unit tiles the batch touches"))
BACKENDS.register("pallas-auto", lambda: Backend(
    "pallas-auto", find_winners_reference,
    _autotuned_update_phase(os.environ.get("REPRO_AUTOTUNE_TABLE")),
    "shape-autotuned Update: per-(capacity, m) fastest of reference / "
    "pallas / sparse from the measured selection table "
    "(repro.gson.autotune)"))
BACKENDS.register("ann-windowed", lambda: Backend(
    "ann-windowed", _ann_windowed(), None,
    "approximate Find Winners: windowed top-1 -> exact top-2 rerank, "
    "window count from the birthday recall model at recall 0.95"))
BACKENDS.register("ann-grid", lambda: Backend(
    "ann-grid", _ann_grid(), None,
    "approximate Find Winners: hash-grid quantizer -> stencil "
    "shortlist -> exact rerank, grid rebuilt on the refresh cadence"))
BACKENDS.register("indexed", lambda: Backend(
    "indexed", _indexed_find_winners(), None,
    "the paper's Indexed baseline (Sec. 3.1): hash grid with "
    "per-signal exhaustive fallback"))


def ann_backend(kind: str = "ann-windowed",
                recall_target: float = 0.95) -> Backend:
    """A registered-shape ANN :class:`Backend` at a custom recall
    target (the ``--recall-target`` CLI path). Instances hash by value,
    so equal targets share jit caches with the registered entries."""
    if kind == "ann-windowed":
        fw = _ann_windowed(recall_target)
    elif kind == "ann-grid":
        fw = _ann_grid(recall_target)
    else:
        raise KeyError(
            f"ann_backend kind must be 'ann-windowed' or 'ann-grid', "
            f"got {kind!r}")
    return Backend(
        f"{kind}@r{recall_target:g}", fw, None,
        f"{kind} at recall_target={recall_target:g}")


def resolve_backend(backend: str | Any | None) -> Backend:
    """Name / Backend / bare FindWinnersFn -> a :class:`Backend`.

    A bare callable is accepted for compatibility with the original
    Find-Winners-only axis (e.g. the shard_map searches in
    ``core/gson/distributed.py``) and runs the reference Update phase.
    ``None`` selects the reference for both phases.

    Backends compose with device meshes rather than registering sharded
    variants here: a ``RunSpec.mesh`` (signal axis) wraps whichever
    ``find_winners`` this resolves to in the data-parallel shard_map
    program (``distributed.signal_sharded_find_winners``), so e.g.
    ``backend="pallas"`` + mesh runs the Pallas kernel per shard.
    """
    if backend is None:
        return Backend("reference")
    if isinstance(backend, Backend):
        return backend
    if isinstance(backend, str):
        factory = BACKENDS.get(backend)
        try:
            return factory()
        except PlatformMismatchError:
            raise
        except Exception as e:                  # noqa: BLE001
            # a kernel backend whose construction fails (missing Pallas
            # toolchain, import error in the kernel package) must not
            # kill the run — the reference implements the same contract
            warnings.warn(
                f"backend {backend!r} failed to construct "
                f"({type(e).__name__}: {e}); falling back to the "
                "reference backend", RuntimeWarning, stacklevel=2)
            return Backend("reference", find_winners_reference, None)
    if not callable(backend):
        raise TypeError(
            f"backend must be a registered name, a Backend, or a "
            f"FindWinnersFn; got {type(backend)!r}")
    return Backend("custom", find_winners=backend)


def reference_fallback(find_winners, update_phase,
                       err: BaseException) -> tuple | None:
    """Recovery decision for a backend that failed to *lower* at first
    use (compile/trace-time failure of a kernel program).

    If ``(find_winners, update_phase)`` is already the pure-jnp
    reference pair, the error cannot be a backend problem — returns
    ``None`` and the caller re-raises. Otherwise warns and returns the
    reference pair ``(find_winners_reference, None)`` for the caller to
    swap in and retry; the reference implements the identical phase
    contract, so the run proceeds with the same results, just slower.
    Session and fleet drivers call this around their first step only —
    lowering failures surface on the first call of a compiled program.
    A :class:`~repro.kernels.PlatformMismatchError` (kernels on a
    non-TPU accelerator, an autotune table from another device) is not
    a lowering failure: ``None``, so the caller re-raises it.
    """
    if isinstance(err, PlatformMismatchError):
        return None
    if ((find_winners is None or find_winners is find_winners_reference)
            and update_phase is None):
        return None
    warnings.warn(
        f"backend (find_winners={getattr(find_winners, '__name__', find_winners)!r}, "
        f"update_phase={getattr(update_phase, '__name__', update_phase)!r}) "
        f"failed to lower ({type(err).__name__}: {err}); falling back "
        "to the reference backend for this run", RuntimeWarning,
        stacklevel=3)
    return find_winners_reference, None


# ---------------------------------------------------------------------------
# Variants: registered by repro.gson.variants at import time (the
# strategy classes need this module, so registration lives there).

VARIANTS: Registry[Any] = Registry("variant")
