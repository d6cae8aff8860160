"""RunSpec: one declarative description of a GSON experiment.

The paper's experiments are points in a (variant, model, surface)
grid with shared hyper-parameters (Sec. 3.1); a RunSpec is one such
point plus the execution knobs the paper fixes implicitly (pool
geometry, run limits, backend).

A spec names (or carries) one entry per registry axis — variant, model,
sampler, backend (the per-phase device kernels: Find Winners + dense
Update, see ``repro.gson.registry.Backend``) — plus the pool geometry
and run limits shared by every variant. ``resolve(spec)`` turns it into
the concrete strategy + Runtime the session drives; everything
downstream (Session, GSONEngine shim, serving, benchmarks) goes through
this one function.

Distributed execution is declared the same way: a :class:`MeshSpec`
names a device mesh, and ``RunSpec.mesh`` (signal-axis sharding of one
network, the paper's data partitioning) or ``FleetSpec.mesh``
(network-axis sharding of a cohort, see ``repro.gson.fleet``) places
the run on it — no call-site changes anywhere downstream.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache
from typing import Any

from repro.core.gson.state import GSONParams
from repro.gson.registry import (VARIANTS, resolve_backend, resolve_model,
                                 resolve_sampler)
from repro.gson.variants import Runtime, VariantStrategy


@dataclass(frozen=True)
class MeshSpec:
    """A declarative device mesh: which axis to shard, over how many
    devices.

    ``axis`` picks the parallelization strategy (paper Sec. 2.5
    taxonomy, see ``repro.core.gson.distributed``):

    * ``"network"`` — shard a *fleet*'s leading B axis: each device
      owns ``B/ndev`` whole networks, zero per-iteration collectives.
      Goes on :class:`~repro.gson.fleet.FleetSpec`.
    * ``"signal"`` — shard the signal batch of ONE network's multi-
      signal step (the paper's data partitioning): each device finds
      winners for its local signals, the Update phase runs as a
      replicated deterministic state machine. Goes on
      :class:`RunSpec`; composes with any Find Winners backend.

    ``devices=None`` uses every visible device. The spec is a frozen,
    hashable value — it participates in cohort jit keys — and the
    concrete ``jax.sharding.Mesh`` is only built when a session starts
    (:meth:`build`), never at import time.
    """

    axis: str = "network"           # "network" | "signal"
    devices: int | None = None      # None = all visible devices
    axis_name: str = "gson"         # mesh axis label

    def __post_init__(self):
        if self.axis not in ("network", "signal"):
            raise ValueError(
                f"MeshSpec.axis must be 'network' (shard a fleet's B "
                f"axis) or 'signal' (shard one network's signal "
                f"batch); got {self.axis!r}")
        if self.devices is not None and self.devices < 1:
            raise ValueError(
                f"MeshSpec.devices must be >= 1 or None (= all "
                f"visible), got {self.devices}")

    def ndev(self) -> int:
        import jax
        return (self.devices if self.devices is not None
                else len(jax.devices()))

    def build(self):
        """The concrete single-axis ``jax.sharding.Mesh`` (memoized, so
        equal specs share one mesh — and downstream one jit cache)."""
        return _build_mesh(self)


@lru_cache(maxsize=None)
def _build_mesh(ms: MeshSpec):
    import jax
    import numpy as np
    devices = jax.devices()
    n = ms.ndev()
    if n > len(devices):
        raise RuntimeError(
            f"MeshSpec wants {n} devices, found {len(devices)}; on a "
            "host-only platform run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return jax.sharding.Mesh(np.asarray(devices[:n]), (ms.axis_name,))


@dataclass(frozen=True)
class RunSpec:
    """Everything needed to reproduce one run (modulo the PRNG seed).

    Axis fields accept a registered name or a concrete object; the typed
    per-variant knobs live in ``variant_config`` (``None`` means the
    variant's defaults). ``mesh`` (optional) shards the signal axis of
    the multi-signal step across a device mesh — see :class:`MeshSpec`.

    ``backend`` selects the hot-phase kernels by name (see
    ``docs/api.md``); ``backend="pallas-auto"`` resolves to ONE shared
    shape-autotuned Update adapter, so cohort/jit cache keys — which
    hash the resolved callables, here and in fleet/mesh cohorts — are
    exactly as stable as for any single-kernel backend while each
    compiled ``(capacity, m)`` shape runs whatever the measured
    selection table says is fastest (``repro.gson.autotune``).
    """

    variant: str | Any = "multi"
    model: str | GSONParams = "soam"
    sampler: str | Any = "sphere"
    backend: str | Any | None = "reference"
    variant_config: Any = None
    mesh: MeshSpec | None = None

    # pool geometry
    capacity: int = 4096
    dim: int = 3
    max_deg: int = 16

    # run limits + convergence (shared by all variants)
    max_iterations: int = 100_000
    max_signals: int = 50_000_000
    check_every: int = 10         # iterations between convergence checks
    qe_threshold: float = 1e-3    # GNG/GWR convergence
    n_probe: int = 2048

    def replace(self, **kw) -> "RunSpec":
        return dataclasses.replace(self, **kw)


def resolve_variant(variant: str | Any) -> VariantStrategy:
    if isinstance(variant, str):
        variant = VARIANTS.get(variant)
    if isinstance(variant, type):
        # classes registered via the @VARIANTS.register decorator (or
        # passed directly) are instantiated here: strategies are
        # stateless, so a fresh instance is equivalent to a singleton
        variant = variant()
    if not isinstance(variant, VariantStrategy):
        raise TypeError(
            f"variant must be a registered name or a VariantStrategy "
            f"(prepare/step/convergence hooks); got {type(variant)!r}")
    return variant


def resolve(spec: RunSpec) -> tuple[VariantStrategy, Runtime]:
    """Assemble the concrete strategy + runtime context from the spec."""
    strategy = resolve_variant(spec.variant)
    vcfg = spec.variant_config
    if vcfg is None:
        vcfg = strategy.config_cls()
    elif not isinstance(vcfg, strategy.config_cls):
        raise TypeError(
            f"variant {strategy.name!r} takes a "
            f"{strategy.config_cls.__name__}, got {type(vcfg).__name__}")
    be = resolve_backend(spec.backend)
    find_winners, update_phase = be.find_winners, be.update_phase
    if spec.mesh is not None:
        if spec.mesh.axis != "signal":
            raise ValueError(
                "RunSpec.mesh shards the signal axis of one network "
                "(MeshSpec(axis='signal')); to shard a fleet's network "
                "axis put the MeshSpec on the FleetSpec instead")
        # memoized per (mesh, axes, backend): ONE sharded adapter
        # instance, so every program that keys its jit cache on the
        # find_winners callable compiles once
        from repro.core.gson.distributed import (
            replicated_update_phase, signal_sharded_find_winners)
        find_winners = signal_sharded_find_winners(
            spec.mesh.build(), (spec.mesh.axis_name,),
            inner=be.find_winners)
        if update_phase is not None:
            # a Pallas Update kernel outside a shard_map cannot be
            # partitioned across the mesh's devices
            update_phase = replicated_update_phase(spec.mesh.build(),
                                                   update_phase)
    rt = Runtime(
        spec=spec,
        params=resolve_model(spec.model),
        vcfg=vcfg,
        sampler=resolve_sampler(spec.sampler),
        find_winners=find_winners,
        update_phase=update_phase,
    )
    return strategy, rt
