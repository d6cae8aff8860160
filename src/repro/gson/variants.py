"""Variant strategies: the pluggable parallelization axis.

The paper's contribution is a *variant* of the growing-network loop —
same rule set, different execution schedule (Sec. 2.2: the multi-signal
iteration; Sec. 3.1: the sequential and indexed baselines it is
measured against; the fused superstep and fleet execution are this
repo's beyond-paper extensions). Each variant is a strategy
object with three hooks:

  prepare(rt)                  — resolve derived config once per run
                                 (e.g. the fused superstep's buffer size)
  step(rt, state, rng, it, n)  — advance up to ``n`` iterations, timing
                                 the paper's phases through
                                 ``repro.utils.timing.span``; returns a
                                 StepResult
  convergence(rt, state)       — the termination predicate (shared
                                 default: SOAM topology criterion or
                                 quantization error)

and a typed config dataclass (``config_cls``) holding only the knobs
that variant actually reads — no more flat 18-field config mixing the
single-signal chunk size with the fused superstep length.

Strategies are stateless singletons registered in ``VARIANTS``; per-run
state lives in the :class:`Runtime` the session owns.

The multi-signal strategies ("multi", "multi-fused") execute through
the **fleet core** (``repro.core.gson.fleet``): their ``step`` is the
B=1 view of the same vmapped device program that
``repro.gson.fleet.FleetSession`` drives for B networks at once, so a
session run is bit-identical per network to a fleet run with the same
seeds. A fleet-capable strategy declares ``fleet_capable = True``, a
``fleet_mode`` ("host" = one device call per iteration, "device" =
whole supersteps on device) and a ``fleet_cfg(spec, params, vcfg)``
resolver for the static program config. The sequential reference
variants ("single", "indexed") remain host loops by design.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.ann import GridFindWinners, indexed_scan
from repro.core.gson import fleet as fleet_core
from repro.core.gson import metrics
from repro.core.gson.multi import refresh_topology, soam_converged
from repro.core.gson.single import single_signal_scan
from repro.core.gson.state import GSONParams
from repro.core.gson.superstep import SuperstepConfig, next_pow2
from repro.gson.registry import MODELS, VARIANTS
from repro.utils.timing import span

DEFAULT_BBOX = ((-3.0, -3.0, -3.0), (3.0, 3.0, 3.0))


# ---------------------------------------------------------------------------
# Typed per-variant configs (all frozen; nested configs use
# default_factory so instances are never shared across spec objects).

@dataclass(frozen=True)
class MultiConfig:
    """Host-dispatched multi-signal loop (paper Sec. 2.2/2.5)."""

    fixed_m: int | None = None    # override the paper's m-schedule
    min_m: int = 4                # floor of the m-schedule
    refresh_every: int = 5        # SOAM topo refresh cadence (iterations)


@dataclass(frozen=True)
class FusedConfig:
    """On-device fused superstep (S iterations per device call)."""

    superstep: SuperstepConfig = field(default_factory=SuperstepConfig)
    fixed_m: int | None = None
    min_m: int = 4
    refresh_every: int = 5


@dataclass(frozen=True)
class SingleConfig:
    """Sequential single-signal reference (paper's baseline)."""

    chunk: int = 256              # signals per device call
    refresh_every: int = 200      # per-signal SOAM refresh cadence


@dataclass(frozen=True)
class IndexedConfig:
    """Single-signal with the hash-grid Find Winners index (Sec. 3.1)."""

    chunk: int = 256
    refresh_every: int = 200
    grid_per_axis: int = 24
    per_cell_cap: int = 24
    rebuild_every: int = 64
    bbox: tuple = DEFAULT_BBOX    # ((min,)*dim, (max,)*dim)


# ---------------------------------------------------------------------------

@dataclass
class Runtime:
    """Resolved per-run context the session hands to its strategy."""

    spec: Any                     # the RunSpec (kept duck-typed: no cycle)
    params: GSONParams
    vcfg: Any                     # the variant's typed config
    sampler: Any                  # f(rng, n) -> (n, dim) f32, pure JAX
    find_winners: Any             # FindWinnersFn | None
    update_phase: Any = None      # UpdatePhaseFn | None
    probes: jax.Array | None = None
    scratch: dict = field(default_factory=dict)   # strategy-owned
    session_id: int = 0           # the ``session`` id of its trace spans

    @property
    def check_every(self) -> int:
        return self.spec.check_every

    @property
    def qe_threshold(self) -> float:
        return self.spec.qe_threshold


@dataclass
class StepResult:
    """Outcome of one strategy step (1 iteration, or a fused superstep)."""

    state: Any
    rng: jax.Array
    iterations: int               # iterations actually executed
    checked: bool                 # convergence predicate evaluated?
    done: bool
    qe: float
    timings: dict = field(default_factory=dict)   # phase -> seconds


@runtime_checkable
class VariantStrategy(Protocol):
    name: str
    config_cls: type

    def prepare(self, rt: Runtime) -> None: ...

    def step(self, rt: Runtime, state, rng, it: int,
             max_iters: int) -> StepResult: ...

    def convergence(self, rt: Runtime, state) -> tuple[bool, float, Any]: ...


def check_convergence(rt: Runtime, state):
    """Shared termination predicate, selected by the model's registered
    ``ModelDef.convergence``: "topology" runs SOAM's criterion on a
    fresh state ladder, "qe" compares quantization error vs the probe
    set. (The fused superstep's on-device check follows the compiled
    rule set instead — see ``superstep._convergence_check``.)"""
    p = rt.params
    mode = (MODELS.get(p.model).convergence if p.model in MODELS
            else "qe")
    if mode == "topology":
        state = refresh_topology(state, p)
        ok = bool(soam_converged(state))
        qe = float(metrics.quantization_error(state, rt.probes))
        return ok, qe, state
    done, qe = metrics.qe_convergence(state, rt.probes, rt.qe_threshold)
    return bool(done), float(qe), state


class _HostVariant:
    """Shared host-dispatched loop body: sample, update, cadenced check.

    Subclasses choose the signal count per iteration (``_m``) and the
    update call (``_update``)."""

    def prepare(self, rt: Runtime) -> None:
        pass

    def convergence(self, rt: Runtime, state):
        return check_convergence(rt, state)

    def _m(self, rt: Runtime, state) -> int:
        raise NotImplementedError

    def _update(self, rt: Runtime, state, signals, it: int):
        raise NotImplementedError

    def step(self, rt: Runtime, state, rng, it: int,
             max_iters: int) -> StepResult:
        timings = {}
        with span("sample", timings, session=rt.session_id):
            rng, k_sig = jax.random.split(rng)
            signals = rt.sampler(k_sig, self._m(rt, state))
            signals.block_until_ready()

        with span("step", timings, session=rt.session_id):
            state = self._update(rt, state, signals, it)
            state.w.block_until_ready()

        it += 1
        checked = it % rt.check_every == 0
        done, qe = False, float("nan")
        if checked:
            with span("convergence", timings, session=rt.session_id):
                done, qe, state = self.convergence(rt, state)
        return StepResult(state, rng, 1, checked, done, qe, timings)


class _FleetBacked:
    """Shared base of the strategies that execute through the fleet
    core (``repro.core.gson.fleet``): ONE step function, used at B=1 by
    the session and at B=N by ``repro.gson.fleet.FleetSession`` — which
    is what makes a fleet network bit-identical to a same-seed session.

    ``fleet_mode`` selects the dispatch granularity the fleet driver
    uses: "host" re-crosses the host<->device boundary every iteration
    (the paper's multi-signal loop), "device" runs whole supersteps on
    device (``run_fleet_superstep``).
    """

    fleet_capable = True
    fleet_mode = "host"

    def fleet_cfg(self, spec, params: GSONParams,
                  vcfg) -> SuperstepConfig:
        """Resolve the static fleet-program config (a jit cache key)
        from the spec-level knobs. Must agree between session (B=1)
        and fleet (B=N) callers — both call exactly this."""
        raise NotImplementedError

    def prepare(self, rt: Runtime) -> None:
        rt.scratch["fleet_cfg"] = self.fleet_cfg(rt.spec, rt.params,
                                                 rt.vcfg)
        rt.scratch["fleet_sampler"] = fleet_core.BroadcastSampler(
            rt.sampler)

    def convergence(self, rt: Runtime, state):
        return check_convergence(rt, state)


class MultiVariant(_FleetBacked):
    """Host-dispatched multi-signal loop on the fleet core (B=1).

    Each session iteration is one ``fleet_iterate`` device call: the
    signal buffer has the static ``max_parallel`` row count and the
    device m-schedule masks the first ``m_t = next_pow2(n_active)``
    rows — the same program the fused superstep (and the fleet) runs,
    dispatched one iteration at a time.
    """

    name = "multi"
    config_cls = MultiConfig

    def fleet_cfg(self, spec, params, vcfg) -> SuperstepConfig:
        if vcfg.fixed_m is not None:
            # exact buffer: the device schedule always yields
            # min(fixed_m, cap), so no row is ever masked — same
            # per-iteration compute as the legacy exact-m sampling
            buf = min(params.max_parallel, vcfg.fixed_m)
        else:
            buf = min(params.max_parallel, next_pow2(spec.capacity))
        return SuperstepConfig(
            length=1, max_parallel=buf, min_m=vcfg.min_m,
            fixed_m=vcfg.fixed_m, refresh_every=vcfg.refresh_every,
            check_every=spec.check_every,
            qe_threshold=spec.qe_threshold)

    def step(self, rt: Runtime, state, rng, it: int,
             max_iters: int) -> StepResult:
        cfg = rt.scratch["fleet_cfg"]
        one = jnp.ones((1,), bool)
        timings = {}
        # sampling runs inside the device program now; the whole
        # iteration is accounted under "step" like the fused variant
        with span("step", timings, session=rt.session_id):
            fs = fleet_core.wrap_single(state, rng, it)
            fs = fleet_core.fleet_iterate(
                fs, one, sampler=rt.scratch["fleet_sampler"],
                params=rt.params, cfg=cfg, find_winners=rt.find_winners,
                update_phase=rt.update_phase)
            it += 1
            checked = it % rt.check_every == 0
            done, qe = False, float("nan")
            if checked:
                fs = fleet_core.fleet_check(fs, rt.probes[None], one,
                                            params=rt.params, cfg=cfg)
                with span("readback", session=rt.session_id):
                    done = bool(fs.converged[0])
                with span("readback", session=rt.session_id):
                    qe = float(fs.qe[0])
            state, rng = fs.network(0), fs.rng[0]
            state.w.block_until_ready()
        return StepResult(state, rng, 1, checked, done, qe, timings)


class SingleVariant(_HostVariant):
    name = "single"
    config_cls = SingleConfig

    def _m(self, rt: Runtime, state) -> int:
        return rt.vcfg.chunk

    def _update(self, rt: Runtime, state, signals, it: int):
        return single_signal_scan(state, signals, rt.params,
                                  refresh_every=rt.vcfg.refresh_every,
                                  find_winners=rt.find_winners)


class IndexedVariant(_HostVariant):
    """The paper's Indexed baseline on the ``repro.ann`` grid backend:
    same hash-grid quantizer the ``indexed``/``ann-grid`` BACKENDS
    entries use, in its exhaustive-fallback discipline, with the aux
    rebuilt in the scan carry every ``rebuild_every`` signals."""

    name = "indexed"
    config_cls = IndexedConfig

    def prepare(self, rt: Runtime) -> None:
        cfg = rt.vcfg
        rt.scratch["grid_fw"] = GridFindWinners(
            grid_per_axis=cfg.grid_per_axis,
            per_cell_cap=cfg.per_cell_cap,
            n_anchors=0, bbox=cfg.bbox, fallback="exact")

    def _m(self, rt: Runtime, state) -> int:
        return rt.vcfg.chunk

    def _update(self, rt: Runtime, state, signals, it: int):
        cfg = rt.vcfg
        return indexed_scan(
            state, signals, rt.params, rt.scratch["grid_fw"],
            rebuild_every=cfg.rebuild_every,
            refresh_every=cfg.refresh_every)


class FusedVariant(_FleetBacked):
    """Whole iterate-sample-converge loop on device (fleet superstep)."""

    name = "multi-fused"
    fleet_mode = "device"
    config_cls = FusedConfig

    def fleet_cfg(self, spec, params, vcfg) -> SuperstepConfig:
        # spec-level convergence/refresh knobs are the single source of
        # truth; cfg.superstep contributes only the fused-loop shape
        ss = vcfg.superstep.resolve(spec.capacity, params)
        return dataclasses.replace(
            ss,
            refresh_every=vcfg.refresh_every,
            check_every=spec.check_every,
            qe_threshold=spec.qe_threshold,
            min_m=vcfg.min_m,
            fixed_m=(vcfg.fixed_m if vcfg.fixed_m is not None
                     else ss.fixed_m))

    def step(self, rt: Runtime, state, rng, it: int,
             max_iters: int) -> StepResult:
        ss = rt.scratch["fleet_cfg"]
        ids = {"session": rt.session_id}
        timings = {}
        # the fused variant cannot split phases on the host (that is the
        # point): its whole superstep is accounted under "step", and the
        # phases are the device program's named scopes in the trace
        with span("step", timings, **ids):
            with span("step.prepare", **ids):
                # bound by BOTH remaining budgets: iterations, and
                # signals (worst case one iteration consumes
                # max_parallel signals) — overshoot is at most one
                # iteration's m, like the host loop. The bound is a
                # dynamic operand, so partial-length supersteps share
                # one jit signature instead of retracing per length.
                with span("readback", **ids):
                    signals = int(state.signal_count)
                sig_left = rt.spec.max_signals - signals
                length = max(1, min(ss.length, max_iters,
                                    -(-sig_left // ss.max_parallel)))
                fs = fleet_core.wrap_single(state, rng, it)
            with span("step.dispatch", **ids):
                fs, steps = fleet_core.run_fleet_superstep(
                    fs, rt.probes[None], jnp.asarray([length], jnp.int32),
                    sampler=rt.scratch["fleet_sampler"], params=rt.params,
                    cfg=ss, find_winners=rt.find_winners,
                    update_phase=rt.update_phase)
            with span("step.unwrap", **ids):
                state, rng = fs.network(0), fs.rng[0]
            with span("step.wait", **ids):
                state.w.block_until_ready()
        with span("readback", **ids):
            n = int(steps[0])
        with span("readback", **ids):
            done = bool(fs.converged[0])
        with span("readback", **ids):
            qe = float(fs.qe[0])
        return StepResult(state, rng, n, True, done, qe, timings)


# stateless singletons: one instance per registered name
VARIANTS.register("single", SingleVariant())
VARIANTS.register("indexed", IndexedVariant())
VARIANTS.register("multi", MultiVariant())
VARIANTS.register("multi-fused", FusedVariant())
