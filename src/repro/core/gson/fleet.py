"""Fleet core: one device program stepping B independent networks.

The paper widens the data-parallel axis *within* one network (m signals
per iteration). This module widens it one level up: B whole networks
advance through a single compiled program, with every array leaf of
:class:`~repro.core.gson.state.NetworkState` carrying a leading batch
axis. The per-network computation is exactly the masked multi-signal
iterate the fused superstep runs (``multi_signal_step_impl`` with the
device m-schedule), lifted with ``jax.vmap`` — verified bit-identical
per network to the unbatched program for any batch size, which is what
lets ``Session`` be a thin B=1 view over these same functions (see
``repro.gson.variants``) and makes fleet-vs-session bit-identity hold
by construction.

Three jitted entry points (all donate the fleet state, so the B unit
pools update in place):

  * :func:`fleet_init`       — batched init: per-network key schedule,
    seed points, probe sets (mirrors ``Session._start``).
  * :func:`fleet_iterate`    — ONE masked multi-signal iteration for
    every network selected by ``mask`` (the host-dispatched path).
  * :func:`fleet_check`      — the convergence predicate (SOAM topology
    criterion or quantization error), vmapped, for masked networks.
  * :func:`run_fleet_superstep` — up to ``max_steps[i]`` fused
    iterations per network in ONE device call (`lax.while_loop` over
    the two functions above). Converged networks — and networks whose
    per-network budget is spent — freeze in place via a batched select,
    so the batch shape stays static while stragglers keep running:
    the serving engine's wave pattern, on the network axis.

Per-network heterogeneity: PRNG keys, iteration counters, convergence
flags and step budgets are (B,) operands; samplers may differ per
network through :class:`GroupedSampler`. Everything that is a jit
cache key (pool geometry, model params, variant config, backend) must
be shared — that is a *cohort*, grouped by ``repro.gson.fleet``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.gson import metrics
from repro.core.gson.multi import (FindWinnersFn, UpdatePhaseFn,
                                   multi_signal_step_impl,
                                   refresh_topology, soam_converged)
from repro.core.gson.state import (NO_NBR, GSONParams, NetworkState,
                                   init_fleet)
from repro.core.gson.superstep import SuperstepConfig, device_m_schedule


# ---------------------------------------------------------------------------
# FleetState: B networks as one pytree


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("nets", "rng", "iteration", "converged", "qe"),
    meta_fields=(),
)
@dataclass
class FleetState:
    """B stacked networks plus the per-network run carry.

    ``nets`` is a :class:`NetworkState` whose every array leaf has a
    leading ``(B,)`` batch axis; ``rng`` is the per-network *sampling*
    key (distinct from ``nets.rng``, the per-network collision key the
    step threads internally), ``iteration`` the per-network global
    iteration counter that keeps refresh/check cadences continuous
    across calls, and ``converged``/``qe`` the last evaluated
    convergence predicate.
    """

    nets: NetworkState           # every leaf (B, ...)
    rng: jax.Array               # (B,) sampling keys
    iteration: jax.Array         # (B,) i32 global iteration counters
    converged: jax.Array         # (B,) bool
    qe: jax.Array                # (B,) f32 last checked QE (nan = never)

    @property
    def batch(self) -> int:
        return self.nets.w.shape[0]

    def network(self, i: int) -> NetworkState:
        """The i-th network as an unbatched :class:`NetworkState`."""
        return jax.tree.map(lambda x: x[i], self.nets)

    def replace(self, **kw) -> "FleetState":
        return dataclasses.replace(self, **kw)


def stack_states(states) -> NetworkState:
    """Stack unbatched ``NetworkState``s along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def unstack_states(nets: NetworkState) -> list[NetworkState]:
    """Split a stacked ``NetworkState`` back into B unbatched ones."""
    B = nets.w.shape[0]
    return [jax.tree.map(lambda x: x[i], nets) for i in range(B)]


def wrap_single(state: NetworkState, rng: jax.Array,
                iteration, converged=False, qe=float("nan")) -> FleetState:
    """One network as a B=1 fleet (the ``Session`` view)."""
    return FleetState(
        nets=jax.tree.map(lambda x: x[None], state),
        rng=rng[None],
        iteration=jnp.asarray([iteration], jnp.int32),
        converged=jnp.asarray([converged]),
        qe=jnp.asarray([qe], jnp.float32),
    )


def pad_fleet(fstate: FleetState, pad: int) -> FleetState:
    """Append ``pad`` placeholder networks (copies of slot 0, marked
    converged) so the batch divides a device mesh. Placeholders are
    frozen by every driver (mask False / ``max_steps`` 0), so they cost
    one network's worth of memory per device and nothing else; the
    sharded checkpoint format stores only the real networks and re-pads
    on restore (``repro.gson.fleet``)."""
    if pad <= 0:
        return fstate

    def padleaf(x):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            d = jax.random.key_data(x)
            d = jnp.concatenate(
                [d, jnp.broadcast_to(d[:1], (pad,) + d.shape[1:])])
            return jax.random.wrap_key_data(d)
        return jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])

    out = jax.tree.map(padleaf, fstate)
    return out.replace(
        converged=out.converged.at[fstate.batch:].set(True))


def _where(mask: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-network select with broadcasting over trailing axes; handles
    typed PRNG-key leaves (``jnp.where`` rejects extended dtypes)."""
    if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
        da, db = jax.random.key_data(a), jax.random.key_data(b)
        m = mask.reshape(mask.shape + (1,) * (da.ndim - 1))
        return jax.random.wrap_key_data(jnp.where(m, da, db))
    m = mask.reshape(mask.shape + (1,) * (a.ndim - 1))
    return jnp.where(m, a, b)


def select_fleet(mask: jax.Array, new: FleetState,
                 old: FleetState) -> FleetState:
    """``new`` where ``mask`` else ``old``, leaf-wise — the freeze that
    keeps converged/out-of-budget networks in place while the rest of
    the batch advances."""
    return jax.tree.map(lambda a, b: _where(mask, a, b), new, old)


# ---------------------------------------------------------------------------
# Fleet samplers: (rngs (B,), n) -> (B, n, dim)


@dataclass(frozen=True)
class BroadcastSampler:
    """One sampler for every network (homogeneous fleet). Hashable iff
    the base sampler is (``SurfaceSampler``/``NoisySampler`` are)."""

    sampler: Any                 # (rng, n) -> (n, dim), pure JAX

    def __call__(self, rngs: jax.Array, n: int) -> jax.Array:
        return jax.vmap(lambda k: self.sampler(k, n))(rngs)


@dataclass(frozen=True)
class GroupedSampler:
    """Per-network samplers (heterogeneous fleet), one per slot.

    Networks sharing a sampler are vmapped together (per-slice values
    do not depend on the vmap batch size, so a network's signal stream
    is the same whether its group has 1 member or B) and scattered back
    to their slots.
    """

    samplers: tuple              # length B, each (rng, n) -> (n, dim)

    def __call__(self, rngs: jax.Array, n: int) -> jax.Array:
        groups: dict = {}
        for i, s in enumerate(self.samplers):
            groups.setdefault(s, []).append(i)
        out = None
        for s, idxs in groups.items():
            ix = jnp.asarray(idxs, jnp.int32)
            sub = jax.vmap(lambda k, s=s: s(k, n))(rngs[ix])
            if out is None:
                out = jnp.zeros((len(self.samplers),) + sub.shape[1:],
                                sub.dtype)
            out = out.at[ix].set(sub)
        return out


def as_fleet_sampler(samplers) -> Any:
    """Per-network engine samplers -> one hashable fleet sampler."""
    samplers = tuple(samplers)
    if all(s == samplers[0] for s in samplers[1:]):
        return BroadcastSampler(samplers[0])
    return GroupedSampler(samplers)


# ---------------------------------------------------------------------------
# Device programs


@partial(jax.jit, static_argnames=("sampler", "capacity", "dim", "max_deg",
                                   "n_probe", "init_threshold", "n_seed"))
def fleet_init(rng0: jax.Array, *, sampler, capacity: int, dim: int,
               max_deg: int, n_probe: int, init_threshold: float,
               n_seed: int = 2):
    """(B,) initial keys -> fresh ``(FleetState, probes)``.

    Mirrors ``Session._start``'s key schedule per network — ``rng0[i]``
    splits into (sampling key, init key, probe key, seed key) — so a
    fleet network and a same-seed ``Session`` start bit-identically.
    """
    ks = jax.vmap(lambda k: jax.random.split(k, 4))(rng0)      # (B, 4)
    rng, k_init, k_probe, k_seed = (ks[:, 0], ks[:, 1], ks[:, 2],
                                    ks[:, 3])
    seed_pts = sampler(k_seed, n_seed)                         # (B, s, dim)
    nets = init_fleet(k_init, seed_points=seed_pts, capacity=capacity,
                      dim=dim, max_deg=max_deg,
                      init_threshold=init_threshold)
    probes = sampler(k_probe, n_probe)                         # (B, P, dim)
    B = rng0.shape[0]
    fstate = FleetState(
        nets=nets, rng=rng,
        iteration=jnp.zeros((B,), jnp.int32),
        converged=jnp.zeros((B,), bool),
        qe=jnp.full((B,), jnp.nan, jnp.float32))
    return fstate, probes


def fleet_iterate_impl(
    fstate: FleetState,
    mask: jax.Array,
    *,
    sampler,
    params: GSONParams,
    cfg: SuperstepConfig,
    find_winners: FindWinnersFn | None = None,
    update_phase: UpdatePhaseFn | None = None,
    fw_aux=None,
) -> FleetState:
    """One masked multi-signal iteration for every network in ``mask``.

    Per network: split the sampling key, draw a static
    ``(max_parallel, dim)`` signal buffer, run the masked multi-signal
    step with the device m-schedule, and (SOAM) refresh the topology
    ladder on the per-network cadence. Networks outside ``mask`` are
    frozen (state, key and counter unchanged). The phases carry the
    named scopes ``gson.sample`` and ``gson.refresh`` (the step's own
    are in ``multi_signal_step_impl``); :func:`fleet_check_impl` runs
    under ``gson.check``.

    ``fw_aux``: optional batched search structure for stateful Find
    Winners backends (every leaf (B, ...)), carried by
    :func:`run_fleet_superstep_impl`. ``None`` with a stateful backend
    rebuilds per call — correct everywhere (this is what the
    host-dispatched drivers do), just unamortized.
    """
    with jax.named_scope("gson.sample"):
        keys = jax.vmap(jax.random.split)(fstate.rng)          # (B, 2)
        rng, k_sig = keys[:, 0], keys[:, 1]
        signals = sampler(k_sig, cfg.max_parallel)             # (B, m, dim)
    stateful = getattr(find_winners, "stateful", False)
    if stateful and fw_aux is None:
        fw_aux = jax.vmap(find_winners.build)(fstate.nets.w,
                                              fstate.nets.active)

    def one(net, sig, aux):
        m_t = device_m_schedule(net.n_active, cfg)
        smask = jnp.arange(cfg.max_parallel, dtype=jnp.int32) < m_t
        return multi_signal_step_impl(
            net, sig, params, refresh_states=False,
            find_winners=find_winners, signal_mask=smask,
            update_phase=update_phase, fw_aux=aux)

    if stateful:
        nets = jax.vmap(one)(fstate.nets, signals, fw_aux)
    else:
        nets = jax.vmap(lambda n, s: one(n, s, None))(fstate.nets, signals)

    if params.model == "soam":
        # per-network cadence on the pre-increment global counter, like
        # the superstep; the any() gate skips the (vmapped) refresh
        # entirely on iterations where no live network is due
        with jax.named_scope("gson.refresh"):
            due = mask & (fstate.iteration % cfg.refresh_every == 0)

            def do_refresh(n):
                ref = jax.vmap(lambda s: refresh_topology(s, params))(n)
                return jax.tree.map(lambda a, b: _where(due, a, b), ref, n)

            nets = jax.lax.cond(jnp.any(due), do_refresh, lambda n: n,
                                nets)

    new = fstate.replace(nets=nets, rng=rng,
                         iteration=fstate.iteration + 1)
    return select_fleet(mask, new, fstate)


def fleet_check_impl(
    fstate: FleetState,
    probes: jax.Array,
    mask: jax.Array,
    *,
    params: GSONParams,
    cfg: SuperstepConfig,
) -> FleetState:
    """Evaluate the convergence predicate for every network in ``mask``.

    SOAM: recompute the state ladder (the checked network keeps the
    fresh ladder, as in ``superstep._convergence_check``) and apply the
    all-disk/patch criterion; GNG/GWR: quantization error vs the
    per-network probe set against ``cfg.qe_threshold``.
    """

    def one(net, pr):
        if params.model == "soam":
            net = refresh_topology(net, params)
            return net, soam_converged(net), \
                metrics.quantization_error(net, pr)
        done, qe = metrics.qe_convergence(net, pr, cfg.qe_threshold)
        return net, done, qe

    with jax.named_scope("gson.check"):
        nets, done, qe = jax.vmap(one)(fstate.nets, probes)
        new = fstate.replace(nets=nets, converged=done,
                             qe=qe.astype(jnp.float32))
        return select_fleet(mask, new, fstate)


def run_fleet_superstep_impl(
    fstate: FleetState,
    probes: jax.Array,
    max_steps: jax.Array,
    *,
    sampler,
    params: GSONParams,
    cfg: SuperstepConfig,
    find_winners: FindWinnersFn | None = None,
    update_phase: UpdatePhaseFn | None = None,
):
    """Up to ``max_steps[i]`` fused iterations per network, one call.

    The fleet analogue of ``superstep.run_superstep``: every loop turn
    advances all still-running networks by one masked iteration and
    evaluates the cadenced convergence check; a network freezes as soon
    as it converges or exhausts its own ``max_steps`` budget, while the
    loop keeps going until the whole batch is done. Returns
    ``(fstate, steps)`` with ``steps[i]`` the iterations actually
    executed for network i in THIS call.

    ``cfg.early_exit=True`` lowers to ``lax.while_loop`` and stops as
    soon as every network is frozen; ``early_exit=False`` lowers to a
    fixed ``cfg.length``-turn ``lax.scan`` (turns after the whole batch
    froze are no-ops). Both produce bit-identical final states.

    A stateful Find Winners backend (``repro.ann`` grid) gets its
    batched search structure built once at entry and rebuilt on the
    ``cfg.refresh_every`` cadence for still-running networks — the
    fleet analogue of the fused superstep's aux carry.
    """
    steps0 = jnp.zeros((fstate.iteration.shape[0],), jnp.int32)
    stateful = getattr(find_winners, "stateful", False)
    aux0 = (jax.vmap(find_winners.build)(fstate.nets.w,
                                         fstate.nets.active)
            if stateful else None)

    def cond(carry):
        fs, steps, _ = carry
        return jnp.any(~fs.converged & (steps < max_steps))

    def body(carry):
        fs, steps, aux = carry
        running = ~fs.converged & (steps < max_steps)
        fs = fleet_iterate_impl(fs, running, sampler=sampler,
                                params=params, cfg=cfg,
                                find_winners=find_winners,
                                update_phase=update_phase, fw_aux=aux)
        steps = jnp.where(running, steps + 1, steps)
        # cadence on the post-increment global counter (continuous
        # across superstep calls), like superstep._body
        check = running & (fs.iteration % cfg.check_every == 0)
        fs = jax.lax.cond(
            jnp.any(check),
            lambda a: fleet_check_impl(a[0], probes, a[1],
                                       params=params, cfg=cfg),
            lambda a: a[0],
            (fs, check))
        if stateful:
            due = running & (fs.iteration % cfg.refresh_every == 0)

            def rebuild(a):
                fresh = jax.vmap(find_winners.build)(fs.nets.w,
                                                     fs.nets.active)
                return jax.tree.map(
                    lambda x, y: _where(due, x, y), fresh, a)

            aux = jax.lax.cond(jnp.any(due), rebuild, lambda a: a, aux)
        return fs, steps, aux

    if cfg.early_exit:
        fs, steps, _ = jax.lax.while_loop(cond, body,
                                          (fstate, steps0, aux0))
        return fs, steps

    def scan_body(carry, _):
        return jax.lax.cond(cond(carry), body, lambda c: c, carry), None

    (fs, steps, _), _ = jax.lax.scan(scan_body, (fstate, steps0, aux0),
                                     None, length=cfg.length)
    return fs, steps


def fleet_health_impl(fstate: FleetState) -> jax.Array:
    """(B,) bool — True where a network's state passes the cheap
    on-device health screen.

    The screen catches the two corruption classes a poisoned signal or a
    bad kernel produces: **non-finite state** (weights / error / firing /
    threshold of active units) and **topology invariant violations**
    (neighbor ids out of range or self-referential, edges pointing at
    inactive units, ``n_active`` disagreeing with the active mask).
    O(B · capacity · max_deg) of elementwise reductions — orders of
    magnitude below one multi-signal iteration — so drivers can afford
    to run it every superstep. Read-only: quarantine itself is the
    caller masking the network out of subsequent steps (the same freeze
    path converged networks use).
    """

    def one(net: NetworkState) -> jax.Array:
        act = net.active
        col = act[:, None]
        finite = (
            jnp.all(jnp.isfinite(jnp.where(col, net.w, 0.0)))
            & jnp.all(jnp.isfinite(jnp.where(act, net.error, 0.0)))
            & jnp.all(jnp.isfinite(jnp.where(act, net.firing, 0.0)))
            & jnp.all(jnp.isfinite(jnp.where(act, net.threshold, 0.0)))
            & jnp.all(jnp.isfinite(jnp.where(col, net.age, 0.0))))
        cap = net.nbr.shape[0]
        ids = jnp.arange(cap, dtype=net.nbr.dtype)[:, None]
        has = net.nbr >= 0
        topo = (
            jnp.all((net.nbr >= NO_NBR) & (net.nbr < cap))
            & jnp.all(net.nbr != ids)
            & jnp.all(jnp.where(has,
                                act[jnp.clip(net.nbr, 0)] & col,
                                True))
            & (net.n_active == jnp.sum(act.astype(jnp.int32))))
        return finite & topo

    return jax.vmap(one)(fstate.nets)


# read-only screen: no donation (the caller keeps stepping the state)
fleet_health = jax.jit(fleet_health_impl)


# Donated fleet state: the B unit pools are by far the largest buffers
# and every caller rebinds (``fstate = fleet_iterate(fstate, ...)``),
# so XLA updates them in place across calls.
fleet_iterate = jax.jit(
    fleet_iterate_impl,
    static_argnames=("sampler", "params", "cfg", "find_winners",
                     "update_phase"),
    donate_argnames=("fstate",))

fleet_check = jax.jit(
    fleet_check_impl,
    static_argnames=("params", "cfg"),
    donate_argnames=("fstate",))

run_fleet_superstep = jax.jit(
    run_fleet_superstep_impl,
    static_argnames=("sampler", "params", "cfg", "find_winners",
                     "update_phase"),
    donate_argnames=("fstate",))
