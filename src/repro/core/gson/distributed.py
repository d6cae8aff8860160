"""Distributed Find Winners / full steps / fleets for the production mesh.

Three parallelization strategies. The first two follow the taxonomy the
paper builds on (Lawrence et al. 99) for ONE network:

* **data partitioning** (the paper's choice, Sec. 1/2.5): the m signals
  are sharded across devices, the network state is replicated. Each
  device finds winners for its local signals, then the *whole* signal
  batch + winner ids are all-gathered and the Update phase runs as a
  replicated deterministic state machine — every device applies the
  identical update, so no state divergence and no further collectives.
  Collective volume per iteration: O(m·(dim+2)) — independent of N.
  Parallelism is bounded by m only (the paper's scalability argument).

* **network partitioning** (the literature-standard baseline the paper
  argues against, the Parallel-SOM lineage of Weigang 98): the unit
  pool is sharded, every device sees all signals, local top-2s are
  merged with an all-gather tournament. Collective volume:
  O(m · shards) and the map-reduce parallelism is bounded by N — both
  scale poorly, which the roofline table quantifies.

The third widens the paper's argument one level up, to **fleets**
(:mod:`repro.core.gson.fleet`):

* **fleet sharding** (:func:`make_sharded_fleet_programs`): the leading
  ``(B,)`` network axis of a :class:`~repro.core.gson.fleet.FleetState`
  is sharded across devices, so a cohort of B networks runs as ONE
  shard_map program with each device owning ``B/ndev`` whole networks.
  Networks are independent, so the program has **zero per-iteration
  collectives** — each device's ``lax.while_loop`` even exits early on
  its own schedule. Per-network values are exactly the vmapped fleet
  core's (verified bitwise on the reference backend), which is what
  lets the public API pin sharded-fleet == unsharded-fleet == B
  Sessions (``tests/test_fleet_mesh.py``).

All are pure shard_map programs: they lower/compile on the 2x16x16
multi-pod mesh in launch/dryrun.py. The public API reaches them
through ``repro.gson.MeshSpec`` (see ``repro.gson.spec``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.gson.fleet import (FleetState, fleet_check_impl,
                                   fleet_health_impl, fleet_iterate_impl,
                                   run_fleet_superstep_impl)
from repro.core.gson.multi import (find_winners_reference,
                                   multi_signal_step_impl)
from repro.core.gson.state import GSONParams, NetworkState


def data_parallel_find_winners(mesh: Mesh, signal_axes=("pod", "data"),
                               inner=None):
    """Find Winners with signals sharded, units replicated.

    Returns fw(signals, w, active) -> (wid, sid, d2b, d2s), all gathered
    back to replicated layout (the Update phase needs the full batch).

    ``inner`` is the per-device top-2 search run on the local signal
    shard (default: the pure-jnp reference) — this is how the sharded
    path composes with the Pallas Find Winners backend.
    """
    axes = tuple(a for a in signal_axes if a in mesh.axis_names)
    local_fw = inner if inner is not None else find_winners_reference
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,  # outputs are replicated by the all_gathers below
    )
    def fw(sig_local, w, active):
        wid, sid, d2b, d2s = local_fw(sig_local, w, active)
        # gather the (small) per-signal results so Update can replicate
        def gather(x):
            for ax in reversed(axes):
                x = jax.lax.all_gather(x, ax, tiled=True)
            return x
        return gather(wid), gather(sid), gather(d2b), gather(d2s)

    def checked(signals, w, active):
        m = signals.shape[0]
        if m % n_shards != 0:
            raise ValueError(
                f"signal batch of {m} rows is not divisible by the "
                f"{n_shards} devices of mesh axes {axes}; pick a "
                f"max_parallel / fixed_m that the mesh divides")
        return fw(signals, w, active)

    return checked


def network_parallel_find_winners(mesh: Mesh, unit_axis: str = "model"):
    """Find Winners with the unit pool sharded over ``unit_axis``.

    The map-reduce pattern of the prior literature: local top-2 per unit
    shard, then an all-gather tournament merge. Kept as the baseline the
    paper compares against.
    """
    n_shards = mesh.shape[unit_axis]

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(unit_axis), P(unit_axis)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,  # replicated after the tournament all_gather
    )
    def fw(signals, w_local, active_local):
        shard = jax.lax.axis_index(unit_axis)
        c_local = w_local.shape[0]
        wid, sid, d2b, d2s = find_winners_reference(
            signals, w_local, active_local)
        base = shard * c_local
        cand_ids = jnp.stack([wid + base, sid + base], axis=1)   # (m, 2)
        cand_d2 = jnp.stack([d2b, d2s], axis=1)
        all_ids = jax.lax.all_gather(cand_ids, unit_axis, axis=1,
                                     tiled=True)                 # (m, 2S)
        all_d2 = jax.lax.all_gather(cand_d2, unit_axis, axis=1,
                                    tiled=True)
        neg, k = jax.lax.top_k(-all_d2, 2)
        take = jnp.take_along_axis(all_ids, k, axis=1)
        return (take[:, 0].astype(jnp.int32), take[:, 1].astype(jnp.int32),
                jnp.maximum(-neg[:, 0], 0.0), jnp.maximum(-neg[:, 1], 0.0))

    return fw


def make_distributed_step(mesh: Mesh, params: GSONParams,
                          strategy: str = "data",
                          signal_axes=("pod", "data"),
                          unit_axis: str = "model"):
    """jit-compiled multi-signal step on a device mesh.

    ``strategy='data'`` is the paper's scheme: signals sharded over
    ``signal_axes``, state replicated, Update replicated.
    ``strategy='network'`` shards the unit pool instead.
    """
    if strategy == "data":
        fw = data_parallel_find_winners(mesh, signal_axes)
        sig_axes = tuple(a for a in signal_axes if a in mesh.axis_names)
        sig_spec = P(sig_axes)
    elif strategy == "network":
        fw = network_parallel_find_winners(mesh, unit_axis)
        sig_spec = P()
    else:
        raise ValueError(strategy)

    replicated = NamedSharding(mesh, P())

    def step(state: NetworkState, signals: jax.Array) -> NetworkState:
        return multi_signal_step_impl(state, signals, params,
                                      refresh_states=False,
                                      find_winners=fw)

    return jax.jit(
        step,
        in_shardings=(replicated, NamedSharding(mesh, sig_spec)),
        out_shardings=replicated,
    )


@lru_cache(maxsize=None)
def signal_sharded_find_winners(mesh: Mesh, signal_axes=("data",),
                                inner=None):
    """Memoized :func:`data_parallel_find_winners` for the public API.

    The returned callable is a jit cache key of every program that
    threads it (step / superstep / fleet), so ``repro.gson`` must hand
    out ONE instance per ``(mesh, axes, inner backend)`` — the lru_cache
    provides that identity. ``inner`` must itself be hashable (module
    function or a memoized backend adapter).
    """
    return data_parallel_find_winners(mesh, signal_axes, inner=inner)


@lru_cache(maxsize=None)
def replicated_update_phase(mesh: Mesh, update_phase):
    """``update_phase`` as an explicitly replicated shard_map program.

    Under data partitioning the Update phase is a replicated
    deterministic state machine: every device applies the identical
    update to its full copy of the network. GSPMD replicates plain XLA
    ops by itself, but a Pallas (Mosaic) kernel cannot be partitioned
    automatically and must sit inside a shard_map — this wrapper is
    that shard_map, with every operand and result replicated.

    Memoized per ``(mesh, update_phase)``: the returned callable is a
    jit cache key of every program that threads it.
    """
    rep = partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                  check_vma=False)

    def up(state, signals, wid, sid, d2b, k_lock, params,
           signal_mask=None):
        # params is static configuration: closed over, not an operand
        def body(state, signals, wid, sid, d2b, k_lock, signal_mask):
            return update_phase(state, signals, wid, sid, d2b, k_lock,
                                params, signal_mask)
        return rep(body)(state, signals, wid, sid, d2b, k_lock, signal_mask)

    return up


# ---------------------------------------------------------------------------
# Fleet sharding: B whole networks sharded across devices, zero
# per-iteration collectives (the paper's data-partitioning argument one
# level up — the parallel axis is networks, not signals).


@dataclass(frozen=True)
class ShardSwitchSampler:
    """Heterogeneous fleet sampling inside a network-sharded program.

    ``GroupedSampler`` scatters by *global* slot index, which has no
    meaning inside a shard_map region where each device holds a local
    ``(B/ndev,)`` key slice. This wrapper pre-splits the per-slot
    samplers by the (static, positional) mesh layout — branch d is the
    fleet sampler for exactly the slots device d owns — and selects the
    branch with ``lax.axis_index`` at run time. Per-slot values are
    unchanged (a sampler's output for one key does not depend on its
    vmap batch), so sharded == unsharded bitwise.

    Only meaningful inside the shard_map programs below; the unsharded
    ``fleet_init`` keeps using the global sampler.
    """

    samplers: tuple              # ndev per-device fleet samplers
    axis_name: str

    def __call__(self, rngs: jax.Array, n: int) -> jax.Array:
        branches = tuple(
            (lambda k, s=s: s(k, n)) for s in self.samplers)
        return jax.lax.switch(
            jax.lax.axis_index(self.axis_name), branches, rngs)


def _is_key(x) -> bool:
    return jnp.issubdtype(x.dtype, jax.dtypes.prng_key)


def _keys_to_data(fs: FleetState) -> FleetState:
    """Typed PRNG-key leaves -> raw uint32 data at the shard_map
    boundary: extended-dtype arrays cannot be sharded on every pinned
    jax, and the (B, 2) data carries the same leading network axis."""
    return fs.replace(
        rng=jax.random.key_data(fs.rng) if _is_key(fs.rng) else fs.rng,
        nets=fs.nets.replace(
            rng=(jax.random.key_data(fs.nets.rng)
                 if _is_key(fs.nets.rng) else fs.nets.rng)))


def _keys_from_data(fs: FleetState) -> FleetState:
    return fs.replace(
        rng=(fs.rng if _is_key(fs.rng)
             else jax.random.wrap_key_data(fs.rng)),
        nets=fs.nets.replace(
            rng=(fs.nets.rng if _is_key(fs.nets.rng)
                 else jax.random.wrap_key_data(fs.nets.rng))))


@lru_cache(maxsize=None)
def make_sharded_fleet_programs(mesh: Mesh, axis_name: str = "fleet"):
    """The three fleet entry points as shard_map programs over ``B``.

    Drop-in replacements for ``fleet_core.fleet_iterate`` /
    ``fleet_check`` / ``run_fleet_superstep`` (same signatures,
    donation included): every ``(B, ...)`` operand — fleet state,
    masks, probes, per-network budgets — is sharded on its leading
    axis over ``mesh[axis_name]``, and each device runs the *identical*
    vmapped fleet body on its local ``B/ndev`` networks. Because
    networks never interact, the lowered program contains **no
    collectives**; the ``lax.while_loop`` of the superstep form even
    exits early per device once its local networks are all frozen,
    instead of idling until the globally slowest straggler finishes.

    ``B`` must be divisible by the axis size — ``repro.gson.fleet``
    pads cohorts with frozen placeholder networks to guarantee that.

    Memoized per ``(mesh, axis_name)``: the programs are jit cache
    keys downstream.
    """
    spec = P(axis_name)
    shmap = partial(jax.shard_map, mesh=mesh, check_vma=False)

    @partial(jax.jit,
             static_argnames=("sampler", "params", "cfg", "find_winners",
                              "update_phase"),
             donate_argnames=("fstate",))
    def iterate(fstate, mask, *, sampler, params, cfg,
                find_winners=None, update_phase=None):
        def body(fs, mask):
            out = fleet_iterate_impl(
                _keys_from_data(fs), mask, sampler=sampler,
                params=params, cfg=cfg, find_winners=find_winners,
                update_phase=update_phase)
            return _keys_to_data(out)
        out = shmap(body, in_specs=(spec, spec), out_specs=spec)(
            _keys_to_data(fstate), mask)
        return _keys_from_data(out)

    @partial(jax.jit, static_argnames=("params", "cfg"),
             donate_argnames=("fstate",))
    def check(fstate, probes, mask, *, params, cfg):
        def body(fs, probes, mask):
            out = fleet_check_impl(_keys_from_data(fs), probes, mask,
                                   params=params, cfg=cfg)
            return _keys_to_data(out)
        out = shmap(body, in_specs=(spec, spec, spec), out_specs=spec)(
            _keys_to_data(fstate), probes, mask)
        return _keys_from_data(out)

    @partial(jax.jit,
             static_argnames=("sampler", "params", "cfg", "find_winners",
                              "update_phase"),
             donate_argnames=("fstate",))
    def superstep(fstate, probes, max_steps, *, sampler, params, cfg,
                  find_winners=None, update_phase=None):
        def body(fs, probes, max_steps):
            out, steps = run_fleet_superstep_impl(
                _keys_from_data(fs), probes, max_steps, sampler=sampler,
                params=params, cfg=cfg, find_winners=find_winners,
                update_phase=update_phase)
            return _keys_to_data(out), steps
        out, steps = shmap(body, in_specs=(spec, spec, spec),
                           out_specs=(spec, spec))(
            _keys_to_data(fstate), probes, max_steps)
        return _keys_from_data(out), steps

    return iterate, check, superstep


@lru_cache(maxsize=None)
def make_sharded_fleet_health(mesh: Mesh, axis_name: str = "fleet"):
    """Sharded ``fleet_core.fleet_health``: each device screens only its
    own ``B/ndev`` networks (no resharding of the big unit pools), and
    only the tiny (B,) verdict is gathered back to the host. Read-only —
    no donation, the caller keeps stepping the screened state. Memoized
    per ``(mesh, axis_name)`` like the step programs, so the screen is
    one compiled program per mesh for the lifetime of the process.
    """
    spec = P(axis_name)
    shmap = partial(jax.shard_map, mesh=mesh, check_vma=False)

    @jax.jit
    def health(fstate):
        body = lambda fs: fleet_health_impl(_keys_from_data(fs))
        return shmap(body, in_specs=(spec,), out_specs=spec)(
            _keys_to_data(fstate))

    return health
