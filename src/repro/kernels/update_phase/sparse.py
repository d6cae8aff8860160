"""Sparse winner-neighborhood Update phase: slab-gathered Pallas tiles.

The dense suite (``ops.update_phase_op``) contracts every signal tile
against every unit tile — O(m·capacity) work that pays for the *pool*,
not for the *network*. But one multi-signal iteration only ever writes
units touched by the batch: the winners, the seconds, and the winners'
neighbor rows (edge symmetry makes the mirror-aging targets exactly
the winners' neighbors). On a compact pool (the allocator fills free
slots lowest-id-first) those ids cluster into a handful of unit tiles.

This module exploits that: **gather just the touched unit tiles into a
contiguous slab, run the UNCHANGED three Pallas kernels at slab
capacity, scatter the slab back.** Work drops from O(m·capacity) to
O(m·slab) — O(m)-bounded like the scatter reference (the slab is at
most ``slab_tiles`` tiles, a static knob independent of capacity) —
while every reduction stays an MXU-shaped tiled contraction.

Correctness is never data-dependent. The slab size must be static
under jit, so the touched-tile count is checked at runtime and a
batch-level ``lax.cond`` falls back to the dense tiled path whenever
the batch touches more tiles than the slab holds — the same "guard"
discipline ``repro.ann.grid`` uses for its stencil shortfall (scalar
predicate: exactly one branch executes outside ``vmap``; under a
vmapped fleet both branches run and the select keeps the right one,
which costs speed, never parity). Numerics are the dense suite's
contract verbatim — the slab runs the *same kernels* on the *same
values*, only at remapped unit ids: discrete fields bitwise vs the
scatter reference, floats within ~1e-6 on neighbor collisions
(``tests/test_kernels_update_sparse.py`` pins both, property-swept).

Where it wins: capacity ≫ m·(K+2) — big pools serving modest signal
batches (the default ``RunSpec`` ships capacity 4096; the paper's
m-schedule spends most iterations at small m). Where it cannot win
(m ≳ capacity, every tile touched) the guard makes it *equal* to the
dense path, and the shape-aware autotuner (``repro.gson.autotune``)
picks the scatter reference instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.gson import topology as topo
from repro.core.gson.multi import (UpdateOut, stable_units,
                                   update_phase_inputs)
from repro.core.gson.state import GSONParams, NetworkState
from repro.kernels import interpret_mode
from repro.kernels.update_phase.kernel import (BIG_PRIO,
                                               edge_age_pallas_padded,
                                               update_accum_pallas_padded,
                                               winner_lock_pallas_padded)
from repro.kernels.update_phase.ops import (_pad_rows, _round_up,
                                            update_phase_op)


def default_slab_tiles(m: int, tile: int, n_tiles: int) -> int:
    """Static slab budget: ``min(n_tiles, ceil(2m / tile))`` tiles.

    Winners and seconds contribute at most 2m distinct ids, so 2m ids'
    worth of tiles always covers them; on a compact pool the winners'
    neighbor rows share those same tiles. The bound is independent of
    capacity — that is the whole point — and intentionally *not*
    worst-case for neighbors (a fragmented pool can exceed it): the
    runtime guard handles the excess exactly.
    """
    return max(1, min(n_tiles, -(-2 * m // tile)))


def update_phase_sparse(
    state: NetworkState,
    signals: jax.Array,
    wid: jax.Array,
    sid: jax.Array,
    d2b: jax.Array,
    k_lock: jax.Array,
    params: GSONParams,
    signal_mask: jax.Array | None = None,
    *,
    block_m: int = 256,
    block_c: int = 256,
    slab_tiles: int | None = None,
    interpret: bool | None = None,
) -> UpdateOut:
    """The dense Update phase on a gathered winner-neighborhood slab.

    Same ``UpdatePhaseFn`` contract as ``update_phase_reference`` /
    ``ops.update_phase_op``. ``slab_tiles`` caps the gathered slab (in
    ``block_c``-sized unit tiles); ``None`` uses
    :func:`default_slab_tiles`. Batches touching more tiles than the
    slab holds fall back to the dense tiled path via one batch-level
    ``lax.cond``.
    """
    if params.neighbor_collision != "sum":
        raise NotImplementedError(
            "the sparse update-phase kernel implements the deterministic "
            '"sum" neighbor-collision mode only; use the reference '
            'backend to study neighbor_collision="last"')
    interpret = interpret_mode(interpret)
    C, K = state.capacity, state.max_deg
    m, d = signals.shape
    is_gng = params.model == "gng"

    block_m = min(block_m, _round_up(m, 8))
    tile = min(block_c, _round_up(C, 128))
    mp = _round_up(m, block_m)
    cp = _round_up(C, tile)
    n_tiles = cp // tile
    G = (default_slab_tiles(m, tile, n_tiles) if slab_tiles is None
         else max(1, min(slab_tiles, n_tiles)))

    if G >= n_tiles:
        # the slab would be the whole pool: the dense path IS the
        # sparse path here, minus the gather/scatter overhead
        return update_phase_op(state, signals, wid, sid, d2b, k_lock,
                               params, signal_mask, block_m=block_m,
                               block_c=block_c, interpret=interpret)

    # ---- touched unit tiles: winners ∪ seconds ∪ winners' neighbors ------
    # (conservative: pre-lock, every signal's rows count. Edge symmetry
    # means mirror-aging targets are the winners' neighbors, so this
    # superset covers every row any phase output can differ on.)
    wc = jnp.clip(wid, 0, C - 1)
    nb_w = state.nbr[wc]                                     # (m, K)
    touched_ids = jnp.concatenate(
        [wc, jnp.clip(sid, 0, C - 1), jnp.where(nb_w >= 0, nb_w, 0)
         .reshape(-1)])
    touched = jnp.zeros((n_tiles,), bool).at[touched_ids // tile].set(True)
    n_touched = jnp.sum(touched).astype(jnp.int32)

    # touched tiles first (ascending id), untouched filler after — the
    # filler rows round the slab to its static size and are updated as
    # identity (zero accumulator contributions)
    tile_ids = jnp.arange(n_tiles, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(touched, tile_ids, tile_ids + n_tiles))
    tiles = order[:G].astype(jnp.int32)                      # (G,)
    # slab position of each pool tile; n_tiles (≡ off-slab) only ever
    # yields out-of-range slab ids, which the kernels' iota equality
    # drops — reachable only in the fallback branch's dead values
    pos = jnp.full((n_tiles,), G, jnp.int32).at[tiles].set(
        jnp.arange(G, dtype=jnp.int32))
    rows = (tiles[:, None] * tile
            + jnp.arange(tile, dtype=jnp.int32)[None, :]).reshape(-1)
    Gs = G * tile

    def remap(ids):
        """Pool ids -> slab-local ids; negatives pass through."""
        safe = jnp.clip(ids, 0, cp - 1)
        local = pos[safe // tile] * tile + safe % tile
        return jnp.where(ids >= 0, local, ids)

    def sparse_branch():
        f32 = jnp.float32
        wid_s = remap(wid)

        # -- per-signal prologue + kernel 1: lock at slab capacity ----
        prio = jax.random.permutation(k_lock, m).astype(jnp.int32)
        mask = (jnp.ones((m,), bool) if signal_mask is None
                else signal_mask)
        prio_masked = jnp.where(mask, prio, BIG_PRIO)
        best = winner_lock_pallas_padded(
            _pad_rows(wid_s[:, None], mp, 0),
            _pad_rows(prio_masked[:, None], mp, BIG_PRIO),
            Gs, block_m=block_m, block_c=tile,
            interpret=interpret)[0]
        selected = (prio_masked == best[jnp.clip(wid_s, 0, Gs - 1)]) & mask

        (ins, adapt, scale_b, dec_b, _h_b, nb, nb_valid, scale_n,
         dec_n) = update_phase_inputs(state, wid, d2b, selected, params)
        stable_u = stable_units(state, params)
        nb_k = remap(jnp.where(nb_valid, nb, -1))

        # -- slab gathers (pad the pool only when misaligned) ---------
        w_pad = _pad_rows(state.w, cp, 0.0)
        firing_pad = _pad_rows(state.firing, cp, 1.0)
        error_pad = _pad_rows(state.error, cp, 0.0)
        age_pad = _pad_rows(state.age, cp, 0.0)
        nbr_pad = _pad_rows(state.nbr, cp, -1)
        stable_pad = _pad_rows(stable_u, cp, False)

        # -- kernel 2: fused accumulators over slab unit tiles --------
        (w1, nsc, nsx, err_u, decb_u, decn_u,
         wind) = update_accum_pallas_padded(
            _pad_rows(signals, mp, 0.0),
            _pad_rows(wid_s[:, None], mp, 0),
            _pad_rows(selected.astype(f32)[:, None], mp, 0.0),
            _pad_rows(adapt.astype(f32)[:, None], mp, 0.0),
            _pad_rows(scale_b[:, None], mp, 0.0),
            _pad_rows(d2b[:, None], mp, 0.0),
            _pad_rows(dec_b[:, None], mp, 0.0),
            _pad_rows(nb_k, mp, -1),
            _pad_rows(scale_n, mp, 0.0),
            _pad_rows(dec_n, mp, 0.0),
            w_pad[rows],
            block_m=block_m, block_c=tile, interpret=interpret)
        w2_s = w1 + (nsx - nsc * w1)
        firing_s = (firing_pad[rows] if is_gng else
                    jnp.clip(firing_pad[rows] - decb_u[:, 0]
                             - decn_u[:, 0], params.h_min, 1.0))
        error_s = (error_pad[rows] + err_u[:, 0] if is_gng
                   else error_pad[rows])
        win_ind_s = wind[:, 0] > 0.0

        # -- kernel 3: edge aging + winner-second refresh on the slab --
        nbr_s = nbr_pad[rows]                                # (Gs, K)
        valid_s = nbr_s >= 0
        win_full = jnp.zeros((cp,), bool).at[rows].set(win_ind_s)
        nb_safe = jnp.clip(nbr_s, 0, cp - 1)
        winat_s = win_full[nb_safe] & valid_s
        protat_s = stable_pad[nb_safe]
        e_rows = jnp.concatenate([wid, sid])
        e_vals = jnp.concatenate([sid, wid])
        e_m = jnp.concatenate([adapt, adapt])
        slots = topo.find_slots(state.nbr, jnp.where(e_m, e_rows, -1),
                                e_vals)
        ok = e_m & (slots >= 0)
        r_local = remap(jnp.where(ok, e_rows, -1))
        reset_s = jnp.zeros((Gs, K), bool).at[
            jnp.where(ok & (r_local < Gs), r_local, Gs),
            jnp.maximum(slots, 0)].set(True, mode="drop")
        age_s = edge_age_pallas_padded(
            age_pad[rows],
            valid_s.astype(f32),
            win_ind_s.astype(f32)[:, None],
            winat_s.astype(f32),
            stable_pad[rows].astype(f32)[:, None],
            protat_s.astype(f32),
            reset_s.astype(f32),
            block_c=tile, interpret=interpret)

        # -- scatter the slab back (rows are distinct by construction) -
        return UpdateOut(
            selected=selected, adapt=adapt, ins=ins,
            w=w_pad.at[rows].set(w2_s)[:C],
            firing=firing_pad.at[rows].set(firing_s)[:C],
            error=error_pad.at[rows].set(error_s)[:C],
            age=age_pad.at[rows].set(age_s)[:C])

    def dense_branch():
        return update_phase_op(state, signals, wid, sid, d2b, k_lock,
                               params, signal_mask, block_m=block_m,
                               block_c=block_c, interpret=interpret)

    return jax.lax.cond(n_touched <= G, sparse_branch, dense_branch)


def make_sparse_update_phase(block_m: int = 256, block_c: int = 256,
                             slab_tiles: int | None = None,
                             interpret: bool | None = None):
    """Adapter matching the engine's UpdatePhaseFn signature.

    Like ``ops.make_pallas_update_phase``: the returned closure is the
    jit cache key for every program that threads it, so share one
    instance per configuration (the BACKENDS registry memoizes its).
    """

    def up(state, signals, wid, sid, d2b, k_lock, params,
           signal_mask=None):
        return update_phase_sparse(state, signals, wid, sid, d2b,
                                   k_lock, params, signal_mask,
                                   block_m=block_m, block_c=block_c,
                                   slab_tiles=slab_tiles,
                                   interpret=interpret)

    return up
