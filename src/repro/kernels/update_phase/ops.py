"""jit'd public wrapper for the Update-phase kernel suite.

``update_phase_op`` implements the engine's ``UpdatePhaseFn`` contract
(see ``repro.core.gson.multi``): the jnp prologue performs the cheap
O(m) per-signal gathers (winner firing/threshold rows, winner neighbor
lists) and decision logic, the three Pallas kernels perform every
per-unit reduction — lock scatter-min, weight/habituation/error
accumulation, edge aging — and the jnp epilogue applies the
accumulators elementwise. Shapes need not be tile-aligned: activity
and validity are masked in-kernel via sentinel ids / +LARGE
priorities, and signals/unit tables are padded only when their static
shape is actually misaligned (the fused superstep's power-of-two
signal buffer and pool capacities pass through with zero copies).

Numerics vs ``update_phase_reference``, pinned by
``tests/test_kernels_update_phase.py``:

  * bit-exact: ``selected`` / ``adapt`` / ``ins`` (integer lock +
    comparisons), winner weight pulls (post-lock winners are distinct,
    so the one-hot contraction copies instead of summing), winner
    habituation, GNG error accumulation, edge ages;
  * float tolerance (~1e-6): neighbor weight pulls and neighbor
    habituation where several signals share a neighbor — the kernel
    sums collisions in tile order, the reference in scatter order.

``neighbor_collision="last"`` (the GPU write-race emulation mode) is
deliberately not implemented — it exists to *study* nondeterminism,
not to run fast; the op raises so misconfiguration fails at trace time.
"""
from __future__ import annotations

from functools import partial

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


def _round_up(x: int, to: int) -> int:
    return (x + to - 1) // to * to


def _pad_rows(a: jax.Array, rows: int, fill) -> jax.Array:
    if a.shape[0] == rows:
        return a
    pad = jnp.full((rows - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([a, pad], axis=0)


def update_phase_op(
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
    interpret: bool | None = None,
) -> UpdateOut:
    """The dense Update phase through the Pallas suite.

    Same contract as ``repro.core.gson.multi.update_phase_reference``
    (winner lock -> insertion decision -> weight pulls -> habituation
    -> error -> edge aging + winner-second refresh).
    """
    if params.neighbor_collision != "sum":
        raise NotImplementedError(
            "the Pallas update-phase kernel implements the deterministic "
            '"sum" neighbor-collision mode only; use the reference '
            'backend to study neighbor_collision="last"')
    interpret = interpret_mode(interpret)
    C, K = state.capacity, state.max_deg
    m, d = signals.shape
    is_gng = params.model == "gng"

    block_m = min(block_m, _round_up(m, 8))
    block_c = min(block_c, _round_up(C, 128))
    mp = _round_up(m, block_m)
    cp = _round_up(C, block_c)

    # ---- per-signal prologue (O(m) gathers + decisions) ------------------
    prio = jax.random.permutation(k_lock, m).astype(jnp.int32)
    mask = (jnp.ones((m,), bool) if signal_mask is None
            else signal_mask)
    prio_masked = jnp.where(mask, prio, BIG_PRIO)

    # ---- kernel 1: winner lock (per-unit min priority) -------------------
    best = winner_lock_pallas_padded(
        _pad_rows(wid[:, None], mp, 0),
        _pad_rows(prio_masked[:, None], mp, BIG_PRIO),
        cp, block_m=block_m, block_c=block_c, interpret=interpret)[0, :C]
    selected = (prio_masked == best[jnp.clip(wid, 0, C - 1)]) & mask

    # shared per-signal prologue — ONE definition with the reference
    # path (repro.core.gson.multi.update_phase_inputs), so rule changes
    # cannot silently diverge between backends
    (ins, adapt, scale_b, dec_b, _h_b, nb, nb_valid, scale_n,
     dec_n) = update_phase_inputs(state, wid, d2b, selected, params)
    stable_u = stable_units(state, params)
    nb_k = jnp.where(nb_valid, nb, -1)

    # ---- kernel 2: fused per-unit accumulators ---------------------------
    f32 = jnp.float32
    w1, nsc, nsx, err_u, decb_u, decn_u, wind = update_accum_pallas_padded(
        _pad_rows(signals, mp, 0.0),
        _pad_rows(wid[:, None], mp, 0),
        _pad_rows(selected.astype(f32)[:, None], mp, 0.0),
        _pad_rows(adapt.astype(f32)[:, None], mp, 0.0),
        _pad_rows(scale_b[:, None], mp, 0.0),
        _pad_rows(d2b[:, None], mp, 0.0),
        _pad_rows(dec_b[:, None], mp, 0.0),
        _pad_rows(nb_k, mp, -1),
        _pad_rows(scale_n, mp, 0.0),
        _pad_rows(dec_n, mp, 0.0),
        _pad_rows(state.w, cp, 0.0),
        block_m=block_m, block_c=block_c, interpret=interpret)
    w1 = w1[:C]
    # neighbor pull epilogue: sum_i s_i * (x_i - w1) == nsx - nsc * w1
    w2 = w1 + (nsx[:C] - nsc[:C] * w1)
    firing = (state.firing if is_gng else
              jnp.clip(state.firing - decb_u[:C, 0] - decn_u[:C, 0],
                       params.h_min, 1.0))
    error = state.error + err_u[:C, 0] if is_gng else state.error
    win_ind = wind[:C, 0] > 0.0

    # ---- kernel 3: fused edge aging + winner-second refresh --------------
    nbr = state.nbr
    valid = nbr >= 0
    winat = win_ind[jnp.clip(nbr, 0, C - 1)] & valid
    protat = stable_u[jnp.clip(nbr, 0, C - 1)]
    rows = jnp.concatenate([wid, sid])
    vals = jnp.concatenate([sid, wid])
    m2 = jnp.concatenate([adapt, adapt])
    slots = topo.find_slots(nbr, jnp.where(m2, rows, -1), vals)
    ok = m2 & (slots >= 0)
    reset = jnp.zeros((C, K), bool).at[
        jnp.where(ok, rows, C), jnp.maximum(slots, 0)].set(
        True, mode="drop")
    age = edge_age_pallas_padded(
        _pad_rows(state.age, cp, 0.0),
        _pad_rows(valid.astype(f32), cp, 0.0),
        _pad_rows(win_ind.astype(f32)[:, None], cp, 0.0),
        _pad_rows(winat.astype(f32), cp, 0.0),
        _pad_rows(stable_u.astype(f32)[:, None], cp, 0.0),
        _pad_rows(protat.astype(f32), cp, 0.0),
        _pad_rows(reset.astype(f32), cp, 0.0),
        block_c=block_c, interpret=interpret)[:C]

    return UpdateOut(selected=selected, adapt=adapt, ins=ins,
                     w=w2, firing=firing, error=error, age=age)


def make_pallas_update_phase(block_m: int = 256, block_c: int = 256,
                             interpret: bool | None = None):
    """Adapter matching the engine's UpdatePhaseFn signature.

    The returned closure is the jit cache key for every program that
    threads it (step / superstep / fleet), so share one instance per
    configuration — the BACKENDS registry caches exactly that.
    """

    def up(state, signals, wid, sid, d2b, k_lock, params,
           signal_mask=None):
        return update_phase_op(state, signals, wid, sid, d2b, k_lock,
                               params, signal_mask, block_m=block_m,
                               block_c=block_c, interpret=interpret)

    return up
