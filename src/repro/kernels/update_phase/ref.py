"""Pure-jnp oracle for the update_phase kernel suite.

Computes the dense Update phase through full (m, capacity) one-hot
matrices and single whole-array contractions — the kernel's algorithm
with the tiling stripped away, and a numerically distinct witness from
the scatter-based engine reference (``update_phase_reference``). Tests
triangulate all three: kernel vs oracle (same formulation — near-exact),
kernel vs engine reference (documented tolerance on colliding neighbor
sums), oracle vs engine reference.

Because it is plain XLA, this is also the *measurable* form of the
kernel algorithm on backends without a real Pallas lowering (this
container runs Pallas in interpret mode, which times the interpreter,
not the algorithm) — ``benchmarks/bench_update_phase.py`` reports it
alongside the scatter reference and the interpret-mode kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.gson import topology as topo
from repro.core.gson.multi import UpdateOut, stable_units
from repro.core.gson.state import GSONParams, NetworkState

_BIG = jnp.iinfo(jnp.int32).max


def _t_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a.T @ b`` at HIGHEST precision: the one-hot contractions copy
    f32 payloads, which a TPU's default bf16 pass would round."""
    return jnp.matmul(a.T, b, precision=jax.lax.Precision.HIGHEST)


def update_phase_dense(
    state: NetworkState,
    signals: jax.Array,
    wid: jax.Array,
    sid: jax.Array,
    d2b: jax.Array,
    k_lock: jax.Array,
    params: GSONParams,
    signal_mask: jax.Array | None = None,
) -> UpdateOut:
    """UpdatePhaseFn contract via dense one-hot contractions."""
    if params.neighbor_collision != "sum":
        raise NotImplementedError(
            "the dense update-phase formulation implements the "
            'deterministic "sum" neighbor-collision mode only')
    C, K = state.capacity, state.max_deg
    m = signals.shape[0]
    is_gng = params.model == "gng"

    # ---- winner lock: masked min-reduce over the winner one-hot ----------
    prio = jax.random.permutation(k_lock, m).astype(jnp.int32)
    mask = (jnp.ones((m,), bool) if signal_mask is None else signal_mask)
    prio_m = jnp.where(mask, prio, _BIG)
    onehot = wid[:, None] == jnp.arange(C, dtype=jnp.int32)[None, :]
    best = jnp.min(jnp.where(onehot, prio_m[:, None], _BIG), axis=0)
    selected = (prio_m == best[jnp.clip(wid, 0, C - 1)]) & mask

    # ---- per-signal decisions (identical formulas to the reference) ------
    wc = jnp.clip(wid, 0, C - 1)
    dist_b = jnp.sqrt(d2b)
    if is_gng:
        ins = jnp.zeros((m,), bool)
    else:
        ins = (selected
               & (dist_b > state.threshold[wc])
               & (state.firing[wc] < params.firing_threshold))
    adapt = selected if is_gng else (selected & ~ins)

    stable_u = stable_units(state, params)
    h_b = state.firing[wc]
    scale_b = params.eps_b * (jnp.ones_like(h_b) if is_gng else h_b)
    scale_b = jnp.where(stable_u[wc], 0.0, scale_b)

    # ---- winner pull: one-hot copy (post-lock winners are distinct) ------
    o_adapt = (onehot & adapt[:, None]).astype(jnp.float32)
    o_sel = (onehot & selected[:, None]).astype(jnp.float32)
    scale_vec = _t_dot(o_adapt, scale_b[:, None])            # (C, 1)
    sel_x = _t_dot(o_adapt, signals)                         # (C, d)
    w1 = state.w + scale_vec * (sel_x - state.w)

    # ---- neighbor pulls: slot-summed weighted one-hot --------------------
    nb = state.nbr[wc]
    nb_valid = (nb >= 0) & adapt[:, None]
    nb_safe = jnp.clip(nb, 0, C - 1)
    h_n = state.firing[nb_safe]
    scale_n = params.eps_n * (jnp.ones_like(h_n) if is_gng else h_n)
    scale_n = jnp.where(stable_u[nb_safe], 0.0, scale_n)
    scale_n = jnp.where(nb_valid, scale_n, 0.0)
    nb_k = jnp.where(nb_valid, nb, -1)
    o_nb = (nb_k[:, :, None]
            == jnp.arange(C, dtype=jnp.int32)[None, None, :])
    wn = jnp.sum(o_nb * scale_n[:, :, None], axis=1)         # (m, C)
    nsc = jnp.sum(wn, axis=0)[:, None]                       # (C, 1)
    nsx = _t_dot(wn, signals)                                # (C, d)
    w2 = w1 + (nsx - nsc * w1)

    # ---- habituation + GNG error -----------------------------------------
    if is_gng:
        firing = state.firing
        error = state.error + _t_dot(o_sel, d2b[:, None])[:, 0]
    else:
        dec_b = params.tau_b * (h_b - params.h_min)
        dec_n = jnp.where(nb_valid,
                          params.tau_n * (h_n - params.h_min), 0.0)
        dn = jnp.sum(o_nb * dec_n[:, :, None], axis=1)
        firing = jnp.clip(
            state.firing - _t_dot(o_adapt, dec_b[:, None])[:, 0]
            - jnp.sum(dn, axis=0),
            params.h_min, 1.0)
        error = state.error

    # ---- edge aging + winner-second refresh ------------------------------
    nbr = state.nbr
    win_ind = jnp.any(o_sel > 0.0, axis=0)
    valid = nbr >= 0
    winat = win_ind[jnp.clip(nbr, 0, C - 1)] & valid
    keep = stable_u[:, None] & stable_u[jnp.clip(nbr, 0, C - 1)]
    inc = ((win_ind[:, None].astype(jnp.float32)
            + winat.astype(jnp.float32))
           * valid.astype(jnp.float32) * (1.0 - keep.astype(jnp.float32)))
    rows = jnp.concatenate([wid, sid])
    vals = jnp.concatenate([sid, wid])
    m2 = jnp.concatenate([adapt, adapt])
    slots = topo.find_slots(nbr, jnp.where(m2, rows, -1), vals)
    ok = m2 & (slots >= 0)
    reset = jnp.zeros((C, K), bool).at[
        jnp.where(ok, rows, C), jnp.maximum(slots, 0)].set(
        True, mode="drop")
    age = jnp.where(reset, 0.0, state.age + inc)

    return UpdateOut(selected=selected, adapt=adapt, ins=ins,
                     w=w2, firing=firing, error=error, age=age)
