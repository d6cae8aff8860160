"""The paper's multi-signal iteration (Sec. 2.2/2.5), TPU-native.

One call processes m >> 1 signals at once:

  1. Find Winners  — batched top-2 nearest-unit search (pluggable backend:
     pure-jnp reference, Pallas MXU kernel, hash-grid, shard_map).
  2. Winner lock   — among signals sharing a winner, exactly one (uniform
     random priority) survives; the rest are *discarded* (paper Sec. 2.2).
     Implemented as a deterministic scatter-min over unique priorities.
  3. Update        — adaptation + structural changes, fully vectorized
     (the paper leaves Update parallelization as future work; doing it
     batched while preserving the winner-lock semantics is this repo's
     beyond-paper extension — see EXPERIMENTS.md §Perf).

Both device-heavy phases are pluggable. ``find_winners`` swaps the
top-2 search (``FindWinnersFn``); ``update_phase`` swaps the *dense*
half of the Update phase (``UpdatePhaseFn``): winner lock, weight
pulls, habituation, error accumulation and edge aging — everything the
paper's Sec. 2.5 profile shows dominating once Find Winners is
parallelized. :func:`update_phase_reference` is the scatter-based
default; ``repro.kernels.update_phase`` provides the tiled Pallas
suite, selected per-``RunSpec`` through the BACKENDS registry. The
discrete *structural* tail (unit insertion, edge insertion/expiry,
pruning) stays in the shared jnp code below — it is O(capacity) and
branch-heavy, not a bandwidth problem.

Supports the three published models: GNG (Fritzke 95), GWR (Marsland 02)
and SOAM (Piastra 12). The single-signal reference algorithm is this step
at m=1 (see single.py), which makes the coherence between variants
directly testable.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.gson import topology as topo
from repro.core.gson.state import DISK, SINGULAR, GSONParams, NetworkState

_BIG32 = jnp.iinfo(jnp.int32).max

FindWinnersFn = Callable[[jax.Array, jax.Array, jax.Array],
                         tuple[jax.Array, jax.Array, jax.Array, jax.Array]]


class UpdateOut(NamedTuple):
    """Result of the dense Update phase (see ``UpdatePhaseFn``).

    Per-signal decisions feed the structural tail; per-unit arrays are
    the adapted network fields.
    """

    selected: jax.Array   # (m,) bool — winner-lock survivors
    adapt: jax.Array      # (m,) bool — survivors that adapt (vs insert)
    ins: jax.Array        # (m,) bool — GWR/SOAM insertion triggers
    w: jax.Array          # (C, dim) f32 adapted reference vectors
    firing: jax.Array     # (C,) f32 habituation counters
    error: jax.Array      # (C,) f32 GNG error accumulator
    age: jax.Array        # (C, K) f32 aged (and winner-edge-refreshed) ages


# The dense Update phase: (state, signals, wid, sid, d2b, k_lock,
# params, signal_mask) -> UpdateOut. Implementations must preserve the
# winner-lock semantics (one survivor per distinct winner, uniformly
# random among colliders under k_lock) — see update_phase_reference.
#
# The callable is a static jit argument everywhere it threads
# (multi_signal_step / run_superstep / fleet / mesh programs), so ONE
# shared instance per configuration is the contract — and because the
# body runs at trace time, an implementation may specialize on the
# static shapes it sees (``state.capacity`` = ``w.shape[0]``,
# ``signals.shape[0]``) while keeping the outer jit keys unchanged.
# ``repro.gson.autotune.make_autotuned_update_phase`` (the
# ``pallas-auto`` backend) relies on exactly this: per-shape dispatch
# to reference / dense-tiled / sparse-slab kernels inside one stable
# callable.
UpdatePhaseFn = Callable[..., UpdateOut]


def find_winners_reference(signals: jax.Array, w: jax.Array,
                           active: jax.Array):
    """Pure-jnp batched top-2 nearest units.

    dist^2 = |x|^2 - 2 x.w + |w|^2 on the MXU-friendly matmul form, at
    HIGHEST precision: a TPU's default single bf16 pass rounds x.w to
    ~3 digits, coarser than a converged winner's dist^2.
    Top-2 via two masked-min passes (O(mC); ``lax.top_k`` sorts the
    whole row, which dominated step time in profiling — same
    first-lowest-id tie semantics). Returns
    (winner_ids, second_ids, d2_winner, d2_second).
    """
    x2 = jnp.sum(signals * signals, axis=1, keepdims=True)        # (m, 1)
    w2 = jnp.sum(w * w, axis=1)                                   # (C,)
    xw = jnp.matmul(signals, w.T, precision=jax.lax.Precision.HIGHEST)
    d2 = x2 - 2.0 * xw + w2[None, :]                              # (m, C)
    d2 = jnp.where(active[None, :], d2, jnp.inf)
    wid = jnp.argmin(d2, axis=1).astype(jnp.int32)
    d2b = jnp.take_along_axis(d2, wid[:, None], axis=1)[:, 0]
    cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2m = jnp.where(cols == wid[:, None], jnp.inf, d2)
    sid = jnp.argmin(d2m, axis=1).astype(jnp.int32)
    d2s = jnp.take_along_axis(d2m, sid[:, None], axis=1)[:, 0]
    # degenerate (<2 active): duplicate the winner
    invalid = ~jnp.isfinite(d2s)
    sid = jnp.where(invalid, wid, sid)
    d2s = jnp.where(invalid, d2b, d2s)
    return (wid, sid, jnp.maximum(d2b, 0.0), jnp.maximum(d2s, 0.0))


def winner_lock(rng: jax.Array, winner_ids: jax.Array, capacity: int,
                mask: jax.Array | None = None):
    """Paper's collision rule: one surviving signal per distinct winner.

    Uses unique random priorities + scatter-min: deterministic, and the
    survivor is uniformly random among colliding signals — matching the
    'first incoming signal, in a random order' semantics of the paper.

    ``mask``: (m,) bool — rows with mask False never survive and never
    out-prioritize a valid row (the fused superstep runs a fixed-size
    signal buffer with only the first ``m_t`` rows valid).
    """
    m = winner_ids.shape[0]
    prio = jax.random.permutation(rng, m).astype(jnp.int32)
    if mask is not None:
        prio = jnp.where(mask, prio, _BIG32)
    best = jnp.full((capacity,), _BIG32, jnp.int32).at[winner_ids].min(prio)
    selected = prio == best[winner_ids]
    if mask is not None:
        selected = selected & mask
    return selected, prio


def refresh_topology(state: NetworkState, params: GSONParams) -> NetworkState:
    """Recompute the SOAM state ladder + adapt per-unit insertion
    thresholds toward the local feature size (tighten while stuck
    non-disk, relax once locally stable)."""
    topo_state = topo.compute_topo_states(
        state.nbr, state.active, state.firing, params.firing_threshold)
    habituated = state.firing < params.firing_threshold
    stable = (topo_state >= DISK) & (topo_state != SINGULAR)
    stuck = state.active & habituated & ~stable
    inconsistent = jnp.where(stuck, state.inconsistent_for + 1, 0)
    tighten = inconsistent >= params.stuck_window
    thr_min = params.insertion_threshold * params.thr_min_frac
    threshold = jnp.where(
        tighten,
        jnp.maximum(state.threshold * params.thr_decay, thr_min),
        state.threshold)
    inconsistent = jnp.where(tighten, 0, inconsistent)
    threshold = jnp.where(
        state.active & stable,
        jnp.minimum(threshold * params.thr_recover,
                    params.insertion_threshold),
        threshold)
    return state.replace(topo_state=topo_state, threshold=threshold,
                         inconsistent_for=inconsistent)


def stable_units(state: NetworkState, params: GSONParams) -> jax.Array:
    """(C,) bool — units frozen in place by SOAM crystallization.

    SOAM: topologically stable units (disk/patch) stop moving so the
    rest of the mesh can settle (Piastra 12); their mutual edges are
    also protected from aging (EXPERIMENTS.md §H-soam-2).
    """
    if params.model == "soam" and params.freeze_stable:
        return (state.topo_state >= DISK) & (state.topo_state != SINGULAR)
    return jnp.zeros((state.capacity,), bool)


def update_phase_inputs(state: NetworkState, wid: jax.Array,
                        d2b: jax.Array, selected: jax.Array,
                        params: GSONParams):
    """Shared per-signal prologue of the dense Update phase.

    From the lock survivors, derive every per-signal decision and
    coefficient the adaptation needs: insertion triggers, adapt mask,
    winner/neighbor pull scales and habituation decrements, and the
    winners' neighbor rows. One definition serves both
    :func:`update_phase_reference` and the Pallas wrapper
    (``kernels.update_phase.ops``), so rule changes cannot silently
    diverge between backends (the dense oracle in
    ``kernels.update_phase.ref`` keeps its own copy by design).

    Returns ``(ins, adapt, scale_b, dec_b, h_b, nb, nb_valid, scale_n,
    dec_n)`` with ``scale_n``/``dec_n`` zeroed on invalid slots and
    stable units' scales zeroed (SOAM freeze).
    """
    C = state.capacity
    is_gng = params.model == "gng"
    wc = jnp.clip(wid, 0, C - 1)
    if is_gng:
        ins = jnp.zeros(wid.shape, bool)
    else:
        ins = (selected
               & (jnp.sqrt(d2b) > state.threshold[wc])
               & (state.firing[wc] < params.firing_threshold))
    adapt = selected if is_gng else (selected & ~ins)

    stable_u = stable_units(state, params)
    h_b = state.firing[wc]
    scale_b = params.eps_b * (jnp.ones_like(h_b) if is_gng else h_b)
    scale_b = jnp.where(stable_u[wc], 0.0, scale_b)
    dec_b = (jnp.zeros_like(h_b) if is_gng
             else params.tau_b * (h_b - params.h_min))

    nb = state.nbr[wc]                                       # (m, K)
    nb_valid = (nb >= 0) & adapt[:, None]
    nb_safe = jnp.clip(nb, 0, C - 1)
    h_n = state.firing[nb_safe]
    scale_n = params.eps_n * (jnp.ones_like(h_n) if is_gng else h_n)
    scale_n = jnp.where(stable_u[nb_safe], 0.0, scale_n)
    scale_n = jnp.where(nb_valid, scale_n, 0.0)
    dec_n = (jnp.zeros_like(h_n) if is_gng
             else jnp.where(nb_valid,
                            params.tau_n * (h_n - params.h_min), 0.0))
    return ins, adapt, scale_b, dec_b, h_b, nb, nb_valid, scale_n, dec_n


def update_phase_reference(
    state: NetworkState,
    signals: jax.Array,
    wid: jax.Array,
    sid: jax.Array,
    d2b: jax.Array,
    k_lock: jax.Array,
    params: GSONParams,
    signal_mask: jax.Array | None = None,
) -> UpdateOut:
    """The dense Update phase, scatter-based (the reference path).

    Everything between Find Winners and the structural tail of the
    paper's Update (Sec. 2.2 steps 2-6): winner lock, insertion
    decision, winner + neighbor weight pulls, habituation, GNG error
    accumulation, edge aging on winner rows, and the winner-second
    edge-age refresh. All per-unit writes are ``.at[].add/.min``
    scatters with deterministic collision resolution — the formulation
    ``repro.kernels.update_phase`` re-expresses as tiled one-hot
    matmul kernels (same contract, documented float tolerance).
    """
    C, K = state.capacity, state.max_deg
    is_gng = params.model == "gng"

    # ---- 2. winner lock --------------------------------------------------
    selected, prio = winner_lock(k_lock, wid, C, signal_mask)

    sel_w = jnp.where(selected, wid, C)          # sentinel -> scatter drop

    # ---- 3a. per-signal decisions + coefficients (shared prologue) -------
    (ins, adapt, scale_b, dec_b, h_b, nb, nb_valid, scale_n,
     dec_n) = update_phase_inputs(state, wid, d2b, selected, params)

    # ---- 3b. adaptation of winner + neighbors ----------------------------
    w = state.w
    firing = state.firing
    stable_u = stable_units(state, params)
    delta_b = scale_b[:, None] * (signals - w[jnp.clip(wid, 0, C - 1)])
    w = w.at[jnp.where(adapt, wid, C)].add(delta_b, mode="drop")

    nb_safe = jnp.clip(nb, 0, C - 1)
    delta_n = scale_n[..., None] * (signals[:, None, :] - w[nb_safe])
    delta_n = jnp.where(nb_valid[..., None], delta_n, 0.0)
    if params.neighbor_collision == "sum":
        w = w.at[jnp.where(nb_valid, nb, C)].add(delta_n, mode="drop")
    else:  # "last": GPU write-race emulation — one survivor per target row
        flat_nb = jnp.where(nb_valid, nb, C).reshape(-1)
        flat_prio = jnp.broadcast_to(prio[:, None], nb.shape).reshape(-1)
        best_n = jnp.full((C,), _BIG32, jnp.int32).at[flat_nb].min(
            flat_prio, mode="drop")
        keep = (flat_prio == best_n[jnp.clip(flat_nb, 0, C - 1)])
        tgt = jnp.where(keep & (flat_nb < C), flat_nb, C)
        w = w.at[tgt].add(delta_n.reshape(-1, w.shape[1]), mode="drop")

    # ---- 3c. habituation (GWR/SOAM) --------------------------------------
    if not is_gng:
        firing = firing.at[jnp.where(adapt, wid, C)].add(-dec_b, mode="drop")
        firing = firing.at[jnp.where(nb_valid, nb, C)].add(
            -dec_n, mode="drop")
        firing = jnp.clip(firing, params.h_min, 1.0)

    # ---- 3d. GNG error bookkeeping ---------------------------------------
    error = state.error
    if is_gng:
        error = error.at[sel_w].add(d2b, mode="drop")

    # ---- 3e. edge aging on winner rows (distinct winners post-lock) ------
    # stable-stable edges are protected from aging (SOAM crystallization)
    age = topo.age_incident_edges(state.nbr, state.age, wid, selected,
                                  protect=stable_u)
    # refresh the winner-second edge where it already exists (the
    # paper's "set age(b, s) = 0" Update step). The structural tail's
    # insert_edges re-resets the same slots (idempotent) while also
    # inserting missing (b, s) edges — keeping it there preserves the
    # historical bit-exact trajectory; doing it HERE as well lets a
    # fused kernel own the whole age array in one pass.
    age = topo.reset_edge_ages(state.nbr, age, wid, sid, adapt)

    return UpdateOut(selected=selected, adapt=adapt, ins=ins,
                     w=w, firing=firing, error=error, age=age)


def multi_signal_step_impl(
    state: NetworkState,
    signals: jax.Array,
    params: GSONParams,
    refresh_states: bool = True,
    find_winners: FindWinnersFn | None = None,
    signal_mask: jax.Array | None = None,
    update_phase: UpdatePhaseFn | None = None,
    fw_aux: Any = None,
) -> NetworkState:
    """One multi-signal iteration. ``signals``: (m, dim) float32.

    Un-jitted implementation — compose freely inside scans / shard_map.
    ``multi_signal_step`` below is the jitted entry point.

    ``signal_mask``: optional (m,) bool. Rows with mask False are inert:
    they never win the lock, never adapt/insert, and are not counted as
    consumed signals. This is how the fused superstep keeps a single jit
    signature while the paper's m-schedule varies per iteration — the
    signal buffer has a static ``max_parallel`` rows and the mask selects
    the first ``m_t`` of them. A masked call with k valid rows is
    equivalent to an unmasked call with those k signals (up to the
    random priorities used for collision resolution).

    ``update_phase``: optional ``UpdatePhaseFn`` replacing the dense
    Update phase (``update_phase_reference``) — the second pluggable
    backend axis, e.g. ``repro.kernels.update_phase``'s Pallas suite.

    ``fw_aux``: optional precomputed search structure for *stateful*
    Find Winners backends (``find_winners.stateful`` is True, e.g. the
    ``repro.ann`` hash-grid quantizer). Such backends expose
    ``build(w, active) -> aux`` and accept the result via
    ``__call__(..., aux=)``; loop drivers (fused superstep, fleet
    superstep, the indexed scan) carry the aux and rebuild it on the
    refresh cadence, then pass it here. ``None`` means the backend
    rebuilds internally — always correct, just unamortized.
    """
    if find_winners is None:
        find_winners = find_winners_reference
    if update_phase is None:
        update_phase = update_phase_reference
    C, K = state.capacity, state.max_deg
    m = signals.shape[0]
    m_eff = m if signal_mask is None else (
        jnp.sum(signal_mask).astype(jnp.int32))
    is_gng = params.model == "gng"
    is_soam = params.model == "soam"

    rng, k_lock = jax.random.split(state.rng)

    # ---- 1. Find Winners -------------------------------------------------
    with jax.named_scope("gson.find_winners"):
        if fw_aux is not None:
            wid, sid, d2b, _ = find_winners(signals, state.w, state.active,
                                            aux=fw_aux)
        else:
            wid, sid, d2b, _ = find_winners(signals, state.w, state.active)

    # ---- 2-3e. dense Update phase (pluggable backend) --------------------
    with jax.named_scope("gson.update"):
        up = update_phase(state, signals, wid, sid, d2b, k_lock, params,
                          signal_mask)
    # ---- 3f-3h. structural tail ---------------------------------------------
    with jax.named_scope("gson.tail"):
        selected, adapt, ins = up.selected, up.adapt, up.ins
        w, firing, error, age = up.w, up.firing, up.error, up.age
        n_sel = jnp.sum(selected).astype(jnp.int32)
        nbr = state.nbr

        # ---- 3f. GWR/SOAM unit insertion ------------------------------------
        active = state.active
        threshold = state.threshold
        topo_state = state.topo_state
        inconsistent = state.inconsistent_for
        n_active = state.n_active
        dropped_units = state.dropped_units

        free_order = jnp.argsort(active, stable=True)       # inactive first
        n_free = C - n_active

        if not is_gng:
            rank = jnp.cumsum(ins.astype(jnp.int32)) - 1
            fits = ins & (rank < n_free)
            dropped_units = dropped_units + jnp.sum(ins & ~fits)
            new_id = jnp.where(fits, free_order[jnp.clip(rank, 0, C - 1)], C)
            w_new = 0.5 * (w[jnp.clip(wid, 0, C - 1)] + signals)
            w = w.at[new_id].set(w_new, mode="drop")
            active = active.at[new_id].set(True, mode="drop")
            firing = firing.at[new_id].set(1.0, mode="drop")
            error = error.at[new_id].set(0.0, mode="drop")
            threshold = threshold.at[new_id].set(
                threshold[jnp.clip(wid, 0, C - 1)], mode="drop")
            topo_state = topo_state.at[new_id].set(0, mode="drop")
            inconsistent = inconsistent.at[new_id].set(0, mode="drop")
            n_active = n_active + jnp.sum(fits).astype(jnp.int32)

            # edges: (new, b) and (new, s); drop (b, s)
            e_a = jnp.concatenate([new_id, new_id])
            e_b = jnp.concatenate([wid, sid])
            e_m = jnp.concatenate([fits, fits])
            nbr, age, d1 = topo.insert_edges(nbr, age, e_a, e_b, e_m)
            nbr, age = topo.remove_edge_pairs(nbr, age, wid, sid, fits)
            # refresh/insert (b, s) for adapting signals
            nbr, age, d2_ = topo.insert_edges(nbr, age, wid, sid, adapt)
            dropped_edges = state.dropped_edges + d1 + d2_
        else:
            nbr, age, d2_ = topo.insert_edges(nbr, age, wid, sid, selected)
            dropped_edges = state.dropped_edges + d2_

        # ---- 3g. GNG periodic insertion at max-error units ------------------
        eff_old = state.signal_count - state.discarded
        eff_new = eff_old + n_sel
        if is_gng:
            k_cap = 8  # static cap on inserts per iteration
            n_ins = ((eff_new // params.gng_lambda)
                     - (eff_old // params.gng_lambda))
            n_ins = jnp.clip(n_ins, 0, k_cap)
            err_masked = jnp.where(active, error, -jnp.inf)
            _, q_ids = jax.lax.top_k(err_masked, k_cap)
            q_ids = q_ids.astype(jnp.int32)
            take = jnp.arange(k_cap) < n_ins
            # worst neighbor f of each q
            q_nb = nbr[q_ids]                                  # (k, K)
            q_nb_err = jnp.where(q_nb >= 0,
                                 error[jnp.clip(q_nb, 0, C - 1)], -jnp.inf)
            f_slot = jnp.argmax(q_nb_err, axis=1)
            f_ids = q_nb[jnp.arange(k_cap), f_slot]
            take = take & (f_ids >= 0)
            rank = jnp.cumsum(take.astype(jnp.int32)) - 1
            fits = take & (rank < n_free)
            dropped_units = dropped_units + jnp.sum(take & ~fits)
            new_id = jnp.where(fits, free_order[jnp.clip(rank, 0, C - 1)], C)
            f_safe = jnp.clip(f_ids, 0, C - 1)
            w_new = 0.5 * (w[q_ids] + w[f_safe])
            w = w.at[new_id].set(w_new, mode="drop")
            active = active.at[new_id].set(True, mode="drop")
            firing = firing.at[new_id].set(1.0, mode="drop")
            n_active = n_active + jnp.sum(fits).astype(jnp.int32)
            # error redistribution
            error = error.at[jnp.where(fits, q_ids, C)].multiply(
                params.gng_alpha, mode="drop")
            error = error.at[jnp.where(fits, f_ids, C)].multiply(
                params.gng_alpha, mode="drop")
            error = error.at[new_id].set(
                params.gng_alpha * error[q_ids], mode="drop")
            e_a = jnp.concatenate([new_id, new_id])
            e_b = jnp.concatenate([q_ids, f_ids])
            e_m = jnp.concatenate([fits, fits])
            nbr, age, d3 = topo.insert_edges(nbr, age, e_a, e_b, e_m)
            nbr, age = topo.remove_edge_pairs(nbr, age, q_ids, f_ids, fits)
            dropped_edges = dropped_edges + d3
            # global error decay, once per effective signal
            error = error * (1.0 - params.gng_beta) ** n_sel

        # ---- 3h. expiry + pruning -------------------------------------------
        nbr, age, _ = topo.expire_edges(nbr, age, params.age_max)
        active, _ = topo.prune_isolated(active, nbr, firing)
        n_active = jnp.sum(active).astype(jnp.int32)
        nbr = jnp.where(active[:, None], nbr, jnp.int32(-1))
        nbr, age = topo.drop_edges_to_inactive(nbr, age, active)

        out = state.replace(
            w=w, active=active, nbr=nbr, age=age, error=error, firing=firing,
            threshold=threshold, topo_state=topo_state,
            inconsistent_for=inconsistent, n_active=n_active,
            signal_count=state.signal_count + m_eff,
            discarded=state.discarded + (m_eff - n_sel),
            dropped_edges=dropped_edges, dropped_units=dropped_units, rng=rng,
        )
    # ---- 3i. SOAM: topology states + adaptive insertion threshold --------
    if is_soam and refresh_states:
        with jax.named_scope("gson.refresh"):
            out = refresh_topology(out, params)
    return out


# ``state`` is donated: NetworkState is by far the largest buffer in the
# hot loop and every caller rebinds it (``state = multi_signal_step(state,
# ...)``), so XLA updates the pool in place instead of copying it each
# call. Donation invalidates the caller's input buffers — re-feeding the
# same state must go through ``multi_signal_step_impl`` (un-jitted or
# under a caller-owned jit), as the benchmarks do.
multi_signal_step = jax.jit(
    multi_signal_step_impl,
    static_argnames=("params", "refresh_states", "find_winners",
                     "update_phase"),
    donate_argnames=("state",))


def soam_converged(state: NetworkState) -> jax.Array:
    """Paper's termination: every unit's neighborhood is a (patch of a)
    disk — threshold-free. Requires a fresh ``topo_state``."""
    stable = ((state.topo_state == DISK) | (state.topo_state == DISK + 1))
    return jnp.all(jnp.where(state.active, stable, True)) & (
        state.n_active >= 4)
