"""Quality / faithfulness metrics for reconstructed networks.

Euler characteristic and genus are host-side (numpy) reporting utilities:
for a converged SOAM triangulation V - E + F must equal 2 - 2*genus of
the sampled surface — the strongest faithfulness check available.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gson.state import STATE_NAMES, NetworkState


def quantization_error(state: NetworkState, probes: jax.Array) -> jax.Array:
    """Mean squared distance from probe signals to their winner."""
    x2 = jnp.sum(probes * probes, axis=1, keepdims=True)
    w2 = jnp.sum(state.w * state.w, axis=1)
    # HIGHEST: QE drives convergence, and a single bf16 pass on a TPU
    # would swamp a converged network's d^2 in rounding error
    xw = jnp.matmul(probes, state.w.T, precision=jax.lax.Precision.HIGHEST)
    d2 = x2 - 2.0 * xw + w2[None, :]
    d2 = jnp.where(state.active[None, :], d2, jnp.inf)
    return jnp.mean(jnp.maximum(jnp.min(d2, axis=1), 0.0))


def qe_convergence(state: NetworkState, probes: jax.Array,
                   threshold: float) -> tuple[jax.Array, jax.Array]:
    """GNG/GWR termination predicate: (done, qe), both device scalars.

    Shared by the host engine loop and the fused on-device superstep so
    the two paths cannot drift.
    """
    qe = quantization_error(state, probes)
    done = (qe < threshold) & (state.n_active > 8)
    return done, qe


def edge_count(state: NetworkState) -> int:
    return int(np.sum(np.asarray(state.nbr) >= 0)) // 2


def state_histogram(state: NetworkState) -> dict:
    st = np.asarray(state.topo_state)
    act = np.asarray(state.active)
    return {name: int(np.sum(act & (st == i)))
            for i, name in enumerate(STATE_NAMES)}


def euler_characteristic(state: NetworkState) -> tuple[int, int, int, int]:
    """(V, E, F, chi) from the neighbor lists; F = 3-cliques."""
    nbr = np.asarray(state.nbr)
    active = np.asarray(state.active)
    ids = np.nonzero(active)[0]
    v = len(ids)
    adj = {int(i): set(int(j) for j in nbr[i] if j >= 0) for i in ids}
    e = sum(len(s) for s in adj.values()) // 2
    f = 0
    for a, nb in adj.items():
        for b in nb:
            if b <= a:
                continue
            f += len([c for c in (adj[a] & adj[b]) if c > b])
    chi = v - e + f
    return v, e, f, chi


def genus(state: NetworkState) -> float:
    _, _, _, chi = euler_characteristic(state)
    return (2 - chi) / 2.0


class TopologyQuality(NamedTuple):
    """Verdict of :func:`topology_quality` (all host-side scalars)."""

    chi: int              # Euler characteristic of the candidate
    exact_chi: int        # Euler characteristic of the exact run
    chi_match: bool
    qe: float             # candidate quantization error (nan: no probes)
    exact_qe: float
    qe_rel: float         # (qe - exact_qe) / exact_qe, signed
    qe_ok: bool
    ok: bool              # chi_match and qe_ok


def topology_quality(state: NetworkState, exact_state: NetworkState,
                     probes=None, qe_tol: float = 0.05) -> TopologyQuality:
    """Quality-not-bitwise acceptance gate for approximate backends.

    An approximate Find Winners backend (``repro.ann``) is accepted
    when the network it grows is *topologically* as good as the exact
    backend's: equal Euler characteristic (same reconstructed surface
    class) and quantization error within ``qe_tol`` of the exact run's
    — one-sided, since a *lower* QE is never a defect. ``probes=None``
    skips the QE clause (chi only).
    """
    _, _, _, chi = euler_characteristic(state)
    _, _, _, exact_chi = euler_characteristic(exact_state)
    chi_match = chi == exact_chi
    if probes is None:
        return TopologyQuality(chi, exact_chi, chi_match,
                               float("nan"), float("nan"), float("nan"),
                               True, chi_match)
    qe = float(quantization_error(state, probes))
    exact_qe = float(quantization_error(exact_state, probes))
    qe_rel = (qe - exact_qe) / max(exact_qe, 1e-30)
    qe_ok = qe <= exact_qe * (1.0 + qe_tol)
    return TopologyQuality(chi, exact_chi, chi_match, qe, exact_qe,
                           qe_rel, qe_ok, chi_match and qe_ok)


def summary(state: NetworkState) -> dict:
    return {
        "units": int(state.n_active),
        "edges": edge_count(state),
        "signals": int(state.signal_count),
        "discarded": int(state.discarded),
        "dropped_edges": int(state.dropped_edges),
        "dropped_units": int(state.dropped_units),
        "states": state_histogram(state),
    }
