"""Windowed approximate top-2 (the ``ann-windowed`` backend).

The MXU-friendly two-stage search of ``jax.experimental.ann``
(arXiv:2206.14286), specialized to the engine's top-2 contract:

  stage 1  partition the capacity axis into L windows and take the
           top-1 of each — the distance matrix comes from the same
           quadratic-expansion matmul the exact backends use (one MXU
           contraction), and the per-window reduction is a single
           min/argmin pass instead of the reference's two full masked
           passes over ``(m, capacity)``;
  stage 2  exact top-2 rerank (:func:`repro.ann.rerank.exact_top2`)
           over the L per-window champions.

Windows are *interleaved* (unit i -> window ``i % L``) rather than
contiguous: growing networks allocate correlated ids for spatially
nearby units (a unit and its graph neighbors are inserted together),
and the second winner is lost exactly when it shares the winner's
window — striding decorrelates ids from space, so measured recall
tracks the uniform-assignment birthday model (:mod:`repro.ann.recall`)
instead of falling below it.

The winner itself is always exact (it wins its own window), so the
only fallible output is the *second* — lost exactly when it shares the
winner's window (probability ~1/L, the birthday model). The default
``refine=True`` closes that hole with one cheap extra pass: the
winner's window column (``capacity / L`` entries) is re-read exactly
and its runner-up merged into the rerank set. Any true second outside
the winner's window is already some other window's champion, so the
refined rerank set provably contains the true top-2 — the k=2 search
becomes exact while the reduction stays a fraction of the reference's
two full masked passes. ``refine=False`` exposes the pure
birthday-collision regime (recall ~ exp(-1/L)) that
:mod:`repro.ann.recall` models and ``tests/test_ann.py`` measures.

With ``n_windows >= capacity`` every window holds one unit and the
search degenerates to the exact reference — bitwise, including
tie-breaks — which is the parity hook ``tests/test_ann.py`` pins.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.ann.recall import shortlist_size
from repro.ann.rerank import exact_top2


@dataclass(frozen=True)
class WindowedFindWinners:
    """A ``FindWinnersFn``: windowed top-1 -> exact top-2 rerank.

    Frozen/hashable — instances are jit cache keys for every program
    that threads them (step / superstep / fleet), like every other
    registered backend. ``recall_target`` is carried for reporting;
    ``n_windows`` is the derived knob the search actually uses.
    """

    n_windows: int
    recall_target: float | None = None
    refine: bool = True            # winner-window runner-up merge

    def __post_init__(self):
        if self.n_windows < 2:
            raise ValueError(
                f"n_windows must be >= 2 for a top-2 search, got "
                f"{self.n_windows}")

    def __call__(self, signals: jax.Array, w: jax.Array,
                 active: jax.Array):
        m = signals.shape[0]
        C = w.shape[0]
        L = min(self.n_windows, C)
        rows = -(-C // L)                       # units per window (ceil)

        x2 = jnp.sum(signals * signals, axis=1, keepdims=True)    # (m, 1)
        w2 = jnp.sum(w * w, axis=1)                               # (C,)
        xw = jnp.matmul(signals, w.T,
                        precision=jax.lax.Precision.HIGHEST)
        d2 = x2 - 2.0 * xw + w2[None, :]                          # (m, C)
        d2 = jnp.where(active[None, :], d2, jnp.inf)

        pad = rows * L - C
        if pad:
            d2 = jnp.pad(d2, ((0, 0), (0, pad)),
                         constant_values=jnp.inf)
        # column j*L + l lands in window l at row j: the interleaved
        # assignment (unit id stride L within a window)
        d2w = d2.reshape(m, rows, L)
        vals = jnp.min(d2w, axis=1)                               # (m, L)
        # argmin returns the FIRST minimum; rows are ordered by
        # ascending id within a window, so ties break to the lowest id
        # — the engine-wide tie contract
        row = jnp.argmin(d2w, axis=1).astype(jnp.int32)           # (m, L)
        ids = row * L + jnp.arange(L, dtype=jnp.int32)[None, :]
        if not self.refine:
            return exact_top2(vals, ids)
        # refinement: the true second can only be missing when it
        # shares the winner's window — re-read that one column exactly
        # (O(m * capacity / L)) and merge its runner-up. The merged set
        # then provably contains the true top-2, and the final rerank's
        # tie contract does the rest.
        wid, _, _, _ = exact_top2(vals, ids)
        lstar = wid % L                                           # (m,)
        col = jnp.take_along_axis(
            d2w, lstar[:, None, None], axis=2)[..., 0]            # (m, rows)
        col_ids = (jnp.arange(rows, dtype=jnp.int32)[None, :] * L
                   + lstar[:, None])
        # runner-up within the winner's window (mask the winner's slot)
        col = jnp.where(col_ids == wid[:, None], jnp.inf, col)
        r2 = jnp.min(col, axis=1)
        r2_id = jnp.min(jnp.where(col <= r2[:, None], col_ids,
                                  jnp.int32(2 ** 30)), axis=1)
        return exact_top2(
            jnp.concatenate([vals, r2[:, None]], axis=1),
            jnp.concatenate([ids, r2_id[:, None]], axis=1))


def windowed_find_winners(recall_target: float = 0.95
                          ) -> WindowedFindWinners:
    """Construct the backend from a recall target: the window count is
    the birthday-model shortlist size for top-2 at that recall."""
    return WindowedFindWinners(
        n_windows=shortlist_size(recall_target, k=2),
        recall_target=recall_target)
