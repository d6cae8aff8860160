"""jit'd public wrapper for the Find Winners kernel (paper Sec. 2.5):
shape padding on misaligned tiles only, in-kernel activity masking,
and the engine-facing ``FindWinnersFn`` adapter."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.find_winners.kernel import LARGE, find_winners_pallas_padded


def _round_up(x: int, to: int) -> int:
    return (x + to - 1) // to * to


@partial(jax.jit, static_argnames=("block_m", "block_c", "interpret"))
def find_winners_op(signals: jax.Array, w: jax.Array, active: jax.Array,
                    *, block_m: int = 256, block_c: int = 512,
                    interpret: bool | None = None):
    """Top-2 nearest active units for each signal, via the Pallas kernel.

    Returns (top2_d2 (m, 2) f32, top2_ids (m, 2) i32).
    Shapes need not be tile-aligned — but tile-aligned inputs (the fused
    superstep's static power-of-two signal buffer, pow-of-two capacity
    pools) pass through with ZERO copies: activity masking happens
    inside the kernel via the (1, C) activity row, and signals/w are
    padded only when their static shape is actually misaligned.
    """
    interpret = interpret_mode(interpret)
    m, d = signals.shape
    c = w.shape[0]
    block_m = min(block_m, _round_up(m, 8))
    block_c = min(block_c, _round_up(c, 128))
    mp = _round_up(m, block_m)
    cp = _round_up(c, block_c)

    if mp != m:
        signals = jnp.pad(signals, ((0, mp - m), (0, 0)))
    if cp != c:
        w = jnp.pad(w, ((0, cp - c), (0, 0)))
        active = jnp.pad(active, (0, cp - c))   # pad slots are inactive
    act = active.astype(jnp.float32)[None, :]

    out_d, out_i = find_winners_pallas_padded(
        signals, w, act, block_m=block_m, block_c=block_c,
        interpret=interpret)
    out_d, out_i = out_d[:m], out_i[:m]
    # degenerate case (<2 active units): duplicate the winner into the
    # second slot instead of reporting a masked/padded pseudo-unit
    invalid2 = out_d[:, 1] >= jnp.float32(LARGE / 2)
    out_i = out_i.at[:, 1].set(
        jnp.where(invalid2, out_i[:, 0], out_i[:, 1]))
    out_d = out_d.at[:, 1].set(
        jnp.where(invalid2, out_d[:, 0], out_d[:, 1]))
    return out_d, out_i


def make_pallas_find_winners(block_m: int = 256, block_c: int = 512,
                             interpret: bool | None = None):
    """Adapter matching the engine's FindWinnersFn signature."""

    def fw(signals, w, active):
        d2, ids = find_winners_op(signals, w, active, block_m=block_m,
                                  block_c=block_c, interpret=interpret)
        return (ids[:, 0], ids[:, 1],
                jnp.maximum(d2[:, 0], 0.0), jnp.maximum(d2[:, 1], 0.0))

    return fw
