"""Pallas TPU kernels for the multi-signal Update phase (paper Sec. 2.5).

The paper parallelizes Find Winners and measures Update becoming the
new bottleneck (Fig. 8); parallelizing Update is its named future work.
This suite is that step, as a TPU-native rethink of the CUDA
data-partitioning recipe (one thread per signal, atomics into the unit
pool):

  * the GPU's atomic scatter-adds become **one-hot matmuls on the MXU**:
    a (block_m, block_c) indicator of "signal i writes unit c",
    contracted against the per-signal payloads. Both factors live in
    VMEM; the per-unit output block is resident across the signal-tile
    grid axis (flash-attention-style streaming accumulation), so each
    unit tile is written to HBM exactly once per phase;
  * the GPU's atomicMin winner lock becomes a **masked min-reduce**
    over the same indicator (`_lock_kernel`) — deterministic, and
    bit-identical to the reference scatter-min;
  * edge aging + the winner-second age refresh fuse into a single
    elementwise pass over the (capacity, max_deg) age table
    (`_edge_age_kernel`) — one HBM round trip instead of four.

Three kernels, composed by ``ops.update_phase_op``:

  1. ``_lock_kernel``      — per-unit minimum signal priority (the
     m-signal conflict resolution, Sec. 2.2).
  2. ``_update_accum_kernel`` — fused per-unit accumulators: winner
     weight pull (exact: post-lock winners are distinct, so the one-hot
     contraction *copies* rather than sums), neighbor pull accumulators,
     habituation decrements, GNG error sums, and the winner indicator
     that drives edge aging.
  3. ``_edge_age_kernel``  — edge-age increment (winner rows + mirrored
     slots, stable-stable edges protected) and winner-second reset.

Masking is in-kernel (sentinel ids never match a unit column; masked
priorities are +LARGE), so tile-aligned inputs pass through with zero
copies and padding happens only on misaligned shapes — same contract as
``repro.kernels.find_winners``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain ints/floats: jnp scalars would be captured consts in the kernel
BIG_PRIO = jnp.iinfo(jnp.int32).max


def _col_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """(bm, bc) x (bm, n) -> (bc, n), contracting the signal axis on
    the MXU with f32 accumulation. HIGHEST precision: the one-hot
    contractions must copy f32 payloads exactly, which one bf16 pass
    would round to ~3 significant digits."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# 1. winner lock: per-unit min priority (the paper's collision rule)


def _lock_kernel(wid_ref, prio_ref, best_ref, *, block_c: int):
    i = pl.program_id(0)          # unit tile (output-resident)
    j = pl.program_id(1)          # signal tile (accumulation axis)

    wid = wid_ref[...]            # (bm, 1) i32
    prio = prio_ref[...]          # (bm, 1) i32, BIG_PRIO on masked rows
    ids = i * block_c + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_c), 1)
    onehot = wid == ids                                     # (bm, bc)
    masked = jnp.where(onehot, prio, BIG_PRIO)
    blk = jnp.min(masked, axis=0, keepdims=True)            # (1, bc)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = blk

    @pl.when(j > 0)
    def _merge():
        best_ref[...] = jnp.minimum(best_ref[...], blk)


@functools.partial(jax.jit,
                   static_argnames=("capacity", "block_m", "block_c",
                                    "interpret"))
def winner_lock_pallas_padded(
    wid: jax.Array,        # (M, 1) i32, M % block_m == 0
    prio: jax.Array,       # (M, 1) i32, BIG_PRIO on masked/padded rows
    capacity: int,         # C % block_c == 0
    *,
    block_m: int = 512,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-unit minimum priority over all signals: the scatter-min of
    ``multi.winner_lock`` as a tiled masked min-reduce. Returns (1, C)."""
    m = wid.shape[0]
    grid = (capacity // block_c, m // block_m)
    return pl.pallas_call(
        functools.partial(_lock_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, capacity), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(wid, prio)


# ---------------------------------------------------------------------------
# 2. fused dense-update accumulators


def _update_accum_kernel(x_ref, wid_ref, sel_ref, adapt_ref, sb_ref,
                         db_ref, decb_ref, nb_ref, sn_ref, decn_ref,
                         w_ref,
                         w1_ref, nsc_ref, nsx_ref, err_ref, decbu_ref,
                         decnu_ref, wind_ref, *, block_c: int,
                         max_deg: int):
    i = pl.program_id(0)          # unit tile (output-resident)
    j = pl.program_id(1)          # signal tile (accumulation axis)

    x = x_ref[...]                # (bm, d)
    wid = wid_ref[...]            # (bm, 1) i32
    sel = sel_ref[...]            # (bm, 1) f32 0/1 lock survivors
    adp = adapt_ref[...]          # (bm, 1) f32 0/1 adapting survivors
    sb = sb_ref[...]              # (bm, 1) f32 winner pull scale
    db = db_ref[...]              # (bm, 1) f32 winner distance^2
    decb = decb_ref[...]          # (bm, 1) f32 winner habituation dec
    w = w_ref[...]                # (bc, d)

    ids = i * block_c + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_c), 1)
    o_eq = wid == ids                                       # (bm, bc)
    o_adapt = (o_eq & (adp > 0.0)).astype(jnp.float32)
    o_sel = (o_eq & (sel > 0.0)).astype(jnp.float32)

    # winner pull: post-lock winners are DISTINCT, so each unit column
    # has at most one nonzero — the contractions below *copy* the
    # winner signal / its scale exactly, and
    #   dw = scale * (x_winner - w)
    # reproduces the reference's delta_b bit-for-bit.
    scale_vec = _col_dot(o_adapt, sb)                       # (bc, 1)
    sel_x = _col_dot(o_adapt, x)                            # (bc, d)
    dw = scale_vec * (sel_x - w)

    err = _col_dot(o_sel, db)                               # (bc, 1)
    decb_u = _col_dot(o_adapt, decb)                        # (bc, 1)
    wind = _col_dot(o_sel, sel)                             # (bc, 1) 0/1

    # neighbor pulls: per neighbor slot, a scale-weighted one-hot of
    # "signal i pulls unit c"; summed over slots into one (bm, bc)
    # weight matrix, then contracted once on the MXU. Collisions
    # (several signals sharing a neighbor) sum here in tile order —
    # the documented float-tolerance vs the reference scatter order.
    wn = jnp.zeros_like(o_adapt)
    dn = jnp.zeros_like(o_adapt)
    for k in range(max_deg):
        o_k = (nb_ref[:, k:k + 1] == ids).astype(jnp.float32)
        wn = wn + o_k * sn_ref[:, k:k + 1]
        dn = dn + o_k * decn_ref[:, k:k + 1]
    ones = jnp.ones_like(sb)
    nsc = _col_dot(wn, ones)                                # (bc, 1)
    nsx = _col_dot(wn, x)                                   # (bc, d)
    decn_u = _col_dot(dn, ones)                             # (bc, 1)

    @pl.when(j == 0)
    def _init():
        w1_ref[...] = w + dw
        nsc_ref[...] = nsc
        nsx_ref[...] = nsx
        err_ref[...] = err
        decbu_ref[...] = decb_u
        decnu_ref[...] = decn_u
        wind_ref[...] = wind

    @pl.when(j > 0)
    def _accum():
        w1_ref[...] += dw
        nsc_ref[...] += nsc
        nsx_ref[...] += nsx
        err_ref[...] += err
        decbu_ref[...] += decb_u
        decnu_ref[...] += decn_u
        wind_ref[...] += wind


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_c", "interpret"))
def update_accum_pallas_padded(
    signals: jax.Array,    # (M, d) f32, M % block_m == 0
    wid: jax.Array,        # (M, 1) i32
    sel: jax.Array,        # (M, 1) f32 0/1
    adapt: jax.Array,      # (M, 1) f32 0/1
    scale_b: jax.Array,    # (M, 1) f32
    d2b: jax.Array,        # (M, 1) f32
    dec_b: jax.Array,      # (M, 1) f32
    nb: jax.Array,         # (M, K) i32, -1 on invalid slots
    scale_n: jax.Array,    # (M, K) f32, 0 on invalid slots
    dec_n: jax.Array,      # (M, K) f32, 0 on invalid slots
    w: jax.Array,          # (C, d) f32, C % block_c == 0
    *,
    block_m: int = 256,
    block_c: int = 256,
    interpret: bool = False,
):
    """One streaming pass over the signal tiles; returns per-unit
    ``(w1, nsc, nsx, err, dec_b, dec_n, win_ind)`` — the winner-updated
    weights plus every accumulator the epilogue needs."""
    m, d = signals.shape
    c = w.shape[0]
    k = nb.shape[1]
    grid = (c // block_c, m // block_m)
    sig_spec = lambda i, j: (j, 0)                          # noqa: E731
    unit_spec = lambda i, j: (i, 0)                         # noqa: E731
    return pl.pallas_call(
        functools.partial(_update_accum_kernel, block_c=block_c,
                          max_deg=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), sig_spec),
            pl.BlockSpec((block_m, 1), sig_spec),
            pl.BlockSpec((block_m, 1), sig_spec),
            pl.BlockSpec((block_m, 1), sig_spec),
            pl.BlockSpec((block_m, 1), sig_spec),
            pl.BlockSpec((block_m, 1), sig_spec),
            pl.BlockSpec((block_m, 1), sig_spec),
            pl.BlockSpec((block_m, k), sig_spec),
            pl.BlockSpec((block_m, k), sig_spec),
            pl.BlockSpec((block_m, k), sig_spec),
            pl.BlockSpec((block_c, d), unit_spec),
        ],
        out_specs=[
            pl.BlockSpec((block_c, d), unit_spec),
            pl.BlockSpec((block_c, 1), unit_spec),
            pl.BlockSpec((block_c, d), unit_spec),
            pl.BlockSpec((block_c, 1), unit_spec),
            pl.BlockSpec((block_c, 1), unit_spec),
            pl.BlockSpec((block_c, 1), unit_spec),
            pl.BlockSpec((block_c, 1), unit_spec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, d), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, d), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(signals, wid, sel, adapt, scale_b, d2b, dec_b, nb, scale_n,
      dec_n, w)


# ---------------------------------------------------------------------------
# 3. fused edge aging + winner-second refresh


def _edge_age_kernel(age_ref, valid_ref, win_ref, winat_ref, prot_ref,
                     protat_ref, reset_ref, out_ref):
    age = age_ref[...]            # (bc, K)
    valid = valid_ref[...]        # (bc, K) 1.0 where nbr slot occupied
    win = win_ref[...]            # (bc, 1) 1.0 where unit is a winner
    winat = winat_ref[...]        # (bc, K) 1.0 where nbr is a winner
    prot = prot_ref[...]          # (bc, 1) 1.0 stable (SOAM)
    protat = protat_ref[...]      # (bc, K) 1.0 stable neighbor
    reset = reset_ref[...]        # (bc, K) 1.0 on winner-second slots

    # forward (whole winner row) + mirror (slot pointing back at a
    # winner) increments; stable-stable edges crystallize (no aging);
    # the winner-second edge is refreshed LAST, like the reference.
    keep = prot * protat
    inc = (win + winat) * valid * (1.0 - keep)
    out_ref[...] = jnp.where(reset > 0.0, 0.0, age + inc)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def edge_age_pallas_padded(
    age: jax.Array,        # (C, K) f32, C % block_c == 0
    valid: jax.Array,      # (C, K) f32 0/1
    win: jax.Array,        # (C, 1) f32 0/1
    winat: jax.Array,      # (C, K) f32 0/1
    prot: jax.Array,       # (C, 1) f32 0/1
    protat: jax.Array,     # (C, K) f32 0/1
    reset: jax.Array,      # (C, K) f32 0/1
    *,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Edge-age increment + winner-second reset in ONE pass over the
    age table (the reference path takes four: forward scatter, mirror
    scatter, slot search, reset scatter)."""
    c, k = age.shape
    grid = (c // block_c,)
    row = lambda i: (i, 0)                                  # noqa: E731
    return pl.pallas_call(
        _edge_age_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, k), row),
            pl.BlockSpec((block_c, k), row),
            pl.BlockSpec((block_c, 1), row),
            pl.BlockSpec((block_c, k), row),
            pl.BlockSpec((block_c, 1), row),
            pl.BlockSpec((block_c, k), row),
            pl.BlockSpec((block_c, k), row),
        ],
        out_specs=pl.BlockSpec((block_c, k), row),
        out_shape=jax.ShapeDtypeStruct((c, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(age, valid, win, winat, prot, protat, reset)
