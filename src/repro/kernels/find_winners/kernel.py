"""Pallas TPU kernel for the multi-signal Find Winners phase.

TPU-native rethink of the paper's CUDA kernel (Sec. 2.5):

  GPU: one thread per signal; a block cooperatively stages a tile of
       reference vectors in shared memory (coalesced), then each thread
       scans the tile sequentially keeping top-2 registers.

  TPU: grid (signal-tiles x unit-tiles). Each step stages one
       (block_c, dim) tile of reference vectors in VMEM via BlockSpec
       (the shared-memory staging analogue), forms all pairwise squared
       distances with ONE MXU matmul through the quadratic expansion
         ||x - w||^2 = ||x||^2 - 2 x.w + ||w||^2,
       and maintains a *streaming top-2* in the resident output block
       across the unit-tile grid axis (flash-attention-style online
       reduction). The per-thread sequential scan becomes a systolic
       matmul; the top-2 registers become an output-block carry.

Inactive unit slots are masked via a bias row (+LARGE) instead of
branching — SIMT divergence concerns do not exist here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LARGE = 1e30  # plain float: jnp scalars would be captured consts in the kernel


def _two_smallest_with_ids(d2: jax.Array, ids: jax.Array):
    """Row-wise two smallest values (+their ids) of (bm, n). Ties -> lowest id."""
    big_id = jnp.int32(2**30)
    m1 = jnp.min(d2, axis=1, keepdims=True)                      # (bm, 1)
    is1 = d2 <= m1
    i1 = jnp.min(jnp.where(is1, ids, big_id), axis=1, keepdims=True)
    masked = jnp.where(ids == i1, LARGE, d2)
    m2 = jnp.min(masked, axis=1, keepdims=True)
    is2 = masked <= m2
    i2 = jnp.min(jnp.where(is2, ids, big_id), axis=1, keepdims=True)
    return (jnp.concatenate([m1, m2], axis=1),
            jnp.concatenate([i1, i2], axis=1).astype(jnp.int32))


def _find_winners_kernel(x_ref, w_ref, act_ref, out_d_ref, out_i_ref,
                         *, block_c: int):
    j = pl.program_id(1)

    x = x_ref[...]                       # (bm, d)  VMEM
    w = w_ref[...]                       # (bc, d)  VMEM staged tile
    act = act_ref[...]                   # (1, bc)  1.0 active / 0.0 masked

    # ||x||^2 - 2 x.w + ||w||^2 — the matmul hits the MXU. HIGHEST:
    # one bf16 pass would round x.w to ~3 digits, far coarser than a
    # converged winner's d^2, and scramble the top-2.
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    w2 = jnp.sum(w * w, axis=1)[None, :]
    xw = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                       # (bm, bc)
    # inactive/padded slots masked IN the kernel (bias add, no branch) —
    # the wrapper no longer materializes a bias row in HBM per call
    d2 = jnp.maximum(x2 - 2.0 * xw + w2, 0.0) + (1.0 - act) * LARGE

    ids = j * block_c + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    blk_d, blk_i = _two_smallest_with_ids(d2, ids)

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = blk_d
        out_i_ref[...] = blk_i

    @pl.when(j > 0)
    def _merge():
        cat_d = jnp.concatenate([out_d_ref[...], blk_d], axis=1)  # (bm, 4)
        cat_i = jnp.concatenate([out_i_ref[...], blk_i], axis=1)
        md, mi = _two_smallest_with_ids(cat_d, cat_i)
        out_d_ref[...] = md
        out_i_ref[...] = mi


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_c", "interpret"))
def find_winners_pallas_padded(
    signals: jax.Array,     # (M, d) f32, M % block_m == 0
    w: jax.Array,           # (C, d) f32, C % block_c == 0
    act: jax.Array,         # (1, C) f32, 1.0 active / 0.0 inactive-or-pad
    *,
    block_m: int = 256,
    block_c: int = 512,
    interpret: bool = False,
):
    m, d = signals.shape
    c = w.shape[0]
    grid = (m // block_m, c // block_c)
    out_d, out_i = pl.pallas_call(
        functools.partial(_find_winners_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, 2), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 2), jnp.float32),
            jax.ShapeDtypeStruct((m, 2), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(signals, w, act)
    return out_d, out_i
