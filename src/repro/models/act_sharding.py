"""Activation-sharding constraints, injected without threading rules
through every model signature.

The step factories (launch/steps.py) install an ActivationSharding for
the duration of tracing; model code calls ``constrain(x, kind)`` at layer
boundaries. Outside any context this is the identity, so smoke tests and
the GSON engine never touch mesh state.

Kinds:
  "residual"  — the (B, S, D) layer carry. Baseline: batch only.
                With ``seq_shard`` (the beyond-paper SP optimization,
                see EXPERIMENTS.md §Perf): batch x (seq -> model), which
                divides the per-layer remat save by the TP width.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


@dataclass(frozen=True)
class ActivationSharding:
    batch_axes: tuple = ()
    seq_axis: str | None = None     # SP: shard S of (B, S, D) residuals

    def residual_spec(self, shape, axis_sizes: dict) -> P | None:
        if len(shape) != 3:
            return None
        bat_axes, prod = [], 1
        for a in self.batch_axes:   # greedy: divisibility vs the product
            size = max(axis_sizes.get(a, 1), 1)
            if shape[0] % (prod * size) == 0:
                bat_axes.append(a)
                prod *= size
        bat = tuple(bat_axes) if bat_axes else None
        seq = self.seq_axis
        if seq is not None and shape[1] % max(
                axis_sizes.get(seq, 1), 1) != 0:
            seq = None
        if bat is None and seq is None:
            return None
        return P(bat, seq, None)


@contextlib.contextmanager
def activation_sharding(spec: ActivationSharding, mesh):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = (spec, mesh)
    try:
        yield
    finally:
        _STATE.ctx = prev


def constrain(x: jax.Array, kind: str = "residual") -> jax.Array:
    """kind="residual": the (B,S,D) layer carry — seq-sharded under SP.
    kind="matmul_in": post-norm activations entering weight matmuls —
    explicitly gathered back to full sequence. Without this, GSPMD
    resolves the (seq->model) x (mlp->model) operand conflict by
    replicating the WEIGHTS (f32, per layer, per microbatch — the
    dominant collective in the naive-SP dry-run); gathering the much
    smaller bf16 activations is the Megatron-SP pattern."""
    ctx = getattr(_STATE, "ctx", None)
    if ctx is None:
        return x
    spec, mesh = ctx
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if kind == "residual":
        ps = spec.residual_spec(x.shape, sizes)
    elif kind == "matmul_in" and spec.seq_axis is not None:
        ps = spec.residual_spec(x.shape, sizes)
        if ps is not None:
            ps = P(ps[0], None, *([None] * (len(x.shape) - 2)))
    else:
        ps = None
    if ps is None:
        return x
    # inside a partially-manual shard_map (e.g. the pod-compression
    # path) the constraint must be built on the CONTEXT abstract mesh,
    # whose axis types carry the Manual markings
    am = jax.sharding.get_abstract_mesh()
    target = am if (am is not None and not am.empty) else mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(target, ps))
