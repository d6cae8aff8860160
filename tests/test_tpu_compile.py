"""The Pallas kernels compile through Mosaic for a TPU v5e.

Interpret mode (how every other kernel test runs on the CPU) accepts
constructs the TPU compiler refuses, so these tests compile the
kernels of the main path for a *described* v5e chip — the TPU compiler
is installed even where no chip is attached — at the paper's width:
8192 signals x 32768 units, d=3, K=16, with ``interpret=False``, each
kernel alone and inside the fused superstep. The compiled text must
carry the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and a test worker
that describes it keeps it until it exits.

A CPU test pins the other half of running on a TPU: the f32 distance
matmuls must ask for HIGHEST precision, since a TPU's default is one
bf16 pass.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import gson
from repro.configs.soam_paper import paper_spec
from repro.core.gson import fleet as fleet_core
from repro.core.gson import metrics
from repro.core.gson.distributed import (replicated_update_phase,
                                         signal_sharded_find_winners)
from repro.core.gson.multi import (find_winners_reference,
                                   multi_signal_step_impl)
from repro.core.gson.state import GSONParams, init_state
from repro.kernels.find_winners.kernel import find_winners_pallas_padded
from repro.kernels.find_winners.ops import make_pallas_find_winners
from repro.kernels.update_phase.kernel import (edge_age_pallas_padded,
                                               update_accum_pallas_padded,
                                               winner_lock_pallas_padded)
from repro.kernels.update_phase.ops import make_pallas_update_phase

M, C, D, K = 8192, 32768, 3, 16        # paper width (configs.soam_paper)
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, one_chip):
    """(fn, argument shapes) of one kernel, block sizes as the ops
    wrappers pass them."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "find_winners":
        return (lambda x, w, a: find_winners_pallas_padded(
            x, w, a, block_m=256, block_c=512, interpret=False),
            (s((M, D), f32), s((C, D), f32), s((1, C), f32)))
    if name == "winner_lock":
        return (lambda wid, prio: winner_lock_pallas_padded(
            wid, prio, C, block_m=256, block_c=256, interpret=False),
            (s((M, 1), i32), s((M, 1), i32)))
    if name == "update_accum":
        return (lambda *a: update_accum_pallas_padded(
            *a, block_m=256, block_c=256, interpret=False),
            (s((M, D), f32), s((M, 1), i32))
            + tuple(s((M, 1), f32) for _ in range(5))
            + (s((M, K), i32), s((M, K), f32), s((M, K), f32),
               s((C, D), f32)))
    assert name == "edge_age"
    return (lambda *a: edge_age_pallas_padded(*a, block_c=256,
                                              interpret=False),
            (s((C, K), f32), s((C, K), f32), s((C, 1), f32),
             s((C, K), f32), s((C, 1), f32), s((C, K), f32),
             s((C, K), f32)))


@pytest.mark.parametrize("name", ["find_winners", "winner_lock",
                                  "update_accum", "edge_age"])
def test_kernel_compiles_for_v5e_at_paper_width(one_chip, name):
    fn, shapes = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_signal_mesh_step_compiles_for_four_chips(topo):
    """Signal-axis data partitioning with both Pallas phases: the
    Update kernel must sit in an explicit (replicated) shard_map, since
    Mosaic kernels cannot be partitioned automatically."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("gson",))
    fw = signal_sharded_find_winners(
        mesh, ("gson",), inner=make_pallas_find_winners(interpret=False))
    up = replicated_update_phase(
        mesh, make_pallas_update_phase(interpret=False))
    replicated = NamedSharding(mesh, P())

    def on_mesh(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated)

    p = GSONParams(model="soam")
    state = jax.tree.map(on_mesh, jax.eval_shape(lambda: init_state(
        jax.random.key(0), capacity=256, dim=3, max_deg=K,
        seed_points=jnp.zeros((2, 3), f32))))
    signals = on_mesh(jax.ShapeDtypeStruct((64, 3), f32))
    step = jax.jit(lambda st, x: multi_signal_step_impl(
        st, x, p, find_winners=fw, update_phase=up))
    compiled = step.lower(state, signals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_superstep_compiles_for_v5e_at_paper_width(one_chip):
    """The fused superstep (``multi-fused``: the fleet superstep program
    a Session runs as a B=1 view) of ``paper_spec("sphere",
    "multi-fused")`` with the pallas-full pair keeps both Mosaic kernels
    inside its on-device loop."""
    spec = paper_spec("sphere", "multi-fused")
    strategy, rt = gson.resolve(spec)
    cfg = strategy.fleet_cfg(spec, rt.params, rt.vcfg)
    assert (spec.capacity, cfg.max_parallel) == (C, M)
    sampler = fleet_core.BroadcastSampler(rt.sampler)
    fstate, probes = jax.eval_shape(lambda k: fleet_core.fleet_init(
        k, sampler=sampler, capacity=spec.capacity, dim=spec.dim,
        max_deg=spec.max_deg, n_probe=spec.n_probe,
        init_threshold=rt.params.insertion_threshold),
        jax.ShapeDtypeStruct((1,), jax.random.key(0).dtype))

    def put(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    compiled = fleet_core.run_fleet_superstep.lower(
        jax.tree.map(put, fstate), put(probes),
        jax.ShapeDtypeStruct((1,), i32, sharding=one_chip),
        sampler=sampler, params=rt.params, cfg=cfg,
        find_winners=make_pallas_find_winners(interpret=False),
        update_phase=make_pallas_update_phase(interpret=False)).compile()
    text = compiled.as_text()
    assert "while" in text
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fn", ["find_winners_reference",
                                "quantization_error"])
def test_distance_matmuls_ask_for_highest_precision(fn):
    x = jnp.zeros((16, 3), f32)
    w = jnp.zeros((64, 3), f32)
    active = jnp.ones((64,), bool)
    if fn == "find_winners_reference":
        lowered = jax.jit(find_winners_reference).lower(x, w, active)
    else:
        st = init_state(jax.random.key(0), capacity=64, dim=3, max_deg=K,
                        seed_points=jnp.zeros((2, 3), f32))
        lowered = jax.jit(metrics.quantization_error).lower(st, x)
    text = lowered.as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots, text
    assert all("HIGHEST" in ln for ln in dots), dots
