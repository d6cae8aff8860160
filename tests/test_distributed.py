"""Multi-device tests (8 host devices via subprocess — XLA_FLAGS must be
set before jax initializes, which cannot happen in-process).

Covers: GSON data/network partitioning equivalence, MoE EP vs dense
reference, int8 EF-compressed psum, flash_decode vs replicated decode,
and smoke-cell lowering on a (pod, data, model) mesh.
"""
from __future__ import annotations

import pytest

pytestmark = pytest.mark.slow


def test_gson_distributed_equivalence(devices8):
    out = devices8("""
        import jax, numpy as np
        from repro.launch.mesh import make_debug_mesh
        from repro.core.gson.distributed import make_distributed_step
        from repro.core.gson.state import GSONParams, init_state
        from repro.core.gson.multi import multi_signal_step_impl
        from repro.core.gson.sampling import make_sampler

        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        p = GSONParams(model="soam", insertion_threshold=0.3)
        sampler = make_sampler("sphere")
        st = init_state(jax.random.key(3), capacity=256, dim=3, max_deg=16,
                        seed_points=sampler(jax.random.key(1), 2))
        # advance a few steps so the network is non-trivial
        rng = jax.random.key(9)
        for _ in range(10):
            rng, k = jax.random.split(rng)
            st = multi_signal_step_impl(st, sampler(k, 64), p,
                                        refresh_states=False)
        sig = sampler(jax.random.key(5), 64)
        ref = multi_signal_step_impl(st, sig, p, refresh_states=False)

        def edge_set(nbr):
            nbr = np.asarray(nbr)
            out = set()
            for a in range(nbr.shape[0]):
                for b in nbr[a]:
                    if b >= 0:
                        out.add((min(a, int(b)), max(a, int(b))))
            return out

        e_ref = edge_set(ref.nbr)
        for strat in ("data", "network"):
            step = make_distributed_step(mesh, p, strategy=strat)
            got = step(st, sig)
            # the paper's core claim: the replicated Update is a
            # deterministic state machine — re-running the same step is
            # bitwise identical (no write races, no device divergence)
            got2 = step(st, sig)
            assert np.array_equal(np.asarray(got.nbr),
                                  np.asarray(got2.nbr)), strat
            assert np.array_equal(np.asarray(got.w),
                                  np.asarray(got2.w)), strat
            assert np.allclose(np.asarray(ref.w), np.asarray(got.w),
                               atol=1e-5), strat
            assert int(ref.n_active) == int(got.n_active)
            assert int(ref.discarded) == int(got.discarded)
            # exact edge equality vs the single-device reference is NOT
            # guaranteed for the data strategy: sharded-signal
            # compilation tiles the distance matmul differently, 1-ulp
            # d2 shifts flip near-tie insertion decisions, and one flip
            # cascades through the free-slot ranking (measured jaccard
            # ~0.59 on this workload). The network strategy shards
            # units, not signals, so its distances are bitwise-stable
            # and its edge set must match exactly.
            e_got = edge_set(got.nbr)
            if strat == "network":
                assert e_got == e_ref, (strat, len(e_ref), len(e_got))
            else:
                jacc = len(e_ref & e_got) / max(len(e_ref | e_got), 1)
                assert jacc >= 0.5, (strat, jacc, len(e_ref), len(e_got))
        print("OK")
        """)
    assert "OK" in out


def test_moe_ep_matches_dense_reference(devices8):
    out = devices8("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_debug_mesh
        from repro.configs import get_config
        from repro.models.registry import get_bundle, smoke_config
        from repro.models.moe import moe_ffn_ep, moe_ffn_reference

        mesh = make_debug_mesh((2, 4), ("data", "model"))
        cfg = smoke_config(get_config("qwen2-moe-a2.7b"))
        cfg = cfg.replace(capacity_factor=8.0)   # no drops => exact match
        bundle = get_bundle(cfg)
        params = bundle.init(jax.random.key(0))
        lp = {k[len("layers/"):]: v[0] for k, v in params.items()
              if k.startswith("layers/") and k not in
              ("layers/ln1", "layers/ln2")}
        x = 0.5 * jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model))
        y_ref, aux_ref = moe_ffn_reference(lp, x, cfg)
        with jax.set_mesh(mesh):
            y_ep, aux_ep = jax.jit(
                lambda lp, x: moe_ffn_ep(lp, x, cfg, mesh))(lp, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(float(aux_ref), float(aux_ep), rtol=1e-2)
        print("OK")
        """)
    assert "OK" in out


def test_compressed_psum_error_feedback(devices8):
    out = devices8("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from repro.launch.mesh import make_debug_mesh
        from repro.training.compression import compressed_psum, init_ef_state
        from jax.sharding import PartitionSpec as P

        mesh = make_debug_mesh((4,), ("pod",))
        g_global = jax.random.normal(jax.random.key(0), (4, 64))
        ef0 = jnp.zeros((4, 64))

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
                 out_specs=(P("pod"), P("pod")), check_vma=False)
        def run(g, e):
            grads, ef = compressed_psum({"w": g[0]}, {"w": e[0]}, "pod", 4)
            return grads["w"][None], ef["w"][None]

        true_mean = jnp.mean(g_global, axis=0)
        total_err = None
        g1, ef = run(g_global, ef0)
        # every pod sees the same dequantized mean
        assert np.allclose(np.asarray(g1[0]), np.asarray(g1[1]))
        err1 = float(jnp.max(jnp.abs(g1[0] - true_mean)))
        scale = float(jnp.max(jnp.abs(g_global))) / 127.0
        assert err1 <= 2 * scale, (err1, scale)
        # error feedback: feeding the SAME gradient again, the residual
        # pushes the two-step average toward the truth
        g2, ef = run(g_global, ef)
        two_step = (g1[0] + g2[0]) / 2
        err2 = float(jnp.max(jnp.abs(two_step - true_mean)))
        assert err2 <= err1 + 1e-6
        print("OK")
        """)
    assert "OK" in out


def test_flash_decode_matches_replicated(devices8):
    out = devices8("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_debug_mesh
        from repro.models import attention as attn

        mesh = make_debug_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(4, 1, 8, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(4, 32, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(4, 32, 2, 16)), jnp.float32)
        length = jnp.asarray([32, 17, 8, 25], jnp.int32)
        ref = attn.decode_attention(q, k, v, length)
        got = jax.jit(lambda q, k, v, l: attn.flash_decode(
            mesh, q, k, v, l))(q, k, v, length)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
        """)
    assert "OK" in out


def test_smoke_cells_lower_on_pod_mesh(devices8):
    out = devices8("""
        import jax
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import steps
        from repro.configs import get_config
        from repro.models.registry import smoke_config
        from repro.models.common import SMOKE_SHAPES

        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch in ("yi-34b", "qwen3-moe-235b-a22b", "zamba2-2.7b"):
            cfg = smoke_config(get_config(arch))
            for shp in ("train_4k", "decode_32k"):
                lowered = steps.lower_cell(cfg, shp, mesh,
                                           shapes=SMOKE_SHAPES)
                lowered.compile()
        print("OK")
        """, timeout=560)
    assert "OK" in out


def test_train_step_with_compression_and_straggler_masking(devices8):
    out = devices8("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import DeployCfg, build_train_step
        from repro.configs import get_config
        from repro.models.common import SMOKE_SHAPES, rules_for_mesh
        from repro.models.registry import get_bundle, smoke_config
        from repro.data.tokens import synthetic_batch
        from repro.training import optimizer as opt_lib
        from repro.training.compression import init_ef_state

        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = smoke_config(get_config("granite-3-2b"))
        bundle = get_bundle(cfg)
        rules = rules_for_mesh(mesh)
        dep = DeployCfg(microbatches=1, compress_pods=True,
                        straggler_masking=True)
        step, _, tcfg = build_train_step(bundle, mesh, rules, dep)
        params = bundle.init(jax.random.key(0))
        opt = opt_lib.init_opt_state(tcfg.opt, params)
        ef = init_ef_state(params)
        shape = SMOKE_SHAPES["train_4k"]
        batch = synthetic_batch(cfg, shape, 0)
        health = jnp.asarray([1.0, 0.5], jnp.float32)
        params, opt, ef, m = step(params, opt, batch, ef, health)
        assert np.isfinite(float(m["loss"]))
        for leaf in jax.tree.leaves(params):
            assert bool(jnp.all(jnp.isfinite(leaf)))
        print("OK")
        """, timeout=560)
    assert "OK" in out
