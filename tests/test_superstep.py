"""Equivalence of the fused on-device superstep with the host loop.

The fused runner (superstep.py) must be a pure performance transform:
  * a masked step (signal_mask with k valid rows) == an m=k step;
  * S fused iterations == S sequential masked multi_signal_step calls
    under the same keys (identical n_active / signal_count, weights
    within float tolerance);
  * the lax.scan and lax.while_loop forms agree bit-for-bit;
  * the while form early-exits at the first satisfied convergence check.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gson import topology
from repro.core.gson.engine import EngineConfig, GSONEngine
from repro.core.gson.multi import (find_winners_reference,
                                   multi_signal_step_impl, refresh_topology)
from repro.core.gson.sampling import make_sampler
from repro.core.gson.state import GSONParams, init_state
from repro.core.gson.superstep import (SuperstepConfig, device_m_schedule,
                                       next_pow2, run_superstep)

NO_CHECK = 10**6   # check cadence that never fires within a test run


def _grown_state(model="soam", capacity=128, steps=15, m=32, thr=0.35):
    """A network that has grown past the seed (so insertion, aging and
    pruning paths are all live in the comparisons below)."""
    p = GSONParams(model=model, insertion_threshold=thr)
    sampler = make_sampler("sphere")
    st = init_state(jax.random.key(0), capacity=capacity, dim=3,
                    max_deg=16, seed_points=sampler(jax.random.key(1), 2),
                    init_threshold=p.insertion_threshold)
    for i in range(steps):
        st = multi_signal_step_impl(
            st, sampler(jax.random.key(100 + i), m), p,
            refresh_states=False)
    return p, sampler, st


def _host_m_schedule(n_active: int, cfg: SuperstepConfig) -> int:
    if cfg.fixed_m is not None:
        return min(cfg.fixed_m, cfg.max_parallel)
    return max(min(cfg.min_m, cfg.max_parallel),
               min(next_pow2(n_active), cfg.max_parallel))


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 63, 64, 65, 500, 4096, 10**6])
def test_device_m_schedule_matches_host(n):
    cfg = SuperstepConfig(max_parallel=1024, min_m=4)
    assert int(device_m_schedule(jnp.int32(n), cfg)) == \
        _host_m_schedule(n, cfg)


@pytest.mark.parametrize("model", ["gng", "gwr", "soam"])
def test_masked_step_equals_unmasked_at_k(model):
    """signal_mask with k valid rows == an m=k call, given collision-free
    signals (collision resolution draws different priorities for
    different buffer sizes, so the comparison pins distinct winners)."""
    p, sampler, st = _grown_state(model=model)
    cand = sampler(jax.random.key(7), 64)
    # order signals so the first k have pairwise-distinct winners -> the
    # winner lock is deterministic and priorities cannot matter
    wid, *_ = find_winners_reference(cand, st.w, st.active)
    wid = np.asarray(wid)
    seen, chosen = set(), []
    for i in range(64):
        if wid[i] not in seen:
            seen.add(wid[i])
            chosen.append(i)
    rest = [i for i in range(64) if i not in set(chosen)]
    buf = jnp.asarray(np.asarray(cand)[chosen + rest])[:24]
    k = min(len(chosen), 24)
    assert k >= 2, "test fixture degenerate: fewer than 2 distinct winners"

    out_k = multi_signal_step_impl(st, buf[:k], p, refresh_states=False)
    mask = jnp.arange(buf.shape[0]) < k
    out_m = multi_signal_step_impl(st, buf, p, refresh_states=False,
                                   signal_mask=mask)

    assert int(out_k.n_active) == int(out_m.n_active)
    assert int(out_k.signal_count) == int(out_m.signal_count)
    assert int(out_k.discarded) == int(out_m.discarded)
    np.testing.assert_allclose(np.asarray(out_k.w), np.asarray(out_m.w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out_k.nbr),
                                  np.asarray(out_m.nbr))
    np.testing.assert_array_equal(np.asarray(out_k.active),
                                  np.asarray(out_m.active))


def test_masked_counters_only_count_valid_rows():
    p, sampler, st = _grown_state(model="gwr")
    buf = sampler(jax.random.key(11), 32)
    mask = jnp.arange(32) < 5
    before = int(st.signal_count)
    out = multi_signal_step_impl(st, buf, p, refresh_states=False,
                                 signal_mask=mask)
    assert int(out.signal_count) == before + 5
    assert int(out.discarded) - int(st.discarded) <= 5


@pytest.mark.parametrize("model", ["gng", "gwr", "soam"])
def test_superstep_equals_sequential_masked_steps(model):
    """S fused iterations == S sequential masked steps, same keys."""
    p, sampler, st0 = _grown_state(model=model)
    cfg = SuperstepConfig(length=10, max_parallel=64, min_m=4,
                          refresh_every=3, check_every=NO_CHECK,
                          early_exit=False)
    probes = sampler(jax.random.key(55), 64)
    rng = jax.random.key(42)

    # sequential host reference, replicating the superstep's key schedule
    st_seq = st0
    r = rng
    for i in range(cfg.length):
        r, k_sig = jax.random.split(r)
        signals = sampler(k_sig, cfg.max_parallel)
        m_t = _host_m_schedule(int(st_seq.n_active), cfg)
        mask = jnp.arange(cfg.max_parallel) < m_t
        st_seq = multi_signal_step_impl(st_seq, signals, p,
                                        refresh_states=False,
                                        signal_mask=mask)
        if p.model == "soam" and i % cfg.refresh_every == 0:
            st_seq = refresh_topology(st_seq, p)

    res = run_superstep(st0, rng, probes, 0, sampler=sampler, params=p,
                        cfg=cfg)
    assert int(res.iterations) == cfg.length
    assert int(res.state.n_active) == int(st_seq.n_active)
    assert int(res.state.signal_count) == int(st_seq.signal_count)
    assert int(res.state.discarded) == int(st_seq.discarded)
    np.testing.assert_allclose(np.asarray(res.state.w),
                               np.asarray(st_seq.w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(res.state.nbr),
                                  np.asarray(st_seq.nbr))
    # history is the scan form's per-iteration n_active trace
    assert res.history.shape == (cfg.length,)
    assert int(res.history[-1]) == int(st_seq.n_active)


def _searchsorted_rank(rows):
    """The collision rank as a binary search found it, kept to check the
    cumulative-max form against."""
    order = jnp.argsort(rows, stable=True)
    sorted_rows = rows[order]
    first = jnp.searchsorted(sorted_rows, sorted_rows, side="left")
    rank_sorted = (jnp.arange(rows.shape[0], dtype=jnp.int32)
                   - first.astype(jnp.int32))
    return jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)


def _soam_c768_steps(steps=6):
    """SOAM at capacity 768 on a 1024-row buffer with 700 valid rows, from
    256 habituated seeds, so unit and edge insertions collide in rows."""
    p = GSONParams(model="soam", insertion_threshold=0.35)
    sampler = make_sampler("sphere")
    st = init_state(jax.random.key(0), capacity=768, dim=3, max_deg=16,
                    seed_points=sampler(jax.random.key(1), 256),
                    init_threshold=p.insertion_threshold)
    st = st.replace(firing=jnp.full((768,), 0.05),
                    threshold=jnp.full((768,), 0.1))
    mask = jnp.arange(1024) < 700

    @jax.jit   # traced afresh on every call, after any monkeypatch
    def step(st, key):
        return multi_signal_step_impl(st, sampler(key, 1024), p,
                                      refresh_states=False, signal_mask=mask)

    for i in range(steps):
        st = step(st, jax.random.key(100 + i))
    return st


def test_rank_by_cummax_grows_the_searchsorted_network(monkeypatch):
    new = _soam_c768_steps()
    monkeypatch.setattr(topology, "_rank_within_rows", _searchsorted_rank)
    old = _soam_c768_steps()
    assert int(new.n_active) > 256 and int(jnp.sum(new.nbr >= 0)) > 1000
    for field in ("nbr", "age", "active", "w"):
        np.testing.assert_array_equal(np.asarray(getattr(new, field)),
                                      np.asarray(getattr(old, field)),
                                      err_msg=field)


def test_scan_and_while_forms_agree():
    p, sampler, st0 = _grown_state(model="soam")
    probes = sampler(jax.random.key(55), 64)
    base = SuperstepConfig(length=12, max_parallel=64, refresh_every=3,
                           check_every=5)
    rng = jax.random.key(9)
    # run_superstep donates its state argument -> each form gets a copy
    st_a = jax.tree_util.tree_map(jnp.array, st0)
    st_b = jax.tree_util.tree_map(jnp.array, st0)
    res_w = run_superstep(st_a, rng, probes, 0, sampler=sampler, params=p,
                          cfg=dataclasses.replace(base, early_exit=True))
    res_s = run_superstep(st_b, rng, probes, 0, sampler=sampler, params=p,
                          cfg=dataclasses.replace(base, early_exit=False))
    assert int(res_w.iterations) == int(res_s.iterations)
    assert bool(res_w.converged) == bool(res_s.converged)
    assert int(res_w.state.n_active) == int(res_s.state.n_active)
    assert int(res_w.state.signal_count) == int(res_s.state.signal_count)
    np.testing.assert_array_equal(np.asarray(res_w.state.w),
                                  np.asarray(res_s.state.w))


def test_while_form_early_exits_on_convergence():
    # a permissive QE threshold converges at the first check; the while
    # form must stop there instead of burning the remaining iterations
    p, sampler, st0 = _grown_state(model="gwr")
    assert int(st0.n_active) > 8
    probes = sampler(jax.random.key(55), 64)
    cfg = SuperstepConfig(length=50, max_parallel=64, check_every=4,
                          qe_threshold=1e9, early_exit=True)
    res = run_superstep(st0, jax.random.key(3), probes, 0,
                        sampler=sampler, params=p, cfg=cfg)
    assert bool(res.converged)
    assert int(res.iterations) == 4
    assert np.isfinite(float(res.qe))


def test_engine_multi_fused_runs_and_reports():
    cfg = EngineConfig(
        params=GSONParams(model="gwr", insertion_threshold=0.5),
        capacity=128, max_deg=12, variant="multi-fused",
        superstep=SuperstepConfig(length=16),
        max_iterations=48, check_every=8, qe_threshold=0.05)
    eng = GSONEngine(cfg, make_sampler("sphere"))
    state, stats = eng.run(jax.random.key(0))
    assert 0 < stats.iterations <= 48
    assert stats.signals > 0
    assert stats.units == int(state.n_active)
    assert stats.time_step > 0
    assert stats.history   # one entry per superstep call


def test_engine_fused_matches_multi_unit_count_ballpark():
    """Same seed, same schedule: the fused variant must land in the same
    unit-count ballpark as the host-dispatched multi variant (they draw
    different signal streams, so exact equality is not expected)."""
    def run(variant):
        cfg = EngineConfig(
            params=GSONParams(model="soam", insertion_threshold=0.35,
                              age_max=64.0, eps_b=0.1, eps_n=0.01,
                              stuck_window=60),
            capacity=256, max_deg=16, variant=variant,
            superstep=SuperstepConfig(length=25),
            check_every=25, refresh_every=2, max_iterations=150)
        eng = GSONEngine(cfg, make_sampler("sphere"))
        _, stats = eng.run(jax.random.key(42))
        return stats

    s_multi = run("multi")
    s_fused = run("multi-fused")
    assert s_fused.units == pytest.approx(s_multi.units, rel=0.15)
