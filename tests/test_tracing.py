"""The program's trace marks: host spans (``repro.utils.timing.span``)
and the device phase scopes of the fleet programs."""
from __future__ import annotations

import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import gson
from repro.core.gson import fleet as fleet_core
from repro.gson import session as session_mod
from repro.gson import variants as variants_mod
from repro.gson.spec import resolve
from repro.utils import timing

PHASES = ("gson.sample", "gson.find_winners", "gson.update", "gson.tail",
          "gson.refresh", "gson.check")


def tiny_spec(variant="multi-fused", **kw):
    vcfg = (gson.FusedConfig(superstep=gson.SuperstepConfig(length=8),
                             refresh_every=2)
            if variant == "multi-fused" else None)
    return gson.RunSpec(variant=variant, model="soam", sampler="sphere",
                        backend="reference", variant_config=vcfg,
                        capacity=64, max_deg=8, max_iterations=24,
                        check_every=8, n_probe=64, **kw)


def host_events(trace_dir):
    from jax.profiler import ProfileData
    f, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)
    return [(e.name, dict(e.stats))
            for p in ProfileData.from_file(f).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith(timing.PREFIX)]


def test_span_adds_its_seconds_to_timings():
    t = {}
    with timing.span("step", t):
        pass
    first = t["step"]
    with timing.span("step", t):
        pass
    assert 0 < first < t["step"]
    assert set(t) == {"step"}


def test_span_with_the_profiler_off_and_no_dict_does_nothing():
    assert timing.span("a") is timing.span("b", session=1)
    with timing.span("a"):
        pass


def test_span_writes_its_name_and_ids_into_the_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with timing.span("superstep", session=3, iteration=128):
            pass
    assert ("gson.superstep", {"session": 3, "iteration": 128}) in \
        host_events(str(tmp_path))


def _final_state(spec, seed=5):
    sess = gson.Session(spec, seed=seed)
    sess.run()
    return sess.state


def _leaves(state):
    return [np.asarray(jax.random.key_data(x)
                       if jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
                       else x) for x in jax.tree.leaves(state)]


def test_spans_leave_the_network_bitwise_unchanged(monkeypatch, tmp_path):
    spec = tiny_spec()
    with_spans = _final_state(spec)
    with jax.profiler.trace(str(tmp_path)):
        traced = _final_state(spec)

    def no_span(*_, **__):
        return contextlib.nullcontext()

    monkeypatch.setattr(session_mod, "span", no_span)
    monkeypatch.setattr(variants_mod, "span", no_span)
    without = _final_state(spec)
    for a, b, c in zip(_leaves(with_spans), _leaves(without),
                       _leaves(traced)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
        assert np.array_equal(a, c)


@pytest.mark.parametrize("variant", ["multi-fused", "multi", "single"])
def test_run_stats_phase_times_come_from_the_spans(variant):
    sess = gson.Session(tiny_spec(variant, max_signals=4096), seed=1)
    stats = sess.run(budget=8)
    assert 0 < stats.time_step <= stats.time_total
    if variant == "single":
        assert stats.time_sample > 0 and stats.time_convergence > 0
    else:
        assert stats.time_sample == 0 and stats.time_convergence == 0


def test_lowered_superstep_carries_every_phase_scope():
    spec = tiny_spec()
    strategy, rt = resolve(spec)
    sampler = fleet_core.BroadcastSampler(rt.sampler)
    fs, probes = fleet_core.fleet_init(
        jax.random.key(0)[None], sampler=sampler, capacity=spec.capacity,
        dim=spec.dim, max_deg=spec.max_deg, n_probe=spec.n_probe,
        init_threshold=rt.params.insertion_threshold)
    strategy.prepare(rt)
    text = fleet_core.run_fleet_superstep.lower(
        fs, probes, jnp.asarray([8], jnp.int32), sampler=sampler,
        params=rt.params, cfg=rt.scratch["fleet_cfg"],
        find_winners=rt.find_winners,
        update_phase=rt.update_phase).as_text(debug_info=True)
    for scope in PHASES:
        assert scope in text, scope
