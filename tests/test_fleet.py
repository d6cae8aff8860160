"""The fleet API: vmapped multi-network execution.

Covers the redesign's acceptance surface:

  * **bit-identity** — a B=8 fleet of identical-shape specs produces
    per-network states bit-identical to 8 independent ``Session`` runs
    with the same seeds, for both the host-dispatched ("multi") and the
    on-device ("multi-fused") strategies;
  * heterogeneous samplers within one cohort (each network still
    bit-identical to its own session);
  * cohort grouping: same-shaped specs share one compiled program,
    mixed shapes produce one cohort each;
  * per-network convergence masks: finished networks freeze while the
    batch keeps running;
  * topology invariants (symmetric neighbors/ages, no self edges, no
    edges to inactive units) on EVERY network of a stacked
    ``FleetState`` after vmapped growth/removal;
  * ``FleetSession`` pause/resume and checkpoint/restore, both
    bit-identical to an uninterrupted run;
  * ``Registry`` polish: decorator registration, sorted ``names()``,
    sorted options in the miss message.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_gson_invariants import assert_invariants

from repro import gson
from repro.core.gson import fleet as fleet_core
from repro.core.gson.state import GSONParams

SURFACES = ("sphere", "torus", "eight", "trefoil")

STATE_FIELDS = ("w", "active", "nbr", "age", "error", "firing",
                "threshold", "topo_state", "inconsistent_for",
                "n_active", "signal_count", "discarded")


def short_spec(variant="multi", **kw) -> gson.RunSpec:
    base = dict(
        variant=variant,
        model=GSONParams(model="gwr", insertion_threshold=0.5),
        sampler="sphere",
        capacity=128, max_deg=12, max_iterations=40, check_every=10,
        qe_threshold=1e-9, n_probe=256)
    base.update(kw)
    return gson.RunSpec(**base)


FLOAT_FIELDS = ("w", "age", "error", "firing", "threshold")


def assert_states_equal(a, b, ctx=""):
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{ctx}: field {name!r} differs")


def assert_states_close(a, b, ctx=""):
    """Discrete fields bitwise, float fields within the 1e-6 of
    ``tests/test_fleet_mesh.py``."""
    for name in STATE_FIELDS:
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        if name in FLOAT_FIELDS:
            assert np.allclose(x, y, atol=1e-6), (ctx, name)
        else:
            np.testing.assert_array_equal(
                x, y, err_msg=f"{ctx}: field {name!r} differs")


# ---------------------------------------------------------------------------
# the acceptance criterion: fleet == B independent sessions, bitwise

@pytest.mark.parametrize("variant", ["multi", "multi-fused"])
def test_fleet_bit_identical_to_sessions(variant):
    spec = short_spec(variant)
    B = 8
    fleet = gson.FleetSession(gson.FleetSpec.broadcast(spec,
                                                       seeds=range(B)))
    assert len(fleet.cohorts) == 1      # one compiled program for all 8
    fleet.run()
    for i in range(B):
        sess = gson.Session(spec, seed=i)
        sess.run()
        st_s, stats_s = sess.result()
        st_f, stats_f = fleet.result(i)
        assert_states_equal(st_s, st_f, f"{variant} network {i}")
        assert stats_s.iterations == stats_f.iterations
        assert stats_s.units == stats_f.units
        assert stats_s.signals == stats_f.signals


def test_heterogeneous_samplers_one_cohort_bit_identical():
    """One sampler per network, same pool shape -> ONE cohort; each
    network matches its own single-surface session: discrete fields
    bitwise, floats within 1e-6. The grouped sampler draws every
    surface in one program, which XLA fuses differently from a
    single-surface draw; on jax 0.9 that reassociation moves a few
    weights by one ulp (3e-8) while ids and topology stay equal."""
    spec = short_spec("multi-fused", max_iterations=20)
    fleet = gson.FleetSession(gson.FleetSpec.broadcast(
        spec, seeds=range(len(SURFACES)), samplers=SURFACES))
    assert len(fleet.cohorts) == 1
    fleet.run()
    for i, surf in enumerate(SURFACES):
        sess = gson.Session(spec.replace(sampler=surf), seed=i)
        sess.run()
        st_s, _ = sess.result()
        st_f, _ = fleet.result(i)
        assert_states_close(st_s, st_f, f"surface {surf}")


# ---------------------------------------------------------------------------
# cohorts and per-network freezing

def test_mixed_shapes_make_one_cohort_each():
    fs = gson.FleetSpec(
        (short_spec(), short_spec(capacity=64), short_spec()),
        (0, 1, 2))
    fleet = gson.FleetSession(fs)
    assert len(fleet.cohorts) == 2
    fleet.run()
    assert list(fleet.iterations) == [40, 40, 40]


def test_per_network_budgets_freeze_within_cohort():
    # different max_iterations in ONE cohort: finished networks freeze
    # (bit-identical to their own shorter session) while others run on
    specs = tuple(short_spec("multi-fused", max_iterations=n)
                  for n in (12, 40, 24))
    fleet = gson.FleetSession(gson.FleetSpec(specs, (0, 1, 2)))
    assert len(fleet.cohorts) == 1      # run limits are not a shape key
    fleet.run()
    assert list(fleet.iterations) == [12, 40, 24]
    for i, n in enumerate((12, 40, 24)):
        sess = gson.Session(specs[i], seed=i)
        sess.run()
        st_s, _ = sess.result()
        assert_states_equal(st_s, fleet.result(i)[0],
                            f"budget {n} network {i}")


def test_non_fleet_variant_raises():
    with pytest.raises(ValueError, match="not fleet-capable"):
        gson.FleetSession([short_spec("single")])


# ---------------------------------------------------------------------------
# topology invariants on every network of the stacked state

@pytest.mark.parametrize("variant", ["multi", "multi-fused"])
def test_fleet_topology_invariants_per_network(variant):
    # SOAM on a small pool exercises growth, aging, expiry and pruning
    # through the vmapped step; every network of the stacked FleetState
    # must independently satisfy the structural invariants
    spec = short_spec(
        variant,
        model=GSONParams(model="soam", insertion_threshold=0.35,
                         age_max=20.0),
        capacity=96, max_iterations=30, check_every=10)
    fleet = gson.FleetSession(gson.FleetSpec.broadcast(spec,
                                                       seeds=range(4)))
    fleet.run()
    c = fleet.cohorts[0]
    assert isinstance(c.fstate, fleet_core.FleetState)
    assert c.fstate.batch == 4
    for i in range(4):
        net = c.fstate.network(i)
        assert int(net.n_active) > 2, f"network {i} did not grow"
        assert_invariants(net.nbr, net.age, net.active)
        assert int(net.n_active) == int(jnp.sum(net.active))
        assert bool(jnp.all(jnp.isfinite(net.w)))


def test_stack_unstack_roundtrip():
    spec = short_spec()
    sessions = [gson.Session(spec, seed=s) for s in range(3)]
    for s in sessions:
        s.run(budget=5)
    stacked = fleet_core.stack_states([s.state for s in sessions])
    back = fleet_core.unstack_states(stacked)
    for s, st in zip(sessions, back):
        assert_states_equal(s.state, st)


# ---------------------------------------------------------------------------
# session contract: stream, pause/resume, checkpoint/restore

def test_fleet_streams_rows_per_network():
    rows_cb = []
    fleet = gson.FleetSession(
        gson.FleetSpec.broadcast(short_spec(), seeds=range(3)),
        on_history=rows_cb.append)
    streamed = list(fleet.stream())
    assert streamed == rows_cb
    nets = {r["network"] for r in streamed}
    assert nets == {0, 1, 2}
    for r in streamed:
        assert r["iteration"] % 10 == 0     # check cadence
        assert r["units"] > 0


@pytest.mark.parametrize("variant", ["multi", "multi-fused"])
def test_fleet_pause_resume_matches_uninterrupted(variant):
    fs = gson.FleetSpec.broadcast(short_spec(variant, max_iterations=48),
                                  seeds=range(3))
    a = gson.FleetSession(fs)
    a.run()
    b = gson.FleetSession(fs)
    b.run(budget=13)            # pause mid-run (not on a check boundary)
    assert all(b.iterations == 13)
    b.resume(budget=20)
    b.resume()                  # to termination
    for i in range(3):
        assert_states_equal(a.result(i)[0], b.result(i)[0],
                            f"network {i}")


def test_fused_scan_form_matches_while_form():
    # SuperstepConfig.early_exit=False must reach the fixed-length scan
    # lowering through the public API and agree bitwise with the
    # early-exit while form
    def run_form(early_exit):
        spec = short_spec(
            "multi-fused", max_iterations=32,
            variant_config=gson.FusedConfig(
                superstep=gson.SuperstepConfig(length=12,
                                               early_exit=early_exit)))
        sess = gson.Session(spec, seed=5)
        sess.run()
        return sess.result()[0]

    assert_states_equal(run_form(True), run_form(False))


def test_fleet_checkpoint_restore_matches_uninterrupted(tmp_path):
    fs = gson.FleetSpec.broadcast(
        short_spec("multi-fused", max_iterations=48), seeds=range(3))
    a = gson.FleetSession(fs)
    a.run()

    b = gson.FleetSession(fs, checkpoint_dir=str(tmp_path))
    b.run(budget=17)
    b.checkpoint()
    del b                       # simulate the process dying

    c = gson.FleetSession.restore(fs, str(tmp_path))
    assert all(c.iterations == 17)
    c.resume()
    for i in range(3):
        assert_states_equal(a.result(i)[0], c.result(i)[0],
                            f"network {i}")
        assert c.result(i)[1].iterations == a.result(i)[1].iterations


# ---------------------------------------------------------------------------
# Registry polish (satellite): decorator form, sorted names, sorted miss

def test_registry_decorator_form_and_sorted_names():
    reg = gson.Registry("thing")

    @reg.register("zeta")
    def zeta():
        return "z"

    @reg.register("alpha")
    def alpha():
        return "a"

    assert zeta() == "z"                 # decorator returns the object
    assert reg.get("alpha") is alpha
    assert reg.names() == ("alpha", "zeta")     # sorted helper
    assert list(reg) == ["alpha", "zeta"]


def test_registry_miss_lists_sorted_options():
    reg = gson.Registry("thing")
    reg.register("bb", 2)
    reg.register("aa", 1)
    with pytest.raises(KeyError, match=r"aa, bb"):
        reg.get("zz")


def test_variant_registry_decorator_runs_through_runspec():
    from repro.gson.variants import MultiVariant

    if "fleet-test-variant" not in gson.VARIANTS:
        @gson.VARIANTS.register("fleet-test-variant")
        class _Decorated(MultiVariant):
            name = "fleet-test-variant"
    # a class registered via decorator resolves through RunSpec (the
    # resolver instantiates types)
    state, stats = gson.run(short_spec("fleet-test-variant",
                                       max_iterations=8),
                            jax.random.key(0))
    assert stats.iterations == 8
    assert int(state.n_active) > 2
