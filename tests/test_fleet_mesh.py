"""Mesh-sharded fleet execution (8 host devices via subprocess).

The PR5 acceptance surface — the public ``MeshSpec`` path must be a
first-class citizen, not an orphaned shard_map program:

  * **sharded-fleet bit-identity** — a B=8 fleet sharded across 8 host
    devices (``FleetSpec.mesh``) produces networks bitwise-identical on
    discrete fields / 1e-6-close on floats to the unsharded B=8 fleet
    AND to 8 independent ``Session`` runs, for both "multi" and
    "multi-fused";
  * **padding** — a batch that does not divide the mesh is padded with
    frozen placeholder networks, with no effect on any real network;
  * **resharding on restore** — a checkpoint written under 8-way
    sharding restores bit-identically on a 4-device mesh, a 3-device
    mesh (padding), and with no mesh at all;
  * **signal-axis sharding** — ``RunSpec.mesh`` threads the
    data-parallel Find Winners through the session/fused/fleet paths
    (Update stays a replicated deterministic state machine);
  * **serving** — ``ReconstructionServer(mesh=...)`` places waves onto
    the mesh and still matches dedicated sessions;
  * host-side ``MeshSpec`` validation (no devices needed).

None of these tests skip, which is what the CI ``multi-device`` job
enforces.
"""
from __future__ import annotations

import pytest

from repro import gson
from repro.core.gson.state import GSONParams

# the subprocess tests are marked slow individually; the host-side
# validation tests at the bottom stay cheap and run in every tier-1
# invocation (including the jax version matrix legs)
slow = pytest.mark.slow

# Shared subprocess prelude: a short GWR spec (unreachable QE threshold,
# fixed iteration budget) and the per-field comparator implementing the
# acceptance tolerance — discrete fields bitwise, floats 1e-6.
PRELUDE = """
import numpy as np
from repro import gson
from repro.core.gson.state import GSONParams

def short_spec(variant="multi", **kw):
    base = dict(
        variant=variant,
        model=GSONParams(model="gwr", insertion_threshold=0.5),
        sampler="sphere", capacity=128, max_deg=12, max_iterations=40,
        check_every=10, qe_threshold=1e-9, n_probe=256)
    base.update(kw)
    return gson.RunSpec(**base)

FLOATS = ("w", "age", "error", "firing", "threshold")
DISCRETE = ("active", "nbr", "topo_state", "inconsistent_for",
            "n_active", "signal_count", "discarded")

def assert_close(a, b, ctx):
    for name in DISCRETE:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), \\
            (ctx, name, "discrete field must be bitwise identical")
    for name in FLOATS:
        assert np.allclose(np.asarray(getattr(a, name)),
                           np.asarray(getattr(b, name)), atol=1e-6), \\
            (ctx, name, "float field beyond 1e-6")
"""


@slow
@pytest.mark.parametrize("variant", ["multi", "multi-fused"])
def test_sharded_fleet_bit_identical(devices8, variant):
    # the acceptance criterion: B=8 fleet over 8 devices == unsharded
    # fleet == 8 independent Sessions, per network
    out = devices8(PRELUDE + f"""
variant = {variant!r}
spec = short_spec(variant)
mesh = gson.MeshSpec(axis="network", devices=8)
sharded = gson.FleetSession(
    gson.FleetSpec.broadcast(spec, seeds=range(8), mesh=mesh))
assert len(sharded.cohorts) == 1
assert sharded.cohorts[0].pad == 0
sharded.run()
plain = gson.FleetSession(gson.FleetSpec.broadcast(spec, seeds=range(8)))
plain.run()
for i in range(8):
    st_m, stats_m = sharded.result(i)
    st_p, stats_p = plain.result(i)
    assert_close(st_p, st_m, (variant, "fleet", i))
    sess = gson.Session(spec, seed=i)
    sess.run()
    st_s, stats_s = sess.result()
    assert_close(st_s, st_m, (variant, "session", i))
    assert stats_s.iterations == stats_m.iterations == stats_p.iterations
    assert stats_s.signals == stats_m.signals
print("OK")
""", timeout=560)
    assert "OK" in out


@slow
def test_sharded_fleet_pads_non_divisible_batch(devices8):
    # B=6 over 4 devices: two frozen placeholders, zero effect on the
    # six real networks; B=3 over 8 devices: more devices than networks
    out = devices8(PRELUDE + """
spec = short_spec("multi-fused")
for B, ndev, pad in ((6, 4, 2), (3, 8, 5)):
    mesh = gson.MeshSpec(axis="network", devices=ndev)
    fleet = gson.FleetSession(
        gson.FleetSpec.broadcast(spec, seeds=range(B), mesh=mesh))
    assert fleet.cohorts[0].pad == pad, (B, ndev, fleet.cohorts[0].pad)
    fleet.run()
    assert fleet.cohorts[0].fstate.batch == B + pad
    for i in range(B):
        sess = gson.Session(spec, seed=i)
        sess.run()
        assert_close(sess.result()[0], fleet.result(i)[0],
                     (B, ndev, i))
print("OK")
""", timeout=560)
    assert "OK" in out


@slow
def test_sharded_heterogeneous_samplers_one_cohort(devices8):
    # per-network samplers (GroupedSampler) scatter by GLOBAL slot
    # index; the sharded path must pre-split them per device
    # (ShardSwitchSampler) — each network still matches its own
    # single-surface session, padding included (B=3 over 4 devices)
    out = devices8(PRELUDE + """
surfaces = ("sphere", "torus", "eight")
spec = short_spec("multi-fused", max_iterations=20)
fleet = gson.FleetSession(gson.FleetSpec.broadcast(
    spec, seeds=range(3), samplers=surfaces,
    mesh=gson.MeshSpec(axis="network", devices=4)))
assert len(fleet.cohorts) == 1 and fleet.cohorts[0].pad == 1
fleet.run()
for i, surf in enumerate(surfaces):
    sess = gson.Session(spec.replace(sampler=surf), seed=i)
    sess.run()
    assert_close(sess.result()[0], fleet.result(i)[0], surf)
print("OK")
""", timeout=560)
    assert "OK" in out


@slow
def test_sharded_restore_on_different_device_count(devices8):
    # resharding on restore: the checkpoint stores only logical network
    # state, so an 8-way-sharded snapshot continues bit-identically on
    # 4 devices, on 3 (re-padded), and with no mesh at all
    out = devices8(PRELUDE + """
import tempfile
spec = short_spec("multi-fused", max_iterations=48)
ref = gson.FleetSession(gson.FleetSpec.broadcast(spec, seeds=range(8)))
ref.run()
with tempfile.TemporaryDirectory() as d:
    a = gson.FleetSession(
        gson.FleetSpec.broadcast(
            spec, seeds=range(8),
            mesh=gson.MeshSpec(axis="network", devices=8)),
        checkpoint_dir=d)
    a.run(budget=17)          # pause off the check cadence
    a.checkpoint()
    del a
    for restore_mesh in (gson.MeshSpec(axis="network", devices=4),
                         gson.MeshSpec(axis="network", devices=3),
                         None):
        b = gson.FleetSession.restore(
            gson.FleetSpec.broadcast(spec, seeds=range(8),
                                     mesh=restore_mesh), d)
        assert all(b.iterations == 17)
        b.resume()
        for i in range(8):
            assert_close(ref.result(i)[0], b.result(i)[0],
                         (restore_mesh, i))
print("OK")
""", timeout=560)
    assert "OK" in out


@slow
def test_signal_axis_sharding(devices8):
    # RunSpec.mesh = the paper's data partitioning: signals sharded,
    # Update replicated. Sharded compilation may tile the distance
    # matmul differently (1-ulp d2 shifts flip near-tie decisions —
    # see test_distributed), so the contract is a *valid run*, not
    # bit-identity: every path executes, invariants hold, and the
    # reconstruction reaches the same scale as the unsharded run.
    out = devices8(PRELUDE + """
import jax, jax.numpy as jnp
mesh = gson.MeshSpec(axis="signal", devices=4)
for variant in ("multi", "multi-fused"):
    sess = gson.Session(short_spec(variant, mesh=mesh), seed=0)
    sess.run()
    st, stats = sess.result()
    ref = gson.Session(short_spec(variant), seed=0)
    ref.run()
    st_r, stats_r = ref.result()
    assert stats.iterations == stats_r.iterations == 40
    assert stats.signals == stats_r.signals
    assert int(st.n_active) > 2
    assert abs(int(st.n_active) - int(st_r.n_active)) <= 5, \\
        (variant, int(st.n_active), int(st_r.n_active))
    assert bool(jnp.all(jnp.isfinite(st.w)))
# a sharded fleet of signal-sharded networks is rejected (no nesting)
try:
    gson.FleetSpec.broadcast(short_spec("multi", mesh=mesh),
                             seeds=range(2),
                             mesh=gson.MeshSpec(axis="network"))
    raise SystemExit("nested mesh must raise")
except ValueError:
    pass
# ... but an UNsharded fleet of signal-sharded networks is fine
fleet = gson.FleetSession(gson.FleetSpec.broadcast(
    short_spec("multi-fused", mesh=mesh, max_iterations=12),
    seeds=range(2)))
fleet.run()
assert list(fleet.iterations) == [12, 12]
print("OK")
""", timeout=560)
    assert "OK" in out


@slow
def test_serving_places_waves_on_mesh(devices8):
    out = devices8(PRELUDE + """
from repro.serving.engine import ReconstructionServer
mesh = gson.MeshSpec(axis="network", devices=8)
srv = ReconstructionServer(slots=4, slice_iters=10, mesh=mesh)
budgets = (12, 25, 25, 18, 25)
jobs = [srv.submit(short_spec("multi-fused", max_iterations=n), seed=s)
        for s, n in enumerate(budgets)]
done = srv.run(max_ticks=100)
assert len(done) == len(jobs)
for s, (job, n) in enumerate(zip(jobs, budgets)):
    sess = gson.Session(short_spec("multi-fused", max_iterations=n),
                        seed=s)
    sess.run()
    st_s, stats_s = sess.result()
    assert job.stats.iterations == stats_s.iterations == n
    assert job.stats.units == stats_s.units
    assert job.stats.signals == stats_s.signals
print("OK")
""", timeout=560)
    assert "OK" in out


# ---------------------------------------------------------------------------
# host-side validation: no device mesh required


def test_meshspec_validation():
    with pytest.raises(ValueError, match="axis"):
        gson.MeshSpec(axis="nope")
    with pytest.raises(ValueError, match="devices"):
        gson.MeshSpec(devices=0)
    # RunSpec.mesh shards signals; network-axis belongs on FleetSpec
    spec = gson.RunSpec(mesh=gson.MeshSpec(axis="network"))
    with pytest.raises(ValueError, match="FleetSpec"):
        gson.resolve(spec)
    # FleetSpec.mesh shards the network axis, not signals
    with pytest.raises(ValueError, match="network axis"):
        gson.FleetSpec.broadcast(gson.RunSpec(), seeds=range(2),
                                 mesh=gson.MeshSpec(axis="signal"))


def test_signal_mesh_is_a_cohort_key():
    # same shape, different RunSpec.mesh -> different compiled programs
    base = gson.RunSpec(
        variant="multi",
        model=GSONParams(model="gwr", insertion_threshold=0.5),
        sampler="sphere", capacity=64, max_deg=12, max_iterations=4,
        check_every=2, qe_threshold=1e-9, n_probe=64)
    meshed = base.replace(
        mesh=gson.MeshSpec(axis="signal", devices=1))
    fleet = gson.FleetSession(gson.FleetSpec((base, meshed), (0, 1)))
    assert len(fleet.cohorts) == 2
    fleet.run()
    assert list(fleet.iterations) == [4, 4]


def test_meshspec_build_is_memoized():
    a = gson.MeshSpec(axis="network", devices=1)
    b = gson.MeshSpec(axis="network", devices=1)
    assert a.build() is b.build()
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        gson.MeshSpec(devices=10_000).build()
