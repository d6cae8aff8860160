"""Unit tests for the SOAM topological state ladder on hand-built graphs."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gson import topology as topo
from repro.core.gson.state import (ACTIVE, CONNECTED, DISK, HABITUATED,
                                   HALF_DISK, PATCH, SINGULAR)

K = 8


def build(n, edges, cap=16):
    nbr = np.full((cap, K), -1, np.int32)
    for a, b in edges:
        for x, y in ((a, b), (b, a)):
            slot = np.nonzero(nbr[x] < 0)[0][0]
            nbr[x, slot] = y
    active = np.zeros((cap,), bool)
    active[:n] = True
    return jnp.asarray(nbr), jnp.asarray(active)


def states(nbr, active, habituated=True):
    firing = jnp.where(active, 0.05 if habituated else 1.0, 1.0)
    return np.asarray(topo.compute_topo_states(nbr, active, firing, 0.3))


def test_isolated_unit_is_habituated():
    nbr, active = build(1, [])
    assert states(nbr, active)[0] == HABITUATED


def test_not_habituated_is_active():
    nbr, active = build(3, [(0, 1), (1, 2), (0, 2)])
    assert states(nbr, active, habituated=False)[0] == ACTIVE


def test_path_neighborhood_is_half_disk():
    # unit 0 with neighbors 1-2-3 linked in a path
    nbr, active = build(4, [(0, 1), (0, 2), (0, 3), (1, 2), (2, 3)])
    st = states(nbr, active)
    assert st[0] == HALF_DISK


def test_cycle_neighborhood_is_disk_then_patch():
    # tetrahedron: every unit's neighborhood is a 3-cycle -> disk; since
    # all neighbors are disks, all are PATCH
    edges = [(a, b) for a in range(4) for b in range(a + 1, 4)]
    nbr, active = build(4, edges)
    st = states(nbr, active)
    assert all(st[i] == PATCH for i in range(4))


def test_octahedron_all_disk():
    # octahedron: 6 vertices, each neighborhood is a 4-cycle
    # vertices: 0=+x 1=-x 2=+y 3=-y 4=+z 5=-z; edges between non-opposite
    opp = {0: 1, 1: 0, 2: 3, 3: 2, 4: 5, 5: 4}
    edges = [(a, b) for a in range(6) for b in range(a + 1, 6)
             if opp[a] != b]
    nbr, active = build(6, edges)
    st = states(nbr, active)
    assert all(st[i] == PATCH for i in range(6)), st[:6]


def test_disconnected_neighborhood_not_disk():
    # unit 0 sees two separate linked pairs (1-2) and (3-4)
    nbr, active = build(
        5, [(0, 1), (0, 2), (0, 3), (0, 4), (1, 2), (3, 4)])
    st = states(nbr, active)
    assert st[0] not in (DISK, PATCH, HALF_DISK)
    assert st[0] == CONNECTED


def test_overlinked_neighborhood_singular():
    # unit 0's neighborhood contains a node linked to 3 others (K4 inside
    # the neighborhood of 0) -> rowsum > 2 -> singular (non-manifold)
    edges = [(0, i) for i in (1, 2, 3, 4)]
    edges += [(1, 2), (1, 3), (1, 4), (2, 3), (3, 4), (2, 4)]
    nbr, active = build(5, edges)
    st = states(nbr, active)
    assert st[0] == SINGULAR


def test_soam_convergence_criterion_on_octahedron():
    from repro.core.gson.multi import soam_converged
    from repro.core.gson.state import init_state
    import jax

    opp = {0: 1, 1: 0, 2: 3, 3: 2, 4: 5, 5: 4}
    edges = [(a, b) for a in range(6) for b in range(a + 1, 6)
             if opp[a] != b]
    nbr, active = build(6, edges)
    st_ = init_state(jax.random.key(0), capacity=16, dim=3, max_deg=K,
                     n_seed=6)
    st_ = st_.replace(nbr=nbr, active=active,
                      firing=jnp.full((16,), 0.05),
                      n_active=jnp.asarray(6, jnp.int32))
    from repro.core.gson.multi import refresh_topology
    from repro.core.gson.state import GSONParams
    st_ = refresh_topology(st_, GSONParams())
    assert bool(soam_converged(st_))


def test_expire_edges_symmetric_and_counted():
    nbr, active = build(3, [(0, 1), (1, 2)])
    age = jnp.zeros_like(nbr, jnp.float32)
    age = topo.age_incident_edges(nbr, age, jnp.asarray([1], jnp.int32),
                                  jnp.asarray([True]), amount=50.0)
    nbr2, age2, n = topo.expire_edges(nbr, age, 30.0)
    assert int(n) == 2
    assert int(jnp.sum(nbr2 >= 0)) == 0


def test_drop_edges_to_inactive():
    nbr, active = build(3, [(0, 1), (1, 2)])
    age = jnp.zeros_like(nbr, jnp.float32)
    active = active.at[1].set(False)
    # the step clears inactive rows first, then drops dangling references
    nbr = jnp.where(active[:, None], nbr, jnp.int32(-1))
    nbr2, _ = topo.drop_edges_to_inactive(nbr, age, active)
    assert int(jnp.sum(nbr2 >= 0)) == 0  # both edges referenced unit 1


def _numpy_rank_within_rows(rows):
    """Each entry's count of earlier entries with the same value."""
    rank = np.empty_like(rows)
    seen = {}
    for i, r in enumerate(rows.tolist()):
        rank[i] = seen.get(r, 0)
        seen[r] = rank[i] + 1
    return rank


@pytest.mark.parametrize("n", [1, 2, 7, 2048, 4096])
def test_rank_within_rows_matches_numpy(n):
    rng = np.random.default_rng(n)
    rows = rng.integers(0, max(1, n // 8), n).astype(np.int32)
    rows[rng.random(n) < 0.5] = int(topo._BIG)
    got = np.asarray(jax.jit(topo._rank_within_rows)(jnp.asarray(rows)))
    np.testing.assert_array_equal(got, _numpy_rank_within_rows(rows))


def _primitive_names(jaxpr):
    """Names of every primitive in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitive_names(sub)


def test_insert_edges_has_no_loop_at_c768_shapes():
    # the collision rank is a sort and a cumulative max: a binary search
    # (a loop of dependent gathers; jnp.searchsorted traces to a scan,
    # which XLA lowers to a while) must not come back
    C, Kc, m = 768, 16, 2048
    jaxpr = jax.make_jaxpr(topo.insert_edges)(
        jnp.full((C, Kc), -1, jnp.int32), jnp.zeros((C, Kc), jnp.float32),
        jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.int32),
        jnp.zeros((m,), bool))
    names = set(_primitive_names(jaxpr.jaxpr))
    assert not names & {"while", "scan"}, sorted(names)
    assert "sort" in names and "cummax" in names
