"""Vectorized topology ops on fixed-degree neighbor lists.

The network graph is stored as per-unit neighbor lists ``nbr: (C, K) i32``
(``NO_NBR``/-1 = empty slot) plus aligned edge ages ``age: (C, K) f32``.
Every edge (a, b) is stored twice — in row a and in row b — and all ops
below preserve exact symmetry (same neighbor sets, identical ages), which
``tests/test_gson_invariants.py`` asserts.

Batched structural updates are the TPU-side answer to the paper's Update
phase: the winner lock guarantees *distinct winners*, but distinct winners
may still touch the same rows (shared neighbors, same new edge), so each
op here resolves intra-batch collisions deterministically (sort + rank +
masked scatter) instead of relying on GPU write-race order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.gson.state import (ACTIVE, CONNECTED, DISK, HABITUATED,
                                   HALF_DISK, NO_NBR, PATCH, SINGULAR)

_BIG = jnp.int32(2**30)


def degrees(nbr: jax.Array) -> jax.Array:
    """(C,) number of occupied neighbor slots per unit."""
    return jnp.sum(nbr >= 0, axis=1).astype(jnp.int32)


def find_slots(nbr: jax.Array, rows: jax.Array, vals: jax.Array) -> jax.Array:
    """Slot index of ``vals[i]`` inside ``nbr[rows[i]]`` or -1 if absent.

    ``rows`` entries that are out of range are treated as absent.
    """
    safe_rows = jnp.clip(rows, 0, nbr.shape[0] - 1)
    row_vals = nbr[safe_rows]                             # (n, K)
    hit = (row_vals == vals[:, None]) & (vals[:, None] >= 0)
    slot = jnp.argmax(hit, axis=1).astype(jnp.int32)
    found = jnp.any(hit, axis=1) & (rows >= 0) & (rows < nbr.shape[0])
    return jnp.where(found, slot, -1)


def _rank_within_rows(rows: jax.Array) -> jax.Array:
    """For each entry, its 0-based rank among equal values of ``rows``.

    Invalid rows must already be set to a large sentinel so they group
    together (their ranks are unused).
    """
    iota = jnp.arange(rows.shape[0], dtype=jnp.int32)
    sorted_rows, order = jax.lax.sort((rows, iota), num_keys=1,
                                      is_stable=True)
    # rank in sorted order = position - first position of this row value;
    # the first position is the latest run start at or before it (a
    # cumulative max, where a binary search would gather once per round)
    start = (iota == 0) | (sorted_rows != jnp.roll(sorted_rows, 1))
    first = jax.lax.cummax(jnp.where(start, iota, 0))
    rank = jnp.zeros_like(iota).at[order].set(iota - first)
    return rank


def reset_edge_ages(nbr: jax.Array, age: jax.Array, a: jax.Array,
                    b: jax.Array, mask: jax.Array) -> jax.Array:
    """Set age of existing edges (a, b) to zero, both directions."""
    C = nbr.shape[0]
    rows = jnp.concatenate([a, b])
    vals = jnp.concatenate([b, a])
    m2 = jnp.concatenate([mask, mask])
    slots = find_slots(nbr, jnp.where(m2, rows, -1), vals)
    ok = m2 & (slots >= 0)
    srows = jnp.where(ok, rows, C)  # OOB -> dropped by scatter
    return age.at[srows, jnp.maximum(slots, 0)].set(0.0, mode="drop")


def insert_edges(nbr: jax.Array, age: jax.Array, a: jax.Array, b: jax.Array,
                 mask: jax.Array):
    """Symmetric insert-or-refresh of edges (a[i], b[i]) where mask[i].

    Existing edges get their age reset to 0. New edges are placed in free
    slots; intra-batch duplicates are deduplicated; an edge is dropped
    (counted) unless BOTH endpoint rows have a free slot.

    Returns (nbr, age, dropped_count).
    """
    C, K = nbr.shape
    m = a.shape[0]
    valid = mask & (a >= 0) & (b >= 0) & (a != b)

    # --- refresh existing edges ---
    slot_ab = find_slots(nbr, jnp.where(valid, a, -1), b)
    exists = slot_ab >= 0
    age = reset_edge_ages(nbr, age, a, b, valid & exists)

    new = valid & ~exists
    # --- deduplicate identical new edges within the batch ---
    # int32 key is safe while C^2 < 2^31 (capacity <= 46340)
    assert C <= 46340, "capacity too large for int32 edge keys"
    lo = jnp.minimum(a, b)
    hi = jnp.maximum(a, b)
    key = jnp.where(new, lo * C + hi, jnp.iinfo(jnp.int32).max)
    new = new & (_rank_within_rows(key) == 0)

    # --- directed entries, rank within target row, pick free slots ---
    rows = jnp.concatenate([a, b])
    vals = jnp.concatenate([b, a])
    emask = jnp.concatenate([new, new])
    rrows = jnp.where(emask, rows, _BIG)
    rank = _rank_within_rows(rrows)

    safe_rows = jnp.clip(rows, 0, C - 1)
    occupied = nbr[safe_rows] >= 0                       # (2m, K)
    free_count = (K - jnp.sum(occupied, axis=1)).astype(jnp.int32)
    # stable argsort: False (free) slots first, ascending position
    slot_order = jnp.argsort(occupied, axis=1, stable=True)
    slot = jnp.take_along_axis(
        slot_order, jnp.minimum(rank, K - 1)[:, None].astype(jnp.int32), axis=1
    )[:, 0]
    fits = emask & (rank < free_count)

    # an edge lands only if BOTH directions fit (symmetry)
    edge_ok = fits[:m] & fits[m:]
    dropped = jnp.sum(new & ~edge_ok).astype(jnp.int32)
    ok2 = jnp.concatenate([edge_ok, edge_ok])
    srows = jnp.where(ok2, rows, C)
    nbr = nbr.at[srows, slot].set(vals.astype(jnp.int32), mode="drop")
    age = age.at[srows, slot].set(0.0, mode="drop")
    return nbr, age, dropped


def remove_edge_pairs(nbr: jax.Array, age: jax.Array, a: jax.Array,
                      b: jax.Array, mask: jax.Array):
    """Remove edges (a[i], b[i]) where mask[i], both directions."""
    C = nbr.shape[0]
    rows = jnp.concatenate([a, b])
    vals = jnp.concatenate([b, a])
    m2 = jnp.concatenate([mask, mask])
    slots = find_slots(nbr, jnp.where(m2, rows, -1), vals)
    ok = m2 & (slots >= 0)
    srows = jnp.where(ok, rows, C)
    nbr = nbr.at[srows, jnp.maximum(slots, 0)].set(NO_NBR, mode="drop")
    age = age.at[srows, jnp.maximum(slots, 0)].set(0.0, mode="drop")
    return nbr, age


def age_incident_edges(nbr: jax.Array, age: jax.Array, winners: jax.Array,
                       mask: jax.Array, amount: float = 1.0,
                       protect: jax.Array | None = None):
    """Increment the age of every edge incident to ``winners`` (symmetric).

    Post winner-lock, winners are distinct, so each winner row is touched
    once; mirrored increments on neighbor rows may collide across winners
    and are accumulated with scatter-add (deterministic).

    ``protect``: (C,) bool — edges whose BOTH endpoints are protected do
    not age. SOAM freezes topologically stable (disk/patch)
    neighborhoods so completed surface regions crystallize instead of
    churning through expiry (see EXPERIMENTS.md H-soam-2).
    """
    C, K = nbr.shape
    if protect is None:
        protect = jnp.zeros((C,), bool)
    w = jnp.where(mask, winners, C)
    # forward: whole winner row
    wc = jnp.clip(winners, 0, C - 1)
    row_nbrs = nbr[wc]                                    # (m, K)
    row_valid = row_nbrs >= 0
    keep = (protect[wc][:, None]
            & protect[jnp.clip(row_nbrs, 0, C - 1)])
    inc = row_valid & ~keep
    age = age.at[w[:, None], jnp.arange(K)[None, :]].add(
        amount * inc.astype(age.dtype), mode="drop")
    # mirror: for each neighbor c of winner b, slot of b inside row c
    nbrs = row_nbrs
    safe_nbrs = jnp.clip(nbrs, 0, C - 1)
    back = nbr[safe_nbrs]                                 # (m, K, K)
    onehot = (back == winners[:, None, None]) & (nbrs[:, :, None] >= 0)
    onehot = onehot & ~keep[:, :, None]
    tgt_rows = jnp.where(mask[:, None] & (nbrs >= 0), nbrs, C)
    age = age.at[tgt_rows[:, :, None], jnp.arange(K)[None, None, :]].add(
        amount * onehot.astype(age.dtype), mode="drop")
    return age


def expire_edges(nbr: jax.Array, age: jax.Array, age_max: float):
    """Drop all edges with age > age_max. Symmetric because ages are."""
    expired = (nbr >= 0) & (age > age_max)
    nbr = jnp.where(expired, NO_NBR, nbr)
    age = jnp.where(expired, 0.0, age)
    return nbr, age, jnp.sum(expired).astype(jnp.int32) // 2


def prune_isolated(active: jax.Array, nbr: jax.Array, firing: jax.Array):
    """Deactivate units that lost all their edges (and have fired)."""
    deg = degrees(nbr)
    remove = active & (deg == 0) & (firing < 1.0 - 1e-6)
    return active & ~remove, jnp.sum(remove).astype(jnp.int32)


def drop_edges_to_inactive(nbr: jax.Array, age: jax.Array, active: jax.Array):
    """Remove dangling references to deactivated units."""
    safe = jnp.clip(nbr, 0, active.shape[0] - 1)
    ok = (nbr >= 0) & active[safe]
    return jnp.where(ok, nbr, NO_NBR), jnp.where(ok, age, 0.0)


# ---------------------------------------------------------------------------
# SOAM topological state ladder
# ---------------------------------------------------------------------------

def _neighborhood_linkgraph(nbr: jax.Array, unit_nbrs: jax.Array) -> jax.Array:
    """M[p, q] = True iff neighbors p and q of a unit are linked.

    ``unit_nbrs``: (K,) neighbor ids of one unit. Returns (K, K) bool.
    """
    C = nbr.shape[0]
    valid = unit_nbrs >= 0
    rows = nbr[jnp.clip(unit_nbrs, 0, C - 1)]            # (K, K)
    m = jnp.any(rows[:, None, :] == unit_nbrs[None, :, None], axis=-1)
    m = m & valid[:, None] & valid[None, :]
    m = m & ~jnp.eye(unit_nbrs.shape[0], dtype=bool)
    return m


def _is_connected(m: jax.Array, valid: jax.Array) -> jax.Array:
    """All valid nodes mutually reachable in the (K, K) link graph."""
    K = m.shape[0]
    reach = m | jnp.eye(K, dtype=bool)
    n_sq = max(1, K.bit_length())
    for _ in range(n_sq):
        # 0/1 operands with small integer sums: exact at any precision
        reach = reach | (
            (reach.astype(jnp.float32) @ reach.astype(jnp.float32)) > 0)
    first = jnp.argmax(valid)
    from_first = reach[first]
    return jnp.all(jnp.where(valid, from_first, True))


def compute_topo_states(nbr: jax.Array, active: jax.Array, firing: jax.Array,
                        firing_threshold: float) -> jax.Array:
    """Full-network SOAM state ladder (vectorized over all capacity rows).

    Returns (C,) int32 states. Inactive rows get ACTIVE (ignored upstream).
    """
    C, K = nbr.shape

    def per_unit(unit_nbrs):
        valid = unit_nbrs >= 0
        deg = jnp.sum(valid)
        m = _neighborhood_linkgraph(nbr, unit_nbrs)
        rowsum = jnp.sum(m, axis=1)
        rowsum = jnp.where(valid, rowsum, 0)
        conn = _is_connected(m, valid)
        all1plus = jnp.all(jnp.where(valid, rowsum >= 1, True))
        n_end = jnp.sum(jnp.where(valid, rowsum == 1, False))
        n_mid = jnp.sum(jnp.where(valid, rowsum == 2, False))
        overlinked = jnp.any(jnp.where(valid, rowsum > 2, False))
        is_path = (deg >= 2) & conn & (n_end == 2) & (n_mid == deg - 2)
        is_cycle = (deg >= 3) & conn & (n_mid == deg) & ~overlinked
        is_conn_state = (deg >= 2) & all1plus
        return deg, is_conn_state, is_path, is_cycle, overlinked

    deg, conn_s, path_s, cycle_s, over = jax.vmap(per_unit)(nbr)
    habituated = firing < firing_threshold

    state = jnp.full((C,), ACTIVE, jnp.int32)
    state = jnp.where(habituated, HABITUATED, state)
    state = jnp.where(habituated & conn_s, CONNECTED, state)
    state = jnp.where(habituated & path_s, HALF_DISK, state)
    state = jnp.where(habituated & cycle_s, DISK, state)
    singular = habituated & ((deg >= K) | (over & ~cycle_s & (deg >= 3)))
    state = jnp.where(singular, SINGULAR, state)

    # PATCH: disk whose neighbors are all disk-or-patch
    safe = jnp.clip(nbr, 0, C - 1)
    nb_disk = (state[safe] >= DISK) & (state[safe] != SINGULAR)
    nb_ok = jnp.all(jnp.where(nbr >= 0, nb_disk, True), axis=1)
    state = jnp.where((state == DISK) & nb_ok, PATCH, state)
    state = jnp.where(active, state, ACTIVE)
    return state
