"""Plain reference of the SOAM multi-signal growing network.

A straightforward single-file statement of the semantics the system
under test implements (the multi-signal iteration of arXiv:1503.08294,
Sec. 2.2, with SOAM's rule set, Piastra 2012, in this repository's
batched form): the device m-schedule, exact Find Winners, the winner
lock, the Update, the structural tail (unit insertion, edge insertion,
expiry, pruning), the periodic topology refresh and the convergence
check. Each surface it samples is a file of its own, found by name
(``bench/reference/surfaces/<name>.py``, see ``bench.reference``). It
imports nothing of the program: every rule is written out here, in
plain ``jax.numpy`` at float32 with ``HIGHEST`` contractions, and no
kernel, fleet or session code is used.

The harness checks the program against it superstep by superstep: from
the program's network at the start of a superstep (or from the job's
seed, for the first), :func:`advance` runs the same iterations
and the two networks are compared field by field (:func:`compare`).

``precision`` selects how the two distance contractions are computed:
``"highest"`` is what the configuration states; ``"high"`` (three bf16
passes, emulated here so it means the same on any backend) and
``"bf16"`` (one pass) are the lower precisions the control runs at.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

NO_NBR = -1
BIG32 = int(np.iinfo(np.int32).max)
BIG30 = 1 << 30

# SOAM topological state ladder
ACTIVE, HABITUATED, CONNECTED, HALF_DISK, DISK, PATCH, SINGULAR = range(7)

DISCRETE = ("active", "nbr", "topo_state", "inconsistent_for", "n_active",
            "signal_count", "discarded", "dropped_edges", "dropped_units",
            "rng")
FLOATS = ("w", "age", "error", "firing", "threshold")


class Net(NamedTuple):
    """One network: a fixed pool of ``capacity`` unit slots."""

    w: jax.Array                 # (C, dim) f32
    active: jax.Array            # (C,) bool
    nbr: jax.Array               # (C, K) i32, -1 = empty
    age: jax.Array               # (C, K) f32
    error: jax.Array             # (C,) f32
    firing: jax.Array            # (C,) f32
    threshold: jax.Array         # (C,) f32
    topo_state: jax.Array        # (C,) i32
    inconsistent_for: jax.Array  # (C,) i32
    n_active: jax.Array          # () i32
    signal_count: jax.Array      # () i32
    discarded: jax.Array         # () i32
    dropped_edges: jax.Array     # () i32
    dropped_units: jax.Array     # () i32
    rng: jax.Array               # () typed PRNG key (collision key)


@dataclasses.dataclass(frozen=True)
class Params:
    """The rule set's constants, as a configuration file states them."""

    eps_b: float
    eps_n: float
    age_max: float
    insertion_threshold: float
    firing_threshold: float
    tau_b: float
    tau_n: float
    h_min: float
    thr_decay: float
    thr_recover: float
    thr_min_frac: float
    stuck_window: int
    capacity: int
    dim: int
    max_deg: int
    max_parallel: int            # signal buffer rows
    min_m: int
    refresh_every: int
    check_every: int
    precision: str = "highest"   # "highest" | "high" | "bf16"


# ---------------------------------------------------------------------------
# contractions

def _mm(a, b):
    return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)


def cross(a, b, precision: str):
    """a @ b.T in the stated precision (f32 accumulation throughout)."""
    if precision == "highest":
        return _mm(a, b)
    a_hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    b_hi = b.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "bf16":
        return _mm(a_hi, b_hi)
    if precision == "high":
        a_lo = (a - a_hi).astype(jnp.bfloat16).astype(jnp.float32)
        b_lo = (b - b_hi).astype(jnp.bfloat16).astype(jnp.float32)
        return _mm(a_hi, b_hi) + _mm(a_hi, b_lo) + _mm(a_lo, b_hi)
    raise ValueError(f"unknown precision {precision!r}")


def sq_dists(x, w, active, precision):
    """(m, C) squared distances, inf on inactive units."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    w2 = jnp.sum(w * w, axis=1)
    d2 = x2 - 2.0 * cross(x, w, precision) + w2[None, :]
    return jnp.where(active[None, :], d2, jnp.inf)


def find_winners(x, w, active, precision):
    """Exact top-2: (winner, second, d2 winner); lowest id on ties."""
    d2 = sq_dists(x, w, active, precision)
    wid = jnp.argmin(d2, axis=1).astype(jnp.int32)
    d2b = jnp.take_along_axis(d2, wid[:, None], axis=1)[:, 0]
    cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2m = jnp.where(cols == wid[:, None], jnp.inf, d2)
    sid = jnp.argmin(d2m, axis=1).astype(jnp.int32)
    d2s = jnp.take_along_axis(d2m, sid[:, None], axis=1)[:, 0]
    sid = jnp.where(jnp.isfinite(d2s), sid, wid)
    return wid, sid, jnp.maximum(d2b, 0.0)


# ---------------------------------------------------------------------------
# edges on fixed-degree neighbour lists (every edge stored in both rows)

def _find_slots(nbr, rows, vals):
    safe = jnp.clip(rows, 0, nbr.shape[0] - 1)
    hit = (nbr[safe] == vals[:, None]) & (vals[:, None] >= 0)
    slot = jnp.argmax(hit, axis=1).astype(jnp.int32)
    found = jnp.any(hit, axis=1) & (rows >= 0) & (rows < nbr.shape[0])
    return jnp.where(found, slot, -1)


def _rank_within_rows(rows):
    order = jnp.argsort(rows, stable=True)
    srt = rows[order]
    first = jnp.searchsorted(srt, srt, side="left")
    rank_sorted = (jnp.arange(rows.shape[0], dtype=jnp.int32)
                   - first.astype(jnp.int32))
    return jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)


def _reset_ages(nbr, age, a, b, mask):
    C = nbr.shape[0]
    rows = jnp.concatenate([a, b])
    vals = jnp.concatenate([b, a])
    m2 = jnp.concatenate([mask, mask])
    slots = _find_slots(nbr, jnp.where(m2, rows, -1), vals)
    ok = m2 & (slots >= 0)
    return age.at[jnp.where(ok, rows, C), jnp.maximum(slots, 0)].set(
        0.0, mode="drop")


def _insert_edges(nbr, age, a, b, mask):
    """Insert (a, b) where mask, or zero its age if present; an edge
    lands only where both rows have a free slot. -> nbr, age, dropped."""
    C, K = nbr.shape
    m = a.shape[0]
    valid = mask & (a >= 0) & (b >= 0) & (a != b)
    exists = _find_slots(nbr, jnp.where(valid, a, -1), b) >= 0
    age = _reset_ages(nbr, age, a, b, valid & exists)
    new = valid & ~exists
    if C * C >= 2 ** 31:
        raise ValueError("capacity too large for int32 edge keys")
    key = jnp.where(new, jnp.minimum(a, b) * C + jnp.maximum(a, b), BIG32)
    order = jnp.argsort(key)
    skey = key[order]
    first = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    new = new & jnp.zeros((m,), bool).at[order].set(first)

    rows = jnp.concatenate([a, b])
    vals = jnp.concatenate([b, a])
    emask = jnp.concatenate([new, new])
    rank = _rank_within_rows(jnp.where(emask, rows, BIG30))
    occupied = nbr[jnp.clip(rows, 0, C - 1)] >= 0
    free_count = (K - jnp.sum(occupied, axis=1)).astype(jnp.int32)
    slot_order = jnp.argsort(occupied, axis=1, stable=True)
    slot = jnp.take_along_axis(
        slot_order, jnp.minimum(rank, K - 1)[:, None].astype(jnp.int32),
        axis=1)[:, 0]
    fits = emask & (rank < free_count)
    edge_ok = fits[:m] & fits[m:]
    dropped = jnp.sum(new & ~edge_ok).astype(jnp.int32)
    srows = jnp.where(jnp.concatenate([edge_ok, edge_ok]), rows, C)
    nbr = nbr.at[srows, slot].set(vals.astype(jnp.int32), mode="drop")
    age = age.at[srows, slot].set(0.0, mode="drop")
    return nbr, age, dropped


def _remove_edges(nbr, age, a, b, mask):
    C = nbr.shape[0]
    rows = jnp.concatenate([a, b])
    vals = jnp.concatenate([b, a])
    m2 = jnp.concatenate([mask, mask])
    slots = _find_slots(nbr, jnp.where(m2, rows, -1), vals)
    srows = jnp.where(m2 & (slots >= 0), rows, C)
    nbr = nbr.at[srows, jnp.maximum(slots, 0)].set(NO_NBR, mode="drop")
    age = age.at[srows, jnp.maximum(slots, 0)].set(0.0, mode="drop")
    return nbr, age


def _age_winner_edges(nbr, age, winners, mask, protect):
    """+1 on every edge at a (distinct) winner, both rows; edges between
    two protected units do not age."""
    C, K = nbr.shape
    wc = jnp.clip(winners, 0, C - 1)
    row = nbr[wc]
    keep = protect[wc][:, None] & protect[jnp.clip(row, 0, C - 1)]
    inc = (row >= 0) & ~keep
    age = age.at[jnp.where(mask, winners, C)[:, None],
                 jnp.arange(K)[None, :]].add(inc.astype(age.dtype),
                                             mode="drop")
    back = nbr[jnp.clip(row, 0, C - 1)]
    onehot = ((back == winners[:, None, None]) & (row[:, :, None] >= 0)
              & ~keep[:, :, None])
    tgt = jnp.where(mask[:, None] & (row >= 0), row, C)
    return age.at[tgt[:, :, None], jnp.arange(K)[None, None, :]].add(
        onehot.astype(age.dtype), mode="drop")


# ---------------------------------------------------------------------------
# SOAM state ladder

def _unit_shape(nbr, unit_nbrs):
    C, K = nbr.shape
    valid = unit_nbrs >= 0
    deg = jnp.sum(valid)
    rows = nbr[jnp.clip(unit_nbrs, 0, C - 1)]
    link = jnp.any(rows[:, None, :] == unit_nbrs[None, :, None], axis=-1)
    link = link & valid[:, None] & valid[None, :] & ~jnp.eye(K, dtype=bool)
    rowsum = jnp.where(valid, jnp.sum(link, axis=1), 0)
    reach = link | jnp.eye(K, dtype=bool)
    for _ in range(max(1, K.bit_length())):
        reach = reach | ((reach.astype(jnp.float32)
                          @ reach.astype(jnp.float32)) > 0)
    conn = jnp.all(jnp.where(valid, reach[jnp.argmax(valid)], True))
    n_end = jnp.sum(jnp.where(valid, rowsum == 1, False))
    n_mid = jnp.sum(jnp.where(valid, rowsum == 2, False))
    over = jnp.any(jnp.where(valid, rowsum > 2, False))
    is_path = (deg >= 2) & conn & (n_end == 2) & (n_mid == deg - 2)
    is_cycle = (deg >= 3) & conn & (n_mid == deg) & ~over
    is_conn = (deg >= 2) & jnp.all(jnp.where(valid, rowsum >= 1, True))
    return deg, is_conn, is_path, is_cycle, over


def topo_states(nbr, active, firing, firing_threshold):
    C, K = nbr.shape
    deg, conn, path, cycle, over = jax.vmap(
        lambda u: _unit_shape(nbr, u))(nbr)
    hab = firing < firing_threshold
    st = jnp.full((C,), ACTIVE, jnp.int32)
    st = jnp.where(hab, HABITUATED, st)
    st = jnp.where(hab & conn, CONNECTED, st)
    st = jnp.where(hab & path, HALF_DISK, st)
    st = jnp.where(hab & cycle, DISK, st)
    st = jnp.where(hab & ((deg >= K) | (over & ~cycle & (deg >= 3))),
                   SINGULAR, st)
    safe = jnp.clip(nbr, 0, C - 1)
    nb_ok = jnp.all(jnp.where(nbr >= 0, (st[safe] >= DISK)
                              & (st[safe] != SINGULAR), True), axis=1)
    st = jnp.where((st == DISK) & nb_ok, PATCH, st)
    return jnp.where(active, st, ACTIVE)


def refresh(net: Net, p: Params) -> Net:
    """State ladder + per-unit insertion thresholds (tighten while stuck
    non-disk, relax once locally stable)."""
    st = topo_states(net.nbr, net.active, net.firing, p.firing_threshold)
    stable = (st >= DISK) & (st != SINGULAR)
    stuck = net.active & (net.firing < p.firing_threshold) & ~stable
    inc = jnp.where(stuck, net.inconsistent_for + 1, 0)
    tighten = inc >= p.stuck_window
    thr = jnp.where(tighten,
                    jnp.maximum(net.threshold * p.thr_decay,
                                p.insertion_threshold * p.thr_min_frac),
                    net.threshold)
    inc = jnp.where(tighten, 0, inc)
    thr = jnp.where(net.active & stable,
                    jnp.minimum(thr * p.thr_recover, p.insertion_threshold),
                    thr)
    return net._replace(topo_state=st, threshold=thr, inconsistent_for=inc)


def converged(net: Net):
    """Every active unit's neighbourhood is a disk (or a patch of one)."""
    ok = (net.topo_state == DISK) | (net.topo_state == PATCH)
    return jnp.all(jnp.where(net.active, ok, True)) & (net.n_active >= 4)


# ---------------------------------------------------------------------------
# one multi-signal iteration

def m_schedule(n_active, p: Params):
    """Smallest power of two above n_active, within [min_m, buffer]."""
    pows = jnp.asarray([1 << k for k in range(
        max(p.max_parallel.bit_length(), 1))], jnp.int32)
    m = jnp.minimum(jnp.min(jnp.where(pows > n_active, pows, BIG30)),
                    p.max_parallel)
    return jnp.maximum(m, min(p.min_m, p.max_parallel))


def step(net: Net, x, mask, p: Params) -> Net:
    """Signals ``x`` (rows where ``mask``) through one iteration."""
    C = p.capacity
    rng, k_lock = jax.random.split(net.rng)
    wid, sid, d2b = find_winners(x, net.w, net.active, p.precision)
    wc = jnp.clip(wid, 0, C - 1)

    # winner lock: one survivor per winner, by random unique priority
    prio = jax.random.permutation(k_lock, x.shape[0]).astype(jnp.int32)
    prio = jnp.where(mask, prio, BIG32)
    best = jnp.full((C,), BIG32, jnp.int32).at[wid].min(prio)
    sel = (prio == best[wid]) & mask
    n_sel = jnp.sum(sel).astype(jnp.int32)

    # insertion trigger, adaptation of winner and neighbours
    ins = (sel & (jnp.sqrt(d2b) > net.threshold[wc])
           & (net.firing[wc] < p.firing_threshold))
    adapt = sel & ~ins
    frozen = (net.topo_state >= DISK) & (net.topo_state != SINGULAR)
    h_b = net.firing[wc]
    scale_b = jnp.where(frozen[wc], 0.0, p.eps_b * h_b)
    nb = net.nbr[wc]
    nb_ok = (nb >= 0) & adapt[:, None]
    nb_c = jnp.clip(nb, 0, C - 1)
    h_n = net.firing[nb_c]
    scale_n = jnp.where(nb_ok & ~frozen[nb_c], p.eps_n * h_n, 0.0)
    dec_n = jnp.where(nb_ok, p.tau_n * (h_n - p.h_min), 0.0)

    w = net.w.at[jnp.where(adapt, wid, C)].add(
        scale_b[:, None] * (x - net.w[wc]), mode="drop")
    d_n = jnp.where(nb_ok[..., None],
                    scale_n[..., None] * (x[:, None, :] - w[nb_c]), 0.0)
    w = w.at[jnp.where(nb_ok, nb, C)].add(d_n, mode="drop")
    firing = net.firing.at[jnp.where(adapt, wid, C)].add(
        -(p.tau_b * (h_b - p.h_min)), mode="drop")
    firing = firing.at[jnp.where(nb_ok, nb, C)].add(-dec_n, mode="drop")
    firing = jnp.clip(firing, p.h_min, 1.0)
    age = _age_winner_edges(net.nbr, net.age, wid, sel, frozen)
    age = _reset_ages(net.nbr, age, wid, sid, adapt)

    # unit insertion halfway between winner and signal
    free = jnp.argsort(net.active, stable=True)
    rank = jnp.cumsum(ins.astype(jnp.int32)) - 1
    fits = ins & (rank < C - net.n_active)
    new = jnp.where(fits, free[jnp.clip(rank, 0, C - 1)], C)
    w = w.at[new].set(0.5 * (w[wc] + x), mode="drop")
    active = net.active.at[new].set(True, mode="drop")
    firing = firing.at[new].set(1.0, mode="drop")
    error = net.error.at[new].set(0.0, mode="drop")
    threshold = net.threshold.at[new].set(net.threshold[wc], mode="drop")
    topo_state = net.topo_state.at[new].set(0, mode="drop")
    inconsistent = net.inconsistent_for.at[new].set(0, mode="drop")

    # edges (new, b), (new, s); drop (b, s); refresh (b, s) on adaptation
    nbr, age, d1 = _insert_edges(net.nbr, age, jnp.concatenate([new, new]),
                                 jnp.concatenate([wid, sid]),
                                 jnp.concatenate([fits, fits]))
    nbr, age = _remove_edges(nbr, age, wid, sid, fits)
    nbr, age, d2 = _insert_edges(nbr, age, wid, sid, adapt)

    # expiry, then prune units left without edges
    expired = (nbr >= 0) & (age > p.age_max)
    nbr = jnp.where(expired, NO_NBR, nbr)
    age = jnp.where(expired, 0.0, age)
    lone = active & (jnp.sum(nbr >= 0, axis=1) == 0) & (firing < 1.0 - 1e-6)
    active = active & ~lone
    nbr = jnp.where(active[:, None], nbr, NO_NBR)
    ok = (nbr >= 0) & active[jnp.clip(nbr, 0, C - 1)]
    nbr = jnp.where(ok, nbr, NO_NBR)
    age = jnp.where(ok, age, 0.0)

    m_eff = jnp.sum(mask).astype(jnp.int32)
    return Net(
        w=w, active=active, nbr=nbr, age=age, error=error, firing=firing,
        threshold=threshold, topo_state=topo_state,
        inconsistent_for=inconsistent,
        n_active=jnp.sum(active).astype(jnp.int32),
        signal_count=net.signal_count + m_eff,
        discarded=net.discarded + (m_eff - n_sel),
        dropped_edges=net.dropped_edges + d1 + d2,
        dropped_units=net.dropped_units + jnp.sum(ins & ~fits).astype(
            jnp.int32),
        rng=rng)


# ---------------------------------------------------------------------------
# a job: init, and supersteps of iterations with cadenced checks

class Carry(NamedTuple):
    net: Net
    key: jax.Array       # sampling key
    iteration: jax.Array  # () i32 global iteration counter
    done: jax.Array      # () bool last check's verdict


def init(seed_key, sample, p: Params):
    """A job's first network and its sampling key. (The third key
    draws the probe set that only the reported error uses.)"""
    key, k_init, _, k_seed = jax.random.split(seed_key, 4)
    pts = sample(k_seed, 2)
    C, K = p.capacity, p.max_deg
    net = Net(
        w=jnp.zeros((C, p.dim), jnp.float32).at[:2].set(pts),
        active=jnp.zeros((C,), bool).at[:2].set(True),
        nbr=jnp.full((C, K), NO_NBR, jnp.int32),
        age=jnp.zeros((C, K), jnp.float32),
        error=jnp.zeros((C,), jnp.float32),
        firing=jnp.ones((C,), jnp.float32),
        threshold=jnp.full((C,), p.insertion_threshold, jnp.float32),
        topo_state=jnp.zeros((C,), jnp.int32),
        inconsistent_for=jnp.zeros((C,), jnp.int32),
        n_active=jnp.int32(2), signal_count=jnp.int32(0),
        discarded=jnp.int32(0), dropped_edges=jnp.int32(0),
        dropped_units=jnp.int32(0),
        rng=jax.random.split(k_init)[0])
    return net, key


def sampling_key(key, iterations):
    """The sampling key after ``iterations`` iterations."""
    return jax.lax.fori_loop(0, iterations,
                             lambda _, k: jax.random.split(k)[0], key)


def superstep(carry: Carry, max_steps, sample, p: Params):
    """Up to ``max_steps`` iterations; stops early once a check finds
    the network converged. -> (carry, iterations run)."""

    def body(c):
        (net, key, it, _), n = c
        key, k_sig = jax.random.split(key)
        x = sample(k_sig, p.max_parallel)
        mask = jnp.arange(p.max_parallel) < m_schedule(net.n_active, p)
        net = step(net, x, mask, p)
        net = jax.lax.cond(it % p.refresh_every == 0,
                           lambda s: refresh(s, p), lambda s: s, net)
        it = it + 1

        def check(s):
            s = refresh(s, p)
            return s, converged(s)

        net, done = jax.lax.cond(it % p.check_every == 0, check,
                                 lambda s: (s, jnp.asarray(False)), net)
        return Carry(net, key, it, done), n + 1

    def cond(c):
        carry, n = c
        return ~carry.done & (n < max_steps)

    return jax.lax.while_loop(cond, body, (carry, jnp.int32(0)))


@partial(jax.jit, static_argnames=("surface", "p"))
def start(seed_key, *, surface: str, p: Params):
    """-> Carry at iteration 0."""
    net, key = init(seed_key, reference.surface(surface).sample, p)
    return Carry(net, key, jnp.int32(0), jnp.asarray(False))


@partial(jax.jit, static_argnames=("surface", "p"))
def advance(carry: Carry, max_steps, *, surface: str, p: Params):
    """One superstep from ``carry`` -> (carry, iterations run)."""
    return superstep(carry, max_steps, reference.surface(surface).sample, p)


@partial(jax.jit, static_argnames=("surface", "p"))
def resume_key(seed_key, iteration, *, surface: str, p: Params):
    """The sampling key of a job at ``iteration``."""
    _, key = init(seed_key, reference.surface(surface).sample, p)
    return sampling_key(key, iteration)


# ---------------------------------------------------------------------------
# comparison

def host_fields(net) -> dict:
    """Any network with these fields -> {name: numpy array}; PRNG keys
    as their raw data."""
    out = {}
    for name in Net._fields:
        v = getattr(net, name)
        if name == "rng" and jnp.issubdtype(v.dtype, jax.dtypes.prng_key):
            v = jax.random.key_data(v)
        out[name] = np.asarray(v)
    return out


def as_net(fields: dict) -> Net:
    """{name: array} (as :func:`host_fields` gives) -> a device Net."""
    vals = {k: jnp.asarray(v) for k, v in fields.items()}
    vals["rng"] = jax.random.wrap_key_data(vals["rng"])
    return Net(**vals)


def compare(a: dict, b: dict) -> tuple[int, float]:
    """(differing discrete elements, widest float gap) of two networks
    given as :func:`host_fields` dicts."""
    diff = sum(int(np.sum(a[n] != b[n])) for n in DISCRETE)
    gap = max(float(np.max(np.abs(a[n].astype(np.float64)
                                  - b[n].astype(np.float64))))
              for n in FLOATS)
    return diff, gap


def euler_characteristic(nbr: np.ndarray, active: np.ndarray) -> int:
    """V - E + F of the graph, F counted as its 3-cliques."""
    ids = np.nonzero(active)[0]
    adj = {int(i): {int(j) for j in nbr[i] if j >= 0} for i in ids}
    e = sum(len(s) for s in adj.values()) // 2
    f = sum(1 for a, nb in adj.items() for b in nb if b > a
            for c in adj[a] & adj[b] if c > b)
    return len(ids) - e + f
