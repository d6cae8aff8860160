"""Shape-aware Update-phase backend autotuning.

No single Update-phase implementation wins everywhere: the tiled
one-hot contraction (``kernels/update_phase``) beats the scatter
reference at production pool sizes but collapses past the
capacity crossover (O(m·capacity) dense vs O(m) sparse —
``BENCH_gson.json: update_phase`` measured 0.37–0.47× at units
≥ 1024), while the winner-neighborhood slab (``sparse.py``) wins
exactly where capacity dwarfs the batch. The per-shape
kernel-selection discipline here mirrors the CUDA SSO practice of
timing per-configuration once and dispatching from a table.

This module measures all three once per ``(units, capacity, m)``
cell, persists a **versioned JSON selection table**, and exposes the
``pallas-auto`` BACKENDS entry: an ``UpdatePhaseFn`` adapter that
consults the table with the *static* shapes available at trace time
(``state.capacity``, ``signals.shape[0]``), so every jit key —
step / superstep / fleet / mesh cohorts — sees one stable callable
and each compiled shape gets the backend the table says is fastest
there. Unmeasured shapes fall back to the nearest measured cell in
log-shape space (the paper's m-schedule ties units ≈ m/2, which is
the default units estimate for a lookup that only knows ``m``).

Table resolution order (first hit wins):

1. an explicit ``table=`` argument (``SelectionTable`` or a path);
2. ``$REPRO_AUTOTUNE_TABLE`` — an operator override, loaded strictly
   (a broken override is an error, not a silent fallback);
3. a local cache, only when ``$REPRO_AUTOTUNE_CACHE`` names it (write
   one with ``python -m repro.gson.autotune --out``) — loaded leniently
   (corrupt/stale-schema caches are warned away);
4. the committed default ``autotune_table.json`` next to this module,
   regenerated by ``python -m repro.gson.autotune``.

Every table read from a file records the JAX backend it was measured
on (``meta.backend``); loading one on another backend raises
:class:`TableBackendError` — timings from one device say nothing about
another, so the table must be regenerated on this one.

``tests/test_autotune.py`` pins: fake-timer determinism, JSON
round-trip + schema-version rejection, nearest-cell fallback, the env
override, and that selection always returns the measured-best backend
of the committed table.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

from repro.kernels import PlatformMismatchError

SCHEMA_VERSION = 1
ENV_TABLE = "REPRO_AUTOTUNE_TABLE"
ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
PACKAGED_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "autotune_table.json")

# the measured grid: the bench m-schedule (m = 2·units) across the
# production pool, the past-the-crossover cliff rows, and the
# big-pool/modest-batch regime the sparse slab targets
DEFAULT_CELLS: tuple[tuple[int, int, int], ...] = (
    (32, 768, 64),
    (64, 768, 128),
    (128, 768, 256),
    (256, 768, 512),
    (384, 768, 768),
    (1024, 2048, 2048),
    (2048, 2048, 4096),
    (256, 4096, 512),
    (384, 8192, 768),
)

# TimerFn: (candidate_name, zero-arg compiled thunk) -> seconds/call.
# Injectable so tests drive selection with a fake clock.
TimerFn = Callable[[str, Callable[[], Any]], float]


class TableSchemaError(ValueError):
    """A selection table whose ``schema`` is not SCHEMA_VERSION."""


class TableBackendError(PlatformMismatchError):
    """A selection table measured on another JAX backend."""


@dataclass(frozen=True)
class Cell:
    """One measured grid point: per-backend µs and the winner."""

    units: int
    capacity: int
    m: int
    best: str
    t_us: Mapping[str, float]

    def to_json(self) -> dict:
        return {"units": self.units, "capacity": self.capacity,
                "m": self.m, "best": self.best, "t_us": dict(self.t_us)}

    @classmethod
    def from_json(cls, row: Mapping) -> "Cell":
        return cls(units=int(row["units"]), capacity=int(row["capacity"]),
                   m=int(row["m"]), best=str(row["best"]),
                   t_us={str(k): float(v)
                         for k, v in dict(row["t_us"]).items()})


@dataclass(frozen=True)
class SelectionTable:
    """The persisted measurement: shape cells -> fastest backend."""

    cells: tuple[Cell, ...]
    schema: int = SCHEMA_VERSION
    meta: Mapping[str, Any] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"schema": self.schema, "meta": dict(self.meta),
                "cells": [c.to_json() for c in self.cells]}

    @classmethod
    def from_json(cls, payload: Mapping) -> "SelectionTable":
        schema = payload.get("schema")
        if schema != SCHEMA_VERSION:
            raise TableSchemaError(
                f"selection-table schema {schema!r} != supported "
                f"{SCHEMA_VERSION}; regenerate it with "
                f"`python -m repro.gson.autotune`")
        cells = tuple(Cell.from_json(r) for r in payload.get("cells", ()))
        if not cells:
            raise ValueError("selection table has no cells")
        return cls(cells=cells, meta=dict(payload.get("meta", {})))

    def nearest(self, capacity: int, m: int,
                units: int | None = None) -> Cell:
        """Nearest measured cell in log-shape space.

        An exact ``(capacity, m)`` grid point has distance 0 on those
        axes and wins outright. ``units`` defaults to ``m // 2`` — the
        paper's m-schedule (m = 2·units), which is also how the
        measured grid was laid out — because the caller resolving a
        jit key knows the static shapes but not the (dynamic) live
        unit count. Ties break on the lexicographically smallest cell
        so selection is deterministic.
        """
        u = units if units is not None else max(1, m // 2)

        def dist(c: Cell):
            return (math.log2(c.capacity / capacity) ** 2
                    + math.log2(c.m / m) ** 2
                    + math.log2(c.units / max(1, u)) ** 2,
                    (c.capacity, c.m, c.units))

        return min(self.cells, key=dist)

    def select(self, capacity: int, m: int,
               units: int | None = None) -> str:
        """Backend name for a shape (nearest-cell winner)."""
        return self.nearest(capacity, m, units).best


# ---------------------------------------------------------------------------
# measurement


@functools.lru_cache(maxsize=None)
def update_phase_candidates() -> Mapping[str, Any]:
    """The autotunable UpdatePhaseFn implementations, by table name.

    Shares the registry's memoized adapter instances so an autotuned
    dispatch and a directly selected ``pallas-update`` /
    ``pallas-sparse`` backend hit the same jit caches.
    """
    from repro.core.gson.multi import update_phase_reference
    from repro.gson.registry import (_pallas_update_phase,
                                     _sparse_update_phase)
    return {
        "reference": update_phase_reference,
        "pallas": _pallas_update_phase(),
        "sparse": _sparse_update_phase(),
    }


def _cell_inputs(units: int, capacity: int, m: int, *, dim: int = 3,
                 max_deg: int = 16, model: str = "soam", seed: int = 0):
    """A synthetic phase input at the requested shape: ``units`` active
    pool rows seeded from the benchmark sphere, one signal batch, Find
    Winners held fixed outside any timer (same recipe as
    ``benchmarks/bench_update_phase.py``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.gson.multi import find_winners_reference
    from repro.core.gson.sampling import make_sampler
    from repro.core.gson.state import GSONParams, init_state

    p = GSONParams(model=model)
    sampler = make_sampler("sphere")
    st = init_state(jax.random.key(seed), capacity=capacity, dim=dim,
                    max_deg=max_deg,
                    seed_points=sampler(jax.random.key(seed + 1), units))
    st = st.replace(active=jnp.zeros((capacity,), bool)
                    .at[:units].set(True),
                    n_active=jnp.asarray(units, jnp.int32))
    signals = sampler(jax.random.key(seed + 2), m)
    wid, sid, d2b, _ = find_winners_reference(signals, st.w, st.active)
    return st, signals, wid, sid, d2b, jax.random.key(seed + 3), p


def wall_timer(n: int = 5, warmup: int = 2) -> TimerFn:
    """The default timer: jit-warmed, blocked, wall-clock seconds."""
    from repro.utils.timing import timed

    def t(_name: str, thunk: Callable[[], Any]) -> float:
        _, dt = timed(thunk, n=n, warmup=warmup)
        return dt

    return t


def measure_cell(units: int, capacity: int, m: int, *,
                 candidates: Mapping[str, Any] | None = None,
                 timer: TimerFn | None = None, n: int = 5,
                 warmup: int = 2, **input_kw) -> Cell:
    """Time every candidate at one shape; returns the measured Cell.

    Deterministic by construction: the winner is the (time, name)
    minimum, so equal fake-timer readings always pick the same backend.
    """
    import functools as ft

    import jax

    cands = dict(candidates if candidates is not None
                 else update_phase_candidates())
    timer = timer if timer is not None else wall_timer(n=n, warmup=warmup)
    st, signals, wid, sid, d2b, k_lock, p = _cell_inputs(
        units, capacity, m, **input_kw)
    t_us: dict[str, float] = {}
    for name in sorted(cands):
        # undonated jit: the timer re-feeds the same state every call
        fn = jax.jit(ft.partial(cands[name], st, signals, wid, sid,
                                d2b, k_lock, p))
        t_us[name] = timer(name, fn) * 1e6
    best = min(t_us, key=lambda k: (t_us[k], k))
    return Cell(units=units, capacity=capacity, m=m, best=best,
                t_us=t_us)


def build_table(cells: tuple[tuple[int, int, int], ...] = DEFAULT_CELLS,
                *, candidates: Mapping[str, Any] | None = None,
                timer: TimerFn | None = None, n: int = 5,
                warmup: int = 2, meta: Mapping[str, Any] | None = None,
                ) -> SelectionTable:
    """Measure a shape grid into a SelectionTable."""
    measured = tuple(
        measure_cell(u, c, m, candidates=candidates, timer=timer, n=n,
                     warmup=warmup)
        for (u, c, m) in cells)
    info = dict(meta or {})
    if "jax_version" not in info:
        try:
            import jax
            info.setdefault("jax_version", jax.__version__)
            info.setdefault("backend", jax.default_backend())
        except Exception:                                   # noqa: BLE001
            pass
    return SelectionTable(cells=measured, meta=info)


# ---------------------------------------------------------------------------
# persistence


def save_table(table: SelectionTable, path: str) -> str:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(table.to_json(), f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def _read_table(path: str) -> SelectionTable:
    import jax

    with open(path) as f:
        table = SelectionTable.from_json(json.load(f))
    measured_on = table.meta.get("backend")
    if measured_on != jax.default_backend():
        raise TableBackendError(
            f"autotune table {path!r} was measured on backend "
            f"{measured_on!r}, this process runs on "
            f"{jax.default_backend()!r}; regenerate it on this device "
            "with `python -m repro.gson.autotune --out PATH`")
    return table


def load_table(source: str | SelectionTable | None = None,
               ) -> SelectionTable:
    """Resolve a selection table (see the module docstring's order).

    ``source``: a ready table (returned as-is), an explicit path
    (loaded strictly), or ``None`` for the env → cache → committed
    chain. Only the lenient cache step degrades (with a
    RuntimeWarning), and never past a backend mismatch; explicit paths
    and the env override raise.
    """
    if isinstance(source, SelectionTable):
        return source
    if source is not None:
        return _read_table(source)
    env = os.environ.get(ENV_TABLE)
    if env:
        return _read_table(env)
    cache = os.environ.get(ENV_CACHE)
    if cache and os.path.exists(cache):
        try:
            return _read_table(cache)
        except (ValueError, OSError, KeyError) as e:
            warnings.warn(
                f"ignoring unusable autotune cache {cache!r} "
                f"({type(e).__name__}: {e}); using the committed "
                "default table", RuntimeWarning, stacklevel=2)
    return _read_table(PACKAGED_TABLE)


# ---------------------------------------------------------------------------
# the pallas-auto UpdatePhaseFn adapter


def select_update_phase(table: SelectionTable, capacity: int, m: int,
                        units: int | None = None) -> str:
    """Table winner for a shape, constrained to known candidates (a
    table naming a backend this build lacks degrades to reference)."""
    name = table.select(capacity, m, units)
    if name not in update_phase_candidates():
        warnings.warn(
            f"autotune table selects unknown update-phase backend "
            f"{name!r}; using the reference", RuntimeWarning,
            stacklevel=2)
        return "reference"
    return name


def make_autotuned_update_phase(table: str | SelectionTable | None = None):
    """An ``UpdatePhaseFn`` that dispatches per static shape.

    The returned closure is ONE stable callable — the jit cache key
    every program threads (step / superstep / fleet / mesh cohorts)
    stays exactly as for any other backend — and the backend choice
    happens inside the trace, where ``state.capacity`` and
    ``signals.shape[0]`` are concrete Python ints. Table loading is
    deferred to the first trace so constructing the backend never
    touches the filesystem. ``neighbor_collision="last"`` always
    routes to the reference (the kernel paths deliberately raise).
    """
    box: dict[str, SelectionTable] = {}

    def resolve_table() -> SelectionTable:
        if "t" not in box:
            box["t"] = load_table(table)
        return box["t"]

    def auto_update_phase(state, signals, wid, sid, d2b, k_lock, params,
                          signal_mask=None):
        if params.neighbor_collision != "sum":
            name = "reference"
        else:
            name = select_update_phase(resolve_table(), state.capacity,
                                       signals.shape[0])
        impl = update_phase_candidates()[name]
        return impl(state, signals, wid, sid, d2b, k_lock, params,
                    signal_mask)

    auto_update_phase.resolve_table = resolve_table
    auto_update_phase.select = (
        lambda capacity, m, units=None:
        select_update_phase(resolve_table(), capacity, m, units))
    return auto_update_phase


# ---------------------------------------------------------------------------
# CLI: regenerate the committed default (or a local cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measure the update-phase backends per shape cell "
                    "and write a selection table")
    ap.add_argument("--out", default=PACKAGED_TABLE,
                    help="table path (default: the committed package "
                         "table)")
    ap.add_argument("--cells", default=None,
                    help="comma list of units:capacity:m triples "
                         "(default: the benchmark grid)")
    ap.add_argument("--n", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args(argv)
    cells = DEFAULT_CELLS
    if args.cells:
        cells = tuple(
            tuple(int(x) for x in spec.split(":"))
            for spec in args.cells.split(",") if spec)
        if any(len(c) != 3 for c in cells):
            ap.error("--cells wants units:capacity:m triples")
    table = build_table(cells, n=args.n, warmup=args.warmup)
    save_table(table, args.out)
    for c in table.cells:
        times = "  ".join(f"{k}={v / 1e3:.2f}ms"
                          for k, v in sorted(c.t_us.items()))
        print(f"({c.units:>5}, {c.capacity:>5}, {c.m:>5}) -> "
              f"{c.best:<9} {times}")
    print(f"[autotune] wrote {len(table.cells)} cells to {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
