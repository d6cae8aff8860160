"""Streaming, resumable run sessions.

The paper runs fixed experiments to convergence (Sec. 3); a Session is
that loop productionized — the same iterate-sample-converge schedule,
but observable (streaming history rows), budgetable (pause/resume) and
durable (checkpoint/restore), without changing a single emitted signal.

``Session`` replaces the monolithic ``GSONEngine.run`` with a driver
that can stop and continue:

  * **streaming** — every convergence check produces a history row that
    is appended to ``stats.history``, pushed to registered callbacks,
    and yielded from :meth:`stream`, while the run is in flight;
  * **budgeted** — ``session.run(budget=N)`` advances at most N
    iterations and returns; ``session.resume()`` (or another ``run``
    call) continues exactly where it stopped. Signals are a pure
    function of the session RNG, which is threaded through every step,
    so a paused-and-resumed run produces the same network as an
    uninterrupted one;
  * **restartable** — :meth:`checkpoint` snapshots the ``NetworkState``
    (+ both PRNG keys + progress counters) through
    ``repro.checkpoint.manager``'s atomic format, and
    :meth:`Session.restore` reconstructs a live session from the newest
    (or any) snapshot — long reconstructions survive preemption.

``run(spec)`` is the one-shot convenience wrapper.

Distributed runs need no session changes: a ``RunSpec`` carrying a
signal-axis :class:`~repro.gson.spec.MeshSpec` resolves to a sharded
Find Winners program (``resolve`` swaps the backend callable), and the
checkpoint format stores logical network state only, so snapshots move
freely between device counts. Network-axis sharding lives one level up,
on ``FleetSpec`` (see ``repro.gson.fleet``).
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt
from repro.core.gson import fleet as fleet_core
from repro.core.gson import metrics
from repro.gson import registry
from repro.gson.spec import RunSpec, resolve
from repro.utils.timing import span

# the ``session`` id every trace span of one Session carries
_SESSION_IDS = itertools.count()


@dataclass
class RunStats:
    """Aggregate run statistics (one row of the paper's tables)."""

    iterations: int = 0
    signals: int = 0
    discarded: int = 0
    units: int = 0
    connections: int = 0
    converged: bool = False
    quantization_error: float = float("nan")
    time_total: float = 0.0
    time_sample: float = 0.0
    time_step: float = 0.0        # Find Winners + Update (fused under jit)
    time_convergence: float = 0.0
    history: list = field(default_factory=list)

    def row(self) -> dict:
        d = self.__dict__.copy()
        d.pop("history")
        return d


def _key_data(key: jax.Array) -> jax.Array:
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def _wrap_key(data) -> jax.Array:
    data = jnp.asarray(data)
    if jnp.issubdtype(data.dtype, jax.dtypes.prng_key):
        return data
    return jax.random.wrap_key_data(data)


HistoryCallback = Callable[[dict], None]


class Session:
    """One (spec, seed) experiment with pause / stream / checkpoint."""

    def __init__(self, spec: RunSpec, rng: jax.Array | None = None, *,
                 seed: int = 0, on_history: HistoryCallback | None = None,
                 verbose: bool = False, checkpoint_dir: str | None = None,
                 checkpoint_every: int = 0, keep: int = 3):
        self.spec = spec
        self.strategy, self.rt = resolve(spec)
        self.id = self.rt.session_id = next(_SESSION_IDS)
        self._rng0 = rng if rng is not None else jax.random.key(seed)
        self._callbacks: list[HistoryCallback] = []
        if on_history is not None:
            self._callbacks.append(on_history)
        self.verbose = verbose
        self.stats = RunStats()
        self.state = None
        self._rng = None
        self.iteration = 0
        self.converged = False
        self.checkpoint_every = checkpoint_every
        self._last_ckpt = -1
        self._stepped = False
        self._mgr = (ckpt.CheckpointManager(checkpoint_dir, keep=keep)
                     if checkpoint_dir else None)

    # ------------------------------------------------------------------
    def add_callback(self, f: HistoryCallback) -> None:
        self._callbacks.append(f)

    @property
    def started(self) -> bool:
        return self.state is not None

    @property
    def active(self) -> bool:
        """More work to do? (not converged, limits not exhausted)"""
        if self.converged:
            return False
        if self.iteration >= self.spec.max_iterations:
            return False
        if not self.started:
            return True
        with span("readback", session=self.id):
            signals = int(self.state.signal_count)
        return signals < self.spec.max_signals

    # ------------------------------------------------------------------
    def _init_from(self, rng0: jax.Array):
        """State + probes + sampling key through the fleet core's
        batched init at B=1 — the SAME jitted program a
        ``repro.gson.fleet.FleetSession`` runs for B networks, so a
        session and a same-seed fleet slot start bit-identically."""
        spec, p = self.spec, self.rt.params
        fs, probes = fleet_core.fleet_init(
            rng0[None],
            sampler=fleet_core.BroadcastSampler(self.rt.sampler),
            capacity=spec.capacity, dim=spec.dim, max_deg=spec.max_deg,
            n_probe=spec.n_probe,
            init_threshold=p.insertion_threshold)
        return fs.network(0), probes[0], fs.rng[0]

    def _start(self) -> None:
        if self.started:
            return
        # NOT timed: the legacy engine started its clock after state /
        # probe init, and BENCH_gson.json per-iteration rows divide
        # time_total by iterations — counting setup here would skew the
        # perf trajectory against the PR1 baseline
        with span("session.start", session=self.id):
            self.state, self.rt.probes, self._rng = self._init_from(
                self._rng0)
            self.strategy.prepare(self.rt)

    def _emit(self, qe: float) -> dict:
        """Build the history row of a completed check and publish it."""
        with span("session.emit", session=self.id):
            with span("readback", session=self.id):
                units = int(self.state.n_active)
            with span("readback", session=self.id):
                signals = int(self.state.signal_count)
            row = {"iteration": self.iteration, "units": units,
                   "signals": signals, "qe": qe}
            self.stats.history.append(row)
            for f in self._callbacks:
                f(row)
            if self.verbose:
                print(f"  it={row['iteration']:6d} "
                      f"units={row['units']:6d} "
                      f"signals={row['signals']:9d} qe={row['qe']:.5f}")
        return row

    # ------------------------------------------------------------------
    def stream(self, budget: int | None = None) -> Iterator[dict]:
        """Advance the run, yielding history rows as checks complete.

        ``budget`` bounds the iterations executed by THIS call; the
        session stays live afterwards and can be resumed.
        """
        self._start()
        spec = self.spec
        spent = 0
        t_wall = time.perf_counter()
        try:
            while self.active and (budget is None or spent < budget):
                row = None
                with span("superstep", session=self.id,
                          iteration=self.iteration):
                    max_iters = spec.max_iterations - self.iteration
                    if budget is not None:
                        max_iters = min(max_iters, budget - spent)
                    try:
                        res = self.strategy.step(self.rt, self.state,
                                                 self._rng, self.iteration,
                                                 max_iters)
                    except Exception as e:            # noqa: BLE001
                        # first-call lowering failure of a kernel
                        # backend: swap in the reference pair (identical
                        # results, slower) and retry; anything else
                        # re-raises
                        fb = (None if self._stepped
                              else registry.reference_fallback(
                                  self.rt.find_winners,
                                  self.rt.update_phase, e))
                        if fb is None:
                            raise
                        self.rt.find_winners, self.rt.update_phase = fb
                        res = self.strategy.step(self.rt, self.state,
                                                 self._rng, self.iteration,
                                                 max_iters)
                    self._stepped = True
                    self.state, self._rng = res.state, res.rng
                    self.iteration += res.iterations
                    spent += res.iterations
                    self.stats.time_sample += res.timings.get("sample", 0.0)
                    self.stats.time_step += res.timings.get("step", 0.0)
                    self.stats.time_convergence += res.timings.get(
                        "convergence", 0.0)
                    if res.done:
                        self.converged = True
                        self.stats.converged = True
                        self.stats.quantization_error = res.qe
                    if res.checked:
                        row = self._emit(res.qe)
                # the span closes before the yield: the caller's work
                # between rows is not the session's
                if row is not None:
                    yield row
                if (self._mgr is not None and self.checkpoint_every > 0
                        and self.iteration - self._last_ckpt
                        >= self.checkpoint_every):
                    self.checkpoint()
        finally:
            self.stats.time_total += time.perf_counter() - t_wall
            self.stats.iterations = self.iteration

    def run(self, budget: int | None = None) -> RunStats:
        """Advance until convergence / limits, or ``budget`` iterations."""
        for _ in self.stream(budget):
            pass
        return self.stats

    def resume(self, budget: int | None = None) -> RunStats:
        """Continue a paused (or restored) session."""
        return self.run(budget)

    def result(self):
        """Finalize and return ``(state, stats)`` (engine-compatible)."""
        self._start()
        with span("session.result", session=self.id):
            st = self.state
            self.stats.iterations = self.iteration
            self.stats.signals = int(st.signal_count)
            self.stats.discarded = int(st.discarded)
            self.stats.units = int(st.n_active)
            self.stats.connections = metrics.edge_count(st)
            if np.isnan(self.stats.quantization_error):
                self.stats.quantization_error = float(
                    metrics.quantization_error(st, self.rt.probes))
        return st, self.stats

    # ------------------------------------------------------------------
    # checkpointing
    def _savable_tree(self) -> dict:
        st = self.state
        return {
            "state": st.replace(rng=_key_data(st.rng)),
            "rng": _key_data(self._rng),
            "rng0": _key_data(self._rng0),
        }

    def checkpoint(self, step: int | None = None) -> None:
        """Atomic snapshot via ``repro.checkpoint.manager``."""
        if self._mgr is None:
            raise RuntimeError(
                "Session was created without checkpoint_dir")
        self._start()
        step = self.iteration if step is None else step
        extra = {
            "iteration": self.iteration,
            "converged": self.converged,
            "quantization_error": self.stats.quantization_error,
            "history": self.stats.history,
            "checkpoint_every": self.checkpoint_every,
        }
        self._mgr.save(self._savable_tree(), step, extra)
        self._last_ckpt = self.iteration

    @classmethod
    def restore(cls, spec: RunSpec, checkpoint_dir: str,
                step: int | None = None, **kw) -> "Session":
        """Rebuild a live session from a snapshot directory.

        The snapshot carries both PRNG keys and the periodic-checkpoint
        cadence, so the restored session continues the exact signal
        stream of the original run AND keeps snapshotting — no seed or
        cadence bookkeeping required from the caller (an explicit
        ``checkpoint_every=`` kwarg still overrides the saved one).
        """
        sess = cls(spec, checkpoint_dir=checkpoint_dir, **kw)
        sess._start()
        tree, _, extra = sess._mgr.restore(sess._savable_tree(), step)
        sess._rng0 = _wrap_key(tree["rng0"])
        # probes are a pure function of the initial key: re-derive them
        # (through the same jitted init program) so convergence checks
        # match the original run exactly
        _, sess.rt.probes, _ = sess._init_from(sess._rng0)
        state = tree["state"]
        sess.state = state.replace(rng=_wrap_key(state.rng))
        sess._rng = _wrap_key(tree["rng"])
        sess.iteration = int(extra["iteration"])
        sess.converged = bool(extra["converged"])
        if "checkpoint_every" not in kw:
            sess.checkpoint_every = int(extra.get("checkpoint_every", 0))
        sess._last_ckpt = sess.iteration
        sess.stats.converged = sess.converged
        sess.stats.iterations = sess.iteration
        sess.stats.quantization_error = float(
            extra.get("quantization_error", float("nan")))
        sess.stats.history = list(extra.get("history", []))
        return sess


def run(spec: RunSpec, rng: jax.Array | None = None, *, seed: int = 0,
        verbose: bool = False, on_history: HistoryCallback | None = None):
    """One-shot: assemble from the registries, run to termination.

    Returns ``(state, stats)`` like the legacy ``GSONEngine.run``.
    """
    sess = Session(spec, rng, seed=seed, verbose=verbose,
                   on_history=on_history)
    sess.run()
    return sess.result()
