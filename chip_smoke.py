"""Drive the surface-reconstruction path on a TPU and check its results.

    python chip_smoke.py              # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4    # four chips: phase (d) only

Every phase goes through the entry points a user calls (``RunSpec`` ->
``Session`` / ``FleetSession`` / ``ReconstructionServer``) with the
Pallas kernels compiled by Mosaic (backend ``pallas-full``):

  (a) reconstruction: the README configuration (sphere, SOAM,
      multi-fused, superstep 64, capacity 768, max_deg 16, 800
      iterations, seed 42), once with ``pallas-full`` and once with the
      pure-jnp ``reference``, both on the chip. The two networks must
      agree by ``metrics.topology_quality`` and keep |chi| <= 20.
  (b) paper width: ``configs.soam_paper.paper_spec`` (capacity 32768,
      an 8192-row signal buffer, so every iteration runs the kernels at
      8192 x 32768) for three supersteps per backend. Discrete state
      fields must be equal and floats within ``FLOAT_TOL``; where near
      ties split the discrete fields, the phase says so and falls back
      to ``topology_quality``.
  (c) server: ``ReconstructionServer(slots=4)`` serves sphere, torus,
      eight and trefoil at capacity 768 for 400 iterations each; every
      job must finish ``done`` with no fault record.
  (d) four chips (``--chips 4``, and only then): a B=8 fleet sharded
      over four chips against the same fleet on one chip (discrete
      fields bitwise, floats within 1e-6), and a signal-sharded session
      against the unsharded one (``topology_quality``).

No fallback may fire: the registry's reference-fallback warnings are
errors here, and after each phase every session, fleet cohort and
server wave must still hold the kernels it started with. Each phase
prints one JSON line (compile seconds from the spans of JAX's compile
events, in all and per kind: trace, lowering, backend compile; steady
wall seconds, iterations, units, edges, chi, QE, parity); the
last line is ``{"ok": true, "device": {...}}``. Any failure exits
non-zero before it. There is no CPU path: without a TPU the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

import jax
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

FLOAT_TOL = 1e-5          # phase (b): |w|, |age|, |firing| after 192 iterations
MESH_FLOAT_TOL = 1e-6     # phase (d): the tests/test_fleet_mesh.py contract
CHI_BOUND = 20            # |chi| beyond this at 800 iterations = broken topology
DISCRETE = ("active", "nbr", "topo_state", "n_active", "signal_count",
            "discarded")
FLOATS = ("w", "age", "firing")
SURFACES = ("sphere", "torus", "eight", "trefoil")
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
                  "/jax/core/compile/backend_compile_duration":
                      "backend_compile_s"}


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or degraded result."""


def covered(spans) -> float:
    """Seconds covered by the union of (start, end) spans."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache), from the time
    spans of its compile events.

    A jit called inside another is traced within the outer trace, so
    spans nest: each kind, and ``compile_s`` over all three, is the
    length of the union of its spans, never their sum.
    """

    def __init__(self):
        self._spans = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self._spans.append((COMPILE_EVENTS[event], start, end))

    def lap(self) -> dict:
        spans, self._spans = self._spans, []
        row = {"compile_s": covered((a, b) for _, a, b in spans)}
        for kind in COMPILE_EVENTS.values():
            row[kind] = covered((a, b) for k, a, b in spans if k == kind)
        return row


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def example_spec(surface: str, backend: str, iters: int, mesh: int = 0):
    """The spec ``examples/surface_reconstruction.py`` builds for
    ``--surface S --variant multi-fused --superstep 64 --capacity 768``."""
    from examples.surface_reconstruction import build_spec
    args = argparse.Namespace(
        surface=surface, variant="multi-fused", backend=backend,
        recall_target=None, superstep=64, mesh=mesh, capacity=768,
        iters=iters)
    return build_spec(args, signal_mesh=bool(mesh))


def held_kernels(find_winners, update_phase, backend: str):
    """The (find_winners, update_phase) pair a run must keep to the end."""
    from repro.core.gson.multi import find_winners_reference
    if backend == "pallas-full":
        require(update_phase is not None
                and find_winners is not find_winners_reference,
                "run did not start on the Pallas kernels")
    return find_winners, update_phase


def check_held(before, after, where: str) -> None:
    require(before[0] is after[0] and before[1] is after[1],
            f"{where}: the backend was swapped during the run")


def describe(state, stats_iterations: int, qe: float) -> dict:
    from repro.core.gson import metrics
    _, e, _, chi = metrics.euler_characteristic(state)
    return {"iterations": stats_iterations, "units": int(state.n_active),
            "edges": e, "chi": chi, "qe": qe}


def field_diffs(a, b) -> tuple[dict, dict]:
    """(differing element count per discrete field, max |diff| per float
    field) between two NetworkStates."""
    discrete = {n: int(np.sum(np.asarray(getattr(a, n))
                              != np.asarray(getattr(b, n))))
                for n in DISCRETE}
    floats = {n: float(np.max(np.abs(np.asarray(getattr(a, n))
                                     - np.asarray(getattr(b, n)))))
              for n in FLOATS}
    return discrete, floats


def timed_run(runner, budgets, clock: CompileClock) -> dict:
    """Advance a Session or FleetSession: the first budget carries the
    compile, the second (None = to the end) is the steady window.
    ``steady_compile_s`` is compile time inside that window, which
    should be 0: anything else is not steady work."""
    clock.lap()
    t0 = time.perf_counter()
    runner.run(budget=budgets[0])
    first_s = time.perf_counter() - t0
    compile_row = clock.lap()
    t0 = time.perf_counter()
    runner.run(budget=budgets[1])
    wall_s = time.perf_counter() - t0
    return {**compile_row, "first_call_s": first_s, "wall_s": wall_s,
            "steady_compile_s": clock.lap()["compile_s"]}


def run_session(spec, key, budgets, clock: CompileClock, backend: str):
    """One timed session. Returns (state, probes, row)."""
    from repro import gson
    sess = gson.Session(spec, key)
    held = held_kernels(sess.rt.find_winners, sess.rt.update_phase,
                        backend)
    row = {"backend": backend, **timed_run(sess, budgets, clock),
           "wall_iterations": sess.iteration - budgets[0]}
    check_held(held, (sess.rt.find_winners, sess.rt.update_phase),
               f"{backend} session")
    state, stats = sess.result()
    row.update(describe(state, stats.iterations,
                        float(stats.quantization_error)))
    return state, sess.rt.probes, row


def phase_reconstruction(clock: CompileClock) -> None:
    from repro.core.gson import metrics
    states, rows = {}, {}
    for backend in ("pallas-full", "reference"):
        spec = example_spec("sphere", backend, iters=800)
        states[backend], probes, rows[backend] = run_session(
            spec, jax.random.key(42), (64, None), clock, backend)
    q = metrics.topology_quality(states["pallas-full"],
                                 states["reference"], probes=probes)
    discrete, floats = field_diffs(states["pallas-full"],
                                   states["reference"])
    chi_ok = all(abs(r["chi"]) <= CHI_BOUND for r in rows.values())
    emit({"phase": "a_reconstruction", "runs": rows, "parity": {
        "check": "topology_quality", "ok": bool(q.ok and chi_ok),
        "chi": q.chi, "exact_chi": q.exact_chi, "qe": q.qe,
        "exact_qe": q.exact_qe, "qe_rel": q.qe_rel,
        "discrete_diff": discrete, "float_max_abs_diff": floats}})
    require(chi_ok, f"|chi| > {CHI_BOUND}: topology broken")
    require(q.ok, f"pallas-full vs reference: {q}")


def phase_paper_width(clock: CompileClock) -> None:
    from repro.configs.soam_paper import paper_spec
    from repro.core.gson import metrics
    states, rows = {}, {}
    spec = paper_spec("sphere", "multi-fused")
    for backend in ("pallas-full", "reference"):
        spec = spec.replace(backend=backend)
        states[backend], probes, rows[backend] = run_session(
            spec, jax.random.key(0), (64, 128), clock, backend)
        require(rows[backend]["iterations"] == 192,
                f"{backend}: ran {rows[backend]['iterations']} of 192 "
                "iterations")
    discrete, floats = field_diffs(states["pallas-full"],
                                   states["reference"])
    parity = {"discrete_diff": discrete, "float_max_abs_diff": floats,
              "float_tol": FLOAT_TOL}
    if not any(discrete.values()):
        parity.update(check="fields", ok=all(
            v <= FLOAT_TOL for v in floats.values()))
    else:
        # near ties flipped a discrete decision: the trajectories split,
        # so only the reconstruction's quality can be compared
        q = metrics.topology_quality(states["pallas-full"],
                                     states["reference"], probes=probes)
        parity.update(check="topology_quality (discrete fields differ)",
                      ok=bool(q.ok), chi=q.chi, exact_chi=q.exact_chi,
                      qe=q.qe, exact_qe=q.exact_qe, qe_rel=q.qe_rel)
    emit({"phase": "b_paper_width", "capacity": spec.capacity,
          "runs": rows, "parity": parity})
    require(parity["ok"], f"paper width pallas-full vs reference: {parity}")


def phase_server(clock: CompileClock) -> None:
    from repro.serving.engine import ReconstructionServer
    server = ReconstructionServer(slots=4)
    jobs = [server.submit(example_spec(s, "pallas-full", iters=400),
                          seed=i) for i, s in enumerate(SURFACES)]
    clock.lap()
    t0 = time.perf_counter()
    server.run()
    wall_s = time.perf_counter() - t0
    compile_row = clock.lap()
    rows, waves = [], {}
    for surface, job in zip(SURFACES, jobs):
        # a wave's networks are its jobs in admission (= submit) order
        wave = [j for j in jobs if j.session is job.session]
        state, stats = job.session.result(wave.index(job))
        waves[id(job.session)] = job.session
        rows.append({"surface": surface, "status": job.status,
                     "retries": job.retries, "error": job.error,
                     **describe(state, stats.iterations,
                                float(stats.quantization_error))})
    emit({"phase": "c_server", "slots": 4, **compile_row,
          "wall_s": wall_s, "jobs": rows})
    for r, job in zip(rows, jobs):
        require(r["status"] == "done" and r["error"] is None
                and r["retries"] == 0
                and r["iterations"] == job.spec.max_iterations,
                f"server job {r['surface']}: {r}")
    for fleet in waves.values():
        require(not fleet.faults, f"fault records: {fleet.faults}")
        for c in fleet.cohorts:
            held_kernels(c.find_winners, c.update_phase, "pallas-full")


def phase_mesh(clock: CompileClock) -> None:
    from repro import gson
    from repro.core.gson import metrics
    spec = example_spec("sphere", "pallas-full", iters=400)
    fleets, rows = {}, {}
    for name, mesh in (("sharded_4", gson.MeshSpec(axis="network",
                                                   devices=4)),
                       ("one_chip", None)):
        fleet = gson.FleetSession(gson.FleetSpec.broadcast(
            spec, seeds=range(8), mesh=mesh))
        held = [(c.find_winners, c.update_phase) for c in fleet.cohorts]
        for c in fleet.cohorts:
            held_kernels(c.find_winners, c.update_phase, "pallas-full")
        rows[name] = timed_run(fleet, (64, None), clock)
        for h, c in zip(held, fleet.cohorts):
            check_held(h, (c.find_winners, c.update_phase), name)
        fleets[name] = fleet
        rows[name]["iterations"] = [int(i) for i in fleet.iterations]
    worst_discrete, worst_float = 0, 0.0
    for i in range(8):
        a, _ = fleets["sharded_4"].result(i)
        b, _ = fleets["one_chip"].result(i)
        discrete, _ = field_diffs(a, b)
        worst_discrete = max(worst_discrete, sum(discrete.values()))
        for n in ("w", "age", "error", "firing", "threshold"):
            worst_float = max(worst_float, float(np.max(np.abs(
                np.asarray(getattr(a, n)) - np.asarray(getattr(b, n))))))
    fleet_ok = worst_discrete == 0 and worst_float <= MESH_FLOAT_TOL
    st0, stats0 = fleets["one_chip"].result(0)
    emit({"phase": "d_network_mesh", "batch": 8, "chips": 4, "runs": rows,
          **describe(st0, stats0.iterations,
                     float(stats0.quantization_error)),
          "parity": {"check": "discrete bitwise, floats within 1e-6",
                     "ok": fleet_ok, "discrete_diff": worst_discrete,
                     "float_max_abs_diff": worst_float}})
    require(fleet_ok, "sharded fleet differs from the one-chip fleet")

    states, srows = {}, {}
    for name, mesh in (("signal_4", 4), ("unsharded", 0)):
        s = example_spec("sphere", "pallas-full", iters=800, mesh=mesh)
        states[name], probes, srows[name] = run_session(
            s, jax.random.key(42), (64, None), clock, "pallas-full")
    q = metrics.topology_quality(states["signal_4"], states["unsharded"],
                                 probes=probes)
    discrete, floats = field_diffs(states["signal_4"], states["unsharded"])
    emit({"phase": "d_signal_mesh", "chips": 4, "runs": srows, "parity": {
        "check": "topology_quality", "ok": bool(q.ok), "chi": q.chi,
        "exact_chi": q.exact_chi, "qe": q.qe, "exact_qe": q.exact_qe,
        "qe_rel": q.qe_rel, "discrete_diff": discrete,
        "float_max_abs_diff": floats}})
    require(q.ok, f"signal-sharded vs unsharded: {q}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(c) on one chip; 4: the mesh "
                         "phase (d) alone, on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{devices[0].platform!r} devices")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips}: only {len(devices)} "
                         "devices visible")
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    # a kernel that fails to build or lower must fail the smoke, not
    # turn into a silent run of the reference
    warnings.filterwarnings(
        "error", message=r".*falling back to the reference",
        category=RuntimeWarning)
    from repro.kernels import interpret_mode
    require(interpret_mode(None) is False, "kernels would be interpreted")

    clock = CompileClock()
    if args.chips == 4:
        phase_mesh(clock)
    else:
        phase_reconstruction(clock)
        phase_paper_width(clock)
        phase_server(clock)
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
