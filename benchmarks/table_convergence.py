"""Tables 1-4 analog (+ Fig 7/10): convergence stats per surface x impl.

Two tables, because the four implementations differ by orders of
magnitude in CPU wall time (the paper's single-signal bunny consumed
620k signals on a workstation; this container is one core):

  A. SOAM topological convergence (the paper's termination criterion)
     for the multi-signal variant (+ the Pallas kernel backend in
     interpret mode): units/edges/signals/discarded + Euler check.

  B. The paper's headline behavioral claim (Sec. 3.2): effective
     signals to reach the same quantization error, single vs indexed
     vs multi, using GWR's threshold termination — CPU-feasible for
     the sequential variants and hardware-independent.

Implementations: single (sequential reference), indexed (hash grid),
multi (batched jnp), kernel (Pallas find_winners; interpreted on a CPU).
"""
from __future__ import annotations

import argparse

import jax

from benchmarks.common import (SURFACE_THRESHOLDS, emit, run_one,
                               variant_config_for)
from repro import gson
from repro.core.gson.state import GSONParams

COLS_A = ["surface", "variant", "iterations", "signals", "discarded",
          "effective_signals", "units", "connections", "avg_degree",
          "converged", "chi", "qe", "time_sample", "time_step", "wall"]

COLS_B = ["surface", "variant", "iterations", "effective_signals",
          "units", "converged", "qe", "wall", "signals_vs_multi"]


def run_soam(surfaces, budget) -> list[dict]:
    caps = {"quick": dict(capacity=640, max_iterations=1500),
            "full": dict(capacity=1024, max_iterations=4000)}[budget]
    rows = []
    for surface in surfaces:
        r = run_one(surface, "multi", **caps)
        st_rows = [("multi", r)]
        rk = run_one(surface, "multi", backend="pallas",
                     **dict(caps, max_iterations=40))
        rk["variant"] = "kernel(interp,40it)"
        st_rows.append(("kernel", rk))
        rows.extend(r for _, r in st_rows)
    emit("table_convergence_soam", rows, COLS_A)
    return rows


def _gwr_spec(surface, variant, qe_threshold, max_iterations):
    # finer insertion threshold than the SOAM runs so the QE target is
    # reachable by unit growth alone (GWR has no topological criterion)
    p = GSONParams(model="gwr",
                   insertion_threshold=0.7 * SURFACE_THRESHOLDS[surface],
                   age_max=64.0, eps_b=0.1, eps_n=0.01)
    vcfg = variant_config_for(variant, chunk=128)
    return gson.RunSpec(variant=variant, model=p, sampler=surface,
                        variant_config=vcfg, capacity=512, max_deg=16,
                        check_every=5, qe_threshold=qe_threshold,
                        max_iterations=max_iterations, n_probe=1024)


def run_signal_ratio(surfaces, budget) -> list[dict]:
    """Paper Sec. 3.2: effective signals to the same QE, per variant."""
    import time
    qe_target = {"sphere": 0.022, "torus": 0.013, "eight": 0.009,
                 "trefoil": 0.005}
    iters = {"quick": (800, 3000), "full": (2500, 6000)}[budget]
    rows = []
    for surface in surfaces:
        per = {}
        for variant, max_it in (("single", iters[0]),
                                ("indexed", iters[0]),
                                ("multi", iters[1])):
            spec = _gwr_spec(surface, variant, qe_target[surface],
                             max_it)
            t0 = time.time()
            state, stats = gson.run(spec, jax.random.key(7))
            row = dict(surface=surface, variant=variant,
                       iterations=stats.iterations,
                       effective_signals=stats.signals - stats.discarded,
                       units=stats.units, converged=stats.converged,
                       qe=stats.quantization_error,
                       wall=round(time.time() - t0, 1))
            per[variant] = row
            rows.append(row)
        m = per["multi"]["effective_signals"] or 1
        for v in per.values():
            v["signals_vs_multi"] = round(
                v["effective_signals"] / m, 2)
    emit("table_signal_ratio", rows, COLS_B)
    print("\n### paper Sec 3.2: single/multi effective-signal ratio "
          "(paper: 1x-4x, growing with complexity)")
    for surface in surfaces:
        s = [r for r in rows if r["surface"] == surface]
        single = next(r for r in s if r["variant"] == "single")
        print(f"  {surface}: {single['signals_vs_multi']}x")
    return rows


def run(surfaces=("sphere", "torus"), budget="quick") -> list[dict]:
    a = run_soam(surfaces, budget)
    b = run_signal_ratio(surfaces, budget)
    return a + b


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="quick", choices=("quick", "full"))
    ap.add_argument("--surfaces", default="sphere,torus")
    args = ap.parse_args(argv)
    run(tuple(args.surfaces.split(",")), args.budget)


if __name__ == "__main__":
    main()
