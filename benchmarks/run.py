"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--budget quick|full]

Outputs markdown tables to stdout, JSON per table to .runs/bench/, and a
machine-readable aggregate ``BENCH_gson.json`` at the repo root so future
PRs have a perf trajectory to regress against (per-variant step time,
per-signal time, convergence stats).
"""
from __future__ import annotations

import argparse
import json
import os
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(REPO_ROOT, "BENCH_gson.json")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="quick", choices=("quick", "full"))
    ap.add_argument("--only", default=None,
                    help="comma list: convergence,phase,per_signal,"
                         "update,superstep,roofline,variants,fleet,mesh,"
                         "faults,ann")
    ap.add_argument("--out", default=BENCH_JSON,
                    help="aggregate JSON path (default: repo root)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    results = {}
    if want("per_signal"):
        from benchmarks import fig_per_signal
        results["per_signal"] = fig_per_signal.run()
    if want("phase"):
        from benchmarks import fig_phase_times
        results["phase_times"] = fig_phase_times.run()
    if want("update"):
        from benchmarks import bench_update_phase
        results["update_phase"] = bench_update_phase.run()
    if want("superstep"):
        from benchmarks import bench_superstep
        results["superstep"] = bench_superstep.run()
    if want("ann"):
        # approximate Find Winners crossover vs the exact dense scan;
        # speedup_ann_* keys gate nightly at >=64k units
        from benchmarks import ann_matrix
        results["ann_matrix"] = ann_matrix.run(budget=args.budget)
    if want("variants"):
        # enumerated from repro.gson.VARIANTS: newly registered variants
        # appear in BENCH_gson.json without touching the benchmarks
        from benchmarks import variant_matrix
        results["variant_matrix"] = variant_matrix.run(budget=args.budget)
    if want("fleet"):
        # batched multi-network execution vs looped Sessions
        from benchmarks import fleet_matrix
        results["fleet_matrix"] = fleet_matrix.run(budget=args.budget)
    if want("mesh"):
        # sharded fleets at forced host device counts (subprocesses)
        from benchmarks import mesh_matrix
        results["mesh_matrix"] = mesh_matrix.run(budget=args.budget)
    if want("faults"):
        # fault-tolerance overhead + recovery latency (informational:
        # no speedup/sps keys, so the nightly gate ignores it)
        from benchmarks import fault_matrix
        results["fault_matrix"] = fault_matrix.run(budget=args.budget)
    if want("convergence"):
        from benchmarks import table_convergence
        results["convergence"] = table_convergence.run(budget=args.budget)
    if want("roofline"):
        from benchmarks import roofline_table
        results["roofline"] = roofline_table.run()

    # partial (--only) runs MERGE into the existing aggregate instead of
    # clobbering the tables they didn't produce — BENCH_gson.json is the
    # perf trajectory future PRs regress against
    merged = dict(results)
    if only and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prev = json.load(f).get("results", {})
            merged = {**prev, **results}
        except (json.JSONDecodeError, OSError):
            pass
    payload = {
        "generated_by": "benchmarks.run",
        "budget": args.budget,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "wall_seconds": round(time.time() - t0, 1),
        "results": merged,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"\n[benchmarks] aggregate written to {args.out}")
    print(f"[benchmarks] done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
