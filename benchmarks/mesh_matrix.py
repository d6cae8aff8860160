"""Sharded-fleet scaling matrix at forced host device counts.

The mesh claim (paper Sec. 2.5, one level up): a B-network cohort
sharded over ``ndev`` devices runs as ONE shard_map program with zero
per-iteration collectives, so aggregate throughput should track the
device count until the per-device batch stops amortizing dispatch.
This benchmark measures aggregate ``signals/sec`` for a B=8 fleet at
``ndev`` in {1, 2, 4, 8} *forced host devices*
(``XLA_FLAGS=--xla_force_host_platform_device_count``), sharded vs the
ndev=1 unsharded baseline, and lands in ``BENCH_gson.json:
mesh_matrix``.

On the CPU each cell runs in a fresh subprocess — XLA device-count
flags must be set before jax first initializes, exactly like
``tests/conftest.run_with_devices``. Host "devices" are threads over
the same physical cores, so absolute scaling is bounded by the
machine's core count (measured numbers in EXPERIMENTS.md §Sharding);
the table's job is to pin the *shape* of the curve and catch
structural regressions (a sharded program that suddenly inserts
collectives or resharding copies shows up as a falling
``speedup_vs_1dev`` long before a TPU pod ever runs it).

On an accelerator the cells run in this process over the first
``ndev`` of ``jax.devices()`` (counts above the visible devices are
skipped): a chip belongs to the one process that opened it, so a child
could not reach it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COLS = ["variant", "batch", "ndev", "iters_per_net", "wall", "sps",
        "speedup_vs_1dev"]

NDEVS = (1, 2, 4, 8)
BATCH = 8


def _measure(variant: str, ndev: int, batch: int, iters: int) -> dict:
    """One cell: a B-network fleet on ``ndev`` devices, timed warm."""
    from repro import gson
    from repro.core.gson.state import GSONParams

    spec = gson.RunSpec(
        variant=variant,
        model=GSONParams(model="gwr", insertion_threshold=0.3),
        sampler="sphere", capacity=128, max_deg=12,
        max_iterations=iters, check_every=20,
        qe_threshold=1e-9,              # never converges: fixed workload
        n_probe=256)
    mesh = (gson.MeshSpec(axis="network", devices=ndev)
            if ndev > 1 else None)
    fspec = gson.FleetSpec.broadcast(spec, seeds=range(batch), mesh=mesh)

    def once() -> int:
        fleet = gson.FleetSession(fspec)
        fleet.run()
        return sum(int(c.signals.sum()) for c in fleet.cohorts)

    once()                              # warmup: compile
    t0 = time.perf_counter()
    signals = once()
    return {"signals": signals, "wall": time.perf_counter() - t0}


def _worker(args) -> None:
    """One cell, inside the forced-device-count subprocess."""
    print(json.dumps(_measure(args.variant, args.ndev, args.batch,
                              args.iters)))


def _cell(variant: str, ndev: int, iters: int) -> dict:
    import jax

    if jax.default_backend() != "cpu":
        payload = _measure(variant, ndev, BATCH, iters)
    else:
        payload = _host_cell(variant, ndev, iters)
    return {
        "variant": variant,
        "batch": BATCH,
        "ndev": ndev,
        "iters_per_net": iters,
        "wall": round(payload["wall"], 3),
        "sps": round(payload["signals"] / payload["wall"], 1),
    }


def _host_cell(variant: str, ndev: int, iters: int) -> dict:
    """One CPU cell in a child with ``ndev`` forced host devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ndev}")
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.mesh_matrix", "--worker",
         "--variant", variant, "--ndev", str(ndev),
         "--batch", str(BATCH), "--iters", str(iters)],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=REPO_ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh_matrix worker (ndev={ndev}) failed:\n"
            f"{proc.stdout}\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(budget: str = "quick") -> list[dict]:
    import jax

    from benchmarks.common import emit

    iters = {"quick": 40, "full": 120}[budget]
    variants = (("multi-fused",) if budget == "quick"
                else ("multi", "multi-fused"))
    ndevs = (NDEVS if jax.default_backend() == "cpu"
             else tuple(n for n in NDEVS if n <= len(jax.devices())))
    rows = []
    for variant in variants:
        base_sps = None
        for ndev in ndevs:
            row = _cell(variant, ndev, iters)
            if ndev == 1:
                base_sps = row["sps"]
            row["speedup_vs_1dev"] = round(row["sps"] / base_sps, 2)
            rows.append(row)
    emit("mesh_matrix", rows, COLS)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--variant", default="multi-fused")
    ap.add_argument("--ndev", type=int, default=1)
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--budget", default="quick",
                    choices=("quick", "full"))
    args = ap.parse_args(argv)
    if args.worker:
        _worker(args)
    else:
        run(budget=args.budget)


if __name__ == "__main__":
    main()
