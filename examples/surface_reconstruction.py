"""End-to-end driver for the paper's task: surface reconstruction.

  PYTHONPATH=src python examples/surface_reconstruction.py \
      --surface eight --variant multi --iters 1500 --out eight.obj

  # N surfaces at once, one batched device program, one mesh each:
  PYTHONPATH=src python examples/surface_reconstruction.py \
      --fleet 4 --variant multi-fused --iters 800 --out meshes.obj

Built on the composable ``repro.gson`` API: the run is declared as a
``RunSpec`` whose variant / model / sampler / backend are names resolved
through the registries (``--variant`` choices are enumerated from
``gson.VARIANTS`` at startup, so a newly registered variant appears here
automatically), and driven by a streaming ``gson.Session``:

  * progress rows print as convergence checks complete (``stream``);
  * ``--checkpoint-dir`` snapshots the network every
    ``--checkpoint-every`` iterations through ``repro.checkpoint``;
    re-running with ``--resume`` continues from the newest snapshot —
    the same signal stream, as if the run had never stopped.

``--fleet N`` reconstructs N surfaces concurrently — one sampler each,
cycling through ``gson.SAMPLERS`` — as a ``gson.FleetSession``: every
network steps inside the same vmapped program (grouped into one cohort
per distinct insertion threshold), streams its own progress rows, and
exports its own mesh (``--out base.obj`` -> ``base_0_sphere.obj``, ...).

``--mesh D`` shards execution over D devices (``gson.MeshSpec``): with
``--fleet`` it shards the fleet's network axis (each device owns whole
networks, zero per-iteration collectives; cohorts pad themselves when
the fleet does not divide D), without it, the signal axis of the single
network (the paper's data partitioning). On a CPU-only host, force the
device count first:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python examples/surface_reconstruction.py \\
      --fleet 8 --mesh 4 --variant multi-fused

After the run each reconstructed topology is validated (Euler
characteristic vs the surface's known genus) and optionally exported as
a Wavefront .obj.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import gson
from repro.core.gson import metrics
from repro.utils.compile_cache import enable_compile_cache

GENUS = {"sphere": 0, "torus": 1, "eight": 2, "trefoil": 1}
THRESH = {"sphere": 0.35, "torus": 0.25, "eight": 0.22, "trefoil": 0.12}


def export_obj(state, path: str):
    nbr = np.asarray(state.nbr)
    active = np.asarray(state.active)
    w = np.asarray(state.w)
    ids = np.nonzero(active)[0]
    remap = {int(u): i + 1 for i, u in enumerate(ids)}   # obj is 1-based
    adj = {int(u): set(int(x) for x in nbr[u] if x >= 0) for u in ids}
    faces = set()
    for a in ids:
        a = int(a)
        for b in adj[a]:
            if b <= a:
                continue
            for c in adj[a] & adj[b]:
                if c > b:
                    faces.add((a, b, c))
    with open(path, "w") as f:
        f.write("# repro multi-signal SOAM reconstruction\n")
        for u in ids:
            f.write(f"v {w[u, 0]:.6f} {w[u, 1]:.6f} {w[u, 2]:.6f}\n")
        for a, b, c in sorted(faces):
            f.write(f"f {remap[a]} {remap[b]} {remap[c]}\n")
    return len(ids), len(faces)


def build_spec(args, *, signal_mesh: bool = False) -> gson.RunSpec:
    variant, backend = args.variant, args.backend
    if variant == "kernel":     # legacy alias: multi + Pallas backend
        variant = "multi"
        if backend == "reference":      # only the untouched default
            backend = "pallas"
    if args.recall_target is not None:
        if backend not in ("ann-windowed", "ann-grid"):
            raise SystemExit(
                "--recall-target tunes the approximate backends; pair "
                "it with --backend ann-windowed or ann-grid")
        # a concrete Backend object rides the spec in place of a name
        backend = gson.ann_backend(backend, args.recall_target)
    vcfg = None
    if variant == "multi-fused":
        vcfg = gson.FusedConfig(
            superstep=gson.SuperstepConfig(length=args.superstep),
            refresh_every=2)
    elif variant == "multi":
        vcfg = gson.MultiConfig(refresh_every=2)
    mesh = (gson.MeshSpec(axis="signal", devices=args.mesh)
            if signal_mesh and args.mesh else None)
    return gson.RunSpec(
        variant=variant,
        model=gson.GSONParams(model="soam",
                              insertion_threshold=THRESH.get(
                                  args.surface, 0.25),
                              age_max=64.0, eps_b=0.1, eps_n=0.01,
                              stuck_window=60),
        sampler=args.surface,
        backend=backend,
        variant_config=vcfg,
        mesh=mesh,
        capacity=args.capacity, max_deg=16,
        check_every=25, max_iterations=args.iters)


def report(state, stats, surface: str, variant: str, out: str | None):
    v, e, f, chi = metrics.euler_characteristic(state)
    expect_chi = 2 - 2 * GENUS.get(surface, 0)
    print(f"\n{surface} via {variant}: converged="
          f"{stats.converged} units={stats.units} edges={e} faces={f}")
    print(f"Euler characteristic {chi} (target {expect_chi}, genus "
          f"{GENUS.get(surface, 0)})  signals={stats.signals} "
          f"discarded={stats.discarded}")
    if out:
        nv, nf = export_obj(state, out)
        print(f"wrote {out}: {nv} vertices, {nf} faces")


def run_fleet(args) -> None:
    """N surfaces, one fleet run, one mesh per network."""
    import os

    surfaces = sorted(gson.SAMPLERS.names())
    picks = [surfaces[i % len(surfaces)] for i in range(args.fleet)]
    specs = tuple(build_spec(args).replace(
        sampler=s,
        model=gson.GSONParams(
            model="soam", insertion_threshold=THRESH.get(s, 0.25),
            age_max=64.0, eps_b=0.1, eps_n=0.01, stuck_window=60))
        for s in picks)
    fleet_mesh = (gson.MeshSpec(axis="network", devices=args.mesh)
                  if args.mesh else None)
    fspec = gson.FleetSpec(specs, tuple(range(args.fleet)), fleet_mesh)
    if args.resume:
        if not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        sess = gson.FleetSession.restore(
            fspec, args.checkpoint_dir, verbose=True,
            checkpoint_every=args.checkpoint_every)
        print(f"resumed at iterations {list(sess.iterations)}")
    else:
        sess = gson.FleetSession(
            fspec, verbose=True, checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=(args.checkpoint_every
                              if args.checkpoint_dir else 0))
    print(f"fleet of {args.fleet} networks "
          f"({', '.join(picks)}) in {len(sess.cohorts)} cohort(s)")
    sess.run()
    if args.checkpoint_dir:
        sess.checkpoint()
    stem, ext = (os.path.splitext(args.out) if args.out
                 else (None, None))
    for i, surface in enumerate(picks):
        state, stats = sess.result(i)
        out = f"{stem}_{i}_{surface}{ext}" if args.out else None
        report(state, stats, surface, args.variant, out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--surface", default="sphere",
                    choices=sorted(gson.SAMPLERS.names()))
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="reconstruct N surfaces (cycling through the "
                         "registered samplers) as one fleet run, one "
                         "mesh per network")
    ap.add_argument("--variant", default="multi",
                    choices=sorted(gson.VARIANTS.names()) + ["kernel"])
    ap.add_argument("--backend", default="reference",
                    choices=sorted(gson.BACKENDS.names()),
                    help="per-phase device kernels (Find Winners + "
                         "dense Update) — see docs/api.md")
    ap.add_argument("--recall-target", type=float, default=None,
                    metavar="R",
                    help="top-2 recall target for the ann-* backends "
                         "(sizes the shortlist via the birthday-"
                         "collision model, e.g. 0.95 -> 20 windows)")
    ap.add_argument("--superstep", type=int, default=64,
                    help="iterations per device call (multi-fused)")
    ap.add_argument("--mesh", type=int, default=0, metavar="D",
                    help="shard over D devices: the fleet's network "
                         "axis with --fleet, else the signal axis of "
                         "the single network (see gson.MeshSpec; on "
                         "CPU force the device count via XLA_FLAGS)")
    ap.add_argument("--iters", type=int, default=800)
    ap.add_argument("--capacity", type=int, default=768)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=None, help="export .obj path")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot directory (enables --resume)")
    ap.add_argument("--checkpoint-every", type=int, default=200,
                    help="iterations between snapshots")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest snapshot")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.fleet:
        run_fleet(args)
        return

    spec = build_spec(args, signal_mesh=True)
    if args.resume:
        if not args.checkpoint_dir:
            ap.error("--resume requires --checkpoint-dir")
        sess = gson.Session.restore(spec, args.checkpoint_dir,
                                    verbose=True,
                                    checkpoint_every=args.checkpoint_every)
        print(f"resumed from iteration {sess.iteration}")
    else:
        sess = gson.Session(
            spec, jax.random.key(args.seed), verbose=True,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=(args.checkpoint_every
                              if args.checkpoint_dir else 0))
    sess.run()
    if args.checkpoint_dir:
        sess.checkpoint()
    state, stats = sess.result()

    v, e, f, chi = metrics.euler_characteristic(state)
    expect_chi = 2 - 2 * GENUS.get(args.surface, 0)
    print(f"\n{args.surface} via {args.variant}: converged="
          f"{stats.converged} units={stats.units} edges={e} faces={f}")
    print(f"Euler characteristic {chi} (target {expect_chi}, genus "
          f"{GENUS.get(args.surface, 0)})  signals={stats.signals} "
          f"discarded={stats.discarded}")
    if stats.time_sample or stats.time_convergence:
        print(f"phase times: sample {stats.time_sample:.1f}s  "
              f"step {stats.time_step:.1f}s  "
              f"convergence-check {stats.time_convergence:.1f}s")
    else:
        # sampling and the check run inside the device program: the
        # host times the whole step, the trace splits it by phase
        print(f"step time {stats.time_step:.1f}s (sampling, update and "
              f"convergence check on the device; their gson.* scopes "
              f"are in a jax.profiler trace, see README)")
    if args.out:
        nv, nf = export_obj(state, args.out)
        print(f"wrote {args.out}: {nv} vertices, {nf} faces")


if __name__ == "__main__":
    main()
