"""Surfaces are files of their own (``bench/reference/surfaces/``): the
reference's samplers draw the program's points bit for bit, a cell on
a surface the benchmark has never seen is added as new files only, the
surface's file decides the Euler characteristic a converged job must
reach, and a cell whose traffic or configuration misses a surface
fails before set-up."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import harness, reference

ROOT = harness.ROOT
SURFACES = sorted(f[:-3] for f in os.listdir(reference.SURFACE_DIR)
                  if f.endswith(".py"))


def test_sphere_and_torus_are_files():
    assert {"sphere", "torus"} <= set(SURFACES)
    assert [reference.surface(s).GENUS for s in ("sphere", "torus")] == [0, 1]


@pytest.mark.parametrize("n", [1, 7, 1024])
@pytest.mark.parametrize("seed", [0, 42, 2718281828])
@pytest.mark.parametrize("name", SURFACES)
def test_reference_surfaces_match_the_program(name, seed, n):
    """What ``correct`` rests on: every surface file samples the points
    the program's sampler of its name samples, eagerly and under jit."""
    from repro.core.gson import sampling
    program = getattr(sampling, f"sample_{name}")
    ours = reference.surface(name).sample
    key = jax.random.key(seed)
    for wrap in (lambda f: f, lambda f: jax.jit(f, static_argnums=1)):
        want = np.asarray(wrap(program)(key, n))
        got = np.asarray(wrap(ours)(key, n))
        assert got.dtype == want.dtype == np.float32
        assert got.shape == want.shape == (n, 3)
        assert got.tobytes() == want.tobytes()


def cell_with(tmp_path, monkeypatch, config=None, traffic=None):
    """The manifest as it is, with the c768 cell's configuration and
    traffic updated by ``config`` and ``traffic``, read from
    ``tmp_path``."""
    for kind, name, update in (("configs", "soam-c768", config),
                               ("traffic", "solo-sphere-torus", traffic)):
        data = harness.load_json(os.path.join(harness.BENCH, kind,
                                              f"{name}.json"))
        (tmp_path / "bench" / kind).mkdir(parents=True)
        (tmp_path / "bench" / kind / f"{name}.json").write_text(
            json.dumps({**data, **(update or {})}))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path / "bench"))


def never(job):
    raise AssertionError("set-up began")


def test_traffic_naming_a_surface_without_a_file_fails_before_set_up(
        tmp_path, monkeypatch):
    cell_with(tmp_path, monkeypatch,
              config={"insertion_threshold": {"sphere": 0.35,
                                              "ellipsoid": 0.3}},
              traffic={"rounds": [[{"surface": "sphere", "seed": 42},
                                   {"surface": "ellipsoid", "seed": 42}]]})
    with pytest.raises(harness.BenchError,
                       match=r"'ellipsoid'.*surfaces/ellipsoid\.py"):
        harness.run_cell("c768-solo", 1, 0.5, False, require_chip=False,
                         make_session=never)


def test_config_without_a_threshold_for_a_surface_fails_before_set_up(
        tmp_path, monkeypatch):
    cell_with(tmp_path, monkeypatch,
              config={"insertion_threshold": {"sphere": 0.35}})
    with pytest.raises(harness.BenchError,
                       match="insertion_threshold for surface 'torus'"):
        harness.run_cell("c768-solo", 1, 0.5, False, require_chip=False,
                         make_session=never)


ELLIPSOID = '''"""An ellipsoid of semi-axes 1, 0.8 and 0.6 (a test surface)."""
import jax
import jax.numpy as jnp

GENUS = {genus}


def sample(rng, n):
    v = jax.random.normal(rng, (n, 3))
    v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
    return (v * jnp.asarray([1.0, 0.8, 0.6])).astype(jnp.float32)
'''

NEW_SURFACE = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
cell = harness.find_cell("c128-ellipsoid")
def make(job):
    return harness.ReferenceSession(cell.config, job.surface, job.seed,
                                    "highest")
r = harness.run_cell("c128-ellipsoid", 1, 0.5, False, require_chip=False,
                     make_session=make)
print(json.dumps({{"correct": r["correct"], "attempted": r["attempted"],
                  "checked": {{k: v["value"]
                              for k, v in r["checked"].items()}}}}))
"""


def run_new_surface(tmp_path, genus):
    """A copy of the benchmark's paths, to which a surface
    (``surfaces/ellipsoid.py``, stating ``genus``), a configuration, a
    traffic mix and a cell are added as new files and manifest entries;
    the cell run on the CPU with the reference in the program's place.
    -> its result, after checking that no file of the copy changed."""
    paths = harness.manifest()["paths"]
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/reference/surfaces/ellipsoid.py").write_text(
        ELLIPSOID.format(genus=genus))
    cfg = harness.load_json(harness.config_file("soam-c768"))
    cfg.update(capacity=128, backend="reference", max_iterations=1500,
               insertion_threshold={"ellipsoid": 0.7})
    (tmp_path / "bench/configs/soam-c128-ellipsoid.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/ellipsoid-solo.json").write_text(json.dumps({
        "driver": "session",
        "rounds": [[{"surface": "ellipsoid", "seed": 3}]],
        "window_end": "round", "check": {"supersteps": 2},
        "trace_seconds": 1}))
    man = harness.manifest()
    man["configs"].append({"name": "soam-c128-ellipsoid",
                           "source": "a test",
                           "file": "bench/configs/soam-c128-ellipsoid.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append({"name": "c128-ellipsoid",
                             "config": "soam-c128-ellipsoid",
                             "traffic": "ellipsoid-solo", "chips": 1,
                             "why": "a test"})
    for m in man["end_to_end"]:
        if "workloads" in m and "c768-solo" in m["workloads"]:
            m["workloads"].append("c128-ellipsoid")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    code = NEW_SURFACE.format(root=str(tmp_path),
                              src=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=400,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    for p in paths:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert filecmp.cmp(os.path.join(ROOT, rel), tmp_path / rel,
                                   shallow=False), rel
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_new_surface_from_new_files_only(tmp_path):
    """A cell on a surface the benchmark has never seen, added as new
    files and manifest entries only, runs and comes out correct: its
    ellipsoid job converges to the sphere's Euler characteristic, 2."""
    out = run_new_surface(tmp_path, genus=0)
    assert out["correct"], out
    assert out["attempted"] >= 1 and out["checked"]["jobs_wrong"] == 0


def test_surface_file_with_the_wrong_genus_is_not_correct(tmp_path):
    """The same cell with a surface file that states genus 1: every
    job, converged and matching the reference superstep for superstep,
    misses the Euler characteristic 0 its file asks for."""
    out = run_new_surface(tmp_path, genus=1)
    assert not out["correct"], out
    assert out["checked"]["split_supersteps"] == 0
    assert out["checked"]["jobs_wrong"] == out["attempted"] >= 1
