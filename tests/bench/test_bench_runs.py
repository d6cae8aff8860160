"""Whole runs of the harness on the CPU at a small size, through its
library entry: a sound run is correct, and the control (the plain
reference at a lower precision in the program's place) and each fault
the session cells can have (a superstep that returns its state
unchanged, half of the signals left out, an answer altered where it is
produced) come out not correct."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import harness

ROOT = harness.ROOT


def small(backend="reference"):
    """The c768 cell cut to what a CPU test holds: 128 units, a sphere
    threshold at which jobs converge in a few hundred iterations."""
    def hook(cell):
        config = dict(cell.config, capacity=128, backend=backend,
                      max_iterations=1500,
                      insertion_threshold={"sphere": 0.7})
        traffic = dict(cell.traffic, window_end="round",
                       rounds=[[{"surface": "sphere", "seed": 1}],
                               [{"surface": "sphere", "seed": 2}]],
                       check={"supersteps": 4})
        return dataclasses.replace(cell, config=config, traffic=traffic)
    return hook


def run(hook, make_session=None, seconds=0.5):
    return harness.run_cell("c768-solo", 5, seconds, False,
                            require_chip=False, cell_hook=hook,
                            make_session=make_session)


def specs(backend="reference"):
    cell = small(backend)(harness.find_cell("c768-solo"))
    return cell.config, harness.build_spec(cell.config, "sphere")


def test_sound_run_is_correct():
    r = run(small())
    assert r["correct"], r["checked"]
    assert r["checked"]["split_supersteps"]["value"] == 0
    assert set(r["metrics"]) == {"job_s", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["attempted"] >= 1
    assert list(r)[-1] == "checked"


def test_sound_run_pallas_full_interpreted():
    r = run(small("pallas-full"))
    assert r["correct"], r["checked"]


def first_supersteps(cell):
    """The c768 cell at its own sizes, cut to the first eight supersteps
    of its sphere job, every one of them checked."""
    config = dict(cell.config, backend="reference", max_iterations=512)
    traffic = dict(cell.traffic, rounds=[[{"surface": "sphere",
                                           "seed": 42}]],
                   check={"supersteps": 8})
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.mark.parametrize("system,cut", [("program", first_supersteps),
                                        ("high", first_supersteps),
                                        ("bf16", small())],
                         ids=["program", "high", "bf16"])
def test_control_fails_the_comparison(system, cut):
    """The control (the reference computed at ``high``, three bf16
    passes, at the cell's own width; or at one bf16 pass, small) splits
    discrete fields in more supersteps than the limit allows; the
    program, on the same supersteps, splits none."""
    cell = cut(harness.find_cell("c768-solo"))
    make = None
    if system != "program":
        def make(job):
            return harness.ReferenceSession(cell.config, job.surface,
                                            job.seed, system)
    r = run(lambda c: cell, make)
    split = r["checked"]["split_supersteps"]
    if system == "program":
        assert split["value"] == 0
        assert r["checked"]["float_gap"]["value"] <= \
            r["checked"]["float_gap"]["limit"]
    else:
        assert split["value"] > split["limit"], r["checked"]


class Unchanged:
    """A session whose supersteps hand back the state they started
    from."""

    def __init__(self, sess):
        self.sess, self.state = sess, None

    def __getattr__(self, name):
        return getattr(self.sess, name)

    def stream(self):
        for row in self.sess.stream():
            if self.state is None:
                self.state = self.sess.state
            yield row


class Altered:
    """A session whose every superstep moves one unit by 1e-3."""

    def __init__(self, sess):
        self.sess = sess

    def __getattr__(self, name):
        return getattr(self.sess, name)

    @property
    def state(self):
        s = self.sess.state
        return s.replace(w=s.w.at[0, 0].add(1e-3))


def half_batch_backend():
    from repro import gson
    from repro.core.gson.multi import (find_winners_reference,
                                       update_phase_reference)

    def half(state, signals, wid, sid, d2b, k_lock, params, mask=None):
        n = jnp.sum(mask)
        mask = mask & (jnp.cumsum(mask) <= n // 2)
        return update_phase_reference(state, signals, wid, sid, d2b, k_lock,
                                      params, mask)
    return gson.Backend("half-batch", find_winners_reference, half)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_not_correct(fault):
    from repro import gson
    config, spec = specs()
    if fault == "half_batch":
        spec = spec.replace(backend=half_batch_backend())
    wrap = {"unchanged": Unchanged, "altered": Altered}.get(fault,
                                                          lambda s: s)

    def broken(job):
        return wrap(gson.Session(spec, seed=job.seed))
    r = run(small(), broken)
    assert not r["correct"], r["checked"]


def test_kernels_swapped_in_the_window_fail_the_run():
    """A window session that runs other kernels than set-up's (the
    program's reference pair in place of the Pallas kernels) stops the
    run: it would time the wrong code."""
    from repro import gson
    config, spec = specs("pallas-full")
    made = []

    def swapping(job):
        made.append(job)
        backend = "pallas-full" if len(made) == 1 else "reference"
        return gson.Session(spec.replace(backend=backend), seed=job.seed)
    with pytest.raises(harness.BenchError, match="swapped"):
        run(small("pallas-full"), swapping)


class FallingBack:
    """A session whose first superstep warns as the program's registry
    does when a kernel backend fails to lower."""

    def __init__(self, sess):
        self.sess = sess

    def __getattr__(self, name):
        return getattr(self.sess, name)

    def stream(self):
        import warnings
        warnings.warn("backend failed to lower; falling back to the "
                      "reference backend for this run", RuntimeWarning)
        yield from self.sess.stream()


def test_kernel_fallback_warning_fails_the_run():
    from repro import gson
    _, spec = specs()
    with pytest.raises(harness.BenchError, match="fell back"):
        run(small(), lambda job: FallingBack(gson.Session(spec,
                                                          seed=job.seed)))


def cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_no_tpu_exits_nonzero_with_no_result():
    p = cli(["--workload", "c768-solo", "--seed", "1", "--seconds", "1",
             "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in harness.manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = cli(["--workload", "c768-solo", "--seed", "1", "--seconds", "1",
             "--trace", "0"], tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


NEW_CELL = """
import dataclasses, json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
cell = harness.find_cell("c768-new")
assert cell.traffic["rounds"][0][0]["seed"] == 3
assert harness.load_reader("units_new").read(None) == 1.0
def hook(c):
    return dataclasses.replace(c, config=dict(c.config, capacity=128,
        backend="reference", insertion_threshold={{"sphere": 0.7}}))
r = harness.run_cell("c768-new", 1, 0.5, False, require_chip=False,
                     cell_hook=hook)
print(json.dumps({{"correct": r["correct"], "metrics": sorted(r["metrics"])}}))
"""


def test_new_cell_from_new_files_only(tmp_path):
    """A cell, a configuration, a traffic mix and a per-layer metric
    added as new files and manifest entries, with no file changed."""
    for p in harness.manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    man = harness.manifest()
    cfg = harness.load_json(harness.config_file("soam-c768"))
    (tmp_path / "bench/configs/soam-new.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/sphere-new.json").write_text(json.dumps({
        "driver": "session", "rounds": [[{"surface": "sphere", "seed": 3}]],
        "window_end": "round", "check": {"supersteps": 2},
        "trace_seconds": 1}))
    (tmp_path / "bench/metrics/units_new.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    man["configs"].append({"name": "soam-new", "source": "a test",
                           "file": "bench/configs/soam-new.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append({"name": "c768-new", "config": "soam-new",
                             "traffic": "sphere-new", "chips": 1,
                             "why": "a test"})
    for m in man["end_to_end"]:
        if "workloads" in m and "c768-solo" in m["workloads"]:
            m["workloads"].append("c768-new")
    man["per_layer"].append({"name": "units_new", "unit": "units",
                             "better": "higher", "source": "host_clock",
                             "layer": "a test", "moves": "job_s",
                             "workloads": ["c768-new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    code = NEW_CELL.format(root=str(tmp_path),
                           src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=400,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"correct": True, "metrics": ["job_s", "setup_s"]}
