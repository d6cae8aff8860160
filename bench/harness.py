"""One benchmark cell in one process: set up, measure, check, report.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``, played by ``bench/generator.py``)
and one reader per per-layer metric (``bench/metrics/<metric>.py``).
A new cell, configuration, traffic mix or metric is a new file and a
manifest entry; nothing here names one.

From the program the harness takes only the system under test (the
``repro.gson`` session API and the backend the configuration names),
JAX's compile events and the profiler trace. The yardstick (traffic,
work counts, peaks, trace reduction and the plain reference that
decides ``correct``) lives under ``bench/``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import random
import shutil
import sys
import time
import warnings
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Any, Callable

import numpy as np

from bench import generator
from bench import trace as tr

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
                  "/jax/core/compile/backend_compile_duration":
                      "backend_compile_s"}


# what the program's registry warns when a kernel backend fails to lower
# and it swaps in its reference backend
FALLBACK = r".*falling back to the reference"


class BenchError(RuntimeError):
    """The cell cannot be run as declared (no chip, a missing file)."""


# ---------------------------------------------------------------------------
# the cell, by name

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def config_file(name: str) -> str:
    return os.path.join(BENCH, "configs", f"{name}.json")


def traffic_file(name: str) -> str:
    return os.path.join(BENCH, "traffic", f"{name}.json")


def reader_file(metric: str) -> str:
    return os.path.join(BENCH, "metrics", f"{metric}.py")


def load_reader(metric: str):
    """The module ``bench/metrics/<metric>.py``: ``read(ctx)`` returns
    the metric's value, or None where the run has nothing to read."""
    path = reader_file(metric)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def find_cell(workload: str) -> Cell:
    man = manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; cells: "
                         f"{', '.join(cells)}")
    w = cells[workload]
    cfg = next(c for c in man["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(ROOT, cfg["file"]))
    traffic = load_json(traffic_file(w["traffic"]))
    generator.validate(traffic)
    e2e = [m for m in man["end_to_end"] if applies(m, workload)]
    layer = [m for m in man["per_layer"] if applies(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


# ---------------------------------------------------------------------------
# clocks

def covered(spans) -> float:
    """Seconds covered by the union of (start, end) spans."""
    return sum(e - s for s, e in tr.union(spans))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), from the spans of its
    compile events; nested spans count once."""

    def __init__(self):
        import jax
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


class Spans:
    """The harness's own host spans, written into the profiler's trace
    as ``bench.<name>`` when ``annotate`` (the traced run)."""

    def __init__(self, annotate: bool):
        self.annotate = annotate

    def __call__(self, name: str):
        import jax
        return (jax.profiler.TraceAnnotation(tr.HOST_PREFIX + name)
                if self.annotate else contextlib.nullcontext())


# ---------------------------------------------------------------------------
# the system under test, from a configuration file

def buffer_rows(config: dict) -> int:
    """Signal buffer rows: the m-schedule never exceeds the smallest
    power of two above the capacity, nor the model's cap on m."""
    return min(config["model"]["max_parallel"],
               1 << int(config["capacity"]).bit_length())


def build_spec(config: dict, surface: str, backend: Any = None):
    """The configuration as a ``repro.gson.RunSpec`` for one surface."""
    from repro import gson
    params = gson.GSONParams(**{
        **config["model"],
        "insertion_threshold": config["insertion_threshold"][surface]})
    vcfg = gson.FusedConfig(
        superstep=gson.SuperstepConfig(length=config["superstep"]),
        refresh_every=config["refresh_every"], min_m=config["min_m"])
    return gson.RunSpec(
        variant=config["variant"], model=params, sampler=surface,
        backend=config["backend"] if backend is None else backend,
        variant_config=vcfg, capacity=config["capacity"],
        dim=config["dim"], max_deg=config["max_deg"],
        check_every=config["check_every"],
        max_iterations=config["max_iterations"],
        max_signals=config["max_signals"], n_probe=config["n_probe"])


def reference_params(config: dict, surface: str, precision: str = "highest"):
    from bench.reference import soam
    m = config["model"]
    if (m["model"], m["freeze_stable"], m["neighbor_collision"]) != (
            "soam", True, "sum"):
        raise BenchError("the reference states SOAM with frozen stable "
                         "units and summed neighbour pulls only")
    return soam.Params(
        eps_b=m["eps_b"], eps_n=m["eps_n"], age_max=m["age_max"],
        insertion_threshold=config["insertion_threshold"][surface],
        firing_threshold=m["firing_threshold"], tau_b=m["tau_b"],
        tau_n=m["tau_n"], h_min=m["h_min"], thr_decay=m["thr_decay"],
        thr_recover=m["thr_recover"], thr_min_frac=m["thr_min_frac"],
        stuck_window=m["stuck_window"], capacity=config["capacity"],
        dim=config["dim"], max_deg=config["max_deg"],
        max_parallel=buffer_rows(config), min_m=config["min_m"],
        refresh_every=config["refresh_every"],
        check_every=config["check_every"], precision=precision)


def superstep_length(config: dict, iteration: int, signals: int) -> int:
    """Iterations the session asks of one superstep call."""
    sig_left = config["max_signals"] - signals
    return max(1, min(config["superstep"],
                      config["max_iterations"] - iteration,
                      -(-sig_left // buffer_rows(config))))


class ReferenceSession:
    """The plain reference driven like a ``gson.Session``: what the
    control puts in the program's place."""

    def __init__(self, config: dict, surface: str, seed: int,
                 precision: str):
        import jax
        from bench.reference import soam
        self._soam, self.config, self.surface = soam, config, surface
        self.p = reference_params(config, surface, precision)
        self._carry = soam.start(jax.random.key(seed), surface=surface,
                                 p=self.p)
        self.iteration, self.converged = 0, False

    @property
    def state(self):
        return self._carry.net

    def stream(self):
        c = self.config
        while not self.converged and self.iteration < c["max_iterations"]:
            n = superstep_length(c, self.iteration,
                                 int(self._carry.net.signal_count))
            self._carry, k = self._soam.advance(
                self._carry, n, surface=self.surface, p=self.p)
            self.iteration += int(k)
            self.converged = bool(self._carry.done)
            yield {"iteration": self.iteration}

    def result(self):
        return self.state, None


# ---------------------------------------------------------------------------
# the window

@dataclass
class Snap:
    """The network a superstep returned, and where the job stood."""

    iteration: int
    converged: bool
    state: Any


@dataclass
class JobRecord:
    surface: str
    seed: int
    start: float
    end: float = 0.0
    snaps: list = field(default_factory=list)
    superstep_s: float = 0.0
    converged: bool = False
    chi: int | None = None
    chi_expected: int | None = None

    @property
    def wall_s(self) -> float:
        return self.end - self.start

    @property
    def ok(self) -> bool:
        return self.converged and self.chi == self.chi_expected


GENUS = {"sphere": 0, "torus": 1, "eight": 2, "trefoil": 1}


def kernels_of(sess):
    """The (find_winners, update_phase) pair a ``gson.Session`` runs, or
    None for a session object without one (the reference in the
    program's place)."""
    rt = getattr(sess, "rt", None)
    return None if rt is None else (rt.find_winners, rt.update_phase)


def play_window(make_session: Callable, traffic: dict, seed: int,
                seconds: float, spans: Spans, held: dict,
                trace_dir: str | None = None):
    """Run the traffic for ``seconds``. -> (jobs, traced).

    The window closes after the round in flight when ``seconds`` run
    out. ``held`` maps each surface to the kernels its set-up session
    ran (``kernels_of``); a job whose session ends on others raises
    BenchError. With ``trace_dir`` the profiler records the window's first
    ``trace_seconds`` (to the next superstep or job boundary) there,
    inside the host span ``bench.traced_window``; ``traced`` is then the
    number of supersteps recorded."""
    import jax
    from bench.reference import soam
    traced_window, traced = None, None
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        traced_window = jax.profiler.TraceAnnotation(tr.WINDOW)
        traced_window.__enter__()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    trace_until = t0 + float(traffic["trace_seconds"])
    jobs: list[JobRecord] = []
    n_snaps = 0

    def stop_trace_if_due(now, force=False):
        nonlocal traced_window, traced
        if traced_window is not None and (force or now >= trace_until):
            traced_window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced_window, traced = None, n_snaps

    for job in generator.jobs(traffic, seed):
        now = time.perf_counter()
        if now >= deadline and job.first:
            break
        rec = JobRecord(job.surface, job.seed, now)
        jobs.append(rec)
        with spans("session_init"):
            sess = make_session(job)
        stream = sess.stream()
        while True:
            t = time.perf_counter()
            with spans("superstep"):
                row = next(stream, None)
            done_t = time.perf_counter()
            if row is None:
                break
            rec.superstep_s += done_t - t
            rec.snaps.append(Snap(sess.iteration, bool(sess.converged),
                                  sess.state))
            n_snaps += 1
            stop_trace_if_due(done_t)
        with spans("result"):
            state, _ = sess.result()
        with spans("chi_check"):
            rec.converged = bool(sess.converged)
            rec.chi = soam.euler_characteristic(
                np.asarray(state.nbr), np.asarray(state.active))
            rec.chi_expected = 2 - 2 * GENUS[job.surface]
        rec.end = time.perf_counter()
        if kernels_of(sess) != held[job.surface]:
            raise BenchError(f"{job.surface} job: the session's kernels "
                             "were swapped during the run")
        stop_trace_if_due(rec.end)
    stop_trace_if_due(time.perf_counter(), force=True)
    return jobs, traced


# ---------------------------------------------------------------------------
# correct: supersteps of the window against the plain reference

def sample_supersteps(jobs: list[JobRecord], k: int | str, seed: int):
    """(job, superstep) pairs to check: the last superstep of the longest
    job, the first superstep of the first job, and the rest drawn from
    the seed among the supersteps the window ran, each distinct one
    once (a job played again from the same seed repeats its own); with
    ``k == "all"`` every distinct one."""
    seen, every = set(), []
    for j, r in enumerate(jobs):
        for s in range(len(r.snaps)):
            if (r.surface, r.seed, s) not in seen:
                seen.add((r.surface, r.seed, s))
                every.append((j, s))
    longest = max(range(len(jobs)), key=lambda j: jobs[j].snaps[-1].iteration
                  if jobs[j].snaps else -1)
    must = list(dict.fromkeys([(longest, len(jobs[longest].snaps) - 1),
                               (0, 0)]))
    rest = [x for x in every if x not in must]
    if k == "all":
        return must + rest
    rng = random.Random(seed * 7919 + 17)
    return must + rng.sample(rest, min(len(rest), max(0, k - len(must))))


def check_against_reference(jobs: list[JobRecord], config: dict, k: int | str,
                            seed: int) -> dict:
    """Run the reference over sampled supersteps, each from the state the
    superstep started from, and compare with what the window produced.
    -> {"split_supersteps": supersteps whose discrete fields (or
    iteration count, or verdict) differ, "float_gap": the widest float
    gap among the others, "supersteps": checked, "iterations": checked
    iterations, "each": [(differing discrete elements, float gap) per
    superstep checked]}."""
    import jax
    from bench.reference import soam
    iters, each = 0, []
    pairs = sample_supersteps(jobs, k, seed)
    for j, s in pairs:
        rec = jobs[j]
        p = reference_params(config, rec.surface)
        seed_key = jax.random.key(rec.seed)
        if s == 0:
            carry = soam.start(seed_key, surface=rec.surface, p=p)
            it0, signals = 0, 0
        else:
            prev = rec.snaps[s - 1]
            fields = soam.host_fields(prev.state)
            it0, signals = prev.iteration, int(fields["signal_count"])
            carry = soam.Carry(
                soam.as_net(fields),
                soam.resume_key(seed_key, it0, surface=rec.surface, p=p),
                jax.numpy.int32(it0), jax.numpy.asarray(False))
        want = superstep_length(config, it0, signals)
        carry, n = soam.advance(carry, want, surface=rec.surface, p=p)
        snap = rec.snaps[s]
        d, g = soam.compare(soam.host_fields(snap.state),
                            soam.host_fields(carry.net))
        d += int(int(n) != snap.iteration - it0)
        d += int(bool(carry.done) != snap.converged)
        iters += int(n)
        each.append((d, g))
    return {"split_supersteps": sum(1 for d, _ in each if d),
            "float_gap": max([g for d, g in each if not d] or [0.0]),
            "supersteps": len(pairs), "iterations": iters, "each": each}


def verdict(jobs: list[JobRecord], numbers: dict, limits: dict) -> dict:
    """Every number compared, beside its limit."""
    out = {name: {"value": numbers[name], "limit": limits[name]}
           for name in ("split_supersteps", "float_gap")}
    out["jobs_wrong"] = {"value": sum(1 for r in jobs if not r.ok),
                         "limit": 0}
    return out


# ---------------------------------------------------------------------------
# per-layer context

def superstep_work(jobs: list[JobRecord], upto: int | None) -> list[dict]:
    """Per superstep of the window (the first ``upto``): the iterations
    it ran, the active units and signals it started from, the signals
    that held a winner and the mean degree it started from."""
    out = []
    for rec in jobs:
        prev = None
        for snap in rec.snaps:
            if upto is not None and len(out) >= upto:
                return out
            f = {n: np.asarray(getattr(snap.state, n))
                 for n in ("n_active", "signal_count", "discarded")}
            if prev is None:
                it0, n0, sig0, dis0, deg0 = 0, 2, 0, 0, 0.0
            else:
                it0 = prev.iteration
                n0 = int(np.asarray(prev.state.n_active))
                sig0 = int(np.asarray(prev.state.signal_count))
                dis0 = int(np.asarray(prev.state.discarded))
                deg0 = float(np.sum(np.asarray(prev.state.nbr) >= 0)) / max(n0, 1)
            out.append({
                "iterations": snap.iteration - it0, "n_active": n0,
                "survivors": (int(f["signal_count"]) - sig0)
                - (int(f["discarded"]) - dis0),
                "degree": deg0})
            prev = snap
    return out


def peak_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# a run

def require_chips(chips: int):
    """The accelerator devices, or BenchError: no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform!r} "
                         "devices")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; {len(devices)} "
                         "visible")
    return devices


def warm_up(make_session: Callable, surfaces: list, backend: str) -> dict:
    """Set-up: one superstep of a job per surface, so that every program
    the window runs is compiled (or loaded from the cache) here.
    -> {surface: the kernels its session ran (``kernels_of``)}."""
    from repro.core.gson.multi import find_winners_reference
    held = {}
    for s in surfaces:
        sess = make_session(generator.Job(s, 0, 0, True))
        for _ in zip(range(1), sess.stream()):
            pass
        np.asarray(sess.state.n_active)
        held[s] = kernels_of(sess)
        if (backend != "reference" and held[s] is not None
                and held[s][0] is find_winners_reference):
            raise BenchError(f"backend {backend!r} runs the reference "
                             "Find Winners")
    return held


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True,
             make_session: Callable | None = None,
             cell_hook: Callable | None = None,
             t0: float | None = None) -> dict:
    """The run's result line as a dict. ``require_chip=False``,
    ``make_session`` (job -> session) and ``cell_hook`` (Cell -> Cell)
    exist for the tests: they drive the same run on the CPU, at a small
    size, or with the timed path broken underneath. ``t0`` is the
    ``time.monotonic()`` at which set-up began (the process's start,
    for ``bench/run.py``)."""
    t0 = time.monotonic() if t0 is None else t0
    import jax
    cell = find_cell(workload)
    if cell_hook is not None:
        cell = cell_hook(cell)
    if require_chip:
        devices = require_chips(cell.chips)
        from repro.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    else:
        devices = jax.devices()
    config = cell.config
    clock = CompileClock()
    spans = Spans(annotate=trace)
    surfaces = generator.surfaces(cell.traffic)
    specs = {s: build_spec(config, s) for s in surfaces}

    if make_session is None:
        from repro import gson

        def make_session(job):
            return gson.Session(specs[job.surface], seed=job.seed)

    try:
        with warnings.catch_warnings():
            # a kernel that fails to lower must fail the run, not turn
            # into a quiet run of the program's reference backend
            warnings.filterwarnings("error", message=FALLBACK,
                                    category=RuntimeWarning)
            held = warm_up(make_session, surfaces, config["backend"])
            setup_compile = clock.lap()
            tdir = None
            if trace:
                tdir = os.path.join(OUT, "trace", workload)
                shutil.rmtree(tdir, ignore_errors=True)
                os.makedirs(tdir, exist_ok=True)
            setup_s = time.monotonic() - t0
            jobs, n_traced = play_window(make_session, cell.traffic, seed,
                                         seconds, spans, held, tdir)
    except RuntimeWarning as e:
        raise BenchError(f"a kernel fell back to the reference: {e}") from e
    window_compile = clock.lap()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:cell.chips])

    t_check = time.perf_counter()
    numbers = check_against_reference(
        jobs, config, cell.traffic["check"]["supersteps"], seed)
    check_s = time.perf_counter() - t_check
    compared = verdict(jobs, numbers, config["limits"])
    correct = all(v["value"] <= v["limit"] for v in compared.values())

    good = [r for r in jobs if r.ok]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": int(peak_bytes)}
    result: dict[str, Any] = {"correct": correct,
                              "attempted": len(jobs),
                              "failed": len(jobs) - len(good)}
    if not trace:
        values = {"setup_s": setup_s,
                  "job_s": (sum(r.wall_s for r in good) / len(good)
                            if good else None)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    else:
        events = tr.load(tdir)
        readers = {m["name"]: load_reader(m["name"])
                   for m in cell.per_layer}
        kernels = {}
        for mod in readers.values():
            kernels.update(getattr(mod, "KERNELS", {}))
        window = tr.window_of(events, tr.WINDOW)
        red = tr.reduce(events, window, kernels)
        ctx = SimpleNamespace(
            trace=red, dim=config["dim"],
            buffer=buffer_rows(config), min_m=config["min_m"],
            peak=peak_for(devices[0].device_kind),
            supersteps=superstep_work(jobs, n_traced),
            setup_compile=setup_compile, window_compile=window_compile,
            jobs=jobs)
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
        shutil.rmtree(tdir, ignore_errors=True)
    result.update(metrics=metrics, device=device,
                  check_scope={"supersteps": numbers["supersteps"],
                               "iterations": numbers["iterations"],
                               "seconds": check_s})
    result["checked"] = compared
    return result


def main(argv=None, t0: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=t0)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, v in result["checked"].items():
        print(f"{name} {v['value']} limit {v['limit']}", file=sys.stderr)
    return 0
