"""The program's spans and scopes read from a trace (``bench/phases.py``)
and the readers of the metrics built on them: a session traced on the
CPU, synthetic events counted by hand, and traces recorded on a TPU v5e."""
import os
from types import SimpleNamespace

import pytest

from bench import harness, phases, trace

RECORDED = os.path.join(harness.BENCH, "recorded")
SCOPED = os.path.join(RECORDED, "c768-scoped.json.gz")
PLAIN = os.path.join(RECORDED, "c768-superstep.json.gz")
READERS = ("sample_ms.solo", "tail_ms.solo", "refresh_ms.solo",
           "check_ms.solo", "session_host_ms.solo", "host_idle_share.solo",
           "readbacks_per_superstep.solo")
KERNELS = {"find_winners": ("find_winners_pallas_padded",),
           "update": ("winner_lock_pallas_padded",
                      "update_accum_pallas_padded",
                      "edge_age_pallas_padded")}
PHASES = ("gson.sample", "gson.find_winners", "gson.update", "gson.tail",
          "gson.refresh", "gson.check")


def read_all(ctx):
    return {m: harness.load_reader(m).read(ctx) for m in READERS}


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_session_spans_in_a_cpu_trace(tmp_path):
    import jax
    from repro import gson
    spec = gson.RunSpec(
        variant="multi-fused", model="soam", sampler="sphere",
        backend="reference", capacity=64, max_deg=8, max_iterations=24,
        check_every=8, n_probe=64,
        variant_config=gson.FusedConfig(
            superstep=gson.SuperstepConfig(length=8)))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            sessions = [gson.Session(spec, seed=s) for s in (1, 2)]
            rows = [list(sess.stream()) for sess in sessions]
            for sess in sessions:
                sess.result()
    ev = phases.load(str(tmp_path))
    assert ev["devices"] == {}           # the CPU has no device plane
    host = [h for h in ev["host"] if h[0].startswith("gson.")]
    for sess, got in zip(sessions, rows):
        mine = [h for h in host if h[3]["session"] == sess.id]
        names = [h[0] for h in mine]
        for name in ("gson.session.start", "gson.step.prepare",
                     "gson.step.dispatch", "gson.step.unwrap",
                     "gson.step.wait", "gson.readback", "gson.session.emit",
                     "gson.session.result"):
            assert name in names, name
        steps = [h for h in mine if h[0] == "gson.superstep"]
        # one span per superstep run, a history row from each
        assert len(steps) == len(got) == 3
        assert [h[3]["iteration"] for h in steps] == [0, 8, 16]
        for h in mine:
            if h[0].startswith("gson.step."):
                step = [s for s in mine if s[0] == "gson.step"
                        and inside(h, s)]
                assert len(step) == 1 and any(
                    inside(step[0], s) for s in steps)
            if h[0] == "gson.session.emit":
                assert any(inside(h, s) for s in steps)
        # per superstep: the active check before it, one in the step's
        # prepare and three of its result, two in the history row
        assert names.count("gson.readback") == 3 * (1 + 1 + 3 + 2)
    assert len({h[3]["session"] for h in host}) == 2
    with pytest.raises(ValueError):      # no device operation to reduce
        phases.reduce(ev, trace.window_of(phases.plain(ev), trace.WINDOW))


MS = 1_000_000
BODY = "jit(run_fleet_superstep_impl)/while/body/"


def synthetic():
    """Two supersteps of 8 iterations in a 50 ms window, times in ms:
    device busy [0, 22] and [30, 40]; the host waits in [21, 23] and
    [38, 41]."""
    ops = [
        ["while.1", 0, 22, "jit(run_fleet_superstep_impl)/while"],
        ["fusion.1", 0, 2, BODY + "gson.sample/vmap()/slice"],
        ["find_winners_pallas_padded.7", 2, 4,
         BODY + "vmap(gson.find_winners)/jit(find_winners_pallas_padded)"],
        ["fusion.2", 4, 5, BODY + "vmap(gson.update)/gather"],
        ["update_accum_pallas_padded.3", 5, 7,
         BODY + "vmap(gson.update)/jit(update_accum_pallas_padded)"],
        ["fusion.3", 7, 15, BODY + "vmap(gson.tail)/jit(searchsorted)"],
        ["fusion.4", 15, 16, ""],                  # XLA gave no op_name
        ["conditional.1", 16, 20, BODY + "gson.refresh/cond"],
        ["fusion.5", 17, 19, BODY + "gson.refresh/cond/vmap()/and"],
        ["fusion.6", 20, 22, BODY + "gson.check/vmap()/reduce"],
        ["while.1", 30, 40, "jit(run_fleet_superstep_impl)/while"],
        ["fusion.3", 30, 40, BODY + "vmap(gson.tail)/jit(searchsorted)"],
    ]
    host = [
        ["bench.traced_window", 0, 50, {}],
        ["bench.superstep", 0, 46, {}],
        ["gson.superstep", 0, 25, {"session": 1, "iteration": 0}],
        ["gson.step", 0, 23, {"session": 1}],
        ["gson.step.wait", 21, 23, {"session": 1}],
        ["gson.readback", 23.5, 24, {"session": 1}],
        ["gson.readback", 24, 24.5, {"session": 1}],
        ["gson.readback", 26, 27, {"session": 1}],
        ["gson.superstep", 28, 45, {"session": 1, "iteration": 8}],
        ["gson.step.wait", 38, 41, {"session": 1}],
        ["gson.readback", 42, 43, {"session": 1}],
    ]
    def ns(evs):
        return [[n, s * MS, e * MS, *rest] for n, s, e, *rest in evs]
    return {"devices": {"/device:TPU:0": ns(ops)}, "host": ns(host)}


def synthetic_ctx():
    ev = synthetic()
    red = phases.reduce(ev, trace.window_of(phases.plain(ev), trace.WINDOW),
                        KERNELS)
    return SimpleNamespace(phases=red, trace=red, supersteps=[
        {"iterations": 8}, {"iterations": 8}])


def test_reduce_synthetic_phases():
    red = synthetic_ctx().phases
    assert red.window_s == pytest.approx(0.050)
    assert red.busy_s == pytest.approx(0.032)
    assert red.kernel_s == {"find_winners": pytest.approx(0.002),
                            "update": pytest.approx(0.002)}
    # the conditional keeps 2 of its 4 ms, fusion.4 has no scope
    assert red.scope_s == pytest.approx({
        "gson.sample": 0.002, "gson.find_winners": 0.002,
        "gson.update": 0.003, "gson.tail": 0.018, "gson.refresh": 0.004,
        "gson.check": 0.002})
    # gaps labelled by the innermost span of either prefix: [22, 30]
    # (middle 26, in the readback [26, 27]) and [40, 50] (middle 45, in
    # gson.superstep [28, 45] before bench.superstep [0, 46])
    assert dict(red.idle_gaps) == pytest.approx(
        {"gson.readback": 0.008, "gson.superstep": 0.010})
    assert red.supersteps == 2 and red.readbacks == 4
    # idle 18 ms, of which the host waits in [22, 23] and [40, 41]
    assert red.host_idle_s == pytest.approx(0.016)
    # supersteps [0, 25] and [28, 45] outside busy-or-waiting [0, 23]
    # and [30, 41]: 2 + 6 ms
    assert red.session_host_s == pytest.approx(0.008)


def test_readers_synthetic():
    got = read_all(synthetic_ctx())
    assert got == pytest.approx({
        "sample_ms.solo": 2 / 16, "tail_ms.solo": 18 / 16,
        "refresh_ms.solo": 4 / 16, "check_ms.solo": 2 / 16,
        "session_host_ms.solo": 4.0, "host_idle_share.solo": 32.0,
        "readbacks_per_superstep.solo": 2.0})


def test_scope_of():
    assert phases.scope_of(BODY + "vmap(gson.tail)/gather:") == "gson.tail"
    assert phases.scope_of(
        BODY + "gson.check/vmap(gson.refresh)/x") == "gson.refresh"
    assert phases.scope_of("jit(f)/while/body/sort") is None
    assert phases.scope_of("") is None and phases.scope_of(None) is None


def test_three_field_trace_reads_with_no_phases():
    """The trace recorded before the program had spans or scopes: the
    harness's numbers come out as before, and every reader of the
    program's marks finds nothing."""
    ev = trace.read(PLAIN)
    window = trace.window_of(ev, trace.WINDOW)
    red = phases.reduce(ev, window, KERNELS)
    old = trace.reduce(ev, window, KERNELS)
    assert (red.busy_s, red.kernel_s, red.top_ops, red.idle_gaps) == \
        (old.busy_s, old.kernel_s, old.top_ops, old.idle_gaps)
    assert red.scope_s == {} and red.supersteps == 0
    ctx = SimpleNamespace(phases=red, trace=red,
                          supersteps=[{"iterations": 5}])
    assert set(read_all(ctx).values()) == {None}


def test_of_takes_the_newest_trace_of_the_same_window(monkeypatch, tmp_path):
    d = tmp_path / "trace" / "c768-solo" / "plugins"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    monkeypatch.setattr(phases, "load", lambda _: synthetic())
    phases._reduced.cache_clear()
    same = SimpleNamespace(trace=SimpleNamespace(window_s=0.050))
    other = SimpleNamespace(trace=SimpleNamespace(window_s=0.051))
    try:
        assert phases.of(same).supersteps == 2
        assert phases.of(other) is None
    finally:
        phases._reduced.cache_clear()


def recorded_ctx():
    """Two 4-iteration supersteps of the c768 sphere job (iterations 521
    to 529, a convergence check at 525, a refresh every other
    iteration), recorded on a TPU v5e with ``Session.run(budget=4)``
    twice."""
    ev = trace.read(SCOPED)
    red = phases.reduce(ev, trace.window_of(phases.plain(ev), trace.WINDOW),
                        KERNELS)
    return SimpleNamespace(phases=red, trace=red, supersteps=[
        {"iterations": 4}, {"iterations": 4}])


def test_reduce_recorded_scoped_trace():
    red = recorded_ctx().phases
    assert set(red.scope_s) == set(PHASES)
    assert all(v > 0 for v in red.scope_s.values())
    assert 0 < sum(red.kernel_s.values()) < red.scope_s["gson.update"] \
        + red.scope_s["gson.find_winners"]
    # the six phases hold 86% of device time; the rest is operations
    # XLA gives no op_name (scatter fusions, sorts, copies) and loop
    # control
    six = sum(red.scope_s.values())
    assert 0.85 * red.busy_s <= six <= red.busy_s
    # the four phases with the two kernel groups hold 61%: the Update's
    # gather around its kernels (a quarter of busy time) is under
    # gson.update but in no kernel
    four = sum(red.scope_s[s] for s in ("gson.sample", "gson.tail",
                                        "gson.refresh", "gson.check"))
    assert 0.55 * red.busy_s < four + sum(red.kernel_s.values()) \
        < 0.9 * red.busy_s
    assert red.scope_s["gson.update"] - red.kernel_s["update"] \
        > 0.2 * red.busy_s
    # the idle gaps name the program's host work
    assert {g for g, _ in red.idle_gaps} <= {
        "gson.step.prepare", "gson.step.unwrap", "gson.readback",
        "gson.session.emit", "gson.superstep", "gson.step"}


def test_readers_recorded_scoped_trace():
    ctx = recorded_ctx()
    got = read_all(ctx)
    assert all(v is not None and v > 0 for v in got.values())
    assert got["tail_ms.solo"] > got["refresh_ms.solo"] > \
        got["sample_ms.solo"]
    assert got["host_idle_share.solo"] <= 100.0 * ctx.trace.idle_share
    # seven reads per superstep, and one more per ``run`` call: the
    # active check that ends it after its budget is spent
    assert got["readbacks_per_superstep.solo"] == 8.0
