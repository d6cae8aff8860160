"""Work counts at known shapes, the traffic generator, and the trace
reduction on a synthetic trace and on one recorded on a TPU v5e."""
import os

import pytest

from bench import generator, harness, trace, work

RECORDED = os.path.join(harness.BENCH, "recorded", "c768-superstep.json.gz")


@pytest.mark.parametrize("n,buffer,expect", [
    (0, 1024, 4), (2, 1024, 4), (3, 1024, 4), (4, 1024, 8), (94, 1024, 128),
    (211, 1024, 256), (700, 1024, 1024), (5000, 8192, 8192),
    (190, 8192, 256), (1, 2, 2)])
def test_m_schedule(n, buffer, expect):
    assert work.m_schedule(n, buffer) == expect


def test_m_schedule_matches_the_program():
    import jax.numpy as jnp
    from repro.core.gson.superstep import SuperstepConfig, device_m_schedule
    for buf in (1024, 8192):
        cfg = SuperstepConfig(max_parallel=buf)
        for n in (0, 2, 5, 63, 64, 65, 190, 1023, 1024, 4097, 9000):
            assert int(device_m_schedule(jnp.int32(n), cfg)) == \
                work.m_schedule(n, buf)


@pytest.mark.parametrize("capacity", [128, 768, 32768])
def test_buffer_rows_matches_the_program(capacity):
    from repro.gson.registry import VARIANTS
    cfg = dict(harness.load_json(harness.config_file("soam-c768")),
               capacity=capacity)
    from repro.gson.spec import resolve
    spec = harness.build_spec(cfg, "sphere")
    _, rt = resolve(spec)
    fused = VARIANTS.get("multi-fused")
    assert fused.fleet_cfg(spec, rt.params, rt.vcfg).max_parallel == \
        harness.buffer_rows(cfg)


def test_find_winners_work():
    assert work.find_winners(256, 190, 3) == (256 * 190 * 9,
                                              4 * (256 * 3 + 190 * 4 + 768))
    assert work.find_winners(8192, 32768, 3)[0] == 8192 * 32768 * 9


def test_update_work():
    flops, nbytes = work.update(100, 6.0, 3)
    assert flops == 100 * 7 * 11 + 100 * 12
    assert nbytes == 4 * (100 * 7 * 8 + 100 * 24)
    assert work.update(0, 6.0, 3) == (0.0, 0.0)


def test_generator_same_jobs_in_another_order():
    t = harness.load_json(harness.traffic_file("solo-sphere-torus"))
    runs = []
    for seed in (1, 2, 2 ** 31 + 5):
        jobs = generator.jobs(t, seed)
        runs.append([next(jobs) for _ in range(8)])
    for r in runs:
        assert sorted((j.surface, j.seed) for j in r) == \
            sorted((j.surface, j.seed) for j in runs[0])
        assert [j.first for j in r] == [True, False] * 4
    again = generator.jobs(t, 2)
    assert [next(again) for _ in range(8)] == runs[1]


def test_generator_rejects_bad_traffic():
    with pytest.raises(ValueError):
        generator.validate({"driver": "session", "rounds": [],
                            "window_end": "round",
                            "check": {"supersteps": 1}, "trace_seconds": 1})


def synthetic():
    ms = 1_000_000
    return {"devices": {
        "/device:TPU:0": [["while.1", 0, 4 * ms],
                          ["find_winners_pallas_padded.7", 0, 2 * ms],
                          ["fusion.1", 2 * ms, 3 * ms],
                          ["winner_lock_pallas_padded.3", 5 * ms, 6 * ms],
                          ["fusion.2", 9 * ms, 12 * ms]],
        "/device:TPU:1": [["fusion.2", 0, 10 * ms]]},
        "host": [["bench.traced_window", 0, 10 * ms],
                 ["bench.superstep", 0, 10 * ms],
                 ["bench.result", 3 * ms, 5 * ms]]}


def test_reduce_synthetic():
    ev = synthetic()
    red = trace.reduce(ev, trace.window_of(ev, "bench.traced_window"),
                       {"fw": ("find_winners_pallas_padded",),
                        "up": ("winner_lock_pallas_padded",)})
    # device 0 busy: [0,4] + [5,6] + [9,10] = 6 ms; device 1: 10 ms
    assert red.window_s == pytest.approx(0.010)
    assert red.busy_s == pytest.approx(0.008)
    assert red.idle_share == pytest.approx(0.2)
    assert red.kernel_s == {"fw": pytest.approx(0.001),
                            "up": pytest.approx(0.0005)}
    ops = dict(red.top_ops)
    assert red.top_ops[0] == ["fusion.2", pytest.approx(0.0055)]
    # the loop keeps only the time its nested operations leave
    assert ops["while.1"] == pytest.approx(0.0005)
    gaps = dict(red.idle_gaps)
    # gaps on device 0: [4,5] under bench.result, [6,9] under superstep
    assert gaps["bench.result"] == pytest.approx(0.0005)
    assert gaps["bench.superstep"] == pytest.approx(0.0015)


def test_union():
    assert trace.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]


def test_reduce_recorded_tpu_trace():
    ev = trace.read(RECORDED)
    assert any(name.startswith("/device:TPU") for name in ev["devices"])
    window = trace.window_of(ev, "bench.traced_window")
    red = trace.reduce(ev, window, {"fw": ("find_winners_pallas_padded",)})
    assert 0 < red.busy_s <= red.window_s
    assert 0.0 <= red.idle_share < 1.0
    assert 0 < red.kernel_s["fw"] < red.busy_s
    assert len(red.top_ops) <= 10 and len(red.idle_gaps) <= 10


def test_load_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(str(tmp_path))
    assert trace.window_of(ev, "bench.traced_window")
    assert ev["devices"] == {}           # the CPU has no device plane
    path = tmp_path / "ev.json.gz"
    trace.save(ev, str(path))
    assert trace.read(str(path)) == ev
