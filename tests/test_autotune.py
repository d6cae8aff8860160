"""Shape-aware Update-phase autotuner (``repro.gson.autotune``).

Pins the selection machinery without real timing: a fake ``TimerFn``
drives measurement deterministically, the JSON table round-trips and
rejects foreign schema versions, unmeasured shapes resolve to the
nearest measured cell in log-shape space, ``$REPRO_AUTOTUNE_TABLE``
overrides the committed default, and — the regression the committed
table exists for — ``pallas-auto`` always dispatches to the backend
the table measured fastest, including the units ≥ 1024 cliff rows
where the dense kernel loses to the scatter reference.
"""
from __future__ import annotations

import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro import gson
from repro.core.gson.multi import (multi_signal_step_impl,
                                   update_phase_reference)
from repro.gson import autotune as at

FAKE_US = {"reference": 50.0, "pallas": 30.0, "sparse": 10.0}


def fake_timer(name, thunk):
    # never calls the thunk: selection must not depend on execution
    return FAKE_US[name] * 1e-6


def tiny_cells():
    return ((8, 64, 16), (8, 256, 16))


def hand_table(cells, backend=None):
    """A table built without any jax work (hand-written Cells),
    keyed to ``backend`` (default: this process's)."""
    made = tuple(
        at.Cell(units=u, capacity=c, m=m,
                best=min(FAKE_US, key=lambda k: (FAKE_US[k], k)),
                t_us=dict(FAKE_US))
        for (u, c, m) in cells)
    return at.SelectionTable(
        cells=made, meta={"backend": backend or jax.default_backend()})


# ---------------------------------------------------------------------------
# measurement determinism


def test_measure_cell_is_deterministic_under_fake_timer():
    a = at.measure_cell(8, 64, 16, timer=fake_timer)
    b = at.measure_cell(8, 64, 16, timer=fake_timer)
    assert a == b
    assert a.best == "sparse"
    assert a.t_us == pytest.approx(FAKE_US)


def test_tied_timings_break_deterministically():
    tied = lambda name, thunk: 1.0          # noqa: E731
    cell = at.measure_cell(8, 64, 16, timer=tied)
    # (time, name) minimum: the lexicographically smallest candidate
    assert cell.best == min(at.update_phase_candidates())


def test_build_table_reproducible():
    t1 = at.build_table(tiny_cells(), timer=fake_timer, meta={})
    t2 = at.build_table(tiny_cells(), timer=fake_timer, meta={})
    assert t1 == t2
    assert [c.best for c in t1.cells] == ["sparse", "sparse"]


# ---------------------------------------------------------------------------
# persistence


def test_json_round_trip(tmp_path):
    table = at.build_table(tiny_cells(), timer=fake_timer)
    path = at.save_table(table, str(tmp_path / "t.json"))
    assert at.load_table(path) == table


def test_schema_version_rejected(tmp_path):
    table = at.build_table(tiny_cells(), timer=fake_timer)
    payload = table.to_json()
    payload["schema"] = at.SCHEMA_VERSION + 1
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(payload))
    with pytest.raises(at.TableSchemaError, match="regenerate"):
        at.load_table(str(bad))
    with pytest.raises(ValueError):
        at.SelectionTable.from_json({"schema": at.SCHEMA_VERSION,
                                     "cells": []})


def test_env_override_wins(tmp_path, monkeypatch):
    table = hand_table(((4, 32, 8),))
    path = at.save_table(table, str(tmp_path / "env.json"))
    monkeypatch.setenv(at.ENV_TABLE, path)
    assert at.load_table() == table
    # and strictly: a broken override is an error, not a fallback
    (tmp_path / "broken.json").write_text("{")
    monkeypatch.setenv(at.ENV_TABLE, str(tmp_path / "broken.json"))
    with pytest.raises(json.JSONDecodeError):
        at.load_table()


def test_table_from_another_backend_raises(tmp_path, monkeypatch):
    """A table measured on one backend never dispatches on another:
    not as an explicit path, the env override, or the local cache."""
    path = at.save_table(hand_table(((4, 32, 8),), backend="tpu-elsewhere"),
                         str(tmp_path / "foreign.json"))
    with pytest.raises(at.TableBackendError, match="regenerate"):
        at.load_table(path)
    monkeypatch.setenv(at.ENV_TABLE, path)
    with pytest.raises(at.TableBackendError):
        at.load_table()
    monkeypatch.delenv(at.ENV_TABLE)
    monkeypatch.setenv(at.ENV_CACHE, path)
    with pytest.raises(at.TableBackendError):
        at.load_table()


def test_local_cache_read_only_when_named(tmp_path, monkeypatch):
    """A table left in the working directory steers nothing: only a
    cache that ``$REPRO_AUTOTUNE_CACHE`` names is read."""
    cache = hand_table(((4, 32, 8),))
    monkeypatch.delenv(at.ENV_TABLE, raising=False)
    monkeypatch.delenv(at.ENV_CACHE, raising=False)
    monkeypatch.chdir(tmp_path)
    at.save_table(cache, str(tmp_path / ".runs" / "autotune_table.json"))
    assert at.load_table() == at.load_table(at.PACKAGED_TABLE)
    monkeypatch.setenv(at.ENV_CACHE,
                       str(tmp_path / ".runs" / "autotune_table.json"))
    assert at.load_table() == cache


def test_corrupt_cache_warns_and_falls_through(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    cache.write_text("not json at all")
    monkeypatch.delenv(at.ENV_TABLE, raising=False)
    monkeypatch.setenv(at.ENV_CACHE, str(cache))
    with pytest.warns(RuntimeWarning, match="unusable autotune cache"):
        table = at.load_table()
    # fell through to the committed package default
    assert table == at.load_table(at.PACKAGED_TABLE)


# ---------------------------------------------------------------------------
# selection


def test_exact_cell_wins():
    table = at.SelectionTable(cells=(
        at.Cell(8, 256, 16, "pallas", {"pallas": 1.0, "reference": 2.0}),
        at.Cell(512, 4096, 1024, "reference",
                {"pallas": 9.0, "reference": 1.0}),
    ))
    assert table.select(256, 16, units=8) == "pallas"
    assert table.select(4096, 1024, units=512) == "reference"


def test_nearest_cell_fallback_for_unmeasured_shapes():
    table = at.SelectionTable(cells=(
        at.Cell(8, 128, 16, "sparse", {"sparse": 1.0}),
        at.Cell(1024, 8192, 2048, "reference", {"reference": 1.0}),
    ))
    # log-space nearest: shapes near each measured corner map to it,
    # with units defaulting to m/2 (the paper's m-schedule) when unknown
    assert table.select(150, 20) == "sparse"
    assert table.select(6000, 1500) == "reference"
    assert table.select(128, 16, units=8) == "sparse"


def test_unknown_backend_in_table_degrades_to_reference():
    table = at.SelectionTable(cells=(
        at.Cell(8, 128, 16, "cuda-warp", {"cuda-warp": 1.0}),))
    with pytest.warns(RuntimeWarning, match="unknown update-phase"):
        assert at.select_update_phase(table, 128, 16) == "reference"


def test_committed_table_always_selects_measured_best():
    """The pin behind ``pallas-auto``: at every committed cell the
    selection returns exactly the backend measured fastest there — in
    particular the units ∈ {1024, 2048} cliff rows can never again
    dispatch to a backend the table measured slower."""
    table = at.load_table(at.PACKAGED_TABLE)
    assert len(table.cells) >= 7
    for cell in table.cells:
        best = min(cell.t_us, key=lambda k: (cell.t_us[k], k))
        sel = at.select_update_phase(table, cell.capacity, cell.m,
                                     cell.units)
        assert sel == best == cell.best, cell
    # the cliff rows exist and are pinned
    cliff = {(c.units, c.capacity, c.m) for c in table.cells}
    assert {(1024, 2048, 2048), (2048, 2048, 4096)} <= cliff


# ---------------------------------------------------------------------------
# the pallas-auto adapter


def test_adapter_dispatch_matches_forced_reference():
    """An adapter whose table maps everything to 'reference' is the
    reference: bitwise-identical UpdateOut on a real phase input."""
    table = at.SelectionTable(cells=(
        at.Cell(8, 64, 16, "reference", {"reference": 1.0}),))
    up = at.make_autotuned_update_phase(table)
    st, sig, wid, sid, d2b, k_lock, p = at._cell_inputs(8, 64, 16)
    ref = update_phase_reference(st, sig, wid, sid, d2b, k_lock, p)
    got = up(st, sig, wid, sid, d2b, k_lock, p)
    for field in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, field)),
            np.asarray(getattr(got, field)), field)


def test_adapter_routes_last_collision_to_reference():
    table = at.SelectionTable(cells=(
        at.Cell(8, 64, 16, "sparse", {"sparse": 1.0}),))
    up = at.make_autotuned_update_phase(table)
    st, sig, wid, sid, d2b, k_lock, p = at._cell_inputs(8, 64, 16)
    p = dataclasses.replace(p, neighbor_collision="last")
    # the kernel paths raise on "last"; the adapter must not
    out = up(st, sig, wid, sid, d2b, k_lock, p)
    ref = update_phase_reference(st, sig, wid, sid, d2b, k_lock, p)
    np.testing.assert_array_equal(np.asarray(ref.w), np.asarray(out.w))


def test_registry_pallas_auto_is_shared_and_runs():
    be = gson.resolve_backend("pallas-auto")
    assert gson.resolve_backend("pallas-auto").update_phase \
        is be.update_phase
    # the adapter exposes its resolved selection for introspection
    sel = be.update_phase.select(768, 64)
    assert sel in at.update_phase_candidates()
    # and a short public-API run dispatches through it end to end
    spec = gson.RunSpec(variant="multi", model="gwr", sampler="sphere",
                        backend="pallas-auto", capacity=128, max_deg=12,
                        max_iterations=8, check_every=8,
                        qe_threshold=1e-4, n_probe=128)
    st_a, _ = gson.run(spec, seed=0)
    st_r, _ = gson.run(spec.replace(backend="reference"), seed=0)
    np.testing.assert_array_equal(np.asarray(st_a.nbr),
                                  np.asarray(st_r.nbr))
    np.testing.assert_allclose(np.asarray(st_a.w), np.asarray(st_r.w),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("driver,variant", [("session", "multi"),
                                            ("fleet", "multi-fused")])
def test_foreign_table_fails_the_run(tmp_path, monkeypatch, driver,
                                     variant):
    """A table from another device fails a pallas-auto run through the
    entry points. It loads at the first trace, inside the first step,
    where the lowering fallback must not turn it into a warning and a
    reference run."""
    path = at.save_table(hand_table(((4, 32, 8),), backend="tpu-elsewhere"),
                         str(tmp_path / "foreign.json"))
    monkeypatch.setenv(at.ENV_TABLE, path)
    spec = gson.RunSpec(variant=variant, model="gwr", sampler="sphere",
                        backend="pallas-auto", capacity=128, max_deg=12,
                        max_iterations=8, check_every=8,
                        qe_threshold=1e-4, n_probe=128)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        with pytest.raises(at.TableBackendError, match="regenerate"):
            if driver == "session":
                gson.run(spec, seed=0)
            else:
                gson.run_fleet(gson.FleetSpec.broadcast(spec, seeds=(0, 1)))


def test_platform_mismatch_is_not_a_lowering_failure(monkeypatch):
    """Kernels asked to run on a non-TPU accelerator raise
    PlatformMismatchError, and the lowering fallback hands it back to
    the caller instead of swapping in the reference."""
    from repro.gson import registry
    from repro.kernels import PlatformMismatchError, interpret_mode

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(PlatformMismatchError, match="'gpu'"):
        interpret_mode(None)
    assert interpret_mode(True) is True
    be = gson.resolve_backend("pallas-full")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        for err in (PlatformMismatchError("gpu"),
                    at.TableBackendError("foreign table")):
            assert registry.reference_fallback(
                be.find_winners, be.update_phase, err) is None


# ---------------------------------------------------------------------------
# the cliff can never silently return


@pytest.mark.slow
def test_units_1024_cliff_regression():
    """One full step at the cliff shape (units=1024, capacity=2048,
    m=2048) under pallas-auto vs the reference path: the autotuned
    dispatch must be within 1.1x of reference wall time. Before the
    autotuner this shape ran the dense kernel at ~2.1-2.7x reference
    (BENCH_gson.json speedup_kernel 0.47/0.37)."""
    import jax

    from repro.utils.timing import timed

    up = gson.resolve_backend("pallas-auto").update_phase
    st, sig, wid, sid, d2b, k_lock, p = at._cell_inputs(1024, 2048, 2048)
    # caller-owned jit (params static via closure, no donation: the
    # timers re-feed the same state buffers)
    step_auto = jax.jit(lambda s, x: multi_signal_step_impl(
        s, x, p, refresh_states=False, update_phase=up))
    step_ref = jax.jit(lambda s, x: multi_signal_step_impl(
        s, x, p, refresh_states=False))
    for step in (step_auto, step_ref):
        timed(step, st, sig, n=1, warmup=2)
    # interleaved single calls, min of each: load from other processes
    # (parallel test workers) then falls on both sides alike
    t_auto, t_ref = float("inf"), float("inf")
    for _ in range(5):
        t_auto = min(t_auto, timed(step_auto, st, sig, n=1, warmup=0)[1])
        t_ref = min(t_ref, timed(step_ref, st, sig, n=1, warmup=0)[1])
    assert t_auto <= 1.1 * t_ref, (
        f"pallas-auto {t_auto * 1e3:.1f}ms vs reference "
        f"{t_ref * 1e3:.1f}ms at the units=1024 cliff")
