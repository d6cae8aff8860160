"""BENCHMARK.json against the benchmark's contract, and every file a
cell names found by name."""
import json
import os
import re

import pytest

from bench import generator, harness

ROOT = harness.ROOT
MAN = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in MAN["paths"])


def test_names_and_units():
    names = [c["name"] for c in MAN["configs"]]
    names += [w["name"] for w in MAN["workloads"]]
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    for kind in (names, [m["name"] for m in metrics]):
        assert len(kind) == len(set(kind))
    for n in names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("entry", MAN["configs"], ids=lambda c: c["name"])
def test_config_files(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert one_line(entry["source"]) and one_line(entry["why"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert cfg["reduced"] == entry["reduced"]
    for key in ("source", "assumed", "backend", "precision", "limits"):
        assert key in cfg
    assert cfg["backend"] == "pallas-full"
    assert any(w["config"] == entry["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_cells_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    c = harness.find_cell(cell["name"])
    generator.validate(c.traffic)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]).read)


def test_metrics_entries():
    workloads = {w["name"] for w in MAN["workloads"]}
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", workloads)) <= workloads
    layers = {m["layer"] for m in MAN["per_layer"]}
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"]) and m["layer"] in layers
        moved = e2e[m["moves"]]
        for w in m.get("workloads", workloads):
            assert w in workloads
            assert w in moved.get("workloads", workloads)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_run_seconds_fits_a_full_check():
    cells = 24
    total = ((2 + 14 * cells) * (MAN["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert 1 <= MAN["run_seconds"] <= 51 and total <= 43200


def test_four_chip_cells_at_most_half():
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_peaks_table_raises_on_unknown_device():
    assert harness.peak_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peak_for("no such chip")


def test_traffic_files_are_data():
    for f in os.listdir(os.path.join(ROOT, "bench", "traffic")):
        assert f.endswith(".json")
        generator.validate(harness.load_json(harness.traffic_file(f[:-5])))


def test_manifest_is_json_with_no_duplicate_keys():
    def hook(pairs):
        keys = [k for k, _ in pairs]
        assert len(keys) == len(set(keys))
        return dict(pairs)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        json.load(f, object_pairs_hook=hook)
