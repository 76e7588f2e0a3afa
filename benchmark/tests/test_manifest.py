"""``BENCHMARK.json`` against the contract's limits that a test can see:
allowed characters, exact keys, files that exist, readers that load."""
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
MANIFEST = os.path.join(harness.REPO, "BENCHMARK.json")

if not os.path.exists(MANIFEST):
    pytest.skip("no BENCHMARK.json yet", allow_module_level=True)
M = harness.load_json(MANIFEST)


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in M["paths"])
    assert len(M["command"]) <= 32


def test_names_units_and_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        assert os.path.exists(os.path.join(harness.REPO, c["file"]))
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    e2e = {m["name"] for m in M["end_to_end"]}
    assert "setup_s" in e2e
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_finds_its_files_and_reports_enough():
    cells = {w["name"] for w in M["workloads"]}
    for name in cells:
        cell = harness.Cell(MANIFEST, name)
        harness.load_part("runners", cell.config["kind"])
        harness.load_part("models", cell.config["family"])
        harness.load_part("reference", cell.config["reference"])
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e        # reported where this one is
            assert callable(harness.load_part(
                "layer_metrics", m["name"].split(".")[0]).read)
    for m in M["end_to_end"] + M["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)


def test_files_under_paths_are_named_from_allowed_characters():
    for p in M["paths"]:
        for root, dirs, files in os.walk(os.path.join(harness.REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), harness.REPO)
                assert PATH.match(rel), rel
