"""``setup_trace_s``, ``setup_compile_s``, ``setup_cache_misses``: what the
set-up spent on compiles, from the program's counters; nothing from a
program without them (the parent of PR 36), and a warm run's 0 misses is a
reading, not a gap."""
import json
import os

from benchmark import harness

TRACE = harness.load_part("layer_metrics", "setup_trace_s")
COMPILE = harness.load_part("layer_metrics", "setup_compile_s")
MISSES = harness.load_part("layer_metrics", "setup_cache_misses")


def _seconds(phase):
    return f'paddle_compile_seconds_total{{phase="{phase}"}}'


def test_nothing_from_a_program_without_the_counters(monkeypatch):
    from paddle_tpu.inference import telemetry
    monkeypatch.setattr(telemetry, "_runtime_counters",
                        {"paddle_to_static_compiles_total": 2})
    assert TRACE.read({}) is None
    assert COMPILE.read({}) is None
    assert MISSES.read({}) is None
    # half a family is no reading either
    telemetry.runtime_counter(_seconds("trace"), 1.5)
    assert TRACE.read({}) is None


def test_readings_on_a_stubbed_registry(monkeypatch):
    from paddle_tpu.inference import telemetry
    monkeypatch.setattr(telemetry, "_runtime_counters", {
        _seconds("trace"): 2.5, _seconds("lower"): 1.25,
        _seconds("backend"): 40.0, _seconds("cache_load"): 3.0,
        "paddle_compile_cache_hits_total": 7,
        "paddle_compile_cache_misses_total": 0})
    assert TRACE.read({}) == 3.75
    assert COMPILE.read({}) == 43.0
    assert MISSES.read({}) == 0          # a warm run: a value, not None
    telemetry.runtime_counter("paddle_compile_cache_misses_total", 2)
    assert MISSES.read({}) == 2


def test_the_programs_own_names():
    """The readers spell the counters as the program does."""
    from paddle_tpu.inference import telemetry
    for phase in ("trace", "lower", "backend", "cache_load"):
        assert telemetry.compile_seconds_counter(phase) == _seconds(phase)
    counters = telemetry.runtime_registry_snapshot()["counters"]
    assert "paddle_compile_cache_misses_total" in counters
    assert TRACE.read({}) is not None and COMPILE.read({}) is not None


def test_nine_manifest_entries_move_setup_s():
    manifest = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    mine = [m for m in manifest["per_layer"]
            if m["name"].startswith("setup_")]
    cells = {"train": "gpt2_124m.pretrain",
             "qwen3next": "qwen3next_80b.pretrain_8k",
             "sdar": "sdar_30b.blockdiff_8k"}
    assert sorted(m["name"] for m in mine) == sorted(
        f"{r}.{s}" for r in ("setup_trace_s", "setup_compile_s",
                             "setup_cache_misses") for s in cells)
    for m in mine:
        reader, suffix = m["name"].split(".")
        assert m["workloads"] == [cells[suffix]]
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            "trainer", "program_counter", "setup_s", "lower")
        assert m["unit"] == ("count" if reader == "setup_cache_misses"
                             else "s")
        assert harness.load_part("layer_metrics", reader).read
    assert len(json.dumps(manifest)) < 64 * 1024
