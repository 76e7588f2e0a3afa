"""What every runner shares: the manifest (``BENCHMARK.json``) and the files
it names, the no-CPU-fallback device check, the table of published peaks,
the compile cache's fixed place, the loader of per-layer readers, and the
one result line.

The harness is driven by data. A workload names its ``config`` and
``traffic``; the configuration file names its ``kind`` (which selects
``benchmark/runners/<kind>.py``) and its ``family`` (which selects
``benchmark/models/<family>.py``); a per-layer metric ``<reader>.<suffix>``
is read by ``benchmark/layer_metrics/<reader>.py``. A later PR adds files
and manifest entries and edits nothing that is here.
"""
from __future__ import annotations

import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)

# Published peaks of ONE chip, keyed by JAX's ``device_kind``. The
# benchmark's own copy (the program keeps another in
# paddle_tpu/device/chip.py, which a later PR could change). A device that
# is not here is an error, never a default.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


class BenchmarkError(RuntimeError):
    """The run cannot produce a result: exit non-zero, print no result."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise BenchmarkError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/harness.py::PEAKS (known: {sorted(PEAKS)})") from None


# ---------------------------------------------------------------- manifest
def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of the manifest with everything it names, loaded."""

    def __init__(self, manifest_path, name):
        root = os.path.dirname(os.path.abspath(manifest_path))
        self.manifest = load_json(manifest_path)
        try:
            self.workload = next(w for w in self.manifest["workloads"]
                                 if w["name"] == name)
        except StopIteration:
            raise BenchmarkError(
                f"no workload {name!r} in {manifest_path} (has: "
                f"{[w['name'] for w in self.manifest['workloads']]})"
            ) from None
        entry = next(c for c in self.manifest["configs"]
                     if c["name"] == self.workload["config"])
        self.name = name
        self.chips = int(self.workload["chips"])
        self.config = load_json(os.path.join(root, entry["file"]))
        self.traffic = load_json(_find(root, self.manifest["paths"], os.path.join(
            "traffic", self.workload["traffic"] + ".json")))

    def metrics(self, group):
        """The manifest's ``end_to_end`` or ``per_layer`` entries that this
        cell reports (an entry without ``workloads`` is in every cell)."""
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]


def _find(root, paths, rel):
    """``rel`` under the first of the manifest's ``paths`` that has it."""
    for p in paths:
        full = os.path.join(root, p, rel)
        if os.path.exists(full):
            return full
    raise BenchmarkError(f"no {rel} under any of {paths}")


def load_part(package, name):
    """``benchmark/<package>/<name>.py`` as a module, found by name."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    try:
        return importlib.import_module(f"benchmark.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{package}.{name}":
            raise
        raise BenchmarkError(
            f"benchmark/{package}/{name}.py does not exist") from None


# ------------------------------------------------------------------ device
def require_chips(n):
    """The devices, if JAX reports ``n`` TPU chips; raises otherwise. Never
    sets the platform: a number from XLA:CPU is not a smaller measurement
    of this system."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchmarkError(
            f"the benchmark measures on a TPU and JAX reports platform="
            f"{devs[0].platform!r} ({devs[0].device_kind}); there is no CPU "
            "fallback")
    if len(devs) < n:
        raise BenchmarkError(
            f"the cell needs {n} chip(s), JAX reports {len(devs)}")
    return devs[:n]


def use_compile_cache():
    """JAX's persistent compilation cache at a FIXED path inside the
    checkout (the path is part of the cache's key): the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<repo>/.jax_cache``. Every
    program is kept, however quickly it compiled, so that the second run of
    a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_record(devs, trace=None):
    """The ``device`` key of the result line: the device as JAX reports it,
    the peak memory of the fullest chip and, from a traced run, the busy
    seconds (mean over the chips used) and the traced window's length."""
    peak = 0
    for d in devs:
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        rec["busy_s"] = trace["busy_s"]
        rec["window_s"] = trace["window_s"]
    return rec


# ------------------------------------------------------------------ result
def read_layer_metrics(cell, obs):
    """Every per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        reader = load_part("layer_metrics", m["name"].split(".")[0])
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def pick_end_to_end(cell, values):
    """The cell's end-to-end metrics out of what the runner measured; one
    that the runner did not measure is an error, not a gap."""
    out = {}
    for m in cell.metrics("end_to_end"):
        if values.get(m["name"]) is None:
            raise BenchmarkError(
                f"{cell.name}: runner measured no {m['name']!r}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def say(msg):
    """An earlier line of the output (never the last)."""
    print(f"benchmark: {msg}", flush=True)
