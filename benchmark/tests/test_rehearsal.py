"""Both runners end to end at a tiny size on the CPU, through ``run.main``
with the manifest beside this file (never a cell), and the contract's shape
of the last line. The device check is patched HERE; the benchmark has no
option that lets it run without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness, run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.json")


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    import jax
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setitem(harness.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))


def _last_line(capsys, argv):
    run.main(argv + ["--manifest", TINY], t_start=time.monotonic())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    return line


def test_serve_runner_above_the_knee(on_cpu, capsys):
    line = _last_line(capsys, ["--workload", "tiny.chat_overload", "--seed",
                               str(2 ** 31 + 5), "--seconds", "6"])
    assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
    assert line["correct"] is True          # reference, paths, 0 retraces
    assert line["metrics"]["serve_tok_s"]["value"] > 0
    assert line["failed"] == 0      # how many finished depends on the CPU


def test_train_runner(on_cpu, capsys):
    line = _last_line(capsys, ["--workload", "tiny.pretrain", "--seed",
                               "7", "--seconds", "2"])
    assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
    assert line["attempted"] > 2 and line["failed"] == 0
    # off the chip the flash kernel is interpreted, so the lowered step has
    # no tpu_custom_call and the run is, rightly, not ``correct``
    assert line["correct"] is False


def test_no_tpu_no_result():
    """Unpatched, on a machine without a TPU: non-zero exit, no result."""
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "gpt2_124m.pretrain", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "no CPU fallback" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
