"""chip_smoke.py and the helpers it shares with the bench scripts
(paddle_tpu/device/chip.py): the refusals that keep CPU numbers out of
device metrics, and a CPU rehearsal of every chip_smoke phase at tiny
width (on-chip-measurement guide, section 2.1/2.2) — with the platform
check patched HERE, not through a flag of the script."""
import json
import os
import sys

import pytest

import jax

from paddle_tpu.testing.child import REPO_ROOT, cpu_env, run_child

sys.path.insert(0, REPO_ROOT)

import chip_smoke
from paddle_tpu.device import chip


# ------------------------------------------------------ the shared helpers
def test_require_tpu_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="no CPU fallback"):
        chip.require_tpu()


def test_compile_cache_env_is_left_alone(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX honours it itself, the helper
    touches nothing."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert chip.use_compile_cache() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "cc").exists()


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = chip.use_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert chip.use_compile_cache() == path      # fixed, not minted
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ------------------------------------------------------------ chip_smoke.py
def test_chip_smoke_fails_without_a_chip():
    """As the driver's sandbox runs it: non-zero exit, no result line."""
    r = run_child([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                  env=cpu_env())
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no CPU fallback" in r.stderr


@pytest.fixture
def tiny_smoke(monkeypatch):
    """chip_smoke at rehearsal size: the platform and compiled-by-Mosaic
    checks stubbed (interpret-mode kernels stand in for the chip's), every
    other check live."""
    monkeypatch.setattr(chip_smoke, "_require_chips",
                        lambda n: jax.devices()[0])
    monkeypatch.setattr(chip_smoke, "_check_mosaic", lambda *a, **k: None)
    monkeypatch.setattr(chip, "use_compile_cache", lambda: "off (rehearsal)")
    # the pytest process holds other tests' arrays: free nothing here
    monkeypatch.setattr(chip, "release_device_memory", lambda: 0)
    # the kernels are TPU-only; the one gate takes them (interpret mode)
    # on the CPU
    from paddle_tpu.ops import pallas
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    monkeypatch.setattr(chip_smoke, "TRAIN", dict(
        build="gpt2_tiny", batch=2, seq=128, steps=5, lr=1e-3))
    monkeypatch.setattr(chip_smoke, "SERVE", dict(
        hidden=64, heads=4, ffn=128, layers=2, vocab=256, slots=4,
        smax=256, new_tokens=6, prefix_blocks=8, shared=130,
        warm=((130, 6), (130, 9), (0, 16)),
        measured=((130, 8), (130, 12), (0, 150), (0, 16), (0, 70),
                  (0, 33)),
        quant_prompts=(20, 40), quant_new_tokens=4))
    monkeypatch.setattr(chip_smoke, "HYBRID", dict(
        hidden=64, layers=2, heads=4, ffn=128, vocab=256, batch=4, seq=32,
        steps=3, lr=1e-3))


def test_chip_smoke_rehearsal_one_chip(tiny_smoke, capsys):
    chip_smoke.main([])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_chip_smoke_rehearsal_four_chips(tiny_smoke, capsys):
    chip_smoke.main(["--chips", "4"])
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True
    assert "[trainer]" not in out and "[server]" not in out   # that phase only
    assert "residency identity holds" in out
