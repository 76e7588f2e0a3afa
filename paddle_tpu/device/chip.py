"""What a script that measures on the chip needs before it starts: a TPU
or a refusal, the chip's published peak rates, and one place for the
persistent compile cache. Shared by chip_smoke.py, bench.py,
bench_serving.py, bench_decode.py and the profiling tools, so that none
of them carries a CPU fallback, a default peak or a cache path of its own.
"""
from __future__ import annotations

import os

__all__ = ["require_tpu", "device_record", "peak_rates",
           "use_compile_cache", "release_device_memory", "PEAK_RATES"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Published peaks of ONE chip, keyed by jax's ``device_kind``. A device
# that is not here is an error, never a default.
PEAK_RATES = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def require_tpu():
    """``jax.devices()[0]`` if it is a TPU; otherwise raise, so the script
    exits non-zero with the reason. A timing of XLA:CPU is not a smaller
    measurement of this system, it is a measurement of something nobody
    deploys — there is no CPU fallback."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"this script measures on a TPU and JAX reports platform="
            f"{dev.platform!r} ({dev.device_kind}); no CPU fallback — run "
            "it through the chip tool (see README.md, 'Layout')")
    return dev


def device_record(dev) -> dict:
    """The device a result ran on, as every record names it."""
    import jax
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_rates(device_kind: str) -> dict:
    """The published peak rates of ``device_kind`` (``PEAK_RATES``)."""
    try:
        return PEAK_RATES[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device_kind {device_kind!r}: add "
            "it to paddle_tpu.device.chip.PEAK_RATES with its source "
            f"(known: {sorted(PEAK_RATES)})") from None


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and
    nothing is touched; otherwise the cache goes to ``<repo>/.jax_cache``
    — fixed and inside the checkout, because the path is part of the
    cache key and a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def release_device_memory() -> int:
    """Free every live device array between phases that each rebuild
    their state from a seed (dead models' buffers otherwise linger until
    a gc pass breaks the Layer/tape reference cycles, and the next phase
    runs out of HBM). Returns the number of arrays deleted."""
    import gc

    import jax
    gc.collect()
    n = 0
    for a in jax.live_arrays():
        a.delete()
        n += 1
    jax.clear_caches()
    return n
