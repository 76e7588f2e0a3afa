"""What a script that runs on the chip needs before it starts: a TPU or a
refusal, the record that names the device, and one place for the
persistent compile cache. Shared by chip_smoke.py and the tools that
stay (tools/decode_profile.py, decode_alias_probe.py, longctx_bench.py,
vit_profile.py, llama_1b.py), so that none of them carries a CPU fallback
or a cache path of its own. Peak rates are the benchmark's
(benchmark/harness.py::PEAKS), on purpose.
"""
from __future__ import annotations

import os

__all__ = ["require_tpu", "device_record", "use_compile_cache",
           "release_device_memory"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


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
