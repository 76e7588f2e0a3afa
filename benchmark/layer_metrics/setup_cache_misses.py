"""trainer: programs this process compiled because the persistent
compilation cache did not hold them, the program's counter
``paddle_compile_cache_misses_total`` (JAX counts a miss as it writes the
new entry). 0 on a tree's second run in a row; anything else says that the
tree's cache key moved or that the cache was evicted, which is what a cold
``setup_s`` means. Nothing from a program without the counter (before PR
36)."""


def read(obs):
    from paddle_tpu.inference import telemetry
    counters = telemetry.runtime_registry_snapshot()["counters"]
    return counters.get("paddle_compile_cache_misses_total")
