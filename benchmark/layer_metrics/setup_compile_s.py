"""trainer: seconds this process has waited for executables, since it
began: phases ``backend`` (XLA's compile) and ``cache_load`` (an executable
read back from the persistent compilation cache) of the program's counter
``paddle_compile_seconds_total``. JAX times the two as one
(``backend_compile_duration`` spans the cache's retrieval); the program
keeps them beside each other and this is their sum: the machine's part of
``setup_s``, small from a warm cache and minutes from a cold one. Nothing
from a program without the counter (before PR 36)."""
from benchmark.layer_metrics.setup_trace_s import seconds


def read(obs):
    return seconds("backend", "cache_load")
