"""trainer: seconds this process has spent turning Python into programs,
since it began: phases ``trace`` (Python to jaxpr) and ``lower`` (jaxpr to
an MLIR module) of the program's counter ``paddle_compile_seconds_total``,
which sums what JAX reports of every compile (the step's two, the eager
comparison's operations). It is the program's own Python: what a new
kernel's equations or a stamp on every operation cost ``setup_s``, cache or
no cache. Nothing from a program without the counter (before PR 36)."""


def seconds(*phases):
    """The counter's seconds over ``phases``; None where one is missing."""
    from paddle_tpu.inference import telemetry
    counters = telemetry.runtime_registry_snapshot()["counters"]
    names = [f'paddle_compile_seconds_total{{phase="{p}"}}' for p in phases]
    if any(n not in counters for n in names):
        return None
    return sum(counters[n] for n in names)


def read(obs):
    return seconds("trace", "lower")
