"""trainer: median host milliseconds of the jitted call alone inside
``StaticFunction.__call__`` (``call_timeline()``'s ``dur_s``, the
``to_static.dispatch`` span): JAX's handling of the arguments and results
and the enqueue, what donation or fewer leaves would cut."""
from benchmark.layer_metrics.to_static_call_ms import median_ms


def read(obs):
    return median_ms(lambda r: r["dur_s"])
