"""trainer: median host milliseconds of the program's own Python around the
jitted call (``call_timeline()``'s ``call_s - dur_s``: the entry key, the
argument lists, the write-back), what a change to
``paddle_tpu/jit/__init__.py`` would cut."""
from benchmark.layer_metrics.to_static_call_ms import median_ms


def read(obs):
    return median_ms(lambda r: r["call_s"] - r["dur_s"])
