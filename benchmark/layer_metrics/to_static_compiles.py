"""trainer: entries ``to_static`` traced and compiled in this process, the
program's counter ``paddle_to_static_compiles_total``: 2 in a training cell
(the optimizer's slots, then the steady signature); a third is a retrace
inside the window. Nothing from a program without the counter (before PR
25)."""
from benchmark.layer_metrics.to_static_call_ms import timeline


def read(obs):
    if timeline() is None:
        return None
    from paddle_tpu.inference import telemetry
    return telemetry.runtime_counter("paddle_to_static_compiles_total", 0)
