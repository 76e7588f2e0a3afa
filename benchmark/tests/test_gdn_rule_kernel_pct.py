"""``gdn_rule_kernel_pct``: the share of the delta rule's traces that took
the kernel, from the program's two counters; nothing when neither moved (a
program without the layer, or from before the counters)."""
from benchmark import harness

READER = harness.load_part("layer_metrics", "gdn_rule_kernel_pct")


def test_share_of_the_traces_that_took_the_kernel(monkeypatch):
    from paddle_tpu.inference import telemetry
    monkeypatch.setattr(telemetry, "_runtime_counters", {})
    assert READER.read({}) is None
    telemetry.runtime_counter("paddle_gdn_rule_kernel_traces_total", 3)
    assert READER.read({}) == 100.0
    telemetry.runtime_counter("paddle_gdn_rule_composite_traces_total", 1)
    assert READER.read({}) == 75.0
