"""The readers of the program's own spans and counters (layer "trainer")
on hand-made call timelines: the median over calls that compiled nothing,
nothing under 8 of them, nothing from a program without the timeline, and
the compile count."""
import pytest

from benchmark import harness

READERS = {name: harness.load_part("layer_metrics", name) for name in (
    "to_static_call_ms", "to_static_dispatch_ms", "to_static_guard_ms",
    "to_static_compiles")}


def _rec(call_ms, dispatch_ms, fresh=False):
    return {"kind": "to_static", "fresh": fresh, "call_s": call_ms / 1e3,
            "dur_s": dispatch_ms / 1e3, "key_s": 0.0, "writeback_s": 0.0}


@pytest.fixture
def program(monkeypatch):
    """Stands in for the program's timeline: set ``program.records``."""
    from paddle_tpu import jit

    class Program:
        records = []
    monkeypatch.setattr(jit, "call_timeline",
                        lambda: list(Program.records), raising=False)
    return Program


def _read(name):
    return READERS[name].read({"kind": "train", "trace": None})


def test_medians_are_over_the_calls_that_compiled_nothing(program):
    program.records = [_rec(60000, 59000, fresh=True)] * 2 + [
        _rec(10 + i, 4 + i / 2) for i in range(9)]
    assert _read("to_static_call_ms") == pytest.approx(14.0)
    assert _read("to_static_dispatch_ms") == pytest.approx(6.0)
    assert _read("to_static_guard_ms") == pytest.approx(8.0)


def test_only_the_newest_64_count(program):
    program.records = [_rec(500, 400)] * 300 + [_rec(70, 65)] * 64
    assert _read("to_static_call_ms") == pytest.approx(70.0)
    assert _read("to_static_guard_ms") == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["to_static_call_ms",
                                  "to_static_dispatch_ms",
                                  "to_static_guard_ms"])
def test_under_eight_steady_calls_there_is_nothing_to_report(program, name):
    program.records = [_rec(9, 3, fresh=True)] * 20 + [_rec(9, 3)] * 7
    assert _read(name) is None
    program.records.append(_rec(9, 3))
    assert _read(name) is not None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_timeline_reports_nothing(monkeypatch, name):
    from paddle_tpu import jit
    monkeypatch.delattr(jit, "call_timeline")       # the parent commit
    assert _read(name) is None


def test_the_compile_count_is_the_programs_counter(program):
    from paddle_tpu.inference import telemetry
    before = _read("to_static_compiles")
    telemetry.runtime_counter("paddle_to_static_compiles_total", 2)
    assert _read("to_static_compiles") - before == 2


def test_the_readers_read_a_real_timeline():
    """The field names are the program's: a real step, read back."""
    import numpy as np
    import paddle_tpu as paddle
    lin = paddle.nn.Linear(4, 4)

    @paddle.jit.to_static
    def fwd(x):
        return lin(x)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    for _ in range(9):
        fwd(x)
    call, dispatch, guard = (_read(n) for n in (
        "to_static_call_ms", "to_static_dispatch_ms", "to_static_guard_ms"))
    assert call >= dispatch > 0.0 and 0.0 < guard <= call
