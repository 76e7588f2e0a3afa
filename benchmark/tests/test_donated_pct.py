"""The reader of the trainer's donation counter on hand-made call
timelines: the median share over the steady calls, nothing from a program
whose records lack ``donated`` / ``kept`` (the parent of PR 27) or that has
no timeline at all, and a real step read back."""
import pytest

from benchmark import harness

READER = harness.load_part("layer_metrics", "to_static_donated_pct")


def _rec(donated=None, kept=None, fresh=False):
    rec = {"kind": "to_static", "fresh": fresh, "call_s": 0.01,
           "dur_s": 0.005}
    if donated is not None:
        rec.update(donated=donated, kept=kept)
    return rec


def _read(monkeypatch, records):
    from paddle_tpu import jit
    monkeypatch.setattr(jit, "call_timeline", lambda: list(records))
    return READER.read({"kind": "train", "trace": None})


def test_the_median_share_of_the_steady_calls(monkeypatch):
    records = [_rec(0, 889, fresh=True)] * 2 + [_rec(889, 0)] * 9
    assert _read(monkeypatch, records) == pytest.approx(100.0)
    records = [_rec(3, 1)] * 5 + [_rec(1, 3)] * 4
    assert _read(monkeypatch, records) == pytest.approx(75.0)


def test_records_without_the_fields_report_nothing(monkeypatch):
    assert _read(monkeypatch, [_rec()] * 20) is None
    assert _read(monkeypatch, [_rec(5, 0)] * 7) is None      # under 8 calls


def test_a_program_without_the_timeline_reports_nothing(monkeypatch):
    from paddle_tpu import jit
    monkeypatch.delattr(jit, "call_timeline")
    assert READER.read({"kind": "train", "trace": None}) is None


def test_it_reads_a_real_timeline():
    import numpy as np
    import paddle_tpu as paddle
    lin = paddle.nn.Linear(4, 4)

    @paddle.jit.to_static
    def fwd(x):
        return lin(x)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    for _ in range(10):
        fwd(x)
    assert READER.read({"kind": "train", "trace": None}) == 100.0
