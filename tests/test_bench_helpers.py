"""bench.py helper contracts: budget-gated scan fallback reports the
EFFECTIVE scan_k, a failing step ends the run, and there is no CPU
fallback."""
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench


class _FakeStep:
    """Callable train-step double with a run_steps surface."""

    def __init__(self):
        self.plain_calls = 0
        self.scan_calls = 0

    def __call__(self, *args):
        self.plain_calls += 1

        class Out:
            _data = np.asarray(0.5, np.float32)
        return Out()

    def run_steps(self, k, *args):
        self.scan_calls += 1

        class Out:
            _data = np.asarray([0.5] * k, np.float32)
        return Out()


def test_timed_train_scan_reports_effective_k(monkeypatch):
    step = _FakeStep()
    monkeypatch.setitem(bench.__dict__, "_T0", time.monotonic())
    bench._BUDGET_S[0] = 10_000.0          # plenty of budget: scan runs
    med, loss, k = bench._timed_train(step, (1, 2), lambda: (1, 2),
                                      steps=6, scan_k=3)
    assert k == 3 and step.scan_calls > 0 and step.plain_calls == 0

    # budget exhausted: falls back to per-dispatch timing AND reports 0
    bench._BUDGET_S[0] = 0.0
    step2 = _FakeStep()
    med, loss, k = bench._timed_train(step2, (1, 2), lambda: (1, 2),
                                      steps=4, scan_k=3)
    assert k == 0 and step2.scan_calls == 0 and step2.plain_calls == 4
    bench._BUDGET_S[0] = 1500.0            # restore default


def test_warm_propagates_a_step_failure():
    """A config that raises ends the run: warm-up neither retries nor
    swallows (the retry-then-"error"-row loop is gone)."""
    class Boom(_FakeStep):
        def __call__(self, *args):
            raise RuntimeError("compile failure")

    with pytest.raises(RuntimeError, match="compile failure"):
        bench._warm(Boom(), (), 1)
    ok = _FakeStep()
    bench._warm(ok, (), 3)
    assert ok.plain_calls == 3


def test_bench_main_refuses_the_cpu(monkeypatch, capsys):
    """No CPU fallback: on the CPU the bench raises before building any
    model, and prints no record."""
    with pytest.raises(RuntimeError, match="no CPU fallback"):
        bench.main()
    assert capsys.readouterr().out == ""
