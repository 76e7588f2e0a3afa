"""The trainer layer measures itself: spans, the call timeline and the
counters inside ``to_static``'s call path, ``RecordEvent``, and the names on
the Pallas kernels (PERF.md section 3, layer "trainer").

The spans are ``jax.profiler.TraceAnnotation``s, so they are read back from
the profiler's own trace (``.xplane.pb``, ``jax.profiler.ProfileData``): the
same file and clock as the device's operations on a chip.
"""
import glob
import os

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import jit
from paddle_tpu.inference import telemetry
from paddle_tpu.testing import pallas_call_sites

CALLS = "paddle_to_static_calls_total"
COMPILES = "paddle_to_static_compiles_total"
SECONDS = "paddle_to_static_call_seconds"


@pytest.fixture
def timeline(monkeypatch):
    """A ring of this test's own (the module's is shared by every test of
    the process)."""
    t = telemetry.Telemetry(ring=64)
    monkeypatch.setattr(jit, "_timeline", t)
    return t


def _train_step(seed=0):
    """A step whose optimizer builds its slots lazily: it traces once to
    create them and once more for the steady signature."""
    paddle.seed(seed)
    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=lin.parameters())

    @paddle.jit.to_static
    def train_step(x):
        loss = (lin(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    return train_step


def _x(rows=2):
    return paddle.to_tensor(np.ones((rows, 4), np.float32))


def _counter(name):
    return telemetry.runtime_counter(name, 0)


# ------------------------------------------------------------- the timeline
def test_one_record_a_call_and_fresh_marks_the_compiles(timeline):
    step = _train_step()
    calls0, compiles0 = _counter(CALLS), _counter(COMPILES)
    for _ in range(4):
        step(_x())
    step(_x(3))                         # a new input shape: a new entry
    step(_x())
    recs = jit.call_timeline()
    assert len(recs) == 6
    assert [r["fresh"] for r in recs] == [True, True, False, False, True,
                                          False]
    assert _counter(CALLS) - calls0 == 6
    assert _counter(COMPILES) - compiles0 == 3
    assert [r["n"] for r in recs] == list(range(calls0 + 1, calls0 + 7))
    for r in recs:
        assert r["kind"] == "to_static"
        assert r["fn"].endswith("train_step")
        assert min(r["key_s"], r["dur_s"], r["writeback_s"]) >= 0.0
        assert r["key_s"] + r["dur_s"] + r["writeback_s"] <= r["call_s"]
    # a compile is far longer than a dispatch of this step
    assert min(r["dur_s"] for r in recs if r["fresh"]) > max(
        r["dur_s"] for r in recs if not r["fresh"])


def test_call_seconds_histogram_counts_the_calls(timeline):
    step = _train_step()
    before = telemetry.runtime_histogram(SECONDS).count
    for _ in range(3):
        step(_x())
    assert telemetry.runtime_histogram(SECONDS).count - before == 3
    text = "\n".join(telemetry.runtime_prometheus())
    for name in (CALLS, COMPILES, SECONDS + "_count"):
        assert name in text


def test_run_steps_records_too(timeline):
    step = _train_step()
    step(_x())
    step(_x())
    compiles0 = _counter(COMPILES)
    stacked = paddle.to_tensor(np.ones((3, 2, 4), np.float32))
    step.run_steps(3, stacked)
    step.run_steps(3, stacked)
    recs = jit.call_timeline()
    assert len(recs) == 4
    assert [r["fresh"] for r in recs[2:]] == [True, False]
    assert _counter(COMPILES) - compiles0 == 1
    assert all(r["fn"].endswith("train_step") for r in recs)


def test_ring_zero_records_nothing_and_changes_no_output(monkeypatch):
    def losses():
        step = _train_step(seed=3)
        return [float(step(_x())) for _ in range(4)]

    monkeypatch.setattr(jit, "_timeline", telemetry.Telemetry(ring=64))
    with_ring = losses()
    assert len(jit.call_timeline()) == 4

    monkeypatch.setenv("PADDLE_TELEMETRY_RING", "0")
    off = telemetry.Telemetry()         # as the module builds its own
    monkeypatch.setattr(off, "clock", lambda: pytest.fail(
        "a clock reading with the ring at 0"))
    monkeypatch.setattr(jit, "_timeline", off)
    calls0, seconds0 = _counter(CALLS), telemetry.runtime_histogram(
        SECONDS).count
    assert losses() == with_ring
    assert jit.call_timeline() == []
    assert _counter(CALLS) - calls0 == 4    # the counters stay on
    assert telemetry.runtime_histogram(SECONDS).count == seconds0


def test_the_eager_path_records_nothing(timeline):
    step = _train_step()
    calls0 = _counter(CALLS)
    jit.enable_to_static(False)
    try:
        step(_x())
    finally:
        jit.enable_to_static(True)
    assert jit.call_timeline() == []
    assert _counter(CALLS) == calls0


def test_a_failed_call_leaves_no_record_and_no_compile(timeline):
    @paddle.jit.to_static
    def broken(x):
        raise ValueError("inside the trace")

    calls0, compiles0 = _counter(CALLS), _counter(COMPILES)
    with pytest.raises(ValueError, match="inside the trace"):
        broken(_x())
    assert jit.call_timeline() == []
    assert _counter(CALLS) - calls0 == 1
    assert _counter(COMPILES) == compiles0
    assert not broken._cache            # the fresh entry was evicted


# ------------------------------------------------------------------ donation
def _lin_step(donate_state=None, seed=0, forward_only=False):
    """(layer, compiled step): a training step past its two compiles, or a
    forward pass past its one."""
    paddle.seed(seed)
    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=lin.parameters())

    def train_step(x):
        loss = (lin(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(lin.forward if forward_only else train_step,
                                donate_state=donate_state)
    for _ in range(1 if forward_only else 2):
        step(_x())
    return lin, step


def _leaves():
    """How many persistent tensors the next compiled call threads (the
    registry is the process's: other tests' survivors count)."""
    import gc
    from paddle_tpu.tensor.tensor import persistent_tensors
    gc.collect()
    return len(persistent_tensors())


def _default():
    lin, step = _lin_step()
    return lin, step, (_x(),), 0, True


def _opted_out():
    lin, step = _lin_step(donate_state=False)
    return lin, step, (_x(),), _leaves(), False


def _state_as_argument():
    """``step(lin.bias)``: the bias array is a state leaf and an argument;
    donating it would hand the program a buffer it was also told to read."""
    paddle.seed(0)
    lin = paddle.nn.Linear(4, 4)

    @paddle.jit.to_static
    def step(b):
        lin.weight.set_value(lin.weight + b.reshape([1, 4]))
        return b * 2.0
    return lin, step, (lin.bias,), 1, True


def _two_leaves_one_array():
    """A registered ``detach()`` shares its source's array: neither may be
    donated, each comes back with its own result."""
    from paddle_tpu.tensor.tensor import register_persistent
    lin, step = _lin_step()
    twin = lin.weight.detach()
    register_persistent(twin)
    lin.twin = twin                     # lives as long as the layer
    return lin, step, (_x(),), 2, False


@pytest.mark.parametrize("case", [_default, _opted_out, _state_as_argument,
                                  _two_leaves_one_array])
def test_a_call_donates_the_state_it_may_and_keeps_the_rest(timeline, case):
    lin, step, args, kept, weight_consumed = case()
    n = _leaves()
    donated0 = _counter("paddle_to_static_donated_leaves_total")
    held = lin.weight._data             # the pre-step array
    before = np.asarray(held).copy()
    bias = np.asarray(lin.bias._data).copy()
    out = step(*args)
    rec = jit.call_timeline()[-1]
    assert (rec["donated"], rec["kept"]) == (n - kept, kept)
    assert (_counter("paddle_to_static_donated_leaves_total") - donated0
            == n - kept)
    assert held.is_deleted() == weight_consumed
    if not weight_consumed:
        np.testing.assert_array_equal(np.asarray(held), before)
    if case is _state_as_argument:
        np.testing.assert_array_equal(np.asarray(out._data), 2.0 * bias)
        np.testing.assert_allclose(np.asarray(lin.weight._data),
                                   before + bias.reshape(1, 4))
        np.testing.assert_array_equal(np.asarray(lin.bias._data), bias)
    else:
        assert np.isfinite(float(out))
        assert not np.array_equal(np.asarray(lin.weight._data), before)
    if case is _two_leaves_one_array:
        # the twin passed through unchanged, and is its own array now:
        # the next call donates both (one retrace, by the key)
        np.testing.assert_array_equal(np.asarray(lin.twin._data), before)
        step(*args)
        rec = jit.call_timeline()[-1]
        assert rec["fresh"] and (rec["donated"], rec["kept"]) == (n, 0)


def test_a_forward_only_call_hands_every_parameter_back(timeline):
    lin, forward = _lin_step(forward_only=True)
    want = [np.asarray(p._data).copy() for p in lin.parameters()]
    first = np.asarray(forward(_x())._data)
    for _ in range(3):
        held = lin.weight._data
        np.testing.assert_array_equal(np.asarray(forward(_x())._data), first)
        assert held.is_deleted()
        for p, w in zip(lin.parameters(), want):
            np.testing.assert_array_equal(np.asarray(p._data), w)
    assert jit.call_timeline()[-1]["kept"] == 0


def test_run_steps_donates_and_matches_single_steps(timeline):
    lin1, step1 = _lin_step(seed=5)
    lin2, step2 = _lin_step(seed=5)
    xs = np.random.RandomState(0).randn(3, 2, 4).astype(np.float32)
    single = [float(step1(paddle.to_tensor(xs[i]))) for i in range(3)]
    held = lin2.weight._data
    scanned = step2.run_steps(3, paddle.to_tensor(xs))
    rec = jit.call_timeline()[-1]
    assert held.is_deleted() and rec["kept"] == 0
    assert rec["donated"] == _leaves()
    np.testing.assert_allclose(np.asarray(scanned._data), single,
                               rtol=1e-6, atol=1e-7)
    for p, q in zip(lin1.parameters(), lin2.parameters()):
        np.testing.assert_allclose(np.asarray(p._data), np.asarray(q._data),
                                   rtol=1e-6, atol=1e-7)


def test_donated_steps_match_undonated_ones_bit_for_bit(timeline):
    def losses(donate_state):
        _, step = _lin_step(donate_state=donate_state, seed=9)
        return [np.asarray(step(_x())._data).tobytes() for _ in range(3)]
    assert losses(None) == losses(False)


def test_the_error_after_a_failure_says_what_was_consumed(timeline):
    """A failure while tracing consumed nothing: the state is as it was and
    the error is the function's own. A failure of the running program, after
    the buffers were handed over, says that the state is gone."""
    lin, step = _lin_step()
    held = lin.weight._data
    before = np.asarray(held).copy()

    @paddle.jit.to_static
    def broken(x):
        lin.weight.set_value(lin.weight * 0.0)
        raise ValueError("inside the trace")
    with pytest.raises(ValueError, match="inside the trace"):
        broken(_x())
    assert lin.weight._data is held and not held.is_deleted()
    np.testing.assert_array_equal(np.asarray(held), before)
    step(_x())                          # and the good step still runs

    key, (jitted, *boxes) = list(step._cache.items())[-1]    # the steady one

    def fails_on_the_device(*a):
        jitted(*a)
        raise RuntimeError("the program failed while running")
    step._cache[key] = (fails_on_the_device, *boxes)
    with pytest.raises(RuntimeError, match="state buffers were donated"):
        step(_x())


def test_the_eager_optimizer_step_consumes_nothing():
    """``opt.step()`` in eager mode compiles its update through
    ``to_static`` but does not own the state: an array held from before
    it stays readable."""
    paddle.seed(0)
    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=lin.parameters())
    for _ in range(3):
        held = lin.weight.detach()
        before = held.numpy().copy()
        (lin(_x()) ** 2).mean().backward()
        opt.step()
        opt.clear_grad()
        np.testing.assert_array_equal(held.numpy(), before)
        assert not np.array_equal(lin.weight.numpy(), before)
    assert opt._fused_fn is not None    # the compiled path is what ran


# ---------------------------------------------------------- the shared clock
def _host_events(trace_dir):
    """``{(plane, line): [(name, start_ns, end_ns, stats)]}`` of the newest
    trace under ``trace_dir``, host planes only."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out[(plane.name, line.name)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events]
    return out


def _trace(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # as the benchmark's runners trace
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(trace_dir))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_nest_in_the_profilers_own_trace(timeline, tmp_path):
    step = _train_step()
    step(_x())
    step(_x())                          # steady from here

    def body():
        for _ in range(3):
            step(_x())
        step(_x(5))                     # the one fresh call of the trace
    lines = _trace(tmp_path, body)
    ours = [evs for evs in lines.values()
            if any(e[0] == "to_static.call" for e in evs)]
    assert len(ours) == 1, "the spans of one thread lie on one line"
    by_name = {}
    for e in ours[0]:
        by_name.setdefault(e[0], []).append(e)
    calls = sorted(by_name["to_static.call"], key=lambda e: e[1])
    assert len(calls) == 4
    assert len(by_name["to_static.dispatch"]) == 3
    assert len(by_name["to_static.trace_compile"]) == 1
    assert len(by_name["to_static.key"]) == 4
    assert len(by_name["to_static.writeback"]) == 4
    # every phase lies inside exactly one call; the compile in the last
    for name in ("to_static.key", "to_static.dispatch",
                 "to_static.trace_compile", "to_static.writeback"):
        for e in by_name[name]:
            assert sum(_inside(e, c) for c in calls) == 1, (name, e)
    assert all(_inside(d, c) for d, c in zip(
        sorted(by_name["to_static.dispatch"], key=lambda e: e[1]), calls))
    assert _inside(by_name["to_static.trace_compile"][0], calls[3])
    # inside a call: key, then the jitted call, then the write-back
    for c in calls:
        mine = sorted((e for e in ours[0] if e is not c and _inside(e, c)
                       and e[0].startswith("to_static.")),
                      key=lambda e: e[1])
        assert mine[0][0] == "to_static.key"
        assert mine[-1][0] == "to_static.writeback"
        assert all(a[2] <= b[1] for a, b in zip(mine, mine[1:]))
    # the outermost span carries what the timeline's record carries
    recs = jit.call_timeline()[-4:]
    assert [int(c[3]["step"]) for c in calls] == [r["n"] for r in recs]
    assert all(c[3]["fn"] == r["fn"] for c, r in zip(calls, recs))


def test_record_event_nests_in_the_same_trace(timeline, tmp_path):
    from paddle_tpu.profiler import RecordEvent
    step = _train_step()
    step(_x())
    step(_x())
    outer = RecordEvent("user_outer")

    def body():
        with outer:
            with RecordEvent("user_inner") as inner:
                step(_x())
            body.inner = inner
    lines = _trace(tmp_path, body)
    assert outer.begin_ns <= body.inner.begin_ns
    assert body.inner.begin_ns <= body.inner.end_ns <= outer.end_ns
    evs = next(evs for evs in lines.values()
               if any(e[0] == "user_outer" for e in evs))
    one = {e[0]: e for e in evs}
    assert _inside(one["user_inner"], one["user_outer"])
    assert _inside(one["to_static.call"], one["user_inner"])
    assert _inside(one["to_static.dispatch"], one["to_static.call"])


def test_record_event_begin_end_without_a_trace():
    from paddle_tpu.profiler import RecordEvent
    ev = RecordEvent("plain")
    ev.begin()
    ev.end()
    assert ev.begin_ns <= ev.end_ns
    ev.end()                            # a second end is harmless


# ------------------------------------------------------- the kernels' names
_SITES = pallas_call_sites()
# where the file is named after the rule and the wrapper after its form
_WRAPPER = {"gated_delta_rule.py": "gdn_chunk_rule"}


@pytest.mark.parametrize(
    "site", _SITES, ids=[f"{f}:{name or line}" for f, line, name in _SITES])
def test_every_pallas_call_is_named(site):
    fname, line, name = site
    assert isinstance(name, str) and name, (
        f"{fname}:{line}: pallas_call without a name= string; the device "
        "trace would name the kernel after its enclosing Python function")
    # "<public wrapper>_<role>": the wrappers live in the file of their name
    assert name.startswith(_WRAPPER.get(fname, fname[:-3])), (fname, name)


def test_pallas_call_names_are_unique():
    names = [name for _, _, name in _SITES]
    assert len(names) == 20
    assert len(set(names)) == len(names), sorted(names)
