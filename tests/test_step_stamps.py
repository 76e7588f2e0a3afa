"""The stamps of a compiled step, the profiler's reduction by them, and the
set-up's compile counters (PR 36).

Every operation of a ``to_static`` step says in its HLO ``op_name`` which
pass (``bwd`` / ``replay`` / ``opt``), layer and scope it came from
(``tensor/tensor.py``, "stamps"); ``paddle.profiler`` reduces a device trace
by them (``load_profiler_result``: checked here on a hand-made ``XSpace``
with event-metadata stats, the thing ``jax.profiler.ProfileData`` does not
show); ``inference/telemetry.py`` sums what JAX reports of every compile.
"""
import contextlib
import gc
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler
from paddle_tpu.distributed.fleet.utils import recompute
from paddle_tpu.inference import telemetry
from paddle_tpu.profiler import SummaryView, pass_of, scope_of


# ------------------------------------------------------------ (a) the stamps
class _Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 8)

    def forward(self, x):
        with jax.named_scope("blk.mlp"):
            h = paddle.nn.functional.relu(self.fc1(x))
        return x + self.fc2(h)


class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.blocks = nn.LayerList([_Block(), _Block()])
        self.head = nn.Linear(8, 4)

    def forward(self, x, y):
        x = self.blocks[0](x)
        x = recompute(self.blocks[1], x)
        return ((self.head(x) - y) ** 2).mean()


def _step():
    paddle.seed(11)
    model = _Net()
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))

    def step(x, y):
        loss = model(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.linspace(-1, 1, 5 * 8, dtype=np.float32)
                         .reshape(5, 8))
    y = paddle.to_tensor(np.ones((5, 4), np.float32))
    st = paddle.jit.to_static(step)
    st(x, y)
    st(x, y)
    # the model is part of what the step holds: keep it alive with it
    return st, x, y, model


def _op_names(st, x, y):
    """``[(operation, op_name)]`` of the step as it is handed to XLA: the
    lowered module with its locations, each of which is JAX's name stack
    and the primitive. (The compiled text would do on the TPU; XLA's CPU
    compiler drops the replay's barrier and merges the replayed products
    with the first forward's.)"""
    text = st.lower(x, y).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found = re.findall(r'= "?(stablehlo\.\w+|call)\b[^\n]* loc\((#loc\d+)\)',
                       text)
    return [(op, locs[ref]) for op, ref in found if ref in locs]


@pytest.fixture(scope="module")
def compiled():
    st, x, y, model = _step()
    return _op_names(st, x, y)


def _dots(compiled):
    return [name for _, name in compiled if name.endswith("/dot_general")]


def test_layer_path_is_the_name_in_the_parent():
    net = _Net()
    assert net.blocks[1]._scope == "blocks/1"
    assert net.blocks[1].fc2._scope == "fc2"
    assert net.head._scope == "head"
    assert "_scope" not in net.__dict__        # the root: its class's name
    seq = nn.Sequential(nn.Linear(2, 2), nn.ReLU())
    assert seq[0]._scope == "0"
    # a slice is a view: it renames nothing
    part = net.blocks[1:]
    assert len(part) == 1 and net.blocks[1]._scope == "blocks/1"
    # a list that gets its name late hands it on; so does an insert
    late = nn.LayerList([nn.Linear(2, 2)])
    holder = nn.Layer()
    holder.stack = late
    assert late[0]._scope == "stack/0"
    late.insert(0, nn.ReLU())
    assert [sub._scope for sub in late] == ["stack/0", "stack/1"]
    d = nn.LayerDict({"a": nn.ReLU()})
    holder.named = d
    assert d["a"]._scope == "named/a"


def test_first_forward_carries_layer_and_no_pass(compiled):
    fwd = [n for n in _dots(compiled) if pass_of(n) == "fwd"]
    assert any("/_Net/blocks/0/blk.mlp/fc1/" in n for n in fwd), fwd
    assert any("/_Net/blocks/1/" in n for n in fwd), fwd   # under no_grad
    assert any("/_Net/head/" in n for n in fwd), fwd
    for n in fwd:
        assert not re.search(r"(^|/)(bwd|replay|opt)(/|$)", n), n


def test_backward_carries_bwd_and_its_layer(compiled):
    bwd = [n for n in _dots(compiled) if pass_of(n) == "bwd"]
    plain = [n for n in bwd if "/replay/" not in n]
    assert any(re.search(r"/bwd/_Net/blocks/0/blk\.mlp/fc1/transpose\(", n)
               for n in plain), bwd
    assert any(re.search(r"/bwd/_Net/head/transpose\(", n) for n in plain)
    # the replayed layer's backward: both markers, and it is backward
    nested = [n for n in bwd if "/replay/" in n]
    assert any(re.search(r"/bwd/_Net/replay/blocks/1/fc2/transpose\(", n)
               for n in nested), bwd
    # the marker is there once however deep the walk is nested
    assert all(len(re.findall(r"(?:^|/)bwd(?:/|$)", n)) == 1 for n in bwd)


def test_replay_carries_replay_and_its_layer(compiled):
    rep = [n for n in _dots(compiled) if pass_of(n) == "replay"]
    assert any("/bwd/_Net/replay/blocks/1/blk.mlp/fc1/" in n for n in rep)
    assert all("transpose(" not in n and "/blocks/0/" not in n for n in rep)


def test_optimizer_carries_opt(compiled):
    opt = [n for _, n in compiled if pass_of(n) == "opt"]
    assert any("/opt/adamw/" in n for n in opt), opt[:5]
    assert any("/opt/clip/" in n for n in opt), opt[:5]
    assert not any(n.endswith("/dot_general") for n in opt)


def test_no_matrix_product_is_left_with_jaxs_components_alone(compiled):
    dots = _dots(compiled)
    assert len(dots) >= 12          # 5 forward, 2 replayed, their backward
    for n in dots:
        layer, scope = scope_of(n)
        assert scope.startswith("_Net"), n
    # and nothing at all of the step is `jit(pure)/transpose(jvp())/...` or
    # `jit(pure)/jvp()/...` with no stamp in front
    for _, n in compiled:
        assert not re.match(r"jit\(\w+\)/(transpose\(jvp\(\)\)|jvp\(\))/", n), n


def test_the_stamps_are_metadata_only(monkeypatch):
    """The step lowers to the same module, byte for byte, with the stamps
    and with every scope switched off: no value, shape, order or barrier
    came with them."""
    def lowered():
        # a compiled step takes every persistent tensor of the process: each
        # model is built, lowered and dropped before the next
        st, x, y, model = _step()
        out = st.lower(x, y).as_text(), _op_names(st, x, y)
        del st, model
        gc.collect()
        return out

    with_stamps, names = lowered()
    assert any("_Net" in n for _, n in names)
    off = lambda *a, **k: contextlib.nullcontext()  # noqa: E731
    from paddle_tpu.autograd import backward_engine
    monkeypatch.setattr(jax, "named_scope", off)
    monkeypatch.setattr(backward_engine, "set_name_stack", off)
    without, names = lowered()
    assert not any("_Net" in n for _, n in names)
    assert without == with_stamps


def test_eager_backward_walks_under_the_scopes_too():
    """Outside a trace the walk re-enters scopes all the same (and leaves
    JAX's name stack as it found it)."""
    from jax._src.source_info_util import current_name_stack
    net = _Net()
    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    before = current_name_stack()
    net(x, paddle.to_tensor(np.zeros((2, 4), np.float32))).backward()
    assert current_name_stack() == before
    assert net.blocks[1].fc1.weight.grad is not None


@pytest.mark.parametrize("path, want", [
    ("jit(pure)/M/h/0/attn/jvp()/dot_general", "fwd"),
    ("jit(pure)/M/layers/2/mlp/dot_general", "fwd"),
    ("jit(pure)/bwd/M/h/0/attn/transpose(jvp())/dot_general", "bwd"),
    ("jit(pure)/bwd/M/layers/2/replay/mlp/jvp()/dot_general", "replay"),
    ("jit(pure)/bwd/M/layers/2/replay/mlp/transpose(jvp())/dot_general",
     "bwd"),
    ("jit(pure)/bwd/M/h/0/add", "bwd"),
    ("jit(pure)/opt/adamw/mul", "opt"),
    ("jit(pure)/opt/clip/transpose(jvp())/mul", "opt"),
    ("jit(pure)/M/optics/dot_general", "fwd"),       # a name is not a marker
    ("jit(pure)/transpose(jvp(replay))/mul", "bwd"),
    ("", "fwd"),
])
def test_the_one_rule_of_precedence(path, want):
    assert pass_of(path) == want


@pytest.mark.parametrize("path, want", [
    ("jit(pure)/bwd/M/model/layers/2/replay/mlp/moe.experts/"
     "transpose(jvp())/dot_general", (2, "M/model/layers/*/mlp/moe.experts")),
    # a custom_vjp's backward carries the forward's path again
    ("jit(pure)/bwd/M/layers/1/mixer/transpose(M)/layers/1/mixer/"
     "jvp(gdn.chunk_rule)/mul", (1, "M/layers/*/mixer/gdn.chunk_rule")),
    # a jitted callee that XLA inlined carries its caller's path again
    ("jit(pure)/M/layers/0/mlp/moe.route/jit(searchsorted)/jit(pure)/M/"
     "layers/0/mlp/moe.route/jit(searchsorted)/while/body/closed_call/"
     "select_n", (0, "M/layers/*/mlp/moe.route/while")),
    ("jit(pure)/M/h/7/attn/jvp(bhqk,bhkd->bhqd)/dot_general",
     (7, "M/h/*/attn")),
    ("jit(pure)/M/stack/3/experts/5/jvp()/dot_general",
     (3, "M/stack/*/experts/*")),
    ("jit(pure)/opt/adamw/sqrt", (None, "adamw")),
    ("jit(pure)/transpose(jvp())/dot_general", (None, "")),
    ("jit(pure)/jit(floor_divide)/rem", (None, "")),
    ("", (None, "")),
])
def test_scope_of_an_op_name(path, want):
    assert scope_of(path) == want


# ------------------------------------------- (b) the reduction of a trace
_STATS = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "bytes_accessed",
          5: "source", 6: "loop fusion", 7: "device_offset_ps"}


def _metadata(mid, name, op_name=None, category=None, flops=0, nbytes=0,
              source=None):
    stats = ""
    if op_name is not None:
        stats += f'stats {{ metadata_id: 1 str_value: "{op_name}:" }} '
    if category == "loop fusion":           # a string kept by reference
        stats += "stats { metadata_id: 2 ref_value: 6 } "
    elif category:
        stats += f'stats {{ metadata_id: 2 str_value: "{category}" }} '
    if flops:
        stats += f"stats {{ metadata_id: 3 uint64_value: {flops} }} "
    if nbytes:
        stats += f"stats {{ metadata_id: 4 int64_value: {nbytes} }} "
    if source:
        stats += f'stats {{ metadata_id: 5 str_value: "{source}" }} '
    short = name.split(" = ")[0].lstrip("%")
    return (f"event_metadata {{ key: {mid} value {{ id: {mid} "
            f'name: "{name}" display_name: "{short}" {stats}}} }}\n')


def _events(events):
    return " ".join(
        f"events {{ metadata_id: {mid} offset_ps: {off * US} "
        f"duration_ps: {dur * US} "
        f"stats {{ metadata_id: 7 uint64_value: {off * US} }} }}"
        for mid, off, dur in events)


US = 10 ** 6        # the hand-made trace counts in microseconds
# two steps of 1000 us; in each: a forward fusion of layer 0 and of layer 1
# (100 + 100), an unnamed copy behind it (50), a `while` of 300 whose body
# runs two operations of 100 (so 100 of its own), a flash kernel in the
# backward (200), an optimizer fusion (100); 150 idle
_META = (
    _metadata(1, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop",
              "jit(pure)/M/layers/0/mlp/jvp()/mul", "loop fusion", 1000, 64)
    + _metadata(2, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.2), kind=kLoop",
                "jit(pure)/M/layers/1/mlp/jvp()/mul", "loop fusion", 1000, 64)
    + _metadata(3, "%copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.2)",
                "donated_arrays[3]", "data formatting", 0, 128)   # no path
    + _metadata(4, "%while.4 = (s32[], f32[8]{0}) while(%tuple.1), "
                "condition=%c, body=%b", "jit(pure)/M/moe.route/jit(f)/"
                "jit(pure)/M/moe.route/jit(f)/while", "while")
    + _metadata(5, "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p.5), kind=kLoop",
                "jit(pure)/M/moe.route/jit(f)/jit(pure)/M/moe.route/jit(f)/"
                "while/body/closed_call/add", "loop fusion", 10, 8)
    + _metadata(6, "%jvp_flash_attention_bwd_dq_.3 = bf16[8]{0} custom-call("
                "bf16[8]{0} %p.6), custom_call_target=\\\"tpu_custom_call\\\"",
                "jit(pure)/bwd/M/layers/1/replay/attn/transpose(M)/layers/1/"
                "replay/attn/jvp()/jvp_flash_attention_bwd_dq_/pallas_call",
                "custom-call")
    + _metadata(7, "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p.7), kind=kLoop",
                "jit(pure)/opt/adamw/sqrt", "loop fusion", 500, 96,
                "/repo/paddle_tpu/optimizer/optimizer.py:440")
    + _metadata(8, "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p.8), kind=kLoop",
                "jit(pure)/jit(floor_divide)/rem", "loop fusion", 0, 8,
                "/repo/x.py:1")
    + _metadata(100, "0") + _metadata(101, "1"))


def _one_step(t):
    return [(8, t, 10), (1, t + 10, 100), (2, t + 110, 100),
            (3, t + 210, 50), (4, t + 300, 300), (5, t + 310, 100),
            (5, t + 450, 100), (6, t + 600, 200), (7, t + 800, 90)]


def _xspace(tmp_path):
    from jax.profiler import ProfileData
    stat_md = "".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in _STATS.items())
    text = (
        'planes { name: "/host:CPU" lines { name: "main" '
        + _events([(1, 0, 10)]) + ' } }\n'
        'planes { name: "/device:TPU:0"\n'
        '  lines { name: "Steps" ' + _events([(100, 0, 1000),
                                              (101, 1000, 1000)]) + ' }\n'
        '  lines { name: "XLA Ops" timestamp_ns: 5 '
        + _events(_one_step(0) + _one_step(1000)) + ' }\n'
        '  lines { name: "Async XLA Ops" ' + _events([(3, 0, 777)]) + ' }\n'
        + _META + stat_md + '}\n')
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_reduction_of_a_hand_made_trace(tmp_path):
    res = profiler.load_profiler_result(_xspace(tmp_path))
    assert (res.steps, res.devices) == (2, 1)
    # nesting taken out: the while keeps 100 of its 300, and the rows sum to
    # the busy time of the line (the union of its intervals), a step
    busy = 10 + 100 + 100 + 50 + 300 + 200 + 90
    assert res.busy_s == pytest.approx(busy * 1e-6)
    assert sum(r["seconds"] for r in res.rows) == pytest.approx(res.busy_s)
    rows = {(r["pass"], r["layer"], r["scope"], r["category"]): r
            for r in res.rows}
    # layer indices split off, one row a layer, the scope shared
    for layer in (0, 1):
        r = rows[("fwd", layer, "M/layers/*/mlp", "loop fusion")]
        assert r["seconds"] == pytest.approx(100e-6)
        assert (r["flops"], r["bytes"], r["events"]) == (1000, 64, 1)
    # the unnamed copy counts with the operation before it, and says so
    r = rows[("fwd", 1, "M/layers/*/mlp", "data formatting")]
    assert r["seconds"] == r["unnamed"] == pytest.approx(50e-6)
    # repeated paths collapse; the body's operations say they are in a loop
    assert rows[("fwd", None, "M/moe.route", "while")]["seconds"] == \
        pytest.approx(100e-6)
    body = rows[("fwd", None, "M/moe.route/while", "loop fusion")]
    assert body["seconds"] == pytest.approx(200e-6) and body["events"] == 2
    assert body["flops"] == 20
    # the kernel of the replayed layer's backward: pass, layer, name
    k = next(r for r in res.rows if r["kernel"])
    assert (k["pass"], k["layer"], k["kernel"]) == (
        "bwd", 1, "jvp_flash_attention_bwd_dq_")
    assert k["scope"] == "M/layers/*/attn/jvp_flash_attention_bwd_dq_"
    assert rows[("opt", None, "adamw", "loop fusion")]["seconds"] == \
        pytest.approx(90e-6)
    # what has no layer or scope is listed by its source line
    assert res.unscoped == [(pytest.approx(10e-6), "fusion.8", "/repo/x.py:1")]
    by_pass = res.by("pass")
    assert by_pass["fwd"]["seconds"] == pytest.approx(560e-6)
    assert list(res.by("scope"))[:2] == ["M/layers/*/mlp", "M/moe.route/while"]


def test_xplane_reader_takes_bytes_and_skips_unwanted_planes(tmp_path):
    from paddle_tpu.profiler import xplane
    path = xplane.find_xplane(_xspace(tmp_path))
    with open(path, "rb") as f:
        space = xplane.read_xspace(f.read(),
                                   want=lambda n: n.startswith("/device"))
    host, dev = space["planes"]
    assert host["name"] == "/host:CPU" and host["lines"] == []
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")
    assert ops["timestamp_ns"] == 5 and ops["events"][1] == (1, 10 * US, 100 * US)
    md = dev["event_metadata"][1]
    assert md["display_name"] == "fusion.1"
    assert md["stats"] == {"tf_op": "jit(pure)/M/layers/0/mlp/jvp()/mul:",
                           "hlo_category": "loop fusion", "flops": 1000,
                           "bytes_accessed": 64}
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path / "plugins" / "none"))


# ----------------------------------------------------------- (c) the views
def test_the_three_views_print(tmp_path, capsys):
    where = _xspace(tmp_path)
    prof = profiler.Profiler(
        on_trace_ready=profiler.export_chrome_tracing(where))
    prof._taken = True              # the trace under `where` is hand-made
    prof.summary()
    out = capsys.readouterr().out
    assert "ModelView" in out and "OperatorView" in out and "KernelView" in out
    model = out.split("ModelView")[1].split("OperatorView")[0]
    assert re.search(r"^fwd +0\.560 +65\.88 ", model, re.M)
    assert re.search(r"^opt ", model, re.M) and re.search(r"^bwd ", model, re.M)
    assert "M/layers/*/mlp" in out and "jvp_flash_attention_bwd_dq_" in out
    assert "/repo/x.py:1" in out
    prof.summary(views=SummaryView.KernelView)
    out = capsys.readouterr().out
    assert "KernelView" in out and "ModelView" not in out
    # the same from the command line
    profiler.main([where, "--top", "3"])
    assert "OperatorView" in capsys.readouterr().out


@pytest.mark.parametrize("view", [SummaryView.DeviceView,
                                  SummaryView.OverView,
                                  SummaryView.DistributedView,
                                  SummaryView.MemoryView])
def test_a_view_that_is_not_implemented_raises(view, tmp_path):
    prof = profiler.Profiler(timer_only=True)
    with pytest.raises(NotImplementedError, match=view.name):
        prof.summary(views=[SummaryView.ModelView, view])
    with pytest.raises(NotImplementedError, match=view.name):
        profiler.ProfilerResult().summary(views=view)


def test_summary_without_a_trace_raises_and_errors_come_out(tmp_path):
    prof = profiler.Profiler(
        on_trace_ready=profiler.export_chrome_tracing(str(tmp_path / "a")))
    with pytest.raises(RuntimeError, match="no trace was taken"):
        prof.summary()
    # a failure to start the session is the caller's to see: a second
    # session while one is open
    other = profiler.Profiler(
        on_trace_ready=profiler.export_chrome_tracing(str(tmp_path / "b")))
    with prof:
        with pytest.raises(Exception):
            other.start()
        assert not other._active
    assert prof._taken and not other._taken


def test_profiler_takes_a_trace_and_reads_it_back(tmp_path, capsys):
    """On the CPU the trace has no ``/device:TPU`` plane: the views say so
    and print nothing invented."""
    where = str(tmp_path / "trace")
    f = jax.jit(lambda a: a * 2 + 1)
    f(jnp.ones(4))
    with profiler.Profiler(
            on_trace_ready=profiler.export_chrome_tracing(where, "w0")) as p:
        for _ in range(3):
            with profiler.RecordEvent("user_span"):
                jax.block_until_ready(f(jnp.ones(4)))
            p.step(num_samples=4)
    assert os.path.exists(os.path.join(where, "w0.steps.json"))
    assert "samples/s" in p.step_info()
    p.summary()
    out = capsys.readouterr().out
    assert "avg step time" in out and "no device operation" in out
    res = profiler.load_profiler_result(where)
    assert res.rows == [] and res.devices == 0


def test_scheduler_decides_when_a_trace_is_taken():
    ready = []
    prof = profiler.Profiler(scheduler=(1, 3), timer_only=True,
                             on_trace_ready=ready.append)
    prof.start()
    states = [prof.state]
    for _ in range(4):
        prof.step()
        states.append(prof.state)
    prof.stop()
    S = profiler.ProfilerState
    assert states == [S.CLOSED, S.RECORD, S.RECORD_AND_RETURN, S.CLOSED,
                      S.CLOSED]
    assert prof.state is S.CLOSED and ready == [prof]


# ------------------------------------------------- (d) the compile counters
def _compile_counters():
    counters = telemetry.runtime_registry_snapshot()["counters"]
    return {k: v for k, v in counters.items()
            if k.startswith("paddle_compile")}


def test_compile_counters_move_when_something_compiles():
    seen = []

    def listen(event, seconds, **kw):
        seen.append((event.rsplit("/", 1)[1], seconds))

    x = jnp.arange(12.0).reshape(3, 4)
    jax.block_until_ready(x)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # primitives only: a jitted library function inside would be traced
        # (and report) inside this function's trace
        f = jax.jit(lambda a: jax.lax.add(jax.lax.mul(a, a), a))
        before = _compile_counters()
        f(x)
        first = _compile_counters()
        events = list(seen)
        f(x)
        second = _compile_counters()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    # one trace, one lowering, one backend compile of the fresh function,
    # each counted with the seconds JAX reported for it
    for phase, event in (("trace", "jaxpr_trace_duration"),
                         ("lower", "jaxpr_to_mlir_module_duration"),
                         ("backend", "backend_compile_duration")):
        mine = [s for e, s in events if e == event]
        name = telemetry.compile_seconds_counter(phase)
        assert len(mine) == 1, events
        assert first[name] - before[name] == pytest.approx(mine[0])
        assert mine[0] > 0
    # and nothing on the second call: the listeners run when something
    # compiles and never in a steady step
    assert second == first and len(seen) == len(events)
    text = "\n".join(telemetry.runtime_prometheus())
    assert text.count("# TYPE paddle_compile_seconds_total counter") == 1
    assert 'paddle_compile_seconds_total{phase="trace"} ' in text
    assert "paddle_compile_cache_misses_total " in text


def test_nested_and_cached_phases_are_not_counted_twice(monkeypatch):
    """A function traced inside another's trace reports its seconds inside
    the outer's; the retrieval from the persistent cache is reported inside
    the backend's."""
    monkeypatch.setattr(telemetry, "_runtime_counters", {})
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    load = "/jax/compilation_cache/cache_retrieval_time_sec"
    jax.monitoring.record_scalar(trace, 0.0, fun_name="outer")
    jax.monitoring.record_scalar(trace, 0.0, fun_name="inner")
    jax.monitoring.record_event_duration_secs(trace, 2.0, fun_name="inner")
    jax.monitoring.record_event_duration_secs(trace, 5.0, fun_name="outer")
    jax.monitoring.record_scalar(backend, 0.0)
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(load, 3.0)
    jax.monitoring.record_event_duration_secs(backend, 3.5)
    jax.monitoring.record_scalar(backend, 0.0)
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event_duration_secs(backend, 7.0)
    got = _compile_counters()
    assert got[telemetry.compile_seconds_counter("trace")] == 5.0
    assert got[telemetry.compile_seconds_counter("cache_load")] == 3.0
    assert got[telemetry.compile_seconds_counter("backend")] == 7.5
    assert got["paddle_compile_cache_hits_total"] == 1
    assert got["paddle_compile_cache_misses_total"] == 1
