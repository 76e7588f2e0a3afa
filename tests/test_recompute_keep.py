"""``fleet.utils.recompute`` keeps what a Pallas kernel's forward wrote and
replays the rest (interpret mode on the CPU): a region gives the same bits
with the store as without it and traces the kernel's forward once and not
twice; nested regions and ``recompute_sequential``; a replay that takes
another path raises; on a mesh the flash forward is computed again; outside
a region, and with composites alone, nothing is kept and nothing counted."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.utils import recompute_mod
from paddle_tpu.distributed.fleet.utils.recompute_mod import (
    recompute, recompute_sequential)
from paddle_tpu.inference.telemetry import runtime_counter
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.tensor.tensor import RecomputeKeepError


@pytest.fixture
def kernels_on(monkeypatch):
    monkeypatch.setattr(pallas, "_enabled", lambda: True)


class _NoStore:
    """A region that is never opened: ``recompute`` as it was."""

    def __init__(self, name):
        pass

    def forward(self):
        return contextlib.nullcontext()

    replay = forward


def _counters():
    return tuple(runtime_counter(f"paddle_recompute_{which}_total")
                 for which in ("kept", "replayed"))


def _moved(before):
    return tuple(b - a for a, b in zip(before, _counters()))


def _tensors(arrays):
    return [paddle.to_tensor(a, stop_gradient=False) for a in arrays]


def _step(body, arrays):
    """``body`` in a region, a square-sum loss, one backward pass: the
    region's output and every input's gradient, as arrays."""
    inputs = _tensors(arrays)
    out = recompute(body, *inputs)
    (out * out).sum().backward()
    return (out._data,) + tuple(t.grad._data for t in inputs)


def _pallas_calls(jaxpr, name):
    """The ``pallas_call`` equations called ``name``, sub-programs too."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found += eqn.params["name"] == name
            continue                    # the kernel's own body holds none
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub, name)
    return found


def _kernel_calls(body, arrays, name):
    return _pallas_calls(
        jax.make_jaxpr(lambda *a: _step(body, a))(*arrays).jaxpr, name)


def _with_and_without(body, arrays, name, monkeypatch):
    """The region with the store and without it: bit-equal results, the
    kernel's forward once and twice in the traced forward + backward."""
    paddle.seed(11)
    kept = _step(body, arrays)
    once = _kernel_calls(body, arrays, name)
    monkeypatch.setattr(recompute_mod, "KeptRegion", _NoStore)
    paddle.seed(11)
    plain = _step(body, arrays)
    twice = _kernel_calls(body, arrays, name)
    for got, want in zip(kept, plain):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (once, twice) == (1, 2)


def _qkv(seq=64, heads=4, kv_heads=2, d=32):
    rng = np.random.default_rng(seq + heads)
    return [rng.standard_normal((2, seq, n, d)).astype(np.float32)
            for n in (heads, kv_heads, kv_heads)]


# ------------------------------------------------------- (a) flash attention
@pytest.mark.parametrize("kwargs", [
    dict(is_causal=True),
    dict(),
    dict(structured_mask=fa.block_diffusion_mask(32, 4)),
    dict(is_causal=True, dropout_p=0.25),
], ids=["causal", "unmasked", "block_diffusion", "dropout"])
def test_flash_forward_runs_once_a_region(kwargs, kernels_on, monkeypatch):
    def body(q, k, v):
        # work on both sides of the kernel, so that the replay has
        # something to replay and the kept ``o`` something to feed
        return F.scaled_dot_product_attention(q * 0.5, k, v, **kwargs) * 3.0
    before = _counters()
    _with_and_without(body, _qkv(), "flash_attention_fwd", monkeypatch)
    # the eager step and the traced one each took one entry; nothing was
    # computed again, with the store or without it
    assert _moved(before) == (2, 0)


def test_flash_keeps_lse_without_its_padded_axis(kernels_on, monkeypatch):
    """What the region holds for one attention: ``o`` as the kernel wrote
    it and ``lse`` as [B, H, S], not the kernel's [B, H, S, 1] that HBM
    pads to 128 lanes."""
    held = []

    class Spy(recompute_mod.KeptRegion):
        def __init__(self, name):
            super().__init__(name)
            held.append(self)

    monkeypatch.setattr(recompute_mod, "KeptRegion", Spy)
    _step(lambda q, k, v: F.scaled_dot_product_attention(
        q, k, v, is_causal=True), _qkv(seq=64, heads=4, kv_heads=2))
    (label, _, (o, lse)), = held[0].entries
    assert label == "flash_attention_fwd"
    assert (o.shape, lse.shape, lse.dtype) == (
        (2, 4, 64, 32), (2, 4, 64), jnp.float32)


# --------------------------------------------------- (b) the gated delta rule
def _rule_inputs(seq=128, hk=1, hv=2, d=128):
    rng = np.random.default_rng(seq)
    q, k = (rng.standard_normal((1, seq, hk, d)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((1, seq, hv, d)).astype(np.float32)
    g = -np.exp(rng.uniform(-4, 1, (1, seq, hv))).astype(np.float32)
    beta = rng.uniform(0, 1, (1, seq, hv)).astype(np.float32)
    return [q, k, v, g, beta]


def test_delta_rule_forward_runs_once_a_region(kernels_on, monkeypatch):
    def body(q, k, v, g, beta):
        return F.chunk_gated_delta_rule(q * 0.5, k, v, g, beta) * 3.0
    before = _counters()
    _with_and_without(body, _rule_inputs(), "gdn_chunk_rule_fwd",
                      monkeypatch)
    assert _moved(before) == (2, 0)


# --------------------------------- (c) nested regions, recompute_sequential
class _Attend(paddle.nn.Layer):
    """[B, S, 64] -> [B, S, 64]: a projection, flash attention over two
    heads of 32, a projection."""

    def __init__(self):
        super().__init__()
        self.qkv = paddle.nn.Linear(64, 192)
        self.out = paddle.nn.Linear(64, 64)

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        q, k, v = (t.reshape([b, s, 2, 32])
                   for t in paddle.split(self.qkv(x), 3, axis=-1))
        o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return x + self.out(o.reshape([b, s, 64]))


def _two_layers(seed=3):
    paddle.seed(seed)
    layers = [_Attend(), _Attend()]
    x = np.random.default_rng(seed).standard_normal(
        (2, 64, 64)).astype(np.float32)
    return layers, x


def _traced_grads(run, layers, x):
    """``run`` on ``x``, a square-sum loss, one backward pass: the output
    and the gradients of ``x`` and every parameter (traceable)."""
    xt = paddle.Tensor(x, stop_gradient=False)
    out = run(xt)
    (out * out).sum().backward()
    grads = [xt.grad._data] + [p.grad._data for l in layers
                               for p in l.parameters()]
    for l in layers:
        l.clear_gradients()
    return out._data, grads


def _grads(run, layers, x):
    out, grads = _traced_grads(run, layers, jnp.asarray(x))
    return np.asarray(out), [np.asarray(g) for g in grads]


def _equal(a, b):
    np.testing.assert_array_equal(a[0], b[0])
    assert len(a[1]) == len(b[1])
    for got, want in zip(a[1], b[1]):
        np.testing.assert_array_equal(got, want)


def test_nested_regions_take_from_the_outer_replay(kernels_on, monkeypatch):
    """An inner region's first forward that runs in the outer region's
    replay takes the outer region's entries and keeps them for its own
    replay: two attentions, two forward kernels, whatever the nesting."""
    layers, x = _two_layers()

    def nested(xt):
        return recompute(lambda t: recompute(layers[1], layers[0](t)), xt)

    before = _counters()
    got = _grads(nested, layers, x)
    # outer replay: 2 entries taken; the inner region's replay: 1
    assert _moved(before) == (3, 0)
    assert _pallas_calls(jax.make_jaxpr(
        lambda a: _traced_grads(nested, layers, a))(x).jaxpr,
        "flash_attention_fwd") == 2
    monkeypatch.setattr(recompute_mod, "KeptRegion", _NoStore)
    _equal(got, _grads(nested, layers, x))


def test_recompute_sequential_keeps_each_segments_own(kernels_on,
                                                      monkeypatch):
    layers, x = _two_layers(seed=4)

    def sequential(xt):
        return recompute_sequential({"segments": 2}, layers, xt)

    before = _counters()
    got = _grads(sequential, layers, x)
    assert _moved(before) == (2, 0)
    assert _pallas_calls(jax.make_jaxpr(
        lambda a: _traced_grads(sequential, layers, a))(x).jaxpr,
        "flash_attention_fwd") == 2
    monkeypatch.setattr(recompute_mod, "KeptRegion", _NoStore)
    _equal(got, _grads(sequential, layers, x))


def test_a_retained_region_replays_twice(kernels_on):
    """``backward(retain_graph=True)`` runs the region's replay again: the
    entries are taken from the start each time."""
    q, k, v = _tensors(_qkv())
    out = recompute(lambda q, k, v: F.scaled_dot_product_attention(
        q, k, v, is_causal=True), q, k, v)
    loss = (out * out).sum()
    loss.backward(retain_graph=True)
    first = np.asarray(q.grad._data)
    q.clear_grad()
    loss.backward()
    np.testing.assert_array_equal(np.asarray(q.grad._data), first)


# -------------------------------------------- (d) a replay on another path
@pytest.mark.parametrize("paths,says", [
    (({"is_causal": True}, {"is_causal": False}), "entry 0 was kept by"),
    (({"is_causal": True}, None), "not taken"),
    ((None, {"is_causal": True}), "the first forward kept 0"),
], ids=["another_call", "entry_left", "entry_missing"])
def test_a_replay_on_another_path_raises(paths, says, kernels_on):
    """The first forward and the replay disagree about what the region
    holds: an error that names the region and the entry, not a gradient
    from the wrong ``o``."""
    passes = iter(paths)

    def fickle(q, k, v):
        kwargs = next(passes)
        if kwargs is None:
            return q * 2.0
        return F.scaled_dot_product_attention(q, k, v, **kwargs)

    out = recompute(fickle, *_tensors(_qkv(heads=2)))
    with pytest.raises(RecomputeKeepError, match=says) as err:
        (out * out).sum().backward()
    assert "recompute(" in str(err.value) and "fickle" in str(err.value)
    assert "another path" in str(err.value)


# ------------------------------------------------------------ (e) on a mesh
def test_on_a_mesh_the_flash_forward_is_computed_again(kernels_on,
                                                       monkeypatch):
    """Two devices: the kernel runs per shard inside ``shard_map``, whose
    values another ``shard_map`` body cannot take, so the replay computes
    the forward again and says so."""
    import paddle_tpu.parallel as parallel

    def body(q, k, v):
        return F.scaled_dot_product_attention(q * 0.5, k, v,
                                              is_causal=True) * 3.0
    arrays = _qkv()
    alone = _step(body, arrays)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1, 1, 1),
                ("pp", "dp", "sharding", "sep", "mp"))
    monkeypatch.setattr(parallel, "current_mesh", lambda: mesh)
    before = _counters()
    sharded = _step(body, arrays)
    kept, replayed = _moved(before)
    assert kept == 0 and replayed >= 1
    assert _kernel_calls(body, arrays, "flash_attention_fwd") == 2
    for got, want in zip(sharded, alone):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------- (f) nothing to keep, nothing to count
def test_outside_a_region_nothing_is_kept(kernels_on):
    q, k, v = _tensors(_qkv())
    before = _counters()
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    (out * out).sum().backward()
    rule = _tensors(_rule_inputs())
    F.chunk_gated_delta_rule(*rule).sum().backward()
    assert q.grad is not None and rule[0].grad is not None
    assert _moved(before) == (0, 0)


def test_a_region_of_composites_keeps_nothing():
    """The kernels are off (the CPU's own choice): ``_sdpa_ref`` and
    ``_chunk_rule`` are replayed whole."""
    def body(q, k, v):
        return F.scaled_dot_product_attention(q, k, v, is_causal=True)
    before = _counters()
    _step(body, _qkv())
    _step(lambda *a: F.chunk_gated_delta_rule(*a, chunk_size=16),
          _rule_inputs(seq=64, d=16))
    assert _moved(before) == (0, 0)


# ----------------------------------------------------- (g) under to_static
def _train(static, steps=3):
    from paddle_tpu.models.llama import llama_tiny
    paddle.seed(21)
    model = llama_tiny(recompute=True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ids = paddle.to_tensor(np.random.default_rng(2).integers(
        0, 256, (2, 64)).astype(np.int64))

    def step(ids):
        loss = model(ids, labels=ids)
        loss = loss[0] if isinstance(loss, (tuple, list)) else loss
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    if static:
        step = paddle.jit.to_static(step)
    return [float(step(ids)) for _ in range(steps)]


def test_to_static_trains_as_eager_does(kernels_on):
    """Two layers, each a region with a flash attention in it: under
    ``to_static`` the kept values are tracers of the step's own trace."""
    before = _counters()
    eager = _train(False)
    assert _moved(before) == (6, 0)             # 2 layers x 3 steps
    static = _train(True)
    assert eager[-1] < eager[0]
    np.testing.assert_allclose(static, eager, rtol=2e-6)


# ------------------------------------------- the benchmark's reader of both
def test_replay_kept_pct_reads_the_two_counters(monkeypatch):
    """``benchmark/layer_metrics/replay_kept_pct.py``: nothing where neither
    counter moved (a program from before them: the parent commit), else the
    share of the replayed kernel forwards that came from the store."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    from benchmark import harness
    from paddle_tpu.inference import telemetry
    reader = harness.load_part("layer_metrics", "replay_kept_pct")
    monkeypatch.setattr(telemetry, "_runtime_counters", {})
    assert reader.read({}) is None
    telemetry.runtime_counter("paddle_recompute_kept_total", 6)
    assert reader.read({}) == 100.0
    telemetry.runtime_counter("paddle_recompute_replayed_total", 2)
    assert reader.read({}) == 75.0
