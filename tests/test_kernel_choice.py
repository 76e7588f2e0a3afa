"""The training path picks its kernels from what it can observe (backend,
shapes, dtype, mask, mesh), in ``paddle_tpu/ops/pallas/``, and from nothing
the shell exported. Also holds the XLA composites that took over from the
deleted opt-in kernels (``ops/pallas/layer_norm.py``, ``fused_ffn.py``) to
float64 NumPy references, forward and gradients, at those kernels' test
shapes.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture
def kernels_on(monkeypatch):
    """The one gate, flipped: the functionals take the kernels (interpreted)
    on the CPU, as they do on a TPU."""
    monkeypatch.setattr(pallas, "_enabled", lambda: True, raising=False)


@pytest.fixture
def flash_calls(monkeypatch, kernels_on):
    calls = []
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: (
        calls.append(a[0].shape), real(*a, **kw))[1])
    return calls


# ------------------------------------------------- no lever in the trace
def _gpt2_tiny():
    from paddle_tpu.models.gpt import gpt2_tiny
    model = gpt2_tiny()
    rng = np.random.RandomState(0)
    return model, rng.randint(0, 1024, (2, 128)).astype(np.int64)


def _qwen3next_tiny():
    from paddle_tpu.models.qwen3_next import qwen3_next_tiny
    model = qwen3_next_tiny(vocab_size=128, experts_held=[0, 1, 2, 3],
                            recompute=True)
    rng = np.random.RandomState(0)
    return model, rng.randint(0, 128, (2, 64)).astype(np.int64)


def _qwen3next_heads_of_128():
    """The tiny model with the Gated DeltaNet heads at the published 128
    and chunks of 64: what the delta rule's kernel takes."""
    from paddle_tpu.models.qwen3_next import qwen3_next_tiny
    model = qwen3_next_tiny(vocab_size=128, experts_held=[0, 1, 2, 3],
                            recompute=True, linear_num_key_heads=1,
                            linear_num_value_heads=2, linear_key_head_dim=128,
                            linear_value_head_dim=128, chunk_size=64)
    rng = np.random.RandomState(0)
    return model, rng.randint(0, 128, (2, 64)).astype(np.int64)


def _rule_traces():
    from paddle_tpu.inference.telemetry import runtime_counter
    return tuple(runtime_counter(f"paddle_gdn_rule_{which}_traces_total")
                 for which in ("kernel", "composite"))


@pytest.mark.parametrize("build,rule,calls", [
    (_gpt2_tiny, (False, False), 3), (_qwen3next_tiny, (False, True), 3),
    (_qwen3next_heads_of_128, (True, False), 1)],
    ids=["gpt2_tiny", "qwen3next_tiny", "qwen3next_rule_kernel"])
def test_training_trace_reads_no_kernel_env(build, rule, calls, monkeypatch,
                                            flash_calls):
    """Both cells' families, the step under ``to_static`` as the benchmark
    runs it: while it is traced (twice: the optimizer's slots appear in the
    first call) no ``PADDLE_TPU_*`` variable is read but the two named
    debts (ROADMAP.md D11). ``rule``: whether the traces took the delta
    rule's kernel, and its composite (heads of 16 in chunks of 16 are not
    the kernel's; GPT-2 has no such layer). The third case differs from the
    second in the rule's sizes alone, which the first trace shows: it is
    not traced again with the optimizer's slots."""
    before = _rule_traces()
    paddle.seed(5)
    model, ids = build()
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    step = paddle.jit.to_static(step)
    x = paddle.to_tensor(ids)
    y = paddle.to_tensor(np.roll(ids, -1, axis=1))

    read = []
    environ = type(os.environ)
    getitem = environ.__getitem__
    with monkeypatch.context() as spy:     # every get() and [] ends here
        spy.setattr(environ, "__getitem__", lambda self, key: (
            read.append(key), getitem(self, key))[1])
        losses = [float(np.asarray(step(x, y)._data, np.float32))
                  for _ in range(calls)]

    assert all(math.isfinite(v) for v in losses)
    assert flash_calls, "the flash kernel was not in the traced step"
    levers = {k for k in read if k.startswith("PADDLE_TPU_")}
    assert levers <= {"PADDLE_TPU_PRNG_IMPL", "PADDLE_TPU_FUSE_EAGER_STEP"}
    assert tuple(b > a for a, b in zip(before, _rule_traces())) == rule


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("path", [
    "paddle_tpu/ops/pallas", "paddle_tpu/nn/functional/attention.py",
    "paddle_tpu/nn/functional/norm.py", "paddle_tpu/models/gpt.py",
    "paddle_tpu/incubate/autotune"])
def test_the_choosers_never_touch_the_environment(path):
    """What the trace test sees at run time, held in the sources too: the
    modules that choose a kernel, a tile or a backward on the measured path
    do not mention ``os.environ`` (or ``getenv``) at all."""
    full = os.path.join(REPO, path)
    files = [full] if full.endswith(".py") else [
        os.path.join(d, f) for d, _, fs in os.walk(full) for f in fs
        if f.endswith(".py")]
    assert files
    for f in files:
        text = open(f).read()
        assert "environ" not in text and "getenv" not in text, f


def test_autotune_set_config_writes_no_environment(monkeypatch):
    from paddle_tpu.incubate import autotune
    monkeypatch.setattr(autotune, "_config", autotune.get_config())
    before = dict(os.environ)
    autotune.set_config({"kernel": {"enable": True,
                                    "tuning_range": [64, 64]}})
    assert autotune.get_config()["kernel"] == {"enable": True,
                                               "tuning_range": [64, 64]}
    assert dict(os.environ) == before
    assert fa._block_sizes(1024, 1024, 64) == (1024, 1024)
    autotune.set_config(None)
    assert dict(os.environ) == before


@pytest.mark.parametrize("on", [True, False], ids=["gate_on", "gate_off"])
def test_decode_attn_reads_the_one_gate(on, monkeypatch):
    """``_enabled()``'s other reader: the eager decode step takes the Pallas
    flash-decode kernel over the whole static cache when the gate is on and
    dense attention over the valid prefix when it is off; either way new
    token r attends the history and the new tokens up to itself."""
    from paddle_tpu.incubate.nn.functional import _decode_attn
    from paddle_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(pallas, "_enabled", lambda: on)
    calls = []
    real = da.decode_attention_bhsd
    monkeypatch.setattr(da, "decode_attention_bhsd", lambda *a, **kw: (
        calls.append(a[0].shape), real(*a, **kw))[1])
    rng = np.random.RandomState(4)
    b, h, d, smax, ts, s = 2, 4, 32, 256, 17, 3
    q = rng.randn(b, s, h, d).astype(np.float32)
    cache = rng.randn(2, b, h, smax, d).astype(np.float32)
    out = _decode_attn(paddle.to_tensor(q), paddle.to_tensor(cache), ts, s,
                       None)
    assert bool(calls) is on
    q64, kc, vc = (a.astype(np.float64) for a in (q, cache[0], cache[1]))
    want = np.empty((b, s, h, d))
    for r in range(s):
        n = ts + r + 1
        logits = np.einsum("bhd,bhkd->bhk", q64[:, r], kc[:, :, :n]) \
            * d ** -0.5
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want[:, r] = np.einsum("bhk,bhkd->bhd", p, vc[:, :, :n])
    _close(out, want, tol=1e-5)


# ------------------------------------------------ tiles from shapes alone
@pytest.mark.parametrize("sq,sk,d,tiles,exported", [
    (1024, 1024, 64, (1024, 1024), False),   # gpt2_124m.pretrain
    (8192, 8192, 256, (512, 512), False),    # qwen3next_80b.pretrain_8k
    (4096, 4096, 64, (1024, 1024), False),
    (1536, 1536, 64, (512, 512), False),     # not 1024: no 78% padding
    (100, 200, 64, (128, 256), False),       # under the cap: next 2^n
    (8192, 8192, 128, (1024, 1024), False),  # head 128 keeps 1024
    (1024, 1024, 64, (1024, 1024), True),    # the shell changes nothing
])
def test_flash_tiles_follow_from_shape(sq, sk, d, tiles, exported,
                                       monkeypatch):
    if exported:
        # the deleted tile levers, spelt in two halves so that a grep for
        # them over the tree finds no reader and no writer
        for side in ("BQ", "BK"):
            monkeypatch.setenv("PADDLE_TPU_" + "FLASH_" + side, "64")
    assert fa._block_sizes(sq, sk, d) == tiles


# ---------------------------------------- one question, asked in one place
def _pp2_mesh():
    return Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1, 1, 1),
                ("pp", "dp", "sharding", "sep", "mp"))


@pytest.mark.parametrize("q,k,mask,mesh,taken", [
    ((2, 16, 4, 32), (2, 16, 4, 32), False, None, True),
    ((2, 16, 4, 32), (2, 16, 4, 32), True, None, False),
    ((2, 16, 3, 32), (2, 16, 2, 32), False, None, False),
    ((1, 16, 2, 320), (1, 16, 2, 320), False, None, False),
    ((2, 16, 4, 32), (2, 16, 4, 32), False, _pp2_mesh, False),
], ids=["supported", "mask", "heads_no_multiple", "head_dim_past_256",
        "pp_mesh"])
def test_sdpa_takes_the_kernel_when_it_can(q, k, mask, mesh, taken,
                                           monkeypatch, flash_calls):
    if mesh is not None:
        import paddle_tpu.parallel as parallel
        held = mesh()
        monkeypatch.setattr(parallel, "current_mesh", lambda: held)
    rng = np.random.RandomState(1)
    qt, kt, vt = (paddle.to_tensor(rng.randn(*s).astype(np.float32))
                  for s in (q, k, k))
    m = paddle.to_tensor(np.zeros((q[1], k[1]), np.float32)) if mask \
        else None
    if q[2] != k[2] and not taken:
        # the composite serves grouped heads only when they divide
        with pytest.raises(Exception):
            F.scaled_dot_product_attention(qt, kt, vt, attn_mask=m,
                                           is_causal=True)
    else:
        out = F.scaled_dot_product_attention(qt, kt, vt, attn_mask=m,
                                             is_causal=True)
        assert tuple(out.shape) == q
    assert bool(flash_calls) is taken


# --------------------------------- the delta rule: kernel or composite
_CELL_Q, _CELL_V = (2, 8192, 16, 128), (2, 8192, 32, 128)


@pytest.mark.parametrize("on,q,v,chunk,dtype,mm,mesh,taken", [
    (True, _CELL_Q, _CELL_V, 64, "float32", "bfloat16", None, True),
    (True, (2, 100, 1, 128), (2, 100, 1, 128), 64, "bfloat16", "float32",
     None, True),
    (False, _CELL_Q, _CELL_V, 64, "float32", "bfloat16", None, False),
    (True, _CELL_Q, _CELL_V, 16, "float32", "bfloat16", None, False),
    (True, (2, 64, 2, 16), (2, 64, 4, 128), 64, "float32", "float32", None,
     False),
    (True, (2, 64, 2, 128), (2, 64, 4, 8), 64, "float32", "float32", None,
     False),
    (True, (2, 64, 2, 128), (2, 64, 3, 128), 64, "float32", "float32", None,
     False),
    (True, (2, 64, 2, 512), (2, 64, 4, 512), 64, "float32", "float32", None,
     False),
    (True, _CELL_Q, _CELL_V, 64, "float16", "float16", None, False),
    (True, _CELL_Q, _CELL_V, 64, "float32", "bfloat16", _pp2_mesh, False),
], ids=["the_cell_on_a_tpu", "one_head_any_length", "backend_off",
        "chunk_16", "d_k_16", "d_v_8", "heads_no_multiple",
        "tiles_past_vmem", "float16", "pp_mesh"])
def test_delta_rule_kernel_is_chosen_from_shapes(on, q, v, chunk, dtype, mm,
                                                 mesh, taken, monkeypatch):
    from paddle_tpu.ops.pallas import gated_delta_rule as gdr
    monkeypatch.setattr(pallas, "_enabled", lambda: on)
    assert gdr.is_supported(q, v, chunk, [jnp.dtype(dtype)] * 5,
                            jnp.dtype(mm), mesh and mesh(), 16) is taken


@pytest.mark.parametrize("on,dk,chunk,mesh,kernel", [
    (True, 128, 64, None, True), (False, 128, 64, None, False),
    (True, 16, 64, None, False), (True, 128, 16, None, False),
    (True, 128, 64, _pp2_mesh, False),
], ids=["kernel", "backend_off", "d_k_16", "chunk_16", "pp_mesh"])
def test_delta_rule_dispatch_moves_its_counter(on, dk, chunk, mesh, kernel,
                                               monkeypatch):
    """``chunk_gated_delta_rule`` asks ``is_supported`` and nothing else;
    whichever it takes, the result is the rule's and one of the two
    counters moves. The one decision covers both passes: under a gradient
    the kernel's shapes trace the backward kernel and move its counter,
    the composite's shapes leave both alone."""
    from paddle_tpu.inference.telemetry import runtime_counter
    from paddle_tpu.nn.functional import linear_attention as la
    from paddle_tpu.ops.pallas import gated_delta_rule as gdr
    monkeypatch.setattr(pallas, "_enabled", lambda: on)
    if mesh is not None:
        held = mesh()
        monkeypatch.setattr(la, "current_mesh", lambda: held)
    calls = {"fwd": [], "bwd": []}
    for which in calls:
        real = getattr(gdr, f"gdn_chunk_rule_{which}")
        monkeypatch.setattr(
            gdr, f"gdn_chunk_rule_{which}",
            lambda *a, _real=real, _seen=calls[which], **kw: (
                _seen.append(a[0].shape), _real(*a, **kw))[1])
    rng = np.random.RandomState(2)
    q, k = (rng.randn(1, 64, 1, dk).astype(np.float32) for _ in range(2))
    v = rng.randn(1, 64, 2, 128).astype(np.float32)
    g = -rng.uniform(0.01, 1.0, (1, 64, 2)).astype(np.float32)
    beta = rng.uniform(0, 1, (1, 64, 2)).astype(np.float32)
    arrays = (q, k, v, g, beta)
    before = _rule_traces()
    backward = runtime_counter("paddle_gdn_rule_bwd_kernel_traces_total")
    ts = [paddle.to_tensor(a) for a in arrays]
    for t in ts:
        t.stop_gradient = False
    out = F.chunk_gated_delta_rule(*ts, chunk_size=chunk)
    moved = tuple(b - a for a, b in zip(before, _rule_traces()))
    assert moved == ((1, 0) if kernel else (0, 1))
    assert bool(calls["fwd"]) is kernel
    want, vjp = jax.vjp(lambda *a: la._chunk_rule(
        *a, chunk=chunk, mm=jnp.float32), *map(jnp.asarray, arrays))
    np.testing.assert_allclose(np.asarray(out._data), want, atol=2e-6)
    out.sum().backward()
    assert (runtime_counter("paddle_gdn_rule_bwd_kernel_traces_total")
            - backward) == int(kernel)
    assert bool(calls["bwd"]) is kernel
    for t, wg in zip(ts, vjp(jnp.ones_like(want))):
        np.testing.assert_allclose(np.asarray(t.grad._data), wg,
                                   atol=2e-5 * max(1.0, np.abs(wg).max()))


# -------------------------------------------- the backward by block count
@pytest.mark.parametrize("seq,kernels,moved", [
    (1024, {"flash_attention_fwd", "flash_attention_bwd_fused"}, [0, 0]),
    (2048, {"flash_attention_fwd", "flash_attention_bwd_onepass"}, [1, 0]),
    # 32,768 positions of head 64 (a row of 128 lanes in VMEM) are the
    # budget, ``_ONEPASS_DQ_BYTES``: the plane's float32 dQ at its edge...
    (32768, {"flash_attention_fwd", "flash_attention_bwd_onepass"}, [1, 0]),
    # ... and past it, where the pair remains
    (32768 + 1024, {"flash_attention_fwd", "flash_attention_bwd_dkv",
                    "flash_attention_bwd_dq"}, [0, 1]),
], ids=["one_block", "two_blocks", "at_the_vmem_budget",
        "past_the_vmem_budget"])
def test_backward_by_block_count(seq, kernels, moved):
    """One tile a plane: the fused kernel. Several: ONE kernel while the
    plane's dQ fits the stated VMEM budget, the dK/dV + dQ pair past it;
    from the shapes alone, and the two counters say which."""
    from paddle_tpu.inference.telemetry import runtime_counter
    names = [f"paddle_flash_bwd_{n}_traces_total" for n in ("onepass",
                                                            "split")]
    before = [runtime_counter(n) for n in names]
    q = jax.ShapeDtypeStruct((1, seq, 1, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, causal=True)),
        (0, 1, 2)))(q, q, q)
    assert set(re.findall(r"flash_attention_\w+", str(jaxpr))) == kernels
    assert [runtime_counter(n) - b for n, b in zip(names, before)] == moved
    assert fa._ONEPASS_DQ_BYTES == 32768 * 128 * 4


def test_flash_bwd_onepass_pct_reads_the_two_counters(monkeypatch):
    """``benchmark/layer_metrics/flash_bwd_onepass_pct.py``: nothing where
    neither counter moved (one tile a plane; a program from before them, as
    the parent commit is), else the share of the multi-tile backward traces
    that took the one kernel; both are in ``runtime_prometheus()``; the
    manifest names the reader once for each cell that runs the kernel."""
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    from benchmark import harness
    from paddle_tpu.inference import telemetry
    reader = harness.load_part("layer_metrics", "flash_bwd_onepass_pct")
    monkeypatch.setattr(telemetry, "_runtime_counters", {})
    assert reader.read({}) is None
    def trace(seq):     # a shape of its own each: JAX keeps a trace it made
        q = jax.ShapeDtypeStruct((1, seq, 1, 64), jnp.float32)
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=True)), (0, 1, 2)))(q, q, q)
    trace(2048)
    assert reader.read({}) == 100.0
    monkeypatch.setattr(fa, "_ONEPASS_DQ_BYTES", 0)
    trace(3072)
    trace(5120)
    assert reader.read({}) == pytest.approx(100.0 / 3)
    lines = telemetry.runtime_prometheus()
    assert "paddle_flash_bwd_onepass_traces_total 1" in lines
    assert "paddle_flash_bwd_split_traces_total 2" in lines
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"].startswith("flash_bwd_onepass_pct.")]
    assert [(m["name"].split(".")[1], m["workloads"], m["layer"],
             m["moves"]) for m in entries] == [
        ("sdar", ["sdar_30b.blockdiff_8k"], "kernels", "train_tok_s"),
        ("qwen3next", ["qwen3next_80b.pretrain_8k"], "kernels",
         "train_tok_s")]


# ------------------------- the composites that took over, against float64
def _t(a, grad=True):
    return paddle.to_tensor(np.asarray(a, np.float32),
                            stop_gradient=not grad)


def _close(got, want, tol=2e-4):
    got = np.asarray(got._data if hasattr(got, "_data") else got, np.float64)
    np.testing.assert_allclose(got, want, atol=tol * max(
        1.0, float(np.abs(want).max())), rtol=0)


def _ln64(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    rstd = 1.0 / np.sqrt(x.var(-1, keepdims=True) + eps)
    xh = (x - mu) * rstd
    return xh * g + b, xh, rstd


def _ln64_bwd(dy, xh, rstd, g):
    dxh = dy * g
    dx = rstd * (dxh - dxh.mean(-1, keepdims=True)
                 - xh * (dxh * xh).mean(-1, keepdims=True))
    lead = tuple(range(dy.ndim - 1))
    return dx, (dy * xh).sum(lead), dy.sum(lead)


@pytest.mark.parametrize("n,d", [(256, 512), (64, 768), (40, 384)])
def test_layer_norm_composite_vs_float64(n, d):
    rng = np.random.RandomState(0)
    x, g, b, r = (rng.randn(*s) for s in ((n, d), (d,), (d,), (n, d)))
    xt, gt, bt = _t(x), _t(g), _t(b)
    y = F.layer_norm(xt, d, gt, bt, 1e-5)
    (y * _t(r, False)).sum().backward()
    x, g, b, r = (a.astype(np.float32).astype(np.float64)
                  for a in (x, g, b, r))
    want, xh, rstd = _ln64(x, g, b, 1e-5)
    _close(y, want)
    for got, ref in zip((xt.grad, gt.grad, bt.grad),
                        _ln64_bwd(r, xh, rstd, g)):
        _close(got, ref)


def test_rms_norm_composite_vs_float64():
    rng = np.random.RandomState(0)
    n, d, eps = 128, 512, 1e-6
    x, g, r = (rng.randn(*s).astype(np.float32).astype(np.float64)
               for s in ((n, d), (d,), (n, d)))
    xt, gt = _t(x), _t(g)
    y = F.rms_norm(xt, gt, eps)
    (y * _t(r, False)).sum().backward()
    rrms = 1.0 / np.sqrt((x * x).mean(-1, keepdims=True) + eps)
    _close(y, x * rrms * g)
    dxh = r * g
    _close(xt.grad, rrms * dxh
           - x * rrms ** 3 * (dxh * x).mean(-1, keepdims=True))
    _close(gt.grad, (r * x * rrms).sum(0))


def _gelu64(h, exact):
    if exact:
        cdf = 0.5 * (1.0 + np.vectorize(math.erf)(h / math.sqrt(2.0)))
        pdf = np.exp(-0.5 * h * h) / math.sqrt(2.0 * math.pi)
        return h * cdf, cdf + h * pdf
    c = math.sqrt(2.0 / math.pi)
    t = np.tanh(c * (h + 0.044715 * h ** 3))
    return 0.5 * h * (1.0 + t), 0.5 * (1.0 + t) + \
        0.5 * h * (1.0 - t * t) * c * (1.0 + 3 * 0.044715 * h * h)


def _ffn64(x, w1, b1, w2, b2, dy, exact):
    """out = gelu(x @ w1 + b1) @ w2 + b2 and the gradients of
    sum(out * dy), over any leading dimensions."""
    h = x @ w1 + b1
    a, da_dh = _gelu64(h, exact)
    dh = (dy @ w2.T) * da_dh
    lead = tuple(range(x.ndim - 1))
    flat = lambda z: z.reshape(-1, z.shape[-1])
    return a @ w2 + b2, dict(
        x=dh @ w1.T, w1=flat(x).T @ flat(dh), b1=dh.sum(lead),
        w2=flat(a).T @ flat(dy), b2=dy.sum(lead))


def _ffn_case(lead, k, f, seed):
    rng = np.random.RandomState(seed)
    shapes = dict(x=lead + (k,), w1=(k, f), b1=(f,), w2=(f, k), b2=(k,),
                  dy=lead + (k,))
    scale = dict(w1=0.05, b1=0.1, w2=0.05, b2=0.1)
    return {n: (rng.randn(*s) * scale.get(n, 1.0))
            .astype(np.float32).astype(np.float64)
            for n, s in shapes.items()}


@pytest.mark.parametrize("lead,k,f", [
    ((64,), 128, 256),          # the deleted kernel's base shape
    ((16,), 128, 2816),         # the LLaMA width, no multiple of 512
    ((2, 3, 8), 128, 256),      # batched leading dimensions
], ids=["tanh_gelu", "llama_width", "batched"])
def test_gpt_mlp_vs_float64(lead, k, f):
    """models/gpt.py::GPTMLP (tanh GELU), forward and every gradient."""
    from paddle_tpu.models.gpt import GPTConfig, GPTMLP
    c = _ffn_case(lead, k, f, seed=3)
    mlp = GPTMLP(GPTConfig(hidden_size=k, intermediate_size=f,
                           num_layers=1, num_heads=1))
    for p, n in ((mlp.fc1.weight, "w1"), (mlp.fc1.bias, "b1"),
                 (mlp.fc2.weight, "w2"), (mlp.fc2.bias, "b2")):
        p._data = jnp.asarray(c[n], jnp.float32)
    x = _t(c["x"])
    out = mlp(x)
    (out * _t(c["dy"], False)).sum().backward()
    want, grads = _ffn64(c["x"], c["w1"], c["b1"], c["w2"], c["b2"],
                         c["dy"], exact=False)
    _close(out, want)
    for got, n in ((x.grad, "x"), (mlp.fc1.weight.grad, "w1"),
                   (mlp.fc1.bias.grad, "b1"), (mlp.fc2.weight.grad, "w2"),
                   (mlp.fc2.bias.grad, "b2")):
        _close(got, grads[n])


def test_fused_feedforward_exact_gelu_vs_float64():
    """incubate ``fused_feedforward``: pre-norm, exact (erf) GELU, inert
    dropouts, the residual; forward and every gradient."""
    from paddle_tpu.incubate.nn.functional import fused_feedforward
    k, f = 128, 256
    c = _ffn_case((2, 16), k, f, seed=5)
    rng = np.random.RandomState(6)
    g, b = (rng.randn(k).astype(np.float32).astype(np.float64)
            for _ in range(2))
    t = {n: _t(c[n]) for n in ("x", "w1", "b1", "w2", "b2")}
    gt, bt = _t(g), _t(b)
    out = fused_feedforward(t["x"], t["w1"], t["w2"], t["b1"], t["b2"],
                            ln1_scale=gt, ln1_bias=bt, dropout1_rate=0.0,
                            dropout2_rate=0.0, activation="gelu",
                            pre_layer_norm=True)
    (out * _t(c["dy"], False)).sum().backward()
    xn, xh, rstd = _ln64(c["x"], g, b, 1e-5)
    want, grads = _ffn64(xn, c["w1"], c["b1"], c["w2"], c["b2"], c["dy"],
                         exact=True)
    dx, dg, db = _ln64_bwd(grads["x"], xh, rstd, g)
    _close(out, c["x"] + want)
    _close(t["x"].grad, c["dy"] + dx)
    for got, ref in ((gt.grad, dg), (bt.grad, db),
                     (t["w1"].grad, grads["w1"]), (t["b1"].grad, grads["b1"]),
                     (t["w2"].grad, grads["w2"]), (t["b2"].grad, grads["b2"])):
        _close(got, ref)
