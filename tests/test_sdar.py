"""SDAR block-diffusion training on the CPU at a small size (hidden 64, two
layers, 4 query / 2 KV heads of 32, 8 experts top-2, vocabulary 256, blocks
of 4): the program against the plain float32 reference
(``benchmark/reference/sdar.py``, which shares no code with it) on seeded
weights and ONE shared draw of the noise; the expert layer's shares against
the uncut layer; the rule leaks nothing forward; the first loss's mean and
spread; the compiled training step and its counters."""
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle                                     # noqa: E402
from benchmark.models import sdar_train as family               # noqa: E402
from benchmark.reference import sdar as ref                     # noqa: E402
from benchmark.runners import train_blockdiff                   # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (        # noqa: E402
    DroplessMoELayer)
from paddle_tpu.inference import telemetry                      # noqa: E402
from paddle_tpu.models.sdar import noise_stats, sdar_tiny       # noqa: E402

VOCAB, BATCH, SEQ, BLOCK = 256, 2, 40, 4
CFG = {"block_length": BLOCK, "noise_eps": 0.05}


def _tokens(seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (BATCH, SEQ),
                                                dtype=np.int32)


def _reference(model, x, masked, t):
    """(noisy-half logits [B, L, V], mean weighted loss, gradients as the
    reference's tree)."""
    w = family.reference_weights(model)
    leaves, tree = jax.tree_util.tree_flatten(w)
    real = [i for i, a in enumerate(leaves)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)]

    def loss(values):
        full = list(leaves)
        for i, v in zip(real, values):
            full[i] = v
        w_ = jax.tree_util.tree_unflatten(tree, full)
        return jnp.mean(jnp.stack([ref.loss(w_, x[i], masked[i], t[i])
                                   for i in range(x.shape[0])]))
    values = [jnp.asarray(leaves[i], jnp.float32) for i in real]
    value, grads = jax.value_and_grad(loss)(values)
    full = [None] * len(leaves)
    for i, g in zip(real, grads):
        full[i] = g
    logits = np.stack([np.asarray(ref.logits(w, x[i], masked[i]))
                       for i in range(x.shape[0])])
    return logits, float(value), jax.tree_util.tree_unflatten(tree, full)


def _program(model, x, masked, t):
    """(loss, gradients arranged like the reference's weights, eval-mode
    logits of the noisy half): forward and backward as ONE compiled program,
    as the benchmark's step has them, and not some hundreds of
    one-operation compiles. ``reference_weights`` is a linear rearrangement,
    so it maps gradients as it maps weights."""
    params = list(model.parameters())

    def loss_and_grads(ids):
        loss = model(ids, masked, t, labels=ids)
        loss.backward()
        return loss, [p.grad for p in params]
    ids = paddle.to_tensor(x)
    loss, grads = paddle.jit.to_static(loss_and_grads)(ids)
    saved = [p._data for p in params]
    for p, g in zip(params, grads):
        p._data = g._data
    try:
        arranged = family.reference_weights(model)
    finally:
        for p, a in zip(params, saved):
            p._data = a
    model.eval()
    with paddle.no_grad():
        logits = paddle.jit.to_static(lambda ids: model(ids, masked, t))(ids)
    return (float(loss._data), arranged,
            np.asarray(logits._data, np.float32))


# The tolerances and their reasons are ``tests/test_qwen3_next.py``'s: float32
# against float32 differs by summation order alone; bf16 rounds every
# activation and FLIPS a token's last expert choice where two probabilities
# are closer than the rounding, so its gradients are held to 0.3 of their
# tensor's norm. The forward alone does not hold the backward (PR 29): the
# gradients of EVERY parameter are compared, the experts' in their parts.
@pytest.mark.parametrize("dtype,tol,grad_tol", [("float32", 1e-4, 1e-4),
                                                ("bfloat16", 0.05, 0.3)])
def test_logits_loss_and_gradients_match_the_reference(dtype, tol, grad_tol):
    paddle.seed(11)
    model = sdar_tiny(vocab_size=VOCAB, experts_held=[0, 1, 2, 5])
    if dtype == "bfloat16":
        model.bfloat16()
    x = _tokens()
    masked, t = train_blockdiff.noise(CFG, 5, BATCH, SEQ)
    want_logits, want_loss, want_grads = _reference(model, x, masked, t)
    got_loss, got_grads, got_logits = _program(model, x, masked, t)
    assert got_logits.shape == (BATCH, SEQ, VOCAB)      # the noisy half only
    scale = np.abs(want_logits).max()
    assert np.abs(got_logits - want_logits).max() <= tol * scale
    assert abs(got_loss - want_loss) <= tol * want_loss

    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    checked = 0
    for path, want in jax.tree_util.tree_leaves_with_path(want_grads):
        diff = np.asarray(flat_got[path], np.float32) - np.asarray(want)
        size = np.abs if dtype == "float32" else np.linalg.norm
        assert np.max(size(diff)) <= grad_tol * max(
            np.max(size(np.asarray(want))), 1e-6), jax.tree_util.keystr(path)
        checked += 1
    # every parameter, [gate|up] twice in each of the two layers
    assert checked == len(list(model.parameters())) + 2


def test_losses_reports_the_bound_and_the_masked_mean():
    paddle.seed(4)
    model = sdar_tiny(vocab_size=VOCAB)
    x = _tokens(3)
    masked, t = train_blockdiff.noise(CFG, 9, BATCH, SEQ)
    ids = paddle.to_tensor(x)
    with paddle.no_grad():      # each a compiled program, not eager
        bound, plain = paddle.jit.to_static(
            lambda ids: model.losses(ids, masked, t))(ids)
        again = paddle.jit.to_static(
            lambda ids: model(ids, masked, t, labels=ids))(ids)
    assert float(bound._data) == pytest.approx(float(again._data))
    w = family.reference_weights(model)
    ce = []
    for i in range(BATCH):
        logp = jax.nn.log_softmax(ref.logits(w, x[i], masked[i]))
        ce.append(-np.asarray(logp)[np.arange(SEQ), x[i]][masked[i]])
    assert float(plain._data) == pytest.approx(
        float(np.concatenate(ce).mean()), rel=1e-4)


def test_the_eight_ranks_parts_add_up_to_the_uncut_layer():
    """16 of 128 experts a rank, as the cell cuts them: the parts that the
    eight shares give add up to what the reference gives with all 128."""
    d, f, n, k = 32, 16, 128, 8
    paddle.seed(21)
    whole = DroplessMoELayer(d, f, n, k)
    x = np.random.default_rng(2).standard_normal((2, 24, d)).astype(
        np.float32)
    total = np.zeros_like(x)
    for rank in range(8):
        held = list(range(16 * rank, 16 * rank + 16))
        part = DroplessMoELayer(d, f, n, k, experts_held=held)
        part.router._data = whole.router._data
        part.experts_gate_up._data = whole.experts_gate_up._data[
            jnp.asarray(held)]
        part.experts_down._data = whole.experts_down._data[jnp.asarray(held)]
        total += np.asarray(part(paddle.to_tensor(x))._data)
    p = {"router": whole.router._data,
         "held": jnp.arange(n, dtype=jnp.int32),
         "w_gate": whole.experts_gate_up._data[:, :, :f],
         "w_up": whole.experts_gate_up._data[:, :, f:],
         "w_down": whole.experts_down._data}
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref.moe(jnp.asarray(x[i]), p, k))
                         for i in range(2)])
    np.testing.assert_allclose(total, want, atol=1e-5 * np.abs(want).max())
    # the uncut program layer too
    np.testing.assert_allclose(np.asarray(whole(paddle.to_tensor(x))._data),
                               want, atol=1e-5 * np.abs(want).max())


def test_the_rule_leaks_nothing_forward():
    """Changing a CLEAN token of block k leaves the logits of blocks <= k as
    they were and moves block k + 1's. (Its noisy copy is masked in both
    runs, so the noisy half's input does not change.)"""
    paddle.seed(6)
    model = sdar_tiny(vocab_size=VOCAB)
    model.eval()
    x = _tokens(1)[:1]
    k = 4
    i = k * BLOCK + 1
    masked = np.zeros((1, SEQ), bool)
    masked[0, i] = True
    masked[0, ::3] = True
    t = np.full((1, SEQ // BLOCK), 0.5, np.float32)
    other = x.copy()
    other[0, i] = (x[0, i] + 7) % (VOCAB - 1)
    forward = paddle.jit.to_static(lambda ids: model(ids, masked, t))
    with paddle.no_grad():      # one compiled program, called twice
        a = np.asarray(forward(paddle.to_tensor(x))._data)
        b = np.asarray(forward(paddle.to_tensor(other))._data)
    upto = (k + 1) * BLOCK
    np.testing.assert_array_equal(a[0, :upto], b[0, :upto])
    later = np.abs(a[0, upto:upto + BLOCK] - b[0, upto:upto + BLOCK]).max()
    assert later > 1e-4 * np.abs(a).max()


def test_the_first_loss_is_ln_vocabulary_within_its_spread():
    """The mean of the first loss over 20 seeds, at the CELL's number of
    blocks (2,048 of 4, here as 32 rows of 64 blocks: the loss is one mean
    over all of them): at initialisation every token's cross-entropy is
    ln V + half the logits' variance (64 x 0.02^2 / 2 = 0.013 here; 0.41 at
    the published width), the weights ``m / t`` have mean 1, and the bound
    spreads ``sqrt(E[(1 - t) / t] / L)`` = 1.62% around it at ``eps`` 0.05
    (``E[(1 - t) / t] = (ln(1 / eps) - (1 - eps)) / (1 - eps)`` = 2.15).
    The masked mean, which the benchmark's step reports, does not move with
    the draw."""
    rows, seq, eps = 32, 256, 0.05
    paddle.seed(1)
    model = sdar_tiny(vocab_size=VOCAB, num_layers=1, noise_eps=eps)
    model.eval()
    rng = np.random.default_rng(0)
    bounds, plains = [], []
    # one compiled program, called 20 times: the key is state of the step,
    # so every call draws its own noise from next_key()
    losses = paddle.jit.to_static(model.losses)
    with paddle.no_grad():
        for _ in range(20):
            ids = paddle.to_tensor(rng.integers(0, VOCAB, (rows, seq),
                                                dtype=np.int32))
            bound, plain = losses(ids)
            bounds.append(float(bound._data))
            plains.append(float(plain._data))
    centre = math.log(VOCAB) + 0.5 * 64 * 0.02 ** 2
    predicted = math.sqrt((math.log(1 / eps) - (1 - eps)) / (1 - eps)
                          / (rows * seq))
    assert predicted == pytest.approx(0.0162, abs=2e-4)
    spread = np.std(bounds) / centre
    assert 0.6 * predicted < spread < 1.5 * predicted, spread
    # the mean of 20 lies within 3 standard errors of the centre
    assert abs(np.mean(bounds) - centre) < 3 * predicted * centre / 20 ** 0.5
    assert np.std(plains) / centre < 0.002
    assert abs(np.mean(plains) - centre) < 0.002 * centre


def test_compiled_step_draws_fresh_noise_and_keeps_its_counts():
    paddle.seed(3)
    model = sdar_tiny(vocab_size=VOCAB, recompute=True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def step(x):
        loss, plain = model.losses(x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss, plain

    fn = paddle.jit.to_static(step)
    x = paddle.to_tensor(_tokens(2))
    before = noise_stats()
    composite = telemetry.runtime_counter(
        "paddle_flash_mask_composite_traces_total")
    out = [tuple(float(v._data) for v in fn(x)) for _ in range(6)]
    after = noise_stats()
    assert after["tokens"] - before["tokens"] == 6 * BATCH * SEQ
    masked = after["masked"] - before["masked"]
    assert 0.3 * 6 * BATCH * SEQ < masked < 0.75 * 6 * BATCH * SEQ
    # one batch, six draws: the bound moves with the noise, and training on
    # it lowers the masked tokens' cross-entropy
    assert len({round(b, 5) for b, _ in out}) == 6
    assert out[-1][1] < out[0][1]
    assert telemetry.runtime_counter("paddle_to_static_compiles_total") >= 2
    # off the chip the composite builds the dense mask from the same rule
    assert telemetry.runtime_counter(
        "paddle_flash_mask_composite_traces_total") > composite
    text = "\n".join(telemetry.runtime_prometheus())
    for name in ("paddle_sdar_tokens_total", "paddle_sdar_masked_tokens_total",
                 "paddle_flash_mask_composite_traces_total"):
        assert f"\n{name} " in text
