"""Qwen3-Next as a whole on the CPU at a small size (hidden 64, one period
of three Gated DeltaNet layers and one gated attention layer, 8 experts top-2,
vocabulary 256): the program against the plain float32 reference
(``benchmark/reference/qwen3_next.py``, which shares no code with it) on
seeded weights, and the compiled training step. Whatever traces or compiles
the whole model does so ONCE: the forward and backward as one compiled
program, and the five training steps of each model in a module-scoped fixture
whose numbers four tests read. The mixer, the rule and the expert layer are
``tests/test_qwen3_next.py``'s."""
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
from benchmark.models import qwen3_next_train as family         # noqa: E402
from benchmark.reference import qwen3_next as ref               # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (        # noqa: E402
    routing_stats)
from paddle_tpu.inference import telemetry                      # noqa: E402
from paddle_tpu.models.qwen3_next import qwen3_next_tiny        # noqa: E402

VOCAB, BATCH, SEQ = 256, 2, 40      # 40 tokens: two and a half chunks of 16


def _tokens(seed=0):
    ids = np.random.default_rng(seed).integers(
        0, VOCAB, (BATCH, SEQ + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def _reference(model, x, y):
    """(logits [B, T, V], mean loss, gradients as the reference's tree)."""
    w = family.reference_weights(model)
    leaves, tree = jax.tree_util.tree_flatten(w)
    real = [i for i, a in enumerate(leaves)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)]

    def loss(values):
        full = list(leaves)
        for i, v in zip(real, values):
            full[i] = v
        w_ = jax.tree_util.tree_unflatten(tree, full)
        return jnp.mean(jnp.stack([ref.loss(w_, x[i], y[i])
                                   for i in range(x.shape[0])]))
    values = [jnp.asarray(leaves[i], jnp.float32) for i in real]
    value, grads = jax.value_and_grad(loss)(values)
    full = [None] * len(leaves)
    for i, g in zip(real, grads):
        full[i] = g
    logits = np.stack([np.asarray(ref.logits(w, x[i]))
                       for i in range(x.shape[0])])
    return logits, float(value), jax.tree_util.tree_unflatten(tree, full)


def _program(model, x, y):
    """(loss, gradients arranged like the reference's weights, eval-mode
    logits). Forward and backward are ONE compiled program, as the
    benchmark's step has them: eager they are some hundreds of
    one-operation compiles, 24 s of this test's 37. ``reference_weights``
    is a linear rearrangement, so it maps gradients as it maps weights."""
    params = list(model.parameters())

    def loss_and_grads(x, y):
        loss = model(x, labels=y)
        loss.backward()
        return loss, [p.grad for p in params]
    x, y = paddle.to_tensor(x), paddle.to_tensor(y)
    loss, grads = paddle.jit.to_static(loss_and_grads)(x, y)
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
        logits = paddle.jit.to_static(model)(x)
    return (float(loss._data), arranged,
            np.asarray(logits._data, np.float32))


# float32 against float32 differs by summation order alone (the chunked rule
# against the recurrence, one fused projection against three, a grouped
# product against a masked loop): 1e-4 of the largest value is 100 x what
# those leave at this size, and a wrong term (a missing decay, a wrong head
# mapping, an unnormalised weight) misses it by orders of magnitude.
# bf16 rounds every activation at 2**-9, and the benchmark's runner uses the
# same 0.05 of the largest logit on the chip. A bf16 router also FLIPS a
# token's last choice where two experts' probabilities are closer than the
# rounding, and that token's gradient then goes to another expert: bf16
# gradients are held to 0.3 of their tensor's norm (measured: up to 0.14 on
# the experts, 0.24 on a router, 0.03 elsewhere), float32 ones to 1e-4 of
# their tensor's largest entry.
@pytest.mark.parametrize("dtype,tol,grad_tol", [("float32", 1e-4, 1e-4),
                                                ("bfloat16", 0.05, 0.3)])
def test_logits_loss_and_gradients_match_the_reference(dtype, tol, grad_tol):
    paddle.seed(11)
    model = qwen3_next_tiny(vocab_size=VOCAB, experts_held=[0, 1, 2, 5])
    if dtype == "bfloat16":
        model.bfloat16()
    x, y = _tokens()
    want_logits, want_loss, want_grads = _reference(model, x, y)
    got_loss, got_grads, got_logits = _program(model, x, y)
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
    # every parameter, the fused ones in their parts: [q|k|v|z] and [b|a] of
    # three layers, [query|gate] of one, [gate|up] twice in each of four
    assert checked == len(list(model.parameters())) + 3 * 4 + 1 + 4 * 2


# ---------------------------------------------------------- compiled step
def _five_steps(recompute):
    """What five compiled AdamW steps on one batch leave of a float32 model
    (seed 21, the same for both values of ``recompute``): the losses, the
    parameters after the third step, the traces and the last three calls'
    records, and what this model's four expert layers counted."""
    paddle.seed(21)
    model = qwen3_next_tiny(vocab_size=VOCAB, experts_held=[0, 1, 2, 3],
                            recompute=recompute)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    step = paddle.jit.to_static(step)
    x, y = (paddle.to_tensor(a) for a in _tokens(5))

    def counts():      # of this model's four layers, the newest alive
        mine = routing_stats()["layers"][-4:]
        return {k: sum(r[k] for r in mine) for k in ("pairs",
                                                     "pairs_dropped")}
    compiles = telemetry.runtime_counter("paddle_to_static_compiles_total")
    before = counts()
    losses = []
    for i in range(5):
        losses.append(float(np.asarray(step(x, y)._data)))
        if i == 2:
            after_three = [np.asarray(p._data) for p in model.parameters()]
    after = counts()
    return {
        "losses": losses, "after_three": after_three,
        "traces": telemetry.runtime_counter(
            "paddle_to_static_compiles_total") - compiles,
        "steady": paddle.jit.call_timeline()[-3:],
        "pairs": after["pairs"] - before["pairs"],
        "dropped": after["pairs_dropped"] - before["pairs_dropped"],
        "top_k": model.config.num_experts_per_tok}


@pytest.fixture(scope="module")
def five_steps():
    """``recompute`` -> ``_five_steps(recompute)``, each run once a module:
    two traces and two compiles of the whole step a model, which three
    tests used to pay eight times."""
    runs = {}

    def run(recompute):
        if recompute not in runs:
            runs[recompute] = _five_steps(recompute)
        return runs[recompute]
    return run


@pytest.mark.parametrize("recompute", [False, True])
def test_to_static_step_trains_donates_and_does_not_retrace(recompute,
                                                            five_steps):
    run = five_steps(recompute)
    losses = run["losses"]
    assert losses[-1] < losses[2] < losses[0]           # the same batch
    # two traces (the optimizer's slots appear in the first), then none
    assert run["traces"] == 2
    assert all(not r["fresh"] and r["kept"] == 0 and r["donated"] > 0
               for r in run["steady"])
    # the counters are state of the step: updated on the device, once a
    # step whether or not its forward is replayed by recompute
    assert run["pairs"] == 5 * 4 * BATCH * SEQ * run["top_k"]
    assert run["dropped"] == 0


def test_recompute_under_to_static_leaves_the_update_as_it_was(five_steps):
    """The compiled step replays every layer behind an optimization barrier
    (``fleet.utils.recompute``); the runner's comparison sees the eval-mode
    forward only, so HERE the replayed step is held to the plain one: the
    same float32 weights and batch give the same losses and, after three
    AdamW steps, the same parameters."""
    plain, replayed = five_steps(False), five_steps(True)
    np.testing.assert_allclose(replayed["losses"][:3], plain["losses"][:3],
                               rtol=1e-6)
    # AdamW moves a parameter by about the learning rate a step whatever
    # its gradient's size, so where a gradient is near 0 its last bits show:
    # 5e-5 of the 3e-3 a wrong gradient's sign would make
    for a, b in zip(replayed["after_three"], plain["after_three"]):
        np.testing.assert_allclose(a, b, atol=5e-5)
