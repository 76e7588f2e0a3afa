"""JoyAI-LLM-Flash on the CPU at a small size (hidden 64; the dense leading
layer, two expert layers of 8 experts top-2 and the multi-token-prediction
module; vocabulary 256): the program against the plain float32 reference
(``benchmark/reference/joyai_flash.py``) on seeded weights, both heads'
logits, the loss and gradients, from ONE compiled forward and backward
under ``recompute``; the interleaved rotation against its pair-by-pair
definition; the generalised latent-attention layer against Kimi Linear's
own. The expert layer's shares against the uncut layer are
``tests/test_kimi_linear.py::test_the_shares_of_four_ranks_add_up_to_the_
uncut_layer``'s: the same layer with the same options (sigmoid scores, a
selection bias, a routed scale, an ungated shared expert)."""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle                                     # noqa: E402
import paddle_tpu.ops.pallas as pallas                          # noqa: E402
from paddle_tpu import profiler                                 # noqa: E402
from benchmark.models import joyai_flash_train as family        # noqa: E402
from benchmark.reference import joyai_flash as ref              # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (        # noqa: E402
    routing_stats)
from paddle_tpu.models import kimi_linear                       # noqa: E402
from paddle_tpu.models.joyai_flash import (                     # noqa: E402
    JoyAIFlashConfig, joyai_flash_tiny)

VOCAB, BATCH, SEQ = 256, 2, 24


def _tokens(seed=0):
    ids = np.random.default_rng(seed).integers(
        0, VOCAB, (BATCH, SEQ + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def compiled():
    """A float32 model with every decoder layer under ``recompute``: the
    training loss and the gradients it gives (ONE compiled program, whose
    lowered text with its stamps is kept), the eval-mode logits of both
    heads, the reference's weights, and the pairs the three expert layers
    counted in that one step."""
    paddle.seed(11)
    model = joyai_flash_tiny(vocab_size=VOCAB, experts_held=[0, 1, 2, 5],
                             recompute=True)
    params = list(model.parameters())

    def loss_and_grads(x, y):
        loss, main = model.losses(x, y)
        loss.backward()
        return loss, main, [p.grad for p in params]
    x, y = (paddle.to_tensor(a) for a in _tokens())
    step = paddle.jit.to_static(loss_and_grads)
    before = sum(r["pairs"] for r in routing_stats()["layers"][-3:])
    loss, main, grads = step(x, y)
    pairs = sum(r["pairs"] for r in routing_stats()["layers"][-3:]) - before
    saved = [p._data for p in params]
    for p, g in zip(params, grads):
        p._data = g._data
    arranged = family.reference_weights(model)
    for p, a in zip(params, saved):
        p._data = a
    model.eval()
    with paddle.no_grad():
        logits = paddle.jit.to_static(model)(x)
    out = {"loss": float(loss._data), "main": float(main._data),
           "grads": arranged, "weights": family.reference_weights(model),
           "logits": np.asarray(logits._data), "pairs": pairs,
           "text": step.lower(x, y).as_text(debug_info=True)}
    del model, step, params, grads, saved
    return out


def test_both_heads_and_the_loss_match_the_reference(compiled):
    x, y = _tokens()
    w = compiled["weights"]
    want = np.stack([np.asarray(ref.logits(w, x[i])) for i in range(BATCH)])
    # the main head's SEQ rows, then the MTP head's SEQ - 1
    assert compiled["logits"].shape == want.shape == (BATCH, 2 * SEQ - 1,
                                                      VOCAB)
    # float32 against float32: summation order alone
    scale = np.abs(want).max()
    assert np.abs(compiled["logits"] - want).max() <= 1e-4 * scale
    total = np.mean([float(ref.loss(w, x[i], y[i])) for i in range(BATCH)])
    main = np.mean([float(ref._ce(want[i, :SEQ], y[i]))
                    for i in range(BATCH)])
    assert compiled["loss"] == pytest.approx(total, rel=1e-5)
    assert compiled["main"] == pytest.approx(main, rel=1e-5)
    # the MTP loss is in it, with its weight
    assert total - main == pytest.approx(0.3 * np.mean(
        [float(ref._ce(want[i, SEQ:], y[i, 1:])) for i in range(BATCH)]),
        rel=1e-5)
    # once a step though recompute replays every layer: 2 expert layers
    # and the MTP module's, every token, top-2
    assert compiled["pairs"] == 3 * BATCH * SEQ * 2


PATHS = [("layers", 1, "mixer", "w_qa"), ("mtp", "w_eh"),
         ("layers", 2, "ffn", "w_up"), ("mtp", "layer", "ffn", "w_down"),
         ("head",)]


def _pick(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _put(tree, path, leaf):
    if not path:
        return leaf
    tree = list(tree) if isinstance(tree, list) else dict(tree)
    tree[path[0]] = _put(tree[path[0]], path[1:], leaf)
    return tree


@pytest.fixture(scope="module")
def reference_grads(compiled):
    """``jax.grad`` of the reference's mean loss by the leaves of
    ``PATHS``, in one differentiation."""
    x, y = _tokens()
    w = compiled["weights"]             # a float32 model's

    def loss(leaves):
        w_ = w
        for path, leaf in zip(PATHS, leaves):
            w_ = _put(w_, path, leaf)
        return jnp.mean(jnp.stack([ref.loss(w_, x[i], y[i])
                                   for i in range(BATCH)]))
    return jax.jit(jax.grad(loss))([_pick(w, p) for p in PATHS])


@pytest.mark.parametrize("i", range(len(PATHS)),
                         ids=["/".join(map(str, p)) for p in PATHS])
def test_gradients_match_the_reference(compiled, reference_grads, i):
    """``q_a_proj`` of an expert layer, ``W_eh``, a held expert of the main
    model and of the MTP module, the shared head: the program's gradient of
    ``L_main + 0.3 L_MTP`` under ``recompute``."""
    want = np.asarray(reference_grads[i])
    got = np.asarray(_pick(compiled["grads"], PATHS[i]), np.float32)
    assert np.abs(want).max() > 0
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_the_interleaved_rotation_turns_each_pair():
    """``_rope_interleaved`` against the rotation written out pair by pair,
    at a base whose angles wrap many times."""
    rng = np.random.default_rng(3)
    a = rng.standard_normal((2, 37, 3, 8)).astype(np.float32)
    got = np.asarray(kimi_linear._rope_interleaved(jnp.asarray(a), 3.2e7))
    want = np.empty_like(a)
    for p in range(37):
        for j in range(4):
            ang = np.float32(p) * np.float32(3.2e7 ** (-2 * j / 8))
            c, s = np.cos(ang), np.sin(ang)
            x0, x1 = a[:, p, :, 2 * j], a[:, p, :, 2 * j + 1]
            want[:, p, :, 2 * j] = x0 * c - x1 * s
            want[:, p, :, 2 * j + 1] = x0 * s + x1 * c
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    # the reference's form (an axis of pairs) is the same rotation
    np.testing.assert_allclose(np.asarray(ref.rope_pairs(
        jnp.asarray(a[0]), 3.2e7)), want[0], rtol=0, atol=1e-5)


@pytest.mark.parametrize("scope", ["mla.q_compress", "mla.rope",
                                   "mla.kv_compress", "attn.mla", "mtp",
                                   "mtp.embed_proj", "mtp.head_loss"])
def test_the_step_names_every_new_scope(scope, compiled):
    """What ``paddle.profiler`` reduces a trace by, and ``mtp_device_pct``
    and ``mla_rope_device_pct`` read: each scope is a component of
    ``profiler.scope_of`` of the step's HLO op names in the first forward
    and the backward, and in the replay where a decoder layer holds it."""
    names = set(re.findall(r'loc\("(jit\(pure\)/[^"]*)"', compiled["text"]))
    passes = {profiler.pass_of(n) for n in names
              if scope in profiler.scope_of(n)[1].split("/")}
    assert {"fwd", "bwd"} <= passes
    if scope.startswith(("mla", "attn")) or scope == "mtp":
        assert "replay" in passes


# The latent-attention layer's forward as Kimi Linear had it before the
# layer learnt query compression and rotary positions, word for word.
def _kimi_forward(self, x):
    reshape, apply_op, F = (kimi_linear.reshape, kimi_linear.apply_op,
                            kimi_linear.F)
    b, s = x.shape[0], x.shape[1]
    h, r, dn, dr, dv = self.h, self.r, self.dn, self.dr, self.dv
    with jax.named_scope("attn.mla"):
        q = reshape(self.q_proj(x), [b, s, h, dn + dr])
        with jax.named_scope("mla.kv_compress"):
            ckr = self.kv_a_proj_with_mqa(x)
            kv = reshape(self.kv_b_proj(self.kv_a_layernorm(
                ckr[:, :, :r])), [b, s, h, dn + dv])
            k = apply_op(lambda a, kr: jnp.concatenate(
                [a[..., :dn], jnp.broadcast_to(
                    kr[:, :, None, :], (b, s, h, dr))], -1),
                kv, ckr[:, :, r:])
        out = F.scaled_dot_product_attention(q, k, kv[:, :, :, dn:],
                                             is_causal=True)
        return self.o_proj(reshape(out, [b, s, h * dv]))


def _kimi_step_text():
    paddle.seed(4)
    model = kimi_linear.kimi_linear_tiny(
        vocab_size=VOCAB, num_layers=1, full_attn_layers=(1,),
        recompute=True)
    model.bfloat16()

    def step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        return loss, [p.grad for p in model.parameters()]
    x, y = (paddle.to_tensor(a) for a in _tokens(1))
    step = paddle.jit.to_static(step)
    step(x, y)
    return step.lower(x, y).as_text()


def test_kimi_linear_lowers_to_the_text_it_had(monkeypatch):
    """Kimi Linear's configuration (no query rank, no rotary base) takes
    the generalised layer to the same module as the layer it replaced."""
    now = _kimi_step_text()
    monkeypatch.setattr(kimi_linear.KimiMLA, "forward", _kimi_forward)
    assert _kimi_step_text() == now


def test_the_layer_at_published_heads_takes_the_flash_kernel(monkeypatch):
    """Query-key heads of 128 + 64 and value heads of 128, as published:
    the rotated queries and keys reach the flash kernel (interpret mode)."""
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    paddle.seed(5)
    layer = kimi_linear.KimiMLA(JoyAIFlashConfig(
        hidden_size=64, num_heads=1, q_lora_rank=48, kv_lora_rank=32))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 64, 64)),
                    jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda a: layer(paddle.to_tensor(a))._data)(x))
    assert "flash_attention_fwd" in jaxpr
