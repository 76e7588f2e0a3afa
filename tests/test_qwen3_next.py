"""Qwen3-Next on the CPU at a small size (hidden 64, one period of three
Gated DeltaNet layers and one gated attention layer, 8 experts top-2,
vocabulary 256): the program against the plain float32 reference
(``benchmark/reference/qwen3_next.py``, which shares no code with it) on
seeded weights; the chunked delta rule against the token-by-token
recurrence; the expert layer's shares against the uncut layer; the compiled
training step."""
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
    DroplessMoELayer, routing_stats)
from paddle_tpu.models.qwen3_next import qwen3_next_tiny        # noqa: E402
from paddle_tpu.nn import functional as F                       # noqa: E402

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


def _program_grads(model):
    """The program's parameter gradients, arranged like the reference's
    weights: ``reference_weights`` is a linear rearrangement, so it maps
    gradients as it maps weights."""
    saved = [(p, p._data) for p in model.parameters()]
    for p, _ in saved:
        p._data = p.grad._data
    try:
        return family.reference_weights(model)
    finally:
        for p, a in saved:
            p._data = a


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
    loss = model(paddle.to_tensor(x), labels=paddle.to_tensor(y))
    loss.backward()
    got_grads = _program_grads(model)
    model.eval()
    with paddle.no_grad():
        got_logits = np.asarray(model(paddle.to_tensor(x))._data, np.float32)
    scale = np.abs(want_logits).max()
    assert np.abs(got_logits - want_logits).max() <= tol * scale
    assert abs(float(loss._data) - want_loss) <= tol * want_loss

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


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule on [B, T, h, d] inputs, given
    normalised and scaled ``q`` and ``k`` per VALUE head."""
    return jnp.stack([ref.gated_delta_rule(q[i], k[i], v[i], g[i], beta[i])
                      for i in range(q.shape[0])])


@pytest.mark.parametrize("seq,chunk", [(64, 16), (128, 64), (37, 16),
                                       (100, 64), (300, 16)])
def test_chunked_delta_rule_matches_the_recurrence(seq, chunk):
    """Values and gradients, for lengths that are and are not a multiple of
    the chunk, in one block of 16 chunks and in two (300 tokens are 19
    chunks of 16: the state crosses the checkpointed blocks). Decays down
    to exp(-8) a token make a chunk's exponents span hundreds: the chunked
    form must not overflow."""
    rng = np.random.default_rng(seq)
    b, hk, hv, dk, dv = 2, 2, 4, 16, 8
    q, k = (rng.standard_normal((b, seq, hk, dk)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((b, seq, hv, dv)).astype(np.float32)
    g = -np.exp(rng.uniform(-4, 2, (b, seq, hv))).astype(np.float32)
    beta = rng.uniform(0, 1, (b, seq, hv)).astype(np.float32)
    cot = rng.standard_normal((b, seq, hv, dv)).astype(np.float32)

    def recurrent(q, k, v, g, beta):
        qn = jnp.repeat(ref.l2norm(q) * dk ** -0.5, hv // hk, axis=2)
        kn = jnp.repeat(ref.l2norm(k), hv // hk, axis=2)
        return _recurrence(qn, kn, v, g, beta)

    want, vjp = jax.vjp(recurrent, q, k, v, g, beta)
    want_grads = vjp(jnp.asarray(cot))
    ts = [paddle.to_tensor(a) for a in (q, k, v, g, beta)]
    for t in ts:
        t.stop_gradient = False
    got = F.chunk_gated_delta_rule(*ts, chunk_size=chunk)
    (got * paddle.to_tensor(cot)).sum().backward()
    np.testing.assert_allclose(np.asarray(got._data), want, atol=2e-5)
    for t, wg in zip(ts, want_grads):
        np.testing.assert_allclose(np.asarray(t.grad._data), wg,
                                   atol=2e-5 * max(1.0, np.abs(wg).max()))


@pytest.mark.parametrize("n", [8, 16, 64])
def test_unit_lower_inverse_is_exact_where_a_series_would_cancel(n):
    """Neighbouring keys that nearly agree, beta 1 and no decay make ``A``
    all ones under the diagonal: its inverse is benign (1 and -1), but the
    powers of ``A`` reach 1e17 at 64, so a series over the whole chunk would
    cancel them in float32. Block forward substitution does not."""
    from paddle_tpu.nn.functional.linear_attention import _inverse_unit_lower
    rng = np.random.default_rng(n)
    for a in (np.tril(np.full((n, n), 0.999, np.float32), -1),
              np.tril(rng.standard_normal((3, n, n)), -1).astype(np.float32)):
        got = np.asarray(_inverse_unit_lower(jnp.asarray(a)))
        want = np.linalg.inv(np.eye(n) + a.astype(np.float64))
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_causal_conv_matches_the_reference():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 9, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    got = F.causal_conv1d(paddle.to_tensor(x), paddle.to_tensor(w))
    want = np.stack([np.asarray(ref.causal_conv(jnp.asarray(x[i]),
                                                jnp.asarray(w)))
                     for i in range(2)])
    np.testing.assert_allclose(np.asarray(got._data), want, atol=1e-6)
    # causal: the output at t does not see t + 1
    x2 = x.copy()
    x2[:, 5:] = 0
    got2 = F.causal_conv1d(paddle.to_tensor(x2), paddle.to_tensor(w))
    np.testing.assert_array_equal(np.asarray(got2._data)[:, :5],
                                  np.asarray(got._data)[:, :5])


def _plain_conv(a, w, silu):
    """``causal_conv1d`` as it was before its backward pass was written
    out: JAX's own transpose is the oracle."""
    k, t = w.shape[1], a.shape[1]
    pad = jnp.pad(a, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    y = sum(pad[:, j:j + t] * w[:, j] for j in range(k))
    return (jax.nn.silu(y) if silu else y).astype(a.dtype)


@pytest.mark.parametrize("seq", [9, 130])
@pytest.mark.parametrize("activation", [None, "silu"])
def test_causal_conv_gradients_match_jax_own(seq, activation):
    rng = np.random.default_rng(seq)
    x, cot = (rng.standard_normal((2, seq, 6)).astype(np.float32)
              for _ in range(2))
    w = rng.standard_normal((6, 4)).astype(np.float32)
    want = jax.grad(lambda a, w_: jnp.sum(
        _plain_conv(a, w_, activation == "silu") * cot), (0, 1))(x, w)
    xt, wt = paddle.to_tensor(x), paddle.to_tensor(w)
    xt.stop_gradient = wt.stop_gradient = False
    got = F.causal_conv1d(xt, wt, activation=activation)
    np.testing.assert_allclose(
        np.asarray(got._data),
        np.asarray(_plain_conv(x, w, activation == "silu")), atol=1e-6)
    (got * paddle.to_tensor(cot)).sum().backward()
    for t, g in zip((xt, wt), want):
        np.testing.assert_allclose(np.asarray(t.grad._data), g,
                                   atol=1e-5 * max(1.0, np.abs(g).max()))


def _former_rule(q, k, v, g, beta, chunk):
    """``_chunk_rule`` as PR 28 had it (float32 throughout): the norms in
    front, ``blocks()`` that reshapes the sequence and the heads FIRST and
    transposes afterwards, the result back by the same way. The oracle of
    the layout the program keeps now."""
    from paddle_tpu.nn.functional import linear_attention as la
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    q, k = la._l2norm(q) * dk ** -0.5, la._l2norm(k)
    nb = min(la._BLOCK_CHUNKS, -(-t // chunk))
    pad = -t % (chunk * nb)
    n_blocks = (t + pad) // (chunk * nb)

    def blocks(x, heads, to):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n_blocks, nb, chunk) + heads + x.shape[3:])
        return x.transpose(to)
    q = blocks(q, (hk,), (1, 0, 4, 2, 3, 5))
    k = blocks(k, (hk,), (1, 0, 4, 2, 3, 5))
    v = blocks(v, (hk, r), (1, 0, 4, 5, 2, 3, 6))
    g = blocks(g, (hk, r), (1, 0, 4, 5, 2, 3))
    beta = blocks(beta, (hk, r), (1, 0, 4, 5, 2, 3))
    ii = jnp.arange(chunk)
    lower, strict = ii[:, None] >= ii[None, :], ii[:, None] > ii[None, :]

    def step(s, xs):
        w_n, u_n, qg_n, aqk_n, kd_n, last_n = xs
        vp = u_n - jnp.einsum("bhrcd,bhrde->bhrce", w_n, s)
        o = jnp.einsum("bhrcd,bhrde->bhrce", qg_n, s) + jnp.einsum(
            "bhrij,bhrje->bhrie", aqk_n, vp)
        return last_n[..., None, None] * s + jnp.einsum(
            "bhrcd,bhrce->bhrde", kd_n, vp), o

    def block_of_chunks(s, xs):
        q_, k_, v_, g_, beta_ = xs
        gamma = jnp.cumsum(g_, axis=-1)
        decay = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        kk = jnp.einsum("bhnid,bhnjd->bhnij", k_, k_)[:, :, None]
        a = jnp.where(strict, beta_[..., None] * kk * decay, 0.0)
        kr = k_[:, :, None]
        rhs = jnp.concatenate([kr * (beta_ * jnp.exp(gamma))[..., None],
                               v_ * beta_[..., None]], axis=-1)
        wu = jnp.matmul(la._inverse_unit_lower(a), rhs,
                        precision=jax.lax.Precision.HIGHEST)
        qg = q_[:, :, None] * jnp.exp(gamma)[..., None]
        aqk = jnp.einsum("bhnid,bhnjd->bhnij", q_, k_)[:, :, None] * decay
        kd = kr * jnp.exp(gamma[..., -1:] - gamma)[..., None]
        xs = (wu[..., :dk], wu[..., dk:], qg, aqk, kd,
              jnp.exp(gamma[..., -1]))
        return jax.lax.scan(step, s,
                            tuple(jnp.moveaxis(x, 3, 0) for x in xs))
    _, o = jax.lax.scan(block_of_chunks,
                        jnp.zeros((b, hk, r, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o.transpose(2, 0, 1, 5, 3, 4, 6).reshape(b, t + pad, hv, dv)
    return o[:, :t]


def _former_mixer(m, x, w_qkvz, w_ba, conv_w, a_log, dt_bias, norm_w, w_out):
    """``Qwen3NextGatedDeltaNet.forward`` as PR 28 had it: ONE product
    ``[q | k | v | z]``, sliced; one convolution over ``[q | k | v]``,
    sliced; the gated norm on ``[B, T, hv, dv]``."""
    b, s = x.shape[0], x.shape[1]
    key, value = m.hk * m.dk, m.hv * m.dv
    qkvz = x @ w_qkvz
    qkv = _plain_conv(qkvz[:, :, :2 * key + value], conv_w, True)
    q = qkv[:, :, :key].reshape(b, s, m.hk, m.dk)
    k = qkv[:, :, key:2 * key].reshape(b, s, m.hk, m.dk)
    v = qkv[:, :, 2 * key:].reshape(b, s, m.hv, m.dv)
    ba = x @ w_ba
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., m.hv:] + dt_bias)
    o = _former_rule(q, k, v, g, jax.nn.sigmoid(ba[..., :m.hv]),
                     m.chunk_size)
    h = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m.eps)
    z = qkvz[:, :, 2 * key + value:].reshape(b, s, m.hv, m.dv)
    return (h * norm_w * jax.nn.silu(z)).reshape(b, s, value) @ w_out


@pytest.mark.parametrize("seq", [300, 296])
def test_the_mixer_agrees_with_its_former_layout(seq):
    """The mixer's result and every gradient on the layout it keeps now
    (projections a consumer, heads moved as whole tiles, the norms inside
    the blocks) against the former path, in float32, where each key head
    serves two value heads and the sequence fills one block of 16 chunks
    of 16 and part of a second; 300 is no multiple of 8 either, so the
    gated norm takes its one-row view, 296 the tiles'."""
    paddle.seed(9)
    mixer = qwen3_next_tiny(num_layers=1).model.layers[0].mixer
    assert mixer.hv // mixer.hk == 2
    rng = np.random.default_rng(seq)
    x = rng.standard_normal((2, seq, 64)).astype(np.float32)
    cot = rng.standard_normal((2, seq, 64)).astype(np.float32)
    params = [mixer.in_proj_qkvz.weight, mixer.in_proj_ba.weight,
              mixer.conv_weight, mixer.A_log, mixer.dt_bias,
              mixer.norm_weight, mixer.out_proj.weight]
    want, vjp = jax.vjp(lambda x_, *ws: _former_mixer(mixer, x_, *ws),
                        jnp.asarray(x), *[p._data for p in params])
    want_grads = vjp(jnp.asarray(cot))
    xt = paddle.to_tensor(x)
    xt.stop_gradient = False
    got = mixer(xt)
    (got * paddle.to_tensor(cot)).sum().backward()
    np.testing.assert_allclose(np.asarray(got._data), want,
                               atol=1e-5 * np.abs(want).max())
    for t, g in zip([xt] + params, want_grads):
        np.testing.assert_allclose(np.asarray(t.grad._data), g,
                                   atol=2e-5 * max(np.abs(g).max(), 1e-6))


# ------------------------------------------------------------ expert layer
D, FF, E, K = 32, 16, 8, 2


def _expert_layer(held, seed=5, **kw):
    paddle.seed(seed)       # the same seed: the same router and experts
    full = DroplessMoELayer(D, FF, E, K, shared_hidden=FF, **kw)
    if held is None:
        return full
    part = DroplessMoELayer(D, FF, E, K, experts_held=held, shared_hidden=FF,
                            **kw)
    for name in ("router", "shared_gate_up", "shared_down", "shared_gate"):
        getattr(part, name)._data = getattr(full, name)._data
    idx = jnp.asarray(held)
    part.experts_gate_up._data = full.experts_gate_up._data[idx]
    part.experts_down._data = full.experts_down._data[idx]
    return part


def _reference_moe(layer, x):
    """The reference's expert layer on the layer's own weights."""
    f = layer.experts_down.shape[1]
    p = {"router": layer.router._data,
         "held": jnp.asarray(layer.experts_held, jnp.int32),
         "w_gate": layer.experts_gate_up._data[:, :, :f],
         "w_up": layer.experts_gate_up._data[:, :, f:],
         "w_down": layer.experts_down._data,
         "shared_gate": layer.shared_gate_up._data[:, :f],
         "shared_up": layer.shared_gate_up._data[:, f:],
         "shared_down": layer.shared_down._data,
         "shared_sigmoid": layer.shared_gate._data[:, 0]}
    return np.asarray(ref.moe(jnp.asarray(x.reshape(-1, D)), p, K))


def _shared_part(layer, x):
    """What every rank computes alike: the layer with no routed expert's
    part, i.e. its result minus its routed part."""
    f = layer.shared_down.shape[0]
    a = jnp.asarray(x.reshape(-1, D))
    gate = jax.nn.sigmoid(a @ layer.shared_gate._data)
    return np.asarray(gate * ref.swiglu(
        a, layer.shared_gate_up._data[:, :f],
        layer.shared_gate_up._data[:, f:], layer.shared_down._data))


def test_the_shares_of_four_ranks_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: the routed parts that
    experts_held = each of 4 disjoint quarters give, plus the shared expert
    counted once, are the uncut reference's expert layer."""
    x = np.random.default_rng(2).standard_normal((2, 24, D)).astype(
        np.float32)
    whole = _expert_layer(None)
    want = _reference_moe(whole, x)
    shared = _shared_part(whole, x)
    total = shared.copy()
    local = 0
    for held in ([0, 1], [2, 3], [4, 5], [6, 7]):
        part = _expert_layer(held)
        out = np.asarray(part(paddle.to_tensor(x))._data).reshape(-1, D)
        total += out - shared
        rec = routing_stats()["layers"][-1]
        assert rec["experts_held"] == held and rec["pairs_dropped"] == 0
        assert rec["pairs"] == 2 * 24 * K
        assert sum(rec["rows_per_expert"]) == rec["pairs_local"]
        local += rec["pairs_local"]
    assert local == 2 * 24 * K      # every pair is some rank's
    np.testing.assert_allclose(total, want, atol=1e-5)
    # and the uncut program layer is the uncut reference
    np.testing.assert_allclose(
        np.asarray(whole(paddle.to_tensor(x))._data).reshape(-1, D), want,
        atol=1e-5)


def test_a_skewed_router_drops_nothing_and_matches_the_reference():
    """A router that sends most tokens to one held expert: the buffer (2 x
    the even share) still holds every local pair."""
    x = np.random.default_rng(3).standard_normal((2, 64, D)).astype(
        np.float32)
    part = _expert_layer([0, 1])
    # the input's mean direction points at expert 1's router column
    bias = np.zeros((D, E), np.float32)
    bias[:, 1] = 0.5
    part.router._data = part.router._data + jnp.asarray(bias)
    x = x + 1.0
    before = routing_stats()["layers"][-1]
    out = np.asarray(part(paddle.to_tensor(x))._data).reshape(-1, D)
    rec = routing_stats()["layers"][-1]
    rows = [a - b for a, b in zip(rec["rows_per_expert"],
                                  before["rows_per_expert"])]
    assert rows[1] > 0.9 * 2 * 64           # nearly every token chose it
    assert rows[1] > 4 * max(rows[0], 1)
    assert rec["pairs_dropped"] == 0
    assert rec["rows_computed"] == part.buffer_rows(2 * 64)
    np.testing.assert_allclose(out, _reference_moe(part, x), atol=1e-5)


def test_rows_the_grouped_product_leaves_unwritten_reach_nothing(monkeypatch):
    """``jax.lax.ragged_dot`` writes the rows of its groups and no others:
    past them its result, and its input's gradient, are zeros on the CPU
    and whatever the buffer held on the TPU, where they reached the
    tokens' gradients (PERF.md section 6, PR 29: gradients 10^3-10^4 times
    too large above every expert layer). Here those rows are NaN: the
    layer's result and every gradient must come out as without them."""
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        inside = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]

        @jax.custom_vjp
        def dot(lhs, rhs):
            return jnp.where(inside, real(lhs, rhs, group_sizes, **kw),
                             jnp.nan)

        def fwd(lhs, rhs):
            return dot(lhs, rhs), (lhs, rhs)

        def bwd(res, ct):
            d_lhs, d_rhs = jax.vjp(
                lambda a, b: real(a, b, group_sizes, **kw), *res)[1](
                    jnp.where(inside, ct, 0.0))
            return jnp.where(inside, d_lhs, jnp.nan), d_rhs
        dot.defvjp(fwd, bwd)
        return dot(lhs, rhs)

    x = np.random.default_rng(4).standard_normal((2, 24, D)).astype(
        np.float32)
    runs = []
    for poison in (False, True):
        if poison:
            monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
        part = _expert_layer([0, 1])
        xt = paddle.to_tensor(x)
        xt.stop_gradient = False
        out = part(xt)
        rec = routing_stats()["layers"][-1]
        assert 0 < rec["pairs_local"] < rec["rows_computed"]
        (out * out).sum().backward()
        runs.append([np.asarray(t._data) for t in (
            out, xt.grad, part.router.grad, part.experts_gate_up.grad,
            part.experts_down.grad)])
    for want, got in zip(*runs):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_a_pair_past_the_buffer_is_counted_not_lost_in_silence():
    """A router that sends nearly every token to BOTH experts held here
    routes 2 pairs a token to a rank whose even share is 1/2 a token: twice
    what the buffer (2 x the even share) holds."""
    x = np.random.default_rng(4).standard_normal((1, 1024, D)).astype(
        np.float32) + 1.0
    part = _expert_layer([0, 1])
    bias = np.zeros((D, E), np.float32)
    bias[:, :2] = 0.5
    part.router._data = part.router._data + jnp.asarray(bias)
    rows = part.buffer_rows(1024)
    assert rows == 1024 == 2 * (1024 * K * 2 // E)
    part(paddle.to_tensor(x))
    rec = routing_stats()["layers"][-1]
    assert rec["pairs_local"] > 1.8 * 1024
    assert rec["pairs_dropped"] == rec["pairs_local"] - rows


def test_routing_counts_reach_the_runtime_exposition():
    from paddle_tpu.inference import telemetry
    layer = _expert_layer([0, 1])       # counted while the layer lives
    layer(paddle.to_tensor(np.ones((1, 8, D), np.float32)))
    text = "\n".join(telemetry.runtime_prometheus())
    stats = routing_stats()
    for name, key in (("paddle_moe_pairs_total", "pairs"),
                      ("paddle_moe_pairs_local_total", "pairs_local"),
                      ("paddle_moe_pairs_dropped_total", "pairs_dropped")):
        assert f"{name} {stats[key]}" in text
    assert stats["pairs"] >= 8 * K


# ---------------------------------------------------------- compiled step
@pytest.mark.parametrize("recompute", [False, True])
def test_to_static_step_trains_donates_and_does_not_retrace(recompute):
    from paddle_tpu.inference import telemetry
    paddle.seed(21)
    model = qwen3_next_tiny(vocab_size=VOCAB, experts_held=[0, 1, 2, 3],
                            recompute=recompute)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    step = paddle.jit.to_static(step)
    x, y = (paddle.to_tensor(a) for a in _tokens(5))
    compiles = telemetry.runtime_counter("paddle_to_static_compiles_total")
    def counts():      # of this model's four layers, the newest alive
        mine = routing_stats()["layers"][-4:]
        return {k: sum(r[k] for r in mine) for k in ("pairs",
                                                     "pairs_dropped")}
    before = counts()
    losses = [float(np.asarray(step(x, y)._data, np.float32))
              for _ in range(5)]
    assert losses[-1] < losses[2] < losses[0]           # the same batch
    # two traces (the optimizer's slots appear in the first), then none
    assert telemetry.runtime_counter(
        "paddle_to_static_compiles_total") - compiles == 2
    steady = paddle.jit.call_timeline()[-3:]
    assert all(not r["fresh"] and r["kept"] == 0 and r["donated"] > 0
               for r in steady)
    # the counters are state of the step: updated on the device, once a
    # step whether or not its forward is replayed by recompute
    after = counts()
    assert after["pairs"] - before["pairs"] == \
        5 * 4 * BATCH * SEQ * model.config.num_experts_per_tok
    assert after["pairs_dropped"] == before["pairs_dropped"]


def test_recompute_under_to_static_leaves_the_update_as_it_was():
    """The compiled step replays every layer behind an optimization barrier
    (``fleet.utils.recompute``); the runner's comparison sees the eval-mode
    forward only, so HERE the replayed step is held to the plain one: the
    same float32 weights and batch give the same losses and, after three
    AdamW steps, the same parameters."""
    runs = []
    for recompute in (False, True):
        paddle.seed(33)
        model = qwen3_next_tiny(vocab_size=VOCAB, experts_held=[0, 1, 2, 3],
                                recompute=recompute)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())

        def step(x, y, model=model, opt=opt):
            loss = model(x, labels=y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        step = paddle.jit.to_static(step)
        x, y = (paddle.to_tensor(a) for a in _tokens(6))
        losses = [float(np.asarray(step(x, y)._data)) for _ in range(3)]
        runs.append((losses, [np.asarray(p._data)
                              for p in model.parameters()]))
    (plain_losses, plain), (losses, replayed) = runs
    np.testing.assert_allclose(losses, plain_losses, rtol=1e-6)
    # AdamW moves a parameter by about the learning rate a step whatever
    # its gradient's size, so where a gradient is near 0 its last bits show:
    # 5e-5 of the 3e-3 a wrong gradient's sign would make
    for a, b in zip(replayed, plain):
        np.testing.assert_allclose(a, b, atol=5e-5)
