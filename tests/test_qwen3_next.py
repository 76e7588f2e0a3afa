"""Qwen3-Next's parts on the CPU at a small size: the chunked delta rule
against the token-by-token recurrence of the plain float32 reference
(``benchmark/reference/qwen3_next.py``, which shares no code with the
program); the causal convolution; the mixer against its former layout; the
expert layer's shares against the uncut layer. The whole model and its
compiled step are ``tests/test_qwen3_next_model.py``'s, so that two workers
share what was one chain."""
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
from benchmark.reference import qwen3_next as ref               # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (        # noqa: E402
    DroplessMoELayer, routing_stats)
from paddle_tpu.models.qwen3_next import qwen3_next_tiny        # noqa: E402
from paddle_tpu.nn import functional as F                       # noqa: E402


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

    @jax.jit        # each side one compiled program: a case is its compiles
    def reference(cot, *a):
        out, vjp = jax.vjp(recurrent, *a)
        return out, vjp(cot)
    want, want_grads = reference(cot, q, k, v, g, beta)

    @paddle.jit.to_static
    def chunked(cot, *ts):
        for t in ts:
            t.stop_gradient = False
        out = F.chunk_gated_delta_rule(*ts, chunk_size=chunk)
        (out * cot).sum().backward()
        return out, [t.grad for t in ts]
    got, got_grads = chunked(*map(paddle.to_tensor, (cot, q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got._data), want, atol=2e-5)
    for t, wg in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(t._data), wg,
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


@pytest.fixture(scope="module")
def mixer():
    paddle.seed(9)
    return qwen3_next_tiny(num_layers=1).model.layers[0].mixer


@pytest.mark.parametrize("seq", [300, 296])
def test_the_mixer_agrees_with_its_former_layout(seq, mixer):
    """The mixer's result and every gradient on the layout it keeps now
    (projections a consumer, heads moved as whole tiles, the norms inside
    the blocks) against the former path, in float32, where each key head
    serves two value heads and the sequence fills one block of 16 chunks
    of 16 and part of a second; 300 is no multiple of 8 either, so the
    gated norm takes its one-row view, 296 the tiles'. Each side is one
    compiled program: eager, the two were 12 of this test's 14 s."""
    assert mixer.hv // mixer.hk == 2
    rng = np.random.default_rng(seq)
    x = rng.standard_normal((2, seq, 64)).astype(np.float32)
    cot = rng.standard_normal((2, seq, 64)).astype(np.float32)
    params = [mixer.in_proj_qkvz.weight, mixer.in_proj_ba.weight,
              mixer.conv_weight, mixer.A_log, mixer.dt_bias,
              mixer.norm_weight, mixer.out_proj.weight]

    @jax.jit
    def former(x_, cot_, *ws):
        out, vjp = jax.vjp(lambda *a: _former_mixer(mixer, *a), x_, *ws)
        return out, vjp(cot_)
    want, want_grads = former(x, cot, *[p._data for p in params])

    @paddle.jit.to_static
    def now(xt, cot_):
        xt.stop_gradient = False
        out = mixer(xt)
        (out * cot_).sum().backward()
        return out, [xt.grad] + [p.grad for p in params]
    got, got_grads = now(paddle.to_tensor(x), paddle.to_tensor(cot))
    np.testing.assert_allclose(np.asarray(got._data), want,
                               atol=1e-5 * np.abs(want).max())
    for t, g in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(t._data), g,
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
