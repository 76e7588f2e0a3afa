"""The gated delta rule's forward kernel (``ops/pallas/gated_delta_rule``,
interpreted on the CPU) against the rule: its outputs against the
token-by-token recurrence and against the composite, the gradients through
its ``custom_vjp`` against the composite's own, and the states it saves
against the composite scan's carries. At the kernel's own sizes: heads of
128, chunks of 64, one chunk, under a block of 16 and past a block."""
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
from paddle_tpu.inference.telemetry import runtime_counter      # noqa: E402
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.nn.functional import linear_attention as la     # noqa: E402
from paddle_tpu.ops import pallas                               # noqa: E402
from paddle_tpu.ops.pallas import gated_delta_rule as gdr       # noqa: E402

B, D, CHUNK = 2, 128, 64


def _inputs(seq, hk, hv):
    """Decays down to exp(-8) a token, as
    ``test_chunked_delta_rule_matches_the_recurrence`` draws them."""
    rng = np.random.default_rng(seq + hv)
    q, k = (rng.standard_normal((B, seq, hk, D)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((B, seq, hv, D)).astype(np.float32)
    g = -np.exp(rng.uniform(-4, 2, (B, seq, hv))).astype(np.float32)
    beta = rng.uniform(0, 1, (B, seq, hv)).astype(np.float32)
    cot = rng.standard_normal((B, seq, hv, D)).astype(np.float32)
    return (q, k, v, g, beta), cot


def _recurrence(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    qn = jnp.repeat(ref.l2norm(q) * D ** -0.5, r, axis=2)
    kn = jnp.repeat(ref.l2norm(k), r, axis=2)
    return jnp.stack([ref.gated_delta_rule(qn[i], kn[i], v[i], g[i], beta[i])
                      for i in range(B)])


@pytest.mark.parametrize("mm", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [64, 192, 1100])
@pytest.mark.parametrize("hk,hv", [(1, 1), (2, 4)])
def test_kernel_against_the_rule(hk, hv, seq, mm, monkeypatch):
    """Through ``F.chunk_gated_delta_rule`` with the gate on, so the choice,
    the padding (1100 tokens are a block of 16 chunks and a part of one)
    and the ``custom_vjp`` are in it. One value head a key head runs the
    kernel one head wide, two run it two side by side."""
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    arrays, cot = _inputs(seq, hk, hv)
    before = runtime_counter("paddle_gdn_rule_kernel_traces_total")

    # each side is one compiled program: a case is its traces and compiles,
    # not its arithmetic (64 tokens took 4 s eager, 1100 took 7)
    @paddle.jit.to_static
    def through_the_kernel(cot, *ts):
        for t in ts:
            t.stop_gradient = False
        out = F.chunk_gated_delta_rule(*ts, chunk_size=CHUNK,
                                       matmul_dtype=mm)
        (out * cot).sum().backward()
        return out, [t.grad for t in ts]
    got, got_grads = through_the_kernel(*map(paddle.to_tensor,
                                             (cot,) + arrays))
    assert runtime_counter("paddle_gdn_rule_kernel_traces_total") > before

    # the composite, and what JAX derives from it
    @jax.jit
    def composite(cot, *a):
        out, vjp = jax.vjp(lambda *a: la._chunk_rule(
            *a, chunk=CHUNK, mm=jnp.dtype(mm)), *a)
        return out, vjp(cot)
    want, want_grads = composite(cot, *arrays)
    exact = mm == "float32"
    np.testing.assert_allclose(np.asarray(got._data), want,
                               atol=2e-5 if exact else 1e-5)
    for t, wg in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(t._data), wg,
                                   atol=2e-5 * max(1.0, np.abs(wg).max()))
    # the recurrence: float32 at the chunked rule's own tolerance, bf16
    # products within bf16's 2^-8 of the largest output a few times over
    rec = np.asarray(jax.jit(_recurrence)(*arrays))
    np.testing.assert_allclose(
        np.asarray(got._data), rec,
        atol=2e-5 if exact else 2e-2 * np.abs(rec).max())


@pytest.mark.parametrize("hk,hv", [(1, 1), (2, 4)])
def test_saved_states_are_the_scan_s_carries(hk, hv):
    """What the backward pass replays from: the state at each block's
    start, as the composite's scan over blocks carries it."""
    arrays, _ = _inputs(1100, hk, hv)
    arrays = tuple(map(jnp.asarray, arrays))
    _, states = gdr.gdn_chunk_rule_fwd(*arrays, mm=jnp.float32,
                                       block_chunks=la._BLOCK_CHUNKS)
    to_blocks, block_of_chunks, _, state, _ = la._composite(
        arrays[0].shape, arrays[2].shape, jnp.float32, CHUNK, jnp.float32)
    xs = to_blocks(*arrays)
    s = jnp.zeros(state, jnp.float32)
    assert states.shape == (len(xs[0]), B, hv, D, D)
    for i in range(len(xs[0])):
        np.testing.assert_allclose(
            np.asarray(states[i]).reshape(s.shape), s, atol=2e-6)
        s, _ = block_of_chunks(s, tuple(x[i] for x in xs))
    assert float(jnp.abs(s).max()) > 0.01     # a state worth comparing
