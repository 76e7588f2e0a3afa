"""The gated delta rule's two kernels (``ops/pallas/gated_delta_rule``,
interpreted on the CPU) against the rule: the forward's outputs against the
token-by-token recurrence and against the composite, the backward kernel's
five gradients against what JAX derives from the composite, and the states
the forward saves against the composite scan's carries. At the kernels' own
sizes: heads of 128, chunks of 64, one chunk, under a tile of 16 and past a
tile; one, two and four value heads a key head."""
import functools
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


def _rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _through_the_kernels(arrays, cot, mm):
    """``o`` and the five gradients through ``F.chunk_gated_delta_rule`` with
    the gate on, so the choice, the padding and the ``custom_vjp`` are in
    it. One compiled program: a case is its traces and compiles, not its
    arithmetic (64 tokens took 4 s eager, 1100 took 7)."""
    @paddle.jit.to_static
    def run(cot, *ts):
        for t in ts:
            t.stop_gradient = False
        out = F.chunk_gated_delta_rule(*ts, chunk_size=CHUNK,
                                       matmul_dtype=mm)
        (out * cot).sum().backward()
        return out, [t.grad for t in ts]
    out, grads = run(*map(paddle.to_tensor, (cot,) + arrays))
    return np.asarray(out._data), [np.asarray(t._data) for t in grads]


@functools.partial(jax.jit, static_argnames="mm")
def _composite(cot, *a, mm):
    """The composite, and what JAX derives from it."""
    out, vjp = jax.vjp(lambda *a: la._chunk_rule(
        *a, chunk=CHUNK, mm=jnp.dtype(mm)), *a)
    return out, vjp(cot)


def _hold_gradients(got_grads, arrays, cot, mm):
    """The backward kernel's gradients against the composite's with float32
    products: within 2e-5 of the largest one where the kernel's products
    are float32 too; where they are bf16, no further off (relative L2) than
    1.5 x the CONTROL, the composite with bf16 products, which is another
    program and rounds elsewhere."""
    _, want = _composite(cot, *arrays, mm="float32")
    if mm == "float32":
        for got, wg in zip(got_grads, want):
            np.testing.assert_allclose(
                got, wg, atol=2e-5 * max(1.0, np.abs(wg).max()))
        return
    _, control = _composite(cot, *arrays, mm=mm)
    for got, wg, cg in zip(got_grads, want, control):
        assert _rel(got, wg) <= 1.5 * _rel(cg, wg)


@pytest.mark.parametrize("mm", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [64, 192, 1100])
@pytest.mark.parametrize("hk,hv", [(1, 1), (2, 4)])
def test_kernel_against_the_rule(hk, hv, seq, mm, monkeypatch):
    """Both kernels through the functional (1100 tokens are a tile of 16
    chunks and a part of one). One value head a key head runs the kernels
    one head wide, two run them two side by side."""
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    arrays, cot = _inputs(seq, hk, hv)
    before = [runtime_counter(f"paddle_gdn_rule_{which}kernel_traces_total")
              for which in ("", "bwd_")]
    got, got_grads = _through_the_kernels(arrays, cot, mm)
    for which, was in zip(("", "bwd_"), before):
        assert runtime_counter(
            f"paddle_gdn_rule_{which}kernel_traces_total") > was
    exact = mm == "float32"
    want, _ = _composite(cot, *arrays, mm=mm)
    np.testing.assert_allclose(got, want, atol=2e-5 if exact else 1e-5)
    _hold_gradients(got_grads, arrays, cot, mm)
    # the recurrence: float32 at the chunked rule's own tolerance, bf16
    # products within bf16's 2^-8 of the largest output a few times over
    rec = np.asarray(jax.jit(_recurrence)(*arrays))
    np.testing.assert_allclose(
        got, rec, atol=2e-5 if exact else 2e-2 * np.abs(rec).max())


@pytest.mark.parametrize("mm", ["float32", "bfloat16"])
def test_two_groups_share_a_key_head(mm, monkeypatch):
    """Four value heads a key head are two groups of two side by side: the
    backward kernel writes each group's ``dq`` and ``dk`` and the wrapper
    adds them for the head."""
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    arrays, cot = _inputs(192, 1, 4)
    got, got_grads = _through_the_kernels(arrays, cot, mm)
    assert got_grads[0].shape == arrays[0].shape
    want, _ = _composite(cot, *arrays, mm=mm)
    np.testing.assert_allclose(got, want,
                               atol=2e-5 if mm == "float32" else 1e-5)
    _hold_gradients(got_grads, arrays, cot, mm)


def test_cotangent_that_ends_before_the_padded_tail(monkeypatch):
    """1100 tokens are padded to two tiles; ``do`` is zero from token 1030
    on, inside the last tile. The gradients are the composite's, and a
    token past 1030 gets none through ``v``, ``g`` or ``beta``: what it
    wrote is never read."""
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    arrays, cot = _inputs(1100, 2, 4)
    cot[:, 1030:] = 0.0
    _, got_grads = _through_the_kernels(arrays, cot, "float32")
    _hold_gradients(got_grads, arrays, cot, "float32")
    for grad in got_grads[2:]:
        assert np.abs(grad[:, :1030]).max() > 0
        assert not grad[:, 1030:].any()


def _primitives(jaxpr, into):
    """The names of ``jaxpr``'s primitives, a Pallas kernel by its own,
    through every sub-jaxpr but a kernel's body."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into.append(eqn.params["name"])
            continue
        into.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, into)
    return into


def test_backward_is_the_kernel_and_no_replay(monkeypatch):
    """The rule's gradient holds the two kernels and no loop outside them:
    the composite's ``block_of_chunks`` is not replayed behind the forward
    kernel (it was, as a reverse ``scan`` over the blocks, until PR 35)."""
    arrays, cot = _inputs(1100, 2, 4)
    before = runtime_counter("paddle_gdn_rule_bwd_kernel_traces_total")
    jaxpr = jax.make_jaxpr(lambda cot, *a: jax.vjp(
        lambda *a: la._kernel_rule(*a, jnp.dtype("bfloat16")), *a)[1](cot))(
            cot, *arrays)
    assert runtime_counter(
        "paddle_gdn_rule_bwd_kernel_traces_total") == before + 1
    names = _primitives(jaxpr.jaxpr, [])
    assert not {"while", "scan"} & set(names), names
    assert sorted(n for n in names if n.startswith("gdn_")) == [
        "gdn_chunk_rule_bwd", "gdn_chunk_rule_fwd"]


@pytest.mark.parametrize("hk,hv", [(1, 1), (2, 4)])
def test_saved_states_are_the_scan_s_carries(hk, hv):
    """What the backward kernel starts each tile from: the state at each
    block's start, as the composite's scan over blocks carries it."""
    arrays, _ = _inputs(1100, hk, hv)
    arrays = tuple(map(jnp.asarray, arrays))
    _, states = gdr.gdn_chunk_rule_fwd(*arrays, mm=jnp.float32,
                                       block_chunks=la._BLOCK_CHUNKS)
    to_blocks, block_of_chunks, _, state = la._composite(
        arrays[0].shape, arrays[2].shape, jnp.float32, CHUNK, jnp.float32)
    xs = to_blocks(*arrays)
    s = jnp.zeros(state, jnp.float32)
    assert states.shape == (len(xs[0]), B, hv, D, D)
    for i in range(len(xs[0])):
        np.testing.assert_allclose(
            np.asarray(states[i]).reshape(s.shape), s, atol=2e-6)
        s, _ = block_of_chunks(s, tuple(x[i] for x in xs))
    assert float(jnp.abs(s).max()) > 0.01     # a state worth comparing
