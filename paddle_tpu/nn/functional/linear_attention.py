"""Linear-attention functionals: the gated delta rule in chunked form and
the causal depthwise convolution that stands in front of it.

The gated delta rule (Yang et al. 2024, "Gated Delta Networks") keeps one
state ``S`` [d_k, d_v] per head and per token does

    S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t

``chunk_gated_delta_rule`` computes the same outputs from CHUNKS of tokens:
everything that does not need the state is a batched matrix product over
a block of chunks at once, and only four small products a chunk sit in the
scan that carries ``S``. Within a chunk, with ``gamma_i`` the running sum of
``g``: ``A_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)`` for ``j < i``,
``[W | U] = (I + A)^-1 [beta exp(gamma) K | beta V]`` by forward
substitution (block by block; the 8 x 8 diagonal blocks by their exact
series). Across chunks:
``V' = U - W S``, ``O = (Q exp(gamma)) S + tril(Q K^T exp(gamma_i -
gamma_j)) V'``, ``S <- exp(gamma_C) S + (K exp(gamma_C - gamma))^T V'``.
Every exponent is a difference ``gamma_i - gamma_j`` with ``j <= i`` or
``gamma`` itself, so it is never positive: nothing overflows, however
strong the decay.

Decays and the solve run in float32; the matrix products take their
operands in ``matmul_dtype`` (the dtype of ``v`` unless stated; bf16 in a
bf16 model) and accumulate in float32. The backward pass is JAX's own
through this code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...tensor.tensor import apply_op

__all__ = ["causal_conv1d", "chunk_gated_delta_rule"]

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST    # float32 products in float32
# Chunks prepared together and replayed together in the backward pass: the
# rule's working set is one block's (1024 tokens at chunk 64) whatever T.
_BLOCK_CHUNKS = 16


def causal_conv1d(x, weight, activation=None, name=None):
    """Causal depthwise convolution along the sequence, no bias:
    ``y[:, t, c] = sum_j weight[c, j] * x[:, t - (K - 1) + j, c]`` with
    zeros before the sequence's start. ``x`` [B, T, C], ``weight`` [C, K];
    ``activation`` None or "silu". Accumulates in float32, returns ``x``'s
    dtype."""
    if activation not in (None, "silu"):
        raise ValueError(f"causal_conv1d: activation {activation!r}")

    def f(a, w):
        k, t = w.shape[1], a.shape[1]
        pad = jnp.pad(a, ((0, 0), (k - 1, 0), (0, 0))).astype(_F32)
        w = w.astype(_F32)
        y = sum(pad[:, j:j + t] * w[:, j] for j in range(k))
        if activation == "silu":
            y = jax.nn.silu(y)
        return y.astype(a.dtype)
    return apply_op(f, x, weight)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _inverse_unit_lower(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., n, n] in
    float32, ``n`` a power of two >= 8, as whole-matrix products in the
    array's own layout (a triangular solve is, on the v5e, one custom call
    of 5 ms a block of 16 chunks; gathering the diagonal blocks into axes
    of their own costs as much in copies: PERF.md section 6, PR 28).

    The 8 x 8 diagonal blocks ``d`` first: ``d`` is nilpotent of index 8,
    so ``(I + d)^-1 = (I - d)(I + d^2)(I + d^4)`` exactly, and no power
    past the 7th of an 8 x 8 block is formed, whose entries stay within 35
    times the inverse's (the same series over the whole chunk would cancel
    terms of 1e17). Then block forward substitution, doubling the block:
    with ``T`` the block-diagonal inverse so far and ``a21`` the blocks
    under its odd-numbered diagonal blocks, the inverse of each pair is
    ``[[T11, 0], [-T22 a21 T11, T22]] = T - T a21 T``."""
    n = a.shape[-1]
    ii = jnp.arange(n)

    def same(size):
        return (ii[:, None] // size) == (ii[None, :] // size)

    def mm(x, y):
        return jnp.matmul(x, y, precision=_EXACT)

    eye = jnp.eye(n, dtype=a.dtype)
    d = jnp.where(same(8), a, 0.0)
    d2 = mm(d, d)
    inv, size = mm(mm(eye - d, eye + d2), eye + mm(d2, d2)), 8
    while size < n:
        below = same(2 * size) & ~same(size) & (ii[:, None] > ii[None, :])
        inv = inv - mm(mm(inv, jnp.where(below, a, 0.0)), inv)
        size *= 2
    return inv


def _chunk_rule(q, k, v, g, beta, *, chunk, mm):
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, out = hv // hk, v.dtype      # each key head serves r value heads
    q = _l2norm(q.astype(_F32)) * dk ** -0.5
    k = _l2norm(k.astype(_F32))
    nb = min(_BLOCK_CHUNKS, -(-t // chunk))     # chunks a block
    pad = -t % (chunk * nb)         # a padded token decays nothing (g 0)
    n_blocks = (t + pad) // (chunk * nb)    # and writes nothing (beta, k 0)

    def blocks(x, heads, to):
        """[B, T, *heads-flat, ...] -> one leading entry a block."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n_blocks, nb, chunk) + heads + x.shape[3:])
        return x.transpose(to)

    q = blocks(q, (hk,), (1, 0, 4, 2, 3, 5))            # [G,B,hk,nb,C,dk]
    k = blocks(k, (hk,), (1, 0, 4, 2, 3, 5))
    v = blocks(v.astype(_F32), (hk, r), (1, 0, 4, 5, 2, 3, 6))
    g = blocks(g.astype(_F32), (hk, r), (1, 0, 4, 5, 2, 3))  # [G,B,hk,r,nb,C]
    beta = blocks(beta.astype(_F32), (hk, r), (1, 0, 4, 5, 2, 3))

    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(mm), y.astype(mm),
                          preferred_element_type=_F32)

    ii = jnp.arange(chunk)
    lower, strict = ii[:, None] >= ii[None, :], ii[:, None] > ii[None, :]

    def step(s, xs):
        """One chunk: ``s`` [B,hk,r,dk,dv] in float32."""
        w_n, u_n, qg_n, aqk_n, kd_n, last_n = xs
        vp = u_n - dot("bhrcd,bhrde->bhrce", w_n, s)
        o = dot("bhrcd,bhrde->bhrce", qg_n, s) + dot("bhrij,bhrje->bhrie",
                                                     aqk_n, vp)
        s = last_n[..., None, None] * s + dot("bhrcd,bhrce->bhrde", kd_n, vp)
        return s, o

    @jax.checkpoint
    def block_of_chunks(s, xs):
        """What needs no state for ``nb`` chunks at once, then the scan
        over them. Checkpointed: the backward pass keeps the state at the
        block's start and recomputes the block, so that the rule's working
        set is one block's and not the sequence's."""
        q_, k_, v_, g_, beta_ = xs
        gamma = jnp.cumsum(g_, axis=-1)                 # [B,hk,r,nb,C]
        # exp(gamma_i - gamma_j) where j <= i, 0 above the diagonal; masked
        # BEFORE the exponential, where the difference would be positive
        decay = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        kk = dot("bhnid,bhnjd->bhnij", k_, k_)[:, :, None]
        a = jnp.where(strict, beta_[..., None] * kk * decay, 0.0)
        kr = k_[:, :, None]                             # [B,hk,1,nb,C,dk]
        rhs = jnp.concatenate(
            [kr * (beta_ * jnp.exp(gamma))[..., None],
             v_ * beta_[..., None]], axis=-1)
        wu = jnp.matmul(_inverse_unit_lower(a), rhs, precision=_EXACT)
        qg = q_[:, :, None] * jnp.exp(gamma)[..., None]
        aqk = dot("bhnid,bhnjd->bhnij", q_, k_)[:, :, None] * decay
        kd = kr * jnp.exp(gamma[..., -1:] - gamma)[..., None]
        xs = (wu[..., :dk].astype(mm), wu[..., dk:], qg.astype(mm),
              aqk.astype(mm), kd.astype(mm), jnp.exp(gamma[..., -1]))
        s, o = jax.lax.scan(step, s, tuple(jnp.moveaxis(x, 3, 0) for x in xs))
        return s, o                                     # o [nb,B,hk,r,C,dv]

    _, o = jax.lax.scan(block_of_chunks,
                        jnp.zeros((b, hk, r, dk, dv), _F32),
                        (q, k, v, g, beta))
    # [G,nb,B,hk,r,C,dv] -> [B,(G,nb,C),(hk,r),dv]
    o = o.transpose(2, 0, 1, 5, 3, 4, 6).reshape(b, t + pad, hv, dv)
    return o[:, :t].astype(out)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=64,
                           matmul_dtype=None, name=None):
    """The gated delta rule's outputs ``o`` [B, T, hv, d_v] from ``q``,
    ``k`` [B, T, hk, d_k], ``v`` [B, T, hv, d_v], the log-decay ``g`` <= 0
    and the write strength ``beta`` [B, T, hv], from a zero state, in
    chunks of ``chunk_size`` tokens (any ``T``; the last chunk is padded).
    ``hk`` divides ``hv``: value head ``h`` reads key head ``h // (hv //
    hk)``. ``q`` and ``k`` are first divided by their norms over ``d_k``
    (in float32, ``x * rsqrt(sum(x^2) + 1e-6)``) and ``q`` multiplied by
    ``d_k ** -0.5``: that is part of the rule as the Gated DeltaNet layer
    uses it. The matrix products take their operands in
    ``matmul_dtype`` (default: ``v``'s dtype) and accumulate in float32; the
    result comes in ``v``'s dtype. 16 chunks at a time are prepared
    together and recomputed in the backward pass: the working set is one
    block's whatever ``T``."""
    if chunk_size < 8 or chunk_size & (chunk_size - 1):
        raise ValueError(f"chunk_gated_delta_rule: chunk_size {chunk_size} "
                         "is not a power of two >= 8")
    if v.shape[2] % q.shape[2]:
        raise ValueError(
            f"chunk_gated_delta_rule: {q.shape[2]} key heads do not divide "
            f"{v.shape[2]} value heads")

    def f(q_, k_, v_, g_, beta_):
        with jax.named_scope("gdn.chunk_rule"):
            return _chunk_rule(q_, k_, v_, g_, beta_, chunk=int(chunk_size),
                               mm=matmul_dtype or v_.dtype)
    return apply_op(f, q, k, v, g, beta)
