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
bf16 model) and accumulate in float32. The convolution's backward pass is
written out (``_causal_conv_bwd``).

Two forms of one arithmetic, chosen per call and for BOTH passes from
backend, shapes, dtypes and mesh by
``ops/pallas/gated_delta_rule.is_supported`` (on a TPU, chunks of 64, heads
of a multiple of 128, no multi-device mesh): the KERNELS
``gdn_chunk_rule_fwd`` and ``gdn_chunk_rule_bwd``, which keep a chunk's
matrices, the state and its gradient in VMEM (the forward saves the state
at each tile's start; the backward walks the tiles from the last, prepares
a tile's chunks again and carries ``dS``), and the COMPOSITE ``_chunk_rule``
below, batched XLA products over blocks of 16 chunks, whose backward pass is
JAX's own through its checkpointed scan. ``block_of_chunks`` is the one
definition of the rule outside the kernels.

Layout. The TPU keeps the last two axes of an array in (8, 128) tiles: 8
rows in the sublanes, 128 columns in the lanes. A ``[B, T, H * 128]``
array therefore already lies as the rule wants it: the sequence in the
sublanes, one head's 128 features in the lanes, a head a range of lane
tiles, a chunk of 64 tokens a range of 8 row tiles. Reshaping it to ``[B,
T, H, 128]`` is free only as long as nothing asks for ``H`` in the
sublanes: XLA has no name for "rows of 8, then heads, then the row in the
tile" on a 4-axis shape, so a reduction over the head's features, a
transposition that ends ``[..., T', H, 128]`` or a size-2 axis next to
the features each make it copy the array into another tiling (at 2 x 8192
tokens, 128 to 256 MiB a copy; PERF.md section 6, PR 29). What is free is
to split the sequence into ``(T / 8, 8)`` FIRST and move the heads in
front of the 8: then every step is a move of whole tiles, or no move at
all. ``_chunk_rule`` forms its blocks that way and returns its result by
the mirrored path; a caller that works on heads (the layer's gated norm)
does the same with ``TILE_ROWS``. The kernel reads the same layout in
place, through ``BlockSpec``s of ``(1, 16 chunks, heads * 128)``, and writes
its result the same way, as the backward kernel does with the gradients:
only the composite still moves tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...inference.telemetry import runtime_counter
from ...ops.pallas import gated_delta_rule
from ...parallel import current_mesh
from ...tensor.tensor import apply_op, kept_over_replay, kept_region_open

__all__ = ["causal_conv1d", "chunk_gated_delta_rule"]

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST    # float32 products in float32
# Chunks prepared together and replayed together in the backward pass: the
# rule's working set is one block's (1024 tokens at chunk 64) whatever T.
_BLOCK_CHUNKS = 16
# Rows of one (8, 128) tile (the module docstring's "Layout").
TILE_ROWS = 8


def _shifted(a, k, shift):
    """The ``k`` views ``a[:, t + j - shift]``, ``j < k``, of ``a`` [B, T, C]
    in float32, zeros outside the sequence."""
    t = a.shape[1]
    a = jnp.pad(a.astype(_F32), ((0, 0), (shift, k - 1 - shift), (0, 0)))
    return [a[:, j:j + t] for j in range(k)]


def _taps(views, w):
    return sum(x * w[:, j] for j, x in enumerate(views))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_conv(a, w, silu):
    """``causal_conv1d`` on arrays, for a caller that is inside an op of its
    own (the Gated DeltaNet layer)."""
    k = w.shape[1]
    y = _taps(_shifted(a, k, k - 1), w.astype(_F32))
    return (jax.nn.silu(y) if silu else y).astype(a.dtype)


def _causal_conv_fwd(a, w, silu):
    return causal_conv(a, w, silu), (a, w)


def _causal_conv_bwd(silu, res, dy):
    """Keeps the input and recomputes the sum (four multiply-adds an
    element; behind a barrier, or XLA keeps the first sum instead); each
    tap of the weight's gradient is REDUCED as it is multiplied: JAX's own
    transpose writes the ``K`` products ``[B, T, C]`` out before it sums
    them to ``[C, K]``. The input's gradient is the same sum run the other
    way: taps reversed, zeros after the sequence's end."""
    a, w = res
    a, dy = jax.lax.optimization_barrier((a, dy))
    k, w32, dy = w.shape[1], w.astype(_F32), dy.astype(_F32)
    views = _shifted(a, k, k - 1)
    if silu:
        dy, = jax.vjp(jax.nn.silu, _taps(views, w32))[1](dy)
    dw = jnp.stack([jnp.sum(x * dy, axis=(0, 1)) for x in views], axis=1)
    da = _taps(_shifted(dy, k, 0), w32[:, ::-1])
    return da.astype(a.dtype), dw.astype(w.dtype)


causal_conv.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def causal_conv1d(x, weight, activation=None, name=None):
    """Causal depthwise convolution along the sequence, no bias:
    ``y[:, t, c] = sum_j weight[c, j] * x[:, t - (K - 1) + j, c]`` with
    zeros before the sequence's start. ``x`` [B, T, C], ``weight`` [C, K];
    ``activation`` None or "silu". Accumulates in float32, returns ``x``'s
    dtype."""
    if activation not in (None, "silu"):
        raise ValueError(f"causal_conv1d: activation {activation!r}")
    return apply_op(lambda a, w: causal_conv(a, w, activation == "silu"),
                    x, weight)


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


def _composite(q_shape, v_shape, out, chunk, mm):
    """The rule's composite for these shapes, in three pieces:
    ``to_blocks(q, k, v, g, beta)`` (the operands a block of ``nb`` chunks,
    [G, B, ...]), ``block_of_chunks(s, xs)`` (one block from its start state
    ``s`` [B,hk,r,dk,dv]: the one definition of the rule outside the
    kernels) and ``from_blocks(o)`` ([G,nb,B,hk,r,C,dv] -> [B,T,hv,dv] in
    ``out``); then the shape of ``s``."""
    b, t, hk, dk = q_shape
    hv, dv = v_shape[2], v_shape[3]
    r = hv // hk                    # each key head serves r value heads
    nb = min(_BLOCK_CHUNKS, -(-t // chunk))     # chunks a block
    pad = -t % (chunk * nb)         # a padded token decays nothing (g 0)
    n_blocks = (t + pad) // (chunk * nb)    # and writes nothing (beta, k 0)

    def blocks(x, heads):
        """[B, T, H, d] -> [G, B, *heads, nb, C, d]: the sequence splits
        into (G, nb, C/8, 8) FIRST, so that what then moves past the heads
        is whole (8, d) tiles."""
        x = jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        d = x.shape[-1]
        x = x.reshape(b, n_blocks, nb, chunk // TILE_ROWS, TILE_ROWS, -1, d)
        x = x.transpose(1, 0, 5, 2, 3, 4, 6)
        return x.reshape((n_blocks, b) + heads + (nb, chunk, d))

    def gate_blocks(x):
        """[B, T, hv] -> [G, B, hk, r, nb, C]: a few MiB, any way will do."""
        x = jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0)))
        x = x.reshape(b, n_blocks, nb, chunk, hk, r)
        return x.transpose(1, 0, 4, 5, 2, 3)

    def to_blocks(q, k, v, g, beta):
        return (blocks(q, (hk,)), blocks(k, (hk,)),     # [G,B,hk,nb,C,dk]
                blocks(v, (hk, r)),                     # [G,B,hk,r,nb,C,dv]
                gate_blocks(g), gate_blocks(beta))

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

    def block_of_chunks(s, xs):
        """What needs no state for ``nb`` chunks at once, then the scan
        over them."""
        q_, k_, v_, g_, beta_ = xs
        q_, k_ = _l2norm(q_) * dk ** -0.5, _l2norm(k_)
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

    def from_blocks(o):
        """[G,nb,B,hk,r,C,dv] -> [B,T,hv,dv] by the mirrored path."""
        o = o.reshape(n_blocks, nb, b, hv, chunk // TILE_ROWS, TILE_ROWS, dv)
        o = o.transpose(2, 0, 1, 4, 5, 3, 6).reshape(b, t + pad, hv, dv)
        return o[:, :t].astype(out)

    return to_blocks, block_of_chunks, from_blocks, (b, hk, r, dk, dv)


def _chunk_rule(q, k, v, g, beta, *, chunk, mm):
    """The composite, whole. ``block_of_chunks`` is checkpointed: the
    backward pass keeps the state at each block's start and recomputes the
    block, so that the rule's working set is one block's and not the
    sequence's."""
    to_blocks, block_of_chunks, from_blocks, state = _composite(
        q.shape, v.shape, v.dtype, chunk, mm)
    _, o = jax.lax.scan(jax.checkpoint(block_of_chunks),
                        jnp.zeros(state, _F32), to_blocks(q, k, v, g, beta))
    return from_blocks(o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_rule(q, k, v, g, beta, mm):
    """The rule as ``ops/pallas/gated_delta_rule``'s two kernels (chunks of
    64): the forward saves the state at each tile's start, the backward
    takes the five inputs, those states and ``o``'s cotangent."""
    return _kernel_rule_fwd(q, k, v, g, beta, mm)[0]


def _rule_kernel(q, k, v, g, beta, mm):
    return gated_delta_rule.gdn_chunk_rule_fwd(
        q, k, v, g, beta, mm=mm, block_chunks=_BLOCK_CHUNKS)


def _kernel_rule_fwd(q, k, v, g, beta, mm):
    o, states = _rule_kernel(q, k, v, g, beta, mm)
    return o, (q, k, v, g, beta, states)


def _kernel_rule_bwd(mm, res, do):
    runtime_counter("paddle_gdn_rule_bwd_kernel_traces_total", 1)
    return gated_delta_rule.gdn_chunk_rule_bwd(
        *res, do, mm=mm, block_chunks=_BLOCK_CHUNKS)


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kernel_rule_kept(q, k, v, g, beta, o, states, mm):
    """``_kernel_rule`` whose forward kernel has run: ``o`` and ``states``
    are what it wrote. The backward is ``_kernel_rule_bwd`` on them."""
    return o


def _kernel_rule_kept_fwd(q, k, v, g, beta, o, states, mm):
    return o, (q, k, v, g, beta, states)


def _kernel_rule_kept_bwd(mm, res, do):
    return _kernel_rule_bwd(mm, res, do) + (None, None)


_kernel_rule_kept.defvjp(_kernel_rule_kept_fwd, _kernel_rule_kept_bwd)


def _kernel_rule_in_region(q, k, v, g, beta, mm):
    """``_kernel_rule`` inside a ``fleet.utils.recompute`` region: the
    kernel runs in the region's first forward alone, and the replay gets its
    ``o`` and ``states`` back (``tensor.kept_over_replay``)."""
    arrays = (q, k, v, g, beta)
    o, states = kept_over_replay(
        "gdn_chunk_rule_fwd",
        tuple((x.shape, x.dtype) for x in arrays) + (mm,),
        lambda: _rule_kernel(*arrays, mm))
    return _kernel_rule_kept(*arrays, o, states, mm)


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
    together and prepared again in the backward pass: the working set is one
    block's whatever ``T``. Takes the Pallas kernel where
    ``gated_delta_rule.is_supported`` says so and the composite elsewhere
    (the module docstring); ``paddle_gdn_rule_kernel_traces_total`` or
    ``paddle_gdn_rule_composite_traces_total`` counts each trace, and
    ``paddle_gdn_rule_bwd_kernel_traces_total`` each trace of the kernels'
    backward."""
    if chunk_size < 8 or chunk_size & (chunk_size - 1):
        raise ValueError(f"chunk_gated_delta_rule: chunk_size {chunk_size} "
                         "is not a power of two >= 8")
    if v.shape[2] % q.shape[2]:
        raise ValueError(
            f"chunk_gated_delta_rule: {q.shape[2]} key heads do not divide "
            f"{v.shape[2]} value heads")

    mesh = current_mesh()

    def f(*arrays):
        mm = jnp.dtype(matmul_dtype or arrays[2].dtype)
        kernel = gated_delta_rule.is_supported(
            arrays[0].shape, arrays[2].shape, int(chunk_size),
            [a.dtype for a in arrays], mm, mesh, _BLOCK_CHUNKS)
        runtime_counter("paddle_gdn_rule_kernel_traces_total" if kernel
                        else "paddle_gdn_rule_composite_traces_total", 1)
        with jax.named_scope("gdn.chunk_rule"):
            if kernel:
                rule = (_kernel_rule_in_region if kept_region_open()
                        else _kernel_rule)
                return rule(*arrays, mm)
            return _chunk_rule(*arrays, chunk=int(chunk_size), mm=mm)
    return apply_op(f, q, k, v, g, beta)
