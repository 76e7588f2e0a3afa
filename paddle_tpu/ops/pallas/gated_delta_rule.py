"""The chunked gated delta rule's forward as one TPU Pallas (Mosaic) kernel.

``nn/functional/linear_attention.py`` defines the rule and its composite
(``_chunk_rule``); this is the same arithmetic with a chunk's working set
kept on the chip. The composite prepares 16 chunks at a time as batched
XLA products whose operands (the decay matrix, ``(I + A)^-1`` in ten
products, ``W``, ``U``: 64 x 64 and 64 x 256 float32 arrays) each travel
through HBM; here one grid step holds a tile of the sequence for one group
of value heads in VMEM, walks its chunks in order and carries the state
``S`` [d_k, d_v] in a VMEM scratch across the sequence, the last and
sequential grid axis.

Reads ``q``, ``k``, ``v`` where the mixer left them, ``[B, T, H * d]``
(the sequence in the sublanes, a head's features in the lanes), through
``BlockSpec``s of ``(1, tile, d)``: nothing is moved into another tiling
on the way in or out. The gates are 2 MiB each; the wrapper takes the
running sum of ``g`` inside each chunk and lays both out a chunk a row.

Value heads that share a key head share ``K K^T`` and ``Q K^T``, and a
64 x 64 float32 matrix fills half of a vector register's lanes and a
quarter of the 128 x 128 MXU. So an even group is handled two heads at a
time, SIDE BY SIDE in the lanes: ``[64, 128] = [X1 | X2]``. Element-wise
work then runs on full registers, and ``[X1 | X2] @ blockdiag(Y1, Y2) =
[X1 Y1 | X2 Y2]`` makes every product of the inverse one full-width
product. With an odd group the same code runs one head wide.

A chunk's state-free work is a chain of eleven dependent float32 products,
and the MXU takes products in program order: so four chunks are traced IN
STEP (``_fwd_kernel.together``), each filling the others' waits, and only
then does the state walk through them.

What is float32 in the composite is float32 here (the norms, the decays,
``(I + A)^-1`` and its product with ``[beta exp(gamma) K | beta V]`` at
``Precision.HIGHEST``, the state and every accumulation); the six
products that take ``matmul_dtype`` operands there take them here.

Besides ``o`` the kernel writes the state at every tile's start,
``f32[T / tile, B, hv, d_k, d_v]``: with the five inputs that is all the
backward pass needs (it replays a tile from its start state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

__all__ = ["gdn_chunk_rule_fwd", "is_supported"]

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST
CHUNK = 64              # the one chunk size the kernel is written for
# q, k, v and o tiles, double-buffered, of the 16 MiB a kernel may ask for
_TILE_BYTES = 8 << 20


def _pack(hk, hv):
    """Value heads handled side by side: two where a key head serves an
    even number of them."""
    return 2 if (hv // hk) % 2 == 0 else 1


def is_supported(q_shape, v_shape, chunk, dtypes, matmul_dtype, mesh,
                 block_chunks) -> bool:
    """Whether ``gdn_chunk_rule_fwd`` takes this call: on a TPU
    (``_pallas._enabled()``), chunks of 64 tokens, ``d_k`` and ``d_v``
    multiples of 128 whose tiles fit VMEM, key heads that divide the value
    heads, float32 or bf16 operands, and no multi-device mesh (a Mosaic
    kernel cannot sit under automatic partitioning, and no per-shard form
    is written). From shapes, dtypes, backend and mesh alone;
    ``block_chunks`` is the caller's tile, in chunks."""
    if not _pallas._enabled() or chunk != CHUNK:
        return False
    if len(q_shape) != 4 or len(v_shape) != 4:
        return False
    (hk, dk), (hv, dv) = q_shape[2:], v_shape[2:]
    if dk % 128 or dv % 128 or hv % hk:
        return False
    tile = block_chunks * chunk * 4 * 2
    if tile * (2 * dk + 2 * _pack(hk, hv) * dv) > _TILE_BYTES:
        return False
    floats = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    if any(jnp.dtype(d) not in floats for d in (*dtypes, matmul_dtype)):
        return False
    return mesh is None or mesh.devices.size == 1


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, st_ref, s_ref,
                *, nb, pack, dk, dv, mm, ahead):
    """One tile of ``nb`` chunks for ``pack`` value heads of one key head.
    ``gam_ref`` / ``beta_ref`` [1, 1, nb, pack * C]: a chunk a row, the
    heads side by side. ``s_ref`` [pack, dk, dv] carries the state. The
    chunks go ``ahead`` at a time: what needs no state in step for all of
    them, then the state through them in order."""
    c_, width = CHUNK, pack * CHUNK
    shift = c_.bit_length() - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
    st_ref[0, 0, 0] = s_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (c_, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c_, width), 1)
    col, head = lane & (c_ - 1), lane >> shift
    lower, strict, eye = row >= col, row > col, row == col
    eye_f = jnp.where(eye, 1.0, 0.0).astype(_F32)
    r2 = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    c2 = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    own = (r2 >> shift) == (c2 >> shift)

    def same(size):
        s = size.bit_length() - 1
        return (row >> s) == (col >> s)

    def diag(x):
        """[X1 | X2] -> blockdiag(X1, X2)."""
        return jnp.where(own, jnp.concatenate([x] * pack, axis=0), 0.0)

    def exact(x, y):
        return jnp.dot(x, y, precision=_EXACT, preferred_element_type=_F32)

    def mmx(x, y):
        """[X1 Y1 | X2 Y2] in float32."""
        return exact(x, diag(y))

    def inverse(a):
        """``linear_attention._inverse_unit_lower`` on the heads side by
        side: the 8 x 8 diagonal blocks by their exact series, then block
        forward substitution, doubling the block. Yields after each product
        (``together`` says why)."""
        d = jnp.where(same(8), a, 0.0)
        d2 = mmx(d, d)
        yield
        inv = mmx(eye_f - d, eye_f + d2)
        d4 = mmx(d2, d2)
        yield
        inv, size = mmx(inv, eye_f + d4), 8
        while size < c_:
            yield
            below = same(2 * size) & ~same(size) & strict
            step = mmx(inv, jnp.where(below, a, 0.0))
            yield
            inv = inv - mmx(step, inv)
            size *= 2
        return inv

    def columns(x):
        """A chunk's row [1, pack * C] -> one [C, 1] column a head (the
        diagonal of the row spread over the sublanes, summed: exact)."""
        e = jnp.where(eye, x, 0.0)
        return [jnp.sum(jnp.where(head == p, e, 0.0), axis=1, keepdims=True)
                for p in range(pack)]

    def beside(cols):
        """One [C, 1] column a head -> [C, pack * C], each over its lanes."""
        x = jnp.broadcast_to(cols[0], (c_, width))
        for p in range(1, pack):
            x = jnp.where(head == p, cols[p], x)
        return x

    def dot(x, y, dims=((1,), (0,))):
        return jax.lax.dot_general(x.astype(mm), y.astype(mm), (dims, ((), ())),
                                   preferred_element_type=_F32)

    def prepare(c):
        """What needs no state, for chunk ``c`` of the tile (a generator:
        ``together``)."""
        rows = pl.ds(pl.multiple_of(c * c_, c_), c_)
        q = _l2norm(q_ref[0, rows, :].astype(_F32)) * dk ** -0.5
        k = _l2norm(k_ref[0, rows, :].astype(_F32))
        gam_row = gam_ref[0, 0, pl.ds(c, 1), :]
        gam, beta = columns(gam_row), columns(beta_ref[0, 0, pl.ds(c, 1), :])
        # exp(gamma_i - gamma_j) where j <= i; masked BEFORE the exponential
        decay = jnp.exp(jnp.where(lower, beside(gam) - gam_row, -jnp.inf))
        k_rep = jnp.concatenate([k.astype(mm)] * pack, axis=0)
        kk = dot(k, k_rep, ((1,), (1,)))                    # [K K^T | K K^T]
        qk = dot(q, k_rep, ((1,), (1,)))
        yield
        a = jnp.where(strict, beside(beta) * kk * decay, 0.0)
        rhs = jnp.concatenate([jnp.concatenate(
            [k * (beta[p] * jnp.exp(gam[p])),
             v_ref[0, rows, p * dv:(p + 1) * dv].astype(_F32) * beta[p]],
            axis=1) for p in range(pack)], axis=0)          # [pack * C, dk+dv]
        inv = yield from inverse(a)
        yield
        wu = exact(diag(inv), rhs)
        aqk = diag(qk * decay).astype(mm)
        heads = []
        for p in range(pack):
            last = gam[p][c_ - 1:c_, :]                     # [1, 1]
            heads.append((
                wu[p * c_:(p + 1) * c_, :dk].astype(mm),
                wu[p * c_:(p + 1) * c_, dk:],
                (q * jnp.exp(gam[p])).astype(mm),
                (k * jnp.exp(last - gam[p])).astype(mm), jnp.exp(last)))
        return rows, aqk, heads

    def advance(rows, aqk, heads):
        """The four products with the state, and the state's step."""
        states = [s_ref[p] for p in range(pack)]
        vps = [u - dot(w, s) for (w, u, _, _, _), s in zip(heads, states)]
        inner = dot(aqk, jnp.concatenate(vps, axis=0))      # [pack * C, dv]
        for p, ((_, _, qg, kd, last), s, vp) in enumerate(
                zip(heads, states, vps)):
            o = dot(qg, s) + inner[p * c_:(p + 1) * c_]
            o_ref[0, rows, p * dv:(p + 1) * dv] = o.astype(o_ref.dtype)
            s_ref[p] = last * s + dot(kd, vp, ((0,), (0,)))

    def together(chunks):
        """``prepare`` for several chunks in step. The chain of eleven
        dependent float32 products is one chunk's critical path, and the MXU
        takes its products in program order: traced one chunk after the
        other, the second chunk's chain starts when the first one's ends.
        Each generator yields where a product's result is next needed, so
        the chunks' products alternate and one fills the other's waits."""
        gens = [prepare(c) for c in chunks]
        live, done = list(gens), {}
        while live:
            for gen in list(live):
                try:
                    next(gen)
                except StopIteration as end:
                    done[gen] = end.value
                    live.remove(gen)
        return [done[gen] for gen in gens]

    def body(i, carry):
        for ready in together([i * ahead + j for j in range(ahead)]):
            advance(*ready)
        return carry
    jax.lax.fori_loop(0, nb // ahead, body, 0)


@functools.partial(jax.jit, static_argnames=("mm", "block_chunks"))
def gdn_chunk_rule_fwd(q, k, v, g, beta, *, mm, block_chunks):
    """``o`` [B, T, hv, d_v] in ``v``'s dtype and the state at the start of
    every tile of ``block_chunks`` chunks, ``f32[tiles, B, hv, d_k, d_v]``,
    of the gated delta rule in chunks of 64 tokens from a zero state.
    ``q``, ``k`` [B, T, hk, d_k], ``v`` [B, T, hv, d_v], ``g``, ``beta``
    [B, T, hv]; ``mm`` is the matrix products' operand dtype. A ``T`` that
    is no whole number of tiles is padded as the composite pads it: a
    padded token decays nothing (``g`` 0) and writes nothing (``beta``,
    ``k`` 0)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    pack = _pack(hk, hv)
    nb = min(block_chunks, -(-t // CHUNK))
    tile = nb * CHUNK
    pad = -t % tile
    tiles, chunks = (t + pad) // tile, (t + pad) // CHUNK

    def flat(x):            # [B, T, H, d] -> [B, T', H * d]: no move
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(b, t + pad, -1)

    def rows(x):            # [B, T', hv] -> [B, hv / pack, chunks, pack * C]
        x = x.reshape(b, chunks, CHUNK, hv // pack, pack)
        return x.transpose(0, 3, 1, 4, 2).reshape(b, hv // pack, chunks,
                                                  pack * CHUNK)
    g, beta = (jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0)))
               for x in (g, beta))
    gamma = jnp.cumsum(g.reshape(b, chunks, CHUNK, hv), axis=2)
    r = hv // hk
    # chunks prepared in step (``_fwd_kernel.together``): one chunk at a time
    # takes 9.9 ms at Qwen3-Next's 2 x 8192 x 32 heads on a v5e, two 6.4,
    # four 5.7; eight schedule no denser than four (PERF.md section 6, PR 31)
    ahead = next(n for n in (4, 2, 1) if nb % n == 0)

    def seq(width, head):
        return pl.BlockSpec((1, tile, width),
                            lambda b_, p_, t_: (b_, t_, head(p_)))
    gate = pl.BlockSpec((1, 1, nb, pack * CHUNK),
                        lambda b_, p_, t_: (b_, p_, t_, 0))
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, nb=nb, pack=pack, dk=dk, dv=dv,
                          mm=jnp.dtype(mm), ahead=ahead),
        grid=(b, hv // pack, tiles),
        in_specs=[seq(dk, lambda p_: p_ * pack // r),
                  seq(dk, lambda p_: p_ * pack // r),
                  seq(pack * dv, lambda p_: p_), gate, gate],
        out_specs=[
            seq(pack * dv, lambda p_: p_),
            pl.BlockSpec((1, 1, 1, pack, dk, dv),
                         lambda b_, p_, t_: (t_, b_, p_, 0, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, t + pad, hv * dv), v.dtype),
            jax.ShapeDtypeStruct((tiles, b, hv // pack, pack, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((pack, dk, dv), _F32)],
        name="gdn_chunk_rule_fwd",
        interpret=_pallas._interpret(),
    )(flat(q), flat(k), flat(v), rows(gamma), rows(beta))
    return (o[:, :t].reshape(b, t, hv, dv),
            states.reshape(tiles, b, hv, dk, dv))
