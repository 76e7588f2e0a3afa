"""Blockwise (o, lse) attention chunk for ring attention, as Pallas kernels.

Capability parity: the per-step compute of ring-flash-attention (the
Paddle-ecosystem long-context variant SURVEY §5.7 names; upstream anchor
`sep` degree in python/paddle/distributed/fleet/base/topology.py). The
inter-chip ring (ppermute schedule, lse merge, remat) lives in
paddle_tpu/parallel/context_parallel.py — THIS module is the on-chip leg:
one Q chunk against one visiting KV chunk, returning the normalized chunk
output AND its per-row log-sum-exp so chunks merge exactly.

Differences from flash_attention.py (why a separate module, not a flag):

* the causal boundary is a TRACED offset, not a static one — in the ring,
  the same compiled kernel serves every (my_rank - src_rank) diagonal:
  row r attends col c iff c <= r + offset. offset >= Sk-1 degenerates to
  full attention, offset < 0 shifts the diagonal (zigzag schedules),
  offset <= -Sq masks everything (lse -> -inf rows that merge as zero
  weight). It rides in SMEM; the block-skip predicate stays traced.
* lse is a first-class OUTPUT with a gradient: ring merges weight chunks
  by lse, so the chunk vjp receives (dO, dlse). The lse cotangent folds
  into the standard FA backward exactly — d s = P∘(dP - delta + dlse)
  row-broadcast — so the backward kernels take delta_eff = rowsum(dO∘O)
  - dlse and are otherwise the textbook split dKV/dQ pair.
* no dropout (the reference's CP stack does not thread attention dropout
  through the ring either); GQA via the same index_map trick.

Layout: [B, H, S, D] (kernel layout; context_parallel transposes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas
from .flash_attention import NEG_INF, _block_sizes

__all__ = ["ring_chunk_attention", "is_supported"]


def is_supported(q_shape, k_shape, dtype) -> bool:
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    if q_shape[-1] > 256:
        return False
    if q_shape[1] % k_shape[1] != 0:   # GQA: kv_heads | q_heads ([B,H,S,D])
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, sq, sk, bq, bk):
    off = off_ref[0]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    q_start = qi * bq
    k_start = ki * bk
    # block-skip on the traced diagonal: any row of this q block may see
    # the first col of this k block only if k_start <= q_end + off
    run = q_start + bq - 1 + off >= k_start

    @pl.when(run)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (cols < sk) & (cols <= rows + off)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:] = m_new
        v = v_ref[0, 0]
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + pv

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        # fully-masked rows keep lse ~ NEG_INF so the ring merge gives
        # them zero weight (matches the composite _chunk_attn contract)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF, m_sc[:] + jnp.log(l_safe))


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                    *, scale, sq, sk, bq, bk):
    off = off_ref[0]
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q_start = qi * bq
    k_start = ki * bk
    run = q_start + bq - 1 + off >= k_start

    @pl.when(run)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (cols < sk) & (rows < sq) & (cols <= rows + off)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)

        dv_sc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_sc, *, scale, sq, sk, bq, bk):
    off = off_ref[0]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q_start = qi * bq
    k_start = ki * bk
    run = q_start + bq - 1 + off >= k_start

    @pl.when(run)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (cols < sk) & (rows < sq) & (cols <= rows + off)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _sds(shape, dtype, *likes):
    """ShapeDtypeStruct carrying the union of the inputs' varying-mesh-axes
    (vma) type — required when the kernel runs INSIDE shard_map (jax>=0.9
    check_vma: out_shape.vma must not be None there). Outside shard_map
    the inputs' vma is empty/absent and a plain struct is returned."""
    vma = frozenset()
    have = False
    for a in likes:
        v = getattr(jax.typeof(a), "vma", None)
        if v is not None:
            have = True
            vma |= frozenset(v)
    if have and vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_s(x, target):
    s = x.shape[2]
    return jnp.pad(x, ((0, 0), (0, 0), (0, target - s), (0, 0))) \
        if target != s else x


def _specs(bq, bk, d, group):
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kspec = pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    return qspec, kspec, rowspec


def _fwd(q, k, v, offset, scale):
    b, h, sq, d = q.shape
    hk = k.shape[1]
    group = h // hk
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk)
    sq_p = math.ceil(sq / bq) * bq
    sk_p = math.ceil(sk / bk) * bk
    q_ = _pad_s(q, sq_p)
    k_, v_ = _pad_s(k, sk_p), _pad_s(v, sk_p)
    qspec, kspec, _ = _specs(bq, bk, d, group)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, sq=sq, sk=sk,
                          bq=bq, bk=bk),
        grid=(b, h, sq_p // bq, sk_p // bk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), qspec, kspec,
                  kspec],
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, bq, 1),
                                lambda b_, h_, i, j: (b_, h_, i, 0))],
        out_shape=[
            _sds((b, h, sq_p, d), q.dtype, q_, k_, v_),
            _sds((b, h, sq_p, 1), jnp.float32, q_, k_, v_),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="ring_chunk_attention_fwd",
        interpret=_pallas._interpret(),
    )(jnp.reshape(offset.astype(jnp.int32), (1,)), q_, k_, v_)
    return o[:, :, :sq], lse[:, :, :sq, 0]        # lse: [B, H, Sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ring_chunk(q, k, v, offset, scale):
    return _fwd(q, k, v, offset, scale)


def _vjp_fwd(q, k, v, offset, scale):
    o, lse = _fwd(q, k, v, offset, scale)
    return (o, lse), (q, k, v, o, lse, offset)


def _vjp_bwd(scale, res, cts):
    do, dlse = cts
    q, k, v, o, lse, offset = res
    b, h, sq, d = q.shape
    hk = k.shape[1]
    group = h // hk
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk)
    sq_p = math.ceil(sq / bq) * bq
    sk_p = math.ceil(sk / bk) * bk

    # the lse cotangent folds into the delta row-broadcast exactly:
    # ds = P∘(dP - rowsum(dO∘O) + dlse)
    delta_eff = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True) - dlse[..., None]

    q_, do_ = _pad_s(q, sq_p), _pad_s(do, sq_p)
    k_, v_ = _pad_s(k, sk_p), _pad_s(v, sk_p)
    lse_ = _pad_s(lse[..., None], sq_p)
    delta_ = _pad_s(delta_eff, sq_p)
    off = jnp.reshape(offset.astype(jnp.int32), (1,))

    kvq = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, j, i: (b_, h_, i, 0))
    kvk = pl.BlockSpec((1, 1, bk, d),
                       lambda b_, h_, j, i, g=group: (b_, h_ // g, j, 0))
    kvrow = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, j, i: (b_, h_, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, sq=sq, sk=sk,
                          bq=bq, bk=bk),
        grid=(b, h, sk_p // bk, sq_p // bq),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  kvq, kvk, kvk, kvq, kvrow, kvrow],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            _sds((b, h, sk_p, d), jnp.float32, q_, k_, v_, do_),
            _sds((b, h, sk_p, d), jnp.float32, q_, k_, v_, do_),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="ring_chunk_attention_bwd_dkv",
        interpret=_pallas._interpret(),
    )(off, q_, k_, v_, do_, lse_, delta_)

    qspec, kspec, rowspec = _specs(bq, bk, d, group)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, sq=sq, sk=sk,
                          bq=bq, bk=bk),
        grid=(b, h, sq_p // bq, sk_p // bk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=_sds((b, h, sq_p, d), q.dtype, q_, k_, v_, do_),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="ring_chunk_attention_bwd_dq",
        interpret=_pallas._interpret(),
    )(off, q_, k_, v_, do_, lse_, delta_)

    dq = dq[:, :, :sq]
    dk = dk[:, :, :sk]
    dv = dv[:, :, :sk]
    if group > 1:
        dk = dk.reshape(b, hk, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, hk, group, sk, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None


_ring_chunk.defvjp(_vjp_fwd, _vjp_bwd)


def ring_chunk_attention(q, k, v, offset, scale=None):
    """One ring step: normalized chunk attention + lse, offset-masked.

    q: [B, H, Sq, D]; k, v: [B, Hk, Sk, D] (GQA: Hk | H); offset: traced
    int32 scalar — row r attends col c iff c <= r + offset (offset >=
    Sk-1 == full attention, offset <= -Sq == fully masked). Returns
    (o [B, H, Sq, D] in q.dtype, lse [B, H, Sq] fp32). Differentiable,
    including through lse (ring-merge weights).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_chunk(q, k, v, jnp.asarray(offset, jnp.int32),
                       float(scale))
