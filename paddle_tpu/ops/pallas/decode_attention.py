"""Flash-decode attention against a KV cache, as a TPU Pallas kernel.

Capability parity: the attention inner loop of
paddle/fluid/operators/fused/fused_multi_transformer_op.cu ::
FusedMultiTransformerOp (masked decode attention over the growing KV cache,
cuBLASLt + fmha_ref.h in the reference). NOT a port: this is the
online-softmax flash layout for TPU — the query tile (decode: a handful of
rows, padded to the 8-row sublane minimum) stays resident in VMEM while KV
cache blocks stream through, with per-batch valid-length masking read from
SMEM so one compiled kernel serves every step of the autoregressive loop
(static shapes: cache is a fixed ring buffer, the length is data).

q: [B, Sq, H, D] (Sq small — 1 for greedy decode), cache: [B, Smax, Hk, D]
(GQA: Hk | H), cache_lens: [B] int32 valid prefix lengths. New tokens at
positions cache_lens..cache_lens+Sq-1 attend causally among themselves and
fully to the cache prefix. Forward-only (inference).

The `cache_lens < Smax` invariant (write kernels clamp a full row's write
to a drop) has FIVE clients: the serving engine's eviction-as-data slot
reuse, the submit-time `prompt + max_new_tokens <= Smax` bound, the
prefix cache's block-granular adopt copy (inference/prefix_cache.py) —
adopted block writes land at positions < plen <= Smax - max_new_tokens
with the pow-2 ladder tail masked out of bounds and dropped, so a
block-granular splat can never push a row to (or past) Smax either —
the speculative-decoding verify step (inference/spec_decode.py +
generation._build_verify_core): its K+1 block writes at positions
lens..lens+K are per-position masked to `lens + j < Smax` (masked
positions scatter out of bounds and drop), and drafting caps K at the
row's remaining budget, so lens + dlen <= prompt + max_new - 1 < Smax —
and the PAGED write path (inference/paged_kv.py + the paged branches in
generation._build_step_core): every K/V write resolves position t to
(block_tables[b, t // Bt], t % Bt), a masked row's position Smax maps
to table index Smax/Bt which is re-pointed at the OUT-OF-BOUNDS
sentinel block `num_blocks` and dropped, and an unmapped table entry
holds the same sentinel — so a write past a slot's mapped blocks (or
any masked write) lands nowhere, exactly the dense clamp's discipline.
Smax % Bt == 0 is asserted at BlockPool construction with a clear
error, so the table arithmetic can never itself gather out of bounds.

A SIXTH client rides the verify step's discipline: the token-budget
scheduler's budget core (generation._build_budget_core, serving's
chunked prefill + decode packing) writes per-row SEGMENTS at positions
lens..lens+seg-1 through the same spec_hidden write-masked path —
validity is (col < seg) & (pos < Smax), decode segments stay under the
submit-time budget exactly like drafts, and prefill segments stay
under plen <= Smax - max_new by construction.

The SEVENTH client is the FLAT budget core
(generation._build_flat_budget_core, serving's
PADDLE_SERVING_FLAT_BUDGET token-flattened dispatch): every token of
the ragged [T] stream scatters to (slot[t], pos[t]) — a padding token
carries the slot SENTINEL B, which resolves to batch index B (dense
ring: out of bounds on the batch axis) or to the pool's sentinel
block `num_blocks` (paged), so mode="drop" skips it; real tokens
inherit the submit-time `prompt + max_new <= Smax` bound through the
packer (a segment's positions are lens..lens+seg-1, exactly the
budget core's window), so `pos < Smax` holds for every landed write.
Both flat READ kernels consume that discipline: the fp flavor
(decode_attention_paged_flat) and the int8 flavor
(decode_attention_paged_flat_i8, which dequants the quantized pool +
its mirrored scales in kernel) address blocks through the same
chunk-clamped table translation, so every position a flat chunk can
attend was landed under the packer's `pos < Smax` bound.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

__all__ = ["decode_attention", "decode_attention_stacked",
           "decode_attention_stacked_i8", "decode_attention_stacked_write",
           "decode_attention_stacked_i8_write",
           "decode_attention_paged", "decode_attention_paged_i8",
           "decode_attention_paged_flat", "decode_attention_paged_flat_i8",
           "is_supported", "stacked_is_supported",
           "stacked_i8_is_supported", "stacked_write_is_supported",
           "stacked_i8_write_is_supported", "paged_is_supported",
           "paged_i8_is_supported", "paged_flat_is_supported",
           "paged_flat_i8_is_supported", "FLAT_CHUNK"]

NEG_INF = -1e30


def is_supported(q_shape, cache_shape, dtype) -> bool:
    if len(q_shape) != 4 or len(cache_shape) != 4:
        return False
    if q_shape[-1] > 256 or q_shape[1] > 128:
        return False
    if q_shape[2] % cache_shape[2] != 0:
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _online_softmax_block(q, k, v, n_valid, k_start, acc_sc, m_sc, l_sc,
                          *, scale, sq, bq, bk,
                          k_col_scale=None, v_col_scale=None,
                          exclusive=False):
    """One KV block's update of the running (acc, m, l) flash state —
    shared by the per-layer and stacked-cache kernels (the only thing
    that differs between them is how refs address their blocks).

    k_col_scale / v_col_scale ([1, bk] fp32, optional) are the int8
    cache's per-row dequant scales applied COLUMN-wise to the score
    matrix instead of row-wise to k/v: scales factor out of the dots
    (q·(c·k) == c·(q·k), p·(c·v) == (c·p)·v), and a [1, bk] lane-major
    operand is a Mosaic-legal layout whereas the previous [bk, 1]
    (lane dim 1) scale block was a known compile risk on real TPUs."""
    # dots in input dtype (bf16 MXU full rate), f32 accumulation/softmax
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if k_col_scale is not None:
        s = s * k_col_scale          # [bq, bk] * [1, bk]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)  # q row
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # row r is the token at global position n_valid + r: attends the
    # prefix (cols < n_valid) and itself/earlier new tokens (causal).
    # exclusive=True: prefix ONLY (cols < n_valid) — the write-kernel's
    # cache blocks hold stale bytes at the new token's slot; its
    # self-attention term enters via the seeded running stats instead.
    if exclusive:
        mask = (rows < sq) & (cols < n_valid)
    else:
        mask = (rows < sq) & (cols <= n_valid + rows)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_sc[:] = l_sc[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_sc[:] = m_new
    if v_col_scale is not None:
        p = p * v_col_scale          # fold v dequant into p (fp32)
    acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc,
            *, scale, sq, bq, bk):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]   # cache prefix length for this batch

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    # skip blocks entirely past the last attendable position
    run = k_start < n_valid + sq

    @pl.when(run)
    def _():
        _online_softmax_block(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
                              n_valid, k_start, acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq, bq=bq, bk=bk)

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_lens, scale=None):
    """Returns [B, Sq, H, D] attention of the new queries over cache + self.

    The caches hold the prefix in positions [0, cache_lens[b]) and must
    already contain the new tokens' K/V at positions
    [cache_lens[b], cache_lens[b] + Sq) (standard write-then-attend decode
    step order).
    """
    qt = jnp.swapaxes(q, 1, 2)                       # [B, H, Sq, D]
    kt = jnp.swapaxes(k_cache, 1, 2)                 # [B, Hk, Smax, D]
    vt = jnp.swapaxes(v_cache, 1, 2)
    return jnp.swapaxes(
        decode_attention_bhsd(qt, kt, vt, cache_lens, scale), 1, 2)


def decode_attention_bhsd(qt, kt, vt, cache_lens, scale=None):
    """Same as decode_attention but in kernel layout [B, H, S, D] in AND
    out — the compiled multi-layer decode loop stores its KV cache in this
    layout so no per-step full-cache transpose is materialized."""
    b, h, sq, d = qt.shape
    smax = kt.shape[2]
    hk = kt.shape[1]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    # in-kernel dots run in the operand dtype: harmonize a mixed-precision
    # cache with the query dtype (bf16 q + f32 cache was accepted before
    # the bf16-dot change and must keep working)
    if kt.dtype != qt.dtype:
        kt = kt.astype(qt.dtype)
    if vt.dtype != qt.dtype:
        vt = vt.astype(qt.dtype)

    bq = max(8, 1 << (sq - 1).bit_length()) if sq < 128 else 128
    bk = min(256, smax) if smax % 256 == 0 or smax < 256 else 128
    sk_p = math.ceil(smax / bk) * bk
    if sk_p != smax:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_p - smax), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_p - smax), (0, 0)))
    if bq != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, bq - sq), (0, 0)))

    lens = cache_lens.astype(jnp.int32).reshape(b)
    grid = (b, h, sk_p // bk)

    # Same last-valid-block clamp as the stacked kernels (see
    # _stacked_setup): blocks past n_valid + sq re-address the last valid
    # block so the pipeline elides their HBM copies — without it, a long
    # ring buffer with a short prefix streams mostly padding. lens rides
    # in as a scalar-prefetch operand so the index maps can read it.
    def _cl(j, len_r, b_):
        return jnp.minimum(j, (len_r[b_] + sq - 1) // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), sq=sq, bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, j, len_r: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, j, len_r, g=group:
                             (b_, h_ // g, _cl(j, len_r, b_), 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, j, len_r, g=group:
                             (b_, h_ // g, _cl(j, len_r, b_), 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b_, h_, j, len_r: (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), qt.dtype),
        name="decode_attention_bhsd",
        interpret=_pallas._interpret(),
    )(lens, qt, kt, vt)
    return out[:, :, :sq]


# ---------------------------------------------------------------------------
# Stacked-cache variant: the multi-layer decode loop's KV cache is ONE
# [L, 2, B, Hk, Smax, D] buffer carried through the layer scan. Slicing
# caches[l] on the host side materializes a full per-layer copy as the
# kernel operand every (token, layer); here the LAYER INDEX rides in as a
# scalar-prefetch argument and the BlockSpec index_map addresses layer l's
# blocks directly in the stacked buffer — zero-copy reads, which is what
# makes the carry-with-in-place-update cache design actually bandwidth-
# minimal (reference anchor: fused_multi_transformer_op.cu's per-step
# in-place cache write).
# ---------------------------------------------------------------------------

def _stacked_setup(qt, hk, smax, group):
    """Shared host-side setup for the stacked-cache kernels: block sizes,
    q padding, grid, and the layer/kv-addressed index maps. ONE owner for
    the tiling rules so the fp and int8 wrappers cannot diverge."""
    b, h, sq, d = qt.shape
    bq = max(8, 1 << (sq - 1).bit_length()) if sq < 128 else 128
    if smax % 256 == 0:
        bk = 256
    elif smax % 128 == 0:
        bk = 128
    else:
        raise ValueError(
            f"stacked decode kernels: Smax {smax} must be a multiple of "
            "128 (pad the ring buffer at init, not per call)")
    if bq != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, bq - sq), (0, 0)))
    grid = (b, h, smax // bk)

    # Clamp the sequence-block coordinate at this batch row's LAST valid
    # block. The kernel body already pl.when-skips compute for blocks past
    # n_valid + sq, but a monotone index map would still DMA every one of
    # the Smax//bk blocks from HBM — at serving shapes (short prefix,
    # Smax-sized ring) that is almost all padding traffic and decode is
    # bandwidth-bound. With the clamp, every grid step past the last valid
    # block re-addresses that same block, and the Pallas pipeline elides
    # copies whose block index is unchanged — only the valid prefix is
    # ever streamed (splash/paged-attention style).
    def _clamp(j, len_r, b_):
        return jnp.minimum(j, (len_r[b_] + sq - 1) // bk)

    # ONE kv-block operand: the (1, 2, 1, 1, bk, d) block spans BOTH the
    # K and V planes of the kv axis, so the cache rides in as a single
    # operand. Passing the same buffer twice (separate K and V specs) was
    # observed to defeat XLA's in-place aliasing of the scan-carried
    # cache update — the compiled decode step materialized TWO full-cache
    # copies per layer (HLO inspected 2026-08-01).
    kvidx = lambda b_, h_, j, lay_r, len_r, g=group: (  # noqa: E731
        lay_r[0], 0, b_, h_ // g, _clamp(j, len_r, b_), 0)
    qidx = lambda b_, h_, j, lay_r, len_r: (b_, h_, 0, 0)  # noqa: E731
    return qt, bq, bk, grid, kvidx, qidx, _clamp


def stacked_i8_is_supported(q_shape, caches_shape, dtype) -> bool:
    """Support predicate for decode_attention_stacked_i8: same layout and
    tiling rules as the fp stacked kernel, cache dtype is int8 by
    construction (scales ride separately), compute dtype is the query's."""
    return stacked_is_supported(q_shape, caches_shape, dtype,
                                cache_dtype=None)


def stacked_is_supported(q_shape, caches_shape, dtype,
                         cache_dtype=None) -> bool:
    """caches: [L, 2, B, Hk, Smax, D]; q: [B, Sq, H, D] (layout as
    decode_attention). The Smax axis must tile exactly (padding the
    stacked buffer would copy all layers), and q/cache dtypes must MATCH:
    unlike decode_attention_bhsd (which upcasts the cache to the query
    dtype), upcasting the stacked buffer would copy every layer — mixed
    precision goes to the unstacked or dense path instead."""
    if len(q_shape) != 4 or len(caches_shape) != 6:
        return False
    if q_shape[-1] > 256 or q_shape[1] > 128:
        return False
    if q_shape[2] % caches_shape[3] != 0:
        return False
    smax = caches_shape[4]
    if not any(smax % bk == 0 for bk in (256, 128)):
        return False
    if cache_dtype is not None and jnp.dtype(cache_dtype) != jnp.dtype(dtype):
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _stacked_kernel(lay_ref, len_ref, q_ref, kv_ref, o_ref,
                    acc_sc, m_sc, l_sc, *, scale, sq, bq, bk):
    # same flash math as _kernel (shared _online_softmax_block); the
    # (1, 2, 1, 1, bk, d) kv block comes out of the stacked buffer
    # addressed by the prefetched layer scalar — K is plane 0, V plane 1
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    run = k_start < n_valid + sq

    @pl.when(run)
    def _():
        _online_softmax_block(q_ref[0, 0], kv_ref[0, 0, 0, 0],
                              kv_ref[0, 1, 0, 0], n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq, bq=bq, bk=bk)

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_stacked(qt, caches, layer, cache_lens, scale=None):
    """qt: [B, H, Sq, D] (kernel layout); caches: [L, 2, B, Hk, Smax, D]
    (kv axis: 0 = K, 1 = V); layer: scalar int32 (traced OK — it is a
    scalar-prefetch operand); cache_lens: [B] int32. Returns
    [B, H, Sq, D] — attention of the new queries over layer `layer`'s
    cache prefix + the just-written new positions."""
    b, h, sq, d = qt.shape
    hk, smax = caches.shape[3], caches.shape[4]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    if caches.dtype != qt.dtype:
        # downcasting q would silently lose dot/softmax precision and
        # upcasting the stacked cache would copy every layer — the mixed-
        # precision cases belong on decode_attention_bhsd (which upcasts
        # the single-layer cache) or the dense path
        raise ValueError(
            f"decode_attention_stacked: query dtype {qt.dtype} != cache "
            f"dtype {caches.dtype}; gate with stacked_is_supported(..., "
            "cache_dtype=...) and use the unstacked/dense path instead")
    out_dtype = qt.dtype

    qt, bq, bk, grid, kvidx, qidx, _ = _stacked_setup(qt, hk, smax,
                                                      group)
    lens = cache_lens.astype(jnp.int32).reshape(b)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_stacked_kernel, scale=float(scale), sq=sq,
                          bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, bk, d), kvidx),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d), qidx),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), caches.dtype),
        name="decode_attention_stacked",
        interpret=_pallas._interpret(),
    )(lay, lens, qt, caches)
    return out[:, :, :sq].astype(out_dtype)


# ---------------------------------------------------------------------------
# int8-quantized stacked cache: the serving-side cache-quant mode of
# fused_multi_transformer_op.cu (cache_kv int8). Decode is HBM-bandwidth
# bound — an int8 cache halves the bytes the kernel streams per token.
# K/V rows are quantized per (layer, kv, batch, head, position) with an
# fp32 absmax scale; the kernel dequantizes blocks in VMEM right before
# the dots (which still run in the query dtype on the MXU).
# ---------------------------------------------------------------------------

def _stacked_i8_kernel(lay_ref, len_ref, q_ref, kv_ref, kvs_ref,
                       o_ref, acc_sc, m_sc, l_sc,
                       *, scale, sq, bq, bk):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    run = k_start < n_valid + sq

    @pl.when(run)
    def _():
        q = q_ref[0, 0]                                     # [bq, d]
        # int8 -> compute dtype conversion only (values in [-127, 127]
        # are exact in bf16); the per-row dequant scales are applied
        # column-wise to the SCORE matrix inside the softmax block,
        # where they arrive as Mosaic-legal [1, bk] lane-major tiles.
        # Like the fp kernel, cache and scales each ride in as ONE
        # operand whose block spans both kv planes (single-pass buffers
        # keep the scan-carry update aliasable).
        k = kv_ref[0, 0, 0, 0].astype(q.dtype)              # [bk, d]
        v = kv_ref[0, 1, 0, 0].astype(q.dtype)
        _online_softmax_block(q, k, v, n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq, bq=bq, bk=bk,
                              k_col_scale=kvs_ref[0, 0, 0, 0],  # [1, bk]
                              v_col_scale=kvs_ref[0, 1, 0, 0])

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_stacked_i8(qt, caches_i8, cache_scales, layer,
                                cache_lens, scale=None):
    """qt: [B, H, Sq, D] (query dtype = compute dtype); caches_i8:
    [L, 2, B, Hk, Smax, D] int8; cache_scales: [L, 2, B, Hk, 1, Smax]
    fp32 per-row absmax scales (positions on the LAST axis so scale
    blocks are [1, bk] lane-major — Mosaic-legal, unlike a [bk, 1]
    lane-1 block); layer: scalar int32 (scalar-prefetch).
    Returns [B, H, Sq, D] in the query dtype."""
    b, h, sq, d = qt.shape
    hk, smax = caches_i8.shape[3], caches_i8.shape[4]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    if caches_i8.dtype != jnp.int8:
        raise ValueError("decode_attention_stacked_i8: cache must be int8")

    if cache_scales.shape != caches_i8.shape[:4] + (1, smax):
        raise ValueError(
            "decode_attention_stacked_i8: scales must be "
            f"[L, 2, B, Hk, 1, Smax], got {cache_scales.shape}")

    out_dtype = qt.dtype
    qt, bq, bk, grid, kvidx, qidx, clamp = _stacked_setup(
        qt, hk, smax, group)
    kvsidx = lambda b_, h_, j, lay_r, len_r, g=group: (  # noqa: E731
        lay_r[0], 0, b_, h_ // g, 0, clamp(j, len_r, b_))
    lens = cache_lens.astype(jnp.int32).reshape(b)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_stacked_i8_kernel, scale=float(scale), sq=sq,
                          bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, bk, d), kvidx),
                pl.BlockSpec((1, 2, 1, 1, 1, bk), kvsidx),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d), qidx),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), out_dtype),
        name="decode_attention_stacked_i8",
        interpret=_pallas._interpret(),
    )(lay, lens, qt, caches_i8, cache_scales)
    return out[:, :, :sq]


# ---------------------------------------------------------------------------
# Fused write+attend: the kernel updates the cache IN PLACE via
# input_output_aliases and attends in the same pass. This removes the
# XLA-side dynamic_update_slice on the scan-carried buffer entirely —
# the aliasing is declared at the custom-call level, so copy-insertion
# cannot conservatively materialize full-cache copies (the failure mode
# HLO-inspected on 2026-08-01: the carry update behind a kernel read
# copied the whole [L,2,B,Hk,Smax,D] buffer). Only the ONE block
# containing the write slot is ever written back; all other cache blocks
# are untouched HBM. (Reference anchor: fused_multi_transformer_op.cu's
# in-place cache write inside the attention kernel.)
# ---------------------------------------------------------------------------

def _stacked_write_kernel(lay_ref, len_ref, q_ref, kvn_ref, kv_ref,
                          kvo_ref, o_ref, acc_sc, m_sc, l_sc,
                          *, scale, bq, bk):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]
    # block holding the write slot, clamped to the LAST real block: at a
    # full cache (n_valid == Smax — an eviction-invariant violation) the
    # unclamped jw would be nk, one past the grid, and the matching
    # output index map would address undefined HBM. Clamped, the write
    # row-select misses every row (off == bk) so the kernel copies the
    # last block through unchanged — the new token is DROPPED, never a
    # wild write.
    jw = jnp.minimum(n_valid // bk, nk - 1)

    @pl.when(ki == 0)
    def _():
        # seed the running flash stats with the NEW token's own column
        # (its k/v ride in via kvn_ref — the cache block's bytes at the
        # write slot are stale until this kernel writes them)
        # fp32 operands: Mosaic lowers the one-row rhs as a broadcast,
        # which must not change element type (bf16 -> f32 fails to
        # verify); bf16 products are exact in fp32, so the score is the
        # same number the bf16 dot accumulates
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, d]
        kn = kvn_ref[0, 0, 0, 0].astype(jnp.float32)     # [1, d]
        vn = kvn_ref[0, 1, 0, 0]                         # [1, d]
        s = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        valid = rows < 1                                 # sq == 1
        m_sc[:] = jnp.where(valid, s, NEG_INF)
        l_sc[:] = jnp.where(valid, 1.0, 0.0)
        acc_sc[:] = jnp.where(valid, 1.0, 0.0) * vn.astype(jnp.float32)

    k_start = ki * bk

    @pl.when(k_start < n_valid)
    def _():
        _online_softmax_block(q_ref[0, 0], kv_ref[0, 0, 0, 0],
                              kv_ref[0, 1, 0, 0], n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=1, bq=bq, bk=bk,
                              exclusive=True)

    @pl.when(ki == jw)
    def _():
        # copy-through the write block with the new token's row selected
        # in (row-mask select — one vector op per plane, no dynamic-
        # offset store for Mosaic to choke on). The output index map is
        # CONSTANT at jw, so this is the only cache block pallas ever
        # writes back; the copy is one VMEM-resident block, not HBM
        # traffic beyond the block itself.
        off = n_valid - jw * bk
        rows = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        hit = rows == off
        kvo_ref[0, 0, 0, 0] = jnp.where(hit, kvn_ref[0, 0, 0, 0],
                                        kv_ref[0, 0, 0, 0])
        kvo_ref[0, 1, 0, 0] = jnp.where(hit, kvn_ref[0, 1, 0, 0],
                                        kv_ref[0, 1, 0, 0])

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def stacked_write_is_supported(q_shape, caches_shape, dtype,
                               cache_dtype=None) -> bool:
    """Same layout/tiling rules as the read-only stacked kernel, plus the
    write path's own restriction: exactly one new token per call (the
    chunked decode scans step one token at a time; a multi-row write
    could straddle two sequence blocks)."""
    return q_shape[1] == 1 and stacked_is_supported(
        q_shape, caches_shape, dtype, cache_dtype=cache_dtype)


def decode_attention_stacked_write(qt, kv_new, caches, layer, cache_lens,
                                   scale=None):
    """qt: [B, H, 1, D] (kernel layout); kv_new: [2, B, Hk, 1, D] — the
    new token's K/V for layer `layer`; caches: [L, 2, B, Hk, Smax, D],
    DONATED (aliased to the first output). Returns (caches, attn) where
    caches is the SAME buffer with the new rows landed at position
    cache_lens[b] and attn is [B, H, 1, D].

    The caller must NOT dynamic_update_slice the cache first — the write
    happens inside the kernel, and the new token's self-attention term is
    seeded from kv_new directly.

    INVARIANT: cache_lens[b] < Smax for every row — the ring must have a
    free slot (the serving engine's slot-eviction logic frees a row
    BEFORE re-admitting into it, maintaining exactly this). A full row
    (cache_lens[b] == Smax) cannot raise from traced code; instead both
    the in-kernel write block and the output index map clamp to the last
    sequence block, so the new token is dropped and the cache bytes are
    left untouched (attn still includes the new token's seeded
    self-attention term). Never rely on the drop: it exists to make an
    invariant violation non-corrupting, not to implement eviction."""
    b, h, sq, d = qt.shape
    hk, smax = caches.shape[3], caches.shape[4]
    group = h // hk
    if sq != 1:
        raise ValueError("decode_attention_stacked_write: one new token "
                         f"per call (got Sq={sq}); gate with "
                         "stacked_write_is_supported")
    if scale is None:
        scale = d ** -0.5
    if caches.dtype != qt.dtype:
        raise ValueError(
            f"decode_attention_stacked_write: query dtype {qt.dtype} != "
            f"cache dtype {caches.dtype}")
    out_dtype = qt.dtype

    qt, bq, bk, grid, kvidx, qidx, _clamp = _stacked_setup(
        qt, hk, smax, group)
    kvnidx = lambda b_, h_, j, lay_r, len_r, g=group: (  # noqa: E731
        0, 0, b_, h_ // g, 0, 0)
    # The OUTPUT map is the write-slot block UNCONDITIONALLY (constant in
    # j) — it must NOT reuse the read clamp min(j, jw): for j < jw that
    # addresses prefix blocks the kernel never stores to, and Pallas
    # would write their stale VMEM windows back over live cache. With a
    # constant map, exactly one block per (b, hk) is ever written back;
    # every other cache block stays untouched HBM through the alias.
    # min(..., nblk-1) mirrors the kernel's jw clamp: a full row
    # (cache_lens == Smax) must address the LAST block, not one past it
    # (see the invariant note in the docstring).
    nblk = smax // bk
    kvoidx = lambda b_, h_, j, lay_r, len_r, g=group, bk_=bk: (  # noqa: E731
        lay_r[0], 0, b_, h_ // g,
        jnp.minimum(len_r[b_] // bk_, nblk - 1), 0)
    kv_new = kv_new[None]                  # [1, 2, B, Hk, 1, D]
    lens = cache_lens.astype(jnp.int32).reshape(b)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    caches_out, out = pl.pallas_call(
        functools.partial(_stacked_write_kernel, scale=float(scale),
                          bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, 1, d), kvnidx),
                pl.BlockSpec((1, 2, 1, 1, bk, d), kvidx),
            ],
            out_specs=[
                pl.BlockSpec((1, 2, 1, 1, bk, d), kvoidx),
                pl.BlockSpec((1, 1, bq, d), qidx),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(caches.shape, caches.dtype),
            jax.ShapeDtypeStruct((b, h, bq, d), out_dtype),
        ],
        input_output_aliases={4: 0},   # caches operand -> caches output
        name="decode_attention_stacked_write",
        interpret=_pallas._interpret(),
    )(lay, lens, qt, kv_new.astype(caches.dtype), caches)
    return caches_out, out[:, :, :sq].astype(out_dtype)


def _stacked_i8_write_kernel(lay_ref, len_ref, q_ref, kvn_ref, kv_ref,
                             kvs_ref, kvo_ref, kvso_ref, o_ref,
                             acc_sc, m_sc, l_sc, *, scale, bq, bk):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]
    # same full-cache clamp as _stacked_write_kernel: at n_valid == Smax
    # the write row/lane selects miss (off == bk) and the last block +
    # scales copy through unchanged — token dropped, never a wild write
    jw = jnp.minimum(n_valid // bk, nk - 1)

    # the new row's quantization (per-row absmax, same recipe as the
    # host-side cache-quant write) — computed where needed; the seeded
    # self-attention term uses the DEQUANTIZED values so the kernel is
    # bit-consistent with the DUS-then-read int8 path
    def _quant(row):                                     # [1, d] fp
        r32 = row.astype(jnp.float32)
        amax = jnp.max(jnp.abs(r32), axis=-1, keepdims=True)
        sc = amax / 127.0
        qi = jnp.clip(jnp.round(r32 / jnp.maximum(sc, 1e-8)),
                      -127, 127)
        return qi, sc

    @pl.when(ki == 0)
    def _():
        # seed arithmetic MIRRORS the read kernel exactly (bit-for-bit
        # with the DUS-then-read path in every dtype): dot the RAW int
        # values in the query dtype (all of [-127, 127] is exact in
        # bf16), apply the k scale to the SCORE, fold the v scale into p
        # and cast p to the operand dtype before the v dot
        # The two seed dots have a ONE-row operand, which Mosaic lowers
        # as a broadcast that must not change element type — so they
        # take fp32 operands. Same numbers: q and p are rounded to the
        # query dtype first, and their products with the int values
        # are exact in fp32 either way.
        q = q_ref[0, 0]                                  # [bq, d]
        kq, ksc = _quant(kvn_ref[0, 0, 0, 0])
        vq, vsc = _quant(kvn_ref[0, 1, 0, 0])
        s = jax.lax.dot_general(q.astype(jnp.float32), kq,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * scale * ksc
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        valid = rows < 1                                 # sq == 1
        m_sc[:] = jnp.where(valid, s, NEG_INF)
        l_sc[:] = jnp.where(valid, 1.0, 0.0)
        pv = (jnp.where(valid, 1.0, 0.0) * vsc).astype(q.dtype)
        acc_sc[:] = jax.lax.dot_general(
            pv.astype(jnp.float32), vq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    k_start = ki * bk

    @pl.when(k_start < n_valid)
    def _():
        q = q_ref[0, 0]
        k = kv_ref[0, 0, 0, 0].astype(q.dtype)
        v = kv_ref[0, 1, 0, 0].astype(q.dtype)
        _online_softmax_block(q, k, v, n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=1, bq=bq, bk=bk,
                              k_col_scale=kvs_ref[0, 0, 0, 0],
                              v_col_scale=kvs_ref[0, 1, 0, 0],
                              exclusive=True)

    @pl.when(ki == jw)
    def _():
        off = n_valid - jw * bk
        rows = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        hit = rows == off
        kq, ksc = _quant(kvn_ref[0, 0, 0, 0])
        vq, vsc = _quant(kvn_ref[0, 1, 0, 0])
        kvo_ref[0, 0, 0, 0] = jnp.where(hit, kq.astype(jnp.int8),
                                        kv_ref[0, 0, 0, 0])
        kvo_ref[0, 1, 0, 0] = jnp.where(hit, vq.astype(jnp.int8),
                                        kv_ref[0, 1, 0, 0])
        # scales tile is [1, bk] lane-major: the write slot is a LANE
        # select at `off` (no dynamic store)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        lhit = lanes == off
        kvso_ref[0, 0, 0, 0] = jnp.where(lhit, ksc.reshape(1, 1),
                                         kvs_ref[0, 0, 0, 0])
        kvso_ref[0, 1, 0, 0] = jnp.where(lhit, vsc.reshape(1, 1),
                                         kvs_ref[0, 1, 0, 0])

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def stacked_i8_write_is_supported(q_shape, caches_shape, dtype) -> bool:
    """Gate for decode_attention_stacked_i8_write: the int8 read rules
    plus the write path's one-new-token restriction (same rationale as
    stacked_write_is_supported)."""
    return q_shape[1] == 1 and stacked_i8_is_supported(
        q_shape, caches_shape, dtype)


def decode_attention_stacked_i8_write(qt, kv_new, caches_i8, cache_scales,
                                      layer, cache_lens, scale=None):
    """int8 variant of decode_attention_stacked_write: quantizes the new
    token's K/V rows IN KERNEL (per-row absmax, bit-identical to the
    host-side cache-quant write), lands row + scale in place (both
    buffers aliased), and attends in the same pass. qt: [B, H, 1, D];
    kv_new: [2, B, Hk, 1, D] (fp); caches_i8: [L, 2, B, Hk, Smax, D]
    int8 DONATED; cache_scales: [L, 2, B, Hk, 1, Smax] fp32 DONATED.
    Returns (caches_i8, cache_scales, attn).

    INVARIANT: cache_lens[b] < Smax (see decode_attention_stacked_write);
    a full row clamps to the last block and drops the write — cache and
    scales come back byte-identical for that row, never corrupted."""
    b, h, sq, d = qt.shape
    hk, smax = caches_i8.shape[3], caches_i8.shape[4]
    group = h // hk
    if sq != 1:
        raise ValueError("decode_attention_stacked_i8_write: one new "
                         f"token per call (got Sq={sq})")
    if scale is None:
        scale = d ** -0.5
    if caches_i8.dtype != jnp.int8:
        raise ValueError("decode_attention_stacked_i8_write: cache must "
                         "be int8")
    if cache_scales.shape != caches_i8.shape[:4] + (1, smax):
        raise ValueError(
            "decode_attention_stacked_i8_write: scales must be "
            f"[L, 2, B, Hk, 1, Smax], got {cache_scales.shape}")
    out_dtype = qt.dtype

    qt, bq, bk, grid, kvidx, qidx, clamp = _stacked_setup(
        qt, hk, smax, group)
    kvnidx = lambda b_, h_, j, lay_r, len_r, g=group: (  # noqa: E731
        0, 0, b_, h_ // g, 0, 0)
    kvsidx = lambda b_, h_, j, lay_r, len_r, g=group: (  # noqa: E731
        lay_r[0], 0, b_, h_ // g, 0, clamp(j, len_r, b_))
    # constant-at-jw output maps, clamped to the last block exactly like
    # decode_attention_stacked_write (cache_lens < Smax invariant)
    nblk = smax // bk
    kvoidx = lambda b_, h_, j, lay_r, len_r, g=group, bk_=bk: (  # noqa: E731
        lay_r[0], 0, b_, h_ // g,
        jnp.minimum(len_r[b_] // bk_, nblk - 1), 0)
    kvsoidx = lambda b_, h_, j, lay_r, len_r, g=group, bk_=bk: (  # noqa: E731
        lay_r[0], 0, b_, h_ // g, 0,
        jnp.minimum(len_r[b_] // bk_, nblk - 1))
    kv_new = kv_new[None]                  # [1, 2, B, Hk, 1, D]
    lens = cache_lens.astype(jnp.int32).reshape(b)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    caches_out, scales_out, out = pl.pallas_call(
        functools.partial(_stacked_i8_write_kernel, scale=float(scale),
                          bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, 1, d), kvnidx),
                pl.BlockSpec((1, 2, 1, 1, bk, d), kvidx),
                pl.BlockSpec((1, 2, 1, 1, 1, bk), kvsidx),
            ],
            out_specs=[
                pl.BlockSpec((1, 2, 1, 1, bk, d), kvoidx),
                pl.BlockSpec((1, 2, 1, 1, 1, bk), kvsoidx),
                pl.BlockSpec((1, 1, bq, d), qidx),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(caches_i8.shape, jnp.int8),
            jax.ShapeDtypeStruct(cache_scales.shape, jnp.float32),
            jax.ShapeDtypeStruct((b, h, bq, d), out_dtype),
        ],
        input_output_aliases={4: 0, 5: 1},
        name="decode_attention_stacked_i8_write",
        interpret=_pallas._interpret(),
    )(lay, lens, qt, kv_new.astype(jnp.float32), caches_i8, cache_scales)
    return caches_out, scales_out, out[:, :, :sq].astype(out_dtype)


# ---------------------------------------------------------------------------
# Paged-cache variant: the KV cache is ONE shared block pool
# [L, 2, NBtotal, Hk, Bt, D] and each batch row's positions resolve
# through a per-slot block table [B, Smax/Bt] int32 (vLLM PagedAttention
# layout; see inference/paged_kv.py for the allocator). The table rides
# in as a SCALAR-PREFETCH operand so the kv BlockSpec index map can
# translate grid step j into the row's j-th pool block — the kernel
# streams exactly the blocks the row owns, in table order, and the
# last-valid-block clamp re-addresses past-the-end steps at the last
# valid block so the pipeline elides their HBM copies (same trick as
# the stacked kernels). The sequence-block size IS the pool's Bt, so
# one compiled kernel serves every slot/table content — block ids are
# data, never structure.
# ---------------------------------------------------------------------------

def _paged_sublane(dtype) -> int:
    """Minimum Mosaic sublane multiple for the pool's Bt axis: the kv
    block (1, 2, 1, 1, Bt, D) puts Bt on the second-to-minor dim."""
    d = jnp.dtype(dtype)
    if d == jnp.int8:
        return 32
    if d in (jnp.bfloat16, jnp.float16):
        return 16
    return 8


def paged_is_supported(q_shape, pool_shape, dtype,
                       cache_dtype=None) -> bool:
    """pool: [L, 2, NB, Hk, Bt, D]; q: [B, Sq, H, D]. Bt must satisfy
    the dtype's sublane tiling (fp32: 8, bf16/fp16: 16, int8: 32) —
    smaller block_tokens values fall back to the gather-dense path in
    generation.py. Like the stacked kernels, q and cache dtypes must
    MATCH (upcasting the pool would copy every block)."""
    if len(q_shape) != 4 or len(pool_shape) != 6:
        return False
    if q_shape[-1] > 256 or q_shape[1] > 128:
        return False
    if pool_shape[3] == 0 or q_shape[2] % pool_shape[3] != 0:
        return False
    bt = pool_shape[4]
    sub = _paged_sublane(cache_dtype if cache_dtype is not None else dtype)
    if bt < sub or bt % sub:
        return False
    if cache_dtype is not None and jnp.dtype(cache_dtype) != jnp.dtype(dtype):
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def paged_i8_is_supported(q_shape, pool_shape, dtype) -> bool:
    """int8 pool flavor: same layout rules with the int8 sublane
    minimum (Bt % 32 == 0); compute dtype is the query's."""
    if len(q_shape) != 4 or len(pool_shape) != 6:
        return False
    if q_shape[-1] > 256 or q_shape[1] > 128:
        return False
    if pool_shape[3] == 0 or q_shape[2] % pool_shape[3] != 0:
        return False
    bt = pool_shape[4]
    if bt < 32 or bt % 32:
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _paged_setup(qt, bt, nblk, nb, group):
    """Shared host-side setup for the paged kernels: q padding, grid,
    and the table-translated index maps. Index-map signature:
    (b, h, j, lay_ref, len_ref, tbl_ref) — tables are the THIRD
    scalar-prefetch operand. Unmapped/sentinel table entries are
    clamped to block nb - 1 (their contents are never attendable: the
    kernel masks cols >= n_valid + sq, and the clamp below only
    re-addresses steps past the last valid block anyway)."""
    b, h, sq, d = qt.shape
    bq = max(8, 1 << (sq - 1).bit_length()) if sq < 128 else 128
    if bq != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, bq - sq), (0, 0)))
    grid = (b, h, nblk)

    def _clamp(j, len_r, b_):
        # same pipeline-copy-elision clamp as the stacked kernels:
        # steps past this row's last valid block re-address that block
        return jnp.minimum(j, (len_r[b_] + sq - 1) // bt)

    def _blk(j, len_r, tbl_r, b_):
        return jnp.minimum(tbl_r[b_, _clamp(j, len_r, b_)], nb - 1)

    kvidx = lambda b_, h_, j, lay_r, len_r, tbl_r, g=group: (  # noqa: E731
        lay_r[0], 0, _blk(j, len_r, tbl_r, b_), h_ // g, 0, 0)
    qidx = lambda b_, h_, j, lay_r, len_r, tbl_r: (  # noqa: E731
        b_, h_, 0, 0)
    return qt, bq, grid, kvidx, qidx, _blk


def _paged_kernel(lay_ref, len_ref, tbl_ref, q_ref, kv_ref, o_ref,
                  acc_sc, m_sc, l_sc, *, scale, sq, bq, bk):
    # flash math identical to _stacked_kernel (shared
    # _online_softmax_block); only the addressing differs — the
    # (1, 2, 1, 1, bk, d) kv block was fetched through the block table
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    run = k_start < n_valid + sq

    @pl.when(run)
    def _():
        _online_softmax_block(q_ref[0, 0], kv_ref[0, 0, 0, 0],
                              kv_ref[0, 1, 0, 0], n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq, bq=bq, bk=bk)

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_paged(qt, pool, tables, layer, cache_lens,
                           scale=None):
    """qt: [B, H, Sq, D] (kernel layout); pool: [L, 2, NB, Hk, Bt, D]
    — the ONE shared block pool; tables: [B, Smax/Bt] int32 per-slot
    block tables (sentinel NB for unmapped entries); layer: scalar
    int32 (scalar-prefetch); cache_lens: [B] int32. Returns
    [B, H, Sq, D] — attention of the new queries over the row's
    table-resolved prefix + the just-written new positions."""
    b, h, sq, d = qt.shape
    hk, bt = pool.shape[3], pool.shape[4]
    nb = pool.shape[2]
    nblk = tables.shape[1]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    if pool.dtype != qt.dtype:
        raise ValueError(
            f"decode_attention_paged: query dtype {qt.dtype} != pool "
            f"dtype {pool.dtype}; gate with paged_is_supported(..., "
            "cache_dtype=...) and use the gather-dense path instead")
    out_dtype = qt.dtype

    qt, bq, grid, kvidx, qidx, _ = _paged_setup(qt, bt, nblk, nb, group)
    lens = cache_lens.astype(jnp.int32).reshape(b)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    tbl = tables.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale), sq=sq,
                          bq=bq, bk=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, bt, d), kvidx),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d), qidx),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), pool.dtype),
        name="decode_attention_paged",
        interpret=_pallas._interpret(),
    )(lay, lens, tbl, qt, pool)
    return out[:, :, :sq].astype(out_dtype)


def _paged_i8_kernel(lay_ref, len_ref, tbl_ref, q_ref, kv_ref, kvs_ref,
                     o_ref, acc_sc, m_sc, l_sc, *, scale, sq, bq, bk):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    run = k_start < n_valid + sq

    @pl.when(run)
    def _():
        q = q_ref[0, 0]
        # int8 -> compute dtype conversion; per-row dequant scales
        # applied column-wise to the score matrix as [1, bk] lane-major
        # tiles — identical discipline to _stacked_i8_kernel
        k = kv_ref[0, 0, 0, 0].astype(q.dtype)
        v = kv_ref[0, 1, 0, 0].astype(q.dtype)
        _online_softmax_block(q, k, v, n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq, bq=bq, bk=bk,
                              k_col_scale=kvs_ref[0, 0, 0, 0],
                              v_col_scale=kvs_ref[0, 1, 0, 0])

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0, 0] = (acc_sc[:] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_paged_i8(qt, pool_i8, pool_scales, tables, layer,
                              cache_lens, scale=None):
    """int8 paged flavor: pool_i8 [L, 2, NB, Hk, Bt, D] int8 with
    per-row absmax scales pool_scales [L, 2, NB, Hk, 1, Bt] fp32 (the
    scales pool mirrors the kv pool block-for-block, so both resolve
    through the SAME table entry). Returns [B, H, Sq, D] in the query
    dtype."""
    b, h, sq, d = qt.shape
    hk, bt = pool_i8.shape[3], pool_i8.shape[4]
    nb = pool_i8.shape[2]
    nblk = tables.shape[1]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    if pool_i8.dtype != jnp.int8:
        raise ValueError("decode_attention_paged_i8: pool must be int8")
    if pool_scales.shape != pool_i8.shape[:4] + (1, bt):
        raise ValueError(
            "decode_attention_paged_i8: scales must be "
            f"[L, 2, NB, Hk, 1, Bt], got {pool_scales.shape}")
    out_dtype = qt.dtype

    qt, bq, grid, kvidx, qidx, blkf = _paged_setup(qt, bt, nblk, nb,
                                                   group)
    kvsidx = lambda b_, h_, j, lay_r, len_r, tbl_r, g=group: (  # noqa: E731
        lay_r[0], 0, blkf(j, len_r, tbl_r, b_), h_ // g, 0, 0)
    lens = cache_lens.astype(jnp.int32).reshape(b)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    tbl = tables.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_paged_i8_kernel, scale=float(scale), sq=sq,
                          bq=bq, bk=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, bt, d), kvidx),
                pl.BlockSpec((1, 2, 1, 1, 1, bt), kvsidx),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d), qidx),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), out_dtype),
        name="decode_attention_paged_i8",
        interpret=_pallas._interpret(),
    )(lay, lens, tbl, qt, pool_i8, pool_scales)
    return out[:, :, :sq]


# ---------------------------------------------------------------------------
# Flat-stream variant: the token-budget scheduler's FLAT dispatch packs
# every request's segment (a prefill chunk, a decode token + draft
# claim) into ONE ragged [T] token stream instead of the row-aligned
# [B, C] block — T real tokens cost T positions of compute, where the
# row layout paid B x C regardless of packing (a lone long prefill
# wasted (B-1) x C positions per dispatch). This kernel is the
# block-flash attend for that stream: the packer aligns segment starts
# to FLAT_CHUNK so every FLAT_CHUNK-sized query chunk belongs to ONE
# slot, per-chunk (slot, base position, valid count) ride in as
# scalar-prefetch metadata, and each chunk streams its slot's paged KV
# blocks through the block table with block-causal masking — the
# Sq > 1 write-then-attend generalization from the verify step,
# extended to ragged multi-request streams. Pad chunks (slot sentinel)
# carry n == 0: no block runs, l stays 0, the output row is zeroed by
# the l == 0 guard.
# ---------------------------------------------------------------------------

# the packer's segment-start alignment = the kernel's query-chunk size:
# 8 is the fp32 sublane minimum, so the q block (1, FLAT_CHUNK, d)
# tiles legally for every supported dtype
FLAT_CHUNK = 8


def paged_flat_is_supported(t, h, d, pool_shape, dtype,
                            cache_dtype=None) -> bool:
    """Support predicate for decode_attention_paged_flat: stream width
    t must tile into FLAT_CHUNK query chunks; the pool obeys the same
    Bt-sublane and dtype-match rules as the row-aligned paged kernel.
    Int8 pools have their own flavor — gate those with
    paged_flat_i8_is_supported (whose Bt gate is the int8 sublane
    minimum); only pools passing NEITHER predicate take the
    gather-dense fallback (paged_kv.flat_gather_view, the parity
    oracle)."""
    if len(pool_shape) != 6:
        return False
    if t < FLAT_CHUNK or t % FLAT_CHUNK:
        return False
    if d > 256:
        return False
    if pool_shape[3] == 0 or h % pool_shape[3] != 0:
        return False
    bt = pool_shape[4]
    sub = _paged_sublane(cache_dtype if cache_dtype is not None else dtype)
    if bt < sub or bt % sub:
        return False
    if cache_dtype is not None and jnp.dtype(cache_dtype) != jnp.dtype(dtype):
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _paged_flat_kernel(lay_ref, cslot_ref, cbase_ref, cn_ref, tbl_ref,
                       q_ref, kv_ref, o_ref, acc_sc, m_sc, l_sc,
                       *, scale, bq, bk):
    # flash math identical to _paged_kernel; the addressing unit is a
    # QUERY CHUNK instead of a batch row — chunk ci's tokens are the
    # contiguous positions cbase[ci] .. cbase[ci] + cn[ci] - 1 of slot
    # cslot[ci], so the standard causal mask applies with the chunk's
    # base as the prefix length and its valid count as the (dynamic)
    # query count
    ci = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = cbase_ref[ci]
    sq_dyn = cn_ref[ci]

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    run = (sq_dyn > 0) & (k_start < n_valid + sq_dyn)

    @pl.when(run)
    def _():
        _online_softmax_block(q_ref[0], kv_ref[0, 0, 0, 0],
                              kv_ref[0, 1, 0, 0], n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq_dyn, bq=bq, bk=bk)

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0] = (acc_sc[:] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_paged_flat(q, pool, tables, chunk_slot, chunk_base,
                                chunk_n, layer, scale=None):
    """q: [T, H, D] — the flat token stream's queries, segment starts
    aligned to FLAT_CHUNK so each FLAT_CHUNK query chunk is single-slot;
    pool: [L, 2, NB, Hk, Bt, D]; tables: [B(+sentinel rows ok), Smax/Bt]
    int32; chunk_slot/chunk_base/chunk_n: [T/FLAT_CHUNK] int32 per-chunk
    metadata (slot id CLAMPED in-bounds by the caller, base position of
    the chunk's first token, number of valid tokens — 0 for pad
    chunks). Returns [T, H, D]: token i attends its slot's
    table-resolved positions <= its own position (block-causal; the
    chunk's K/V must already be written — write-then-attend)."""
    t, h, d = q.shape
    hk, bt = pool.shape[3], pool.shape[4]
    nb = pool.shape[2]
    nblk = tables.shape[1]
    group = h // hk
    nc = t // FLAT_CHUNK
    if t % FLAT_CHUNK:
        raise ValueError(
            f"decode_attention_paged_flat: stream width {t} must be a "
            f"multiple of FLAT_CHUNK={FLAT_CHUNK} (gate with "
            "paged_flat_is_supported)")
    if scale is None:
        scale = d ** -0.5
    if pool.dtype != q.dtype:
        raise ValueError(
            f"decode_attention_paged_flat: query dtype {q.dtype} != "
            f"pool dtype {pool.dtype}; gate with paged_flat_is_supported"
            "(..., cache_dtype=...) and use the gather-dense fallback")
    out_dtype = q.dtype
    # [T, H, D] -> [H, T, D]: heads ride their own grid axis, the token
    # chunk is the q block's sublane axis
    qt = jnp.swapaxes(q, 0, 1)
    grid = (nc, h, nblk)

    def _blk(ci, j, cb_r, cn_r, tbl_r, cs_r):
        # last-valid-block clamp per CHUNK (pipeline copy elision, the
        # stacked/paged kernels' trick): the chunk's highest attendable
        # position is cbase + cn - 1; later grid steps re-address that
        # block. Pad chunks (cn == 0) pin to the chunk's base block.
        last = (cb_r[ci] + jnp.maximum(cn_r[ci], 1) - 1) // bt
        return jnp.minimum(tbl_r[cs_r[ci], jnp.minimum(j, last)], nb - 1)

    kvidx = lambda ci, h_, j, lay_r, cs_r, cb_r, cn_r, tbl_r, g=group: (  # noqa: E731
        lay_r[0], 0, _blk(ci, j, cb_r, cn_r, tbl_r, cs_r), h_ // g, 0, 0)
    qidx = lambda ci, h_, j, lay_r, cs_r, cb_r, cn_r, tbl_r: (  # noqa: E731
        h_, ci, 0)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_paged_flat_kernel, scale=float(scale),
                          bq=FLAT_CHUNK, bk=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, FLAT_CHUNK, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, bt, d), kvidx),
            ],
            out_specs=pl.BlockSpec((1, FLAT_CHUNK, d), qidx),
            scratch_shapes=[
                pltpu.VMEM((FLAT_CHUNK, d), jnp.float32),
                pltpu.VMEM((FLAT_CHUNK, 1), jnp.float32),
                pltpu.VMEM((FLAT_CHUNK, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, t, d), pool.dtype),
        name="decode_attention_paged_flat",
        interpret=_pallas._interpret(),
    )(lay, chunk_slot.astype(jnp.int32), chunk_base.astype(jnp.int32),
      chunk_n.astype(jnp.int32), tables.astype(jnp.int32), qt, pool)
    return jnp.swapaxes(out, 0, 1).astype(out_dtype)


def paged_flat_i8_is_supported(t, h, d, pool_shape, dtype) -> bool:
    """Support predicate for decode_attention_paged_flat_i8: the flat
    layout rules (FLAT_CHUNK-tiled stream, head grouping, d <= 256)
    with the int8 pool's sublane gate (Bt % 32 == 0 — the Mosaic
    minimum for an int8 second-to-minor axis); compute dtype is the
    query's. Pools failing this go to the gather-dense fallback
    (flat_gather_view's sc path), which stays the parity oracle."""
    if len(pool_shape) != 6:
        return False
    if t < FLAT_CHUNK or t % FLAT_CHUNK:
        return False
    if d > 256:
        return False
    if pool_shape[3] == 0 or h % pool_shape[3] != 0:
        return False
    bt = pool_shape[4]
    if bt < 32 or bt % 32:
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _paged_flat_i8_kernel(lay_ref, cslot_ref, cbase_ref, cn_ref, tbl_ref,
                          q_ref, kv_ref, kvs_ref, o_ref, acc_sc, m_sc,
                          l_sc, *, scale, bq, bk):
    # _paged_flat_kernel's chunk addressing with _paged_i8_kernel's
    # dequant: int8 KV casts to the compute dtype and the per-row
    # absmax scales apply COLUMN-wise to the score matrix as [1, bk]
    # lane-major tiles (see _online_softmax_block)
    ci = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_valid = cbase_ref[ci]
    sq_dyn = cn_ref[ci]

    @pl.when(ki == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    k_start = ki * bk
    run = (sq_dyn > 0) & (k_start < n_valid + sq_dyn)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = kv_ref[0, 0, 0, 0].astype(q.dtype)
        v = kv_ref[0, 1, 0, 0].astype(q.dtype)
        _online_softmax_block(q, k, v, n_valid, k_start,
                              acc_sc, m_sc, l_sc,
                              scale=scale, sq=sq_dyn, bq=bq, bk=bk,
                              k_col_scale=kvs_ref[0, 0, 0, 0],
                              v_col_scale=kvs_ref[0, 1, 0, 0])

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        o_ref[0] = (acc_sc[:] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_paged_flat_i8(q, pool_i8, pool_scales, tables,
                                   chunk_slot, chunk_base, chunk_n,
                                   layer, scale=None):
    """int8 flavor of the flat-stream kernel: pool_i8
    [L, 2, NB, Hk, Bt, D] int8 with mirrored per-row absmax scales
    pool_scales [L, 2, NB, Hk, 1, Bt] fp32 (the scales pool resolves
    through the SAME chunk-clamped table translation block-for-block,
    like the row-aligned decode_attention_paged_i8). q: [T, H, D] in
    the compute dtype; chunk metadata as decode_attention_paged_flat.
    Returns [T, H, D] in the QUERY dtype — the output of a quantized
    pool is fp, never int8."""
    t, h, d = q.shape
    hk, bt = pool_i8.shape[3], pool_i8.shape[4]
    nb = pool_i8.shape[2]
    nblk = tables.shape[1]
    group = h // hk
    nc = t // FLAT_CHUNK
    if t % FLAT_CHUNK:
        raise ValueError(
            f"decode_attention_paged_flat_i8: stream width {t} must be "
            f"a multiple of FLAT_CHUNK={FLAT_CHUNK} (gate with "
            "paged_flat_i8_is_supported)")
    if scale is None:
        scale = d ** -0.5
    if pool_i8.dtype != jnp.int8:
        raise ValueError(
            "decode_attention_paged_flat_i8: pool must be int8")
    if pool_scales.shape != pool_i8.shape[:4] + (1, bt):
        raise ValueError(
            "decode_attention_paged_flat_i8: scales must be "
            f"[L, 2, NB, Hk, 1, Bt], got {pool_scales.shape}")
    out_dtype = q.dtype
    qt = jnp.swapaxes(q, 0, 1)                    # [H, T, D]
    grid = (nc, h, nblk)

    def _blk(ci, j, cb_r, cn_r, tbl_r, cs_r):
        # same per-chunk last-valid-block clamp as the fp flavor
        last = (cb_r[ci] + jnp.maximum(cn_r[ci], 1) - 1) // bt
        return jnp.minimum(tbl_r[cs_r[ci], jnp.minimum(j, last)], nb - 1)

    kvidx = lambda ci, h_, j, lay_r, cs_r, cb_r, cn_r, tbl_r, g=group: (  # noqa: E731
        lay_r[0], 0, _blk(ci, j, cb_r, cn_r, tbl_r, cs_r), h_ // g, 0, 0)
    qidx = lambda ci, h_, j, lay_r, cs_r, cb_r, cn_r, tbl_r: (  # noqa: E731
        h_, ci, 0)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_paged_flat_i8_kernel, scale=float(scale),
                          bq=FLAT_CHUNK, bk=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, FLAT_CHUNK, d), qidx),
                pl.BlockSpec((1, 2, 1, 1, bt, d), kvidx),
                pl.BlockSpec((1, 2, 1, 1, 1, bt), kvidx),
            ],
            out_specs=pl.BlockSpec((1, FLAT_CHUNK, d), qidx),
            scratch_shapes=[
                pltpu.VMEM((FLAT_CHUNK, d), jnp.float32),
                pltpu.VMEM((FLAT_CHUNK, 1), jnp.float32),
                pltpu.VMEM((FLAT_CHUNK, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, t, d), out_dtype),
        name="decode_attention_paged_flat_i8",
        interpret=_pallas._interpret(),
    )(lay, chunk_slot.astype(jnp.int32), chunk_base.astype(jnp.int32),
      chunk_n.astype(jnp.int32), tables.astype(jnp.int32), qt, pool_i8,
      pool_scales)
    return jnp.swapaxes(out, 0, 1)
