"""Flash attention as a TPU Pallas (Mosaic) kernel.

Capability parity: paddle/phi/kernels/gpu/flash_attn_kernel.cu ::
FlashAttnKernel / flash_attn_grad_kernel.cu (FA-2 wrapper over
third_party/flashattn).  This is NOT a port of that CUDA: it is the
blockwise online-softmax algorithm laid out for the TPU memory hierarchy —
Q/K/V tiles staged in VMEM, the S = QK^T and P·V contractions on the MXU in
the INPUT dtype (bf16 runs at full MXU rate) with fp32 accumulation, the
softmax math and running stats (m, l) in fp32 VMEM scratch carried across
the KV-block grid dimension.

Layout convention follows the reference flash_attn API: [batch, seq,
num_heads, head_dim]; the wrapper transposes to [B, H, S, D] so the kernel
works on (seq, head_dim) tiles (last dim = lanes).

Supports: causal masking, GQA/MQA (kv_heads divides q_heads; realized in the
BlockSpec index_map — zero-copy), bf16/f32 inputs (dots in input dtype,
fp32 accumulate + softmax), seq
lengths not divisible by the block size (masked tail blocks).  Backward,
with delta = rowsum(dO * O) precomputed, is ONE kernel wherever a (batch,
head) plane's float32 dQ fits VMEM (``_bwd``): a single tile a plane gives
dq, dk and dv at once (``_bwd_fused``); several tiles run the dK/dV grid (KV
blocks, Q scanned) with dQ summed in a plane-sized VMEM accumulator beside
dK and dV (``_bwd_onepass``). Only a plane too long for that takes the
standard two-kernel split, dKV then dQ (grid over Q blocks, scan KV), which
computes S, P, dP and dS twice (``_bwd_pair``).

Masks are STRUCTURED: a rule on an entry's row and column, never an array.
Inside this file ``mask`` is ``None``, ``"causal"`` or
``("block_diffusion", L, b)`` (``block_diffusion_mask``): the attention rule
of block-diffusion training over ``[noisy ; clean]`` copies of an ``L``-token
sequence cut into blocks of ``b`` (``_bd_allowed``). Every kernel skips a tile
that holds no allowed entry, decided from the tile's first and last row and
column (``_tile_runs``), and applies the rule per entry elsewhere
(``_tile_mask``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas
from ...inference.telemetry import runtime_counter
from ...tensor.tensor import (computed_in_replay, kept_over_replay,
                              kept_region_open)

__all__ = ["flash_attention", "is_supported", "block_diffusion_mask",
           "dense_mask"]

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free
_NEVER = 1 << 30    # a column code no row code reaches


def block_diffusion_mask(seq_len, block_length):
    """The structured mask of block-diffusion training: rows and columns are
    positions of ``[noisy ; clean]``, two copies of a ``seq_len``-token
    sequence cut into blocks of ``block_length`` (``beta(j) = (j mod L) //
    b``; "noisy" is ``j < L``)::

        noisy i -> noisy j : beta(j) == beta(i)
        noisy i -> clean j : beta(j) <  beta(i)
        clean i -> clean j : beta(j) <= beta(i)
        clean i -> noisy j : never
    """
    seq_len, block_length = int(seq_len), int(block_length)
    if seq_len < 1 or block_length < 1:
        raise ValueError(f"block_diffusion_mask({seq_len}, {block_length})")
    return ("block_diffusion", seq_len, block_length)


def is_supported(q_shape, dtype, mask=None, k_shape=None,
                 dropout_p=0.0) -> bool:
    """Wrapper-level gate: rank-4 [B,S,H,D], supported dtype, head_dim ≤ 256;
    under a structured ``mask`` also self-attention over its ``2 L``
    positions and no dropout (the composite takes that combination)."""
    if len(q_shape) != 4:
        return False
    d = q_shape[-1]
    if d > 256:
        return False
    if mask is not None:
        if mask[0] != "block_diffusion" or dropout_p > 0.0 \
                or q_shape[1] != 2 * mask[1] \
                or (k_shape is not None and k_shape[1] != q_shape[1]):
            return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _block_sizes(sq: int, sk: int, d: int = 64):
    """The tiles follow from the shapes alone. 1024-wide (the cap): the
    [bq,d]x[d,bk] and [bq,bk]x[bk,d] dots must be large enough to fill
    the MXU pipeline — 128x128 tiles measure ~5-9 TFLOP/s on v5e,
    512x512 ~12, 1024x1024 ~16 (r3 s4 sweep:
    fwd+bwd 4.76 -> 3.56 ms/layer at the GPT-2 headline shape; headline
    step 91.7 -> 86.6 ms). VMEM per program at 1024 tiles is ~6 MB
    (s/p [1024,1024] f32 + q/k/v/acc tiles), still < the ~16 MB budget.
    Past head_dim 128 the cap is 512: at 256 the dk/dv kernel's
    1024 tiles ask for 17.05 MB of the 16 MB the v5e's compiler grants."""
    def pick(n, cap):
        if n < cap:
            return max(8, 1 << (n - 1).bit_length())
        # n >= cap: prefer the block size that minimizes ceil-padding —
        # e.g. S=1536 under a 1024 cap would pad to 2048 (+78% masked
        # tile compute) while 512 tiles fit exactly; ties go to the
        # larger (more MXU-efficient) block
        cands = [c for c in (cap, cap // 2) if c >= 256] or [cap]
        return min(cands, key=lambda c: (math.ceil(n / c) * c, -c))

    cap = 1024 if d <= 128 else 512
    return pick(sq, cap), pick(sk, cap)


# ---------------------------------------------------------------------------
# Structured masks: the rule per entry, and per tile
# ---------------------------------------------------------------------------

def _block_of(pos, b, xp):
    """``pos // b`` for positions >= 0 (a shift where ``b`` is a power of
    two): Python ints, numpy arrays or traced int32 alike."""
    if b & (b - 1) == 0:
        return pos >> (b.bit_length() - 1)
    return jax.lax.div(pos, jnp.int32(b)) if xp is jnp else pos // b


def _bd_allowed(rows, cols, seq, b):
    """The block-diffusion rule on positions ``rows`` [n, 1] and ``cols``
    [1, m] as two comparisons of integer codes: entry (i, j) is allowed iff
    ``same_c[j] == same_r[i]`` (both noisy, one block) or ``past_c[j] <
    past_r[i]`` (a clean column of an earlier block, for a clean row of its
    own block too). Positions past ``2 seq`` (a padded tail) get codes that
    allow nothing. Returns [n, m] bool."""
    def half(pos):
        noisy = pos < seq
        return noisy, _block_of(jnp.where(noisy, pos, pos - seq), b, jnp)
    r_noisy, r_blk = half(rows)
    c_noisy, c_blk = half(cols)
    same_r = jnp.where(r_noisy, r_blk, -2)
    past_r = jnp.where(rows < 2 * seq, jnp.where(r_noisy, r_blk, r_blk + 1), 0)
    same_c = jnp.where(c_noisy, c_blk, -1)
    past_c = jnp.where(c_noisy | (cols >= 2 * seq), _NEVER, c_blk)
    return (same_c == same_r) | (past_c < past_r)


def dense_mask(mask):
    """The structured ``mask`` as a boolean [S, S] array (True: allowed):
    what the composite applies off the chip, and what the tests hold the
    kernels to."""
    _, seq, b = mask
    pos = jnp.arange(2 * seq, dtype=jnp.int32)
    return _bd_allowed(pos[:, None], pos[None, :], seq, b)


def _tile_runs(mask, q_start, k_start, bq, bk, sq, sk, xp=jnp):
    """Whether the tile of rows ``q_start .. q_start + bq - 1`` and columns
    ``k_start .. k_start + bk - 1`` holds an allowed entry: a scalar, from
    the tile's first and last row and column alone. Traced on the program
    ids inside a kernel, and on numpy arrays of tile starts for the count
    of visited tiles."""
    if mask is None:
        return True
    if mask == "causal":
        # bottom-right alignment (FA2 convention): row i attends key j iff
        # j <= i + sk - sq; skip blocks strictly above that diagonal
        return q_start + bq - 1 + (sk - sq) >= k_start
    _, seq, b = mask
    r0, r1 = q_start, xp.minimum(q_start + bq, 2 * seq) - 1
    c0, c1 = k_start, xp.minimum(k_start + bk, 2 * seq) - 1

    def blk(pos):
        return _block_of(pos, b, xp)
    # the noisy and the clean part of the rows and of the columns, each a
    # range of blocks (used only where that part is not empty)
    r_noisy, r_clean = r0 < seq, r1 >= seq
    c_noisy, c_clean = c0 < seq, c1 >= seq
    rn_lo, rn_hi = blk(r0), blk(xp.minimum(r1, seq - 1))
    rc_hi = blk(r1 - seq)
    cn_lo, cn_hi = blk(c0), blk(xp.minimum(c1, seq - 1))
    cc_lo = blk(xp.maximum(c0, seq) - seq)
    return ((r_noisy & c_noisy & (cn_lo <= rn_hi) & (rn_lo <= cn_hi))
            | (r_noisy & c_clean & (cc_lo < rn_hi))
            | (r_clean & c_clean & (cc_lo <= rc_hi)))


def _tile_mask(mask, q_start, k_start, bq, bk, sq, sk, rows_too):
    """The allowed entries of a tile, [bq, bk] bool: inside the sequence
    (the key-padding tail; with ``rows_too`` the padded query rows as well)
    and under ``mask``."""
    single = isinstance(q_start, int)       # the one-tile kernel: starts 0
    if mask is None or mask == "causal":
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        if not single:
            rows = q_start + rows
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if not single:
            cols = k_start + cols
        ok = cols < sk
        if rows_too:
            ok = ok & (rows < sq)
        if mask == "causal":
            ok = ok & (cols <= rows + (sk - sq))
        return ok
    _, seq, b = mask
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return _bd_allowed(rows, cols, seq, b)


def _count_tiles(mask, planes, bq, bk, sq, sk):
    """``paddle_flash_tiles_visited_total`` / ``paddle_flash_tiles_total``:
    the tiles the kernels' grids compute and the tiles they have, counted
    when the kernels are traced, from the static grid: ``planes`` (batch x
    heads x kernels) times the tiles of one [sq, sk] plane."""
    q0 = np.arange(0, sq, bq, dtype=np.int64)[:, None]
    k0 = np.arange(0, sk, bk, dtype=np.int64)[None, :]
    runs = np.broadcast_to(_tile_runs(mask, q0, k0, bq, bk, sq, sk, np),
                           (q0.size, k0.size))
    runtime_counter("paddle_flash_tiles_visited_total",
                    planes * int(runs.sum()))
    runtime_counter("paddle_flash_tiles_total", planes * runs.size)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _drop_mode(drop):
    """How a kernel gets its dropout: 0 none, 1 a mask array (``('mask',
    dmask)``, interpret mode), 2 drawn in the kernel (``('prng', seed,
    p)``)."""
    return 0 if drop is None else (1 if drop[0] == "mask" else 2)


def _drop_tile(seed_ref, bi, hi, qi, ki, bq, bk, dropout_p):
    """Scaled keep multiplier generated in-kernel (TPU hardware PRNG, zero
    HBM traffic); seeded per (call, batch, head, q-block, k-block) so the
    backward kernels regenerate the identical mask. Mosaic takes at most 2
    seed words — fold the block coordinates into one."""
    nh = pl.num_programs(1)
    # q/k block counts differ between the three kernels' grids, but the
    # (qi, ki) pair itself is kernel-invariant; fold with fixed strides
    # large enough for any block count
    tile_id = ((bi * nh + hi) * 4096 + qi) * 4096 + ki
    pltpu.prng_seed(seed_ref[0], tile_id)
    bits = pltpu.prng_random_bits((bq, bk)).astype(jnp.uint32)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return jnp.where(bits >= thresh, 1.0 / (1.0 - dropout_p), 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, mask, sq, sk, bq, bk,
                drop_mode=0, dropout_p=0.0):
    # drop_mode: 0 = no dropout, 1 = mask input (interpret), 2 = in-kernel
    # PRNG (TPU). Mode 1/2 append dmask / SMEM seed to the inputs.
    if drop_mode == 1:
        dmask_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
        seed_ref = None
    elif drop_mode == 2:
        seed_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
        dmask_ref = None
    else:
        o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
        dmask_ref = seed_ref = None
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

    # skip a tile that holds no allowed entry (causal: strictly above the
    # aligned diagonal) entirely
    @pl.when(_tile_runs(mask, q_start, k_start, bq, bk, sq, sk))
    def _():
        # dots run in the input dtype (bf16 MXU full rate) with f32
        # accumulation; only the softmax math is f32
        q = q_ref[0, 0]                               # [bq, d]
        k = k_ref[0, 0]                               # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk] f32

        ok = _tile_mask(mask, q_start, k_start, bq, bk, sq, sk, False)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_sc[:]                                   # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # [bq, bk]
        p = jnp.where(ok, p, 0.0)

        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:] = m_new
        # dropout on the softmax probs (post-normalization semantics: the
        # l denominator above uses the raw p)
        if dmask_ref is not None:
            p = p * dmask_ref[0, 0]
        elif seed_ref is not None:
            p = p * _drop_tile(seed_ref, pl.program_id(0), pl.program_id(1),
                               qi, ki, bq, bk, dropout_p)
        v = v_ref[0, 0]                                    # [bk, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + pv

    @pl.when(ki == nk - 1)
    def _():
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)     # padded q rows: garbage-free
        o_ref[0, 0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[:] + jnp.log(l_safe)      # [bq, 1]


def _scanned_spec(mask, rows, d, group, bq, bk, own_is_q):
    """The BlockSpec of an operand whose tiles a kernel scans along its
    last grid axis (``rows`` each; K and V have a head for every ``group``
    query heads, the Q side has ``group`` None): tile ``j`` at step ``(b,
    h, own, j)``. Under a structured mask a step that is skipped holds its
    own tile's diagonal tile instead (position i always sees position i, so
    that tile runs; rows and columns are one sequence there, so ``bq ==
    bk``): consecutive skipped steps then name one tile and their copies
    are elided, where ``j`` would fetch a tile nobody reads (forward +
    backward of 16,384 positions: 60.5 ms without this, 50.8 with; chip run
    of PR 32, PERF.md section 6)."""
    def head(h_):
        return h_ if group is None else h_ // group

    def scanned(own, j):
        if mask is None or mask == "causal":
            return j
        q0, k0 = (own * bq, j * bk) if own_is_q else (j * bq, own * bk)
        return jnp.where(_tile_runs(mask, q0, k0, bq, bk, None, None), j, own)
    return pl.BlockSpec((1, 1, rows, d), lambda b_, h_, i, j: (
        b_, head(h_), scanned(i, j), 0))


def _fwd(q, k, v, drop=None, *, mask, scale, bq, bk):
    """q,k,v: [B,H,S,D] (kv may have fewer heads for GQA). Returns (o, lse).
    drop: None, ('mask', dmask [B,H,Sq_p,Sk_p] f32) or ('prng', seed, p)."""
    b, h, sq, d = q.shape
    hk = k.shape[1]
    group = h // hk
    sq_p = math.ceil(sq / bq) * bq
    sk_p = math.ceil(k.shape[2] / bk) * bk
    sk = k.shape[2]
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    grid = (b, h, sq_p // bq, sk_p // bk)
    drop_mode = _drop_mode(drop)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, mask=mask, sq=sq, sk=sk, bq=bq, bk=bk,
        drop_mode=drop_mode,
        dropout_p=drop[2] if drop_mode == 2 else 0.0)
    _count_tiles(mask, b * h, bq, bk, sq, sk)
    kvspec = _scanned_spec(mask, bk, d, group, bq, bk, True)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        kvspec, kvspec,
    ]
    args = [q, k, v]
    if drop_mode == 1:
        in_specs.append(pl.BlockSpec((1, 1, bq, bk),
                                     lambda b_, h_, i, j: (b_, h_, i, j)))
        args.append(drop[1])
    elif drop_mode == 2:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.reshape(drop[1].astype(jnp.int32), (1,)))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=_pallas._interpret(),
    )(*args)
    return o[:, :, :sq], lse[:, :, :sq]        # lse: [B, H, Sq, 1]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, mask, sq, sk, bq, bk, drop_mode=0,
                    dropout_p=0.0, with_dq=False):
    """dK and dV of K tile ``ki``, summed over the Q tiles scanned innermost.
    ``with_dq`` (``_bwd_onepass``): dQ too, from the same S, P, dP and dS:
    ``dq_sc`` holds the whole plane's dQ ``[sq_p, d]`` in float32, row tile
    ``qi`` of it zeroed at the first K tile, summed over the K tiles in
    ascending order as ``_bwd_dq_kernel`` sums them, and written to the
    plane's output block at the last."""
    dmask_ref = seed_ref = dq_ref = dq_sc = None
    if drop_mode == 1:
        dmask_ref, *rest = rest
    elif drop_mode == 2:
        seed_ref, *rest = rest
    if with_dq:
        dk_ref, dv_ref, dq_ref, dk_sc, dv_sc, dq_sc = rest
    else:
        dk_ref, dv_ref, dk_sc, dv_sc = rest
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q_start = qi * bq
    k_start = ki * bk
    if with_dq:
        rows = pl.ds(pl.multiple_of(q_start, bq), bq)

        @pl.when(ki == 0)
        def _():
            dq_sc[rows, :] = jnp.zeros((bq, dq_sc.shape[1]), jnp.float32)

    @pl.when(_tile_runs(mask, q_start, k_start, bq, bk, sq, sk))
    def _():
        q = q_ref[0, 0]                                   # [bq, d]
        k = k_ref[0, 0]                                   # [bk, d]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                               # [bq, 1]
        delta = delta_ref[0, 0]                           # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(mask, q_start, k_start, bq, bk, sq, sk, True)
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)          # [bq, bk] f32

        if dmask_ref is not None:
            dm = dmask_ref[0, 0]
        elif seed_ref is not None:
            # same (b, h, q-block, k-block) seeding as the forward kernel
            dm = _drop_tile(seed_ref, pl.program_id(0), pl.program_id(1),
                            qi, ki, bq, bk, dropout_p)
        else:
            dm = None
        # dv += (D∘P)^T dO
        pd = p * dm if dm is not None else p
        dv_sc[:] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds = P * (D∘(dO V^T) - delta) * scale   (delta = rowsum(dO∘O)
        # absorbs the dropout mask exactly — see derivation in _flash_bwd)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dm is not None:
            dp = dp * dm
        ds = p * (dp - delta) * scale
        # dk += dS^T Q
        ds = ds.astype(q.dtype)
        dk_sc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            # dq[rows] += dS K: the fifth product of the tile
            dq_sc[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when(ki == pl.num_programs(2) - 1)
        def _():
            dq_ref[0, 0, rows, :] = dq_sc[rows, :].astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, mask, sq, sk, bq, bk, drop_mode=0,
                   dropout_p=0.0):
    if drop_mode == 1:
        dmask_ref, dq_ref, dq_sc = rest
        seed_ref = None
    elif drop_mode == 2:
        seed_ref, dq_ref, dq_sc = rest
        dmask_ref = None
    else:
        dq_ref, dq_sc = rest
        dmask_ref = seed_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q_start = qi * bq
    k_start = ki * bk

    @pl.when(_tile_runs(mask, q_start, k_start, bq, bk, sq, sk))
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                               # [bq, 1]
        delta = delta_ref[0, 0]                           # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(mask, q_start, k_start, bq, bk, sq, sk, True)
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dmask_ref is not None:
            dp = dp * dmask_ref[0, 0]
        elif seed_ref is not None:
            dp = dp * _drop_tile(seed_ref, pl.program_id(0),
                                 pl.program_id(1), qi, ki, bq, bk, dropout_p)
        ds = p * (dp - delta) * scale
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, scale, mask, sq, sk, drop_mode=0,
                      dropout_p=0.0):
    """Single-block backward: when the whole (b, h) slice fits one
    (bq, bk) tile (the common S <= 1024 training shape), dq, dk and dv
    come out of ONE kernel — S and dP are computed once instead of once
    per split kernel (9 dots -> 7) and q/k/v/do are read once instead of
    twice. Measured r3 s4: attention fwd+bwd 32.1 -> ~24 ms/step on the
    GPT-2 headline."""
    if drop_mode == 1:
        dmask_ref, dq_ref, dk_ref, dv_ref = rest
        seed_ref = None
    elif drop_mode == 2:
        seed_ref, dq_ref, dk_ref, dv_ref = rest
        dmask_ref = None
    else:
        dq_ref, dk_ref, dv_ref = rest
        dmask_ref = seed_ref = None
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    q = q_ref[0, 0]                                   # [bq, d]
    k = k_ref[0, 0]                                   # [bk, d]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]                               # [bq, 1]
    delta = delta_ref[0, 0]                           # [bq, 1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = _tile_mask(mask, 0, 0, bq, bk, sq, sk, True)
    p = jnp.where(ok, jnp.exp(s - lse), 0.0)          # [bq, bk] f32

    if dmask_ref is not None:
        dm = dmask_ref[0, 0]
    elif seed_ref is not None:
        # same (b, h, q-block=0, k-block=0) seeding as the forward kernel
        dm = _drop_tile(seed_ref, pl.program_id(0), pl.program_id(1),
                        0, 0, bq, bk, dropout_p)
    else:
        dm = None
    pd = p * dm if dm is not None else p
    dv_ref[0, 0] = jax.lax.dot_general(
        pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if dm is not None:
        dp = dp * dm
    ds = p * (dp - delta) * scale                     # [bq, bk] f32
    dk_ref[0, 0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)
    dq_ref[0, 0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _bwd_fused(q_, k_, v_, do_, lse_, delta_, drop, drop_arg, *,
               mask, scale, sq, sk, group):
    """Single-block fused backward dispatch; inputs are pre-padded to one
    (bq, bk) = (sq_p, sk_p) block. Returns (dq, dk_perq, dv_perq) with dk/dv
    still per-q-head (GQA segment-sum happens in the caller)."""
    b, h, sq_p, d = q_.shape
    sk_p = k_.shape[2]
    drop_mode = _drop_mode(drop)
    qspec = pl.BlockSpec((1, 1, sq_p, d), lambda b_, h_: (b_, h_, 0, 0))
    kspec = pl.BlockSpec((1, 1, sk_p, d),
                         lambda b_, h_, g=group: (b_, h_ // g, 0, 0))
    rowspec = pl.BlockSpec((1, 1, sq_p, 1), lambda b_, h_: (b_, h_, 0, 0))
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    args = [q_, k_, v_, do_, lse_, delta_]
    if drop_mode == 1:
        in_specs.append(pl.BlockSpec((1, 1, sq_p, sk_p),
                                     lambda b_, h_: (b_, h_, 0, 0)))
        args.append(drop_arg())
    elif drop_mode == 2:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(drop_arg())
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, mask=mask,
                          sq=sq, sk=sk, drop_mode=drop_mode,
                          dropout_p=drop[2] if drop_mode == 2 else 0.0),
        grid=(b, h),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, sq_p, d), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, sk_p, d), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, sk_p, d), lambda b_, h_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d), q_.dtype),
            jax.ShapeDtypeStruct((b, h, sk_p, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk_p, d), jnp.float32),
        ],
        name="flash_attention_bwd_fused",
        interpret=_pallas._interpret(),
    )(*args)
    return dq, dk, dv


# The one-pass backward keeps a plane's dQ [sq_p, d] in float32 for the whole
# plane: 8 MiB at 16,384 positions of head 128 and at 8,192 of head 256. The
# v5e has 128 MiB of VMEM a core (the 16 MiB a kernel gets by default are a
# scoped limit, which ``vmem_limit_bytes`` lifts); an accumulator of up to an
# eighth of that leaves its output block's two buffers (as much again in
# float32) and the tiles well inside it: 63.1 MiB at the most, float32 at
# 32,768 positions of head 128 (sandbox AOT, PR 38). A longer plane takes
# the pair.
_ONEPASS_DQ_BYTES = 16 << 20
# what the compiler may keep beside the buffers ``_onepass_vmem_bytes``
# counts; the limit is a ceiling, not an allocation
_ONEPASS_HEADROOM = 8 << 20


def _lanes(d):
    return -(-d // 128) * 128       # a row of d features in VMEM


def _onepass_vmem_bytes(sq_p, bq, bk, d, itemsize):
    """What ``flash_attention_bwd_onepass`` holds in VMEM: the plane's dQ
    accumulator and its output block (two buffers); the tiles of q, do, k,
    v, dk, dv (two buffers each) and of lse and delta (a lane of 128 each);
    the dK and dV scratch; and a tile's [bq, bk] temporaries, S / P, dP and
    dS in float32 and P and dS in the input dtype. The v5e's compiler
    allocates 27.6 MiB at SDAR's shape and 23.1 at Qwen3-Next's where this
    counts 39 and 26 (sandbox AOT, PR 38: it keeps fewer temporaries
    alive)."""
    row = _lanes(d)
    plane = sq_p * row * (4 + 2 * itemsize)
    tiles = 2 * (2 * bq * row * itemsize + 2 * bk * row * itemsize
                 + 2 * bk * row * 4 + 2 * bq * 128 * 4)
    return (plane + tiles + 2 * bk * row * 4
            + bq * bk * (3 * 4 + 2 * itemsize))


def _dkv_call(q_, k_, v_, do_, lse_, delta_, drop, drop_arg, *,
              mask, scale, sq, sk, bq, bk, group, with_dq):
    """What the dK/dV kernel's two ``pallas_call``s share: grid (b, h, K
    tile j, Q tile i), the Q side scanned; with ``with_dq`` the one-pass
    kernel, which keeps the plane's dQ in VMEM as well. Returns the kernel,
    the call's keywords but its name, and the operands; the call gives (dk,
    dv) or (dk, dv, dq), dk and dv float32 and a query head's own."""
    b, h, sq_p, d = q_.shape
    sk_p = k_.shape[2]
    drop_mode = _drop_mode(drop)
    qspec = _scanned_spec(mask, bq, d, None, bq, bk, False)
    kspec = pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, j, i, g=group: (b_, h_ // g, j, 0))
    rowspec = _scanned_spec(mask, bq, 1, None, bq, bk, False)
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    args = [q_, k_, v_, do_, lse_, delta_]
    if drop_mode == 1:
        in_specs.append(pl.BlockSpec((1, 1, bq, bk),
                                     lambda b_, h_, j, i: (b_, h_, i, j)))
        args.append(drop_arg())
    elif drop_mode == 2:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(drop_arg())
    kv_out = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0))
    out_specs = [kv_out, kv_out]
    out_shape = [jax.ShapeDtypeStruct((b, h, sk_p, d), jnp.float32)] * 2
    scratch = [pltpu.VMEM((bk, d), jnp.float32)] * 2
    compiler_params = None
    if with_dq:
        # the plane's block: its index moves with (b, h) alone, so it is
        # written back once a plane
        out_specs.append(pl.BlockSpec((1, 1, sq_p, d),
                                      lambda b_, h_, j, i: (b_, h_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq_p, d), q_.dtype))
        scratch.append(pltpu.VMEM((sq_p, d), jnp.float32))
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=_onepass_vmem_bytes(
                sq_p, bq, bk, d, q_.dtype.itemsize) + _ONEPASS_HEADROOM)
    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, mask=mask, sq=sq, sk=sk, bq=bq, bk=bk,
        drop_mode=drop_mode, dropout_p=drop[2] if drop_mode == 2 else 0.0,
        with_dq=with_dq)
    return kernel, dict(
        grid=(b, h, sk_p // bk, sq_p // bq), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=compiler_params,
        interpret=_pallas._interpret()), args


def _bwd_onepass(q_, k_, v_, do_, lse_, delta_, drop, drop_arg, *,
                 mask, scale, sq, sk, bq, bk, group):
    """Several tiles a plane, ONE kernel: S, P, dP and dS once a visited
    tile, five products (dV, dP, dK, dQ and S itself) where the pair runs
    seven. Inputs pre-padded to whole tiles; returns (dq, dk, dv), dk and dv
    still a query head's own, as ``_bwd_fused`` and ``_bwd_pair`` do."""
    b, h = q_.shape[:2]
    _count_tiles(mask, b * h, bq, bk, sq, sk)
    kernel, call, args = _dkv_call(
        q_, k_, v_, do_, lse_, delta_, drop, drop_arg, mask=mask,
        scale=scale, sq=sq, sk=sk, bq=bq, bk=bk, group=group, with_dq=True)
    dk, dv, dq = pl.pallas_call(
        kernel, name="flash_attention_bwd_onepass", **call)(*args)
    return dq, dk, dv


def _bwd_pair(q_, k_, v_, do_, lse_, delta_, drop, drop_arg, *,
              mask, scale, sq, sk, bq, bk, group):
    """Several tiles a plane whose dQ outgrows ``_ONEPASS_DQ_BYTES``: the
    standard split, dK/dV (grid over KV blocks, Q scanned) then dQ (grid
    over Q blocks, KV scanned, a tile-sized accumulator), each computing S,
    P, dP and dS of every visited tile. Same arguments and results as
    ``_bwd_onepass``."""
    b, h, sq_p, d = q_.shape
    sk_p = k_.shape[2]
    drop_mode = _drop_mode(drop)
    _count_tiles(mask, 2 * b * h, bq, bk, sq, sk)    # dK/dV and dQ
    # GQA: per-Q-head dk/dv (shape [B,H,...]), segment-summed to [B,Hk,...]
    # by the caller — XLA turns that into a cheap reshape-sum.
    kernel, call, args = _dkv_call(
        q_, k_, v_, do_, lse_, delta_, drop, drop_arg, mask=mask,
        scale=scale, sq=sq, sk=sk, bq=bq, bk=bk, group=group, with_dq=False)
    dk, dv = pl.pallas_call(
        kernel, name="flash_attention_bwd_dkv", **call)(*args)

    qspec2 = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kspec2 = _scanned_spec(mask, bk, d, group, bq, bk, True)
    rowspec2 = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    dq_in = [qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2]
    dq_args = [q_, k_, v_, do_, lse_, delta_]
    if drop_mode == 1:
        dq_in.append(pl.BlockSpec((1, 1, bq, bk),
                                  lambda b_, h_, i, j: (b_, h_, i, j)))
        dq_args.append(drop_arg())
    elif drop_mode == 2:
        dq_in.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_args.append(drop_arg())
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, mask=mask,
                          sq=sq, sk=sk, bq=bq, bk=bk, drop_mode=drop_mode,
                          dropout_p=drop[2] if drop_mode == 2 else 0.0),
        grid=(b, h, sq_p // bq, sk_p // bk),
        in_specs=dq_in,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q_.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=_pallas._interpret(),
    )(*dq_args)
    return dq, dk, dv


def _bwd(q, k, v, o, lse, do, drop=None, *, mask, scale, bq, bk):
    """dq, dk, dv. Which kernels run is decided here, from shapes alone: one
    tile a plane -> ``_bwd_fused``; several, and the plane's float32 dQ
    within ``_ONEPASS_DQ_BYTES`` -> ``_bwd_onepass``; a longer plane ->
    ``_bwd_pair``. ``paddle_flash_bwd_onepass_traces_total`` /
    ``paddle_flash_bwd_split_traces_total`` count the traces of the last
    two."""
    b, h, sq, d = q.shape
    hk = k.shape[1]
    group = h // hk
    sk = k.shape[2]
    sq_p = math.ceil(sq / bq) * bq
    sk_p = math.ceil(sk / bk) * bk
    drop_mode = _drop_mode(drop)

    def drop_arg():
        if drop_mode == 1:
            return drop[1]
        return jnp.reshape(drop[1].astype(jnp.int32), (1,))

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [B, H, Sq, 1]

    def padq(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))) \
            if sq_p != sq else x

    def padk(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))) \
            if sk_p != sk else x

    if sq_p == bq and sk_p == bk:
        # whole slice is one block: dq, dk and dv at once, no tile to scan
        run = _bwd_fused
    elif sq_p * _lanes(d) * 4 <= _ONEPASS_DQ_BYTES:
        run = functools.partial(_bwd_onepass, bq=bq, bk=bk)
        runtime_counter("paddle_flash_bwd_onepass_traces_total", 1)
    else:
        run = functools.partial(_bwd_pair, bq=bq, bk=bk)
        runtime_counter("paddle_flash_bwd_split_traces_total", 1)
    dq, dk, dv = run(
        padq(q), padk(k), padk(v), padq(do), padq(lse), padq(delta), drop,
        drop_arg, mask=mask, scale=scale, sq=sq, sk=sk, group=group)
    dq = dq[:, :, :sq]
    dk = dk[:, :, :sk]
    dv = dv[:, :, :sk]
    if group > 1:
        dk = dk.reshape(b, hk, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, hk, group, sk, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API (custom_vjp; [B, S, H, D] layout like the reference flash_attn)
# ---------------------------------------------------------------------------

def _dropout_mask(seed, shape, dropout_p):
    """Scaled keep-mask [B,H,Sq_p,Sk_p] regenerated identically fwd/bwd from
    the int32 seed — the residual is the seed, not the O(S^2) mask (the
    philox-offset recompute trick of the reference FA2, done with the JAX
    PRNG at the XLA level)."""
    key = jax.random.PRNGKey(seed)
    keep = jax.random.bernoulli(key, 1.0 - dropout_p, shape)
    return keep.astype(jnp.float32) / (1.0 - dropout_p)


def _padded_sizes(sq, sk, d=64):
    bq, bk = _block_sizes(sq, sk, d)
    return bq, bk, math.ceil(sq / bq) * bq, math.ceil(sk / bk) * bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, seed, mask, scale, dropout_p):
    o, _ = _core_fwd(q, k, v, seed, mask, scale, dropout_p)
    return o


def _make_drop(q, k, seed, dropout_p):
    """TPU: in-kernel PRNG (zero HBM mask traffic); interpret: explicit
    seed-regenerated mask array (prng_* primitives have no CPU lowering)."""
    if dropout_p <= 0.0:
        return None
    if not _pallas._interpret():
        return ("prng", seed, dropout_p)
    bq, bk, sq_p, sk_p = _padded_sizes(q.shape[2], k.shape[2], q.shape[3])
    return ("mask",
            _dropout_mask(seed, (q.shape[0], q.shape[1], sq_p, sk_p),
                          dropout_p))


def _core_fwd(q, k, v, seed, mask, scale, dropout_p):
    bq, bk, _, _ = _padded_sizes(q.shape[2], k.shape[2], q.shape[3])
    drop = _make_drop(q, k, seed, dropout_p)
    return _fwd(q, k, v, drop, mask=mask, scale=scale, bq=bq, bk=bk)


def _flash_fwd(q, k, v, seed, mask, scale, dropout_p):
    o, lse = _core_fwd(q, k, v, seed, mask, scale, dropout_p)
    return o, (q, k, v, o, lse, seed)


def _flash_bwd(mask, scale, dropout_p, res, g):
    q, k, v, o, lse, seed = res
    bq, bk, _, _ = _padded_sizes(q.shape[2], k.shape[2], q.shape[3])
    drop = _make_drop(q, k, seed, dropout_p)
    dq, dk, dv = _bwd(q, k, v, o, lse, g, drop, mask=mask, scale=scale,
                      bq=bq, bk=bk)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash_kept(q, k, v, seed, o, lse, mask, scale, dropout_p):
    """``_flash`` whose forward kernel has run: ``o`` and ``lse`` [B, H, S]
    are what it wrote. The backward is ``_flash_bwd`` on them."""
    return o


def _flash_kept_fwd(q, k, v, seed, o, lse, mask, scale, dropout_p):
    return o, (q, k, v, o, lse, seed)


def _flash_kept_bwd(mask, scale, dropout_p, res, g):
    q, k, v, o, lse, seed = res
    return _flash_bwd(mask, scale, dropout_p,
                      (q, k, v, o, lse[..., None], seed), g) + (None, None)


_flash_kept.defvjp(_flash_kept_fwd, _flash_kept_bwd)


def _flash_in_region(q, k, v, seed, mask, scale, dropout_p):
    """``_flash`` inside a ``fleet.utils.recompute`` region: the forward
    kernel runs in the region's first forward alone, and the replay gets its
    ``o`` and ``lse`` back (``tensor.kept_over_replay``). ``lse`` is kept
    without the kernel's last axis of 1, which HBM pads to 128 lanes."""
    def first():
        o, lse = _core_fwd(q, k, v, seed, mask, scale, dropout_p)
        return o, lse[..., 0]
    call = tuple((x.shape, x.dtype) for x in (q, k, v)) + (
        mask, scale, dropout_p)
    o, lse = kept_over_replay("flash_attention_fwd", call, first)
    return _flash_kept(q, k, v, seed, o, lse, mask, scale, dropout_p)


def flash_attention(q, k, v, causal=False, scale=None, dropout_p=0.0,
                    dropout_seed=None, mask=None):
    """q,k,v: [batch, seq, heads, head_dim] (kv heads may divide q heads).

    Returns [batch, seq, heads, head_dim]; differentiable (custom VJP with
    flash backward kernels). dropout_p > 0 applies attention-prob dropout
    (upscaled) with a seed-regenerated mask — pass dropout_seed (int32
    scalar, traced ok) for reproducibility. ``mask`` is a structured mask
    (``block_diffusion_mask(L, b)``, over ``2 L`` positions of q and k
    alike; not with ``causal``, not with dropout), never an array.
    Inside a ``fleet.utils.recompute`` region the forward kernel runs once:
    the region keeps its outputs for the replay (``_flash_in_region``). Not
    on a mesh of several devices, where this call is the body of a
    ``shard_map`` (``nn.functional.attention._per_shard``) and a value made
    in one such body cannot be handed to another: there the replay computes
    them again.
    """
    if mask is not None and (causal or not is_supported(
            q.shape, q.dtype, mask, k.shape, dropout_p)):
        raise ValueError(
            f"flash_attention: mask {mask!r} with q {q.shape}, k {k.shape}, "
            f"causal={causal}, dropout_p={dropout_p}: a structured mask "
            "covers its own 2 L positions, alone")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(
            f"q heads ({q.shape[2]}) must be a multiple of kv heads "
            f"({k.shape[2]}) for GQA flash attention")
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if dropout_seed is None:
        dropout_seed = jnp.zeros((), jnp.int32)
    from ...parallel import current_mesh
    flash = _flash
    mesh = current_mesh()
    if mesh is not None and mesh.devices.size > 1:
        computed_in_replay()
    elif kept_region_open():
        flash = _flash_in_region
    o = flash(qt, kt, vt, dropout_seed, "causal" if causal else mask,
              float(scale), float(dropout_p))
    return jnp.swapaxes(o, 1, 2)
