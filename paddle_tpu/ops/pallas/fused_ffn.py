"""Fused transformer FFN as one TPU Pallas kernel.

Capability parity: paddle/fluid/operators/fused/fused_feedforward_op.cu
(the training-side fused FFN block the BASELINE north-star names). NOT a
port: one pallas_call computes  out = gelu(x @ W1 + b1) @ W2 + b2  with
the [bm, bf] activation tile living ONLY in VMEM — the [M, F] gelu
intermediate (50 MB at the GPT-2 headline shape) is never written to or
read back from HBM. Grid: (M/bm, F/bf) with the F axis innermost; the
fp32 output accumulator is revisited across F blocks and written once.

Backward (custom_vjp) recomputes the intermediate from x (flash-style
residual discipline: only the INPUTS are saved). Default: plain-jnp grad
matmuls. Opt-in PADDLE_TPU_FUSED_FFN_BWD=1: a two-kernel Pallas backward
(dx kernel + dw1/dw2/db1 kernel — see the bwd section) that keeps every
[M, F] intermediate (pre/t/dt/dpre, 4 x ~50 MB fp32 at the headline
shape) in VMEM tiles. Both halves A/B'd against the XLA composite on TPU
before becoming any default (the r3 LayerNorm lesson: pallas_call is a
fusion barrier, composites sometimes win — measure).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

__all__ = ["fused_ffn", "ffn_is_supported"]


def _gelu_tanh(x):
    # GPT-2's approximate gelu, computed in fp32 inside the kernel
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _gelu_erf(x):
    # exact gelu (the reference fused_feedforward_op's "gelu")
    return 0.5 * x * (1.0 + jax.lax.erf(x * (2.0 ** -0.5)))


_ACTS = {"gelu_tanh": _gelu_tanh, "gelu": _gelu_erf}


def ffn_is_supported(m, k, f, dtype) -> bool:
    """x: [M, K], W1: [K, F], W2: [F, K]. Lane-dim tiling: K and F must
    be 128-multiples (the bench shapes are: 768/3072, 1024/2816...)."""
    if k % 128 or f % 128:
        return False
    if m < 8:
        return False
    return jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16, jnp.float16)


def _kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc_sc,
            *, bm, bf, nf, act="gelu_tanh"):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    x = x_ref[...]                                   # [bm, K]
    w1 = w1_ref[...]                                 # [K, bf]
    pre = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    pre = pre + b1_ref[...].astype(jnp.float32)      # [bm, bf]
    t = _ACTS[act](pre).astype(x.dtype)
    w2 = w2_ref[...]                                 # [bf, K]
    acc_sc[:] += jax.lax.dot_general(t, w2, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _():
        o_ref[...] = (acc_sc[:] +
                      b2_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _fwd_kernel_call(x, w1, b1, w2, b2, bm, bf, act):
    m, k = x.shape
    f = w1.shape[1]
    nf = f // bf
    grid = (m // bm, nf)
    return pl.pallas_call(
        functools.partial(_kernel, bm=bm, bf=bf, nf=nf, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((k, bf), lambda mi, fi: (0, fi)),
            pl.BlockSpec((1, bf), lambda mi, fi: (0, fi)),
            pl.BlockSpec((bf, k), lambda mi, fi: (fi, 0)),
            pl.BlockSpec((1, k), lambda mi, fi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda mi, fi: (mi, 0)),
        scratch_shapes=[pltpu.VMEM((bm, k), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        name="fused_ffn_fwd",
        interpret=_pallas._interpret(),
    )(x, w1, b1.reshape(1, f), w2, b2.reshape(1, k))


def _pick_bm(m, k, f, bf, dtype):
    """Row-tile: big enough to feed the MXU, small enough that
    x + w1/w2 blocks + fp32 acc fit VMEM (~16 MB budget)."""
    itemsize = jnp.dtype(dtype).itemsize
    for bm in (1024, 512, 256, 128, 64, 32, 16, 8):
        if m % bm:
            continue
        vmem = (bm * k * itemsize          # x tile
                + 2 * k * bf * itemsize    # w1 + w2 blocks
                + bm * bf * 4              # pre/t tile (fp32)
                + bm * k * 4)              # accumulator
        if vmem <= 12 * 1024 * 1024:
            return bm
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_ffn(x, w1, b1, w2, b2, activation="gelu_tanh"):
    """out = act(x @ w1 + b1) @ w2 + b2 with act in {gelu_tanh, gelu
    (exact/erf)}; x: [..., K] flattened to [M, K] internally. Falls back
    to the XLA composite when shapes don't tile (callers may also gate
    on ffn_is_supported)."""
    out, _ = _fused_ffn_fwd(x, w1, b1, w2, b2, activation)
    return out


def _composite(x2, w1, b1, w2, b2, activation="gelu_tanh"):
    t = _ACTS[activation]((x2 @ w1 + b1).astype(jnp.float32)) \
        .astype(x2.dtype)
    return t @ w2 + b2


def _fused_ffn_fwd(x, w1, b1, w2, b2, activation="gelu_tanh"):
    lead = x.shape[:-1]
    k = x.shape[-1]
    f = w1.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bf = _pick_bf(f)
    bm = _pick_bm(m, k, f, bf or 128, x.dtype)
    if not ffn_is_supported(m, k, f, x.dtype) or bm is None or bf is None:
        out = _composite(x2, w1, b1, w2, b2, activation)
    else:
        out = _fwd_kernel_call(x2, w1, b1, w2, b2, bm, bf, activation)
    return out.reshape(*lead, k), (x, w1, b1, w2, b2)


def _dgelu(pre, activation):
    if activation == "gelu_tanh":
        c = math.sqrt(2.0 / math.pi)
        u = c * (pre + 0.044715 * pre ** 3)
        th = jnp.tanh(u)
        return 0.5 * (1.0 + th) + 0.5 * pre * (1.0 - th * th) * c * (
            1.0 + 3 * 0.044715 * pre ** 2)
    # exact gelu: d/dx = Phi(x) + x*phi(x)
    return (0.5 * (1.0 + jax.lax.erf(pre * (2.0 ** -0.5)))
            + pre * jnp.exp(-0.5 * pre * pre)
            * (1.0 / math.sqrt(2.0 * math.pi)))


# ---------------------------------------------------------------------------
# Fused BACKWARD (opt-in PADDLE_TPU_FUSED_FFN_BWD=1 — gated on the
# forward's on-chip A/B first, r5 verdict #5). The composite backward
# materializes pre/t/dt/dpre at [M, F] in fp32 (4 x ~50 MB of HBM
# traffic at the GPT-2 headline shape); these kernels recompute the
# [bm, bf] tiles in VMEM instead, reading only x/g row tiles and weight
# blocks. A Pallas TPU output block may only be revisited on CONSECUTIVE
# grid steps, and dx accumulates over F while dw1/dw2/db1 accumulate
# over M — two kernels with opposite inner grid axes:
#   bwd-dx : grid (M/bm, F/bf), F inner, dx_acc revisited per row tile;
#   bwd-dw : grid (F/bf, M/bm), M inner, dw1/dw2/db1 accs per F block.
# Reference: the grad kernels of fused_feedforward_op.cu.
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(x_ref, g_ref, w1_ref, b1_ref, w2_ref, o_ref, acc_sc,
                   *, nf, act):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    x = x_ref[...]                                   # [bm, K]
    g = g_ref[...]                                   # [bm, K]
    pre = jax.lax.dot_general(x, w1_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    pre = pre + b1_ref[...].astype(jnp.float32)      # [bm, bf]
    dt = jax.lax.dot_general(g, w2_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dpre = (dt * _dgelu(pre, act)).astype(x.dtype)   # [bm, bf]
    acc_sc[:] += jax.lax.dot_general(dpre, w1_ref[...],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _():
        o_ref[...] = acc_sc[:].astype(o_ref.dtype)


def _bwd_dw_kernel(x_ref, g_ref, w1_ref, b1_ref, w2_ref,
                   dw1_ref, dw2_ref, db1_ref,
                   dw1_sc, dw2_sc, db1_sc, *, nm, act):
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _():
        dw1_sc[:] = jnp.zeros_like(dw1_sc)
        dw2_sc[:] = jnp.zeros_like(dw2_sc)
        db1_sc[:] = jnp.zeros_like(db1_sc)

    x = x_ref[...]                                   # [bm, K]
    g = g_ref[...]                                   # [bm, K]
    pre = jax.lax.dot_general(x, w1_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    pre = pre + b1_ref[...].astype(jnp.float32)      # [bm, bf]
    t = _ACTS[act](pre).astype(x.dtype)
    dt = jax.lax.dot_general(g, w2_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dpre32 = dt * _dgelu(pre, act)
    dpre = dpre32.astype(x.dtype)
    dw1_sc[:] += jax.lax.dot_general(x, dpre, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dw2_sc[:] += jax.lax.dot_general(t, g, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    db1_sc[:] += jnp.sum(dpre32, axis=0, keepdims=True)

    @pl.when(mi == nm - 1)
    def _():
        dw1_ref[...] = dw1_sc[:].astype(dw1_ref.dtype)
        dw2_ref[...] = dw2_sc[:].astype(dw2_ref.dtype)
        db1_ref[...] = db1_sc[:].astype(db1_ref.dtype)


def _pick_bf(f):
    """Shared F-tile choice: bf must DIVIDE f exactly (nf = f // bf
    silently drops tail columns otherwise) — fwd and bwd must agree."""
    return next((c for c in (512, 256, 128) if f % c == 0), None)


def _pick_bm_bwd(m, k, bf, dtype, which):
    """Row tile for ONE bwd kernel ('dx' or 'dw') — each pallas_call has
    its own VMEM, so each is budgeted for only its own tiles/scratch."""
    itemsize = jnp.dtype(dtype).itemsize
    for bm in (512, 256, 128, 64, 32, 16, 8):
        if m % bm:
            continue
        vmem = (2 * bm * k * itemsize      # x + g tiles
                + 2 * k * bf * itemsize    # w1 + w2 blocks
                + 3 * bm * bf * 4)         # pre/dt/dpre32 (fp32)
        if which == "dx":
            vmem += bm * bf * itemsize     # dpre cast for the dot
            vmem += bm * k * 4             # dx accumulator
        else:
            vmem += 2 * bm * bf * itemsize  # t + dpre casts
            vmem += 2 * k * bf * 4 + bf * 4  # dw1/dw2/db1 accumulators
        if vmem <= 12 * 1024 * 1024:
            return bm
    return None


def _bwd_kernel_calls(x2, g2, w1, b1, w2, bm_dx, bm_dw, bf, act):
    m, k = x2.shape
    f = w1.shape[1]
    nf = f // bf
    b1r = b1.reshape(1, f)
    bm, nm = bm_dx, m // bm_dx
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, nf=nf, act=act),
        grid=(nm, nf),
        in_specs=[
            pl.BlockSpec((bm, k), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((bm, k), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((k, bf), lambda mi, fi: (0, fi)),
            pl.BlockSpec((1, bf), lambda mi, fi: (0, fi)),
            pl.BlockSpec((bf, k), lambda mi, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda mi, fi: (mi, 0)),
        scratch_shapes=[pltpu.VMEM((bm, k), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, k), x2.dtype),
        name="fused_ffn_bwd_dx",
        interpret=_pallas._interpret(),
    )(x2, g2, w1, b1r, w2)
    bm, nm = bm_dw, m // bm_dw
    dw1, dw2, db1 = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, nm=nm, act=act),
        grid=(nf, nm),
        in_specs=[
            pl.BlockSpec((bm, k), lambda fi, mi: (mi, 0)),
            pl.BlockSpec((bm, k), lambda fi, mi: (mi, 0)),
            pl.BlockSpec((k, bf), lambda fi, mi: (0, fi)),
            pl.BlockSpec((1, bf), lambda fi, mi: (0, fi)),
            pl.BlockSpec((bf, k), lambda fi, mi: (fi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, bf), lambda fi, mi: (0, fi)),
            pl.BlockSpec((bf, k), lambda fi, mi: (fi, 0)),
            pl.BlockSpec((1, bf), lambda fi, mi: (0, fi)),
        ],
        scratch_shapes=[pltpu.VMEM((k, bf), jnp.float32),
                        pltpu.VMEM((bf, k), jnp.float32),
                        pltpu.VMEM((1, bf), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((k, f), w1.dtype),
                   jax.ShapeDtypeStruct((f, k), w2.dtype),
                   jax.ShapeDtypeStruct((1, f), jnp.float32)],
        name="fused_ffn_bwd_dw",
        interpret=_pallas._interpret(),
    )(x2, g2, w1, b1r, w2)
    return dx, dw1, dw2, db1.reshape(f)


def _fused_ffn_bwd(activation, res, g):
    import os
    x, w1, b1, w2, b2 = res
    k = x.shape[-1]
    f = w1.shape[1]
    x2 = x.reshape(-1, k)
    g2 = g.reshape(-1, k)
    m = x2.shape[0]
    db2 = jnp.sum(g2.astype(jnp.float32), axis=0)
    bf = _pick_bf(f)
    bm_dx = _pick_bm_bwd(m, k, bf or 128, x.dtype, "dx")
    bm_dw = _pick_bm_bwd(m, k, bf or 128, x.dtype, "dw")
    if (os.environ.get("PADDLE_TPU_FUSED_FFN_BWD") == "1"
            and ffn_is_supported(m, k, f, x.dtype)
            and bm_dx is not None and bm_dw is not None
            and bf is not None):
        dx, dw1, dw2, db1 = _bwd_kernel_calls(x2, g2, w1, b1, w2,
                                              bm_dx, bm_dw, bf,
                                              activation)
        return (dx.reshape(x.shape), dw1, db1.astype(b1.dtype),
                dw2, db2.astype(b2.dtype))
    # composite backward: recompute the intermediate (inputs-only
    # residuals); grads as plain XLA matmuls with fp32 accumulation
    pre = (jax.lax.dot_general(x2, w1, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + b1.astype(jnp.float32))
    t = _ACTS[activation](pre)
    dt = jax.lax.dot_general(g2, w2, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dpre = dt * _dgelu(pre, activation)
    dx = jax.lax.dot_general(dpre.astype(x2.dtype), w1,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dw1 = jax.lax.dot_general(x2, dpre.astype(x2.dtype),
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dw2 = jax.lax.dot_general(t.astype(x2.dtype), g2,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    db1 = jnp.sum(dpre, axis=0)
    return (dx.astype(x.dtype).reshape(x.shape),
            dw1.astype(w1.dtype), db1.astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(b2.dtype))


fused_ffn.defvjp(_fused_ffn_fwd, _fused_ffn_bwd)
