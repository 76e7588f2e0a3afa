"""Fused LayerNorm / RMSNorm as TPU Pallas kernels.

Capability parity: paddle/phi/kernels/gpu/layer_norm_kernel.cu ::
LayerNormKernel / LayerNormGradKernel (Welford rows + fused affine), and
rms_norm from the fused kernel set.  TPU-first layout: rows tiled onto
(sublane × lane) VMEM blocks, mean/rstd kept per-row in fp32, one pass for
statistics + normalize (D fits VMEM for transformer widths), custom VJP with
a two-kernel backward (dx fused; dgamma/dbeta via per-block partial sums
reduced by XLA).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import pallas as _pallas

__all__ = ["layer_norm", "rms_norm", "is_supported"]


def is_supported(shape, dtype) -> bool:
    d = shape[-1]
    n = math.prod(shape[:-1]) if len(shape) > 1 else 1
    # D must fit VMEM comfortably; small-N falls back to XLA.
    return d <= 16384 and n >= 8 and jnp.dtype(dtype) in (
        jnp.float32, jnp.bfloat16, jnp.float16)


def _row_block(n: int) -> int:
    for bn in (256, 128, 64, 32, 16):
        if n % bn == 0:
            return bn
    return 8   # callers pad row counts to a multiple of 8


def _pad_rows(x2):
    """Pad the row dim to a multiple of 8 (Mosaic sublane tiling)."""
    n = x2.shape[0]
    pad = (-n) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, n


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                     # [bn, D]
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                   dx_ref, dg_ref, db_ref):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd

    dg_ref[0, 0] = jnp.sum(dy * xhat, axis=0)
    db_ref[0, 0] = jnp.sum(dy, axis=0)

    wdy = dy * gamma
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _ln_fwd(x2, gamma, beta, eps):
    n, d = x2.shape
    bn = _row_block(n)
    grid = (n // bn,)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        name="layer_norm_fwd",
        interpret=_pallas._interpret(),
    )(x2, gamma[None, :], beta[None, :])
    return y, mean, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2, gamma, beta, eps):
    return _ln_fwd(x2, gamma, beta, eps)[0]


def _ln_vjp_fwd(x2, gamma, beta, eps):
    y, mean, rstd = _ln_fwd(x2, gamma, beta, eps)
    return y, (x2, gamma, mean, rstd)


def _ln_vjp_bwd(eps, res, dy):
    x2, gamma, mean, rstd = res
    n, d = x2.shape
    bn = _row_block(n)
    nb = n // bn
    dx, dg_part, db_part = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
        ],
        name="layer_norm_bwd",
        interpret=_pallas._interpret(),
    )(x2, gamma[None, :], mean, rstd, dy)
    dgamma = jnp.sum(dg_part, axis=(0, 1)).astype(gamma.dtype)
    dbeta = jnp.sum(db_part, axis=(0, 1)).astype(gamma.dtype)
    return dx, dgamma, dbeta


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def layer_norm(x, gamma, beta, eps=1e-5):
    """Normalize over the last dim.  x: [..., D]; gamma/beta: [D]."""
    shape = x.shape
    d = shape[-1]
    x2, n = _pad_rows(x.reshape(-1, d))
    y = _ln(x2, gamma, beta, float(eps))
    return y[:n].reshape(shape)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, g_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y_ref[:] = (x * rstd * g_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(x_ref, g_ref, rstd_ref, dy_ref, dx_ref, dg_ref):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    dg_ref[0, 0] = jnp.sum(dy * xhat, axis=0)
    wdy = dy * gamma
    c = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx_ref[:] = ((wdy - xhat * c) * rstd).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2, gamma, eps):
    return _rms_fwd(x2, gamma, eps)[0]


def _rms_fwd(x2, gamma, eps):
    n, d = x2.shape
    bn = _row_block(n)
    y, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        name="rms_norm_fwd",
        interpret=_pallas._interpret(),
    )(x2, gamma[None, :])
    return y, rstd


def _rms_vjp_fwd(x2, gamma, eps):
    y, rstd = _rms_fwd(x2, gamma, eps)
    return y, (x2, gamma, rstd)


def _rms_vjp_bwd(eps, res, dy):
    x2, gamma, rstd = res
    n, d = x2.shape
    bn = _row_block(n)
    nb = n // bn
    dx, dg_part = pl.pallas_call(
        _rms_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
        ],
        name="rms_norm_bwd",
        interpret=_pallas._interpret(),
    )(x2, gamma[None, :], rstd, dy)
    return dx, jnp.sum(dg_part, axis=(0, 1)).astype(gamma.dtype)


_rms.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm(x, gamma, eps=1e-6):
    shape = x.shape
    x2, n = _pad_rows(x.reshape(-1, shape[-1]))
    y = _rms(x2, gamma, float(eps))
    return y[:n].reshape(shape)
