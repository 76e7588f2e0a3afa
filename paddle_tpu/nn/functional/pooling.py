"""Pooling functionals over lax.reduce_window. Parity: nn/functional/pooling.py."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...tensor.tensor import Tensor, apply_op

__all__ = ["avg_pool1d", "avg_pool2d", "avg_pool3d", "max_pool1d",
           "max_pool2d", "max_pool3d", "adaptive_avg_pool1d",
           "adaptive_avg_pool2d", "adaptive_avg_pool3d", "adaptive_max_pool1d",
           "adaptive_max_pool2d", "adaptive_max_pool3d"]


def _tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(i) for i in v)


def _pool(x, kernel, stride, padding, n, op, ceil_mode=False,
          exclusive=True, data_format="NCHW"):
    k = _tuple(kernel, n)
    s = _tuple(stride if stride is not None else kernel, n)
    if isinstance(padding, str):
        pad_same = padding.upper() == "SAME"
        p = None
    else:
        pad_same = False
        p = _tuple(padding, n) if not isinstance(padding, (list, tuple)) or \
            len(padding) == n else tuple(padding)
        if isinstance(p[0], (list, tuple)):
            p = tuple(tuple(i) for i in p)
        else:
            p = tuple((i, i) for i in p)
    is_nc = data_format.upper().startswith("NC")

    def f(a):
        nd = a.ndim
        if is_nc:
            window = (1, 1) + k
            strides = (1, 1) + s
            pads = ((0, 0), (0, 0)) + (p if p else ((0, 0),) * n)
        else:
            window = (1,) + k + (1,)
            strides = (1,) + s + (1,)
            pads = ((0, 0),) + (p if p else ((0, 0),) * n) + ((0, 0),)
        if pad_same:
            pads = "SAME"
        if op == "max":
            init = -jnp.inf
            out = jax.lax.reduce_window(a, init, jax.lax.max, window, strides,
                                        pads)
            return out
        # avg
        out = jax.lax.reduce_window(a, 0.0, jax.lax.add,
                                    window, strides, pads)
        if exclusive and not pad_same and p is not None and any(
                pi != (0, 0) for pi in (p or ())):
            ones = jnp.ones_like(a)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           strides, pads)
            return out / counts
        denom = 1
        for kk in k:
            denom *= kk
        return out / denom
    return apply_op(f, x)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    return _pool(x, kernel_size, stride, padding, 1, "avg", ceil_mode,
                 exclusive, "NCH")


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 2, "avg", ceil_mode,
                 exclusive, data_format)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 3, "avg", ceil_mode,
                 exclusive, data_format)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    if return_mask:
        assert not ceil_mode, "return_mask supports ceil_mode=False"
        xd = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        pooled, mask = max_pool2d_with_mask(
            Tensor(xd[:, :, None, :]), (1, _tuple(kernel_size, 1)[0]),
            (1, _tuple(stride if stride is not None else kernel_size, 1)[0]),
            (0, _tuple(padding, 1)[0]))
        return (apply_op(lambda a: a[:, :, 0, :], pooled),
                apply_op(lambda a: a[:, :, 0, :], mask))
    return _pool(x, kernel_size, stride, padding, 1, "max", ceil_mode,
                 data_format="NCH")


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCHW", name=None):
    if return_mask:
        assert data_format == "NCHW" and not ceil_mode, \
            "return_mask supports NCHW, ceil_mode=False"
        assert not isinstance(padding, str) and not (
            isinstance(padding, (list, tuple)) and padding
            and isinstance(padding[0], (list, tuple))), \
            "return_mask supports int / (int, int) padding"
        return max_pool2d_with_mask(x, kernel_size, stride, padding)
    return _pool(x, kernel_size, stride, padding, 2, "max", ceil_mode,
                 data_format=data_format)


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW", name=None):
    if return_mask:
        assert data_format == "NCDHW" and not ceil_mode, \
            "return_mask supports NCDHW, ceil_mode=False"
        return max_pool3d_with_mask(x, kernel_size, stride, padding)
    return _pool(x, kernel_size, stride, padding, 3, "max", ceil_mode,
                 data_format=data_format)


def _adaptive(x, output_size, n, op):
    out_sz = _tuple(output_size, n)

    def f(a):
        spatial = a.shape[2:]
        res = a
        # decompose into per-axis adaptive windows
        for i, (dim, osz) in enumerate(zip(spatial, out_sz)):
            ax = 2 + i
            # static window bounds: NumPy, so that they stay numbers when
            # the caller is traced
            starts = (np.arange(osz) * dim) // osz
            ends = ((np.arange(osz) + 1) * dim + osz - 1) // osz
            segs = []
            for j in range(osz):
                sl = jax.lax.slice_in_dim(res, int(starts[j]), int(ends[j]),
                                          axis=ax)
                red = jnp.max(sl, axis=ax, keepdims=True) if op == "max" else \
                    jnp.mean(sl, axis=ax, keepdims=True)
                segs.append(red)
            res = jnp.concatenate(segs, axis=ax)
        return res
    return apply_op(f, x)


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive(x, output_size, 1, "avg")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive(x, output_size, 2, "avg")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive(x, output_size, 3, "avg")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    return _adaptive(x, output_size, 1, "max")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive(x, output_size, 2, "max")


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    return _adaptive(x, output_size, 3, "max")


# ---- round-2 breadth: mask-returning max pool, unpool, lp_pool ------------
# Parity: python/paddle/nn/functional/pooling.py :: max_pool2d(return_mask),
# max_unpool2d, lp_pool2d (+ MaxUnPool2D/LPPool2D layers in nn/layer).

def _patches2d(a, kh, kw, sh, sw, ph, pw, pad_value):
    """a [N,C,H,W] → patches [N,C,Ho,Wo,kh*kw] + flat input index per tap."""
    N, C, H, W = a.shape
    ap = jnp.pad(a, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                 constant_values=pad_value)
    Ho = (H + 2 * ph - kh) // sh + 1
    Wo = (W + 2 * pw - kw) // sw + 1
    iy = jnp.arange(Ho)[:, None] * sh + jnp.arange(kh)[None, :]  # [Ho,kh]
    ix = jnp.arange(Wo)[:, None] * sw + jnp.arange(kw)[None, :]  # [Wo,kw]
    pat = ap[:, :, iy[:, None, :, None], ix[None, :, None, :]]
    # → [N,C,Ho,Wo,kh,kw]
    pat = pat.reshape(N, C, Ho, Wo, kh * kw)
    # flat index into the UNPADDED input for each tap (clip to borders)
    yy = jnp.clip(iy - ph, 0, H - 1)[:, None, :, None]
    xx = jnp.clip(ix - pw, 0, W - 1)[None, :, None, :]
    flat = (yy * W + xx).reshape(Ho, Wo, kh * kw)
    return pat, flat, Ho, Wo


def max_pool2d_with_mask(x, kernel_size, stride=None, padding=0, name=None):
    """→ (pooled, mask) where mask holds flat H*W argmax positions (the
    reference's return_mask=True contract, consumed by max_unpool2d)."""
    kh, kw = _tuple(kernel_size, 2)
    sh, sw = _tuple(stride if stride is not None else kernel_size, 2)
    ph, pw = _tuple(padding, 2)

    def fn(a):
        pat, flat, Ho, Wo = _patches2d(a, kh, kw, sh, sw, ph, pw, -jnp.inf)
        best = jnp.argmax(pat, axis=-1)                   # [N,C,Ho,Wo]
        pooled = jnp.take_along_axis(pat, best[..., None], axis=-1)[..., 0]
        mask = flat[jnp.arange(Ho)[:, None], jnp.arange(Wo)[None, :],
                    best]                                  # [N,C,Ho,Wo]
        return pooled, mask.astype(jnp.int32)
    return apply_op(fn, x, n_outputs=2)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    """Scatter pooled values back to their argmax positions; everything
    else zero (reference max_unpool2d)."""
    assert data_format == "NCHW", "max_unpool2d supports NCHW"
    kh, kw = _tuple(kernel_size, 2)
    sh, sw = _tuple(stride if stride is not None else kernel_size, 2)
    ph, pw = _tuple(padding, 2)
    idx = indices._data if isinstance(indices, Tensor) else jnp.asarray(
        indices)

    def fn(a):
        N, C, Ho, Wo = a.shape
        if output_size is not None:
            H, W = output_size[-2:]
        else:
            H = (Ho - 1) * sh - 2 * ph + kh
            W = (Wo - 1) * sw - 2 * pw + kw
        flat = jnp.zeros((N, C, H * W), a.dtype)
        # .set, not .add: overlapping windows whose argmax is the same
        # input cell all carry that cell's value — writing once is the
        # reference semantics (summing would multiply it)
        out = flat.at[
            jnp.arange(N)[:, None, None],
            jnp.arange(C)[None, :, None],
            idx.reshape(N, C, -1)].set(a.reshape(N, C, -1))
        return out.reshape(N, C, H, W)
    return apply_op(fn, x)


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW", name=None):
    """(sum over window |x|^p)^(1/p) (reference lp_pool2d). ceil_mode pads
    zeros on the bottom/right (|0|^p adds nothing to the window sum)."""
    assert data_format == "NCHW", "lp_pool2d supports NCHW"
    p = float(norm_type)
    kh, kw = _tuple(kernel_size, 2)
    sh, sw = _tuple(stride if stride is not None else kernel_size, 2)
    ph, pw = _tuple(padding, 2)

    def fn(a):
        H, W = a.shape[-2:]
        extra_h = extra_w = 0
        if ceil_mode:
            out_h = -(-(H + 2 * ph - kh) // sh) + 1
            out_w = -(-(W + 2 * pw - kw) // sw) + 1
            extra_h = max((out_h - 1) * sh + kh - (H + 2 * ph), 0)
            extra_w = max((out_w - 1) * sw + kw - (W + 2 * pw), 0)
        powd = jnp.abs(a) ** p
        s = jax.lax.reduce_window(
            powd, 0.0, jax.lax.add, (1, 1, kh, kw), (1, 1, sh, sw),
            ((0, 0), (0, 0), (ph, ph + extra_h), (pw, pw + extra_w)))
        return s ** (1.0 / p)
    return apply_op(fn, x)


__all__ += ["max_pool2d_with_mask", "max_pool3d_with_mask", "max_unpool2d", "lp_pool2d",
            "max_unpool1d", "max_unpool3d"]


def max_pool3d_with_mask(x, kernel_size, stride=None, padding=0, name=None):
    """→ (pooled, mask) with flat D*H*W argmax positions, consumed by
    max_unpool3d (reference max_pool3d return_mask=True contract)."""
    kd, kh, kw = _tuple(kernel_size, 3)
    sd, sh, sw = _tuple(stride if stride is not None else kernel_size, 3)
    pd, ph, pw = _tuple(padding, 3)

    def fn(a):
        N, C, D, H, W = a.shape
        ap = jnp.pad(a, ((0, 0), (0, 0), (pd, pd), (ph, ph), (pw, pw)),
                     constant_values=-jnp.inf)
        Do = (D + 2 * pd - kd) // sd + 1
        Ho = (H + 2 * ph - kh) // sh + 1
        Wo = (W + 2 * pw - kw) // sw + 1
        iz = jnp.arange(Do)[:, None] * sd + jnp.arange(kd)[None, :]
        iy = jnp.arange(Ho)[:, None] * sh + jnp.arange(kh)[None, :]
        ix = jnp.arange(Wo)[:, None] * sw + jnp.arange(kw)[None, :]
        pat = ap[:, :,
                 iz[:, None, None, :, None, None],
                 iy[None, :, None, None, :, None],
                 ix[None, None, :, None, None, :]]
        # → [N,C,Do,Ho,Wo,kd,kh,kw]
        pat = pat.reshape(N, C, Do, Ho, Wo, kd * kh * kw)
        best = jnp.argmax(pat, axis=-1)
        pooled = jnp.take_along_axis(pat, best[..., None], axis=-1)[..., 0]
        zz = jnp.clip(iz - pd, 0, D - 1)[:, None, None, :, None, None]
        yy = jnp.clip(iy - ph, 0, H - 1)[None, :, None, None, :, None]
        xx = jnp.clip(ix - pw, 0, W - 1)[None, None, :, None, None, :]
        flat = ((zz * H + yy) * W + xx).reshape(Do, Ho, Wo, kd * kh * kw)
        mask = flat[jnp.arange(Do)[:, None, None],
                    jnp.arange(Ho)[None, :, None],
                    jnp.arange(Wo)[None, None, :], best]
        return pooled, mask.astype(jnp.int32)
    return apply_op(fn, x, n_outputs=2)


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
    """1-D unpool via the 2-D scatter path on a width-1 spatial axis."""
    assert data_format == "NCL", "max_unpool1d supports NCL"
    xd = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    idx = indices._data if isinstance(indices, Tensor) else jnp.asarray(
        indices)
    out2 = max_unpool2d(
        Tensor(xd[:, :, None, :]), Tensor(idx[:, :, None, :]),
        (1, _tuple(kernel_size, 1)[0]),
        (1, _tuple(stride if stride is not None else kernel_size, 1)[0]),
        (0, _tuple(padding, 1)[0]),
        output_size=(1, output_size[-1]) if output_size is not None else None)
    return apply_op(lambda a: a[:, :, 0, :], out2)


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
    """Scatter pooled values back to their argmax positions in D*H*W."""
    assert data_format == "NCDHW", "max_unpool3d supports NCDHW"
    kd, kh, kw = _tuple(kernel_size, 3)
    sd, sh, sw = _tuple(stride if stride is not None else kernel_size, 3)
    pd, ph, pw = _tuple(padding, 3)
    idx = indices._data if isinstance(indices, Tensor) else jnp.asarray(
        indices)

    def fn(a):
        N, C, Do, Ho, Wo = a.shape
        if output_size is not None:
            D, H, W = output_size[-3:]
        else:
            D = (Do - 1) * sd - 2 * pd + kd
            H = (Ho - 1) * sh - 2 * ph + kh
            W = (Wo - 1) * sw - 2 * pw + kw
        flat = jnp.zeros((N, C, D * H * W), a.dtype)
        out = flat.at[
            jnp.arange(N)[:, None, None],
            jnp.arange(C)[None, :, None],
            idx.reshape(N, C, -1)].set(a.reshape(N, C, -1))
        return out.reshape(N, C, D, H, W)
    return apply_op(fn, x)
