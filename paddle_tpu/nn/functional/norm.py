"""Normalization functionals. Parity: python/paddle/nn/functional/norm.py.

layer_norm here is the reference's north-star Phi kernel
(paddle/phi/kernels/gpu/layer_norm_kernel.cu :: LayerNormKernel); on TPU it
is this jnp composite, which XLA fuses into its neighbours' epilogues (a
hand-written Pallas kernel is a fusion barrier and lost to it on the chip:
PERF.md section 6, PR 30).
"""
from __future__ import annotations

import jax.numpy as jnp

from ...tensor.tensor import Tensor, apply_op

__all__ = ["layer_norm", "batch_norm", "instance_norm", "group_norm",
           "local_response_norm", "rms_norm"]


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))

    def core(a, *wb):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        mean = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mean) * jnp.reciprocal(jnp.sqrt(var + epsilon))
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(core, *args)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm (LLaMA-family). Stats in fp32, output in input dtype."""
    def core(a, *w):
        var = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=-1, keepdims=True)
        out = a.astype(jnp.float32) * jnp.reciprocal(jnp.sqrt(var + epsilon))
        out = out.astype(a.dtype)
        if w:
            out = out * w[0]
        return out
    if weight is not None:
        return apply_op(core, x, weight)
    return apply_op(core, x)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    c_axis = 1 if data_format.upper().startswith("NC") else -1

    def stats_axes(nd):
        ax = list(range(nd))
        ax.remove(c_axis % nd)
        return tuple(ax)

    use_batch_stats = training and not use_global_stats
    if use_batch_stats:
        axes = stats_axes(x.ndim)
        batch_mean = jnp.mean(x._data.astype(jnp.float32), axis=axes)
        batch_var = jnp.var(x._data.astype(jnp.float32), axis=axes)
        # update running stats in place (buffer semantics)
        if running_mean is not None:
            running_mean._data = (momentum * running_mean._data +
                                  (1 - momentum) * batch_mean.astype(running_mean.dtype))
        if running_var is not None:
            n = x.size / batch_var.size
            unbiased = batch_var * (n / max(n - 1, 1))
            running_var._data = (momentum * running_var._data +
                                 (1 - momentum) * unbiased.astype(running_var.dtype))
        mean_used, var_used = batch_mean, batch_var

        def core(a, *wb):
            shape = [1] * a.ndim
            shape[c_axis % a.ndim] = a.shape[c_axis % a.ndim]
            ax = stats_axes(a.ndim)
            m = jnp.mean(a.astype(jnp.float32), axis=ax, keepdims=True)
            v = jnp.var(a.astype(jnp.float32), axis=ax, keepdims=True)
            out = (a.astype(jnp.float32) - m) / jnp.sqrt(v + epsilon)
            out = out.astype(a.dtype)
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(shape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(shape)
            return out
    else:
        rm = running_mean._data
        rv = running_var._data

        def core(a, *wb):
            shape = [1] * a.ndim
            shape[c_axis % a.ndim] = a.shape[c_axis % a.ndim]
            out = (a - rm.reshape(shape)) / jnp.sqrt(rv.reshape(shape) + epsilon)
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(shape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(shape)
            return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(core, *args)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    def core(a, *wb):
        axes = tuple(range(2, a.ndim))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) / jnp.sqrt(v + eps)
        shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(core, *args)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    def core(a, *wb):
        n, c = a.shape[0], a.shape[1]
        rest = a.shape[2:]
        g = a.reshape(n, num_groups, c // num_groups, *rest)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) / jnp.sqrt(v + epsilon)).reshape(a.shape)
        shape = [1, c] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out
    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(core, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def core(a):
        sq = jnp.square(a)
        c = a.shape[1]
        half = size // 2
        padded = jnp.pad(sq, ((0, 0), (half, size - 1 - half)) +
                         ((0, 0),) * (a.ndim - 2))
        acc = jnp.zeros_like(a)
        for i in range(size):
            acc = acc + jax_slice_channel(padded, i, c)
        return a / (k + alpha * acc) ** beta
    return apply_op(core, x)


def jax_slice_channel(a, start, length):
    import jax.lax as lax
    return lax.slice_in_dim(a, start, start + length, axis=1)
