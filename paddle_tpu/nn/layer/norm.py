"""Norm layers. Parity: python/paddle/nn/layer/norm.py."""
from __future__ import annotations

import jax.numpy as jnp

from ...tensor.tensor import Parameter, Tensor
from .. import functional as F
from ..initializer import Constant
from .layers import Layer

__all__ = ["LayerNorm", "RMSNorm", "BatchNorm", "BatchNorm1D", "BatchNorm2D",
           "BatchNorm3D", "SyncBatchNorm", "InstanceNorm1D", "InstanceNorm2D",
           "InstanceNorm3D", "GroupNorm", "LocalResponseNorm", "SpectralNorm"]


class LayerNorm(Layer):
    """Parity: nn/layer/norm.py :: LayerNorm → Phi layer_norm kernel
    (paddle/phi/kernels/gpu/layer_norm_kernel.cu). On TPU: fp32-stat composite
    that XLA fuses."""

    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = Parameter(jnp.ones(self.normalized_shape, self._dtype))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = Parameter(jnp.zeros(self.normalized_shape, self._dtype))

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias,
                            self.epsilon)

    def extra_repr(self):
        return f"normalized_shape={list(self.normalized_shape)}, epsilon={self.epsilon}"


class RMSNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-6, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        self.weight = Parameter(jnp.ones(self.normalized_shape, self._dtype))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.epsilon)


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = Parameter(jnp.ones((num_features,), self._dtype))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = Parameter(jnp.zeros((num_features,), self._dtype))
        self.register_buffer("_mean", Tensor(jnp.zeros((num_features,),
                                                       self._dtype)))
        self.register_buffer("_variance", Tensor(jnp.ones((num_features,),
                                                          self._dtype)))

    def forward(self, x):
        return F.batch_norm(x, self._mean, self._variance, self.weight,
                            self.bias, self.training, self.momentum,
                            self.epsilon, self.data_format,
                            self.use_global_stats)


class BatchNorm(_BatchNormBase):
    pass


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm: stats psum'd over the dp axis when inside a
    sharded computation (otherwise identical to BatchNorm).

    Parity: nn/layer/norm.py :: SyncBatchNorm (NCCL allreduce of stats).
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, cls):
            new = cls(layer.num_features, layer.momentum, layer.epsilon,
                      data_format=layer.data_format)
            if layer.weight is not None:
                new.weight.set_value(layer.weight._data)
            if layer.bias is not None:
                new.bias.set_value(layer.bias._data)
            new._mean.set_value(layer._mean._data)
            new._variance.set_value(layer._variance._data)
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return layer


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
            self.bias = None
        else:
            self.weight = Parameter(jnp.ones((num_features,), self._dtype))
            self.bias = Parameter(jnp.zeros((num_features,), self._dtype))

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self.epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.num_groups = num_groups
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = Parameter(jnp.ones((num_channels,), self._dtype))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = Parameter(jnp.zeros((num_channels,), self._dtype))

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.epsilon, self.weight,
                            self.bias)


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self.args)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 dtype="float32"):
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.eps = eps
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        from ...core.rng import next_key
        import jax
        self.register_buffer("weight_u", Tensor(
            jax.random.normal(next_key(), (h,), jnp.float32)))
        self.register_buffer("weight_v", Tensor(
            jax.random.normal(next_key(), (w,), jnp.float32)))

    def forward(self, weight):
        from ...tensor.tensor import apply_op
        dim = self.dim
        u0 = self.weight_u._data
        v0 = self.weight_v._data
        iters = self.power_iters
        eps = self.eps

        def f(w):
            wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            u, v = u0, v0
            for _ in range(iters):
                v = wm.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = wm @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ wm @ v
            return w / sigma
        out = apply_op(f, weight)
        return out
