"""Layer base: parameter registration, sublayers, state_dict, hooks.

Parity: python/paddle/nn/layer/layers.py :: Layer, LayerList, ParameterList,
Sequential. TPU-first: parameters are jax-array-backed Parameters in a pytree;
``to(dtype)`` recasts arrays; there is no device copy (XLA places data).
"""
from __future__ import annotations

import collections
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dtype import convert_dtype
from ...tensor.tensor import Parameter, Tensor, no_grad

__all__ = ["Layer", "LayerList", "LayerDict", "ParameterList", "Sequential",
           "enable_static", "disable_static", "in_dynamic_mode"]

_dynamic_mode = [True]


def enable_static():
    _dynamic_mode[0] = False
    from ...static import _install_capture
    _install_capture()


def disable_static():
    _dynamic_mode[0] = True
    from ...static import _remove_capture
    _remove_capture()


def in_dynamic_mode() -> bool:
    return _dynamic_mode[0]


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    """Base class for all network layers (paddle.nn.Layer parity).

    ``forward`` runs under a ``jax.named_scope`` of the name the layer has in
    its parent (the attribute it was assigned to, its key in a container), so
    that JAX's name stack composes to the layer's path and every operation
    of a compiled step names its layer in the HLO
    (``GPTForCausalLM/gpt/h/7/attn/...``; the reference wraps ``forward`` in
    a ``RecordEvent``: ``Layer._dygraph_call_func``). A layer that no parent
    registered, the root of a model, runs under its class's name. A
    container that is never called itself (``LayerList``, ``LayerDict``)
    hands its own name on in front of its children's keys."""

    # whether the layer is only a holder of sublayers that are called one by
    # one by its parent's forward: its name goes in front of theirs
    _holds_only = False

    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        self._parameters: "collections.OrderedDict[str, Parameter]" = collections.OrderedDict()
        self._sub_layers: "collections.OrderedDict[str, Layer]" = collections.OrderedDict()
        self._buffers: "collections.OrderedDict[str, Tensor]" = collections.OrderedDict()
        self._non_persistable_buffer_names: set[str] = set()
        self._forward_pre_hooks: "collections.OrderedDict[int, Callable]" = collections.OrderedDict()
        self._forward_post_hooks: "collections.OrderedDict[int, Callable]" = collections.OrderedDict()
        self.training = True
        self._dtype = convert_dtype(dtype)
        self._name_scope = name_scope or type(self).__name__.lower()
        self._hook_id = 0

    # ------------------------------------------------------------ attribute
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning layers")
            layers[name] = value
            self.__dict__.pop(name, None)
            self._name_sublayer(name, value)
        else:
            if params is not None and name in params:
                if value is None:
                    del params[name]
                else:
                    params[name] = value
                    return
            if layers is not None and name in layers:
                if value is None:
                    del layers[name]
                else:
                    layers[name] = value
                    return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        if "_parameters" in self.__dict__ and name in self.__dict__["_parameters"]:
            return self.__dict__["_parameters"][name]
        if "_sub_layers" in self.__dict__ and name in self.__dict__["_sub_layers"]:
            return self.__dict__["_sub_layers"][name]
        if "_buffers" in self.__dict__ and name in self.__dict__["_buffers"]:
            return self.__dict__["_buffers"][name]
        raise AttributeError(f"{type(self).__name__} has no attribute {name!r}")

    def __delattr__(self, name):
        if name in self._parameters:
            del self._parameters[name]
        elif name in self._sub_layers:
            del self._sub_layers[name]
        elif name in self._buffers:
            del self._buffers[name]
        else:
            object.__delattr__(self, name)

    # ------------------------------------------------------------- registry
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            self._name_sublayer(str(name), sublayer)
        return sublayer

    def _name_sublayer(self, key: str, sublayer: "Layer"):
        own = self.__dict__.get("_scope")
        sublayer._set_scope(
            f"{own}/{key}" if self._holds_only and own else key)

    def _set_scope(self, scope: str):
        """``scope`` is the name this layer has in its parent: what its
        ``forward`` runs under."""
        self.__dict__["_scope"] = scope
        if self._holds_only:
            for key, sub in self._sub_layers.items():
                if sub is not None:
                    sub._set_scope(f"{scope}/{key}")

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from ...tensor.creation import create_parameter as _cp
        p = _cp(shape, dtype or self._dtype, attr=attr, is_bias=is_bias,
                default_initializer=default_initializer)
        if attr is not None and getattr(attr, "name", None):
            p.name = attr.name
        return p

    # ------------------------------------------------------------ iterators
    def named_parameters(self, prefix: str = "", include_sublayers: bool = True
                         ) -> Iterator[tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p

    def parameters(self, include_sublayers: bool = True) -> list[Parameter]:
        return [p for _, p in self.named_parameters()]

    def named_sublayers(self, prefix: str = "", include_self: bool = False,
                        layers_set=None) -> Iterator[tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=sub_prefix, include_self=True,
                                           layers_set=layers_set)

    def sublayers(self, include_self: bool = False) -> list["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self._sub_layers.items():
            if l is not None:
                yield l

    def named_children(self):
        yield from self._sub_layers.items()

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def buffers(self, include_sublayers: bool = True):
        return [b for _, b in self.named_buffers()]

    def apply(self, fn: Callable):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # ----------------------------------------------------------------- mode
    def train(self):
        self.training = True
        for l in self.children():
            l.train()
        return self

    def eval(self):
        self.training = False
        for l in self.children():
            l.eval()
        return self

    # ---------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ----------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        with jax.named_scope(self.__dict__.get("_scope")
                             or type(self).__name__):
            outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    # ------------------------------------------------------------ state-dict
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "", use_hook: bool = True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, layer in self.named_sublayers(include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                key = f"{name}.{bname}" if name else bname
                dest[key] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            target = own[k]
            arr = v._data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            target.set_value(arr)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -------------------------------------------------------------- casting
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = convert_dtype(dtype)
            self._cast_all(dt)
        return self

    def astype(self, dtype):
        self._cast_all(convert_dtype(dtype))
        return self

    def _cast_all(self, dt, floating_only: bool = True):
        for _, p in self.named_parameters():
            if not floating_only or jnp.issubdtype(p.dtype, jnp.floating):
                p._data = p._data.astype(dt)
        for _, b in self.named_buffers():
            if not floating_only or jnp.issubdtype(b.dtype, jnp.floating):
                b._data = b._data.astype(dt)

    def float(self):
        self._cast_all(jnp.float32)
        return self

    def bfloat16(self):
        self._cast_all(jnp.bfloat16)
        return self

    def float16(self):
        self._cast_all(jnp.float16)
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}" if extra else f"{type(self).__name__}("]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            lines.append(f"  ({name}): " + "\n  ".join(sub_repr))
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else lines[0] + ")"


def _view(container, layers):
    """``container`` over ``layers`` that another container holds: a slice
    does not rename them."""
    for i, layer in enumerate(layers):
        container._sub_layers[str(i)] = layer
    return container


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                layers[0] and isinstance(layers[0][0], tuple):
            for name, layer in layers[0]:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                if isinstance(layer, tuple):
                    self.add_sublayer(layer[0], layer[1])
                else:
                    self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return _view(Sequential(), list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class LayerList(Layer):
    _holds_only = True

    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return _view(LayerList(), list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __setitem__(self, idx, layer):
        self.add_sublayer(str(idx), layer)

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self

    def forward(self, *a, **k):
        raise NotImplementedError("LayerList is a container")


class LayerDict(Layer):
    """Ordered dict of sublayers (reference: nn.LayerDict)."""

    _holds_only = True

    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(str(key), layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def clear(self):
        self._sub_layers.clear()

    def pop(self, key):
        layer = self._sub_layers[key]
        del self._sub_layers[key]
        return layer

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def update(self, sublayers):
        if isinstance(sublayers, dict):
            sublayers = sublayers.items()
        for k, v in sublayers:
            self.add_sublayer(str(k), v)
        return self


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return list(self._parameters.values())[idx]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self


import contextlib


@contextlib.contextmanager
def substitute_param_arrays(params, arrays):
    """Temporarily swap each Parameter's backing array (functionalization
    helper: lets jit/grad trace a Layer forward with the params supplied as
    function arguments instead of captured constants). Restores the
    originals on exit."""
    old = [p._data for p in params]
    for p, a in zip(params, arrays):
        p._data = a
    try:
        yield
    finally:
        for p, a in zip(params, old):
            p._data = a
