"""Reverse-mode engine over the flat tape.

Parity target: ``paddle/fluid/eager/backward.cc :: Backward`` — reverse
topological traversal of GradNodes with gradient accumulation into leaf
``.grad`` (GradNodeAccumulation). Here the tape is already in execution order,
so reverse order IS a valid topological order; accumulation is a dict keyed by
tensor uid, hooks run at accumulation time.

Each node's ``vjp_fn`` runs under the name stack the node was recorded
under, behind the pass marker ``bwd`` (``tensor.scope_of_pass``), so that a
compiled step's backward operations name their layer and scope in the HLO.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax.numpy as jnp

from ..tensor.tensor import (PASS_BACKWARD, Tensor, _tape,
                             current_name_stack, scope_of_pass,
                             set_name_stack)


def run_backward(tensors: Sequence[Tensor],
                 grad_tensors: Sequence[Optional[Tensor]],
                 retain_graph: bool = False) -> None:
    grads: dict[int, object] = {}
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}")
            g_arr = jnp.ones_like(t._data)
        else:
            g_arr = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        grads[t._uid] = grads.get(t._uid, 0) + g_arr

    nodes = _tape.nodes
    # the walk has thousands of nodes and its Python is paid at trace time:
    # a scope is entered only where it differs from the previous node's
    base = current_name_stack()
    opened = None
    with contextlib.ExitStack() as scope:
        for node in reversed(nodes):
            if not any(oid in grads for oid in node.output_ids):
                continue
            if node.scope is not opened:
                scope.close()
                scope.enter_context(set_name_stack(
                    scope_of_pass(base, PASS_BACKWARD, node.scope)))
                opened = node.scope
            cots = tuple(
                grads.pop(oid) if oid in grads else jnp.zeros(shape, dtype)
                for oid, (shape, dtype) in zip(node.output_ids,
                                               node.outputs_meta)
            )
            in_cots = node.vjp_fn(cots)
            for t, ct in zip(node.inputs, in_cots):
                if t.stop_gradient or ct is None:
                    continue
                if t._is_leaf:
                    _accumulate_leaf(t, ct)
                else:
                    grads[t._uid] = grads.get(t._uid, 0) + ct

    # any remaining grads map to leaves the engine saw only as seeds
    for t, g in zip(tensors, grad_tensors):
        if t._is_leaf and not t.stop_gradient and t._uid in grads:
            _accumulate_leaf(t, grads.pop(t._uid))

    if not retain_graph:
        _tape.nodes.clear()


def _accumulate_leaf(t: Tensor, ct) -> None:
    for hook in t._hooks:
        out = hook(Tensor(ct))
        if out is not None:
            ct = out._data if isinstance(out, Tensor) else out
    if t.grad is None:
        t.grad = Tensor(jnp.asarray(ct, dtype=t.dtype))
    else:
        t.grad = Tensor(t.grad._data + jnp.asarray(ct, dtype=t.dtype))
