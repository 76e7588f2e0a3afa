"""Activation recompute. Parity:
python/paddle/distributed/fleet/utils/recompute.py :: recompute /
recompute_sequential / RecomputeFunction (PyLayer + RNG-state replay).

Tape-level realization: forward runs under no_grad (zero residual memory);
a single tape node is recorded whose vjp re-runs the function with gradients
enabled and backprops through the sub-tape — parameter gradients accumulate
into .grad exactly as in the reference's RecomputeFunction.backward. RNG
replay is exact because the global PRNG key is snapshotted and restored
(explicit keys — stronger than the reference's CUDA RNG state juggling).
Under paddle.jit.to_static the replay sits behind an optimization barrier,
so that XLA recomputes and does not merge it with the first forward.

What is kept and what is replayed. Each call of ``recompute`` is a region
with a store of its own (``tensor.KeptRegion``). An operation whose forward
is a Pallas kernel keeps the kernel's outputs there in the first forward
and gets them back in the replay (``tensor.kept_over_replay``): flash
attention its ``o`` and ``lse`` (``ops/pallas/flash_attention.py``), the
gated delta rule its ``o`` and block-start ``states``
(``nn/functional/linear_attention.py``). Their backward kernels read ``q``,
``k``, ``v``, which are cheap to replay, and these outputs, so the replay
runs everything in ``function`` BUT those kernels, each of which runs once
a step. The kept arrays stay alive from the forward to the backward pass
and do not pass the barrier; the replay's inputs do. An inner region whose
first forward runs in an outer region's replay takes from the outer store.
A replay that meets other kernel calls than the first forward kept
(``function`` branched) raises ``tensor.RecomputeKeepError``, which names
the region and the entry. The store does not engage, and the replay
computes as before, where nothing in the region is such a kernel (the
composites ``_sdpa_ref`` and ``_chunk_rule`` keep nothing) and on a mesh of
several devices, where the flash kernel runs inside ``shard_map`` and a
value made in one such body cannot be handed to another: the code sees both
for itself, and nothing switches it. ``paddle_recompute_kept_total`` counts
the kernel forwards a traced replay took from its store,
``paddle_recompute_replayed_total`` those it computed again.
"""
from __future__ import annotations

from typing import Callable

import jax

from ....core.rng import get_rng_state, get_rng_tensor, set_rng_state
from ....tensor.tensor import (PASS_REPLAY, KeptRegion, Parameter, Tensor,
                               _TapeNode, _tape, enable_grad,
                               is_grad_enabled, no_grad, persistent_tensors)
from ....autograd.backward_engine import run_backward

__all__ = ["recompute", "recompute_sequential", "RecomputeFunction"]


def recompute(function: Callable, *args, **kwargs):
    kwargs.pop("use_reentrant", None)
    preserve_rng_state = kwargs.pop("preserve_rng_state", True)

    if not is_grad_enabled():
        return function(*args, **kwargs)

    tensor_positions = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    tensor_inputs = [args[i] for i in tensor_positions]
    rng_snapshot = get_rng_state() if preserve_rng_state else None
    kept = KeptRegion(getattr(function, "__qualname__",
                              type(function).__name__))

    with no_grad(), kept.forward():
        out = function(*args, **kwargs)
    multi = isinstance(out, (tuple, list))
    outs_raw = tuple(out) if multi else (out,)
    outs = tuple(Tensor(o._data, stop_gradient=False) for o in outs_raw)
    for o in outs:
        o._is_leaf = False

    def vjp_fn(cots):
        if preserve_rng_state:
            rng_after = get_rng_state()
            set_rng_state(rng_snapshot)
        detached = []
        rebuilt = list(args)
        # Under jit the replayed forward is, to XLA, the first forward over
        # again, and common-subexpression elimination would merge the two
        # and keep every activation alive after all. Behind the barrier the
        # replay's inputs are new values that exist only once the cotangents
        # do (jax.checkpoint's own device). What the region kept does not
        # pass it: XLA may then also merge what the replay computes from a
        # kept array and parameters alone (the projection after attention)
        # with the first forward's, and keep that too (PERF.md section 6,
        # PR 33).
        held, cots = jax.lax.optimization_barrier(
            ([t._data for t in tensor_inputs], list(cots)))
        for i, t, arr in zip(tensor_positions, tensor_inputs, held):
            d = Tensor(arr, stop_gradient=t.stop_gradient)
            d._is_leaf = True
            detached.append(d)
            rebuilt[i] = d
        mark = len(_tape.nodes)
        # The replay is the first forward over again: state that a layer
        # moves in its forward (counters, running statistics) keeps what the
        # first forward left there. The RNG key has its own rule above.
        key = get_rng_tensor()
        moved = [(t, t._data) for t in persistent_tensors()
                 if t is not key and not isinstance(t, Parameter)]
        # the pass marker of every operation the replay traces (the rule of
        # precedence among the markers: tensor.py, "stamps")
        with enable_grad(), kept.replay(), jax.named_scope(PASS_REPLAY):
            out2 = function(*rebuilt, **kwargs)
        for t, data in moved:
            t._data = data
        outs2 = tuple(out2) if isinstance(out2, (tuple, list)) else (out2,)
        seeds = [Tensor(c) for c in cots]
        run_backward(list(outs2), seeds, retain_graph=True)
        del _tape.nodes[mark:]
        if preserve_rng_state:
            set_rng_state(rng_after)
        result = []
        for d, t in zip(detached, tensor_inputs):
            result.append(None if d.grad is None else d.grad._data)
        return tuple(result)

    node = _TapeNode(
        inputs=list(tensor_inputs),
        output_ids=[o._uid for o in outs],
        vjp_fn=vjp_fn,
        outputs_meta=[(tuple(o.shape), o.dtype) for o in outs],
    )
    from ....tensor.tensor import _register_node
    _register_node(node, outs)
    return outs if multi else outs[0]


class RecomputeFunction:
    @staticmethod
    def apply(function, *args, **kwargs):
        return recompute(function, *args, **kwargs)


def recompute_sequential(ctx, functions, *args):
    """Parity: recompute_sequential — chunked recompute over a Sequential."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    if hasattr(functions, "_sub_layers"):
        layers = list(functions._sub_layers.values())
    else:
        layers = list(functions)
    import numpy as np
    parts = np.array_split(np.arange(len(layers)), segments)
    out = args[0] if len(args) == 1 else args

    def run_segment(seg_layers):
        def f(x):
            for l in seg_layers:
                x = l(x)
            return x
        return f

    for part in parts:
        seg = [layers[i] for i in part]
        out = recompute(run_segment(seg), out)
    return out
