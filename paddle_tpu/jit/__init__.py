"""paddle.jit — to_static / save / load.

Parity: python/paddle/jit/ (dy2static program_translator, jit.save). The
reference AST-transforms Python into a static ProgramDesc; here XLA already is
the static graph, so ``to_static`` compiles the *same eager code* by tracing:

  1. snapshot every persistent tensor (Parameters, optimizer slots, RNG key),
  2. build a pure function (state_in, args) -> (out, state_out) that binds
     tracers into those tensors and runs the user fn — the eager tape,
     ``backward()`` and ``optimizer.step()`` all work under tracing,
  3. jax.jit it with the state DONATED: each state result takes its input's
     buffer, so a call allocates nothing for them and the pre-step arrays
     are consumed (``to_static(fn, donate_state=False)`` keeps them valid),
  4. write the updated state back after each call.

This turns a dygraph train step into ONE fused XLA program: the per-op
dispatch the reference pays per Python call disappears, and AdamW over the
whole pytree becomes the fused multi-tensor form for free.

The call path measures itself (PERF.md section 3, layer "trainer"): every
compiled call runs under ``jax.profiler.TraceAnnotation`` spans
(``to_static.call`` around ``.key``, ``.dispatch`` or ``.trace_compile``,
and ``.writeback``), which land in the profiler's trace on the device's
clock, and leaves one record in a ``telemetry.Telemetry`` step ring
(``call_timeline()``; off with ``PADDLE_TELEMETRY_RING=0``) plus the
``paddle_to_static_*`` counters of the runtime registry.
"""
from __future__ import annotations

import collections
import functools
import os
import pickle
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.profiler import TraceAnnotation

from ..inference import telemetry as _telemetry
from ..tensor.tensor import (Tensor, persistent_tensors,
                             unregister_persistent_many, _tape)

__all__ = ["to_static", "not_to_static", "ignore_module", "save", "load",
           "TranslatedLayer", "enable_to_static", "call_timeline"]

# The trainer's step ring: one record per compiled call (kind "to_static").
_timeline = _telemetry.Telemetry()


def call_timeline():
    """One record per compiled ``to_static`` call (``run_steps`` included),
    oldest first: ``n`` (the call's ordinal in the process, the ``step=`` of
    its ``to_static.call`` span), ``fn`` (the function's qualified name),
    ``fresh`` (this call traced and compiled a new entry), and host seconds
    ``call_s`` (all of the call), ``key_s`` (building the entry key),
    ``dur_s`` (the jitted call alone) and ``writeback_s`` (everything after
    it returned), and ``donated`` / ``kept``: how many persistent-state
    leaves this call handed to the program to consume and how many it did not
    (``StaticFunction._entry_key`` says which). The newest
    ``PADDLE_TELEMETRY_RING`` calls are kept (default 2048); with the ring at
    0 the list is empty and a call reads no clock."""
    return list(_timeline.steps)


_to_static_enabled = [True]


def enable_to_static(flag: bool):
    _to_static_enabled[0] = bool(flag)


class _TensorRef:
    """Placeholder for a Tensor leaf inside a flattened arg/out spec."""

    __slots__ = ("idx", "stop_gradient")

    def __init__(self, idx, stop_gradient):
        self.idx = idx
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"_TensorRef({self.idx})"


def _tree_flatten_args(args, kwargs):
    leaves = []

    def walk(x):
        if isinstance(x, Tensor):
            leaves.append(x)
            return _TensorRef(len(leaves) - 1, x.stop_gradient)
        if isinstance(x, (list, tuple)):
            return type(x)(walk(i) for i in x)
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        return x
    spec = walk((args, kwargs))
    return leaves, spec


def _tree_unflatten_args(spec, arrays):
    def walk(x):
        if isinstance(x, _TensorRef):
            return Tensor(arrays[x.idx], stop_gradient=x.stop_gradient)
        if isinstance(x, (list, tuple)):
            return type(x)(walk(i) for i in x)
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        return x
    args, kwargs = walk(spec)
    return args, kwargs


def _flatten_out(out):
    arrays = []

    def walk(x):
        if isinstance(x, Tensor):
            arrays.append(x._data)
            return _TensorRef(len(arrays) - 1, x.stop_gradient)
        if isinstance(x, (list, tuple)):
            return type(x)(walk(i) for i in x)
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        return x
    spec = walk(out)
    return arrays, spec


def _unflatten_out(spec, arrays):
    def walk(x):
        if isinstance(x, _TensorRef):
            return Tensor(arrays[x.idx], stop_gradient=x.stop_gradient)
        if isinstance(x, (list, tuple)):
            return type(x)(walk(i) for i in x)
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        return x
    return walk(spec)


def _constrain_to_spec(t, arr):
    """Pin a persistent tensor's post-step placement to its annotated
    PartitionSpec (replicated when unannotated) on the active hybrid mesh.

    Without this, GSPMD's propagation is free to re-shard state outputs —
    e.g. ZeRO-1 annotates only optimizer moments, but params touching
    sharded moments could come back sharded too, silently changing the
    sharding level's semantics. A no-op for already-conforming layouts and
    off-mesh runs."""
    try:
        from ..parallel import current_mesh, _valid_spec
        mesh = current_mesh()
        if mesh is None or not hasattr(arr, "ndim"):
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = getattr(t, "sharding_spec", None)
        pspec = P(*spec) if (spec is not None and
                             _valid_spec(arr, spec, mesh)) else P()
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, pspec))
    except Exception:
        return arr


def _undonatable(state, arg_arrays):
    """Indices of the state leaves whose arrays a call must not donate
    (``StaticFunction._entry_key`` says why), ascending."""
    arrays = [t._data for t in state]
    uses = collections.Counter(map(id, arrays))
    uses.update(map(id, arg_arrays))    # an argument's array: a second use
    dead = {tp for tp in set(map(type, arrays))
            if not issubclass(tp, jax.Array) or issubclass(tp, jax.core.Tracer)}
    return tuple(i for i, a in enumerate(arrays)
                 if uses[id(a)] > 1 or type(a) in dead)


def _split(state_arrays, kept):
    """(donated, kept): the state's arrays as the two list arguments of a
    compiled entry, ``kept`` the ascending indices of the second."""
    held = set(kept)
    return ([a for i, a in enumerate(state_arrays) if i not in held],
            [state_arrays[i] for i in kept])


def _merge(donated, kept_arrays, kept):
    """``_split``'s two lists back in the state's order."""
    held, rest = dict(zip(kept, kept_arrays)), iter(donated)
    return [held[i] if i in held else next(rest)
            for i in range(len(donated) + len(kept))]


class StaticFunction:
    """Compiled wrapper around an eager function (dygraph → XLA program).

    A compiled call OWNS the persistent state for its duration: it donates
    the state's buffers to the program, which updates them in place, so an
    array taken from a parameter before the call (``p.detach()``,
    ``p._data``) is deleted by it; the tensors themselves, ``numpy()``,
    ``clone()`` and ``state_dict()`` are not affected.
    ``donate_state=False`` keeps the pre-step arrays valid, at the cost of
    a second copy of the state and one allocation per leaf and call."""

    def __init__(self, fn: Callable, input_spec=None, build_strategy=None,
                 backend=None, donate_state: bool = None, static_argnames=None):
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._input_spec = input_spec
        self._donate_state = donate_state is None or bool(donate_state)
        self._cache: dict = {}
        self._bound_instance = None
        # what the spans and the timeline call this function
        self._qualname = getattr(fn, "__qualname__", type(fn).__name__)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction(self._fn.__get__(instance, owner),
                               self._input_spec,
                               donate_state=self._donate_state)
        setattr(instance, self._fn.__name__, bound)
        return bound

    @property
    def dygraph_function(self):
        return self._fn

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled[0]:
            return self._fn(*args, **kwargs)
        return self._run(args, kwargs)

    def _entry_key(self, args, kwargs, k=None):
        """(arg arrays, persistent state, indices of the state leaves NOT to
        donate, arg spec, compiled-entry key); ``k`` is ``run_steps``' scan
        length.

        A leaf is kept when donating it would be an error or a lie: its
        array is also a call argument (``step(model.weight)``), is shared
        with another state leaf (a registered ``detach()``), or is not a
        live device array (``None``, a NumPy value, a tracer of an outer
        trace); with ``donate_state=False`` every leaf is. The split is
        part of the key: when it changes, the call retraces once."""
        arg_tensors, spec = _tree_flatten_args(args, kwargs)
        if k is not None:
            _check_stacked(arg_tensors, k)
        arg_arrays = [t._data for t in arg_tensors]
        state = persistent_tensors()
        kept = (_undonatable(state, arg_arrays) if self._donate_state
                else tuple(range(len(state))))
        key = (
            tuple((tuple(a.shape), str(a.dtype)) for a in arg_arrays),
            tuple(id(t) for t in state),
            kept,
            _spec_key(spec),
        )
        if k is not None:
            key = ("scan", k) + key
        return arg_arrays, state, kept, spec, key

    def lower(self, *args, **kwargs):
        """The jax ``Lowered`` of the compiled entry these arguments
        select; nothing executes. ``.as_text()`` shows what the step
        lowers to (is a Pallas kernel, ``tpu_custom_call``, in it?),
        ``.compile().as_text()`` the collectives the compiler put in.
        The entry must exist: call the step with these arguments first."""
        arg_arrays, state, kept, _, key = self._entry_key(args, kwargs)
        entry = self._cache.get(key)
        if entry is None:
            raise RuntimeError(
                "to_static.lower: no compiled entry for these arguments "
                "and the current persistent state; call the step first")
        return entry[0].lower(
            *_split([t._data for t in state], kept), arg_arrays)

    def _make_pure(self, state, spec, out_spec_box, state_after_box):
        """(state_arrays, arg_arrays) -> (out_arrays, new_state): bind the
        arrays into the persistent tensors, run the eager fn under trace,
        capture outputs + post-step state, restore bindings."""
        fn = self._fn

        def pure(state_arrays, arg_arrays):
            old = [t._data for t in state]
            for t, a in zip(state, state_arrays):
                t._data = a
            _tape.nodes.clear()
            args, kwargs = _tree_unflatten_args(spec, arg_arrays)
            out = fn(*args, **kwargs)
            out_arrays, out_spec = _flatten_out(out)
            out_spec_box[0] = out_spec
            state_after = persistent_tensors()
            state_after_box[0] = state_after
            new_state = [_constrain_to_spec(t, t._data)
                         for t in state_after]
            for t, a in zip(state, old):
                t._data = a
            for t in state_after:
                t.grad = None
            _tape.nodes.clear()
            return out_arrays, new_state
        return pure

    def _run(self, args, kwargs, k=None):
        """One compiled call (``k``: ``run_steps``' scan length) with
        tape/grad save-restore and the donation-aware error contract, under
        the trainer's spans; leaves one record in ``call_timeline()``."""
        n = _telemetry.runtime_counter("paddle_to_static_calls_total", 1)
        timed = _timeline.enabled
        clock = _timeline.clock if timed else float     # float() is 0.0
        with TraceAnnotation("to_static.call", step=n, fn=self._qualname):
            t0 = clock()
            with TraceAnnotation("to_static.key"):
                call_arrays, state, kept, spec, key = self._entry_key(
                    args, kwargs, k)
            t1 = clock()
            entry = self._cache.get(key)
            fresh = entry is None
            if fresh:
                entry = self._build(state, kept, spec, key, k)
            jitted, out_spec_box, state_after_box = entry
            state_arrays = [t._data for t in state]
            donated_arrays, kept_arrays = _split(state_arrays, kept)
            saved_nodes = _tape.nodes[:]
            saved_grads = [(t, t.grad) for t in state]

            def restore():
                _tape.nodes[:] = saved_nodes
                for t, arr in zip(state, state_arrays):
                    t._data = arr
                for t, g in saved_grads:
                    t.grad = g

            t2 = clock()
            try:
                with TraceAnnotation("to_static.trace_compile" if fresh
                                     else "to_static.dispatch"):
                    out_arrays, new_state = jitted(
                        donated_arrays, kept_arrays, call_arrays)
            except BaseException as e:
                restore()
                if isinstance(e, Exception):
                    self._failed(e, state, donated_arrays, entry, key, fresh,
                                 k is not None)
                raise
            t3 = clock()
            with TraceAnnotation("to_static.writeback"):
                restore()       # undo any tracer leakage before writeback
                # the state after may be a superset of state: persistent
                # tensors created during tracing (e.g. lazily-built optimizer
                # slots) are captured as extra outputs; the next call's key
                # sees the superset and recompiles once into the steady
                # signature.
                for t, arr in zip(state_after_box[0] or state, new_state):
                    t._data = arr
                # The donated pre-step arrays were consumed by the call and
                # own no buffer now. The others (kept leaves, all of them
                # under donate_state=False) are released HERE, together, and
                # not one by one inside the loop above: on the v5e that order
                # cost the next call's result allocation 5-8% of an undonated
                # gpt2_124m step (PERF.md section 6, PR 25).
                del state_arrays, donated_arrays, kept_arrays
                out = _unflatten_out(out_spec_box[0], out_arrays)
            if fresh:
                _telemetry.runtime_counter(
                    "paddle_to_static_compiles_total", 1)
            n_kept = len(kept)
            n_donated = len(state) - n_kept
            _telemetry.runtime_counter(
                "paddle_to_static_donated_leaves_total", n_donated)
            if timed:
                t4 = clock()
                _telemetry.runtime_histogram(
                    "paddle_to_static_call_seconds").observe(t4 - t0)
                _timeline.step_event(
                    "to_static", t0, t3 - t2, call_s=t4 - t0, key_s=t1 - t0,
                    writeback_s=t4 - t3, fresh=fresh, fn=self._qualname, n=n,
                    donated=n_donated, kept=n_kept)
        return out

    def _failed(self, e, state, donated_arrays, entry, key, fresh, scan):
        """The jitted call raised ``e``: roll back what its trace created,
        and raise the error the caller should see if it is not ``e``."""
        # Persistent tensors CREATED during the failed trace/compile
        # (lazily-built optimizer slots, master weights) hold escaped
        # tracers; left registered they poison every later to_static
        # call in the process with UnexpectedTracerError. Their true
        # values never existed, so roll them back hard: drop from the
        # registry and mark dead (_data=None) — owners that cache them
        # (Optimizer._acc/_seed_master) recreate dead slots on reuse.
        pre_existing = {id(t) for t in state}
        killed = [t for t in persistent_tensors()
                  if id(t) not in pre_existing]
        unregister_persistent_many(killed)
        for t in killed:
            t._data = None
        if killed or fresh:
            # only evict when this call's trace may be inconsistent —
            # a transient EXECUTE failure of a long-good compiled entry
            # must not force a retrace (compiles cost minutes)
            entry[2][0] = None
            self._cache.pop(key, None)
        if scan and "carry" in str(e):
            raise RuntimeError(
                "run_steps traced new persistent state (e.g. "
                "lazily-built optimizer slots) inside the scan body; "
                "call the step function once normally before run_steps "
                "so state is steady.") from e
        if any(a.is_deleted() for a in donated_arrays):
            # the program started and consumed its donated state (a failure
            # in tracing or compiling consumes nothing, and the rollback
            # above is complete): the restored arrays are deleted — say so
            # instead of surfacing a bare "Array has been deleted" later
            raise RuntimeError(
                "to_static step failed after state buffers were donated; "
                "persistent state may be invalid. Re-create the model/"
                "optimizer or use to_static(donate_state=False) for "
                "rollback-on-error semantics.") from e

    def _build(self, state, kept, spec, key, k=None):
        out_spec_box = [None]
        state_after_box = [None]
        step = self._make_pure(state, spec, out_spec_box, state_after_box)

        # the compiled module keeps its name in traces: jit_pure
        def pure(donated_arrays, kept_arrays, arg_arrays):
            return step(_merge(donated_arrays, kept_arrays, kept), arg_arrays)

        def scanned(donated_arrays, kept_arrays, stacked):
            def body(carry, xs):
                out_arrays, new_state = step(carry, list(xs))
                return new_state, out_arrays
            final_state, outs = jax.lax.scan(
                body, _merge(donated_arrays, kept_arrays, kept),
                tuple(stacked), length=k)
            return outs, final_state

        # The first argument is donated: params/optimizer slots update in
        # place (XLA aliases each state result to its input's buffer), so
        # PjRt allocates nothing for them and the next call can be enqueued
        # while this one runs. Which leaves are in it is _entry_key's
        # decision (none under donate_state=False); writeback replaces
        # every tensor's _data with the outputs.
        jitted = jax.jit(pure if k is None else scanned,
                         donate_argnums=(0,))
        entry = (jitted, out_spec_box, state_after_box)
        self._cache[key] = entry
        return entry

    def concrete_program(self, *args, **kwargs):
        return None

    def run_steps(self, k: int, *args, **kwargs):
        """Run k steps of this function in ONE device program (lax.scan over
        the compiled step, persistent state threaded as the carry).

        Every Tensor argument must be stacked to a [k, ...] leading axis —
        step i consumes slice [i]. Returns the per-step outputs stacked the
        same way. This is the TPU analogue of the reference's CUDA-Graph
        whole-iteration capture (paddle/fluid/platform/cuda_graph*, SURVEY
        §2.3 row 29) taken one level further: the host dispatches once per k
        steps, so per-call dispatch latency amortizes over k.

        Call the function once normally first (a warmup step): lazily
        created persistent state (optimizer slots) must exist before the
        scan fixes the carry structure.
        """
        if not _to_static_enabled[0]:
            # eager fallback: python loop over the k slices; outputs are
            # stacked to match the compiled path's [k, ...] convention
            leaves, spec_ = _tree_flatten_args(args, kwargs)
            _check_stacked(leaves, k)
            step_outs = []
            for i in range(k):
                a_i, kw_i = _tree_unflatten_args(
                    spec_, [t._data[i] for t in leaves])
                step_outs.append(self._fn(*a_i, **kw_i))
            flat = [_flatten_out(o) for o in step_outs]
            stacked_arrays = [jnp.stack([f[0][j] for f in flat])
                              for j in range(len(flat[0][0]))]
            return _unflatten_out(flat[0][1], stacked_arrays)

        return self._run(args, kwargs, k)


def _check_stacked(tensors, k):
    for t in tensors:
        if len(t.shape) == 0 or t.shape[0] != k:
            raise ValueError(
                f"run_steps({k}): every Tensor arg needs a [k, ...] leading "
                f"axis (scalars included — stack per-step values), got "
                f"shape {list(t.shape)}")


def _spec_key(spec):
    def walk(x):
        if isinstance(x, (list, tuple)):
            return tuple(walk(i) for i in x)
        if isinstance(x, dict):
            return tuple(sorted((k, walk(v)) for k, v in x.items()))
        if isinstance(x, (int, float, str, bool, type(None))):
            return x
        return str(x)
    return walk(spec)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper compiling an eager function into one XLA program."""
    donate = kwargs.get("donate_state", None)

    def decorate(fn):
        if isinstance(fn, StaticFunction):
            return fn
        from ..nn.layer.layers import Layer
        if isinstance(fn, Layer):
            layer = fn
            layer.forward = StaticFunction(layer.forward, input_spec,
                                           donate_state=donate)
            return layer
        return StaticFunction(fn, input_spec, donate_state=donate)
    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


class TranslatedLayer:
    """Loaded inference bundle (jit.save counterpart)."""

    def __init__(self, state_dict, forward_fn=None, meta=None):
        self._state = state_dict
        self._meta = meta or {}

    def state_dict(self):
        return self._state


def save(layer, path, input_spec=None, **configs):
    """jit.save parity: persist params (+ structure note) for inference.

    Reference exports a ProgramDesc; the TPU-native equivalent persists the
    state_dict and (optionally) an input spec — reload with jit.load, rebind
    to the model class, and jax.jit recompiles on first call (XLA is the
    portable program format here, recompiled per topology).
    """
    from ..framework.io import save as fsave
    from ..nn.layer.layers import Layer
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    if isinstance(layer, Layer):
        sd = layer.state_dict()
    else:
        sd = layer
    fsave(sd, path + ".pdparams")
    meta = {"input_spec": repr(input_spec), "class": type(layer).__name__}
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(meta, f)


def load(path, **configs):
    from ..framework.io import load as fload
    sd = fload(path + ".pdparams")
    meta = {}
    if os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            meta = pickle.load(f)
    return TranslatedLayer(sd, meta=meta)
