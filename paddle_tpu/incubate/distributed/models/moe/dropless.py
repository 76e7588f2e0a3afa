"""A dropless expert layer that holds a stated subset of the experts.

``DroplessMoELayer`` is one expert-parallel rank's view of a mixture of
experts: the router keeps its full width (``num_experts``) and its
``top_k``, the layer is told which experts it holds (``experts_held``, all
of them by default), and it returns the part of the layer's result that ITS
experts give, plus the shared expert, which every rank computes alike::

    p = softmax(x W_r)                        over all experts, float32
    chosen, w = the top_k largest p, w / sum(w)   (over all chosen, held or not)
    y = sum over chosen e held here of w_e SwiGLU_e(x)
        + sigmoid(x . w_sg) SwiGLU_shared(x)
    SwiGLU(x) = (SiLU(x W_g) * (x W_u)) W_d         no biases

What the absent experts would add is left out (their rank adds it in a
deployment; no code here stands in for the exchange). ``MoELayer`` beside
this file is the capacity-truncated GShard form and is not touched.

Nothing is dropped, at static shapes: the (token, choice) pairs whose expert
is held are sorted by expert into a buffer of ``buffer_rows(tokens)`` rows,
the experts run as ONE grouped product over each one's rows
(``jax.lax.ragged_dot``), and the weighted rows are added back to their
tokens. The buffer is ``BUFFER_FACTOR`` (2) x the rows an even router sends
here (``tokens * top_k * held / num_experts``), rounded up to 512: a stated
bound. A pair that does not fit is not computed and is COUNTED, never lost in
silence (``routing_stats()["pairs_dropped"]``); traffic under which the
count leaves 0 needs a larger factor.

Every layer keeps its routing counts on the device, as state of the step
(``counts``, a persistent tensor that ``to_static`` threads and donates like
a parameter): no host transfer happens until ``routing_stats()`` is called.
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .....inference import telemetry as _telemetry
from .....nn.initializer import Normal
from .....nn.layer.layers import Layer
from .....tensor.tensor import (Parameter, Tensor, apply_op,
                                register_persistent, unregister_persistent)

__all__ = ["DroplessMoELayer", "routing_stats"]

# The sorted buffer's rows over the rows an even router sends here. A random
# router at Qwen3-Next's widths loads its fullest held expert 2.2-2.5 x the
# mean, but the buffer is shared: 32 held experts together receive 0.96-1.02 x
# their even share (PERF.md section 6, PR 28).
BUFFER_FACTOR = 2.0

_F32 = jnp.float32
# counts[:, :4] are routing_stats()'s four totals, then one column per held
# expert (the pairs routed to it)
_PER_EXPERT = 4
# A count is two int32 words, the low 30 bits and the rest, because the
# device has no int64 and 163,840 pairs a step pass 2**31 in 13,107 steps.
_LOW_BITS = 30

# a weak reference to every layer alive, in order of construction
_layers: list = []


def add_counts(old, inc):
    """``old`` [2, n] int32 (low words, high words) plus ``inc`` [n] int32:
    counts kept on the device as state of a step, past 2**31."""
    low = old[0] + inc
    return jnp.stack([low & ((1 << _LOW_BITS) - 1),
                      old[1] + (low >> _LOW_BITS)])


def read_counts(raw):
    """The Python ints of a fetched [2, n] array of ``add_counts``."""
    return [int(lo) + (int(hi) << _LOW_BITS) for lo, hi in zip(raw[0], raw[1])]


def _swiglu(x, w_gate_up, w_down, dot):
    """``(SiLU(x W_g) * (x W_u)) W_d`` with ``[W_g | W_u]`` fused, through
    ``dot`` (a plain or a grouped product that accumulates in float32)."""
    f = w_down.shape[-2]
    h = dot(x, w_gate_up)
    return dot((jax.nn.silu(h[..., :f]) * h[..., f:]).astype(x.dtype), w_down)


class DroplessMoELayer(Layer):
    """``forward(x)``: [B, S, d_model] -> [B, S, d_model], this rank's part.

    ``d_hidden`` is one routed expert's width and ``shared_hidden`` the
    shared expert's (None: no shared expert). Expert weights are stacked
    over the experts held: ``experts_gate_up`` [held, d_model, 2 d_hidden]
    (gate, then up) and ``experts_down`` [held, d_hidden, d_model]."""

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 experts_held=None, shared_hidden=None,
                 initializer_range=0.02):
        super().__init__()
        held = (list(range(num_experts)) if experts_held is None
                else [int(e) for e in experts_held])
        if len(set(held)) != len(held) or not held or \
                min(held) < 0 or max(held) >= num_experts:
            raise ValueError(f"experts_held {held!r}: distinct ids below "
                             f"num_experts {num_experts}")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held = tuple(held)
        init = Normal(0.0, initializer_range)
        n = len(held)
        self.router = Parameter(init((d_model, num_experts), _F32))
        self.experts_gate_up = Parameter(
            init((n, d_model, 2 * d_hidden), _F32))
        self.experts_down = Parameter(init((n, d_hidden, d_model), _F32))
        if shared_hidden:
            self.shared_gate_up = Parameter(
                init((d_model, 2 * shared_hidden), _F32))
            self.shared_down = Parameter(init((shared_hidden, d_model), _F32))
            self.shared_gate = Parameter(init((d_model, 1), _F32))
        else:
            self.shared_gate_up = None
        # the expert's slot in the stacked weights, len(held) if not held
        slot = np.full((num_experts,), n, np.int32)
        slot[held] = np.arange(n, dtype=np.int32)
        self._slot_of = slot
        self.counts = Tensor(jnp.zeros((2, _PER_EXPERT + n), jnp.int32))
        register_persistent(self.counts)
        # once the layer is gone no later step threads its counts; a step in
        # flight (the collector may run inside one) still writes its result
        # back into this same tensor
        weakref.finalize(self, unregister_persistent, self.counts)
        _layers.append(weakref.ref(self, _layers.remove))

    def buffer_rows(self, tokens):
        """Rows of the sorted buffer for ``tokens`` tokens: the stated
        bound on the pairs this rank computes in one call."""
        even = tokens * self.top_k * len(self.experts_held) / self.num_experts
        return int(min(tokens * self.top_k,
                       -(-BUFFER_FACTOR * even // 512) * 512))

    def _plan(self, probs):
        """Which pair goes to which row: integers only, no gradient.
        ``probs`` [N, num_experts] -> every token's chosen experts [N, k],
        (token, expert, valid) of each buffer row, the rows of each held
        expert, and this call's counts."""
        n_tok, k, n = probs.shape[0], self.top_k, len(self.experts_held)
        rows = self.buffer_rows(n_tok)
        _, chosen = jax.lax.top_k(probs, k)                     # [N, k]
        slot = jnp.asarray(self._slot_of)[chosen].reshape(-1)
        # stable sort by slot: the held pairs first, expert by expert
        slot, pair = jax.lax.sort(
            (slot, jnp.arange(n_tok * k, dtype=jnp.int32)), num_keys=1)
        starts = jnp.searchsorted(slot, jnp.arange(n + 1, dtype=jnp.int32))
        sizes = jnp.diff(starts)                # pairs routed to each held
        group = jnp.diff(jnp.minimum(starts, rows))     # ... that fit
        local = starts[-1]
        counts = jnp.concatenate([
            jnp.stack([jnp.int32(n_tok * k), local, jnp.int32(rows),
                       local - jnp.minimum(local, rows)]), sizes])
        pair, slot = pair[:rows], slot[:rows]
        return (chosen, pair // k, chosen.reshape(-1)[pair], slot < n,
                group.astype(jnp.int32), counts.astype(jnp.int32))

    def _count(self, counts):
        self.counts._data = add_counts(self.counts._data, counts)

    def forward(self, x):
        b, s, d = x.shape

        def route(a, router):
            logits = jnp.matmul(a.reshape(b * s, d), router,
                                preferred_element_type=_F32)
            return jax.nn.softmax(logits, axis=-1)

        with jax.named_scope("moe.route"):
            probs = apply_op(route, x, self.router)
            chosen, token, expert, valid, group, counts = self._plan(
                probs._data)
        self._count(counts)

        def experts(a, p, w_gate_up, w_down):
            a = a.reshape(b * s, d)
            total = jnp.take_along_axis(p, chosen, axis=1).sum(-1)
            w = p[token, expert] / total[token]
            # The buffer's rows past the held pairs are pairs of real tokens
            # with experts not held here, and the grouped product leaves
            # those rows unwritten, of its result and of its input's
            # gradient alike (zeros on the CPU, whatever the memory held on
            # the TPU). A select on each side keeps them out of the sum and
            # out of the tokens' gradients, whatever they hold.
            y = _swiglu(jnp.where(valid[:, None], a[token], 0),  # [rows, d]
                        w_gate_up, w_down,
                        lambda u, v: jax.lax.ragged_dot(
                            u, v, group, preferred_element_type=_F32))
            y = jnp.where(valid[:, None], y, 0.0) * w[:, None]
            out = jnp.zeros((b * s, d), _F32).at[token].add(y)
            return out.astype(a.dtype).reshape(b, s, d)

        with jax.named_scope("moe.experts"):
            out = apply_op(experts, x, probs, self.experts_gate_up,
                           self.experts_down)
        if self.shared_gate_up is None:
            return out

        def shared(a, w_gate_up, w_down, w_sig):
            def dot(u, v):
                return jnp.matmul(u, v, preferred_element_type=_F32)
            gate = jax.nn.sigmoid(dot(a, w_sig))
            return (gate * _swiglu(a, w_gate_up, w_down, dot)).astype(a.dtype)

        with jax.named_scope("moe.shared"):
            return out + apply_op(shared, x, self.shared_gate_up,
                                  self.shared_down, self.shared_gate)


def routing_stats():
    """The routing counts of every ``DroplessMoELayer`` alive, read from the
    device in one transfer: totals and ``layers`` (in order of
    construction), each with ``pairs``
    (token-choice pairs routed, ``tokens x top_k``), ``pairs_local`` (to
    experts held here),
    ``rows_computed`` (rows of the sorted buffer), ``pairs_dropped`` (local
    pairs past the buffer: not computed) and, per layer,
    ``rows_per_expert`` (local pairs of each held expert, in the order of
    ``experts_held``). Counted since the layer was built; a forward that
    ``recompute`` replays counts once (the replay leaves state as the
    first forward left it)."""
    layers = [layer for layer in (r() for r in _layers) if layer is not None]
    raw = jax.device_get([layer.counts._data for layer in layers])
    names = ("pairs", "pairs_local", "rows_computed", "pairs_dropped")
    out = {name: 0 for name in names}
    out["layers"] = []
    for layer, c in zip(layers, raw):
        vals = read_counts(c)
        rec = dict(zip(names, vals))
        rec["experts_held"] = list(layer.experts_held)
        rec["rows_per_expert"] = vals[_PER_EXPERT:]
        for name in names:
            out[name] += rec[name]
        out["layers"].append(rec)
    return out


@_telemetry.runtime_collector
def _prometheus_counters():
    """``runtime_prometheus()``'s ``paddle_moe_*`` counters: nothing from a
    process that holds no such layer."""
    stats = routing_stats()
    if not stats["layers"]:
        return {}
    return {"paddle_moe_pairs_total": stats["pairs"],
            "paddle_moe_pairs_local_total": stats["pairs_local"],
            "paddle_moe_pairs_dropped_total": stats["pairs_dropped"]}
