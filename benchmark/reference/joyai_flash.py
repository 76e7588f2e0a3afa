"""The plain reference: JoyAI-LLM-Flash's forward pass (the DeepSeek-V3 block
of ``jdopensource/JoyAI-LLM-Flash``) and its multi-token-prediction (MTP)
module, in straightforward float32 ``jax.numpy``, one sequence at a time: no
kernel, no cache, no batching, no program code. Every matrix product runs
under ``jax.default_matmul_precision("highest")``. The plain RMSNorm, the
SwiGLU and the expert layer are ``benchmark/reference/kimi_linear.py``'s
(the same definitions: sigmoid scores, the top-k of ``s + bias``, the
chosen scores renormalised and scaled, a loop over the held experts, the
ungated shared expert).

Layer ``i`` is ``x += MLA(norm(x)); x += ffn_i(norm(x))``:

* MLA: ``q = norm(x W_qa) W_qb`` [T, h, dn + dr]; ``[c | k_r] = x W_kva``;
  ``[k_n | v] = norm(c) W_kvb``; the last ``dr`` channels of each query head
  and ``k_r`` (one for all heads) rotated as PAIRS: channels ``(2j, 2j +
  1)`` at position ``p`` turn by ``p * theta^(-2j / dr)``
  (``rope_interleave``; the angles in float32); ``k = [k_n | k_r]``; a full
  masked softmax at ``1 / sqrt(dn + dr)`` in blocks of queries.
* ffn: the dense SwiGLU or the expert layer, as the weights say.

Main logits: ``norm(h) W_head``, ``h`` the last layer's output. MTP, at
positions ``i = 0 .. T - 2`` with ``t_{i+1}`` the next token: ``u = [norm(
embed[t_{i+1}]) ; norm(h_i)] W_eh``, one more decoder layer, ``norm``, the
same ``W_head``; it predicts ``t_{i+2}``. The loss is ``L_main +
MTP_LOSS_WEIGHT x L_MTP``.

Departures from the published model, which the program makes alike: the
experts not held are absent (the layer's part from the held ones and the
shared expert goes on, as on one expert-parallel rank with no exchange);
the MTP module's form and its weight 0.3 are DeepSeek-V3's (arXiv:2412.19437
section 2.2; the published config gives ``num_nextn_predict_layers`` 1 and
no equations or weight); ``h_i`` is taken before the final norm.

It reads the SAME seeded weights the program holds, through
``benchmark.models.joyai_flash_train.reference_weights``, in this form
(``E`` hidden, ``h`` heads, ``q`` / ``r`` the query and latent ranks)::

    {"eps", "top_k", "routed_scale", "rope_theta", "embed": [V, E],
     "norm": [E], "head": [E, V], "layers": [layer, ...],
     "mtp": {"enorm", "hnorm": [E], "w_eh": [2E, E], "norm": [E],
             "layer": layer}}
    layer: {"norm1", "norm2": [E], "mixer": {"w_qa": [E, q], "q_norm": [q],
            "w_qb": [q, h, dn+dr], "w_kva": [E, r+dr], "kv_norm": [r],
            "w_kvb": [r, h, dn+dv], "w_o": [h*dv, E]}, "ffn": dense or moe
            as in ``kimi_linear``}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kimi_linear import (Q_BLOCK, _f32, moe, norm,
                                             swiglu, token_loss as _ce)

# DeepSeek-V3's first-phase weight of the MTP loss (the config gives none).
MTP_LOSS_WEIGHT = 0.3


def rope_pairs(x, theta):
    """``x`` [T, ..., d] with channel pairs ``(2j, 2j + 1)`` at position
    ``p`` turned by ``p * theta^(-2j / d)``."""
    t, d = x.shape[0], x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2) / d), jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def mla_mixer(x, p, eps, theta):
    """Latent attention with a compressed query and decoupled rotary on
    ``x`` [T, E]."""
    t = x.shape[0]
    h, dq = p["w_qb"].shape[1:]
    r = p["kv_norm"].shape[0]
    dr = p["w_kva"].shape[1] - r
    dn = dq - dr
    q = jnp.einsum("tc,chd->thd", norm(x @ p["w_qa"], p["q_norm"], eps),
                   p["w_qb"])
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], theta)], -1)
    ckr = x @ p["w_kva"]
    kv = jnp.einsum("tr,rhd->thd", norm(ckr[:, :r], p["kv_norm"], eps),
                    p["w_kvb"])
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        rope_pairs(ckr[:, r:], theta)[:, None], (t, h, dr))], -1)
    v = kv[..., dn:]
    pad = -t % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, h, dq)

    def block(args):
        i, qi = args
        s = jnp.einsum("thd,shd->hts", qi, k) * dq ** -0.5
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where((rows[:, None] >= jnp.arange(t))[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    out = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    return out.reshape(-1, h * v.shape[-1])[:t] @ p["w_o"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scale",
                                             "theta", "low"))
def _layer(x, p, *, eps, top_k, scale, theta, low):
    p, x = _f32(p, low), _f32(x, low)
    x = x + mla_mixer(norm(x, p["norm1"], eps), p["mixer"], eps, theta)
    h = norm(x, p["norm2"], eps)
    f = p["ffn"]
    if "router" in f:
        return x + moe(h, f, top_k, scale)
    return x + swiglu(h, f["w_gate"], f["w_up"], f["w_down"])


def _heads(w, tokens, nxt, low):
    """Logits [T + T - 1, V]: the main head's, then the MTP head's at
    positions 0 .. T - 2 given the next tokens ``nxt`` [T - 1]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    nxt = jnp.asarray(nxt, jnp.int32)
    kw = dict(eps=w["eps"], top_k=w["top_k"], scale=w["routed_scale"],
              theta=w["rope_theta"], low=low)
    eps = w["eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed"][tokens], low)
        for p in w["layers"]:
            x = _layer(x, p, **kw)
        head = _f32(w["head"], low)
        x = _f32(x, low)
        main = norm(x, _f32(w["norm"], low), eps) @ head
        m = _f32({k: a for k, a in w["mtp"].items() if k != "layer"}, low)
        u = jnp.concatenate(
            [norm(_f32(w["embed"][nxt], low), m["enorm"], eps),
             norm(x[:-1], m["hnorm"], eps)], -1) @ m["w_eh"]
        u = _f32(_layer(u, w["mtp"]["layer"], **kw), low)
        return jnp.concatenate([main, norm(u, m["norm"], eps) @ head])


def logits(w, tokens, low=None):
    """Float32 logits [2T - 1, V] of one token sequence: the main head's T
    rows, then the MTP head's T - 1 (position ``i`` reads ``tokens[i +
    1]``). ``low`` names a dtype (``"float8_e4m3fn"``) for the CONTROL of a
    comparison's limits: the same computation with every weight and every
    layer's input rounded to it."""
    return _heads(w, tokens, jnp.asarray(tokens)[1:], low)


def token_loss(lg, labels, mtp_weight=MTP_LOSS_WEIGHT):
    """``L_main + mtp_weight x L_MTP`` of logits [2T - 1, V] laid out as
    ``logits`` lays them, with ``labels`` [T] the next tokens: the main
    rows against ``labels``, the MTP rows against ``labels[1:]``."""
    t = jnp.asarray(labels).shape[0]
    labels = jnp.asarray(labels, jnp.int32)
    return _ce(lg[:t], labels) + mtp_weight * _ce(lg[t:], labels[1:])


def loss(w, tokens, labels):
    """The training loss of one sequence, float32; the MTP module reads
    ``labels[:-1]`` as the next tokens."""
    return token_loss(_heads(w, tokens, jnp.asarray(labels)[:-1], None),
                      labels)
