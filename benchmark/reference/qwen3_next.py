"""The plain reference: Qwen3-Next's forward pass (the block of
``Qwen/Qwen3-Next-80B-A3B-Instruct``; Gated DeltaNet after Yang et al. 2024)
in straightforward float32 ``jax.numpy``, one sequence at a time: no kernel,
no chunks, no cache, no batching, no program code. Every matrix product runs
under ``jax.default_matmul_precision("highest")``.

Layer ``i`` (0-based) is ``x += mixer_i(norm(x)); x += moe(norm(x))`` with
``norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``; the mixer is gated
softmax attention where ``(i + 1) % interval == 0`` and a Gated DeltaNet
otherwise; a final ``norm`` and an untied head follow.

* Gated DeltaNet: the delta rule as a ``lax.scan`` over TOKENS, which is the
  rule's definition: ``S <- exp(g_t) S; S <- S + k_t (beta_t (v_t - S^T
  k_t))^T; o_t = S^T q_t``.
* Gated attention: a full masked softmax, computed in blocks of queries so
  that a long sequence fits.
* Experts: a loop over the ids in ``experts_held`` (all of them in the uncut
  model) with the routing weight as a mask; the parts of experts that are
  not held are left out, and that partial result goes on to the next layer,
  as in the program (the `model-configs` guide, section 4).

It reads the SAME seeded weights the program holds, through a family file's
``reference_weights``, in this canonical form (``E`` hidden, ``hk``/``hv``
key/value heads of the delta rule, ``H``/``G`` query/KV heads of attention,
``X`` the router's width, ``n`` experts held, ``F`` expert width)::

    {"eps": 1e-6, "rope_theta": 1e7, "rotary_dim": 64, "top_k": 10,
     "embed": [V, E], "norm": [E], "head": [E, V],
     "layers": [{"norm1": [E], "norm2": [E],
                 "mixer": <one of the two below>,
                 "moe": {"router": [E, X], "held": int32 [n],
                         "w_gate", "w_up": [n, E, F], "w_down": [n, F, E],
                         "shared_gate", "shared_up": [E, Fs],
                         "shared_down": [Fs, E], "shared_sigmoid": [E]}}]}
    linear mixer: {"w_q", "w_k": [E, hk, dk], "w_v", "w_z": [E, hv, dv],
                   "w_b", "w_a": [E, hv], "conv": [hk*dk*2 + hv*dv, K]
                   (channels in the order q, k, v), "A_log", "dt_bias": [hv],
                   "norm_g": [dv], "w_o": [hv*dv, E]}
    full mixer:   {"w_q", "w_gate": [E, H, D], "w_k", "w_v": [E, G, D],
                   "q_norm", "k_norm": [D], "w_o": [H*D, E]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # queries a block of the full softmax


def _f32(p, low=None):
    """Every floating array of ``p`` in float32, after a round trip through
    the dtype named ``low`` if one is given (the control, see ``logits``)."""
    def one(a):
        a = jnp.asarray(a)
        if jnp.issubdtype(a.dtype, jnp.integer):
            return a
        a = a.astype(jnp.float32)
        return a if low is None else a.astype(low).astype(jnp.float32)
    return jax.tree_util.tree_map(one, p)


def norm(x, w, eps):
    """The zero-centred RMSNorm: the weight is stored as its offset from 1."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def gated_delta_rule(q, k, v, g, beta):
    """The recurrence, token by token, from a zero state. ``q``, ``k``
    [T, h, dk] (already normalised and scaled), ``v`` [T, h, dv], ``g``,
    ``beta`` [T, h]; returns ``o`` [T, h, dv]."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        old = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - old))
        return s, jnp.einsum("hkv,hk->hv", s, q_t)
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def causal_conv(x, w):
    """Depthwise, causal, no bias: ``x`` [T, C], ``w`` [C, K]."""
    k = w.shape[1]
    pad = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(pad[j:j + x.shape[0]] * w[:, j] for j in range(k))


def linear_mixer(x, p, eps):
    """Gated DeltaNet on one sequence ``x`` [T, E]."""
    t = x.shape[0]
    hk, dk = p["w_q"].shape[1:]
    hv, dv = p["w_v"].shape[1:]
    qkv = jnp.concatenate(
        [jnp.einsum("te,ehd->thd", x, p[n]).reshape(t, -1)
         for n in ("w_q", "w_k", "w_v")], axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, p["conv"]))
    q = qkv[:, :hk * dk].reshape(t, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(x @ p["w_b"])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["w_a"] + p["dt_bias"])
    rep = hv // hk                      # each key head serves rep value heads
    q = jnp.repeat(l2norm(q) * dk ** -0.5, rep, axis=1)
    k = jnp.repeat(l2norm(k), rep, axis=1)
    o = gated_delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * p["norm_g"]
    z = jnp.einsum("te,ehd->thd", x, p["w_z"])
    return (o * jax.nn.silu(z)).reshape(t, -1) @ p["w_o"]


def rope(x, theta, rotary_dim):
    """Rotate-half rotary embedding on the first ``rotary_dim`` of the head
    dimension; ``x`` [T, h, D]."""
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                    / rotary_dim)
    ang = pos[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    r, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rot = jnp.concatenate([-r[..., half:], r[..., :half]], -1)
    return jnp.concatenate([r * cos + rot * sin, rest], -1)


def full_mixer(x, p, eps, theta, rotary_dim):
    """Gated softmax attention on one sequence ``x`` [T, E]."""
    t = x.shape[0]
    h, d = p["w_q"].shape[1:]
    g = p["w_k"].shape[1]
    q = norm(jnp.einsum("te,ehd->thd", x, p["w_q"]), p["q_norm"], eps)
    k = norm(jnp.einsum("te,egd->tgd", x, p["w_k"]), p["k_norm"], eps)
    v = jnp.einsum("te,egd->tgd", x, p["w_v"])
    q, k = rope(q, theta, rotary_dim), rope(k, theta, rotary_dim)
    k, v = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    out = []
    for start in range(0, t, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) * d ** -0.5
        seen = (start + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(t)
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    a = jnp.concatenate(out).reshape(t, -1)
    gate = jnp.einsum("te,ehd->thd", x, p["w_gate"]).reshape(t, -1)
    return (a * jax.nn.sigmoid(gate)) @ p["w_o"]


def moe(x, p, top_k):
    """The expert layer's part that the experts in ``p["held"]`` and the
    shared expert give, on ``x`` [T, E]."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)          # over all top_k chosen
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)

    def one(y, e):
        held, w_gate, w_up, w_down = e
        return y + weight[:, held, None] * swiglu(x, w_gate, w_up,
                                                  w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["held"], p["w_gate"], p["w_up"], p["w_down"]))
    shared = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y + jax.nn.sigmoid(x @ p["shared_sigmoid"])[:, None] * shared


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rotary_dim",
                                             "top_k", "low"))
def _layer(x, p, *, eps, theta, rotary_dim, top_k, low):
    p, x = _f32(p, low), _f32(x, low)
    h = norm(x, p["norm1"], eps)
    if "A_log" in p["mixer"]:
        x = x + linear_mixer(h, p["mixer"], eps)
    else:
        x = x + full_mixer(h, p["mixer"], eps, theta, rotary_dim)
    return x + moe(norm(x, p["norm2"], eps), p["moe"], top_k)


def logits(w, tokens, low=None):
    """Float32 logits [T, V] of one token sequence. ``low`` names a dtype
    (``"float8_e4m3fn"``) for the CONTROL of a comparison's limits: the same
    computation with every weight and every layer's input rounded to it,
    which a comparison tight enough for a bf16 program has to refuse."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed"][tokens], low)
        for p in w["layers"]:
            x = _layer(x, p, eps=w["eps"], theta=w["rope_theta"],
                       rotary_dim=w["rotary_dim"], top_k=w["top_k"], low=low)
        x = norm(_f32(x, low), _f32(w["norm"], low), w["eps"])
        return x @ _f32(w["head"], low)


def token_loss(lg, labels):
    """Mean next-token cross-entropy of logits [T, V], float32."""
    logp = jax.nn.log_softmax(jnp.asarray(lg, jnp.float32), axis=-1)
    labels = jnp.asarray(labels, jnp.int32)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def loss(w, tokens, labels):
    """Mean next-token cross-entropy of one sequence, float32."""
    return token_loss(logits(w, tokens), labels)
