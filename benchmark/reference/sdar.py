"""The plain reference: SDAR's block-diffusion forward pass and loss (the
block of ``JetLM/SDAR-30B-A3B-Chat``; the objective after BD3-LM, Arriola et
al. 2025, and LLaDA's masked-diffusion bound) in straightforward float32
``jax.numpy``, one sequence at a time: no kernel, no structured mask, no
cache, no batching, no program code. Every matrix product runs under
``jax.default_matmul_precision("highest")``.

One sequence ``x0`` of ``L`` tokens and one draw of the noise (``masked``
[L] bool) give ``z = [x_t ; x0]``, ``2 L`` positions with position ids ``[0
.. L-1 ; 0 .. L-1]``. Layer: ``h += attn(norm(h)); h += moe(norm(h))`` with
``norm(x) = x * rsqrt(mean(x^2) + eps) * w``; a final ``norm`` and an untied
head over the noisy half follow.

* Attention: ``q = rope(norm_D(x W_q))``, ``k = rope(norm_D(x W_k))``, ``v
  = x W_v``, rotate-half RoPE on the whole head, a full softmax under the
  DENSE boolean mask built from ``beta(j) = (j mod L) // b`` ("noisy" is ``j
  < L``), in blocks of queries so that 16,384 positions fit::

      noisy i -> noisy j : beta(j) == beta(i)
      noisy i -> clean j : beta(j) <  beta(i)
      clean i -> clean j : beta(j) <= beta(i)
      clean i -> noisy j : never

* Experts: a loop over the ids in ``held`` with the routing weight as a
  mask (softmax over all experts, top-k renormalised over the chosen, no
  shared expert); the parts of experts that are not held are left out, and
  that partial result goes on to the next layer, as in the program.
* Loss: ``(1 / L) sum_{i < L} m_i (1 / t_beta(i)) CE(logits_i, x0_i)``, no
  shift.

It reads the SAME seeded weights the program holds, through the family
file's ``reference_weights``, in this canonical form (``E`` hidden, ``H`` /
``G`` query / KV heads of ``D``, ``X`` the router's width, ``n`` experts
held, ``F`` expert width)::

    {"eps": 1e-6, "rope_theta": 1e6, "top_k": 8, "block_length": 4,
     "mask_token_id": V - 1, "embed": [V, E], "norm": [E], "head": [E, V],
     "layers": [{"norm1": [E], "norm2": [E],
                 "attn": {"w_q": [E, H, D], "w_k", "w_v": [E, G, D],
                          "q_norm", "k_norm": [D], "w_o": [H*D, E]},
                 "moe": {"router": [E, X], "held": int32 [n],
                         "w_gate", "w_up": [n, E, F], "w_down": [n, F, E]}}]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512       # queries a block of the full softmax


def _f32(p, low=None):
    """Every floating array of ``p`` in float32, after a round trip through
    the dtype named ``low`` if one is given (the control, see ``logits``)."""
    def one(a):
        a = jnp.asarray(a)
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        a = a.astype(jnp.float32)
        return a if low is None else a.astype(low).astype(jnp.float32)
    return jax.tree_util.tree_map(one, p)


def norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def rope(x, pos, theta):
    """Rotate-half rotary embedding on the whole head; ``x`` [T, h, D] at
    the positions ``pos`` [T]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def allowed(rows, seq, b):
    """The rule, dense: [len(rows), 2 seq] bool for the query positions
    ``rows`` of ``[noisy ; clean]`` against every key position."""
    cols = jnp.arange(2 * seq)
    beta_r, beta_c = (rows % seq) // b, (cols % seq) // b
    noisy_r, noisy_c = (rows < seq)[:, None], (cols < seq)[None, :]
    beta_r, beta_c = beta_r[:, None], beta_c[None, :]
    return jnp.where(noisy_r,
                     jnp.where(noisy_c, beta_c == beta_r, beta_c < beta_r),
                     ~noisy_c & (beta_c <= beta_r))


def attention(x, p, eps, theta, b):
    """Attention on ``x`` [2 L, E] over ``[noisy ; clean]``."""
    t = x.shape[0]
    seq = t // 2
    h, d = p["w_q"].shape[1:]
    g = p["w_k"].shape[1]
    pos = jnp.arange(t) % seq
    q = norm(jnp.einsum("te,ehd->thd", x, p["w_q"]), p["q_norm"], eps)
    k = norm(jnp.einsum("te,egd->tgd", x, p["w_k"]), p["k_norm"], eps)
    v = jnp.einsum("te,egd->tgd", x, p["w_v"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    k, v = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    out = []
    for start in range(0, t, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) * d ** -0.5
        seen = allowed(start + jnp.arange(qb.shape[0]), seq, b)
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out).reshape(t, -1) @ p["w_o"]


def moe(x, p, top_k):
    """The expert layer's part that the experts in ``p["held"]`` give, on
    ``x`` [T, E]."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)          # over all top_k chosen
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)

    def one(y, e):
        held, w_gate, w_up, w_down = e
        return y + weight[:, held, None] * swiglu(x, w_gate, w_up,
                                                  w_down), None
    return jax.lax.scan(one, jnp.zeros_like(x),
                        (p["held"], p["w_gate"], p["w_up"], p["w_down"]))[0]


@functools.partial(jax.jit, static_argnames=("eps", "theta", "top_k", "b",
                                             "low"))
def _layer(x, p, *, eps, theta, top_k, b, low):
    p, x = _f32(p, low), _f32(x, low)
    x = x + attention(norm(x, p["norm1"], eps), p["attn"], eps, theta, b)
    return x + moe(norm(x, p["norm2"], eps), p["moe"], top_k)


def logits(w, tokens, masked, low=None):
    """Float32 logits [L, V] of the NOISY half for one token sequence
    ``tokens`` [L] whose positions ``masked`` [L] are replaced by the mask
    id. ``low`` names a dtype (``"float8_e4m3fn"``) for the CONTROL of a
    comparison's limits: the same computation with every weight and every
    layer's input rounded to it, which a comparison tight enough for a bf16
    program has to refuse."""
    tokens = jnp.asarray(tokens, jnp.int32)
    seq = tokens.shape[0]
    noisy = jnp.where(jnp.asarray(masked, bool), w["mask_token_id"], tokens)
    with jax.default_matmul_precision("highest"):
        x = _f32(w["embed"][jnp.concatenate([noisy, tokens])], low)
        for p in w["layers"]:
            x = _layer(x, p, eps=w["eps"], theta=w["rope_theta"],
                       top_k=w["top_k"], b=w["block_length"], low=low)
        x = norm(_f32(x[:seq], low), _f32(w["norm"], low), w["eps"])
        return x @ _f32(w["head"], low)


def token_loss(lg, tokens, masked, t, block_length):
    """The weighted loss of logits [L, V]: ``t`` [blocks] is each block's
    noise level."""
    logp = jax.nn.log_softmax(jnp.asarray(lg, jnp.float32), axis=-1)
    tokens = jnp.asarray(tokens, jnp.int32)
    ce = -jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    t_tok = jnp.asarray(t, jnp.float32)[
        jnp.arange(tokens.shape[0]) // block_length]
    return (jnp.asarray(masked, jnp.float32) / t_tok * ce).mean()


def loss(w, tokens, masked, t):
    """The block-diffusion loss of one sequence under one draw, float32."""
    return token_loss(logits(w, tokens, masked), tokens, masked, t,
                      w["block_length"])
