"""The plain reference: GPT-2's forward pass (Radford et al. 2019; the block
of ``openai-community/gpt2*``) in straightforward float32 ``jax.numpy``.

Pre-LN multi-head causal self-attention, GELU (tanh form, "gelu_new") FFN
of 4x width, final LayerNorm, linear head; one sequence at a time, the whole
sequence at once: no kernel, no cache, no batching, no program code. Every
matrix product runs under ``jax.default_matmul_precision("highest")`` (on a
TPU a float32 product is otherwise computed in bf16 passes).

It reads the SAME seeded weights the program holds, through a family file's
``reference_weights`` (``benchmark/models/``), in this canonical form::

    {"eps": 1e-5,
     "wte": [V, E], "wpe": [P, E] or None,      # None: no position term
     "blocks": [{"ln1_g", "ln1_b",               # [E]
                 "w_qkv": [E, 3, n_head, E/n_head], "b_qkv": [3, n_head, hd],
                 "w_o": [E, E], "b_o": [E], "ln2_g", "ln2_b",
                 "w_fc": [E, F], "b_fc": [F], "w_proj": [F, E], "b_proj"}],
     "lnf_g": [E] or None, "lnf_b": [E] or None,  # None: no final LayerNorm
     "head": [E, V]}

Departures a configuration makes from the published model (no learned
positions in the serving stack) are made HERE by passing None, and are
listed in that configuration's file under ``assumed``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(p):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, eps):
    """One GPT-2 block on one sequence ``x`` [T, E]."""
    t = x.shape[0]
    hd = p["w_qkv"].shape[-1]
    h = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = jnp.einsum("te,ecnd->ctnd", h, p["w_qkv"]) + p["b_qkv"][:, None]
    q, k, v = qkv[0], qkv[1], qkv[2]                    # [T, n_head, hd]
    s = jnp.einsum("tnd,snd->nts", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(t, -1) @ p["w_o"] + p["b_o"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    return x + gelu_new(h @ p["w_fc"] + p["b_fc"]) @ p["w_proj"] \
        + p["b_proj"]


_block = jax.jit(lambda x, p, eps: block(x, _f32(p), eps))


def logits(w, tokens):
    """Float32 logits [T, V] of one token sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w["wte"][tokens], jnp.float32)
        if w["wpe"] is not None:
            x = x + jnp.asarray(w["wpe"], jnp.float32)[:tokens.shape[0]]
        for p in w["blocks"]:
            x = _block(x, p, w["eps"])
        if w["lnf_g"] is not None:
            x = layer_norm(x, jnp.asarray(w["lnf_g"], jnp.float32),
                           jnp.asarray(w["lnf_b"], jnp.float32), w["eps"])
        return x @ jnp.asarray(w["head"], jnp.float32)


def loss(w, tokens, labels):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(w, tokens)
    logp = jax.nn.log_softmax(lg, axis=-1)
    labels = jnp.asarray(labels, jnp.int32)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
