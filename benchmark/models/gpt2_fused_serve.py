"""Family ``gpt2_fused_serve``: GPT-2 as the serving stack builds it,
``Embedding`` + ``FusedMultiTransformer`` (pre-LN) + head, the head being
``Sequential(LayerNorm, Linear)`` so that GPT-2's final LayerNorm is there.

What the program's stack cannot express, and the reference therefore omits
too (the configuration file lists both under ``assumed``): the learned
position embedding (the stack has rotary or none), and the tie between the
head and the embedding (the head is a matrix of its own, same shape).

Weights: the layers' own constructors run (the program's, leaf by leaf),
then EVERY parameter is overwritten from ``--seed`` by one jitted call on
the device in bf16, GPT-2's initialisation (N(0, 0.02), residual
projections scaled by 1/sqrt(2 L)) with biases and LayerNorm parameters
drawn too, so that the comparison with the reference exercises them.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from benchmark import seeded


def build(config, seed):
    """(fmt, embed, head) with seeded bf16 weights on the device."""
    from paddle_tpu.incubate.nn import FusedMultiTransformer
    from paddle_tpu.nn.layer.common import Embedding, Linear
    from paddle_tpu.nn.layer.layers import Sequential
    from paddle_tpu.nn.layer.norm import LayerNorm

    e, nh, ff = config["n_embd"], config["n_head"], config["n_inner"]
    nl, v = config["n_layer"], config["padded_vocab_size"]
    eps = config["layer_norm_epsilon"]
    embed = Embedding(v, e)
    fmt = FusedMultiTransformer(e, nh, ff, num_layers=nl,
                                normalize_before=True, epsilon=eps)
    head = Sequential(LayerNorm(e, eps), Linear(e, v, bias_attr=False))
    for lay in (embed, fmt, head):
        lay.bfloat16()
    fmt.eval()

    std, res = 0.02, 0.02 / math.sqrt(2 * nl)
    ln_f, lin = head[0], head[1]
    groups = [      # (parameters, mean, std)
        ([embed.weight], 0.0, std),
        (list(fmt.ln_scales), 1.0, std), (list(fmt.ln_biases), 0.0, std),
        (list(fmt.qkv_weights), 0.0, std), (list(fmt.qkv_biases), 0.0, std),
        (list(fmt.linear_weights), 0.0, res),
        (list(fmt.linear_biases), 0.0, std),
        (list(fmt.ffn_ln_scales), 1.0, std),
        (list(fmt.ffn_ln_biases), 0.0, std),
        (list(fmt.ffn1_weights), 0.0, std), (list(fmt.ffn1_biases), 0.0, std),
        (list(fmt.ffn2_weights), 0.0, res),
        (list(fmt.ffn2_biases), 0.0, std),
        ([ln_f.weight], 1.0, std), ([ln_f.bias], 0.0, std),
        ([lin.weight], 0.0, std),
    ]
    n_given = sum(len(ps) for ps, _, _ in groups)
    n_all = sum(len(list(lay.parameters())) for lay in (embed, fmt, head))
    if n_given != n_all:
        raise RuntimeError(f"{n_all} parameters, {n_given} seeded")
    drawn = seeded.normal_arrays(
        seed, [(ps[0].shape, m, s, len(ps)) for ps, m, s in groups],
        jnp.bfloat16)
    for (ps, _, _), arrays in zip(groups, drawn):
        for p, a in zip(ps, arrays):
            p._data = a
    return fmt, embed, head


def reference_weights(model):
    """The program's arrays in the reference's canonical form."""
    fmt, embed, head = model
    blocks = []
    for i in range(fmt.num_layers):
        blocks.append({
            "ln1_g": fmt.ln_scales[i]._data, "ln1_b": fmt.ln_biases[i]._data,
            # program: [3, n_head, hd, E], used as h @ W^T
            "w_qkv": jnp.transpose(fmt.qkv_weights[i]._data, (3, 0, 1, 2)),
            "b_qkv": fmt.qkv_biases[i]._data,
            "w_o": fmt.linear_weights[i]._data,
            "b_o": fmt.linear_biases[i]._data,
            "ln2_g": fmt.ffn_ln_scales[i]._data,
            "ln2_b": fmt.ffn_ln_biases[i]._data,
            "w_fc": fmt.ffn1_weights[i]._data,
            "b_fc": fmt.ffn1_biases[i]._data,
            "w_proj": fmt.ffn2_weights[i]._data,
            "b_proj": fmt.ffn2_biases[i]._data})
    return {"eps": fmt.epsilon, "wte": embed.weight._data, "wpe": None,
            "blocks": blocks, "lnf_g": head[0].weight._data,
            "lnf_b": head[0].bias._data, "head": head[1].weight._data}
