"""Family ``gpt2_lm_train``: ``paddle_tpu.models.gpt.GPTForCausalLM`` at the
configuration's sizes (learned positions, pre-LN blocks, final LayerNorm,
head tied to the embedding), as a user of the framework trains it: bf16
parameters, ``AdamW(multi_precision=True)`` with float32 master weights,
the step wrapped by ``paddle.jit.to_static`` AT ITS DEFAULTS.

Weights come from the program's own initialisers under
``paddle.seed(seed)`` (GPT-2's scheme, which puts the first loss at
ln(vocabulary)); the model's constructor draws them leaf by leaf on the
device, which only a change to the program can shorten.
"""
from __future__ import annotations

import jax.numpy as jnp


def build(config, seed):
    """(model, compiled step)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(int(seed) % (2 ** 31 - 1))
    dropout = config["resid_pdrop"]
    if not dropout == config["attn_pdrop"] == config["embd_pdrop"]:
        raise ValueError("the program's GPT has one dropout rate")
    model = GPTForCausalLM(GPTConfig(
        vocab_size=config["padded_vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position=config["n_positions"], dropout=dropout,
        layer_norm_eps=config["layer_norm_epsilon"],
        initializer_range=config["initializer_range"]))
    model.bfloat16()
    t = config["training"]
    opt = paddle.optimizer.AdamW(learning_rate=t["learning_rate"],
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, paddle.jit.to_static(step)


def reference_weights(model):
    """The program's arrays in the reference's canonical form."""
    g = model.gpt
    c = model.config
    nh, hd = c.num_heads, c.hidden_size // c.num_heads
    blocks = []
    for b in g.h:
        blocks.append({
            "ln1_g": b.ln1.weight._data, "ln1_b": b.ln1.bias._data,
            # program: [E, 3 E] with the 3 E axis laid out (3, n_head, hd)
            "w_qkv": b.attn.qkv_proj.weight._data.reshape(-1, 3, nh, hd),
            "b_qkv": b.attn.qkv_proj.bias._data.reshape(3, nh, hd),
            "w_o": b.attn.out_proj.weight._data,
            "b_o": b.attn.out_proj.bias._data,
            "ln2_g": b.ln2.weight._data, "ln2_b": b.ln2.bias._data,
            "w_fc": b.mlp.fc1.weight._data, "b_fc": b.mlp.fc1.bias._data,
            "w_proj": b.mlp.fc2.weight._data,
            "b_proj": b.mlp.fc2.bias._data})
    return {"eps": c.layer_norm_eps, "wte": g.wte.weight._data,
            "wpe": g.wpe.weight._data, "blocks": blocks,
            "lnf_g": g.ln_f.weight._data, "lnf_b": g.ln_f.bias._data,
            "head": jnp.transpose(g.wte.weight._data)}


def flops_per_token(config, seq):
    """Model FLOPs a trained token needs, forward and backward, with no
    recomputation counted: 6 N for the matrix products over the N
    parameters that take part in one (the embedding counts once, as the
    tied head; the position table is a lookup) plus 12 L H S for the
    attention scores and their weighted sum (Kaplan et al. 2020, as used
    for MFU in PaLM's appendix B)."""
    e, nl, ff = config["n_embd"], config["n_layer"], config["n_inner"]
    n_matmul = nl * (4 * e * e + 2 * e * ff) + config["padded_vocab_size"] * e
    return 6 * n_matmul + 12 * nl * e * seq
