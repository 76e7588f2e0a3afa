"""Family ``sdar_train``: ``paddle_tpu.models.sdar.SDARForBlockDiffusion`` at
the configuration's sizes, as a user of the framework trains it: bf16
parameters, ``AdamW(multi_precision=True)`` with float32 master weights, the
step wrapped by ``paddle.jit.to_static`` AT ITS DEFAULTS. The step draws its
noise inside the compiled program, fresh every call.

The configuration states one expert-parallel rank's share: ``num_experts``
is the router's width, ``experts_held`` (a range ``"0-15"``) the experts
whose weights live here, ``vocab_size`` the slice of the vocabulary, whose
last row is the mask id. Weights come from the program's own initialisers
under ``paddle.seed(seed)``.
"""
from __future__ import annotations

from benchmark.models.qwen3_next_train import _held


# The newest model built. The expert layers' routing counts and the model's
# noise counts live as long as it does, and the readers come after the
# runner has returned.
_built = None


def build(config, seed):
    """(model, compiled step). The step takes ``(x, y)`` as every training
    family's does and ignores ``y``: position i restores token i. It
    differentiates the weighted bound and REPORTS the mean cross-entropy of
    the masked tokens (``SDARForBlockDiffusion.losses``): runner ``train``
    holds the first reported loss within 5% of ln(vocabulary), and at
    initialisation the bound of one 8,192-token sequence spreads 1.6% around
    ln V + 0.41 (half the logits' variance, 2048 x 0.02^2), 4.2% above ln V
    already; the masked mean sits there too and spreads 0.14%."""
    global _built
    import paddle_tpu as paddle
    from paddle_tpu.models.sdar import SDARConfig, SDARForBlockDiffusion

    paddle.seed(int(seed) % (2 ** 31 - 1))
    t = config["training"]
    model = SDARForBlockDiffusion(SDARConfig(
        vocab_size=config["padded_vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=config["rope_theta"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        experts_held=_held(config), rms_eps=config["rms_norm_eps"],
        initializer_range=config["initializer_range"],
        recompute=bool(t.get("recompute")),
        block_length=config["block_length"], noise_eps=config["noise_eps"],
        mask_token_id=config["mask_token_id"]))
    model.bfloat16()
    _built = model
    opt = paddle.optimizer.AdamW(learning_rate=t["learning_rate"],
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def step(x, y):
        loss, masked_ce = model.losses(x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return masked_ce

    return model, paddle.jit.to_static(step)


def reference_weights(model):
    """The program's arrays in the reference's canonical form
    (``benchmark/reference/sdar.py``), in the program's own dtype: the
    reference makes its float32 copy one layer at a time."""
    import jax.numpy as jnp
    c = model.config
    layers = []
    for lyr in model.model.layers:
        m, e = lyr.self_attn, lyr.mlp
        f = e.experts_down.shape[1]
        layers.append({
            "norm1": lyr.input_layernorm.weight._data,
            "norm2": lyr.post_attention_layernorm.weight._data,
            "attn": {"w_q": m.q_proj.weight._data.reshape(-1, m.h, m.d),
                     "w_k": m.k_proj.weight._data.reshape(-1, m.g, m.d),
                     "w_v": m.v_proj.weight._data.reshape(-1, m.g, m.d),
                     "q_norm": m.q_norm.weight._data,
                     "k_norm": m.k_norm.weight._data,
                     "w_o": m.o_proj.weight._data},
            "moe": {"router": e.router._data,
                    "held": jnp.asarray(e.experts_held, jnp.int32),
                    "w_gate": e.experts_gate_up._data[:, :, :f],
                    "w_up": e.experts_gate_up._data[:, :, f:],
                    "w_down": e.experts_down._data}})
    return {"eps": c.rms_eps, "rope_theta": c.rope_theta,
            "top_k": c.num_experts_per_tok, "block_length": c.block_length,
            "mask_token_id": c.mask_token_id,
            "embed": model.model.embed_tokens.weight._data,
            "norm": model.model.norm.weight._data,
            "head": model.lm_head.weight._data, "layers": layers}


def allowed_entries(seq, block_length):
    """Entries of the ``2 seq x 2 seq`` score matrix that the block-diffusion
    rule allows, exactly: a noisy block sees itself and the clean copy of
    every earlier block, a clean block the clean copy of itself and of
    every earlier block. ``seq (seq + block_length)`` where the blocks are
    whole."""
    sizes = [min(block_length, seq - s) for s in range(0, seq, block_length)]
    total = before = 0
    for n in sizes:
        total += n * n + n * before + n * (before + n)
        before += n
    return total


def attention_flops(config, seq):
    """What the flash kernels are asked to compute for ONE data token (its
    noisy and its clean position), all layers: the allowed entries a data
    token x 2 d H FLOPs a product and entry x 7 products (Q K^T and P V
    forward; the recomputed Q K^T, dV, dP, dK and dQ backward). The
    forward that ``recompute`` replays and the masked entries of visited
    tiles are work of the implementation and are not in it."""
    h, d = config["num_attention_heads"], config["head_dim"]
    per_token = allowed_entries(seq, config["block_length"]) / seq
    return config["num_hidden_layers"] * per_token * 7 * 2 * d * h


def flops_per_token(config, seq):
    """Model FLOPs a trained DATA token needs HERE, forward and backward,
    with no recomputation counted. A data token is two positions (its
    noisy and its clean copy), and both go through every matrix of every
    layer: 2 x 6 x the matrix parameters a position touches on this rank
    (``q_proj`` E x H D, ``k_proj`` + ``v_proj`` 2 E G D, ``o_proj`` H D x
    E, the router E x X, the routed experts at the EXPECTED rows a position
    sends to the experts held here, top_k x held / X (1), x 3 E F each);
    attention by the rule's exact count of allowed entries, 12 d H each (Q
    K^T and P V forward, four products backward); the untied head E x V
    once, on the noisy position (the embedding is a lookup)."""
    e = config["hidden_size"]
    h, g, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    x, top_k = config["num_experts"], config["num_experts_per_tok"]
    routed = top_k * config["num_experts_held"] / x
    layer = (e * h * d + 2 * e * g * d + h * d * e + e * x
             + routed * 3 * e * config["moe_intermediate_size"])
    n_layers = config["num_hidden_layers"]
    per_token = allowed_entries(seq, config["block_length"]) / seq
    return (6 * (2 * n_layers * layer + e * config["padded_vocab_size"])
            + 12 * n_layers * per_token * d * h)
