"""Family ``qwen3_next_train``:
``paddle_tpu.models.qwen3_next.Qwen3NextForCausalLM`` at the configuration's
sizes, as a user of the framework trains it: bf16 parameters,
``AdamW(multi_precision=True)`` with float32 master weights, the step wrapped
by ``paddle.jit.to_static`` AT ITS DEFAULTS.

The configuration states one expert-parallel rank's share: ``num_experts``
is the router's width, ``experts_held`` (a range ``"0-31"``) the experts
whose weights live here, ``vocab_size`` the slice of the vocabulary. Weights
come from the program's own initialisers under ``paddle.seed(seed)``.
"""
from __future__ import annotations


def _held(config):
    first, last = (int(v) for v in config["experts_held"].split("-"))
    held = list(range(first, last + 1))
    if len(held) != config["num_experts_held"]:
        raise ValueError("experts_held and num_experts_held disagree")
    return held


# The newest model built. The expert layers' routing counts live as long as
# their layers, and the ``moe_*`` readers come after the runner has returned.
_built = None


def build(config, seed):
    """(model, compiled step)."""
    global _built
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)

    paddle.seed(int(seed) % (2 ** 31 - 1))
    t = config["training"]
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=config["padded_vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=config["rope_theta"],
        full_attention_interval=config["full_attention_interval"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        experts_held=_held(config), rms_eps=config["rms_norm_eps"],
        initializer_range=config["initializer_range"],
        recompute=bool(t.get("recompute"))))
    model.bfloat16()
    _built = model
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
    """The program's arrays in the reference's canonical form
    (``benchmark/reference/qwen3_next.py``), in the program's own dtype: the
    reference makes its float32 copy one layer at a time."""
    import jax.numpy as jnp
    c = model.config
    layers = []
    for lyr in model.model.layers:
        m = lyr.mixer
        if lyr.kind == "full_attention":
            qg = m.q_proj.weight._data.reshape(-1, m.h, 2, m.d)
            mixer = {"w_q": qg[:, :, 0], "w_gate": qg[:, :, 1],
                     "w_k": m.k_proj.weight._data.reshape(-1, m.g, m.d),
                     "w_v": m.v_proj.weight._data.reshape(-1, m.g, m.d),
                     "q_norm": m.q_norm.weight._data,
                     "k_norm": m.k_norm.weight._data,
                     "w_o": m.o_proj.weight._data}
        else:
            key, value = m.hk * m.dk, m.hv * m.dv
            w = m.in_proj_qkvz.weight._data
            ba = m.in_proj_ba.weight._data
            mixer = {"w_q": w[:, :key].reshape(-1, m.hk, m.dk),
                     "w_k": w[:, key:2 * key].reshape(-1, m.hk, m.dk),
                     "w_v": w[:, 2 * key:2 * key + value].reshape(
                         -1, m.hv, m.dv),
                     "w_z": w[:, 2 * key + value:].reshape(-1, m.hv, m.dv),
                     "w_b": ba[:, :m.hv], "w_a": ba[:, m.hv:],
                     "conv": m.conv_weight._data, "A_log": m.A_log._data,
                     "dt_bias": m.dt_bias._data,
                     "norm_g": m.norm_weight._data,
                     "w_o": m.out_proj.weight._data}
        e = lyr.mlp
        f, fs = e.experts_down.shape[1], e.shared_down.shape[0]
        moe = {"router": e.router._data,
               "held": jnp.asarray(e.experts_held, jnp.int32),
               "w_gate": e.experts_gate_up._data[:, :, :f],
               "w_up": e.experts_gate_up._data[:, :, f:],
               "w_down": e.experts_down._data,
               "shared_gate": e.shared_gate_up._data[:, :fs],
               "shared_up": e.shared_gate_up._data[:, fs:],
               "shared_down": e.shared_down._data,
               "shared_sigmoid": e.shared_gate._data[:, 0]}
        layers.append({"norm1": lyr.input_layernorm.weight._data,
                       "norm2": lyr.post_attention_layernorm.weight._data,
                       "mixer": mixer, "moe": moe})
    return {"eps": c.rms_eps, "rope_theta": c.rope_theta,
            "rotary_dim": c.rotary_dim, "top_k": c.num_experts_per_tok,
            "embed": model.model.embed_tokens.weight._data,
            "norm": model.model.norm.weight._data,
            "head": model.lm_head.weight._data, "layers": layers}


def flops_per_token(config, seq):
    """Model FLOPs a trained token needs HERE, forward and backward, with no
    recomputation counted: 6 x the matrix parameters the token touches on
    this rank, plus the products that have no parameter. Per layer kind:

    * Gated DeltaNet: ``in_proj_qkvz`` E x (2 hk dk + 2 hv dv), ``in_proj_ba``
      E x 2 hv, ``out_proj`` hv dv x E. The chunked rule's products per
      token and value head, with chunk C = 64 (2 FLOPs a multiply-add):
      K K^T and Q K^T over the chunk 2 x 2 C dk; the unit-triangular solve
      for [W | U], C (dk + dv) (half the square); W S and Q S 2 x 2 dk dv;
      tril(Q K^T) V' 2 C dv; K^T V' 2 dk dv: forward, and 3 x that with the
      backward.
    * Gated attention: ``q_proj`` E x 2 H D, ``k_proj`` + ``v_proj`` 2 E G D,
      ``o_proj`` H D x E; scores and their weighted sum 12 H D seq forward
      and backward (Kaplan et al. 2020's term, the full square as PaLM's
      appendix B counts it, as for GPT-2's family here).
    * Expert layer: router E x X; the shared expert 3 E Fs and its gate E;
      the routed experts at the EXPECTED rows a token sends to the experts
      held here, top_k x held / X (0.625), x 3 E F each.

    and the untied head E x V (the embedding is a lookup)."""
    e = config["hidden_size"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    h, g, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    x, top_k = config["num_experts"], config["num_experts_per_tok"]
    f, fs = (config["moe_intermediate_size"],
             config["shared_expert_intermediate_size"])
    chunk = 64
    linear = e * (2 * hk * dk + 2 * hv * dv) + e * 2 * hv + hv * dv * e
    rule = hv * (2 * 2 * chunk * dk + chunk * (dk + dv) + 2 * 2 * dk * dv
                 + 2 * chunk * dv + 2 * dk * dv)
    full = e * 2 * h * d + 2 * e * g * d + h * d * e
    routed = top_k * config["num_experts_held"] / x
    moe = e * x + 3 * e * fs + e + routed * 3 * e * f
    n_layers = config["num_hidden_layers"]
    n_full = n_layers // config["full_attention_interval"]
    n_linear = n_layers - n_full
    n_matmul = (n_linear * linear + n_full * full + n_layers * moe
                + e * config["padded_vocab_size"])
    return (6 * n_matmul + 3 * n_linear * rule + 12 * n_full * h * d * seq)
