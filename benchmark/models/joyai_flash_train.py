"""Family ``joyai_flash_train``:
``paddle_tpu.models.joyai_flash.JoyAIFlashForCausalLM`` at the
configuration's sizes, as a user of the framework trains it: bf16
parameters, ``AdamW(multi_precision=True)`` with float32 master weights, the
step wrapped by ``paddle.jit.to_static`` AT ITS DEFAULTS. The step
differentiates ``L_main + mtp_loss_weight x L_MTP`` and RETURNS ``L_main``,
the next-token cross-entropy, which ``train.run`` holds near ln V.

The configuration states one expert-parallel rank's share, under the
published keys: ``n_routed_experts`` is the router's width,
``experts_held`` (a range ``"0-15"``) the experts whose weights live here,
``vocab_size`` the slice of the vocabulary. Weights come from the program's
own initialisers under ``paddle.seed(seed)``.
"""
from __future__ import annotations

from benchmark.models.qwen3_next_train import _held

# The newest model built. The expert layers' routing counts live as long as
# their layers, and the ``moe_*`` readers come after the runner has returned.
_built = None


def build(config, seed):
    """(model, compiled step)."""
    global _built
    import paddle_tpu as paddle
    from paddle_tpu.models.joyai_flash import (JoyAIFlashConfig,
                                              JoyAIFlashForCausalLM)

    paddle.seed(int(seed) % (2 ** 31 - 1))
    t = config["training"]
    if (not config["rope_interleave"] or config["rope_scaling"] is not None
            or config["scoring_func"] != "sigmoid"
            or config["n_group"] != 1 or config["topk_group"] != 1
            or not config["norm_topk_prob"]
            or config["num_nextn_predict_layers"] != 1):
        raise ValueError("joyai_flash_train: interleaved rotary without "
                         "scaling, sigmoid scores renormalised, one group "
                         "and one MTP module are what the program computes")
    model = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        vocab_size=config["padded_vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        intermediate_size=config["intermediate_size"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        mtp_loss_weight=config["mtp_loss_weight"],
        experts_held=_held(config), rms_eps=config["rms_norm_eps"],
        initializer_range=config["initializer_range"],
        recompute=bool(t.get("recompute"))))
    model.bfloat16()
    _built = model
    opt = paddle.optimizer.AdamW(learning_rate=t["learning_rate"],
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def step(x, y):
        loss, main = model.losses(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return main

    return model, paddle.jit.to_static(step)


def _layer(lyr):
    """One decoder layer's arrays in the reference's form."""
    import jax.numpy as jnp
    m, e = lyr.mixer, lyr.mlp
    mixer = {"w_qa": m.q_a_proj.weight._data,
             "q_norm": m.q_a_layernorm.weight._data,
             "w_qb": m.q_b_proj.weight._data.reshape(m.q_rank, m.h,
                                                     m.dn + m.dr),
             "w_kva": m.kv_a_proj_with_mqa.weight._data,
             "kv_norm": m.kv_a_layernorm.weight._data,
             "w_kvb": m.kv_b_proj.weight._data.reshape(m.r, m.h,
                                                       m.dn + m.dv),
             "w_o": m.o_proj.weight._data}
    if hasattr(e, "router"):
        f, fs = e.experts_down.shape[1], e.shared_down.shape[0]
        ffn = {"router": e.router._data, "bias": e.selection_bias._data,
               "held": jnp.asarray(e.experts_held, jnp.int32),
               "w_gate": e.experts_gate_up._data[:, :, :f],
               "w_up": e.experts_gate_up._data[:, :, f:],
               "w_down": e.experts_down._data,
               "shared_gate": e.shared_gate_up._data[:, :fs],
               "shared_up": e.shared_gate_up._data[:, fs:],
               "shared_down": e.shared_down._data}
    else:
        ffn = {"w_gate": e.gate_proj.weight._data,
               "w_up": e.up_proj.weight._data,
               "w_down": e.down_proj.weight._data}
    return {"norm1": lyr.input_layernorm.weight._data,
            "norm2": lyr.post_attention_layernorm.weight._data,
            "mixer": mixer, "ffn": ffn}


def reference_weights(model):
    """The program's arrays in the reference's canonical form
    (``benchmark/reference/joyai_flash.py``), in the program's own dtype: the
    reference makes its float32 copy one layer at a time."""
    c, mtp = model.config, model.mtp
    return {"eps": c.rms_eps, "top_k": c.num_experts_per_tok,
            "routed_scale": c.routed_scaling_factor,
            "rope_theta": c.rope_theta,
            "embed": model.model.embed_tokens.weight._data,
            "norm": model.model.norm.weight._data,
            "head": model.lm_head.weight._data,
            "layers": [_layer(lyr) for lyr in model.model.layers],
            "mtp": {"enorm": mtp.enorm.weight._data,
                    "hnorm": mtp.hnorm.weight._data,
                    "w_eh": mtp.eh_proj.weight._data,
                    "norm": mtp.norm.weight._data,
                    "layer": _layer(mtp.layer)}}


def _mla_layers(config):
    """Latent-attention layers run a token: every decoder layer and the
    MTP module's."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def kda_rule_flops(config, seq):
    """No delta rule in this model (``train_topk_scoped`` asks every
    family)."""
    return 0


def attention_flops(config, seq):
    """What the flash kernels are asked to compute for ONE token, every MLA
    layer (the MTP module's too): the causal entries a token, ``(seq + 1) /
    2``, x the heads x (2 x 192 + 2 x 128) forward (Q K^T at the query-key
    head, P V at the value head) and (3 x 2 x 192 + 2 x 2 x 128) backward
    (the recomputed Q K^T, dK and dQ; dV and dP). The forward that
    ``recompute`` replays is work of the implementation and is not in it.
    The MTP module runs all ``seq`` positions (its last reads no next token
    and is not in its loss), so it is counted at ``seq``."""
    h = config["num_attention_heads"]
    dq = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    per_entry = 2 * dq + 2 * dv + 3 * 2 * dq + 2 * 2 * dv
    return _mla_layers(config) * (seq + 1) / 2 * h * per_entry


def flops_per_token(config, seq):
    """Model FLOPs a trained token needs HERE, forward and backward, with no
    recomputation counted: 6 x the matrix parameters the token touches on
    this rank, plus the products that have no parameter. Per MLA layer
    ``W_qa`` E q + ``W_qb`` q h (dn + dr) + ``W_kva`` E (r + dr) + ``W_kvb`` r
    h (dn + dv) + ``W_o`` h dv E, and causal attention at ``(seq + 1) / 2``
    entries a token, 3 x (2 x 192 + 2 x 128) a head and entry; the dense
    SwiGLU 3 E F_dense; an expert layer the router E x X, the shared expert
    3 E Fs and the routed experts at the EXPECTED rows a token sends to the
    experts held here, top_k x held / X, x 3 E F each. The MTP module is one
    more MLA and expert layer with ``W_eh`` 2 E x E, and the head E x V
    counts twice (main and MTP; the embedding is a lookup)."""
    e, h = config["hidden_size"], config["num_attention_heads"]
    q, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    x, top_k = config["n_routed_experts"], config["num_experts_per_tok"]
    f = config["moe_intermediate_size"]
    fs = config["n_shared_experts"] * f
    mla = (e * q + q * h * (dn + dr) + e * (r + dr) + r * h * (dn + dv)
           + h * dv * e)
    dense = 3 * e * config["intermediate_size"]
    moe = (e * x + 3 * e * fs
           + top_k * config["num_experts_held"] / x * 3 * e * f)
    n = config["num_hidden_layers"]
    n_dense = min(config["first_k_dense_replace"], n)
    n_mtp = config["num_nextn_predict_layers"]
    n_moe = n - n_dense + n_mtp
    n_matmul = (_mla_layers(config) * mla + n_dense * dense + n_moe * moe
                + n_mtp * 2 * e * e
                + (1 + n_mtp) * e * config["padded_vocab_size"])
    causal = (_mla_layers(config) * (seq + 1) / 2 * h * 3
              * (2 * (dn + dr) + 2 * dv))
    return 6 * n_matmul + causal
