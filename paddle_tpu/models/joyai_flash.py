"""JoyAI-LLM-Flash: latent attention with a compressed query and decoupled,
interleaved rotary positions in every layer, sparse experts after the
leading dense layers, and one multi-token-prediction (MTP) module on the
shared embedding and head.

Shape follows ``jdopensource/JoyAI-LLM-Flash``'s published config (a
DeepSeek-V3 block). Layer ``i`` (0-based) is ``x += MLA(norm(x)); x +=
ffn_i(norm(x))`` with the plain RMSNorm ``norm``:

* MLA: ``kimi_linear.KimiMLA`` with a query rank and a rotary base: ``q =
  RMSNorm(x W_qa) W_qb`` of ``qk_nope + qk_rope`` a head; ``[c | k_r] = x
  W_kva``, ``[k_n | v] = RMSNorm(c) W_kvb``; the last ``qk_rope`` channels
  of every query head and the one ``k_r`` all heads share turn by position
  in pairs ``(2j, 2j + 1)`` (``rope_interleave``); causal attention at ``1 /
  sqrt(qk_nope + qk_rope)`` (``rope_scaling`` null: no YaRN factor).
* ffn: a dense SwiGLU for ``i < first_k_dense_replace``, after that
  ``DroplessMoELayer`` with sigmoid scores, the top-k of the scores plus a
  selection bias (a buffer of zeros; ``noaux_tc`` with one group is the
  plain top-k), the chosen scores renormalised and scaled by
  ``routed_scaling_factor``, and one ungated shared expert; told which
  experts it holds: one expert-parallel rank's part of the model.

A final ``norm`` and an untied head give the main logits. The MTP module
(DeepSeek-V3's form, arXiv:2412.19437 section 2.2) takes ``h_i``, the last
layer's output BEFORE the final norm, and the next token's embedding:
``h'_i = W_eh [norm(Emb(t_{i+1})) ; norm(h_i)]``, one more decoder layer
(MLA and experts), its own final ``norm`` and the SHARED head, predicting
``t_{i+2}``. Training minimises ``L_main + mtp_loss_weight * L_MTP``; no
auxiliary balancing loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..tensor.manipulation import concat, reshape
from ..tensor.tensor import Tensor
from .kimi_linear import KimiDecoderLayer
from .qwen3_next import _linear

__all__ = ["JoyAIFlashConfig", "JoyAIFlashForCausalLM", "joyai_flash_tiny"]


class JoyAIFlashConfig:
    """The published keys under the program's names (``num_experts`` for
    ``n_routed_experts``, ``num_layers`` for ``num_hidden_layers``); the
    defaults are the published model's, whose ``num_nextn_predict_layers``
    is 1: one MTP module. ``mtp_loss_weight`` is DeepSeek-V3's
    first-phase weight (the config gives none); ``experts_held`` (None: all)
    and ``recompute`` are this program's."""

    def __init__(self, vocab_size=129280, hidden_size=2048, num_layers=40,
                 num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=3.2e7, intermediate_size=7168,
                 first_k_dense_replace=1, num_experts=256,
                 num_experts_per_tok=8, moe_intermediate_size=768,
                 num_shared_experts=1, routed_scaling_factor=2.5,
                 mtp_loss_weight=0.3, experts_held=None, rms_eps=1e-6,
                 initializer_range=0.02, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = float(rope_theta)
        self.intermediate_size = intermediate_size
        self.first_k_dense_replace = first_k_dense_replace
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.mtp_loss_weight = float(mtp_loss_weight)
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        self.initializer_range = initializer_range
        self.recompute = recompute

    def layer_kind(self, i):
        return "full_attention"


class JoyAIFlashModel(Layer):
    """Embedding, the decoder layers and the final norm; ``forward`` returns
    the last layer's output before that norm, which the MTP module reads."""

    def __init__(self, c: JoyAIFlashConfig):
        super().__init__()
        from ..nn.utils_ import ParamAttr
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                initializer=Normal(0.0, c.initializer_range)))
        self.layers = LayerList([KimiDecoderLayer(c, i)
                                 for i in range(c.num_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return x


class JoyAIMTP(Layer):
    """``norm(layer(W_eh [norm(embed(next_ids)) ; norm(h)]))``: the decoder
    layer is an expert layer (its index is past the dense ones)."""

    def __init__(self, c: JoyAIFlashConfig):
        super().__init__()
        self.enorm = RMSNorm(c.hidden_size, c.rms_eps)
        self.hnorm = RMSNorm(c.hidden_size, c.rms_eps)
        self.eh_proj = _linear(c, 2 * c.hidden_size, c.hidden_size)
        self.layer = KimiDecoderLayer(c, max(c.num_layers,
                                             c.first_k_dense_replace))
        self.norm = RMSNorm(c.hidden_size, c.rms_eps)

    def forward(self, h, next_ids, embed):
        with jax.named_scope("mtp.embed_proj"):
            u = self.eh_proj(concat([self.enorm(embed(next_ids)),
                                     self.hnorm(h)], axis=-1))
        return self.norm(self.layer(u))


class JoyAIFlashForCausalLM(Layer):
    """``forward(input_ids)`` in eval -> the main head's logits [B, T, V]
    followed along the tokens by the MTP head's [B, T - 1, V] (position
    ``i`` of the second part reads ``input_ids[i + 1]`` and predicts the
    token after it); with ``labels`` -> ``L_main + mtp_loss_weight * L_MTP``
    (``losses`` gives both). ``labels[i]`` is the MTP input at ``i`` and
    ``labels[i + 1]`` its target, for ``i <= T - 2``."""

    def __init__(self, c: JoyAIFlashConfig):
        super().__init__()
        self.config = c
        self.model = JoyAIFlashModel(c)
        self.lm_head = _linear(c, c.hidden_size, c.vocab_size)
        self.mtp = JoyAIMTP(c)

    def forward(self, input_ids, labels=None):
        if labels is None:
            main, mtp = self._heads(input_ids, None)
            return concat([main, mtp[:, :-1]], axis=1)
        return self.losses(input_ids, labels)[0]

    def losses(self, input_ids, labels):
        """(``L_main + mtp_loss_weight * L_MTP``, ``L_main``): the loss to
        differentiate, and the next-token cross-entropy to report."""
        c = self.config
        main, mtp = self._heads(input_ids, labels)
        loss = F.cross_entropy(reshape(main, [-1, c.vocab_size]),
                               reshape(labels, [-1]))
        with jax.named_scope("mtp"), jax.named_scope("mtp.head_loss"):
            lab = getattr(labels, "_data", labels)
            after = jnp.concatenate([lab[:, 1:], jnp.full_like(
                lab[:, :1], -100)], 1)          # ignored: nothing follows
            mtp_loss = F.cross_entropy(reshape(mtp, [-1, c.vocab_size]),
                                       Tensor(after.reshape(-1)),
                                       ignore_index=-100)
        return loss + c.mtp_loss_weight * mtp_loss, loss

    def _heads(self, input_ids, labels):
        """Logits [B, T, V] of the main head and of the MTP head (its last
        row reads no real next token when ``labels`` is None)."""
        h = self.model(input_ids)
        main = self.lm_head(self.model.norm(h))
        if labels is None:
            ids = input_ids._data
            nxt = Tensor(jnp.concatenate([ids[:, 1:], ids[:, -1:]], 1))
        else:
            nxt = labels
        g = self.mtp(h, nxt, self.model.embed_tokens)
        with jax.named_scope("mtp"), jax.named_scope("mtp.head_loss"):
            return main, self.lm_head(g)


def joyai_flash_tiny(vocab_size=256, **kw):
    """The dense leading layer and two expert layers, then the MTP module,
    at hidden 64 with 8 experts, top-2: the CPU tests' size."""
    kw = {**dict(hidden_size=64, num_layers=3, num_heads=4, q_lora_rank=48,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, rope_theta=1e4, intermediate_size=96,
                 num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32), **kw}
    return JoyAIFlashForCausalLM(JoyAIFlashConfig(vocab_size=vocab_size,
                                                  **kw))
