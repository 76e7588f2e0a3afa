"""Kimi Linear family: a hybrid of Kimi Delta Attention (the delta rule with
a decay per key channel) and latent attention without positions, every
layer after the leading dense ones followed by a sparse expert layer.

Shape follows ``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s published block.
Layer ``i`` (0-based) is ``x += mixer_i(norm(x)); x += ffn_i(norm(x))``: the
mixer is ``KimiMLA`` where ``i + 1`` is in ``full_attn_layers`` (the config's
1-based list) and ``KimiDeltaAttention`` otherwise; the ffn is a dense
SwiGLU for ``i < first_k_dense_replace`` and the expert layer after; ``norm``
is the plain RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; a final ``norm``
and an untied head follow.

* Kimi Delta Attention: ``q, k, v = SiLU(conv4(x W))`` each (a causal
  depthwise convolution of 4 taps a channel), the decay per head and key
  channel ``g = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``, the write
  strength ``beta = sigmoid(x W_b)``, the rule ``S_t = (I - beta k k^T)
  Diag(exp(g_t)) S_{t-1} + beta k v^T``, ``o_t = S_t^T q_t`` in chunks
  (``F.chunk_gated_delta_rule`` with ``g`` [B, T, h, d_k], which normalises
  ``q`` and ``k`` per head), then ``RMSNorm_dv(o) * w * sigmoid(x W_ga
  W_gb)`` and ``W_o``.
* Latent attention with no positional encoding (``mla_use_nope``): ``q = x
  W_q`` (``q_lora_rank`` null) of ``qk_nope + qk_rope`` a head; ``[c | k_r]
  = x W_kva``, ``[k_n | v] = RMSNorm(c) W_kvb``; ``k = [k_n | k_r]`` with the
  one ``k_r`` shared by every head; no rotary anywhere;
  ``F.scaled_dot_product_attention`` (the flash kernels on the chip, the
  value head 128 beside the query-key head 192), ``W_o``.
* Expert layer: ``DroplessMoELayer`` with sigmoid scores, the top-k of the
  scores plus a selection bias (a buffer of zeros), the chosen scores
  renormalised and scaled by ``routed_scaling_factor``, and a shared expert
  with no gate; told which experts it holds: one expert-parallel rank's part
  of the model.

No auxiliary balancing loss and no multi-token-prediction head: the
published config has ``num_nextn_predict_layers`` 0 and no coefficient.
One expert group (``num_expert_group`` 1, ``topk_group`` 1): the grouped
choice is the plain top-k.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.rng import next_key
from ..incubate.distributed.models.moe.dropless import DroplessMoELayer
from ..nn import functional as F
from ..nn.functional.linear_attention import TILE_ROWS, causal_conv
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..tensor.manipulation import reshape
from ..tensor.tensor import Parameter, apply_op
from .qwen3_next import _linear

__all__ = ["KimiLinearConfig", "KimiLinearModel", "KimiLinearForCausalLM",
           "kimi_linear_tiny"]

_F32 = jnp.float32


class KimiLinearConfig:
    """The published keys under the published names' meaning (the
    ``linear_attn_config`` group's as ``kda_*``); the defaults are the
    48B-A3B model's (``q_lora_rank`` None and ``rope_theta`` None: no query
    compression and no rotary, ``mla_use_nope``; ``KimiMLA`` says what a
    number means). ``experts_held`` (None: all), ``chunk_size`` and
    ``recompute`` are this program's."""

    def __init__(self, vocab_size=163840, hidden_size=2304, num_layers=27,
                 num_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=None,
                 rope_theta=None,
                 full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
                 kda_num_heads=32, kda_head_dim=128,
                 short_conv_kernel_size=4, intermediate_size=9216,
                 first_k_dense_replace=1, num_experts=256,
                 num_experts_per_tok=8, moe_intermediate_size=1024,
                 num_shared_experts=1, routed_scaling_factor=2.446,
                 experts_held=None, rms_eps=1e-5, initializer_range=0.02,
                 chunk_size=64, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.q_lora_rank = q_lora_rank
        self.rope_theta = rope_theta
        self.full_attn_layers = tuple(int(i) for i in full_attn_layers)
        self.kda_num_heads = kda_num_heads
        self.kda_head_dim = kda_head_dim
        self.short_conv_kernel_size = short_conv_kernel_size
        self.intermediate_size = intermediate_size
        self.first_k_dense_replace = first_k_dense_replace
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        self.initializer_range = initializer_range
        self.chunk_size = chunk_size
        self.recompute = recompute

    def layer_kind(self, i):
        return ("full_attention" if i + 1 in self.full_attn_layers
                else "linear_attention")


def _project(a, w):
    return jnp.matmul(a, w, preferred_element_type=_F32)


class KimiDeltaAttention(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.h, self.d = c.kda_num_heads, c.kda_head_dim
        self.chunk_size, self.eps = c.chunk_size, c.rms_eps
        e, p = c.hidden_size, self.h * self.d
        self.q_proj = _linear(c, e, p)
        self.k_proj = _linear(c, e, p)
        self.v_proj = _linear(c, e, p)
        # torch's Conv1d default for 4 taps, one filter a channel:
        # U(-1/sqrt(K), 1/sqrt(K)); rows q, k, v
        bound = c.short_conv_kernel_size ** -0.5
        self.conv_weight = Parameter(jax.random.uniform(
            next_key(), (3 * p, c.short_conv_kernel_size), _F32, -bound,
            bound))
        self.f_a_proj = _linear(c, e, self.d)
        self.f_b_proj = _linear(c, self.d, p)
        self.b_proj = _linear(c, e, self.h)
        self.g_a_proj = _linear(c, e, self.d)
        self.g_b_proj = _linear(c, self.d, p)
        self.A_log = Parameter(jnp.log(jax.random.uniform(
            next_key(), (self.h,), _F32, 1.0, 16.0)))
        # softplus^-1 of a step drawn log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(next_key(), (p,), _F32,
                                        math.log(1e-3), math.log(1e-1)))
        self.dt_bias = Parameter(dt + jnp.log(-jnp.expm1(-dt)))
        self.o_norm_weight = Parameter(jnp.ones((self.d,), _F32))
        self.o_proj = _linear(c, p, e)

    def forward(self, x):
        """Products in ``x``'s dtype with float32 accumulation; what lies
        between them (the projections' results, the convolution, the
        decay, the rule's result) stays in float32, as in the Gated DeltaNet
        layer (``qwen3_next``). Every large array is ``[B, T, heads *
        128]`` from the projections to ``W_o`` (``linear_attention``'s
        docstring, "Layout"); the ``[B, T, h, d]`` operands of the rule are
        reshapes of it."""
        b, s = x.shape[0], x.shape[1]
        h, d, eps, mm = self.h, self.d, self.eps, x.dtype
        p = h * d

        def front(a, wq, wk, wv, cw):
            """q, k, v after their convolution: ONE op."""
            def conv(w, lo):
                with jax.named_scope("kda.conv"):
                    y = causal_conv(_project(a, w), cw[lo:lo + p], True)
                return y.reshape(b, s, h, d)
            return conv(wq, 0), conv(wk, p), conv(wv, 2 * p)
        q, k, v = apply_op(front, x, self.q_proj.weight, self.k_proj.weight,
                           self.v_proj.weight, self.conv_weight, n_outputs=3)

        def gates(a, wfa, wfb, wb, a_log, dt_bias):
            f = _project(_project(a, wfa).astype(mm), wfb) + dt_bias.astype(
                _F32)
            g = -jnp.exp(a_log.astype(_F32))[:, None] * jax.nn.softplus(
                f.reshape(b, s, h, d))
            return g, jax.nn.sigmoid(_project(a, wb))
        with jax.named_scope("kda.gate"):
            g, beta = apply_op(gates, x, self.f_a_proj.weight,
                               self.f_b_proj.weight, self.b_proj.weight,
                               self.A_log, self.dt_bias, n_outputs=2)
        o = F.chunk_gated_delta_rule(q, k, v, g, beta,
                                     chunk_size=self.chunk_size,
                                     matmul_dtype=mm)

        def gated_norm(o_, a, wga, wgb, w):
            z = _project(_project(a, wga).astype(mm), wgb)
            rows = TILE_ROWS if s % TILE_ROWS == 0 else 1

            def tiles(u):       # [B, T, h (x) d] -> [B, T/8, h, 8, d]
                return u.reshape(b, s // rows, rows, h, d).transpose(
                    0, 1, 3, 2, 4)
            o_, z = tiles(o_.astype(_F32)), tiles(z)
            y = o_ * jax.lax.rsqrt(jnp.mean(o_ * o_, -1, keepdims=True) + eps)
            y = (y * w.astype(_F32) * jax.nn.sigmoid(z)).astype(mm)
            return y.transpose(0, 1, 3, 2, 4).reshape(b, s, p)
        with jax.named_scope("kda.out_gate"):
            y = apply_op(gated_norm, o, x, self.g_a_proj.weight,
                         self.g_b_proj.weight, self.o_norm_weight)
        return self.o_proj(y)


def _rope_interleaved(a, theta):
    """The rotation of the last axis (``d`` channels) of ``a`` [B, T, ...,
    d] by position, pairs ``(2j, 2j + 1)`` turned by ``pos * theta^(-2j /
    d)`` in place; ``pos`` is the index along T. The angles, their cosines
    and sines and the products in float32; the result in ``a``'s dtype. Each
    channel meets its partner through shifts of the last axis by one (no
    axis of 2)."""
    d, s = a.shape[-1], a.shape[1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2) / d), _F32)
    ang = jnp.arange(s, dtype=_F32)[:, None] * jnp.repeat(inv, 2)
    ang = ang.reshape((s,) + (1,) * (a.ndim - 3) + (d,))
    even = jnp.arange(d) % 2 == 0
    x = a.astype(_F32)
    partner = jnp.where(even, jnp.roll(x, -1, -1), jnp.roll(x, 1, -1))
    sin = jnp.where(even, -jnp.sin(ang), jnp.sin(ang))
    return (x * jnp.cos(ang) + partner * sin).astype(a.dtype)


class KimiMLA(Layer):
    """Latent attention, with or without query compression and positions.
    ``q_lora_rank`` None: ``q = x W_q``; a rank: ``q = RMSNorm(x W_qa)
    W_qb``. ``rope_theta`` None: no positions (Kimi's ``mla_use_nope``); a
    base: the last ``qk_rope_head_dim`` channels of every query head and the
    one ``k_r`` the heads share turn by ``_rope_interleaved`` (DeepSeek-V3's
    decoupled rotary, ``rope_interleave``), the softmax scale staying
    ``1 / sqrt(qk_nope + qk_rope)``."""

    def __init__(self, c):
        super().__init__()
        self.h, self.r = c.num_heads, c.kv_lora_rank
        self.dn, self.dr, self.dv = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                                     c.v_head_dim)
        self.q_rank, self.theta = c.q_lora_rank, c.rope_theta
        if self.q_rank is None:
            self.q_proj = _linear(c, c.hidden_size,
                                  self.h * (self.dn + self.dr))
        else:
            self.q_a_proj = _linear(c, c.hidden_size, self.q_rank)
            self.q_a_layernorm = RMSNorm(self.q_rank, c.rms_eps)
            self.q_b_proj = _linear(c, self.q_rank,
                                    self.h * (self.dn + self.dr))
        self.kv_a_proj_with_mqa = _linear(c, c.hidden_size, self.r + self.dr)
        self.kv_a_layernorm = RMSNorm(self.r, c.rms_eps)
        self.kv_b_proj = _linear(c, self.r, self.h * (self.dn + self.dv))
        self.o_proj = _linear(c, self.h * self.dv, c.hidden_size)

    def _query(self, x):
        if self.q_rank is None:
            return self.q_proj(x)
        with jax.named_scope("mla.q_compress"):
            return self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        h, r, dn, dr, dv = self.h, self.r, self.dn, self.dr, self.dv
        theta = self.theta
        with jax.named_scope("attn.mla"):
            q = reshape(self._query(x), [b, s, h, dn + dr])
            if theta is not None:
                with jax.named_scope("mla.rope"):
                    q = apply_op(lambda a: jnp.concatenate(
                        [a[..., :dn], _rope_interleaved(a[..., dn:],
                                                        theta)], -1), q)
            with jax.named_scope("mla.kv_compress"):
                ckr = self.kv_a_proj_with_mqa(x)
                kv = reshape(self.kv_b_proj(self.kv_a_layernorm(
                    ckr[:, :, :r])), [b, s, h, dn + dv])
                k_r = ckr[:, :, r:]
                if theta is not None:
                    with jax.named_scope("mla.rope"):
                        k_r = apply_op(lambda a: _rope_interleaved(
                            a, theta), k_r)
                k = apply_op(lambda a, kr: jnp.concatenate(
                    [a[..., :dn], jnp.broadcast_to(
                        kr[:, :, None, :], (b, s, h, dr))], -1),
                    kv, k_r)
            out = F.scaled_dot_product_attention(q, k, kv[:, :, :, dn:],
                                                 is_causal=True)
            return self.o_proj(reshape(out, [b, s, h * dv]))


class KimiMLP(Layer):
    """The dense SwiGLU of the leading layers, ``(SiLU(x W_g) * x W_u)
    W_d``."""

    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.gate_proj = _linear(c, c.hidden_size, c.intermediate_size)
        self.up_proj = _linear(c, c.hidden_size, c.intermediate_size)
        self.down_proj = _linear(c, c.intermediate_size, c.hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class KimiDecoderLayer(Layer):
    def __init__(self, c: KimiLinearConfig, index):
        super().__init__()
        self.kind = c.layer_kind(index)
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_eps)
        self.mixer = (KimiMLA(c) if self.kind == "full_attention"
                      else KimiDeltaAttention(c))
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_eps)
        if index < c.first_k_dense_replace:
            self.mlp = KimiMLP(c)
        else:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, experts_held=c.experts_held,
                shared_hidden=(c.num_shared_experts
                               * c.moe_intermediate_size),
                initializer_range=c.initializer_range, score="sigmoid",
                routed_scale=c.routed_scaling_factor, shared_gated=False)
        self._recompute = c.recompute

    def _body(self, x):
        x = x + self.mixer(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.utils.recompute_mod import recompute
            return recompute(self._body, x)
        return self._body(x)


class KimiLinearModel(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        from ..nn.utils_ import ParamAttr
        self.config = c
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
        return self.norm(x)


class KimiLinearForCausalLM(Layer):
    """``forward(input_ids)`` -> logits [B, S, V]; with ``labels`` the mean
    next-token cross-entropy (the head is not tied to the embedding)."""

    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.config = c
        self.model = KimiLinearModel(c)
        self.lm_head = _linear(c, c.hidden_size, c.vocab_size)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is None:
            return logits
        return F.cross_entropy(reshape(logits, [-1, self.config.vocab_size]),
                               reshape(labels, [-1]))


def kimi_linear_tiny(vocab_size=256, **kw):
    """The dense leading layer, two KDA layers, one latent-attention layer
    and one more KDA layer (the cell's pattern) at hidden 64 with 8 experts,
    top-2: the CPU tests' size."""
    kw = {**dict(hidden_size=64, num_layers=5, num_heads=4, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 kda_num_heads=4, kda_head_dim=16, intermediate_size=96,
                 num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32, chunk_size=16), **kw}
    return KimiLinearForCausalLM(KimiLinearConfig(vocab_size=vocab_size,
                                                  **kw))
