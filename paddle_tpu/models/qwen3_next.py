"""Qwen3-Next family: a hybrid of Gated DeltaNet (linear attention) and gated
softmax attention, every layer followed by a sparse expert layer.

Shape follows ``Qwen/Qwen3-Next-80B-A3B-Instruct``'s published block. Layer
``i`` (0-based) is ``x += mixer_i(norm(x)); x += moe(norm(x))``: the mixer
is ``Qwen3NextAttention`` where ``(i + 1) % full_attention_interval == 0``
and ``Qwen3NextGatedDeltaNet`` otherwise, so the mixer's kind is a property
of the layer's index; ``norm`` is the zero-centred RMSNorm ``x * rsqrt(
mean(x^2) + eps) * (1 + w)``; a final ``norm`` and an untied head follow.

* Gated DeltaNet: fused projections ``[q | k | v | z]`` and ``[b | a]``, a
  causal depthwise convolution + SiLU over ``[q | k | v]``, the gated delta
  rule in chunks (``F.chunk_gated_delta_rule``, which normalises ``q`` and
  ``k`` per head), a per-head RMSNorm gated by ``SiLU(z)``, ``out_proj``.
* Gated attention: ``q_proj`` gives query and gate per head, ``q`` and
  ``k`` go through a zero-centred RMSNorm over the head, rotary embedding
  (rotate-half) on the first ``partial_rotary_factor`` of the head,
  ``F.scaled_dot_product_attention`` (the flash kernel on the chip; the KV
  heads are NOT repeated, the kernel reads each one for its group of query
  heads), a sigmoid gate on the output, ``o_proj``.
* Expert layer: ``DroplessMoELayer`` (softmax over all experts, top-k
  renormalised, a shared expert behind a sigmoid gate), told which experts
  it holds: one expert-parallel rank's part of the model.

No auxiliary balancing loss and no multi-token-prediction head: the
published config has neither a coefficient nor an MTP key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.rng import next_key
from ..incubate.distributed.models.moe.dropless import DroplessMoELayer
from ..nn import functional as F
from ..nn.functional.linear_attention import TILE_ROWS, causal_conv
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..tensor.manipulation import reshape
from ..tensor.tensor import Parameter, apply_op

__all__ = ["Qwen3NextConfig", "Qwen3NextModel", "Qwen3NextForCausalLM",
           "qwen3_next_tiny"]

_F32 = jnp.float32


class Qwen3NextConfig:
    """The published keys under the published names' meaning; the defaults
    are the 80B-A3B model's. ``experts_held`` (None: all) and ``recompute``
    are this program's."""

    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=16, num_kv_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=1e7,
                 full_attention_interval=4, linear_num_key_heads=16,
                 linear_num_value_heads=32, linear_key_head_dim=128,
                 linear_value_head_dim=128, linear_conv_kernel_dim=4,
                 num_experts=512, num_experts_per_tok=10,
                 moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, experts_held=None,
                 rms_eps=1e-6, initializer_range=0.02,
                 chunk_size=64, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.rope_theta = float(rope_theta)
        self.full_attention_interval = full_attention_interval
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        self.initializer_range = initializer_range
        self.chunk_size = chunk_size
        self.recompute = recompute

    def layer_kind(self, i):
        return ("full_attention" if (i + 1) % self.full_attention_interval == 0
                else "linear_attention")


def _linear(c, n_in, n_out):
    from ..nn.utils_ import ParamAttr
    return Linear(n_in, n_out, bias_attr=False, weight_attr=ParamAttr(
        initializer=Normal(0.0, c.initializer_range)))


class ZeroCentredRMSNorm(Layer):
    """``x * rsqrt(mean(x^2) + eps) * (1 + weight)``, ``weight`` from 0, over
    the last axis, in float32; the result in ``x``'s dtype."""

    def __init__(self, size, epsilon=1e-6):
        super().__init__()
        self.epsilon = epsilon
        self.weight = Parameter(jnp.zeros((size,), _F32))

    def forward(self, x):
        eps = self.epsilon

        def f(a, w):
            h = a.astype(_F32)
            h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
            return (h * (1.0 + w.astype(_F32))).astype(a.dtype)
        return apply_op(f, x, self.weight)


class Qwen3NextGatedDeltaNet(Layer):
    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        self.hk, self.hv = c.linear_num_key_heads, c.linear_num_value_heads
        self.dk, self.dv = c.linear_key_head_dim, c.linear_value_head_dim
        self.chunk_size, self.eps = c.chunk_size, c.rms_eps
        key, value = self.hk * self.dk, self.hv * self.dv
        # columns: q (hk x dk), k (hk x dk), v (hv x dv), z (hv x dv)
        self.in_proj_qkvz = _linear(c, c.hidden_size, 2 * key + 2 * value)
        # columns: b (hv), a (hv)
        self.in_proj_ba = _linear(c, c.hidden_size, 2 * self.hv)
        # torch's Conv1d default: U(-1/sqrt(K), 1/sqrt(K)), one filter a channel
        bound = c.linear_conv_kernel_dim ** -0.5
        self.conv_weight = Parameter(jax.random.uniform(
            next_key(), (2 * key + value, c.linear_conv_kernel_dim), _F32,
            -bound, bound))
        self.A_log = Parameter(jnp.log(jax.random.uniform(
            next_key(), (self.hv,), _F32, 1e-4, 16.0)))
        self.dt_bias = Parameter(jnp.ones((self.hv,), _F32))
        self.norm_weight = Parameter(jnp.ones((self.dv,), _F32))
        self.out_proj = _linear(c, value, c.hidden_size)

    def forward(self, x):
        """Matrix products take ``x``'s dtype (bf16 in a bf16 model) and
        accumulate in float32; what lies between them stays in float32 (the
        projections' results, the convolution, the rule's result): each is
        read once by a float32 computation (a norm, a sigmoid, a running
        sum), and a bf16 round trip there costs a third of the mixer's
        distance from the float32 reference (PERF.md section 6, PR 28).

        One layout from the projections to ``out_proj``: every large array
        is ``[B, T, heads * 128]``, the sequence in the sublanes and a
        head's features in the lanes (``linear_attention``'s docstring).
        So each consumer's projection is taken from its own columns of the
        ONE fused weight (a slice of the 48-MiB weight, not of the 768-MiB
        product, whose backward would pad and add it back), the convolution
        runs on ``q``, ``k`` and ``v`` apart, and the ``[B, T, h, d]``
        operands the rule asks for are reshapes that nothing reads with
        the heads in the sublanes: the rule moves whole tiles, and the
        gated norm reduces each head's features through the same view."""
        b, s = x.shape[0], x.shape[1]
        key, value = self.hk * self.dk, self.hv * self.dv
        hv, dv, eps, mm = self.hv, self.dv, self.eps, x.dtype

        hk, dk = self.hk, self.dk

        def project(a, w):
            return jnp.matmul(a, w, preferred_element_type=_F32)

        def front(a, w, cw):
            """q, k, v after their convolution, and z: ONE op, so that the
            step's trace holds one node for them and not fourteen."""
            def conv(lo, hi, heads, d):
                with jax.named_scope("gdn.conv"):
                    y = causal_conv(project(a, w[:, lo:hi]), cw[lo:hi], True)
                return y.reshape(b, s, heads, d)
            return (conv(0, key, hk, dk), conv(key, 2 * key, hk, dk),
                    conv(2 * key, 2 * key + value, hv, dv),
                    project(a, w[:, 2 * key + value:]))
        q, k, v, z = apply_op(front, x, self.in_proj_qkvz.weight,
                              self.conv_weight, n_outputs=4)

        def gates(a, w, a_log, dt_bias):
            ba = project(a, w)
            g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(_F32))
            return g, jax.nn.sigmoid(ba[..., :hv])
        g, beta = apply_op(gates, x, self.in_proj_ba.weight, self.A_log,
                           self.dt_bias, n_outputs=2)
        o = F.chunk_gated_delta_rule(q, k, v, g, beta,
                                     chunk_size=self.chunk_size,
                                     matmul_dtype=mm)

        def gated_norm(o_, z, w):
            rows = TILE_ROWS if s % TILE_ROWS == 0 else 1

            def tiles(a):       # [B, T, hv (x) dv] -> [B, T/8, hv, 8, dv]
                return a.reshape(b, s // rows, rows, hv, dv).transpose(
                    0, 1, 3, 2, 4)
            o_, z = tiles(o_), tiles(z)
            h = o_ * jax.lax.rsqrt(jnp.mean(o_ * o_, -1, keepdims=True) + eps)
            y = (h * w.astype(_F32) * jax.nn.silu(z)).astype(mm)
            return y.transpose(0, 1, 3, 2, 4).reshape(b, s, hv * dv)
        y = apply_op(gated_norm, o, z, self.norm_weight)
        return self.out_proj(y)


def _rope(x, theta, rotary_dim, pos=None):
    """Rotate-half rotary embedding on the first ``rotary_dim`` of the head
    dimension of ``x`` [B, S, H, D], angles in float32; at the positions
    ``pos`` [S] (default: 0 .. S - 1)."""
    pos = (jnp.arange(x.shape[1], dtype=_F32) if pos is None
           else pos.astype(_F32))
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=_F32) / rotary_dim)
    ang = pos[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    r = x[..., :rotary_dim].astype(_F32)
    half = rotary_dim // 2
    rot = jnp.concatenate([-r[..., half:], r[..., :half]], -1)
    return jnp.concatenate([(r * cos + rot * sin).astype(x.dtype),
                            x[..., rotary_dim:]], -1)


class Qwen3NextAttention(Layer):
    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        self.h, self.g, self.d = c.num_heads, c.num_kv_heads, c.head_dim
        self.theta, self.rotary_dim = c.rope_theta, c.rotary_dim
        # columns: per head, the query (d) then its gate (d)
        self.q_proj = _linear(c, c.hidden_size, 2 * self.h * self.d)
        self.k_proj = _linear(c, c.hidden_size, self.g * self.d)
        self.v_proj = _linear(c, c.hidden_size, self.g * self.d)
        self.q_norm = ZeroCentredRMSNorm(self.d, c.rms_eps)
        self.k_norm = ZeroCentredRMSNorm(self.d, c.rms_eps)
        self.o_proj = _linear(c, self.h * self.d, c.hidden_size)

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        h, g, d = self.h, self.g, self.d
        theta, rotary_dim = self.theta, self.rotary_dim
        with jax.named_scope("attn.gated"):
            qg = reshape(self.q_proj(x), [b, s, h, 2 * d])
            q = self.q_norm(qg[:, :, :, :d])
            k = self.k_norm(reshape(self.k_proj(x), [b, s, g, d]))
            v = reshape(self.v_proj(x), [b, s, g, d])
            q = apply_op(lambda a: _rope(a, theta, rotary_dim), q)
            k = apply_op(lambda a: _rope(a, theta, rotary_dim), k)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            out = apply_op(
                lambda a, gate: (a * jax.nn.sigmoid(gate.astype(_F32))
                                 ).astype(a.dtype).reshape(b, s, h * d),
                out, qg[:, :, :, d:])
            return self.o_proj(out)


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, c: Qwen3NextConfig, index):
        super().__init__()
        self.kind = c.layer_kind(index)
        self.input_layernorm = ZeroCentredRMSNorm(c.hidden_size, c.rms_eps)
        self.mixer = (Qwen3NextAttention(c) if self.kind == "full_attention"
                      else Qwen3NextGatedDeltaNet(c))
        self.post_attention_layernorm = ZeroCentredRMSNorm(c.hidden_size,
                                                           c.rms_eps)
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, experts_held=c.experts_held,
            shared_hidden=c.shared_expert_intermediate_size,
            initializer_range=c.initializer_range)
        self._recompute = c.recompute

    def _body(self, x):
        x = x + self.mixer(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.utils.recompute_mod import recompute
            return recompute(self._body, x)
        return self._body(x)


class Qwen3NextModel(Layer):
    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        from ..nn.utils_ import ParamAttr
        self.config = c
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                initializer=Normal(0.0, c.initializer_range)))
        self.layers = LayerList([Qwen3NextDecoderLayer(c, i)
                                 for i in range(c.num_layers)])
        self.norm = ZeroCentredRMSNorm(c.hidden_size, c.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class Qwen3NextForCausalLM(Layer):
    """``forward(input_ids)`` -> logits [B, S, V]; with ``labels`` the mean
    next-token cross-entropy (the head is not tied to the embedding)."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        self.config = c
        self.model = Qwen3NextModel(c)
        self.lm_head = _linear(c, c.hidden_size, c.vocab_size)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is None:
            return logits
        return F.cross_entropy(reshape(logits, [-1, self.config.vocab_size]),
                               reshape(labels, [-1]))


def qwen3_next_tiny(vocab_size=256, **kw):
    """One period (three Gated DeltaNet layers, one gated attention layer)
    at hidden 64 with 8 experts, top-2: the CPU tests' size."""
    kw = {**dict(hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2,
                 head_dim=32, linear_num_key_heads=2,
                 linear_num_value_heads=4, linear_key_head_dim=16,
                 linear_value_head_dim=16, num_experts=8,
                 num_experts_per_tok=2, moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, chunk_size=16), **kw}
    return Qwen3NextForCausalLM(Qwen3NextConfig(vocab_size=vocab_size, **kw))
