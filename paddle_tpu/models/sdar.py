"""SDAR family: a mixture-of-experts GQA decoder trained by diffusion over
blocks.

Shape follows ``JetLM/SDAR-30B-A3B-Chat`` (``model_type: sdar_moe``):
identical layers ``h += Attn(RMSNorm(h)); h += MoE(RMSNorm(h))``, a final
RMSNorm and an untied head. RMSNorm is the plain ``x * rsqrt(mean(x^2) +
eps) * w``.

* Attention: ``q = RoPE(RMSNorm(W_q x))``, ``k = RoPE(RMSNorm(W_k x))``
  (the norms over each head, rotate-half RoPE on the whole head at explicit
  position ids), ``v = W_v x``, ``F.scaled_dot_product_attention`` under the
  block-diffusion mask (the flash kernels on the chip; the KV heads are NOT
  repeated), ``W_o``; no biases.
* Expert layer: ``DroplessMoELayer`` (softmax over all experts, top-k
  renormalised, no shared expert), told which experts it holds: one
  expert-parallel rank's part of the model.

The objective (block diffusion; BD3-LM's and LLaDA's masked-diffusion
bound). A sequence ``x0`` of ``L`` tokens is cut into blocks of
``block_length``. Block ``k`` draws a noise level ``t_k = eps + (1 - eps)
u``, ``u ~ U[0, 1)``; each of its tokens is replaced by ``mask_token_id``
with probability ``t_k``. The model runs ONCE over ``z = [x_t ; x0]``, ``2
L`` positions with position ids ``[0 .. L-1 ; 0 .. L-1]``, under
``ops.pallas.flash_attention.block_diffusion_mask(L, block_length)``: a
noisy block sees itself and the clean copy of every earlier block. The head
runs over the noisy half only, and::

    loss = (1 / L) sum_{i < L} m_i (1 / t_block(i)) CE(logits_i, x0_i)

with no shift: position ``i`` restores token ``i``. Generation (a block is
denoised over several steps against a cache of final blocks) is a serving
path and is not here.
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp

from ..core.rng import next_key
from ..incubate.distributed.models.moe.dropless import (DroplessMoELayer,
                                                        add_counts,
                                                        read_counts)
from ..inference import telemetry as _telemetry
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..ops.pallas.flash_attention import block_diffusion_mask
from ..tensor.manipulation import reshape
from ..tensor.tensor import (Tensor, apply_op, register_persistent,
                             unregister_persistent)
from .qwen3_next import _linear, _rope

__all__ = ["SDARConfig", "SDARModel", "SDARForBlockDiffusion", "sdar_tiny",
           "noise_stats"]

_F32 = jnp.float32

# a weak reference to every SDARForBlockDiffusion alive
_models: list = []


class SDARConfig:
    """The published keys under the published names' meaning; the defaults
    are the 30B-A3B model's. ``experts_held`` (None: all), ``recompute``,
    ``block_length``, ``noise_eps`` and ``mask_token_id`` (None: the last
    row of the vocabulary) are this program's: the published config gives
    neither a block length nor a noise schedule."""

    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e6,
                 num_experts=128, num_experts_per_tok=8,
                 moe_intermediate_size=768, experts_held=None, rms_eps=1e-6,
                 initializer_range=0.02, recompute=False, block_length=4,
                 noise_eps=0.05, mask_token_id=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.rope_theta = float(rope_theta)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.experts_held = experts_held
        self.rms_eps = rms_eps
        self.initializer_range = initializer_range
        self.recompute = recompute
        self.block_length = int(block_length)
        self.noise_eps = float(noise_eps)
        self.mask_token_id = (vocab_size - 1 if mask_token_id is None
                              else int(mask_token_id))


class SDARAttention(Layer):
    def __init__(self, c: SDARConfig):
        super().__init__()
        self.h, self.g, self.d = c.num_heads, c.num_kv_heads, c.head_dim
        self.theta, self.block_length = c.rope_theta, c.block_length
        self.q_proj = _linear(c, c.hidden_size, self.h * self.d)
        self.k_proj = _linear(c, c.hidden_size, self.g * self.d)
        self.v_proj = _linear(c, c.hidden_size, self.g * self.d)
        self.q_norm = RMSNorm(self.d, c.rms_eps)
        self.k_norm = RMSNorm(self.d, c.rms_eps)
        self.o_proj = _linear(c, self.h * self.d, c.hidden_size)

    def forward(self, x, position_ids):
        """``x`` [B, 2 L, E] over ``[noisy ; clean]``."""
        b, s = x.shape[0], x.shape[1]
        h, g, d, theta = self.h, self.g, self.d, self.theta
        pos = position_ids._data
        with jax.named_scope("attn.block_diffusion"):
            q = self.q_norm(reshape(self.q_proj(x), [b, s, h, d]))
            k = self.k_norm(reshape(self.k_proj(x), [b, s, g, d]))
            v = reshape(self.v_proj(x), [b, s, g, d])
            q = apply_op(lambda a: _rope(a, theta, d, pos), q)
            k = apply_op(lambda a: _rope(a, theta, d, pos), k)
            out = F.scaled_dot_product_attention(
                q, k, v, structured_mask=block_diffusion_mask(
                    s // 2, self.block_length))
            return self.o_proj(reshape(out, [b, s, h * d]))


class SDARDecoderLayer(Layer):
    def __init__(self, c: SDARConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_eps)
        self.self_attn = SDARAttention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_eps)
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, experts_held=c.experts_held,
            initializer_range=c.initializer_range)
        self._recompute = c.recompute

    def _body(self, x, position_ids):
        x = x + self.self_attn(self.input_layernorm(x), position_ids)
        return x + self.mlp(self.post_attention_layernorm(x))

    def forward(self, x, position_ids):
        if self._recompute and self.training:
            from ..distributed.fleet.utils.recompute_mod import recompute
            return recompute(self._body, x, position_ids)
        return self._body(x, position_ids)


class SDARModel(Layer):
    """``forward(input_ids, position_ids)``: ids [B, 2 L] over ``[noisy ;
    clean]`` and their positions [2 L] -> hidden states [B, 2 L, E] after
    the final norm."""

    def __init__(self, c: SDARConfig):
        super().__init__()
        from ..nn.utils_ import ParamAttr
        self.config = c
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                initializer=Normal(0.0, c.initializer_range)))
        self.layers = LayerList([SDARDecoderLayer(c)
                                 for _ in range(c.num_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_eps)

    def forward(self, input_ids, position_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, position_ids)
        return self.norm(x)


class SDARForBlockDiffusion(Layer):
    """``forward(tokens, masked=None, t=None, labels=None)``: ``tokens`` [B,
    L] clean ids. ``masked`` [B, L] (bool: replaced by the mask id) and ``t``
    [B, ceil(L / block_length)] (each block's noise level) are drawn from
    ``next_key()`` when absent, so a compiled step draws fresh noise every
    call; given, they are used, so that a program and a reference see one
    draw. Returns the noisy half's logits [B, L, V], or with ``labels`` (the
    clean ids to restore) the weighted loss of the module docstring.
    ``losses`` returns that loss and, beside it, the plain mean
    cross-entropy of the masked tokens: the figure to log, because the
    ``1 / t`` weights make the bound of ONE sequence a noisy estimate (at
    initialisation it spreads ``sqrt(E[(1 - t) / t] / L)`` around its mean:
    1.6% for 8,192 tokens at ``noise_eps`` 0.05) while the masked mean
    does not move with the draw."""

    def __init__(self, c: SDARConfig):
        super().__init__()
        self.config = c
        self.model = SDARModel(c)
        self.lm_head = _linear(c, c.hidden_size, c.vocab_size)
        # tokens seen and tokens masked, on the device as state of the step
        self.counts = Tensor(jnp.zeros((2, 2), jnp.int32))
        register_persistent(self.counts)
        weakref.finalize(self, unregister_persistent, self.counts)
        _models.append(weakref.ref(self, _models.remove))

    def draw_noise(self, batch, seq_len):
        """(masked [B, L] bool, t [B, blocks] float32) from ``next_key()``:
        the linear schedule, one level a block."""
        c = self.config
        blocks = -(-seq_len // c.block_length)
        k_t, k_m = jax.random.split(next_key())
        t = c.noise_eps + (1.0 - c.noise_eps) * jax.random.uniform(
            k_t, (batch, blocks), _F32)
        block_of = jnp.arange(seq_len) // c.block_length
        masked = jax.random.uniform(k_m, (batch, seq_len), _F32) \
            < t[:, block_of]
        return masked, t

    def forward(self, tokens, masked=None, t=None, labels=None):
        return self._run(tokens, masked, t, labels)[0]

    def losses(self, tokens, masked=None, t=None):
        """(the weighted loss, the mean cross-entropy of the masked tokens)
        of restoring ``tokens`` from one draw of the noise."""
        return self._run(tokens, masked, t, tokens)

    def _run(self, tokens, masked, t, labels):
        c = self.config
        b, seq_len = tokens.shape[0], tokens.shape[1]
        with jax.named_scope("sdar.noise"):
            if masked is None:
                masked, t = self.draw_noise(b, seq_len)
            else:
                masked = jnp.asarray(getattr(masked, "_data", masked), bool)
                t = jnp.asarray(getattr(t, "_data", t), _F32)
            x0 = tokens._data.astype(jnp.int32)
            z = jnp.concatenate(
                [jnp.where(masked, jnp.int32(c.mask_token_id), x0), x0], 1)
            pos = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32), 2)
            self.counts._data = add_counts(self.counts._data, jnp.stack(
                [jnp.int32(b * seq_len), masked.sum(dtype=jnp.int32)]))
        hidden = self.model(Tensor(z), Tensor(pos))
        with jax.named_scope("sdar.head_loss"):
            logits = self.lm_head(hidden[:, :seq_len])
            if labels is None:
                return logits, None
            ce = F.cross_entropy(reshape(logits, [-1, c.vocab_size]),
                                 reshape(labels, [-1]), reduction="none")
            block_of = jnp.arange(seq_len) // c.block_length
            weight = (masked / t[:, block_of]).reshape(-1) / (b * seq_len)
            plain = masked.reshape(-1) / jnp.maximum(masked.sum(), 1)
            return ((ce * Tensor(weight)).sum(),
                    (ce.detach() * Tensor(plain)).sum())


def noise_stats():
    """``{"tokens", "masked"}``: the data tokens every model alive has seen
    and how many of them its noise replaced by the mask id, read from the
    device in one transfer; counted since the model was built."""
    models = [m for m in (r() for r in _models) if m is not None]
    raw = jax.device_get([m.counts._data for m in models])
    out = {"tokens": 0, "masked": 0}
    for c in raw:
        tokens, masked = read_counts(c)
        out["tokens"] += tokens
        out["masked"] += masked
    return out


@_telemetry.runtime_collector
def _prometheus_counters():
    """``runtime_prometheus()``'s ``paddle_sdar_*`` counters: nothing from
    a process that built no such model."""
    stats = noise_stats()
    if not stats["tokens"]:
        return {}
    return {"paddle_sdar_tokens_total": stats["tokens"],
            "paddle_sdar_masked_tokens_total": stats["masked"]}


def sdar_tiny(vocab_size=256, **kw):
    """Two layers at hidden 64 with 8 experts, top-2, blocks of 4: the CPU
    tests' size."""
    kw = {**dict(hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                 head_dim=32, num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32, block_length=4), **kw}
    return SDARForBlockDiffusion(SDARConfig(vocab_size=vocab_size, **kw))
