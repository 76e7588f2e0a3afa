"""GPT-2 family (BASELINE configs[0]: GPT-2 124M dygraph LM).

Parity target: the PaddleNLP-style GPT implemented on this framework's
nn.Layer surface (the reference core repo hosts the layers; the model shape
follows GPT-2: learned positions, pre-LN blocks, tied LM head).

TPU-first notes: attention routes through F.scaled_dot_product_attention
(Pallas flash kernel on TPU); all projections are [in,out] single matmuls;
sequence length and batch are static under jit.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import LayerNorm
from ..tensor.tensor import Parameter, Tensor
from ..tensor.manipulation import reshape

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt2_124m",
           "gpt2_tiny"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_position=1024,
                 dropout=0.1, layer_norm_eps=1e-5, initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position = max_position
        self.dropout = dropout
        self.layer_norm_eps = layer_norm_eps
        self.initializer_range = initializer_range


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        init = Normal(0.0, c.initializer_range)
        self.qkv_proj = Linear(c.hidden_size, 3 * c.hidden_size,
                               weight_attr=_attr(init))
        self.out_proj = Linear(c.hidden_size, c.hidden_size,
                               weight_attr=_attr(Normal(
                                   0.0, c.initializer_range /
                                   math.sqrt(2 * c.num_layers))))
        self.dropout = c.dropout

    def forward(self, x, kv_cache=None):
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        qkv = reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.dropout if self.training else 0.0)
        out = reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        init = Normal(0.0, c.initializer_range)
        self.fc1 = Linear(c.hidden_size, c.intermediate_size,
                          weight_attr=_attr(init))
        self.fc2 = Linear(c.intermediate_size, c.hidden_size,
                          weight_attr=_attr(Normal(
                              0.0, c.initializer_range /
                              math.sqrt(2 * c.num_layers))))

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln2 = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.mlp = GPTMLP(config)
        self.drop = Dropout(config.dropout)

    def forward(self, x):
        x = x + self.drop(self.attn(self.ln1(x)))
        x = x + self.drop(self.mlp(self.ln2(x)))
        return x


def _attr(init):
    from ..nn.utils_ import ParamAttr
    return ParamAttr(initializer=init)


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.config = c
        init = Normal(0.0, c.initializer_range)
        self.wte = Embedding(c.vocab_size, c.hidden_size,
                             weight_attr=_attr(init))
        self.wpe = Embedding(c.max_position, c.hidden_size,
                             weight_attr=_attr(init))
        self.drop = Dropout(c.dropout)
        self.h = LayerList([GPTBlock(c) for _ in range(c.num_layers)])
        self.ln_f = LayerNorm(c.hidden_size, c.layer_norm_eps)

    def forward(self, input_ids, position_ids=None):
        b, s = input_ids.shape[0], input_ids.shape[1]
        if position_ids is None:
            from ..tensor.creation import arange
            position_ids = arange(s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        for blk in self.h:
            x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    """LM head tied to wte (standard GPT-2 weight tying)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def forward(self, input_ids, labels=None):
        h = self.gpt(input_ids)
        logits = F.linear(h, _transpose_param(self.gpt.wte.weight))
        if labels is not None:
            loss = F.cross_entropy(
                reshape(logits, [-1, self.config.vocab_size]),
                reshape(labels, [-1]))
            return loss
        return logits


def _transpose_param(w):
    from ..tensor.tensor import apply_op
    return apply_op(lambda a: a.T, w)


def gpt2_124m(vocab_size=50304, **kw):
    return GPTForCausalLM(GPTConfig(vocab_size=vocab_size, hidden_size=768,
                                    num_layers=12, num_heads=12, **kw))


def gpt2_tiny(vocab_size=1024, **kw):
    return GPTForCausalLM(GPTConfig(vocab_size=vocab_size, hidden_size=64,
                                    num_layers=2, num_heads=2,
                                    max_position=128, **kw))
