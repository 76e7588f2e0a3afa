"""Fused functionals. Parity: python/paddle/incubate/nn/functional/
(fused_multi_head_attention, fused_feedforward, fused_matmul_bias,
fused_rotary_position_embedding, fused_bias_dropout_residual_layer_norm,
fused_multi_transformer) over the CUDA monoliths in
paddle/fluid/operators/fused/*.cu.

TPU-native: each is ONE composite that XLA fuses into a handful of MXU ops —
there is no monolithic kernel to maintain; the attention core routes through
the Pallas flash kernel via F.scaled_dot_product_attention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...nn import functional as F
from ...tensor.tensor import Tensor, apply_op

__all__ = ["fused_matmul_bias", "fused_linear", "fused_feedforward",
           "fused_multi_head_attention", "fused_rotary_position_embedding",
           "fused_bias_dropout_residual_layer_norm", "fused_linear_activation",
           "fused_multi_transformer"]


def fused_matmul_bias(x, weight, bias=None, transpose_x=False,
                      transpose_y=False, name=None):
    def f(a, w, *b):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            w = jnp.swapaxes(w, -1, -2)
        out = jnp.matmul(a, w)
        if b:
            out = out + b[0]
        return out
    if bias is not None:
        return apply_op(f, x, weight, bias)
    return apply_op(f, x, weight)


fused_linear = fused_matmul_bias


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    out = fused_matmul_bias(x, y, bias, trans_x, trans_y)
    return getattr(F, activation)(out)


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode="upscale_in_train",
                      name=None):
    """Parity: fused_feedforward_op.cu — LN→linear→act→dropout→linear→dropout
    →residual(+LN), as an XLA composite."""
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, x.shape[-1:], ln1_scale, ln1_bias, ln1_epsilon)
    out = F.linear(x, linear1_weight, linear1_bias)
    out = getattr(F, activation)(out)
    out = F.dropout(out, dropout1_rate, training=training, mode=mode)
    out = F.linear(out, linear2_weight, linear2_bias)
    out = F.dropout(out, dropout2_rate, training=training, mode=mode)
    out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1:], ln2_scale, ln2_bias,
                           ln2_epsilon)
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, name=None):
    """Parity: fused_attention_op.cu (fmha_ref.h). qkv_weight layout
    [3, num_heads, head_dim, embed_dim] as in the reference."""
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, x.shape[-1:], pre_ln_scale, pre_ln_bias,
                         pre_ln_epsilon)
    three, n_heads, head_dim, embed = qkv_weight.shape

    def qkv_fn(a, w, *b):
        wr = w.reshape(3 * n_heads * head_dim, embed).T
        out = jnp.matmul(a, wr)
        if b:
            out = out + b[0].reshape(-1)
        return out
    if qkv_bias is not None:
        qkv = apply_op(qkv_fn, x, qkv_weight, qkv_bias)
    else:
        qkv = apply_op(qkv_fn, x, qkv_weight)
    b, s = qkv.shape[0], qkv.shape[1]
    from ...tensor.manipulation import reshape, split as tsplit
    qkv = reshape(qkv, [b, s, 3, n_heads, head_dim])
    q, k, v = tsplit(qkv, 3, axis=2)
    q = reshape(q, [b, s, n_heads, head_dim])
    k = reshape(k, [b, s, n_heads, head_dim])
    v = reshape(v, [b, s, n_heads, head_dim])
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0)
    out = reshape(out, [b, s, n_heads * head_dim])
    out = F.linear(out, linear_weight, linear_bias)
    out = F.dropout(out, dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1:], ln_scale, ln_bias, ln_epsilon)
    return out


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style
                                    =True, time_major=False, rotary_emb_base
                                    =10000.0, position_offset=0):
    """Parity: fused_rotary_position_embedding (phi fusion). Layout
    [batch, seq, heads, head_dim]. position_offset (int or traced scalar)
    shifts the rotary positions — the KV-cache decode step at time t rotates
    its single new token with position t, not 0."""
    def rope(x):
        bsz, seq, nh, hd = x.shape
        if sin is None:
            inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, hd, 2,
                                                        dtype=jnp.float32) / hd))
            t = jnp.arange(seq, dtype=jnp.float32) + position_offset
            freqs = jnp.outer(t, inv)
            s = jnp.sin(freqs)
            c = jnp.cos(freqs)
        else:
            s = sin._data.reshape(seq, hd // 2) if isinstance(sin, Tensor) else sin
            c = cos._data.reshape(seq, hd // 2) if isinstance(cos, Tensor) else cos
        s = s[None, :, None, :]
        c = c[None, :, None, :]

        def f(arr):
            if use_neox_rotary_style:
                x1 = arr[..., : hd // 2]
                x2 = arr[..., hd // 2:]
                ss = jnp.concatenate([s, s], axis=-1)
                cc = jnp.concatenate([c, c], axis=-1)
                rot = jnp.concatenate([-x2, x1], axis=-1)
                return arr * cc.astype(arr.dtype) + rot * ss.astype(arr.dtype)
            x1 = arr[..., 0::2]
            x2 = arr[..., 1::2]
            o1 = x1 * c - x2 * s
            o2 = x2 * c + x1 * s
            return jnp.stack([o1, o2], axis=-1).reshape(arr.shape)
        return f
    outs = []
    for t in (q, k, v):
        if t is None:
            outs.append(None)
        else:
            outs.append(apply_op(rope(t), t))
    return tuple(outs)


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True,
                                           mode="upscale_in_train", name=None):
    """Parity: fused_bias_dropout_residual_layer_norm (phi fusion gpu)."""
    out = x
    if bias is not None:
        out = out + bias
    out = F.dropout(out, dropout_rate, training=training, mode=mode)
    out = out + residual
    return F.layer_norm(out, out.shape[-1:], ln_scale, ln_bias, ln_epsilon)


def _decode_attn(q, cache, ts, s, attn_mask):
    """Cache attention for the decode step. TPU: the Pallas flash-decode
    kernel over the full static-shape cache with length masking (no
    per-step recompiles); fallback: dense sdpa over the valid prefix."""
    from ...ops import pallas
    if attn_mask is None and pallas._enabled():
        from ...ops.pallas import decode_attention as da
        kc = cache._data[0]          # [B, H, Smax, D]
        if da.is_supported(tuple(q.shape),
                           (kc.shape[0], kc.shape[2], kc.shape[1], kc.shape[3]),
                           q.dtype):
            # inference-only kernel (no VJP) — bypass the autograd tape;
            # the cache is already in kernel layout [B, H, Smax, D], so use
            # the bhsd entry point (no full-cache transposes per step)
            lens = jnp.full((q.shape[0],), ts, jnp.int32)
            out = da.decode_attention_bhsd(
                jnp.swapaxes(jax.lax.stop_gradient(q._data), 1, 2),
                jax.lax.stop_gradient(cache._data[0]),
                jax.lax.stop_gradient(cache._data[1]),
                lens)
            return Tensor(jnp.swapaxes(out, 1, 2))
    k_full = Tensor(jnp.swapaxes(cache._data[0, :, :, :ts + s], 1, 2))
    v_full = Tensor(jnp.swapaxes(cache._data[1, :, :, :ts + s], 1, 2))
    if attn_mask is None and s > 1:
        # match the kernel path: new token r attends the prefix plus new
        # tokens <= r (causal among the chunk)
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(ts + s)[None, :]
        attn_mask = Tensor((cols <= ts + rows)[None, None])
    return F.scaled_dot_product_attention(q, k_full, v_full,
                                          attn_mask=attn_mask)


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-5, cache_kvs=None, pre_caches=None,
                            seq_lens=None, rotary_embs=None, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            activation="gelu", training=False,
                            mode="upscale_in_train", trans_qkvw=True,
                            ring_id=-1, name=None):
    """Parity: fused_multi_transformer_op.cu :: FusedMultiTransformerOp — the
    full decoder stack with KV cache, the north-star inference kernel.
    Returns (out, cache_kvs). Cache layout [2, batch, heads, max_seq, head_dim]
    as in the reference; decode path appends at time_step.
    """
    from ...tensor.manipulation import reshape
    out = x
    new_caches = []
    n_layers = len(qkv_weights)
    for i in range(n_layers):
        residual = out
        if pre_layer_norm:
            h = F.layer_norm(out, out.shape[-1:], ln_scales[i], ln_biases[i],
                             epsilon)
        else:
            h = out
        qkv_w = qkv_weights[i]
        # reference layout (trans_qkvw): [3, heads, head_dim, embed]
        three, nh, hd, emb = qkv_w.shape

        def qkv_fn(a, w, *b):
            wr = w.reshape(3 * nh * hd, emb).T
            o = jnp.matmul(a, wr)
            if b:
                o = o + b[0].reshape(-1)
            return o
        if qkv_biases[i] is not None:
            qkv = apply_op(qkv_fn, h, qkv_w, qkv_biases[i])
        else:
            qkv = apply_op(qkv_fn, h, qkv_w)
        b, s = qkv.shape[0], qkv.shape[1]
        qkv = reshape(qkv, [b, s, 3, nh, hd])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        cache = cache_kvs[i] if cache_kvs is not None else None
        ts = None
        if cache is not None and time_step is not None:
            ts = int(time_step.item()) if isinstance(time_step, Tensor) \
                else int(time_step)
        if rotary_embs is not None:
            # decode: the new token sits at absolute position ts, so its
            # rotary phase is ts — not 0
            q, k, _ = fused_rotary_position_embedding(
                q, k, position_offset=ts or 0)
        if ts is not None:

            def upd(c, kk, vv):
                c = c.at[0, :, :, ts:ts + s].set(jnp.swapaxes(kk, 1, 2))
                c = c.at[1, :, :, ts:ts + s].set(jnp.swapaxes(vv, 1, 2))
                return c
            cache._data = upd(cache._data, k._data, v._data)
            attn = _decode_attn(q, cache, ts, s, attn_mask)
            new_caches.append(cache)
        else:
            attn = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                  is_causal=attn_mask is None)
            if cache_kvs is not None:
                new_caches.append(cache)
        attn = reshape(attn, [b, s, nh * hd])
        attn = F.linear(attn, linear_weights[i], linear_biases[i])
        out = residual + attn
        if not pre_layer_norm:
            out = F.layer_norm(out, out.shape[-1:], ln_scales[i], ln_biases[i],
                               epsilon)
        # FFN
        residual = out
        if pre_layer_norm:
            h = F.layer_norm(out, out.shape[-1:], ffn_ln_scales[i],
                             ffn_ln_biases[i], epsilon)
        else:
            h = out
        h = F.linear(h, ffn1_weights[i], ffn1_biases[i])
        h = getattr(F, activation)(h)
        h = F.linear(h, ffn2_weights[i], ffn2_biases[i])
        out = residual + h
        if not pre_layer_norm:
            out = F.layer_norm(out, out.shape[-1:], ffn_ln_scales[i],
                               ffn_ln_biases[i], epsilon)
    return out, (new_caches if cache_kvs is not None else None)


def softmax_mask_fuse(x, mask, name=None):
    """Fused (x + mask) softmax over the last axis. Parity:
    incubate/nn/functional/fused_softmax_mask.py (CUDA fused kernel) —
    XLA fuses the add into the softmax reduction on TPU, so this wrapper
    IS the fused form."""
    return apply_op(
        lambda a, m: jax.nn.softmax(a + m, axis=-1), x, mask)


def softmax_mask_fuse_upper_triangle(x, name=None):
    """Fused causal-mask softmax (the reference's GPT-path kernel)."""
    def fn(a):
        s = a.shape[-1]
        mask = jnp.triu(jnp.full((s, s), -1e9, a.dtype), k=1)
        return jax.nn.softmax(a + mask, axis=-1)
    return apply_op(fn, x)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """dropout(x) + y in one fused graph (reference
    incubate/nn/functional/fused_dropout_add.py)."""
    dropped = F.dropout(x, p=p, training=training, mode=mode)
    return apply_op(jnp.add, dropped, y)


__all__ += ["softmax_mask_fuse", "softmax_mask_fuse_upper_triangle",
            "fused_dropout_add"]
