"""Attention functionals: scaled_dot_product_attention / flash_attention.

Parity: python/paddle/nn/functional/flash_attention.py (FlashAttnKernel route,
paddle/phi/kernels/gpu/flash_attn_kernel.cu) — on TPU this dispatches to the
Pallas flash-attention kernel (paddle_tpu/ops/pallas/flash_attention.py) when
available, else an XLA composite that the compiler fuses well.

Layout: [batch, seq, num_heads, head_dim] (paddle flash_attn convention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.rng import next_key
from ...inference.telemetry import runtime_counter
from ...tensor.tensor import Tensor, apply_op

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdp_kernel"]


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, dropout_key=None,
              structured_mask=None):
    """Composite attention: [B,S,H,D] layout; fp32 softmax for stability.
    Attention dropout (reference: dropout on the softmax probs, upscaled)
    is applied when dropout_p > 0 and a key is supplied. A structured mask
    (``scaled_dot_product_attention``) becomes the dense boolean mask of
    the rule the flash kernels apply."""
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if kt.shape[1] != qt.shape[1]:  # GQA: each KV head serves a group
        kt = jnp.repeat(kt, qt.shape[1] // kt.shape[1], axis=1)
        vt = jnp.repeat(vt, qt.shape[1] // vt.shape[1], axis=1)
    s = scale if scale is not None else (q.shape[-1] ** -0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    logits = logits.astype(jnp.float32)
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(cm, logits, -jnp.inf)
    if structured_mask is not None:
        from ...ops.pallas.flash_attention import dense_mask
        logits = jnp.where(dense_mask(structured_mask), logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def _per_shard(q_shape, k_shape):
    """How the flash kernel runs under the active mesh. A pallas_call
    cannot sit under GSPMD auto-partitioning — on more than one device jax
    refuses to lower it ("Mosaic kernels cannot be automatically
    partitioned"; interpret mode on a virtual CPU mesh hides this) — so
    there the kernel runs PER SHARD through shard_map: attention is
    independent across batch rows and heads, so the batch splits over the
    data axes (dp x sharding) and the heads over 'mp', with no collective.
    Returns a wrapper for ``kern(q, k, v, seed)``: the identity off-mesh,
    the shard_map form on a mesh, or None when this layout cannot split
    that way (pipeline/sequence axes in use, indivisible batch or heads)
    and the caller takes the XLA composite."""
    from ...parallel import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return lambda kern: kern
    dims = dict(mesh.shape)
    data, mp = dims["dp"] * dims["sharding"], dims["mp"]
    if dims["pp"] > 1 or dims["sep"] > 1 or q_shape[0] % data \
            or q_shape[2] % mp or k_shape[2] % mp:
        return None
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    spec = P(("dp", "sharding"), None, "mp", None)

    def wrap(kern):
        def body(q, k, v, seed):
            # a different dropout stream on every shard
            shard = jax.lax.axis_index(("dp", "sharding", "mp"))
            return kern(q, k, v, seed + shard * 7919)
        # check_vma=False: the kernel's out_shape carries no vma (same
        # as the decode kernels under the serving mesh)
        return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, P()),
                         out_specs=spec, check_vma=False)
    return wrap


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None,
                                 structured_mask=None):
    """``structured_mask`` names a rule instead of an array
    (``ops.pallas.flash_attention.block_diffusion_mask(L, b)``): the flash
    kernels evaluate it on row and column ids and skip the tiles it
    empties; the composite builds the dense mask from the same rule.
    ``paddle_flash_mask_kernel_traces_total`` or
    ``paddle_flash_mask_composite_traces_total`` counts each trace."""
    mask_arr = attn_mask._data if isinstance(attn_mask, Tensor) else attn_mask
    drop_p = float(dropout_p) if training else 0.0
    if structured_mask is not None and (is_causal or mask_arr is not None):
        raise ValueError("scaled_dot_product_attention: structured_mask "
                         "comes alone, without is_causal or attn_mask")

    # the one question: does the flash kernel take these shapes, this
    # dtype, this mask, under this mesh? Dropout enters only under a
    # structured mask (refused there): elsewhere the kernel draws its mask
    # in-kernel (no O(S^2) mask in HBM), so with dropout it is taken
    # exactly when it would be without. No blanket except: an
    # import or gate error must surface, not silently downgrade every
    # attention call to the O(S^2) composite.
    from ...ops import pallas
    fa = pallas.flash_attention
    q_shape, k_shape = tuple(query.shape), tuple(key.shape)
    wrap = None
    if mask_arr is None and pallas._enabled() \
            and q_shape[2] % k_shape[2] == 0 \
            and fa.is_supported(q_shape, query.dtype, structured_mask,
                                k_shape, drop_p):
        wrap = _per_shard(q_shape, k_shape)
    if structured_mask is not None:
        runtime_counter("paddle_flash_mask_kernel_traces_total"
                        if wrap is not None else
                        "paddle_flash_mask_composite_traces_total", 1)
    if wrap is not None:
        seed = jnp.zeros((), jnp.int32)
        if drop_p > 0.0:
            import jax.random as jrandom
            seed = jrandom.randint(next_key(), (), 0, 2 ** 31 - 1,
                                   dtype=jnp.int32)

        def kern(q, k, v, s):
            return fa.flash_attention(q, k, v, causal=is_causal,
                                      dropout_p=drop_p, dropout_seed=s,
                                      mask=structured_mask)

        def f(q, k, v):
            return wrap(kern)(q, k, v, seed)
        return apply_op(f, query, key, value)

    key_ = next_key() if drop_p > 0.0 else None

    def f(q, k, v, *m):
        return _sdpa_ref(q, k, v, m[0] if m else None, drop_p, is_causal,
                         None, dropout_key=key_,
                         structured_mask=structured_mask)
    if attn_mask is not None:
        return apply_op(f, query, key, value, attn_mask)
    return apply_op(f, query, key, value)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, name=None):
    """Varlen flash attention: segment-masked single-sequence attention."""
    cq = cu_seqlens_q._data if isinstance(cu_seqlens_q, Tensor) else cu_seqlens_q
    ck = cu_seqlens_k._data if isinstance(cu_seqlens_k, Tensor) else cu_seqlens_k

    def f(q, k, v):
        total_q = q.shape[0]
        total_k = k.shape[0]
        seg_q = jnp.cumsum(
            jnp.zeros(total_q, jnp.int32).at[cq[1:-1]].add(1))
        seg_k = jnp.cumsum(
            jnp.zeros(total_k, jnp.int32).at[ck[1:-1]].add(1))
        s = scale if scale is not None else q.shape[-1] ** -0.5
        logits = jnp.einsum("qhd,khd->hqk", q, k) * s
        same = (seg_q[:, None] == seg_k[None, :])
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cq, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(ck, seg_k)
            same = same & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.where(same[None], logits.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        probs = jnp.where(same[None], probs, 0.0)
        return jnp.einsum("hqk,khd->qhd", probs, v)
    out = apply_op(f, query, key, value)
    if return_softmax:
        return out, None
    return out, None


class sdp_kernel:
    """Context selecting the attention backend (API parity shim)."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
