"""Context parallelism for long sequences: ring attention + Ulysses.

Capability parity: the reference's long-context stack (SURVEY §5.7) — the
2.6-era `sep` hybrid degree in
python/paddle/distributed/fleet/base/topology.py :: HybridCommunicateGroup
(Ulysses-style head-scatter alltoall through attention) and the
ring-flash-attention variants that live in the Paddle ecosystem repos.

TPU-native design (NOT a port): the sequence dim is a named mesh axis
("sep"); both schemes are written against `shard_map` collectives so XLA
schedules the ICI neighbor exchange / all_to_all asynchronously with the
per-chunk compute:

- **Ring attention**: K/V chunks rotate around the sep axis with
  `jax.lax.ppermute` (the natural match for TPU ICI ring topology); each
  step computes blockwise attention of the local Q chunk against the
  visiting K/V chunk and merges the partial results with the numerically
  stable log-sum-exp accumulation (same online-softmax identity as flash
  attention, lifted to the inter-chip level). Exact — not an approximation.
  Differentiable through `lax.scan` + `ppermute` (and each step can be
  rematerialized with `jax.checkpoint`, making activation memory O(S/n)).

- **Ulysses**: `all_to_all` re-shards [B, S/n, H, D] → [B, S, H/n, D] so
  attention itself runs dense per device over full sequence with a head
  slice, then the inverse all_to_all restores sequence sharding. Requires
  heads % sep == 0; preferred when H ≥ sep and sequence fits per-device
  memory after the gather.

Both are called INSIDE shard_map (see `make_ring_attention_fn` /
fleet sep wiring); inputs are the device-local chunks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "make_ring_attention_fn",
           "make_ulysses_attention_fn"]

_NEG_INF = -1e30


def _chunk_attn(q, k, v, scale, mask):
    """Blockwise attention returning (out, lse) for one KV chunk.

    q: [B, Sq, H, D]; k, v: [B, Sk, Hk, D] (GQA: H % Hk == 0).
    mask: broadcastable to [Sq, Sk] boolean (True = attend), or None.
    out is the *normalized* chunk output; lse the per-row log-sum-exp —
    the pair merges exactly across chunks. fp32 softmax stats.
    """
    bq, sq, h, d = q.shape
    hk = k.shape[2]
    if h != hk:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, _NEG_INF)          # all-masked rows stay finite
    p = jnp.exp(s - m)
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    lse = (m + jnp.log(jnp.where(l == 0.0, 1.0, l)))[..., 0]   # [B,H,Sq]
    lse = jnp.where(l[..., 0] == 0.0, _NEG_INF, lse)
    denom = jnp.where(l == 0.0, 1.0, l)
    o = o / jnp.swapaxes(denom, 1, 2)      # [B,Sq,H,1] broadcast
    return o, lse


def _merge(o_a, lse_a, o_b, lse_b):
    """Merge two normalized partial attentions via their lse (exact)."""
    lse_m = jnp.maximum(lse_a, lse_b)
    # guard fully-masked (-inf-ish) rows
    lse_m = jnp.maximum(lse_m, _NEG_INF)
    wa = jnp.exp(lse_a - lse_m)
    wb = jnp.exp(lse_b - lse_m)
    denom = wa + wb
    denom = jnp.where(denom == 0.0, 1.0, denom)
    lse_new = lse_m + jnp.log(denom)
    wa = (wa / denom)[..., None].swapaxes(1, 2)   # [B,Sq,H,1]
    wb = (wb / denom)[..., None].swapaxes(1, 2)
    return o_a * wa + o_b * wb, lse_new


def _use_ring_kernel(q, k) -> bool:
    """Dispatch the per-step chunk to the Pallas flash kernel on real TPU
    only (PADDLE_TPU_RING_COMPOSITE=1 forces the dense composite).

    On CPU the composite stays the default (interpret-mode pallas is
    orders slower), but PADDLE_TPU_RING_KERNEL_CPU=1 forces the kernel —
    _cp_fn's check_vma=False lifted the jax-0.9 limitation that used to
    make pallas-inside-shard_map impossible on CPU, so the COMBINED
    ring+kernel path is now CPU-testable (r4 weak #3); on-chip
    validation still happens in the session window."""
    import os
    if os.environ.get("PADDLE_TPU_RING_COMPOSITE") == "1":
        return False
    if jax.default_backend() != "tpu" and \
            os.environ.get("PADDLE_TPU_RING_KERNEL_CPU") != "1":
        return False
    # deliberately NOT a blanket except: an ImportError/regression in the
    # kernel module must surface, not silently downgrade every TPU ring
    # step to the O(S^2) dense composite
    from ..ops.pallas.ring_chunk_attention import is_supported
    # is_supported takes kernel layout [B, H, S, D]; ring holds
    # [B, S, H, D]
    qs = (q.shape[0], q.shape[2], q.shape[1], q.shape[3])
    ks = (k.shape[0], k.shape[2], k.shape[1], k.shape[3])
    return is_supported(qs, ks, q.dtype)


def ring_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                   scale: Optional[float] = None, remat: bool = True):
    """Exact ring attention over a named mesh axis; call inside shard_map.

    q,k,v: device-local [B, S/n, H, D] chunks, sequence sharded over
    `axis_name` in ring order (chunk i on mesh index i). Returns the local
    output chunk [B, S/n, H, D] in q.dtype.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    sq = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]   # kv moves to next rank

    def causal_mask(src):
        # global rows my*sq + r ; cols src*sq + c ; attend iff col <= row
        rows = my * sq + jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
        cols = src * sq + jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
        return cols <= rows

    use_kernel = _use_ring_kernel(q, k)

    def step(carry, t):
        o_acc, lse_acc, k_cur, v_cur = carry
        src = (my - t) % n          # which rank's chunk is visiting

        if use_kernel:
            # Pallas flash chunk (ops/pallas/ring_chunk_attention): the
            # visiting diagonal is the traced offset (my - src) * sq —
            # one compiled kernel serves every ring step; lse is a
            # differentiated output so merge weights backprop exactly
            def compute(q_, k_, v_):
                from ..ops.pallas.ring_chunk_attention import \
                    ring_chunk_attention
                off = (my - src) * sq if causal else k_.shape[1]
                o_t, lse_t = ring_chunk_attention(
                    jnp.swapaxes(q_, 1, 2), jnp.swapaxes(k_, 1, 2),
                    jnp.swapaxes(v_, 1, 2), off, scale)
                return jnp.swapaxes(o_t, 1, 2).astype(jnp.float32), lse_t
        else:
            mask = causal_mask(src) if causal else None

            def compute(q_, k_, v_):
                return _chunk_attn(q_, k_, v_, scale, mask)

        if remat:
            compute = jax.checkpoint(compute)
        o_i, lse_i = compute(q, k_cur, v_cur)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_i, lse_i)

        def rotate(kv):
            k_, v_ = kv
            return (jax.lax.ppermute(k_, axis_name, perm),
                    jax.lax.ppermute(v_, axis_name, perm))

        # last step's rotation would be discarded — skip the ICI exchange
        k_nxt, v_nxt = jax.lax.cond(t < n - 1, rotate, lambda kv: kv,
                                    (k_cur, v_cur))
        return (o_acc, lse_acc, k_nxt, v_nxt), None

    # initial accumulators must carry the same varying-over-axes type as the
    # per-step outputs (jax>=0.8 vma typing inside shard_map); deriving them
    # from q inherits q's full vma set (e.g. (pp, sep) when nested inside a
    # pipeline shard_map), which a bare pcast over axis_name would not
    zero_q = q.astype(jnp.float32) * 0.0
    o0 = zero_q
    lse0 = jnp.swapaxes(zero_q[..., 0], 1, 2) + _NEG_INF   # [B,H,Sq]
    (o, _, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v),
                                   jnp.arange(n))
    return o.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                      scale: Optional[float] = None):
    """Ulysses sequence parallelism: all_to_all seq-shard → head-shard,
    dense attention per device, inverse all_to_all. Call inside shard_map.

    q,k,v: local [B, S/n, H, D]; H % n == 0 required. Exact.
    """
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by sep={n}")
    if k.shape[2] % n != 0:
        raise ValueError(
            f"kv heads {k.shape[2]} not divisible by sep={n}; Ulysses "
            f"re-shards heads across the sep axis — use ring_attention for "
            f"GQA configs with kv_heads < sep")

    def scatter_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def gather_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # the per-device attention runs over the FULL sequence — exactly
    # where the dense composite's O(S^2) score materialization hurts
    # (S=16k => gigabytes of [B, H/n, S, S] fp32). Stream the flash
    # kernel instead whenever it tiles (TPU; PADDLE_TPU_ULYSSES_FLASH_CPU
    # =1 exercises the same path in interpret mode for tests), with the
    # dense composite as the untileable-shape fallback.
    import os
    from ..ops.pallas import flash_attention as fa
    use_flash = (jax.default_backend() == "tpu"
                 or os.environ.get("PADDLE_TPU_ULYSSES_FLASH_CPU") == "1")
    if use_flash and os.environ.get(
            "PADDLE_TPU_ULYSSES_COMPOSITE") != "1" and \
            fa.is_supported(qh.shape, qh.dtype):
        o = fa.flash_attention(qh, kh, vh, causal=causal, scale=scale)
        return gather_heads(o.astype(q.dtype))
    sq = qh.shape[1]
    mask = None
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
        mask = cols <= rows
    o, _ = _chunk_attn(qh, kh, vh, scale, mask)
    return gather_heads(o.astype(q.dtype))


def _cp_fn(impl, mesh: Mesh, axis_name: str, causal: bool,
           scale: Optional[float]):
    spec = P(None, axis_name, None, None)

    # The varying-manual-axes static check trips on interpret-mode
    # pallas_call inside shard_map (jax-0.9; the error itself prescribes
    # check_vma=False) — that limitation is interpret-only, so the check
    # stays LIVE on real TPU (it catches wrong out_spec / replication
    # bugs at trace time) and is disabled off-chip, which makes the
    # combined ring+kernel path testable on the CPU mesh (r4 weak #3).
    # PADDLE_TPU_CP_CHECK_VMA=0 force-disables it everywhere — the
    # escape hatch if the first on-chip compile trips it after all.
    import os
    vma = (jax.default_backend() == "tpu"
           and os.environ.get("PADDLE_TPU_CP_CHECK_VMA") != "0")

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=vma)
    def fn(q, k, v):
        return impl(q, k, v, axis_name=axis_name, causal=causal, scale=scale)

    return fn


def make_ring_attention_fn(mesh: Mesh, axis_name: str = "sep",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Global-view ring attention: takes/returns full [B, S, H, D] arrays
    sharded P(None, axis, None, None); jit-compatible."""
    return _cp_fn(ring_attention, mesh, axis_name, causal, scale)


def make_ulysses_attention_fn(mesh: Mesh, axis_name: str = "sep",
                              causal: bool = False,
                              scale: Optional[float] = None):
    return _cp_fn(ulysses_attention, mesh, axis_name, causal, scale)
