"""Pallas kernel correctness vs jnp references (interpret mode on CPU).

Mirrors the reference's OpTest pattern (test/legacy_test/op_test.py):
forward checked against a NumPy/jnp oracle, backward against autodiff of the
oracle.  On CPU the kernels run through the Pallas interpreter; the same code
compiles via Mosaic on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


def _sdpa_ref(q, k, v, causal):
    qt, kt, vt = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    if kt.shape[1] != qt.shape[1]:
        g = qt.shape[1] // kt.shape[1]
        kt = jnp.repeat(kt, g, axis=1)
        vt = jnp.repeat(vt, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * (q.shape[-1] ** -0.5)
    if causal:
        m = jnp.tril(jnp.ones((s.shape[-2], s.shape[-1]), bool),
                     k=s.shape[-1] - s.shape[-2])
        s = jnp.where(m, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


@pytest.mark.parametrize(
    "b,sq,h,hk,d,causal,sk",
    [
        (2, 128, 4, 4, 64, False, 128),
        (1, 256, 4, 2, 64, True, 256),   # GQA
        (1, 100, 2, 2, 32, True, 100),   # non-divisible seq
        (2, 128, 4, 4, 64, False, 200),  # cross-attn, padded kv
        (1, 128, 8, 1, 64, True, 128),   # MQA
        (1, 64, 2, 2, 32, True, 128),    # causal decode chunk (sq < sk,
                                         # bottom-right alignment)
        (1, 8, 2, 2, 64, True, 100),     # short q tail over long history
    ],
)
def test_flash_attention_fwd_bwd(b, sq, h, hk, d, causal, sk):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, sq, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, sk, hk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, sk, hk, d), jnp.float32)

    o = fa.flash_attention(q, k, v, causal=causal)
    o_ref = _sdpa_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=1e-4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * 0.1)

    g1 = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _sdpa_ref(q, k, v, causal)),
                  (0, 1, 2))(q, k, v)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_multiblock_split_bwd(monkeypatch, causal):
    """64-wide tiles make a 128/160-seq case run the MULTI-block grids
    and the split dKV/dQ backward (every default-tiling test shape is
    single-block now that caps are 1024, and the fused single-block
    backward handles those)."""
    monkeypatch.setattr(fa, "_block_sizes", lambda sq, sk, d=64: (64, 64))
    rng = np.random.RandomState(1)
    b, sq, h, hk, d, sk = 1, 128, 4, 2, 32, 160
    q = jnp.asarray(rng.randn(b, sq, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, sk, hk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, sk, hk, d), jnp.float32)

    o = fa.flash_attention(q, k, v, causal=causal)
    o_ref = _sdpa_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=1e-4)
    g1 = jax.grad(lambda *a: jnp.sum(
        fa.flash_attention(*a, causal=causal) * 0.1), (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(_sdpa_ref(*a, causal) * 0.1),
                  (0, 1, 2))(q, k, v)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=2e-4, rtol=1e-3)


def test_flash_fused_vs_split_bwd_dropout(monkeypatch):
    """The fused single-block backward and the split backward must produce
    IDENTICAL gradients for the same dropout seed — both regenerate the
    forward's mask from (seed, b, h, q-block, k-block) tile seeding, and a
    drift here corrupts training only on one dispatch path."""
    rng = np.random.RandomState(2)
    b, s, h, d = 1, 128, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    seed = jnp.asarray(7, jnp.int32)

    def g(path_split):
        # the default tiles make the 128-row slice one block (fused);
        # 64-wide tiles make it two (the split pair)
        if path_split:
            monkeypatch.setattr(fa, "_block_sizes",
                                lambda sq, sk, d=64: (64, 64))
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, causal=True, dropout_p=0.3, dropout_seed=seed) * 0.1),
            (0, 1, 2))(q, k, v)

    for a, bb in zip(g(False), g(True)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("off", [64, 0, -17, -64])
def test_ring_chunk_attention_vs_composite(off):
    """ops/pallas/ring_chunk_attention: one ring step's (o, lse) with a
    TRACED diagonal offset, differentiable through BOTH outputs (the ring
    merge weights chunks by lse, so dlse != 0). Checked against a dense
    composite, GQA included; the loss routes through o AND a bounded
    function of lse to exercise the delta_eff = rowsum(dO*O) - dlse
    fold."""
    from paddle_tpu.ops.pallas.ring_chunk_attention import \
        ring_chunk_attention

    def composite(q, k, v, offset, scale):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        sq, sk = q.shape[2], k.shape[2]
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = cols <= rows + offset
        s = jnp.where(mask[None, None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(mask[None, None], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        lsafe = jnp.where(l == 0, 1.0, l)
        o = jnp.einsum("bhqk,bhkd->bhqd", p / lsafe, v.astype(jnp.float32))
        lse = jnp.where(l[..., 0] == 0, -1e30, (m + jnp.log(lsafe))[..., 0])
        return o.astype(q.dtype), lse

    rng = np.random.RandomState(0)
    B, H, Hk, S, D = 1, 4, 2, 64, 32
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hk, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hk, S, D), jnp.float32)
    g = H // Hk
    kr, vr = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

    o1, lse1 = ring_chunk_attention(q, k, v, off)
    o2, lse2 = composite(q, kr, vr, off, D ** -0.5)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse1), np.asarray(lse2),
                               atol=2e-4, rtol=1e-4)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            lsec = jnp.clip(lse, -30.0, 30.0)
            return jnp.sum(o * jax.nn.sigmoid(lsec)[..., None] * 0.1)
        return f

    g1 = jax.grad(loss(lambda q, k, v: ring_chunk_attention(
        q, k, v, off)), (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: composite(
        q, k, v, off, D ** -0.5)), (0, 1, 2))(q, kr, vr)
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                               atol=2e-4, rtol=1e-3)
    # GQA: composite grads are per-q-head — segment-sum to kv heads
    for gi, gref in ((1, g2[1]), (2, g2[2])):
        gref = gref.reshape(B, Hk, g, S, D).sum(axis=2)
        np.testing.assert_allclose(np.asarray(g1[gi]), np.asarray(gref),
                                   atol=2e-4, rtol=1e-3)


def test_flash_attention_bf16():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.bfloat16)
    o = fa.flash_attention(q, k, v, causal=True)
    ref = _sdpa_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_functional_layer_norm_uses_tape():
    """F.layer_norm still differentiates through the Tensor tape."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    x = paddle.to_tensor(np.random.randn(16, 32).astype(np.float32),
                         stop_gradient=False)
    w = paddle.to_tensor(np.ones(32, np.float32), stop_gradient=False)
    b = paddle.to_tensor(np.zeros(32, np.float32), stop_gradient=False)
    y = F.layer_norm(x, 32, w, b)
    y.sum().backward()
    assert x.grad is not None and w.grad is not None


def test_forced_pallas_dispatch_through_tape(monkeypatch):
    """With the one gate flipped, sdpa routes through the Pallas flash
    kernel (interpret mode on CPU) including backward — catches
    apply_op→custom_vjp wiring breaks before they hit real TPU."""
    from paddle_tpu.ops import pallas
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    q = paddle.to_tensor(np.random.randn(2, 16, 4, 32).astype(np.float32),
                         stop_gradient=False)
    k = paddle.to_tensor(np.random.randn(2, 16, 4, 32).astype(np.float32),
                         stop_gradient=False)
    v = paddle.to_tensor(np.random.randn(2, 16, 4, 32).astype(np.float32),
                         stop_gradient=False)
    o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    o.sum().backward()
    assert q.grad is not None and k.grad is not None and v.grad is not None


def test_sdpa_dropout_applied():
    """dropout_p > 0 under training actually drops attention probs."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    q = paddle.to_tensor(np.random.randn(1, 8, 2, 16).astype(np.float32))
    k = paddle.to_tensor(np.random.randn(1, 8, 2, 16).astype(np.float32))
    v = paddle.to_tensor(np.ones((1, 8, 2, 16), np.float32))
    o_nodrop = F.scaled_dot_product_attention(q, k, v, dropout_p=0.0)
    o_drop = F.scaled_dot_product_attention(q, k, v, dropout_p=0.9,
                                            training=True)
    o_eval = F.scaled_dot_product_attention(q, k, v, dropout_p=0.9,
                                            training=False)
    assert not np.allclose(np.asarray(o_drop._data),
                           np.asarray(o_nodrop._data))
    np.testing.assert_allclose(np.asarray(o_eval._data),
                               np.asarray(o_nodrop._data))


@pytest.mark.parametrize("sq,group", [(1, 1), (4, 2)])
def test_decode_attention_vs_dense(sq, group):
    """Flash-decode kernel vs dense masked attention over a KV cache."""
    from paddle_tpu.ops.pallas import decode_attention as da
    b, h, d, smax = 2, 4, 32, 64
    hk = h // group
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, sq, h, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, smax, hk, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, smax, hk, d), jnp.float32)
    lens = jnp.asarray([17, 40], jnp.int32)

    out = da.decode_attention(q, kc, vc, lens)

    # dense oracle
    scale = d ** -0.5
    kr = jnp.repeat(kc, group, axis=2)
    vr = jnp.repeat(vc, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * scale
    rows = jnp.arange(sq)[None, None, :, None]
    cols = jnp.arange(smax)[None, None, None, :]
    mask = cols <= (lens[:, None, None, None] + rows)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("group", [1, 2])
def test_decode_attention_stacked_vs_unstacked(group):
    """Stacked-cache variant (scalar-prefetch layer index into the full
    [L,2,B,Hk,Smax,D] buffer — the zero-copy read half of the in-place
    decode cache design) must match the per-layer kernel, including with
    a TRACED layer index inside a scan (the real multi-layer decode)."""
    from paddle_tpu.ops.pallas import decode_attention as da
    L, b, h, d, smax = 3, 2, 4, 32, 128
    hk = h // group
    rng = np.random.RandomState(1)
    caches = jnp.asarray(rng.randn(L, 2, b, hk, smax, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    lens = jnp.asarray([9, 77], jnp.int32)
    assert da.stacked_is_supported((b, 1, h, d), caches.shape, q.dtype)

    for l in range(L):
        ref = da.decode_attention_bhsd(q, caches[l, 0], caches[l, 1], lens)
        got = da.decode_attention_stacked(q, caches, l, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def body(carry, l):
        return carry, da.decode_attention_stacked(q, caches, l, lens)
    _, outs = jax.jit(lambda: jax.lax.scan(body, 0, jnp.arange(L)))()
    for l in range(L):
        ref = da.decode_attention_bhsd(q, caches[l, 0], caches[l, 1], lens)
        np.testing.assert_allclose(np.asarray(outs[l]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_decode_attention_clamped_index_multiblock():
    """The last-valid-block index-map clamp (DMA elision for padding
    blocks) must be numerically invisible. Exercised where it ENGAGES:
    Smax=512 -> bk=256 -> 2 sequence blocks, with short per-batch lens so
    block 1 is clamped back to block 0 for every row — an off-by-one in
    the clamp would mis-address the last valid block and corrupt the
    output. Covers fp stacked, int8 stacked, and the bhsd fallback."""
    from paddle_tpu.ops.pallas import decode_attention as da
    L, b, h, d, smax, sq = 2, 3, 4, 32, 512, 1
    rng = np.random.RandomState(7)
    caches = jnp.asarray(rng.randn(L, 2, b, h, smax, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, h, sq, d), jnp.float32)
    # lens straddle the block-0/block-1 boundary: 30 (block 0 only),
    # 255/256 (the exact edge: the new token lands at position len)
    lens = jnp.asarray([30, 255, 256], jnp.int32)

    def dense_ref(kc, vc):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kc) * (d ** -0.5)
        rows = jnp.arange(sq)[None, None, :, None]
        cols = jnp.arange(smax)[None, None, None, :]
        mask = cols <= (lens[:, None, None, None] + rows)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vc)

    for l in range(L):
        ref = dense_ref(caches[l, 0], caches[l, 1])
        got = da.decode_attention_stacked(q, caches, l, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        got_bhsd = da.decode_attention_bhsd(q, caches[l, 0],
                                            caches[l, 1], lens)
        np.testing.assert_allclose(np.asarray(got_bhsd), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    # int8: per-row absmax quant, scales [L, 2, B, Hk, 1, Smax]
    absmax = jnp.max(jnp.abs(caches), axis=-1, keepdims=True)
    scales = jnp.maximum(absmax, 1e-8) / 127.0
    caches_i8 = jnp.round(caches / scales).astype(jnp.int8)
    scales = jnp.swapaxes(scales, -1, -2)       # [L,2,B,Hk,1,Smax]
    deq = caches_i8.astype(jnp.float32) * jnp.swapaxes(scales, -1, -2)
    for l in range(L):
        ref = dense_ref(deq[l, 0], deq[l, 1])
        got = da.decode_attention_stacked_i8(q, caches_i8, scales, l,
                                             lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


def test_decode_attention_stacked_write_parity():
    """Fused write+attend (in-place cache via input_output_aliases) must
    equal DUS-then-read exactly: attention output AND the full cache
    buffer (landed rows, untouched prefix, untouched other layers) —
    across lens that sit mid-block and exactly on a block boundary."""
    from paddle_tpu.ops.pallas import decode_attention as da
    L, b, h, d, smax = 2, 3, 4, 32, 512
    rng = np.random.RandomState(7)
    caches = jnp.asarray(rng.randn(L, 2, b, h, smax, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    kv_new = jnp.asarray(rng.randn(2, b, h, 1, d), jnp.float32)
    lens = jnp.asarray([30, 255, 256], jnp.int32)
    assert da.stacked_write_is_supported((b, 1, h, d), caches.shape,
                                         q.dtype)

    for l in range(L):
        ref_caches = caches
        for bi in range(b):
            for kv in range(2):
                ref_caches = jax.lax.dynamic_update_slice(
                    ref_caches,
                    kv_new[kv, bi, :, 0][None, None, None, :, None, :],
                    (l, kv, bi, 0, int(lens[bi]), 0))
        ref_o = da.decode_attention_stacked(q, ref_caches, l, lens)
        got_caches, got_o = da.decode_attention_stacked_write(
            q, kv_new, caches, l, lens)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(ref_o),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(got_caches),
                                      np.asarray(ref_caches))


class TestFlashDropout:
    """Flash attention with seed-regenerated dropout (fwd/bwd mask parity)."""

    def _qkv(self, b=2, s=32, h=2, d=16):
        rng = np.random.RandomState(5)
        mk = lambda: jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        return mk(), mk(), mk()

    def test_matches_reference_with_same_mask(self):
        from paddle_tpu.ops.pallas import flash_attention as fa
        q, k, v = self._qkv()
        p_drop, seed = 0.3, jnp.asarray(7, jnp.int32)
        out = fa.flash_attention(q, k, v, causal=True, dropout_p=p_drop,
                                 dropout_seed=seed)
        # reference with the identical regenerated mask
        bq, bk, sq_p, sk_p = fa._padded_sizes(q.shape[1], k.shape[1])
        dm = fa._dropout_mask(seed, (q.shape[0], q.shape[2], sq_p, sk_p),
                              p_drop)[:, :, :q.shape[1], :k.shape[1]]
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
        msk = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s_ = jnp.where(msk[None, None], s_, -1e30)
        p_ = jax.nn.softmax(s_, axis=-1) * dm
        ref = jnp.einsum("bhqk,bkhd->bqhd", p_, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grads_match_reference(self):
        from paddle_tpu.ops.pallas import flash_attention as fa
        q, k, v = self._qkv(s=16)
        p_drop, seed = 0.25, jnp.asarray(3, jnp.int32)
        bq, bk, sq_p, sk_p = fa._padded_sizes(q.shape[1], k.shape[1])
        dm = fa._dropout_mask(seed, (q.shape[0], q.shape[2], sq_p, sk_p),
                              p_drop)[:, :, :q.shape[1], :k.shape[1]]

        def loss_flash(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, dropout_p=p_drop,
                                   dropout_seed=seed)
            return jnp.sum(o ** 2)

        def loss_ref(q, k, v):
            s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
            msk = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
            s_ = jnp.where(msk[None, None], s_, -1e30)
            p_ = jax.nn.softmax(s_, axis=-1) * dm
            return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p_, v) ** 2)

        gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_sdpa_routes_dropout_to_flash(self, monkeypatch):
        from paddle_tpu.ops import pallas
        monkeypatch.setattr(pallas, "_enabled", lambda: True)
        taken = []
        real = fa.flash_attention
        monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: (
            taken.append(kw["dropout_p"]), real(*a, **kw))[1])
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        paddle.seed(4)
        q = paddle.to_tensor(np.random.randn(1, 16, 2, 16).astype(np.float32),
                             stop_gradient=False)
        k = paddle.to_tensor(np.random.randn(1, 16, 2, 16).astype(np.float32))
        v = paddle.to_tensor(np.ones((1, 16, 2, 16), np.float32))
        o_drop = F.scaled_dot_product_attention(q, k, v, dropout_p=0.9,
                                                training=True, is_causal=True)
        o_ref = F.scaled_dot_product_attention(q.detach(), k, v,
                                               dropout_p=0.0, is_causal=True)
        assert taken == [0.9, 0.0]
        assert not np.allclose(np.asarray(o_drop._data),
                               np.asarray(o_ref._data))
        o_drop.sum().backward()
        assert q.grad is not None


def test_decode_attention_stacked_i8_write_parity():
    """int8 fused write+attend: in-kernel quantization must be
    bit-identical to the host-side cache-quant write (int8 rows AND fp32
    scales), and the attention output must match quant-then-read."""
    from paddle_tpu.ops.pallas import decode_attention as da
    L, b, h, d, smax = 2, 3, 4, 32, 512
    rng = np.random.RandomState(7)
    cf = jnp.asarray(rng.randn(L, 2, b, h, smax, d), jnp.float32)
    amax = jnp.max(jnp.abs(cf), axis=-1, keepdims=True)
    sc = amax / 127.0
    c_i8 = jnp.clip(jnp.round(cf / jnp.maximum(sc, 1e-8)),
                    -127, 127).astype(jnp.int8)
    scales = jnp.swapaxes(sc, -1, -2)          # [L,2,B,H,1,Smax]
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    kv_new = jnp.asarray(rng.randn(2, b, h, 1, d), jnp.float32)
    lens = jnp.asarray([30, 255, 256], jnp.int32)

    def host_quant(row):
        r32 = row.astype(jnp.float32)
        am = jnp.max(jnp.abs(r32), axis=-1, keepdims=True)
        s = am / 127.0
        qv = jnp.clip(jnp.round(r32 / jnp.maximum(s, 1e-8)),
                      -127, 127).astype(jnp.int8)
        return qv, s

    for l in range(L):
        rc, rs = c_i8, scales
        for bi in range(b):
            for kv in range(2):
                qv, s = host_quant(kv_new[kv, bi, :, 0])   # [h,d],[h,1]
                rc = jax.lax.dynamic_update_slice(
                    rc, qv[None, None, None, :, None, :],
                    (l, kv, bi, 0, int(lens[bi]), 0))
                rs = jax.lax.dynamic_update_slice(
                    rs, s.reshape(1, 1, 1, h, 1, 1),
                    (l, kv, bi, 0, 0, int(lens[bi])))
        ref_o = da.decode_attention_stacked_i8(q, rc, rs, l, lens)
        gc, gs, go = da.decode_attention_stacked_i8_write(
            q, kv_new, c_i8, scales, l, lens)
        np.testing.assert_allclose(np.asarray(go), np.asarray(ref_o),
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_array_equal(np.asarray(gc), np.asarray(rc))
        np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                                   atol=1e-7)
