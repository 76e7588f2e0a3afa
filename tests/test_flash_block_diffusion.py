"""The block-diffusion mask inside the flash kernels (interpret mode on the
CPU): values and dq, dk, dv against the dense-mask composite; the count of
visited tiles against a brute-force count; the causal and unmasked paths
bit-equal to what they gave before the mask existed; the one dispatch; and
the one-pass backward (several tiles a plane, dQ summed in VMEM beside dK and
dV) bit-equal to the dK/dV + dQ pair it replaces."""
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.ops.pallas as pallas
from paddle_tpu.inference import telemetry
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional.attention import _sdpa_ref
from paddle_tpu.ops.pallas import flash_attention as fa


def _rule(seq, b):
    """The rule written out from its definition, [2 seq, 2 seq] bool."""
    pos = np.arange(2 * seq)
    beta, noisy = (pos % seq) // b, pos < seq
    return np.where(
        noisy[:, None],
        np.where(noisy[None], beta[None] == beta[:, None],
                 beta[None] < beta[:, None]),
        ~noisy[None] & (beta[None] <= beta[:, None]))


def _qkv(seq, h, hk, d, dtype=jnp.float32, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((batch, 2 * seq, n, d)),
                             dtype) for n in (h, hk, hk)) + (
        jnp.asarray(rng.standard_normal((batch, 2 * seq, h, d)),
                    jnp.float32),)


def _counts():
    return tuple(telemetry.runtime_counter(f"paddle_flash_tiles_{n}total")
                 for n in ("visited_", ""))


@pytest.mark.parametrize("seq,b", [(64, 4), (100, 32), (37, 3), (128, 128)])
def test_dense_mask_is_the_rule(seq, b):
    mask = fa.block_diffusion_mask(seq, b)
    np.testing.assert_array_equal(np.asarray(fa.dense_mask(mask)),
                                  _rule(seq, b))


# seq a multiple of the tile and not; one tile a side (the fused backward)
# and several (the one-pass backward); 8 query heads on one KV head
@pytest.mark.parametrize("seq,b,h,hk,d,tiles", [
    (64, 4, 4, 4, 64, None),            # one 128-wide tile: fused backward
    (96, 32, 8, 1, 64, (64, 64)),       # 3 x 3 tiles, GQA 8:1
    (100, 4, 8, 1, 128, (64, 64)),      # 200 positions: a padded tail
    (128, 4, 2, 2, 256, (64, 64)),      # tiles end on the halves' boundary
    (128, 32, 2, 1, 128, (32, 32)),     # a block spans a whole tile
    (60, 3, 2, 1, 64, (32, 32)),        # blocks that are no power of two
])
def test_kernels_match_the_dense_mask_composite(seq, b, h, hk, d, tiles,
                                                monkeypatch):
    if tiles:
        monkeypatch.setattr(fa, "_block_sizes", lambda sq, sk, d=64: tiles)
    q, k, v, w = _qkv(seq, h, hk, d)
    mask = fa.block_diffusion_mask(seq, b)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask)

    def composite(q, k, v):
        return _sdpa_ref(q, k, v, None, 0.0, False, None,
                         structured_mask=mask)

    before = _counts()
    got = kernel(q, k, v)
    visited, total = (a - b_ for a, b_ in zip(_counts(), before))
    want = composite(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)
             for f in (kernel, composite)]
    for g, g_want in zip(*grads):
        np.testing.assert_allclose(g, g_want, atol=5e-5)
    # the forward's grid: a brute-force count of the tiles that hold an
    # allowed entry, times batch and heads
    bq, bk = fa._block_sizes(2 * seq, 2 * seq, d)
    rule = _rule(seq, b)
    n = -(-2 * seq // bq)
    brute = sum(bool(rule[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any())
                for i in range(n) for j in range(n))
    assert (visited, total) == (2 * h * brute, 2 * h * n * n)
    assert brute < n * n or n == 1


def test_visited_tiles_of_the_benchmark_cell_and_of_the_other_masks():
    """The count is made from the static grid when a kernel is traced: 80
    of 256 tiles a head at 8,192 tokens and 1024-wide tiles; a causal mask
    the lower triangle; no mask all."""
    shape = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16)
    for mask, causal, want in ((fa.block_diffusion_mask(8192, 4), False, 80),
                               (None, True, 136), (None, False, 256)):
        before = _counts()
        jax.eval_shape(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, mask=mask), shape, shape, shape)
        visited, total = (a - b for a, b in zip(_counts(), before))
        assert (visited, total) == (2 * want, 2 * 256)


def test_bf16_under_the_mask(monkeypatch):
    monkeypatch.setattr(fa, "_block_sizes", lambda sq, sk, d=64: (64, 64))
    q, k, v, w = _qkv(96, 8, 1, 128, jnp.bfloat16)
    mask = fa.block_diffusion_mask(96, 4)
    got = fa.flash_attention(q, k, v, mask=mask).astype(jnp.float32)
    want = _sdpa_ref(*(a.astype(jnp.float32) for a in (q, k, v)), None, 0.0,
                     False, None, structured_mask=mask)
    assert float(jnp.abs(got - want).max()) < 0.03


def _digest(causal, s, h, hk, d, tiles, dtype, monkeypatch):
    rng = np.random.default_rng(1234)
    q, k, v = (jnp.asarray(rng.standard_normal((2, s, n, d)), dtype)
               for n in (h, hk, hk))
    w = jnp.asarray(rng.standard_normal((2, s, h, d)), jnp.float32)
    if tiles:
        monkeypatch.setattr(fa, "_block_sizes", lambda sq, sk, d=64: tiles)

    def f(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal)
    out = (f(q, k, v),) + jax.grad(lambda *a: jnp.sum(
        f(*a).astype(jnp.float32) * w), (0, 1, 2))(q, k, v)
    m = hashlib.sha256()
    for a in out:
        m.update(np.asarray(a.astype(jnp.float32)).tobytes())
    return m.hexdigest()[:16]


# digests of o, dq, dk, dv taken on the parent commit (before the kernels
# knew a third mask) on these inputs: the causal and the unmasked path give
# bit-equal results still
@pytest.mark.parametrize("case,want", [
    ((True, 128, 4, 4, 64, None, "float32"), "013c3a38e9ee6fc1"),
    ((False, 128, 4, 2, 64, None, "float32"), "63f64f0e2f585f1f"),
    ((True, 200, 4, 2, 32, (64, 64), "float32"), "5e5d9662d52948c2"),
    ((False, 200, 4, 2, 32, (64, 64), "float32"), "d46508ecf0def84a"),
    ((True, 256, 2, 1, 128, (128, 128), "bfloat16"), "d885654125799c6a"),
])
def test_causal_and_unmasked_paths_are_bit_equal_to_the_parents(
        case, want, monkeypatch):
    assert _digest(*case, monkeypatch) == want


def _dense(q, k, v, causal, mask, keep=None):
    """Dense float32 attention on [B, S, H, D]; ``keep`` the scaled dropout
    multiplier [B, H, Sq, Sk] on the probabilities."""
    qt, kt, vt = (jnp.swapaxes(x.astype(jnp.float32), 1, 2)
                  for x in (q, k, v))
    group = qt.shape[1] // kt.shape[1]
    kt, vt = jnp.repeat(kt, group, 1), jnp.repeat(vt, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * q.shape[-1] ** -0.5
    sq, sk = s.shape[-2:]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq), s,
                      -jnp.inf)
    if mask is not None:
        s = jnp.where(fa.dense_mask(mask), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = p * keep
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


_ONEPASS_CASES = {
    # sq, sk, query heads, KV heads, head size, causal, block, dtype, dropout
    "unmasked_f32": (192, 192, 4, 4, 64, False, None, "float32", 0.0),
    "causal_f32_gqa8": (192, 192, 8, 1, 64, True, None, "float32", 0.0),
    "blockdiff_f32_gqa8": (192, 192, 8, 1, 128, False, 4, "float32", 0.0),
    "causal_f32_ragged": (200, 200, 4, 2, 32, True, None, "float32", 0.0),
    "blockdiff_f32_ragged": (200, 200, 2, 2, 64, False, 4, "float32", 0.0),
    "causal_f32_sq_lt_sk": (100, 200, 4, 2, 64, True, None, "float32", 0.0),
    "unmasked_f32_sq_gt_sk": (256, 128, 2, 2, 64, False, None, "float32", 0.0),
    "unmasked_f32_cross": (128, 200, 4, 4, 64, False, None, "float32", 0.0),
    "unmasked_bf16": (192, 192, 4, 4, 64, False, None, "bfloat16", 0.0),
    "causal_bf16_gqa8": (192, 192, 8, 1, 128, True, None, "bfloat16", 0.0),
    "blockdiff_bf16_gqa8": (192, 192, 8, 1, 128, False, 4, "bfloat16", 0.0),
    "causal_f32_dropout": (192, 192, 4, 2, 64, True, None, "float32", 0.3),
    "unmasked_bf16_ragged_dropout": (200, 200, 4, 4, 64, False, None,
                                     "bfloat16", 0.1),
}


@pytest.mark.parametrize("case", list(_ONEPASS_CASES))
def test_one_pass_backward_is_the_pair_bit_for_bit(case, monkeypatch):
    """3-4 tiles of 64 a side: dq, dk, dv of ``flash_attention_bwd_onepass``
    are the bits of the dK/dV + dQ pair (every sum in the same order) and
    stand within the file's tolerance of the dense composite's; the dropout
    cases draw the keep mask the kernels get (the array form, which
    interpret mode takes), and the kernel counts ONE grid's tiles."""
    sq, sk, h, hk, d, causal, block, dtype, dropout_p = _ONEPASS_CASES[case]
    monkeypatch.setattr(fa, "_block_sizes", lambda sq, sk, d=64: (64, 64))
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((2, s, n, d)), dtype)
               for s, n in ((sq, h), (sk, hk), (sk, hk)))
    w = jnp.asarray(rng.standard_normal((2, sq, h, d)), jnp.float32)
    mask = block and fa.block_diffusion_mask(sq // 2, block)
    seed = jnp.int32(9)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, mask=mask,
                                  dropout_p=dropout_p, dropout_seed=seed)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w),
                        (0, 1, 2))(q, k, v)

    names = [f"paddle_flash_bwd_{n}_traces_total" for n in ("onepass",
                                                            "split")]
    before = [telemetry.runtime_counter(n) for n in names] + list(_counts())
    got = grads(kernel)
    moved = [a - b for a, b in zip(
        [telemetry.runtime_counter(n) for n in names] + list(_counts()),
        before)]
    nq, nk = -(-sq // 64), -(-sk // 64)
    # the forward's grid and the backward's ONE grid, batch 2
    assert moved[:2] == [1, 0] and moved[3] == 2 * 2 * h * nq * nk
    monkeypatch.setattr(fa, "_bwd_onepass", fa._bwd_pair)
    before = _counts()[1]
    two = grads(kernel)
    assert _counts()[1] - before == 3 * 2 * h * nq * nk   # forward + the pair
    for a, b in zip(got, two):
        assert a.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
    keep = None
    if dropout_p:
        keep = fa._dropout_mask(seed, (2, h, nq * 64, nk * 64),
                                dropout_p)[:, :, :sq, :sk]
    want = grads(lambda q, k, v: _dense(q, k, v, causal, mask, keep))
    for a, b in zip(got, want):
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=5e-5)
        else:
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
            assert np.linalg.norm(a - b) < 0.01 * np.linalg.norm(b)


def test_one_pass_backward_traces_the_in_kernel_dropout(monkeypatch):
    """On the chip the keep mask is drawn inside the kernels from ``(seed,
    b, h, q tile, k tile)``; the hardware generator has no CPU form, so what
    can be held here is that the one-pass kernel traces with it, one seed
    word in SMEM, the tile's pair as the forward folds it."""
    monkeypatch.setattr(pallas, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((2, 4096, 12, 64), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, causal=True, dropout_p=0.1).astype(
            jnp.float32)), (0, 1, 2)))(q, q, q))
    assert re.findall(r"flash_attention_\w+", jaxpr) == [
        "flash_attention_fwd", "flash_attention_bwd_onepass"]
    # one draw a kernel: the forward's and the backward's
    assert jaxpr.count("prng_seed") == 2 == jaxpr.count("prng_random_bits")


def test_what_the_kernel_refuses():
    mask = fa.block_diffusion_mask(64, 4)
    ok = ((2, 128, 4, 64), jnp.bfloat16, mask, (2, 128, 2, 64))
    assert fa.is_supported(*ok)
    assert not fa.is_supported(*ok, dropout_p=0.1)
    assert not fa.is_supported((2, 96, 4, 64), jnp.bfloat16, mask)
    assert not fa.is_supported((2, 128, 4, 64), jnp.bfloat16, mask,
                               (2, 64, 4, 64))
    assert not fa.is_supported((2, 128, 4, 64), jnp.bfloat16,
                               ("sliding_window", 64, 4))
    q, k, v, _ = _qkv(64, 4, 4, 64)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, causal=True, mask=mask)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, dropout_p=0.1, mask=mask)
    with pytest.raises(ValueError):
        fa.flash_attention(q[:, :100], k[:, :100], v[:, :100], mask=mask)
    with pytest.raises(ValueError):
        fa.block_diffusion_mask(64, 0)


def test_sdpa_dispatches_the_structured_mask_in_one_place(monkeypatch):
    """``scaled_dot_product_attention(structured_mask=)``: the kernel where
    ``is_supported`` takes it, the composite's dense mask elsewhere (off the
    chip, with dropout), one counter each; never beside another mask."""
    q, k, v, w = _qkv(64, 4, 2, 64)
    mask = fa.block_diffusion_mask(64, 4)
    names = [f"paddle_flash_mask_{n}_traces_total"
             for n in ("kernel", "composite")]

    def traces():
        return [telemetry.runtime_counter(n) for n in names]

    def run(**kw):
        ts = [paddle.to_tensor(np.asarray(a)) for a in (q, k, v)]
        for t in ts:
            t.stop_gradient = False
        out = F.scaled_dot_product_attention(*ts, structured_mask=mask, **kw)
        (out * paddle.to_tensor(np.asarray(w))).sum().backward()
        return np.asarray(out._data), [np.asarray(t.grad._data) for t in ts]

    t0 = traces()
    off_chip, off_grads = run()
    assert [a - b for a, b in zip(traces(), t0)] == [0, 1]
    monkeypatch.setattr(pallas, "_enabled", lambda: True)
    t0 = traces()
    on_chip, on_grads = run()
    assert [a - b for a, b in zip(traces(), t0)] == [1, 0]
    np.testing.assert_allclose(on_chip, off_chip, atol=2e-5)
    for g, g_want in zip(on_grads, off_grads):
        np.testing.assert_allclose(g, g_want, atol=5e-5)
    t0 = traces()
    run(dropout_p=0.1)                  # dropout under the mask: composite
    assert [a - b for a, b in zip(traces(), t0)] == [0, 1]
    run(dropout_p=0.1, training=False)  # no dropout in eval mode: kernel
    assert traces()[0] - t0[0] == 1
    ts = [paddle.to_tensor(np.asarray(a)) for a in (q, k, v)]
    with pytest.raises(ValueError):
        F.scaled_dot_product_attention(*ts, structured_mask=mask,
                                       is_causal=True)
    with pytest.raises(ValueError):
        F.scaled_dot_product_attention(
            *ts, structured_mask=mask,
            attn_mask=paddle.to_tensor(np.ones((128, 128), bool)))
