"""Ring attention + Ulysses context parallelism vs serial attention.

SURVEY §5.7: the reference-era long-context stack (sep axis / Ulysses
alltoall; ring attention from the ecosystem). Oracle is dense softmax
attention computed serially — the same serial-vs-parallel allclose pattern
the reference's fleet tests use (SURVEY §4).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.context_parallel import (
    make_ring_attention_fn, make_ulysses_attention_fn)


def dense_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None])
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def make_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sep",))


def rand_qkv(b=2, s=64, h=4, d=8, hk=None, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hk or h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hk or h, d), jnp.float32)
    return q, k, v


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n", [4, 8])
    def test_matches_dense(self, causal, n):
        mesh = make_mesh(n)
        q, k, v = rand_qkv()
        ref = dense_attention(q, k, v, causal=causal)
        fn = jax.jit(make_ring_attention_fn(mesh, causal=causal))
        out = fn(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        mesh = make_mesh(4)
        q, k, v = rand_qkv(h=8, hk=2)
        ref = dense_attention(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                              causal=True)
        out = jax.jit(make_ring_attention_fn(mesh, causal=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_dense(self):
        mesh = make_mesh(4)
        q, k, v = rand_qkv(s=32)

        def loss_ring(q, k, v):
            return jnp.sum(make_ring_attention_fn(mesh, causal=True)(
                q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=3e-4)


class TestRingAttentionLongContext:
    """VERDICT r4 #5: ring-vs-dense at S well beyond a single ring chunk
    (S=2048 over 8 devices = 256-token chunks, multiple flash tiles per
    chunk) — the long-context orchestration §5.7 exists for, fwd + bwd."""

    def test_long_seq_matches_dense_fwd_bwd(self):
        mesh = make_mesh(8)
        q, k, v = rand_qkv(b=1, s=2048, h=2, d=32, seed=3)
        ref = dense_attention(q, k, v, causal=True)
        fn = jax.jit(make_ring_attention_fn(mesh, causal=True))
        out = fn(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)

        def loss_ring(q, k, v):
            return jnp.mean(make_ring_attention_fn(mesh, causal=True)(
                q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.mean(dense_attention(q, k, v, causal=True) ** 2)
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-3)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        mesh = make_mesh(4)
        q, k, v = rand_qkv(h=8)
        ref = dense_attention(q, k, v, causal=causal)
        out = jax.jit(make_ulysses_attention_fn(mesh, causal=causal))(
            q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients(self):
        mesh = make_mesh(4)
        q, k, v = rand_qkv(s=32, h=4)

        def loss_u(q, k, v):
            return jnp.sum(make_ulysses_attention_fn(mesh, causal=True)(
                q, k, v) ** 2)

        def loss_d(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        gu = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gu, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=3e-4)


class TestLlamaContextParallel:
    def test_llama_ring_matches_dense(self):
        """llama with context_parallel=True on a sep mesh == dense llama
        with identical weights (SURVEY §5.7 long-context first-class)."""
        import paddle_tpu as paddle
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models.llama import llama_tiny

        paddle.seed(5)
        dense = llama_tiny(tensor_parallel=False)
        paddle.seed(5)
        ring = llama_tiny(tensor_parallel=False, context_parallel=True)
        for a, b in zip(dense.parameters(), ring.parameters()):
            np.testing.assert_array_equal(np.asarray(a._data),
                                          np.asarray(b._data))

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                                   "pp_degree": 1, "sharding_degree": 1,
                                   "sep_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        try:
            x = paddle.to_tensor(np.random.RandomState(0).randint(
                0, 256, (2, 32)).astype(np.int32))
            dense.eval(); ring.eval()
            with paddle.no_grad():      # each forward one compiled program
                out_d = paddle.jit.to_static(dense)(x)
                out_r = paddle.jit.to_static(ring)(x)
            np.testing.assert_allclose(np.asarray(out_r._data),
                                       np.asarray(out_d._data),
                                       atol=3e-5, rtol=3e-5)
        finally:
            from paddle_tpu.distributed.fleet.base.topology import \
                _HYBRID_GROUP
            _HYBRID_GROUP[0] = None


def _compare_kernel_vs_composite(monkeypatch, make_fn, kernel_env,
                                 composite_env, seed):
    """Shared A/B harness for the sep-parallel kernel paths: build the
    REAL production wrapper twice (kernel env vs composite env), compare
    fwd outputs and grad-of-sum-of-squares for q/k/v."""
    import jax
    from jax.sharding import Mesh
    import paddle_tpu.parallel.context_parallel as cp
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sep",))
    B, S, H, D = 2, 256, 4, 64
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    monkeypatch.setenv(*kernel_env)
    fn_k = jax.jit(make_fn(cp, mesh))
    out_k = fn_k(q, k, v)
    gk = jax.grad(lambda *a: jnp.sum(fn_k(*a) ** 2), (0, 1, 2))(q, k, v)
    monkeypatch.delenv(kernel_env[0])
    monkeypatch.setenv(*composite_env)
    fn_c = jax.jit(make_fn(cp, mesh))
    out_c = fn_c(q, k, v)
    gc_ = jax.grad(lambda *a: jnp.sum(fn_c(*a) ** 2), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(gk, gc_):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
    return (B, S, H, D)


class TestRingKernelCombinedCPU:
    def test_ring_with_pallas_kernel_matches_composite(self, monkeypatch):
        """r4 weak #3: the COMBINED ring-schedule + Pallas chunk-kernel
        path used to be untestable off-chip (pallas-in-shard_map tripped
        jax's check_vma); with _cp_fn disabling the check off-chip it
        runs on the CPU mesh — fwd AND bwd must match the composite."""
        import paddle_tpu.parallel.context_parallel as cp
        monkeypatch.setenv("PADDLE_TPU_RING_KERNEL_CPU", "1")
        # pin that the kernel path is actually taken (not a vacuous
        # composite-vs-composite comparison)
        assert cp._use_ring_kernel(
            jnp.zeros((2, 64, 4, 64), jnp.float32),
            jnp.zeros((2, 64, 4, 64), jnp.float32))
        _compare_kernel_vs_composite(
            monkeypatch,
            lambda cp_, mesh: cp_.make_ring_attention_fn(mesh,
                                                         causal=True),
            ("PADDLE_TPU_RING_KERNEL_CPU", "1"),
            ("PADDLE_TPU_RING_COMPOSITE", "1"), seed=1)


class TestUlyssesFlash:
    def test_ulysses_flash_matches_composite(self, monkeypatch):
        """r5: the per-device full-sequence attention inside Ulysses
        streams the flash kernel (the dense composite materializes
        O(S^2) scores — the failure mode sep parallelism exists to
        avoid). Kernel path vs composite, fwd AND bwd, through the real
        production wrapper; _chunk_attn is boobytrapped on the kernel
        build so a dead flash gate cannot pass vacuously."""
        import paddle_tpu.parallel.context_parallel as cp
        from paddle_tpu.ops.pallas import flash_attention as fa
        assert fa.is_supported((2, 256, 1, 64), jnp.float32)

        orig = cp._chunk_attn
        state = {"trap": True}

        def trap(*a, **k):
            if state["trap"]:
                raise AssertionError(
                    "Ulysses fell back to the dense composite while the "
                    "flash path was requested")
            return orig(*a, **k)
        monkeypatch.setattr(cp, "_chunk_attn", trap)
        monkeypatch.delenv("PADDLE_TPU_ULYSSES_COMPOSITE", raising=False)

        def make(cp_, mesh):
            return cp_.make_ulysses_attention_fn(mesh, causal=True)

        import jax
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sep",))
        B, S, H, D = 2, 256, 4, 64
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        monkeypatch.setenv("PADDLE_TPU_ULYSSES_FLASH_CPU", "1")
        fn_k = jax.jit(make(cp, mesh))
        out_k = fn_k(q, k, v)          # trap armed: composite would raise
        gk = jax.grad(lambda *a: jnp.sum(fn_k(*a) ** 2), (0, 1, 2))(
            q, k, v)
        state["trap"] = False
        monkeypatch.setenv("PADDLE_TPU_ULYSSES_COMPOSITE", "1")
        fn_c = jax.jit(make(cp, mesh))
        out_c = fn_c(q, k, v)
        gc_ = jax.grad(lambda *a: jnp.sum(fn_c(*a) ** 2), (0, 1, 2))(
            q, k, v)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(gk, gc_):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)


class TestModelUlyssesOption:
    @pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
    def test_llama_context_parallel_ulysses_matches_dense(self):
        """r5: context_parallel='ulysses' at the model level runs the
        reference sep scheme (head-scatter all_to_all) — loss must match
        the no-mesh dense run."""
        import paddle_tpu as paddle
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        def build(cp):
            paddle.seed(40)
            return LlamaForCausalLM(LlamaConfig(
                vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                intermediate_size=128, max_position=256,
                context_parallel=cp))

        ids = np.random.RandomState(8).randint(0, 128, (2, 64)).astype(
            np.int32)
        x, y = paddle.to_tensor(ids), paddle.to_tensor(ids)
        ref = build(False)
        with paddle.no_grad():          # each forward one compiled program
            loss_ref = float(np.asarray(
                paddle.jit.to_static(ref)(x, labels=y)._data))

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                                   "pp_degree": 1, "sharding_degree": 1,
                                   "sep_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        m = build("ulysses")
        with paddle.no_grad():
            loss_u = float(np.asarray(
                paddle.jit.to_static(m)(x, labels=y)._data))
        np.testing.assert_allclose(loss_u, loss_ref, rtol=2e-5)
        # and the scheme actually selected ulysses
        attn = m.llama.layers[0].self_attn
        attn._ring_fn()
        assert attn._ring_cache[2] == "ulysses"
