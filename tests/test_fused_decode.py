"""Compiled multi-layer fused decode vs the model-agnostic generate oracle:
greedy, eos, tensor parallel, rotary, the int8 cache. Beams are
``tests/test_fused_decode_beams.py``'s; the weight and head flavors, the logit
controls, the cache-write kernel and the bulk prefill
``tests/test_fused_decode_more.py``'s (one file was the run's second longest
chain).

Parity target: fused_multi_transformer_op.cu's decode driver — same tokens
as re-running the full forward on the growing prefix (the reference's
correctness contract for the fused path).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import jax

from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference.generation import (generate, generate_fused,
                                             FusedDecoder)
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.nn.layer.layers import Layer

V, E, H, FF, L = 97, 32, 4, 64, 3

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 virtual devices")


class TinyFusedLM(Layer):
    def __init__(self):
        super().__init__()
        self.embed = Embedding(V, E)
        self.fmt = FusedMultiTransformer(E, H, FF, num_layers=L,
                                         normalize_before=True)
        self.head = Linear(E, V, bias_attr=False)

    # the oracle re-runs this on every prefix length: one compiled program
    # a length, where eager is some sixty one-operation compiles a length
    @paddle.jit.to_static
    def forward(self, ids):
        return self.head(self.fmt(self.embed(ids)))


def _prompt(b=2, s=5, seed=0):
    return np.random.RandomState(seed).randint(1, V, (b, s)).astype(np.int32)


class TestFusedDecode:
    def test_matches_oracle_greedy(self):
        paddle.seed(3)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt()
        ref = generate(m, paddle.to_tensor(ids), max_new_tokens=6)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                            head=m.head, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_decoder_reuse_one_executable(self):
        paddle.seed(4)
        m = TinyFusedLM()
        m.eval()
        dec = FusedDecoder(m.fmt, m.embed, m.head, max_seq_len=32)
        ids = _prompt(seed=1)
        out1 = dec.generate(paddle.to_tensor(ids), max_new_tokens=4)
        cache1 = dict(dec._scan_cache)
        assert cache1                       # scan variants compiled
        out2 = dec.generate(paddle.to_tensor(_prompt(seed=2)),
                            max_new_tokens=4)
        # same chunk ladder -> every compiled scan variant reused, none added
        assert dec._scan_cache == cache1 and all(
            dec._scan_cache[k] is cache1[k] for k in cache1)
        assert out1.shape[1] == ids.shape[1] + 4
        assert out2.shape[1] == ids.shape[1] + 4

    def test_eos_mid_chunk_matches_generate(self):
        """Force eos to fire INSIDE a scan chunk: the trailing all-eos
        padding the chunk produces must be trimmed so output matches
        generate()'s per-token early stop exactly."""
        paddle.seed(9)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=4)
        free = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                              head=m.head, max_new_tokens=8)
        eos = int(np.asarray(free._data)[0, ids.shape[1] + 2])  # 3rd token
        ref = generate(m, paddle.to_tensor(ids), max_new_tokens=8,
                       eos_token_id=eos)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8,
                             eos_token_id=eos)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_eos_early_stop(self):
        paddle.seed(5)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=3)
        ref = generate(m, paddle.to_tensor(ids), max_new_tokens=8,
                       eos_token_id=7)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                            head=m.head, max_new_tokens=8, eos_token_id=7)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


@needs8
class TestFusedDecodeTP:
    def test_mp_sharded_heads_match(self):
        """Under an mp=4 mesh the decode step compiles SPMD with the head
        dim sharded; tokens must match the no-mesh run exactly."""
        from paddle_tpu.distributed import fleet
        paddle.seed(6)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=4)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=5)

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                                   "pp_degree": 1, "sharding_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


class TestFusedDecodeRotary:
    def test_rotary_prefill_decode_consistent(self):
        """use_rotary: prefill must rotate cached prompt K exactly as the
        decode step rotates new tokens — oracle is the eager fused stack
        with rotary_embs on the growing prefix."""
        paddle.seed(9)
        m = TinyFusedLM()
        m.eval()

        class RotaryLM(Layer):
            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            @paddle.jit.to_static       # as TinyFusedLM's: a program a length
            def forward(self, ids):
                h = self.inner.embed(ids)
                h = self.inner.fmt(h, rotary_embs=True)
                return self.inner.head(h)

        ids = _prompt(seed=5)
        ref = generate(RotaryLM(m), paddle.to_tensor(ids), max_new_tokens=6)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6, use_rotary=True)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


class TestFusedDecodeHygiene:
    def test_greedy_does_not_consume_rng(self):
        paddle.seed(11)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=6)
        from paddle_tpu.core.rng import next_key
        paddle.seed(123)
        generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                       head=m.head, max_new_tokens=4)
        k_after_fused = np.asarray(jax.random.key_data(next_key()))
        paddle.seed(123)
        k_ref = np.asarray(jax.random.key_data(next_key()))
        np.testing.assert_array_equal(k_after_fused, k_ref)

    def test_decode_does_not_clobber_pending_tape(self):
        paddle.seed(12)
        m = TinyFusedLM()
        lin = paddle.nn.Linear(4, 1)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = (lin(x) ** 2).mean()          # pending backward graph
        m.eval()
        generate_fused(m.fmt, paddle.to_tensor(_prompt(seed=7)),
                       embed=m.embed, head=m.head, max_new_tokens=3)
        loss.backward()                      # must still produce grads
        assert lin.weight.grad is not None
        assert float(np.abs(np.asarray(lin.weight.grad._data)).sum()) > 0


class TestInt8Cache:
    def test_int8_cache_decode_matches_fp(self, monkeypatch):
        """PADDLE_TPU_DECODE_INT8_CACHE=1 (the reference's cache_kv int8
        serving mode): generated tokens must match the fp cache run on a
        well-separated-logits model — quantization noise (cos>0.999 at
        the kernel level) must not flip greedy argmax here."""
        paddle.seed(12)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=12)
        monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_CACHE", raising=False)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8)
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


class TestWeightSwapRestack:
    def test_weight_swap_releases_old_stack(self):
        """r4 verdict weak #7: the stacked-param cache must not pin the
        PREVIOUS parameter arrays alive across a weight swap (loading a
        new checkpoint into the same decoder) — at serving scale that is
        a full dead model copy held in HBM. The identity anchors are
        weakrefs: after a swap the old arrays must be collectable, and a
        restack must produce the new values."""
        import gc
        import weakref
        from paddle_tpu.inference.generation import FusedDecoder
        paddle.seed(21)
        m = TinyFusedLM()
        dec = FusedDecoder(m.fmt, m.embed, m.head, max_seq_len=32)
        stk1 = dec._stacked()
        old_w = m.fmt.qkv_weights[0]._data
        wr = weakref.ref(old_w)
        v1 = np.asarray(stk1["qkv_w"][0])

        # swap every parameter to a fresh array (checkpoint-load shape)
        for p in m.fmt.parameters():
            p._data = p._data + 1.0
        del old_w, stk1
        gc.collect()
        assert wr() is None, (
            "old parameter array still pinned after weight swap")

        stk2 = dec._stacked()
        v2 = np.asarray(stk2["qkv_w"][0])
        np.testing.assert_allclose(v2, v1 + 1.0, rtol=1e-6)
        # cache hit on the NEW identities (no rebuild churn)
        assert dec._stacked() is stk2


class TestTPKernelDecode:
    @needs8
    @pytest.mark.parametrize("int8", [False, True])
    def test_mp2_streams_kernel_not_fallback(self, monkeypatch, int8):
        """r5 (reference: mp-sharded heads in fused_multi_transformer_op
        .cu): under an mp>=2 mesh the stacked decode kernel must run
        TP-sharded via shard_map — numeric token parity with the no-mesh
        run AND the kernel path (not the dense fallback) taken. The int8
        cache composes (stack + scales both shard on the head axis)."""
        from paddle_tpu.distributed import fleet
        from paddle_tpu.ops.pallas import decode_attention as da
        if int8:
            monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_CACHE",
                               raising=False)
        paddle.seed(22)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=9)
        # smax=128 so the kernel's Smax tiling rule holds (bk in 256/128)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6,
                             max_seq_len=128)

        kernel_calls = []
        real = (da.decode_attention_stacked_i8 if int8
                else da.decode_attention_stacked)
        name = ("decode_attention_stacked_i8" if int8
                else "decode_attention_stacked")

        def spy(*a, **k):
            kernel_calls.append(1)
            return real(*a, **k)
        monkeypatch.setattr(da, name, spy)

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                                   "pp_degree": 1, "sharding_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6,
                             max_seq_len=128)
        assert kernel_calls, (
            "mp decode took the dense fallback, not the shard_map kernel")
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))
