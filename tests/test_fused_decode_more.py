"""Compiled multi-layer fused decode vs the model-agnostic generate oracle,
third part (``tests/test_fused_decode.py`` has the model and the first): the
int8 weight and head flavors, the decode step's HLO under a mesh, the logit
controls, the cache-write kernel, the bulk prefill."""
import numpy as np
import pytest

import paddle_tpu as paddle
import jax
import jax.numpy as jnp

from paddle_tpu.inference.generation import (generate, generate_fused,
                                             FusedDecoder)
from test_fused_decode import L, TinyFusedLM, _prompt, needs8


class TestInt8Weights:
    def test_int8_weight_decode_matches_fp(self, monkeypatch):
        """PADDLE_TPU_DECODE_INT8_WEIGHTS=1 (reference: Predictor's
        weight-only int8 applied to the fused decode stack): greedy
        tokens must match the fp-weight run on a well-separated-logits
        model — per-out-channel absmax noise must not flip argmax."""
        paddle.seed(26)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=15)
        monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", raising=False)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8)
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", "1")
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_int8_weights_compose_with_int8_cache_and_beams(
            self, monkeypatch):
        """Both quant modes on simultaneously, under beam search — the
        full serving-lever stack must still match the fp beam run."""
        paddle.seed(27)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=17)
        monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", raising=False)
        monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_CACHE", raising=False)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6, num_beams=3)
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", "1")
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6, num_beams=3)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_env_flip_rebuilds_stack(self, monkeypatch):
        """The stacked-param cache is keyed on the quant env flag: a flip
        must rebuild (old behavior would silently reuse the fp stack)."""
        from paddle_tpu.inference.generation import FusedDecoder
        paddle.seed(28)
        m = TinyFusedLM()
        dec = FusedDecoder(m.fmt, m.embed, m.head, max_seq_len=32)
        monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", raising=False)
        s_fp = dec._stacked()
        assert "qkv_w_s" not in s_fp
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_WEIGHTS", "1")
        s_q = dec._stacked()
        assert "qkv_w_s" in s_q and s_q["qkv_w"].dtype == jnp.int8


class TestInt8Head:
    def test_int8_head_logits_near_exact_tokens_agree(self, monkeypatch):
        """PADDLE_TPU_DECODE_INT8_HEAD=1: the LM head (the largest single
        weight stream of the decode step) quantizes per vocab column.
        Unlike the cache/weight modes (whose noise washes through layer
        norms), head quant perturbs LOGITS directly, so on a random tiny
        model with near-uniform logits exact argmax match is not the
        contract — assert logits cosine ~1 and high token agreement."""
        paddle.seed(29)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=19)
        monkeypatch.delenv("PADDLE_TPU_DECODE_INT8_HEAD", raising=False)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8)
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_HEAD", "1")
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8)
        a, b = np.asarray(out._data), np.asarray(ref._data)
        agree = float((a == b).mean())
        assert agree >= 0.75, f"token agreement {agree}"
        # logits-level: dequantized head is near-exact
        from paddle_tpu.inference.generation import FusedDecoder
        dec = FusedDecoder(m.fmt, m.embed, m.head, max_seq_len=32)
        w = m.head.weight._data.astype(jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).randn(4, 1, w.shape[0]),
                        jnp.float32)
        qa = dec._maybe_quant_head([m.head.weight._data])
        assert qa[0].dtype == jnp.int8
        lq = (x @ qa[0].astype(x.dtype)) * qa[1].astype(x.dtype)
        lf = x @ w
        cos = float(jnp.sum(lq * lf) /
                    (jnp.linalg.norm(lq) * jnp.linalg.norm(lf)))
        assert cos > 0.9995, cos

    def test_full_int8_serving_stack_beams(self, monkeypatch):
        """Weights + cache quant under beam search — the exact-match
        half of the serving stack (head quant perturbs logits directly;
        its contract is the agreement test above)."""
        paddle.seed(30)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=21)
        for k in ("PADDLE_TPU_DECODE_INT8_HEAD",
                  "PADDLE_TPU_DECODE_INT8_CACHE",
                  "PADDLE_TPU_DECODE_INT8_WEIGHTS"):
            monkeypatch.delenv(k, raising=False)
        ref = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6, num_beams=3)
        for k in ("PADDLE_TPU_DECODE_INT8_CACHE",
                  "PADDLE_TPU_DECODE_INT8_WEIGHTS"):
            monkeypatch.setenv(k, "1")
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=6, num_beams=3)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


class TestTPDecodeHLO:
    @needs8
    def test_mp_decode_compiles_without_gathering_cache(self):
        """Compiled-HLO guard (pattern of test_moe_ep's all-to-all
        assertion): with q/cache sharded over 'mp' on the head axis, the
        shard_map'd stacked kernel must compile with ZERO all-gathers —
        head-parallel attention needs no collectives, and an all-gather
        would mean GSPMD replicated the cache (the exact failure the
        shard_map path exists to prevent)."""
        import jax
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.pallas import decode_attention as da
        L, b, h, d, smax = 2, 2, 4, 32, 128
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("mp",))
        hsp = P(None, "mp", None, None)
        csp = P(None, None, None, "mp", None, None)
        fn = jax.jit(shard_map(
            da.decode_attention_stacked, mesh=mesh,
            in_specs=(hsp, csp, P(), P()), out_specs=hsp,
            check_vma=False))
        q = jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32,
                                 sharding=NamedSharding(mesh, hsp))
        caches = jax.ShapeDtypeStruct((L, 2, b, h, smax, d), jnp.float32,
                                      sharding=NamedSharding(mesh, csp))
        lay = jax.ShapeDtypeStruct((), jnp.int32)
        lens = jax.ShapeDtypeStruct((b,), jnp.int32)
        hlo = fn.lower(q, caches, lay, lens).compile().as_text()
        assert "all-gather" not in hlo, "cache was gathered/replicated"
        assert "all-reduce" not in hlo


class TestLogitControls:
    """r5: reference generate() logit processors — min_length suppresses
    eos until N generated tokens; repetition_penalty penalizes every
    context token. Fused decode applies them INSIDE the compiled step
    (presence-mask carry) and must match the model-agnostic path."""

    def test_fused_matches_generate_with_controls(self):
        paddle.seed(31)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=23)
        kw = dict(max_new_tokens=10, eos_token_id=7, min_length=5,
                  repetition_penalty=1.3)
        ref = generate(m, paddle.to_tensor(ids), **kw)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, **kw)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_min_length_delays_eos(self):
        """Force a model whose argmax is eos immediately: min_length must
        hold eos out for exactly min_length tokens."""
        paddle.seed(32)
        m = TinyFusedLM()
        m.eval()
        # bias the head so eos (id 7) wins every step
        bias_w = np.asarray(m.head.weight._data).copy()
        bias_w[:, 7] += 100.0
        m.head.weight.set_value(bias_w)
        ids = _prompt(seed=25)
        out = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=8,
                             eos_token_id=7, min_length=4)
        gen = np.asarray(out._data)[:, ids.shape[1]:]
        assert (gen[:, :4] != 7).all(), gen   # suppressed while nt < 4
        assert (gen[:, 4] == 7).all(), gen    # first allowed step: eos

    def test_repetition_penalty_reduces_repeats(self):
        paddle.seed(33)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=27)
        plain = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                               head=m.head, max_new_tokens=12)
        pen = generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                             head=m.head, max_new_tokens=12,
                             repetition_penalty=2.0)

        def rep_frac(a):
            g = np.asarray(a._data)
            return np.mean([len(r) - len(set(r.tolist()))
                            for r in g]) / g.shape[1]
        assert rep_frac(pen) <= rep_frac(plain) + 1e-9


class TestKernelCacheWrite:
    """r5 s2: PADDLE_TPU_KERNEL_CACHE_WRITE=1 — the fused write+attend
    kernel lands the new K/V row in place (input_output_aliases) instead
    of an XLA-side dynamic_update_slice on the scan carry. Token parity
    with the default path across greedy, sampling, and beam decode, and
    the kernel path must actually be taken."""

    def _run(self, monkeypatch, on, **gen_kw):
        import paddle_tpu as paddle
        if on:
            monkeypatch.setenv("PADDLE_TPU_KERNEL_CACHE_WRITE", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_KERNEL_CACHE_WRITE",
                               raising=False)
        paddle.seed(61)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(seed=17)
        return generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                              head=m.head, max_seq_len=128, **gen_kw)

    def test_greedy_parity_and_path(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as da
        ref = self._run(monkeypatch, on=False, max_new_tokens=8)
        calls = []
        real = da.decode_attention_stacked_write

        def spy(*a, **k):
            calls.append(1)
            return real(*a, **k)
        monkeypatch.setattr(da, "decode_attention_stacked_write", spy)
        out = self._run(monkeypatch, on=True, max_new_tokens=8)
        assert calls, "write-kernel mode fell back to the DUS path"
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_beam_parity(self, monkeypatch):
        ref = self._run(monkeypatch, on=False, max_new_tokens=6,
                        num_beams=3)
        out = self._run(monkeypatch, on=True, max_new_tokens=6,
                        num_beams=3)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_int8_cache_parity(self, monkeypatch):
        from paddle_tpu.ops.pallas import decode_attention as da
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
        ref = self._run(monkeypatch, on=False, max_new_tokens=8)
        calls = []
        real = da.decode_attention_stacked_i8_write

        def spy(*a, **k):
            calls.append(1)
            return real(*a, **k)
        monkeypatch.setattr(da, "decode_attention_stacked_i8_write", spy)
        out = self._run(monkeypatch, on=True, max_new_tokens=8)
        assert calls, "int8 write-kernel mode fell back to the DUS path"
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))


class TestBulkPrefill:
    """r5 s2: PADDLE_TPU_BULK_PREFILL=1 — whole-prompt prefill (causal
    flash over [B, S], cache built by padding the K/V scan output; no
    per-token scan, no DUS). Token parity with the chunked per-token
    prefill across greedy, rotary, int8-cache, and beam modes."""

    def _run(self, monkeypatch, bulk, rotary=False, **gen_kw):
        import paddle_tpu as paddle
        if bulk:
            monkeypatch.setenv("PADDLE_TPU_BULK_PREFILL", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_BULK_PREFILL", raising=False)
        paddle.seed(71)
        m = TinyFusedLM()
        m.eval()
        ids = _prompt(b=2, s=33, seed=21)
        return generate_fused(m.fmt, paddle.to_tensor(ids), embed=m.embed,
                              head=m.head, max_seq_len=128,
                              use_rotary=rotary, **gen_kw)

    @pytest.mark.parametrize("rotary", [False, True])
    def test_greedy_parity(self, monkeypatch, rotary):
        ref = self._run(monkeypatch, bulk=False, rotary=rotary,
                        max_new_tokens=8)
        out = self._run(monkeypatch, bulk=True, rotary=rotary,
                        max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))

    def test_int8_cache_and_beam_parity(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DECODE_INT8_CACHE", "1")
        ref = self._run(monkeypatch, bulk=False, max_new_tokens=6,
                        num_beams=3)
        out = self._run(monkeypatch, bulk=True, max_new_tokens=6,
                        num_beams=3)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ref._data))
