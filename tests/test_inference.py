"""Inference engine: Config/Predictor handles (AnalysisPredictor parity,
paddle/fluid/inference/api/analysis_predictor.cc) + generation loops."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (Config, PrecisionType, create_predictor)
from paddle_tpu.inference.generation import generate


def small_lm():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=32,
                                    num_layers=2, num_heads=2,
                                    max_position=64, dropout=0.0))


class TestPredictor:
    def test_run_direct_api(self):
        model = small_lm()
        cfg = Config()
        cfg.set_model_obj(model)
        pred = create_predictor(cfg)
        x = np.random.RandomState(0).randint(0, 97, (2, 8)).astype(np.int32)
        outs = pred.run([x])
        assert outs[0].shape == (2, 8, 97)

    def test_handle_api_and_reuse(self):
        model = small_lm()
        cfg = Config()
        cfg.set_model_obj(model)
        pred = create_predictor(cfg)
        x = np.random.RandomState(1).randint(0, 97, (1, 4)).astype(np.int32)
        h = pred.get_input_handle("x")
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
        assert out.shape == (1, 4, 97)
        # deterministic eval: same input, same output
        pred.run()
        out2 = pred.get_output_handle(
            pred.get_output_names()[0]).copy_to_cpu()
        np.testing.assert_allclose(out, out2)

    def test_save_load_roundtrip(self, tmp_path):
        model = small_lm()
        path = str(tmp_path / "m")
        paddle.jit.save(model, path)
        cfg = Config(path)
        pred = create_predictor(cfg)
        # TranslatedLayer isn't callable as the model class; rebind params
        model2 = small_lm()
        loaded = paddle.jit.load(path)
        model2.set_state_dict(loaded.state_dict())
        cfg2 = Config()
        cfg2.set_model_obj(model2)
        pred2 = create_predictor(cfg2)
        x = np.random.RandomState(2).randint(0, 97, (1, 4)).astype(np.int32)
        cfg3 = Config()
        cfg3.set_model_obj(model)
        np.testing.assert_allclose(
            create_predictor(cfg3).run([x])[0], pred2.run([x])[0],
            atol=1e-6)


class TestGeneration:
    @pytest.fixture(scope="class")
    def model(self):
        """``small_lm()`` once for the class (every test built the same
        seeded weights), its forward compiled: ``generate`` re-runs it on
        every prefix length, one program a length where eager is some sixty
        one-operation compiles a length, in every test again."""
        return paddle.jit.to_static(small_lm())

    def test_greedy_deterministic(self, model):
        x = np.random.RandomState(3).randint(0, 97, (2, 4)).astype(np.int32)
        out1 = generate(model, paddle.to_tensor(x), max_new_tokens=5)
        out2 = generate(model, paddle.to_tensor(x), max_new_tokens=5)
        assert out1.shape == [2, 9]
        np.testing.assert_array_equal(np.asarray(out1._data),
                                      np.asarray(out2._data))
        # prefix preserved
        np.testing.assert_array_equal(np.asarray(out1._data)[:, :4], x)

    def test_sampling_topk(self, model):
        paddle.seed(11)
        x = np.zeros((1, 2), np.int32)
        out = generate(model, paddle.to_tensor(x), max_new_tokens=4,
                       do_sample=True, top_k=5, temperature=0.8)
        assert out.shape == [1, 6]
        assert np.asarray(out._data).max() < 97

    def test_eos_early_stop(self, model):
        x = np.zeros((1, 2), np.int32)
        # whatever token greedy picks first, treat as eos -> stops at len 3
        first = generate(model, paddle.to_tensor(x), max_new_tokens=1)
        eos = int(np.asarray(first._data)[0, -1])
        out = generate(model, paddle.to_tensor(x), max_new_tokens=8,
                       eos_token_id=eos)
        assert out.shape[1] <= 4

    def test_beam_search_beats_or_ties_greedy_logprob(self, model):
        """num_beams>1: the returned sequence's total log-prob must be >=
        greedy's (beam search explores a superset); num_beams=1-equivalent
        check: beams are deterministic and keep the prefix."""
        import jax
        import jax.numpy as jnp
        x = np.random.RandomState(9).randint(0, 97, (2, 3)).astype(np.int32)
        g = generate(model, paddle.to_tensor(x), max_new_tokens=5)
        bm = generate(model, paddle.to_tensor(x), max_new_tokens=5,
                      num_beams=4)
        assert bm.shape == [2, 8]
        np.testing.assert_array_equal(np.asarray(bm._data)[:, :3], x)

        def seq_logprob(seq):
            arr = jnp.asarray(seq)
            logits = model(paddle.to_tensor(arr[:, :-1]))._data
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            tgt = arr[:, 1:]
            # score only the generated region (last 5 tokens)
            pick = jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
            return np.asarray(pick[:, -5:].sum(-1))

        lp_g = seq_logprob(np.asarray(g._data))
        lp_b = seq_logprob(np.asarray(bm._data))
        assert (lp_b >= lp_g - 1e-4).all(), (lp_b, lp_g)

        bm2 = generate(model, paddle.to_tensor(x), max_new_tokens=5,
                       num_beams=4)
        np.testing.assert_array_equal(np.asarray(bm._data),
                                      np.asarray(bm2._data))

    def test_beam_search_eos_freezes_finished(self, model):
        x = np.zeros((1, 2), np.int32)
        first = generate(model, paddle.to_tensor(x), max_new_tokens=1)
        eos = int(np.asarray(first._data)[0, -1])
        out = generate(model, paddle.to_tensor(x), max_new_tokens=6,
                       eos_token_id=eos, num_beams=3)
        arr = np.asarray(out._data)[0]
        # after the first eos, a frozen beam only ever continues with eos
        gen = arr[2:]
        if eos in gen.tolist():
            i = gen.tolist().index(eos)
            assert all(t == eos for t in gen.tolist()[i:])


class TestInt8Precision:
    def test_int8_weight_only_predictor(self):
        """Int8 precision mode swaps Linears for weight-only-int8 twins:
        high-cosine logits vs fp32, exact on grid-aligned weights."""
        from paddle_tpu import inference
        from paddle_tpu.models.gpt import gpt2_tiny

        paddle.seed(0)
        m = gpt2_tiny(); m.eval()
        x = np.random.RandomState(0).randint(0, 1024, (2, 16)).astype(np.int32)
        cfg = inference.Config(); cfg.set_model_obj(m)
        ref = inference.create_predictor(cfg).run([x])[0]

        paddle.seed(0)
        m8 = gpt2_tiny(); m8.eval()
        cfg8 = inference.Config(); cfg8.set_model_obj(m8)
        cfg8.enable_tensorrt_engine(
            precision_mode=inference.PrecisionType.Int8)
        q = inference.create_predictor(cfg8).run([x])[0]

        cos = (ref * q).sum() / (np.linalg.norm(ref) * np.linalg.norm(q))
        assert cos > 0.999
        assert (ref.argmax(-1) == q.argmax(-1)).mean() > 0.9

    def test_int8_twin_exact_on_grid(self):
        from paddle_tpu.inference import _int8_twin
        import paddle_tpu.nn as nn
        rng = np.random.RandomState(1)
        scales = np.array([0.5, 0.25, 1.0], np.float32)
        ints = rng.randint(-127, 128, (4, 3)).astype(np.float32)
        ints[np.abs(ints).argmax(0), np.arange(3)] = 127
        lin = nn.Linear(4, 3)
        lin.weight._data = paddle.to_tensor(ints * scales)._data
        tw = _int8_twin(lin)
        xi = paddle.to_tensor(rng.randn(5, 4).astype(np.float32))
        np.testing.assert_allclose(np.asarray(lin(xi)._data),
                                   np.asarray(tw(xi)._data),
                                   rtol=1e-5, atol=1e-5)

    def test_int8_swap_releases_fp32_weights(self):
        """The int8 twin must not retain the original Linear — the swapped
        fp32 weight must drop out of the persistent registry (WeakSet)."""
        import gc
        import weakref
        from paddle_tpu import inference
        from paddle_tpu.models.gpt import gpt2_tiny

        paddle.seed(0)
        m = gpt2_tiny(); m.eval()
        w_ref = weakref.ref(m.gpt.h[0].attn.qkv_proj.weight)
        cfg = inference.Config(); cfg.set_model_obj(m)
        cfg.enable_tensorrt_engine(
            precision_mode=inference.PrecisionType.Int8)
        inference.create_predictor(cfg)
        gc.collect()
        assert w_ref() is None


class TestNoRepeatNgram:
    def test_no_repeat_bigram_bans_repeats(self):
        """Reference no_repeat_ngram logits processor: with n=2, any
        bigram may appear at most once in the generated sequence."""
        import paddle_tpu as paddle
        from paddle_tpu.inference.generation import generate
        from paddle_tpu.nn.layer.common import Embedding, Linear
        from paddle_tpu.nn.layer.layers import Layer
        import numpy as np

        class TinyLM(Layer):
            def __init__(self):
                super().__init__()
                self.emb = Embedding(50, 16)
                self.head = Linear(16, 50)

            def forward(self, ids):
                return self.head(self.emb(ids))

        paddle.seed(44)
        m = TinyLM()
        ids = np.random.RandomState(31).randint(1, 50, (2, 4)).astype(
            np.int32)
        out = generate(m, paddle.to_tensor(ids), max_new_tokens=16,
                       no_repeat_ngram_size=2)
        g = np.asarray(out._data)
        for row in g:
            bigrams = list(zip(row[:-1].tolist(), row[1:].tolist()))
            assert len(bigrams) == len(set(bigrams)), (
                f"repeated bigram in {row}")
