"""Model-family smoke + training-descent tests (tiny configs on CPU)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def _train_steps(model, make_batch, n=6, lr=1e-2):
    """``n`` AdamW steps as one compiled program (two traces), as the
    families are trained: eager, the first step alone is some hundreds of
    one-operation compiles a family."""
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def step(*batch):
        loss = model(*batch)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    return [float(step(*make_batch()).numpy()) for _ in range(n)]


class TestGPT:
    def test_forward_shapes(self):
        from paddle_tpu.models.gpt import gpt2_tiny
        m = gpt2_tiny()
        ids = paddle.to_tensor(np.random.randint(0, 1000, (2, 16)).astype(
            np.int32))
        logits = m(ids)
        assert logits.shape == [2, 16, 1024]

    def test_lm_loss_descends(self):
        from paddle_tpu.models.gpt import gpt2_tiny
        paddle.seed(1)
        m = gpt2_tiny()
        data = np.random.randint(0, 1000, (4, 17)).astype(np.int32)
        x = paddle.to_tensor(data[:, :-1])
        y = paddle.to_tensor(data[:, 1:])
        losses = _train_steps(m, lambda: (x, y), n=8)
        assert losses[-1] < losses[0]

    def test_jit_matches_eager(self):
        from paddle_tpu.models.gpt import gpt2_tiny
        paddle.seed(3)
        m = gpt2_tiny(dropout=0.0)
        m.eval()
        ids = paddle.to_tensor(np.random.randint(0, 1000, (2, 8)).astype(
            np.int32))
        eager = m(ids).numpy()

        @paddle.jit.to_static
        def fwd(t):
            return m(t)
        jitted = fwd(ids).numpy()
        np.testing.assert_allclose(eager, jitted, rtol=1e-4, atol=1e-4)


class TestLlama:
    def test_forward_and_descent(self):
        from paddle_tpu.models.llama import llama_tiny
        paddle.seed(2)
        m = llama_tiny(tensor_parallel=False)
        data = np.random.randint(0, 255, (2, 17)).astype(np.int32)
        x = paddle.to_tensor(data[:, :-1])
        y = paddle.to_tensor(data[:, 1:])
        losses = _train_steps(m, lambda: (x, y), n=6)
        assert losses[-1] < losses[0]

    def test_tp_layers_match_dense_serially(self):
        """TP model on a 1-degree mesh must equal the dense model: the
        reference's serial-vs-parallel allclose contract."""
        from paddle_tpu.models.llama import llama_tiny
        paddle.seed(5)
        m_tp = llama_tiny(tensor_parallel=True)
        paddle.seed(5)
        m_dense = llama_tiny(tensor_parallel=False)
        ids = paddle.to_tensor(np.random.randint(0, 255, (2, 8)).astype(
            np.int32))
        m_tp.eval()
        m_dense.eval()
        np.testing.assert_allclose(m_tp(ids).numpy(), m_dense(ids).numpy(),
                                   rtol=1e-4, atol=1e-4)

    def test_recompute_variant_matches(self):
        from paddle_tpu.models.llama import llama_tiny
        paddle.seed(7)
        m1 = llama_tiny(tensor_parallel=False, recompute=False)
        paddle.seed(7)
        m2 = llama_tiny(tensor_parallel=False, recompute=True)
        data = np.random.randint(0, 255, (2, 9)).astype(np.int32)
        x = paddle.to_tensor(data[:, :-1])
        y = paddle.to_tensor(data[:, 1:])
        l1 = m1(x, labels=y)
        l1.backward()
        l2 = m2(x, labels=y)
        l2.backward()
        np.testing.assert_allclose(l1.numpy(), l2.numpy(), rtol=1e-5)
        g1 = m1.llama.layers[0].self_attn.q_proj.weight.grad.numpy()
        g2 = m2.llama.layers[0].self_attn.q_proj.weight.grad.numpy()
        np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


class TestBert:
    def test_pretrain_loss(self):
        from paddle_tpu.models.bert import BertForPretraining, bert_tiny
        paddle.seed(4)
        m = BertForPretraining(bert_tiny())
        ids = paddle.to_tensor(np.random.randint(0, 1000, (2, 16)).astype(
            np.int32))
        mlm = np.full((2, 16), -100, np.int64)
        mlm[:, 3] = 7
        loss = m(ids, masked_lm_labels=paddle.to_tensor(mlm),
                 next_sentence_labels=paddle.to_tensor(
                     np.array([0, 1], np.int64)))
        assert np.isfinite(loss.numpy())
        loss.backward()
        assert m.bert.embeddings.word_embeddings.weight.grad is not None

    def test_classification(self):
        from paddle_tpu.models.bert import (BertForSequenceClassification,
                                            bert_tiny)
        m = BertForSequenceClassification(bert_tiny(), num_classes=3)
        ids = paddle.to_tensor(np.random.randint(0, 1000, (2, 12)).astype(
            np.int32))
        logits = m(ids)
        assert logits.shape == [2, 3]


class TestViT:
    def test_forward_and_train(self):
        from paddle_tpu.models.vit import vit_tiny
        paddle.seed(6)
        m = vit_tiny()
        x = paddle.to_tensor(np.random.randn(2, 3, 32, 32).astype(np.float32))
        y = paddle.to_tensor(np.array([1, 3], np.int64))
        logits = m(x)
        assert logits.shape == [2, 10]
        losses = _train_steps(m, lambda: (x, y), n=5)
        assert losses[-1] < losses[0]

    def test_granular_remat_matches(self):
        """recompute=N (every Nth block) must be numerically identical to
        no remat — it only changes what is saved vs recomputed."""
        from paddle_tpu.models.vit import vit_tiny

        def run(rc):
            paddle.seed(11)
            m = vit_tiny(recompute=rc)
            m.train()
            x = paddle.to_tensor(np.random.RandomState(3).randn(
                2, 3, 32, 32).astype(np.float32))
            y = paddle.to_tensor(np.array([0, 5], np.int64))
            loss = paddle.nn.functional.cross_entropy(m(x), y)
            loss.backward()
            return (float(np.asarray(loss._data)),
                    np.asarray(m.blocks[0].mlp.fc1.weight.grad._data))

        l0, g0 = run(False)
        for rc in (True, 2, 3):
            l1, g1 = run(rc)
            assert l0 == l1
            np.testing.assert_allclose(g0, g1, atol=1e-6, rtol=1e-6)

    def test_patch_matmul_matches_conv(self, monkeypatch):
        """Space-to-depth patch embedding (one GEMM on the conv's own
        weights) must match the strided-conv formulation exactly — fwd
        logits AND the patch-embed weight grad (r4 ViT perf lever)."""
        from paddle_tpu.models.vit import vit_tiny

        def run(force_conv):
            if force_conv:
                monkeypatch.setenv("PADDLE_TPU_PATCH_CONV", "1")
            else:
                monkeypatch.delenv("PADDLE_TPU_PATCH_CONV", raising=False)
            paddle.seed(9)
            m = vit_tiny()
            x = paddle.to_tensor(np.random.RandomState(4).randn(
                2, 3, 32, 32).astype(np.float32))
            y = paddle.to_tensor(np.array([2, 7], np.int64))
            loss = paddle.nn.functional.cross_entropy(m(x), y)
            loss.backward()
            return (float(np.asarray(loss._data)),
                    np.asarray(m.patch_embed.weight.grad._data))

        l_mm, g_mm = run(force_conv=False)
        l_cv, g_cv = run(force_conv=True)
        np.testing.assert_allclose(l_mm, l_cv, rtol=1e-5)
        np.testing.assert_allclose(g_mm, g_cv, atol=1e-4, rtol=1e-4)


class TestMoE:
    def test_moe_layer_capacity_routing(self):
        from paddle_tpu.incubate.distributed.models.moe import MoELayer
        paddle.seed(8)
        layer = MoELayer(d_model=16, d_hidden=32, num_experts=4,
                         gate="switch")
        x = paddle.to_tensor(np.random.randn(2, 12, 16).astype(np.float32))
        out = layer(x)
        assert out.shape == [2, 12, 16]
        assert layer.gate.aux_loss is not None

    def test_gshard_gate_masks(self):
        from paddle_tpu.incubate.distributed.models.moe.gate import GShardGate
        g = GShardGate(8, 4, capacity_factor=2.0)
        g.eval()
        x = paddle.to_tensor(np.random.randn(1, 8, 8).astype(np.float32))
        combine, dispatch, aux = g(x)
        c = combine.numpy()
        d = dispatch.numpy()
        assert c.shape[:3] == (1, 8, 4)
        # each token dispatched to ≤2 experts, each slot one-hot
        assert d.sum(axis=(2, 3)).max() <= 2.0 + 1e-6
        assert np.isfinite(aux.numpy())

    def test_moe_model_descends(self):
        from paddle_tpu.models.moe import ernie_moe_tiny
        paddle.seed(9)
        m = ernie_moe_tiny()
        data = np.random.randint(0, 500, (2, 17)).astype(np.int32)
        x = paddle.to_tensor(data[:, :-1])
        y = paddle.to_tensor(data[:, 1:])
        losses = _train_steps(m, lambda: (x, y), n=6, lr=3e-3)
        assert losses[-1] < losses[0]


class TestFusedLayers:
    def test_fused_feedforward_matches_composite(self):
        import paddle_tpu.incubate.nn.functional as IF
        import paddle_tpu.nn.functional as F
        x_np = np.random.randn(2, 4, 8).astype(np.float32)
        w1 = np.random.randn(8, 16).astype(np.float32) * 0.1
        w2 = np.random.randn(16, 8).astype(np.float32) * 0.1
        x = paddle.to_tensor(x_np)
        out = IF.fused_feedforward(
            x, paddle.to_tensor(w1), paddle.to_tensor(w2),
            dropout1_rate=0.0, dropout2_rate=0.0, pre_layer_norm=True,
            ln1_scale=paddle.to_tensor(np.ones(8, np.float32)),
            ln1_bias=paddle.to_tensor(np.zeros(8, np.float32)),
            training=False).numpy()
        # manual
        mu = x_np.mean(-1, keepdims=True)
        var = x_np.var(-1, keepdims=True)
        h = (x_np - mu) / np.sqrt(var + 1e-5)
        ref = x_np + np.maximum(h @ w1, 0) @ w2
        np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)

    def test_fused_multi_transformer_decode_cache(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        import jax.numpy as jnp
        paddle.seed(11)
        m = FusedMultiTransformer(embed_dim=16, num_heads=2,
                                  dim_feedforward=32, num_layers=2)
        m.eval()
        x = paddle.to_tensor(np.random.randn(1, 4, 16).astype(np.float32))
        caches = [paddle.zeros([2, 1, 2, 32, 8]) for _ in range(2)]
        out, caches = m(x, caches=caches, time_step=0)
        assert out.shape == [1, 4, 16]
        # decode one more token
        nxt = paddle.to_tensor(np.random.randn(1, 1, 16).astype(np.float32))
        out2, caches = m(nxt, caches=caches, time_step=4)
        assert out2.shape == [1, 1, 16]

    def test_rotary_embedding_norm_preserving(self):
        from paddle_tpu.incubate.nn.functional import (
            fused_rotary_position_embedding)
        q = paddle.to_tensor(np.random.randn(1, 6, 2, 8).astype(np.float32))
        q2, _, _ = fused_rotary_position_embedding(q)
        np.testing.assert_allclose(
            np.linalg.norm(q.numpy(), axis=-1),
            np.linalg.norm(q2.numpy(), axis=-1), rtol=1e-4)


class TestBertPerfPaths:
    """r4 BERT MFU levers: fused self-attn QKV GEMM and the MLM
    masked-position gather must be numerically transparent."""

    def test_mha_fused_qkv_matches_separate_projections(self):
        from paddle_tpu.nn.layer.transformer import MultiHeadAttention
        paddle.seed(15)
        mha = MultiHeadAttention(32, 4)
        mha.eval()
        x = paddle.to_tensor(np.random.RandomState(5).randn(
            2, 10, 32).astype(np.float32))
        out_fused = mha(x)                       # self-attn: fused path
        # oracle: force the separate-projection path via cross-attn form
        # with an independent copy of the same content
        x2 = paddle.to_tensor(np.asarray(x._data).copy())
        out_sep = mha(x, x2, x2)                 # key is not query obj
        np.testing.assert_allclose(np.asarray(out_fused._data),
                                   np.asarray(out_sep._data),
                                   atol=1e-5, rtol=1e-5)
        # grads flow through the fused concat back to separate weights
        loss = (mha(x) ** 2).mean()
        loss.backward()
        for p in (mha.q_proj.weight, mha.k_proj.weight, mha.v_proj.weight):
            assert p.grad is not None

    def test_mlm_gather_loss_matches_full(self, monkeypatch):
        from paddle_tpu.models.bert import BertForPretraining, bert_tiny
        paddle.seed(16)
        m = BertForPretraining(bert_tiny())
        m.eval()               # no dropout: the two forwards must match
        rng = np.random.RandomState(6)
        ids = rng.randint(0, 500, (2, 32)).astype(np.int32)
        labels = np.full_like(ids, -100)
        # mask ~15% (5 of 32) - under the 22% gather budget
        for b in range(2):
            pos = rng.choice(32, 5, replace=False)
            labels[b, pos] = rng.randint(0, 500, 5)
        nsp = rng.randint(0, 2, (2,)).astype(np.int32)

        monkeypatch.setenv("PADDLE_TPU_MLM_GATHER", "0")
        full = m(paddle.to_tensor(ids),
                 masked_lm_labels=paddle.to_tensor(labels),
                 next_sentence_labels=paddle.to_tensor(nsp))
        monkeypatch.delenv("PADDLE_TPU_MLM_GATHER", raising=False)
        gathered = m(paddle.to_tensor(ids),
                     masked_lm_labels=paddle.to_tensor(labels),
                     next_sentence_labels=paddle.to_tensor(nsp))
        np.testing.assert_allclose(float(np.asarray(gathered._data)),
                                   float(np.asarray(full._data)),
                                   rtol=1e-5)


class TestLlamaFusedProjections:
    """r4: the fused QKV / gate-up fast paths must be numerically
    transparent incl. GQA slicing, and must honor AMP autocast."""

    def test_gqa_fused_slicing_matches_separate(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        c = LlamaConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=8, num_kv_heads=2, intermediate_size=96,
                        max_position=64)
        paddle.seed(20)
        m = LlamaForCausalLM(c)
        m.eval()
        ids = paddle.to_tensor(np.random.RandomState(7).randint(
            0, 128, (2, 12)).astype(np.int32))
        fused = m(ids)

        # oracle: same weights through the separate projections — force
        # the slow path by disguising the Linear type check
        import paddle_tpu.models.llama as llama_mod
        attn = m.llama.layers[0].self_attn

        class NotLinear(type(attn.q_proj)):
            pass
        orig_types = []
        for blk in m.llama.layers:
            a, mlp = blk.self_attn, blk.mlp
            orig_types.append((a.q_proj.__class__, mlp.gate_proj.__class__))
            a.q_proj.__class__ = NotLinear
            mlp.gate_proj.__class__ = NotLinear
        sep = m(ids)
        for blk, (ta, tm) in zip(m.llama.layers, orig_types):
            blk.self_attn.q_proj.__class__ = ta
            blk.mlp.gate_proj.__class__ = tm
        np.testing.assert_allclose(np.asarray(fused._data),
                                   np.asarray(sep._data),
                                   atol=1e-5, rtol=1e-5)

    def test_fused_paths_honor_autocast(self):
        """r4 review: the fused GEMMs must run in the amp dtype under
        auto_cast O1, exactly like F.linear — not silently in fp32."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        import jax.numpy as jnp
        c = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=4, intermediate_size=48, max_position=32)
        paddle.seed(21)
        m = LlamaForCausalLM(c)
        ids = paddle.to_tensor(np.random.RandomState(8).randint(
            0, 64, (1, 8)).astype(np.int32))
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            out = m(ids)
        assert out._data.dtype == jnp.bfloat16, out._data.dtype


class TestMLMTracedBudget:
    def test_traced_overflow_poisons_loss(self, monkeypatch):
        """Advisor r4 (medium): under tracing the concrete density check
        cannot run, so a row denser than the 22% gather budget must
        NaN-poison the loss (loud) instead of silently dropping loss
        terms; a legal-density batch through the same trace stays
        finite."""
        from paddle_tpu.models.bert import BertForPretraining, bert_tiny
        monkeypatch.delenv("PADDLE_TPU_MLM_GATHER", raising=False)
        paddle.seed(17)
        m = BertForPretraining(bert_tiny())
        m.eval()
        rng = np.random.RandomState(7)
        ids = rng.randint(0, 500, (2, 32)).astype(np.int32)

        def make_labels(n_masked):
            lab = np.full_like(ids, -100)
            for b in range(2):
                pos = rng.choice(32, n_masked, replace=False)
                lab[b, pos] = rng.randint(0, 500, n_masked)
            return lab

        step = paddle.jit.to_static(
            lambda i, l: m(i, masked_lm_labels=l))
        # budget = ceil(22% of 32) = 8: a 12-label row overflows
        legal = float(np.asarray(step(
            paddle.to_tensor(ids),
            paddle.to_tensor(make_labels(5)))._data))
        assert np.isfinite(legal)
        poisoned = float(np.asarray(step(
            paddle.to_tensor(ids),
            paddle.to_tensor(make_labels(12)))._data))
        assert np.isnan(poisoned), (
            "over-budget MLM row must poison the traced loss, got "
            f"{poisoned}")
