"""The float32 reference against the program's own GPT-2
(``paddle_tpu.models.gpt``) at ``gpt2_tiny``, float32 on both sides, and the
serving family's weight mapping against the engine at a tiny width."""
import numpy as np
import pytest

from benchmark.models import gpt2_fused_serve, gpt2_lm_train
from benchmark.reference import gpt2 as ref


def test_reference_matches_models_gpt_at_gpt2_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt2_tiny
    paddle.seed(3)
    model = gpt2_tiny()
    model.eval()
    # biases and LayerNorm parameters start at 0 and 1: move them, so that
    # a reference that forgot one would fail
    rng = np.random.default_rng(0)
    for p in model.parameters():
        if len(p.shape) == 1:
            p._data = p._data + rng.normal(0, 0.05, p.shape).astype(
                np.float32)
    ids = rng.integers(0, 1024, (2, 65)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(x))._data)
        got_loss = float(np.asarray(model(
            paddle.to_tensor(x), labels=paddle.to_tensor(y))._data))
    w = gpt2_lm_train.reference_weights(model)
    want = np.stack([np.asarray(ref.logits(w, x[i])) for i in range(2)])
    # float32 against float32: rounding in another order, nothing more
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    want_loss = np.mean([float(ref.loss(w, x[i], y[i])) for i in range(2)])
    assert got_loss == pytest.approx(want_loss, rel=1e-5)


def test_reference_notices_a_missing_bias():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt2_tiny
    paddle.seed(3)
    model = gpt2_tiny()
    model.eval()
    x = np.arange(1, 33, dtype=np.int32)
    w = gpt2_lm_train.reference_weights(model)
    base = np.asarray(ref.logits(w, x))
    w["blocks"][1]["b_fc"] = w["blocks"][1]["b_fc"] + 0.5
    assert np.abs(np.asarray(ref.logits(w, x)) - base).max() > 1e-2


def test_flops_per_token_of_gpt2_124m():
    import json
    import os
    cfg = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                      "configs", "gpt2_124m_train.json")))
    # 6 N + 12 L H S with N = 123.6 M matmul parameters: 854 MFLOP a token
    assert gpt2_lm_train.flops_per_token(cfg, 1024) == pytest.approx(
        854.6e6, rel=2e-3)
