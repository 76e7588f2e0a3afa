"""incubate.asp (n:m sparsity), incubate.optimizer (LookAhead /
ModelAverage), incubate.autotune — parity surface tests."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate import asp, autotune
from paddle_tpu.incubate.optimizer import LookAhead, ModelAverage


class TwoLayer(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = paddle.nn.Linear(16, 8)
        self.fc2 = paddle.nn.Linear(8, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def test_asp_prune_creates_2_4_sparsity():
    paddle.seed(0)
    model = TwoLayer()
    masks = asp.prune_model(model, n=2, m=4)
    assert len(masks) == 2
    for layer in (model.fc1, model.fc2):
        w = np.asarray(layer.weight._data)
        assert asp.check_sparsity(layer.weight, n=2, m=4)
        # every group of 4 input rows keeps at most 2 nonzeros per column
        g = w.reshape(-1, 4, w.shape[-1])
        assert (np.count_nonzero(g, axis=1) <= 2).all()
        dens = asp.calculate_density(layer.weight)
        assert dens <= 0.5 + 1e-6


def test_asp_decorated_optimizer_keeps_masks():
    paddle.seed(1)
    model = TwoLayer()
    opt = asp.decorate(paddle.optimizer.SGD(
        learning_rate=0.1, parameters=model.parameters()))
    asp.prune_model(model, n=2, m=4)
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 16)
                         .astype(np.float32))
    y = paddle.to_tensor(np.array([0, 1, 2, 3], np.int32))
    for _ in range(3):
        loss = paddle.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert asp.check_sparsity(model.fc1.weight, n=2, m=4)
    assert asp.check_sparsity(model.fc2.weight, n=2, m=4)


def test_asp_excluded_layers():
    paddle.seed(2)
    asp.reset_excluded_layers()
    model = TwoLayer()
    asp.set_excluded_layers(["fc2"])
    try:
        asp.prune_model(model, n=2, m=4)
        assert asp.check_sparsity(model.fc1.weight)
        w2 = np.asarray(model.fc2.weight._data)
        assert asp.calculate_density(model.fc2.weight) > 0.9
    finally:
        asp.reset_excluded_layers()


def test_lookahead_slow_weights():
    paddle.seed(3)
    model = TwoLayer()
    opt = LookAhead(paddle.optimizer.SGD(learning_rate=0.05,
                                         parameters=model.parameters()),
                    alpha=0.5, k=2)
    w0 = np.asarray(model.fc1.weight._data).copy()
    x = paddle.to_tensor(np.random.RandomState(1).randn(4, 16)
                         .astype(np.float32))
    y = paddle.to_tensor(np.array([0, 1, 0, 1], np.int32))

    def one_step():
        loss = paddle.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return np.asarray(model.fc1.weight._data).copy()

    w1 = one_step()               # fast step 1 (no sync)
    w2 = one_step()               # k=2 -> slow sync: w = 0.5*w0 + 0.5*fast2
    # control: plain SGD from the same seed gives the raw fast trajectory
    paddle.seed(3)
    ctrl = TwoLayer()
    copt = paddle.optimizer.SGD(learning_rate=0.05,
                                parameters=ctrl.parameters())
    for _ in range(2):
        closs = paddle.nn.functional.cross_entropy(ctrl(x), y)
        closs.backward()
        copt.step()
        copt.clear_grad()
    fast2 = np.asarray(ctrl.fc1.weight._data)
    # slow was seeded at w0, so the sync must land exactly halfway —
    # catches lazily-seeded slow weights (which would leave w2 == fast2)
    np.testing.assert_allclose(w2, 0.5 * w0 + 0.5 * fast2,
                               rtol=1e-5, atol=1e-6)
    assert opt._step_count == 2 and len(opt._slow) > 0
    sd = opt.state_dict()
    assert "@LookAhead.step_count" in sd
    assert any(k.endswith("@SLOW") for k in sd)
    # restore roundtrip
    opt.set_state_dict(sd)
    assert opt._step_count == 2


def test_model_average_apply_restore():
    paddle.seed(4)
    model = TwoLayer()
    ma = ModelAverage(0.15, parameters=model.parameters(),
                      min_average_window=2, max_average_window=100)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    x = paddle.to_tensor(np.random.RandomState(2).randn(4, 16)
                         .astype(np.float32))
    y = paddle.to_tensor(np.array([1, 0, 1, 0], np.int32))
    snaps = []
    for _ in range(3):
        loss = paddle.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        ma.step()
        snaps.append(np.asarray(model.fc1.weight._data).copy())
    current = np.asarray(model.fc1.weight._data).copy()
    with ma.apply():
        avg = np.asarray(model.fc1.weight._data)
        np.testing.assert_allclose(avg, np.mean(snaps, axis=0),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(model.fc1.weight._data),
                               current)                # restored


def test_autotune_config():
    autotune.set_config({"kernel": {"enable": True,
                                    "tuning_range": [512, 512]}})
    cfg = autotune.get_config()
    assert cfg["kernel"]["enable"] is True
    # the round trip, and nothing else: tiles follow from shapes
    assert cfg["kernel"]["tuning_range"] == [512, 512]
