"""The vision zoo's families forward on tiny inputs (SURVEY §2.6). A file of
its own, so that a second worker takes it: with the language-model families
of ``tests/test_models.py`` it was the run's longest chain."""
import numpy as np

import paddle_tpu as paddle


class TestVisionZooAdditions:
    """New zoo families forward on tiny inputs (SURVEY §2.6 vision zoo)."""

    def _forward(self, model, size=64):
        """The forward as ONE compiled program: eager, each of these
        networks is several hundred one-operation compiles (densenet121:
        749 of them, four fifths of its time) and no more arithmetic."""
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(1, 3, size, size).astype(
                np.float32))
        model.eval()
        with paddle.no_grad():
            return paddle.jit.to_static(model)(x)

    def _run(self, model, size=64):
        out = self._forward(model, size)
        assert out.shape == [1, 10]
        assert np.isfinite(np.asarray(out._data)).all()

    def test_alexnet(self):
        from paddle_tpu.vision.models import alexnet
        self._run(alexnet(num_classes=10), size=128)

    def test_squeezenet(self):
        from paddle_tpu.vision.models import squeezenet1_1
        self._run(squeezenet1_1(num_classes=10), size=64)

    def test_densenet(self):
        from paddle_tpu.vision.models import densenet121
        self._run(densenet121(num_classes=10), size=64)

    def test_shufflenet(self):
        from paddle_tpu.vision.models import shufflenet_v2_x0_25
        self._run(shufflenet_v2_x0_25(num_classes=10), size=64)

    def test_googlenet(self):
        from paddle_tpu.vision.models import googlenet
        out, aux1, aux2 = self._forward(googlenet(num_classes=10))
        assert out.shape == [1, 10] and aux1.shape == [1, 10] \
            and aux2.shape == [1, 10]

    def test_mobilenet_v1(self):
        from paddle_tpu.vision.models import mobilenet_v1
        self._run(mobilenet_v1(scale=0.25, num_classes=10), size=64)

    def test_mobilenet_v3(self):
        from paddle_tpu.vision.models import (mobilenet_v3_small,
                                              mobilenet_v3_large)
        self._run(mobilenet_v3_small(scale=0.5, num_classes=10), size=64)
        self._run(mobilenet_v3_large(scale=0.35, num_classes=10), size=64)

    def test_resnext_and_wide(self):
        from paddle_tpu.vision.models import (resnext50_32x4d,
                                              wide_resnet50_2)
        self._run(resnext50_32x4d(num_classes=10), size=64)
        self._run(wide_resnet50_2(num_classes=10), size=64)

    def test_inception_v3(self):
        from paddle_tpu.vision.models import inception_v3
        self._run(inception_v3(num_classes=10), size=299)
