"""paddle_tpu — a TPU-native framework with PaddlePaddle's capabilities.

Not a port: the compute path is JAX/XLA/Pallas, distribution is named-mesh
collectives over ICI/DCN, and the Paddle-shaped user surface (Tensor, nn.Layer,
optimizer, amp, fleet) sits on top. Blueprint: /root/repo/SURVEY.md.

Usage parity with the reference:
    import paddle_tpu as paddle
    x = paddle.to_tensor([1., 2.]); y = (x * 2).sum(); y.backward()
"""
from __future__ import annotations

__version__ = "0.1.0"

# core
from .core import dtype as _dtype_mod
from .core.dtype import (float16, bfloat16, float32, float64, int8, int16,
                         int32, int64, uint8, bool_, complex64, complex128,
                         set_default_dtype, get_default_dtype, iinfo, finfo)
from .core.place import (Place, CPUPlace, TPUPlace, XLAPlace, CUDAPlace,
                         set_device, get_device, device_count,
                         is_compiled_with_cuda, is_compiled_with_xpu,
                         is_compiled_with_tpu)
from .core.rng import seed, get_rng_state, set_rng_state
from .core.flags import get_flags, set_flags

# tensor + autograd
from .tensor import *  # noqa: F401,F403
from .tensor import Tensor, Parameter
from .tensor import linalg  # paddle.linalg namespace
from .tensor.tensor import no_grad, enable_grad, is_grad_enabled, set_grad_enabled
from . import autograd
from .autograd import grad

# subsystems (populated as the build proceeds)
from . import nn
from . import optimizer
from . import amp
from . import io
from . import jit
from . import static
from . import device
from . import distributed
from . import incubate
from . import vision
from . import profiler
from . import hapi
from . import metric
from . import regularizer
from . import distribution
from . import fft
from . import signal
from . import version
from . import inference
from . import text
from . import utils
from . import sparse
from . import audio
from . import geometric
from . import quantization
from . import sysconfig
from . import hub
from . import onnx
from . import fluid
from . import reader
from .reader import batch
from .hapi.model import Model
from .framework.io import save, load
from .framework.layer_helpers import DataParallel
from .nn.layer.layers import disable_static, enable_static, in_dynamic_mode

# expose F-style namespaces the way paddle does
from .nn import functional  # noqa: F401

# re-bind subpackage names the star-imports above shadowed
import sys as _sys
tensor = _sys.modules["paddle_tpu.tensor"]


def ones_like_(x):  # pragma: no cover - compat shim
    from .tensor.creation import ones_like
    return ones_like(x)
