"""TPU Pallas kernel library — the performance core.

Parity target: the reference's GPU kernel library (paddle/phi/kernels/gpu/,
paddle/fluid/operators/fused/) re-designed as TPU Mosaic kernels:

  flash_attention   — flash_attn_kernel.cu :: FlashAttnKernel
  decode_attention  — fused_multi_transformer_op.cu (KV-cache decode path; built in a later milestone this round)

Each module exposes ``is_supported(...)`` so functional wrappers can fall
back to XLA composites for unsupported configs.  Kernels run in interpret
mode when the default backend is not a TPU, which is how the unit tests
exercise them on the CPU; tests/test_chip_compile.py compiles them for a
described v5e instead.
"""
import jax


def _interpret() -> bool:
    """Pallas interpret mode everywhere but a real TPU. The ONE gate every
    kernel module reads (as ``_pallas._interpret()``, looked up at trace
    time, so a test can steer all of them with one monkeypatch)."""
    return jax.default_backend() != "tpu"


def _enabled() -> bool:
    """Whether the functional wrappers take a kernel at all: on a TPU. Off
    it interpret mode is slower than the XLA composite, so they take that.
    ``_interpret()``'s sibling, read the same way: a dispatch test flips it
    with one monkeypatch and runs the kernels interpreted."""
    return jax.default_backend() == "tpu"


from . import flash_attention  # noqa: F401
