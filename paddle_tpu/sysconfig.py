"""paddle.sysconfig. Parity: python/paddle/sysconfig.py :: get_include,
get_lib — paths a C++ extension build needs to find headers/libraries."""
from __future__ import annotations

import os

__all__ = ["get_include", "get_lib"]

_PKG = os.path.dirname(os.path.abspath(__file__))


def get_include() -> str:
    """Directory of C headers shipped with the package (the native runtime's
    plain-C ABI declarations live alongside csrc)."""
    return os.path.join(_PKG, "include")


def get_lib() -> str:
    """Directory containing the framework's compiled shared libraries
    (libpaddle_tpu_runtime-<hash>.so is built on demand next to its source — see
    paddle_tpu/core/native.py::_lib_path)."""
    return os.path.join(os.path.dirname(_PKG), "csrc")
