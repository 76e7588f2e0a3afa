"""Device places — the north-star's `XLAPlace`/`tpu` device alongside CPUPlace.

Parity: ``paddle/phi/common/place.h :: Place/CPUPlace/GPUPlace/CustomPlace`` and
``python/paddle/device`` set_device/get_device. TPU-first: a Place names a JAX
device; there are no streams to manage — XLA owns scheduling.
"""
from __future__ import annotations

import jax

__all__ = ["Place", "CPUPlace", "TPUPlace", "XLAPlace", "CUDAPlace",
           "set_device", "get_device", "is_compiled_with_cuda",
           "is_compiled_with_xpu", "is_compiled_with_tpu", "device_count"]


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def get_device_id(self) -> int:
        return self.device_id

    def jax_device(self):
        devs = [d for d in jax.devices() if _matches(d, self.device_type)]
        if not devs:
            devs = jax.devices()
        return devs[min(self.device_id, len(devs) - 1)]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


def _matches(dev, kind: str) -> bool:
    p = dev.platform.lower()
    if kind == "cpu":
        return p == "cpu"
    if kind == "tpu":
        return p == "tpu"
    return True


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


# Aliases so reference-shaped code keeps working: CUDAPlace routes to the
# accelerator (TPU) — "no GPU in the loop" per the north-star.
XLAPlace = TPUPlace
CUDAPlace = TPUPlace


_state = {"place": None}


def _default_place() -> Place:
    plats = {d.platform.lower() for d in jax.devices()}
    if "tpu" in plats:
        return TPUPlace(0)
    return CPUPlace(0)


def _current_place() -> Place:
    if _state["place"] is None:
        _state["place"] = _default_place()
    return _state["place"]


def set_device(device: str):
    """paddle.set_device("tpu")/"cpu"/"gpu:0" (gpu aliases to the accelerator)."""
    if isinstance(device, Place):
        _state["place"] = device
        return device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        _state["place"] = CPUPlace(idx)
    elif name in ("tpu", "xla", "gpu", "cuda"):
        _state["place"] = TPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _state["place"]


def get_device() -> str:
    p = _current_place()
    return f"{p.device_type}:{p.device_id}"


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform.lower() == "tpu" for d in jax.devices())
