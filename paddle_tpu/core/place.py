"""Device places.

TPU-native equivalent of the reference's ``phi::Place`` / ``CUDAPlace``
(reference: paddle/phi/common/place.h). A Place names a logical device; the
backing object is a ``jax.Device``. ``TPUPlace`` replaces ``CUDAPlace``;
``CPUPlace`` is kept for host tensors and for the virtual-device test mesh.
"""
from __future__ import annotations

import functools

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "set_device", "get_device",
    "device_count", "current_place", "is_compiled_with_tpu",
]


@functools.lru_cache(maxsize=None)
def _devices_for(platform: str):
    try:
        return tuple(jax.devices(platform))
    except RuntimeError:
        return ()


def _accelerator_platform() -> str | None:
    """The non-CPU platform jax was initialized with, if any."""
    backend = jax.default_backend()
    return None if backend == "cpu" else backend


class Place:
    """Base place: (device_kind, device_id)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = int(device_id)

    def jax_device(self) -> jax.Device:
        if self.device_type == "cpu":
            devs = _devices_for("cpu")
        else:
            # a 'tpu' place is the live accelerator platform; with no
            # accelerator there is no device for it (the error below) —
            # it is never quietly a host device.
            plat = _accelerator_platform()
            devs = _devices_for(plat) if plat else ()
        if not devs:
            raise RuntimeError(f"no devices for place {self}")
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    # paddle compat: CUDAPlace queries map to the accelerator
    def is_gpu_place(self):
        return self.is_tpu_place()


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("cpu", device_id)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


_current_place: Place | None = None


def _default_place() -> Place:
    if _accelerator_platform() is not None:
        return TPUPlace(0)
    return CPUPlace(0)


def current_place() -> Place:
    return _current_place if _current_place is not None else _default_place()


def set_device(device: str) -> Place:
    """``set_device("tpu:0")`` / ``"cpu"`` — mirrors ``paddle.set_device``."""
    global _current_place
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("tpu", "gpu", "cuda", "xpu"):  # accept gpu spelling for compat
        _current_place = TPUPlace(idx)
    elif kind == "cpu":
        _current_place = CPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def device_count() -> int:
    plat = _accelerator_platform()
    return len(_devices_for(plat) if plat else _devices_for("cpu"))


def is_compiled_with_tpu() -> bool:
    return _accelerator_platform() is not None
