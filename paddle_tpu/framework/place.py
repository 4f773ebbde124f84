"""Device abstraction (Paddle ``Place`` parity) over jax devices.

Reference parity: ``phi::Place`` / ``paddle/fluid/platform`` device management.
On TPU the runtime owns device placement, so Place is a thin descriptor that
maps onto ``jax.devices()``. ``CUDAPlace`` is accepted for source compatibility
and aliases the accelerator (TPU) place.
"""
from __future__ import annotations

import functools

import jax


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        if self.device_type == "cpu":
            return "Place(cpu)"
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        if isinstance(other, Place):
            return (self.device_type, self.device_id) == (
                other.device_type, other.device_id)
        if isinstance(other, str):
            return _parse_device_str(other) == (self.device_type, self.device_id)
        return NotImplemented

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = _devices_of_type(self.device_type)
        if self.device_type == "cpu":
            # Paddle has ONE host place; an id past the virtual CPU
            # devices of a test mesh still means "the host"
            return devs[min(self.device_id, len(devs) - 1)]
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: device id {self.device_id} out of range — "
                f"{len(devs)} device(s) visible "
                f"({devs[0].platform}, {devs[0].device_kind})")
        return devs[self.device_id]

    # Paddle API compat
    def is_gpu_place(self):
        return self.device_type in ("gpu", "tpu")

    def is_cpu_place(self):
        return self.device_type == "cpu"


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(Place):
    """Source-compat alias: CUDA code runs on the accelerator (TPU) here."""

    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class XPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


@functools.lru_cache(maxsize=None)
def _devices_of_type(device_type: str):
    if device_type == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple(jax.devices())
    # tpu / gpu both mean "the accelerator backend"
    return tuple(jax.devices())


def _parse_device_str(device: str):
    device = device.lower()
    if ":" in device:
        kind, _, idx = device.partition(":")
        return kind, int(idx)
    return device, 0


_default_place = None


def set_device(device):
    """``paddle.device.set_device`` parity."""
    global _default_place
    if isinstance(device, Place):
        _default_place = device
    else:
        kind, idx = _parse_device_str(str(device))
        if kind in ("gpu", "cuda", "xpu", "tpu"):
            kind = "tpu"
        _default_place = Place(kind, idx)
    return _default_place


def get_device() -> str:
    p = _get_default_place()
    if p.device_type == "cpu":
        return "cpu"
    return f"{p.device_type}:{p.device_id}"


def _get_default_place() -> Place:
    global _default_place
    if _default_place is None:
        backend = jax.default_backend()
        _default_place = Place("cpu" if backend == "cpu" else "tpu", 0)
    return _default_place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() not in ("cpu",)
