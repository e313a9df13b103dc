"""Device identity (Place) over JAX devices.

TPU-native analog of `paddle/phi/common/place.h` — instead of an AllocationType enum plus
device id, a Place wraps a `jax.Device`. `TPUPlace(i)`/`CPUPlace()` mirror the reference's
`GPUPlace(i)`/`CPUPlace()` API surface.
"""
from __future__ import annotations

import functools


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    @property
    def jax_device(self):
        """The `jax.Device` this place names. A place that names a device
        JAX does not have is an error — never another device in its
        stead: `jax.devices("tpu")` raises where there is no TPU, and an
        id past the last device raises here."""
        import jax

        devs = jax.devices(self.device_type)
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: JAX has {len(devs)} {self.device_type} "
                "device(s)")
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


# CUDAPlace is accepted for API compatibility and maps to the accelerator.
class CUDAPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPinnedPlace(Place):
    """Pinned host memory place. On TPU, host staging buffers are managed by
    PJRT; this maps to the host (CPU) side of the transfer."""

    def __init__(self):
        super().__init__("cpu", 0)


@functools.lru_cache(maxsize=None)
def _default_accelerator_type() -> str:
    import jax

    return jax.devices()[0].platform


_expected_place = None


def get_device() -> str:
    p = _get_expected_place()
    return f"{p.device_type}:{p.device_id}"


def set_device(device: str) -> Place:
    global _expected_place
    if ":" in device:
        dtype_, did = device.split(":")
        did = int(did)
    else:
        dtype_, did = device, 0
    if dtype_ in ("gpu", "cuda", "xpu"):
        dtype_ = _default_accelerator_type()
    _expected_place = Place(dtype_, did)
    return _expected_place


def _get_expected_place() -> Place:
    if _expected_place is not None:
        return _expected_place
    return Place(_default_accelerator_type(), 0)


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return _default_accelerator_type() == "tpu"


def device_count() -> int:
    import jax

    return jax.device_count()
