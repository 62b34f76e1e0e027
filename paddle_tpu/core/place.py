"""Device / Place abstraction.

Reference: ``paddle/phi/common/place.h`` (Place/CPUPlace/GPUPlace/XPUPlace)
and ``python/paddle/device/__init__.py`` (set_device/get_device).  Here the
first-class accelerator is the TPU: ``TPUPlace(i)`` maps to ``jax.devices()[i]``.
XLA's CPU backend backs ``CPUPlace`` so every test can run device-free.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place. Equality is by (kind, device id)."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.kind, self._device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self._device_id})"

    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        devs = [d for d in jax.devices() if d.platform == self.kind]
        if not devs:
            # Fall back to the default backend (e.g. CPUPlace when only TPU
            # or only CPU is present).
            devs = jax.devices()
        return devs[self._device_id % len(devs)]


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    kind = "tpu"


class CustomPlace(Place):
    """Custom-device plugin analog (reference: phi/backends/custom/)."""

    def __init__(self, dev_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.kind = dev_type


# GPU alias kept for API compatibility; resolves to whatever accelerator
# backend jax exposes (on this stack: TPU).
class CUDAPlace(TPUPlace):
    pass


CUDAPinnedPlace = CPUPlace
XPUPlace = TPUPlace


@functools.lru_cache(None)
def _accel_available() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


_current_place: Place | None = None


def set_device(device) -> Place:
    """paddle.device.set_device — accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0', a Place."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        _current_place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "xpu", "cuda"):
        _current_place = TPUPlace(idx)
    else:
        _current_place = CustomPlace(name, idx)
    return _current_place


def get_device() -> str:
    p = _get_current_place()
    return f"{p.kind}:{p.get_device_id()}"


def _get_current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = TPUPlace(0) if _accel_available() else CPUPlace(0)
    return _current_place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()
