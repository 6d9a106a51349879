"""Device / place API.

Analog of reference paddle/fluid/platform/place.h (Place variant) and
platform/device_context.* (DeviceContextPool). On TPU, XLA/PJRT owns device
contexts and streams, so a Place is a thin handle over a jax.Device; the
DeviceContextPool's job (one context+stream per device) is done by PJRT.
"""
from __future__ import annotations

import jax

__all__ = ["CPUPlace", "CUDAPlace", "TPUPlace", "XPUPlace", "CUDAPinnedPlace",
           "set_device", "get_device", "get_all_devices", "device_count",
           "is_compiled_with_cuda", "is_compiled_with_xpu",
           "is_compiled_with_tpu"]


class Place:
    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (isinstance(other, Place) and self._kind == other._kind
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    _kind = "cpu"


class CUDAPlace(Place):
    # Accepted for API parity; maps to the default accelerator.
    _kind = "gpu"


class CUDAPinnedPlace(Place):
    _kind = "pinned"


class XPUPlace(Place):
    _kind = "xpu"


class TPUPlace(Place):
    _kind = "tpu"


_current = None


def _platform():
    return jax.devices()[0].platform


def set_device(device: str):
    """paddle.set_device — accepted for parity. XLA owns placement; sharding
    (paddle_tpu.distributed) is the multi-device mechanism."""
    global _current
    _current = device
    return device


def get_device() -> str:
    if _current is not None:
        return _current
    p = _platform()
    return f"{p}:0"


def get_all_devices():
    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"
