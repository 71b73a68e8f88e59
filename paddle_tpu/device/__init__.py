"""paddle.device (ref: /root/reference/python/paddle/device/__init__.py —
set_device/get_device/device_count and the cuda stream/event surface).

TPU mapping: devices come from jax; streams/events are XLA's async
dispatch (every jitted call is stream-ordered), so Stream/Event are thin
ordering objects whose synchronize() forces completion via a host sync.
"""
from __future__ import annotations

import jax

from ..framework.device import (TPUPlace, CPUPlace, CustomPlace,  # noqa: F401
                                CUDAPlace, CUDAPinnedPlace, XPUPlace,
                                get_device, is_compiled_with_cuda,
                                is_compiled_with_tpu, is_compiled_with_xpu,
                                set_device)
from . import cuda  # noqa: F401

__all__ = ["get_device", "set_device", "get_all_device_type",
           "get_all_custom_device_type", "get_available_device",
           "get_available_custom_device", "device_count", "cuda",
           "is_compiled_with_cuda", "is_compiled_with_xpu",
           "is_compiled_with_tpu", "XPUPlace", "IPUPlace", "MLUPlace"]


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type():
    return sorted({d.platform for d in jax.devices()}
                  - {"cpu", "gpu", "tpu"})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in ("cpu", "gpu", "tpu")]


def device_count(device_type=None):
    if device_type is None:
        return len(jax.devices())
    try:
        return len(jax.devices(device_type))
    except RuntimeError:
        return 0


IPUPlace = MLUPlace = XPUPlace
