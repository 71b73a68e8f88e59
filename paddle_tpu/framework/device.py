"""Device / Place management.

Mirrors ``paddle.set_device`` / ``paddle.get_device`` and the Place hierarchy
(ref: /root/reference/paddle/phi/common/place.h, python/paddle/device/__init__.py).
On TPU the native placement unit is a jax.Device; Places are thin wrappers so
paddle-style code (``paddle.CUDAPlace(0)`` etc.) keeps working, with 'tpu' as
the first-class device kind.
"""
from __future__ import annotations

import os

import jax

from ..flags import get_flag


def on_tpu() -> bool:
    """THE platform predicate: every kernel-vs-interpret, packed-step and
    flash-attention choice in the package asks this one function. No
    ``except``: a backend that cannot list its devices is an error to
    surface, not a reason to take the CPU path."""
    return jax.devices()[0].platform == "tpu"


def use_pallas_kernels() -> bool:
    """Do Pallas kernels run here: on a TPU, with
    ``FLAGS_enable_pallas_kernels`` on. THE answer to "kernel or jnp
    fallback" for every caller (the paged-cache seam, the serving
    cores, the scheduler's packed step, sdpa, the int8 matmul). Call it
    by attribute on this module (``device.use_pallas_kernels()``), never
    bound by name at import: a test that patches it here then reaches
    every caller at once."""
    return on_tpu() and bool(get_flag("FLAGS_enable_pallas_kernels", True))


def compile_cache_dir() -> str:
    """Where the persistent XLA compile cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when set, else ``.jax_cache`` at
    the checkout root (next to the ``paddle_tpu`` package). The path is
    part of every cache key, so it must not move between runs — no
    tempfile, pid or time in it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def configure_compile_cache() -> None:
    """Point jax's persistent compile cache at ``compile_cache_dir()``.
    Called once from ``paddle_tpu/__init__`` (the one place every entry
    point passes through). When the environment variable is set jax
    reads it itself and nothing is set here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())


class Place:
    """Base place. Holds a device kind + index resolved against jax.devices()."""

    kind = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self):
        return f"Place({self.kind}:{self._device_id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self.kind == other.kind and \
            self._device_id == other._device_id

    def __hash__(self):
        return hash((self.kind, self._device_id))

    def jax_device(self):
        backend = {"tpu": "tpu", "gpu": "gpu", "cpu": "cpu"}.get(self.kind)
        devs = jax.devices() if backend is None else _devices_for(backend)
        return devs[self._device_id % len(devs)]


def _devices_for(backend):
    try:
        return jax.devices(backend)
    except RuntimeError:
        return jax.devices()


class TPUPlace(Place):
    kind = "tpu"


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self):
        super().__init__(0)


class CUDAPlace(Place):
    # Accepted for API parity; resolves to whatever accelerator jax has.
    kind = "gpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(Place):
    kind = "xpu"


class CustomPlace(Place):
    def __init__(self, dev_type, device_id=0):
        super().__init__(device_id)
        self.kind = dev_type


_CURRENT_DEVICE = None  # lazily resolved


def _default_device_str():
    return f"{jax.default_backend()}:0"


def set_device(device):
    """paddle.set_device('tpu') / 'tpu:0' / 'cpu' / 'gpu:1'."""
    global _CURRENT_DEVICE
    if isinstance(device, Place):
        _CURRENT_DEVICE = f"{device.kind}:{device.get_device_id()}"
        return device
    device = str(device)
    if ":" not in device:
        device = device + ":0"
    kind, idx = device.split(":")
    if kind in ("gpu", "cuda", "tpu", "xpu", "npu"):
        # All accelerator names alias the real accelerator backend on this host.
        _CURRENT_DEVICE = f"{kind}:{idx}"
        place = TPUPlace(int(idx)) if kind == "tpu" else CUDAPlace(int(idx))
    elif kind == "cpu":
        _CURRENT_DEVICE = "cpu:0"
        place = CPUPlace()
    else:
        _CURRENT_DEVICE = device
        place = CustomPlace(kind, int(idx))
    return place


def get_device() -> str:
    global _CURRENT_DEVICE
    if _CURRENT_DEVICE is None:
        _CURRENT_DEVICE = _default_device_str()
    return _CURRENT_DEVICE


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()
