"""Device API. Reference analog: python/paddle/device/__init__.py
(set_device :328, get_all_custom_device_type :427) over phi Place/DeviceManager.

TPU-first: devices are jax devices; XLA owns streams/allocators, so this module
is a thin selection/query layer (SURVEY.md §7 translation table row 2).
"""
from __future__ import annotations

import jax

__all__ = ["set_device", "get_device", "get_all_device_type",
           "get_all_custom_device_type", "get_available_device",
           "device_count", "is_compiled_with_cuda", "is_compiled_with_rocm",
           "is_compiled_with_xpu", "is_compiled_with_npu",
           "is_compiled_with_custom_device", "CPUPlace", "CUDAPlace",
           "TPUPlace", "CUDAPinnedPlace", "XLADevice", "synchronize"]

_current_device = None


class _PlaceBase:
    device_type = "cpu"

    def __init__(self, device_id=0):
        self._device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    def __eq__(self, other):
        return (type(self) is type(other) and
                self._device_id == other._device_id)

    def get_device_id(self):
        return self._device_id


class CPUPlace(_PlaceBase):
    device_type = "cpu"


class TPUPlace(_PlaceBase):
    device_type = "tpu"


class CUDAPlace(_PlaceBase):
    # accepted for API parity; maps onto the default accelerator
    device_type = "gpu"


class CUDAPinnedPlace(_PlaceBase):
    device_type = "cpu"


class XLADevice:
    """Wrapper over a jax.Device."""

    def __init__(self, jax_device):
        self.jax_device = jax_device

    def __repr__(self):
        return f"XLADevice({self.jax_device.platform}:{self.jax_device.id})"


def _platform():
    return jax.devices()[0].platform


def set_device(device):
    """Accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0' (mapped to default accelerator)."""
    global _current_device
    name = device if isinstance(device, str) else getattr(
        device, "device_type", "cpu")
    _current_device = name
    return get_device()


def get_device():
    if _current_device is not None:
        return _current_device
    return f"{_platform()}:0"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [p for p in get_all_device_type() if p not in ("cpu", "gpu")]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_custom_device(device_type="tpu"):
    return device_type in get_all_device_type()


def synchronize():
    """Block until all enqueued device work completes."""
    for d in jax.live_arrays():
        d.block_until_ready()


class NPUPlace(_PlaceBase):
    """Parity shims for the reference's vendor places (no such backends
    here; they exist so configs naming them still parse)."""
    device_type = "npu"


class XPUPlace(_PlaceBase):
    device_type = "xpu"


class MLUPlace(_PlaceBase):
    device_type = "mlu"


class IPUPlace(_PlaceBase):
    device_type = "ipu"


def get_cudnn_version():
    """No cuDNN in the TPU build (reference: device/__init__.py returns
    None when not compiled with CUDA)."""
    return None


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_mlu():
    return False


def get_available_custom_device():
    """Custom-device inventory (reference: device/__init__.py) — the TPU
    build's accelerators surface through jax."""
    import jax
    try:
        return [f"{d.platform}:{d.id}" for d in jax.devices()
                if d.platform not in ("cpu",)]
    except RuntimeError:
        return []


__all__ += ["get_cudnn_version", "is_compiled_with_ipu",
            "is_compiled_with_cinn", "is_compiled_with_mlu",
            "get_available_custom_device"]
