"""Dtype system: paddle-style dtype names over jax/numpy dtypes.

Reference analog: paddle/phi/common/data_type.h (DataType enum) and the
python-visible `paddle.float32`-style handles (python/paddle/framework/dtype.py).
TPU-first: bfloat16 is a first-class dtype; default float dtype is configurable
(paddle.set_default_dtype).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes

# paddle exposes float64/int64 as first-class dtypes (phi/common/data_type.h);
# jax needs x64 enabled for them. Kernels pick their compute dtype explicitly
# (bf16/f32 on TPU), so this only widens what users may request. NOTE: this is
# a process-wide jax config change — bare jnp.ones(...) elsewhere becomes
# float64, which the TPU compiles but emulates (a float64 sort+cumsum over
# [8, 50304] took 110 s to compile for a v5e). It stays on because the
# main-path programs are free of 64-bit device types all the same:
# chip_smoke.py fails if the TrainStep program text holds an f64 (PR 21:
# none in the 124M/355M train steps, nor in the engine's decode and prefill
# programs compiled for the chip). Set PADDLE_TPU_X64=0 to opt out and
# forfeit float64 tensor support.
import os as _os

if _os.environ.get("PADDLE_TPU_X64", "1") != "0":
    jax.config.update("jax_enable_x64", True)

__all__ = [
    "DType", "convert_dtype", "to_jax_dtype", "to_paddle_dtype",
    "set_default_dtype", "get_default_dtype",
    "uint8", "int8", "int16", "int32", "int64",
    "float16", "bfloat16", "float32", "float64",
    "complex64", "complex128", "bool_",
    "is_floating_point_dtype", "is_integer_dtype", "is_complex_dtype",
]


class DType:
    """A paddle-style dtype handle wrapping a canonical numpy dtype."""

    __slots__ = ("name", "np_dtype")
    _registry: dict[str, "DType"] = {}

    def __init__(self, name: str, np_dtype):
        self.name = name
        self.np_dtype = np.dtype(np_dtype)
        DType._registry[name] = self

    def __repr__(self):
        return f"paddle_tpu.{self.name}"

    def __eq__(self, other):
        if isinstance(other, DType):
            return self.name == other.name
        try:
            return self.np_dtype == np.dtype(convert_dtype(other))
        except TypeError:
            return NotImplemented

    def __hash__(self):
        return hash(self.name)

    @property
    def itemsize(self):
        return self.np_dtype.itemsize


uint8 = DType("uint8", np.uint8)
int8 = DType("int8", np.int8)
int16 = DType("int16", np.int16)
int32 = DType("int32", np.int32)
int64 = DType("int64", np.int64)
float16 = DType("float16", np.float16)
bfloat16 = DType("bfloat16", ml_dtypes.bfloat16)
float32 = DType("float32", np.float32)
float64 = DType("float64", np.float64)
complex64 = DType("complex64", np.complex64)
complex128 = DType("complex128", np.complex128)
bool_ = DType("bool", np.bool_)

_NP_TO_NAME = {
    np.dtype(np.uint8): "uint8",
    np.dtype(np.int8): "int8",
    np.dtype(np.int16): "int16",
    np.dtype(np.int32): "int32",
    np.dtype(np.int64): "int64",
    np.dtype(np.float16): "float16",
    np.dtype(ml_dtypes.bfloat16): "bfloat16",
    np.dtype(np.float32): "float32",
    np.dtype(np.float64): "float64",
    np.dtype(np.complex64): "complex64",
    np.dtype(np.complex128): "complex128",
    np.dtype(np.bool_): "bool",
}

_FLOAT_NAMES = {"float16", "bfloat16", "float32", "float64"}
_INT_NAMES = {"uint8", "int8", "int16", "int32", "int64"}
_COMPLEX_NAMES = {"complex64", "complex128"}

_default_dtype = float32


def set_default_dtype(d) -> None:
    """Set default float dtype (accepts 'float32'/'bfloat16'/'float64'/'float16')."""
    global _default_dtype
    d = to_paddle_dtype(d)
    if d.name not in _FLOAT_NAMES:
        raise TypeError(
            f"set_default_dtype only supports float dtypes, got {d.name}")
    _default_dtype = d


def get_default_dtype() -> str:
    return _default_dtype.name


def convert_dtype(dtype) -> str:
    """Normalize any dtype spec (DType / str / np.dtype / jnp dtype) to its name."""
    if isinstance(dtype, DType):
        return dtype.name
    if isinstance(dtype, str):
        if dtype in DType._registry:
            return dtype
        # numpy-style aliases
        alias = {"float": "float32", "double": "float64", "half": "float16",
                 "int": "int32", "long": "int64", "bool_": "bool"}.get(dtype)
        if alias:
            return alias
        raise TypeError(f"Unsupported dtype string: {dtype!r}")
    npd = np.dtype(dtype)
    name = _NP_TO_NAME.get(npd)
    if name is None:
        raise TypeError(f"Unsupported dtype: {dtype!r}")
    return name


def to_paddle_dtype(dtype) -> DType:
    return DType._registry[convert_dtype(dtype)]


def to_jax_dtype(dtype):
    return to_paddle_dtype(dtype).np_dtype


def is_floating_point_dtype(dtype) -> bool:
    return convert_dtype(dtype) in _FLOAT_NAMES


def is_integer_dtype(dtype) -> bool:
    return convert_dtype(dtype) in _INT_NAMES


def is_complex_dtype(dtype) -> bool:
    return convert_dtype(dtype) in _COMPLEX_NAMES


class iinfo:
    """Integer type info (paddle.iinfo). Reference analog:
    python/paddle/framework exposing np.iinfo-backed machine limits."""

    def __init__(self, dtype):
        npd = to_jax_dtype(dtype)
        info = np.iinfo(npd)
        self.min = int(info.min)
        self.max = int(info.max)
        self.bits = int(info.bits)
        self.dtype = convert_dtype(dtype)

    def __repr__(self):
        return (f"paddle.iinfo(min={self.min}, max={self.max}, "
                f"bits={self.bits}, dtype={self.dtype})")


class finfo:
    """Float type info (paddle.finfo) — works for bfloat16 too (np.finfo
    supports ml_dtypes.bfloat16 via jax's numpy extension types)."""

    def __init__(self, dtype):
        npd = to_jax_dtype(dtype)
        try:
            info = np.finfo(npd)
        except ValueError:
            # np.finfo rejects the ml_dtypes extension types (bfloat16,
            # float8_*) — ml_dtypes ships its own finfo for them
            import ml_dtypes
            info = ml_dtypes.finfo(npd)
        self.min = float(info.min)
        self.max = float(info.max)
        self.eps = float(info.eps)
        self.tiny = float(info.tiny)
        self.smallest_normal = float(info.tiny)
        self.resolution = float(info.resolution)
        self.bits = int(info.bits)
        self.dtype = convert_dtype(dtype)

    def __repr__(self):
        return (f"paddle.finfo(min={self.min}, max={self.max}, "
                f"eps={self.eps}, bits={self.bits}, dtype={self.dtype})")
