"""Build the native runtime library (csrc/ -> libpaddle_tpu_core.so).

Reference analog: the reference compiles its runtime with CMake into
`libpaddle` (python/setup.py.in bundles it); here the native surface is small
enough that a direct g++ invocation at first import (cached by source mtime)
replaces the build system. Falls back gracefully: importers must handle
load_library() returning None and use pure-Python equivalents.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

_LIB = None
_TRIED = False

# Everything the program builds at run time (this library, JAX's compile
# cache) lands under one git-ignored directory of the checkout — fixed, so a
# second run finds what the first one built. Defined here, with no import
# from the rest of the package: worker processes load paddle_tpu.core alone.
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache")

_SRC_FILES = ("tcp_store.cc", "workqueue.cc", "host_tracer.cc",
              "ckpt_writer.cc")


def _csrc_dir():
    """csrc/ in the source tree (repo root) or bundled in the wheel
    (paddle_tpu/csrc, packaged by setup.py)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo_csrc = os.path.join(os.path.dirname(pkg), "csrc")
    if os.path.isdir(repo_csrc):
        return repo_csrc
    return os.path.join(pkg, "csrc")


def _prebuilt_path():
    """Wheel builds ship the compiled library next to this module."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "libpaddle_tpu_core.so")


def _cache_dir():
    """Fixed build directory inside the checkout (git-ignored)."""
    d = os.path.join(CACHE_ROOT, "native")
    os.makedirs(d, exist_ok=True)
    return d


def _needs_rebuild(lib_path, sources):
    if not os.path.exists(lib_path):
        return True
    lib_mtime = os.path.getmtime(lib_path)
    return any(os.path.getmtime(s) > lib_mtime for s in sources)


def build_library(verbose=False):
    """Compile csrc/*.cc into a shared library; returns path or None.
    A library prebuilt by the wheel (setup.py BuildNative) wins outright."""
    pre = _prebuilt_path()
    if os.path.exists(pre):
        return pre
    csrc = _csrc_dir()
    sources = [os.path.join(csrc, f) for f in _SRC_FILES]
    if not all(os.path.exists(s) for s in sources):
        return None
    try:
        lib_path = os.path.join(_cache_dir(), "libpaddle_tpu_core.so")
    except OSError:                     # read-only install location
        return None
    if not _needs_rebuild(lib_path, sources):
        return lib_path
    # compile to a private temp name and atomically rename so a concurrent
    # process never dlopens a half-written library
    tmp_path = lib_path + f".tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
           "-o", tmp_path] + sources + ["-lpthread"]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if res.returncode != 0:
        if verbose:
            print("paddle_tpu native build failed:\n" + res.stderr)
        return None
    try:
        os.replace(tmp_path, lib_path)
    except OSError:
        return None
    return lib_path


def load_library():
    """Build (if needed) and dlopen the native library. Returns the ctypes
    CDLL or None when no toolchain is available."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("PADDLE_TPU_DISABLE_NATIVE"):
        return None
    path = build_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None

    c = ctypes
    lib.pd_store_server_start.restype = c.c_void_p
    lib.pd_store_server_start.argtypes = [c.c_int, c.POINTER(c.c_int)]
    lib.pd_store_server_stop.argtypes = [c.c_void_p]
    lib.pd_store_client_connect.restype = c.c_void_p
    lib.pd_store_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pd_store_client_close.argtypes = [c.c_void_p]
    lib.pd_store_set.restype = c.c_int64
    lib.pd_store_set.argtypes = [c.c_void_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_uint32]
    lib.pd_store_get.restype = c.c_int64
    lib.pd_store_get.argtypes = [c.c_void_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_uint32]
    lib.pd_store_add.restype = c.c_int64
    lib.pd_store_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64,
                                 c.POINTER(c.c_int64)]
    lib.pd_store_wait.restype = c.c_int64
    lib.pd_store_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.pd_store_delete.restype = c.c_int64
    lib.pd_store_delete.argtypes = [c.c_void_p, c.c_char_p]
    lib.pd_store_ping.restype = c.c_int64
    lib.pd_store_ping.argtypes = [c.c_void_p]

    lib.pd_pool_create.restype = c.c_void_p
    lib.pd_pool_create.argtypes = [c.c_int]
    lib.pd_pool_destroy.argtypes = [c.c_void_p]
    lib.pd_pool_parallel_memcpy.argtypes = [
        c.c_void_p, c.POINTER(c.c_void_p), c.POINTER(c.c_void_p),
        c.POINTER(c.c_uint64), c.c_int]

    lib.pd_queue_create.restype = c.c_void_p
    lib.pd_queue_create.argtypes = [c.c_uint64]
    lib.pd_queue_destroy.argtypes = [c.c_void_p]
    lib.pd_queue_close.argtypes = [c.c_void_p]
    lib.pd_queue_push.restype = c.c_int
    lib.pd_queue_push.argtypes = [c.c_void_p, c.c_uint64, c.c_int64]
    lib.pd_queue_pop.restype = c.c_int
    lib.pd_queue_pop.argtypes = [c.c_void_p, c.POINTER(c.c_uint64), c.c_int64]
    lib.pd_queue_size.restype = c.c_uint64
    lib.pd_queue_size.argtypes = [c.c_void_p]

    lib.pd_trace_register_name.restype = c.c_uint32
    lib.pd_trace_register_name.argtypes = [c.c_char_p]
    lib.pd_trace_enable.argtypes = [c.c_int]
    lib.pd_trace_is_enabled.restype = c.c_int
    lib.pd_trace_now_ns.restype = c.c_uint64
    lib.pd_trace_span.argtypes = [c.c_uint32, c.c_uint64, c.c_uint64]
    lib.pd_trace_harvest.restype = c.c_uint64
    lib.pd_trace_harvest.argtypes = [c.POINTER(c.c_uint64), c.c_uint64]
    lib.pd_trace_pending.restype = c.c_uint64
    lib.pd_trace_name.restype = c.c_int64
    lib.pd_trace_name.argtypes = [c.c_uint32, c.c_char_p, c.c_uint64]

    _LIB = lib
    return _LIB
