"""paddle.sysconfig equivalent: include/lib paths for building extensions
against the native runtime (csrc/). Reference analog:
python/paddle/sysconfig.py."""
from __future__ import annotations

import os

from .core._build import CACHE_ROOT

__all__ = ["get_include", "get_lib", "CACHE_ROOT"]

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def get_include():
    """Directory of the native runtime sources/headers (csrc/)."""
    return os.path.join(_ROOT, "csrc")


def get_lib():
    """Directory holding the built native libraries (.so)."""
    from .core._build import _cache_dir
    return _cache_dir()
