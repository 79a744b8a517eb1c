"""Where JAX keeps compiled programs between processes.

One rule for every entry point that compiles (chip_smoke.py, the
benchmark's programs, tests/conftest.py): the cache directory is part of
the cache's key, so it is either where `JAX_COMPILATION_CACHE_DIR` says — JAX reads
that variable itself, and nothing here overrides it — or one fixed
directory inside the checkout. Never a temporary name, a pid or the time:
a directory that moves never hits.
"""
from __future__ import annotations

import os

from ..sysconfig import CACHE_ROOT

__all__ = ["enable_compile_cache"]


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache; returns its directory.
    Call before the first compile."""
    import jax
    # the eager path compiles hundreds of sub-second per-op programs;
    # JAX's default threshold (1 s) would keep none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CACHE_ROOT, "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
