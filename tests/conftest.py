"""Test env: force an 8-device virtual CPU platform BEFORE jax import.

Mirrors the reference's fake-backend fixture strategy
(python/paddle/fluid/tests/custom_runtime/ CustomCPU plugin): tests run
against a pluggable non-accelerator backend so CI needs no TPU; the driver
separately dry-runs the multi-chip path.
"""
import os

# the tests run on the CPU whatever the machine holds
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")

import jax

assert jax.devices()[0].platform == "cpu", "tests must run on CPU"
assert jax.device_count() == 8, "tests expect an 8-device virtual CPU mesh"

# Persistent XLA compilation cache: the distributed suites (pipeline /
# hybrid / auto-parallel over the 8-device mesh) are dominated by large
# SPMD compiles that are identical run-to-run, so a second run of the
# same checkout finds them. The AOT executable store (ops/aot_cache.py)
# defaults to $PADDLE_TPU_CACHE_DIR/aot, so it follows the same root.
from paddle_tpu.framework.compile_cache import enable_compile_cache

os.environ.setdefault("PADDLE_TPU_CACHE_DIR", enable_compile_cache())

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _no_mesh_outlives_its_module():
    """A worker runs many test files in one process, in an order that
    load decides (`--dist loadfile`). A global mesh left set by one file
    is read by the next: it is part of the AOT store's environment
    fingerprint, so a file that compares this process with FRESH child
    processes (tests/test_aot_cache.py) then misses every artifact they
    wrote. That is what failed `test_same_and_disjoint_key_races` under
    six workers and never alone."""
    yield
    from paddle_tpu.distributed.mesh import set_global_mesh
    set_global_mesh(None)


@pytest.fixture(autouse=True)
def _seed_everything():
    np.random.seed(0)
    import paddle_tpu as paddle
    paddle.seed(0)
    yield
