"""Shared helpers for the Pallas TPU kernels."""
from __future__ import annotations

import numpy as np
import jax

# index maps must emit i32 — a python literal 0 traces as i64 under the
# framework's x64 mode, which Mosaic cannot legalize
ZERO = np.int32(0)


def on_tpu():
    """Whether the default device executes Pallas TPU kernels. A backend
    that fails to initialise raises here: answering False would route
    every kernel to its XLA path without a trace of why."""
    return jax.devices()[0].platform == "tpu"
