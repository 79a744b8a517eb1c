"""Model-parallel comm primitives.

Reference analog: fleet/layers/mpu/mp_ops.py — _c_identity (:31), _c_concat
(:105), _c_split (:167), _mp_allreduce (:233), split API (:679).

TPU-first: these are *axis-name aware*. Outside any SPMD trace they are
identities over global arrays (the pjit partitioner inserts real collectives
from sharding constraints). Inside a shard_map over the "model" axis they emit
the explicit XLA collective (psum / all_gather / dynamic slice by axis_index).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....framework.core import Tensor
from ....ops._helpers import ensure_tensor, call_op
from ....ops.dispatch import mark_collective

__all__ = ["_c_identity", "_c_concat", "_c_split", "_mp_allreduce", "split",
           "in_spmd_axis", "MODEL_AXIS"]

MODEL_AXIS = "model"


def _mp_collective_key(kind, *extra):
    """Collective identity for an mp-axis fn: (kind, axis, bound axis
    size) — shapes ride in as dispatch inputs, so nothing else varies.
    None (→ the explicit unkeyable marker, so the poison is attributed
    instead of silent) when the axis size cannot be read."""
    try:
        return (kind, MODEL_AXIS, int(jax.lax.axis_size(MODEL_AXIS))) + extra
    except Exception:
        return None


def in_spmd_axis(axis_name=MODEL_AXIS):
    """True when called inside a shard_map/pmap trace binding `axis_name`
    with more than one shard. A bound size-1 axis carries no sharding —
    collectives over it are identities — so it does not count: this keeps
    dispatch decisions (ring attention, mp collectives) correct when a
    shard_map binds every mesh axis, degenerate ones included."""
    try:
        jax.lax.axis_index(axis_name)
    except (NameError, KeyError, TypeError, Exception):
        return False
    try:
        return jax.lax.axis_size(axis_name) > 1
    except Exception:
        return True


def _c_identity(tensor, group=None, skip_c_identity_dynamic=False):
    """Forward identity; backward all-reduce over the mp axis (column-parallel
    input)."""
    t = ensure_tensor(tensor)
    if not in_spmd_axis():
        return t

    def fn(v):
        @jax.custom_vjp
        def ident(x):
            return x

        def fwd(x):
            return x, None

        def bwd(_, g):
            return (jax.lax.psum(g, MODEL_AXIS),)
        ident.defvjp(fwd, bwd)
        return ident(v)
    mark_collective(fn, _mp_collective_key("c_identity"))
    return call_op("c_identity", fn, (t,))


def _mp_allreduce(tensor, group=None, use_calc_stream=True,
                  use_model_parallel=True, op=None):
    """Forward all-reduce; backward identity (row-parallel output)."""
    t = ensure_tensor(tensor)
    if not in_spmd_axis():
        return t

    def fn(v):
        @jax.custom_vjp
        def allred(x):
            return jax.lax.psum(x, MODEL_AXIS)

        def fwd(x):
            return jax.lax.psum(x, MODEL_AXIS), None

        def bwd(_, g):
            return (g,)
        allred.defvjp(fwd, bwd)
        return allred(v)
    mark_collective(fn, _mp_collective_key("mp_allreduce"))
    return call_op("mp_allreduce", fn, (t,))


def _c_concat(tensor, group=None):
    """All-gather along the last dim over the mp axis."""
    t = ensure_tensor(tensor)
    if not in_spmd_axis():
        return t

    def fn(v):
        return jax.lax.all_gather(v, MODEL_AXIS, axis=v.ndim - 1, tiled=True)
    mark_collective(fn, _mp_collective_key("c_concat"))
    return call_op("c_concat", fn, (t,))


def _c_split(tensor, group=None):
    """Slice this shard's chunk of the last dim."""
    t = ensure_tensor(tensor)
    if not in_spmd_axis():
        return t

    def fn(v):
        n = jax.lax.axis_size(MODEL_AXIS)
        idx = jax.lax.axis_index(MODEL_AXIS)
        chunk = v.shape[-1] // n
        return jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk,
                                            axis=v.ndim - 1)
    return call_op("c_split", fn, (t,))


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Reference analog: mp_ops.py:679 paddle.distributed.split — build a
    row/column-parallel linear or vocab-parallel embedding."""
    from .mp_layers import (ColumnParallelLinear, RowParallelLinear,
                            VocabParallelEmbedding)
    if operation == "linear":
        if axis == 0:
            layer = RowParallelLinear(size[0], size[1],
                                      weight_attr=weight_attr,
                                      has_bias=bias_attr is not False)
        else:
            layer = ColumnParallelLinear(size[0], size[1],
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out)
        return layer(x)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
        return layer(x)
    raise ValueError(f"unsupported split operation {operation!r}")
