"""Mixture-of-Experts layer with expert parallelism over a mesh axis.

Reference analog: python/paddle/incubate/distributed/models/moe/
moe_layer.py:259 (MoELayer) — there, tokens are routed with argsort and moved
between ranks by the `global_scatter`/`global_gather` collective ops
(fluid/operators/collective/global_scatter_op.*), with per-rank dynamic token
counts exchanged first.

TPU-first redesign: GShard-style static-shape dispatch. The router builds
dispatch/combine tensors [tokens, experts, capacity]; token movement is two
einsums plus `jax.lax.all_to_all` over the expert-parallel mesh axis (the
global_scatter/global_gather analog, riding ICI), and expert FFNs are one
batched einsum over stacked weights [E, ...] — no per-expert loops, no
dynamic shapes, everything lands on the MXU.

Axis-name aware like mp_ops: inside a shard_map binding `moe_axis`, each
device owns E/ep experts and exchanges capacity buckets via all-to-all;
outside SPMD the layer computes all experts locally (and under pjit the same
einsum formulation lets XLA partition it).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .....framework.core import Tensor
from .....nn.layer_base import Layer
from .....nn import initializer as I
from .....nn.initializer_util import materialize_parameter, ParamAttr
from .....ops._helpers import ensure_tensor, call_op_multi
from .....ops.dispatch import mark_collective
from .....distributed.mesh import current_mesh, mesh_key
from .....distributed.fleet.meta_parallel.mp_ops import in_spmd_axis
from .gate import top1_dispatch, top2_dispatch, naive_dispatch

__all__ = ["MoELayer"]

_GATES = {"switch": top1_dispatch, "gshard": top2_dispatch,
          "naive": naive_dispatch}


class MoELayer(Layer):
    """Expert-parallel mixture of FFN experts.

    Args:
        d_model: token embedding size.
        d_hidden: expert FFN hidden size.
        num_experts: total experts across the expert-parallel group.
        gate: "gshard" (top-2), "switch" (top-1), or "naive" (top-1, no aux).
        capacity_factor: per-expert buffer = cf * top_k * tokens / experts.
        moe_axis: mesh axis name carrying expert parallelism (the reference's
            moe_group; typically the data axis).
    After forward, `self.l_aux` holds the load-balance loss to add to the
    training objective (reference MoELayer exposes the same attribute).
    """

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 capacity_factor=1.25, eval_capacity_factor=2.0,
                 moe_axis="data", weight_attr=None, group=None,
                 recompute_interval=0, name=None):
        super().__init__()
        if gate not in _GATES:
            raise ValueError(f"unknown gate {gate!r}; one of {list(_GATES)}")
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.gate_type = gate
        self.top_k = 2 if gate == "gshard" else 1
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.moe_axis = moe_axis
        self.l_aux = None

        init = I.XavierNormal()
        self.gate_weight = materialize_parameter(
            [d_model, num_experts], ParamAttr(initializer=init), "float32")
        self.w1 = materialize_parameter(
            [num_experts, d_model, d_hidden], weight_attr or
            ParamAttr(initializer=init), self._dtype)
        self.b1 = materialize_parameter([num_experts, d_hidden], None,
                                        self._dtype, is_bias=True)
        self.w2 = materialize_parameter(
            [num_experts, d_hidden, d_model], weight_attr or
            ParamAttr(initializer=init), self._dtype)
        self.b2 = materialize_parameter([num_experts, d_model], None,
                                        self._dtype, is_bias=True)

    def _capacity(self, tokens, experts):
        cf = self.capacity_factor if self.training else \
            self.eval_capacity_factor
        return max(4, int(math.ceil(cf * self.top_k * tokens / experts)))

    def forward(self, x):
        x = ensure_tensor(x)
        dispatch_fn = _GATES[self.gate_type]
        axis = self.moe_axis
        # static trace-time facts
        spmd = in_spmd_axis(axis)

        def fn(xv, wg, w1, b1, w2, b2):
            tokens = xv.reshape(-1, self.d_model)
            t = tokens.shape[0]
            e_total = wg.shape[1]
            cap = self._capacity(t, e_total)

            logits = tokens.astype(jnp.float32) @ wg.astype(jnp.float32)
            gates = jax.nn.softmax(logits, axis=-1)
            disp, combine, aux = dispatch_fn(gates, cap)
            disp = disp.astype(xv.dtype)
            combine = combine.astype(xv.dtype)

            # bucket tokens per (expert, capacity slot): [E, C, M]
            buckets = jnp.einsum("tec,tm->ecm", disp, tokens)
            if spmd:
                ep = jax.lax.axis_size(axis)
                e_local = w1.shape[0]
                if e_local * ep != e_total:
                    raise ValueError(
                        f"expert weights carry {e_local} local experts × "
                        f"ep={ep} but router has {e_total} experts")
                # exchange: every device sends each peer its share of
                # experts; receives [E_local, ep*C, M]
                buckets = jax.lax.all_to_all(buckets, axis, split_axis=0,
                                             concat_axis=1, tiled=True)
            h = jnp.einsum("ecm,emh->ech", buckets, w1) + b1[:, None, :]
            h = jax.nn.gelu(h)
            out = jnp.einsum("ech,ehm->ecm", h, w2) + b2[:, None, :]
            if spmd:
                out = jax.lax.all_to_all(out, axis, split_axis=1,
                                         concat_axis=0, tiled=True)
                # aux loss averaged over the expert-parallel group
                aux = jax.lax.pmean(aux, axis)
            y = jnp.einsum("tec,ecm->tm", combine, out)
            return y.reshape(xv.shape), aux.astype(jnp.float32)

        # Funnel keying: fn closes over `self` (unkeyable by the closure
        # scan), but the traced program is fully determined by the gate
        # kind, embedding size, the expert axis + mesh, and the ACTIVE
        # capacity factor — token/expert counts ride in via input shapes.
        # Stamping that identity (ops/dispatch.mark_collective) lets MoE
        # dispatch join chain fusion and the super-cycle instead of
        # poisoning every cycle as `collective_unkeyed`.
        mkey = mesh_key(current_mesh()) if spmd else None
        cf = self.capacity_factor if self.training else \
            self.eval_capacity_factor
        key = None
        if not spmd or mkey is not None:
            key = ("moe_layer", self.gate_type, self.top_k, self.d_model,
                   axis, bool(spmd), float(cf), mkey)
        mark_collective(fn, key)
        y, aux = call_op_multi(
            "moe_layer", fn,
            (x, self.gate_weight, self.w1, self.b1, self.w2, self.b2), 2)
        self.l_aux = aux
        return y
