"""Megatron-style tensor-parallel layers.

Reference analog: fleet/layers/mpu/mp_layers.py — VocabParallelEmbedding (:37),
ColumnParallelLinear (:175), RowParallelLinear (:334), ParallelCrossEntropy
(:500); RNG isolation RNGStatesTracker (mpu/random.py:32).

TPU-first: weights are FULL logical tensors annotated with NamedSharding over
the mesh "model" axis — the pjit partitioner holds one shard per device and
inserts the all-reduce/all-gather the reference codes by hand (SURVEY.md §7
row "mp layers"). The explicit-collective path (mp_ops) activates inside
shard_map for kernels that need manual comm placement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ....framework.core import Tensor
from ....nn.layer_base import Layer
from ....nn.initializer_util import materialize_parameter
from ....nn import initializer as I
from ....nn import functional as F
from ....ops._helpers import ensure_tensor, call_op, const_input
from ....ops.dispatch import mark_collective
from ...mesh import get_global_mesh
from .mp_ops import (_c_identity, _mp_allreduce, _c_concat, in_spmd_axis,
                     _mp_collective_key)

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear",
           "RowParallelLinear", "ParallelCrossEntropy", "RNGStatesTracker",
           "get_rng_state_tracker", "model_parallel_random_seed"]


def _try_shard(param, spec):
    """Annotate a parameter with a mesh sharding (no-op without a multi-device
    mesh)."""
    try:
        mesh = get_global_mesh()
        if mesh is None or mesh.size <= 1:
            return
        param._value = jax.device_put(param._value,
                                      NamedSharding(mesh, spec))
    except Exception:
        pass


class VocabParallelEmbedding(Layer):
    """Vocab-sharded embedding. Reference analog: mp_layers.py:37 over
    operators/collective/c_embedding_op.cc — each rank holds a contiguous
    vocab slice, looks up in-range ids locally (out-of-range rows produce
    zeros), and the partial results are summed over the mp group.

    Under pjit the P("model", None) weight placement lets the partitioner
    derive that pattern; inside shard_map the explicit masked-lookup + psum
    (exact c_embedding semantics) is emitted."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self.weight = materialize_parameter(
            [num_embeddings, embedding_dim], weight_attr, self._dtype,
            default_initializer=I.XavierNormal())
        _try_shard(self.weight, P("model", None))

    def forward(self, x):
        if not in_spmd_axis():
            return F.embedding(x, self.weight)
        x = ensure_tensor(x)

        def fn(w_local, ids):
            # inside shard_map the weight is this rank's vocab slice
            # [V/n, D] (same contract as Column/RowParallelLinear): rank i
            # owns rows [i*vshard, (i+1)*vshard)
            ids = ids.astype(jnp.int32)
            idx = jax.lax.axis_index("model")
            vshard = w_local.shape[0]
            local = ids - idx * vshard
            in_range = (local >= 0) & (local < vshard)
            safe = jnp.clip(local, 0, vshard - 1)
            out = jnp.take(w_local, safe, axis=0)
            out = jnp.where(in_range[..., None], out, jnp.zeros_like(out))
            return jax.lax.psum(out, "model")
        # ids ride as a dispatch input (the PR 3 embedding fix): a
        # captured id array would re-key the op on every batch
        mark_collective(fn, _mp_collective_key("c_embedding"))
        return call_op("c_embedding", fn,
                       (ensure_tensor(self.weight), const_input(x)))


class ColumnParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.gather_output = gather_output
        self.weight = materialize_parameter(
            [in_features, out_features], weight_attr, self._dtype,
            default_initializer=I.XavierNormal())
        self.bias = materialize_parameter(
            [out_features], None if has_bias in (None, True) else False,
            self._dtype, is_bias=True) if has_bias is not False else None
        _try_shard(self.weight, P(None, "model"))
        if self.bias is not None:
            _try_shard(self.bias, P("model"))

    def forward(self, x):
        if in_spmd_axis():
            x = _c_identity(x)
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            if in_spmd_axis():
                out = _c_concat(out)
            else:
                out = _constrain_replicated(out)
        return out


class RowParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = materialize_parameter(
            [in_features, out_features], weight_attr, self._dtype,
            default_initializer=I.XavierNormal())
        self.bias = materialize_parameter(
            [out_features], None, self._dtype, is_bias=True) \
            if has_bias is not False else None
        _try_shard(self.weight, P("model", None))

    def forward(self, x):
        out = F.linear(x, self.weight, None)
        if in_spmd_axis():
            out = _mp_allreduce(out)
        else:
            out = _constrain_replicated(out)
        if self.bias is not None:
            out = out + self.bias
        return out


def _constrain_replicated(t):
    """Ask the partitioner to produce a replicated (fully-reduced) value —
    this is where XLA inserts the all-reduce for row-parallel matmuls."""
    try:
        mesh = get_global_mesh()
        if mesh is None or mesh.size <= 1:
            return t

        def fn(v):
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P()))
        return call_op("sharding_constraint", fn, (ensure_tensor(t),))
    except Exception:
        return t


class ParallelCrossEntropy(Layer):
    """Reference analog: mp_layers.py:500 ParallelCrossEntropy over
    c_softmax_with_cross_entropy_op — vocab-sharded softmax CE that never
    materializes the gathered logits.

    Under pjit, plain cross-entropy over vocab-sharded logits is partitioned by
    XLA into exactly that pattern; inside shard_map the explicit psum-based
    formulation is used."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        input = ensure_tensor(input)
        label = ensure_tensor(label)
        if not in_spmd_axis():
            from ....nn.functional.loss import cross_entropy
            return cross_entropy(input, label, reduction="none",
                                 ignore_index=self.ignore_index)
        ignore_index = self.ignore_index

        def fn(logits, lab_v):
            # shard-local logits: [.., V/mp]; global softmax via psum
            n = jax.lax.axis_size("model")
            idx = jax.lax.axis_index("model")
            vshard = logits.shape[-1]
            local_max = jnp.max(logits, axis=-1, keepdims=True)
            # the max-shift cancels in d(softmax-CE)/d(logits); pmax has no
            # VJP rule, and none is needed — cut the tape before it
            gmax = jax.lax.pmax(jax.lax.stop_gradient(local_max), "model")
            ex = jnp.exp(logits - gmax)
            denom = jax.lax.psum(jnp.sum(ex, axis=-1, keepdims=True), "model")
            # pick the target logit if it lives in this shard
            lab = lab_v
            if lab.ndim == logits.ndim:
                lab = lab.squeeze(-1)
            local_lab = lab - idx * vshard
            in_range = (local_lab >= 0) & (local_lab < vshard)
            safe = jnp.clip(local_lab, 0, vshard - 1).astype(jnp.int32)
            picked = jnp.take_along_axis(logits - gmax, safe[..., None],
                                         axis=-1)[..., 0]
            picked = jnp.where(in_range, picked, 0.0)
            picked = jax.lax.psum(picked, "model")
            loss = jnp.log(denom[..., 0]) - picked
            # parity with the dense path: ignored labels contribute 0 loss
            # (and therefore 0 gradient — loss is constant in logits there)
            return jnp.where(lab == ignore_index,
                             jnp.zeros_like(loss), loss)
        mark_collective(fn, _mp_collective_key("parallel_cross_entropy",
                                               ignore_index))
        return call_op("parallel_cross_entropy", fn,
                       (input, const_input(label)))


class RNGStatesTracker:
    """Per-parallel-region RNG isolation. Reference analog: mpu/random.py:32 —
    tracks named states so dropout inside/outside mp regions decorrelates."""

    def __init__(self):
        self.states_ = {}
        self.seeds_ = set()

    def reset(self):
        self.states_ = {}
        self.seeds_ = set()

    def add(self, name, seed):
        if seed in self.seeds_:
            raise ValueError(f"seed {seed} already exists")
        if name in self.states_:
            raise ValueError(f"state {name} already exists")
        self.seeds_.add(seed)
        self.states_[name] = jax.random.key(seed)

    def rng_state(self, name="model-parallel-rng"):
        import contextlib

        @contextlib.contextmanager
        def cm():
            if name not in self.states_:
                raise ValueError(f"state {name} does not exist")
            from ....framework import random as frandom
            key = self.states_[name]
            key, sub = jax.random.split(key)
            self.states_[name] = key
            with frandom.tracing_key_scope(sub):
                yield
        return cm()


_RNG_STATE_TRACKER = RNGStatesTracker()


def get_rng_state_tracker():
    return _RNG_STATE_TRACKER


def model_parallel_random_seed(seed=None):
    import random as pyrandom
    seed = seed or (pyrandom.randint(0, 2 ** 31 - 1))
    global_seed = seed
    local_seed = seed + 1024 + 1  # + mp rank in the reference
    _RNG_STATE_TRACKER.reset()
    _RNG_STATE_TRACKER.add("global-seed", global_seed)
    _RNG_STATE_TRACKER.add("model-parallel-rng", local_seed)
