"""SPMD pipeline parallelism over the mesh "pipe" axis.

Reference analog: the 1F1B runtime (fleet/meta_parallel/pipeline_parallel.py:117
forward_backward_pipeline, pp_utils/p2p_communication.py:53 SendRecvMeta) and
the FleetExecutor actor runtime (fluid/distributed/fleet_executor/carrier.h:49).

TPU-first design — no actor runtime, no p2p handshake. The pipeline is ONE
XLA program:

  - stage parameters are stacked on a leading dim and sharded over the mesh
    "pipe" axis, so each device group holds exactly its stage's weights;
  - the schedule is a `lax.scan` over timesteps inside `shard_map`: at step t
    device (stage) i computes micro-batch t-i, then hands its activation to
    stage i+1 with a single `lax.ppermute` hop over ICI;
  - `jax.grad` through the scan+ppermute yields the reverse pipeline
    automatically (ppermute transposes to the reversed ring), so the backward
    schedule mirrors the forward one with no hand-written p2p;
  - activation memory is bounded with `jax.checkpoint` on the per-stage body
    (the 1F1B memory discipline, achieved by remat instead of schedule order).

Schedule shape: GPipe-style fill/steady/drain — M+S-1 steps, steady-state
concurrency S (all stages busy on different micro-batches). The bubble
fraction is (S-1)/(M+S-1); choose num_microbatches >= num_stages.
`pipeline_schedule` exposes the (timestep -> {(stage, microbatch)}) map for
inspection and testing.

Interleaved virtual stages (num_virtual=V > 1, reference analog
PipelineParallelWithInterleave): device s holds model chunks s, s+S, ...,
s+(V-1)S; the grouped schedule (see interleaved_schedule) stays
ring-compatible — one hop, one chunk-application per device per step —
and cuts the fill/drain bubble to (S-1)/(V*M + S-1). Chunk selection inside
the scan is a dynamic-index over the lap dim — branchless on purpose: the
lap predicate diverges across pipe stages, and divergent lax.switch branches
deadlock once the partitioner plants resharding collectives for the auto
(data/sharding/model) axes inside them.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_map

from ....framework.core import Tensor
from ....framework import random as _random
from ....framework.autograd import set_grad_enabled

__all__ = ["pipeline_schedule", "interleaved_schedule", "spmd_pipeline",
           "PipelineTrainStep", "stack_stage_params", "find_block_run"]


def pipeline_schedule(num_micro, num_stages):
    """Forward schedule: list over timesteps of {(stage, microbatch)} active
    simultaneously. Steady state has all `num_stages` stages busy — this is
    the micro-batch overlap the schedule guarantees."""
    sched = []
    for t in range(num_micro + num_stages - 1):
        active = {(s, t - s) for s in range(num_stages)
                  if 0 <= t - s < num_micro}
        sched.append(active)
    return sched


def interleaved_schedule(num_micro, num_stages, num_virtual):
    """Grouped interleaved schedule (reference analog:
    PipelineParallelWithInterleave, fleet/meta_parallel/
    pipeline_parallel.py:461 — virtual pipeline stages, device s owns model
    chunks s, s+S, ..., s+(V-1)S).

    Device idx's work item at global chunk-step t is derived from its local
    step u = t - idx: group g = u // (S*V) (S micro-batches complete all V
    laps per group), lap l = (u % (S*V)) // S, member j = u % S, micro-batch
    m = g*S + j, chunk = l. This is exactly ring-compatible: the producer of
    (m, lap, stage-1) finishes at global step t-1, so one ppermute hop per
    step suffices and each device holds a single in-flight activation.

    Returns (timesteps list of {(stage, lap, micro)}, total_steps,
    bubble_fraction). Total steps = V*M + S - 1; bubble (S-1)/(V*M + S - 1),
    a V-fold reduction of the GPipe fill/drain cost.
    """
    S, V, M = num_stages, num_virtual, num_micro
    if M % S != 0:
        raise ValueError(
            f"interleaved schedule needs num_microbatches ({M}) divisible "
            f"by num_stages ({S})")
    total = V * M + S - 1
    sched = []
    for t in range(total):
        active = set()
        for s in range(S):
            u = t - s
            if not 0 <= u < V * M:
                continue
            g, r = divmod(u, S * V)
            l, j = divmod(r, S)
            active.add((s, l, g * S + j))
        sched.append(active)
    bubble = (S - 1) / total
    return sched, total, bubble


def spmd_pipeline(stage_fn, stage_params, x, *, mesh, axis="pipe", key=None,
                  num_virtual=1):
    """Run `x` through a pipeline of S stages laid out over `axis`.

    stage_fn(params_one_stage, mb) -> mb   (same shape/dtype out as in);
    when `key` is given, called as stage_fn(params, mb, subkey) with a key
    folded over (timestep, stage) so dropout masks differ per micro-batch
    and per stage.
    stage_params: pytree whose leaves have leading dim S, sharded over `axis`
    (with num_virtual=V > 1: leading dims [V, S], dim 1 sharded — device s
    holds model chunks s, s+S, ..., s+(V-1)S and the schedule follows
    interleaved_schedule, cutting the fill/drain bubble V-fold)
    x: [M, *mb_shape] micro-batched activations, replicated over `axis`
    returns [M, *mb_shape]: last stage's outputs, replicated over `axis`.

    Everything happens inside one shard_map over only the pipe axis; other
    mesh axes (data/model/sharding) stay in auto mode so existing Megatron
    shardings on the stage parameters keep working inside each stage.

    All shard_map inputs/outputs ride the pipe axis as `varying` values (x is
    tiled over the axis, the output is the stacked per-stage buffer with the
    last stage's slice selected OUTSIDE the shard_map): the program contains
    no psum, so collecting the result is a copy off the last stage rather
    than an all-reduce, and no AD transpose introduces one either (bf16
    psum inside shard_map over a sub-axis of a multi-axis mesh also breaks
    XLA:CPU float normalization, which the virtual-mesh tests would hit).
    """
    S = mesh.shape[axis]
    M = x.shape[0]
    V = num_virtual
    if S == 1:
        # degenerate pipeline: just apply the stage(s) to each microbatch
        params0 = tree_map(lambda l: l[0], stage_params) if V == 1 else None

        def all_chunks(mb, t):
            if V == 1:
                if key is None:
                    return stage_fn(params0, mb)
                return stage_fn(params0, mb, jax.random.fold_in(key, t))
            for l in range(V):
                chunk = tree_map(lambda p: p[l, 0], stage_params)
                k = None if key is None else jax.random.fold_in(
                    jax.random.fold_in(key, t), l)
                mb = stage_fn(chunk, mb) if k is None \
                    else stage_fn(chunk, mb, k)
            return mb
        return lax.map(lambda tm: all_chunks(tm[1], tm[0]),
                       (jnp.arange(M), x))
    if V > 1 and M % S != 0:
        raise ValueError(
            f"interleaved pipeline needs num_microbatches ({M}) divisible "
            f"by num_stages ({S})")
    perm = [(i, (i + 1) % S) for i in range(S)]
    total = V * M + S - 1

    def per_device(params_local, x_local):
        # V=1 leaves are [1, ...] (pipe dim); V>1 leaves are [V, 1, ...]
        my = tree_map(lambda l: jnp.squeeze(l, 0 if V == 1 else 1),
                      params_local)
        x_full = jnp.squeeze(x_local, 0)
        idx = lax.axis_index(axis)

        def body(carry, t):
            state, outs = carry
            # interleaved work item at local step u = t - idx (see
            # interleaved_schedule): lap l, member j, micro g*S + j
            u = t - idx
            g, r = jnp.divmod(u, S * V)
            l, j = jnp.divmod(r, S)
            micro = g * S + j
            # feed: stage 0 picks up a fresh micro-batch on its lap-0 steps
            inp = lax.dynamic_index_in_dim(x_full,
                                           jnp.clip(micro, 0, M - 1), 0,
                                           keepdims=False)
            feed = (idx == 0) & (l == 0)
            state = jnp.where(feed, inp, state)
            if V == 1:
                chunk = my
            else:
                # dynamic-index (NOT lax.switch): the lap predicate diverges
                # across pipe stages, and divergent branches deadlock when
                # the partitioner plants resharding collectives for the
                # auto (data/sharding/model) axes inside them. l is already
                # in [0, V-1] by floor-mod, even during fill (u < 0).
                chunk = tree_map(
                    lambda p: lax.dynamic_index_in_dim(p, l, 0,
                                                       keepdims=False), my)
            if key is None:
                out = stage_fn(chunk, state)
            else:
                out = stage_fn(chunk, state,
                               jax.random.fold_in(
                                   jax.random.fold_in(key, t), idx))
            # collect: stage S-1 emits micro `micro` on its last-lap steps
            # (micro <= M-1 holds whenever u >= 0 at the last stage)
            m_out = jnp.clip(micro, 0, M - 1)
            collect = (idx == S - 1) & (l == V - 1) & (u >= 0)
            prev = lax.dynamic_index_in_dim(outs, m_out, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(collect, out, prev), m_out, 0)
            # rotate: one ICI hop to the next stage
            state = lax.ppermute(out, axis, perm)
            return (state, outs), None

        # the carry varies across the pipe axis from step 1 on; x_full is
        # already varying (in_specs P(axis)), so zeros_like inherits it
        init = (jnp.zeros_like(x_full[0]), jnp.zeros_like(x_full))
        (_, outs), _ = lax.scan(body, init, jnp.arange(total))
        return outs[None]

    pspec = P(axis) if V == 1 else P(None, axis)
    mapped = jax.shard_map(per_device, mesh=mesh, axis_names={axis},
                           in_specs=(pspec, P(axis)), out_specs=P(axis))
    x_tiled = jnp.broadcast_to(x[None], (S,) + x.shape)
    stacked = mapped(stage_params, x_tiled)
    # only the last stage's buffer is real: select it outside the shard_map
    return lax.index_in_dim(stacked, S - 1, 0, keepdims=False)


def find_block_run(layers, num_stages, require_multiple=True):
    """Locate the longest contiguous run of structurally identical layers
    (the pipeline-able transformer blocks) in `layers`.

    Returns (start, count); with require_multiple (the uniform schedule)
    count is rounded down to a positive multiple of num_stages, otherwise
    (ragged LayerDesc partitions) any count >= num_stages is kept. Raises
    if no usable run exists. Layers outside the run become the prologue
    (before) and epilogue (after) — executed outside the pipelined region
    with their parameters sharded over the pipe axis (see
    PipelineTrainStep._place_edge_params), not replicated.
    """
    def sig(layer):
        return (type(layer).__name__,
                tuple((tuple(p.shape), str(p.dtype), p.stop_gradient)
                      for p in layer.parameters()))

    sigs = [sig(l) for l in layers]
    best = (0, 0)
    i = 0
    while i < len(layers):
        j = i
        while j < len(layers) and sigs[j] == sigs[i]:
            j += 1
        if sigs[i][1] and j - i > best[1]:   # has params and longer
            best = (i, j - i)
        i = j
    start, count = best
    if require_multiple:
        count = (count // num_stages) * num_stages
    elif count < num_stages:
        count = 0
    if count == 0:
        raise ValueError(
            f"no contiguous run of >= {num_stages} structurally identical "
            f"layers found; cannot partition into {num_stages} pipeline "
            f"stages")
    return start, count


def stack_stage_params(blocks, num_stages, mesh, axis="pipe",
                       num_virtual=1, stage_sizes=None):
    """Stack the parameters of `blocks` (len = V * S * per) into leaves of
    shape [S, per, *param_shape] (V=1) or [V, S, per, *param_shape] (V>1,
    interleaved: chunk l*S+s — blocks [(l*S+s)*per, ...) — lands at
    leaf[l, s]), sharded over `axis` on the stage dim and preserving each
    parameter's existing named sharding on the trailing dims (so Megatron
    "model"-axis placements survive stacking).

    stage_sizes: per-CHUNK block counts for HETEROGENEOUS partitions
    (reference analog: LayerDesc segmentation, pp_layers.py:92 SegmentLayers
    — stages need not be equal; with interleave the reference segments into
    S*V chunks and composes with PipelineParallelWithInterleave,
    pipeline_parallel.py:461). len(stage_sizes) == S (V=1) or S*V (V>1,
    chunk c = l*S + s holds blocks[offsets[c]:offsets[c+1]]). Leaves become
    [S, per_max, ...] (or [V, S, per_max, ...]) padded with copies of each
    chunk's first block (NaN-safe placeholders the masked schedule never
    selects); returns (stacked, valid_mask[S, per_max] or [V, S, per_max]).
    """
    S, V = num_stages, num_virtual
    n_chunks = S * V
    proto_params = blocks[0].parameters()
    ragged = stage_sizes is not None
    if ragged:
        if len(stage_sizes) != n_chunks or sum(stage_sizes) != len(blocks):
            raise ValueError(
                f"stage_sizes {stage_sizes} must have {n_chunks} entries "
                f"summing to {len(blocks)} blocks")
    else:
        # uniform = the degenerate ragged partition (equal chunks, no mask)
        stage_sizes = [len(blocks) // n_chunks] * n_chunks
    per_max = max(stage_sizes)
    offsets = np.cumsum([0] + list(stage_sizes))
    mask = np.zeros((V, S, per_max), bool)
    stacked = []
    for k, pp in enumerate(proto_params):
        laps = []
        for l in range(V):
            rows = []
            for s in range(S):
                c = l * S + s
                vals = [blocks[offsets[c] + j].parameters()[k]._value
                        for j in range(stage_sizes[c])]
                mask[l, s, :stage_sizes[c]] = True
                # padding slots are copies of the chunk's first block:
                # NaN-safe placeholders the masked schedule never selects
                vals += [vals[0]] * (per_max - stage_sizes[c])
                rows.append(jnp.stack(vals))
            laps.append(jnp.stack(rows))             # [S, per_max, *shape]
        leaf = laps[0] if V == 1 else jnp.stack(laps)
        spec = P()
        shd = getattr(pp._value, "sharding", None)
        if isinstance(shd, NamedSharding):
            spec = shd.spec
        lead = (axis, None) if V == 1 else (None, axis, None)
        full_spec = P(*lead, *tuple(spec))
        stacked.append(jax.device_put(leaf, NamedSharding(mesh, full_spec)))
    if not ragged:
        return stacked
    mask_np = mask[0] if V == 1 else mask
    mask_spec = P(axis, None) if V == 1 else P(None, axis, None)
    mask_leaf = jax.device_put(jnp.asarray(mask_np),
                               NamedSharding(mesh, mask_spec))
    return stacked, mask_leaf


def _acc_sharding(mesh, base_spec, shape, axis="sharding"):
    """Sharding for an optimizer-state leaf: keep the parameter's placement
    and additionally shard the largest free dim over the ZeRO `axis` (stage-1
    optimizer-state sharding, sharding_opt.py's policy lifted to stacked
    pipeline leaves)."""
    dims = list(base_spec) + [None] * (len(shape) - len(base_spec))
    n = mesh.shape.get(axis, 1)
    if n > 1:
        used = set()
        for d in dims:
            if isinstance(d, tuple):
                used.update(d)
            elif d is not None:
                used.add(d)
        if axis not in used:
            for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
                if dims[i] is None and shape[i] % n == 0 and shape[i] >= n:
                    dims[i] = axis
                    break
    return NamedSharding(mesh, P(*dims))


class PipelineTrainStep:
    """Fully-fused pipeline-parallel training step (fwd+bwd+optimizer in one
    jitted program), the pipe-axis sibling of paddle_tpu.jit.TrainStep.

    layers: a PipelineLayer or a flat list of nn.Layer executed sequentially.
    The longest run of identical layers is pipelined over the mesh "pipe"
    axis; everything before/after runs replicated (prologue/epilogue) under
    normal auto sharding. Weight tying between prologue and epilogue (e.g.
    GPT's tied wte/lm_head) is handled by parameter identity: a shared
    Parameter is a single leaf and its gradients accumulate through jax AD.
    """

    def __init__(self, layers, loss_fn, optimizer, *, mesh=None,
                 num_microbatches=1, axis="pipe", remat=True,
                 num_virtual=1, stage_sizes=None):
        from .pp_layers import PipelineLayer
        self._pp_segments = None
        if isinstance(layers, PipelineLayer):
            flat = [l for stage in layers._stage_layers for l in stage]
            if loss_fn is None:
                loss_fn = layers._loss_fn
            self._pp_segments = list(layers.segment_parts)
        else:
            flat = list(layers)
        self._stage_sizes = list(stage_sizes) if stage_sizes else None
        if self._stage_sizes is not None:
            if any(s <= 0 for s in self._stage_sizes):
                raise ValueError(f"stage_sizes must be positive, got "
                                 f"{self._stage_sizes}")
        if mesh is None:
            from ...mesh import get_global_mesh
            mesh = get_global_mesh()
        self.mesh = mesh
        self.axis = axis
        self.num_stages = mesh.shape[axis]
        self.num_virtual = num_virtual
        self.num_microbatches = num_microbatches
        if num_microbatches < self.num_stages:
            raise ValueError(
                f"num_microbatches ({num_microbatches}) must be >= pipeline "
                f"stages ({self.num_stages}) for a useful schedule")
        if num_virtual > 1 and num_microbatches % self.num_stages != 0:
            raise ValueError(
                f"interleaved pipeline (num_virtual={num_virtual}) needs "
                f"num_microbatches ({num_microbatches}) divisible by "
                f"stages ({self.num_stages})")
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._remat = remat
        self._flat = flat
        self._jitted = None
        self._program = None

    # -- construction -----------------------------------------------------
    def _resolve_stage_sizes(self, flat, start, count):
        """Per-chunk block counts (S entries for V=1, S*V for interleave —
        reference composes SegmentLayers uneven parts with
        PipelineParallelWithInterleave, pp_layers.py:92 +
        pipeline_parallel.py:461). Priority: explicit stage_sizes → a
        PipelineLayer's LayerDesc segmentation → uniform."""
        n_chunks = self.num_stages * self.num_virtual
        if self._stage_sizes is not None:
            if len(self._stage_sizes) != n_chunks:
                raise ValueError(
                    f"stage_sizes has {len(self._stage_sizes)} entries for "
                    f"{n_chunks} pipeline chunks (stages x virtual)")
            return self._stage_sizes
        if self._pp_segments is not None and \
                len(self._pp_segments) == n_chunks + 1:
            sizes = []
            for s in range(n_chunks):
                a, b = self._pp_segments[s], self._pp_segments[s + 1]
                sizes.append(max(0, min(b, start + count) - max(a, start)))
            if sum(sizes) == count and all(sz > 0 for sz in sizes):
                return sizes
        return None

    def _place_edge_params(self, outer):
        """Shard prologue/epilogue parameters over the PIPE axis instead of
        replicating them on every stage group. The reference balances an
        embedding-heavy stage 0 by segmentation (pp_layers.py:208); the
        TPU-first answer distributes the edge tensors across ALL pipe
        groups (largest divisible dim, e.g. the vocab dim of wte/lm_head)
        and lets the auto partitioner place the lookup/projection compute —
        better balanced than any single-stage placement, and a tied
        embedding (SharedLayerDesc) is one sharded leaf serving both
        ends."""
        if self.num_stages <= 1:
            return
        for p in outer:
            shd = getattr(p._value, "sharding", None)
            spec = tuple(shd.spec) if isinstance(shd, NamedSharding) else ()
            target = _acc_sharding(self.mesh, P(*spec), p._value.shape,
                                   axis=self.axis)
            p._value = jax.device_put(p._value, target)

    def _build(self):
        S = self.num_stages
        V = self.num_virtual
        flat = self._flat
        may_ragged = (self._stage_sizes is not None
                      or self._pp_segments is not None)
        start, count = find_block_run(flat, S * V,
                                      require_multiple=not may_ragged)
        sizes = self._resolve_stage_sizes(flat, start, count) if may_ragged \
            else None
        if sizes is not None and len(set(sizes)) == 1:
            sizes = None                       # uniform after all
        if sizes is None and count % (S * V) != 0:
            count = (count // (S * V)) * (S * V)
            if count == 0:
                raise ValueError(
                    f"cannot split the block run into {S * V} stages")
        self._blocks = flat[start:start + count]
        pre_layers = flat[:start]
        post_layers = flat[start + count:]
        self._stage_sizes_eff = sizes
        per = max(sizes) if sizes is not None else count // (S * V)
        self._per_stage = per

        # outer (non-pipelined) params, deduped by identity so tied weights
        # are a single leaf
        outer, seen = [], set()
        for l in pre_layers + post_layers:
            for p in l.parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    outer.append(p)
        self._place_edge_params(outer)
        self._outer_params = outer
        proto = self._blocks[0]
        self._proto_params = proto.parameters()

        opt = self.optimizer

        # stacked block params [S, per, ...] (or [V, S, per, ...]) over the
        # pipe axis; ragged partitions add a validity mask of shape
        # [S, per_max] (V=1) or [V, S, per_max] (interleaved)
        if sizes is not None:
            self._stacked, self._block_mask = stack_stage_params(
                self._blocks, S, self.mesh, self.axis, num_virtual=V,
                stage_sizes=sizes)
        else:
            self._stacked = stack_stage_params(self._blocks, S, self.mesh,
                                               self.axis, num_virtual=V)
            self._block_mask = None

        # accumulators: probe shapes/dtypes with the real (un-stacked) params
        probe = [p for p in outer + self._proto_params if not p.stop_gradient]
        opt._create_accumulators(probe)
        acc_names = sorted(opt._accumulators.keys())
        acc_names = [n for n in acc_names if opt._accumulators[n]]
        self._acc_names = acc_names

        def acc_like(p, leaf_val):
            # master_weight (multi_precision bf16 + f32 master, reference
            # analog: master-weight handling in fluid/operators/optimizers/
            # adamw_op + hybrid_parallel_optimizer.py:186) starts as the f32
            # copy of the (possibly stacked) parameter, not zeros; params
            # without a master entry (already f32) carry None.
            out = []
            for n in acc_names:
                a = opt._accumulators[n].get(p.name)
                if a is None:
                    out.append(None)
                elif n == "master_weight":
                    out.append(leaf_val.astype(jnp.float32))
                else:
                    out.append(jnp.zeros(leaf_val.shape[:len(leaf_val.shape) -
                                                        len(a.shape)]
                                         + a.shape, a.dtype))
            return out

        def spec_of(val):
            shd = getattr(val, "sharding", None)
            return tuple(shd.spec) if isinstance(shd, NamedSharding) else ()

        # accumulators inherit the param placement plus ZeRO-1 sharding of
        # the largest free dim over the "sharding" axis
        def place_accs(alist, base_spec):
            return [a if a is None else
                    jax.device_put(a, _acc_sharding(self.mesh, base_spec,
                                                    a.shape))
                    for a in alist]

        self._outer_accs = [
            place_accs(acc_like(p, p._value), spec_of(p._value))
            for p in outer if not p.stop_gradient]
        self._stacked_accs = [
            place_accs(acc_like(pp, leaf), spec_of(leaf))
            for pp, leaf in zip(self._proto_params, self._stacked)
            if not pp.stop_gradient]

        loss_fn = self.loss_fn
        mesh, axis, M = self.mesh, self.axis, self.num_microbatches

        def swap_apply(layers, params, pvals, x):
            saved = [p._value for p in params]
            try:
                for p, v in zip(params, pvals):
                    p._value = v
                out = x if isinstance(x, Tensor) else Tensor(
                    x, stop_gradient=True)
                with set_grad_enabled(False):
                    for l in layers:
                        out = l(out)
                return out._value
            finally:
                for p, v in zip(params, saved):
                    p._value = v

        def block_apply(pvals, x, k=None):
            # the key is an explicit argument so jax.checkpoint's recompute
            # trace sees the same randomness as the forward trace
            if k is None:
                return swap_apply([proto], self._proto_params, pvals, x)
            with _random.tracing_key_scope(k):
                return swap_apply([proto], self._proto_params, pvals, x)

        if self._remat:
            block_apply = jax.checkpoint(block_apply)

        ragged = self._block_mask is not None

        def stage_fn(stage_leaves, x, k=None):
            if ragged:
                mask, stage_leaves = stage_leaves[-1], stage_leaves[:-1]
            for j in range(per):
                kj = None if k is None else jax.random.fold_in(k, j)
                y = block_apply([leaf[j] for leaf in stage_leaves], x, kj)
                # ragged: padded slots are identity (the padding params are
                # NaN-safe copies, their output discarded and their grads
                # zeroed by the where-transpose)
                x = jnp.where(mask[j], y, x) if ragged else y
            return x

        outer_trainable = [p for p in outer if not p.stop_gradient]
        proto_trainable_ix = [k for k, p in enumerate(self._proto_params)
                              if not p.stop_gradient]

        block_mask = self._block_mask

        def loss_of(outer_vals, stacked_vals, x, y, key):
            with _random.tracing_key_scope(key):
                h = swap_apply(pre_layers, outer, outer_vals, x)
                mb_shape = (M, h.shape[0] // M) + h.shape[1:]
                hm = jnp.reshape(h, mb_shape)
                sv = stacked_vals if block_mask is None \
                    else list(stacked_vals) + [block_mask]
                ym = spmd_pipeline(stage_fn, sv, hm,
                                   mesh=mesh, axis=axis,
                                   key=jax.random.fold_in(key, 0x5049),
                                   num_virtual=V)
                h2 = jnp.reshape(ym, h.shape[:1] + ym.shape[2:])
                out = swap_apply(post_layers, outer, outer_vals, h2)
                loss = loss_fn(Tensor(out, stop_gradient=True),
                               Tensor(y, stop_gradient=True))
                return loss._value

        acc_names_l = acc_names

        def apply_updates(pvals, grads, accs, lr, step_count, names,
                          stacked=False):
            new_p, new_a = [], []
            # bake AdamW decay flags in call order
            if hasattr(opt, "_decay_skip"):
                opt._current_decay_flags = [n not in opt._decay_skip
                                            for n in names]
            elif hasattr(opt, "_decay_flags"):
                opt._current_decay_flags = [opt._decay_flags.get(n, True)
                                            for n in names]
            for pv, gv, ac in zip(pvals, grads, accs):
                acc_dict = dict(zip(acc_names_l, ac))
                if stacked:
                    # per-block update: vmap over the (S, per) — or
                    # (V, S, per) when interleaved — leading dims so
                    # norm-based optimizers (Lamb/Lars) see one block's
                    # parameter at a time, exactly as un-stacked training
                    def upd(pv_, gv_, ad_):
                        return opt._single_update(pv_, gv_, ad_, lr,
                                                  step_count)
                    vm = upd
                    for _ in range(2 if V == 1 else 3):
                        vm = jax.vmap(vm)
                    np_, na_ = vm(pv, gv, acc_dict)
                else:
                    np_, na_ = opt._single_update(pv, gv, acc_dict, lr,
                                                  step_count)
                new_p.append(np_)
                new_a.append([na_.get(n) for n in acc_names_l])
            return new_p, new_a

        outer_names = [p.name for p in outer_trainable]
        block_names = [self._proto_params[k].name for k in proto_trainable_ix]

        def step(outer_vals, stacked_vals, outer_accs, stacked_accs,
                 x, y, lr, step_count, key):
            from ....profiler.step_fusion import STEP_STATS
            STEP_STATS.retraces += 1   # side effect: runs only while tracing

            def closure(train_outer, train_stacked):
                full_outer, ti = [], 0
                for p, v in zip(outer, outer_vals):
                    if p.stop_gradient:
                        full_outer.append(v)
                    else:
                        full_outer.append(train_outer[ti])
                        ti += 1
                full_stacked, ti = [], 0
                for k, v in enumerate(stacked_vals):
                    if k in proto_trainable_ix:
                        full_stacked.append(train_stacked[ti])
                        ti += 1
                    else:
                        full_stacked.append(v)
                return loss_of(full_outer, full_stacked, x, y, key)

            t_outer = [v for p, v in zip(outer, outer_vals)
                       if not p.stop_gradient]
            t_stacked = [stacked_vals[k] for k in proto_trainable_ix]
            loss, (g_outer, g_stacked) = jax.value_and_grad(
                closure, argnums=(0, 1))(t_outer, t_stacked)
            new_outer, new_oaccs = apply_updates(
                t_outer, g_outer, outer_accs, lr, step_count, outer_names)
            new_stacked, new_saccs = apply_updates(
                t_stacked, g_stacked, stacked_accs, lr, step_count,
                block_names, stacked=True)
            # reassemble full lists with frozen params untouched
            out_outer, ti = [], 0
            for p, v in zip(outer, outer_vals):
                if p.stop_gradient:
                    out_outer.append(v)
                else:
                    out_outer.append(new_outer[ti])
                    ti += 1
            out_stacked, ti = [], 0
            for k, v in enumerate(stacked_vals):
                if k in proto_trainable_ix:
                    out_stacked.append(new_stacked[ti])
                    ti += 1
                else:
                    out_stacked.append(v)
            return loss, out_outer, out_stacked, new_oaccs, new_saccs

        # Route the program through the promotion funnel
        # (ops/spmd_fusion.py pipeline registry) instead of an anonymous
        # bare jit: the compiled step gets a canonical mesh-keyed
        # signature, step.promote/step.fire flight-recorder events, and
        # schedule churn over the same mesh + stage structure is
        # attributed as `pipe_schedule_mismatch`.
        from ....ops import spmd_fusion as _spmd_fusion
        stage_struct = tuple(
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in self._stacked)
        stage_struct += (("outer",) + tuple(
            (tuple(p._value.shape), str(p._value.dtype),
             bool(p.stop_gradient)) for p in outer),)
        if self._stage_sizes_eff is not None:
            stage_struct += (("ragged",) + tuple(self._stage_sizes_eff),)
        if self._remat:
            stage_struct += (("remat",),)
        # architecture + per-model token: same-shaped models with
        # different block code (or config buried in layer attributes)
        # must never alias one compiled program
        stage_struct += (("arch",)
                         + tuple(type(l).__qualname__ for l in flat)
                         + (id(flat[0]) if flat else 0,),)
        sig = _spmd_fusion.pipeline_signature(
            mesh, axis, S, V, M, stage_struct, opt)
        label = (f"pipeline[{S}pp×{V}v×{M}mb]+{type(opt).__name__}"
                 f"@mesh[{axis}]")
        # unfused-schedule launch estimate: per micro-batch one forward
        # and one backward launch per block plus the boundary update
        n_launches = M * max(1, len(self._blocks)) * 2 + 1
        self._program = _spmd_fusion.promote_pipeline(
            sig, label, lambda: jax.jit(step, donate_argnums=(2, 3)),
            n_launches=n_launches)
        # donate accumulators only: params are aliased by live eager
        # Parameter wrappers on the first step (same policy as TrainStep)
        self._jitted = self._program.exe
        self._outer_vals = [p._value for p in outer]

    # -- execution --------------------------------------------------------
    def __call__(self, x, y):
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
        if self._jitted is None:
            self._build()
        if xv.shape[0] % self.num_microbatches != 0:
            raise ValueError(
                f"batch {xv.shape[0]} not divisible by num_microbatches "
                f"{self.num_microbatches}")
        opt = self.optimizer
        if not hasattr(opt, "_step_count"):
            opt._step_count = 0
        opt._step_count += 1
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        sc = jnp.asarray(opt._step_count, jnp.int32)
        key = _random.get_rng_key()
        loss, self._outer_vals, self._stacked, self._outer_accs, \
            self._stacked_accs = self._jitted(
                self._outer_vals, self._stacked, self._outer_accs,
                self._stacked_accs, xv, yv, lr, sc, key)
        if self._program is not None:
            from ....ops import spmd_fusion as _spmd_fusion
            _spmd_fusion.fire_pipeline(self._program)
        from ....profiler import goodput as _goodput
        _goodput.on_step(opt)
        from ....framework.flags import _FLAGS
        if _FLAGS.get("FLAGS_check_nan_inf") and \
                not bool(jnp.isfinite(loss)):
            raise FloatingPointError(
                "PipelineTrainStep produced a non-finite loss "
                "(FLAGS_check_nan_inf); the step's updates were already "
                "applied to the stacked stage state")
        return Tensor(loss, stop_gradient=True)

    def _block_coords(self):
        """(block_index, leading-index tuple into a stacked leaf) for every
        REAL block — ragged padding slots are skipped."""
        S, V, per = self.num_stages, self.num_virtual, self._per_stage
        if self._stage_sizes_eff is not None:
            # ragged: one entry per chunk c = l*S + s; V=1 leaves index
            # (s, j), V>1 leaves index (l, s, j)
            off = 0
            for c, sz in enumerate(self._stage_sizes_eff):
                for j in range(sz):
                    yield off + j, (c, j) if V == 1 else (c // S, c % S, j)
                off += sz
        elif V == 1:
            for c in range(S):
                for j in range(per):
                    yield c * per + j, (c, j)
        else:
            for c in range(S * V):
                for j in range(per):
                    yield c * per + j, (c // S, c % S, j)

    def sync_to_model(self):
        """Write the step's state back into the wrapper Parameters AND the
        optimizer's accumulator dict, so eager inspection (state_dict,
        p.numpy(), optimizer.state_dict for checkpointing) sees current
        values."""
        for p, v in zip(self._outer_params, self._outer_vals):
            p._value = v
        for k, leaf in enumerate(self._stacked):
            # ONE host transfer per stacked leaf, then numpy slicing —
            # per-(stage, block) device indexing would issue thousands of
            # small cross-device slices for a large model
            host = np.asarray(jax.device_get(leaf))
            for b, coord in self._block_coords():
                self._blocks[b].parameters()[k]._value = jnp.asarray(
                    host[coord])
        opt = self.optimizer
        names = self._acc_names
        t_outer = [p for p in self._outer_params if not p.stop_gradient]
        for p, accs in zip(t_outer, self._outer_accs):
            for n, a in zip(names, accs):
                if a is None:
                    continue
                # copy: the next jitted step donates self._outer_accs, which
                # would leave the optimizer dict pointing at deleted buffers
                opt._accumulators[n][p.name] = jnp.array(a, copy=True)
        trainable_ix = [k for k, pp in enumerate(self._proto_params)
                        if not pp.stop_gradient]
        for k, accs in zip(trainable_ix, self._stacked_accs):
            for n, a in zip(names, accs):
                if a is None:
                    continue
                # batched like the param loop: one host transfer per leaf
                host = np.asarray(jax.device_get(a))
                for b, coord in self._block_coords():
                    blk_p = self._blocks[b].parameters()[k]
                    opt._accumulators[n][blk_p.name] = jnp.asarray(
                        host[coord])
