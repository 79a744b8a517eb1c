"""Collective communication API + ProcessGroupXLA.

Reference analog: the ProcessGroup abstract API
(fluid/distributed/collective/ProcessGroup.h:52) + ProcessGroupNCCL and the
python surface python/paddle/distributed/collective.py /
communication/{all_reduce,...}.py.

TPU-first (SURVEY.md §5): collectives are XLA ops over the device mesh. A
Group is a set of *devices* (single-controller SPMD world); an eager collective
builds a global array over the group's 1-D mesh and runs a jitted
shard_map(psum/all_gather/...) over ICI. Async semantics (`Task`) exist for API
parity — XLA already overlaps independent collectives; `wait()` blocks on the
result buffer.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "all_gather", "all_gather_object", "reduce", "broadcast", "scatter",
           "alltoall", "alltoall_single", "reduce_scatter", "send", "recv",
           "isend", "irecv", "barrier", "wait", "destroy_process_group",
           "get_backend", "ProcessGroupXLA", "partial_send", "partial_recv",
           "P2POp", "batch_isend_irecv"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Task:
    """Awaitable collective result (ProcessGroup::Task analog)."""

    def __init__(self, buffers):
        self._buffers = buffers

    def wait(self, timeout=None):
        for b in self._buffers:
            b.block_until_ready()
        return True

    def is_completed(self):
        try:
            for b in self._buffers:
                b.is_ready()
            return True
        except Exception:
            return False

    def synchronize(self):
        self.wait()


class ProcessGroupXLA:
    """Executes collectives over a 1-D device mesh with jitted shard_map.

    One instance per Group (reference: one ProcessGroupNCCL per (places, gid)).
    Compiled collectives are cached per (op, shape, dtype).
    """

    def __init__(self, devices, gid=0):
        self.devices = list(devices)
        self.gid = gid
        self.mesh = Mesh(np.array(self.devices), ("g",))
        self._cache = {}

    @property
    def size(self):
        return len(self.devices)

    def _compiled(self, kind, reduce_op=None, **kw):
        key = (kind, reduce_op, tuple(sorted(kw.items())))
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        mesh = self.mesh

        red = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
               ReduceOp.MIN: jax.lax.pmin,
               ReduceOp.AVG: lambda x, a: jax.lax.pmean(x, a),
               ReduceOp.PROD: lambda x, a: jnp.exp(
                   jax.lax.psum(jnp.log(x), a))}.get(reduce_op)

        if kind == "all_reduce":
            def body(x):
                return red(x, "g")
            fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=P("g"),
                                       out_specs=P("g")))
        elif kind == "all_gather":
            def body(x):
                return jax.lax.all_gather(x, "g", tiled=True)
            fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=P("g"),
                                       out_specs=P("g")))
        elif kind == "reduce_scatter":
            # block [1, n, chunk...] -> each device keeps its reduced chunk
            def body(x):
                if reduce_op == ReduceOp.SUM:
                    return jax.lax.psum_scatter(x[0], "g",
                                                scatter_dimension=0)[None]
                y = red(x[0], "g")                       # [n, chunk...]
                return jnp.take(y, jax.lax.axis_index("g"), axis=0)[None]
            fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=P("g"),
                                       out_specs=P("g")))
        elif kind == "broadcast":
            src = kw["src_index"]

            def body(x):
                from_src = jax.lax.all_gather(x, "g")[src]
                return from_src

            fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=P("g"),
                                       out_specs=P("g")))
        elif kind == "alltoall":
            # block [1, n, chunk...]: row j goes to device j
            def body(x):
                return jax.lax.all_to_all(x[0], "g", split_axis=0,
                                          concat_axis=0)[None]
            fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=P("g"),
                                       out_specs=P("g")))
        elif kind == "p2p":
            perm = kw["perm"]

            def body(x):
                return jax.lax.ppermute(x, "g", list(perm))
            fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=P("g"),
                                       out_specs=P("g")))
        else:
            raise ValueError(kind)
        self._cache[key] = fn
        return fn

    # -- helpers -------------------------------------------------------------
    def _replicated(self, value):
        """Stack a host value once per device → device-sharded global array of
        shape [n, ...] (single-controller path)."""
        n = self.size
        stacked = jnp.stack([value] * n) if not isinstance(value, np.ndarray) \
            else jnp.asarray(np.stack([value] * n))
        sharding = NamedSharding(self.mesh, P("g"))
        return jax.device_put(stacked, sharding)

    def _global(self, value):
        """Global [n, ...] array, row i = the value device i's process
        contributed. Multi-controller: every process commits its local value
        to its own addressable devices and the rows assemble into one global
        array (reference analog: each NCCL rank's input buffer)."""
        if jax.process_count() == 1:
            return self._replicated(value)
        v = jnp.asarray(value)
        pi = jax.process_index()
        local = [d for d in self.devices if d.process_index == pi]
        rows = [jax.device_put(v[None], d) for d in local]
        return jax.make_array_from_single_device_arrays(
            (self.size,) + v.shape, NamedSharding(self.mesh, P("g")), rows)

    def _local_shard(self, out):
        """This process's shard of a P('g')-sharded result."""
        return jnp.asarray(out.addressable_shards[0].data)

    def _row0(self, out):
        if jax.process_count() == 1:
            return out[0]
        return self._local_shard(out)[0]

    def all_reduce(self, value, op=ReduceOp.SUM):
        if self.size == 1:
            return value
        out = self._compiled("all_reduce", op)(self._global(value))
        return self._row0(out)

    def broadcast(self, value, src_index):
        if self.size == 1:
            return value
        out = self._compiled("broadcast", None,
                             src_index=src_index)(self._global(value))
        return self._row0(out)

    def gather_all(self, value):
        """[n, ...] — every group member's value, on every member."""
        if self.size == 1:
            return jnp.asarray(value)[None]
        out = self._compiled("all_gather", None)(self._global(value))
        if jax.process_count() == 1:
            return out[:self.size]      # device 0's (complete) gather
        return self._local_shard(out)

    def reduce_scatter(self, value_rows, op=ReduceOp.SUM):
        """value_rows: [n, chunk...] per rank; returns this rank's reduced
        chunk [chunk...]."""
        out = self._compiled("reduce_scatter", op)(self._global(value_rows))
        return self._row0(out)

    def alltoall(self, value_rows):
        """value_rows: [n, chunk...]; row j is for rank j. Returns the
        [n, chunk...] this rank received (row i from rank i)."""
        out = self._compiled("alltoall", None)(self._global(value_rows))
        return self._row0(out)

    def p2p(self, value, src_index, dst_index):
        """One collective-permute step: src's value lands on dst. Both ends
        (and every group member, SPMD) must call with the same pair."""
        out = self._compiled("p2p", None,
                             perm=((src_index, dst_index),))(
                                 self._global(value))
        return self._row0(out)


_groups = {}
_default_group = None
_next_gid = 1


class Group:
    """Reference analog: distributed/collective.py Group."""

    def __init__(self, rank, nranks, id=0, ranks=None, pg=None):
        self.rank = rank
        self.nranks = nranks
        self.id = id
        self.ranks = ranks if ranks is not None else list(range(nranks))
        self.pg = pg

    @property
    def world_size(self):
        return self.nranks

    @property
    def process_group(self):
        return self.pg

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def is_member(self):
        return self.rank >= 0

    def __repr__(self):
        return f"Group(rank={self.rank}, nranks={self.nranks}, id={self.id})"


def _rank_devices():
    """One device per RANK. Multi-process: rank == process, represented by
    its first local device (a process with several chips still contributes
    exactly one row to eager rank-level collectives — data-plane sharding
    uses the full Mesh, not this path). Single-process: rank == device."""
    devices = jax.devices()
    if jax.process_count() == 1:
        return devices
    by_proc = {}
    for d in devices:
        by_proc.setdefault(d.process_index, d)
    return [by_proc[p] for p in sorted(by_proc)]


def _ensure_default_group():
    global _default_group
    if _default_group is None:
        from .env import get_rank, get_world_size
        pg = ProcessGroupXLA(_rank_devices(), gid=0)
        _default_group = Group(get_rank(), get_world_size(), id=0,
                               ranks=list(range(get_world_size())), pg=pg)
        _groups[0] = _default_group
    return _default_group


def new_group(ranks=None, backend=None, timeout=None):
    global _next_gid
    from .env import get_rank, get_world_size
    if ranks is None:
        ranks = list(range(get_world_size()))
    gid = _next_gid
    _next_gid += 1
    my_rank = get_rank()
    group_rank = ranks.index(my_rank) if my_rank in ranks else -1
    devices = _rank_devices()
    # device-backed subgroup when the "ranks" map onto devices 1:1
    sub = [devices[r] for r in ranks if r < len(devices)] or devices[:1]
    pg = ProcessGroupXLA(sub, gid=gid)
    g = Group(group_rank, len(ranks), id=gid, ranks=list(ranks), pg=pg)
    _groups[gid] = g
    return g


def get_group(gid=0):
    return _groups.get(gid)


def get_backend(group=None):
    return "xla"


def destroy_process_group(group=None):
    global _default_group
    if group is None:
        _groups.clear()
        _default_group = None
    else:
        _groups.pop(group.id, None)


def _group_or_default(group):
    return group if group is not None else _ensure_default_group()


def _multi_process(group):
    return group.nranks > 1 and jax.process_count() > 1


# ---------------------------------------------------------------------------
# dispatch-funnel routing: collectives are KEYED eager ops
# ---------------------------------------------------------------------------
# Real-work collectives (all_reduce / all_gather / broadcast / scatter /
# reduce_scatter / alltoall(_single)) go through ops/dispatch.call_op with
# a canonical collective key — (kind, reduce op, mesh key of the group) —
# so they land in the per-op executable cache, the chain detector, and the
# step-cycle recorder like any other op (the fusion stack's collective
# awareness, ops/spmd_fusion.py, starts here; the host-mediated p2p family
# stays control-plane). A Group with no mesh-backed process group cannot
# be keyed: its collective dispatches as an explicit `collective_unkeyed`
# bypass, which poisons the observation cycle with a reason the fusion
# doctor reports directly ("step never promoted: `dist.all_reduce`
# collective_unkeyed ×N").

def _collective_key(kind, op, group, *extra):
    from .mesh import mesh_key
    pg = getattr(group, "pg", None)
    mk = mesh_key(getattr(pg, "mesh", None))
    if mk is None:
        return None
    return (kind, op, mk) + tuple(extra)


def _dispatch_collective(name, fn, tensor, key):
    """Run a collective's value function through the eager dispatch
    funnel (no-grad: collectives are data-plane ops, not tape nodes)."""
    from ..ops.dispatch import call_op, mark_collective
    from ..framework.autograd import no_grad
    from ..profiler import metrics as _metrics
    if _metrics.enabled():
        # telemetry plane: per-kind collective dispatch counter (the
        # per-mesh fused-step timing lives in goodput's spmd histogram)
        _metrics.TRAIN.collectives.labels(kind=name).inc()
    mark_collective(fn, key)
    with no_grad():
        return call_op(name, fn, [tensor])


def _unkeyed_group(group):
    """True for a hand-built Group with nranks>1 but no mesh-backed
    process group — its collectives can be neither keyed nor fused."""
    return group.nranks > 1 and getattr(group, "pg", None) is None


def _dispatch_unkeyed(name, tensor):
    """Attribute an unkeyable collective in the flight recorder (and
    poison any step cycle in observation) by dispatching its identity
    through the funnel with the unkeyable-collective marker."""
    _dispatch_collective(name, lambda v: v, tensor, None)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place all-reduce of `tensor` across the group.

    Single-process groups are the identity (one controller owns all data —
    in the sharded single-controller world the gradient sync is the psum
    the SPMD step promoter fuses in, ops/spmd_fusion.py); multi-process
    dispatches a KEYED collective op through the eager funnel.
    """
    group = _group_or_default(group)
    if _unkeyed_group(group):
        _dispatch_unkeyed("dist.all_reduce", tensor)
        return Task([tensor._value])
    if group.nranks == 1 or not _multi_process(group):
        return Task([tensor._value])
    pg = group.pg
    out = _dispatch_collective(
        "dist.all_reduce", lambda v: pg.all_reduce(v, op), tensor,
        _collective_key("all_reduce", op, group))
    tensor._value = out._value
    return Task([tensor._value])


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    group = _group_or_default(group)
    if _unkeyed_group(group):
        _dispatch_unkeyed("dist.all_gather", tensor)
        tensor_list.clear()
        tensor_list.append(tensor.clone() if hasattr(tensor, "clone")
                           else tensor)
        return Task([tensor._value])
    if group.nranks == 1 or not _multi_process(group):
        tensor_list.clear()
        tensor_list.append(tensor.clone() if hasattr(tensor, "clone")
                           else tensor)
        return Task([tensor._value])
    pg = group.pg
    rows = _dispatch_collective(
        "dist.all_gather", lambda v: pg.gather_all(v), tensor,
        _collective_key("all_gather", None, group))._value
    tensor_list.clear()
    tensor_list.extend(Tensor(rows[i], stop_gradient=True)
                       for i in range(group.nranks))
    return Task([rows])


def all_gather_object(object_list, obj, group=None):
    """Gather arbitrary picklable objects (reference:
    communication/all_gather.py all_gather_object: pickle → uint8 tensor →
    padded all_gather)."""
    group = _group_or_default(group)
    if group.nranks == 1 or not _multi_process(group):
        object_list.clear()
        object_list.extend([obj] * group.nranks)
        return
    import pickle
    payload = np.frombuffer(pickle.dumps(obj), np.uint8)
    length = jnp.asarray([payload.size], jnp.int32)
    lengths = np.asarray(group.pg.gather_all(length))[:, 0]
    cap = int(lengths.max())
    padded = np.zeros((cap,), np.uint8)
    padded[:payload.size] = payload
    rows = np.asarray(group.pg.gather_all(jnp.asarray(padded)))
    object_list.clear()
    object_list.extend(
        pickle.loads(rows[i, :int(lengths[i])].tobytes())
        for i in range(group.nranks))


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def broadcast(tensor, src, group=None, sync_op=True):
    group = _group_or_default(group)
    if _unkeyed_group(group):
        _dispatch_unkeyed("dist.broadcast", tensor)
        return Task([tensor._value])
    if group.nranks == 1 or not _multi_process(group):
        return Task([tensor._value])
    pg = group.pg
    src_index = max(group.get_group_rank(src), 0)
    out = _dispatch_collective(
        "dist.broadcast", lambda v: pg.broadcast(v, src_index), tensor,
        _collective_key("broadcast", None, group, src_index))
    tensor._value = out._value
    return Task([tensor._value])


def _my_index(group):
    from .env import get_rank
    return group.get_group_rank(get_rank())


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    group = _group_or_default(group)
    if _unkeyed_group(group):
        _dispatch_unkeyed("dist.scatter", tensor)
        if tensor_list:
            tensor._assign_value_(tensor_list[0]._value)
        return Task([tensor._value])
    if group.nranks == 1 or not _multi_process(group):
        if tensor_list:
            tensor._assign_value_(tensor_list[0]._value)
        return Task([tensor._value])
    n = group.nranks
    src_index = group.get_group_rank(src)
    if src_index < 0:
        raise ValueError(f"scatter src rank {src} is not a member of "
                         f"group {group.ranks}")
    if tensor_list:
        stacked = jnp.stack([t._value for t in tensor_list])
    else:   # non-src ranks contribute a same-shaped placeholder
        stacked = jnp.zeros((n,) + tuple(tensor._value.shape),
                            tensor._value.dtype)
    pg = group.pg
    rows = _dispatch_collective(
        "dist.scatter", lambda v: pg.broadcast(v, src_index),
        Tensor(stacked, stop_gradient=True),
        _collective_key("scatter", None, group, src_index))._value
    tensor._assign_value_(rows[_my_index(group)])
    return Task([tensor._value])


def alltoall(in_tensor_list, out_tensor_list, group=None, sync_op=True):
    group = _group_or_default(group)
    if _unkeyed_group(group) and in_tensor_list:
        _dispatch_unkeyed("dist.alltoall", in_tensor_list[0])
        out_tensor_list.clear()
        out_tensor_list.extend(in_tensor_list)
        return Task([t._value for t in in_tensor_list])
    if group.nranks == 1 or not _multi_process(group):
        out_tensor_list.clear()
        out_tensor_list.extend(in_tensor_list)
        return Task([t._value for t in in_tensor_list])
    stacked = jnp.stack([t._value for t in in_tensor_list])   # [n, chunk...]
    pg = group.pg
    mine = _dispatch_collective(
        "dist.alltoall", lambda v: pg.alltoall(v),
        Tensor(stacked, stop_gradient=True),
        _collective_key("alltoall", None, group))._value
    out_tensor_list.clear()
    out_tensor_list.extend(Tensor(mine[i], stop_gradient=True)
                           for i in range(group.nranks))
    return Task([mine])


def alltoall_single(in_tensor, out_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    group = _group_or_default(group)
    if _unkeyed_group(group):
        _dispatch_unkeyed("dist.alltoall", in_tensor)
    if group.nranks == 1 or not _multi_process(group):
        out_tensor._assign_value_(in_tensor._value)
        return Task([out_tensor._value])
    if in_split_sizes is not None or out_split_sizes is not None:
        raise NotImplementedError(
            "alltoall_single with unequal splits is not supported; pad to "
            "equal chunks")
    n = group.nranks
    v = in_tensor._value
    if v.shape[0] % n:
        raise ValueError(
            f"alltoall_single dim0 ({v.shape[0]}) must divide the group "
            f"size {n}")
    rows = v.reshape((n, v.shape[0] // n) + tuple(v.shape[1:]))
    pg = group.pg
    mine = _dispatch_collective(
        "dist.alltoall", lambda x: pg.alltoall(x),
        Tensor(rows, stop_gradient=True),
        _collective_key("alltoall", None, group))._value
    out_tensor._assign_value_(mine.reshape(v.shape))
    return Task([out_tensor._value])


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    group = _group_or_default(group)
    if _unkeyed_group(group):
        _dispatch_unkeyed("dist.reduce_scatter", tensor)
    if group.nranks == 1 or not _multi_process(group):
        acc = tensor_list[0]._value
        for t in tensor_list[1:]:
            acc = acc + t._value
        tensor._assign_value_(acc if group.nranks == 1 else acc)
        return Task([tensor._value])
    rows = jnp.stack([t._value for t in tensor_list])         # [n, chunk...]
    pg = group.pg
    mine = _dispatch_collective(
        "dist.reduce_scatter", lambda v: pg.reduce_scatter(v, op),
        Tensor(rows, stop_gradient=True),
        _collective_key("reduce_scatter", op, group))._value
    tensor._assign_value_(mine)
    return Task([tensor._value])


_p2p_seq = {}


def _p2p_key(group, src, dst):
    """Monotonic per-direction key so repeated sends never collide."""
    k = (group.id, src, dst)
    _p2p_seq[k] = _p2p_seq.get(k, 0) + 1
    return f"p2p/{group.id}/{src}->{dst}/{_p2p_seq[k]}"


def send(tensor, dst=0, group=None, sync_op=True):
    """Eager point-to-point send (reference analog: collective/send_v2
    over NCCL). Cross-process: host-mediated through the rendezvous
    TCPStore — pairwise-correct for ANY send/recv pattern, unlike an SPMD
    collective which would require every rank to participate. The
    *performance* p2p path is ppermute inside compiled programs
    (spmd_pipeline / ProcessGroupXLA.p2p); eager send/recv is control-plane
    traffic."""
    group = _group_or_default(group)
    if group.nranks == 1 or not _multi_process(group):
        _p2p_buffers.setdefault(group.id, {})[dst] = tensor._value
        return Task([tensor._value])
    from .env import get_store
    store = get_store()
    if store is None:
        # bootstrapped without our store (external jax.distributed init):
        # SPMD collective-permute — both ends must call in matching order
        out = group.pg.p2p(tensor._value, _my_index(group),
                           group.get_group_rank(dst))
        return Task([out])
    import pickle
    arr = np.asarray(tensor._value)
    store.set(_p2p_key(group, _my_index(group), group.get_group_rank(dst)),
              pickle.dumps(arr, protocol=4))
    return Task([tensor._value])


def recv(tensor, src=0, group=None, sync_op=True):
    group = _group_or_default(group)
    if group.nranks == 1 or not _multi_process(group):
        buf = _p2p_buffers.get(group.id, {})
        from .env import get_rank
        if get_rank() in buf:
            tensor._assign_value_(buf.pop(get_rank()))
        return Task([tensor._value])
    from .env import get_store
    store = get_store()
    if store is None:
        row = group.pg.p2p(tensor._value, group.get_group_rank(src),
                           _my_index(group))
        tensor._assign_value_(row)
        return Task([tensor._value])
    import pickle
    key = _p2p_key(group, group.get_group_rank(src), _my_index(group))
    arr = pickle.loads(store.get(key))
    store.delete_key(key)
    tensor._assign_value_(jnp.asarray(arr))
    return Task([tensor._value])


_p2p_buffers = {}

isend = send
irecv = recv


def _partial_bounds(tensor, nranks, rank_id):
    numel = int(np.prod(tensor.shape)) if tensor.shape else 1
    if numel % nranks:
        raise ValueError(
            f"partial send/recv needs numel ({numel}) divisible by "
            f"nranks ({nranks})")
    per = numel // nranks
    return per * rank_id, per * (rank_id + 1)


_partial_p2p_warned = False


def _warn_partial_p2p_path():
    """Once-per-process: the eager partial_send/recv ride the host-mediated
    pickle-over-TCPStore control plane. Fine for metadata/handshakes; for
    actual pipeline ACTIVATION traffic the data plane is the compiled
    ppermute path (spmd_pipeline / ProcessGroupXLA.p2p), which stays on
    ICI at full bandwidth."""
    global _partial_p2p_warned
    if _partial_p2p_warned:
        return
    _partial_p2p_warned = True
    import warnings
    warnings.warn(
        "partial_send/partial_recv use the host-mediated (pickle over "
        "TCPStore) control-plane transport — fine for small slices and "
        "handshakes, but pipeline activation traffic should ride the "
        "compiled ppermute data plane (PipelineTrainStep / "
        "ProcessGroupXLA.p2p) for ICI bandwidth",
        category=RuntimeWarning, stacklevel=3)


def partial_send(tensor, dst=0, group=None, nranks=1, rank_id=0):
    """Send one 1/nranks flat slice of `tensor` (reference:
    collective/partial_send_op.cc — the pipeline's tensor-slice p2p that
    lets mp-sharded ranks exchange only the slice they own).

    Transport note: this eager API is host-mediated (control plane); the
    intended data plane for per-step activation slices is the compiled
    ppermute inside the one-program pipeline (spmd_pipeline.py). A
    once-per-process RuntimeWarning marks the distinction."""
    _warn_partial_p2p_path()
    lo, hi = _partial_bounds(tensor, nranks, rank_id)
    flat = jnp.reshape(tensor._value, (-1,))[lo:hi]
    return send(Tensor(flat, stop_gradient=True), dst=dst, group=group)


def partial_recv(tensor, src=0, group=None, nranks=1, rank_id=0):
    """Receive into one 1/nranks flat slice of `tensor` (reference:
    collective/partial_recv_op.cc). Same transport note as partial_send."""
    _warn_partial_p2p_path()
    lo, hi = _partial_bounds(tensor, nranks, rank_id)
    buf = Tensor(jnp.zeros((hi - lo,), tensor._value.dtype),
                 stop_gradient=True)
    task = recv(buf, src=src, group=group)
    flat = jnp.reshape(tensor._value, (-1,))
    flat = flat.at[lo:hi].set(buf._value)
    tensor._assign_value_(jnp.reshape(flat, tensor._value.shape))
    return task


class P2POp:
    """One operation of a batched p2p round (reference:
    communication/batch_isend_irecv.py P2POp)."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv, send, recv):
            raise ValueError("P2POp op must be paddle.distributed.isend or "
                             "irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Run a batch of isend/irecv ops; returns their tasks (reference:
    communication/batch_isend_irecv.py — the NCCL group-call batching;
    here each op is host-mediated/pairwise so issuing in order is the
    batching)."""
    if not p2p_op_list:
        return []
    # sends issue FIRST regardless of list order — recv blocks until the
    # peer's send lands, so a [irecv, isend] batch on both ends (the
    # canonical ring exchange) must not deadlock
    tasks = [None] * len(p2p_op_list)
    for i, op in enumerate(p2p_op_list):
        if op.op in (isend, send):
            tasks[i] = send(op.tensor, dst=op.peer, group=op.group)
    for i, op in enumerate(p2p_op_list):
        if tasks[i] is None:
            tasks[i] = recv(op.tensor, src=op.peer, group=op.group)
    return tasks


def barrier(group=None):
    group = _group_or_default(group)
    if _multi_process(group):
        # a tiny psum doubles as a barrier
        t = Tensor(jnp.zeros((), jnp.float32))
        all_reduce(t, group=group)
        t._value.block_until_ready()
    return None


def wait(tensor, group=None, use_calc_stream=True):
    tensor._value.block_until_ready()
