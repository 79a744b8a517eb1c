"""Eager op-chain fusion: one compiled executable per hot op sequence.

The layer above the per-op executable cache (ops/dispatch.py). The per-op
cache (PR 1) removed re-tracing but still pays one XLA launch + one python
dispatch per op; a repeated `matmul→add→gelu`-style sequence pays that N
times per iteration. This module watches the dispatch stream, detects
repeated sequences, and compiles ONE fused executable for the whole chain —
a forward-only variant and a forward+vjp variant whose pullback crosses the
jit boundary as a `tree_util.Partial` and is recorded in the autograd tape
as a single `FusedChainNode` owning every constituent op's outputs.

Keying. A chain key is the tuple of the constituent PR 1 per-op cache keys
plus the dataflow wiring between the ops (`("prev", i, j)` — input comes
from output j of chain op i — vs `("ext",)` — input comes from outside the
chain). Because the per-op keys already carry op name, fn value-token,
input avals, diff mask, AMP state, and the registry generation token, every
invalidation rule of the per-op cache applies to chains for free: a bumped
registry generation or changed AMP state re-keys the ops, the stale chain
stops matching, and it ages out of the chain LRU
(`FLAGS_eager_chain_cache_size`).

Replay is speculative and transactional. Once a sequence crosses the
hotness threshold (`FLAGS_eager_chain_fusion_min_count`), the next time its
first op key arrives the dispatcher stops launching: each matching op is
deferred, its outputs handed back as `_DeferredTensor` placeholders that
know their (shape, dtype) but hold no buffer. When the last op of the chain
arrives, the fused executable fires and every placeholder is filled in one
launch. Any divergence — a key or wiring mismatch, an intermediate escaping
the chain (its value read, its grad node touched, an unrelated consumer), a
mutated `stop_gradient`, an execution fault — SPLITS the chain: the ops
deferred so far replay through the per-op cached path, so numerics are
bitwise-identical to unfused dispatch in every outcome. Chains that keep
failing to replay are deactivated.

Telemetry: profiler/chain_fusion.py (chains detected, fused replays,
fallback splits, escapes, launches saved, estimated wall time saved),
surfaced by `paddle_tpu.profiler.chain_fusion_stats()`.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque

import jax

from ..framework.core import Tensor
from ..framework import core as _core
from ..framework.autograd import FusedChainNode, GradNode, \
    pack_saved_values as _pack_saved
from ..framework.flags import _FLAGS
from ..profiler.chain_fusion import CHAIN_STATS
from ..profiler.events import EVENTS as _EVENTS

__all__ = ["MANAGER", "MISS", "clear_chain_cache", "chain_cache_info"]


def _key_diff_reason(expected, got):
    """Reason code for a replay key mismatch, by diffing the per-op cache
    key components — (name, fn token, avals, diff mask, AMP, registry
    token). Shared with step fusion (ops/step_fusion.py)."""
    try:
        if expected[0] != got[0]:
            return "key_mismatch"        # a different op arrived
        if expected[2] != got[2]:
            return "shape_mismatch"      # same op, different input avals
        if expected[5] != got[5]:
            return "registry_bump"       # kernel override (de)activated
    except (IndexError, TypeError):
        pass
    return "key_mismatch"                # fn token / diff mask / AMP state

MISS = object()          # step() result: "not handled, take the per-op path"
_PENDING = object()      # placeholder _value before its chain fires

_aot_mod = None


def _aot():
    """ops/aot_cache.py, resolved lazily (it back-imports the chain
    builders for its healing fallbacks)."""
    global _aot_mod
    if _aot_mod is None:
        from . import aot_cache
        _aot_mod = aot_cache
    return _aot_mod

# window / max-chain length: long enough to capture fwd sub-expressions of a
# layer, short enough that detection stays O(1)-ish per dispatch
_WINDOW = 8
# detection-table and key-intern caps (cleared wholesale when exceeded:
# hot signatures re-accumulate within a few iterations)
_MAX_COUNTS = 2048
_MAX_INTERN = 4096
# consecutive failed replays before a chain is deactivated
_MAX_FAIL_STREAK = 8
# stitched-chain length cap: two adjacent hot chains are stitched into one
# longer chain (and stitched chains stitch again), so whole transformer
# blocks fuse without growing the _WINDOW detection cost; past this many ops
# the XLA compile time stops amortizing
_STITCH_MAX_OPS = 96

# slot descriptors of the base Tensor: lets _DeferredTensor shadow `_value`
# / `_grad_node` / `_out_index` with escape-detecting properties while still
# storing the materialized state in the ordinary slots
_VALUE_SLOT = Tensor.__dict__["_value"]
_NODE_SLOT = Tensor.__dict__["_grad_node"]
_IDX_SLOT = Tensor.__dict__["_out_index"]


class _DeferredTensor(Tensor):
    """Placeholder for an output of a deferred (not yet launched) chain op.

    Shape/dtype queries answer from the recorded aval without forcing; any
    access that needs the buffer or the grad node forces the owning pending
    chain to resolve (fire if complete, split otherwise) and then behaves
    like a plain Tensor. After materialization the deferred state is
    dropped and the shadowing properties read straight from the slots.
    """

    __slots__ = ("_pending_chain", "_deferred_aval", "_chain_coord")

    def __init__(self, aval, stop_gradient, pending, coord):
        _VALUE_SLOT.__set__(self, _PENDING)
        _NODE_SLOT.__set__(self, None)
        _IDX_SLOT.__set__(self, 0)
        self.stop_gradient = stop_gradient
        self.grad = None
        self.name = _core._auto_name("deferred")
        self.persistable = False
        self._hooks = []
        self._pending_chain = pending
        self._deferred_aval = aval          # (shape, dtype, weak_type)
        self._chain_coord = coord           # (op position, local out index)

    # -- escape detection ---------------------------------------------------
    def _force(self):
        # the pending's OWNER resolves it: the chain manager for chain
        # replays, the step-fusion manager (ops/step_fusion.py) for
        # whole-step replays — placeholders are shared between the layers
        pending = self._pending_chain
        if pending is not None:
            pending.owner.resolve_pending(pending, escape=True)

    @property
    def _value(self):
        v = _VALUE_SLOT.__get__(self)
        if v is _PENDING:
            self._force()
            v = _VALUE_SLOT.__get__(self)
        return v

    @_value.setter
    def _value(self, v):
        # a user value-swap on a still-pending placeholder sticks: the
        # wiring check sees a non-pending tensor (→ split) and
        # materialization never overwrites a user-assigned slot
        _VALUE_SLOT.__set__(self, v)

    @property
    def _grad_node(self):
        if _VALUE_SLOT.__get__(self) is _PENDING:
            self._force()
        return _NODE_SLOT.__get__(self)

    @_grad_node.setter
    def _grad_node(self, node):
        _NODE_SLOT.__set__(self, node)

    @property
    def _out_index(self):
        if _VALUE_SLOT.__get__(self) is _PENDING:
            self._force()
        return _IDX_SLOT.__get__(self)

    @_out_index.setter
    def _out_index(self, idx):
        _IDX_SLOT.__set__(self, idx)

    # -- aval-answerable meta (no forcing) ----------------------------------
    @property
    def _fusion_aval(self):
        """(shape, dtype, weak_type) while pending, else None — read by the
        dispatcher to build cache keys without materializing."""
        if _VALUE_SLOT.__get__(self) is _PENDING \
                and self._pending_chain is not None:
            return self._deferred_aval
        return None

    @property
    def shape(self):
        v = _VALUE_SLOT.__get__(self)
        if v is _PENDING:
            return list(self._deferred_aval[0])
        return list(v.shape)

    @property
    def dtype(self):
        from ..framework import dtype as dtype_mod
        v = _VALUE_SLOT.__get__(self)
        if v is _PENDING:
            return dtype_mod.to_paddle_dtype(self._deferred_aval[1])
        return dtype_mod.to_paddle_dtype(v.dtype)

    @property
    def ndim(self):
        v = _VALUE_SLOT.__get__(self)
        if v is _PENDING:
            return len(self._deferred_aval[0])
        return v.ndim


def _is_pending(t):
    return isinstance(t, _DeferredTensor) \
        and _VALUE_SLOT.__get__(t) is _PENDING and t._pending_chain is not None


class _ChainOp:
    """Template for one op of a registered chain."""

    __slots__ = ("name", "key", "fn", "wiring", "arg_srcs", "diff_mask",
                 "num_outputs", "out_avals", "out_stop_grads")

    def __init__(self, name, key, fn, wiring, diff_mask, num_outputs,
                 out_avals, out_stop_grads):
        self.name = name
        self.key = key                   # the PR 1 per-op cache key
        self.fn = fn
        self.wiring = wiring             # per input: ("ext",) | ("prev",i,j)
        self.diff_mask = diff_mask       # None → op ran without grad
        self.num_outputs = num_outputs   # None → single-output op
        self.out_avals = out_avals       # ((shape, dtype, weak_type), ...)
        self.out_stop_grads = out_stop_grads
        self.arg_srcs = None             # filled by Chain: ("e",slot)|("p",i,j)


class Chain:
    """A registered (hot) op sequence with its fused executables."""

    __slots__ = ("sig", "ops", "label", "n_ext", "ext_of", "diff_ext_idx",
                 "grad_mode", "flat_avals", "flat_node_avals", "owners",
                 "baseline_ns", "pure_fn", "_fwd", "_fwd_vjp", "dead",
                 "fail_streak", "head_kid", "replays", "check",
                 "aot_digest", "aot_stored")

    def __init__(self, sig, ops, baseline_ns):
        self.sig = sig
        self.ops = ops
        self.label = "→".join(op.name for op in ops)
        self.baseline_ns = baseline_ns
        self.dead = False
        self.fail_streak = 0
        self.replays = 0
        # guardian (FLAGS_check_numerics): the per-op keys carry the check
        # flag as their last component, so a chain's check-ness is fixed by
        # its signature — the fused executable emits ONE all-finite scalar
        # for the whole chain and a flag flip simply re-keys the stream
        self.check = bool(ops and ops[0].key[-1])
        # external-slot enumeration: one slot per ("ext",) wiring entry, in
        # (op, input) order; ext_of[i][k] = slot (or None for prev wiring)
        self.ext_of = []
        diff_ext = []
        n = 0
        for op in ops:
            slots = []
            srcs = []
            for k, w in enumerate(op.wiring):
                if w[0] == "ext":
                    slots.append(n)
                    srcs.append(("e", n))
                    if op.diff_mask is not None and op.diff_mask[k]:
                        diff_ext.append(n)
                    n += 1
                else:
                    slots.append(None)
                    srcs.append(("p", w[1], w[2]))
            op.arg_srcs = tuple(srcs)
            self.ext_of.append(tuple(slots))
        self.n_ext = n
        self.diff_ext_idx = tuple(diff_ext)
        self.grad_mode = any(op.diff_mask is not None for op in ops)
        # flattened output catalog: (op position, local index) per flat slot
        owners = []
        flat = []
        for i, op in enumerate(ops):
            for j, av in enumerate(op.out_avals):
                owners.append((i, j))
                flat.append(av)
        self.owners = tuple(owners)
        self.flat_avals = tuple(flat)
        self.flat_node_avals = tuple((av[0], av[1]) for av in flat)
        self.pure_fn = _chain_pure_fn(self)
        self._fwd = None
        self._fwd_vjp = None
        self.aot_digest = 0          # lazily computed (ops/aot_cache.py)
        self.aot_stored = False

    def fwd(self):
        if self._fwd is None:
            if _aot().enabled():
                self._fwd = _aot().load_chain(self, grad=False)
            if self._fwd is None:
                self._fwd = _build_chain_fwd(self)
        return self._fwd

    def fwd_vjp(self):
        if self._fwd_vjp is None:
            if _aot().enabled():
                self._fwd_vjp = _aot().load_chain(self, grad=True)
            if self._fwd_vjp is None:
                self._fwd_vjp = _build_chain_fwd_vjp(self)
        return self._fwd_vjp


def _chain_pure_fn(chain):
    """Pure function (*ext_vals) -> tuple of every op output in chain order.
    `lax.stop_gradient` walls off ops recorded without grad, mirroring the
    tape's missing-edge semantics inside the fused vjp."""
    ops = chain.ops
    grad_mode = chain.grad_mode

    def run(*ext_vals):
        env = {}
        flat = []
        for i, op in enumerate(ops):
            args = [ext_vals[s[1]] if s[0] == "e" else env[(s[1], s[2])]
                    for s in op.arg_srcs]
            res = op.fn(*args)
            outs = res if op.num_outputs is not None else (res,)
            if grad_mode and op.diff_mask is None:
                outs = tuple(jax.lax.stop_gradient(o) for o in outs)
            for j, o in enumerate(outs):
                env[(i, j)] = o
            flat.extend(outs)
        return tuple(flat)
    return run


def _build_chain_fwd(chain):
    run = chain.pure_fn
    check = chain.check

    def traced(*ext_vals):
        CHAIN_STATS.retraces += 1     # side effect: runs only while tracing
        _EVENTS.emit("chain.compile", chain.label,
                     detail={"ops": len(chain.ops)})
        out = run(*ext_vals)
        if check:
            from . import guardian
            return out, guardian.finite_all(out)
        return out
    return jax.jit(traced)


def _build_chain_fwd_vjp(chain):
    """Jitted (all_outputs, vjp) over the chain's differentiable external
    slots; the pullback comes back as a `tree_util.Partial` (residuals as
    leaves) and runs through the chain-specific jitted applier, exactly the
    PR 1 per-op contract scaled to N ops."""
    run = chain.pure_fn
    diff = chain.diff_ext_idx
    check = chain.check

    def traced(*ext_vals):
        CHAIN_STATS.retraces += 1
        _EVENTS.emit("chain.compile", chain.label,
                     detail={"ops": len(chain.ops), "grad": True})
        if len(diff) == len(ext_vals):
            res = jax.vjp(run, *ext_vals)
        else:
            def pf(*dv):
                full = list(ext_vals)
                for i, v in zip(diff, dv):
                    full[i] = v
                return run(*full)
            res = jax.vjp(pf, *(ext_vals[i] for i in diff))
        if check:
            from . import guardian
            return res, guardian.finite_all(res[0])
        return res
    return jax.jit(traced)


def _apply_chain_vjp(vjp_fn, g):
    CHAIN_STATS.retraces += 1
    return vjp_fn(g)


# chain backward runs through its own shared jitted appliers so its traces
# count against chain telemetry, not the per-op dispatch counters
_chain_vjp_applier = jax.jit(_apply_chain_vjp)
_chain_vjp_applier_donate = jax.jit(_apply_chain_vjp, donate_argnums=(0,))


def _make_chain_vjp(vjp_partial, diff_idx, n_ext):
    """Engine-facing pullback for a fused node (cf. dispatch._make_cached_vjp
    — duplicated here only to route through the chain appliers). An
    AOT-restored chain hands back an AotPullback whose stored
    rematerializing backward replaces the applier (ops/aot_cache.py);
    chain cotangents are always tuples, so multi=True."""
    if isinstance(vjp_partial, _aot().AotPullback):
        return vjp_partial.make_wrapped(diff_idx, n_ext, multi=True)

    def wrapped(g, donate=False):
        if not isinstance(g, tuple):
            g = (g,)
        if donate and _FLAGS.get("FLAGS_eager_op_cache_donate"):
            partial = _chain_vjp_applier_donate(vjp_partial, g)
        else:
            partial = _chain_vjp_applier(vjp_partial, g)
        full = [None] * n_ext
        for i, pg in zip(diff_idx, partial):
            full[i] = pg
        return tuple(full)
    wrapped._supports_donate = True
    return wrapped


def replay_ops_per_op(ops, ext_vals, ext_edges, placeholders, upto,
                      skip_materialized=False):
    """Materialize the first `upto` deferred ops through the per-op cached
    dispatch path, filling their placeholders with values and real
    GradNodes — the transactional-fallback core shared by chain splits and
    step-fusion splits/recomputes (ops/step_fusion.py). Results are
    bitwise-identical to what unfused dispatch would have produced.

    `skip_materialized` leaves placeholders that already hold a value AND a
    grad node untouched (post-fire lazy recompute must not overwrite the
    fused root's value or node)."""
    from .dispatch import _cached_call, _slow_vjp, _make_cached_vjp
    for i in range(upto):
        op = ops[i]
        in_vals = []
        in_edges = []
        for k, src in enumerate(op.arg_srcs):
            if src[0] == "e":
                in_vals.append(ext_vals[src[1]])
                in_edges.append(ext_edges[src[1]])
            else:
                prev = placeholders[src[1]][src[2]]
                in_vals.append(_VALUE_SLOT.__get__(prev))
                if op.diff_mask is not None and op.diff_mask[k]:
                    in_edges.append((_NODE_SLOT.__get__(prev),
                                     _IDX_SLOT.__get__(prev)))
                else:
                    in_edges.append(None)
        in_vals = tuple(in_vals)
        multi = op.num_outputs is not None
        if op.diff_mask is None:
            ok, out_vals = _cached_call(op.key, op.name, op.fn,
                                        None, in_vals)
            if not ok:
                out_vals = op.fn(*in_vals)
            outs_flat = out_vals if multi else (out_vals,)
            node = None
        else:
            diff_idx = tuple(k for k, d in enumerate(op.diff_mask) if d)
            ok, res = _cached_call(op.key, op.name, op.fn, diff_idx,
                                   in_vals)
            if ok:
                out_vals, vjp_partial = res
                wrapped = _make_cached_vjp(vjp_partial, diff_idx,
                                           len(in_vals), multi)
            else:
                out_vals, wrapped = _slow_vjp(op.fn, in_vals, diff_idx,
                                              len(in_vals), multi)
            outs_flat = out_vals if multi else (out_vals,)
            node = GradNode(op.name, wrapped, in_edges,
                            tuple((v.shape, v.dtype) for v in outs_flat))
            node.fwd_fn = op.fn
            node.in_vals, node.unpack_hook = _pack_saved(in_vals, in_edges)
        for j, t in enumerate(placeholders[i]):
            if skip_materialized \
                    and _VALUE_SLOT.__get__(t) is not _PENDING \
                    and _NODE_SLOT.__get__(t) is not None:
                t._pending_chain = None
                continue
            if _VALUE_SLOT.__get__(t) is _PENDING:
                _VALUE_SLOT.__set__(t, outs_flat[j])
            if node is not None:
                _NODE_SLOT.__set__(t, node)
                _IDX_SLOT.__set__(t, j)
            t._pending_chain = None


class _PendingChain:
    """Replay in flight: ops deferred so far and their placeholders.

    `lock` serializes the owner thread's mutation (_defer/_fire/_split)
    against a cross-thread escape: a placeholder handed to another thread
    and forced there resolves under the lock, so it either waits out an
    in-flight fire or splits a consistent prefix — never a half-appended
    one."""

    __slots__ = ("chain", "pos", "ext_vals", "ext_edges", "placeholders",
                 "t0", "done", "lock", "owner", "prev_fire", "gap",
                 "gap_outs", "boundary")

    def __init__(self, chain):
        self.chain = chain
        self.pos = 0
        self.ext_vals = []
        self.ext_edges = []
        self.placeholders = []     # per op: tuple of _DeferredTensor
        self.t0 = time.perf_counter_ns()
        self.done = False
        self.lock = threading.RLock()   # reentrant: _fire's fault path splits
        self.owner = MANAGER
        # stitching state: the preceding fired chain replay plus the per-op
        # records dispatched between it and this replay (set when nothing
        # else intervened), and per ext slot the ("A", i, j) / ("G", g, j)
        # coordinate in that fired chain / gap the input came from
        self.prev_fire = None
        self.gap = ()
        self.gap_outs = {}
        self.boundary = []


class _Recorded:
    """One dispatch observed by the rolling window (record mode)."""

    __slots__ = ("key_id", "name", "key", "fn", "wiring_abs", "diff_mask",
                 "num_outputs", "out_avals", "out_stop_grads", "outs",
                 "ins", "abs_pos", "dur_ns")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _TLS(threading.local):
    def __init__(self):
        self.window = deque()
        self.produced = {}     # id(tensor) -> (abs_pos, out_idx)
        self.pending = None
        self.counter = 0       # abs position of the next recorded dispatch
        self.busy = False
        self.serial = 0        # every keyable dispatch this thread has seen
        self.last_fire = None  # (pending, serial of its last deferred op)
        self.stitch_gap = []   # per-op records dispatched since last_fire


class _FusionManager:
    """Detection + registry + replay. Registry state is process-global
    (guarded by a lock, like the per-op LRU); window and pending state are
    per-thread."""

    def __init__(self):
        self._tls = _TLS()
        self._lock = threading.Lock()
        self._counts = {}                  # sig -> occurrence count
        self._chains = OrderedDict()       # sig -> Chain (LRU)
        self._heads = {}                   # first key_id -> [Chain, ...]
        self._intern = {}                  # per-op key -> small int id

    # -- config ------------------------------------------------------------
    @staticmethod
    def enabled():
        return bool(_FLAGS.get("FLAGS_eager_chain_fusion")) \
            and int(_FLAGS.get("FLAGS_eager_chain_cache_size", 128) or 0) > 0

    @staticmethod
    def stitching_enabled():
        return bool(_FLAGS.get("FLAGS_eager_chain_stitching", True))

    # -- key interning -----------------------------------------------------
    def _intern_key(self, key):
        with self._lock:
            kid = self._intern.get(key)
            if kid is None:
                if len(self._intern) >= _MAX_INTERN:
                    self._intern.clear()
                    self._counts.clear()
                kid = self._intern[key] = len(self._intern)
            return kid

    # -- dispatch hooks ----------------------------------------------------
    def step(self, name, fn, inputs, num_outputs, key, diff_mask,
             bypass_reason=None):
        """Called by the dispatcher before it launches anything. Returns the
        op's result (deferred placeholders, materialized on chain
        completion) or MISS → the caller takes the per-op path and reports
        the outcome through record()/reset(). `bypass_reason` attributes a
        key=None split to the dispatch-level cause (rng_rekey, ...)."""
        st = self._tls
        if st.busy:
            return MISS
        if st.pending is not None and st.pending.done:
            st.pending = None       # resolved by another thread's escape
        if not self.enabled():
            self.flush(reason="flag_off")
            if st.window:
                self._reset_window(st)
            return MISS
        if key is None:
            # un-keyable op: chains cannot cross it
            self.flush(reason=bypass_reason or "unkeyable_closure",
                       blocked_op=name)
            self._reset_window(st)
            st.last_fire = None
            st.stitch_gap = []
            return MISS
        kid = self._intern_key(key)
        st.serial += 1

        # resolve placeholders owned by OTHER threads' pending chains (or by
        # a fired step-fusion replay) before taking our own pending lock:
        # _defer reads ext inputs' values, and forcing a foreign placeholder
        # while holding our lock while that thread forces one of ours would
        # be an ABBA deadlock. Pre-forcing is the same escape split, just
        # ordered lock-free. The stitching boundary chain (last_fire) is
        # exempt: its placeholders are already materialized.
        for t in inputs:
            if _is_pending(t) and t._pending_chain is not st.pending:
                t._pending_chain.owner.resolve_pending(t._pending_chain,
                                                       escape=True)

        if st.pending is not None:
            pending = st.pending
            chain = pending.chain
            with pending.lock:
                if pending.done:   # another thread's escape resolved it
                    st.pending = None
                else:
                    op = chain.ops[pending.pos]
                    if kid == self._intern.get(op.key) \
                            and self._replay_wiring_matches(pending, op,
                                                            inputs):
                        return self._defer(st, pending, op, inputs,
                                           num_outputs)
                    if kid != self._intern.get(op.key):
                        reason = _key_diff_reason(op.key, key)
                    else:
                        reason = "wiring_mismatch"
                    self._split(pending, escape=False, reason=reason,
                                blocked_op=name)
            # fall through: this op may start a new chain or be recorded

        chain = self._lookup_start(kid, key)
        if chain is not None:
            pending = st.pending = _PendingChain(chain)
            if st.last_fire is not None and self.stitching_enabled() \
                    and st.last_fire[1] + len(st.stitch_gap) + 1 \
                    == st.serial:
                # this replay follows a fire with only recorded per-op
                # dispatches (the gap) in between: candidate for stitching
                # fire + gap + this chain into one longer chain
                pending.prev_fire = st.last_fire[0]
                pending.gap = tuple(st.stitch_gap)
                pending.gap_outs = {
                    id(t): (g, j)
                    for g, rec in enumerate(pending.gap)
                    for j, t in enumerate(rec.outs)}
            return self._defer(st, pending, chain.ops[0], inputs,
                               num_outputs)
        return MISS

    def record(self, name, fn, inputs, num_outputs, key, diff_mask,
               outs, dur_ns):
        """Feed the detector after a successful per-op cached dispatch."""
        st = self._tls
        if st.busy or not self.enabled() or key is None:
            return
        abs_pos = st.counter
        st.counter += 1
        wiring_abs = tuple(
            ("prev",) + st.produced[id(t)] if id(t) in st.produced
            else ("ext",)
            for t in inputs)
        out_avals = tuple(
            (v._value.shape, v._value.dtype,
             getattr(v._value, "weak_type", False)) for v in outs)
        rec = _Recorded(
            key_id=self._intern_key(key), name=name, key=key, fn=fn,
            wiring_abs=wiring_abs, diff_mask=diff_mask,
            num_outputs=num_outputs, out_avals=out_avals,
            out_stop_grads=tuple(t.stop_gradient for t in outs),
            outs=tuple(outs), ins=tuple(inputs), abs_pos=abs_pos,
            dur_ns=dur_ns)
        if st.last_fire is not None:
            # per-op dispatches between two chain replays are stitch
            # material: they join the two chains as internal ops of the
            # stitched result. A gap longer than the window stops being a
            # plausible single hot sequence — drop the anchor.
            st.stitch_gap.append(rec)
            if len(st.stitch_gap) > _WINDOW:
                st.last_fire = None
                st.stitch_gap = []
        st.window.append(rec)
        for j, t in enumerate(outs):
            st.produced[id(t)] = (abs_pos, j)
        while len(st.window) > _WINDOW:
            old = st.window.popleft()
            for j, t in enumerate(old.outs):
                if st.produced.get(id(t)) == (old.abs_pos, j):
                    del st.produced[id(t)]
        self._detect(st)

    def reset(self):
        """An un-keyable / un-jittable op broke the stream: drop the window
        (chains cannot span it) and the stitch anchor (the broken stream
        does not bump the serial, so adjacency could otherwise lie)."""
        st = self._tls
        self._reset_window(st)
        st.last_fire = None
        st.stitch_gap = []

    def flush(self, reason=None, blocked_op=None):
        """Resolve any pending chain on this thread (split if incomplete)."""
        st = self._tls
        if st.pending is not None:
            pending = st.pending
            with pending.lock:
                if not pending.done:
                    self._split(pending, escape=False, reason=reason,
                                blocked_op=blocked_op)
            st.pending = None

    def _reset_window(self, st):
        st.window.clear()
        st.produced.clear()

    # -- detection ---------------------------------------------------------
    def _detect(self, st):
        win = list(st.window)
        n = len(win)
        if n < 2:
            return
        min_count = int(
            _FLAGS.get("FLAGS_eager_chain_fusion_min_count", 25) or 1)
        to_register = []
        with self._lock:          # one acquisition for all suffix lengths
            for L in range(2, n + 1):
                start = n - L
                start_abs = win[start].abs_pos
                sig = tuple(
                    (rec.key_id, tuple(
                        ("prev", w[1] - start_abs, w[2])
                        if w[0] == "prev" and w[1] >= start_abs else ("ext",)
                        for w in rec.wiring_abs))
                    for rec in win[start:])
                if sig in self._chains:
                    continue
                if len(self._counts) >= _MAX_COUNTS:
                    self._counts.clear()
                c = self._counts.get(sig, 0) + 1
                self._counts[sig] = c
                if c < min_count:
                    continue
                del self._counts[sig]
                to_register.append((sig, win[start:]))
        for sig, recs in to_register:
            self._register(sig, recs)

    # chain labels can repeat across distinct signatures; events carry the
    # label (human attribution) while the sig stays internal

    def _register(self, sig, recs):
        ops = [
            # the per-record rel wiring is sig's second element — no need
            # to re-derive it from wiring_abs
            _ChainOp(rec.name, rec.key, rec.fn, wiring, rec.diff_mask,
                     rec.num_outputs, rec.out_avals, rec.out_stop_grads)
            for rec, (_kid, wiring) in zip(recs, sig)]
        chain = Chain(sig, ops, sum(r.dur_ns for r in recs))
        if self._insert_chain(sig, chain):
            CHAIN_STATS.detected(chain.label)
            _EVENTS.emit("chain.detect", chain.label,
                         detail={"ops": len(chain.ops)})

    def _insert_chain(self, sig, chain):
        """Registry insertion + LRU eviction, shared by window detection and
        stitching. Returns False when `sig` is already registered."""
        with self._lock:
            if sig in self._chains:
                return False
            self._chains[sig] = chain
            self._chains.move_to_end(sig)
            chain.head_kid = self._intern.get(chain.ops[0].key)
            self._heads.setdefault(chain.head_kid, []).append(chain)
            cap = int(_FLAGS.get("FLAGS_eager_chain_cache_size", 128) or 0)
            while len(self._chains) > max(cap, 1):
                # detection registers every hot suffix, so most entries are
                # overlap variants that never replay: evict dead chains
                # first, then the oldest zero-replay one, before touching a
                # chain that has actually fused (the newest entry — the one
                # just registered — is last in iteration order either way)
                victim = None
                for c in self._chains.values():
                    if c.dead:
                        victim = c
                        break
                    if victim is None and c.replays == 0 and c is not chain:
                        victim = c
                if victim is not None:
                    old = self._chains.pop(victim.sig)
                else:
                    _, old = self._chains.popitem(last=False)
                self._drop_head(old)
                CHAIN_STATS.evictions += 1
        return True

    def _register_stitched(self, prev_pending, pending):
        """Window stitching: a fired chain, the per-op dispatches that
        followed it (the gap), and the chain that replayed right after
        become ONE longer chain when their boundary wiring connects.

        `pending.boundary[slot]` maps each ext slot of the second chain to
        its source — ("A", i, j) = previous chain output, ("G", g, j) = gap
        op output, None = genuinely external — and each gap record's inputs
        are resolved the same way at stitch time. The stitched chain keeps
        the first chain's ops 0..nA-1, appends the gap ops rebased by nA and
        the second chain's ops rebased by nA+nG, rewiring every boundary
        edge as an internal `("prev", i, j)`. It is registered like any
        detected chain — `_lookup_start` prefers the longest viable chain
        from a head key, so the next iteration replays the whole stitched
        sequence in one launch (and stitching composes: stitched chains
        stitch again, so whole transformer blocks converge to a single
        launch without growing the rolling-window detection cost). A
        stitched replay counts launches-saved once for the whole sequence;
        the constituent chains stop replaying, so telemetry never
        double-counts."""
        a, b = prev_pending.chain, pending.chain
        gap = pending.gap
        n_a, n_g = len(a.ops), len(gap)
        if a.dead or b.dead \
                or n_a + n_g + len(b.ops) > _STITCH_MAX_OPS:
            return
        # every op of the stitched result must be reachable as one dataflow:
        # require at least one edge from the gap or the second chain back
        # into the fired chain, else the two replays are unrelated streams
        touches_a = any(c is not None and c[0] == "A"
                        for c in pending.boundary)
        ops = []
        for op in a.ops:
            ops.append(_ChainOp(op.name, op.key, op.fn, op.wiring,
                                op.diff_mask, op.num_outputs, op.out_avals,
                                op.out_stop_grads))
        abs_to_g = {rec.abs_pos: g for g, rec in enumerate(gap)}
        for g, rec in enumerate(gap):
            wiring = []
            for k, w in enumerate(rec.wiring_abs):
                if w[0] == "prev" and w[1] in abs_to_g:
                    wiring.append(("prev", n_a + abs_to_g[w[1]], w[2]))
                    continue
                coord = self._fired_coord(prev_pending, rec.ins[k])
                if coord is not None:
                    wiring.append(("prev", coord[0], coord[1]))
                    touches_a = True
                else:
                    wiring.append(("ext",))
            ops.append(_ChainOp(rec.name, rec.key, rec.fn, tuple(wiring),
                                rec.diff_mask, rec.num_outputs,
                                rec.out_avals, rec.out_stop_grads))
        if not touches_a:
            return
        base_b = n_a + n_g
        boundary = pending.boundary
        slot = 0
        for op in b.ops:
            wiring = []
            for w in op.wiring:
                if w[0] == "prev":
                    wiring.append(("prev", w[1] + base_b, w[2]))
                else:
                    coord = boundary[slot]
                    slot += 1
                    if coord is None:
                        wiring.append(("ext",))
                    elif coord[0] == "A":
                        wiring.append(("prev", coord[1], coord[2]))
                    else:
                        wiring.append(("prev", n_a + coord[1], coord[2]))
            ops.append(_ChainOp(op.name, op.key, op.fn, tuple(wiring),
                                op.diff_mask, op.num_outputs, op.out_avals,
                                op.out_stop_grads))
        sig = tuple((self._intern_key(op.key), op.wiring) for op in ops)
        chain = Chain(sig, ops,
                      a.baseline_ns + b.baseline_ns
                      + sum(r.dur_ns for r in gap))
        if self._insert_chain(sig, chain):
            CHAIN_STATS.stitched(chain.label)
            _EVENTS.emit("chain.stitch", chain.label,
                         detail={"ops": len(chain.ops),
                                 "from_ops": [n_a, n_g, len(b.ops)]})

    def _drop_head(self, chain):
        lst = self._heads.get(chain.head_kid)
        if lst is not None:
            try:
                lst.remove(chain)
            except ValueError:
                pass
            if not lst:
                self._heads.pop(chain.head_kid, None)

    def _lookup_start(self, kid, key):
        with self._lock:
            best = None
            for chain in self._heads.get(kid, ()):
                # small-int ids can collide across intern-table resets: the
                # real key tuples must agree before replay starts
                if chain.dead or chain.ops[0].key != key:
                    continue
                # fewest failed replays first, longest chain as tiebreak: a
                # long chain that keeps escaping (e.g. it spans a tape read)
                # stops shadowing a shorter viable one after a single miss
                rank = (chain.fail_streak, -len(chain.ops))
                if best is None or rank < (best.fail_streak, -len(best.ops)):
                    best = chain
            if best is not None:
                self._chains.move_to_end(best.sig)
            return best

    # -- replay ------------------------------------------------------------
    @staticmethod
    def _replay_wiring_matches(pending, op, inputs):
        if len(inputs) != len(op.wiring):
            return False
        for t, w in zip(inputs, op.wiring):
            if _is_pending(t) and t._pending_chain is pending:
                if w[0] != "prev" or t._chain_coord != (w[1], w[2]):
                    return False
            elif w[0] != "ext":
                return False
        return True

    def _defer(self, st, pending, op, inputs, num_outputs):
        # owner thread only, pending.lock held by the caller (step)
        chain = pending.chain
        for k, t in enumerate(inputs):
            if op.wiring[k][0] != "ext":
                continue
            if pending.prev_fire is not None:
                pending.boundary.append(self._boundary_coord(pending, t))
            pending.ext_vals.append(t._value)
            if op.diff_mask is not None and op.diff_mask[k]:
                node = t._grad_node if t._grad_node is not None \
                    else t._ensure_grad_node()
                pending.ext_edges.append((node, t._out_index))
            else:
                pending.ext_edges.append(None)
        outs = tuple(
            _DeferredTensor(av, op.out_stop_grads[j], pending,
                            (pending.pos, j))
            for j, av in enumerate(op.out_avals))
        pending.placeholders.append(outs)
        pending.pos += 1
        if pending.pos == len(chain.ops):
            self._fire(pending)
        if num_outputs is not None:
            return list(outs)
        return outs[0]

    def resolve_pending(self, pending, escape):
        """Escape hatch: a placeholder of `pending` was touched from
        outside the chain. Complete chains just haven't fired yet only
        transiently (never observable), so resolution is always a split.
        May run on a thread other than the chain's owner (a placeholder
        handed across threads): the pending lock serializes against the
        owner's in-flight _defer/_fire, so the split sees a consistent
        prefix — or finds the chain already resolved and does nothing."""
        st = self._tls
        with pending.lock:
            if not pending.done:
                self._split(pending, escape=escape)
        if st.pending is pending:
            st.pending = None

    @staticmethod
    def _fired_coord(prev, t):
        """(op, out) coordinate of `t` in the fired replay `prev`, or None.
        Identity-checked: a materialized placeholder keeps its _chain_coord,
        and membership in the pending's placeholder table proves
        ownership."""
        if not isinstance(t, _DeferredTensor):
            return None
        coord = t._chain_coord
        try:
            if prev.placeholders[coord[0]][coord[1]] is t:
                return coord
        except (IndexError, AttributeError, TypeError):
            pass
        return None

    @classmethod
    def _boundary_coord(cls, pending, t):
        """Where an ext input of a stitch-candidate replay came from:
        ("A", i, j) = output of the fired previous chain, ("G", g, j) =
        output of gap op g, None = genuinely external."""
        coord = cls._fired_coord(pending.prev_fire, t)
        if coord is not None:
            return ("A",) + coord
        gcoord = pending.gap_outs.get(id(t))
        if gcoord is not None:
            return ("G",) + gcoord
        return None

    @staticmethod
    def _materialize(flat_idx, t, value, node):
        if _VALUE_SLOT.__get__(t) is _PENDING:
            _VALUE_SLOT.__set__(t, value)
        if node is not None:
            _NODE_SLOT.__set__(t, node)
            _IDX_SLOT.__set__(t, flat_idx)
        t._pending_chain = None

    def _fire(self, pending):
        """The chain completed: one fused launch fills every placeholder.
        Runs with pending.lock held (via _defer ← step)."""
        st = self._tls
        chain = pending.chain
        st.busy = True
        try:
            ext = tuple(pending.ext_vals)
            if chain.grad_mode:
                res = chain.fwd_vjp()(*ext)
                if chain.check:
                    from . import guardian
                    res, fin = res
                    guardian.enqueue_fwd(chain.label, fin)
                out_vals, vjp_partial = res
                wrapped = _make_chain_vjp(vjp_partial, chain.diff_ext_idx,
                                          chain.n_ext)
                node = FusedChainNode(
                    [op.name for op in chain.ops], wrapped,
                    list(pending.ext_edges), chain.flat_node_avals,
                    chain.owners)
                node.fwd_fn = chain.pure_fn
                node.in_vals, node.unpack_hook = _pack_saved(
                    ext, pending.ext_edges)
            else:
                out_vals = chain.fwd()(*ext)
                if chain.check:
                    from . import guardian
                    out_vals, fin = out_vals
                    guardian.enqueue_fwd(chain.label, fin)
                node = None
        except jax.errors.JaxRuntimeError:
            # transient execution fault: keep the chain, replay per-op
            st.busy = False
            self._split(pending, escape=False, reason="exec_fault")
            if st.pending is pending:
                st.pending = None
            return
        except Exception:
            # the fused trace itself failed (should be impossible for ops
            # the per-op cache accepted, but never let fusion take eager
            # down): kill the chain and fall back
            chain.dead = True
            CHAIN_STATS.deactivated += 1
            st.busy = False
            self._split(pending, escape=False, reason="trace_fail")
            if st.pending is pending:
                st.pending = None
            return
        from . import guardian as _guardian
        if _guardian.faults_armed():
            # fused-tier chaos (tools/chaos.py): "raise" recovers through
            # the transactional per-op split (bitwise-identical values);
            # "nan_output" poisons the FUSED outputs so downstream
            # detection — the step tier's grads-finite predicate, the
            # guardian's forward checks — is exercised against corruption
            # that originates inside a fused region
            fault = _guardian.poll_fault("fused_chain",
                                         ("nan_output", "raise"))
            if fault == "raise":
                st.busy = False
                self._split(pending, escape=False,
                            reason="injected_fault")
                if st.pending is pending:
                    st.pending = None
                return
            if fault == "nan_output":
                import jax.numpy as jnp
                out_vals = tuple(
                    jnp.full_like(v, jnp.nan)
                    if jnp.issubdtype(v.dtype, jnp.inexact) else v
                    for v in out_vals)
                if _guardian.enabled():
                    # the in-graph chain scalar saw the CLEAN outputs;
                    # queue a check on the poisoned ones so the guardian
                    # still attributes the corruption
                    _guardian.observe(chain.label, out_vals)
        try:
            flat = 0
            for i, op in enumerate(chain.ops):
                op_node = node if op.diff_mask is not None else None
                for j, t in enumerate(pending.placeholders[i]):
                    self._materialize(flat, t, out_vals[flat], op_node)
                    flat += 1
            pending.done = True
            chain.fail_streak = 0
            chain.replays += 1
            if not chain.aot_stored and _aot().enabled():
                # persist the proven executable once (store-if-absent:
                # a restored chain never re-exports)
                chain.aot_stored = True
                _aot().store_chain(chain, ext)
            elapsed = time.perf_counter_ns() - pending.t0
            CHAIN_STATS.replay(chain.label, len(chain.ops),
                               chain.baseline_ns - elapsed)
            _EVENTS.emit("chain.fire", chain.label,
                         detail={"ops": len(chain.ops),
                                 "launches_saved": len(chain.ops) - 1})
            if pending.prev_fire is not None \
                    and any(c is not None for c in pending.boundary):
                self._register_stitched(pending.prev_fire, pending)
            # drop the back-links before becoming the new stitch anchor —
            # otherwise fired pendings form an ever-growing linked list
            pending.prev_fire = None
            pending.gap = ()
            pending.gap_outs = {}
            st.last_fire = (pending, st.serial)
            st.stitch_gap = []
            # the detection window predates the fused regime and record()
            # no longer feeds it while ops defer: dropping it releases the
            # last pre-fusion dispatches' output buffers it pins (chains
            # spanning a fired chain could never match anyway — those ops
            # deferred instead of recording)
            self._reset_window(st)
        finally:
            st.busy = False
            if st.pending is pending:
                st.pending = None

    def _split(self, pending, escape, reason=None, blocked_op=None):
        """Replay the deferred prefix through the per-op cached path,
        filling the placeholders with bitwise-identical results. Callers
        hold pending.lock (owner via step/flush, escapees via
        resolve_pending); the guard below makes a second resolution a
        no-op. `reason` is the flight-recorder attribution (a
        REASON_CODES entry); `blocked_op` names the op that broke the
        chain when the split was caused by a specific dispatch."""
        st = self._tls
        chain = pending.chain
        if pending.done:
            return
        owner = st.pending is pending   # escapes run on a foreign thread
        st.busy = True
        try:
            replay_ops_per_op(chain.ops, pending.ext_vals,
                              pending.ext_edges, pending.placeholders,
                              pending.pos)
            pending.done = True
            pending.prev_fire = None
            pending.gap = ()
            pending.gap_outs = {}
            chain.fail_streak += 1
            deactivated = False
            if chain.fail_streak >= _MAX_FAIL_STREAK and not chain.dead:
                chain.dead = True
                deactivated = True
                CHAIN_STATS.deactivated += 1
            CHAIN_STATS.split(chain.label, escape=escape)
            if reason is None:
                reason = "mid_chain_escape" if escape else "key_mismatch"
            detail = {"pos": pending.pos, "ops": len(chain.ops)}
            if blocked_op:
                detail["blocked_op"] = blocked_op
            if deactivated:
                detail["deactivated"] = True
            _EVENTS.emit("chain.split", chain.label, reason=reason,
                         detail=detail)
        finally:
            st.busy = False
            if st.pending is pending:
                st.pending = None
        if owner:
            # only the owner's detection window saw this chain's stream; a
            # foreign escaping thread must not wipe its own unrelated
            # detection progress (nor its stitch anchor)
            self._reset_window(st)
            st.last_fire = None
            st.stitch_gap = []

    # -- maintenance --------------------------------------------------------
    def clear(self):
        self.flush()
        st = self._tls
        self._reset_window(st)
        st.counter = 0
        st.serial = 0
        st.last_fire = None
        st.stitch_gap = []
        with self._lock:
            self._counts.clear()
            self._chains.clear()
            self._heads.clear()
            self._intern.clear()
        for applier in (_chain_vjp_applier, _chain_vjp_applier_donate):
            try:
                applier.clear_cache()
            except Exception:
                pass

    def info(self):
        with self._lock:
            chains = list(self._chains.values())
        return {
            "entries": len(chains),
            "capacity": int(_FLAGS.get("FLAGS_eager_chain_cache_size", 128)),
            "chains": [{"label": c.label, "ops": len(c.ops),
                        "ext_inputs": c.n_ext, "grad": c.grad_mode,
                        "dead": c.dead, "replays": c.replays}
                       for c in chains],
        }


MANAGER = _FusionManager()


def clear_chain_cache():
    """Drop every registered chain, detection count, and pending replay on
    the calling thread (test hook / manual invalidation)."""
    MANAGER.clear()


def chain_cache_info():
    """Entry count + capacity + per-chain summaries of the chain cache."""
    return MANAGER.info()
