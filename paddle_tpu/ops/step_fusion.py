"""Whole-step eager fusion: auto-TrainStep promotion.

The layer above chain fusion (ops/fusion.py). Chain fusion collapses hot
forward op *sequences* into single launches, but every chain stops at a
tape read: `loss.backward()` forces the pending chain, and the backward
walk plus the optimizer update still launch per-node. `jit.TrainStep`
proves the fast path is ONE executable for the whole step — this module
gets eager loops there automatically, without the user rewriting their
loop.

How it works:

  OBSERVE   Every dispatched op, `Tensor.backward()` call, and optimizer
            `step()`/`clear_grad()` call is recorded into the current
            *cycle* (one training iteration, delimited by `opt.step()`
            entries). A cycle's signature is the ordered tuple of per-op
            cache keys + dataflow wiring + the backward/optimizer events —
            the same keying discipline as chain fusion scaled to a step,
            so every per-op invalidation rule (registry generation, AMP
            state, avals, diff masks) applies for free.

  PROMOTE   After FLAGS_eager_step_fusion_min_count consecutive identical
            cycles, the cycle is compiled into one fused executable:
            forward (rebuilt as a pure function from the recorded ops, the
            re-trace contract of framework/autograd.replay_pure), backward
            (jax.vjp w.r.t. the parameter slots), grad regularization +
            clipping (the optimizer's own clip/regularizer objects traced
            over shims), and the optimizer update (`_single_update`, with
            decay flags baked by jit/train_step.bake_decay_flags).
            Optimizer-slot buffers are donated exactly as the eager
            optimizer's fused update donates them; parameter donation is
            opt-in (FLAGS_eager_step_fusion_donate_params), sharing
            jit/train_step.donation_argnums.

  REPLAY    Speculative and transactional, like chain replay: each
            dispatch is matched against the promoted program and deferred
            as a `_DeferredTensor`; `loss.backward()` is consumed as an
            event (p.grad becomes a pending placeholder); `opt.step()`
            fires the ONE fused launch, updates parameters/slots in place,
            and fills the loss + grad placeholders from the fused outputs.
            The LR-schedule value and the step count are hoisted to scalar
            arguments, so schedulers never split. ANY divergence — an op
            or event mismatch, a mid-step value peek (a `loss.numpy()`
            between backward and step; after the step it is served from
            the fused outputs), a changed optimizer/clip/param set, an
            in-place param mutation, an RNG-key advance (random ops re-key
            every call), an execution fault — SPLITS: the deferred prefix
            replays through the chain/per-op cached path and, if the
            backward event was already consumed, the real tape backward
            runs, so numerics are bitwise-identical to unfused dispatch in
            every outcome. Steps that keep failing to replay are
            deactivated.

Telemetry: profiler/step_fusion.py, surfaced by
`paddle_tpu.profiler.step_fusion_stats()`.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework import autograd as _autograd
from ..framework.autograd import FusedStepNode, run_backward
from ..framework.flags import _FLAGS
from ..profiler.step_fusion import STEP_STATS
from ..profiler.events import EVENTS as _EVENTS
from .fusion import (MANAGER as _CHAIN_MANAGER, Chain, _ChainOp,
                     _DeferredTensor, _PENDING, _VALUE_SLOT, _NODE_SLOT,
                     _IDX_SLOT, _is_pending, _key_diff_reason,
                     replay_ops_per_op)

__all__ = ["STEP", "MISS", "clear_step_cache", "step_cache_info"]

MISS = object()

# consecutive failed replays before a promoted step is deactivated
_MAX_FAIL_STREAK = 4
# recording cap per cycle: a cycle longer than this cannot promote (the
# compile would not amortize) and recording details stop to bound memory
_MAX_CYCLE_OPS = 2048

_UNBUILDABLE = object()     # library sentinel: this sig cannot promote


def _out_aval(t):
    """(shape, dtype, weak_type) without forcing a pending placeholder."""
    av = getattr(t, "_fusion_aval", None)
    if av is not None:
        return av
    v = t._value
    return (v.shape, v.dtype, getattr(v, "weak_type", False))


def _snapshot_obj(obj):
    """Value snapshot of a clip/regularizer object's scalar attributes:
    these are baked into the traced step as constants, so a mutation must
    un-verify the promoted program."""
    if obj is None:
        return None
    attrs = tuple(sorted(
        (k, v) for k, v in vars(obj).items()
        if isinstance(v, (int, float, bool, str))))
    return (type(obj).__name__, attrs)


class _OpRec:
    """One dispatch recorded into the current observation cycle. `ins` and
    `outs` hold strong refs for the cycle's lifetime: the produced-map is
    keyed by id(), so every recorded tensor must stay alive or a freed
    id's reuse would mis-wire a later fresh input as ("prev", i, j)."""

    __slots__ = ("name", "key", "fn", "wiring", "diff_mask", "num_outputs",
                 "out_avals", "out_stop_grads", "ins", "outs")

    def __init__(self, name, key, fn, wiring, diff_mask, num_outputs,
                 out_avals, out_stop_grads, ins, outs):
        self.name = name
        self.key = key
        self.fn = fn
        self.wiring = wiring
        self.diff_mask = diff_mask
        self.num_outputs = num_outputs
        self.out_avals = out_avals
        self.out_stop_grads = out_stop_grads
        self.ins = ins
        self.outs = outs


class _Cycle:
    """Observation state for one training iteration."""

    __slots__ = ("entries", "ops", "produced", "dirty", "t0", "n_backward",
                 "scaler", "rng_epoch0")

    def __init__(self):
        self.entries = []
        self.ops = []
        self.produced = {}     # id(tensor) -> (op index, out index)
        self.dirty = False
        self.t0 = time.perf_counter_ns()
        self.n_backward = 0
        self.scaler = None     # GradScaler seen by on_scaler_step, if any
        # absolute stream position of the cycle's FIRST hoisted RNG input
        # (framework/random.rng_key_input): per-input positions enter the
        # signature as DELTAS from it, so a loop whose randomness advances
        # every step still records the identical structural signature
        self.rng_epoch0 = None

    def poison(self):
        """The cycle cannot promote: drop every recorded detail NOW so a
        dirty (or boundary-less, e.g. pure-inference) stream pins no
        tensors — after this, record() is a cheap early return until the
        next optimizer-step boundary."""
        self.dirty = True
        self.entries.clear()
        self.ops.clear()
        self.produced.clear()
        self.scaler = None
        self.rng_epoch0 = None


class _ParamShim:
    """Minimal stand-in for a Parameter inside the traced grad transform:
    the optimizer's clip/regularizer objects only read `_value`,
    `need_clip`, `name`, and `regularizer`."""

    __slots__ = ("_value", "name", "need_clip", "regularizer")


class _StepProgram:
    """A promoted cycle: the forward chain, the event schedule, the
    optimizer binding, and (lazily) the one fused executable."""

    __slots__ = ("sig", "chain", "entries", "root_coord", "root_flat",
                 "param_refs", "param_names", "param_regs", "need_clip",
                 "param_slots", "ext_order", "opt_ref", "clip_ref",
                 "clip_snapshot", "reg_ref", "reg_snapshot", "extra_key",
                 "acc_names", "label", "n_launches", "baseline_ns",
                 "fail_streak", "dead", "_exe", "_shims", "donate_params",
                 "check", "scaler_ref", "scaler_consts", "aot_digest",
                 "aot_stored", "spmd_plan", "spmd_ok", "rng_slots",
                 "super", "seg_start", "_sub_exe", "_upd_exe", "_zero_acc",
                 "tail_chain", "tail_root_flat", "tail_rng_slots",
                 "_tail_sub_exe")

    def __init__(self):
        self.fail_streak = 0
        self.dead = False
        self._exe = None
        self._shims = None
        self.aot_digest = None   # ops/aot_cache.py warm-start address
        self.aot_stored = False
        # guardian (FLAGS_check_numerics, ops/guardian.py): check-ness is
        # fixed by the signature (the per-op keys carry the flag), and the
        # executable then folds the skip-step where()-rescue in; a fused
        # GradScaler additionally folds unscale/found-inf/scale-update
        self.check = False
        self.scaler_ref = None
        self.scaler_consts = None
        # distributed lowering (ops/spmd_fusion.py): a MeshPlan makes
        # _compile wrap the step in shard_map over the plan's mesh (grad
        # psum + sharded update + all-reduced predicates fused in); the
        # first fire then runs under PROBATION (spmd_ok False → eager
        # results commit, fused-vs-eager compared; a divergence demotes the
        # program to the plain jit lowering)
        self.spmd_plan = None
        self.spmd_ok = True
        # hoisted RNG consumption: ((ext slot, stream delta), ...) — these
        # ext slots are DERIVED in-graph from the hoisted (base key data,
        # first position) device args instead of being fed values
        self.rng_slots = ()
        # super-cycle (grad accumulation): the program's chain is ONE
        # micro-batch segment; replay loops it k times, firing the SUB
        # executable (fwd+vjp, grads added into a device accumulator) at
        # each backward and the UPDATE executable (clip/reg + optimizer +
        # guardian/scaler on the ACCUMULATED grads) at the step boundary —
        # ≤2 executables and zero retraces at ANY k
        self.super = False
        self.seg_start = 0      # entry index of the segment's first entry
        self._sub_exe = None
        self._upd_exe = None
        self._zero_acc = None
        # ragged tail (epoch-boundary batches): a SECOND op template +
        # sub-executable for the one smaller micro-batch closing the
        # accumulation loop — k−1 full rounds fire the main sub, the tail
        # round fires this one into the SAME accumulator (grads share the
        # param avals, so the shapes agree). ≤3 executables total.
        self.tail_chain = None
        self.tail_root_flat = None
        self.tail_rng_slots = ()
        self._tail_sub_exe = None   # (zero grad accumulators, True scalar)

    def release_heavy(self):
        """A deactivated program stays in the library as a tombstone (so
        the same cycle is not re-promoted just to fail again) but must not
        pin its compiled executable or trace shims. The op templates
        (chain) stay: already-fired pendings still lazily recompute
        through them."""
        self._exe = None
        self._shims = None
        self._sub_exe = None
        self._upd_exe = None
        self._zero_acc = None
        self._tail_sub_exe = None

    # -- the fused executable ----------------------------------------------
    def _grad_transform(self, pvals, grads):
        """Regularization + grad clip exactly as Optimizer.step applies
        them, traced over param shims so the user's own clip/regularizer
        objects run unmodified."""
        reg = self.reg_ref
        clip = self.clip_ref
        if reg is None and clip is None:
            return grads
        shims = self._shims
        pgs = []
        for shim, pv, gv in zip(shims, pvals, grads):
            shim._value = pv
            g = Tensor(gv, stop_gradient=True)
            if reg is not None:
                g = reg.apply(shim, g)
            pgs.append((shim, g))
        if clip is not None:
            pgs = clip(pgs)
        return [g._value for _, g in pgs]

    def exe(self):
        if self._exe is not None:
            return self._exe
        from ..jit.train_step import donation_argnums
        from . import aot_cache as _aot
        if _aot.enabled() and self.aot_digest is not None:
            # warm start: deserialize the stored whole-step program (zero
            # fresh traces); a corrupt/mismatched artifact heals through
            # _compile transparently
            self._exe = _aot.load_step(
                self, self._compile,
                donation_argnums(self.donate_params, 0, 2))
            if self._exe is not None:
                if self.spmd_plan is not None:
                    # a stored SPMD artifact only exists because a prior
                    # process fired it AFTER passing probation on this
                    # exact cycle + mesh topology (the env fingerprint
                    # pins both) — the restored program re-validates
                    # nothing and commits fused from its first replay
                    self.spmd_ok = True
                return self._exe
        self._exe = self._compile()
        return self._exe

    def _compile(self):
        from ..jit.train_step import donation_argnums
        from . import guardian
        from . import spmd_fusion as _spmd
        plan = self.spmd_plan
        chain = self.chain
        pure = chain.pure_fn
        root = self.root_flat
        seed_shape, seed_dtype = chain.flat_avals[root][:2]
        param_slots = tuple(sorted(self.param_slots.items()))
        ext_order = self.ext_order
        n_ext = chain.n_ext
        # the closure holds the WEAKREF, not the optimizer: jit retains the
        # traced fn for the program's lifetime, and a strong capture would
        # pin the optimizer (and through _parameter_list the whole model)
        # even after the user discards both. The deref only runs at trace
        # time, when the firing hook has the optimizer live in hand.
        opt_ref = self.opt_ref
        acc_names = self.acc_names
        check = self.check
        scaler_consts = self.scaler_consts
        rng_items = tuple(sorted(self.rng_slots.items())) \
            if self.rng_slots else ()
        self._ensure_shims()

        def step_body(pvals, ext, accs, lr, step_count, rng_state,
                      scaler_state):
            STEP_STATS.retraces += 1   # side effect: runs only while tracing
            full = [None] * n_ext
            for pos, slot in enumerate(ext_order):
                full[slot] = ext[pos]
            if rng_state is not None:
                # hoisted RNG: every key derives IN-GRAPH from (base key
                # data, first stream position) — the same fold_in the
                # eager path applies, so the fused key stream is
                # bit-identical to eager's
                from ..framework import random as _random
                base_kd, ep0 = rng_state
                for slot, delta in rng_items:
                    full[slot] = _random.derive_key_data(base_kd,
                                                         ep0 + delta)

            def fwd(pv):
                env = list(full)
                for slot, k in param_slots:
                    env[slot] = pv[k]
                return pure(*env)[root]

            # stored-sharded (ZeRO) params all-gather to full for the
            # forward; grads come back full so p.grad parity holds
            pvals_full = pvals if plan is None \
                else _spmd.gather_params(plan, pvals)
            root_val, vjp = jax.vjp(fwd, list(pvals_full))
            (grads,) = vjp(jnp.ones(seed_shape, seed_dtype))
            if plan is not None:
                # the gradient all-reduce + loss sync of the distributed
                # lowering (ops/spmd_fusion.py): every grad rides ONE
                # fused pmean region over the batch axes
                root_val, grads = _spmd.sync_root_and_grads(
                    plan, root_val, grads)
            finite_of = guardian.finite_all if plan is None \
                else (lambda vals: _spmd.global_finite(plan, vals))
            extras = ()
            if scaler_state is not None:
                # check_finite_and_unscale + update_loss_scaling, folded
                # in: grads leave the executable UNSCALED (exactly what
                # the eager path leaves in p.grad after scaler.step), and
                # the loss-scale transition is the same pure function the
                # eager GradScaler.update() evaluates. Under a mesh plan
                # found-inf is all-reduced, so the backoff is globally
                # consistent even when one shard saw the blowup.
                scale, good, bad = scaler_state
                inv = jnp.asarray(1.0, jnp.float32) / scale
                grads = [g * inv.astype(g.dtype) for g in grads]
                found_inf = jnp.logical_not(finite_of(grads))
                (_en, _dyn, incr_ratio, decr_ratio,
                 incr_n, decr_n) = scaler_consts
                scale2, good2, bad2 = guardian.update_scaler_state(
                    scale, good, bad, found_inf, incr_ratio, decr_ratio,
                    incr_n, decr_n)
                extras = (found_inf, scale2, good2, bad2)
            upd = self._grad_transform(pvals_full, grads)
            opt = opt_ref()   # trace-time only; firing keeps it alive
            new_p, new_accs = [], []
            for k, (pv, gv, ac) in enumerate(zip(pvals, upd, accs)):
                acc_dict = dict(zip(acc_names, ac))
                if plan is not None and plan.param_shard[k] is not None:
                    # ZeRO-sharded slots: slice-update-allgather
                    np_, na_ = _spmd.sharded_single_update(
                        plan, k, opt, pv, gv, acc_dict, lr, step_count)
                else:
                    np_, na_ = opt._single_update(pv, gv, acc_dict, lr,
                                                  step_count)
                new_p.append(np_)
                new_accs.append([na_.get(n) for n in acc_names])
            if check:
                # skip-step rescue: non-finite grads OR a non-finite
                # updated state make the whole update a bitwise no-op on
                # params AND optimizer slots — ONE fused scalar
                # predicate, zero extra launches. The new params/slots
                # are part of the predicate because finite grads can
                # still blow up the state (an LR spike overflowing
                # `p - lr*g`, a momentum buffer saturating): gating on
                # grads alone would wave the blowup through the gate.
                # Under a mesh plan the predicate is ALL-REDUCED first:
                # sharded slots make it device-varying, and every shard
                # must take the same skip/keep branch.
                new_state = list(new_p) + [v for row in new_accs
                                           for v in row if v is not None]
                upd_finite = finite_of(list(upd) + new_state)
                fwd_finite = finite_of([root_val])
                new_p = [jnp.where(upd_finite, nv, pv)
                         for nv, pv in zip(new_p, pvals)]
                new_accs = [
                    [None if nv is None else jnp.where(upd_finite, nv, ov)
                     for nv, ov in zip(row, ac)]
                    for row, ac in zip(new_accs, accs)]
                extras = (upd_finite, fwd_finite) + extras
            return (root_val, grads, new_p, new_accs) + extras

        n_rng = 2 if rng_items else 0

        def step_fn(pvals, ext, accs, lr, step_count, *tail):
            # tail layout: [base_key_data, epoch0] when the program has
            # hoisted RNG slots, then [scale, good, bad] for a folded
            # GradScaler — both ride as device args so neither randomness
            # nor loss-scale dynamics ever retrace the program
            rng_state = tail[:2] if n_rng else None
            sc = tail[n_rng:]
            scaler_state = tuple(sc) if sc else None
            return step_body(pvals, ext, accs, lr, step_count, rng_state,
                             scaler_state)

        donate = donation_argnums(self.donate_params, 0, 2)
        if plan is not None:
            # the distributed lowering: shard_map over the plan's mesh,
            # same outer signature and donation argnums as the plain path
            n_scaler = 3 if scaler_consts is not None else 0
            n_extras = (2 if check else 0) \
                + (4 if scaler_consts is not None else 0)
            self._exe = _spmd.compile_step(
                plan, step_fn, len(self.param_refs), n_rng + n_scaler,
                n_extras, donate)
            return self._exe
        self._exe = jax.jit(step_fn, donate_argnums=donate)
        return self._exe

    # -- the super-cycle pair (grad accumulation) --------------------------
    def _ensure_shims(self):
        if self._shims is None:
            shims = []
            for nm, nc, pr in zip(self.param_names, self.need_clip,
                                  self.param_regs):
                s = _ParamShim()
                s.name = nm
                s.need_clip = nc
                s.regularizer = pr
                shims.append(s)
            self._shims = shims

    def sub_exe(self):
        """The reusable micro-batch sub-executable: fwd + vjp over the
        param slots, gradients ADDED into the running accumulator. Fired
        once per `loss.backward()` of the accumulation loop — the same
        compiled program at any k."""
        if self._sub_exe is None:
            self._maybe_load_super()
        if self._sub_exe is None:
            self._sub_exe = self._compile_sub()
        return self._sub_exe

    def upd_exe(self):
        """The boundary update executable: clip/regularizer + optimizer
        update + guardian skip predicate + GradScaler transition, all
        evaluated on the ACCUMULATED grads. Fired once per `opt.step()`."""
        if self._upd_exe is None:
            self._maybe_load_super()
        if self._upd_exe is None:
            self._upd_exe = self._compile_update()
        return self._upd_exe

    def tail_sub_exe(self):
        """The ragged-tail sub-executable: the same fwd+vjp+accumulate
        body compiled against the TAIL segment's op template (the one
        smaller epoch-boundary micro-batch). Adds into the same
        accumulator as the main sub — grads share the param avals."""
        if self._tail_sub_exe is None:
            self._tail_sub_exe = self._compile_sub(
                chain=self.tail_chain, root_flat=self.tail_root_flat,
                rng_slots=self.tail_rng_slots)
        return self._tail_sub_exe

    def _maybe_load_super(self):
        """AOT warm start for the super-cycle pair: deserialize both
        stored executables (zero fresh traces); corrupt or mismatched
        artifacts heal through the live compilers transparently."""
        from ..jit.train_step import donation_argnums
        from . import aot_cache as _aot
        if not (_aot.enabled() and self.aot_digest is not None):
            return
        sub, upd = _aot.load_super_step(
            self, self._compile_sub, self._compile_update,
            donation_argnums(self.donate_params, 0, 1))
        if sub is not None:
            self._sub_exe = sub
            self._upd_exe = upd
            if self.spmd_plan is not None:
                # the stored pair proved itself post-probation in the
                # storing process, on this exact cycle + topology —
                # skip probation and fire fused immediately
                self.spmd_ok = True

    def zero_state(self):
        """(zero grad accumulators, all-finite True scalar): the round-0
        inputs of the sub executable. Never donated or mutated — one
        allocation per program, reused every cycle."""
        if self._zero_acc is None:
            from . import spmd_fusion as _spmd
            shapes = []
            for r in self.param_refs:
                v = r()._value     # grads share the param aval
                shapes.append((tuple(v.shape), v.dtype))
            if self.spmd_plan is not None:
                accs = _spmd.zero_accum(self.spmd_plan, shapes)
            else:
                accs = [jnp.zeros(s, d) for s, d in shapes]
            self._zero_acc = (accs, jnp.asarray(True))
        return self._zero_acc

    def _compile_sub(self, chain=None, root_flat=None, rng_slots=None):
        from . import guardian
        from . import spmd_fusion as _spmd
        plan = self.spmd_plan
        chain = self.chain if chain is None else chain
        pure = chain.pure_fn
        root = self.root_flat if root_flat is None else root_flat
        seed_shape, seed_dtype = chain.flat_avals[root][:2]
        param_slots = tuple(sorted(self.param_slots.items()))
        ext_order = self.ext_order
        n_ext = chain.n_ext
        rng_slots = self.rng_slots if rng_slots is None else rng_slots
        rng_items = tuple(sorted(rng_slots.items())) if rng_slots else ()
        n_rng = 2 if rng_items else 0
        check = self.check

        def sub_fn(pvals, ext, acc, *tail):
            STEP_STATS.retraces += 1   # side effect: runs only while tracing
            # tail layout: [base_key_data, epoch0] when the segment
            # consumes hoisted RNG, then [fwd_ok] under the guardian —
            # the running all-rounds-finite predicate threads through
            full = [None] * n_ext
            for pos, slot in enumerate(ext_order):
                full[slot] = ext[pos]
            if n_rng:
                from ..framework import random as _random
                base_kd, ep0 = tail[0], tail[1]
                for slot, delta in rng_items:
                    full[slot] = _random.derive_key_data(base_kd,
                                                         ep0 + delta)

            def fwd(pv):
                env = list(full)
                for slot, k in param_slots:
                    env[slot] = pv[k]
                return pure(*env)[root]

            pvals_full = pvals if plan is None \
                else _spmd.gather_params(plan, pvals)
            root_val, vjp = jax.vjp(fwd, list(pvals_full))
            (grads,) = vjp(jnp.ones(seed_shape, seed_dtype))
            if plan is not None and plan.data_axes:
                # the per-round LOSS syncs (one scalar pmean — it may be
                # served to the caller); the GRADIENTS do not: local sums
                # accumulate, and ONE fused pmean fires in the update
                # executable — k× less collective traffic than syncing
                # every micro-batch
                root_val = jax.lax.pmean(root_val, plan.data_axes)
            new_acc = [a + g for a, g in zip(acc, grads)]
            if check:
                fwd_ok = jnp.logical_and(tail[n_rng],
                                         guardian.finite_all([root_val]))
                return (root_val, new_acc, fwd_ok)
            return (root_val, new_acc)

        if plan is not None:
            sub_fn._returns_fwd_ok = check
            return _spmd.compile_accum(plan, sub_fn, len(self.param_refs),
                                       n_rng + (1 if check else 0))
        return jax.jit(sub_fn)

    def _compile_update(self):
        from ..jit.train_step import donation_argnums
        from . import guardian
        from . import spmd_fusion as _spmd
        plan = self.spmd_plan
        opt_ref = self.opt_ref
        acc_names = self.acc_names
        check = self.check
        scaler_consts = self.scaler_consts
        self._ensure_shims()

        def upd_fn(pvals, accs, gsum, lr, step_count, *tail):
            STEP_STATS.retraces += 1
            # tail layout: [fwd_ok] under the guardian, then
            # [scale, good, bad] for a folded GradScaler. The body mirrors
            # the post-gradient half of _compile's step_body, evaluated on
            # the ACCUMULATED grads — guardian skip and scaler backoff see
            # exactly what the eager path sees in p.grad after k backwards.
            grads = list(gsum)
            if plan is not None and plan.data_axes:
                # the ONE fused gradient collective of the super-cycle
                grads = [jax.lax.pmean(g, plan.data_axes) for g in grads]
            finite_of = guardian.finite_all if plan is None \
                else (lambda vals: _spmd.global_finite(plan, vals))
            i_tail = 0
            fwd_ok = None
            if check:
                fwd_ok = tail[0]
                i_tail = 1
            extras = ()
            sc = tail[i_tail:]
            if sc:
                scale, good, bad = sc
                inv = jnp.asarray(1.0, jnp.float32) / scale
                grads = [g * inv.astype(g.dtype) for g in grads]
                found_inf = jnp.logical_not(finite_of(grads))
                (_en, _dyn, incr_ratio, decr_ratio,
                 incr_n, decr_n) = scaler_consts
                scale2, good2, bad2 = guardian.update_scaler_state(
                    scale, good, bad, found_inf, incr_ratio, decr_ratio,
                    incr_n, decr_n)
                extras = (found_inf, scale2, good2, bad2)
            pvals_full = pvals if plan is None \
                else _spmd.gather_params(plan, pvals)
            upd = self._grad_transform(pvals_full, grads)
            opt = opt_ref()   # trace-time only; firing keeps it alive
            new_p, new_accs = [], []
            for k, (pv, gv, ac) in enumerate(zip(pvals, upd, accs)):
                acc_dict = dict(zip(acc_names, ac))
                if plan is not None and plan.param_shard[k] is not None:
                    np_, na_ = _spmd.sharded_single_update(
                        plan, k, opt, pv, gv, acc_dict, lr, step_count)
                else:
                    np_, na_ = opt._single_update(pv, gv, acc_dict, lr,
                                                  step_count)
                new_p.append(np_)
                new_accs.append([na_.get(n) for n in acc_names])
            if check:
                new_state = list(new_p) + [v for row in new_accs
                                           for v in row if v is not None]
                upd_finite = finite_of(list(upd) + new_state)
                new_p = [jnp.where(upd_finite, nv, pv)
                         for nv, pv in zip(new_p, pvals)]
                new_accs = [
                    [None if nv is None else jnp.where(upd_finite, nv, ov)
                     for nv, ov in zip(row, ac)]
                    for row, ac in zip(new_accs, accs)]
                extras = (upd_finite, fwd_ok) + extras
            return (grads, new_p, new_accs) + extras

        donate = donation_argnums(self.donate_params, 0, 1)
        if plan is not None:
            n_tail = (1 if check else 0) \
                + (3 if scaler_consts is not None else 0)
            n_extras = (2 if check else 0) \
                + (4 if scaler_consts is not None else 0)
            return _spmd.compile_update(plan, upd_fn, len(self.param_refs),
                                        n_tail, n_extras, donate)
        return jax.jit(upd_fn, donate_argnums=donate)


class _PendingStep:
    """A speculative whole-step replay in flight."""

    __slots__ = ("program", "owner", "entry_pos", "op_pos", "ext_vals",
                 "ext_edges", "placeholders", "params", "grad_phs",
                 "backward_done", "fired", "done", "lock", "t0",
                 "rng_epoch0", "rng_base", "rounds", "round_losses",
                 "acc_vals", "fwd_ok", "sub_args", "in_tail", "tail_done")

    def __init__(self, program, params, owner):
        self.program = program
        self.owner = owner
        self.entry_pos = 0
        self.op_pos = 0
        self.ext_vals = []
        self.ext_edges = []
        self.placeholders = []
        self.params = params
        self.grad_phs = None
        self.backward_done = False
        self.fired = False
        self.done = False
        self.lock = threading.RLock()
        self.t0 = time.perf_counter_ns()
        # hoisted RNG: absolute stream position of this cycle's first
        # consumption (the epoch0 device arg of the fused fire) and the
        # BASE KEY the round's tensors were reserved against — the fire
        # must derive from that base, not whatever the global generator
        # holds at boundary time (a mid-cycle reseed swaps it)
        self.rng_epoch0 = None
        self.rng_base = None
        # super-cycle replay (grad accumulation): archived micro-batch
        # rounds [(ext_vals, ext_edges, placeholders, rng_epoch0), ...],
        # the per-round losses from sub-executable fires, the running
        # donated grad accumulator, and the running fwd-finite predicate
        self.rounds = []
        self.round_losses = []
        self.acc_vals = None
        self.fwd_ok = None
        self.sub_args = None    # last MAIN sub fire's args (AOT export)
        # ragged tail: the current round is matching against the TAIL op
        # template (the smaller epoch-boundary micro-batch); tail_done
        # records that a tail round already archived this cycle
        self.in_tail = False
        self.tail_done = False


class _TLS(threading.local):
    def __init__(self):
        self.recording = None      # _Cycle or None
        self.prev_sig = None
        self.streak = 0
        self.library = OrderedDict()   # sig -> _StepProgram | _UNBUILDABLE
        self.active = None         # armed program
        self.replay_arm = False    # next cycle's first entry may start replay
        self.pending = None
        self.busy = False
        self.aot_probe = {}        # sig -> AOT step digest (or None)


class _StepFusionManager:
    """Cycle recorder + promotion + whole-step replay. All state is
    per-thread (a training loop is one thread); cross-thread escapes of
    pending placeholders resolve through the shared owner protocol of
    ops/fusion.py."""

    def __init__(self):
        self._tls = _TLS()

    # -- config ------------------------------------------------------------
    @staticmethod
    def enabled():
        return bool(_FLAGS.get("FLAGS_eager_step_fusion")) \
            and int(_FLAGS.get("FLAGS_eager_step_fusion_cache_size", 8)
                    or 0) > 0 \
            and bool(_FLAGS.get("FLAGS_eager_op_cache")) \
            and int(_FLAGS.get("FLAGS_eager_op_cache_size", 512) or 0) > 0

    # -- dispatch hooks ----------------------------------------------------
    def step(self, name, fn, inputs, num_outputs, key, diff_mask,
             bypass_reason=None):
        """First crack at every non-debug dispatch (before chain fusion).
        Returns deferred placeholders while a whole-step replay is
        matching, else MISS (the dispatcher proceeds and later feeds
        record()). `bypass_reason` attributes a key=None poison/split to
        the dispatch-level cause (rng_rekey, unkeyable_closure, ...)."""
        st = self._tls
        if st.busy:
            return MISS
        if not self.enabled():
            if st.pending is not None or st.recording is not None \
                    or st.active is not None:
                self._disable(st)
            return MISS
        arm = st.replay_arm
        st.replay_arm = False
        if key is None:
            # un-jittable/un-keyable op: the cycle cannot promote
            self._poison(st, bypass_reason or "unkeyable_closure", op=name)
            pending = st.pending
            if pending is not None and not pending.fired:
                with pending.lock:
                    if not pending.done:
                        self._split(pending, escape=False,
                                    reason=bypass_reason
                                    or "unkeyable_closure",
                                    blocked_op=name)
                st.pending = None
            return MISS

        pending = st.pending
        if pending is not None or (arm and st.active is not None):
            # replay matching is about to read input state: genuinely
            # foreign pendings (another thread's chain, a fired step) must
            # be resolved lock-free first, mirroring chain fusion. This
            # thread's own in-flight CHAIN pending is NOT foreign — the
            # chain manager handles it in its own step() — and while step
            # fusion merely observes, no pre-forcing happens at all.
            own_chain = _CHAIN_MANAGER._tls.pending
            for t in inputs:
                if _is_pending(t) and t._pending_chain is not st.pending \
                        and t._pending_chain is not own_chain:
                    t._pending_chain.owner.resolve_pending(
                        t._pending_chain, escape=True)
        if pending is not None and not pending.fired:
            program = pending.program
            with pending.lock:
                if pending.done:
                    st.pending = None
                else:
                    entry = program.entries[pending.entry_pos]
                    if entry[0] != "op":
                        self._split(pending, escape=False,
                                    reason="event_mismatch", blocked_op=name)
                        return MISS
                    mismatch = self._match_round(
                        program, pending, key, inputs, diff_mask,
                        num_outputs)
                    if mismatch is None:
                        return self._defer(st, pending, inputs, num_outputs)
                    self._split(pending, escape=False, reason=mismatch,
                                blocked_op=name)
            return MISS
        if arm and st.active is not None:
            program = st.active
            if program.entries and program.entries[0][0] == "op":
                pending = self._start_pending(st, program)
                if pending is not None:
                    with pending.lock:
                        mismatch = self._match_round(
                            program, pending, key, inputs, diff_mask,
                            num_outputs)
                        if mismatch is None:
                            return self._defer(st, pending, inputs,
                                               num_outputs)
                        self._split(pending, escape=False, reason=mismatch,
                                    blocked_op=name)
        return MISS

    def record(self, name, fn, inputs, num_outputs, key, diff_mask, outs,
               cached_ok, bypass_reason=None):
        """Feed the cycle recorder after a dispatch ran (per-op cached,
        per-op uncached, or deferred into a chain replay)."""
        st = self._tls
        if st.busy or not self.enabled():
            return
        cyc = st.recording
        if cyc is None:
            cyc = st.recording = _Cycle()
        if cyc.dirty:
            return
        if key is None or not cached_ok or len(cyc.ops) >= _MAX_CYCLE_OPS:
            if key is None:
                reason = bypass_reason or "unkeyable_closure"
            elif not cached_ok:
                reason = "uncached_dispatch"
            else:
                reason = "cycle_too_long"
            self._poison(st, reason, op=name)
            return
        wiring = tuple(
            ("prev",) + cyc.produced[id(t)] if id(t) in cyc.produced
            else ("ext",)
            for t in inputs)
        try:
            out_avals = tuple(_out_aval(t) for t in outs)
        except Exception:
            self._poison(st, "tracer_input", op=name)
            return
        # hoisted RNG inputs (framework/random.rng_key_input): note each
        # one's stream position as a DELTA from the cycle's first — the
        # sig stays identical across steps while the stream advances, and
        # _build hoists (base key, first position) into the executable so
        # replay derives every key in-graph
        rng_marks = ()
        for k, t in enumerate(inputs):
            ep = getattr(t, "_rng_epoch", None)
            if ep is None:
                continue
            if cyc.rng_epoch0 is None:
                cyc.rng_epoch0 = ep
            rng_marks += ((k, ep - cyc.rng_epoch0),)
        entry = ("op", key, wiring, diff_mask, num_outputs)
        if rng_marks:
            entry += (rng_marks,)
        cyc.entries.append(entry)
        cyc.ops.append(_OpRec(
            name, key, fn, wiring, diff_mask, num_outputs, out_avals,
            tuple(t.stop_gradient for t in outs), tuple(inputs),
            tuple(outs)))
        i = len(cyc.ops) - 1
        for j, t in enumerate(outs):
            cyc.produced[id(t)] = (i, j)

    def interrupt(self):
        """Debug mode (NaN scan / benchmark sync) needs per-op results:
        resolve any pending replay and poison the cycle."""
        st = self._tls
        if st.busy:
            return
        if st.pending is not None and not st.pending.fired:
            with st.pending.lock:
                if not st.pending.done:
                    self._split(st.pending, escape=False,
                                reason="debug_interrupt")
            st.pending = None
        self._poison(st, "debug_interrupt")

    # -- backward / optimizer hooks ----------------------------------------
    def on_backward(self, tensor, grad_tensor, retain_graph):
        """Called at the top of Tensor.backward. Returns True when the
        backward was consumed by a pending whole-step replay (the caller
        must return immediately)."""
        st = self._tls
        if st.busy or not self.enabled():
            return False
        st.replay_arm = False
        pending = st.pending
        if pending is not None and not pending.fired:
            program = pending.program
            with pending.lock:
                if pending.done:
                    st.pending = None
                    return False
                entry = program.entries[pending.entry_pos]
                clean = entry[0] == "bwd" and grad_tensor is None \
                    and not retain_graph \
                    and not _autograd._saved_tensor_hooks \
                    and self._is_root(pending, tensor)
                if program.super:
                    # super-cycle: this backward closes ONE micro-batch
                    # round — fire the reusable sub-executable (grads
                    # accumulate on device) and keep matching: the next
                    # event is either another round or the boundary
                    round_chain = self._round_template(program, pending)[0]
                    if clean and pending.op_pos == len(round_chain.ops):
                        if pending.rounds:
                            clean = all(
                                p.grad is ph and not p._hooks
                                for p, ph in zip(pending.params,
                                                 pending.grad_phs))
                        else:
                            clean = all(p.grad is None and not p._hooks
                                        for p in pending.params)
                    else:
                        clean = False
                    if clean:
                        if not pending.rounds:
                            self._install_grad_placeholders(pending)
                        pending.backward_done = True
                        if self._fire_sub(st, pending):
                            return True
                        # the sub fire split transactionally: the caller
                        # runs the real backward on the replayed graph
                        return False
                    if entry[0] != "bwd" \
                            or not self._is_root(pending, tensor):
                        reason = "event_mismatch"
                    else:
                        reason = "hook_present"
                    self._split(pending, escape=False, reason=reason,
                                blocked_op="backward")
                    return False
                if clean and all(p.grad is None and not p._hooks
                                 for p in pending.params):
                    pending.entry_pos += 1
                    pending.backward_done = True
                    self._install_grad_placeholders(pending)
                    return True
                if entry[0] != "bwd" or not self._is_root(pending, tensor):
                    reason = "event_mismatch"
                else:
                    # retain_graph / explicit grad seed / saved-tensor or
                    # param hooks / stale grads: semantics a fused replay
                    # cannot honor
                    reason = "hook_present"
                self._split(pending, escape=False, reason=reason,
                            blocked_op="backward")
            return False
        # observation
        cyc = st.recording
        if cyc is None:
            cyc = st.recording = _Cycle()
        if cyc.dirty:
            return False
        cyc.n_backward += 1
        coord = cyc.produced.get(id(tensor))
        if coord is None or grad_tensor is not None or retain_graph \
                or _autograd._saved_tensor_hooks:
            if coord is None:
                reason = "event_mismatch"   # root not in the recorded cycle
            else:
                reason = "hook_present"
            self._poison(st, reason, op="backward")
            return False
        # multiple backwards per cycle are NO LONGER a poison: the
        # boundary tries to canonicalize k×(fwd+bwd)+step into a
        # super-cycle signature (grad accumulation) — unrecognizable
        # multi-backward shapes attribute `unpromotable_cycle` there
        cyc.entries.append(("bwd", coord))
        _EVENTS.emit("step.record", "backward",
                     detail={"kind": "bwd", "pos": len(cyc.ops)})
        return False

    def on_clear_grad(self, opt):
        """Called at the top of Optimizer.clear_grad; the caller always
        proceeds to clear the grads."""
        st = self._tls
        if st.busy or not self.enabled():
            return
        arm = st.replay_arm
        st.replay_arm = False
        pending = st.pending
        if pending is not None and not pending.fired:
            program = pending.program
            with pending.lock:
                if pending.done:
                    st.pending = None
                else:
                    entry = program.entries[pending.entry_pos]
                    if entry[0] == "cg" and opt is program.opt_ref():
                        pending.entry_pos += 1
                    else:
                        self._split(pending, escape=False,
                                    reason="event_mismatch",
                                    blocked_op="clear_grad")
            return
        if arm and st.active is not None:
            program = st.active
            if program.entries and program.entries[0][0] == "cg" \
                    and opt is program.opt_ref():
                pending = self._start_pending(st, program)
                if pending is not None:
                    pending.entry_pos = 1
                    return
        cyc = st.recording
        if cyc is None:
            cyc = st.recording = _Cycle()
        if not cyc.dirty:
            cyc.entries.append(("cg", id(opt)))

    def on_optimizer_step(self, opt):
        """Called at the top of Optimizer.step. Returns True when the
        fused executable performed the whole update (the caller must
        return immediately); always delimits the observation cycle."""
        st = self._tls
        if st.busy or not self.enabled():
            return False
        st.replay_arm = False
        pending = st.pending
        if pending is not None and not pending.fired:
            program = pending.program
            with pending.lock:
                if pending.done:
                    st.pending = None
                else:
                    entry = program.entries[pending.entry_pos]
                    split_reason = "event_mismatch"
                    if program.super:
                        # boundary of a matched accumulation loop: every
                        # round archived (entry_pos back at the segment
                        # start), and a scaler-folded program must arrive
                        # through on_scaler_step instead
                        terminal = program.scaler_ref is None \
                            and bool(pending.rounds) \
                            and pending.op_pos == 0 \
                            and pending.entry_pos == program.seg_start
                    else:
                        terminal = entry[0] == "step" \
                            and pending.entry_pos \
                            == len(program.entries) - 1 \
                            and pending.backward_done \
                            and pending.op_pos == len(program.chain.ops)
                    if terminal:
                        verify_fail = self._verify_fire(program, pending,
                                                        opt)
                        if verify_fail is None:
                            if program.spmd_plan is not None \
                                    and not program.spmd_ok:
                                # SPMD probation: this step commits EAGER
                                # results (the caller proceeds); the fused
                                # lowering is validated on the side
                                if program.super:
                                    self._probation_super(st, pending, opt)
                                else:
                                    self._probation(st, pending, opt)
                                st.pending = None
                                self._after_boundary(st)
                                return False
                            fired = self._fire_super(st, pending, opt) \
                                if program.super \
                                else self._fire(st, pending, opt)
                            if fired:
                                self._after_boundary(st)
                                return True
                            split_reason = None   # _fire already split
                        else:
                            split_reason = verify_fail
                    if not pending.done and split_reason is not None:
                        self._split(pending, escape=False,
                                    reason=split_reason,
                                    blocked_op="optimizer_step")
                    elif not pending.done:
                        self._split(pending, escape=False,
                                    reason="exec_fault",
                                    blocked_op="optimizer_step")
            st.pending = None
            self._boundary(st, opt, dirty=True)
            return False
        self._boundary(st, opt, dirty=False)
        return False

    def on_scaler_step(self, scaler, opt):
        """Called at the top of GradScaler.step (an ENABLED scaler), before
        its eager unscale/step path. Returns True when a pending
        whole-step replay matched through the scaler event and the ONE
        fused executable performed unscale + finite-check + the
        where()-rescued update + the loss-scale transition (the caller
        must skip its eager path and let update() commit the transition).
        During observation it records the scaler into the cycle — only
        under the guardian (FLAGS_check_numerics), whose in-graph
        skip-step semantics make the fold legal — and returns False."""
        from . import guardian
        st = self._tls
        if st.busy or not self.enabled():
            return False
        st.replay_arm = False
        pending = st.pending
        if pending is not None and not pending.fired:
            program = pending.program
            fired = False
            with pending.lock:
                if pending.done:
                    st.pending = None
                    return False
                if program.super:
                    if program.scaler_ref is None:
                        # recorded without this scaler: eager path runs,
                        # its grad reads split the replay
                        return False
                    split_reason = "event_mismatch"
                    if program.scaler_ref() is not scaler \
                            or scaler._consts() != program.scaler_consts:
                        self._kill(program)
                        split_reason = "optimizer_state_change"
                    elif pending.rounds and pending.op_pos == 0 \
                            and pending.entry_pos == program.seg_start:
                        verify_fail = self._verify_fire(program, pending,
                                                        opt)
                        if verify_fail is None:
                            if program.spmd_plan is not None \
                                    and not program.spmd_ok:
                                self._probation_super(st, pending, opt,
                                                      scaler=scaler)
                                st.pending = None
                                self._after_boundary(st)
                                return False
                            if self._fire_super(st, pending, opt,
                                                scaler=scaler):
                                fired = True
                                self._after_boundary(st)
                            else:
                                split_reason = None
                        else:
                            split_reason = verify_fail
                    if not fired and not pending.done \
                            and split_reason is not None:
                        self._split(pending, escape=False,
                                    reason=split_reason,
                                    blocked_op="scaler_step")
                    if fired:
                        return True
                    st.pending = None
                    self._boundary(st, opt, dirty=True)
                    return False
                entry = program.entries[pending.entry_pos]
                if entry[0] != "scaler":
                    # the program was recorded without this scaler (legacy
                    # mode / changed loop): let the eager path run — its
                    # grad reads split the replay
                    return False
                split_reason = "event_mismatch"
                if program.scaler_ref() is not scaler \
                        or scaler._consts() != program.scaler_consts:
                    # the scale hyper-parameters are baked into the traced
                    # loss-scale transition: a change is stale for good
                    self._kill(program)
                    split_reason = "optimizer_state_change"
                elif pending.entry_pos == len(program.entries) - 2 \
                        and pending.backward_done \
                        and pending.op_pos == len(program.chain.ops):
                    pending.entry_pos += 1
                    verify_fail = self._verify_fire(program, pending, opt)
                    if verify_fail is None:
                        if program.spmd_plan is not None \
                                and not program.spmd_ok:
                            # SPMD probation: eager scaler path proceeds
                            self._probation(st, pending, opt,
                                            scaler=scaler)
                            st.pending = None
                            self._after_boundary(st)
                            return False
                        if self._fire(st, pending, opt, scaler=scaler):
                            fired = True
                            self._after_boundary(st)
                        else:
                            split_reason = None   # _fire already split
                    else:
                        split_reason = verify_fail
                if not fired and not pending.done \
                        and split_reason is not None:
                    self._split(pending, escape=False, reason=split_reason,
                                blocked_op="scaler_step")
            if fired:
                return True
            st.pending = None
            self._boundary(st, opt, dirty=True)
            return False
        # observation: the scaler joins the cycle signature so _build folds
        # it into the fused step (guardian mode only — without the in-graph
        # skip the eager scaler syncs found_inf per step and cannot fuse)
        if guardian.skip_step_enabled():
            cyc = st.recording
            if cyc is None:
                cyc = st.recording = _Cycle()
            if not cyc.dirty:
                cyc.entries.append(("scaler", id(scaler), scaler._consts()))
                cyc.scaler = scaler
        return False

    # -- replay internals --------------------------------------------------
    @staticmethod
    def _is_root(pending, tensor):
        i, j = pending.program.root_coord
        try:
            return pending.placeholders[i][j] is tensor
        except IndexError:
            return False

    def _start_pending(self, st, program):
        if program.dead:
            st.active = None
            return None
        params = [r() for r in program.param_refs]
        if any(p is None for p in params):
            program.dead = True
            _EVENTS.emit("step.deactivate", program.label,
                         reason="param_mismatch",
                         detail={"why": "parameter_gc"})
            st.active = None
            return None
        # the chain layer must not be mid-replay under a step replay
        _CHAIN_MANAGER.flush()
        _CHAIN_MANAGER.reset()
        pending = _PendingStep(program, params, self)
        st.pending = pending
        return pending

    @staticmethod
    def _round_template(program, pending):
        """(chain, rng_slots) of the op template the CURRENT round matches
        against — the tail template when a ragged-tail round is in
        flight, else the main segment."""
        if program.super and pending.in_tail \
                and program.tail_chain is not None:
            return program.tail_chain, program.tail_rng_slots
        return program.chain, program.rng_slots

    def _match_round(self, program, pending, key, inputs, diff_mask,
                     num_outputs):
        """Tail-aware round matching: at a round boundary (op_pos 0) of a
        ragged-tail program, a main-template key mismatch retries against
        the TAIL template before splitting — the epoch-boundary batch is
        the recorded second shape, not a replay failure."""
        mismatch = self._op_mismatch_reason(program, pending, key, inputs,
                                            diff_mask, num_outputs)
        if mismatch is not None and program.super \
                and program.tail_chain is not None \
                and pending.op_pos == 0 and not pending.in_tail:
            pending.in_tail = True
            tail_mismatch = self._op_mismatch_reason(
                program, pending, key, inputs, diff_mask, num_outputs)
            if tail_mismatch is None:
                return None
            pending.in_tail = False
        return mismatch

    def _op_mismatch_reason(self, program, pending, key, inputs, diff_mask,
                            num_outputs):
        """None when the incoming dispatch matches the program's next op
        template; else the reason code the split should carry."""
        chain, rng_slots = self._round_template(program, pending)
        op = chain.ops[pending.op_pos]
        if key != op.key:
            return _key_diff_reason(op.key, key)
        if diff_mask != op.diff_mask or num_outputs != op.num_outputs \
                or len(inputs) != len(op.wiring):
            return "key_mismatch"
        slots = chain.ext_of[pending.op_pos]
        for k, (t, w) in enumerate(zip(inputs, op.wiring)):
            if _is_pending(t) and t._pending_chain is pending:
                if w[0] != "prev" or t._chain_coord != (w[1], w[2]):
                    return "wiring_mismatch"
            elif w[0] != "ext":
                return "wiring_mismatch"
            else:
                pk = program.param_slots.get(slots[k])
                if pk is not None and t is not pending.params[pk]:
                    # the slot must be fed by the SAME parameter object the
                    # program was built against — identity is the binding
                    return "param_mismatch"
                delta = rng_slots.get(slots[k]) if rng_slots else None
                if delta is not None:
                    # hoisted RNG slot: the incoming key must sit at the
                    # recorded stream offset from this cycle's first
                    # consumption — a shifted stream (an extra consumer
                    # interleaved, a mid-cycle reseed) cannot replay
                    ep = getattr(t, "_rng_epoch", None)
                    if ep is None:
                        return "rng_rekey"
                    if pending.rng_epoch0 is None:
                        pending.rng_epoch0 = ep - delta
                        pending.rng_base = getattr(t, "_rng_base", None)
                    elif ep - pending.rng_epoch0 != delta \
                            or getattr(t, "_rng_base", None) \
                            is not pending.rng_base:
                        # a shifted position OR a different base key (a
                        # reseed between this round's consumptions): the
                        # recorded derivation would sample wrong
                        return "rng_rekey"
        return None

    def _defer(self, st, pending, inputs, num_outputs):
        program = pending.program
        chain, rng_slots = self._round_template(program, pending)
        op = chain.ops[pending.op_pos]
        slots = chain.ext_of[pending.op_pos]
        for k, t in enumerate(inputs):
            if op.wiring[k][0] != "ext":
                continue
            if rng_slots and slots[k] in rng_slots:
                # hoisted RNG slot: keep the LAZY key tensor — the fused
                # fire derives the key in-graph (nothing launches), and a
                # transactional split forces it then (bitwise the same
                # key, so the eager fallback samples identically)
                pending.ext_vals.append(t)
                pending.ext_edges.append(None)
                continue
            pending.ext_vals.append(t._value)
            if op.diff_mask is not None and op.diff_mask[k]:
                node = t._grad_node if t._grad_node is not None \
                    else t._ensure_grad_node()
                pending.ext_edges.append((node, t._out_index))
            else:
                pending.ext_edges.append(None)
        outs = tuple(
            _DeferredTensor(av, op.out_stop_grads[j], pending,
                            (pending.op_pos, j))
            for j, av in enumerate(op.out_avals))
        pending.placeholders.append(outs)
        pending.op_pos += 1
        pending.entry_pos += 1
        if num_outputs is not None:
            return list(outs)
        return outs[0]

    @staticmethod
    def _force_rng_ext(program, ext_vals):
        """A transactional fallback is about to replay per-op: materialize
        the lazy hoisted-key ext slots. Each derives its reserved stream
        position's exact key (fold_in(base, position)), so the eager
        fallback samples bit-identically to what the fused program would
        have computed in-graph."""
        for s in (program.rng_slots or ()):
            if s >= len(ext_vals):
                continue    # prefix split: the slot was never deferred
            t = ext_vals[s]
            if isinstance(t, Tensor):
                ext_vals[s] = t._value

    @staticmethod
    def _rng_base_data(base):
        """Raw key data of the base the cycle's keys were RESERVED
        against. Never read the live generator here: a reseed between
        dispatch and fire would make the fused derivation diverge from
        what eager (and the transactional split) samples."""
        from ..framework import random as _random
        if base is None:
            return _random.stream_base_data()
        return jax.random.key_data(base)

    def _rng_fire_args(self, pending):
        """The hoisted RNG device args of a fused fire: (base key data,
        this cycle's first stream position)."""
        return (self._rng_base_data(pending.rng_base),
                jnp.asarray(pending.rng_epoch0 or 0, jnp.int32))

    def _install_grad_placeholders(self, pending):
        program = pending.program
        phs = []
        for k, p in enumerate(pending.params):
            v = p._value
            ph = _DeferredTensor((v.shape, v.dtype, False), True, pending,
                                 ("grad", k))
            ph.name = (p.name + "@GRAD") if p.name else "grad"
            p.grad = ph
            phs.append(ph)
        pending.grad_phs = phs

    def _verify_fire(self, program, pending, opt):
        """None when the fused fire may proceed; else the reason code the
        split should carry (optimizer-state changes also kill the
        program: the baked constants are stale for good)."""
        from ..jit.train_step import bake_decay_flags
        if opt is not program.opt_ref():
            return "param_mismatch"
        params = pending.params
        ext_lists = [r[0] for r in pending.rounds] if program.super \
            else [pending.ext_vals]
        if program.spmd_plan is not None:
            from . import spmd_fusion as _spmd
            for evals in ext_lists:
                mm = _spmd.fire_mismatch(program.spmd_plan, evals, params)
                if mm is not None:
                    # the batch moved to another mesh/layout (or a
                    # parameter got sharded): the compiled collectives
                    # would run over the wrong axes — kill and let the
                    # loop re-promote with a fresh plan
                    self._kill(program, reason="mesh_mismatch")
                    return "mesh_mismatch"
        slot_items = program.param_slots.items()
        for evals in ext_lists:
            if any(evals[s] is not params[k]._value for s, k in slot_items):
                # a parameter buffer was swapped mid-cycle (in-place
                # mutation): the forward consumed the captured value, the
                # update would use the new one — not fusable
                return "param_mismatch"
        for p, nm, nc, pr in zip(params, program.param_names,
                                 program.need_clip, program.param_regs):
            if p._hooks:
                return "hook_present"
            if p.stop_gradient or p.name != nm:
                return "param_mismatch"
            if getattr(p, "need_clip", True) != nc:
                return "optimizer_state_change"
            if getattr(p, "regularizer", None) is not pr:
                return "optimizer_state_change"
            node = p._grad_node
            if node is not None and node.out_hooks:
                return "hook_present"
        own = {id(p) for p in params}
        for p in opt._parameter_list:
            if id(p) not in own and p.grad is not None:
                # an outside gradient would be updated by the eager step
                # but not by the fused one
                return "param_mismatch"
        if opt._grad_clip is not program.clip_ref \
                or _snapshot_obj(opt._grad_clip) != program.clip_snapshot:
            self._kill(program)
            return "optimizer_state_change"
        if opt.regularization is not program.reg_ref \
                or _snapshot_obj(opt.regularization) != program.reg_snapshot:
            self._kill(program)
            return "optimizer_state_change"
        bake_decay_flags(opt, params)
        if tuple(opt._extra_cache_key()) != program.extra_key:
            self._kill(program)
            return "optimizer_state_change"
        opt._create_accumulators(params)
        if tuple(sorted(opt._accumulators.keys())) != program.acc_names:
            self._kill(program)
            return "optimizer_state_change"
        return None

    def _kill(self, program, reason="optimizer_state_change"):
        """A baked-in constant (clip/regularizer attrs, optimizer hyper
        params, accumulator structure) changed: the compiled executable is
        stale for good. Drop it so a re-stabilized loop rebuilds."""
        st = self._tls
        if not program.dead:
            program.dead = True
            program.release_heavy()
            STEP_STATS.deactivated += 1
            _EVENTS.emit("step.deactivate", program.label, reason=reason)
        if st.active is program:
            st.active = None
        st.library.pop(program.sig, None)

    def _fire(self, st, pending, opt, scaler=None):
        """All entries matched and the optimizer is verified: run the ONE
        fused executable and commit. Returns False (after splitting) on a
        fault so the caller falls back to the eager step. `scaler` is the
        verified GradScaler of a scaler-folded program (on_scaler_step):
        its state rides as hoisted scalar args and the computed transition
        lands in `scaler._fused_next` for update() to commit."""
        from ..jit.train_step import bake_decay_flags
        from . import guardian as _guardian
        program = pending.program
        params = pending.params
        acc_names = program.acc_names
        check = program.check
        upd_finite = fwd_finite = scale_before = scale_after = None
        if _guardian.faults_armed() and _guardian.poll_fault(
                "fused_step", ("raise", "nan_output")) is not None:
            # fused-tier chaos: ANY untrusted fused-step output means the
            # whole transaction is suspect — recover through the
            # transactional per-op split (bitwise-identical params/grads),
            # exactly the path a real mid-fire fault takes
            self._split(pending, escape=False, reason="injected_fault",
                        blocked_op="chaos")
            return False
        st.busy = True
        if not hasattr(opt, "_step_count"):
            opt._step_count = 0
        opt._step_count += 1
        try:
            bake_decay_flags(opt, params)
            pvals = [p._value for p in params]
            ext = [pending.ext_vals[s] for s in program.ext_order]
            accs = [[opt._accumulators[n].get(p.name) for n in acc_names]
                    for p in params]
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_count = jnp.asarray(opt._step_count, jnp.int32)
            rng_tail = self._rng_fire_args(pending) \
                if program.rng_slots else ()
            if scaler is not None:
                scale_before, good, bad = scaler._state_arrays()
                fire_args = (pvals, ext, accs, lr, step_count, *rng_tail,
                             scale_before, good, bad)
                (root_val, grads, new_p, new_accs, upd_finite, fwd_finite,
                 found_inf, scale_after, good2, bad2) = \
                    program.exe()(*fire_args)
            elif check:
                fire_args = (pvals, ext, accs, lr, step_count, *rng_tail)
                (root_val, grads, new_p, new_accs, upd_finite,
                 fwd_finite) = program.exe()(*fire_args)
            else:
                fire_args = (pvals, ext, accs, lr, step_count, *rng_tail)
                root_val, grads, new_p, new_accs = program.exe()(
                    *fire_args)
        except jax.errors.JaxRuntimeError:
            # transient execution fault: keep the program and replay
            # eagerly — UNLESS the launch already consumed the donated
            # accumulator (or param) buffers, in which case a transparent
            # fallback is impossible and the fault must surface (the
            # eager optimizer's own donating update has the same contract)
            opt._step_count -= 1
            consumed = any(
                getattr(a, "is_deleted", lambda: False)()
                for row in accs for a in row if a is not None)
            if program.donate_params and not consumed:
                consumed = any(
                    getattr(v, "is_deleted", lambda: False)()
                    for v in pvals)
            if consumed:
                st.busy = False
                st.pending = None   # placeholders resolve via escape-split
                self._kill(program, reason="exec_fault")
                raise
            st.busy = False
            self._split(pending, escape=False, reason="exec_fault")
            return False
        except Exception:
            # the fused trace failed: never let fusion take eager down
            opt._step_count -= 1
            st.busy = False
            self._kill(program, reason="trace_fail")
            self._split(pending, escape=False, reason="trace_fail")
            return False
        try:
            for p, v in zip(params, new_p):
                p._value = v
            for p, ac in zip(params, new_accs):
                for n, v in zip(acc_names, ac):
                    if v is not None:
                        opt._accumulators[n][p.name] = v
            # the loss: served from the fused outputs, tape-marked consumed
            i, j = program.root_coord
            root_ph = pending.placeholders[i][j]
            if _VALUE_SLOT.__get__(root_ph) is _PENDING:
                _VALUE_SLOT.__set__(root_ph, root_val)
            node = FusedStepNode(program.label,
                                 (root_val.shape, root_val.dtype))
            _NODE_SLOT.__set__(root_ph, node)
            _IDX_SLOT.__set__(root_ph, 0)
            root_ph._pending_chain = None
            # raw grads land in the placeholders installed at backward
            # (scaler programs emit them UNSCALED, like the eager path)
            for ph, g in zip(pending.grad_phs, grads):
                if _VALUE_SLOT.__get__(ph) is _PENDING:
                    _VALUE_SLOT.__set__(ph, g)
                ph._pending_chain = None
            if scaler is not None:
                # update() commits this instead of re-running the
                # transition (the backoff, if any, is attributed by the
                # note_step flush below — never twice)
                scaler._found_inf = found_inf
                scaler._fused_next = (found_inf, scale_after, good2, bad2)
            if check:
                from . import guardian
                guardian.note_step(program.label, upd_finite, fwd_finite,
                                   scale_before, scale_after,
                                   step_index=opt._step_count)
            pending.fired = True
            program.fail_streak = 0
            if not program.aot_stored:
                from . import aot_cache as _aot
                if _aot.enabled():
                    # persist the ONE fused step right after it proved
                    # itself (store-if-absent; restored programs and
                    # donated-buffer shapes are both handled there)
                    program.aot_stored = True
                    _aot.store_step(program, fire_args)
            elapsed = time.perf_counter_ns() - pending.t0
            STEP_STATS.replay(program.label, program.n_launches,
                              program.baseline_ns - elapsed)
            # telemetry plane (profiler/goodput.py): per-mesh SPMD step
            # labeling + cycle-derived analytic FLOPs/step; one flag
            # check when FLAGS_metrics is off
            from ..profiler import goodput as _goodput
            _goodput.on_fused_fire(program)
            _EVENTS.emit("step.fire", program.label,
                         detail={"ops": len(program.chain.ops),
                                 "launches_saved": program.n_launches - 1})
            self._demote(pending)
        finally:
            st.busy = False
            st.pending = None
        return True

    # -- super-cycle replay internals (grad accumulation) ------------------
    @classmethod
    def _sub_fire_args(cls, program, ext_vals, rng_epoch0, acc, fwd_ok):
        """Concrete arguments of one sub-executable fire: params and side
        inputs from the round's captured ext values, the running grad
        accumulator (program zeros on round 0), and the scalar tail
        (hoisted RNG state — the base the round's keys were reserved
        against, read off the still-lazy key tensors — plus the running
        fwd-finite predicate)."""
        pvals = [None] * len(program.param_refs)
        for s, k in program.param_slots.items():
            pvals[k] = ext_vals[s]
        ext = [ext_vals[s] for s in program.ext_order]
        if acc is None:
            zeros, true = program.zero_state()
            acc = list(zeros)
            fwd_ok = true
        tail = ()
        if program.rng_slots:
            base = None
            for s in program.rng_slots:
                if s < len(ext_vals):
                    base = getattr(ext_vals[s], "_rng_base", None)
                    if base is not None:
                        break
            tail += (cls._rng_base_data(base),
                     jnp.asarray(rng_epoch0 or 0, jnp.int32))
        if program.check:
            tail += (fwd_ok,)
        return (pvals, ext, acc) + tail

    @staticmethod
    def _archive_round(pending):
        """The current micro-batch round matched completely: archive its
        captured state and reset the per-round cursors so the next event
        may open another round or hit the boundary."""
        pending.rounds.append([pending.ext_vals, pending.ext_edges,
                               pending.placeholders, pending.rng_epoch0,
                               pending.in_tail])
        if pending.in_tail:
            pending.tail_done = True
        pending.in_tail = False
        pending.ext_vals = []
        pending.ext_edges = []
        pending.placeholders = []
        pending.rng_epoch0 = None
        pending.rng_base = None
        pending.op_pos = 0
        pending.entry_pos = pending.program.seg_start

    def _fire_sub(self, st, pending):
        """Fire the micro-batch sub-executable for the just-completed
        round (gradients add into the running device accumulator) and
        archive the round. Under SPMD probation nothing fused may commit
        — the fires are deferred to the boundary — but the round archives
        either way. Returns False after a transactional split (the caller
        must run the real backward)."""
        from . import guardian as _guardian
        program = pending.program
        if _guardian.faults_armed() and _guardian.poll_fault(
                "fused_step", ("raise", "nan_output")) is not None:
            self._split(pending, escape=False, reason="injected_fault",
                        blocked_op="chaos")
            return False
        probation = program.spmd_plan is not None and not program.spmd_ok
        if not probation:
            st.busy = True
            try:
                args = self._sub_fire_args(program, pending.ext_vals,
                                           pending.rng_epoch0,
                                           pending.acc_vals,
                                           pending.fwd_ok)
                exe = program.tail_sub_exe() if pending.in_tail \
                    else program.sub_exe()
                out = exe(*args)
            except jax.errors.JaxRuntimeError:
                self._split(pending, escape=False, reason="exec_fault",
                            blocked_op="backward")
                return False
            except Exception:
                self._kill(program, reason="trace_fail")
                self._split(pending, escape=False, reason="trace_fail",
                            blocked_op="backward")
                return False
            finally:
                st.busy = False
            pending.round_losses.append(out[0])
            pending.acc_vals = list(out[1])
            if program.check:
                pending.fwd_ok = out[2]
            if not pending.in_tail:
                # AOT export specs must describe the MAIN sub's arg
                # shapes; a tail round's smaller batch would corrupt them
                pending.sub_args = args
        self._archive_round(pending)
        return True

    def _fire_super(self, st, pending, opt, scaler=None):
        """The boundary of a matched super-cycle: every round's sub fire
        already accumulated the gradient sum; run the ONE update
        executable (clip/reg + optimizer + guardian skip + scaler
        transition, all on the ACCUMULATED grads) and commit — params and
        slots in place, each round's loss placeholder from its sub
        output, p.grad from the accumulated grads. Same transactional
        contract as _fire."""
        from ..jit.train_step import bake_decay_flags
        from . import guardian as _guardian
        program = pending.program
        params = pending.params
        acc_names = program.acc_names
        check = program.check
        upd_finite = fwd_finite = scale_before = scale_after = None
        if _guardian.faults_armed() and _guardian.poll_fault(
                "fused_step", ("raise", "nan_output")) is not None:
            self._split(pending, escape=False, reason="injected_fault",
                        blocked_op="chaos")
            return False
        st.busy = True
        if not hasattr(opt, "_step_count"):
            opt._step_count = 0
        opt._step_count += 1
        try:
            bake_decay_flags(opt, params)
            pvals = [p._value for p in params]
            accs = [[opt._accumulators[n].get(p.name) for n in acc_names]
                    for p in params]
            gsum = pending.acc_vals
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_count = jnp.asarray(opt._step_count, jnp.int32)
            tail = ()
            if check:
                tail += (pending.fwd_ok,)
            if scaler is not None:
                scale_before, good, bad = scaler._state_arrays()
                tail += (scale_before, good, bad)
                (grads, new_p, new_accs, upd_finite, fwd_finite,
                 found_inf, scale_after, good2, bad2) = program.upd_exe()(
                    pvals, accs, gsum, lr, step_count, *tail)
            elif check:
                (grads, new_p, new_accs, upd_finite,
                 fwd_finite) = program.upd_exe()(pvals, accs, gsum, lr,
                                                 step_count, *tail)
            else:
                grads, new_p, new_accs = program.upd_exe()(
                    pvals, accs, gsum, lr, step_count)
        except jax.errors.JaxRuntimeError:
            opt._step_count -= 1
            consumed = any(
                getattr(a, "is_deleted", lambda: False)()
                for row in accs for a in row if a is not None)
            if program.donate_params and not consumed:
                consumed = any(
                    getattr(v, "is_deleted", lambda: False)()
                    for v in pvals)
            if consumed:
                st.busy = False
                st.pending = None
                self._kill(program, reason="exec_fault")
                raise
            st.busy = False
            self._split(pending, escape=False, reason="exec_fault")
            return False
        except Exception:
            opt._step_count -= 1
            st.busy = False
            self._kill(program, reason="trace_fail")
            self._split(pending, escape=False, reason="trace_fail")
            return False
        try:
            for p, v in zip(params, new_p):
                p._value = v
            for p, ac in zip(params, new_accs):
                for n, v in zip(acc_names, ac):
                    if v is not None:
                        opt._accumulators[n][p.name] = v
            # each round's loss: served from its sub-executable output,
            # tape-marked consumed (one FusedStepNode per micro-batch)
            i, j = program.root_coord
            for r, (evals, eedges, rows, ep0, _tail) in \
                    enumerate(pending.rounds):
                root_ph = rows[i][j]
                rv = pending.round_losses[r]
                if _VALUE_SLOT.__get__(root_ph) is _PENDING:
                    _VALUE_SLOT.__set__(root_ph, rv)
                node = FusedStepNode(program.label, (rv.shape, rv.dtype))
                _NODE_SLOT.__set__(root_ph, node)
                _IDX_SLOT.__set__(root_ph, 0)
                root_ph._pending_chain = None
            # accumulated grads land in the placeholders installed at the
            # first round's backward (scaler programs emit them UNSCALED,
            # exactly what the eager path leaves in p.grad)
            for ph, g in zip(pending.grad_phs, grads):
                if _VALUE_SLOT.__get__(ph) is _PENDING:
                    _VALUE_SLOT.__set__(ph, g)
                ph._pending_chain = None
            if scaler is not None:
                scaler._found_inf = found_inf
                scaler._fused_next = (found_inf, scale_after, good2, bad2)
            if check:
                from . import guardian
                guardian.note_step(program.label, upd_finite, fwd_finite,
                                   scale_before, scale_after,
                                   step_index=opt._step_count)
            pending.fired = True
            program.fail_streak = 0
            if not program.aot_stored and pending.sub_args is not None:
                from . import aot_cache as _aot
                if _aot.enabled():
                    # persist the proven PAIR once (store-if-absent; a
                    # restored pair never re-exports)
                    program.aot_stored = True
                    _aot.store_super_step(
                        program, pending.sub_args,
                        (pvals, accs, gsum, lr, step_count) + tail)
            elapsed = time.perf_counter_ns() - pending.t0
            STEP_STATS.replay(program.label, program.n_launches,
                              program.baseline_ns - elapsed)
            from ..profiler import goodput as _goodput
            _goodput.on_fused_fire(program, rounds=len(pending.rounds))
            _EVENTS.emit("step.fire", program.label,
                         detail={"ops": len(program.chain.ops),
                                 "rounds": len(pending.rounds),
                                 "launches_saved": program.n_launches
                                 - len(pending.rounds) - 1})
            self._demote(pending)
        finally:
            st.busy = False
            st.pending = None
        return True

    def _probation_super(self, st, pending, opt, scaler=None):
        """First fire of an SPMD-lowered super-cycle: run every archived
        round's sub fire plus the update on SCRATCH state, replay the
        whole accumulation eagerly (bitwise, through the transactional
        core), and compare per-round losses + accumulated grads. A
        divergence or trace failure demotes to the plain jit lowering,
        attributed `spmd_divergence`. The caller lets the eager
        optimizer/scaler step proceed."""
        import numpy as np
        from ..jit.train_step import bake_decay_flags
        from ..profiler import goodput as _goodput
        from . import spmd_fusion as _spmd
        _goodput.mark("probation")

        def scratch(v):
            return v + jnp.zeros((), v.dtype)

        program = pending.program
        params = pending.params
        acc_names = program.acc_names
        fused = None
        losses = []
        st.busy = True
        try:
            bake_decay_flags(opt, params)
            zeros, fwd_ok = program.zero_state()
            acc = [scratch(z) for z in zeros]
            for evals, eedges, rows, ep0, is_tail in pending.rounds:
                args = self._sub_fire_args(program, evals, ep0, acc,
                                           fwd_ok)
                exe = program.tail_sub_exe() if is_tail \
                    else program.sub_exe()
                out = exe(*args)
                losses.append(out[0])
                acc = list(out[1])
                if program.check:
                    fwd_ok = out[2]
            pvals = [p._value for p in params]
            if program.donate_params:
                pvals = [scratch(v) for v in pvals]
            accs = [[None if opt._accumulators[n].get(p.name) is None
                     else scratch(opt._accumulators[n][p.name])
                     for n in acc_names] for p in params]
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_count = jnp.asarray(
                getattr(opt, "_step_count", 0) + 1, jnp.int32)
            tail = ()
            if program.check:
                tail += (fwd_ok,)
            if scaler is not None:
                scale, good, bad = scaler._state_arrays()
                tail += (scratch(scale), scratch(good), scratch(bad))
            fused = program.upd_exe()(pvals, accs, acc, lr, step_count,
                                      *tail)
        except Exception:
            fused = None
        finally:
            st.busy = False
        self._replay_pending(pending)
        ok = fused is not None
        why = "trace_fail" if fused is None else None
        if ok:
            i, j = program.root_coord
            for r, (evals, eedges, rows, ep0, _tail) in \
                    enumerate(pending.rounds):
                ev = np.asarray(_VALUE_SLOT.__get__(rows[i][j]))
                rt, at = _spmd.probation_tolerance(ev.dtype)
                if not np.allclose(np.asarray(losses[r]), ev, rtol=rt,
                                   atol=at, equal_nan=True):
                    ok = False
                    break
            scale_np = None
            if ok and scaler is not None:
                scale_np = np.asarray(scaler._state_arrays()[0])
            if ok:
                for ph, g in zip(pending.grad_phs, fused[0]):
                    ev = _VALUE_SLOT.__get__(ph)
                    if ev is _PENDING:
                        continue
                    ev = np.asarray(ev)
                    gv = np.asarray(g)
                    if scale_np is not None:
                        gv = gv * scale_np.astype(gv.dtype)
                    rt, at = _spmd.probation_tolerance(ev.dtype)
                    if not np.allclose(gv, ev, rtol=rt, atol=at,
                                       equal_nan=True):
                        ok = False
                        break
            if not ok and why is None:
                why = "numeric_divergence"
        if ok:
            program.spmd_ok = True
            _EVENTS.emit("step.record", program.label,
                         detail={"kind": "spmd_probation", "ok": True,
                                 "super": True})
        else:
            program.spmd_plan = None
            program.spmd_ok = True
            program._exe = None
            program._sub_exe = None
            program._upd_exe = None
            program._zero_acc = None
            _EVENTS.emit("step.record", program.label,
                         reason="spmd_divergence",
                         detail={"kind": "spmd_probation", "ok": False,
                                 "why": why, "super": True})

    @staticmethod
    def _demote(pending):
        """Release the fired step's retention (ROADMAP item 4(c)): swap
        the placeholder store to weakrefs, breaking the strong
        pending↔placeholder cycle that used to keep `ext_vals` — the
        PRE-UPDATE parameter buffers and the batch arrays among them —
        alive into the next step (until a gc pass, in the worst case).
        Post-demote the pending survives only through placeholders the
        CALLER still references (each holds `_pending_chain` strongly),
        so in the common loop — where mid-step intermediates are
        temporaries — everything, ext store included, is refcount-freed
        before `optimizer.step()` returns. A caller that kept an
        intermediate keeps exactly the state its post-fire lazy
        recompute needs, no more."""
        pending.placeholders = [[weakref.ref(t) for t in row]
                                for row in pending.placeholders]
        for rnd in pending.rounds:
            rnd[2] = [[weakref.ref(t) for t in row] for row in rnd[2]]
        # grads were committed to p.grad and the loss to its own handle;
        # the pending's strong duplicates would pin those buffers past
        # clear_grad()
        pending.grad_phs = None
        pending.params = ()
        pending.round_losses = []
        pending.acc_vals = None
        pending.fwd_ok = None

    def _probation(self, st, pending, opt, scaler=None):
        """First fire of an SPMD-lowered program (ops/spmd_fusion.py): run
        the shard_map executable on scratch copies of the donated buffers,
        then replay the step EAGERLY through the transactional core — this
        step's numerics stay bitwise-identical to unfused dispatch — and
        compare loss + grads. A match validates the distributed lowering
        (the next fire commits fused results); a divergence (a sum-reduced
        loss, a batch-coupled op — anything outside the data-parallel
        pmean contract) demotes the program to the plain jit lowering,
        attributed as `spmd_divergence`. Callers hold pending.lock; the
        caller must let the eager optimizer step proceed."""
        import numpy as np
        from ..jit.train_step import bake_decay_flags
        from ..profiler import goodput as _goodput
        from . import spmd_fusion as _spmd
        # goodput: this interval is a probation replay (fused + bitwise
        # eager both run), not a normal productive step
        _goodput.mark("probation")

        def scratch(v):
            # a DISTINCT buffer with the same value and placement, so the
            # executable's donation can never consume live state
            return v + jnp.zeros((), v.dtype)

        program = pending.program
        params = pending.params
        acc_names = program.acc_names
        fused = None
        st.busy = True
        try:
            bake_decay_flags(opt, params)
            pvals = [p._value for p in params]
            if program.donate_params:
                pvals = [scratch(v) for v in pvals]
            ext = [pending.ext_vals[s] for s in program.ext_order]
            accs = [[None if opt._accumulators[n].get(p.name) is None
                     else scratch(opt._accumulators[n][p.name])
                     for n in acc_names] for p in params]
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_count = jnp.asarray(
                getattr(opt, "_step_count", 0) + 1, jnp.int32)
            rng_tail = self._rng_fire_args(pending) \
                if program.rng_slots else ()
            if scaler is not None:
                scale, good, bad = scaler._state_arrays()
                fused = program.exe()(pvals, ext, accs, lr, step_count,
                                      *rng_tail, scratch(scale),
                                      scratch(good), scratch(bad))
            else:
                fused = program.exe()(pvals, ext, accs, lr, step_count,
                                      *rng_tail)
        except Exception:
            # the distributed lowering failed to trace/execute (a baked
            # global shape, an op the manual mapping rejects): demote to
            # the plain jit lowering — still ONE executable — and replay
            # this step eagerly
            fused = None
        finally:
            st.busy = False
        self._replay_pending(pending)
        ok = fused is not None
        why = "trace_fail" if fused is None else None
        if ok:
            i, j = program.root_coord
            root_ph = pending.placeholders[i][j]
            eager_loss = np.asarray(_VALUE_SLOT.__get__(root_ph))
            rtol, atol = _spmd.probation_tolerance(eager_loss.dtype)
            ok = bool(np.allclose(np.asarray(fused[0]), eager_loss,
                                  rtol=rtol, atol=atol, equal_nan=True))
            scale_np = None
            if ok and scaler is not None:
                # fused grads are UNSCALED; the eager tape's (pre-
                # scaler.step) grads still carry the loss scale
                scale_np = np.asarray(scaler._state_arrays()[0])
            if ok:
                for ph, g in zip(pending.grad_phs, fused[1]):
                    ev = _VALUE_SLOT.__get__(ph)
                    if ev is _PENDING:
                        continue
                    ev = np.asarray(ev)
                    gv = np.asarray(g)
                    if scale_np is not None:
                        gv = gv * scale_np.astype(gv.dtype)
                    rt, at = _spmd.probation_tolerance(ev.dtype)
                    if not np.allclose(gv, ev, rtol=rt, atol=at,
                                       equal_nan=True):
                        ok = False
                        break
            if not ok and why is None:
                why = "numeric_divergence"
        if ok:
            program.spmd_ok = True
            _EVENTS.emit("step.record", program.label,
                         detail={"kind": "spmd_probation", "ok": True})
        else:
            program.spmd_plan = None
            program.spmd_ok = True
            program._exe = None
            _EVENTS.emit("step.record", program.label,
                         reason="spmd_divergence",
                         detail={"kind": "spmd_probation", "ok": False,
                                 "why": why})

    def resolve_pending(self, pending, escape):
        """Owner-protocol escape hatch (ops/fusion._DeferredTensor._force).
        Pre-fire: any touch of a pending placeholder splits the replay.
        Post-fire: intermediates are lazily recomputed through the per-op
        path (the fused step only materialized the loss and the grads)."""
        st = self._tls
        with pending.lock:
            if pending.done:
                pass
            elif pending.fired:
                self._recompute(pending)
            else:
                self._split(pending, escape=escape)
        if st.pending is pending:
            st.pending = None

    def _recompute(self, pending):
        """A placeholder of a FIRED step was read: materialize every
        intermediate via the per-op cached path from the captured external
        inputs (the pre-update parameter values among them). The store
        was demoted to weakrefs at the fire (`_demote`); the reader that
        triggered this keeps its own chain of placeholders alive, and
        rows that died anyway are replayed through throwaway carriers —
        their values exist only long enough to feed downstream ops."""
        st = self._tls
        st.busy = True
        try:
            program = pending.program

            def revive(store):
                rows = []
                for row in store:
                    live = []
                    for ref in row:
                        t = ref()
                        if t is None:
                            t = _DeferredTensor(None, True, None, None)
                        live.append(t)
                    rows.append(live)
                return rows

            if program.super:
                # a fired super-cycle's intermediates: every round
                # replays from its own captured inputs (tail rounds
                # through the tail op template)
                for evals, eedges, store, _ep, is_tail in pending.rounds:
                    ops = program.tail_chain.ops if is_tail \
                        else program.chain.ops
                    self._force_rng_ext(program, evals)
                    replay_ops_per_op(ops, evals, eedges,
                                      revive(store), len(ops),
                                      skip_materialized=True)
                pending.done = True
                return
            self._force_rng_ext(program, pending.ext_vals)
            replay_ops_per_op(program.chain.ops, pending.ext_vals,
                              pending.ext_edges, revive(pending.placeholders),
                              pending.op_pos, skip_materialized=True)
            pending.done = True
        finally:
            st.busy = False

    def _replay_pending(self, pending):
        """The bitwise transactional core: replay the deferred prefix
        per-op and, if the backward event was already consumed, run the
        real tape backward so p.grad holds exactly what unfused dispatch
        would have produced. Shared by `_split` (failure fallback) and
        `_probation` (the SPMD first-fire validation, which is not a
        failure). Callers hold pending.lock."""
        st = self._tls
        program = pending.program
        if program.super:
            return self._replay_pending_super(pending)
        st.busy = True
        try:
            self._force_rng_ext(program, pending.ext_vals)
            replay_ops_per_op(program.chain.ops, pending.ext_vals,
                              pending.ext_edges, pending.placeholders,
                              pending.op_pos)
            if pending.backward_done:
                for p in pending.params:
                    p.grad = None
                i, j = program.root_coord
                root = pending.placeholders[i][j]
                node = _NODE_SLOT.__get__(root)
                if node is not None:
                    seed = _autograd._one_cotangent(
                        _VALUE_SLOT.__get__(root).shape,
                        _VALUE_SLOT.__get__(root).dtype)
                    run_backward(node, _IDX_SLOT.__get__(root), seed)
                for p, ph in zip(pending.params, pending.grad_phs):
                    real = p.grad
                    if real is not None:
                        if _VALUE_SLOT.__get__(ph) is _PENDING:
                            _VALUE_SLOT.__set__(ph, real._value)
                        ph._pending_chain = None
                        p.grad = ph
                    else:
                        ph._pending_chain = None
            pending.done = True
        finally:
            st.busy = False

    def _replay_pending_super(self, pending):
        """The super-cycle transactional core: replay every archived
        round per-op AND run its real tape backward (p.grad accumulates
        across rounds exactly as unfused dispatch would), then replay the
        current round's deferred prefix. Nothing fused ever committed —
        the sub fires only touched scratch accumulators — so the result
        is bitwise-identical to eager execution. Callers hold
        pending.lock."""
        st = self._tls
        program = pending.program
        st.busy = True
        try:
            params = pending.params
            i, j = program.root_coord
            if pending.rounds:
                # the cycle began with fresh grads (verified at round 0's
                # backward): re-accumulate from scratch
                for p in params:
                    p.grad = None
            for evals, eedges, rows, _ep, is_tail in pending.rounds:
                ops = program.tail_chain.ops if is_tail \
                    else program.chain.ops
                self._force_rng_ext(program, evals)
                replay_ops_per_op(ops, evals, eedges, rows, len(ops))
                root = rows[i][j]
                node = _NODE_SLOT.__get__(root)
                if node is not None:
                    seed = _autograd._one_cotangent(
                        _VALUE_SLOT.__get__(root).shape,
                        _VALUE_SLOT.__get__(root).dtype)
                    run_backward(node, _IDX_SLOT.__get__(root), seed)
            # current round's deferred prefix (its backward — if one is in
            # flight — is run by the caller on the replayed real graph)
            cur_ops = self._round_template(program, pending)[0].ops
            self._force_rng_ext(program, pending.ext_vals)
            replay_ops_per_op(cur_ops, pending.ext_vals,
                              pending.ext_edges, pending.placeholders,
                              pending.op_pos)
            if pending.grad_phs is not None:
                if not pending.rounds:
                    # split before any round committed (a round-0 sub
                    # fault): grads are None exactly as eager would have
                    # them — withdraw the installed placeholders
                    for p, ph in zip(params, pending.grad_phs):
                        if p.grad is ph:
                            p.grad = None
                        ph._pending_chain = None
                    pending.grad_phs = None
                else:
                    for p, ph in zip(params, pending.grad_phs):
                        real = p.grad
                        if real is not None and real is not ph:
                            if _VALUE_SLOT.__get__(ph) is _PENDING:
                                _VALUE_SLOT.__set__(ph, real._value)
                            ph._pending_chain = None
                            p.grad = ph
                        else:
                            ph._pending_chain = None
            pending.done = True
        finally:
            st.busy = False

    def _split(self, pending, escape, reason=None, blocked_op=None):
        """Transactional fallback: the deferred prefix replays per-op; if
        the backward event was already consumed, the real tape backward
        runs so p.grad holds exactly what unfused dispatch would have
        produced. Callers hold pending.lock. `reason` is the
        flight-recorder attribution (a REASON_CODES entry); `blocked_op`
        names the dispatch/event that broke the replay."""
        st = self._tls
        program = pending.program
        if pending.done:
            return
        try:
            self._replay_pending(pending)
            program.fail_streak += 1
            deactivated = False
            if program.fail_streak >= _MAX_FAIL_STREAK \
                    and not program.dead:
                program.dead = True
                deactivated = True
                program.release_heavy()
                STEP_STATS.deactivated += 1
                if st.active is program:
                    st.active = None
            STEP_STATS.split(program.label, escape=escape)
            if reason is None:
                reason = "mid_step_peek" if escape else "key_mismatch"
            detail = {"entry_pos": pending.entry_pos,
                      "op_pos": pending.op_pos,
                      "ops": len(program.chain.ops)}
            if blocked_op:
                detail["blocked_op"] = blocked_op
            if deactivated:
                detail["deactivated"] = True
            _EVENTS.emit("step.split", program.label, reason=reason,
                         detail=detail)
            if deactivated:
                _EVENTS.emit("step.deactivate", program.label,
                             reason="fail_streak")
            self._mark_dirty(st)
        finally:
            if st.pending is pending:
                st.pending = None

    # -- cycle boundary / promotion ----------------------------------------
    def _mark_dirty(self, st):
        if st.recording is None:
            st.recording = _Cycle()
        st.recording.poison()

    def _poison(self, st, reason, op=""):
        """Mark the observation cycle un-promotable AND record why in the
        flight recorder. The (reason, op) pairs emitted here are exactly
        what the fusion doctor aggregates into "step never promoted:
        <op> <reason> ×N" — every poison call emits (not just the first
        of a cycle) so per-cycle multiplicity survives into the report."""
        if st.recording is None:
            st.recording = _Cycle()
        cyc = st.recording
        _EVENTS.emit("step.record", op, reason=reason,
                     detail={"kind": "poison", "pos": len(cyc.ops),
                             "first": not cyc.dirty})
        cyc.poison()

    def _after_boundary(self, st):
        st.recording = _Cycle()
        st.replay_arm = st.active is not None

    def _boundary(self, st, opt, dirty):
        cyc = st.recording
        if cyc is None or dirty or cyc.dirty:
            _EVENTS.emit("step.record", "optimizer_step",
                         detail={"kind": "cycle", "clean": False})
            st.prev_sig, st.streak = None, 0
            self._after_boundary(st)
            return
        updated = [p for p in opt._parameter_list if p.grad is not None]
        cyc.entries.append(("step", id(opt), tuple(id(p) for p in updated)))
        sig = tuple(cyc.entries)
        if cyc.n_backward > 1:
            # grad accumulation: canonicalize k×(fwd+bwd)+step into the
            # k-INDEPENDENT super-cycle signature, so a k=4 warm-up
            # promotes a program that replays at any k without recompiling
            ssig = self._super_sig(sig)
            if ssig is not None:
                sig = ssig
        if sig == st.prev_sig:
            st.streak += 1
        else:
            st.prev_sig, st.streak = sig, 1
        _EVENTS.emit("step.record", "optimizer_step",
                     detail={"kind": "cycle", "clean": True,
                             "ops": len(cyc.ops), "streak": st.streak})
        min_count = int(
            _FLAGS.get("FLAGS_eager_step_fusion_min_count", 40) or 1)
        promote = st.streak >= min_count
        warm = False
        if not promote and sig not in st.library:
            # AOT warm start (ops/aot_cache.py): when the store already
            # holds this cycle's compiled step, the stability threshold is
            # moot — a restarting worker promotes on its FIRST clean cycle
            # and fires the restored executable on the next one
            warm = self._aot_step_digest(st, sig, opt, updated) is not None
            promote = warm
        if promote:
            program = st.library.get(sig)
            if program is None and sig not in st.library:
                program = self._build(st, cyc, sig, opt, updated,
                                      warm=warm)
                st.library[sig] = program if program is not None \
                    else _UNBUILDABLE
                cap = int(_FLAGS.get("FLAGS_eager_step_fusion_cache_size",
                                     8) or 0)
                while len(st.library) > max(cap, 1):
                    st.library.popitem(last=False)
            if isinstance(program, _StepProgram) and not program.dead:
                st.library.move_to_end(sig)
                st.active = program
        self._after_boundary(st)

    @staticmethod
    def _super_sig(entries):
        """Canonical k-independent signature of a grad-accumulation
        super-cycle, or None when the shape is not recognizable.
        Recognized: [cg?] + k×(ops…, bwd) + [scaler?] + step with k ≥ 2,
        all k segments structurally identical after rebasing wiring, bwd
        coords, and hoisted-RNG stream deltas to segment-local form, and
        NO dataflow crossing a segment boundary."""
        step_e = entries[-1]
        body = list(entries[:-1])
        cg = None
        if body and body[0][0] == "cg":
            cg = body.pop(0)
        scaler_e = None
        if body and body[-1][0] == "scaler":
            scaler_e = body.pop()
        if not body or any(e[0] not in ("op", "bwd") for e in body):
            return None
        cuts = [i for i, e in enumerate(body) if e[0] == "bwd"]
        k = len(cuts)
        if k < 2 or cuts[-1] != len(body) - 1:
            return None
        seg_len = cuts[0] + 1
        if len(body) != k * seg_len \
                or any(cuts[s] != (s + 1) * seg_len - 1 for s in range(k)):
            return None
        canon = []
        for s in range(k):
            seg = body[s * seg_len:(s + 1) * seg_len]
            base = s * (seg_len - 1)       # recorded ops per segment
            rebased = []
            rng0 = None
            for e in seg[:-1]:
                wiring = []
                for w in e[2]:
                    if w[0] == "prev":
                        i2 = w[1] - base
                        if i2 < 0:
                            return None    # cross-segment dataflow
                        wiring.append(("prev", i2, w[2]))
                    else:
                        wiring.append(w)
                ent = ("op", e[1], tuple(wiring), e[3], e[4])
                if len(e) > 5:
                    marks = []
                    for ki, d in e[5]:
                        if rng0 is None:
                            rng0 = d   # segment-local stream anchor
                        marks.append((ki, d - rng0))
                    ent += (tuple(marks),)
                rebased.append(ent)
            bcoord = seg[-1][1]
            if bcoord is None:
                return None
            bi = bcoord[0] - base
            if bi < 0 or bi >= seg_len - 1:
                return None
            rebased.append(("bwd", (bi, bcoord[1])))
            canon.append(tuple(rebased))
        if any(c != canon[0] for c in canon[1:]):
            # Ragged tail: k−1 identical full segments + one differing
            # final segment (the epoch-boundary short micro-batch). The
            # tail shape joins the signature — same sig on every epoch,
            # one extra tail sub-executable, still ≤3 programs total.
            if k >= 3 and canon[-1] != canon[0] \
                    and all(c == canon[0] for c in canon[1:-1]):
                return ("super", cg, canon[0], scaler_e, step_e,
                        canon[-1])
            return None
        return ("super", cg, canon[0], scaler_e, step_e)

    def _aot_step_digest(self, st, sig, opt, updated):
        """The warm-start probe: this cycle's AOT step digest when the
        store holds a matching artifact, else None. The digest computation
        (canonicalizing every op key) is memoized per sig; the existence
        check re-runs each boundary — another worker may populate the
        shared store at any time."""
        from . import aot_cache as _aot
        if not _aot.enabled():
            return None
        dg = st.aot_probe.get(sig, 0)
        if dg == 0:
            dg = _aot.step_digest(sig, opt, updated)
            if len(st.aot_probe) > 64:
                st.aot_probe.clear()
            st.aot_probe[sig] = dg
        if dg is not None and _aot.has_step(dg):
            return dg
        return None

    def _build(self, st, cyc, sig, opt, updated, warm=False):
        """Compile-time qualification + program construction from the last
        observed cycle. Returns None when the cycle cannot promote — every
        None is attributed in the flight recorder (`unpromotable_cycle`
        with a `why` detail) so a loop that records clean cycles but never
        promotes still explains itself."""
        from ..jit.train_step import bake_decay_flags

        if sig and sig[0] == "super":
            return self._build_super(st, cyc, sig, opt, updated, warm=warm)

        def unbuildable(why, op=""):
            _EVENTS.emit("step.record", op, reason="unpromotable_cycle",
                         detail={"kind": "build_fail", "why": why})
            return None

        entries = []
        bwd_entries = [e for e in cyc.entries if e[0] == "bwd"]
        if len(bwd_entries) > 1:
            # a multi-backward cycle that _super_sig could NOT
            # canonicalize (irregular segments, cross-micro-batch
            # dataflow): name the real blocker instead of a generic fail
            return unbuildable("irregular_accum", op="backward")
        if len(bwd_entries) != 1 or bwd_entries[0][1] is None \
                or not cyc.ops or not updated:
            return unbuildable("no_backward_or_params")
        if any(p._hooks or p.stop_gradient for p in updated):
            return unbuildable("param_hooks")
        for p in updated:
            node = p._grad_node
            if node is not None and node.out_hooks:
                return unbuildable("param_hooks")
        ops = [
            _ChainOp(r.name, r.key, r.fn, r.wiring, r.diff_mask,
                     r.num_outputs, r.out_avals, r.out_stop_grads)
            for r in cyc.ops]
        chain = Chain(sig, ops, 0)
        if not chain.grad_mode:
            return unbuildable("no_grad_ops")
        # GradScaler folding (on_scaler_step): requires the guardian —
        # the in-graph where() skip is what makes an unconditional fused
        # update legal — and the scaler event must follow the backward
        # (unscale consumes its grads)
        scaler_es = [e for e in cyc.entries if e[0] == "scaler"]
        scaler_obj = cyc.scaler
        if len(scaler_es) > 1:
            return unbuildable("multi_scaler")
        if scaler_es:
            if scaler_obj is None or id(scaler_obj) != scaler_es[0][1]:
                return unbuildable("scaler_gone")
            if not chain.check:
                return unbuildable("scaler_without_guardian")
            order = [e[0] for e in cyc.entries]
            if order.index("scaler") < order.index("bwd"):
                return unbuildable("scaler_before_backward")
        else:
            scaler_obj = None
        # flat index of the backward root in the chain's output catalog
        root_coord = bwd_entries[0][1]
        root_flat = None
        for flat, owner in enumerate(chain.owners):
            if owner == root_coord:
                root_flat = flat
                break
        if root_flat is None:
            return unbuildable("root_not_in_chain")
        # classify external slots: every differentiable ext input must be
        # one of the optimizer's updated params, every updated param must
        # appear (otherwise the eager step and the fused step would update
        # different sets)
        param_idx = {id(p): k for k, p in enumerate(updated)}
        slot_inputs = {}
        for i, rec in enumerate(cyc.ops):
            slots = chain.ext_of[i]
            for k, s in enumerate(slots):
                if s is not None:
                    slot_inputs[s] = rec.ins[k]
        param_slots = {}
        for s in chain.diff_ext_idx:
            k = param_idx.get(id(slot_inputs[s]))
            if k is None:
                # a differentiable external input that is not an updated
                # parameter (e.g. a float mask with stop_gradient=False)
                return unbuildable("nonparam_diff_input")
            param_slots[s] = k
        if {k for k in param_slots.values()} != set(range(len(updated))):
            return unbuildable("param_set_mismatch")
        # hoisted RNG slots: {ext slot -> stream delta} from the recorded
        # per-op marks — these slots are derived in-graph at fire time
        rng_slots = {}
        op_i = 0
        for e in cyc.entries:
            if e[0] != "op":
                continue
            if len(e) > 5:
                for k, delta in e[5]:
                    s = chain.ext_of[op_i][k]
                    if s is None or s in param_slots:
                        return unbuildable("rng_wiring")
                    rng_slots[s] = delta
            op_i += 1
        # events with per-op entries collapsed to ("op",) markers, in order
        # (the trailing ("step", ...) sig entry becomes the terminal event)
        op_iter = 0
        for e in cyc.entries:
            if e[0] == "op":
                entries.append(("op", op_iter))
                op_iter += 1
            elif e[0] != "step":
                entries.append(e)
        entries.append(("step",))
        program = _StepProgram()
        program.sig = sig
        program.chain = chain
        program.entries = tuple(entries)
        program.root_coord = root_coord
        program.root_flat = root_flat
        program.param_refs = tuple(weakref.ref(p) for p in updated)
        program.param_names = tuple(p.name for p in updated)
        program.param_regs = tuple(
            getattr(p, "regularizer", None) for p in updated)
        program.need_clip = tuple(
            getattr(p, "need_clip", True) for p in updated)
        program.param_slots = param_slots
        program.rng_slots = rng_slots
        program.ext_order = tuple(
            s for s in range(chain.n_ext)
            if s not in param_slots and s not in rng_slots)
        program.opt_ref = weakref.ref(opt)
        program.clip_ref = opt._grad_clip
        program.clip_snapshot = _snapshot_obj(opt._grad_clip)
        program.reg_ref = opt.regularization
        program.reg_snapshot = _snapshot_obj(opt.regularization)
        bake_decay_flags(opt, updated)
        program.extra_key = tuple(opt._extra_cache_key())
        program.acc_names = tuple(sorted(opt._accumulators.keys()))
        program.check = chain.check
        if scaler_obj is not None:
            program.scaler_ref = weakref.ref(scaler_obj)
            program.scaler_consts = scaler_es[0][2]
        # distributed lowering (ops/spmd_fusion.py): when the cycle's
        # inputs live sharded on a mesh, the step compiles through
        # shard_map with the collectives fused in — validated by a
        # probation fire before any fused result commits
        from . import spmd_fusion as _spmd
        plan, plan_reason = _spmd.plan_program(
            chain, slot_inputs, program.ext_order, updated, opt,
            program.acc_names, root_flat)
        if plan_reason is not None:
            # a mesh-level contradiction (inputs spanning meshes) is a
            # first-class reason code, not an anonymous build detail
            _EVENTS.emit("step.record", "", reason=plan_reason,
                         detail={"kind": "build_fail"})
        if plan is not None:
            program.spmd_plan = plan
            program.spmd_ok = False
        names = [op.name for op in ops]
        head = "→".join(names[:3]) + ("→…" if len(names) > 3 else "")
        program.label = (f"{head}[{len(ops)}ops]"
                         f"+{type(opt).__name__}"
                         + ("+GradScaler" if scaler_obj is not None else "")
                         + (f"@mesh[{plan.axes_label}]"
                            if plan is not None else ""))
        program.n_launches = len(ops) + sum(
            1 for op in ops if op.diff_mask is not None) + 1 \
            + (2 if scaler_obj is not None else 0)
        program.baseline_ns = time.perf_counter_ns() - cyc.t0
        program.donate_params = bool(
            _FLAGS.get("FLAGS_eager_step_fusion_donate_params"))
        from . import aot_cache as _aot
        if _aot.enabled():
            # SPMD programs participate too: the env fingerprint's mesh
            # topology token keys artifacts to one mesh shape, so a
            # shard_map module only ever reloads on the topology it was
            # exported from (same-digest different-sharding is impossible
            # across topologies, and within one mesh the plan is a pure
            # function of the cycle)
            dg = st.aot_probe.get(sig, 0)
            program.aot_digest = dg if dg != 0 \
                else _aot.step_digest(sig, opt, updated)
            if warm:
                # AOT warm promote: pull the stored executable NOW so the
                # very next replay fires it — and a restored SPMD program
                # has probation waived before the replay's probation
                # check runs (see exe())
                program.exe()
        STEP_STATS.promoted(program.label)
        _EVENTS.emit("step.promote", program.label,
                     detail={"ops": len(ops), "params": len(updated),
                             "launches_estimate": program.n_launches,
                             "warm_start": warm,
                             "spmd": plan is not None,
                             "mesh": plan.axes_label if plan is not None
                             else None})
        return program

    def _build_super(self, st, cyc, sig, opt, updated, warm=False):
        """Super-cycle qualification + program construction. `sig` is the
        canonical ("super", cg, segment entries, scaler, step) form from
        _super_sig; `cyc` holds the k identically-recorded segments. The
        program's chain is ONE segment — the sub/update executable pair
        replays it at any k."""
        from ..jit.train_step import bake_decay_flags

        def unbuildable(why, op=""):
            _EVENTS.emit("step.record", op, reason="unpromotable_cycle",
                         detail={"kind": "build_fail", "why": why,
                                 "super": True})
            return None

        _tag, cg_e, seg_entries, scaler_e, _step_e = sig[:5]
        tail_entries = sig[5] if len(sig) > 5 else None
        seg_ops = len(seg_entries) - 1
        k = cyc.n_backward
        if not cyc.ops or not updated:
            return unbuildable("no_backward_or_params")
        if any(p._hooks or p.stop_gradient for p in updated):
            return unbuildable("param_hooks")
        for p in updated:
            node = p._grad_node
            if node is not None and node.out_hooks:
                return unbuildable("param_hooks")
        recs = cyc.ops[:seg_ops]
        # segment 0's recorded wiring is already segment-local (its op
        # indices start at 0), so the recs translate directly
        ops = [
            _ChainOp(r.name, r.key, r.fn, r.wiring, r.diff_mask,
                     r.num_outputs, r.out_avals, r.out_stop_grads)
            for r in recs]
        chain = Chain(sig, ops, 0)
        if not chain.grad_mode:
            return unbuildable("no_grad_ops")
        scaler_obj = cyc.scaler
        if scaler_e is not None:
            if scaler_obj is None or id(scaler_obj) != scaler_e[1]:
                return unbuildable("scaler_gone")
            if not chain.check:
                return unbuildable("scaler_without_guardian")
        else:
            scaler_obj = None
        root_coord = seg_entries[-1][1]
        root_flat = None
        for flat, owner in enumerate(chain.owners):
            if owner == root_coord:
                root_flat = flat
                break
        if root_flat is None:
            return unbuildable("root_not_in_chain")
        param_idx = {id(p): kk for kk, p in enumerate(updated)}
        slot_inputs = {}
        for i, rec in enumerate(recs):
            slots = chain.ext_of[i]
            for k2, s in enumerate(slots):
                if s is not None:
                    slot_inputs[s] = rec.ins[k2]
        param_slots = {}
        for s in chain.diff_ext_idx:
            kk = param_idx.get(id(slot_inputs[s]))
            if kk is None:
                return unbuildable("nonparam_diff_input")
            param_slots[s] = kk
        if {v for v in param_slots.values()} != set(range(len(updated))):
            return unbuildable("param_set_mismatch")
        # every segment must feed the SAME param objects into the param
        # slots — micro-batches vary the data, never the binding
        for seg in range(1, k):
            base = seg * seg_ops
            for i in range(seg_ops):
                slots = chain.ext_of[i]
                for k2, s in enumerate(slots):
                    if s in param_slots and \
                            cyc.ops[base + i].ins[k2] is not recs[i].ins[k2]:
                        return unbuildable("accum_param_mismatch")
        # hoisted RNG slots (segment-relative stream deltas)
        rng_slots = {}
        for i, e in enumerate(seg_entries[:-1]):
            if len(e) > 5:
                for k2, delta in e[5]:
                    s = chain.ext_of[i][k2]
                    if s is None or s in param_slots:
                        return unbuildable("rng_wiring")
                    rng_slots[s] = delta
        # ragged tail: build the tail segment's own chain. It compiles to
        # a SECOND sub-executable that adds into the same accumulator —
        # grads share the param avals regardless of batch shape — so the
        # program stays ≤3 executables (main sub, tail sub, update).
        tail_chain = tail_root_flat = None
        tail_rng_slots = {}
        if tail_entries is not None:
            tail_base = (k - 1) * seg_ops
            recs_tail = cyc.ops[tail_base:]
            tail_ops = []
            for r in recs_tail:
                # recorded wiring is cycle-global; rebase to tail-local
                # (cross-segment dataflow already excluded by _super_sig)
                wiring = tuple(
                    ("prev", w[1] - tail_base, w[2]) if w[0] == "prev"
                    else w
                    for w in r.wiring)
                tail_ops.append(_ChainOp(
                    r.name, r.key, r.fn, wiring, r.diff_mask,
                    r.num_outputs, r.out_avals, r.out_stop_grads))
            tail_chain = Chain(sig, tail_ops, 0)
            if not tail_chain.grad_mode \
                    or tail_chain.n_ext != chain.n_ext:
                return unbuildable("ragged_tail_mismatch")
            # the tail must bind the SAME param objects into the SAME
            # slots — only the data inputs (the short batch) may differ
            for i, r in enumerate(recs_tail):
                slots = tail_chain.ext_of[i]
                for k2, s in enumerate(slots):
                    if s in param_slots \
                            and r.ins[k2] is not slot_inputs[s]:
                        return unbuildable("ragged_tail_mismatch")
            troot = tail_entries[-1][1]
            for flat, owner in enumerate(tail_chain.owners):
                if owner == troot:
                    tail_root_flat = flat
                    break
            if tail_root_flat is None:
                return unbuildable("root_not_in_chain")
            for i, e in enumerate(tail_entries[:-1]):
                if len(e) > 5:
                    for k2, delta in e[5]:
                        s = tail_chain.ext_of[i][k2]
                        if s is None or s in param_slots:
                            return unbuildable("rng_wiring")
                        tail_rng_slots[s] = delta
            if set(tail_rng_slots) != set(rng_slots):
                return unbuildable("ragged_tail_mismatch")
        entries = []
        if cg_e is not None:
            entries.append(cg_e)
        seg_start = len(entries)
        for i in range(seg_ops):
            entries.append(("op", i))
        entries.append(("bwd",))
        if scaler_e is not None:
            entries.append(scaler_e)
        entries.append(("step",))
        program = _StepProgram()
        program.super = True
        program.seg_start = seg_start
        program.sig = sig
        program.chain = chain
        program.tail_chain = tail_chain
        program.tail_root_flat = tail_root_flat
        program.tail_rng_slots = tail_rng_slots
        program.entries = tuple(entries)
        program.root_coord = root_coord
        program.root_flat = root_flat
        program.param_refs = tuple(weakref.ref(p) for p in updated)
        program.param_names = tuple(p.name for p in updated)
        program.param_regs = tuple(
            getattr(p, "regularizer", None) for p in updated)
        program.need_clip = tuple(
            getattr(p, "need_clip", True) for p in updated)
        program.param_slots = param_slots
        program.rng_slots = rng_slots
        program.ext_order = tuple(
            s for s in range(chain.n_ext)
            if s not in param_slots and s not in rng_slots)
        program.opt_ref = weakref.ref(opt)
        program.clip_ref = opt._grad_clip
        program.clip_snapshot = _snapshot_obj(opt._grad_clip)
        program.reg_ref = opt.regularization
        program.reg_snapshot = _snapshot_obj(opt.regularization)
        bake_decay_flags(opt, updated)
        program.extra_key = tuple(opt._extra_cache_key())
        opt._create_accumulators(updated)
        program.acc_names = tuple(sorted(opt._accumulators.keys()))
        program.check = chain.check
        if scaler_obj is not None:
            program.scaler_ref = weakref.ref(scaler_obj)
            program.scaler_consts = scaler_e[2]
        from . import spmd_fusion as _spmd
        plan, plan_reason = _spmd.plan_program(
            chain, slot_inputs, program.ext_order, updated, opt,
            program.acc_names, root_flat)
        if plan_reason is not None:
            _EVENTS.emit("step.record", "", reason=plan_reason,
                         detail={"kind": "build_fail"})
        if plan is not None and not plan.data_axes:
            # no batch axis to defer the gradient pmean over: the plain
            # GSPMD lowering already does the right thing
            plan = None
        if plan is not None:
            program.spmd_plan = plan
            program.spmd_ok = False
        names = [op.name for op in ops]
        head = "→".join(names[:3]) + ("→…" if len(names) > 3 else "")
        program.label = (f"{head}[{len(ops)}ops×k]"
                         f"+{type(opt).__name__}+accum"
                         + ("+GradScaler" if scaler_obj is not None else "")
                         + (f"@mesh[{plan.axes_label}]"
                            if plan is not None else ""))
        program.n_launches = k * (len(ops) + sum(
            1 for op in ops if op.diff_mask is not None) + 1) + 1 \
            + (2 if scaler_obj is not None else 0)
        program.baseline_ns = time.perf_counter_ns() - cyc.t0
        program.donate_params = bool(
            _FLAGS.get("FLAGS_eager_step_fusion_donate_params"))
        from . import aot_cache as _aot
        if _aot.enabled():
            dg = st.aot_probe.get(sig, 0)
            program.aot_digest = dg if dg != 0 \
                else _aot.step_digest(sig, opt, updated)
            if warm:
                # AOT warm promote: restore the (sub, update) pair NOW —
                # probation defers sub fires, so a lazy load would never
                # be reached before the probation decision; an eagerly
                # restored SPMD pair waives probation instead
                program._maybe_load_super()
        STEP_STATS.promoted(program.label)
        _EVENTS.emit("step.promote", program.label,
                     detail={"ops": len(ops), "params": len(updated),
                             "super": True, "rounds_seen": k,
                             "launches_estimate": program.n_launches,
                             "warm_start": warm,
                             "spmd": plan is not None,
                             "mesh": plan.axes_label if plan is not None
                             else None})
        return program

    def _disable(self, st):
        """Flag flipped off mid-run: resolve and forget everything."""
        if st.pending is not None and not st.pending.fired:
            with st.pending.lock:
                if not st.pending.done:
                    self._split(st.pending, escape=False,
                                reason="flag_off")
        st.pending = None
        st.recording = None
        st.prev_sig, st.streak = None, 0
        st.active = None
        st.replay_arm = False

    # -- maintenance --------------------------------------------------------
    def clear(self):
        """Drop the calling thread's promoted steps, observation state, and
        any pending replay (test hook / clear_dispatch_cache)."""
        st = self._tls
        self._disable(st)
        st.library.clear()
        st.aot_probe.clear()

    def info(self):
        st = self._tls
        return {
            "library": len(st.library),
            "active": st.active.label if st.active is not None else None,
            "streak": st.streak,
            "programs": [
                {"label": p.label, "ops": len(p.chain.ops),
                 "params": len(p.param_refs), "dead": p.dead,
                 "launches_estimate": p.n_launches,
                 "spmd": (p.spmd_plan.axes_label
                          if p.spmd_plan is not None else None)}
                for p in st.library.values()
                if isinstance(p, _StepProgram)],
        }


STEP = _StepFusionManager()


def clear_step_cache():
    """Drop every promoted whole-step program and observation state on the
    calling thread (test hook / manual invalidation)."""
    STEP.clear()


def step_cache_info():
    """Promoted-step library summary for the calling thread."""
    return STEP.info()
