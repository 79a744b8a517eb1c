"""Non-finite step guardian: fused, in-graph numerics safety.

Reference analog: paddle/fluid/framework/details/nan_inf_utils.h
(CheckOpHasNanOrInf) + the dynamic loss-scaling ops
(fluid/operators/amp/check_finite_and_unscale_op.cc,
update_loss_scaling_op.cc) + auto_checkpoint — the machinery that keeps a
multi-day run alive through NaN/Inf blowups and loss-scale collapse.

The strict `FLAGS_check_nan_inf` mode (ops/dispatch._scan_nan_inf) forces
per-op dispatch with a host sync per inexact output and flushes every
chain/step fusion: perfect for LOCALIZING a known blowup, ruinous as an
always-on production check. `FLAGS_check_numerics` — this module — makes
the check a property of the compiled executables instead:

  per-op tier    the cached forward / forward+vjp executable additionally
                 computes ONE all-finite scalar over its inexact outputs
                 (the check flag is part of the cache key, so flipping it
                 re-keys cleanly);
  chain tier     the fused chain executable emits one scalar for the whole
                 chain (ops/fusion.py);
  step tier      the fused whole-step executable computes a global
                 grads-finite predicate, applies the optimizer update as
                 `where(finite, new_state, old_state)` — a poisoned batch
                 becomes a bitwise no-op step — and, when a GradScaler
                 rides the step, folds unscale / found-inf / loss-scale
                 update in as well (ops/step_fusion.py).

The emitted scalars are NOT synced at the op: they land in a small
per-thread queue and are checked lazily at the next `Tensor.backward()` /
`Optimizer.step()` boundary (`flush()`), one batched device→host transfer
per flush. A non-finite FORWARD output raises `FloatingPointError`
(FLAGS_check_numerics_level=0) or warns (>=1); non-finite GRADIENTS never
raise — the step was already skipped in-graph, the flush only attributes
it (`nonfinite_skip` / `scaler_backoff` in the fusion flight recorder,
profiler/events.py) and counts it in `guardian_stats()`.

Fault injection (tools/chaos.py): `inject_fault()` registers hooks the
dispatch funnel consults — poison an op's output with NaNs or raise a
`ChaosFault` mid-step — each firing attributed as `injected_fault` so the
doctor report distinguishes deliberate chaos from organic blowups.
"""
from __future__ import annotations

import threading
import warnings
from collections import deque

import numpy as np
import jax.numpy as jnp

from ..framework.flags import _FLAGS
from ..profiler.events import EVENTS as _EVENTS

__all__ = [
    "enabled", "skip_step_enabled", "finite_all", "finite_all_reduced",
    "flush", "maybe_flush",
    "guardian_stats", "reset_guardian_stats", "update_scaler_state",
    "mark_scaler_active", "inject_fault", "clear_faults", "poll_fault",
    "faults_armed", "ChaosFault", "GUARD_STATS",
]

# queued-but-unflushed scalars are force-flushed past this depth so a
# boundary-less loop (pure inference with the flag on) cannot grow the
# queue or silently drop checks
_MAX_QUEUE = 1024


def enabled() -> bool:
    """The fused guardian is active. FLAGS_check_nan_inf (the strict
    per-op debug mode) takes precedence: it already materializes and
    checks every output synchronously."""
    return bool(_FLAGS.get("FLAGS_check_numerics")) \
        and not bool(_FLAGS.get("FLAGS_check_nan_inf"))


# the skip-step rescue rides the same flag: a non-finite-gradient step is
# turned into a bitwise no-op update (fused and eager paths alike)
skip_step_enabled = enabled


def finite_all(vals):
    """All-finite scalar over the inexact entries of `vals` — traceable
    (used inside the per-op/chain/step executables) and eager-safe. Empty
    or all-integer input yields a constant True."""
    fin = None
    for v in vals:
        if not jnp.issubdtype(v.dtype, jnp.inexact):
            continue
        f = jnp.isfinite(v).all()
        fin = f if fin is None else fin & f
    return jnp.asarray(True) if fin is None else fin


def finite_all_reduced(vals, axis_names):
    """`finite_all` made GLOBALLY consistent inside a shard_map region:
    the scalar is all-reduced (min) over `axis_names`, so every shard of a
    distributed fused step takes the same skip/keep branch — one shard's
    blowup skips the step everywhere, keeping replicated parameters
    bitwise-identical across the mesh (ops/spmd_fusion.py)."""
    import jax
    p = finite_all(vals)
    if not axis_names:
        return p
    return jax.lax.pmin(p.astype(jnp.int32), tuple(axis_names)) > 0


def update_scaler_state(scale, good, bad, found_inf, incr_ratio,
                        decr_ratio, incr_every_n_steps,
                        decr_every_n_nan_or_inf):
    """Dynamic loss-scaling state transition (update_loss_scaling
    semantics) as one pure jnp function — traced into the fused step
    executable AND evaluated eagerly by GradScaler.update(), so the two
    paths cannot drift. All state stays on device; nothing here syncs."""
    found_inf = jnp.asarray(found_inf)
    bad2 = jnp.where(found_inf, bad + 1, 0)
    good2 = jnp.where(found_inf, 0, good + 1)
    shrink = found_inf & (bad2 >= decr_every_n_nan_or_inf)
    grow = (~found_inf) & (good2 >= incr_every_n_steps)
    scale2 = jnp.where(
        shrink, jnp.maximum(scale * decr_ratio, 1.0),
        jnp.where(grow, scale * incr_ratio, scale))
    return (scale2, jnp.where(grow, 0, good2),
            jnp.where(shrink, 0, bad2))


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

class GuardianStats:
    """Process-wide counters (lock-free best-effort increments, like the
    other profiler counter structs)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.checks_enqueued = 0
        self.flushes = 0
        self.nonfinite_outputs = 0
        self.steps_guarded = 0       # steps that ran with the where() rescue
        self.steps_skipped = 0       # non-finite grads -> bitwise no-op step
        self.scaler_backoffs = 0
        self.faults_injected = 0

    def snapshot(self):
        return {
            "checks_enqueued": self.checks_enqueued,
            "flushes": self.flushes,
            "nonfinite_outputs": self.nonfinite_outputs,
            "steps_guarded": self.steps_guarded,
            "steps_skipped": self.steps_skipped,
            "scaler_backoffs": self.scaler_backoffs,
            "faults_injected": self.faults_injected,
        }


GUARD_STATS = GuardianStats()


def guardian_stats() -> dict:
    """Counters of the non-finite step guardian (FLAGS_check_numerics)."""
    return GUARD_STATS.snapshot()


def reset_guardian_stats():
    GUARD_STATS.reset()


def reset_thread_state():
    """Drop the calling thread's queued checks, in-flight boundary
    batches, and its sticky AMP (scaler-active) marker — test isolation
    hook."""
    _tls.queue.clear()
    _tls.inflight.clear()
    _tls.scaler_active = False


# ---------------------------------------------------------------------------
# the lazy check queue
# ---------------------------------------------------------------------------

# boundary batches allowed in flight before a resolve BLOCKS on the
# device: at depth N, a non-finite finding surfaces at most N boundaries
# after the op ran — the params were already protected in-graph by the
# skip-step rescue, so the delay costs attribution latency, not safety,
# and it keeps the async dispatch pipeline intact (no hard sync per step)
_PIPELINE_DEPTH = 2


class _TLS(threading.local):
    def __init__(self):
        self.queue = deque()
        # (entries, stacked-scalar) boundary batches awaiting host resolve
        self.inflight = deque()
        # set (sticky) once a live GradScaler touches this thread: fp16
        # AMP routinely overflows forward activations, and the scaler's
        # found-inf/skip-step machinery IS the rescue — so flush() must
        # attribute non-finite forward outputs instead of raising
        self.scaler_active = False


_tls = _TLS()


def mark_scaler_active():
    """Called by an enabled GradScaler (scale/step): switches this thread
    to AMP semantics — non-finite FORWARD outputs no longer raise at
    flush(), they are attributed only (`nonfinite_output`), because the
    loss-scale backoff + skip-step rescue is the designed response."""
    _tls.scaler_active = True


def enqueue_fwd(name, finite_scalar):
    """Queue a forward all-finite scalar (per-op or chain label). Called
    from the dispatch/chain tiers with a device scalar — no sync here.
    A TRACER scalar (the op ran inside an outer jit trace — a serving
    prefill/decode build, jit.TrainStep) is dropped: it could never be
    resolved at a later flush (the trace is gone by then) and the
    enclosing compiled program carries its own checks."""
    import jax
    if isinstance(finite_scalar, jax.core.Tracer):
        return
    GUARD_STATS.checks_enqueued += 1
    q = _tls.queue
    q.append(("fwd", name, finite_scalar))
    if len(q) >= _MAX_QUEUE:
        flush()


def observe(name, out_vals):
    """Eager-path check for dispatches that did not go through a cached
    executable (uncached / un-keyable calls): build the finite scalar with
    plain jnp ops and queue it. Still no host sync."""
    vals = [v for v in out_vals
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.inexact)]
    if not vals:
        return
    enqueue_fwd(name, finite_all(vals))


def note_step(label, grads_finite, fwd_finite=None, scale_before=None,
              scale_after=None, step_index=None):
    """Queue a step-level guardian outcome: the skip predicate that drove
    the where() rescue (fused or eager — non-finite update OR non-finite
    new params/slots), the optional forward (loss) finiteness, and the
    loss-scale transition when a GradScaler was folded in. `step_index`
    is the optimizer's step counter at the decision: it rides the queue
    so the flight-recorder events (and the fusion doctor) can say WHICH
    step skipped, not just how many. Step entries never raise at flush —
    the skip already rescued the step; the flush only attributes it."""
    GUARD_STATS.checks_enqueued += 1
    GUARD_STATS.steps_guarded += 1
    q = _tls.queue
    q.append(("step", label, grads_finite, fwd_finite, scale_before,
              scale_after, step_index))
    if len(q) >= _MAX_QUEUE:
        flush()


def _host(v):
    return np.asarray(v)


def maybe_flush():
    """Boundary hook (Tensor.backward, Optimizer.step, GradScaler.step):
    seal the queued scalars into one batch and resolve every in-flight
    batch the device has already finished — WITHOUT blocking on the one
    still computing (up to _PIPELINE_DEPTH boundaries stay in flight, so
    the async dispatch pipeline survives; a finding surfaces at most that
    many boundaries late). A no-op (one truthiness check) when nothing is
    queued — i.e. whenever FLAGS_check_numerics is off."""
    if _tls.queue or _tls.inflight:
        _seal()
        _resolve_ready(block=False)


def flush():
    """Drain the guardian completely: seal the queue and resolve EVERY
    in-flight batch, blocking on the device as needed. Use at loop exit,
    in tests, and in backward-less loops; the per-step boundaries use the
    non-blocking maybe_flush()."""
    _seal()
    _resolve_ready(block=True)


def _seal():
    """Move the queued entries into one in-flight boundary batch together
    with their check scalars. Deliberately NO device work here (stacking
    the scalars would dispatch an op per boundary — measurably worse than
    hosting the handful of ready bool scalars one by one at resolve)."""
    q = _tls.queue
    if not q:
        return
    entries = list(q)
    q.clear()
    GUARD_STATS.flushes += 1
    scalars = []
    for e in entries:
        if e[0] == "fwd":
            scalars.append(e[2])
        elif e[0] == "scaler":
            scalars.append(e[2])   # the no-backoff predicate
        else:
            scalars.append(e[2])
            if e[3] is not None:
                scalars.append(e[3])
    _tls.inflight.append((entries, scalars))


def _resolve_ready(block):
    """Host-resolve in-flight batches: always those the device already
    finished (is_ready), plus — when over _PIPELINE_DEPTH or `block` —
    the ones worth waiting for."""
    inflight = _tls.inflight
    first_error = None
    while inflight:
        entries, scalars = inflight[0]
        if not block and len(inflight) <= _PIPELINE_DEPTH \
                and not _batch_ready(scalars):
            break
        inflight.popleft()
        err = _resolve_batch(entries, scalars)
        if err is not None and first_error is None:
            first_error = err
    if first_error is not None:
        raise first_error


def _batch_ready(scalars):
    for s in scalars:
        ready = getattr(s, "is_ready", None)
        if ready is not None and not ready():
            return False
    return True


def _resolve_batch(entries, scalars):
    """Host the batch's check scalars (tiny, already-computed bools); the
    per-entry walk below only runs when something was non-finite. Returns
    the deferred FloatingPointError (if any) instead of raising so the
    caller can finish resolving the rest of the pipeline first."""
    all_ok = all(bool(_host(s)) for s in scalars)
    if all_ok:
        return None
    first_error = None
    for e in entries:
        if e[0] == "fwd":
            _kind, name, fin = e
            if bool(_host(fin)):
                continue
            GUARD_STATS.nonfinite_outputs += 1
            _EVENTS.emit("step.record", name, reason="nonfinite_output",
                         detail={"kind": "guardian"})
            msg = (f"Operator '{name}' produced a non-finite output "
                   "(FLAGS_check_numerics guardian; re-run with "
                   "FLAGS_check_nan_inf=1 to localize synchronously)")
            if _tls.scaler_active:
                # AMP thread: fp16 overflow in the forward is expected —
                # the GradScaler's found-inf path skips the step and backs
                # the scale off; raising here would make dynamic loss
                # scaling impossible. Attribution only.
                pass
            elif int(_FLAGS.get("FLAGS_check_numerics_level", 0)) == 0:
                if first_error is None:
                    first_error = FloatingPointError(msg)
            else:
                warnings.warn(msg)
        elif e[0] == "scaler":
            _kind, label, no_backoff, s_before, s_after = e
            if bool(_host(no_backoff)):
                continue
            GUARD_STATS.scaler_backoffs += 1
            _EVENTS.emit("step.record", label, reason="scaler_backoff",
                         detail={"kind": "guardian",
                                 "scale": [float(_host(s_before)),
                                           float(_host(s_after))]})
        else:
            _kind, label, grads_fin, fwd_fin, s_before, s_after, step_idx = e
            stamp = {"kind": "guardian"}
            if step_idx is not None:
                stamp["step"] = int(step_idx)
            skipped = not bool(_host(grads_fin))
            if skipped:
                GUARD_STATS.steps_skipped += 1
                _EVENTS.emit("step.record", label, reason="nonfinite_skip",
                             detail=stamp)
            if fwd_fin is not None and not bool(_host(fwd_fin)):
                # the loss itself was non-finite; the skip already rescued
                # the parameters — but the FORWARD contract must match the
                # unfused path: raise at level 0 (attribute-only on AMP
                # threads, where fp16 overflow is the scaler's business)
                GUARD_STATS.nonfinite_outputs += 1
                _EVENTS.emit("step.record", label,
                             reason="nonfinite_output",
                             detail=dict(stamp, rescued=True))
                msg = (f"Fused step '{label}' produced a non-finite loss "
                       "(FLAGS_check_numerics guardian; parameters were "
                       "rescued by the skip-step no-op — re-run with "
                       "FLAGS_check_nan_inf=1 to localize the op)")
                if _tls.scaler_active:
                    pass
                elif int(_FLAGS.get("FLAGS_check_numerics_level", 0)) == 0:
                    if first_error is None:
                        first_error = FloatingPointError(msg)
                else:
                    warnings.warn(msg)
            if s_before is not None and s_after is not None:
                before = float(_host(s_before))
                after = float(_host(s_after))
                if after < before:
                    GUARD_STATS.scaler_backoffs += 1
                    _EVENTS.emit("step.record", label,
                                 reason="scaler_backoff",
                                 detail=dict(stamp,
                                             scale=[before, after]))
    return first_error


def note_scaler(scale_before, scale_after):
    """Queue a loss-scale transition from the EAGER GradScaler.update()
    path so backoffs are attributed without a host sync at the call. The
    no-backoff predicate is computed on device so the resolve fast path
    (all scalars true → no walk) stays correct."""
    GUARD_STATS.checks_enqueued += 1
    q = _tls.queue
    q.append(("scaler", "grad_scaler",
              jnp.asarray(scale_after) >= jnp.asarray(scale_before),
              scale_before, scale_after))
    if len(q) >= _MAX_QUEUE:
        flush()


# ---------------------------------------------------------------------------
# fault injection (the chaos harness's hooks into dispatch)
# ---------------------------------------------------------------------------

class ChaosFault(RuntimeError):
    """Deliberate mid-step failure raised by an injected fault hook."""


class _Injector:
    __slots__ = ("kind", "op", "after", "times", "seen", "fired")

    def __init__(self, kind, op, after, times):
        self.kind = kind
        self.op = op
        self.after = after
        self.times = times
        self.seen = 0
        self.fired = 0

    def remove(self):
        try:
            _INJECTORS.remove(self)
        except ValueError:
            pass


# consulted by ops/dispatch.py only when non-empty (one truthiness check
# on the hot path)
_INJECTORS: list = []


def inject_fault(kind, op=None, after=0, times=1):
    """Register a chaos fault hook (tools/chaos.py / tests).

    kind: "nan_output" — replace the matching dispatch's outputs with NaN;
          "raise"      — raise ChaosFault from inside the dispatch;
          "hang"       — the matching site behaves as if its device work
                         never completed (serving watchdog sites and the
                         fused tiers consult this via `poll_fault`; plain
                         dispatches ignore it — an eager op cannot "hang"
                         without wedging the harness itself). The
                         StepHang is raised WITHOUT burning real time,
                         so recovery-ladder chaos stays fast;
          "stall"      — a hang that DOES burn the real watchdog budget
                         before the StepHang (serving/resilience.py
                         sleeps it out). The wall-clock variant exists
                         for the liveness plane: /healthz
                         (profiler/telemetry_server.py) must flip
                         unhealthy within one watchdog window of a
                         wedged step, which requires the wedge to
                         occupy real time.
    op:   op name to match (None = any dispatched op). Non-dispatch
          sites use reserved names: "serve.decode" / "serve.prefill"
          (engine step futures), "fused_chain" / "fused_step" (the
          fused-tier fires, ops/fusion.py + ops/step_fusion.py).
    after: matching dispatches to let through before firing.
    times: firings before the injector disarms.

    Returns the injector; call .remove() to disarm early.
    """
    if kind not in ("nan_output", "raise", "hang", "stall"):
        raise ValueError(f"unknown fault kind {kind!r}")
    inj = _Injector(kind, op, int(after), int(times))
    _INJECTORS.append(inj)
    return inj


def clear_faults():
    del _INJECTORS[:]


def faults_armed():
    """Any injector registered — the fused-tier fire paths gate their
    poll_fault call on this so chaos costs one truthiness check when
    disarmed (same contract as the dispatch hook)."""
    return bool(_INJECTORS)


def poll_fault(name, kinds):
    """Non-dispatch chaos hook: fire the first armed injector matching
    `name` with a kind in `kinds` and return its kind (or None). Used by
    the serving engine (decode/prefill watchdog + fused-output poison)
    and the fused chain/step fire paths, where outputs are not a flat
    dispatch result `maybe_inject` could transform. The firing is
    attributed `injected_fault` exactly like a dispatch-level one; the
    CALLER implements the fault semantics (simulate a hang, poison its
    outputs, split the replay)."""
    for inj in list(_INJECTORS):
        if inj.fired >= inj.times or inj.kind not in kinds:
            continue
        if inj.op is not None and inj.op != name:
            continue
        inj.seen += 1
        if inj.seen <= inj.after:
            continue
        inj.fired += 1
        GUARD_STATS.faults_injected += 1
        _EVENTS.emit("step.record", name, reason="injected_fault",
                     detail={"kind": "guardian", "fault": inj.kind})
        return inj.kind
    return None


def maybe_inject(name, out_vals, multi):
    """Apply the first matching armed injector to a dispatch's outputs.
    Only called when _INJECTORS is non-empty. Replayed (deferred) chain/
    step ops never reach this hook — chaos poisons their batch inputs
    instead, which exercises the same in-graph detection."""
    for inj in list(_INJECTORS):
        if inj.fired >= inj.times:
            continue
        if inj.kind in ("hang", "stall"):
            # hang/stall faults are only meaningful at monitored-
            # completion sites (poll_fault); a plain dispatch ignores
            # them
            continue
        if inj.op is not None and inj.op != name:
            continue
        inj.seen += 1
        if inj.seen <= inj.after:
            continue
        inj.fired += 1
        GUARD_STATS.faults_injected += 1
        _EVENTS.emit("step.record", name, reason="injected_fault",
                     detail={"kind": "guardian", "fault": inj.kind})
        if inj.kind == "raise":
            raise ChaosFault(
                f"chaos: injected exception at op '{name}' "
                f"(firing {inj.fired}/{inj.times})")
        if multi:
            return tuple(
                jnp.full_like(v, jnp.nan)
                if jnp.issubdtype(v.dtype, jnp.inexact) else v
                for v in out_vals)
        if jnp.issubdtype(out_vals.dtype, jnp.inexact):
            return jnp.full_like(out_vals, jnp.nan)
        return out_vals
    return out_vals
