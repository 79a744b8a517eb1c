"""Live training accountant: rolling MFU, tokens/s, and goodput.

An MFU computed OFFLINE (tokens/s x flops_per_token / chip peak, after the
run) leaves production with no number at all. This module is the
always-on version: a process-global :class:`GoodputAccountant` fed one
call per optimizer-step boundary (hooks in optimizer/optimizer.py and
jit/train_step.py — both the eager and the fused auto-TrainStep paths
pass through `Optimizer.step`, and the explicit `jit.TrainStep` calls in
here directly), publishing into the profiler/metrics.py registry:

  * ``train_step_seconds`` — committed-step wall-time histogram
    (p50/p99);
  * ``train_mfu`` / ``train_tokens_per_second`` — ROLLING window (last
    `_ROLL_WINDOW` steps), so the gauge tracks the live run instead of
    averaging over a restart;
  * ``train_goodput`` + ``goodput_seconds_total{bucket=}`` — wall time
    attributed to `productive` committed steps vs `compile` (any
    dispatch/chain/step retrace or fresh compile inside the interval),
    `skipped` (guardian non-finite skip-steps), `probation` (SPMD
    first-fire bitwise replays), `stalled` (watchdog hangs — the serving
    engine reports the hang wait here too), `warmup` (arm -> first
    boundary), and `other`.

Analytic FLOPs/step come from (in priority order): an explicit
``set_flops_per_step()`` (the caller knows its model),
``set_model()`` (a model exposing ``flops_per_token``/``flops_per_image``,
or counted via the hapi/dynamic_flops machinery), or — automatically at
promotion — :func:`estimate_cycle_flops` over the recorded fused cycle's
op keys (op name + input avals, the same analytic roofline the
cost_model/ static table is derived from). All FLOP counts use the PaLM
2-FLOPs-per-MAC convention (matmul fwd = 2mnk, bwd = 2x fwd) so MFU is
comparable against the hardware peak table below.

Cost contract: every hook checks ``FLAGS_metrics`` first; classification
reads a handful of integer counters off the existing stats structs — no
device work, no allocation beyond a bounded deque.
"""
from __future__ import annotations

import time
from collections import deque

from ..framework.flags import _FLAGS
from . import metrics as _metrics
from . import telemetry_server as _telemetry
from . import sentinel as _sentinel

__all__ = ["GoodputAccountant", "ACCOUNTANT", "on_step", "on_fused_fire",
           "mark", "note_stall", "estimate_cycle_flops",
           "CHIP_PEAKS", "peak_flops_per_chip", "goodput_snapshot",
           "format_step_ranges"]

# rolling throughput window (steps): big enough to smooth scheduler
# jitter, small enough that the gauge tracks LR-phase slowdowns live
_ROLL_WINDOW = 64
# per-bucket step-index attribution ring (PR 13): WHICH steps were
# skipped/stalled/recompiled, bounded so a week of flapping cannot grow
# the accountant — the newest indices win, the counts stay in buckets_s
_ATTR_RING = 64


def format_step_ranges(indices):
    """Render step indices compactly: [1032, 2048, 4096, 4097, 4098]
    -> "1032, 2048, 4096-4098" (the doctor/runbook presentation)."""
    out = []
    run = []
    for i in sorted(set(int(i) for i in indices)):
        if run and i == run[-1] + 1:
            run.append(i)
            continue
        if run:
            out.append(str(run[0]) if len(run) == 1
                       else f"{run[0]}-{run[-1]}")
        run = [i]
    if run:
        out.append(str(run[0]) if len(run) == 1
                   else f"{run[0]}-{run[-1]}")
    return ", ".join(out)


# Published peaks per chip, keyed by jax's `device_kind` — the one table
# every utilization in the repo divides by. A device that is not here has
# no peak: that is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 819 GB/s HBM',
    },
}


def peak_flops_per_chip():
    """bf16 peak FLOP/s of the local chip, from `CHIP_PEAKS`. Raises
    LookupError for a device kind without a published entry (the CPU
    included) — pass `peak=` to `set_flops_per_step` to supply one."""
    import jax
    kind = jax.devices()[0].device_kind
    entry = CHIP_PEAKS.get(kind)
    if entry is None:
        raise LookupError(
            f"no published peak for device kind {kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}")
    return entry["bf16_flops"]


# ---------------------------------------------------------------------------
# analytic FLOPs from a recorded fused cycle
# ---------------------------------------------------------------------------

# Per-op FLOPs declarations (the R7 perf-contract escape hatch): ops whose
# cost the name-family heuristic below would misfile register an explicit
# estimator here. The fn receives the input shapes (tuples, rank >= 1) and
# returns forward FLOPs. Lint rule R7 accepts a heavy-compute op as covered
# when a `declare_op_flops("<name>", ...)` call exists anywhere in the tree.
_DECLARED_FLOPS = {}


def declare_op_flops(name, fn):
    """Declare the forward-FLOPs estimator for op `name` (overrides the
    family heuristic in `_flops_of_op`). `fn(shapes) -> int` where shapes
    is the list of input shape tuples."""
    if not callable(fn):
        raise TypeError(f"declare_op_flops({name!r}): fn must be callable")
    _DECLARED_FLOPS[name] = fn
    return fn


def _flops_of_op(name, avals):
    """Forward FLOPs of one recorded dispatch, from its cache-key input
    avals ((shape, dtype, weak_type) per input). 2 FLOPs per MAC. Coarse
    by design: matmul-family ops dominate every transformer/MLP cycle,
    everything else is counted as O(numel) so the estimate stays a
    roofline, not a lie."""
    shapes = [tuple(av[0]) for av in avals if av and len(av[0]) >= 1]
    if not shapes:
        return 0
    declared = _DECLARED_FLOPS.get(name)
    if declared is not None:
        return int(declared(shapes))
    if "matmul" in name or name in ("linear", "mm", "bmm", "addmm"):
        mats = [s for s in shapes if len(s) >= 2]
        if len(mats) >= 2:
            a, b = mats[0], mats[1]
            # broadcasted batch matmul: [.., m, k] x [.., k, n]; a
            # second operand stored transposed ([n, k], e.g. a tied
            # lm-head weight) is recognized by which axis matches k
            m, k = a[-2], a[-1]
            if b[-2] == k:
                n = b[-1]
            elif b[-1] == k:
                n = b[-2]
            else:
                n = b[-1]
            batch = 1
            for d in a[:-2]:
                batch *= d
            return 2 * batch * m * k * n
    if "conv" in name:
        # no weight-shape access here; fall through to numel
        pass
    if "attention" in name or "softmax" in name:
        total = sum(_numel(s) for s in shapes)
        return 4 * total
    if "embedding" in name:
        return 0
    return sum(_numel(s) for s in shapes)


def _numel(shape):
    n = 1
    for d in shape:
        n *= d
    return n


# Declarations for the contraction ops the name-family heuristic above
# would misfile as O(numel): each is quadratic in its operands. Registered
# here (not in ops/) so the estimator has no import edge into the op
# layer. `shapes` is the list of input shape tuples; 2 FLOPs per MAC.
def _contraction_flops(shapes, k_axes=1):
    """2 * |a| * |b| / k for a pairwise contraction over the trailing
    `k_axes` axes of the first operand."""
    if len(shapes) < 2:
        return sum(_numel(s) for s in shapes)
    a, b = shapes[0], shapes[1]
    k = _numel(a[len(a) - min(k_axes, len(a)):])
    return 2 * _numel(a) * _numel(b) // max(k, 1)


def _chain_matmul_flops(shapes):
    """Left-to-right chain product FLOPs for multi_dot."""
    mats = [s for s in shapes if len(s) >= 2]
    if len(mats) < 2:
        return sum(_numel(s) for s in shapes)
    total, (m, k) = 0, mats[0][-2:]
    for s in mats[1:]:
        n = s[-1] if s[-2] == k else s[-2]
        total += 2 * m * k * n
        k = n
    return total


declare_op_flops("inner", _contraction_flops)
declare_op_flops("tensordot", lambda shapes: _contraction_flops(shapes, 2))
declare_op_flops("outer",
                 lambda shapes: _contraction_flops(shapes, 0) // 2)
declare_op_flops("kron",
                 lambda shapes: _contraction_flops(shapes, 0) // 2)
declare_op_flops("multi_dot", _chain_matmul_flops)
# one n^3 multiply per squaring step; the exponent is not in the shapes,
# so count a single multiply (a roofline floor, like conv's numel)
declare_op_flops("matrix_power",
                 lambda shapes: 2 * _numel(shapes[0]) * shapes[0][-1])
# 2x3 (or 3x4) theta against every output grid point — O(numel) scaled
declare_op_flops("affine_grid",
                 lambda shapes: 6 * sum(_numel(s) for s in shapes))
# k Householder reflectors applied to an m x n matrix: ~4mnk
declare_op_flops("householder_product",
                 lambda shapes: 4 * _numel(shapes[0]) *
                 (_numel(shapes[1]) if len(shapes) > 1 else 1))


def estimate_cycle_flops(entries, training=True):
    """Analytic FLOPs of one recorded step cycle (ops/step_fusion.py
    `_StepProgram.entries` / `_Cycle.entries`): sum the forward op FLOPs
    from each op entry's cache key (key[0] = op name, key[2] = input
    avals), then apply the standard fwd+bwd multiplier (backward ~= 2x
    forward matmul work) when the cycle contains a backward event."""
    fwd = 0
    has_bwd = False
    for e in entries:
        kind = e[0]
        if kind == "op":
            key = e[1]
            try:
                fwd += _flops_of_op(key[0], key[2])
            except Exception:
                pass
        elif kind == "bwd":
            has_bwd = True
    if training and has_bwd:
        return 3 * fwd
    return fwd


# ---------------------------------------------------------------------------
# the accountant
# ---------------------------------------------------------------------------

class GoodputAccountant:
    """Wall-time and throughput accounting over the training step stream.

    One `step_boundary()` per optimizer step classifies the interval
    since the previous boundary into a goodput bucket by diffing the
    existing counter structs (dispatch/chain/step retraces & compiles ->
    `compile`; guardian skip-steps -> `skipped`; SPMD probation marks ->
    `probation`); explicit `note_stall()` calls (watchdog) land in
    `stalled`. Everything before the first boundary is `warmup`.
    """

    def __init__(self):
        # guards the deques (_roll + step_indices rings) against the
        # telemetry server's HTTP threads: snapshot()/publish() iterate
        # them while the training thread appends, and CPython raises
        # "deque mutated during iteration" on that race. Mutations and
        # reads take this lock; scalar bucket sums stay lock-free (GIL
        # float adds, same contract as every counter struct here).
        import threading
        self._ring_lock = threading.Lock()
        self.reset()

    def reset(self, warm=False):
        """Start a fresh accounting window. `warm=True` (a bench window
        opened AFTER compilation settled) skips the first-interval
        `warmup` classification — the first measured step is as
        productive as any other."""
        now = time.perf_counter()
        self._t_arm = now
        self._t_last = now
        self._t_final = None
        self._warmup_pending = not warm
        self.steps = 0
        self.buckets = {b: 0.0 for b in _metrics.GOODPUT_BUCKETS}
        # bounded per-bucket step-index rings: WHICH steps landed in a
        # non-productive bucket (created on first attribution)
        self.step_indices = {}
        self._marks = set()
        self._stalled_extra = 0.0
        self._flops_per_step = None
        self._tokens_per_step = None
        self._peak = None
        self._mesh = None
        self._roll = deque(maxlen=_ROLL_WINDOW)   # (t_end, dt_s)
        self._counter_base = None
        self._flops_source = None
        self._cycle_seen = None   # id() of the last program walked

    # -- configuration ------------------------------------------------------
    @property
    def enabled(self):
        return bool(_FLAGS.get("FLAGS_metrics"))

    def set_flops_per_step(self, flops, tokens=None, peak=None):
        """Pin the analytic FLOPs (and optionally tokens) per training
        step (a caller that knows its model's count)."""
        self._flops_per_step = float(flops)
        if tokens is not None:
            self._tokens_per_step = int(tokens)
        if peak is not None:
            self._peak = float(peak)
        self._flops_source = "explicit"

    def set_model(self, model, batch, seq_len=None, training=True):
        """Derive FLOPs/step from a model: `flops_per_token(seq)` (GPT
        family), `flops_per_image()` (ViT family), or a hapi
        dynamic_flops count as the generic fallback."""
        fpt = getattr(model, "flops_per_token", None)
        if fpt is not None and seq_len is not None:
            self._flops_per_step = float(fpt(seq_len, training=training)) \
                * batch * seq_len
            self._tokens_per_step = batch * seq_len
            self._flops_source = "flops_per_token"
            return
        fpi = getattr(model, "flops_per_image", None)
        if fpi is not None:
            self._flops_per_step = float(fpi(training=training)) * batch
            self._tokens_per_step = batch
            self._flops_source = "flops_per_image"
            return
        try:                               # hapi/dynamic_flops machinery:
            from ..hapi.dynamic_flops import flops as _hapi_flops
            import io as _io
            import contextlib
            with contextlib.redirect_stdout(_io.StringIO()):
                fwd = _hapi_flops(model, inputs=None,
                                  input_size=[1] + ([seq_len] if seq_len
                                                    else []))
            # hapi counts 1 MAC = 1 FLOP; MFU needs 2/MAC, bwd ~= 2x fwd
            self._flops_per_step = float(fwd) * 2 * (3 if training
                                                     else 1) * batch
            self._flops_source = "dynamic_flops"
        except Exception:
            pass

    def maybe_set_cycle_flops(self, entries, label=None):
        """Auto-derive FLOPs/step from a freshly promoted cycle — only
        when nothing more authoritative was pinned."""
        if self._flops_per_step is not None \
                and self._flops_source != "cycle":
            return
        est = estimate_cycle_flops(entries)
        if est > 0:
            self._flops_per_step = float(est)
            self._flops_source = "cycle"

    # -- interval marks -----------------------------------------------------
    def mark(self, kind):
        """Tag the CURRENT inter-boundary interval (e.g. 'probation')."""
        self._marks.add(kind)

    def _attribute_step(self, bucket, index):
        """Record WHICH step landed in a non-productive bucket (bounded
        ring per bucket — the counts live in buckets_s, the indices make
        the report actionable: "steps 1032, 2048 skipped")."""
        with self._ring_lock:
            ring = self.step_indices.get(bucket)
            if ring is None:
                ring = self.step_indices[bucket] = \
                    deque(maxlen=_ATTR_RING)
            if index is not None and (not ring or ring[-1] != index):
                ring.append(int(index))

    def note_stall(self, dt_s, kind="step_hang", step=None):
        """Attribute `dt_s` of wall time to the stalled bucket NOW (the
        watchdog knows exactly how long it waited; the interval diff
        must not double-count it). `step` names the stalled step index —
        the serving engine passes its decode-step counter; a training
        caller defaults to the in-flight boundary."""
        self.buckets["stalled"] += float(dt_s)
        self._stalled_extra += float(dt_s)
        self._attribute_step("stalled",
                             step if step is not None else self.steps + 1)
        self.mark("stalled")

    def drop_stall_carry(self):
        """Forget the pending stall subtraction: the measurement the
        stall was inside never completed (the watchdog's fail-active /
        eager fallback retired the step), so the NEXT productive interval —
        which does not contain the stall — must be booked whole."""
        self._stalled_extra = 0.0

    def note_productive(self, dt_s, tokens=0):
        """Serving-side productive time: a clean decode step. Keeps the
        goodput fraction meaningful in a pure-serving process that never
        crosses an optimizer boundary. Stall time already booked by
        `note_stall` is subtracted first — a decode step that hung and
        then recovered spans the burned watchdog budget, and that budget
        must not be counted BOTH stalled and productive."""
        dt_s = max(0.0, float(dt_s) - self._stalled_extra)
        self._stalled_extra = 0.0
        self.buckets["productive"] += dt_s
        if tokens:
            _metrics.TRAIN.tokens.inc(tokens)

    # -- the boundary -------------------------------------------------------
    def _counters(self):
        from .dispatch import STATS as D
        from .chain_fusion import CHAIN_STATS as C
        from .step_fusion import STEP_STATS as S
        from ..ops.guardian import GUARD_STATS as G
        return (D.misses + D.retraces, C.retraces, S.retraces,
                G.steps_skipped)

    def step_boundary(self, tokens=None):
        now = time.perf_counter()
        dt = now - self._t_last
        self._t_last = now
        self._t_final = None
        try:
            cur = self._counters()
        except Exception:
            cur = None
        first = self.steps == 0 and self._warmup_pending
        self._warmup_pending = False
        self.steps += 1
        compile_seen = False
        skipped = False
        if cur is not None and self._counter_base is not None:
            d_disp, d_chain, d_step, d_skip = (
                a - b for a, b in zip(cur, self._counter_base))
            compile_seen = (d_disp + d_chain + d_step) > 0
            skipped = d_skip > 0
        self._counter_base = cur
        # explicit stall time was already booked by note_stall; the
        # remaining interval classifies below
        dt_left = max(0.0, dt - self._stalled_extra)
        self._stalled_extra = 0.0
        if skipped:
            bucket = "skipped"
        elif "probation" in self._marks:
            bucket = "probation"
        elif first or compile_seen:
            # the very first boundary after arming covers the warmup
            # (imports, tracing, first compiles); later compile activity
            # is attributed as recompilation
            bucket = "warmup" if first else "compile"
        else:
            bucket = "productive"
        self._marks.clear()
        self.buckets[bucket] += dt_left
        if bucket != "productive":
            self._attribute_step(bucket, self.steps)
        if bucket == "productive":
            with self._ring_lock:
                self._roll.append((now, dt_left))
            _metrics.TRAIN.step_s.observe(dt_left)
            if self._mesh:
                _metrics.TRAIN.spmd_step_s.labels(
                    mesh=self._mesh).observe(dt_left)
            n_tok = tokens if tokens is not None \
                else (self._tokens_per_step or 0)
            if n_tok:
                _metrics.TRAIN.tokens.inc(n_tok)

    def finalize(self):
        """Close the measurement window after the caller's final blocking
        read: the tail device time of the last step joins the
        productive bucket instead of silently vanishing."""
        now = time.perf_counter()
        dt = now - self._t_last
        if dt > 0 and self.steps:
            self.buckets["productive"] += dt
            with self._ring_lock:
                if self._roll:
                    t_end, last = self._roll.pop()
                    self._roll.append((now, last + dt))
        self._t_last = now
        self._t_final = now

    # -- publishing / reading ----------------------------------------------
    def _rolling(self):
        """(steps/s over the rolling window, window span s)."""
        with self._ring_lock:
            roll = list(self._roll)
        if len(roll) < 1:
            return 0.0, 0.0
        span = sum(dt for _, dt in roll)
        if span <= 0:
            return 0.0, 0.0
        return len(roll) / span, span

    def publish(self):
        """Refresh the registry gauges from the current state (run as a
        collector before every snapshot/exposition)."""
        T = _metrics.TRAIN
        sps, _span = self._rolling()
        if self._flops_per_step:
            T.flops_per_step._default.set_raw(self._flops_per_step)
            if self._peak is None:
                try:
                    self._peak = peak_flops_per_chip()
                except LookupError:
                    # no published peak for this device: MFU is not
                    # reported (the gauge stays unset), never guessed
                    self._peak = False
            if self._peak:
                T.mfu._default.set_raw(
                    sps * self._flops_per_step / self._peak)
        if self._tokens_per_step:
            T.tokens_per_s._default.set_raw(sps * self._tokens_per_step)
        total = sum(self.buckets.values())
        if total > 0:
            T.goodput._default.set_raw(
                self.buckets["productive"] / total)
        for b, v in self.buckets.items():
            T.goodput_s.labels(bucket=b).set_raw(v)
        # per-step attribution reaches the exposition as a high-water
        # gauge: the LAST step index attributed per bucket ("the
        # guardian most recently skipped step N"); the full bounded
        # rings ride the JSON snapshot / the /goodput endpoint
        with self._ring_lock:
            last_by_bucket = {b: ring[-1]
                              for b, ring in self.step_indices.items()
                              if ring}
        for b, last in last_by_bucket.items():
            T.step_index.labels(bucket=b).set_raw(last)

    def snapshot(self):
        """JSON-able accountant view (the MFU/tokens-per-second here IS
        the registry computation)."""
        self.publish()
        T = _metrics.TRAIN
        sps, span = self._rolling()
        total = sum(self.buckets.values())
        with self._ring_lock:
            indices = {b: list(ring)
                       for b, ring in self.step_indices.items() if ring}
        return {
            "steps": self.steps,
            "wall_s": round((self._t_final or time.perf_counter())
                            - self._t_arm, 4),
            "flops_per_step": self._flops_per_step,
            "flops_source": self._flops_source,
            # significant digits, not decimal places: a CPU-smoke MFU of
            # 1e-7 must not round to an (asserted-on) hard zero
            "mfu": float(f"{T.mfu.value:.6g}"),
            "tokens_per_sec": round(T.tokens_per_s.value, 2),
            "steps_per_sec": round(sps, 4),
            "step_ms_p50": round(T.step_s.quantile(0.5) * 1e3, 4),
            "step_ms_p99": round(T.step_s.quantile(0.99) * 1e3, 4),
            "goodput": round(self.buckets["productive"] / total, 4)
            if total > 0 else 0.0,
            "buckets_s": {b: round(v, 4)
                          for b, v in self.buckets.items()},
            # WHICH steps landed in each non-productive bucket (bounded
            # rings, newest last) + the compact human rendering the
            # doctor prints ("1032, 2048, 4096-4103")
            "step_indices": indices,
            "step_indices_pretty": {b: format_step_ranges(ring)
                                    for b, ring in indices.items()},
        }


ACCOUNTANT = GoodputAccountant()


# ---------------------------------------------------------------------------
# hook entry points (one flag check each when metrics are off)
# ---------------------------------------------------------------------------

def on_step(opt=None, tokens=None):
    """Optimizer-step boundary (optimizer/optimizer.py + the fused
    replay + jit/train_step.py). The telemetry server's liveness
    heartbeat fires BEFORE the metrics gate — /healthz must work on a
    process that never armed FLAGS_metrics (one module-bool check when
    no server runs; the beat keeps its own step counter so the number
    moves even with the accountant disarmed)."""
    _telemetry.beat("train")
    _sentinel.tick()
    if not _FLAGS.get("FLAGS_metrics"):
        return
    ACCOUNTANT.step_boundary(tokens=tokens)


def on_fused_fire(program, rounds=1):
    """A fused whole-step executable fired (ops/step_fusion.py): record
    its mesh label for the per-mesh SPMD histogram and auto-derive
    FLOPs/step from the recorded cycle when nothing better is pinned.
    `rounds` is the micro-batch count of a super-cycle fire (grad
    accumulation): one optimizer step spans rounds× the segment's
    FLOPs. The derivation is memoized per program, so a later k change
    keeps the first fire's estimate — a caller pins exact FLOPs when
    that matters."""
    if not _FLAGS.get("FLAGS_metrics"):
        return
    plan = getattr(program, "spmd_plan", None)
    ACCOUNTANT._mesh = plan.axes_label if plan is not None else None
    if ACCOUNTANT._cycle_seen == id(program):
        return                  # FLOPs already derived for this program
    ACCOUNTANT._cycle_seen = id(program)
    # the promoted program collapses op entries to position markers; the
    # full dispatch keys (op name + input avals) live on its chain's ops
    chain = getattr(program, "chain", None)
    if chain is not None and getattr(chain, "ops", None):
        entries = [("op", op.key) for op in chain.ops] * max(1, rounds)
        if any(e[0] == "bwd" for e in getattr(program, "entries", ())):
            entries.append(("bwd", None))
        ACCOUNTANT.maybe_set_cycle_flops(entries,
                                         getattr(program, "label", None))


def mark(kind):
    if not _FLAGS.get("FLAGS_metrics"):
        return
    ACCOUNTANT.mark(kind)


def note_stall(dt_s, kind="step_hang", step=None):
    if not _FLAGS.get("FLAGS_metrics"):
        return
    ACCOUNTANT.note_stall(dt_s, kind, step=step)


def goodput_snapshot():
    return ACCOUNTANT.snapshot()
