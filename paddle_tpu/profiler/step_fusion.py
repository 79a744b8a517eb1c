"""Whole-step fusion telemetry: counters for the auto-TrainStep layer.

The step-fusion layer (ops/step_fusion.py) sits above chain fusion
(counters in profiler/chain_fusion.py) and replaces an entire eager
training cycle — every forward launch, every per-node backward launch, and
the optimizer's fused update launch — with ONE whole-step executable.
These counters make that visible; tests/test_step_fusion.py holds the
launches they count.

Counter semantics:
  steps_promoted    distinct per-step cycles that stayed identical for
                    FLAGS_eager_step_fusion_min_count iterations and got a
                    whole-step executable built
  fused_steps       completed whole-step replays — each one ran a single
                    fused fwd+bwd+optimizer executable in place of the
                    entire eager cycle
  fallback_splits   replays abandoned mid-cycle (op/event mismatch, an
                    escaping value peek, a changed optimizer/param set, an
                    execution fault) and re-run through the chain/per-op
                    path; numerics are identical either way
  escapes           the subset of splits forced by a tensor of the pending
                    step leaving it (a mid-step `.numpy()`, a grad read
                    before the optimizer step, an unrelated consumer)
  launches_saved    Σ over fused replays of (estimated launches of the
                    unfused cycle − 1): forward op launches + one backward
                    launch per grad-recording op + the optimizer update
  wall_time_saved_ns
                    Σ over fused replays of (wall time of the last observed
                    unfused cycle − measured fused cycle time); an
                    estimate, not a re-measurement
  retraces          jax traces of whole-step executables (side-effect
                    counter that only runs while tracing)
  deactivated       promoted steps disabled after repeatedly failing to
                    replay (persistent mid-cycle divergence)

Like ChainFusionStats, hot-path bumps are plain attribute increments;
snapshot/reset take the lock for a consistent read.
"""
from __future__ import annotations

import threading

__all__ = ["StepFusionStats", "STEP_STATS", "step_fusion_stats",
           "reset_step_fusion_stats"]


class StepFusionStats:
    __slots__ = ("_lock", "steps_promoted", "fused_steps", "fallback_splits",
                 "escapes", "launches_saved", "wall_time_saved_ns",
                 "retraces", "deactivated", "per_step")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.steps_promoted = 0
            self.fused_steps = 0
            self.fallback_splits = 0
            self.escapes = 0
            self.launches_saved = 0
            self.wall_time_saved_ns = 0
            self.retraces = 0
            self.deactivated = 0
            self.per_step = {}    # label -> [replays, splits, saved]

    # -- hot-path bumps ----------------------------------------------------
    def _step(self, label):
        rec = self.per_step.get(label)
        if rec is None:
            rec = self.per_step[label] = [0, 0, 0]
        return rec

    def promoted(self, label):
        self.steps_promoted += 1
        self._step(label)

    def replay(self, label, launches, saved_ns):
        self.fused_steps += 1
        self.launches_saved += launches - 1
        if saved_ns > 0:
            self.wall_time_saved_ns += saved_ns
        rec = self._step(label)
        rec[0] += 1
        rec[2] += launches - 1

    def split(self, label, escape=False):
        self.fallback_splits += 1
        if escape:
            self.escapes += 1
        self._step(label)[1] += 1

    # -- reading -----------------------------------------------------------
    def snapshot(self, per_step: bool = False) -> dict:
        """JSON-ready counter view; `per_step` adds the
        label -> {replays, splits, launches_saved} breakdown."""
        with self._lock:
            attempts = self.fused_steps + self.fallback_splits
            out = {
                "steps_promoted": self.steps_promoted,
                "fused_steps": self.fused_steps,
                "fallback_splits": self.fallback_splits,
                "escapes": self.escapes,
                "launches_saved": self.launches_saved,
                "wall_time_saved_ms":
                    round(self.wall_time_saved_ns / 1e6, 3),
                "retraces": self.retraces,
                "deactivated": self.deactivated,
                "replay_rate": round(self.fused_steps / attempts, 4)
                    if attempts else 0.0,
            }
            if per_step:
                rows = dict(self.per_step)
                out["steps"] = {
                    label: {"replays": r[0], "splits": r[1],
                            "launches_saved": r[2]}
                    for label, r in sorted(rows.items())}
            return out


STEP_STATS = StepFusionStats()


def step_fusion_stats(per_step: bool = False) -> dict:
    """Current whole-step fusion counters (see module docstring for field
    semantics)."""
    return STEP_STATS.snapshot(per_step)


def reset_step_fusion_stats():
    STEP_STATS.reset()
