"""Chain-fusion telemetry: counters for the fused op-chain layer.

The fusion layer (ops/fusion.py) sits on top of the per-op executable cache
(ops/dispatch.py, counters in profiler/dispatch.py) and replaces N per-op
XLA launches of a hot op sequence with one fused launch. These counters make
that visible; tests/test_chain_fusion.py holds the launches they count.

Counter semantics:
  chains_detected   distinct op sequences that crossed the hotness threshold
                    and got a fused executable registered
  fused_replays     completed chain replays — each one ran a single fused
                    executable in place of len(chain) per-op launches
  fallback_splits   chains abandoned mid-replay (key mismatch, an escaping
                    intermediate, or an execution fault) and re-run through
                    the per-op cached path; numerics are identical either way
  escapes           the subset of splits forced by an intermediate tensor
                    leaving the chain (value read, grad-node access, an
                    unrelated consumer) before the chain completed
  launches_saved    Σ over fused replays of (chain length − 1): per-op
                    executable launches that never happened
  wall_time_saved_ns
                    Σ over fused replays of (recorded per-op dispatch time
                    of the sequence − measured fused dispatch time); the
                    baseline is the dispatch wall time measured for the
                    occurrence that crossed the hotness threshold, so this
                    is an estimate, not a re-measurement
  chains_stitched   chains created by window stitching: two chains that
                    replayed back-to-back with matching boundary wiring,
                    registered as ONE longer chain (so blocks longer than
                    the detection window still fuse into a single launch;
                    a stitched replay counts its launches-saved once, the
                    constituent chains no longer replay)
  retraces          jax traces of chain-owned fused executables (side-effect
                    counter that only runs while tracing)
  evictions         chain LRU evictions past FLAGS_eager_chain_cache_size
  deactivated       chains disabled after repeatedly failing to replay
                    (persistent mid-chain escapes)

Like DispatchStats, hot-path bumps are plain attribute increments;
snapshot/reset take the lock for a consistent read.
"""
from __future__ import annotations

import threading

__all__ = ["ChainFusionStats", "CHAIN_STATS", "chain_fusion_stats",
           "reset_chain_fusion_stats"]


class ChainFusionStats:
    __slots__ = ("_lock", "chains_detected", "chains_stitched",
                 "fused_replays", "fallback_splits", "escapes",
                 "launches_saved", "wall_time_saved_ns", "retraces",
                 "evictions", "deactivated", "per_chain")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.chains_detected = 0
            self.chains_stitched = 0
            self.fused_replays = 0
            self.fallback_splits = 0
            self.escapes = 0
            self.launches_saved = 0
            self.wall_time_saved_ns = 0
            self.retraces = 0
            self.evictions = 0
            self.deactivated = 0
            self.per_chain = {}    # chain label -> [replays, splits, saved]

    # -- hot-path bumps ----------------------------------------------------
    def _chain(self, label):
        rec = self.per_chain.get(label)
        if rec is None:
            rec = self.per_chain[label] = [0, 0, 0]
        return rec

    def detected(self, label):
        self.chains_detected += 1
        self._chain(label)

    def stitched(self, label):
        self.chains_stitched += 1
        self._chain(label)

    def replay(self, label, length, saved_ns):
        self.fused_replays += 1
        self.launches_saved += length - 1
        if saved_ns > 0:
            self.wall_time_saved_ns += saved_ns
        rec = self._chain(label)
        rec[0] += 1
        rec[2] += length - 1

    def split(self, label, escape=False):
        self.fallback_splits += 1
        if escape:
            self.escapes += 1
        self._chain(label)[1] += 1

    # -- reading -----------------------------------------------------------
    def snapshot(self, per_chain: bool = False) -> dict:
        """JSON-ready counter view; `per_chain` adds the
        label -> {replays, splits, launches_saved} breakdown."""
        with self._lock:
            attempts = self.fused_replays + self.fallback_splits
            out = {
                "chains_detected": self.chains_detected,
                "chains_stitched": self.chains_stitched,
                "fused_replays": self.fused_replays,
                "fallback_splits": self.fallback_splits,
                "escapes": self.escapes,
                "launches_saved": self.launches_saved,
                "wall_time_saved_ms":
                    round(self.wall_time_saved_ns / 1e6, 3),
                "retraces": self.retraces,
                "evictions": self.evictions,
                "deactivated": self.deactivated,
                "replay_rate": round(self.fused_replays / attempts, 4)
                    if attempts else 0.0,
            }
            if per_chain:
                rows = dict(self.per_chain)
                out["chains"] = {
                    label: {"replays": r[0], "splits": r[1],
                            "launches_saved": r[2]}
                    for label, r in sorted(rows.items())}
            return out


CHAIN_STATS = ChainFusionStats()


def chain_fusion_stats(per_chain: bool = False) -> dict:
    """Current chain-fusion counters (see module docstring for field
    semantics)."""
    return CHAIN_STATS.snapshot(per_chain)


def reset_chain_fusion_stats():
    CHAIN_STATS.reset()
