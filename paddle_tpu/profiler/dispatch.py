"""Eager dispatch telemetry: counters for the per-op executable cache.

Reference analog: the reference tracked per-op dispatch cost with
operators/benchmark/op_tester.cc + profiler/timer.py; here the eager funnel
(ops/dispatch.py) records cache behavior directly so retrace regressions
show up (`dispatch_cache_stats()`) without a profiler run.

Counter semantics:
  hits       cache key found — dispatch reused a compiled executable
  misses     key not found — a new executable was built (and traced on its
             first call)
  bypasses   cache enabled but the call was un-keyable (fn closes over
             arrays/Tensors, tracer inputs, jit-incompatible op) and took
             the uncached eager path
  retraces   actual jax traces of dispatch-owned executables (counted by a
             side effect that only runs while tracing — re-traces of an
             existing executable count too)
  evictions  LRU evictions past FLAGS_eager_op_cache_size
  calls / dispatch_time_ns
             number of call_op/call_op_multi invocations and their
             cumulative wall time (keying + cache lookup + device dispatch)

Counter bumps are plain attribute increments (GIL-protected enough for
telemetry); snapshot/reset take the lock so readers see a consistent view.
"""
from __future__ import annotations

import threading

__all__ = ["DispatchStats", "STATS", "dispatch_cache_stats",
           "reset_dispatch_cache_stats"]


class DispatchStats:
    __slots__ = ("_lock", "hits", "misses", "bypasses", "retraces",
                 "evictions", "calls", "dispatch_time_ns", "per_op")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.hits = 0
            self.misses = 0
            self.bypasses = 0
            self.retraces = 0
            self.evictions = 0
            self.calls = 0
            self.dispatch_time_ns = 0
            self.per_op = {}       # op name -> [hits, misses, bypasses]

    # -- hot-path bumps (no lock: a lost count is fine, a stall is not) ----
    def _op(self, name):
        rec = self.per_op.get(name)
        if rec is None:
            rec = self.per_op[name] = [0, 0, 0]
        return rec

    def hit(self, name):
        self.hits += 1
        self._op(name)[0] += 1

    def miss(self, name):
        self.misses += 1
        self._op(name)[1] += 1

    def bypass(self, name):
        self.bypasses += 1
        self._op(name)[2] += 1

    # -- reading -----------------------------------------------------------
    def snapshot(self, per_op: bool = False) -> dict:
        """A JSON-ready view of the counters; `per_op` adds the
        name -> {hits, misses, bypasses} breakdown."""
        with self._lock:
            keyed = self.hits + self.misses
            out = {
                "hits": self.hits,
                "misses": self.misses,
                "bypasses": self.bypasses,
                "retraces": self.retraces,
                "evictions": self.evictions,
                "calls": self.calls,
                "hit_rate": round(self.hits / keyed, 4) if keyed else 0.0,
                "dispatch_time_ms": round(self.dispatch_time_ns / 1e6, 3),
            }
            if per_op:
                # dict() is a single C-level copy (safe against concurrent
                # lock-free writers); iterating self.per_op directly is not
                rows = dict(self.per_op)
                out["ops"] = {n: {"hits": r[0], "misses": r[1],
                                  "bypasses": r[2]}
                              for n, r in sorted(rows.items())}
            return out


STATS = DispatchStats()


def dispatch_cache_stats(per_op: bool = False) -> dict:
    """Current eager-dispatch cache counters (see module docstring for the
    field semantics)."""
    return STATS.snapshot(per_op)


def reset_dispatch_cache_stats():
    STATS.reset()
