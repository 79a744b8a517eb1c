"""AOT executable-store telemetry: counters for the persistent warm-start
cache (ops/aot_cache.py).

Mirrors the dispatch/chain/step counter structs: plain attribute bumps on
the hot path (GIL-protected enough for telemetry), a locked snapshot for
readers. The
flight recorder carries the per-decision story (`aot.{hit,miss,store,
corrupt,version_skew,evict}` events).

Counter semantics:
  hits            an executable was deserialized from the on-disk store
                  instead of being traced+compiled in this process
  misses          the store had no artifact for a requested key (cold)
  stores          artifacts serialized and atomically written
  store_failures  export/serialize attempts that failed (the live compiled
                  path is unaffected; the artifact is simply not written)
  corrupt         artifacts that failed CRC/deserialization and were
                  quarantined (the caller recompiled transparently)
  version_skew    artifacts present for the key but built under a
                  different environment fingerprint (never deserialized)
  evictions       artifacts removed by the size/age-bounded eviction
  bytes_written / bytes_loaded
                  cumulative artifact payload sizes
"""
from __future__ import annotations

import threading

__all__ = ["AotCacheStats", "STATS", "aot_cache_stats",
           "reset_aot_cache_stats"]


class AotCacheStats:
    __slots__ = ("_lock", "hits", "misses", "stores", "store_failures",
                 "corrupt", "version_skew", "evictions", "bytes_written",
                 "bytes_loaded")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.hits = 0
            self.misses = 0
            self.stores = 0
            self.store_failures = 0
            self.corrupt = 0
            self.version_skew = 0
            self.evictions = 0
            self.bytes_written = 0
            self.bytes_loaded = 0

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "stores": self.stores,
                "store_failures": self.store_failures,
                "corrupt": self.corrupt,
                "version_skew": self.version_skew,
                "evictions": self.evictions,
                "bytes_written": self.bytes_written,
                "bytes_loaded": self.bytes_loaded,
            }


STATS = AotCacheStats()


def aot_cache_stats() -> dict:
    """Current AOT executable-store counters (see module docstring for
    field semantics)."""
    return STATS.snapshot()


def reset_aot_cache_stats():
    STATS.reset()
