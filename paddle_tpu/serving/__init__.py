"""Serving: continuous batching + paged KV cache + compiled decode.

The millions-of-users path of the north star (ROADMAP item 2), replacing
the reference's one-request-per-`AnalysisPredictor` serving model
(inference/api/analysis_predictor.h:95) with:

  * `LLMEngine`     — multi-tenant engine: ONE compiled decode-step
                      executable (fixed slot layout, donated pools, zero
                      retraces under stream churn), bucketed prefill,
                      streaming token callbacks (serving/engine.py);
  * `Scheduler`     — iteration-level (Orca-style) FCFS scheduling with
                      free-block watermark admission and preempt-resume
                      via block-table edits (serving/scheduler.py);
  * `PagedKVCache`  — the vLLM/PagedAttention block-pool memory model,
                      TPU-native (serving/cache.py), paired with
                      `nn.functional.paged_decode_attention`;
  * resilience      — deadlines/TTLs + `cancel()`, bounded-queue
                      backpressure (`ServeRefusal`), hung-step watchdog
                      (`FLAGS_serve_step_timeout_ms` + recovery ladder),
                      eager-fallback degraded mode, and crash-resumable
                      serving state (serving/resilience.py +
                      `incubate.checkpoint.ServeCheckpointer`).

Quick start::

    from paddle_tpu.serving import LLMEngine
    engine = LLMEngine(model, max_batch_size=8, block_size=16)
    outs = engine.generate([[5, 3, 9], [7, 1]], max_new_tokens=32)

Telemetry: `serve.*` events in the fusion flight recorder
(`FLAGS_profiler_events`), `engine.stats()` and the `fusion_doctor`
serving section; speed: the `serve_124m_backlog` cell (`PERF.md`).
"""
from __future__ import annotations

from .cache import (BlockAllocator, PagedKVCache, PagedCacheView,  # noqa: F401
                    scatter_prefill, NULL_BLOCK, pool_bytes_per_block,
                    num_blocks_for_bytes)
from .scheduler import (Request, Scheduler, QUEUED, RUNNING,  # noqa: F401
                        FINISHED, FAILED, CANCELLED, EXPIRED)
from .resilience import ServeRefusal, StepHang  # noqa: F401
from .tenancy import PrefixCache, AdapterSet  # noqa: F401
from .engine import LLMEngine, ServeStats  # noqa: F401

__all__ = ["LLMEngine", "ServeStats", "Request", "Scheduler",
           "PagedKVCache", "PagedCacheView", "BlockAllocator",
           "scatter_prefill", "NULL_BLOCK", "QUEUED", "RUNNING",
           "FINISHED", "FAILED", "CANCELLED", "EXPIRED",
           "ServeRefusal", "StepHang", "pool_bytes_per_block",
           "num_blocks_for_bytes", "PrefixCache", "AdapterSet"]
