"""Fusion flight recorder: a structured event timeline for the dispatch/
fusion pipeline.

The three fusion tiers (per-op executable cache → chain fusion → whole-step
promotion) are the dominant eager-performance variable, but their counter
structs (profiler/{dispatch,chain_fusion,step_fusion}.py) only say HOW OFTEN
something happened — never which op, which reason, or when. This module is
the missing "when/why" layer: a bounded, thread-aware ring buffer of typed
events, each carrying the op (or chain/step label), a cache-key digest, and
a machine-readable reason code. The reference Paddle ships a full Profiler
(HostTracer + CUPTI → chrome trace + summary tables) for its kernel
launches; this is the TPU-native analog for the fusion pipeline's
*decisions*.

Event categories (a public contract — tests assert the set):

  dispatch.hit / dispatch.miss / dispatch.bypass / dispatch.retrace
      per-op executable-cache outcomes (ops/dispatch.py)
  chain.detect / chain.compile / chain.fire / chain.split / chain.stitch
      op-chain fusion lifecycle (ops/fusion.py)
  step.record / step.promote / step.fire / step.split / step.deactivate
      whole-step promotion lifecycle (ops/step_fusion.py; `step.record`
      covers observation-side events: cycle boundaries, cycle poisons,
      eager tape backwards and optimizer steps)
  serve.enqueue / serve.admit / serve.step / serve.evict / serve.complete
      serving-engine request lifecycle (paddle_tpu/serving/engine.py):
      continuous-batching admission, the compiled decode step, KV-pool
      preemption, completion — with `kv_exhausted` / `bucket_retrace`
      reason codes
  serve.cancel / serve.expire / serve.refuse / serve.hang / serve.degrade
  / serve.resume
      serving resilience decisions (PR 7): client cancellation, deadline
      expiry (queued or running), bounded-queue/deadline/KV admission
      refusal, hung-step watchdog firings, degraded-mode transitions
      (recovery ladder rungs, eager decode fallback), and crash-resume
      re-admissions — with `client_cancel` / `deadline_expired` /
      `queue_full` / `deadline_infeasible` / `step_hang` / `decode_fault`
      / `crash_resume` reason codes

Reason codes (also a public contract) attribute every bypass/split/poison
to its cause — `rng_rekey` (the op consumed fresh global randomness and its
closure re-keys every call: dropout), `unkeyable_closure` (an array/Tensor
baked into the op fn), `mid_step_peek` (a pending value was read
mid-replay), `registry_bump`, `shape_mismatch`, ... — see REASON_CODES.
Coarse causes live in the reason code; free-form specifics (which op
blocked a chain, which cycle position poisoned) live in the event's
`detail` dict.

Cost contract: gated by FLAGS_profiler_events; when off, `emit()` is one
dict lookup and a return. When on, an emission is a tuple build
plus a lock-guarded seq increment + deque append (unique seq across
threads is what the Profiler's drain dedup keys on) — the ring
(FLAGS_profiler_events_capacity) never grows unbounded. Events are drained into chrome-trace lanes by the
Profiler (profiler/__init__.py) and aggregated into root-cause reports by
profiler/explain.py / tools/fusion_doctor.py.
"""
from __future__ import annotations

import threading
import time
from collections import deque

from ..framework.flags import _FLAGS

__all__ = ["EVENTS", "CATEGORIES", "REASON_CODES", "FusionEventLog",
           "fusion_events", "clear_fusion_events", "fusion_events_enabled",
           "events_summary"]


CATEGORIES = frozenset({
    "dispatch.hit", "dispatch.miss", "dispatch.bypass", "dispatch.retrace",
    "chain.detect", "chain.compile", "chain.fire", "chain.split",
    "chain.stitch",
    "step.record", "step.promote", "step.fire", "step.split",
    "step.deactivate",
    # serving-engine lifecycle (paddle_tpu/serving/engine.py): request
    # queued / joined the running batch (prefilled) / one compiled decode
    # step ran / preempted-evicted / finished-or-failed
    "serve.enqueue", "serve.admit", "serve.step", "serve.evict",
    "serve.complete",
    # serving resilience (PR 7): cancellation / deadline expiry /
    # admission refusal / hung-step watchdog / degraded-mode transition /
    # crash-resume re-admission
    "serve.cancel", "serve.expire", "serve.refuse", "serve.hang",
    "serve.degrade", "serve.resume",
    # multi-tenant serving (PR 17, serving/tenancy.py): a prefix-cache
    # admission aliased cached prompt KV / prefilled cold / cold entries
    # reclaimed under pool pressure; a live weight hot-swap committed
    "serve.prefix_hit", "serve.prefix_miss", "serve.prefix_evict",
    "serve.swap",
    # compiled stochastic sampling + pipelined decode (PR 18): a request
    # enqueued with a stochastic sampler config, or a speculative token
    # discarded at the commit-lag-1 boundary (reason commit_lag_rollback)
    "serve.sample",
    # persistent AOT executable cache (ops/aot_cache.py): warm-start
    # loads, cold misses, artifact writes, quarantined corruption,
    # environment-fingerprint skew, size/age eviction
    "aot.hit", "aot.miss", "aot.store", "aot.corrupt",
    "aot.version_skew", "aot.evict",
    # kernel tier (kernels/pallas/, nn/functional/attention.py): a
    # requested attention kernel variant was ineligible and fell back
    # (`kernel.fallback`, reason `kernel_fallback` — an ineligible shape
    # is VISIBLE, not silent); an engine whose KV cache runs quantized
    # stamps the informational `kernel.quantized` marker (reason
    # `kv_quantized`) so the fallback stream stays demotions-only
    "kernel.fallback", "kernel.quantized",
    # regression sentinel (profiler/sentinel.py, PR 19): armed/disarmed
    # transitions, one evaluation-window verdict per check (`sentinel.check`
    # is clean; `sentinel.drift` carries the attributed reason + drifted
    # metric in detail), and the recovery transition that clears the
    # /readyz degraded latch
    "sentinel.arm", "sentinel.check", "sentinel.drift", "sentinel.recover",
    # elastic fleet fabric (distributed/fabric.py, PR 20): a host joined
    # the fleet / was declared lost or left cleanly / the coordinator
    # published a new generation (survivors rebuild the mesh through the
    # mesh_mismatch split path) / a restarted host rendezvoused back at
    # the current generation and warm-started from the shared stores
    "fleet.join", "fleet.leave", "fleet.rebuild", "fleet.rejoin",
})

# Machine-readable causes. Stable across releases: the fusion doctor, the
# tests' "no unexplained splits" assertions, and downstream trace tooling
# key on these strings.
REASON_CODES = frozenset({
    # -- why a dispatch bypassed the executable cache ----------------------
    "unkeyable_closure",   # fn closes over an array/Tensor/stateful object
    "rng_rekey",           # stateful RNG closure re-key, or a hoisted-key
                           # replay saw a shifted stream position
    "tracer_input",        # input is a jax tracer (inside an outer trace)
    "cache_disabled",      # cache flag off or size 0
    "unjittable",          # negative-cached: the op cannot be jitted
    # -- why a chain/step replay split -------------------------------------
    "key_mismatch",        # next op's cache key diverged from the template
    "shape_mismatch",      # same op, different input avals
    "wiring_mismatch",     # dataflow wiring diverged from the template
    "registry_bump",       # a kernel override (de)activation re-keyed the op
    "mid_chain_escape",    # a chain intermediate was read before the fire
    "mid_step_peek",       # a pending step value was read before opt.step()
    "event_mismatch",      # backward/clear_grad/step event out of order
    "param_mismatch",      # parameter set/binding/buffer identity changed
    "optimizer_state_change",  # clip/regularizer/hyper-param/slot change
    "hook_present",        # tensor/grad/saved-tensor hooks block fusion
    "exec_fault",          # transient XLA execution fault during the fire
    "trace_fail",          # the fused executable failed to trace
    "debug_interrupt",     # NaN-scan/benchmark mode forced per-op dispatch
    "flag_off",            # a fusion flag flipped off mid-run
    # -- why a cycle could not promote (observation side) ------------------
    "uncached_dispatch",   # an op took the uncached path inside the cycle
    "multi_backward",      # irregular multi-backward cycle (regular grad
                           # accumulation promotes as a super-cycle)
    "cycle_too_long",      # cycle exceeded the recording cap
    "unpromotable_cycle",  # build-time qualification failed (see detail)
    "fail_streak",         # deactivated after repeated failed replays
    # -- step-guardian decisions (FLAGS_check_numerics, ops/guardian.py) ---
    "nonfinite_output",    # a forward output was non-finite (guardian check)
    "nonfinite_skip",      # non-finite grads: the update was a bitwise no-op
    "scaler_backoff",      # GradScaler shrank the loss scale after bad steps
    "injected_fault",      # a chaos-harness fault hook fired (tools/chaos.py)
    # -- serving-engine outcomes (paddle_tpu/serving/) ---------------------
    "kv_exhausted",        # KV block pool dry: eviction / admission refusal
    "bucket_retrace",      # a new prefill length bucket compiled
    # -- serving resilience decisions (paddle_tpu/serving/resilience.py) ---
    "client_cancel",       # cancel(request_id): the client gave up
    "deadline_expired",    # a request's TTL passed (queued or running)
    "queue_full",          # bounded waiting queue at max depth: refused
    "deadline_infeasible", # estimated wait/service exceeds the deadline
    "step_hang",           # a decode/prefill step blew the watchdog budget
    "decode_fault",        # the compiled decode faulted/was poisoned;
                           # requests fell back to eager generate()
    "crash_resume",        # an in-flight request re-admitted after restart
    # -- multi-tenant serving (paddle_tpu/serving/tenancy.py, PR 17) -------
    "prefix_hit",          # admission aliased cached prompt KV blocks:
                           # the shared prefill was paid once (benign)
    "adapter_mismatch",    # a request named an adapter the engine does
                           # not have registered: refused, never silently
                           # served base weights
    "torn_swap",           # a resume snapshot's weight CRC does not match
                           # the serving weights: restore refused rather
                           # than decode half a stream per weight set
    # -- compiled sampling + pipelined decode (serving/sampling.py, PR 18) -
    "sampler_mismatch",    # a sampler config outside the compiled
                           # program's contract (temperature < 0,
                           # top_p outside (0,1], ...): refused at the
                           # door, never a silent clamp or a retrace
    "commit_lag_rollback", # pipelined decode: a stream left its slot
                           # (cancel / expire / preempt / finish) between
                           # launch and the lag-1 commit — its one
                           # speculative token is discarded, by design
    # -- distributed step fusion (ops/spmd_fusion.py) ----------------------
    "collective_unkeyed",  # a collective's group/mesh has no canonical key
    "mesh_mismatch",       # cycle inputs span meshes, or a fired program's
                           # inputs moved to another mesh/layout
    "spmd_divergence",     # probation fire diverged from the eager step:
                           # the cycle violates the data-parallel pmean
                           # contract; demoted to the plain jit lowering
    "pipe_schedule_mismatch",  # a promoted pipeline program's schedule
                           # (micro-batch count / virtual stages /
                           # optimizer binding) changed for the same mesh
                           # + stage structure: a SECOND program compiles
                           # — expected at schedule boundaries, a perf
                           # bug when it churns every step
    # -- AOT executable store decisions (ops/aot_cache.py) -----------------
    "artifact_corrupt",    # torn/garbled artifact: quarantined + recompiled
    "version_skew",        # artifact built under another env fingerprint
    # -- kernel tier (kernels/pallas/, FLAGS_serve_attention_kernel) -------
    "kernel_fallback",     # requested kernel variant ineligible; demoted
    "kv_quantized",        # the engine's KV cache pool runs int8
    # -- promotion-safety static analyzer (paddle_tpu/analysis/, PR 15) ----
    # The fusion linter speaks THIS vocabulary: R1-R4 findings reuse the
    # runtime codes above (unkeyable_closure / rng_rekey / mid_step_peek /
    # collective_unkeyed — a static finding predicts the runtime split),
    # and two classes exist only statically:
    "contract_drift",      # a public contract surface went open: a
                           # REASON_CODES entry without a REASON_HINTS
                           # hint, a METRIC_NAMES entry without a
                           # METRIC_MERGE policy, an emitted category off
                           # CATEGORIES, an unregistered FLAGS_* read
    "lock_discipline",     # blocking I/O / callback invocation while
                           # holding a registry/scheduler lock, or an
                           # inconsistent lock acquisition order
    # -- regression sentinel verdicts (profiler/sentinel.py, PR 19) --------
    # One evaluation window's live record violated its baseline band;
    # the code names WHICH band so the supervisor/readyz consumer can
    # route without parsing prose:
    "perf_drift",          # goodput fraction / tokens-per-sec fell below
                           # the baseline floor
    "split_regression",    # a split/bypass/hang reason absent from the
                           # baseline histogram appeared (or exceeded its
                           # per-reason cap) in a steady window
    "compile_storm",       # dispatch/chain/step retraces or decode/prefill
                           # rebuilds exceeded the baseline allowance
    "latency_drift",       # step-time or serve p50/p99 left its band
    # R7 static twin (analysis/rules/r7_perf_contract.py): a perf meter
    # would silently lie — a heavy-compute @register_op invisible to
    # estimate_cycle_flops, or a program-altering FLAGS_* outside the AOT
    # env fingerprint with no fusion-neutral annotation
    "perf_contract",
    # -- elastic fleet fabric (distributed/fabric.py, PR 20) ---------------
    "host_lost",           # a member missed its full heartbeat lease: the
                           # coordinator declared it dead and bumped the
                           # fleet generation (a slow-but-alive host
                           # inside its lease never trips this)
    "mesh_rebuild",        # survivors adopted a new generation's fleet
                           # spec: the mesh is rebuilt and the promoted
                           # program re-promotes through the
                           # mesh_mismatch split path (checkpoint restore
                           # + AOT warm-start, seconds not a re-warmup)
    "stale_member",        # a host is heartbeating (alive) but still
                           # reports an OLDER generation than the fleet:
                           # it has not adopted the current spec yet —
                           # persistent staleness means its rebuild hook
                           # is wedged
})


class FusionEventLog:
    """The process-global ring. An emission is a tuple build plus a
    lock-guarded seq increment + deque append (the lock is only touched
    when the recorder is ON; the off path is a single flag check).
    `total` is a monotonic high-water mark used by the Profiler to drain
    only the events of its window — seq values must be unique across
    threads or the drain dedup would drop/double events, hence the lock
    rather than a bare `total += 1`."""

    __slots__ = ("_buf", "_lock", "total")

    def __init__(self):
        self._buf = deque(maxlen=self._capacity())
        self._lock = threading.Lock()
        self.total = 0

    @staticmethod
    def _capacity():
        try:
            cap = int(_FLAGS.get("FLAGS_profiler_events_capacity", 65536)
                      or 0)
        except (TypeError, ValueError):
            cap = 65536
        return max(cap, 1)

    @property
    def enabled(self):
        return bool(_FLAGS.get("FLAGS_profiler_events"))

    # -- emission (hot path) ------------------------------------------------
    def emit(self, cat, op="", key=None, reason=None, detail=None):
        """Record one event. No-op (one flag check) when the recorder is
        off. `key` is digested to a short stable hex string so raw cache
        keys (code objects, avals) never sit in the ring."""
        if not _FLAGS.get("FLAGS_profiler_events"):
            return
        row_tail = (threading.get_ident(), cat, op, _key_digest(key),
                    reason, detail)
        with self._lock:
            seq = self.total = self.total + 1
            self._buf.append((seq, time.perf_counter_ns()) + row_tail)

    # -- reading ------------------------------------------------------------
    def snapshot(self, category=None, since_seq=0):
        """Events as dicts, oldest first. `category` filters by exact
        category or by tier prefix ("chain" matches every chain.* event);
        `since_seq` returns only events emitted after that high-water
        mark."""
        rows = list(self._buf)
        out = []
        for seq, ts, tid, cat, op, key, reason, detail in rows:
            if seq <= since_seq:
                continue
            if category is not None and cat != category \
                    and not cat.startswith(category + "."):
                continue
            out.append({"seq": seq, "ts_ns": ts, "tid": tid, "cat": cat,
                        "op": op, "key": key, "reason": reason,
                        "detail": detail})
        return out

    def clear(self):
        """Drop every recorded event and re-apply the capacity flag."""
        with self._lock:
            self._buf = deque(maxlen=self._capacity())

    def __len__(self):
        return len(self._buf)


def _key_digest(key):
    if key is None:
        return None
    try:
        return format(hash(key) & 0xFFFFFFFFFFFF, "012x")
    except TypeError:
        return None


EVENTS = FusionEventLog()


def fusion_events(category=None, since_seq=0):
    """Snapshot of the fusion flight recorder (list of event dicts)."""
    return EVENTS.snapshot(category, since_seq)


def clear_fusion_events():
    EVENTS.clear()


def fusion_events_enabled():
    return EVENTS.enabled


def events_summary(events=None):
    """Aggregate a list of event dicts (default: the live ring) into the
    compact shape `tools/fusion_doctor.py` and the tests read:
    per-category counts plus (category, reason) split/bypass attribution."""
    if events is None:
        events = EVENTS.snapshot()
    by_cat: dict = {}
    reasons: dict = {}
    ops: dict = {}
    for e in events:
        cat = e["cat"]
        by_cat[cat] = by_cat.get(cat, 0) + 1
        r = e.get("reason")
        if r is not None:
            rk = f"{cat}:{r}"
            reasons[rk] = reasons.get(rk, 0) + 1
            ok = (cat, r, e.get("op") or "")
            ops[ok] = ops.get(ok, 0) + 1
    return {
        "events": len(events),
        "by_category": dict(sorted(by_cat.items())),
        "reasons": dict(sorted(reasons.items())),
        "by_op": {f"{c}:{r}:{o}": n
                  for (c, r, o), n in sorted(ops.items())},
    }
