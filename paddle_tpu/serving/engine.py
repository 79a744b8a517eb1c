"""Continuous-batching serving engine: ONE compiled decode step for every
tenant mix.

Reference analog: the reference's serving story is `AnalysisPredictor`
replaying a `fused_multi_transformer` program per request
(inference/api/analysis_predictor.h:95) — static batch, dense caches.
This engine is that layer rebuilt for the north star ("heavy traffic from
millions of users"), combining:

  * a **paged KV cache** (serving/cache.py): one preallocated block pool
    shared by every sequence, per-sequence block tables, admission /
    eviction / preemption as integer-table edits;
  * a **compiled decode step**: a single `jax.jit` executable over a
    fixed max-batch slot layout — ``(slots [S, M + 4], feedback [S],
    sampler [S, 5], history [S, C], k_pools, v_pools, ...) ->
    (next_tokens, rows, sampler, history, new_pools, ...)`` with the
    cache's buffers donated, however many it has
    (`PagedKVCache.buffers()`: the two pools, an int8 pool's scales, a
    per-slot state of layers that are not attention). `slots` is the ONE
    host array of a launch (block tables and the columns tokens,
    override, seq_lens, active), `rows` the ONE array the host fetches
    (token, logprob, panel, the model's counters); the sampler's table
    and history live on the device and are threaded from program to
    program (a
    slot's input is the host's token where `override` is set, else the
    launch before's or its own prefill's, still on the device). Requests
    joining or leaving the batch only change the *values* of the integer
    inputs, never a shape:
    the decode program compiles exactly once and then serves every token
    of every stream (`stats()["decode_compiles"]`, held at 1 by
    tests/test_serving.py);
  * **bucketed prefill**: prompts are right-padded to power-of-two
    length buckets, so admitting a new request compiles at most
    ``log2(max_context)`` prefill programs ever — and never touches the
    decode executable (`bucket_retrace` in the flight recorder marks
    each new bucket). A prefill is launched and not awaited: it hands
    its token to the next decode launch on the device, and a boundary's
    first tokens reach the host in one fetch, a step later;
  * a **continuous-batching scheduler** (serving/scheduler.py): FCFS +
    free-block watermark admission, LIFO preempt-resume via block
    tables, join/leave at token boundaries;
  * **streaming detokenization**: per-request `on_token` callbacks fire
    when a token is committed (optionally through a tokenizer's
    `decode`), not when the request completes: one `step()` after the
    token's launch (decode or prefill), the loop being pipelined at lag
    1 (see `LLMEngine`);
  * a **kernel tier** (PR 11): the decode step's paged attention runs
    streaming softmax over the pages that hold tokens
    (kernels/pallas/paged_attention.py: a Pallas kernel on a TPU over an
    fp pool on the tiles, a length-bounded pure-JAX loop elsewhere, chosen by
    `resolve_paged_kernel`; `attention_kernel=` /
    FLAGS_serve_attention_kernel name one explicitly)
    instead of gathering a dense `[S, T, H, D]` context, and
    `kv_dtype="int8"` halves KV bytes per token via per-block-per-head
    scales (quantization/kv_cache.py) so the same pool admits ~2x the
    streams — both keyed into the dispatch cache and the AOT
    fingerprint, attributed via `kernel.fallback` / `kv_quantized`.

Resilience (PR 7, serving/resilience.py) rides every one of those layers:

  * **deadlines + cancellation** — `add_request(..., ttl_s=)` arms a
    per-request deadline checked at admission and at every iteration
    boundary; `cancel(request_id)` reclaims a stream the client gave up
    on. Expired/cancelled slots are VALUE edits to the fixed layout —
    the decode executable still compiles exactly once;
  * **bounded-queue backpressure** — `max_queue_depth` + an
    estimated-wait feasibility check refuse doomed work early with a
    structured `ServeRefusal` (`queue_full` / `deadline_infeasible` /
    `kv_exhausted`) instead of queueing it to rot, and the scheduler's
    aging guard keeps LIFO preemption from starving a long request;
  * **hung-step watchdog** — decode/prefill fires resolve through a
    monitored completion bounded by `FLAGS_serve_step_timeout_ms`; a
    stuck step emits `serve.hang`, marks the engine degraded, and climbs
    a recovery ladder instead of wedging: a wait at a commit (a
    launch's, or a boundary's prefills') is retried once and then fails
    the active requests with attributed reasons;
  * **degraded-mode fallback** — a faulting/poisoned compiled decode
    finishes its in-flight streams per-request through the eager
    `generate()` path, token-identically, then rebuilds;
  * **crash-resume** — `state_payload()` / `restore_state()` snapshot
    the request/scheduler state (prompts, emitted tokens, arrival order
    — never the KV pool) so a kill-9'd server restarts and finishes
    every stream byte-identically (incubate.checkpoint.ServeCheckpointer
    + tools/chaos.py `serve_kill`).

Multi-tenancy (PR 17, serving/tenancy.py) makes the replica serve MANY
logical models and MANY users off the one compiled decode step:

  * **shared-prefix KV reuse** — `enable_prefix_cache=True` indexes
    every prefilled prompt's blocks by content hash; N streams sharing
    a system prompt alias the same refcounted blocks (admission
    allocates only the private remainder), pay its prefill once, and
    copy-on-write the first block a divergent token would land in;
  * **batched LoRA-style adapters** — `max_adapters=N` installs padded
    per-slot low-rank delta stacks as VALUE inputs to the decode
    executable; tenants join/leave/churn with zero retraces
    (`add_request(..., adapter=name)`, `register_adapter` /
    `unregister_adapter`);
  * **live weight hot-swap** — `hot_swap=True` passes the base weights
    as values too, so `swap_weights(new_values)` cuts every stream over
    to a new checkpoint at an exact iteration boundary (in-flight
    streams are preempted and re-prefill under the new weights, the
    prefix index is invalidated, the weight epoch bumps) — again zero
    retraces, attributed as `serve.swap`.

Telemetry rides the PR 4 fusion flight recorder: `serve.*` events
(enqueue/admit/step/evict/complete + cancel/expire/refuse/hang/degrade/
resume) with reason codes `kv_exhausted` / `bucket_retrace` /
`client_cancel` / `deadline_expired` / `queue_full` /
`deadline_infeasible` / `step_hang` / `decode_fault` / `crash_resume`,
aggregated by `profiler.explain` / `tools/fusion_doctor`.
"""
from __future__ import annotations

import logging
import math
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework.autograd import set_grad_enabled
from ..framework.flags import _FLAGS
from ..profiler import RecordEvent, watch_gc
from ..profiler.events import EVENTS as _EVENTS
from ..profiler.metrics import LogHistogram, SERVE as _M, \
    enabled as _metrics_on
from ..profiler import goodput as _goodput
from ..profiler import telemetry_server as _telemetry
from ..profiler import sentinel as _sentinel
from ..kernels.pallas.paged_attention import (blockwise_streamed_entries,
                                               pallas_copied_pages)
from .cache import (PagedKVCache, PagedCacheView, scatter_prefill,
                    scatter_window_prefill, _is_int8)
from .scheduler import (Request, Scheduler, RUNNING, FINISHED,
                        FAILED, CANCELLED, EXPIRED)
from .resilience import (ServeRefusal, MonitoredWait, StepHang,
                         request_payload, payload_request)
from .tenancy import PrefixCache, AdapterSet
from .sampling import SAMPLER_VERSION, validate_sampler, default_seed, \
    sample_tokens

__all__ = ["LLMEngine", "ServeStats"]

# recent step-time samples averaged into the admission-time wait estimate
_EST_WINDOW = 32

_MIN_BUCKET = 8
# the longest bucket that is a power of two (`LLMEngine._bucket_for`)
_LINEAR_BUCKETS_FROM = 8192

# WHAT CROSSES BETWEEN HOST AND DEVICE IN ONE PROGRAM CALL: one int32
# array in, one int32 array out (floats by their bits); everything else a
# program reads is on the device already. A decode launch takes `[S,
# max_blocks_per_seq + 4]`: the block tables, then these columns. A
# prefill takes `[bucket + max_blocks_per_seq + 8]`: the padded ids, the
# block row, then length, slot and the request's row of the sampler's
# table (`_SAMPLER_NOOP`'s columns), one value spare
_COL_TOKENS, _COL_OVERRIDE, _COL_LENS, _COL_ACTIVE = range(4)
_PREFILL_TAIL = 8
# the sampler's table, a row a slot: temperature, top k, top p, repetition
# penalty, seed. This row (0, 0, 1, 1, 0) samples nothing: a program reads
# it for every slot that is not active, which keeps a batch of greedy
# streams on the cheap branch of `sample_tokens` whatever a departed
# request left in the table
_SAMPLER_NOOP = np.array([0.0, 0, 1.0, 1.0, 0], np.float32).view(np.int32)

# the slow-step rule (`ServeStats`' docstring): a step is reported when it
# is over this many times the window's mean step AND over this many
# seconds, once the window holds this many steps, at most once a period
_SLOW_STEP_FACTOR = 20.0
_SLOW_STEP_MIN_S = 0.25
_SLOW_STEP_MIN_STEPS = 100
_SLOW_STEP_LOG_PERIOD_S = 1.0
_LOG = logging.getLogger("paddle_tpu.serving")


class ServeStats:
    """Engine counters + step-latency histograms. `decode_compiles` is
    incremented INSIDE the traced decode function (the side effect runs
    only while tracing), so it counts real XLA traces — the zero-retrace
    guard reads it directly.

    Latency percentiles come from bounded log-bucket streaming
    histograms (profiler/metrics.py LogHistogram): O(1) memory however
    long the engine runs, and FRESH — the old raw `step_times_s` list
    stopped appending at 100k samples, silently freezing p50/p99 for the
    rest of the process's life. `step_times_s` survives as a short
    recent-sample list (the admission-time wait estimate reads it).

    THE HOST'S TURN. Every span a `step()` opens (`PHASES`), and
    `engine.gc`, a collection of the Python heap wherever it falls
    (`profiler.watch_gc`), owns a histogram in `phase`, fed when the span
    closes; `reset()` starts them anew, `engine.compile` excepted. What
    `snapshot()` makes of them, `<short>` being a span's name without
    `engine.` and with `_` for `.` (`prefill_dispatch`, `gc`, ...):

    `<short>_ms_per_step`
        1e3 x the histogram's exact sum / `steps` (no bucket)
    `<short>_max_ms`
        the longest single span of the window
    `step_unattributed_ms_per_step`
        `engine.step` less its direct children (`DIRECT`), never
        negative: over a tenth of a step, a span is missing. `engine.gc`
        lies inside other spans and is not taken off
    `host_wait_ms_per_step`
        `engine.decode.wait` + `engine.prefill.wait`: the host blocked
        on the device
    `gc_collections`
        collections the window saw, of every generation
    `dispatches`, `dispatches_device_idle`, `dispatches_ran_dry`;
    each also `prefill_…`, `decode_…`
        program calls after a program's first; those that found the
        result of the program dispatched before them ready at ENTRY:
        the device's queue was empty and stayed so until the call
        landed; and those that found it ready only at RETURN: the
        queue ran dry while the host was inside the call
        (`LLMEngine._call_program` asks `is_ready()`, no wait)
    `starved_dispatch_share`, `ran_dry_dispatch_share`; `prefill_…`,
    `decode_…`
        each over `dispatches`: how often the host kept the device
        waiting, from before the call or during it, and for which
        program
    `slowest_step`
        `{index, seconds, phases: {span: seconds in that step}, gc_s}`
        of the window's longest `step()`, or None; one record, replaced
    `decode_host_arrays`, `prefill_host_arrays`
        host values (numpy arrays and scalars) among the arguments of
        the programs called, summed over the calls: over `decode_launches`
        and `prefills`, 1.0 each (the one packed array) in a window in
        which no call uploaded the sampler's record (`state_uploads`: the
        calls that did, two host arrays more each)
    `decode_fetched_arrays`
        arrays brought to the host for the launches' results: one a
        launch

    THE SLOW-STEP RULE. A `step()` that took BOTH over `_SLOW_STEP_FACTOR`
    (20) x the mean of the window's steps before it and over
    `_SLOW_STEP_MIN_S` (0.25 s) writes one WARNING to the logger
    `paddle_tpu.serving` (the step's index, seconds, three largest phases
    and GC seconds): never for a step that opened `engine.compile`, nor
    before the window holds `_SLOW_STEP_MIN_STEPS` (100) steps, and at
    most once in `_SLOW_STEP_LOG_PERIOD_S` (1 s). An ordinary step pays
    one read of each histogram's sum and one comparison."""

    # the spans a `step()` opens, each with a histogram fed on exit; the
    # shares and per-step times of `snapshot()` come from their sums
    PHASES = ("engine.step", "engine.admit", "engine.prefill",
              "engine.prefill.dispatch", "engine.kv_grow",
              "engine.prefill.commit", "engine.prefill.wait",
              "engine.decode", "engine.decode.dispatch",
              "engine.decode.wait", "engine.decode.fetch", "engine.stream")
    # those that lie directly under `engine.step`
    DIRECT = ("engine.admit", "engine.kv_grow", "engine.prefill.commit",
              "engine.decode", "engine.stream")
    GC_SPAN = "engine.gc"
    KINDS = ("prefill", "decode")

    def __init__(self):
        # first calls of programs (`engine.compile`): what the process
        # paid since the engine was built, so no window resets it
        self.compile_hist = LogHistogram()
        self.reset()

    def reset(self):
        """Zero the counters IN PLACE: the compiled decode/prefill
        closures hold a reference to this object (that is how
        decode_compiles counts real traces), so a bench warmup resets the
        window without losing retrace visibility."""
        self.phase = {name: LogHistogram()
                      for name in self.PHASES + (self.GC_SPAN,)}
        watch_gc(self.GC_SPAN, self.phase[self.GC_SPAN])
        # in the order of `_at_entry`, which `step_begin` fills
        self._timed = tuple(self.phase.items())
        self._at_entry = None
        self.phase["engine.compile"] = self.compile_hist
        self.slowest_step = None
        self._warned_at = None
        # program calls after a program's first, by kind; those that
        # found the device's queue empty; those that saw it run dry
        # (`count_dispatch`)
        self.dispatches = dict.fromkeys(self.KINDS, 0)
        self.dispatches_device_idle = dict.fromkeys(self.KINDS, 0)
        self.dispatches_ran_dry = dict.fromkeys(self.KINDS, 0)
        # host values among the arguments of the programs called, by kind;
        # the calls that uploaded the host's record of the sampler's
        # table and history; arrays fetched for the launches' results
        self.host_arrays = dict.fromkeys(self.KINDS, 0)
        self.state_uploads = 0
        self.decode_fetched_arrays = 0
        self.steps = 0
        self.tokens_generated = 0
        self.prefills = 0
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self.admitted = 0
        self.evictions = 0
        self.completed = 0
        self.failed = 0
        self.refused = 0
        # resilience counters (serving/resilience.py semantics)
        self.refused_queue_full = 0
        self.refused_deadline = 0
        self.cancelled = 0
        self.expired = 0
        self.hangs = 0
        self.eager_fallbacks = 0
        self.resumed = 0
        self.occupancy_sum = 0.0
        self.saturated_steps = 0
        self.saturated_occupancy_sum = 0.0
        # multi-tenant counters (PR 17): prefix_prompt_tokens is the
        # hit-rate denominator — every admitted context token that COULD
        # have aliased cached KV, hit or not
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        self.prefix_evictions = 0
        self.cow_copies = 0
        self.adapter_switches = 0
        self.weight_swaps = 0
        # compiled stochastic sampling + pipelined decode (PR 18):
        # sampled_tokens counts committed tokens from slots decoding with
        # temperature > 0 (greedy slots are the same program, different
        # values); commit_rollbacks counts speculative tokens a lag-1
        # commit discarded because the slot's request was cancelled /
        # expired / preempted / finished between launch and commit
        self.sampled_tokens = 0
        self.commit_rollbacks = 0
        # decode launches, and those issued while the launch before them
        # was still uncommitted: the host's turn after such a launch ran
        # beside the device
        self.launches = 0
        self.launches_overlapped = 0
        # prefills whose results the host had not touched when the decode
        # launch that feeds on their tokens was dispatched (not a bucket's
        # first call, which is committed where it stands)
        self.prefills_unawaited = 0
        # decode attention, in block-table entries summed over the decode
        # launches: what the attention's loop reads or its kernel copies,
        # what held a token, and slots x entries (kernels/pallas/
        # paged_attention.py blockwise_streamed_entries /
        # pallas_copied_pages, counted on the host every launch)
        self.attn_entries_streamed = 0
        self.attn_entries_held = 0
        self.attn_entries_total = 0
        # the tokens the active slots attended to, summed over the
        # launches (each slot's context and its new token): exact, where
        # the entries above count whole pages
        self.attn_tokens_held = 0
        # the same of ONE window layer's ring (serving/cache.py
        # `CacheSpec`), in pages summed over the launches: what the
        # attention read, and the pages the slots' windows lie in
        self.window_pages_streamed = 0
        self.window_pages_held = 0
        self.window_tokens_held = 0
        # tokens the programs were given: a prefill's context, a decode
        # launch's active slots
        self.prefill_tokens = 0
        self.decode_tokens = 0
        # the width of every prefill's bucket, beside `prefill_tokens`:
        # what the programs multiplied, padding included
        self.prefill_bucket_tokens = 0
        # what the model's own forward counted (an expert block's
        # routing), summed over the calls of a phase: "<phase>_<name>",
        # and "<phase>_counted" calls; read beside a launch's tokens
        self.model_counts = {}
        # recent raw samples only (the admission wait estimate averages
        # the tail); percentiles live in the windowed histograms below
        self.step_times_s = []
        self.step_hist = LogHistogram()
        self.ttft_hist = LogHistogram()
        self.inter_token_hist = LogHistogram()
        self.queue_wait_hist = LogHistogram()
        self.wall_t0 = None
        self.wall_t1 = None

    def count_model(self, phase, names, values):
        counts = self.model_counts
        counts[phase + "_counted"] = counts.get(phase + "_counted", 0) + 1
        for name, value in zip(names, values.tolist()):
            key = f"{phase}_{name}"
            counts[key] = counts.get(key, 0) + value

    def observe_step(self, active, num_slots, demand, dt_s):
        self.steps += 1
        occ = active / num_slots
        self.occupancy_sum += occ
        if demand >= num_slots:
            self.saturated_steps += 1
            self.saturated_occupancy_sum += occ
        self.step_times_s.append(dt_s)
        if len(self.step_times_s) > 4 * _EST_WINDOW:
            del self.step_times_s[:-_EST_WINDOW]
        self.step_hist.observe(dt_s)

    def count_dispatch(self, kind, ready_at_entry, ready_at_return):
        """One call of a `kind` program, by whether the result of the
        program dispatched before it was ready when the call began and
        when it returned."""
        self.dispatches[kind] += 1
        if ready_at_entry:
            self.dispatches_device_idle[kind] += 1
        elif ready_at_return:
            self.dispatches_ran_dry[kind] += 1

    def step_begin(self):
        """`step()` entry: where every phase's sum stands."""
        self._at_entry = [hist.sum for _, hist in self._timed] \
            + [self.compile_hist.count]

    def step_end(self):
        """`step()` exit, its span closed: keep the step's anatomy if it
        is the window's longest, and report it under the slow-step rule."""
        entry, self._at_entry = self._at_entry, None
        if entry is None:       # the window was reset inside the step
            return
        whole = self.phase["engine.step"]
        seconds = whole.sum - entry[0]
        slowest = self.slowest_step
        longest = slowest is None or seconds > slowest["seconds"]
        slow = seconds > _SLOW_STEP_MIN_S
        if not (longest or slow):
            return
        spent = {name: hist.sum - was
                 for (name, hist), was in zip(self._timed, entry)}
        gc_s = spent.pop(self.GC_SPAN)
        before = whole.count - 1        # the window's steps before this
        if longest:
            self.slowest_step = {"index": before, "seconds": seconds,
                                 "phases": spent, "gc_s": gc_s}
        if not slow or before < _SLOW_STEP_MIN_STEPS \
                or self.compile_hist.count != entry[-1]:
            return
        mean = entry[0] / before
        if seconds <= _SLOW_STEP_FACTOR * mean:
            return
        now = time.monotonic()
        if self._warned_at is not None \
                and now - self._warned_at < _SLOW_STEP_LOG_PERIOD_S:
            return
        self._warned_at = now
        largest = sorted((n for n in spent if n != "engine.step"),
                         key=spent.get, reverse=True)[:3]
        _LOG.warning(
            "engine step %d took %.3f s where the window's mean is %.6f s: "
            "%s; gc %.3f s", before, seconds, mean,
            ", ".join(f"{n} {spent[n]:.3f} s" for n in largest), gc_s)

    def snapshot(self):
        def pct(p):
            return self.step_hist.percentile(p)

        elapsed = None
        if self.wall_t0 is not None and self.wall_t1 is not None:
            elapsed = self.wall_t1 - self.wall_t0

        def share(seconds):
            return seconds / elapsed if elapsed else 0.0

        phase = self.phase
        inside = sum(phase[n].sum for n in (
            "engine.prefill", "engine.decode", "engine.stream"))

        def ms_per_step(seconds):
            return 1e3 * seconds / self.steps if self.steps else 0.0

        turn = {}
        for name, hist in self._timed:
            short = name.split(".", 1)[1].replace(".", "_")
            turn[short + "_ms_per_step"] = ms_per_step(hist.sum)
            turn[short + "_max_ms"] = 1e3 * (hist.max or 0.0)
        for prefix, kinds in [("", self.KINDS)] \
                + [(k + "_", (k,)) for k in self.KINDS]:
            asked = sum(self.dispatches[k] for k in kinds)
            idle = sum(self.dispatches_device_idle[k] for k in kinds)
            dry = sum(self.dispatches_ran_dry[k] for k in kinds)
            turn[prefix + "dispatches"] = asked
            turn[prefix + "dispatches_device_idle"] = idle
            turn[prefix + "dispatches_ran_dry"] = dry
            turn[prefix + "starved_dispatch_share"] = \
                idle / asked if asked else 0.0
            turn[prefix + "ran_dry_dispatch_share"] = \
                dry / asked if asked else 0.0
        return {
            "steps": self.steps,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "decode_compiles": self.decode_compiles,
            "prefill_compiles": self.prefill_compiles,
            "admitted": self.admitted,
            "evictions": self.evictions,
            "completed": self.completed,
            "failed": self.failed,
            "refused": self.refused,
            "refused_queue_full": self.refused_queue_full,
            "refused_deadline": self.refused_deadline,
            "cancelled": self.cancelled,
            "expired": self.expired,
            "hangs": self.hangs,
            "eager_fallbacks": self.eager_fallbacks,
            "resumed": self.resumed,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_hit_rate": (self.prefix_hit_tokens
                                / self.prefix_prompt_tokens
                                if self.prefix_prompt_tokens else 0.0),
            "prefix_evictions": self.prefix_evictions,
            "cow_copies": self.cow_copies,
            "adapter_switches": self.adapter_switches,
            "weight_swaps": self.weight_swaps,
            "sampled_tokens": self.sampled_tokens,
            "commit_rollbacks": self.commit_rollbacks,
            "pipelined_launch_share": (
                self.launches_overlapped / self.launches
                if self.launches else 0.0),
            "prefill_unawaited_share": (
                self.prefills_unawaited / self.prefills
                if self.prefills else 0.0),
            # share of the block table the decode attention read / that
            # held tokens (streamed == held is the ideal, 1.0 a loop over
            # the whole table)
            "attn_streamed_share": (
                self.attn_entries_streamed / self.attn_entries_total
                if self.attn_entries_total else 0.0),
            "attn_held_share": (
                self.attn_entries_held / self.attn_entries_total
                if self.attn_entries_total else 0.0),
            "attn_tokens_held": self.attn_tokens_held,
            # a window layer's ring: pages read over the pages the
            # windows lie in (1.0: the window and no more), and the
            # tokens inside the windows
            "window_pages_streamed": self.window_pages_streamed,
            "window_pages_held": self.window_pages_held,
            "window_tokens_held": self.window_tokens_held,
            "window_streamed_share": (
                self.window_pages_streamed / self.window_pages_held
                if self.window_pages_held else 0.0),
            "prefill_tokens": self.prefill_tokens,
            "prefill_bucket_tokens": self.prefill_bucket_tokens,
            "prefill_padding_tokens": (self.prefill_bucket_tokens
                                       - self.prefill_tokens),
            "decode_tokens": self.decode_tokens,
            "decode_launches": self.launches,
            "decode_host_arrays": self.host_arrays["decode"],
            "prefill_host_arrays": self.host_arrays["prefill"],
            "state_uploads": self.state_uploads,
            "decode_fetched_arrays": self.decode_fetched_arrays,
            **self.model_counts,
            "occupancy_mean": (self.occupancy_sum / self.steps
                               if self.steps else 0.0),
            "occupancy_saturated": (
                self.saturated_occupancy_sum / self.saturated_steps
                if self.saturated_steps else 0.0),
            "p50_step_ms": pct(50) * 1e3,
            "p99_step_ms": pct(99) * 1e3,
            # request-latency percentiles (PR 12): TTFT (enqueue ->
            # first token), inter-token gap, and admission queue wait,
            # all from the same bounded windowed histograms
            "ttft_p50_ms": self.ttft_hist.percentile(50) * 1e3,
            "ttft_p99_ms": self.ttft_hist.percentile(99) * 1e3,
            "inter_token_p50_ms":
                self.inter_token_hist.percentile(50) * 1e3,
            "inter_token_p99_ms":
                self.inter_token_hist.percentile(99) * 1e3,
            "queue_wait_p50_ms":
                self.queue_wait_hist.percentile(50) * 1e3,
            "queue_wait_p99_ms":
                self.queue_wait_hist.percentile(99) * 1e3,
            # host phases of step(), from the engine's own spans
            "prefill_p50_ms": phase["engine.prefill"].percentile(50) * 1e3,
            "decode_dispatch_p50_ms":
                phase["engine.decode.dispatch"].percentile(50) * 1e3,
            "prefill_share": share(phase["engine.prefill"].sum),
            "decode_share": share(phase["engine.decode"].sum),
            "stream_share": share(phase["engine.stream"].sum),
            # step() outside those three: admission bookkeeping, the
            # scheduler, KV growth, housekeeping
            "step_self_share": share(
                max(0.0, phase["engine.step"].sum - inside)),
            # the host's turn, phase by phase (the class docstring's table)
            **turn,
            "step_unattributed_ms_per_step": ms_per_step(max(
                0.0, phase["engine.step"].sum
                - sum(phase[n].sum for n in self.DIRECT))),
            "host_wait_ms_per_step": ms_per_step(
                phase["engine.decode.wait"].sum
                + phase["engine.prefill.wait"].sum),
            "gc_collections": phase[self.GC_SPAN].count,
            "slowest_step": self.slowest_step,
            "compile_s": self.compile_hist.sum,
            "elapsed_s": elapsed,
            "tokens_per_sec": (self.tokens_generated / elapsed
                               if elapsed else 0.0),
        }


class LLMEngine:
    """Multi-tenant autoregressive serving over a decoder-only model.

    Usage::

        engine = LLMEngine(model, max_batch_size=8, block_size=16)
        engine.add_request([1, 2, 3], max_new_tokens=32,
                           on_token=lambda req, tok, text: ...)
        while engine.step():
            pass                      # or engine.run()

    Decoding is greedy (matches ``model.generate(do_sample=False)``
    token-for-token — the parity contract tests/test_serving.py pins).
    The model is put in eval mode and asked what it caches for a token
    (`model.cache_spec()`, serving/cache.py `CacheSpec`: per-head keys
    and values, or one latent row; and, for layers that are not
    attention and still keep something of the past, a fixed per-slot
    state beside the pools): the pools, the state, the prefill's empty
    caches and the attention plan come from that description, and the
    engine holds no head size of its own. A per-slot state follows
    `CacheSpec`'s rule in every loop: a prefill writes its slot's state
    whole, as it stands at the prompt's true length, a decode launch
    shifts the active slots' where it lies, so slot reuse, eviction,
    resume and `restore_state()` need nothing of their own (each
    re-prefills). By default the parameters are
    BAKED into the compiled programs as constants; a model whose class
    sets `serve_weights_as_arguments` (one too large for that) has them
    passed as arguments instead, and one whose class names
    `serve_counter_names` has its forward's counters (an expert block's
    routing) summed into `stats()`. `hot_swap=True` and/or
    `max_adapters>0` switch the programs to the multi-tenant signature
    (serving/tenancy.py): the weights / adapter stacks become VALUE
    inputs, so `swap_weights()` refreshes the base checkpoint mid-traffic
    and tenants churn adapters with zero retraces.
    `enable_prefix_cache=True` adds shared-prefix KV block aliasing with
    copy-on-write — N streams sharing a system prompt pay its prefill
    and its KV bytes once.

    The loop is pipelined at lag 1: a `step()` launches decode N+1 from
    launch N's tokens where they are, on the device, and only then
    commits launch N, so the host's turn
    (callbacks, retirement, the caller's own work between steps, the next
    admission) runs beside the decode program and not after it. For a
    caller that means: a token's `on_token` runs one `step()` after the
    step that launched it (a request's first token, sampled by its
    prefill, too: the prefill is launched and not awaited, and the decode
    launch of the admitting step takes the token from the device); a
    stream that is cancelled, expires or is preempted loses at most the
    tokens it had in flight (`stats()["commit_rollbacks"]`), and one that
    ends by `max_new_tokens` loses none; a finished request's slot is refilled one
    boundary later; `step()` keeps returning True until the last launch
    is committed. WHICH tokens are served is what one request at a time
    through the model's own dense forward is served
    (tests/serving_reference.py).
    """

    def __init__(self, model, max_batch_size=8, block_size=16,
                 num_blocks=None, max_context=None, watermark_blocks=None,
                 dtype=None, tokenizer=None, max_queue_depth=None,
                 aging_max_preemptions=3, kv_dtype=None,
                 attention_kernel=None, enable_prefix_cache=False,
                 max_adapters=0, adapter_rank=4, hot_swap=False,
                 logprobs_topk=0):
        cfg = model.config
        model.eval()
        self._model = model
        self._tokenizer = tokenizer
        self.max_batch_size = int(max_batch_size)
        self.block_size = int(block_size)
        self.max_context = int(max_context
                               or cfg.max_position_embeddings)
        self.max_blocks_per_seq = math.ceil(self.max_context
                                            / self.block_size)
        if num_blocks is None:
            # default: every slot can reach max_context (+ null block)
            num_blocks = 1 + self.max_batch_size * self.max_blocks_per_seq
        self._num_blocks = num_blocks
        if dtype is None:
            params = model.parameters()
            dtype = params[0]._value.dtype if params else jnp.float32
        self._dtype = dtype
        # the model describes what it caches for a token (per-head keys
        # and values, or one latent row); the pools, the attention plan
        # and the prefill's empty caches all come from that description
        spec = model.cache_spec()
        # kv_dtype="int8" stores the pool quantized (per-block-per-head
        # scales, quantization/kv_cache.py) — half the bytes per cached
        # token, so the same pool admits ~2x the streams
        self._kv_dtype = dtype if kv_dtype is None else (
            jnp.int8 if _is_int8(kv_dtype) else kv_dtype)
        self._kv_quantized = _is_int8(self._kv_dtype)
        # resolve the attention variant ONCE: the compiled decode step
        # bakes it in (zero retraces under churn); a flag flip only
        # affects engines built after it
        from ..nn.functional.attention import resolve_paged_kernel
        # what a kind of cache does not do yet is refused by name, here:
        # none of it may run silently wrong. (`CacheSpec` says why of a
        # state and of a ring: a prefix hit skips the prefill that writes
        # them, and neither exists at a prefix's boundary)
        unequal = spec.kind == "kv" and spec.parts[0] != spec.parts[1]
        for cannot, where in (
                (spec.kind == "latent", "over a latent cache"),
                (spec.state_layers, "beside a per-slot state (parts: "
                 + ", ".join(f"{name} {list(shape)}" for name, shape, _
                             in spec.state_parts) + ")"),
                (spec.window_layers or unequal, "over window layers' rings "
                 "or keys wider than their values")):
            for option, on in (
                    ("kv_dtype='int8'", self._kv_quantized),
                    ("enable_prefix_cache", enable_prefix_cache),
                    ("max_adapters", max_adapters > 0)):
                if cannot and on:
                    raise ValueError(
                        f"{option} is not supported {where} "
                        f"({type(model).__name__}.cache_spec())")
        self._attn_kernel = resolve_paged_kernel(
            attention_kernel, num_heads=spec.num_heads,
            head_dim=spec.head_dim, block_size=self.block_size,
            kv_dtype=self._kv_dtype)
        # every other row the cache holds (a value narrower than its key,
        # a window layer's) has to be on the kernel's tiles too
        for heads, width in (spec.parts[1:] if spec.kind == "kv" else ()) \
                + spec.window_parts:
            if self._attn_kernel == "pallas" \
                    and (heads, width) != (spec.num_heads, spec.head_dim):
                self._attn_kernel = resolve_paged_kernel(
                    attention_kernel, num_heads=heads, head_dim=width,
                    block_size=self.block_size, kv_dtype=self._kv_dtype)
        if self._kv_quantized:
            _EVENTS.emit("kernel.quantized", "serve.decode",
                         reason="kv_quantized",
                         detail={"kv_dtype": "int8",
                                 "kernel": self._attn_kernel,
                                 "num_blocks": int(num_blocks),
                                 "block_size": self.block_size})
        self.cache = PagedKVCache(spec, num_blocks, self.block_size,
                                  self._kv_dtype,
                                  num_slots=self.max_batch_size,
                                  state_dtype=self._dtype)
        self.scheduler = Scheduler(self.max_batch_size,
                                   self.cache.allocator, self.block_size,
                                   watermark_blocks,
                                   max_queue_depth=max_queue_depth,
                                   aging_max_preemptions=
                                   aging_max_preemptions)
        # -- multi-tenant layer (PR 17, serving/tenancy.py) -------------
        # prefix cache: content-addressed aliasing of prompt KV blocks
        self._prefix = (PrefixCache(self.cache.allocator, self.block_size)
                        if enable_prefix_cache else None)
        # batched adapters: padded low-rank stacks as decode VALUE inputs
        self._adapters = (AdapterSet(model, max_adapters, adapter_rank,
                                     dtype=self._dtype)
                          if max_adapters > 0 else None)
        self._hot_swap = bool(hot_swap)
        # weights as program ARGUMENTS: for a hot-swapping engine, and for
        # a model whose class says so (one too large to compile into the
        # programs as constants)
        self._weights_as_args = self._hot_swap or bool(
            getattr(type(model), "serve_weights_as_arguments", False))
        # what the model's forward counts for the engine's stats (an
        # expert block's routing), fetched beside a launch's tokens
        self._counter_names = tuple(
            getattr(type(model), "serve_counter_names", ()))
        # aux-input mode: the decode/prefill signatures gain an `aux`
        # pytree (weights as values / adapter stacks + slot indices);
        # with both features off the signatures stay byte-identical to
        # the single-tenant engine
        self._tenant = self._weights_as_args or self._adapters is not None
        self._holder = None
        if self._adapters is not None:
            holder = getattr(model, "_tenancy_holder", None)
            if holder is None:
                holder = {"active": None}
                model._tenancy_holder = holder
            self._holder = holder
            self._adapters.install(holder)
        self._weight_epoch = 0
        self._pending_weights = None
        self._weights_crc = self._params_crc() if self._hot_swap else None
        self._cow_fn = None
        self._stats = ServeStats()
        self._monitor = MonitoredWait()
        # degraded-mode latch: set by the watchdog / a decode fault,
        # cleared by the first clean decode step afterwards (both
        # transitions emit serve.degrade so the flight recorder shows the
        # full degraded window)
        self.degraded = False
        self._logprobs_topk = int(logprobs_topk)
        # how many of a kind of program's arguments are host values, by
        # whether the call uploads the record (`_call_program`)
        self._host_arity = {}
        # what stands in for `_feedback` while there is none (every slot
        # is overridden then): a device array, so that no call uploads one
        self._no_feedback = jnp.zeros(self.max_batch_size, jnp.int32)
        self._reset_slots()
        self._decode_fn = None
        self._prefill_fns = {}
        # AOT warm start (ops/aot_cache.py): the decode digest is computed
        # lazily (it CRCs the weights once); a pending-store tuple means
        # the first successful decode step should persist the executable
        self._aot_digest_cache = None
        self._aot_pending_store = None
        self._next_rid = 0
        # rid -> Request: the id registry (duplicate-id checks, cancel(),
        # introspection). Terminal handles are retained until the caller
        # drains them with pop_finished() — live scheduling state lives
        # in scheduler.waiting/running, never here
        self.requests = {}
        # True while step() is mutating the slot arrays: a cancel()
        # issued from inside a streaming callback then defers to the
        # next iteration boundary instead of editing the layout under
        # the loop's feet
        self._stepping = False
        # liveness heartbeat (profiler/telemetry_server.py /healthz):
        # stamped at step entry and after every clean decode step, so a
        # busy engine whose heartbeat goes stale past the watchdog
        # window reads as wedged — even when the wedge is a blind C++
        # hang the watchdog itself cannot interrupt
        self._hb_ns = None
        # stamped whenever a fresh executable is about to trace (first
        # decode build, a new prefill bucket, watchdog rebuilds):
        # /healthz widens its staleness window during the compile so a
        # supervisor never kills a replica for legitimately compiling
        self._compile_grace_ns = None
        self._decode_called = None      # the decode program last called
        # the first result of the program dispatched last: ready means the
        # device has nothing queued (`_call_program`)
        self._newest_result = None
        _telemetry.maybe_start_from_flags()
        _telemetry.register_engine(self)
        _sentinel.maybe_arm_from_flags()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens=16, request_id=None,
                    eos_token_id=None, on_token=None, ttl_s=None,
                    adapter=None, temperature=0.0, top_k=0, top_p=1.0,
                    repetition_penalty=1.0, seed=None):
        """Enqueue a generation request; returns the Request handle.

        `temperature` / `top_k` / `top_p` / `repetition_penalty` / `seed`
        configure the stream's sampler — VALUES in the one compiled
        decode step (serving/sampling.py), so a batch may mix greedy and
        any number of distinct sampler configs with zero retraces.
        ``temperature=0`` (the default) is greedy under the same program,
        token-identical to ``model.generate(do_sample=False)``; the other
        knobs are inert at temperature 0. `seed` defaults to a stable
        hash of the request id; a given (seed, prompt, sampler config)
        reproduces its stream byte-identically across preemption,
        watchdog rebuild, and crash resume. Out-of-contract values are
        refused as `sampler_mismatch`.

        `ttl_s` arms a deadline: the request is expired (attributed
        `deadline_expired`) if the TTL passes while it waits or runs.

        `adapter` names the registered LoRA-style adapter this stream
        decodes under (None = base weights); an unknown name is refused
        as `adapter_mismatch` — silently serving base weights to a
        tenant that asked for its fine-tune would be a correctness bug,
        not a degraded mode.

        Raises `ServeRefusal` (a ValueError) when admission would be
        doomed work, each refusal attributed in the flight recorder as a
        `serve.refuse` event:

          * `queue_full` — the bounded waiting queue is at
            `max_queue_depth`;
          * `kv_exhausted` — the peak KV footprint can NEVER fit in the
            pool minus the growth watermark;
          * `deadline_infeasible` — the TTL is already spent, or the
            estimated queue wait + service time exceeds it.

        A request that merely cannot fit *right now* is queued, not
        refused. Plain validation errors (empty prompt, context
        overflow, duplicate live id) stay ValueError.
        """
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        rid = request_id
        if rid is None:
            rid = f"r{self._next_rid}"
        self._next_rid += 1
        prev = self.requests.get(rid)
        if prev is not None and not prev.finished:
            # overwriting would orphan a handle the scheduler still runs
            raise ValueError(
                f"request id {rid!r} is already queued/running; ids may "
                "only be reused after the previous request finishes")
        req = Request(rid, prompt, max_new_tokens, eos_token_id, on_token,
                      ttl_s=ttl_s, adapter=adapter,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      repetition_penalty=repetition_penalty,
                      seed=(default_seed(rid) if seed is None
                            else int(seed) & 0xFFFFFFFF))
        try:
            validate_sampler(temperature, top_k, top_p, repetition_penalty)
        except ValueError as e:
            self._refuse(req, "sampler_mismatch",
                         f"request {rid}: {e}",
                         {"temperature": temperature, "top_k": top_k,
                          "top_p": top_p,
                          "repetition_penalty": repetition_penalty})
        if len(prompt) + req.max_new_tokens > self.max_context:
            raise ValueError(
                f"request {rid}: prompt ({len(prompt)}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_context "
                f"({self.max_context})")
        if adapter is not None and (
                self._adapters is None
                or not self._adapters.is_registered(adapter)):
            self._refuse(req, "adapter_mismatch",
                         f"request {rid}: adapter {adapter!r} is not "
                         "registered with this engine; register it (or "
                         "build the engine with max_adapters > 0) before "
                         "routing its tenant here",
                         {"adapter": adapter,
                          "registered": ([] if self._adapters is None
                                         else self._adapters.names())})
        self._admission_policy(req)
        self.scheduler.enqueue(req)
        self.requests[rid] = req
        _EVENTS.emit("serve.enqueue", rid,
                     detail={"prompt_len": len(prompt),
                             "max_new_tokens": req.max_new_tokens,
                             "ttl_s": ttl_s})
        if req.temperature > 0:
            # sampler lifecycle attribution: one event per stochastic
            # stream, carrying the full resolved config — the flight
            # recorder's proof that sampler churn stayed value-only
            _EVENTS.emit("serve.sample", rid,
                         detail={"temperature": req.temperature,
                                 "top_k": req.top_k, "top_p": req.top_p,
                                 "repetition_penalty":
                                     req.repetition_penalty,
                                 "seed": req.seed})
        return req

    def _admission_policy(self, req):
        """Refuse-early backpressure: raise `ServeRefusal` (and emit the
        attributed `serve.refuse` event) for work that is doomed at
        enqueue time. Checked in cost order: queue depth (free), pool
        feasibility (arithmetic), deadline feasibility (needs latency
        samples)."""
        sched = self.scheduler
        if sched.queue_full():
            self._refuse(req, "queue_full",
                         f"request {req.rid}: waiting queue is at "
                         f"max_queue_depth ({sched.max_queue_depth}); "
                         "shed load upstream or add capacity",
                         {"queue_depth": len(sched.waiting),
                          "max_queue_depth": sched.max_queue_depth})
        peak = sched.max_blocks_of(req)
        budget = sched.block_budget()
        shared = 0
        if self._prefix is not None:
            # aliasing credit: blocks this prompt would inherit by
            # reference rather than allocate (counted ONCE — the PR 17
            # accounting bugfix; advisory, so no references are taken)
            shared, _ = self._prefix.probe(req.prompt + req.generated)
        if not sched.can_ever_fit(req, shared_blocks=shared):
            self._refuse(req, "kv_exhausted",
                         f"request {req.rid}: needs {peak} KV blocks at "
                         f"peak but the pool only ever has {budget} "
                         f"(capacity {self.cache.allocator.capacity} - "
                         f"watermark {sched.watermark_blocks}); refuse "
                         "instead of deadlock",
                         {"blocks_needed": peak, "blocks_budget": budget})
        if req.deadline_ns is None:
            return
        remaining = req.ttl_remaining_s()
        if remaining <= 0:
            self._refuse(req, "deadline_infeasible",
                         f"request {req.rid}: deadline already expired "
                         "at enqueue",
                         {"ttl_remaining_s": round(remaining, 6)})
        times = self._stats.step_times_s
        if times:
            avg = sum(times[-_EST_WINDOW:]) / len(times[-_EST_WINDOW:])
            need_steps = sched.estimated_wait_steps(req) \
                + req.max_new_tokens
            est = need_steps * avg
            if est > remaining:
                self._refuse(
                    req, "deadline_infeasible",
                    f"request {req.rid}: estimated wait + service "
                    f"{est:.3f}s exceeds the remaining TTL "
                    f"{remaining:.3f}s; refusing now beats expiring "
                    "later",
                    {"estimated_s": round(est, 4),
                     "ttl_remaining_s": round(remaining, 4),
                     "est_steps": need_steps})

    def _refuse(self, req, reason, message, detail):
        self._stats.refused += 1
        if reason == "queue_full":
            self._stats.refused_queue_full += 1
        elif reason == "deadline_infeasible":
            self._stats.refused_deadline += 1
        if _metrics_on():
            _M.refusals.labels(reason=reason).inc()
        _EVENTS.emit("serve.refuse", req.rid, reason=reason, detail=detail)
        raise ServeRefusal(reason, message, detail)

    def cancel(self, request_id):
        """Client cancellation: reclaim the stream's slot/KV at the next
        safe point. Between steps (the usual driver loop) the request is
        cleared immediately; a cancel issued from inside a streaming
        `on_token` callback — i.e. while step() is mid-iteration over
        the slot arrays — is deferred to the next boundary sweep so the
        fixed layout is only ever edited between decode steps. Either
        way the edit is value-only: the decode executable never
        retraces. Returns True when the request was live, False when it
        was unknown or already terminal (cancel racing completion is a
        no-op)."""
        req = self.requests.get(request_id)
        if req is None or req.finished:
            return False
        req.cancel_requested = True
        if self._stepping and req.slot is not None:
            return True          # boundary sweep picks it up next step
        self._cancel_now(req)
        return True

    def _cancel_now(self, req):
        slot = req.slot
        self.scheduler.remove_waiting(req)
        self.scheduler.release(req)
        if slot is not None:
            self._clear_slot(slot)
        req.state = CANCELLED
        req.error = "client_cancel"
        req.finish_ns = time.perf_counter_ns()
        self._stats.cancelled += 1
        if _metrics_on():
            _M.requests.labels(outcome="cancelled").inc()
        _EVENTS.emit("serve.cancel", req.rid, reason="client_cancel",
                     detail={"was_running": slot is not None,
                             "tokens": len(req.generated)})

    def _expire(self, req):
        """Deadline passed while queued or running: clear the request
        (value-only slot edit) and attribute the decision."""
        slot = req.slot
        where = "running" if slot is not None else "queued"
        self.scheduler.remove_waiting(req)
        self.scheduler.release(req)
        if slot is not None:
            self._clear_slot(slot)
        req.state = EXPIRED
        req.error = "deadline_expired"
        req.finish_ns = time.perf_counter_ns()
        self._stats.expired += 1
        if _metrics_on():
            _M.requests.labels(outcome="expired").inc()
        _EVENTS.emit("serve.expire", req.rid, reason="deadline_expired",
                     detail={"where": where,
                             "tokens": len(req.generated)})

    def _boundary_housekeeping(self):
        """Iteration-boundary sweep: honor cancels deferred from inside
        streaming callbacks, then expire queued requests (an expired
        head must never block FCFS admission) and running ones (their
        slots free up for admission this very boundary)."""
        sched = self.scheduler
        for req in [r for r in list(sched.waiting) + list(sched.running)
                    if r.cancel_requested]:
            self._cancel_now(req)
        now = time.perf_counter_ns()
        for req in sched.expired_waiting(now):
            self._expire(req)
        for req in [r for r in list(sched.running) if r.expired(now)]:
            self._expire(req)

    def step(self):
        """One engine iteration: expire/cancel at the boundary, admit
        (one prefill a request, dispatched and not awaited), grow/evict
        for KV headroom, stream the first tokens of the step before's
        prefills, LAUNCH the ONE compiled decode step for every running
        slot, then stream the tokens of the launch BEFORE it and retire
        finished requests while the new one runs. All waits are the
        watchdog's.
        Returns True while any request is running or waiting, or a
        launch is uncommitted."""
        if self._stats.wall_t0 is None:
            self._stats.wall_t0 = time.perf_counter()
        self._hb_ns = time.perf_counter_ns()
        self._stepping = True
        self._stats.step_begin()
        try:
            with self._span("engine.step"):
                return self._step_locked()
        finally:
            self._stepping = False
            self._stats.step_end()
            self._stats.wall_t1 = time.perf_counter()

    def _span(self, name):
        """The span `name` of this engine; its seconds go to the phase
        histogram of that name where `ServeStats` keeps one."""
        return RecordEvent(name, hist=self._stats.phase.get(name))

    def _call_program(self, name, fn, args, first):
        """`fn(*args)` under the dispatch span `engine.<kind>.dispatch`;
        a program's first call, which traces and compiles it, under
        `engine.compile` too. Every other call asks, without waiting,
        whether the device has finished the program dispatched before
        it: at entry (if so its queue is empty, and stays empty until
        this call lands) and, if not, again at return (it ran dry while
        the host was in here). What is asked is that program's first
        result (the sampled tokens), which no later program donates.
        Counted too: the host values among the arguments (one, the
        packed array; three in a call that uploads the sampler's record,
        which stands where the device's copy of the history would),
        from the arguments' types, once a signature."""
        kind = name.split(".")[1]
        uploads = isinstance(args[-len(self._bufs) - 1], np.ndarray)
        handed = self._host_arity.get((kind, uploads))
        if handed is None:
            handed = self._host_arity[kind, uploads] = sum(
                isinstance(a, (np.ndarray, np.generic)) for a in args)
        self._stats.host_arrays[kind] += handed
        self._stats.state_uploads += uploads
        if first:
            with self._span("engine.compile"), self._span(name):
                res = fn(*args)
        else:
            newest = self._newest_result
            idle = newest is not None and newest.is_ready()
            with self._span(name):
                res = fn(*args)
            self._stats.count_dispatch(
                kind, idle,
                idle or (newest is not None and newest.is_ready()))
        self._newest_result = res[0]
        return res

    def _step_locked(self):
        sched = self.scheduler
        # -- weight hot-swap cutover (exact iteration boundary) --------
        if self._pending_weights is not None:
            self._commit_swap()
        # -- cancel/deadline sweep + admission (token boundary) --------
        self._boundary_housekeeping()
        hook = self._prefix_hook if self._prefix is not None else None
        with self._span("engine.admit"):
            while True:
                # expire a dead head BEFORE admission assigns it a slot —
                # it never ran, and the serve.expire where=queued/running
                # split must stay truthful for queue-sizing diagnosis
                while sched.waiting and sched.waiting[0].expired():
                    self._expire(sched.waiting[0])
                req = sched.try_admit(prefix_hook=hook)
                if req is None:
                    # the pool may be dry only because the prefix index is
                    # hoarding cold entries — release those and retry
                    # before giving up on this boundary (only when a slot
                    # is actually free: batch pressure is not block
                    # pressure)
                    if (self._prefix is not None and sched.waiting
                            and None in sched.slots
                            and self._reclaim_prefix(
                                sched.blocks_needed(
                                    sched.waiting[0].context_len)
                                + sched.watermark_blocks)):
                        continue
                    break
                self._admit(req)
        if not sched.running:
            self._flush_inflight()
            return bool(sched.waiting)
        with self._span("engine.kv_grow"):
            # -- KV growth, preempting (newest first) on a dry pool ----
            for req in sorted(list(sched.running),
                              key=lambda r: r.admit_seq):
                if req.state != RUNNING:
                    continue
                need = sched.blocks_needed(req.cached_len)
                while len(req.blocks) < need and req.state == RUNNING:
                    if sched.grow(req):
                        self._sync_slot(req)
                        continue
                    if self._prefix is not None \
                            and self._reclaim_prefix(1):
                        continue    # cold prefix entries go before tenants
                    victim = sched.preempt_victim(exclude=req)
                    if victim is not None:
                        self._evict(victim)
                        continue
                    if not sched.protected(req):
                        # aging guard: every other tenant is protected —
                        # the grower steps aside (requeued, not failed)
                        self._evict(req)
                        break
                    self._fail(req, "kv_exhausted")
                    break
            # -- copy-on-write boundary: privatize shared write targets -
            if sched.running and self._prefix is not None:
                self._cow_sweep()
        if not sched.running:
            self._flush_inflight()
            return bool(sched.waiting)
        # -- the tail: launch N+1, commit N (lag 1) --------------------
        # commit the first tokens of the prefills the launch before was
        # queued behind, LAUNCH this step's decode against device-fed
        # tokens (the launch before's sampled ids, and this boundary's
        # prefills', feed back as a device array: no host round-trip),
        # then COMMIT the launch before's host work (detokenize,
        # callbacks, retirement) while the device runs the new one. A
        # steady-state step takes max(device, host commit) and not their
        # sum, and the watchdog's monitored wait only ever covers device
        # time
        demand = sched.demand
        n_active = len(sched.running)
        t0 = time.perf_counter()
        # the first tokens of the prefills behind the launch before come
        # to the host BEFORE this launch: it is the second to run over
        # their slots and scatters only its own input into the history
        ok = self._commit_joined(self._inflight)
        launched = None
        if ok:
            with self._span("engine.decode"):
                launched = self._launch_decode()
            if launched is None:
                # no slot takes another token: nothing to commit behind
                ok = self._drain_joined()
        ok = ok and self._commit_inflight()
        if not ok:
            # destructive recovery fired mid-window: the launch just
            # issued consumed suspect pool/token state — discard it too
            if launched is not None:
                self._discard_records(launched)
            self._reset_pipeline()
            if _metrics_on():
                _goodput.ACCOUNTANT.drop_stall_carry()
            return bool(sched.running or sched.waiting)
        self._inflight = launched
        if launched is None:
            return bool(sched.running or sched.waiting)
        dt = time.perf_counter() - t0
        self._stats.observe_step(n_active, self.max_batch_size, demand,
                                 dt)
        self._hb_ns = time.perf_counter_ns()
        # a completed step means any pending compile finished: the
        # /healthz grace window closes and staleness reverts to the
        # watchdog budget
        self._compile_grace_ns = None
        _telemetry.beat("decode", step=self._stats.steps)
        _sentinel.tick()
        if _metrics_on():
            _M.step_s.observe(dt)
            _M.occupancy.set(n_active / self.max_batch_size)
            # productive serving time: the goodput fraction stays
            # meaningful in a process that never crosses an optimizer
            # boundary (stall time lands via the watchdog's note_stall)
            _goodput.ACCOUNTANT.note_productive(dt)
        if _EVENTS.enabled:
            _EVENTS.emit("serve.step", "engine",
                         detail={"active": n_active,
                                 "occupancy": round(
                                     n_active / self.max_batch_size, 4),
                                 "ms": round(dt * 1e3, 4)})
        if self.degraded:
            # first clean decode step after a hang/fault: recovered
            self.degraded = False
            _EVENTS.emit("serve.degrade", "engine",
                         detail={"recovered": True})
        return bool(sched.running or sched.waiting
                    or self._inflight is not None)

    def _launch_decode(self):
        """Dispatch one decode launch asynchronously. Structural state
        (cached_len, lens, chew) advances HERE — the KV write at
        position `lens` is certain regardless of what token the launch
        samples — while token-dependent state (generated, callbacks,
        finish) waits for the lag-1 commit. Returns the inflight record,
        or None when no slot can accept another token."""
        sched = self.scheduler
        if not sched.running:
            return None
        if self._decode_fn is None:
            self._compile_grace_ns = time.perf_counter_ns()
            self._decode_fn = self._build_decode()
        # the launch is asynchronous and reads its host arguments when it
        # runs, while this method goes on to edit `_lens`, `_tokens` and
        # `_override` (and the next admission `_tables`) in place: the
        # packed array is made anew for every launch, the ONE copy, or a
        # slow dispatch would read the NEXT step's values
        args = self._decode_args()
        launch_active = args[0][:, self.max_blocks_per_seq + _COL_ACTIVE]
        pending = self._inflight["records"] if self._inflight else {}
        plan = []
        for req in list(sched.running):
            if req.state != RUNNING or req.slot is None:
                continue
            rec = pending.get(req.slot)
            # uncommitted tokens: the launch before's, and the one of a
            # prefill dispatched at this boundary
            first = self._joined.get(req.slot)
            in_flight = (rec is not None and rec[0] is req) \
                + (first is not None and first[0] is req)
            if (not req.chew
                    and len(req.generated) + in_flight
                    >= req.max_new_tokens):
                # every remaining token is committed or in flight —
                # launching this slot could only overshoot max_new
                launch_active[req.slot] = False
                continue
            plan.append(req)
        if not plan:
            return None
        res = self._call_decode(args)
        # adopt the launch's pool lineage NOW: any prefill issued before
        # the commit must consume THESE outputs, so XLA's dataflow
        # orders the speculative KV write before the reuse
        self._adopt(res)
        self._feedback = res[0]
        # by slot, what the commit needs to know the token is still
        # wanted: the request, its position and its admission
        records = {}
        for req in plan:
            slot = req.slot
            req.cached_len += 1
            self._lens[slot] = req.cached_len
            if req.chew:
                t = req.chew.pop(0)
                self._tokens[slot] = t
                if req.cached_len < self.max_context:
                    self._history[slot, req.cached_len] = t
                self._override[slot] = True
            else:
                # the slot's next input exists only on the device, as
                # this launch's result
                records[slot] = (req, req.cached_len, req.admit_seq)
                self._override[slot] = False
        joined, self._joined = self._joined, {}
        return {"res": res, "records": records, "joined": joined,
                "firsts": self._firsts}

    def _commit_inflight(self):
        """Commit the PREVIOUS launch: monitored wait, then stream its
        tokens through the normal emission path. A record whose request
        was cancelled / expired / preempted / finished since launch is
        discarded as `commit_lag_rollback` — boundary decisions land
        deterministically at lag 1, costing each departed stream exactly
        its one speculative token. Returns False when destructive
        recovery (fail-active / a decode fault) retired the batch."""
        inf, self._inflight = self._inflight, None
        if inf is None:
            return True
        # at a drain point the prefills queued before the launch are
        # still uncommitted: their tokens come first
        if not self._commit_joined(inf):
            return False
        with self._span("engine.decode"):
            out = self._await_launch(inf)
        if out is None:
            return False
        toks, logps, aids, alps = out
        with self._span("engine.stream"):
            for slot, (req, pos, aseq) in inf["records"].items():
                if (req.state != RUNNING or req.slot != slot
                        or req.admit_seq != aseq):
                    self._rollback(req, slot)
                    continue
                tok = int(toks[slot])
                self._tokens[slot] = tok
                if pos < self.max_context:
                    self._history[slot, pos] = tok
                self._emit_token(req, tok, logp=float(logps[slot]),
                                 alts=((aids[slot], alps[slot])
                                       if self._logprobs_topk else None))
        self._maybe_store_decode()
        return True

    def _await_launch(self, inf):
        """The monitored wait for a launch and its results on the host,
        or None when destructive recovery retired the batch."""
        from ..ops import guardian
        res = inf["res"]
        attempt = 1
        while True:
            try:
                with self._span("engine.decode.wait"):
                    self._monitor.wait(res, "decode", attempt)
                break
            except StepHang:
                if not self._retry_commit(
                        inf, attempt, "commit",
                        active=len(self.scheduler.running)):
                    return None
                attempt += 1
            except jax.errors.JaxRuntimeError as e:
                # organic execution fault: the program/device state is
                # suspect: eager-finish the batch, rebuild the program
                self._decode_fault(
                    inf, {"organic": True, "error": str(e)[:200]},
                    rebuild=True)
                return None
        if guardian.poll_fault("serve.decode",
                               ("nan_output", "raise")) is not None:
            # chaos-poisoned decode output: commit NOTHING from this
            # launch. The executable itself is healthy (the poison models
            # a transient device fault), so no rebuild: decode still
            # compiles exactly once
            self._decode_fault(inf, {"injected": True}, rebuild=False)
            return None
        return self._fetch_launch(res)

    def _decode_fault(self, inf, detail, rebuild):
        """A launch that faulted: nothing of it is committed, every
        running stream finishes through the model's own eager
        `generate()` (token-identical to the compiled decode per the PR 6
        parity contract), and the compiled path is restored for queued
        and new requests."""
        self._degrade("decode_fault", detail)
        self._discard_records(inf)
        self._reset_pipeline()
        for req in list(self.scheduler.running):
            self._fallback_eager(req)
        if self._pools_consumed():
            self._reset_kv_state()
        if rebuild:
            self._rebuild_decode()

    def _fetch_launch(self, res):
        """A launch's results on the host, from ONE fetch of its rows
        (`_rows`: a row a slot of token, logprob, panel, the model's
        counters): ``(tokens, logprobs, panel ids, panel logprobs)``, a
        view each. With the watchdog disarmed `_monitor.wait` returns at
        once and THIS is where the host waits for the program: under the
        wait's span, so that the fetch's holds the copy alone."""
        with self._span("engine.decode.wait"):
            res[0].block_until_ready()
        with self._span("engine.decode.fetch"):
            ints = np.asarray(res[1])
            self._stats.decode_fetched_arrays += 1
            # every row carries the launch's counters
            return self._read_rows("decode", ints, ints[0])

    def _read_rows(self, phase, ints, counted):
        """The reader of `_rows`, for one row or a launch's `[S, width]`:
        ``(tokens, logprobs, panel ids, panel logprobs)``, a view each;
        the model's counters, in the tail of the row `counted`, go to the
        stats."""
        topk = self._logprobs_topk
        floats = ints.view(np.float32)
        if self._counter_names:
            self._stats.count_model(phase, self._counter_names,
                                    counted[2 + 2 * topk:])
        return (ints[..., 0], floats[..., 1], ints[..., 2:2 + topk],
                floats[..., 2 + topk:2 + 2 * topk])

    def _retry_commit(self, inf, attempt, phase, **counted):
        """A commit's wait hung: one watchdog firing, attributed, and
        the recovery ladder climbed. True: wait once more. False: the
        last rung was taken. A wedged device holds every outstanding
        program, and a launch whose successor already consumed its pools
        cannot be replayed, so the ladder goes from one retry of the
        WAIT straight to fail-active."""
        self._stats.hangs += 1
        if _metrics_on():
            _M.hangs.inc()
            budget_s = float(_FLAGS.get("FLAGS_serve_step_timeout_ms")
                             or 0) / 1e3
            if budget_s > 0:
                # the wedged wall time (the armed budget the monitor just
                # burned) lands in the goodput `stalled` bucket, with the
                # index of the step ABOUT to commit, so /goodput and the
                # doctor can say WHICH steps stalled
                _goodput.ACCOUNTANT.note_stall(
                    budget_s, kind="step_hang", step=self._stats.steps + 1)
            if phase == "prefill":
                # prefill time is not measured as a productive step: no
                # later interval to subtract the stall from
                _goodput.ACCOUNTANT.drop_stall_carry()
        _EVENTS.emit("serve.hang", "engine", reason="step_hang",
                     detail={"attempt": attempt, "phase": phase, **counted})
        consumed = self._pools_consumed()
        if attempt < 2 and not consumed:
            self._degrade("step_hang", {"rung": "retry", "phase": phase})
            return True
        self._degrade("step_hang", {"rung": "fail_active", "phase": phase,
                                    "pools_consumed": consumed})
        self._discard_records(inf)
        if self._inflight is not None and self._inflight is not inf:
            self._discard_records(self._inflight)
        for req in list(self.scheduler.running):
            self._fail(req, "step_hang")
        self._reset_pipeline()
        if consumed:
            self._reset_kv_state()
        self._rebuild_decode()
        return False

    def _commit_joined(self, inf, programs=None):
        """Commit the first tokens of the prefills a launch record was
        queued behind (`inf["joined"]`, emptied here): one monitored wait
        and ONE fetch for all of them, then each through the normal
        emission path, unless its request left the slot meanwhile
        (`commit_lag_rollback`). `programs` is what the device's queue
        holds up to the last of them. Returns False when the wait hung
        and the ladder retired the batch."""
        joined = inf and inf["joined"]
        if not joined:
            return True
        attempt = 1
        with self._span("engine.prefill.commit"):
            while True:
                try:
                    with self._span("engine.prefill.wait"):
                        self._monitor.wait(
                            (inf["firsts"],), "prefill", attempt,
                            programs=programs or len(joined))
                        # with the watchdog disarmed THIS is where the
                        # host waits for the programs
                        firsts = np.asarray(inf["firsts"])
                    break
                except StepHang:
                    if not self._retry_commit(inf, attempt, "prefill",
                                              prefills=len(joined)):
                        return False
                    attempt += 1
            inf["joined"] = {}
            for slot, (req, pos, aseq) in joined.items():
                tok, logp, aids, alps = self._read_rows(
                    "prefill", firsts[slot], firsts[slot])
                if (req.state != RUNNING or req.slot != slot
                        or req.admit_seq != aseq):
                    self._rollback(req, slot)
                    continue
                tok = int(tok)
                self._tokens[slot] = tok
                if pos < self.max_context:
                    self._history[slot, pos] = tok
                self._emit_token(
                    req, tok, logp=float(logp),
                    alts=(aids, alps) if self._logprobs_topk else None)
        return True

    def _drain_joined(self):
        """Synchronously commit the prefills dispatched since the last
        launch (a bucket's first call, a boundary nothing launches
        behind, a drain point): no launch has fed on their tokens, so
        the host's copy stands for the slot."""
        joined, self._joined = self._joined, {}
        if not joined:
            return True
        inf = self._inflight
        ahead = 0 if inf is None else 1 + len(inf["joined"])
        ok = self._commit_joined(
            {"records": {}, "joined": joined, "firsts": self._firsts},
            programs=len(joined) + ahead)
        if ok:
            for slot, (req, _pos, aseq) in joined.items():
                if req.slot == slot and req.admit_seq == aseq:
                    self._override[slot] = True
        return ok

    def _discard_records(self, inf):
        for records in (inf["joined"], inf["records"]):
            for slot, (req, _pos, _aseq) in records.items():
                self._rollback(req, slot)

    def _rollback(self, req, slot):
        """One speculative token discarded at the lag-1 boundary."""
        self._stats.commit_rollbacks += 1
        if _metrics_on():
            _M.commit_rollbacks.inc()
        _EVENTS.emit("serve.sample", req.rid,
                     reason="commit_lag_rollback",
                     detail={"slot": int(slot), "state": req.state})

    def _flush_inflight(self):
        """Synchronously commit (or roll back) the pending pipelined
        launch and every prefill whose token is uncommitted. Drain
        points — an idle boundary, the weight-swap cutover, explicit
        drains — must not leave a speculative token in flight. After the
        flush the host token mirror is authoritative for every slot.
        No-op when nothing is pending."""
        if self._inflight is not None:
            self._commit_inflight()
        self._drain_joined()
        self._feedback = None
        self._override[:] = True

    def _reset_pipeline(self):
        # destructive recovery: what the suspect programs handed on of
        # the sampler's table and history is not trusted either
        self._sampler_dev = None
        self._inflight = None
        for slot, (req, _pos, _aseq) in self._joined.items():
            self._rollback(req, slot)
        self._joined = {}
        self._feedback = None
        self._override[:] = True

    def run(self, max_steps=None):
        """Drive step() until every request drains (or `max_steps`)."""
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        return n

    def generate(self, prompts, max_new_tokens=16, eos_token_id=None):
        """Batch convenience: enqueue every prompt, run to drain, return
        the generated token lists (continuous batching under the hood —
        prompts of different lengths share slots and the block pool)."""
        reqs = [self.add_request(p, max_new_tokens,
                                 eos_token_id=eos_token_id)
                for p in prompts]
        self.run()
        for r in reqs:
            if r.state in (FAILED, EXPIRED, CANCELLED):
                raise RuntimeError(f"request {r.rid} failed: {r.error}")
        return [list(r.generated) for r in reqs]

    def stats(self):
        """`ServeStats.snapshot()` over the window since `reset_stats()`
        (the keys of the host's turn, per phase and per dispatch: the one
        table in `ServeStats`' docstring) and the engine's own facts:
        scheduler, pool size, block size, attention kernel, KV dtype."""
        snap = self._stats.snapshot()
        snap["scheduler"] = self.scheduler.info()
        snap["kv_blocks"] = self.cache.num_blocks
        snap["block_size"] = self.block_size
        snap["attention_kernel"] = self._attn_kernel
        snap["kv_dtype"] = str(jnp.dtype(self._kv_dtype))
        # the paged pools' bytes that requests hold right now (whole
        # blocks, every cached layer, keys and values)
        allocator = self.cache.allocator
        snap["kv_bytes_held"] = (
            int(self.cache.k_pools.nbytes + self.cache.v_pools.nbytes)
            // self.cache.num_blocks
            * (allocator.capacity - allocator.num_free))
        # a per-slot state: its bytes, whole and by part, and what its
        # layers did in the window (a prefill's scan runs over its whole
        # bucket, the padding masked; a launch moves each active slot's
        # state of each such layer one token on)
        parts = self.cache.slot_state_bytes()
        layers = self.cache.spec.state_layers
        snap["slot_state_bytes"] = sum(parts.values())
        snap["slot_state_bytes_by_part"] = parts
        if layers:
            snap["prefill_scan_tokens"] = \
                layers * snap["prefill_bucket_tokens"]
            snap["prefill_scan_padding_tokens"] = \
                layers * snap["prefill_padding_tokens"]
            snap["decode_state_updates"] = layers * snap["decode_tokens"]
        rings = self.cache.window_pools
        snap["window_ring_bytes"] = 0 if rings is None \
            else int(sum(pool.nbytes for pool in rings))
        if self._prefix is not None:
            snap["prefix_entries"] = self._prefix.entries
        if self._tenant:
            snap["weight_epoch"] = self._weight_epoch
            snap["adapters"] = ([] if self._adapters is None
                                else self._adapters.names())
        return snap

    def reset_stats(self):
        """Start a fresh measurement window (counters AND step-time
        samples); the compiled programs and the KV pool are untouched, so
        a post-warmup window sees decode_compiles == 0 unless something
        actually retraced."""
        self._stats.reset()

    def pop_finished(self):
        """Drain terminal request handles (FINISHED/FAILED/CANCELLED/
        EXPIRED) from the id registry and return them as {rid: Request}.
        A long-running server calls this after collecting results so the
        registry stays O(live); drained ids become reusable, exactly as
        if the handle had been overwritten."""
        done = {rid: r for rid, r in self.requests.items() if r.finished}
        for rid in done:
            del self.requests[rid]
        return done

    # ------------------------------------------------------------------
    # admission / prefill
    # ------------------------------------------------------------------
    @staticmethod
    def _bucket_for(n):
        """The prefill bucket of a context of n tokens: the next power of
        two up to `_LINEAR_BUCKETS_FROM`, and from there the next multiple
        of half of it (8,192, 12,288, 16,384, ...): doubling a long
        bucket pads a prompt just over it by as much again as it holds,
        and a long prompt's prefill is most of what it costs."""
        if n > _LINEAR_BUCKETS_FROM:
            step = _LINEAR_BUCKETS_FROM // 2
            return -(-int(n) // step) * step
        return max(_MIN_BUCKET, 1 << (int(n - 1)).bit_length())

    def _admit(self, req):
        """Bucketed prefill of prompt + already-generated tokens (resume
        case) into the request's freshly assigned blocks, then join the
        decode batch. Never touches the decode executable. A prefix-hit
        admission (try_admit aliased cached blocks) skips the prefill
        entirely. The prefill is dispatched and NOT awaited: its token
        feeds the next decode launch on the device and is committed from
        the record in `_joined` (`_commit_joined`)."""
        ctx = req.prompt + req.generated
        if req.prefix_hit > 0:
            self._admit_prefix_hit(req, ctx)
            return
        if self._prefix is not None:
            self._stats.prefix_prompt_tokens += len(ctx)
            self._note_prefix_rate()
            _EVENTS.emit("serve.prefix_miss", req.rid,
                         detail={"context_len": len(ctx)})
        with self._span("engine.prefill"):
            bucket = self._bucket_for(len(ctx))
            fn = self._prefill_fns.get(bucket)
            new_bucket = fn is None
            if new_bucket:
                # the XLA trace runs on this bucket's FIRST call below —
                # grace the liveness window for it
                self._compile_grace_ns = time.perf_counter_ns()
                fn = self._build_prefill(bucket)
                self._prefill_fns[bucket] = fn
            self._stats.admitted += 1
            self._stats.prefills += 1
            _EVENTS.emit("serve.admit", req.rid,
                         reason="bucket_retrace" if new_bucket else None,
                         detail={"context_len": len(ctx), "bucket": bucket,
                                 "blocks": len(req.blocks),
                                 "resumed": bool(req.generated)})
            now = time.perf_counter_ns()
            if req.admit_ns is None:
                req.admit_ns = now
                wait_s = (now - req.enqueue_ns) / 1e9
                self._stats.queue_wait_hist.observe(wait_s)
                if _metrics_on():
                    _M.queue_wait_s.observe(wait_s)
            # launched, not awaited: what the prefill sampled stays on
            # the device as the slot's next decode input, and nothing of
            # its results is touched here
            res = self._call_program(
                "engine.prefill.dispatch", fn,
                self._prefill_args(ctx, bucket, req), new_bucket)
            self._feedback, self._firsts = res[0], res[1]
            self._adopt(res)
            req.cached_len = len(ctx)
            self._sync_slot(req)
            self._set_adapter_slot(req)
            if self._prefix is not None:
                # index this prompt's blocks for the NEXT tenant sharing it;
                # a resume's partial tail holds generated-token KV, which
                # must never be served as prompt KV
                self._prefix.publish(ctx, req.blocks,
                                     include_tail=not req.generated)
            self._stats.prefill_tokens += len(ctx)
            self._stats.prefill_bucket_tokens += bucket
            self._override[req.slot] = False
            self._joined[req.slot] = (req, len(ctx), req.admit_seq)
        if new_bucket:
            # a bucket's first call traced and compiled: the host has
            # waited for it already, so it is committed where it stands
            self._drain_joined()

    def _admit_prefix_hit(self, req, ctx):
        """Prefix-hit admission: the aliased blocks already hold the
        first `prefix_hit` tokens' KV, so there is NO prefill — the
        stream joins the decode batch at `cached_len = hit` and the
        decode step chews the remaining known suffix tokens (one per
        iteration, nothing emitted) before real sampling resumes. N
        streams sharing a long system prompt pay its prefill — and its
        KV bytes — once."""
        hit = req.prefix_hit
        self._stats.admitted += 1
        self._stats.prefix_hit_tokens += hit
        self._stats.prefix_prompt_tokens += len(ctx)
        _EVENTS.emit("serve.admit", req.rid,
                     detail={"context_len": len(ctx), "bucket": None,
                             "blocks": len(req.blocks),
                             "resumed": bool(req.generated),
                             "prefix_hit": hit})
        _EVENTS.emit("serve.prefix_hit", req.rid, reason="prefix_hit",
                     detail={"hit_tokens": hit,
                             "context_len": len(ctx),
                             "chew": len(ctx) - hit - 1})
        now = time.perf_counter_ns()
        if req.admit_ns is None:
            req.admit_ns = now
            wait_s = (now - req.enqueue_ns) / 1e9
            self._stats.queue_wait_hist.observe(wait_s)
            if _metrics_on():
                _M.queue_wait_s.observe(wait_s)
        if _metrics_on():
            _M.prefix_hit_tokens.inc(hit)
        self._note_prefix_rate()
        req.cached_len = hit
        self._sync_slot(req)
        # no prefill writes this slot's rows on the device
        self._sampler_dev = None
        self._set_adapter_slot(req)
        # decode input: the first token WITHOUT cached KV; the known
        # tokens after it queue as chew (fed, never emitted)
        self._tokens[req.slot] = int(ctx[hit])
        self._override[req.slot] = True
        req.chew = [int(t) for t in ctx[hit + 1:]]

    def _note_prefix_rate(self):
        if _metrics_on() and self._stats.prefix_prompt_tokens:
            _M.prefix_hit_rate.set(self._stats.prefix_hit_tokens
                                   / self._stats.prefix_prompt_tokens)

    def _set_adapter_slot(self, req):
        """Point the request's batch slot at its tenant's adapter stack
        index (0 = base). An index CHANGE is an adapter switch — the
        churn the zero-retrace contract is measured against."""
        if self._adapters is None:
            return
        idx = self._adapters.slot_of(req.adapter)
        if idx != int(self._aslots[req.slot]):
            self._stats.adapter_switches += 1
            if _metrics_on():
                _M.adapter_switches.inc()
        self._aslots[req.slot] = idx

    def _empty_firsts(self):
        """A row a slot for what a prefill hands the host: token,
        logprob, the panel's ids and logprobs, the model's counters."""
        width = 2 + 2 * self._logprobs_topk + len(self._counter_names)
        return jnp.zeros((self.max_batch_size, width), jnp.int32)

    def _prefill_args(self, ctx, bucket, req):
        """A prefill program's positional arguments: ONE host array (the
        padded ids, the request's block row, then length, slot and the
        request's sampler values as the table's row, floats by their
        bits: VALUES, so a new config never re-keys the bucket program;
        `_unpack_prefill` is its reader) and what is on the device.
        `feedback`, `firsts`, the sampler's table and the history are
        threaded through the program as the pools are, and not donated:
        the commit of the launch before still reads the first two."""
        m = self.max_blocks_per_seq
        packed = np.zeros(bucket + m + _PREFILL_TAIL, np.int32)
        packed[:len(ctx)] = ctx
        packed[bucket:bucket + len(req.blocks)] = req.blocks
        tail = packed[bucket + m:]
        tail[0], tail[1] = len(ctx), req.slot
        tail[2:7] = self._sampler_row(
            req.temperature, req.top_k, req.top_p, req.repetition_penalty,
            req.seed or 0)
        base = (packed,)
        if self._tenant:
            base = base + (self._prefill_aux(req),)
        return base + (self._fed(), self._firsts) + self._sampler_state() \
            + self._bufs

    def _fed(self):
        """The sampled tokens as the device holds them, for the next
        program's `feedback`."""
        return self._no_feedback if self._feedback is None \
            else self._feedback

    @staticmethod
    def _sampler_row(temperature, top_k, top_p, repetition_penalty, seed):
        """A request's five sampler values as the table holds them:
        int32, the floats (float32) by their bits, the seed's 32 bits."""
        row = np.empty(5, np.int32)
        row.view(np.float32)[[0, 2, 3]] = (temperature, top_p,
                                           repetition_penalty)
        row[1] = top_k
        row.view(np.uint32)[4] = seed
        return row

    def _sampler_state(self):
        """The sampler's table and the history for a program's
        arguments: the device's, or, when they are stale, the host's
        record itself (copies: the program reads them when it runs),
        which the program hands back as the device's."""
        if self._sampler_dev is not None:
            return self._sampler_dev
        table = np.empty((self.max_batch_size, 5), np.int32)
        for col, record in enumerate((self._temps, self._topks, self._topps,
                                      self._rpens, self._seeds)):
            table[:, col] = record.view(np.int32)
        return table, self._history.copy()

    def _adopt(self, res):
        """Take a program's threaded results for the next call: the
        sampler's table, the history and the cache's buffers (behind the
        two results that are the program's own)."""
        self._sampler_dev = res[2:4]
        self._bufs = res[4:4 + len(self._bufs)]

    def _donated(self, first):
        """The argument numbers of the cache's buffers in a program whose
        signature has them from `first` on."""
        return self._donate(tuple(range(first, first + len(self._bufs))))

    def _split_more(self, more):
        """What a program's signature holds behind the two pools, in
        `buffers()`' order: ``(k_scales, v_scales, slot_state,
        window_pools)``, None where the cache has none."""
        more = list(more)
        scales = (more.pop(0), more.pop(0)) if self._kv_quantized \
            else (None, None)
        state = self.cache.slot_state and tuple(
            more.pop(0) for _ in self.cache.slot_state)
        rings = tuple(more) if self.cache.window_pools is not None else None
        return scales + (state, rings)

    def _view(self, k_pools, v_pools, tables, lens, active, more):
        """The ONE view a decode program threads through the model's
        layers, over the program's own (donated) buffers."""
        k_scales, v_scales, slot_state, rings = self._split_more(more)
        return PagedCacheView(
            k_pools, v_pools, 0, tables, lens, active, self.block_size,
            k_scales=k_scales, v_scales=v_scales, kernel=self._attn_kernel,
            slot_state=slot_state, window_pools=rings,
            window=self.cache.spec.window)

    @staticmethod
    def _written(view):
        """The buffers a program hands back, in `buffers()`' order, from
        the view its last layer returned."""
        out = (view.k_pools, view.v_pools)
        if view.k_scales is not None:
            out += (view.k_scales, view.v_scales)
        if view.slot_state is not None:
            out += tuple(view.slot_state)
        if view.window_pools is not None:
            out += tuple(view.window_pools)
        return out

    def _sync_slot(self, req):
        """An admitted request into its slot of the host's arrays: what a
        launch is handed (table, length, active) and the RECORD of what
        the device holds of it (sampler values, history)."""
        slot = req.slot
        row = np.zeros(self.max_blocks_per_seq, np.int32)
        row[:len(req.blocks)] = req.blocks
        self._tables[slot] = row
        self._lens[slot] = req.cached_len
        self._active[slot] = True
        self._temps[slot] = req.temperature
        self._topks[slot] = req.top_k
        self._topps[slot] = req.top_p
        self._rpens[slot] = req.repetition_penalty
        self._seeds[slot] = req.seed or 0
        # rebuild the slot's context history from the COMMITTED tokens;
        # the in-graph scatter at index `lens` covers the one token a
        # pipelined launch knows only on-device
        ctx = req.prompt + req.generated
        self._history[slot] = 0
        n = min(len(ctx), self.max_context)
        self._history[slot, :n] = ctx[:n]

    def _clear_slot(self, slot):
        self._tables[slot] = 0
        self._lens[slot] = 0
        self._active[slot] = False
        self._tokens[slot] = 0
        # sampler no-op values keep a cleared slot on the all-greedy
        # cond branch (and out of the repetition-penalty seen set)
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._topps[slot] = 1.0
        self._rpens[slot] = 1.0
        self._seeds[slot] = 0
        self._history[slot] = 0
        self._override[slot] = True

    # ------------------------------------------------------------------
    # token delivery / retirement
    # ------------------------------------------------------------------
    def _emit_token(self, req, tok, logp=None, alts=None):
        req.generated.append(tok)
        # logprob panels stay index-aligned with `generated`: None for
        # tokens whose emitting step's outputs no longer exist (prefix
        # chew, crash resume, eager fallback)
        req.token_logprobs.append(logp)
        if alts is None:
            req.alt_ids.append(None)
            req.alt_logprobs.append(None)
        else:
            req.alt_ids.append([int(i) for i in np.asarray(alts[0])])
            req.alt_logprobs.append([float(v)
                                     for v in np.asarray(alts[1])])
        self._stats.tokens_generated += 1
        if req.temperature > 0:
            self._stats.sampled_tokens += 1
            if _metrics_on():
                _M.sampled_tokens.inc()
        now = time.perf_counter_ns()
        mon = _metrics_on()
        if req.first_token_ns is None:
            req.first_token_ns = now
            ttft_s = (now - req.enqueue_ns) / 1e9
            self._stats.ttft_hist.observe(ttft_s)
            if mon:
                _M.ttft_s.observe(ttft_s)
        elif req.last_token_ns is not None:
            gap_s = (now - req.last_token_ns) / 1e9
            self._stats.inter_token_hist.observe(gap_s)
            if mon:
                _M.inter_token_s.observe(gap_s)
        req.last_token_ns = now
        req.token_ns.append(now)
        if mon:
            _M.tokens.inc()
        if req.on_token is not None:
            text = None
            if self._tokenizer is not None:
                try:
                    text = self._tokenizer.decode([tok])
                except Exception:
                    text = None
            req.on_token(req, tok, text)
        done = len(req.generated) >= req.max_new_tokens
        if req.eos_token_id is not None and tok == req.eos_token_id:
            done = True
        if done:
            self._finish(req)

    def _finish(self, req):
        slot = req.slot
        self.scheduler.release(req)
        if slot is not None:
            self._clear_slot(slot)
        req.state = FINISHED
        req.finish_ns = time.perf_counter_ns()
        self._stats.completed += 1
        if _metrics_on():
            _M.requests.labels(outcome="completed").inc()
        _EVENTS.emit("serve.complete", req.rid,
                     detail={"tokens": len(req.generated),
                             "preemptions": req.preemptions})

    def _fail(self, req, why):
        slot = req.slot
        self.scheduler.release(req)
        if slot is not None:
            self._clear_slot(slot)
        req.state = FAILED
        req.error = why
        req.finish_ns = time.perf_counter_ns()
        self._stats.failed += 1
        if _metrics_on():
            _M.requests.labels(outcome="failed").inc()
        _EVENTS.emit("serve.complete", req.rid, reason=why,
                     detail={"failed": True,
                             "tokens": len(req.generated)})

    def _evict(self, victim):
        """Preempt-resume: forget the victim's KV (a block-table edit),
        requeue at its arrival position; resume re-prefills."""
        slot = victim.slot
        self._stats.evictions += 1
        if _metrics_on():
            _M.preemptions.inc()
        _EVENTS.emit("serve.evict", victim.rid, reason="kv_exhausted",
                     detail={"freed_blocks": len(victim.blocks),
                             "cached_tokens": victim.cached_len,
                             "preemptions": victim.preemptions + 1})
        self.scheduler.preempt(victim)
        if slot is not None:
            self._clear_slot(slot)

    # ------------------------------------------------------------------
    # watchdog + degraded-mode recovery (serving/resilience.py)
    # ------------------------------------------------------------------
    def _decode_args(self):
        """The decode program's positional arguments, from the engine's
        own buffers — the single source of truth shared by the launch,
        the AOT spec builder and the tests that lower the program. ONE
        host array, made anew every call: `[S, max_blocks_per_seq + 4]`,
        the block tables and the columns `_COL_TOKENS`, `_COL_OVERRIDE`,
        `_COL_LENS`, `_COL_ACTIVE` behind them (`_unpack_slots` is its
        reader). `feedback` is the previous launch's sampled tokens where
        they are, on the device; the program takes a slot's input from it
        unless `override` says the host wrote the slot's token
        (admission, chew, restore). With no launch to feed from every
        slot is overridden. The sampler's table and the history are the
        device's (`_sampler_state`)."""
        m = self.max_blocks_per_seq
        slots = np.empty((self.max_batch_size, m + 4), np.int32)
        slots[:, :m] = self._tables
        slots[:, m + _COL_TOKENS] = self._tokens
        slots[:, m + _COL_OVERRIDE] = self._override
        slots[:, m + _COL_LENS] = self._lens
        slots[:, m + _COL_ACTIVE] = self._active
        base = (slots, self._fed())
        if self._tenant:
            base = base + (self._decode_aux(),)
        return base + self._sampler_state() + self._bufs

    def _call_decode(self, args):
        fn = self._decode_fn
        stats = self._stats
        stats.launches += 1
        stats.launches_overlapped += self._inflight is not None
        stats.prefills_unawaited += len(self._joined)
        m = self.max_blocks_per_seq
        lens = args[0][:, m + _COL_LENS]
        active = args[0][:, m + _COL_ACTIVE] != 0
        stats.decode_tokens += int(np.count_nonzero(active))
        res = self._call_program("engine.decode.dispatch", fn, args,
                                 fn is not self._decode_called)
        self._decode_called = fn
        # counted behind the dispatch, beside the device
        self._count_attention(lens, active)
        return res

    def _count_attention(self, lens, active):
        """One decode launch's attention in block-table entries, from
        the lengths and the mask the launch is given: what the blockwise
        loop streams or the Pallas kernel copies, each counted by the
        plan its program traces; the dense oracle reads every entry."""
        total = self.max_batch_size * self.max_blocks_per_seq
        if self._attn_kernel == "pallas":
            streamed, held = pallas_copied_pages(
                lens, active, self.max_blocks_per_seq, self.block_size)
        else:
            streamed, held = blockwise_streamed_entries(
                lens, active, self.max_blocks_per_seq, self.block_size,
                self.cache.num_heads, self.cache.head_dim,
                **self.cache.spec.loop_plan(self.block_size))
            if self._attn_kernel != "blockwise":
                streamed = total
        stats = self._stats
        stats.attn_entries_streamed += streamed
        stats.attn_entries_held += held
        stats.attn_entries_total += total
        context = np.where(active, lens, 0).astype(np.int64) + active
        stats.attn_tokens_held += int(context.sum())
        spec = self.cache.spec
        if spec.window_layers:
            # a window layer reads its slots' rings through a table that
            # starts at the block of the window's oldest position
            # (nn/functional/attention.py paged_banded_decode_attention)
            ring = spec.ring_blocks(self.block_size)
            pos = np.where(active, lens, 0).astype(np.int64)
            first = np.maximum(pos - (spec.window - 1), 0) \
                // self.block_size
            eff = pos - first * self.block_size
            if self._attn_kernel == "pallas":
                streamed, held = pallas_copied_pages(
                    eff, active, ring, self.block_size)
            else:
                streamed, held = blockwise_streamed_entries(
                    eff, active, ring, self.block_size,
                    *spec.window_parts[0])
                if self._attn_kernel != "blockwise":
                    streamed = self.max_batch_size * ring
            stats.window_pages_streamed += streamed
            stats.window_pages_held += held
            stats.window_tokens_held += int(
                np.minimum(context, spec.window).sum())

    def _pools_consumed(self):
        return any(b.is_deleted() for b in self._bufs
                   if hasattr(b, "is_deleted"))

    def _degrade(self, reason, detail):
        """Enter (or deepen) degraded mode with an attributed
        transition."""
        self.degraded = True
        _EVENTS.emit("serve.degrade", "engine", reason=reason,
                     detail=detail)

    def _fallback_eager(self, req):
        """Finish one request via model.generate() from its prompt +
        emitted tokens; streams through the same on_token path."""
        self._stats.eager_fallbacks += 1
        _EVENTS.emit("serve.degrade", req.rid, reason="decode_fault",
                     detail={"fallback": "eager_generate",
                             "remaining": req.remaining_tokens})
        remaining = req.remaining_tokens
        if remaining > 0:
            ctx = np.asarray([req.prompt + req.generated], np.int64)
            if self._adapters is not None and req.adapter is not None:
                # the eager path folds the tenant's delta into the
                # weights (values only — generate's cached program does
                # not retrace) so the fallback serves the SAME model
                with self._adapters.merged(req.adapter):
                    out = self._model.generate(
                        ctx, max_new_tokens=remaining, do_sample=False)
            else:
                out = self._model.generate(ctx, max_new_tokens=remaining,
                                           do_sample=False)
            arr = np.asarray(out._value if hasattr(out, "_value")
                             else out)[0]
            for tok in arr.tolist():
                if req.finished:
                    break
                self._emit_token(req, int(tok))
        if not req.finished:
            self._finish(req)

    def _reset_slots(self):
        """The fixed slot layout the compiled programs consume, every
        slot empty, over the cache as it stands: at construction, and
        after a launch consumed or poisoned the KV buffers."""
        s, m = self.max_batch_size, self.max_blocks_per_seq
        self._tables = np.zeros((s, m), np.int32)
        self._lens = np.zeros(s, np.int32)
        self._active = np.zeros(s, bool)
        self._tokens = np.zeros(s, np.int32)
        # per-slot adapter index into the padded stacks (0 = base);
        # deliberately NOT reset by _clear_slot — a stale index on an
        # inactive slot is masked out, and clearing it would count a
        # spurious adapter switch on the next same-tenant admission
        self._aslots = np.zeros(s, np.int32)
        # -- compiled stochastic sampling (PR 18, serving/sampling.py) --
        # per-slot sampler config as fixed [S] VALUE buffers — edited
        # like tokens/lens on join/leave, never reshaping, so arbitrary
        # per-slot sampler churn keeps decode_compiles == 1. Greedy is
        # temperature=0 under the same program; the no-op values below
        # keep a cleared slot on the cheap all-greedy cond branch
        self._temps = np.zeros(s, np.float32)
        self._topks = np.zeros(s, np.int32)
        self._topps = np.ones(s, np.float32)
        self._rpens = np.ones(s, np.float32)
        self._seeds = np.zeros(s, np.uint32)
        # per-slot context-token history for the in-graph repetition
        # penalty; positions <= lens are valid. The decode step scatters
        # its own input token at index `lens` in-graph, so the one token
        # the host has not committed yet is still seen
        self._history = np.zeros((s, self.max_context), np.int32)
        # those six are the host's RECORD (`_sync_slot` on a resume,
        # `state_payload`); what the programs read is on the device: a
        # `[S, 5]` int32 table of the five (floats by their bits) and the
        # history, threaded from program to program as the pools are. A
        # prefill writes its slot's row of both from the request's values
        # and ids it is given anyway, a launch scatters its input tokens
        # and masks the table by `active` (a cleared slot uploads
        # nothing). None: stale. An edit of the record with no prefill
        # behind it (a prefix-hit admission, `restore_state`, a KV reset,
        # a rebuilt decode program) marks them so, and the next program
        # call is handed the record itself, once
        self._sampler_dev = None        # (table, history) on the device
        # -- software-pipelined decode (PR 18) ---------------------------
        # launch step N+1 against device-fed tokens while step N's host
        # commit overlaps: `_inflight` holds the un-committed launch
        # (its result arrays, and by slot the request, position and
        # admission it decoded for), `_feedback` the device next-token
        # array the next launch will consume, and `_override[slot]` marks
        # slots whose HOST token (admission / chew / restore) must win
        # over the device feedback: the decode program selects
        self._inflight = None
        self._feedback = None
        self._override = np.ones(s, bool)
        # -- prefills launched, not awaited ------------------------------
        # a prefill writes what it sampled into `_feedback` at its slot
        # (the next decode launch's input, on the device) and the token's
        # host-side half (id, logprob, panel, the model's counters) as
        # one int32 row of `_firsts`, both threaded from program to
        # program as the pools are. `_joined` holds, by slot, the
        # request, position and admission of the prefills dispatched
        # since the last launch; the launch takes them into its inflight
        # record, and the step after commits them from ONE fetch
        self._firsts = self._empty_firsts()
        self._joined = {}
        # the cache's device buffers (`PagedKVCache.buffers()`: the two
        # pools, the int8 scales, a per-slot state, whichever it has):
        # every program takes them last, donates them and hands them back
        self._bufs = self.cache.buffers()

    def _reset_kv_state(self):
        """Fresh block pool + slot arrays after a launch consumed or
        poisoned the KV buffers. Only legal with an empty running batch
        (callers retire it first); queued requests hold no blocks and
        re-prefill on admission."""
        assert not self.scheduler.running, \
            "KV reset with live streams would corrupt them"
        self.cache = PagedKVCache(self.cache.spec, self._num_blocks,
                                  self.block_size, self._kv_dtype,
                                  num_slots=self.max_batch_size,
                                  state_dtype=self._dtype)
        self.scheduler.allocator = self.cache.allocator
        self._reset_slots()
        if self._prefix is not None:
            # the old pool died with its allocator — the index's
            # references are meaningless now: forget, do not free
            self._prefix.reset(self.cache.allocator)

    # ------------------------------------------------------------------
    # crash-resume (serving/resilience.py + incubate.ServeCheckpointer)
    # ------------------------------------------------------------------
    def state_payload(self):
        """JSON-able snapshot of every in-flight request (prompt, emitted
        tokens, arrival order, remaining TTL) — NOT the KV pool nor a
        per-slot state, which the re-prefill on resume computes again,
        token-identically (`CacheSpec`'s rule). Saved each boundary by
        `incubate.checkpoint.ServeCheckpointer`; feed the loaded payload
        to `restore_state()` in the restarted process."""
        now = time.perf_counter_ns()
        # waiting + running IS the live set — O(live) per snapshot, so
        # the tick-every-step ServeCheckpointer pattern stays affordable
        # on a long-running server (the id registry may hold terminal
        # handles until pop_finished() drains them)
        live = sorted(list(self.scheduler.waiting)
                      + list(self.scheduler.running),
                      key=lambda r: (r.arrival_seq
                                     if r.arrival_seq is not None else -1))
        payload = {"version": 1, "kind": "serve_state",
                   "next_rid": self._next_rid,
                   "requests": [request_payload(r, now) for r in live]}
        if self._tenant:
            # the restore-time torn-swap check keys on these: a snapshot
            # taken under one weight epoch must not resume under another
            payload["weight_epoch"] = self._weight_epoch
            payload["weights_crc"] = self._weights_crc
            payload["swap_pending"] = self._pending_weights is not None
            payload["adapters"] = ([] if self._adapters is None
                                   else self._adapters.names())
        return payload

    def restore_state(self, payload, on_token=None):
        """Re-admit every request of a `state_payload()` snapshot in its
        original arrival order. Each resumes as QUEUED with its emitted
        tokens intact — first admission re-prefills prompt + generated
        and the stream continues byte-identically. `on_token` (callbacks
        never serialize): None, one callable for every request, or a
        {request_id: callable} mapping. Returns the restored Requests."""
        if not payload:
            return []
        crc = payload.get("weights_crc")
        if self._hot_swap and crc is not None \
                and crc != self._weights_crc:
            # torn swap: the snapshot was taken under a different weight
            # set than the one this process loaded — resuming would
            # decode half of every stream under each epoch. Refuse; the
            # supervisor loads the matching checkpoint and retries.
            _EVENTS.emit("serve.refuse", "engine", reason="torn_swap",
                         detail={"payload_crc": crc,
                                 "engine_crc": self._weights_crc,
                                 "payload_epoch":
                                     payload.get("weight_epoch"),
                                 "swap_pending":
                                     payload.get("swap_pending")})
            if _metrics_on():
                _M.refusals.labels(reason="torn_swap").inc()
            raise ServeRefusal(
                "torn_swap",
                f"state snapshot was taken under weights_crc {crc:#x} "
                f"but this engine serves {self._weights_crc:#x}; load "
                "the matching weight set before restoring",
                {"payload_crc": crc, "engine_crc": self._weights_crc})
        restored = []
        for rp in sorted(payload.get("requests", ()),
                         key=lambda p: p.get("arrival_seq") or 0):
            rid = rp["rid"]
            ad = rp.get("adapter")
            if ad is not None and (
                    self._adapters is None
                    or not self._adapters.is_registered(ad)):
                _EVENTS.emit("serve.refuse", rid,
                             reason="adapter_mismatch",
                             detail={"adapter": ad, "resume": True})
                if _metrics_on():
                    _M.refusals.labels(reason="adapter_mismatch").inc()
                raise ServeRefusal(
                    "adapter_mismatch",
                    f"restore_state: request {rid!r} decodes under "
                    f"adapter {ad!r}, which is not registered in this "
                    "engine; re-register every tenant before restoring",
                    {"rid": rid, "adapter": ad})
            prev = self.requests.get(rid)
            if prev is not None and not prev.finished:
                raise ValueError(
                    f"restore_state: request id {rid!r} is already live "
                    "in this engine")
            cb = (on_token.get(rid) if isinstance(on_token, dict)
                  else on_token)
            req = payload_request(rp, cb)
            self.scheduler.enqueue(req)
            self.requests[rid] = req
            self._stats.resumed += 1
            _EVENTS.emit("serve.resume", rid, reason="crash_resume",
                         detail={"generated": len(req.generated),
                                 "remaining": req.remaining_tokens})
            restored.append(req)
        # a process restored is one whose device state nobody vouches
        # for: the next program call is handed the host's record
        self._sampler_dev = None
        self._next_rid = max(self._next_rid,
                             int(payload.get("next_rid") or 0))
        self._weight_epoch = max(self._weight_epoch,
                                 int(payload.get("weight_epoch") or 0))
        return restored

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _donate(self, argnums):
        # CPU ignores buffer donation (with a warning per program) —
        # only request it where it is real
        return argnums if jax.default_backend() != "cpu" else ()

    def _aot_decode_digest(self):
        """Content address of the decode executable: model class + config
        + slot/pool geometry + a CRC over the weights, so a fine-tune or a
        resized pool re-keys instead of replaying stale math. Computed
        once per engine (the CRC walk is O(bytes), paid only with
        FLAGS_aot_cache on)."""
        if self._aot_digest_cache is not None:
            return self._aot_digest_cache or None
        from ..ops import aot_cache as _aot
        import zlib
        try:
            crc = 0
            if not self._hot_swap:
                # hot-swap mode passes the weights as VALUES — they are
                # not baked into the executable, so they must not key it
                for p in self._model.parameters():
                    v = np.asarray(p._value)
                    crc = zlib.crc32(
                        repr((v.shape, str(v.dtype))).encode(), crc)
                    crc = zlib.crc32(v.tobytes(), crc)
            cfg = {k: v for k, v in vars(self._model.config).items()
                   if isinstance(v, (int, float, bool, str, type(None)))}
            # tenant mode re-keys the artifact: the aux-input signature
            # (weights as values, adapter stack rank/shape) is a
            # different program from the baked-weights one
            tenant = (self._tenant, self._hot_swap,
                      0 if self._adapters is None
                      else (self._adapters.max_adapters,
                            self._adapters.rank))
            dg = _aot._digest_of(
                ("decode", type(self._model).__qualname__,
                 tuple(sorted(cfg.items())), self.max_batch_size,
                 self.block_size, self._num_blocks,
                 # the pool's shape is the donated arguments' signature:
                 # an artifact traced for another layout never replays
                 tuple(self.cache.k_pools.shape)
                 + tuple(n for part in self.cache.slot_state or ()
                         for n in part.shape),
                 self.max_blocks_per_seq, str(self._dtype),
                 # the kernel tier re-keys the artifact: a blockwise
                 # executable must never replay as the pallas one, and an
                 # int8 pool has a different signature entirely
                 self._attn_kernel, str(jnp.dtype(self._kv_dtype)), crc,
                 tenant,
                 # the sampler head is part of the program: its math
                 # version, the static logprob panel width and the
                 # history buffer width all change the executable
                 ("sampler", SAMPLER_VERSION, self._logprobs_topk,
                  self.max_context),
                 # the protocol of a call: one packed array each way, the
                 # sampler's table and history on the device
                 "packed"))
        except Exception:
            dg = None
        self._aot_digest_cache = dg or ""
        return dg

    def _maybe_store_decode(self):
        """Persist the decode executable after its first successful step
        (the export re-traces `decode`, honestly counted by
        decode_compiles — paid once, only in storing processes)."""
        pending, self._aot_pending_store = self._aot_pending_store, None
        if pending is None:
            return
        digest, jitted = pending
        from ..ops import aot_cache as _aot
        if not _aot.enabled() or _aot.has_artifact("decode", digest):
            return
        try:
            specs = tuple(_aot._spec_of(a) for a in self._decode_args())
            blobs = [_aot.export_bytes(jitted, specs)]
        except Exception as e:
            from ..profiler.aot import STATS as _ASTATS
            _ASTATS.store_failures += 1
            _EVENTS.emit("aot.store", "serve.decode",
                         detail={"kind": "decode",
                                 "failed": repr(e)[:200]})
            return
        _aot.store_artifact("decode", digest, "serve.decode", blobs,
                            meta={"max_batch_size": self.max_batch_size,
                                  "block_size": self.block_size})

    def _forward(self, ids, caches, length=None):
        """The model over `ids` through `caches`, inside a program's
        trace: ``(logits, caches, extra outputs)``, the extra being the
        model's own counters where its class names some (a prefill's
        `length` keeps its bucket's padding out of them, and tells a
        model that keeps a per-slot state where the prompt ends)."""
        kwargs = {}
        if (self._counter_names or self.cache.spec.state_layers) \
                and length is not None:
            kwargs["valid"] = (jnp.arange(ids.shape[1], dtype=jnp.int32)
                               < length)[None, :]
        with set_grad_enabled(False):
            logits, caches = self._model(
                Tensor(ids, stop_gradient=True), caches=caches, **kwargs)
        extra = ((self._model.pop_serve_counters(),)
                 if self._counter_names else ())
        return logits, caches, extra

    def _unpack_slots(self, slots, feedback):
        """The reader of a launch's packed array (`_decode_args`), inside
        a decode program's trace: ``(tokens, tables, lens, active)``. A
        slot's input is the token the launch before sampled for it,
        still on the device, unless the host wrote one."""
        m = self.max_blocks_per_seq
        tokens = jnp.where(slots[:, m + _COL_OVERRIDE] != 0,
                           slots[:, m + _COL_TOKENS], feedback)
        return (tokens, slots[:, :m], slots[:, m + _COL_LENS],
                slots[:, m + _COL_ACTIVE] != 0)

    @staticmethod
    def _sampler_columns(table):
        """The sampler's table (rows of `_sampler_row`) as
        `sample_tokens`' five arguments, each value the bits it was
        stored with."""
        def real(col):
            return jax.lax.bitcast_convert_type(table[:, col], jnp.float32)
        return (real(0), table[:, 1], real(2), real(3),
                jax.lax.bitcast_convert_type(table[:, 4], jnp.uint32))

    def _decode_results(self, logits, tokens, lens, active, sampler,
                        history, view, extra):
        """What both decode programs do behind the model's forward,
        inside their trace: the launch's tokens sampled and handed on
        where they are. Returns ``(tokens, rows, sampler, history) + the
        written buffers``: the sampled tokens stay a result of their own
        on the device (the next launch's `feedback`), `rows` is the ONE
        array the host fetches (`_rows`), the table and the history are
        threaded on."""
        # the in-graph history scatter: the input token enters the
        # context at index `lens` — it may exist ONLY on-device
        # (feedback). The history lives on the device, so
        # the scatter stands: an active slot's row holds its context up
        # to `lens` whatever the host has committed
        rows = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        idx = jnp.clip(lens, 0, history.shape[1] - 1)
        hist = history.at[rows, idx].set(
            jnp.where(active, tokens, history[rows, idx]))
        valid = (jnp.arange(history.shape[1], dtype=jnp.int32)[None, :]
                 <= lens[:, None])
        # a slot that is not active reads the row that samples nothing,
        # whatever its last request left in the table: a cleared slot
        # uploads nothing and still keeps the batch on the greedy branch
        table = jnp.where(active[:, None], sampler,
                          jnp.asarray(_SAMPLER_NOOP)[None, :])
        # sampling position = known context tokens = lens + 1; every
        # replay (preempt re-prefill, rebuild, kill-9 resume)
        # restores the same positions -> byte-identical streams
        nxt, logp, alt_ids, alt_lps = sample_tokens(
            logits._value[:, -1, :], *self._sampler_columns(table),
            lens + 1, hist, valid, logprobs_topk=self._logprobs_topk)
        return (nxt, self._rows(nxt, logp, alt_ids, alt_lps, extra),
                sampler, hist) + self._written(view)

    def _rebuild_decode(self):
        """The watchdog's rebuild: a FRESH trace in the suspect program's
        place (never the stored bytes, which may embody the fault), fed
        from the host's record: what the old one handed on of the
        sampler's table and the history is suspect with it."""
        self._compile_grace_ns = time.perf_counter_ns()
        self._sampler_dev = None
        self._decode_fn = self._build_decode(use_aot=False)

    def _build_decode(self, use_aot=True):
        if self._tenant:
            # the aux-input program: weights/adapters as values. AOT
            # export of a pytree-carrying signature is not supported —
            # tenant replicas always trace once at start
            return self._build_decode_tenant()
        stats = self._stats

        def decode(slots, feedback, sampler, history, k_pools, v_pools,
                   *more):
            stats.decode_compiles += 1   # runs only while tracing
            tokens, tables, lens, active = self._unpack_slots(slots,
                                                               feedback)
            # ONE view over the stacked (donated) pools: every layer
            # writes at its own index and hands them on, so the pools
            # the last layer returns are the step's, updated in place
            view = self._view(k_pools, v_pools, tables, lens, active, more)
            logits, (view,), extra = self._forward(tokens[:, None], [view])
            return self._decode_results(logits, tokens, lens, active,
                                        sampler, history, view, extra)

        donate = self._donated(4)
        jitted = jax.jit(decode, donate_argnums=donate)
        from ..ops import aot_cache as _aot
        if use_aot and _aot.enabled():
            # warm start: a restarted replica deserializes yesterday's
            # decode program and serves its first token without a trace.
            # The watchdog's rebuild passes use_aot=False — a suspect
            # program must be replaced by a FRESH trace, not by the very
            # bytes that may embody the fault
            digest = self._aot_decode_digest()
            if digest is not None:
                exe = _aot.load_callable(
                    "decode", digest, "serve.decode",
                    fallback=lambda: jitted,
                    donate_argnums=donate)
                if exe is not None:
                    return exe
                self._aot_pending_store = (digest, jitted)
        return jitted

    def _build_decode_tenant(self):
        """The multi-tenant decode executable: same fixed slot layout,
        plus an `aux` pytree of VALUE inputs — the base weights
        (hot-swap mode: a swap writes new values, never retraces) and
        the padded adapter stacks with the per-slot adapter index
        (tenant churn is a value edit). Weight substitution uses the
        same save/swap/restore idiom as `model.generate`: for the
        duration of the trace the parameters' `_value`s ARE the traced
        inputs. Compiles exactly once per engine, like the base
        program."""
        model = self._model
        stats = self._stats
        params = model.parameters()
        holder = self._holder

        def decode(slots, feedback, aux, sampler, history, k_pools,
                   v_pools, *more):
            stats.decode_compiles += 1   # runs only while tracing
            tokens, tables, lens, active = self._unpack_slots(slots,
                                                               feedback)
            pvals = aux.get("params")
            saved = None
            if pvals is not None:
                saved = [pp._value for pp in params]
                for pp, vv in zip(params, pvals):
                    pp._value = vv
            if holder is not None and "adapters" in aux:
                holder["active"] = AdapterSet.trace_ctx(
                    aux["adapters"], slots=aux["aslots"])
            try:
                view = self._view(k_pools, v_pools, tables, lens, active,
                                  more)
                logits, (view,), extra = self._forward(tokens[:, None],
                                                       [view])
            finally:
                if saved is not None:
                    for pp, vv in zip(params, saved):
                        pp._value = vv
                if holder is not None:
                    holder["active"] = None
            return self._decode_results(logits, tokens, lens, active,
                                        sampler, history, view, extra)

        return jax.jit(decode, donate_argnums=self._donated(5))

    def _unpack_prefill(self, packed):
        """The reader of a prefill's packed array (`_prefill_args`),
        inside a prefill program's trace: ``(ids [1, bucket], length,
        block row, slot, the request's row of the sampler's table)``."""
        m = self.max_blocks_per_seq
        bucket = packed.shape[0] - m - _PREFILL_TAIL
        tail = packed[bucket + m:]
        return (packed[None, :bucket], tail[0], packed[bucket:bucket + m],
                tail[1], tail[2:7])

    def _prefill_results(self, ids, length, block_row, row, slot,
                         feedback, firsts, sampler, history, k_pools,
                         v_pools, more, logits, caches, extra):
        """What both prefill programs do behind the model's forward,
        inside their trace: the prompt's KV into the pools, the first
        token sampled, and the token handed on WHERE IT IS: into
        `feedback` at the request's slot (the next decode launch's
        input) and, with its logprob, its panel and the model's counters,
        as one int32 row of `firsts` (floats by their bits), which the
        host fetches once for all of a boundary's prefills. The request's
        `row` of sampler values goes into the `sampler` table at its
        slot, and its ids into the slot's row of `history` (ids below
        `length`, zeros above: what `_sync_slot` records on the host), so
        that the launches find both on the device. Where the
        model keeps a per-slot state, the forward's caches end with the
        tuple of the state's parts for each layer that keeps one, as they
        stand after the prompt's `length`: written WHOLE at the request's
        slot (`CacheSpec`'s
        rule: a reused slot needs no clearing). Returns ``(feedback,
        firsts, sampler, history) + the written buffers``."""
        k_scales, v_scales, slot_state, rings = self._split_more(more)
        spec = self.cache.spec
        paged = caches[:spec.num_layers]
        windowed = caches[len(paged):len(paged) + spec.window_layers]

        def stacked(pairs, part):
            return jnp.stack([c[part]._value[0] for c in pairs])

        written = tuple(scatter_prefill(
            k_pools, v_pools, stacked(paged, 0), stacked(paged, 1),
            block_row, length, self.block_size, k_scales=k_scales,
            v_scales=v_scales))
        if slot_state is not None:
            # a tuple of parts a layer -> each part over the layers
            layers = caches[len(paged) + len(windowed):]
            written += tuple(
                held.at[:, slot].set(jnp.stack(
                    [layer[i]._value[0] for layer in layers])
                    .astype(held.dtype))
                for i, held in enumerate(slot_state))
        if rings is not None:
            # `CacheSpec`'s rule: the last `window` tokens before the
            # prompt's true length, into the slot's own ring
            written += tuple(scatter_window_prefill(
                *rings, stacked(windowed, 0), stacked(windowed, 1), slot,
                length, self.block_size, spec.window))
        last = jax.lax.dynamic_index_in_dim(
            logits._value[0], length - 1, axis=0, keepdims=False)
        # the prompt's first sampled token: position = prompt length
        # (the count of known context tokens), same convention as the
        # decode head — replays land on the same fold_in stream
        valid = (jnp.arange(ids.shape[1], dtype=jnp.int32)
                 < length)[None, :]
        nxt, logp, alt_ids, alt_lps = sample_tokens(
            last[None, :], *self._sampler_columns(row[None, :]),
            jnp.reshape(length, (1,)), ids.astype(jnp.int32), valid,
            logprobs_topk=self._logprobs_topk)
        # the slot's history as a launch reads it: the context's ids,
        # zeros behind them
        width = min(ids.shape[1], history.shape[1])
        known = jnp.zeros(history.shape[1], jnp.int32).at[:width].set(
            jnp.where(valid[0, :width], ids[0, :width], 0))
        return self._hand_on(feedback, firsts, slot, nxt, logp, alt_ids,
                             alt_lps, extra) \
            + (sampler.at[slot].set(row), history.at[slot].set(known)) \
            + written

    @staticmethod
    def _hand_on(feedback, firsts, slot, nxt, logp, alt_ids, alt_lps,
                 extra=()):
        """`feedback` with the sampled token at `slot`, and `firsts`
        with the slot's row (`_rows`)."""
        return (feedback.at[slot].set(nxt[0].astype(feedback.dtype)),
                firsts.at[slot].set(LLMEngine._rows(
                    nxt, logp, alt_ids, alt_lps, extra)[0]))

    @staticmethod
    def _rows(nxt, logp, alt_ids, alt_lps, extra=()):
        """What the programs hand the host of the tokens they sampled,
        one int32 row a token: token, logprob, the panel's ids and
        logprobs, the model's int32 counters of the call in every row
        (floats by their bits). A prefill's one row goes into `firsts`
        at its slot; a launch's `[S, width]` is its second result."""
        def bits(x):
            return jax.lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.int32)
        n = nxt.shape[0]
        return jnp.concatenate(
            [nxt[:, None], bits(logp)[:, None], alt_ids, bits(alt_lps)]
            + [jnp.broadcast_to(jnp.reshape(c, (1, -1)), (n, c.size))
               for c in extra], axis=1).astype(jnp.int32)

    def _build_prefill(self, bucket):
        if self._tenant:
            return self._build_prefill_tenant(bucket)
        spec = self.cache.spec
        params = self._model.parameters()
        dt = params[0]._value.dtype if params else jnp.float32
        stats = self._stats

        def prefill(packed, feedback, firsts, sampler, history, k_pools,
                    v_pools, *more):
            stats.prefill_compiles += 1   # runs only while tracing
            ids, length, block_row, slot, row = self._unpack_prefill(packed)
            logits, caches, extra = self._forward(
                ids, spec.empty_prefill(dt), length)
            return self._prefill_results(
                ids, length, block_row, row, slot, feedback, firsts,
                sampler, history, k_pools, v_pools, more, logits, caches,
                extra)

        return jax.jit(prefill, donate_argnums=self._donated(5))

    def _build_prefill_tenant(self, bucket):
        """Tenant twin of `_build_prefill`: the same bucketed prompt
        program with the aux pytree (weights as values in hot-swap mode;
        the one admitted request's scalar adapter slot)."""
        spec = self.cache.spec
        params = self._model.parameters()
        dt = params[0]._value.dtype if params else jnp.float32
        stats = self._stats
        holder = self._holder

        def prefill(packed, aux, feedback, firsts, sampler, history,
                    k_pools, v_pools, *more):
            stats.prefill_compiles += 1   # runs only while tracing
            ids, length, block_row, slot, row = self._unpack_prefill(packed)
            pvals = aux.get("params")
            saved = None
            if pvals is not None:
                saved = [pp._value for pp in params]
                for pp, vv in zip(params, pvals):
                    pp._value = vv
            if holder is not None and "adapters" in aux:
                holder["active"] = AdapterSet.trace_ctx(
                    aux["adapters"], slot=aux["slot"])
            try:
                logits, caches, extra = self._forward(
                    ids, spec.empty_prefill(dt), length)
            finally:
                if saved is not None:
                    for pp, vv in zip(params, saved):
                        pp._value = vv
                if holder is not None:
                    holder["active"] = None
            return self._prefill_results(
                ids, length, block_row, row, slot, feedback, firsts,
                sampler, history, k_pools, v_pools, more, logits, caches,
                extra)

        return jax.jit(prefill, donate_argnums=self._donated(6))

    # ------------------------------------------------------------------
    # multi-tenant serving (PR 17, serving/tenancy.py)
    # ------------------------------------------------------------------
    def _decode_aux(self):
        """The decode executable's aux VALUE inputs — a pytree with a
        STABLE structure per engine config (keys never appear or vanish
        between calls), so churning its values never re-keys the
        program."""
        aux = {}
        if self._weights_as_args:
            aux["params"] = [p._value
                             for p in self._model.parameters()]
        if self._adapters is not None:
            aux["adapters"] = self._adapters.device_stacks()
            aux["aslots"] = jnp.asarray(self._aslots)
        return aux

    def _prefill_aux(self, req):
        aux = {}
        if self._weights_as_args:
            aux["params"] = [p._value
                             for p in self._model.parameters()]
        if self._adapters is not None:
            aux["adapters"] = self._adapters.device_stacks()
            aux["slot"] = jnp.asarray(
                self._adapters.slot_of(req.adapter), jnp.int32)
        return aux

    def _prefix_hook(self, req):
        """try_admit's shared-prefix acquisition: the longest cached
        block run matching the head's context, increfed for the
        admission. The scheduler undoes the claim symmetrically when
        admission fails anyway (watermark / pool pressure)."""
        return self._prefix.acquire(req.prompt + req.generated)

    def _reclaim_prefix(self, num_free_target):
        """Drop cold prefix-cache entries (leaf-first, LRU) until the
        allocator can serve `num_free_target` free blocks. Attribution
        happens HERE, after the cache released its lock (R6: no events
        under a lock). True when anything was freed."""
        dropped = self._prefix.reclaim(num_free_target)
        if not dropped:
            return False
        self._stats.prefix_evictions += dropped
        _EVENTS.emit("serve.prefix_evict", "engine",
                     detail={"entries": dropped,
                             "free_blocks":
                                 self.cache.allocator.num_free})
        return True

    def _cow_sweep(self):
        """Copy-on-write boundary: before the decode step writes each
        stream's next-token KV at position `cached_len`, any stream
        whose target block is still SHARED (refcount > 1 — a prefix
        entry and/or sibling streams also own it) gets a private copy:
        one jitted block copy, a host table edit, a decref of the
        original. The first divergent write therefore never clobbers KV
        another stream is attending over."""
        sched = self.scheduler
        alloc = self.cache.allocator
        for req in sorted(list(sched.running),
                          key=lambda r: r.admit_seq):
            if req.state != RUNNING:
                continue      # evicted/failed by an earlier COW's ladder
            wi = req.cached_len // self.block_size
            if wi >= len(req.blocks):
                continue
            src = req.blocks[wi]
            if alloc.refcount(src) <= 1:
                continue
            got = alloc.allocate(1)
            while got is None:
                # same pressure ladder as growth: cold prefix entries
                # first, then LIFO preemption, then give up on this one
                if self._reclaim_prefix(1):
                    got = alloc.allocate(1)
                    continue
                victim = sched.preempt_victim(exclude=req)
                if victim is None:
                    break
                self._evict(victim)
                got = alloc.allocate(1)
            if got is None:
                if not sched.protected(req):
                    self._evict(req)
                else:
                    self._fail(req, "kv_exhausted")
                continue
            dst = got[0]
            self._copy_block(src, dst)
            alloc.free([src])
            req.blocks[wi] = dst
            self._sync_slot(req)
            self._stats.cow_copies += 1

    def _copy_block(self, src, dst):
        """One jitted block copy over every buffer of the cache (all
        layers, K+V, and the int8 scale rows: each is `[layer, block,
        ...]`). src/dst are traced int32 scalars, so the copy program
        compiles once and serves every COW."""
        if self._cow_fn is None:
            def cow(src, dst, *bufs):
                return tuple(b.at[:, dst].set(b[:, src]) for b in bufs)

            self._cow_fn = jax.jit(cow, donate_argnums=self._donated(2))
        self._bufs = self._cow_fn(jnp.asarray(src, jnp.int32),
                                  jnp.asarray(dst, jnp.int32), *self._bufs)

    def _params_crc(self):
        """CRC over every parameter's bytes — the weight-set identity
        the hot-swap cutover and the crash-resume torn-swap check key
        on."""
        import zlib
        crc = 0
        for p in self._model.parameters():
            crc = zlib.crc32(np.asarray(p._value).tobytes(), crc)
        return crc

    def register_adapter(self, name, weights=None, scale=1.0, seed=None):
        """Install a tenant's LoRA-style adapter into a free stack slot
        (a VALUE edit of the padded stacks — zero retraces). See
        `tenancy.AdapterSet.register` for the weights layout."""
        if self._adapters is None:
            raise ValueError(
                "engine was built with max_adapters=0; adapters need "
                "max_adapters > 0 at construction (the stack shapes are "
                "baked into the decode executable)")
        return self._adapters.register(name, weights=weights,
                                       scale=scale, seed=seed)

    def unregister_adapter(self, name):
        """Free a departed tenant's slot. Refuses while any live stream
        still decodes under the adapter — zeroing the slot mid-stream
        would silently cut those streams over to base weights."""
        if self._adapters is None:
            raise ValueError("engine was built with max_adapters=0")
        live = [r.rid for r in (list(self.scheduler.waiting)
                                + list(self.scheduler.running))
                if r.adapter == name]
        if live:
            raise ValueError(
                f"adapter {name!r} still serves live requests {live}; "
                "drain or cancel them first")
        return self._adapters.unregister(name)

    def stage_weights(self, values):
        """Stage a live weight hot-swap: `values` (one array per
        `model.parameters()` entry, same shapes) replaces the base
        weights at the next iteration boundary — a byte-exact cutover:
        every token of every stream is produced entirely under one
        weight set or the other, never a mix. Returns True when staged;
        False when the incoming set is byte-identical to the serving
        one (attributed as a skipped `serve.swap`)."""
        if not self._hot_swap:
            raise ValueError(
                "engine was built without hot_swap=True — its weights "
                "are baked into the compiled programs as constants")
        import zlib
        params = self._model.parameters()
        if len(values) != len(params):
            raise ValueError(
                f"stage_weights: got {len(values)} arrays for "
                f"{len(params)} parameters")
        vals, crc = [], 0
        for p, v in zip(params, values):
            arr = jnp.asarray(v).astype(p._value.dtype)
            if arr.shape != p._value.shape:
                raise ValueError(
                    f"stage_weights: shape {arr.shape} does not match "
                    f"parameter shape {p._value.shape}")
            vals.append(arr)
            crc = zlib.crc32(np.asarray(arr).tobytes(), crc)
        if crc == self._weights_crc and self._pending_weights is None:
            _EVENTS.emit("serve.swap", "engine",
                         detail={"skipped": True, "crc_match": True,
                                 "epoch": self._weight_epoch})
            return False
        self._pending_weights = (vals, crc)
        return True

    def swap_weights(self, values):
        """Stage + commit a hot-swap. Called between steps (the usual
        checkpoint-watcher pattern) the cutover happens immediately;
        called from inside a streaming callback mid-step it commits at
        the next iteration boundary. Returns the serving weight epoch
        after the call."""
        if self.stage_weights(values) and not self._stepping:
            self._commit_swap()
        return self._weight_epoch

    def _commit_swap(self):
        """The cutover: preempt every running stream (they re-prefill
        under the new weights and continue from their emitted tokens),
        invalidate the prefix index (cached KV is a function of the base
        weights), write the staged values into the parameters, bump the
        epoch. No compiled program is touched — the weights are VALUE
        inputs."""
        # a pipelined launch in flight was issued under the OLD weights:
        # commit its tokens before the preemption sweep discards them
        self._flush_inflight()
        values, crc = self._pending_weights
        self._pending_weights = None
        sched = self.scheduler
        preempted = 0
        for req in list(sched.running):
            # scheduler.preempt directly — NOT _evict: this is a planned
            # cutover, not kv pressure, and must not pollute the
            # kv_exhausted eviction attribution
            slot = req.slot
            sched.preempt(req)
            if slot is not None:
                self._clear_slot(slot)
            preempted += 1
        dropped = (self._prefix.invalidate()
                   if self._prefix is not None else 0)
        for p, v in zip(self._model.parameters(), values):
            p._value = v
        self._weight_epoch += 1
        self._weights_crc = crc
        self._stats.weight_swaps += 1
        if _metrics_on():
            _M.weight_swaps.inc()
        _EVENTS.emit("serve.swap", "engine",
                     detail={"epoch": self._weight_epoch,
                             "preempted": preempted,
                             "prefix_dropped": dropped})

    @property
    def weight_epoch(self):
        """Serving weight-set generation (0 = construction weights)."""
        return self._weight_epoch
