"""Profiler. Reference analog: python/paddle/profiler/profiler.py:339
(Profiler, ProfilerState, export_chrome_tracing) over platform/profiler/ C++
tracers (HostTracer + CudaTracer/CUPTI).

TPU-first: the device timeline comes from the jax/XLA profiler (xplane →
TensorBoard/perfetto), the CUPTI analog, and `RecordEvent` — the program's
one span class — writes every host span into that same trace, on its
clock, as a `jax.profiler.TraceAnnotation`: "tracing on" means a
`jax.profiler` session is open (`Profiler(targets=[TPU])` opens one).
While a `Profiler` records, spans also land in its chrome-trace lane (the
HostTracer analog); with neither open a span costs an inactive TraceMe and,
where its owner gave it a histogram, one observation. `timer` provides the
ips/tokens-per-second benchmark hooks (reference: profiler/timer.py).
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time
import weakref
from enum import Enum

import jax

from .dispatch import (DispatchStats, dispatch_cache_stats,
                       reset_dispatch_cache_stats)
from .chain_fusion import (ChainFusionStats, chain_fusion_stats,
                           reset_chain_fusion_stats)
from .step_fusion import (StepFusionStats, step_fusion_stats,
                          reset_step_fusion_stats)
from .aot import (AotCacheStats, aot_cache_stats, reset_aot_cache_stats)
from .events import (EVENTS, CATEGORIES, REASON_CODES, FusionEventLog,
                     fusion_events, clear_fusion_events,
                     fusion_events_enabled, events_summary)
from .metrics import (Counter, Gauge, LogHistogram, MetricsRegistry,
                      REGISTRY, METRIC_NAMES, metrics_snapshot,
                      merge_snapshots, reset_metrics,
                      format_metrics_summary)
from .goodput import (GoodputAccountant, ACCOUNTANT, goodput_snapshot,
                      estimate_cycle_flops, peak_flops_per_chip)

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "watch_gc",
           "make_scheduler", "export_chrome_tracing", "export_protobuf",
           "load_profiler_result", "benchmark", "SortedKeys", "SummaryView",
           "DispatchStats", "dispatch_cache_stats",
           "reset_dispatch_cache_stats", "ChainFusionStats",
           "chain_fusion_stats", "reset_chain_fusion_stats",
           "StepFusionStats", "step_fusion_stats",
           "reset_step_fusion_stats",
           "AotCacheStats", "aot_cache_stats", "reset_aot_cache_stats",
           "CATEGORIES", "REASON_CODES", "FusionEventLog", "fusion_events",
           "clear_fusion_events", "fusion_events_enabled", "events_summary",
           "LoadedProfilerResult",
           "Counter", "Gauge", "LogHistogram", "MetricsRegistry",
           "REGISTRY", "METRIC_NAMES", "metrics_snapshot",
           "merge_snapshots", "reset_metrics", "format_metrics_summary",
           "GoodputAccountant", "ACCOUNTANT", "goodput_snapshot",
           "estimate_cycle_flops", "peak_flops_per_chip"]


class SortedKeys(Enum):
    """Summary-table sort keys (reference profiler_statistic.py:48). GPU*
    keys sort by device (TPU) time here."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    """Summary view selector (reference profiler.py:41). FusionView is
    TPU-native (no reference analog): the dispatch/fusion pipeline's
    counters + flight-recorder split tables."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
    FusionView = 9


def export_protobuf(dir_name, worker_name=None):
    """on_trace_ready handler writing the serialized trace (reference
    profiler.py:265 writes the protobuf dump; here the artifact is the
    host-tracer event table in its binary pickle form — the xplane/
    TensorBoard protobuf export is jax.profiler's job on TPU)."""
    import pickle

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{int(time.time())}.pb")
        prof._export_path = path
        with open(path, "wb") as f:
            pickle.dump(prof._events, f, protocol=4)
    return handler


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class _HostEventRecorder:
    """Thread-local event collection (platform/profiler/host_event_recorder.h
    analog)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events = []

    def add(self, name, ts, dur, tid):
        with self._lock:
            self.events.append({"name": name, "ts": ts, "dur": dur,
                                "tid": tid, "ph": "X", "pid": os.getpid(),
                                "cat": "host"})

    def drain(self):
        with self._lock:
            ev, self.events = self.events, []
        return ev


_recorder = _HostEventRecorder()
_active_profiler = None


class RecordEvent:
    """Scoped host span (reference: profiler/event_tracing.h RecordEvent +
    python profiler/utils.py RecordEvent).

    Clock and gating: entering always opens a `jax.profiler.TraceAnnotation`
    of the same name, so inside a `jax.profiler` session the span is on the
    host plane of the `.xplane.pb` that holds the device's `XLA Ops`, nested
    by time under the caller's span; with no session it is an inactive
    TraceMe. `hist` is a bounded histogram (`LogHistogram`) held by the
    object whose work is timed: the span's seconds are observed there on
    exit, session or not. A parent's self time is its histogram's sum less
    its children's. Only while a `Profiler` records does the span also go
    to its chrome-trace lane (native host tracer, else the in-process
    recorder); otherwise neither is touched, so a hot path neither builds
    the native library nor grows a list nobody drains."""

    __slots__ = ("name", "_hist", "_begin", "_annotation")

    def __init__(self, name, event_type=None, hist=None):
        self.name = name
        self._hist = hist
        self._begin = None
        self._annotation = None

    def begin(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        # CLOCK_MONOTONIC, which is also the native host tracer's clock
        self._begin = time.perf_counter_ns()

    def end(self):
        if self._begin is None:
            return
        begin, now = self._begin, time.perf_counter_ns()
        self._begin = None
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        if self._hist is not None:
            self._hist.observe((now - begin) * 1e-9)
        if _active_profiler is not None:
            from ..core import host_tracer
            if host_tracer.is_native:
                host_tracer.span(self.name, begin, now)
            else:
                _recorder.add(self.name, begin / 1000.0,
                              (now - begin) / 1000.0,
                              threading.get_ident())

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


# (span name, weak reference to the histogram) of every `watch_gc`, and
# the spans of the collection that is running
_gc_watchers = []
_gc_open = []


def watch_gc(span_name, hist):
    """Every collection of the Python heap, from now on, is a span
    `span_name` that feeds `hist`: a `RecordEvent` opened when the
    collector starts and closed when it stops, so on the xplane's host
    plane it lies INSIDE whatever span the collection interrupted, and a
    reader that names a moment by its innermost span names the collection.
    One `gc.callbacks` entry serves every watcher of the process, installed
    by the first. `hist` is held weakly: the watch ends with the object
    that owns the histogram."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    _gc_watchers.append((span_name, weakref.ref(hist)))


def _on_gc(phase, info):
    if phase == "start":
        gone = False
        for name, ref in _gc_watchers:
            hist = ref()
            if hist is None:
                gone = True
                continue
            span = RecordEvent(name, hist=hist)
            span.begin()
            _gc_open.append(span)
        if gone:
            _gc_watchers[:] = [w for w in _gc_watchers
                               if w[1]() is not None]
    else:
        while _gc_open:
            _gc_open.pop().end()


def make_scheduler(closed, ready, record, repeat=0, skip_first=0):
    total = closed + ready + record

    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{int(time.time())}.json")
        prof._export_path = path
        prof.export(path)
    return handler


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU]
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._step = 0
        self._events = []
        self._fusion_events = []
        self._events_flag_prev = None
        self._events_since = 0
        self._jax_trace_dir = None
        self._state = ProfilerState.CLOSED

    def start(self):
        global _active_profiler
        _active_profiler = self
        _recorder.drain()
        from ..core import host_tracer
        host_tracer.harvest()          # discard pre-start events
        host_tracer.enable(True)
        # fusion flight recorder (events.py): auto-armed for the window so
        # the exported trace always carries the dispatch/chain/step lanes;
        # the flag is restored on stop() (a user who set it globally keeps
        # recording past the window)
        if not self.timer_only:
            from ..framework.flags import _FLAGS
            self._events_flag_prev = bool(_FLAGS.get("FLAGS_profiler_events"))
            _FLAGS["FLAGS_profiler_events"] = True
            self._events_since = EVENTS.total
        self._state = ProfilerState.RECORD
        if not self.timer_only and ProfilerTarget.TPU in self.targets:
            import tempfile
            import jax
            self._jax_trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            try:
                jax.profiler.start_trace(self._jax_trace_dir)
            except Exception:
                self._jax_trace_dir = None
        return self

    def _drain_native(self):
        from ..core import host_tracer
        for name, b_ns, e_ns, tid in host_tracer.harvest():
            self._events.append({"name": name, "ts": b_ns / 1000.0,
                                 "dur": (e_ns - b_ns) / 1000.0, "tid": tid,
                                 "ph": "X", "pid": os.getpid(),
                                 "cat": "host"})

    def _drain_fusion(self):
        """Pull the window's fusion events out of the ring. Drained
        incrementally (stop() and every step()) so a long window survives
        ring wraparound: only events older than the last drain can be
        lost, and the `since` high-water mark makes drains disjoint."""
        if self._events_flag_prev is None:
            return
        new = EVENTS.snapshot(since_seq=self._events_since)
        if new:
            self._fusion_events.extend(new)
            self._events_since = new[-1]["seq"]

    def stop(self):
        global _active_profiler
        self._events.extend(_recorder.drain())
        self._drain_native()
        self._drain_fusion()
        if self._events_flag_prev is not None:
            from ..framework.flags import _FLAGS
            _FLAGS["FLAGS_profiler_events"] = self._events_flag_prev
            self._events_flag_prev = None
        from ..core import host_tracer
        host_tracer.enable(False)
        if self._jax_trace_dir:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        self._state = ProfilerState.CLOSED
        _active_profiler = None
        if self._on_trace_ready:
            self._on_trace_ready(self)
        return self

    def step(self, num_samples=None):
        self._step += 1
        self._events.extend(_recorder.drain())
        self._drain_native()
        self._drain_fusion()
        benchmark().step(num_samples)

    def step_info(self, unit=None):
        return benchmark().step_info(unit)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def export(self, path, format="json"):
        """Chrome-trace JSON: host lane(s) + one synthetic lane per fusion
        tier (dispatch/chain/step). With `targets=[TPU]` the same host
        spans are also IN the XLA xplane under `jax_trace_dir`, on the
        device operations' clock (`RecordEvent`); this JSON is the lane
        that needs no device profile. The raw event dicts also
        ride along under `fusion_events` so `load_profiler_result`
        round-trips without loss (the lane projection is lossy: chrome
        args stringify keys)."""
        with open(path, "w") as f:
            json.dump({"traceEvents":
                       self._events + _fusion_trace_events(
                           self._fusion_events),
                       "displayTimeUnit": "ms",
                       "fusion_events": self._fusion_events,
                       "jax_trace_dir": self._jax_trace_dir}, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        from collections import defaultdict
        agg = defaultdict(lambda: [0, 0.0, 0.0, float("inf")])
        for e in self._events:
            a = agg[e["name"]]
            a[0] += 1
            a[1] += e["dur"]
            a[2] = max(a[2], e["dur"])
            a[3] = min(a[3], e["dur"])
        # host events only (no separate device timeline — GPU* keys sort
        # by the same host-measured durations)
        key = {
            SortedKeys.CPUTotal: lambda kv: -kv[1][1],
            SortedKeys.GPUTotal: lambda kv: -kv[1][1],
            SortedKeys.CPUAvg: lambda kv: -(kv[1][1] / max(kv[1][0], 1)),
            SortedKeys.GPUAvg: lambda kv: -(kv[1][1] / max(kv[1][0], 1)),
            SortedKeys.CPUMax: lambda kv: -kv[1][2],
            SortedKeys.GPUMax: lambda kv: -kv[1][2],
            SortedKeys.CPUMin: lambda kv: kv[1][3],
            SortedKeys.GPUMin: lambda kv: kv[1][3],
        }.get(sorted_by, lambda kv: -kv[1][1])
        lines = [f"{'name':<40} {'calls':>8} {'total_us':>12}"]
        for name, (calls, dur, _mx, _mn) in sorted(agg.items(), key=key):
            lines.append(f"{name:<40} {calls:>8} {dur:>12.1f}")
        table = "\n".join(lines)
        # FusionView: the dispatch/fusion pipeline counters + the window's
        # flight-recorder split tables, folded into the same summary so
        # one call shows the whole picture (host time AND why/where the
        # fusion tiers hit, split, or never promoted)
        if isinstance(views, SummaryView):
            views = [views]
        if views is None or SummaryView.FusionView in views:
            table += _fusion_summary_table(self._fusion_events,
                                           time_unit=time_unit)
        print(table)
        return table


# synthetic chrome-trace tids for the fusion lifecycle lanes; thread_name
# metadata labels them in perfetto. High values keep clear of real tids.
_FUSION_LANE_TID = {"dispatch": 0x7F5E0001, "chain": 0x7F5E0002,
                    "step": 0x7F5E0003, "serve": 0x7F5E0004,
                    "aot": 0x7F5E0005, "kernel": 0x7F5E0006}

# serve.* categories that begin / end one request's async span (the
# per-request serving trace: enqueue -> admit -> decode ticks ->
# complete/evict/cancel/expire, rendered as an async track in perfetto)
_SERVE_SPAN_BEGIN = "serve.enqueue"
_SERVE_SPAN_END = frozenset({"serve.complete", "serve.cancel",
                             "serve.expire"})
# (refusals never open a span — serve.refuse fires before serve.enqueue
# — so they render as plain serve-lane instants, not span marks)
_SERVE_SPAN_MARK = frozenset({"serve.admit", "serve.evict",
                              "serve.resume"})


def _serve_request_spans(fusion_events, pid):
    """Per-request async spans beside the fusion lanes: each request id
    opens an async 'b' event at serve.enqueue, records admission /
    eviction / resume as nested 'n' instants, and closes with 'e' at its
    terminal event — so perfetto shows every request's enqueue -> admit
    -> decode -> complete lifetime as one bar under the serve lane."""
    out = []
    open_spans = {}
    tid = _FUSION_LANE_TID["serve"]
    for e in fusion_events:
        cat = e["cat"]
        if not cat.startswith("serve."):
            continue
        rid = e.get("op")
        if not rid or rid == "engine":
            continue
        ts = e["ts_ns"] / 1000.0
        base = {"cat": "serve.request", "id": rid, "pid": pid, "tid": tid}
        if cat == _SERVE_SPAN_BEGIN:
            open_spans[rid] = ts
            out.append({**base, "name": f"request {rid}", "ph": "b",
                        "ts": ts,
                        "args": {k: v for k, v in
                                 (e.get("detail") or {}).items()}})
        elif cat in _SERVE_SPAN_END and rid in open_spans:
            out.append({**base, "name": f"request {rid}", "ph": "e",
                        "ts": ts,
                        "args": {"outcome": cat.split(".", 1)[1],
                                 "reason": e.get("reason")}})
            del open_spans[rid]
        elif cat in _SERVE_SPAN_MARK and rid in open_spans:
            out.append({**base, "name": cat.split(".", 1)[1], "ph": "n",
                        "ts": ts,
                        "args": {"reason": e.get("reason"),
                                 "detail": e.get("detail")}})
    return out


def _fusion_trace_events(fusion_events):
    """Project flight-recorder event dicts into chrome-trace instant
    events: one lane (synthetic tid) per tier (dispatch / chain / step /
    serve / aot / kernel) plus per-request async spans, so perfetto shows
    the fusion lifecycles and every serving request's lifetime as
    parallel tracks under the host timeline."""
    if not fusion_events:
        return []
    pid = os.getpid()
    out = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": f"fusion:{tier}"}}
           for tier, tid in _FUSION_LANE_TID.items()]
    for e in fusion_events:
        tier = e["cat"].split(".", 1)[0]
        name = e["cat"] if not e.get("op") else f"{e['cat']}({e['op']})"
        if e.get("reason"):
            name += f" [{e['reason']}]"
        rec = {"name": name, "ph": "i", "s": "t",
               "ts": e["ts_ns"] / 1000.0, "pid": pid,
               "tid": _FUSION_LANE_TID.get(tier, _FUSION_LANE_TID["step"]),
               "cat": f"fusion.{tier}",
               "args": {k: e[k] for k in ("seq", "tid", "op", "key",
                                          "reason", "detail")
                        if e.get(k) is not None}}
        out.append(rec)
    out.extend(_serve_request_spans(fusion_events, pid))
    return out


def _fusion_summary_table(fusion_events, time_unit="ms"):
    """FusionView text: the three counter structs folded with the
    flight-recorder aggregation (per-category counts + per-reason split/
    bypass attribution)."""
    lines = ["", "---------------- Fusion View ----------------"]

    def block(title, d):
        lines.append(f"{title}:")
        for k, v in d.items():
            if isinstance(v, dict):
                continue
            lines.append(f"  {k:<28} {v}")

    block("dispatch_cache", dispatch_cache_stats())
    block("chain_fusion", chain_fusion_stats())
    block("step_fusion", step_fusion_stats())
    block("aot_cache", aot_cache_stats())
    agg = events_summary(fusion_events)
    lines.append(f"fusion events ({agg['events']} in window):")
    for cat, n in agg["by_category"].items():
        lines.append(f"  {cat:<28} {n}")
    if agg["reasons"]:
        lines.append(f"{'split/bypass reason':<40} {'count':>8}")
        for key, n in sorted(agg["reasons"].items(),
                             key=lambda kv: -kv[1]):
            lines.append(f"  {key:<38} {n:>8}")
        by_op = [(k, n) for k, n in agg["by_op"].items()
                 if k.rsplit(":", 1)[-1]]
        for key, n in sorted(by_op, key=lambda kv: -kv[1])[:20]:
            lines.append(f"    {key:<36} {n:>8}")
    return "\n".join(lines)


class LoadedProfilerResult(dict):
    """`load_profiler_result` return value: the exported JSON dict plus
    re-summarization over the round-tripped lanes — `trace_events`,
    `fusion_events`, `events_summary()` and `summary()` re-aggregate from
    the file with no live profiler state."""

    @property
    def trace_events(self):
        return self.get("traceEvents", [])

    @property
    def fusion_events(self):
        return self.get("fusion_events", [])

    def events_summary(self):
        return events_summary(self.fusion_events)

    def summary(self):
        from collections import defaultdict
        agg = defaultdict(lambda: [0, 0.0])
        for e in self.trace_events:
            if e.get("ph") != "X":
                continue
            a = agg[e["name"]]
            a[0] += 1
            a[1] += e.get("dur", 0.0)
        lines = [f"{'name':<40} {'calls':>8} {'total_us':>12}"]
        for name, (calls, dur) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40} {calls:>8} {dur:>12.1f}")
        ev = self.fusion_events
        if ev:
            a = self.events_summary()
            lines.append(f"fusion events: {a['events']}")
            for cat, n in a["by_category"].items():
                lines.append(f"  {cat:<28} {n}")
            for key, n in sorted(a["reasons"].items(),
                                 key=lambda kv: -kv[1]):
                lines.append(f"  {key:<38} {n:>8}")
        return "\n".join(lines)


def load_profiler_result(filename):
    with open(filename) as f:
        return LoadedProfilerResult(json.load(f))


class _Benchmark:
    """ips/throughput tracker (reference: python/paddle/profiler/timer.py)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._last = None
        self._steps = 0
        self._total_time = 0.0
        self._total_samples = 0
        self._window = []

    def begin(self):
        self.reset()
        self._last = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            dt = now - self._last
            self._total_time += dt
            self._steps += 1
            if num_samples:
                self._total_samples += num_samples
                self._window.append((num_samples, dt))
                if len(self._window) > 100:
                    self._window.pop(0)
        self._last = now

    def step_info(self, unit=None):
        if not self._steps:
            return "no steps recorded"
        avg = self._total_time / self._steps
        ips = ""
        if self._window:
            n = sum(w[0] for w in self._window)
            t = sum(w[1] for w in self._window)
            ips = f" ips: {n / t:.3f} {unit or 'samples'}/s"
        return f"batch_cost: {avg:.5f} s{ips}"

    @property
    def ips(self):
        if self._total_time == 0:
            return 0.0
        return self._total_samples / self._total_time

    def end(self):
        self._last = None


_benchmark = _Benchmark()


def benchmark():
    return _benchmark
