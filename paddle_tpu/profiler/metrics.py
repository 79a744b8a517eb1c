"""Production telemetry plane: a typed, thread-safe metrics registry.

The fusion stack's counter structs (profiler/{dispatch,chain_fusion,
step_fusion,aot}.py, ops/guardian.py, serving ServeStats) say how often
things happened *inside one subsystem*;
nothing there is an always-on, queryable metrics plane a
production operator could scrape. This module is that plane:

  * **Counter / Gauge / LogHistogram** metric types, optionally labeled
    (``family.labels(reason="queue_full").inc()``), registered by name in
    a process-global :class:`MetricsRegistry`;
  * **bounded log-bucket streaming histograms** — O(1) memory (two
    preallocated bucket bands, rotated every ``FLAGS_metrics_window``
    observations so long-running processes report FRESH percentiles),
    O(1) observe (one ``log10`` + an array increment, zero allocation on
    the hot path), and **mergeable across processes** (bucket counts
    add) for the multi-host fleet;
  * three export surfaces: :meth:`MetricsRegistry.exposition`
    (Prometheus text format), :meth:`MetricsRegistry.snapshot` (the
    JSON-able form ``tools/metrics_export.py`` sinks to crash-safe JSONL
    and merges across processes), and the ``fusion_doctor --metrics``
    live summary;
  * **collectors** bridging every existing counter struct (dispatch /
    chain / step fusion, guardian, AOT cache) into labeled series at
    snapshot time — zero hot-path cost for those layers.

Cost contract (the flight recorder's proven discipline): everything is
gated by ``FLAGS_metrics``. When off, ``inc()``/``observe()``/``set()``
is ONE dict lookup and a return (tests/test_metrics.py: off, every
metric stays at zero). ``METRIC_NAMES`` is a public
contract like ``REASON_CODES``: dashboards and the fusion doctor key on
the exact strings, and tests/test_metrics.py freezes the set.

MFU / tokens-per-second / goodput derivation lives in the companion
profiler/goodput.py; the serving engine feeds the ``serve_*`` series
directly (paddle_tpu/serving/engine.py).
"""
from __future__ import annotations

import math
import threading

from ..framework.flags import _FLAGS

__all__ = ["Counter", "Gauge", "LogHistogram", "MetricsRegistry",
           "REGISTRY", "METRIC_NAMES", "METRIC_MERGE", "merge_policy",
           "enabled", "counter", "gauge",
           "histogram", "metrics_snapshot", "exposition",
           "merge_snapshots", "reset_metrics", "serve_live_summary",
           "format_metrics_summary"]

# exposition name prefix (kept out of the registry names so the contract
# strings stay short)
_PREFIX = "paddle_tpu_"


def enabled():
    """One dict lookup: the gate every instrumentation site checks."""
    return bool(_FLAGS.get("FLAGS_metrics"))


# ---------------------------------------------------------------------------
# histogram core (ungated: ServeStats embeds it for always-on percentiles)
# ---------------------------------------------------------------------------

# log-spaced buckets covering 1e-9 .. 1e6 (sub-microsecond latencies up to
# ~11 days), 20 buckets per decade => +-6% relative resolution around each
# bucket midpoint. 15 decades * 20 + underflow + overflow = 302 slots,
# preallocated once per band — memory is O(1) in observations.
_LO_EXP = -9
_HI_EXP = 6
_PER_DECADE = 20
_NBUCKETS = (_HI_EXP - _LO_EXP) * _PER_DECADE + 2
_LOG_LO = float(_LO_EXP)


class LogHistogram:
    """Bounded log-bucket streaming histogram with a sliding window.

    Two preallocated bucket bands: observations land in the *current*
    band; every `window` observations the current band becomes the
    *previous* band and a zeroed band takes over. Quantiles read
    current+previous, so the report always covers the last 1-2 windows of
    data — fresh percentiles at O(1) memory, the fix for ServeStats'
    step_times_s list silently freezing after its 100k cap.

    NOT flag-gated: the serving engine's always-on percentiles embed this
    class directly. Registry-owned histograms gate in `observe()`
    (`_Hist`). Thread-safety: bumps are plain int increments on
    preallocated lists (the same GIL-atomicity contract every existing
    counter struct in this package relies on); rotation takes a lock.
    """

    __slots__ = ("_cur", "_prev", "_life", "_window", "_cur_n", "_lock",
                 "count", "sum", "min", "max", "__weakref__")

    def __init__(self, window=None):
        if window is None:
            try:
                window = int(_FLAGS.get("FLAGS_metrics_window",
                                        100_000) or 0)
            except (TypeError, ValueError):
                window = 100_000
        self._window = max(0, int(window))
        self._cur = [0] * _NBUCKETS
        self._prev = None          # allocated on first rotation only
        # cumulative-forever band: what the Prometheus exposition renders
        # (bucket counters must be monotonic and the +Inf bucket must
        # equal _count, or rate()/histogram_quantile() read each window
        # rotation as a counter reset). Allocated on first rotation —
        # until then lifetime == window and _cur serves both.
        self._life = None
        self._cur_n = 0
        self._lock = threading.Lock()
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    @staticmethod
    def bucket_index(v):
        if v <= 0.0:
            return 0
        try:
            i = int((math.log10(v) - _LOG_LO) * _PER_DECADE) + 1
        except (ValueError, OverflowError):
            return 0
        if i < 1:
            return 0
        if i >= _NBUCKETS - 1:
            return _NBUCKETS - 1
        return i

    @staticmethod
    def bucket_upper(i):
        """Upper bound (seconds) of bucket i, +inf for overflow."""
        if i >= _NBUCKETS - 1:
            return float("inf")
        return 10.0 ** (_LOG_LO + i / _PER_DECADE)

    @staticmethod
    def _bucket_mid(i):
        if i == 0:
            return 10.0 ** _LOG_LO / 2
        if i >= _NBUCKETS - 1:
            return 10.0 ** _HI_EXP
        return 10.0 ** (_LO_EXP + (i - 0.5) / _PER_DECADE)

    # -- hot path -----------------------------------------------------------
    def observe(self, v):
        v = float(v)
        i = self.bucket_index(v)
        self._cur[i] += 1
        if self._life is not None:
            self._life[i] += 1
        self.count += 1
        self.sum += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        if self._window:
            self._cur_n += 1
            if self._cur_n >= self._window:
                self._rotate()

    def _rotate(self):
        with self._lock:
            if self._cur_n < self._window:
                return          # another thread rotated first
            if self._life is None:
                # first rotation: lifetime diverges from the window now
                self._life = list(self._cur)
            self._prev = self._cur
            self._cur = [0] * _NBUCKETS
            self._cur_n = 0

    # -- reading ------------------------------------------------------------
    def _bands(self):
        if self._prev is None:
            return list(self._cur)
        return [a + b for a, b in zip(self._cur, self._prev)]

    def window_count(self):
        """Observations inside the current quantile window (<= count)."""
        return sum(self._bands())

    def quantile(self, q):
        """Approximate q-quantile (0..1) over the freshness window.
        Returns 0.0 when empty. Accuracy: one bucket (+-6% relative)."""
        counts = self._bands()
        total = sum(counts)
        if total == 0:
            return 0.0
        rank = q * total
        acc = 0
        for i, c in enumerate(counts):
            acc += c
            if acc >= rank and c:
                return self._bucket_mid(i)
        return self._bucket_mid(_NBUCKETS - 1)

    def percentile(self, p):
        return self.quantile(p / 100.0)

    def snapshot(self):
        """JSON-able, mergeable view. `buckets` is the CUMULATIVE
        lifetime band — consistent with count/sum, monotonic across
        scrapes (what the Prometheus exposition renders); the freshness
        window rides along as `window_buckets` for quantile readers."""
        life = self._life if self._life is not None else self._cur
        return {"buckets": {str(i): c for i, c in enumerate(life) if c},
                "window_buckets": {str(i): c for i, c
                                   in enumerate(self._bands()) if c},
                "count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max}

    @staticmethod
    def merge_snapshot(a, b):
        """Merge two histogram snapshots (cross-process: counts add)."""
        out = {}
        for key in ("buckets", "window_buckets"):
            buckets = dict(a.get(key) or {})
            for i, c in (b.get(key) or {}).items():
                buckets[i] = buckets.get(i, 0) + c
            out[key] = buckets
        mins = [m for m in (a.get("min"), b.get("min")) if m is not None]
        maxs = [m for m in (a.get("max"), b.get("max")) if m is not None]
        out.update({
            "count": (a.get("count") or 0) + (b.get("count") or 0),
            "sum": (a.get("sum") or 0.0) + (b.get("sum") or 0.0),
            "min": min(mins) if mins else None,
            "max": max(maxs) if maxs else None})
        return out

    @staticmethod
    def snapshot_quantile(snap, q):
        """Quantile of a (possibly merged) histogram snapshot — over the
        freshness window when present, else the lifetime band."""
        buckets = snap.get("window_buckets") or snap.get("buckets") or {}
        total = sum(buckets.values())
        if total == 0:
            return 0.0
        rank = q * total
        acc = 0
        for i in sorted(int(k) for k in buckets):
            acc += buckets[str(i)]
            if acc >= rank:
                return LogHistogram._bucket_mid(i)
        return 0.0


# ---------------------------------------------------------------------------
# registry metric types (flag-gated mutators)
# ---------------------------------------------------------------------------

class _Metric:
    """One metric family: unlabeled (a single series) or labeled
    (children created on demand via .labels()). Mutators on an unlabeled
    family hit its default child."""

    kind = "untyped"

    def __init__(self, name, help="", labelnames=()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children = {}
        self._lock = threading.Lock()
        if not self.labelnames:
            self._default = self._new_series()
        else:
            self._default = None

    def _new_series(self):
        raise NotImplementedError

    def labels(self, *values, **kv):
        if kv:
            values = tuple(kv.get(n, "") for n in self.labelnames)
        values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"metric {self.name}: expected labels {self.labelnames}, "
                f"got {values}")
        child = self._children.get(values)
        if child is None:
            with self._lock:
                child = self._children.setdefault(values,
                                                  self._new_series())
        return child

    def series(self):
        """[(label_values, series)] — the default series labels as ()."""
        if self._default is not None:
            return [((), self._default)]
        return sorted(self._children.items())

    def clear(self):
        with self._lock:
            self._children.clear()
            if not self.labelnames:
                self._default = self._new_series()


class _CounterSeries:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, n=1):
        if not _FLAGS.get("FLAGS_metrics"):
            return
        self.value += n

    def set_raw(self, v):
        """Collector backdoor: absolute value read off an existing
        counter struct at snapshot time (never the hot path)."""
        self.value = float(v)


class Counter(_Metric):
    kind = "counter"

    def _new_series(self):
        return _CounterSeries()

    def inc(self, n=1):
        self._default.inc(n)

    @property
    def value(self):
        return self._default.value


class _GaugeSeries:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v):
        if not _FLAGS.get("FLAGS_metrics"):
            return
        self.value = float(v)

    def inc(self, n=1):
        if not _FLAGS.get("FLAGS_metrics"):
            return
        self.value += n

    def set_raw(self, v):
        self.value = float(v)


class Gauge(_Metric):
    kind = "gauge"

    def _new_series(self):
        return _GaugeSeries()

    def set(self, v):
        self._default.set(v)

    def inc(self, n=1):
        self._default.inc(n)

    @property
    def value(self):
        return self._default.value


class _HistSeries(LogHistogram):
    """Flag-gated histogram series for registry-owned metrics."""

    __slots__ = ()

    def observe(self, v):
        if not _FLAGS.get("FLAGS_metrics"):
            return
        LogHistogram.observe(self, v)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help="", labelnames=(), window=None):
        self._window = window
        super().__init__(name, help, labelnames)

    def _new_series(self):
        return _HistSeries(window=self._window)

    def observe(self, v):
        self._default.observe(v)

    def quantile(self, q):
        return self._default.quantile(q)

    @property
    def count(self):
        return self._default.count


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

class MetricsRegistry:
    """Name -> metric family, plus snapshot-time collector callbacks."""

    def __init__(self):
        self._metrics = {}
        self._collectors = []
        self._lock = threading.Lock()

    # -- registration -------------------------------------------------------
    def _register(self, cls, name, help, labelnames, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, labelnames, **kw)
                self._metrics[name] = m
            elif not isinstance(m, cls) \
                    or m.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}{m.labelnames}")
            return m

    def counter(self, name, help="", labelnames=()):
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()):
        return self._register(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(), window=None):
        return self._register(Histogram, name, help, labelnames,
                              window=window)

    def get(self, name):
        return self._metrics.get(name)

    def collect(self, fn):
        """Register a collector run before every snapshot/exposition —
        the bridge from existing counter structs (zero hot-path cost)."""
        self._collectors.append(fn)
        return fn

    def _run_collectors(self):
        for fn in self._collectors:
            try:
                fn(self)
            except Exception:
                pass            # a broken collector must never sink a scrape

    # -- export -------------------------------------------------------------
    def snapshot(self):
        """JSON-able view of every metric family (runs collectors)."""
        self._run_collectors()
        out = {}
        for name, m in sorted(self._metrics.items()):
            series = []
            for values, s in m.series():
                labels = dict(zip(m.labelnames, values))
                if m.kind == "histogram":
                    row = s.snapshot()
                    row["labels"] = labels
                else:
                    row = {"labels": labels, "value": s.value}
                series.append(row)
            out[name] = {"type": m.kind, "help": m.help,
                         "labelnames": list(m.labelnames),
                         "series": series}
        return out

    def exposition(self, snapshot=None):
        """Prometheus text exposition format (one scrape)."""
        if snapshot is None:
            snapshot = self.snapshot()
        return exposition(snapshot)

    def reset(self):
        """Zero every series (keeps registrations and collectors)."""
        for m in self._metrics.values():
            m.clear()


def _fmt_labels(labels, extra=None):
    items = list((labels or {}).items())
    if extra:
        items += list(extra.items())
    if not items:
        return ""
    body = ",".join(f'{k}="{_escape(v)}"' for k, v in items)
    return "{" + body + "}"


def _escape(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_val(v):
    if v is None:
        return "0"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def exposition(snapshot):
    """Render a registry snapshot (live or merged) as Prometheus text."""
    lines = []
    for name, fam in sorted(snapshot.items()):
        full = _PREFIX + name
        if fam.get("help"):
            lines.append(f"# HELP {full} {fam['help']}")
        lines.append(f"# TYPE {full} {fam['type']}")
        for row in fam["series"]:
            labels = row.get("labels") or {}
            if fam["type"] == "histogram":
                acc = 0
                buckets = row.get("buckets") or {}
                for i in sorted(int(k) for k in buckets):
                    acc += buckets[str(i)]
                    le = LogHistogram.bucket_upper(i)
                    if le == float("inf"):
                        continue      # the terminal +Inf line covers it
                    lines.append(
                        f"{full}_bucket"
                        f"{_fmt_labels(labels, {'le': repr(float(le))})} "
                        f"{acc}")
                lines.append(
                    f"{full}_bucket{_fmt_labels(labels, {'le': '+Inf'})} "
                    f"{acc}")
                lines.append(f"{full}_sum{_fmt_labels(labels)} "
                             f"{_fmt_val(row.get('sum'))}")
                lines.append(f"{full}_count{_fmt_labels(labels)} "
                             f"{row.get('count') or 0}")
            else:
                lines.append(f"{full}{_fmt_labels(labels)} "
                             f"{_fmt_val(row.get('value'))}")
    return "\n".join(lines) + "\n"


def merge_policy(name, kind="gauge"):
    """The cross-process merge rule for one metric family: an explicit
    ``METRIC_MERGE`` entry when the name is on the contract, else the
    kind default (occurrence mass — counters/histograms — always adds;
    an unknown gauge keeps the conservative alarm-side max)."""
    pol = METRIC_MERGE.get(name)
    if pol is not None:
        return pol
    return "sum" if kind in ("counter", "histogram") else "max"


def merge_snapshots(snaps):
    """Merge registry snapshots from N processes. Histogram buckets
    always ADD; scalar series honor the per-metric ``METRIC_MERGE``
    policy — `sum` for occurrence mass and fleet-additive gauges
    (tokens/s, occupancy), `max` for watermarks (step indices, MFU,
    FLOPs/step), `last` for configuration-style values (the last
    snapshot in merge order wins; pass snapshots oldest-first). The
    old blanket gauge-max was wrong fleet-wide for
    occupancy/tokens-style gauges (a fleet of 8 engines at 0.9 occupancy
    reported 0.9, not 7.2); the policy map makes the semantics explicit
    per metric and tests/test_metrics.py freezes it."""
    out = {}
    for snap in snaps:
        for name, fam in snap.items():
            dst = out.setdefault(name, {"type": fam["type"],
                                        "help": fam.get("help", ""),
                                        "labelnames":
                                            fam.get("labelnames", []),
                                        "series": []})
            index = {tuple(sorted((r.get("labels") or {}).items())): r
                     for r in dst["series"]}
            for row in fam["series"]:
                key = tuple(sorted((row.get("labels") or {}).items()))
                have = index.get(key)
                if have is None:
                    import copy
                    row = copy.deepcopy(row)
                    dst["series"].append(row)
                    index[key] = row
                elif fam["type"] == "histogram":
                    merged = LogHistogram.merge_snapshot(have, row)
                    merged["labels"] = have.get("labels") or {}
                    have.clear()
                    have.update(merged)
                else:
                    pol = merge_policy(name, fam["type"])
                    if pol == "max":
                        have["value"] = max(have.get("value") or 0.0,
                                            row.get("value") or 0.0)
                    elif pol == "last":
                        have["value"] = row.get("value") or 0.0
                    else:
                        have["value"] = (have.get("value") or 0.0) \
                            + (row.get("value") or 0.0)
    return out


REGISTRY = MetricsRegistry()


def counter(name, help="", labelnames=()):
    return REGISTRY.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()):
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(), window=None):
    return REGISTRY.histogram(name, help, labelnames, window=window)


def metrics_snapshot():
    return REGISTRY.snapshot()


def reset_metrics():
    """Zero every series in the default registry AND the goodput
    accountant (test/bench window hygiene)."""
    REGISTRY.reset()
    from . import goodput
    goodput.ACCOUNTANT.reset()


# ---------------------------------------------------------------------------
# the default metric set — a PUBLIC contract (tests freeze the exact set,
# the fusion doctor and downstream dashboards key on the strings)
# ---------------------------------------------------------------------------

METRIC_NAMES = frozenset({
    # fusion-stack counter structs, bridged by collectors at scrape time
    "dispatch_events_total",        # labels: event (hits/misses/...)
    "chain_events_total",
    "step_fusion_events_total",
    "aot_events_total",
    "guardian_events_total",
    "collectives_total",            # labels: kind (dist.all_reduce/...)
    # training accountant (profiler/goodput.py)
    "train_step_seconds",
    "spmd_step_seconds",            # labels: mesh
    "train_tokens_total",
    "train_flops_per_step",
    "train_mfu",
    "train_tokens_per_second",
    "train_goodput",
    "goodput_seconds_total",        # labels: bucket (productive/...)
    "goodput_step_index",           # labels: bucket — last attributed step
    # serving engine (paddle_tpu/serving/engine.py)
    "serve_step_seconds",
    "serve_ttft_seconds",
    "serve_inter_token_seconds",
    "serve_queue_wait_seconds",
    "serve_tokens_total",
    "serve_occupancy",
    "serve_requests_total",         # labels: outcome
    "serve_refusals_total",         # labels: reason
    "serve_hangs_total",
    "serve_preemptions_total",
    # multi-tenant serving (PR 17, serving/tenancy.py)
    "serve_prefix_hit_tokens_total",
    "serve_prefix_hit_rate",
    "serve_adapter_switches_total",
    "serve_weight_swaps_total",
    # compiled stochastic sampling + pipelined decode (PR 18)
    "serve_sampled_tokens_total",
    "serve_commit_rollbacks_total",
    # regression sentinel (PR 19, profiler/sentinel.py)
    "sentinel_checks_total",        # labels: verdict (clean/perf_drift/...)
    "sentinel_degraded",            # 0/1: the sentinel's readyz latch
})

# goodput wall-time attribution buckets (profiler/goodput.py): where did
# the wall clock go? Also a public contract.
GOODPUT_BUCKETS = ("productive", "compile", "skipped", "stalled",
                   "warmup", "probation", "other")

# Cross-process merge policy per METRIC_NAMES entry — a public contract
# like the names themselves (tests freeze the map; tools/metrics_export
# --merge and tools/fleet_metrics.py both merge through it). Counters
# and histograms are occurrence mass: always `sum`. Gauges get explicit
# semantics: `sum` when the fleet total is the meaningful number
# (throughput, occupied slots), `max` for watermarks (MFU best-chip,
# FLOPs/step, last attributed step index), `last` where the newest
# writer wins. ("last" = the LAST snapshot in the caller's merge order
# — callers pass snapshots oldest-first; no contract metric uses it
# today, it exists so a future config-style gauge has a named policy
# instead of inheriting a wrong sum/max.) Fleet-truthful goodput/MFU
# are DERIVED from the summed goodput_seconds_total buckets by
# tools/fleet_metrics.py — the merged train_goodput gauge is only the
# best-host watermark.
METRIC_MERGE = {
    "dispatch_events_total": "sum",
    "chain_events_total": "sum",
    "step_fusion_events_total": "sum",
    "aot_events_total": "sum",
    "guardian_events_total": "sum",
    "collectives_total": "sum",
    "train_step_seconds": "sum",
    "spmd_step_seconds": "sum",
    "train_tokens_total": "sum",
    "train_flops_per_step": "max",
    "train_mfu": "max",
    "train_tokens_per_second": "sum",
    "train_goodput": "max",
    "goodput_seconds_total": "sum",
    "goodput_step_index": "max",
    "serve_step_seconds": "sum",
    "serve_ttft_seconds": "sum",
    "serve_inter_token_seconds": "sum",
    "serve_queue_wait_seconds": "sum",
    "serve_tokens_total": "sum",
    "serve_occupancy": "sum",
    "serve_requests_total": "sum",
    "serve_refusals_total": "sum",
    "serve_hangs_total": "sum",
    "serve_preemptions_total": "sum",
    "serve_prefix_hit_tokens_total": "sum",
    # per-replica convenience ratio; the fleet-truthful rate is DERIVED
    # from the summed hit-tokens counter over summed admitted context
    # tokens, so the merged gauge is only the best-replica watermark
    "serve_prefix_hit_rate": "max",
    "serve_adapter_switches_total": "sum",
    "serve_weight_swaps_total": "sum",
    "serve_sampled_tokens_total": "sum",
    "serve_commit_rollbacks_total": "sum",
    "sentinel_checks_total": "sum",
    # ANY degraded host degrades the fleet view — a max over 0/1 latches
    "sentinel_degraded": "max",
}


class _Namespace:
    pass


def _install_default_metrics(reg):
    t = _Namespace()
    t.step_s = reg.histogram(
        "train_step_seconds", "training step wall time (committed steps)")
    t.spmd_step_s = reg.histogram(
        "spmd_step_seconds",
        "fused SPMD step wall time per mesh", ("mesh",))
    t.tokens = reg.counter("train_tokens_total",
                           "tokens consumed by committed training steps")
    t.flops_per_step = reg.gauge(
        "train_flops_per_step",
        "analytic model FLOPs per training step (goodput accountant)")
    t.mfu = reg.gauge("train_mfu",
                      "rolling model FLOPs utilization vs chip peak")
    t.tokens_per_s = reg.gauge("train_tokens_per_second",
                               "rolling training throughput")
    t.goodput = reg.gauge(
        "train_goodput",
        "fraction of wall time in productive committed steps")
    t.goodput_s = reg.counter(
        "goodput_seconds_total",
        "wall time attributed per goodput bucket", ("bucket",))
    t.step_index = reg.gauge(
        "goodput_step_index",
        "last step index attributed to a non-productive goodput bucket",
        ("bucket",))
    t.collectives = reg.counter(
        "collectives_total",
        "keyed collective dispatches through the eager funnel", ("kind",))

    s = _Namespace()
    s.step_s = reg.histogram("serve_step_seconds",
                             "compiled decode step wall time")
    s.ttft_s = reg.histogram("serve_ttft_seconds",
                             "time to first token (enqueue -> token 0)")
    s.inter_token_s = reg.histogram("serve_inter_token_seconds",
                                    "inter-token latency per stream")
    s.queue_wait_s = reg.histogram("serve_queue_wait_seconds",
                                   "enqueue -> admission wait")
    s.tokens = reg.counter("serve_tokens_total", "tokens generated")
    s.occupancy = reg.gauge("serve_occupancy",
                            "decode-batch slot occupancy (last step)")
    s.requests = reg.counter("serve_requests_total",
                             "terminal request outcomes", ("outcome",))
    s.refusals = reg.counter("serve_refusals_total",
                             "admission refusals", ("reason",))
    s.hangs = reg.counter("serve_hangs_total", "watchdog firings")
    s.preemptions = reg.counter("serve_preemptions_total",
                                "KV-pressure evictions")
    s.prefix_hit_tokens = reg.counter(
        "serve_prefix_hit_tokens_total",
        "prompt tokens served off shared prefix-cache KV blocks")
    s.prefix_hit_rate = reg.gauge(
        "serve_prefix_hit_rate",
        "prefix-cache hit tokens over admitted context tokens")
    s.adapter_switches = reg.counter(
        "serve_adapter_switches_total",
        "batch-slot adapter index changes (tenant churn)")
    s.weight_swaps = reg.counter(
        "serve_weight_swaps_total",
        "live base-weight hot-swap commits")
    s.sampled_tokens = reg.counter(
        "serve_sampled_tokens_total",
        "tokens emitted by stochastic (temperature > 0) streams")
    s.commit_rollbacks = reg.counter(
        "serve_commit_rollbacks_total",
        "speculative tokens discarded at the pipelined lag-1 commit")

    reg.counter("sentinel_checks_total",
                "sentinel evaluation-window verdicts", ("verdict",))
    reg.gauge("sentinel_degraded",
              "1 while the sentinel's drift latch holds /readyz degraded")

    for name, label in (("dispatch_events_total", "per-op executable "
                         "cache outcomes"),
                        ("chain_events_total", "op-chain fusion counters"),
                        ("step_fusion_events_total",
                         "whole-step fusion counters"),
                        ("aot_events_total",
                         "persistent AOT executable store counters"),
                        ("guardian_events_total",
                         "non-finite step guardian counters")):
        reg.counter(name, label, ("event",))
    return t, s


def _install_collectors(reg):
    """Bridge the existing counter structs into labeled series — read at
    scrape time only, so the instrumented layers pay nothing."""

    def _fill(name, stats):
        fam = reg.get(name)
        for k, v in stats.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            fam.labels(event=k).set_raw(v)

    @reg.collect
    def _fusion_stats(reg):
        from .dispatch import dispatch_cache_stats
        from .chain_fusion import chain_fusion_stats
        from .step_fusion import step_fusion_stats
        from .aot import aot_cache_stats
        _fill("dispatch_events_total", dispatch_cache_stats())
        _fill("chain_events_total", chain_fusion_stats())
        _fill("step_fusion_events_total", step_fusion_stats())
        _fill("aot_events_total", aot_cache_stats())

    @reg.collect
    def _guardian_stats(reg):
        from ..ops.guardian import guardian_stats
        _fill("guardian_events_total", guardian_stats())

    @reg.collect
    def _goodput_gauges(reg):
        from . import goodput
        goodput.ACCOUNTANT.publish()

    @reg.collect
    def _sentinel_gauges(reg):
        from . import sentinel
        sentinel.publish_metrics(reg)


TRAIN, SERVE = _install_default_metrics(REGISTRY)
_install_collectors(REGISTRY)


# ---------------------------------------------------------------------------
# summaries consumed by explain.py / fusion_doctor --metrics
# ---------------------------------------------------------------------------

def serve_live_summary():
    """Compact live serving-latency/refusal view for the fusion doctor's
    serving verdict: a degraded engine's report cites live p99 and
    refusal rates, not just event counts. None when the registry has no
    serving data (metrics off or nothing served)."""
    if SERVE.step_s.count == 0:
        return None
    total_requests = sum(s.value for _, s in SERVE.requests.series())
    refused = sum(s.value for _, s in SERVE.refusals.series())
    seen = total_requests + refused
    out = {
        "p50_step_ms": round(SERVE.step_s.quantile(0.5) * 1e3, 4),
        "p99_step_ms": round(SERVE.step_s.quantile(0.99) * 1e3, 4),
        "refusal_rate": round(refused / seen, 4) if seen else 0.0,
        "hangs": int(SERVE.hangs.value),
    }
    if SERVE.ttft_s.count:
        out["ttft_p99_ms"] = round(SERVE.ttft_s.quantile(0.99) * 1e3, 4)
    if SERVE.inter_token_s.count:
        out["inter_token_p99_ms"] = round(
            SERVE.inter_token_s.quantile(0.99) * 1e3, 4)
    return out


def format_metrics_summary(snapshot=None):
    """Human-readable one-screen registry summary (`fusion_doctor
    --metrics`)."""
    if snapshot is None:
        snapshot = REGISTRY.snapshot()
    lines = ["================ metrics ================"]
    for name, fam in sorted(snapshot.items()):
        rows = []
        for row in fam["series"]:
            labels = row.get("labels") or {}
            tag = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
            if fam["type"] == "histogram":
                n = row.get("count") or 0
                if not n:
                    continue
                p50 = LogHistogram.snapshot_quantile(row, 0.5)
                p99 = LogHistogram.snapshot_quantile(row, 0.99)
                rows.append((tag, f"n={n} p50={p50 * 1e3:.3f}ms "
                                  f"p99={p99 * 1e3:.3f}ms"))
            else:
                v = row.get("value") or 0
                if not v:
                    continue
                rows.append((tag, _fmt_val(v)))
        if not rows:
            continue
        if len(rows) == 1 and not rows[0][0]:
            lines.append(f"{name:<28} {rows[0][1]}")
        else:
            lines.append(f"{name}:")
            for tag, val in rows:
                lines.append(f"  {tag:<26} {val}")
    lines.append("=========================================")
    return "\n".join(lines)
