"""Production telemetry plane (PR 12): metrics registry, goodput
accountant, serving latency story, and export plumbing.

Contracts pinned here:

  * ``METRIC_NAMES`` / ``GOODPUT_BUCKETS`` are stable public APIs like
    ``REASON_CODES`` — dashboards and the fusion doctor key on the exact
    strings, and the default registry pre-installs exactly that set;
  * the bounded log-bucket histogram tracks numpy percentiles on known
    distributions, stays fresh past its window (the ServeStats
    100k-freeze fix), merges across snapshots, and never grows its
    bucket storage;
  * with ``FLAGS_metrics`` off, nothing is recorded — not one sample;
  * the JSONL sink round-trips through the Prometheus/merge tooling,
    merges across two subprocess registries, and survives kill -9
    without a torn file;
  * serving requests report TTFT / inter-token / queue-wait percentiles
    per engine AND per completed handle, emit per-request chrome-trace
    spans, and the doctor's serving verdict cites live latency;
  * the goodput accountant reports live MFU within 2% of the
    offline computation and attributes injected guardian skips and
    watchdog stalls to the right wall-time buckets.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import guardian
from paddle_tpu.ops.dispatch import clear_dispatch_cache
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.profiler import goodput as pg
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events
from paddle_tpu.profiler.explain import explain
from paddle_tpu.profiler import _fusion_trace_events

_DEFAULT_FLAGS = {
    "FLAGS_metrics": False,
    "FLAGS_metrics_window": 100_000,
    "FLAGS_check_numerics": False,
    "FLAGS_check_numerics_level": 0,
    "FLAGS_profiler_events": False,
    "FLAGS_serve_step_timeout_ms": 0,
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
}


@pytest.fixture(autouse=True)
def _fresh():
    set_flags(dict(_DEFAULT_FLAGS))
    pm.reset_metrics()
    clear_fusion_events()
    guardian.clear_faults()
    guardian.reset_thread_state()
    yield
    set_flags(dict(_DEFAULT_FLAGS))
    pm.reset_metrics()
    clear_fusion_events()
    guardian.clear_faults()
    guardian.reset_thread_state()


# ---------------------------------------------------------------------------
# contract freeze
# ---------------------------------------------------------------------------

class TestContract:
    def test_metric_names_frozen(self):
        """The metric-name set is a PUBLIC contract: additions are
        deliberate API changes (update this test AND the README table),
        removals/renames break downstream dashboards."""
        assert pm.METRIC_NAMES == frozenset({
            "dispatch_events_total", "chain_events_total",
            "step_fusion_events_total", "aot_events_total",
            "guardian_events_total", "collectives_total",
            "train_step_seconds", "spmd_step_seconds",
            "train_tokens_total", "train_flops_per_step", "train_mfu",
            "train_tokens_per_second", "train_goodput",
            "goodput_seconds_total", "goodput_step_index",
            "serve_step_seconds", "serve_ttft_seconds",
            "serve_inter_token_seconds", "serve_queue_wait_seconds",
            "serve_tokens_total", "serve_occupancy",
            "serve_requests_total", "serve_refusals_total",
            "serve_hangs_total", "serve_preemptions_total",
            "serve_prefix_hit_tokens_total", "serve_prefix_hit_rate",
            "serve_adapter_switches_total", "serve_weight_swaps_total",
            "serve_sampled_tokens_total", "serve_commit_rollbacks_total",
            "sentinel_checks_total", "sentinel_degraded",
        })

    def test_goodput_buckets_frozen(self):
        assert pm.GOODPUT_BUCKETS == ("productive", "compile", "skipped",
                                      "stalled", "warmup", "probation",
                                      "other")

    def test_merge_policy_map_frozen(self):
        """PR 13 satellite: the per-metric fleet-merge policy is a
        public contract like the names — a policy change silently
        re-means every fleet dashboard. Every METRIC_NAMES entry has an
        explicit policy; occurrence mass (counters/histograms) always
        sums; the gauges that were wrong under the old blanket max
        (occupancy, tokens/s) are explicitly additive; watermarks stay
        max."""
        assert set(pm.METRIC_MERGE) == set(pm.METRIC_NAMES)
        assert set(pm.METRIC_MERGE.values()) <= {"sum", "max", "last"}
        # occurrence mass: every counter/histogram family sums
        snap = pm.metrics_snapshot()
        for name, fam in snap.items():
            if fam["type"] in ("counter", "histogram"):
                assert pm.METRIC_MERGE[name] == "sum", name
        # the gauge semantics the satellite fixes / preserves
        assert pm.METRIC_MERGE["serve_occupancy"] == "sum"
        assert pm.METRIC_MERGE["train_tokens_per_second"] == "sum"
        assert pm.METRIC_MERGE["train_mfu"] == "max"
        assert pm.METRIC_MERGE["train_flops_per_step"] == "max"
        assert pm.METRIC_MERGE["goodput_step_index"] == "max"
        # any degraded host degrades the fleet: the sentinel latch maxes
        assert pm.METRIC_MERGE["sentinel_degraded"] == "max"
        # unknown names keep the kind defaults
        assert pm.merge_policy("_not_a_metric", "counter") == "sum"
        assert pm.merge_policy("_not_a_metric", "gauge") == "max"

    def test_registry_preinstalls_exactly_the_contract(self):
        snap = pm.metrics_snapshot()
        assert set(snap) == pm.METRIC_NAMES
        for name, fam in snap.items():
            assert fam["type"] in ("counter", "gauge", "histogram"), name

    def test_conflicting_reregistration_rejected(self):
        with pytest.raises(ValueError):
            pm.REGISTRY.gauge("serve_tokens_total")
        with pytest.raises(ValueError):
            pm.REGISTRY.counter("serve_refusals_total")   # labels differ


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

class TestHistogram:
    @pytest.mark.parametrize("dist", ["uniform", "lognormal"])
    def test_quantile_accuracy_vs_numpy(self, dist):
        rng = np.random.default_rng(7)
        if dist == "uniform":
            vals = rng.uniform(1e-4, 1e-1, 20_000)
        else:
            vals = rng.lognormal(-6.0, 1.2, 20_000)
        h = pm.LogHistogram(window=0)
        for v in vals:
            h.observe(float(v))
        for p in (50, 90, 99):
            ref = float(np.percentile(vals, p))
            est = h.percentile(p)
            # log buckets at 20/decade: one-bucket resolution is ~12%
            assert abs(est - ref) / ref < 0.15, (p, est, ref)
        assert h.count == len(vals)
        assert abs(h.sum - vals.sum()) / vals.sum() < 1e-6

    def test_constant_stream_lands_in_one_bucket(self):
        h = pm.LogHistogram(window=0)
        for _ in range(1000):
            h.observe(0.004)
        assert abs(h.percentile(50) - 0.004) / 0.004 < 0.12
        assert abs(h.percentile(99) - 0.004) / 0.004 < 0.12

    def test_window_keeps_percentiles_fresh(self):
        """The ServeStats fix: after far more samples than the window,
        NEW samples still move the percentiles — the old raw list froze
        at its 100k cap and reported stale p50/p99 forever."""
        h = pm.LogHistogram(window=500)
        for _ in range(2000):
            h.observe(0.001)           # old regime: 1 ms
        for _ in range(1100):          # > 2 windows of the new regime
            h.observe(0.1)             # new regime: 100 ms
        p50 = h.percentile(50)
        assert abs(p50 - 0.1) / 0.1 < 0.15, \
            f"p50 {p50} still reflects the pre-window regime"

    def test_bounded_memory_under_sustained_observation(self):
        h = pm.LogHistogram(window=1000)
        h.observe(1e-4)
        n0 = len(h._cur)
        size0 = sys.getsizeof(h._cur)
        for i in range(25_000):
            h.observe(1e-5 * (1 + i % 321))
        assert len(h._cur) == n0
        assert sys.getsizeof(h._cur) == size0
        assert h._prev is None or len(h._prev) == n0

    def test_exposition_stays_cumulative_past_the_window(self):
        """Prometheus invariant: bucket counters are monotonic and the
        +Inf bucket equals _count even after the freshness window has
        rotated old samples out — rate()/histogram_quantile() must never
        see a band rotation as a counter reset."""
        set_flags({"FLAGS_metrics": True})
        h = pm.REGISTRY.histogram("_t_rot_seconds", "t", window=200)
        for _ in range(750):                  # several rotations
            h.observe(0.003)
        snap = h._default.snapshot()
        assert sum(snap["buckets"].values()) == 750
        assert snap["count"] == 750
        assert sum(snap["window_buckets"].values()) < 750
        text = pm.exposition({"_t_rot_seconds": {
            "type": "histogram", "help": "", "labelnames": [],
            "series": [dict(snap, labels={})]}})
        lines = text.splitlines()
        assert 'paddle_tpu__t_rot_seconds_bucket{le="+Inf"} 750' in lines
        assert "paddle_tpu__t_rot_seconds_count 750" in lines

    def test_merge_snapshots_adds_counts(self):
        a, b = pm.LogHistogram(window=0), pm.LogHistogram(window=0)
        for _ in range(100):
            a.observe(0.001)
        for _ in range(300):
            b.observe(0.1)
        m = pm.LogHistogram.merge_snapshot(a.snapshot(), b.snapshot())
        assert m["count"] == 400
        # 75% of merged mass at 100ms -> p50 sits in the 100ms bucket
        p50 = pm.LogHistogram.snapshot_quantile(m, 0.5)
        assert abs(p50 - 0.1) / 0.1 < 0.15
        assert m["min"] == a.min and m["max"] == b.max


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------

class TestGate:
    def test_off_gate_records_nothing(self):
        assert not pm.enabled()
        pm.TRAIN.step_s.observe(0.01)
        pm.SERVE.tokens.inc(5)
        pm.SERVE.refusals.labels(reason="queue_full").inc()
        pm.TRAIN.mfu.set(0.5)
        assert pm.TRAIN.step_s.count == 0
        assert pm.SERVE.tokens.value == 0
        assert pm.SERVE.refusals.labels(reason="queue_full").value == 0
        assert pm.TRAIN.mfu.value == 0.0

    def test_on_gate_records(self):
        set_flags({"FLAGS_metrics": True})
        pm.TRAIN.step_s.observe(0.01)
        pm.SERVE.tokens.inc(5)
        pm.TRAIN.mfu.set(0.5)
        assert pm.TRAIN.step_s.count == 1
        assert pm.SERVE.tokens.value == 5
        assert pm.TRAIN.mfu.value == 0.5


# ---------------------------------------------------------------------------
# exposition + merge
# ---------------------------------------------------------------------------

class TestExposition:
    def test_prometheus_text_parses(self):
        set_flags({"FLAGS_metrics": True})
        pm.TRAIN.step_s.observe(0.02)
        pm.SERVE.refusals.labels(reason="queue_full").inc(3)
        text = pm.REGISTRY.exposition()
        lines = text.splitlines()
        assert any(l.startswith("# TYPE paddle_tpu_train_step_seconds "
                                "histogram") for l in lines)
        assert 'paddle_tpu_serve_refusals_total{reason="queue_full"} 3' \
            in lines
        # histogram: cumulative buckets, +Inf terminal, sum/count
        bk = [l for l in lines
              if l.startswith("paddle_tpu_train_step_seconds_bucket")]
        assert bk and bk[-1].startswith(
            'paddle_tpu_train_step_seconds_bucket{le="+Inf"} 1')
        assert "paddle_tpu_train_step_seconds_count 1" in lines
        # every sample line is NAME{labels} VALUE — parseable
        for l in lines:
            if l.startswith("#") or not l:
                continue
            name, _, val = l.rpartition(" ")
            float(val)
            assert name

    def test_merge_counters_add_gauges_max(self):
        set_flags({"FLAGS_metrics": True})
        pm.SERVE.tokens.inc(7)
        pm.TRAIN.mfu.set(0.3)
        pm.TRAIN.step_s.observe(0.01)
        snap = pm.metrics_snapshot()
        other = json.loads(json.dumps(snap))   # simulate a second process
        other["train_mfu"]["series"][0]["value"] = 0.5
        merged = pm.merge_snapshots([snap, other])
        assert merged["serve_tokens_total"]["series"][0]["value"] == 14
        assert merged["train_mfu"]["series"][0]["value"] == 0.5
        assert merged["train_step_seconds"]["series"][0]["count"] == 2
        # merged snapshots render through the same exposition path
        assert "paddle_tpu_serve_tokens_total 14" \
            in pm.exposition(merged).splitlines()

    def test_merge_honors_per_metric_policy(self):
        """PR 13 satellite: merge_snapshots follows METRIC_MERGE — a
        fleet of engines at 0.9 occupancy reports summed occupied
        capacity (1.8 across two hosts), NOT the old blanket max (0.9);
        fleet tokens/s adds; the step-index watermark maxes. Both
        metrics_export --merge and fleet_metrics flow through this one
        implementation."""
        set_flags({"FLAGS_metrics": True})
        pm.SERVE.occupancy.set(0.9)
        pm.TRAIN.tokens_per_s._default.set_raw(100.0)
        pm.TRAIN.step_index.labels(bucket="skipped").set_raw(40)
        snap = pm.metrics_snapshot()
        other = json.loads(json.dumps(snap))
        other["serve_occupancy"]["series"][0]["value"] = 0.7
        other["train_tokens_per_second"]["series"][0]["value"] = 50.0
        other["goodput_step_index"]["series"][0]["value"] = 90
        merged = pm.merge_snapshots([snap, other])
        assert merged["serve_occupancy"]["series"][0]["value"] \
            == pytest.approx(1.6)
        assert merged["train_tokens_per_second"]["series"][0]["value"] \
            == pytest.approx(150.0)
        assert merged["goodput_step_index"]["series"][0]["value"] == 90


# ---------------------------------------------------------------------------
# JSONL sink: cross-process merge + kill-9 safety
# ---------------------------------------------------------------------------

_CHILD_WRITE = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {root!r})
sys.path.insert(0, os.path.join({root!r}, "tools"))
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.profiler import metrics as pm
import metrics_export
set_flags({{"FLAGS_metrics": True}})
pm.SERVE.tokens.inc({tokens})
pm.SERVE.refusals.labels(reason="queue_full").inc({refused})
for _ in range({obs}):
    pm.TRAIN.step_s.observe(0.002)
sink = metrics_export.MetricsSink(path={path!r})
sink.write()
print("WROTE")
"""

_CHILD_SPIN = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {root!r})
sys.path.insert(0, os.path.join({root!r}, "tools"))
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.profiler import metrics as pm
import metrics_export
set_flags({{"FLAGS_metrics": True}})
sink = metrics_export.MetricsSink(path={path!r})
print("READY", flush=True)
i = 0
while True:
    pm.SERVE.tokens.inc()
    i += 1
    sink.write()
"""

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_child(code, timeout=120):
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=timeout,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})


class TestSinkCrossProcess:
    def test_two_process_merge_roundtrip(self, tmp_path):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import metrics_export
        paths = []
        for i, (tok, ref) in enumerate(((11, 2), (31, 5))):
            p = str(tmp_path / f"m{i}.jsonl")
            r = _run_child(_CHILD_WRITE.format(root=_ROOT, tokens=tok,
                                               refused=ref, obs=50,
                                               path=p))
            assert r.returncode == 0, r.stderr[-800:]
            paths.append(p)
        merged = metrics_export.merge_files(paths)
        assert merged["serve_tokens_total"]["series"][0]["value"] == 42
        ref_series = merged["serve_refusals_total"]["series"]
        assert {tuple(r["labels"].items()): r["value"]
                for r in ref_series} == {(("reason", "queue_full"),): 7}
        assert merged["train_step_seconds"]["series"][0]["count"] == 100
        # renders as prometheus text without error
        text = pm.exposition(merged)
        assert "paddle_tpu_serve_tokens_total 42" in text

    def test_kill9_never_leaves_a_torn_sink(self, tmp_path):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import metrics_export
        p = str(tmp_path / "spin.jsonl")
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD_SPIN.format(root=_ROOT, path=p)],
            stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            assert proc.stdout.readline().strip() == "READY"
            deadline = time.time() + 60
            while not os.path.exists(p) and time.time() < deadline:
                time.sleep(0.01)
            time.sleep(0.2)            # let a few rewrite cycles race
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert os.path.exists(p)
        rows = metrics_export.read_sink(p)   # CRC-verified, never torn
        assert rows, "sink unreadable after kill -9"
        last = rows[-1]["metrics"]
        assert last["serve_tokens_total"]["series"][0]["value"] >= 1


# ---------------------------------------------------------------------------
# serving: TTFT / inter-token / queue-wait + spans + doctor live view
# ---------------------------------------------------------------------------

VOCAB = 128


@pytest.fixture(scope="module")
def smodel():
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, int(k)).tolist()
            for k in rng.integers(3, 16, n)]


class TestServingLatency:
    def test_engine_reports_ttft_and_inter_token(self, smodel):
        from paddle_tpu.serving import LLMEngine
        set_flags({"FLAGS_metrics": True})
        engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
        engine.generate(_prompts(6, seed=1), max_new_tokens=5)
        s = engine.stats()
        # per engine: the satellite contract — first_token_ns finally
        # reaches stats(), plus the inter-token and queue-wait story
        for k in ("ttft_p50_ms", "ttft_p99_ms", "inter_token_p50_ms",
                  "inter_token_p99_ms", "queue_wait_p50_ms",
                  "queue_wait_p99_ms"):
            assert k in s
        assert s["ttft_p50_ms"] > 0
        assert s["inter_token_p50_ms"] > 0
        assert s["ttft_p99_ms"] >= s["ttft_p50_ms"]
        # registry sees the same stream
        assert pm.SERVE.ttft_s.count >= 6
        assert pm.SERVE.inter_token_s.count > 0
        assert pm.SERVE.tokens.value > 0

    def test_snapshot_keys_backward_compatible(self, smodel):
        """PR 6/7 consumers of ServeStats.snapshot() keep every key they
        had before the histogram replacement."""
        from paddle_tpu.serving import LLMEngine
        engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
        engine.generate(_prompts(2, seed=2), max_new_tokens=3)
        s = engine.stats()
        for k in ("steps", "tokens_generated", "prefills",
                  "decode_compiles", "prefill_compiles", "admitted",
                  "evictions", "completed", "failed", "refused",
                  "refused_queue_full", "refused_deadline", "cancelled",
                  "expired", "hangs", "eager_fallbacks", "resumed",
                  "occupancy_mean", "occupancy_saturated", "p50_step_ms",
                  "p99_step_ms", "elapsed_s", "tokens_per_sec"):
            assert k in s, f"snapshot lost key {k}"
        assert s["p50_step_ms"] > 0
        # the admission wait estimate still has its recent raw samples
        assert engine._stats.step_times_s

    def test_no_percentile_freeze_on_long_engines(self):
        """The satellite itself: percentiles keep moving long past what
        the old 100k-list cap would have frozen."""
        from paddle_tpu.serving.engine import ServeStats
        st = ServeStats()
        st.step_hist = pm.LogHistogram(window=300)
        for _ in range(1000):
            st.step_hist.observe(0.001)
        frozen = st.snapshot()["p50_step_ms"]
        for _ in range(700):
            st.step_hist.observe(0.05)
        fresh = st.snapshot()["p50_step_ms"]
        assert abs(frozen - 1.0) < 0.2
        assert abs(fresh - 50.0) / 50.0 < 0.2

    def test_per_request_latency_handle(self, smodel):
        from paddle_tpu.serving import LLMEngine
        engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
        req = engine.add_request(_prompts(1, seed=3)[0], max_new_tokens=6)
        engine.run()
        lat = req.latency()
        assert lat["tokens"] == 6
        assert lat["ttft_ms"] > 0
        assert lat["queue_wait_ms"] is not None \
            and lat["queue_wait_ms"] <= lat["ttft_ms"]
        assert lat["inter_token_p50_ms"] > 0
        assert lat["inter_token_p99_ms"] >= lat["inter_token_p50_ms"]

    def test_64_stream_churn_metrics_on_decode_compiles_once(self,
                                                            smodel):
        """Acceptance: under 64-stream churn with the telemetry plane
        ARMED, the engine reports TTFT/inter-token/queue-wait
        percentiles from the bounded histograms and the decode
        executable still compiles exactly once — instrumentation is
        host-side observation, never a traced shape."""
        from paddle_tpu.serving import LLMEngine
        set_flags({"FLAGS_metrics": True})
        engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
        engine.generate(_prompts(64, seed=9), max_new_tokens=5)
        s = engine.stats()
        assert s["decode_compiles"] == 1
        assert s["completed"] == 64
        assert s["ttft_p99_ms"] > 0 and s["inter_token_p99_ms"] > 0
        assert s["queue_wait_p99_ms"] >= 0
        # bounded memory: the histograms never grew past their bands
        for h in (engine._stats.step_hist, engine._stats.ttft_hist,
                  engine._stats.inter_token_hist):
            assert len(h._cur) == len(pm.LogHistogram()._cur)
        assert pm.SERVE.requests.labels(outcome="completed").value == 64

    def test_refusal_and_outcome_counters(self, smodel):
        from paddle_tpu.serving import LLMEngine, ServeRefusal
        set_flags({"FLAGS_metrics": True})
        engine = LLMEngine(smodel, max_batch_size=1, block_size=4,
                           max_queue_depth=2)
        p = _prompts(1, seed=4)[0]
        engine.add_request(p, max_new_tokens=3)
        engine.add_request(p, max_new_tokens=3)     # fills the queue
        with pytest.raises(ServeRefusal):
            engine.add_request(p, max_new_tokens=3)
        engine.run()
        assert pm.SERVE.refusals.labels(reason="queue_full").value == 1
        assert pm.SERVE.requests.labels(outcome="completed").value == 2


class TestServeSpans:
    def test_request_span_lifecycle_in_chrome_trace(self, smodel):
        """Per-request trace spans (the tentpole's third surface): each
        request projects an async begin at enqueue, an admit instant,
        and an end at completion — ordered — beside the fusion lanes."""
        from paddle_tpu.serving import LLMEngine
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
            reqs = [engine.add_request(p, max_new_tokens=3)
                    for p in _prompts(2, seed=5)]
            engine.run()
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        trace = _fusion_trace_events(ev)
        lanes = [t["args"]["name"] for t in trace if t.get("ph") == "M"]
        assert "fusion:serve" in lanes
        for r in reqs:
            spans = [t for t in trace if t.get("cat") == "serve.request"
                     and t.get("id") == r.rid]
            phases = [t["ph"] for t in spans]
            assert phases[0] == "b" and phases[-1] == "e", (r.rid, phases)
            assert "n" in phases                       # admit instant
            ts = [t["ts"] for t in spans]
            assert ts == sorted(ts)
            ends = [t for t in spans if t["ph"] == "e"]
            assert ends[0]["args"]["outcome"] == "complete"
        # engine-wide decode ticks ride the serve lane as instants
        serve_tid = 0x7F5E0004
        assert any(t.get("tid") == serve_tid and t.get("ph") == "i"
                   and "serve.step" in t["name"] for t in trace)

    def test_cancelled_request_span_closes(self, smodel):
        from paddle_tpu.serving import LLMEngine
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
            req = engine.add_request(_prompts(1, seed=6)[0],
                                     max_new_tokens=8)
            engine.step()
            engine.cancel(req.rid)
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        spans = [t for t in _fusion_trace_events(ev)
                 if t.get("cat") == "serve.request"
                 and t.get("id") == req.rid]
        assert spans[-1]["ph"] == "e"
        assert spans[-1]["args"]["outcome"] == "cancel"


# ---------------------------------------------------------------------------
# goodput accounting
# ---------------------------------------------------------------------------

def _train_loop(steps, d=32):
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, d)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((d, d)).astype(np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(d).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w, b])
    for _ in range(steps):
        y = F.gelu(paddle.add(paddle.matmul(x, w), b))
        loss = y.sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    w._value.block_until_ready()


class TestGoodput:
    def test_live_mfu_within_2pct_of_offline(self):
        """Acceptance: the registry-read MFU/tokens-per-second must match
        the pre-PR 12 offline computation (tokens x flops / elapsed /
        peak) on the same run, through a `TrainStep`."""
        import jax.numpy as jnp
        from paddle_tpu.incubate.models import (GPTConfig, GPTForCausalLM,
                                                GPTPretrainingCriterion)
        from paddle_tpu.jit import TrainStep
        paddle.seed(0)
        seq, batch, steps = 64, 2, 12
        cfg = GPTConfig(vocab_size=256, hidden_size=64,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=128,
                        max_position_embeddings=seq,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        crit = GPTPretrainingCriterion()
        step = TrainStep(model, lambda lg, y: crit(lg, y), opt)
        rng = np.random.default_rng(0)
        x = paddle.Tensor(jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
            stop_gradient=True)
        y = paddle.Tensor(jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
            stop_gradient=True)
        float(step(x, y))                          # compile
        set_flags({"FLAGS_metrics": True})
        fpt = model.flops_per_token(seq, training=True)
        peak = 1e12         # the CPU has no published peak: supply one
        pg.ACCOUNTANT.reset(warm=True)
        pg.ACCOUNTANT.set_flops_per_step(fpt * batch * seq,
                                         tokens=batch * seq, peak=peak)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y)
        float(loss)
        pg.ACCOUNTANT.finalize()
        elapsed = time.perf_counter() - t0
        snap = pg.ACCOUNTANT.snapshot()
        offline_tps = batch * seq * steps / elapsed
        offline_mfu = offline_tps * fpt / peak
        assert snap["steps"] == steps
        assert abs(snap["tokens_per_sec"] - offline_tps) / offline_tps \
            < 0.02, (snap["tokens_per_sec"], offline_tps)
        assert abs(snap["mfu"] - offline_mfu) / offline_mfu < 0.02
        assert snap["goodput"] == 1.0              # clean steady window
        # the registry gauges carry the same numbers
        reg = pm.metrics_snapshot()
        assert reg["train_mfu"]["series"][0]["value"] \
            == pytest.approx(snap["mfu"], rel=1e-2, abs=1e-6)

    @pytest.mark.filterwarnings(
        "ignore:Operator .* produced a non-finite output")
    def test_guardian_skip_attributed(self):
        """Acceptance: goodput correctly attributes injected
        guardian-skip time (guardian.inject_fault reuse)."""
        clear_dispatch_cache()
        # per-op dispatch only: the dispatch-level fault hook is not
        # consulted for ops replayed inside fused chains/steps
        set_flags({"FLAGS_metrics": True, "FLAGS_check_numerics": True,
                   "FLAGS_check_numerics_level": 1,
                   "FLAGS_eager_chain_fusion": False,
                   "FLAGS_eager_step_fusion": False})
        pg.ACCOUNTANT.reset(warm=True)
        guardian.inject_fault("nan_output", op="matmul", after=3, times=1)
        try:
            _train_loop(10)
            guardian.flush()
            pg.ACCOUNTANT.step_boundary()   # boundary after the flush
        finally:
            guardian.clear_faults()
        snap = pg.ACCOUNTANT.snapshot()
        assert guardian.guardian_stats()["steps_skipped"] >= 1
        assert snap["buckets_s"]["skipped"] > 0, snap["buckets_s"]
        assert snap["goodput"] < 1.0

    def test_watchdog_stall_attributed(self, smodel):
        """Acceptance: an injected decode hang lands its watchdog budget
        in the stalled bucket and bumps serve_hangs_total."""
        from paddle_tpu.serving import LLMEngine
        set_flags({"FLAGS_metrics": True,
                   "FLAGS_serve_step_timeout_ms": 2000})
        try:
            engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
            reqs = [engine.add_request(p, max_new_tokens=6)
                    for p in _prompts(2, seed=7)]
            engine.step()
            pg.ACCOUNTANT.reset(warm=True)
            guardian.inject_fault("hang", op="serve.decode", times=1)
            engine.run()
        finally:
            guardian.clear_faults()
            set_flags({"FLAGS_serve_step_timeout_ms": 0})
        snap = pg.ACCOUNTANT.snapshot()
        assert pm.SERVE.hangs.value == 1
        assert snap["buckets_s"]["stalled"] >= 2.0   # the 2s budget
        # no double count: the stalled seconds must NOT also appear in
        # productive (the recovered decode step's dt spans the hang)
        assert snap["buckets_s"]["productive"] < 1.0, snap["buckets_s"]
        assert snap["goodput"] < 0.5
        assert all(r.finished for r in reqs)

    def test_cycle_derived_flops(self):
        """With nothing pinned, the accountant derives analytic
        FLOPs/step from the promoted cycle's recorded op keys (matmul
        dominates: 3 x 2mnk for fwd+bwd)."""
        clear_dispatch_cache()
        set_flags({"FLAGS_metrics": True,
                   "FLAGS_eager_step_fusion_min_count": 4})
        pg.ACCOUNTANT.reset(warm=True)
        _train_loop(12)
        snap = pg.ACCOUNTANT.snapshot()
        assert snap["flops_source"] == "cycle"
        expect = 3 * 2 * 16 * 32 * 32              # the matmul term
        assert expect <= snap["flops_per_step"] <= expect * 1.25
        # FLOPs are counted, but the CPU has no published peak to divide
        # by: MFU is not reported rather than guessed from a default
        assert snap["mfu"] == 0

    def test_unknown_device_has_no_peak(self):
        """A device kind outside CHIP_PEAKS is an error, not 197e12: a
        utilization over a guessed denominator reads like a measurement."""
        with pytest.raises(LookupError, match="no published peak"):
            pg.peak_flops_per_chip()
        v5e = pg.CHIP_PEAKS["TPU v5 lite"]
        assert v5e["bf16_flops"] == 197e12
        assert v5e["hbm_bytes_per_s"] == 819e9
        assert "Google Cloud" in v5e["source"]

    def test_explain_serving_cites_live_metrics(self, smodel):
        """Satellite: a degraded engine's doctor report carries the live
        p99/refusal view, not just event counts."""
        from paddle_tpu.serving import LLMEngine
        clear_fusion_events()
        set_flags({"FLAGS_metrics": True, "FLAGS_profiler_events": True,
                   "FLAGS_serve_step_timeout_ms": 2000})
        try:
            engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
            for p in _prompts(2, seed=8):
                engine.add_request(p, max_new_tokens=5)
            engine.step()
            guardian.inject_fault("hang", op="serve.decode", times=1)
            engine.run()
            rep = explain(fusion_events())
        finally:
            guardian.clear_faults()
            set_flags({"FLAGS_profiler_events": False,
                       "FLAGS_serve_step_timeout_ms": 0})
        assert rep["verdict"] == "serving_degraded"
        live = rep["serving"]["live"]
        assert live["p99_step_ms"] > 0
        assert live["hangs"] == 1
        assert "[live:" in rep["headline"]


# ---------------------------------------------------------------------------
# the telemetry plane's counted guards
# ---------------------------------------------------------------------------

class TestPerfGuards:
    def test_off_gate_is_silent_and_histogram_bounded(self):
        assert not pm.enabled()
        h = pm.TRAIN.step_s._default
        for _ in range(10_000):
            pm.TRAIN.step_s.observe(0.001)
        assert h.count == 0
        set_flags({"FLAGS_metrics": True})
        g = pm.LogHistogram(window=2_000)
        g.observe(0.001)
        n0, s0 = len(g._cur), sys.getsizeof(g._cur)
        for i in range(20_000):
            g.observe(0.0001 * (1 + i % 57))
        assert (len(g._cur), sys.getsizeof(g._cur)) == (n0, s0)

    def test_metrics_demo_fixture(self):
        """`fusion_doctor --demo metrics` stays a working acceptance
        fixture: live registry + goodput below 1.0 with the injected
        guardian skip attributed."""
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools",
                                          "fusion_doctor.py"),
             "--demo", "metrics", "--steps", "12", "--json"],
            capture_output=True, text=True, timeout=420,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-800:]
        rep = json.loads(r.stdout)
        assert rep["goodput"]["goodput"] < 1.0
        assert rep["goodput"]["buckets_s"]["skipped"] > 0
        assert set(rep["metrics"]) == set(pm.METRIC_NAMES)
        g = rep["metrics"]["guardian_events_total"]["series"]
        skipped = [s for s in g
                   if s["labels"].get("event") == "steps_skipped"]
        assert skipped and skipped[0]["value"] >= 1
