"""Live HTTP observability plane (PR 13): telemetry server, fleet merge,
and per-step goodput attribution.

Contracts pinned here:

  * every endpoint (/metrics, /metrics.json, /goodput, /doctor, /events,
    /healthz, /readyz) answers with its documented shape, and the server
    is fully inert when off (heartbeats are a no-op, FLAGS_telemetry_port
    defaults to 0);
  * /healthz is a real liveness probe: the train heartbeat goes stale
    past its window on an open accounting window (and not on a finalized
    one), and an injected wall-clock stall (guardian.inject_fault
    "stall") flips a busy engine unhealthy within one watchdog window —
    recovering after the first clean step;
  * /readyz mirrors the engine degraded latch + decode-compiled state;
  * a scraper hammering /metrics + /doctor at ~100 Hz while 64 mixed
    streams churn leaves `decode_compiles == 1` and every response
    parseable; kill-9 mid-scrape leaves no stuck socket — the port
    rebinds immediately;
  * the goodput accountant attributes WHICH steps landed in each
    non-productive bucket (bounded rings), visible in /goodput, the
    doctor report, and the goodput_step_index exposition gauge;
  * tools/fleet_metrics.py merges >=2 process sinks/endpoints into one
    fleet view whose goodput equals the hand-merged accountant
    snapshots (±1e-9), with per-host labels and a drift section;
  * `fusion_doctor --url` renders a live process's /doctor report with
    the same schema as --json.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import guardian
from paddle_tpu.ops.dispatch import clear_dispatch_cache
from paddle_tpu.profiler import goodput as pg
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.profiler import telemetry_server as ts
from paddle_tpu.profiler.events import clear_fusion_events

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DEFAULT_FLAGS = {
    "FLAGS_metrics": False,
    "FLAGS_check_numerics": False,
    "FLAGS_check_numerics_level": 0,
    "FLAGS_profiler_events": False,
    "FLAGS_serve_step_timeout_ms": 0,
    "FLAGS_telemetry_port": 0,
    "FLAGS_telemetry_stale_s": 120.0,
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
}


@pytest.fixture(autouse=True)
def _fresh():
    set_flags(dict(_DEFAULT_FLAGS))
    ts.stop()
    ts._ENGINES.clear()
    pm.reset_metrics()
    clear_fusion_events()
    guardian.clear_faults()
    guardian.reset_thread_state()
    yield
    ts.stop()
    ts._ENGINES.clear()
    set_flags(dict(_DEFAULT_FLAGS))
    pm.reset_metrics()
    clear_fusion_events()
    guardian.clear_faults()
    guardian.reset_thread_state()


def _get(url, timeout=15):
    """(status, parsed body) via the shared client helper — 4xx/5xx
    return their JSON body too, /metrics comes back as text."""
    return ts.probe_endpoint(url, timeout=timeout)


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


# ---------------------------------------------------------------------------
# off-state + unit pieces
# ---------------------------------------------------------------------------

class TestOffState:
    def test_default_flag_is_off_and_beat_is_inert(self):
        assert ts.maybe_start_from_flags() is None
        assert ts.server() is None and ts.server_port() is None
        ts.beat("train", step=7)
        assert ts._HEART == {}          # module-bool gate: nothing stored

    def test_format_step_ranges(self):
        fmt = pg.format_step_ranges
        assert fmt([]) == ""
        assert fmt([5]) == "5"
        assert fmt([1032, 2048, 4096, 4097, 4098, 4099]) \
            == "1032, 2048, 4096-4099"
        assert fmt([3, 1, 2, 9]) == "1-3, 9"
        assert fmt([4, 4, 5]) == "4-5"  # dedup


# ---------------------------------------------------------------------------
# endpoints
# ---------------------------------------------------------------------------

class TestEndpoints:
    def test_every_endpoint_answers(self):
        set_flags({"FLAGS_metrics": True, "FLAGS_profiler_events": True})
        srv = ts.start(port=0)
        assert ts.server_port() == srv.port
        _train_loop(5)
        st, idx = _get(srv.url + "/")
        assert st == 200 and "/metrics" in idx["endpoints"]
        # /metrics: valid Prometheus text matching the registry contract
        st, text = _get(srv.url + "/metrics")
        assert st == 200
        lines = text.splitlines()
        assert any(l.startswith("# TYPE paddle_tpu_train_step_seconds "
                                "histogram") for l in lines)
        for l in lines:
            if l.startswith("#") or not l:
                continue
            name, _, val = l.rpartition(" ")
            float(val)
            assert name.startswith("paddle_tpu_")
        # /metrics.json: the registry snapshot — every contract name
        # present (other suites may register extra families in the
        # shared process registry; registrations survive reset)
        st, snap = _get(srv.url + "/metrics.json")
        assert st == 200 and set(pm.METRIC_NAMES) <= set(snap)
        # /goodput: the accountant snapshot with the attribution rings
        st, good = _get(srv.url + "/goodput")
        assert st == 200 and good["steps"] == 5
        assert "step_indices" in good and "step_indices_pretty" in good
        # /doctor: explain() schema + metrics/goodput sections (armed)
        st, doc = _get(srv.url + "/doctor")
        assert st == 200
        for k in ("verdict", "headline", "findings", "step", "dispatch"):
            assert k in doc
        assert set(pm.METRIC_NAMES) <= set(doc["metrics"])
        assert doc["goodput"]["steps"] == 5
        # /events: bounded tail, newest last
        st, ev = _get(srv.url + "/events?n=5")
        assert st == 200 and ev["returned"] == 5 and len(ev["events"]) == 5
        assert ev["events"][-1]["seq"] > ev["events"][0]["seq"]
        st, ev = _get(srv.url + "/events?n=999999")
        assert st == 200 and ev["returned"] <= 4096
        # liveness/readiness + 404
        st, h = _get(srv.url + "/healthz")
        assert st == 200 and h["healthy"]
        assert h["sources"]["train"]["step"] == 5
        assert h["last_heartbeat_age_s"] is not None
        st, r = _get(srv.url + "/readyz")
        assert st == 200 and r["ready"]
        st, _ = _get(srv.url + "/nope")
        assert st == 404

    def test_metrics_endpoint_matches_registry_snapshot(self):
        """Acceptance: /metrics is the SAME exposition the in-process
        registry renders — one computation, scraped."""
        set_flags({"FLAGS_metrics": True})
        pm.SERVE.tokens.inc(13)
        pm.SERVE.refusals.labels(reason="queue_full").inc(2)
        srv = ts.start(port=0)
        st, text = _get(srv.url + "/metrics")
        assert st == 200
        assert "paddle_tpu_serve_tokens_total 13" in text.splitlines()
        assert ('paddle_tpu_serve_refusals_total{reason="queue_full"} 2'
                in text.splitlines())

    def test_busy_port_warns_instead_of_crashing(self):
        """A bind failure on the implicit flag path (restart racing the
        old socket, a DataLoader worker inheriting the env flag) must
        degrade to no-server with a warning — the diagnostics plane
        never kills the process it monitors."""
        holder = socket.socket()
        holder.bind(("127.0.0.1", 0))
        holder.listen(1)
        port = holder.getsockname()[1]
        try:
            set_flags({"FLAGS_telemetry_port": port})
            with pytest.warns(UserWarning, match="could not bind"):
                assert ts.maybe_start_from_flags() is None
            assert ts.server() is None
            # the explicit API still raises (a deliberate start must
            # not silently do nothing)
            with pytest.raises(OSError):
                ts.start(port=port)
        finally:
            holder.close()

    def test_start_is_idempotent_and_stop_rebinds(self):
        srv = ts.start(port=0)
        assert ts.start(port=0) is srv
        port = srv.port
        ts.stop()
        srv2 = ts.start(port=port)       # same port, fresh server
        st, _ = _get(srv2.url + "/healthz")
        assert st == 200


# ---------------------------------------------------------------------------
# liveness / readiness
# ---------------------------------------------------------------------------

class TestHealth:
    def test_train_heartbeat_staleness_and_finalize(self):
        set_flags({"FLAGS_metrics": True,
                   "FLAGS_telemetry_stale_s": 0.15})
        srv = ts.start(port=0)
        _train_loop(3)
        st, h = _get(srv.url + "/healthz")
        assert st == 200 and not h["sources"]["train"]["stale"]
        time.sleep(0.3)                  # open window + stale heartbeat
        st, h = _get(srv.url + "/healthz")
        assert st == 503 and h["sources"]["train"]["stale"]
        pg.ACCOUNTANT.finalize()         # closed window: idle, not dead
        st, h = _get(srv.url + "/healthz")
        assert st == 200 and h["sources"]["train"]["finalized"]

    def test_stale_s_zero_disables_heartbeat_staleness(self):
        """FLAGS_telemetry_stale_s=0 is the opt-out for scripts with
        legitimate long non-stepping phases (eval/checkpoint): ages stay
        reported, nothing drives /healthz to 503."""
        set_flags({"FLAGS_telemetry_stale_s": 0.0})
        srv = ts.start(port=0)
        _train_loop(2)
        time.sleep(0.2)                  # any window >0 would be stale
        st, h = _get(srv.url + "/healthz")
        assert st == 200 and not h["sources"]["train"]["stale"]
        assert h["sources"]["train"]["age_s"] > 0

    def test_readyz_mirrors_degraded_latch(self, smodel):
        from paddle_tpu.serving import LLMEngine
        srv = ts.start(port=0)
        engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
        # fresh engine: ready (first request pays compile by design)
        st, r = _get(srv.url + "/readyz")
        assert st == 200 and r["ready"]
        assert r["engines"][0]["decode_compiled"] is False
        engine.generate(_prompts(2, seed=1), max_new_tokens=3)
        st, r = _get(srv.url + "/readyz")
        assert st == 200 and r["engines"][0]["decode_compiled"] is True
        assert "aot" in r and "enabled" in r["aot"]
        engine.degraded = True           # the watchdog/fault latch
        st, r = _get(srv.url + "/readyz")
        assert st == 503 and not r["ready"]
        assert r["engines"][0]["degraded"]
        # first clean decode step clears the latch organically
        engine.generate(_prompts(1, seed=2), max_new_tokens=2)
        assert engine.degraded is False
        st, r = _get(srv.url + "/readyz")
        assert st == 200 and r["ready"]

    def test_healthz_flips_within_watchdog_window_of_a_stall(self,
                                                            smodel):
        """Acceptance: an injected wall-clock hang
        (guardian.inject_fault "stall") on a busy engine flips /healthz
        to 503 within one watchdog window, and the endpoint recovers
        after the first clean step. /readyz reads 503 while the
        degraded latch holds."""
        from paddle_tpu.serving import LLMEngine
        budget_ms = 150
        set_flags({"FLAGS_metrics": True,
                   "FLAGS_serve_step_timeout_ms": budget_ms})
        srv = ts.start(port=0)
        engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
        reqs = [engine.add_request(p, max_new_tokens=8)
                for p in _prompts(3, seed=3)]
        for _ in range(3):
            engine.step()                # warm, heartbeat fresh
        st, _ = _get(srv.url + "/healthz")
        assert st == 200
        samples = []
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                for ep in ("/healthz", "/readyz"):
                    try:
                        samples.append(
                            (time.perf_counter(), ep,
                             _get(srv.url + ep, timeout=5)[0]))
                    except Exception:
                        pass
                time.sleep(0.01)

        thr = threading.Thread(target=scraper, daemon=True)
        thr.start()
        t_hang = time.perf_counter()
        guardian.inject_fault("stall", op="serve.decode", times=2)
        try:
            engine.run()                 # wedges ~2 budgets, recovers
        finally:
            guardian.clear_faults()
        stop.set()
        thr.join(timeout=10)
        unhealthy = [t for t, ep, st in samples
                     if ep == "/healthz" and st == 503]
        assert unhealthy, "healthz never flipped during the stall"
        # flip bound: one watchdog window per wedged attempt + scrape
        # cadence slack
        assert min(unhealthy) - t_hang <= 2 * budget_ms / 1e3 + 0.25
        assert any(ep == "/readyz" and st == 503
                   for _, ep, st in samples), \
            "readyz never reported the degraded latch"
        # recovered: healthy, ready, and the streams all finished
        st, h = _get(srv.url + "/healthz")
        assert st == 200, h
        st, _ = _get(srv.url + "/readyz")
        assert st == 200
        assert all(r.finished for r in reqs)
        assert engine.stats()["hangs"] == 2
        # per-step attribution: the stalled decode steps are named
        st, good = _get(srv.url + "/goodput")
        assert good["step_indices"].get("stalled"), good["step_indices"]

    def test_idle_busy_engine_goes_stale_without_steps(self, smodel):
        """The blind-hang shape: requests pending but the driver never
        steps (wedged outside the engine entirely) — /healthz flips once
        the heartbeat passes the window; an IDLE engine never does."""
        from paddle_tpu.serving import LLMEngine
        set_flags({"FLAGS_telemetry_stale_s": 0.1})
        srv = ts.start(port=0)
        engine = LLMEngine(smodel, max_batch_size=2, block_size=4)
        engine.generate(_prompts(1, seed=4), max_new_tokens=2)  # warm
        time.sleep(0.25)
        st, h = _get(srv.url + "/healthz")
        assert st == 200, h              # idle: never dead
        engine.add_request(_prompts(1, seed=5)[0], max_new_tokens=4)
        time.sleep(0.25)                 # busy + no step() = wedged
        st, h = _get(srv.url + "/healthz")
        assert st == 503
        eng = h["engines"][0]
        assert eng["busy"] and eng["stale"]
        engine.run()                     # drains; healthy again
        st, _ = _get(srv.url + "/healthz")
        assert st == 200


# ---------------------------------------------------------------------------
# scrape under churn + kill-9 port reuse (satellite)
# ---------------------------------------------------------------------------

class TestScrapeChurn:
    def test_100hz_scrape_under_64_stream_churn(self, smodel):
        """Satellite: a scraper hammering /metrics + /doctor at ~100 Hz
        while 64 mixed streams churn must leave decode_compiles == 1 and
        produce parseable output on EVERY response."""
        from paddle_tpu.serving import LLMEngine
        set_flags({"FLAGS_metrics": True, "FLAGS_profiler_events": True})
        srv = ts.start(port=0)
        engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
        results = []
        errors = []
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    with urllib.request.urlopen(srv.url + "/metrics",
                                                timeout=10) as r:
                        text = r.read().decode()
                    for l in text.splitlines():
                        if l.startswith("#") or not l:
                            continue
                        float(l.rpartition(" ")[2])   # parseable or die
                    with urllib.request.urlopen(srv.url + "/doctor",
                                                timeout=10) as r:
                        json.loads(r.read().decode())
                    results.append(1)
                except Exception as e:     # noqa: BLE001 — recorded
                    errors.append(repr(e)[:200])
                time.sleep(0.005)          # ~100+ Hz across endpoints

        thr = threading.Thread(target=scraper, daemon=True)
        thr.start()
        try:
            engine.generate(_prompts(64, seed=9), max_new_tokens=5)
        finally:
            stop.set()
            thr.join(timeout=15)
        assert not errors, errors[:3]
        assert len(results) >= 10, "scraper barely ran — guard is moot"
        s = engine.stats()
        assert s["decode_compiles"] == 1, \
            "scraping retraced the decode program"
        assert s["completed"] == 64

    def test_kill9_mid_scrape_leaves_no_stuck_socket(self):
        """Satellite: SIGKILL a serving process mid-scrape; the
        replacement binds the SAME port immediately (allow_reuse_address
        — accepted sockets in TIME_WAIT must not wedge the restart)."""
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        child = _CHILD_SERVER.format(root=_ROOT, port=port)
        proc = subprocess.Popen([sys.executable, "-c", child],
                                stdout=subprocess.PIPE, text=True,
                                env={**os.environ,
                                     "JAX_PLATFORMS": "cpu"})
        try:
            assert proc.stdout.readline().strip() == f"PORT {port}"
            url = f"http://127.0.0.1:{port}"
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        urllib.request.urlopen(url + "/metrics",
                                               timeout=2).read()
                    except Exception:
                        pass

            thr = threading.Thread(target=hammer, daemon=True)
            thr.start()
            time.sleep(0.2)              # scrapes in flight
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
            stop.set()
            thr.join(timeout=5)
        finally:
            if proc.poll() is None:
                proc.kill()
        # restart on the SAME port must succeed immediately
        srv = ts.TelemetryServer(port=port).start()
        try:
            st, h = _get(f"http://127.0.0.1:{port}/healthz")
            assert st in (200, 503) and "healthy" in h
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# per-step goodput attribution
# ---------------------------------------------------------------------------

class TestStepAttribution:
    @pytest.mark.filterwarnings(
        "ignore:Operator .* produced a non-finite output")
    def test_guardian_skip_steps_are_named(self):
        """Tentpole: the accountant records WHICH steps the guardian
        skipped — in the snapshot rings, the /goodput endpoint, and the
        goodput_step_index exposition gauge."""
        clear_dispatch_cache()
        set_flags({"FLAGS_metrics": True, "FLAGS_check_numerics": True,
                   "FLAGS_check_numerics_level": 1,
                   "FLAGS_eager_chain_fusion": False,
                   "FLAGS_eager_step_fusion": False})
        srv = ts.start(port=0)
        pg.ACCOUNTANT.reset(warm=True)
        guardian.inject_fault("nan_output", op="matmul", after=3, times=1)
        try:
            _train_loop(10)
            guardian.flush()
            pg.ACCOUNTANT.step_boundary()
        finally:
            guardian.clear_faults()
        snap = pg.ACCOUNTANT.snapshot()
        skipped = snap["step_indices"].get("skipped")
        assert skipped, snap["step_indices"]
        assert all(1 <= i <= 11 for i in skipped)
        assert snap["step_indices_pretty"]["skipped"] \
            == pg.format_step_ranges(skipped)
        # the endpoint reports the same rings
        st, good = _get(srv.url + "/goodput")
        assert good["step_indices"]["skipped"] == skipped
        # the exposition carries the last-index watermark gauge
        st, text = _get(srv.url + "/metrics")
        assert (f'paddle_tpu_goodput_step_index{{bucket="skipped"}} '
                f"{skipped[-1]}" in text.splitlines())

    def test_attribution_rings_are_bounded(self):
        set_flags({"FLAGS_metrics": True})
        acct = pg.GoodputAccountant()
        for i in range(500):
            acct._attribute_step("skipped", i)
        ring = acct.step_indices["skipped"]
        assert len(ring) == pg._ATTR_RING
        assert list(ring)[-1] == 499      # newest win, oldest dropped

    def test_doctor_cli_prints_step_indices(self, capsys):
        """`fusion_doctor --demo metrics` names the skipped steps in its
        goodput line (the per-step attribution reaching the human)."""
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fusion_doctor
        rc = fusion_doctor.main(["--demo", "metrics", "--steps", "16"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "goodput :" in out
        assert "skipped at step(s)" in out


# ---------------------------------------------------------------------------
# fleet merge (tools/fleet_metrics.py)
# ---------------------------------------------------------------------------

_CHILD_SINK = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {root!r})
sys.path.insert(0, os.path.join({root!r}, "tools"))
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.profiler import goodput as pg
import metrics_export
set_flags({{"FLAGS_metrics": True}})
pm.SERVE.tokens.inc({tokens})
pm.SERVE.occupancy.set({occ})
acct = pg.ACCOUNTANT
acct.steps = {steps}
acct.buckets["productive"] = {prod}
acct.buckets["skipped"] = {skipped}
acct._attribute_step("skipped", {skip_at})
sink = metrics_export.MetricsSink(path={path!r})
sink.write()
print("WROTE")
"""

_CHILD_SERVER = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {root!r})
from paddle_tpu.profiler import telemetry_server as ts
srv = ts.start(port={port})
print("PORT", srv.port, flush=True)
time.sleep(300)
"""


class TestFleetMerge:
    def _write_sinks(self, tmp_path):
        specs = [dict(tokens=11, occ=0.9, steps=10, prod=8.0,
                      skipped=2.0, skip_at=7),
                 dict(tokens=31, occ=0.7, steps=20, prod=12.0,
                      skipped=3.0, skip_at=14)]
        paths = []
        for i, spec in enumerate(specs):
            p = str(tmp_path / f"host{i}.jsonl")
            r = subprocess.run(
                [sys.executable, "-c",
                 _CHILD_SINK.format(root=_ROOT, path=p, **spec)],
                capture_output=True, text=True, timeout=180,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert r.returncode == 0, r.stderr[-800:]
            paths.append(p)
        return paths, specs

    def test_sink_merge_fleet_goodput_exact(self, tmp_path):
        """Acceptance: fleet_metrics merging >=2 process sinks reports
        fleet goodput equal (±1e-9) to hand-merging the snapshots, with
        per-step skip indices visible per host."""
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        paths, specs = self._write_sinks(tmp_path)
        hosts = fleet_metrics.sink_hosts(paths)
        assert len(hosts) == 2
        view = fleet_metrics.fleet_view(hosts)
        # hand merge: sum productive / sum total over the raw snapshots
        prod = sum(s["prod"] for s in specs)
        total = sum(s["prod"] + s["skipped"] for s in specs)
        assert abs(view["fleet_goodput"]["goodput"] - prod / total) \
            <= 1e-9
        assert view["fleet_goodput"]["steps"] == 30
        # policy merge: occupancy ADDS fleet-wide, tokens add
        merged = view["merged"]
        assert merged["serve_occupancy"]["series"][0]["value"] \
            == pytest.approx(1.6)
        assert merged["serve_tokens_total"]["series"][0]["value"] == 42
        # per-host skip indices survive with their host prefix
        idx = view["fleet_goodput"]["step_indices"]["skipped"]
        assert sorted(v[0] for v in idx.values()) == [7, 14]
        # drift: per-host goodput present for both hosts
        per_host = view["drift"]["per_host"]
        assert len(per_host) == 2
        assert all(v["goodput"] is not None for v in per_host.values())
        # the summary renders without error and names the skip steps
        text = fleet_metrics.format_fleet_summary(view)
        assert "goodput" in text and "skipped steps" in text

    def test_host_labeled_exposition(self, tmp_path):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        paths, _ = self._write_sinks(tmp_path)
        hosts = fleet_metrics.sink_hosts(paths)
        view = fleet_metrics.fleet_view(hosts)
        text = pm.exposition(view["labeled"])
        host_lines = [l for l in text.splitlines()
                      if l.startswith("paddle_tpu_serve_tokens_total")]
        # one labeled series per host, values NOT collapsed
        assert len(host_lines) == 2
        assert all('host="' in l for l in host_lines)
        assert {l.rpartition(" ")[2] for l in host_lines} == {"11", "31"}

    def test_cli_merges_sinks(self, tmp_path, capsys):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        paths, _ = self._write_sinks(tmp_path)
        rc = fleet_metrics.main(["--sink", str(tmp_path / "*.jsonl"),
                                 "--merged-prom"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "paddle_tpu_serve_tokens_total 42" in out.splitlines()

    def test_live_url_scrape_two_processes(self, smodel):
        """Fleet merge over LIVE endpoints: this process's server plus a
        subprocess server — two hosts, one drift view."""
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        set_flags({"FLAGS_metrics": True})
        pm.SERVE.tokens.inc(5)
        _train_loop(3)
        srv = ts.start(port=0)
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _CHILD_SERVER.format(root=_ROOT, port=0)],
            stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            line = proc.stdout.readline().strip()
            child_port = int(line.split()[1])
            hosts = {}
            for label, port in (("self", srv.port),
                                ("child", child_port)):
                hosts[label] = fleet_metrics.fetch_host(
                    f"http://127.0.0.1:{port}")
            view = fleet_metrics.fleet_view(hosts)
            assert view["hosts"] == ["child", "self"]
            merged = view["merged"]
            assert merged["serve_tokens_total"]["series"][0]["value"] \
                == 5                      # child contributed zeros
            assert view["fleet_goodput"]["steps"] == 3
        finally:
            proc.kill()
            proc.wait(timeout=30)


class TestFleetGenerations:
    """stale_member classification (elastic fabric, PR 20): a host whose
    `/fleet` generation trails the fleet's — or that the coordinator
    lists in stale_hosts — is named stale_member, excluded from the
    drift ratio, and skipped by the straggler classifier."""

    @staticmethod
    def _goodput(p50):
        return {"steps": 6, "goodput": 0.9, "mfu": 0.1,
                "tokens_per_sec": 0.0, "step_ms_p50": p50,
                "step_ms_p99": p50, "buckets_s": {"productive": 1.0}}

    def _hosts(self):
        return {"h0": ({}, self._goodput(10.0)),
                "h1": ({}, self._goodput(11.0)),
                "h2": ({}, self._goodput(500.0))}

    def test_trailing_generation_is_stale_member(self):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        fleet = {
            "h0": {"armed": True, "generation": 2,
                   "member": {"host": "w0", "generation": 2},
                   "coordinator": {"generation": 2,
                                   "stale_hosts": ["w2"]}},
            "h1": {"armed": True, "generation": 2,
                   "member": {"host": "w1", "generation": 2}},
            "h2": {"armed": True, "generation": 1,
                   "member": {"host": "w2", "generation": 1}},
        }
        view = fleet_metrics.fleet_view(self._hosts(), fleet=fleet)
        drift = view["drift"]
        assert drift["fleet_generation"] == 2
        assert drift["generations"] == {"h0": 2, "h1": 2, "h2": 1}
        # both stale signals (trailing generation, coordinator
        # stale_hosts with host_id->label mapping) agree on h2
        assert drift["stale_members"] == ["h2"]
        per = drift["per_host"]
        assert per["h2"]["status"] == "stale_member"
        assert per["h2"]["generation"] == 1
        assert per["h0"]["status"] == per["h1"]["status"] == "ok"
        # the 50x-slower h2 is STALE, not the straggler: the ratio must
        # come from the two live hosts only
        assert drift["slowest_host"] == "h1"
        assert drift["step_time_ratio"] == pytest.approx(1.1)
        text = fleet_metrics.format_fleet_summary(view)
        assert "stale_member" in text and "generation 2" in text

    def test_coordinator_stale_hosts_without_generations(self):
        """A member crash leaves no `/fleet` scrape for it — only the
        coordinator's stale_hosts names it (by fabric host_id, reported
        as-is when no scraped label matches)."""
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        fleet = {"h0": {"armed": True, "generation": 3,
                        "member": {"host": "w0", "generation": 3},
                        "coordinator": {"generation": 3,
                                        "stale_hosts": ["w9"]}}}
        view = fleet_metrics.fleet_view(self._hosts(), fleet=fleet)
        assert view["drift"]["stale_members"] == ["w9"]
        assert view["drift"]["per_host"]["h0"]["status"] == "ok"

    def test_no_fleet_scrape_degrades_to_metrics_view(self):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fleet_metrics
        for fleet in (None, {}, {"h0": None, "h1": None, "h2": None}):
            view = fleet_metrics.fleet_view(self._hosts(), fleet=fleet)
            drift = view["drift"]
            assert "stale_members" not in drift
            assert "fleet_generation" not in drift
            assert all(v["status"] == "ok"
                       for v in drift["per_host"].values())


# ---------------------------------------------------------------------------
# fusion_doctor --url
# ---------------------------------------------------------------------------

class TestRemoteDoctor:
    def test_doctor_url_same_schema_as_json(self, capsys):
        set_flags({"FLAGS_metrics": True, "FLAGS_profiler_events": True})
        srv = ts.start(port=0)
        _train_loop(8)
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fusion_doctor
        rc = fusion_doctor.main(["--url", srv.url, "--json"])
        out = capsys.readouterr().out
        assert rc == 0
        remote = json.loads(out)
        local = ts.doctor_report()
        assert set(remote) == set(local)   # same schema, same sections
        for k in ("verdict", "headline", "metrics", "goodput"):
            assert k in remote
        # text mode renders the live report + metrics + goodput line
        rc = fusion_doctor.main(["--url", srv.url])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fusion doctor" in out and "goodput :" in out

    def test_doctor_url_unreachable_fails_cleanly(self, capsys):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import fusion_doctor
        rc = fusion_doctor.main(["--url", "http://127.0.0.1:9",
                                 "--json"])
        assert rc == 1
        assert "could not reach" in capsys.readouterr().err
