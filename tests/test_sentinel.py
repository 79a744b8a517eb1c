"""Performance regression sentinel (PR 19, ROADMAP 7(b)).

Contracts pinned here:

  * `classify(record, bands)` names every violated band with a
    REASON_CODES verdict — goodput/throughput floors -> perf_drift,
    p50/p99 bands -> latency_drift, reason-histogram escapes and
    hang/skip storms -> split_regression, retrace/rebuild allowances ->
    compile_storm — sorted worst-first, and stays silent on partial or
    idle records (a band with no observation is not a violation);
  * `bands_from_record` derives the tolerance windows: goodput floor is
    half the observed fraction, latency/throughput scale with `slack`,
    the reason histogram is closed, decode/prefill rebuilds get NO
    headroom;
  * `PerfBaseline` keeps an operator's baseline file honest: add
    requires a note, save/load round-trips, split() three-ways records
    into violations/passed/unbaselined, stale/expire retire dead legs;
  * no baseline file is shipped and none is looked for: a named leg
    (`arm(leg=...)`, FLAGS_sentinel_leg, `fleet_metrics --leg`) without
    a path the operator gave is refused with a message that says so;
  * the live watcher self-calibrates on its first active window, flags
    an injected stall storm as split_regression and a fresh engine's
    decode rebuild as compile_storm, recovers on the next clean window,
    and its disarmed tick is a no-op that never opens windows;
  * /sentinel serves the snapshot schema and /readyz folds the degraded
    latch in: 503 with the machine-readable finding attached while the
    latch is set, 200 again after recovery.
"""
from __future__ import annotations

import json
import os
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
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.profiler import sentinel as snt
from paddle_tpu.profiler import telemetry_server as ts
from paddle_tpu.profiler.events import clear_fusion_events
from paddle_tpu.profiler.sentinel import (PerfBaseline, bands_from_record,
                                          capture_record, classify)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DEFAULT_FLAGS = {
    "FLAGS_metrics": False,
    "FLAGS_check_numerics": False,
    "FLAGS_profiler_events": False,
    "FLAGS_serve_step_timeout_ms": 0,
    "FLAGS_telemetry_port": 0,
    "FLAGS_sentinel": False,
    "FLAGS_sentinel_leg": "",
    "FLAGS_sentinel_baseline": "",
    "FLAGS_sentinel_window_s": 10.0,
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
}


@pytest.fixture(autouse=True)
def _fresh():
    snt.disarm()
    snt.SENTINEL.reset()
    set_flags(dict(_DEFAULT_FLAGS))
    ts.stop()
    ts._ENGINES.clear()
    ts._HEART.clear()
    pm.reset_metrics()
    clear_fusion_events()
    guardian.clear_faults()
    guardian.reset_thread_state()
    yield
    snt.disarm()
    snt.SENTINEL.reset()
    ts.stop()
    ts._ENGINES.clear()
    ts._HEART.clear()
    set_flags(dict(_DEFAULT_FLAGS))
    pm.reset_metrics()
    clear_fusion_events()
    guardian.clear_faults()
    guardian.reset_thread_state()


def _clean_record(**over):
    """A healthy fused-train leg record; tests perturb one axis each."""
    rec = {
        "version": 1, "leg": "unit", "kind": "train",
        "window_s": 2.0, "steps": 40, "serve_steps": 0,
        "goodput": 0.9,
        "buckets_s": {"productive": 1.8, "stalled": 0.2},
        "step_ms_p50": 5.0, "step_ms_p99": 9.0,
        "serve_ms_p50": 0.0, "serve_ms_p99": 0.0,
        "tokens_per_sec": 1000.0,
        "reasons": {"chain.split": {}, },
        "compiles": {"dispatch": 2, "chain": 1, "step": 1,
                     "decode": 0, "prefill": 0},
        "hangs": 0, "skips": 0,
    }
    rec["reasons"] = {"chain.split:shape_change": 3}
    rec.update(over)
    return rec


VERDICTS = ("perf_drift", "split_regression", "compile_storm",
            "latency_drift")


# ---------------------------------------------------------------------------
# classify: one verdict per band family
# ---------------------------------------------------------------------------

class TestClassify:
    def test_clean_record_has_no_findings(self):
        rec = _clean_record()
        assert classify(rec, bands_from_record(rec)) == []

    def test_goodput_drop_is_perf_drift(self):
        bands = bands_from_record(_clean_record())
        fs = classify(_clean_record(goodput=0.2), bands)
        assert fs and fs[0]["reason"] == "perf_drift"
        assert fs[0]["metric"] == "goodput"
        assert fs[0]["observed"] == 0.2
        assert fs[0]["bound"] == pytest.approx(0.45)

    def test_throughput_floor_is_perf_drift(self):
        bands = bands_from_record(_clean_record(), slack=2.0)
        fs = classify(_clean_record(tokens_per_sec=100.0), bands)
        assert [f["reason"] for f in fs] == ["perf_drift"]
        assert fs[0]["metric"] == "tokens_per_sec"

    def test_latency_band_is_latency_drift(self):
        bands = bands_from_record(_clean_record(), slack=2.0)
        fs = classify(_clean_record(step_ms_p99=50.0), bands)
        assert [f["reason"] for f in fs] == ["latency_drift"]
        assert fs[0]["metric"] == "step_ms_p99"

    def test_novel_reason_is_split_regression(self):
        bands = bands_from_record(_clean_record())
        bad = _clean_record(reasons={"chain.split:shape_change": 3,
                                     "step.deactivate:retrace_storm": 1})
        fs = classify(bad, bands)
        assert [f["reason"] for f in fs] == ["split_regression"]
        assert "outside the baseline histogram" in fs[0]["message"]

    def test_reason_storm_over_cap_is_split_regression(self):
        bands = bands_from_record(_clean_record())
        # cap is max(4n, 8) = 12 for the 3x baseline reason
        ok = classify(_clean_record(
            reasons={"chain.split:shape_change": 12}), bands)
        assert ok == []
        fs = classify(_clean_record(
            reasons={"chain.split:shape_change": 13}), bands)
        assert [f["reason"] for f in fs] == ["split_regression"]

    def test_hang_and_skip_storms_are_split_regression(self):
        bands = bands_from_record(_clean_record(hangs=1, skips=1))
        assert classify(_clean_record(hangs=2, skips=2), bands) == []
        fs = classify(_clean_record(hangs=3, skips=3), bands)
        assert {f["metric"] for f in fs} == {"hangs", "skips"}
        assert {f["reason"] for f in fs} == {"split_regression"}

    def test_decode_rebuild_is_compile_storm_with_no_headroom(self):
        bands = bands_from_record(_clean_record())
        bad = _clean_record(compiles={"dispatch": 2, "chain": 1,
                                      "step": 1, "decode": 1,
                                      "prefill": 0})
        fs = classify(bad, bands)
        assert [f["reason"] for f in fs] == ["compile_storm"]
        assert fs[0]["metric"] == "compiles.decode"
        assert fs[0]["bound"] == 0

    def test_severity_order_worst_first(self):
        bands = bands_from_record(_clean_record(), slack=2.0)
        bad = _clean_record(
            goodput=0.1, step_ms_p50=99.0,
            reasons={"serve.hang:watchdog": 5},
            compiles={"dispatch": 99, "chain": 1, "step": 1,
                      "decode": 3, "prefill": 0})
        order = [f["reason"] for f in classify(bad, bands)]
        assert order == sorted(
            order, key=("compile_storm", "split_regression",
                        "perf_drift", "latency_drift").index)
        assert order[0] == "compile_storm"

    def test_idle_record_never_drifts(self):
        bands = bands_from_record(_clean_record())
        idle = _clean_record(steps=0, serve_steps=0, goodput=0.0,
                             tokens_per_sec=0.0, buckets_s={},
                             reasons={}, compiles={})
        assert classify(idle, bands) == []

    def test_partial_record_is_band_neutral(self):
        bands = bands_from_record(_clean_record())
        assert classify({"leg": "unit", "steps": 1}, bands) == []

    def test_every_finding_reason_is_on_the_contract(self):
        from paddle_tpu.profiler.events import REASON_CODES
        assert set(VERDICTS) <= set(REASON_CODES)


class TestBands:
    def test_slack_scales_latency_and_throughput_only(self):
        rec = _clean_record()
        tight = bands_from_record(rec, slack=2.0)
        wide = bands_from_record(rec, slack=20.0)
        assert wide["step_ms_p99_max"] == 10 * tight["step_ms_p99_max"]
        assert wide["tokens_per_sec_min"] == pytest.approx(
            tight["tokens_per_sec_min"] / 10)
        # structural bands are slack-independent
        assert wide["goodput_min"] == tight["goodput_min"] == 0.45
        assert wide["max_compiles"] == tight["max_compiles"]
        assert wide["allowed_reasons"] == tight["allowed_reasons"]

    def test_decode_prefill_get_no_headroom(self):
        mc = bands_from_record(_clean_record())["max_compiles"]
        assert mc["decode"] == 0 and mc["prefill"] == 0
        assert mc["dispatch"] == 4      # 2 + max(2, 2)

    def test_zero_latency_axes_are_unbanded(self):
        bands = bands_from_record(_clean_record())
        assert "serve_ms_p50_max" not in bands
        assert "serve_ms_p99_max" not in bands


# ---------------------------------------------------------------------------
# the baseline file
# ---------------------------------------------------------------------------

class TestPerfBaseline:
    def test_add_save_load_match_round_trip(self, tmp_path):
        path = str(tmp_path / "pb.json")
        bl = PerfBaseline(policy="unit policy")
        entry = bl.add(_clean_record(), note="unit seed", slack=5.0)
        bl.save(path)
        re = PerfBaseline.load(path)
        assert re.policy == "unit policy"
        assert re.match("unit") == entry
        assert re.match("unit")["note"] == "unit seed"
        assert re.match("missing") is None

    def test_add_requires_a_note(self):
        with pytest.raises(ValueError, match="needs a note"):
            PerfBaseline().add(_clean_record(), note="")

    def test_readd_keeps_old_note_when_blank(self):
        bl = PerfBaseline()
        bl.add(_clean_record(), note="first")
        bl.add(_clean_record(step_ms_p50=4.0), note=None)
        assert bl.match("unit")["note"] == "first"
        assert bl.match("unit")["captured"]["step_ms_p50"] == 4.0

    def test_split_three_ways(self):
        bl = PerfBaseline()
        bl.add(_clean_record(), note="n")
        good = _clean_record()
        bad = _clean_record(goodput=0.1)
        unk = _clean_record(leg="other")
        viol, passed, unb = bl.split([good, bad, unk])
        assert passed == [good] and unb == [unk]
        assert len(viol) == 1 and viol[0][0] is bad
        assert viol[0][1][0]["reason"] == "perf_drift"

    def test_stale_and_expire(self):
        bl = PerfBaseline()
        bl.add(_clean_record(), note="n")
        bl.add(_clean_record(leg="dead"), note="n")
        assert bl.stale([_clean_record()]) == ["dead"]
        assert bl.expire([_clean_record()]) == ["dead"]
        assert sorted(bl.legs) == ["unit"]

    def test_version_skew_is_an_error(self, tmp_path):
        path = tmp_path / "pb.json"
        path.write_text('{"version": 99, "legs": {}}')
        with pytest.raises(ValueError, match="version"):
            PerfBaseline.load(str(path))


# ---------------------------------------------------------------------------
# the live watcher
# ---------------------------------------------------------------------------

VOCAB = 128


@pytest.fixture(scope="module")
def smodel():
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=64, max_position_embeddings=64,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, int(k)).tolist()
            for k in rng.integers(3, 16, n)]


def _train_steps(steps, d=32):
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


def _serve_round(engine, n=4, tokens=4):
    """Fixed prompt lengths -> fixed padded prefill shapes: after one
    warm round every compile is paid, so armed windows are compile-free
    unless a test deliberately breaks that."""
    rng = np.random.default_rng(7)
    for k in (4, 7, 10, 14)[:n]:
        engine.add_request(rng.integers(0, VOCAB, k).tolist(),
                           max_new_tokens=tokens)
    engine.run()


def _run_windows(drive, want, timeout=30.0):
    """Drive workload until the sentinel has evaluated >= want
    windows."""
    t0 = time.monotonic()
    while snt.SENTINEL.windows < want:
        drive()
        if time.monotonic() - t0 > timeout:
            raise AssertionError(
                f"sentinel stuck at {snt.SENTINEL.windows} windows "
                f"(wanted {want}): {snt.SENTINEL.snapshot()['checks']}")


def _drive_until_clean(drive, timeout=30.0):
    """Drive clean workload until the watcher has calibrated AND judged
    at least one window clean with the latch down. A single jittery CI
    window can genuinely read latency_drift on the tight 4x
    self-calibration bands — the contract under test is that clean
    traffic always RETURNS to clean, not that noise never fires."""
    t0 = time.monotonic()
    while True:
        s = snt.SENTINEL
        if s.windows >= 2 and not s.degraded \
                and s.checks.get("clean", 0) >= 1:
            return
        drive()
        if time.monotonic() - t0 > timeout:
            raise AssertionError(
                f"no clean settled window in {timeout}s: "
                f"{snt.SENTINEL.snapshot()['checks']}")


class TestLiveWatcher:
    def test_disarmed_tick_is_inert(self):
        for _ in range(1000):
            snt.tick()
        s = snt.SENTINEL.snapshot()
        assert s["windows"] == 0 and not s["armed"]

    def test_self_calibration_then_clean(self):
        # pay compiles + the whole-step promotion retrace BEFORE arming:
        # a trace spike inside an armed window is a REAL latency_drift
        _train_steps(8)
        snt.arm(window_s=0.15)
        try:
            _drive_until_clean(lambda: _train_steps(3))
            s = snt.SENTINEL.snapshot()
            assert s["band_source"] == "self"
            assert s["checks"].get("calibrate") == 1
            assert s["checks"].get("clean", 0) >= 1
            assert not s["degraded"]
            assert s["last_record"]["kind"] == "train"
        finally:
            snt.disarm()

    def test_arm_restores_borrowed_flags_on_disarm(self):
        from paddle_tpu.framework.flags import _FLAGS
        assert not _FLAGS.get("FLAGS_metrics")
        snt.arm(window_s=5.0)
        assert _FLAGS.get("FLAGS_metrics")
        assert _FLAGS.get("FLAGS_profiler_events")
        snt.disarm()
        assert not _FLAGS.get("FLAGS_metrics")
        assert not _FLAGS.get("FLAGS_profiler_events")

    def test_arm_with_unknown_leg_refuses(self, tmp_path):
        path = str(tmp_path / "pb.json")
        bl = PerfBaseline()
        bl.add(_clean_record(), note="n")
        bl.save(path)
        with pytest.raises(ValueError, match="no baseline entry"):
            snt.arm(leg="never_a_leg", baseline=path)

    @pytest.mark.parametrize("via", ["argument", "flag"])
    def test_named_leg_without_a_baseline_path_refuses(self, via):
        """No file is shipped: a leg needs the operator's path, and the
        refusal borrows no flag and arms nothing."""
        from paddle_tpu.framework.flags import _FLAGS
        assert not _FLAGS.get("FLAGS_sentinel_baseline")
        try:
            with pytest.raises(ValueError, match="needs a baseline file"):
                if via == "flag":
                    set_flags({"FLAGS_sentinel_leg": "unit"})
                    snt.arm()
                else:
                    snt.arm(leg="unit")
        finally:
            set_flags({"FLAGS_sentinel_leg": ""})
        assert not snt.SENTINEL.armed
        assert not _FLAGS.get("FLAGS_metrics")
        assert not _FLAGS.get("FLAGS_profiler_events")

    def test_named_leg_arms_from_the_operators_file(self, tmp_path):
        path = str(tmp_path / "pb.json")
        bl = PerfBaseline()
        entry = bl.add(_clean_record(), note="n")
        bl.save(path)
        snt.arm(leg="unit", baseline=path, window_s=5.0)
        try:
            assert snt.SENTINEL.band_source == "baseline"
            assert snt.SENTINEL.bands == entry["bands"]
        finally:
            snt.disarm()

    def test_no_default_baseline_file_is_looked_for(self):
        assert not hasattr(snt, "DEFAULT_PERF_BASELINE")
        with pytest.raises(TypeError):
            PerfBaseline.load()
        with pytest.raises(TypeError):
            PerfBaseline().save()
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools",
                                          "fleet_metrics.py"),
             "--sink", "nothing.jsonl", "--leg", "unit"],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 2
        assert "--leg needs --baseline" in r.stderr

    def test_stall_storm_flips_split_regression_then_recovers(
            self, smodel):
        from paddle_tpu.serving import LLMEngine
        engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
        _serve_round(engine)        # decode compiled before calibration
        snt.arm(window_s=0.15)
        try:
            _drive_until_clean(lambda: _serve_round(engine))
            assert snt.SENTINEL.band_source == "self"
            assert not snt.SENTINEL.degraded
            # arm the watchdog only for the storm: on a loaded CPU a
            # GENUINE >budget step during calibration would seed
            # serve.hang into the allowed histogram
            set_flags({"FLAGS_serve_step_timeout_ms": 60})
            # one stall per round: each watchdog firing emits a
            # serve.hang reason without the two-consecutive-hang decode
            # rebuild (that escalation is the compile_storm test)
            deadline = time.monotonic() + 30
            while not snt.SENTINEL.degraded:
                guardian.inject_fault("stall", op="serve.decode",
                                      times=1)
                _serve_round(engine)
                assert time.monotonic() < deadline, \
                    "stall storm never tripped the sentinel"
            f = snt.SENTINEL.finding
            assert f["reason"] == "split_regression"
            # the storm attributes through a hang-family signal: the
            # serve.hang/serve.degrade reason histogram or the raw
            # hang counter, whichever band trips first
            assert ("hang" in f["metric"]
                    or f["metric"].startswith("serve.")), f
            assert {"observed", "bound", "window", "leg"} <= set(f)
            # recovery: clean windows clear the latch (watchdog off
            # again so jitter hangs can't re-trip it)
            guardian.clear_faults()
            set_flags({"FLAGS_serve_step_timeout_ms": 0})
            deadline = time.monotonic() + 30
            while snt.SENTINEL.degraded:
                _serve_round(engine)
                assert time.monotonic() < deadline, \
                    "sentinel never recovered after the fault cleared"
            assert snt.SENTINEL.finding is not None   # postmortem stays
            assert snt.SENTINEL.snapshot()["finding"] is None
        finally:
            guardian.clear_faults()
            snt.disarm()

    def test_decode_rebuild_flips_compile_storm(self, smodel):
        from paddle_tpu.serving import LLMEngine
        engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
        _serve_round(engine)
        snt.arm(window_s=0.15)
        try:
            _drive_until_clean(lambda: _serve_round(engine))
            assert not snt.SENTINEL.degraded
            # a brand-new engine re-traces decode: zero-headroom band
            engine2 = LLMEngine(smodel, max_batch_size=2, block_size=4)
            deadline = time.monotonic() + 30
            while not snt.SENTINEL.degraded:
                _serve_round(engine2, n=2)
                assert time.monotonic() < deadline, \
                    "decode rebuild never tripped the sentinel"
            f = snt.SENTINEL.finding
            assert f["reason"] == "compile_storm"
            assert f["metric"].startswith("compiles.")
        finally:
            snt.disarm()

    def test_capture_record_shape(self):
        set_flags({"FLAGS_metrics": True, "FLAGS_profiler_events": True})
        _train_steps(5)
        rec = capture_record("unit_leg")
        assert rec["leg"] == "unit_leg" and rec["kind"] == "train"
        assert rec["steps"] >= 5 and rec["version"] == 1
        assert set(rec) >= {"goodput", "buckets_s", "reasons",
                            "compiles", "hangs", "skips",
                            "step_ms_p50", "step_ms_p99",
                            "tokens_per_sec", "window_s"}
        assert json.loads(json.dumps(rec)) == rec    # JSON-able


# ---------------------------------------------------------------------------
# the HTTP surface: /sentinel + the /readyz fold
# ---------------------------------------------------------------------------

class TestHTTPSurface:
    def test_sentinel_endpoint_schema(self):
        srv = ts.start(port=0)
        _train_steps(8)             # promotion retrace paid pre-arm
        snt.arm(window_s=0.15)
        try:
            _drive_until_clean(lambda: _train_steps(3))
            st, body = ts.probe_endpoint(f"{srv.url}/sentinel")
            assert st == 200
            assert set(body) == {
                "armed", "leg", "band_source", "window_s", "windows",
                "checks", "degraded", "finding", "findings",
                "last_record", "bands", "history"}
            assert body["armed"] is True
            assert body["band_source"] == "self"
            assert body["windows"] >= 2
            assert body["degraded"] is False and body["finding"] is None
            assert body["last_record"]["leg"] == "live"
            assert isinstance(body["history"], list)
        finally:
            snt.disarm()

    def test_endpoint_index_lists_sentinel(self):
        srv = ts.start(port=0)
        st, body = ts.probe_endpoint(f"{srv.url}/")
        assert st == 200 and "/sentinel" in body["endpoints"]

    def test_readyz_folds_the_degraded_latch(self, smodel):
        from paddle_tpu.serving import LLMEngine
        srv = ts.start(port=0)
        engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
        _serve_round(engine)
        snt.arm(window_s=0.15)
        try:
            _drive_until_clean(lambda: _serve_round(engine))
            st, body = ts.probe_endpoint(f"{srv.url}/readyz")
            assert st == 200
            assert body["sentinel"]["armed"] is True
            assert body["sentinel"]["degraded"] is False
            set_flags({"FLAGS_serve_step_timeout_ms": 60})
            guardian.inject_fault("stall", op="serve.decode", times=2)
            deadline = time.monotonic() + 30
            while not snt.SENTINEL.degraded:
                _serve_round(engine)
                assert time.monotonic() < deadline
            st, body = ts.probe_endpoint(f"{srv.url}/readyz")
            assert st == 503
            f = body["sentinel"]["finding"]
            assert f and f["reason"] in VERDICTS
            assert {"metric", "observed", "bound"} <= set(f)
            # recovery: fault cleared -> clean window -> 200 again
            guardian.clear_faults()
            set_flags({"FLAGS_serve_step_timeout_ms": 0})
            deadline = time.monotonic() + 30
            ready = False
            while time.monotonic() < deadline and not ready:
                _serve_round(engine)
                st, body = ts.probe_endpoint(f"{srv.url}/readyz")
                ready = (st == 200
                         and body["sentinel"]["degraded"] is False)
            assert ready, "readyz never recovered after the fault"
        finally:
            guardian.clear_faults()
            snt.disarm()

    def test_disarmed_sentinel_never_degrades_readyz(self):
        srv = ts.start(port=0)
        st, body = ts.probe_endpoint(f"{srv.url}/readyz")
        assert st == 200
        assert body["sentinel"] == {"armed": False, "degraded": False,
                                    "finding": None}

    def test_sentinel_metrics_in_exposition(self):
        srv = ts.start(port=0)
        _train_steps(8)             # promotion retrace paid pre-arm
        snt.arm(window_s=0.15)
        try:
            _drive_until_clean(lambda: _train_steps(3))
            assert not snt.SENTINEL.degraded
            st, text = ts.probe_endpoint(f"{srv.url}/metrics")
            assert st == 200
            assert "sentinel_checks_total" in text
            assert 'verdict="calibrate"' in text
            assert "sentinel_degraded 0" in text
        finally:
            snt.disarm()


# ---------------------------------------------------------------------------
# flag arming
# ---------------------------------------------------------------------------

class TestFlagArming:
    def test_maybe_arm_from_flags(self):
        assert snt.maybe_arm_from_flags() is False
        set_flags({"FLAGS_sentinel": True,
                   "FLAGS_sentinel_window_s": 0.5})
        try:
            assert snt.maybe_arm_from_flags() is True
            assert snt.SENTINEL.armed
            assert snt.SENTINEL.window_s == 0.5
            # idempotent: a second call does not re-arm/reset
            snt.SENTINEL.windows = 7
            assert snt.maybe_arm_from_flags() is True
            assert snt.SENTINEL.windows == 7
        finally:
            snt.disarm()
