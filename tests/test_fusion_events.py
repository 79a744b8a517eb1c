"""Fusion flight recorder (profiler/events.py) + doctor + trace lanes.

Covers the PR 4 observability contract end to end:
  * the event-category and reason-code sets are PUBLIC contracts — the
    fusion doctor, the perf-smoke "no unexplained splits" guard, and
    downstream trace tooling key on the exact strings;
  * the ring buffer stays bounded under sustained emission, separates
    emitting threads, and records NOTHING (not one event) when
    FLAGS_profiler_events is off;
  * the three fusion tiers emit their lifecycle (dispatch hit/miss/bypass,
    chain detect/fire/split, step promote/fire/split/record) with reason
    attribution — dropout now PROMOTES (hoisted stream keys; only a
    stateful key baked into a closure still blames `rng_rekey`), masked
    attention and nll_loss no longer bypass at all (PR 4 satellite);
  * profiler/explain.py turns the timeline into the right verdicts;
  * Profiler windows auto-arm the recorder, export chrome traces with
    fusion lanes, and `load_profiler_result` round-trips them losslessly.
"""
from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.dispatch import clear_dispatch_cache
from paddle_tpu.ops import manipulation as manip
from paddle_tpu.profiler import (Profiler, SummaryView, dispatch_cache_stats,
                                 load_profiler_result,
                                 reset_chain_fusion_stats,
                                 reset_dispatch_cache_stats,
                                 reset_step_fusion_stats)
from paddle_tpu.profiler.events import (CATEGORIES, EVENTS, REASON_CODES,
                                        clear_fusion_events, events_summary,
                                        fusion_events)
from paddle_tpu.profiler.explain import explain, format_report

_DEFAULT_FLAGS = {
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 512,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_chain_cache_size": 128,
    "FLAGS_eager_chain_stitching": True,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
    "FLAGS_eager_step_fusion_cache_size": 8,
    "FLAGS_profiler_events": False,
    "FLAGS_profiler_events_capacity": 65536,
}


@pytest.fixture(autouse=True)
def _fresh():
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    clear_fusion_events()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    yield
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    clear_fusion_events()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()


def _train_loop(steps, dropout_p=0.0, with_mask=False, b=4, d=16,
                legacy_rng=False):
    """Tiny fwd+bwd+SGD loop; optional dropout / masked attention /
    a deliberately STATEFUL-RNG op (a fresh key baked into its closure
    every call — the shape the hoisted-key path retired, kept here as the
    rng_rekey attribution fixture)."""
    import jax
    from paddle_tpu.framework.random import get_rng_key
    from paddle_tpu.ops._helpers import unary

    rng = np.random.default_rng(3)
    x = paddle.to_tensor(rng.standard_normal((b, d)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((d, d)).astype(np.float32),
                         stop_gradient=False)
    bias = paddle.to_tensor(rng.standard_normal(d).astype(np.float32),
                            stop_gradient=False)
    mask = None
    if with_mask:
        mask = paddle.to_tensor(np.tril(np.ones((b, b), bool))[None, None])
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w, bias])
    for _ in range(steps):
        h = F.gelu(paddle.add(paddle.matmul(x, w), bias))
        if dropout_p:
            h = F.dropout(h, dropout_p)
        if legacy_rng:
            noise = jax.random.normal(get_rng_key(), (b, d)) * 0.01
            h = unary("legacy_noise", lambda v: v + noise.astype(v.dtype),
                      h)
        if with_mask:
            q = manip.reshape(h, [1, b, 1, d])
            h = manip.reshape(
                F.scaled_dot_product_attention(q, q, q, attn_mask=mask),
                [b, d])
        h.sum().backward()
        opt.step()
        opt.clear_grad()
    return w, bias


class TestPublicContract:
    """The category and reason-code sets are frozen API: changing them
    breaks the doctor, the smoke guard, and saved traces. Additions are
    deliberate (update this test); renames/removals are regressions."""

    def test_categories_exact(self):
        assert CATEGORIES == frozenset({
            "dispatch.hit", "dispatch.miss", "dispatch.bypass",
            "dispatch.retrace",
            "chain.detect", "chain.compile", "chain.fire", "chain.split",
            "chain.stitch",
            "step.record", "step.promote", "step.fire", "step.split",
            "step.deactivate",
            # serving-engine request lifecycle (PR 6, paddle_tpu/serving)
            "serve.enqueue", "serve.admit", "serve.step", "serve.evict",
            "serve.complete",
            # serving resilience (PR 7, serving/resilience.py)
            "serve.cancel", "serve.expire", "serve.refuse", "serve.hang",
            "serve.degrade", "serve.resume",
            # multi-tenant serving (PR 17, serving/tenancy.py)
            "serve.prefix_hit", "serve.prefix_miss", "serve.prefix_evict",
            "serve.swap",
            # compiled stochastic sampling + pipelined decode (PR 18)
            "serve.sample",
            # persistent AOT executable cache (PR 9, ops/aot_cache.py)
            "aot.hit", "aot.miss", "aot.store", "aot.corrupt",
            "aot.version_skew", "aot.evict",
            # kernel tier (PR 11, kernels/pallas/ + int8 KV cache)
            "kernel.fallback", "kernel.quantized",
            # regression sentinel (PR 19, profiler/sentinel.py)
            "sentinel.arm", "sentinel.check", "sentinel.drift",
            "sentinel.recover",
            # elastic fleet fabric (PR 20, distributed/fabric.py)
            "fleet.join", "fleet.leave", "fleet.rebuild", "fleet.rejoin",
        })

    def test_reason_codes_exact(self):
        assert REASON_CODES == frozenset({
            "unkeyable_closure", "rng_rekey", "tracer_input",
            "cache_disabled", "unjittable",
            "key_mismatch", "shape_mismatch", "wiring_mismatch",
            "registry_bump", "mid_chain_escape", "mid_step_peek",
            "event_mismatch", "param_mismatch", "optimizer_state_change",
            "hook_present", "exec_fault", "trace_fail", "debug_interrupt",
            "flag_off",
            "uncached_dispatch", "multi_backward", "cycle_too_long",
            "unpromotable_cycle", "fail_streak",
            # step-guardian decisions (PR 5, FLAGS_check_numerics)
            "nonfinite_output", "nonfinite_skip", "scaler_backoff",
            "injected_fault",
            # serving-engine outcomes (PR 6, paddle_tpu/serving)
            "kv_exhausted", "bucket_retrace",
            # serving resilience decisions (PR 7, serving/resilience.py)
            "client_cancel", "deadline_expired", "queue_full",
            "deadline_infeasible", "step_hang", "decode_fault",
            "crash_resume",
            # multi-tenant serving (PR 17, serving/tenancy.py)
            "prefix_hit", "adapter_mismatch", "torn_swap",
            # compiled sampling + pipelined decode (PR 18,
            # serving/sampling.py)
            "sampler_mismatch", "commit_lag_rollback",
            # distributed step fusion (PR 10, ops/spmd_fusion.py);
            # pipeline promotion registry (PR 16) adds schedule churn
            "collective_unkeyed", "mesh_mismatch", "spmd_divergence",
            "pipe_schedule_mismatch",
            # AOT executable-store decisions (PR 9, ops/aot_cache.py)
            "artifact_corrupt", "version_skew",
            # kernel tier (PR 11, FLAGS_serve_attention_kernel + int8 KV)
            "kernel_fallback", "kv_quantized",
            # promotion-safety static analyzer (PR 15,
            # paddle_tpu/analysis/): static-only finding classes — the
            # R1-R4 rules reuse the runtime codes above
            "contract_drift", "lock_discipline",
            # regression sentinel verdicts (PR 19, profiler/sentinel.py)
            # + the R7 static perf-contract finding class
            "perf_drift", "split_regression", "compile_storm",
            "latency_drift", "perf_contract",
            # elastic fleet fabric (PR 20, distributed/fabric.py)
            "host_lost", "mesh_rebuild", "stale_member",
        })

    def test_every_reason_has_a_doctor_hint(self):
        from paddle_tpu.profiler.explain import REASON_HINTS
        assert set(REASON_HINTS) == REASON_CODES


class TestRingBuffer:
    def test_bounded_under_sustained_emission(self):
        set_flags({"FLAGS_profiler_events": True,
                   "FLAGS_profiler_events_capacity": 64})
        clear_fusion_events()      # re-applies the capacity flag
        for i in range(1000):
            EVENTS.emit("dispatch.hit", f"op{i}")
        assert len(EVENTS) == 64
        snap = fusion_events()
        assert len(snap) == 64
        # oldest dropped, newest kept, seq strictly increasing
        assert snap[-1]["op"] == "op999"
        seqs = [e["seq"] for e in snap]
        assert seqs == sorted(seqs)

    def test_zero_events_when_off(self):
        assert not EVENTS.enabled
        _train_loop(4)
        EVENTS.emit("dispatch.hit", "manual")
        assert len(EVENTS) == 0
        assert fusion_events() == []

    def test_thread_id_separation(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        tids = []

        def worker():
            tids.append(threading.get_ident())
            rng = np.random.default_rng(0)
            a = paddle.to_tensor(
                rng.standard_normal((4, 4)).astype(np.float32))
            for _ in range(6):
                paddle.matmul(a, a)

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ev_tids = {e["tid"] for e in fusion_events("dispatch")}
        assert set(tids) <= ev_tids
        by_thread = {t: [e for e in fusion_events("dispatch")
                         if e["tid"] == t] for t in tids}
        for t in tids:
            assert by_thread[t], f"thread {t} emitted no dispatch events"

    def test_snapshot_filters(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        EVENTS.emit("dispatch.hit", "a")
        EVENTS.emit("chain.fire", "b")
        mark = EVENTS.total
        EVENTS.emit("step.fire", "c")
        assert [e["cat"] for e in fusion_events("chain")] == ["chain.fire"]
        assert [e["op"] for e in fusion_events(since_seq=mark)] == ["c"]

    def test_key_digest_never_leaks_raw_keys(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        EVENTS.emit("dispatch.hit", "op", key=("matmul", 1, (2, 3)))
        EVENTS.emit("dispatch.bypass", "op", key=None, reason="rng_rekey")
        a, b = fusion_events()
        assert isinstance(a["key"], str) and len(a["key"]) == 12
        assert b["key"] is None


class TestLifecycleEvents:
    def test_fused_loop_emits_all_tiers(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(12)
        cats = events_summary()["by_category"]
        for expected in ("dispatch.miss", "dispatch.hit", "chain.detect",
                         "step.promote", "step.fire", "step.record"):
            assert cats.get(expected, 0) > 0, (expected, cats)

    def test_dropout_promotes_with_hoisted_keys(self):
        """Universal promotion: dropout keys on a hoisted stream
        position now — zero rng_rekey poisons, zero dispatch bypasses,
        and the cycle PROMOTES (the exact loop that used to be the
        never-promotes fixture)."""
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(10, dropout_p=0.2)
        poisons = [e for e in fusion_events("step.record")
                   if e["reason"] == "rng_rekey"]
        assert poisons == []
        bypass_ops = [e["op"] for e in fusion_events("dispatch.bypass")]
        assert "dropout" not in bypass_ops
        cats = events_summary()["by_category"]
        assert cats.get("step.promote", 0) >= 1
        assert cats.get("step.fire", 0) >= 1

    def test_stateful_rng_closure_blames_rng_rekey(self):
        """The rng_rekey attribution survives for ops that still bake a
        STATEFUL fresh key into their closure (the legacy shape)."""
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(10, legacy_rng=True)
        poisons = [e for e in fusion_events("step.record")
                   if e["reason"] == "rng_rekey"]
        assert len(poisons) >= 8
        assert {e["op"] for e in poisons} == {"legacy_noise"}
        assert events_summary()["by_category"].get("step.promote", 0) == 0

    def test_masked_attention_and_nll_do_not_bypass(self):
        """PR 4 satellite: mask/label are dispatch inputs now — the
        unkeyable_closure count for these ops must be zero."""
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(6, with_mask=True)
        rng = np.random.default_rng(0)
        logp = paddle.to_tensor(
            np.log(rng.dirichlet(np.ones(5), 8)).astype(np.float32))
        lab = paddle.to_tensor(rng.integers(0, 5, 8))
        F.nll_loss(logp, lab)
        bypass_ops = [e["op"] for e in fusion_events("dispatch.bypass")]
        assert "scaled_dot_product_attention" not in bypass_ops
        assert "nll_loss" not in bypass_ops
        ops = dispatch_cache_stats(per_op=True)["ops"]
        assert ops["scaled_dot_product_attention"]["bypasses"] == 0
        assert ops["nll_loss"]["bypasses"] == 0

    def test_masked_attention_promotes_cleanly(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(12, with_mask=True)
        rep = explain()
        assert rep["verdict"] == "clean_promotion", rep["headline"]
        assert rep["step"]["fired"] > 0

    def test_mid_step_peek_split_reason(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        rng = np.random.default_rng(3)
        x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
        w = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32),
                             stop_gradient=False)
        opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w])
        for i in range(10):
            loss = F.gelu(paddle.matmul(x, w)).sum()
            loss.backward()
            if i == 8:
                float(loss)     # peek mid-replay: must split, attributed
            opt.step()
            opt.clear_grad()
        splits = fusion_events("step.split")
        assert splits and splits[0]["reason"] == "mid_step_peek"

    def test_all_emitted_reasons_are_known(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(10, dropout_p=0.2)
        _train_loop(10, with_mask=True)
        bad = [e for e in fusion_events()
               if e["reason"] is not None and e["reason"] not in REASON_CODES]
        assert bad == []


class TestExplain:
    def test_no_data_verdict(self):
        rep = explain([])
        assert rep["verdict"] == "no_data"

    def test_never_promoted_names_the_op(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(10, legacy_rng=True)
        rep = explain()
        assert rep["verdict"] == "never_promoted"
        assert "rng_rekey" in rep["headline"]
        assert "legacy_noise" in rep["headline"]
        text = format_report(rep)
        assert "never_promoted" in text and "rng_rekey" in text

    def test_report_is_json_ready(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _train_loop(6)
        json.dumps(explain())


class TestProfilerIntegration:
    def test_window_arms_and_restores_flag(self):
        assert not EVENTS.enabled
        prof = Profiler()
        prof.start()
        assert EVENTS.enabled
        _train_loop(3)
        prof.stop()
        assert not EVENTS.enabled
        assert prof._fusion_events

    def test_summary_has_fusion_view(self, capsys):
        prof = Profiler()
        prof.start()
        _train_loop(8)
        prof.stop()
        table = prof.summary()
        capsys.readouterr()
        assert "Fusion View" in table
        assert "step_fusion" in table
        assert "step.fire" in table
        # the pre-existing counter structs are folded in (PR 4 satellite)
        assert "hit_rate" in table and "fused_steps" in table
        # view filtering still honors non-fusion selections
        host_only = prof.summary(views=[SummaryView.OperatorView])
        capsys.readouterr()
        assert "Fusion View" not in host_only

    def test_chrome_trace_lanes_and_roundtrip(self, tmp_path):
        prof = Profiler()
        prof.start()
        _train_loop(10)
        prof.stop()
        path = os.path.join(tmp_path, "trace.json")
        prof.export(path)
        res = load_profiler_result(path)
        lanes = {e.get("cat") for e in res.trace_events
                 if str(e.get("cat", "")).startswith("fusion.")}
        assert lanes == {"fusion.dispatch", "fusion.chain", "fusion.step"}
        names = {e["args"]["name"] for e in res.trace_events
                 if e.get("ph") == "M" and e.get("name") == "thread_name"}
        assert {"fusion:dispatch", "fusion:chain",
                "fusion:step"} <= names
        # lossless round-trip: the raw events survive re-load and
        # re-summarize identically (satellite: load_profiler_result)
        assert len(res.fusion_events) == len(prof._fusion_events)
        assert res.events_summary() == events_summary(prof._fusion_events)
        assert [e["seq"] for e in res.fusion_events] \
            == [e["seq"] for e in prof._fusion_events]
        assert "step.fire" in res.summary()
        # instant events sit on the synthetic lanes with μs timestamps
        inst = [e for e in res.trace_events
                if str(e.get("cat", "")).startswith("fusion.")
                and e.get("ph") == "i"]
        assert inst and all(e["ts"] > 0 for e in inst)


class TestDoctorCLI:
    def test_demo_dropout_promotes_cleanly(self):
        """Universal promotion acceptance: the dropout GPT demo — the
        historical rng_rekey fixture — now reports clean_promotion."""
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                          "fusion_doctor.py"),
             "--demo", "dropout", "--steps", "12", "--json"],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        assert rep["verdict"] == "clean_promotion", rep["headline"]

    def test_demo_accum_promotes_cleanly(self):
        """Universal promotion acceptance: the k=4 grad-accumulation GPT
        demo promotes as a super-cycle with no rng_rekey /
        unpromotable_cycle findings."""
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                          "fusion_doctor.py"),
             "--demo", "accum", "--steps", "12", "--json"],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        assert rep["verdict"] == "clean_promotion", rep["headline"]
        text = json.dumps(rep)
        assert "rng_rekey" not in text
        assert "unpromotable_cycle" not in text

    def test_demo_masked_promotes_cleanly(self):
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                          "fusion_doctor.py"),
             "--demo", "masked", "--steps", "12", "--json"],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        assert rep["verdict"] == "clean_promotion"
        assert rep["step"]["fired"] > 0
