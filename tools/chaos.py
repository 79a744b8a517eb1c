#!/usr/bin/env python
"""Chaos harness: prove the non-finite step guardian + crash-safe
checkpoints (PR 5) and the serving resilience layer (PR 7) survive
deliberately hostile conditions.

Training scenarios, each exercising one failure class a multi-day training
run WILL eventually hit:

  nan        a poisoned (all-NaN) batch lands in a PROMOTED dynamic-loss-
             scaled AMP loop (FLAGS_check_numerics + GradScaler riding ONE
             fused whole-step executable). Must hold: parameters bitwise
             unchanged, loss scale halved, no fusion split and no retrace
             (the skip happened in-graph), and the fusion doctor attributes
             the missing update to `nonfinite_skip`.

  exception  a fault hook (ops/guardian.inject_fault) raises ChaosFault
             from inside a dispatched op mid-step. Must hold: the exception
             surfaces cleanly to the training loop, the loop recovers on
             the next batch, parameters stay finite, and the firing is
             attributed as `injected_fault`.

  kill       a training subprocess (AMP + Momentum + LR schedule +
             EpochRange checkpoints) is SIGKILLed mid-epoch, then re-run.
             Must hold: the rerun resumes from the last atomic checkpoint
             (never a torn one), the optimizer step counter / LR schedule /
             loss scale continue exactly, and the final parameters match an
             uninterrupted run.

  warm_restart  PR 9: a training worker with the persistent AOT executable
             cache armed (FLAGS_aot_cache, ops/aot_cache.py) is SIGKILLed
             mid-run AFTER its fused step was promoted and stored. Must
             hold: the restarted process (same store + StepCheckpointer
             state) records ONE observation cycle and re-promotes the
             fused step at its first boundary with ZERO fresh compiles —
             no dispatch.retrace events, no chain compiles, no whole-step
             retrace; every executable deserializes from the store
             (aot.hit) — firing the restored step on the second cycle,
             and the combined loss trajectory matches an uninterrupted
             run. Then every artifact on disk is corrupted in place: a
             fresh worker must degrade to transparent recompiles
             (attributed `artifact_corrupt`, files quarantined), finish
             the run with an identical trajectory, and never crash.

Serving scenarios (PR 7), the same methodology against LLMEngine:

  serve_hang        an injected decode hang (guardian.inject_fault
                    "hang") trips the FLAGS_serve_step_timeout_ms
                    watchdog. Must hold: one hang (retry) recovers with
                    the decode program still compiled exactly once and
                    every stream token-identical to generate(), two in a
                    row fail the active requests as `step_hang` and the
                    queued one is then served token-identically, and the
                    doctor attributes `step_hang`.

  serve_fused_fault a poisoned fused decode output (`nan_output` on
                    "serve.decode") discards the launch and finishes the
                    in-flight streams through the eager generate() path.
                    Must hold: token-identical outputs, `decode_fault`
                    attributed, NO decode rebuild (the poison models a
                    transient fault), and the engine serves new requests
                    afterwards.

  serve_kill        a serving subprocess (ServeCheckpointer ticking every
                    step) is SIGKILLed mid-serve, then re-run against the
                    same checkpoint dir. Must hold: the restarted engine
                    restores every in-flight request and finishes each
                    stream BYTE-identically to an uninterrupted run —
                    including SAMPLED streams (PR 18), whose serialized
                    (seed, sampler) identity plus position-derived keys
                    make the resume a replay, not a re-roll.

  telemetry         PR 13: a "stall" fault (the wall-clock hang variant)
                    wedges two decode steps under an armed telemetry
                    server. Must hold: /healthz flips 503 within one
                    watchdog window, /readyz is 503 while the degraded
                    latch holds, both recover after the first clean
                    step, streams stay token-identical, and /goodput
                    names the stalled step indices.

  sentinel          PR 19: the perf regression sentinel, armed on short
                    self-calibrated windows, watches the same stall
                    storm. Must hold: the degraded latch flips within
                    one evaluation window with a machine-readable
                    verdict ({reason, metric, observed, bound} on the
                    REASON_CODES contract), /readyz is 503 with the
                    finding attached, the latch recovers on the first
                    clean window after the fault clears, and the storm's
                    streams finish token-identically.

Elastic-fleet scenarios (PR 20, distributed/fabric.py), multi-process:

  fleet_kill        N CPU workers rendezvous through the stdlib-TCP
                    coordinator, train a dp=N data-parallel loop (full
                    deterministic global batch per step, so every
                    replica computes identical state), and ONE worker is
                    SIGKILLed mid-accumulation. Must hold: the
                    coordinator declares the host lost within its lease
                    (`host_lost`), bumps the generation exactly once,
                    and the survivors — within seconds, not a re-warmup
                    — restore the latest StepCheckpointer snapshot,
                    rebuild the dp=N-1 mesh through the `mesh_mismatch`
                    split/re-promote path, and finish with a loss
                    trajectory allclose to an UNINTERRUPTED run on the
                    shrunk mesh. Then a restarted worker rejoins at the
                    current generation and re-promotes with ZERO fresh
                    compiles — every executable deserializes from the
                    shared AOT store (`fleet.rejoin`, aot.hit).

  fleet_flap        a slow-but-alive worker suppresses heartbeats for
                    most of — but less than — its lease while the fleet
                    trains on. Must hold: ZERO rebuilds, the generation
                    never moves, and both workers finish with finite,
                    identical trajectories. Lease grace absorbs slow;
                    only silence past the lease is loss.

Every decision flows through the PR 4 fusion flight recorder, so each
scenario's report embeds the doctor's verdict.

    JAX_PLATFORMS=cpu python tools/chaos.py                # all scenarios
    JAX_PLATFORMS=cpu python tools/chaos.py --scenario serve_hang --json
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable from a source checkout without an install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


# ---------------------------------------------------------------------------
# in-process scenarios
# ---------------------------------------------------------------------------

def _amp_loop_state(seed=0):
    import numpy as np
    import paddle_tpu as paddle

    rng = np.random.default_rng(seed)
    x = paddle.to_tensor(rng.standard_normal((4, 16)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((16, 16)).astype(np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(16).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w, b])
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                   decr_every_n_nan_or_inf=1)
    return x, w, b, opt, scaler


def _amp_step(x, w, b, opt, scaler):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    loss = F.gelu(paddle.add(paddle.matmul(x, w), b)).sum()
    scaler.scale(loss).backward()
    scaler.step(opt)
    scaler.update()
    opt.clear_grad()


def _arm(min_count=5):
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops.dispatch import clear_dispatch_cache
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler.events import clear_fusion_events
    set_flags({"FLAGS_check_numerics": True,
               "FLAGS_eager_chain_fusion": True,
               "FLAGS_eager_step_fusion": True,
               "FLAGS_eager_chain_fusion_min_count": 3,
               "FLAGS_eager_step_fusion_min_count": min_count,
               "FLAGS_profiler_events": True})
    clear_dispatch_cache()
    clear_fusion_events()
    guardian.reset_guardian_stats()
    guardian.clear_faults()


def scenario_nan():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler import step_fusion_stats
    from paddle_tpu.profiler.explain import explain

    _arm()
    x, w, b, opt, scaler = _amp_loop_state()
    for _ in range(10):
        _amp_step(x, w, b, opt, scaler)
    s0 = step_fusion_stats()
    w_before = np.asarray(w._value).copy()
    scale_before = scaler.get_init_loss_scaling()

    xbad = paddle.to_tensor(np.full((4, 16), np.nan, np.float32))
    _amp_step(xbad, w, b, opt, scaler)
    guardian.flush()

    s1 = step_fusion_stats()
    stats = guardian.guardian_stats()
    rep = explain()
    failures = []
    if s0["fused_steps"] == 0:
        failures.append("AMP loop never promoted to a fused step")
    if s1["fused_steps"] <= s0["fused_steps"]:
        failures.append("poisoned batch did not run through the fused step")
    if s1["fallback_splits"] != s0["fallback_splits"]:
        failures.append("poisoned batch split the fused replay")
    if not np.array_equal(w_before, np.asarray(w._value)):
        failures.append("parameters changed on a non-finite batch")
    scale_after = scaler.get_init_loss_scaling()
    if scale_after != scale_before / 2:
        failures.append(
            f"loss scale {scale_before} -> {scale_after}, expected halving")
    if stats["steps_skipped"] < 1 or stats["scaler_backoffs"] < 1:
        failures.append(f"guardian stats missed the skip: {stats}")
    if rep["guardian"].get("nonfinite_skip", {}).get("count", 0) < 1:
        failures.append("doctor did not attribute nonfinite_skip")
    # recovery: a clean batch updates again without a retrace
    _amp_step(x, w, b, opt, scaler)
    s2 = step_fusion_stats()
    if np.array_equal(w_before, np.asarray(w._value)):
        failures.append("parameters did not update after recovery")
    if s2["retraces"] != s1["retraces"]:
        failures.append("recovery retraced the fused step")
    return {"ok": not failures, "failures": failures,
            "scale": [scale_before, scale_after],
            "guardian": stats, "doctor": rep["headline"]}


def scenario_exception():
    import numpy as np
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler.explain import explain

    _arm()
    # stay on per-op dispatch: fault hooks fire on REAL dispatches only —
    # chain/step replays defer their ops, so chaos against fused paths
    # poisons batch inputs instead (the nan scenario)
    from paddle_tpu.framework.flags import set_flags
    set_flags({"FLAGS_eager_chain_fusion": False,
               "FLAGS_eager_step_fusion": False})
    x, w, b, opt, scaler = _amp_loop_state(seed=1)
    for _ in range(4):
        _amp_step(x, w, b, opt, scaler)
    w_before = np.asarray(w._value).copy()

    inj = guardian.inject_fault("raise", op="gelu")
    caught = 0
    try:
        _amp_step(x, w, b, opt, scaler)
    except guardian.ChaosFault:
        caught = 1
        opt.clear_grad()
    finally:
        inj.remove()
    failures = []
    if not caught:
        failures.append("injected mid-step exception did not surface")
    if not np.array_equal(w_before, np.asarray(w._value)):
        failures.append("interrupted step modified parameters")
    # recovery: the loop keeps training afterwards
    for _ in range(3):
        _amp_step(x, w, b, opt, scaler)
    guardian.flush()
    stats = guardian.guardian_stats()
    rep = explain()
    if np.array_equal(w_before, np.asarray(w._value)):
        failures.append("loop did not recover after the exception")
    if not np.all(np.isfinite(np.asarray(w._value))):
        failures.append("parameters went non-finite after recovery")
    if stats["faults_injected"] != 1:
        failures.append(f"expected 1 injected fault, saw {stats}")
    if rep["guardian"].get("injected_fault", {}).get("count", 0) != 1:
        failures.append("doctor did not attribute injected_fault")
    return {"ok": not failures, "failures": failures,
            "guardian": stats, "doctor": rep["headline"]}


# ---------------------------------------------------------------------------
# serving scenarios (PR 7)
# ---------------------------------------------------------------------------

def _arm_serve():
    """Serving-scenario arming: flight recorder on, injectors/stats
    clean — and the numerics guardian OFF (a prior training scenario may
    have left it on; its lazy check queue must not interleave with the
    serving engine's jit-traced model calls)."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler.events import clear_fusion_events
    set_flags({"FLAGS_check_numerics": False,
               "FLAGS_profiler_events": True})
    guardian.flush()
    guardian.reset_thread_state()
    guardian.reset_guardian_stats()
    guardian.clear_faults()
    clear_fusion_events()


def _serve_setup():
    """Deterministic tiny GPT + engine workload shared by the serving
    scenarios (and bit-reproducible across processes: weights come from
    the framework RNG after paddle.seed)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 128, int(n)).tolist() for n in (9, 6, 12)]
    return model, prompts


def _serve_refs(model, prompts, n):
    import numpy as np
    return [np.asarray(model.generate(np.asarray([p], np.int64),
                                      max_new_tokens=n,
                                      do_sample=False)._value)[0].tolist()
            for p in prompts]


def scenario_serve_hang():
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler.events import clear_fusion_events
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.serving import LLMEngine, FAILED, FINISHED

    _arm_serve()
    set_flags({"FLAGS_serve_step_timeout_ms": 2000})
    model, prompts = _serve_setup()
    refs = _serve_refs(model, prompts, 8)
    failures = []
    try:
        # -- the first rung: one hang -> retry, same executable -------------
        clear_fusion_events()
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            engine.step()
        guardian.inject_fault("hang", op="serve.decode", times=1)
        engine.run()
        guardian.clear_faults()
        st = engine.stats()
        if st["hangs"] < 1:
            failures.append("watchdog never fired on the injected hang")
        if st["decode_compiles"] != 1:
            failures.append(
                f"the retry recompiled decode "
                f"{st['decode_compiles']}x, expected exactly 1")
        for r, ref in zip(reqs, refs):
            if r.state != FINISHED or r.generated != ref:
                failures.append(
                    f"stream {r.rid} not token-identical after hang "
                    f"recovery (state {r.state})")
        rep = explain()
        if rep["serving"]["hangs"] < 1 \
                or "step_hang" not in rep["serving"]["reasons"]:
            failures.append("doctor did not attribute step_hang")
        if rep["verdict"] != "serving_degraded":
            failures.append(
                f"doctor verdict {rep['verdict']!r}, expected "
                "serving_degraded")

        # -- the last rung: two hangs in a row -> the active requests fail,
        # the request that waited for a slot is served ---------------------
        engine2 = LLMEngine(model, max_batch_size=2, block_size=4)
        reqs2 = [engine2.add_request(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            engine2.step()
        guardian.inject_fault("hang", op="serve.decode", times=2)
        engine2.run()
        guardian.clear_faults()
        st2 = engine2.stats()
        if st2["hangs"] != 2:
            failures.append(f"expected 2 hangs at the last rung, saw "
                            f"{st2['hangs']}")
        if st2["decode_compiles"] != 2:
            failures.append(
                f"fail-active should trace decode exactly once more "
                f"(saw {st2['decode_compiles']} compiles)")
        for r in reqs2[:2]:
            if r.state != FAILED or r.error != "step_hang":
                failures.append(
                    f"active stream {r.rid} not failed as step_hang "
                    f"(state {r.state}, error {r.error})")
        if reqs2[2].state != FINISHED or reqs2[2].generated != refs[2]:
            failures.append(
                f"queued stream {reqs2[2].rid} not served "
                f"token-identically after fail-active")
        if engine2.degraded:
            failures.append("engine still degraded after serving again")
        return {"ok": not failures, "failures": failures,
                "hangs": [st["hangs"], st2["hangs"]],
                "doctor": rep["headline"]}
    finally:
        guardian.clear_faults()
        set_flags({"FLAGS_serve_step_timeout_ms": 0})


def scenario_serve_fused_fault():
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler.events import clear_fusion_events
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.serving import LLMEngine, FINISHED

    _arm_serve()
    model, prompts = _serve_setup()
    refs = _serve_refs(model, prompts, 8)
    failures = []
    clear_fusion_events()
    engine = LLMEngine(model, max_batch_size=2, block_size=4)
    reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
    for _ in range(3):
        engine.step()
    guardian.inject_fault("nan_output", op="serve.decode", times=1)
    engine.run()
    guardian.clear_faults()
    st = engine.stats()
    if st["eager_fallbacks"] < 1:
        failures.append("poisoned decode did not trigger the eager "
                        "fallback")
    if st["decode_compiles"] != 1:
        failures.append(
            f"transient poison must not rebuild decode (saw "
            f"{st['decode_compiles']} compiles)")
    for r, ref in zip(reqs, refs):
        if r.state != FINISHED or r.generated != ref:
            failures.append(
                f"stream {r.rid} fallback not token-identical "
                f"(state {r.state})")
    rep = explain()
    if "decode_fault" not in rep["serving"]["reasons"]:
        failures.append("doctor did not attribute decode_fault")
    # the engine must still serve NEW work on the compiled path
    again = engine.add_request(prompts[0], max_new_tokens=8)
    engine.run()
    if again.state != FINISHED or again.generated != refs[0]:
        failures.append("engine did not serve new requests after the "
                        "fallback")
    if engine.stats()["decode_compiles"] != 1:
        failures.append("post-fault serving retraced the decode program")
    return {"ok": not failures, "failures": failures,
            "guardian": guardian.guardian_stats(),
            "doctor": rep["headline"]}


def scenario_telemetry():
    """PR 13: the live observability plane under an injected wedge. A
    serving engine runs with the telemetry server armed while a chaos
    "stall" fault (guardian.inject_fault — the wall-clock hang variant)
    wedges two consecutive decode steps for the full watchdog budget.
    Must hold: a scraper polling /healthz at ~100 Hz observes the flip
    to unhealthy (503) within one watchdog window of the hang, /readyz
    reads 503 while the degraded latch is set, BOTH recover after the
    first clean decode step, every stream still finishes
    token-identically, and /goodput names the stalled step indices."""
    import threading
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler import telemetry_server
    from paddle_tpu.profiler.metrics import reset_metrics
    from paddle_tpu.serving import LLMEngine, FINISHED

    _arm_serve()
    budget_ms = 150
    set_flags({"FLAGS_serve_step_timeout_ms": budget_ms,
               "FLAGS_metrics": True})
    reset_metrics()
    model, prompts = _serve_setup()
    refs = _serve_refs(model, prompts, 8)
    failures = []
    srv = telemetry_server.start(port=0)
    samples = []                    # (t, endpoint, status, body)
    stop = threading.Event()

    def probe(ep):
        return telemetry_server.probe_endpoint(f"{srv.url}/{ep}",
                                               timeout=5)

    def scraper():
        while not stop.is_set():
            for ep in ("healthz", "readyz"):
                try:
                    st, body = probe(ep)
                    samples.append((time.perf_counter(), ep, st, body))
                except Exception:
                    pass
            time.sleep(0.01)        # ~100 Hz across both endpoints

    try:
        # one step wedged at both of its commits, then recovery: the
        # wait for the boundary's two prefills (two budgets, where
        # liveness allows one) and the wait for the launch behind them
        # each hang ONCE and are retried: what the ladder survives
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.generate(prompts, max_new_tokens=2)  # warm + heartbeat
        st0, _ = probe("healthz")
        if st0 != 200:
            failures.append("healthz not 200 on a healthy engine")
        thr = threading.Thread(target=scraper, daemon=True)
        thr.start()
        t_hang = time.perf_counter()
        guardian.inject_fault("stall", op="serve.prefill", times=1)
        guardian.inject_fault("stall", op="serve.decode", times=1)
        reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
        engine.run()                # wedges ~3x budget, then recovers
        guardian.clear_faults()
        stop.set()
        thr.join(timeout=10)
        # -- liveness flipped within one watchdog window ----------------
        bad_health = [t for t, ep, st, _ in samples
                      if ep == "healthz" and st == 503]
        if not bad_health:
            failures.append("healthz never flipped unhealthy during the "
                            "injected stall")
        else:
            # scrape cadence (~20ms across endpoints) rides on top of
            # the one-window bound; allow it as slack
            flip_s = min(bad_health) - t_hang
            if flip_s > 2 * budget_ms / 1e3 + 0.25:
                failures.append(
                    f"healthz took {flip_s:.3f}s to flip (watchdog "
                    f"window {budget_ms}ms)")
        if not any(ep == "readyz" and st == 503
                   for _, ep, st, _ in samples):
            failures.append("readyz never reported the degraded latch")
        # -- recovery ---------------------------------------------------
        st_h, body_h = probe("healthz")
        st_r, body_r = probe("readyz")
        if st_h != 200:
            failures.append(f"healthz did not recover (still {st_h}: "
                            f"{body_h})")
        if st_r != 200:
            failures.append(f"readyz did not recover (still {st_r})")
        for r, ref in zip(reqs, refs):
            if r.state != FINISHED or r.generated != ref:
                failures.append(
                    f"stream {r.rid} not token-identical through the "
                    f"stall (state {r.state})")
        _, good = probe("goodput")
        stalled = (good.get("step_indices") or {}).get("stalled") or []
        if len(stalled) < 1:
            failures.append("goodput did not attribute the stalled step "
                            "indices")
        hangs = engine.stats()["hangs"]
        if hangs < 2:
            failures.append(f"expected 2 watchdog firings, saw {hangs}")
        return {"ok": not failures, "failures": failures,
                "hangs": hangs, "scrapes": len(samples),
                "unhealthy_scrapes": len(bad_health),
                "stalled_steps": stalled}
    finally:
        stop.set()
        guardian.clear_faults()
        telemetry_server.stop()
        set_flags({"FLAGS_serve_step_timeout_ms": 0,
                   "FLAGS_metrics": False})


def scenario_sentinel():
    """PR 19: the perf regression sentinel under an injected drift. A
    serving engine runs with the telemetry server up and the sentinel
    armed on short self-calibrated windows; after >=1 clean window, a
    chaos "stall" fault wedges two consecutive decode steps (a split/
    hang storm the baseline histogram has never seen). Must hold: the
    sentinel flips its degraded latch within one evaluation window of
    the storm with a machine-readable verdict (split_regression family,
    {reason, metric, observed, bound}), /readyz reads 503 with that
    finding attached under "sentinel", /sentinel serves the full
    snapshot schema, the latch RECOVERS on the first clean window after
    the fault clears (readyz 200 again), and the streams served through
    the storm finish token-identically."""
    import numpy as np
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler import sentinel as snt
    from paddle_tpu.profiler import telemetry_server
    from paddle_tpu.profiler.metrics import reset_metrics
    from paddle_tpu.serving import LLMEngine, FINISHED

    _arm_serve()
    budget_ms = 120
    window_s = 0.4
    set_flags({"FLAGS_serve_step_timeout_ms": budget_ms,
               "FLAGS_metrics": True})
    reset_metrics()
    snt.disarm()
    snt.SENTINEL.reset()
    model, prompts = _serve_setup()
    refs = _serve_refs(model, prompts, 8)
    failures = []
    srv = telemetry_server.start(port=0)

    def probe(ep):
        return telemetry_server.probe_endpoint(f"{srv.url}/{ep}",
                                               timeout=5)

    def filler(engine, n=3):
        rng = np.random.default_rng(engine.stats()["steps"] + 1)
        for k in (5, 7, 9)[:n]:
            engine.add_request(rng.integers(0, 128, k).tolist(),
                               max_new_tokens=4)
        engine.run()

    try:
        # the storm below wedges two steps, one after the other, and the
        # streams must survive it: each hang is its wait's first, retried
        engine = LLMEngine(model, max_batch_size=4, block_size=4)
        filler(engine)              # decode compiled pre-calibration
        snt.arm(window_s=window_s)
        deadline = time.perf_counter() + 60
        while snt.SENTINEL.windows < 2 and time.perf_counter() < deadline:
            filler(engine)
        if snt.SENTINEL.band_source != "self":
            failures.append("sentinel never self-calibrated on clean "
                            "serve traffic")
        if snt.SENTINEL.degraded:
            failures.append("sentinel degraded on CLEAN traffic before "
                            "any fault was injected")
        st0, body0 = probe("readyz")
        if st0 != 200 or not body0.get("sentinel", {}).get("armed"):
            failures.append(f"readyz pre-fault not 200/armed (st={st0})")

        # -- the storm: two wedged decode steps mid-stream --------------
        t_inject = time.perf_counter()
        guardian.inject_fault("stall", op="serve.decode", times=1)
        # the retried wait passes this one by; the next step's trips it
        guardian.inject_fault("stall", op="serve.decode", after=1, times=1)
        reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
        engine.run()                # wedges ~2x budget, then recovers
        guardian.clear_faults()
        t_evidence = time.perf_counter()   # storm is now in the counters
        while not snt.SENTINEL.degraded \
                and time.perf_counter() < deadline:
            filler(engine, n=1)     # drive the window edge
        trip_s = time.perf_counter() - t_inject
        detect_s = time.perf_counter() - t_evidence
        if not snt.SENTINEL.degraded:
            failures.append("the stall storm never tripped the sentinel")
        elif detect_s > window_s + 5.0:
            # detection latency, not total trip time: engine.run() under a
            # wedged budget stretches with host load, the window edge must
            # not (one window + filler-round slop).
            failures.append(f"sentinel took {detect_s:.2f}s after the "
                            f"storm landed to trip (window {window_s}s)")
        finding = dict(snt.SENTINEL.finding or {})
        if finding.get("reason") not in ("split_regression",
                                         "compile_storm", "perf_drift",
                                         "latency_drift"):
            failures.append(f"verdict {finding.get('reason')!r} is not "
                            "a REASON_CODES drift verdict")
        if not {"metric", "observed", "bound",
                "message"} <= set(finding):
            failures.append(f"finding not machine-readable: {finding}")
        st_r, body_r = probe("readyz")
        if st_r != 503:
            failures.append(f"readyz not 503 while degraded (st={st_r})")
        rz_finding = (body_r.get("sentinel") or {}).get("finding") or {}
        if rz_finding.get("reason") != finding.get("reason"):
            failures.append("readyz did not attach the sentinel finding")
        st_s, body_s = probe("sentinel")
        if st_s != 200 or not {"armed", "degraded", "finding", "windows",
                               "checks", "history",
                               "last_record"} <= set(body_s):
            failures.append("/sentinel snapshot schema incomplete")

        # -- recovery ---------------------------------------------------
        deadline = time.perf_counter() + 60
        while snt.SENTINEL.degraded and time.perf_counter() < deadline:
            filler(engine, n=1)
        if snt.SENTINEL.degraded:
            failures.append("sentinel never recovered after the fault "
                            "cleared")
        st_h, _ = probe("readyz")
        if st_h != 200:
            failures.append(f"readyz did not recover (still {st_h})")
        recovered = any(h.get("verdict") == "clean"
                        for h in snt.SENTINEL.history)
        if not recovered:
            failures.append("no clean window recorded after recovery")
        for r, ref in zip(reqs, refs):
            if r.state != FINISHED or r.generated != ref:
                failures.append(
                    f"stream {r.rid} not token-identical through the "
                    f"storm (state {r.state})")
        return {"ok": not failures, "failures": failures,
                "trip_s": round(trip_s, 3),
                "detect_s": round(detect_s, 3),
                "verdict": finding.get("reason"),
                "finding": finding,
                "windows": snt.SENTINEL.windows,
                "checks": dict(snt.SENTINEL.checks)}
    finally:
        guardian.clear_faults()
        snt.disarm()
        snt.SENTINEL.reset()
        telemetry_server.stop()
        set_flags({"FLAGS_serve_step_timeout_ms": 0,
                   "FLAGS_metrics": False})


def serve_child_main(args):
    """One resumable serving run (invoked as `chaos.py --serve-child`):
    deterministic engine + workload, ServeCheckpointer ticking every
    step, optional SIGKILL at a chosen engine step. Writes {rid: tokens}
    JSON on completion."""
    from paddle_tpu.incubate.checkpoint import ServeCheckpointer
    from paddle_tpu.serving import LLMEngine

    model, prompts = _serve_setup()
    engine = LLMEngine(model, max_batch_size=2, block_size=4)
    ck = ServeCheckpointer(args.ckpt_dir, save_every_n_steps=1,
                           max_checkpoints=3)
    restored = engine.restore_state(ck.restore())
    if not restored:
        for i, p in enumerate(prompts):
            kw = {}
            if i % 2:
                # every other stream samples: (seed, prompt, sampler) must
                # reproduce byte-identically across the kill-9 resume —
                # the serialized sampler identity + fold_in(seed, position)
                # keys make the replayed stream a replay, not a re-roll
                kw = dict(temperature=0.9, top_k=20, top_p=0.9,
                          seed=4242 + i)
            engine.add_request(p, max_new_tokens=10, request_id=f"s{i}",
                               **kw)
    n = 0
    while True:
        if args.kill_at is not None and n == int(args.kill_at):
            os.kill(os.getpid(), signal.SIGKILL)
        alive = engine.step()
        n += 1
        ck.tick(n, engine.state_payload())
        if not alive:
            break
    out = {r.rid: list(r.generated)
           for r in engine.requests.values()}
    out["__resumed__"] = len(restored)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


def _spawn_serve_child(ckpt_dir, out, kill_at=None, timeout=300):
    cmd = [sys.executable, os.path.abspath(__file__), "--serve-child",
           "--ckpt-dir", ckpt_dir, "--out", out]
    if kill_at is not None:
        cmd += ["--kill-at", str(kill_at)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)


def scenario_serve_kill():
    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        ck_a = os.path.join(tmp, "interrupted")
        ck_b = os.path.join(tmp, "clean")
        out_resumed = os.path.join(tmp, "resumed.json")
        out_clean = os.path.join(tmp, "clean.json")

        # run 1: killed after 4 engine steps (streams mid-flight)
        r1 = _spawn_serve_child(ck_a, out_resumed, kill_at=4)
        if r1.returncode != -signal.SIGKILL:
            failures.append(
                f"expected SIGKILL death, rc={r1.returncode} "
                f"stderr={r1.stderr[-500:]}")
        if os.path.exists(out_resumed):
            failures.append("killed serve run still wrote final output")

        # run 2: same ckpt dir — must restore and finish every stream
        r2 = _spawn_serve_child(ck_a, out_resumed)
        if r2.returncode != 0:
            failures.append(f"resumed serve run failed: "
                            f"{r2.stderr[-800:]}")

        # reference: uninterrupted run
        r3 = _spawn_serve_child(ck_b, out_clean)
        if r3.returncode != 0:
            failures.append(f"reference serve run failed: "
                            f"{r3.stderr[-800:]}")

        if not failures:
            with open(out_resumed) as f:
                res = json.load(f)
            with open(out_clean) as f:
                ref = json.load(f)
            if res.pop("__resumed__") < 1:
                failures.append("restarted engine restored no requests")
            ref.pop("__resumed__")
            if set(res) != set(ref):
                failures.append(
                    f"stream sets differ: {sorted(res)} vs {sorted(ref)}")
            for rid in sorted(set(res) & set(ref)):
                if res[rid] != ref[rid]:
                    failures.append(
                        f"stream {rid} not byte-identical after kill-9 "
                        "resume")
    return {"ok": not failures, "failures": failures}


def tenant_child_main(args):
    """One resumable HOT-SWAP serving run (invoked as `chaos.py
    --tenant-child`): a hot_swap engine in lockstep (batch >= streams),
    ServeCheckpointer ticking every step, and a live weight swap staged
    once every stream has >= 3 tokens — a TOKEN-space boundary, so the
    cutover lands at the same token index in every run regardless of how
    resume re-prefills re-shuffle the step count. `--kill-mode staged`
    SIGKILLs between stage and commit (the pending set must die with the
    process); `--kill-mode committed` SIGKILLs after the cutover has
    been checkpointed (the restart must refuse to resume under the OLD
    weights: torn_swap). Writes {rid: tokens} JSON on completion plus
    `__torn_refusals__` — how many restores the torn-swap guard bounced
    before the child loaded the matching weight set."""
    import numpy as np
    from paddle_tpu.incubate.checkpoint import ServeCheckpointer
    from paddle_tpu.serving import LLMEngine, ServeRefusal

    SWAP_TOKENS = 3
    model, prompts = _serve_setup()
    # the incoming weight set, derived from the SEEDED construction
    # weights before anything mutates them: bit-reproducible in every
    # child process, killed or clean
    w2 = [np.asarray(p._value) * np.float32(1.0001)
          for p in model.parameters()]
    engine = LLMEngine(model, max_batch_size=4, block_size=4,
                       hot_swap=True)
    ck = ServeCheckpointer(args.ckpt_dir, save_every_n_steps=1,
                           max_checkpoints=3)
    torn = 0
    payload = ck.restore()
    try:
        restored = engine.restore_state(payload)
    except ServeRefusal as e:
        if e.reason != "torn_swap":
            raise
        # the snapshot was taken under the NEW weights: load them first
        # (the supervisor pattern), then resume — never decode a single
        # token against the torn set
        torn = 1
        engine.swap_weights(w2)
        restored = engine.restore_state(payload)
    if not restored:
        for i, p in enumerate(prompts):
            engine.add_request(p, max_new_tokens=10, request_id=f"s{i}")
    n = 0
    while True:
        live = [r for r in engine.requests.values() if not r.finished]
        if engine.weight_epoch == 0 and live \
                and all(len(r.generated) >= SWAP_TOKENS for r in live):
            if args.kill_mode == "staged":
                # mid-hot-swap: staged, never committed — the pending
                # weights must die with the process
                engine.stage_weights(w2)
                os.kill(os.getpid(), signal.SIGKILL)
            engine.swap_weights(w2)
            if args.kill_mode == "committed":
                # cutover done; checkpoint it, then die before serving
                # another step under the new epoch
                ck.tick(n + 1000, engine.state_payload())
                os.kill(os.getpid(), signal.SIGKILL)
        alive = engine.step()
        n += 1
        ck.tick(n, engine.state_payload())
        if not alive:
            break
    out = {r.rid: list(r.generated) for r in engine.requests.values()}
    out["__resumed__"] = len(restored)
    out["__torn_refusals__"] = torn
    out["__epoch__"] = engine.weight_epoch
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


def _spawn_tenant_child(ckpt_dir, out, kill_mode=None, timeout=300):
    cmd = [sys.executable, os.path.abspath(__file__), "--tenant-child",
           "--ckpt-dir", ckpt_dir, "--out", out]
    if kill_mode:
        cmd += ["--kill-mode", kill_mode]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)


def scenario_tenant_swap():
    """PR 17: SIGKILL around a live weight hot-swap. Three runs share
    the deterministic child: clean (the reference), killed between
    stage and commit (the staged set must vanish with the process), and
    killed after the committed cutover was checkpointed (the restart
    must be REFUSED under the old weights — torn_swap — then finish
    byte-identically once the matching set is loaded)."""
    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        out_clean = os.path.join(tmp, "clean.json")
        r0 = _spawn_tenant_child(os.path.join(tmp, "ck_clean"), out_clean)
        if r0.returncode != 0:
            failures.append(f"clean tenant run failed: {r0.stderr[-800:]}")
            return {"ok": False, "failures": failures}
        with open(out_clean) as f:
            ref = json.load(f)
        if ref["__epoch__"] != 1:
            failures.append(
                f"clean run served epoch {ref['__epoch__']}, expected 1")

        for mode, want_torn in (("staged", 0), ("committed", 1)):
            ck = os.path.join(tmp, f"ck_{mode}")
            out = os.path.join(tmp, f"{mode}.json")
            r1 = _spawn_tenant_child(ck, out, kill_mode=mode)
            if r1.returncode != -signal.SIGKILL:
                failures.append(
                    f"[{mode}] expected SIGKILL death, rc={r1.returncode} "
                    f"stderr={r1.stderr[-500:]}")
                continue
            if os.path.exists(out):
                failures.append(f"[{mode}] killed run wrote final output")
            r2 = _spawn_tenant_child(ck, out)
            if r2.returncode != 0:
                failures.append(
                    f"[{mode}] restarted run failed: {r2.stderr[-800:]}")
                continue
            with open(out) as f:
                res = json.load(f)
            if res["__resumed__"] < 1:
                failures.append(f"[{mode}] restart restored no requests")
            if res["__torn_refusals__"] != want_torn:
                failures.append(
                    f"[{mode}] torn-swap refusals: "
                    f"{res['__torn_refusals__']}, expected {want_torn}")
            if res["__epoch__"] < 1:
                failures.append(
                    f"[{mode}] restart finished on epoch "
                    f"{res['__epoch__']} — streams decoded against the "
                    "old weights")
            for rid in sorted(k for k in ref if not k.startswith("__")):
                if res.get(rid) != ref[rid]:
                    failures.append(
                        f"[{mode}] stream {rid} not byte-identical "
                        "through the kill/restart cutover")
    return {"ok": not failures, "failures": failures}


# ---------------------------------------------------------------------------
# warm-restart scenario (PR 9): AOT store + StepCheckpointer child
# ---------------------------------------------------------------------------

def aot_child_main(args):
    """One AOT-warm-startable training run (invoked as `chaos.py
    --aot-child`): deterministic per-step batches, SGD, the persistent
    executable store armed, StepCheckpointer ticking every step so a
    restart resumes STATE from the checkpoint and COMPILATION from the
    store. Writes a JSON report: per-step losses, the first loop
    iteration (relative to this process) that fired a fused step, and the
    compile/AOT event counts the parent asserts on."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.incubate.checkpoint import StepCheckpointer
    from paddle_tpu.profiler import (dispatch_cache_stats,
                                     chain_fusion_stats,
                                     step_fusion_stats, aot_cache_stats)
    from paddle_tpu.profiler.events import EVENTS

    set_flags({"FLAGS_aot_cache": True,
               "FLAGS_aot_cache_dir": args.aot_dir,
               "FLAGS_eager_chain_fusion_min_count": 3,
               "FLAGS_eager_step_fusion_min_count": 5,
               "FLAGS_profiler_events": True})
    paddle.seed(7)
    rng = np.random.default_rng(11)
    w = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32),
                         stop_gradient=False)
    bias = paddle.to_tensor(rng.standard_normal(8).astype(np.float32),
                            stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w, bias])
    model = {"w": w, "b": bias}
    ck = StepCheckpointer(args.ckpt_dir, save_every_n_steps=1,
                          max_checkpoints=3)
    resumed = ck.restore(model=model, optimizer=opt)
    start = resumed + 1
    kill_at = None if args.kill_at is None else int(args.kill_at)
    losses = {}
    first_fired_rel = None
    # lead with clear_grad so the FIRST cycle already has the steady-state
    # signature (clear_grad otherwise rides the next cycle): the restarted
    # worker's very first boundary then matches the stored step artifact
    opt.clear_grad()
    for rel, step in enumerate(range(start, int(args.steps))):
        if kill_at is not None and step == kill_at:
            os.kill(os.getpid(), signal.SIGKILL)
        srng = np.random.default_rng(1000 + step)
        x = paddle.to_tensor(
            srng.standard_normal((4, 8)).astype(np.float32))
        loss = F.gelu(paddle.add(paddle.matmul(x, w), bias)).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        if first_fired_rel is None \
                and step_fusion_stats()["fused_steps"] > 0:
            first_fired_rel = rel
        losses[str(step)] = float(loss)
        ck.tick(step, model=model, optimizer=opt)
    ev = EVENTS.snapshot()

    def n(cat):
        return sum(1 for e in ev if e["cat"] == cat)

    report = {
        "resumed_step": resumed,
        "losses": losses,
        "first_fired_rel": first_fired_rel,
        "params": {"w": np.asarray(w._value).tolist(),
                   "b": np.asarray(bias._value).tolist()},
        "dispatch_retraces": dispatch_cache_stats()["retraces"],
        "chain_retraces": chain_fusion_stats()["retraces"],
        "step_retraces": step_fusion_stats()["retraces"],
        "steps_promoted": step_fusion_stats()["steps_promoted"],
        "fused_steps": step_fusion_stats()["fused_steps"],
        "aot": aot_cache_stats(),
        "events": {"dispatch_retrace": n("dispatch.retrace"),
                   "chain_compile": n("chain.compile"),
                   "step_promote": n("step.promote"),
                   "step_fire": n("step.fire"),
                   "aot_hit": n("aot.hit"),
                   "aot_store": n("aot.store"),
                   "aot_corrupt": n("aot.corrupt")},
    }
    with open(args.out, "w") as f:
        json.dump(report, f)
    return 0


def _spawn_aot_child(aot_dir, ckpt_dir, out, steps, kill_at=None,
                     timeout=300):
    cmd = [sys.executable, os.path.abspath(__file__), "--aot-child",
           "--aot-dir", aot_dir, "--ckpt-dir", ckpt_dir, "--out", out,
           "--steps", str(steps)]
    if kill_at is not None:
        cmd += ["--kill-at", str(kill_at)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)


def scenario_warm_restart(steps=14, kill_at=9):
    import numpy as np

    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        store = os.path.join(tmp, "aot")
        cold_store = os.path.join(tmp, "aot_cold")
        out_warm = os.path.join(tmp, "warm.json")
        out_ref = os.path.join(tmp, "ref.json")
        out_cor = os.path.join(tmp, "corrupt.json")

        # run 1: populate the store (fused step promotes at min_count 5,
        # the artifact lands on the first fire), then die by SIGKILL
        # mid-run — after promotion, before completion
        r1 = _spawn_aot_child(store, os.path.join(tmp, "ck_a"), out_warm,
                              steps, kill_at=kill_at)
        if r1.returncode != -signal.SIGKILL:
            failures.append(f"expected SIGKILL death, rc={r1.returncode} "
                            f"stderr={r1.stderr[-500:]}")

        # run 2: the warm restart — same store, same checkpoint dir
        r2 = _spawn_aot_child(store, os.path.join(tmp, "ck_a"), out_warm,
                              steps)
        if r2.returncode != 0:
            failures.append(f"warm restart failed: {r2.stderr[-800:]}")

        # reference: uninterrupted run, cold store, fresh checkpoints
        r3 = _spawn_aot_child(cold_store, os.path.join(tmp, "ck_b"),
                              out_ref, steps)
        if r3.returncode != 0:
            failures.append(f"reference run failed: {r3.stderr[-800:]}")

        warm = ref = None
        if not failures:
            with open(out_warm) as f:
                warm = json.load(f)
            with open(out_ref) as f:
                ref = json.load(f)
            if warm["resumed_step"] < 0:
                failures.append("restart did not resume from the "
                                "checkpoint")
            # THE acceptance: zero fresh compiles in the restarted
            # process — every executable deserialized from the store
            for k in ("dispatch_retraces", "chain_retraces",
                      "step_retraces"):
                if warm[k] != 0:
                    failures.append(
                        f"warm restart paid {warm[k]} {k}: the store did "
                        "not eliminate the warmup")
            if warm["events"]["dispatch_retrace"] \
                    or warm["events"]["chain_compile"]:
                failures.append(
                    f"warm restart emitted compile events: "
                    f"{warm['events']}")
            if warm["events"]["aot_hit"] < 3:
                failures.append(
                    f"warm restart loaded only "
                    f"{warm['events']['aot_hit']} artifacts")
            if warm["steps_promoted"] < 1:
                failures.append("warm restart never promoted")
            # promote at the FIRST boundary, fire on the next cycle
            if warm["first_fired_rel"] is None \
                    or warm["first_fired_rel"] > 1:
                failures.append(
                    f"first fused fire at relative cycle "
                    f"{warm['first_fired_rel']} (expected <= 1: promote "
                    "at the first boundary, fire on the next)")
            # loss trajectory: killed-run prefix is gone, but the warm
            # restart's steps must match the uninterrupted reference at
            # the same global indices (the fused ONE-program layout
            # differs from per-op dispatch in the last ULP)
            for k, v in warm["losses"].items():
                if abs(v - ref["losses"][k]) > 1e-4:
                    failures.append(
                        f"loss diverged at step {k}: {v} vs "
                        f"{ref['losses'][k]}")
                    break
            for k in ("w", "b"):
                a = np.asarray(warm["params"][k])
                c = np.asarray(ref["params"][k])
                if not np.allclose(a, c, rtol=0, atol=1e-5):
                    failures.append(
                        f"param {k} diverged after warm restart "
                        f"(max |Δ|={np.max(np.abs(a - c)):.3e})")

        # corruption leg: flip a byte mid-payload in EVERY artifact — a
        # fresh worker must quarantine + recompile, never crash
        import glob as _glob
        for p in _glob.glob(os.path.join(store, "*.aot")):
            with open(p, "rb") as f:
                data = bytearray(f.read())
            data[len(data) // 2] ^= 0xFF
            with open(p, "wb") as f:
                f.write(data)
        r4 = _spawn_aot_child(store, os.path.join(tmp, "ck_c"), out_cor,
                              steps)
        if r4.returncode != 0:
            failures.append(
                f"corrupted store crashed the worker: {r4.stderr[-800:]}")
        elif not failures:
            with open(out_cor) as f:
                cor = json.load(f)
            if cor["events"]["aot_corrupt"] < 1:
                failures.append("corrupted artifacts were not attributed "
                                "artifact_corrupt")
            if cor["steps_promoted"] < 1:
                failures.append("worker did not re-promote after "
                                "recompiling corrupt artifacts")
            for k, v in cor["losses"].items():
                if abs(v - ref["losses"][k]) > 1e-4:
                    failures.append(
                        f"corruption-leg loss diverged at step {k}")
                    break
            if not _glob.glob(os.path.join(store, "*.corrupt")):
                failures.append("corrupt artifacts were not quarantined")
    return {"ok": not failures, "failures": failures}


# ---------------------------------------------------------------------------
# elastic-fleet scenarios (PR 20): coordinator in the parent, one child
# process per fleet host, dp=world data-parallel training per child
# ---------------------------------------------------------------------------

def fleet_child_main(args):
    """One elastic-fleet training worker (invoked as `chaos.py
    --fleet-child`): rendezvous through the stdlib-TCP coordinator, then
    a dp=world data-parallel loop over virtual CPU devices with the FULL
    deterministic global batch each step — every replica computes
    identical state, so fleet size changes move placement, not math.
    Gradient accumulation (two microbatches per step) gives `--kill-at`
    a mid-accumulation SIGKILL point. At every step boundary the worker
    polls the fabric; a new generation restores the latest shared
    StepCheckpointer snapshot, rebuilds the mesh for the new world, and
    re-places its batch — the promoted step drops through the
    `mesh_mismatch` split path and re-promotes (AOT warm when the
    topology was seen before). Rank 0 ticks the shared checkpoint.
    Writes a JSON report of losses, rebuild records, compile/AOT
    counters, and fleet event counts."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.incubate.checkpoint import StepCheckpointer
    from paddle_tpu.distributed import fabric
    from paddle_tpu.distributed.mesh import set_global_mesh
    from paddle_tpu.profiler import (dispatch_cache_stats,
                                     chain_fusion_stats,
                                     step_fusion_stats, aot_cache_stats)
    from paddle_tpu.profiler.events import EVENTS

    set_flags({"FLAGS_aot_cache": True,
               "FLAGS_aot_cache_dir": args.aot_dir,
               "FLAGS_eager_chain_fusion_min_count": 3,
               "FLAGS_eager_step_fusion_min_count": 5,
               "FLAGS_profiler_events": True,
               "FLAGS_metrics": True})
    host, _, port = args.coord.rpartition(":")
    prev_gen = int(args.prev_gen or 0)
    member = fabric.Member((host, int(port)), args.host_id,
                           gen_seen=prev_gen)
    rank, spec = member.join(timeout=120.0)
    mesh = fabric.mesh_for_spec(spec)
    set_global_mesh(mesh)
    sharding = NamedSharding(mesh, P("data"))
    # a rejoiner warms the shared store into the page cache before its
    # first boundary — `artifacts` == 0 here would predict a cold
    # compile. Must run AFTER set_global_mesh: the store fingerprint
    # carries the mesh topology token.
    prefetch = fabric.prefetch_artifacts(args.aot_dir) if prev_gen else None

    def place_params(params, mesh):
        # checkpoint restore materializes on the default device; the
        # stored/promoted program expects the parameters replicated on
        # the live mesh (where committed fused updates leave them)
        repl = NamedSharding(mesh, P())
        for p in params:
            p._value = jax.device_put(p._value, repl)

    paddle.seed(7)
    rng = np.random.default_rng(11)
    w = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32),
                         stop_gradient=False)
    bias = paddle.to_tensor(rng.standard_normal(8).astype(np.float32),
                            stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w, bias])
    model = {"w": w, "b": bias}
    ck = StepCheckpointer(args.ckpt_dir, save_every_n_steps=1,
                          max_checkpoints=3)
    resumed = ck.restore(model=model, optimizer=opt)
    if resumed >= 0:
        place_params([w, bias], mesh)
    kill_at = None if args.kill_at is None else int(args.kill_at)
    pause_at = None if args.pause_at is None else int(args.pause_at)
    losses = {}
    rebuilds = []
    step_wall_t = []
    first_fired_rel = None
    rel = 0
    step = resumed + 1
    opt.clear_grad()
    while step < int(args.steps):
        new_spec = member.poll()
        if new_spec is not None:
            # the fleet changed under us: back to the last consistent
            # snapshot, new mesh, re-place — losing a host costs the
            # steps since the last tick, not a warmup
            resumed = ck.restore(model=model, optimizer=opt)
            mesh = fabric.mesh_for_spec(new_spec)
            set_global_mesh(mesh)
            sharding = NamedSharding(mesh, P("data"))
            place_params([w, bias], mesh)
            rebuilds.append({"at_step": step, "resumed": resumed,
                             "generation": new_spec["generation"],
                             "world": new_spec["world"],
                             "rank": member.rank, "t": time.time()})
            opt.clear_grad()
            step = resumed + 1
            continue
        if pause_at is not None and step == pause_at:
            member.pause_heartbeats(float(args.pause_hb))
            time.sleep(float(args.pause_hb))     # slow-but-alive
        if args.step_ms:
            # pace the loop so the fleet is still mid-run when a lease
            # expires (tiny CPU steps would otherwise outrun detection)
            time.sleep(float(args.step_ms) / 1e3)
        mb_losses = []
        for micro in range(2):
            srng = np.random.default_rng(10_000 * (micro + 1) + step)
            xb = srng.standard_normal((6, 8)).astype(np.float32)
            x = paddle.Tensor(jax.device_put(xb, sharding),
                              stop_gradient=True)
            # MEAN-reduced loss: the data-parallel pmean contract
            # (ops/spmd_fusion.py) needs pmean(local batch means) == the
            # global batch mean — a sum-reduced loss would diverge under
            # probation and demote the program to the plain jit lowering
            loss = F.gelu(paddle.add(paddle.matmul(x, w), bias)).mean()
            loss.backward()
            mb_losses.append(loss)
            if kill_at is not None and step == kill_at and micro == 0:
                with open(args.out + ".kill", "w") as f:
                    f.write(repr(time.time()))
                os.kill(os.getpid(), signal.SIGKILL)
        opt.step()
        opt.clear_grad()
        # read the losses only AFTER the boundary: a host sync inside
        # the accumulation cycle would split the whole-step observation
        total = sum(float(l) for l in mb_losses)
        if first_fired_rel is None \
                and step_fusion_stats()["fused_steps"] > 0:
            first_fired_rel = rel
        losses[str(step)] = total
        step_wall_t.append(time.perf_counter())
        if member.rank == 0:
            ck.tick(step, model=model, optimizer=opt)
        rel += 1
        step += 1
    ev = EVENTS.snapshot()
    try:
        # a harness that embeds this child's report can restamp the leg
        # name (the pattern the serve legs use); chaos scenarios ignore it
        from paddle_tpu.profiler.sentinel import capture_record
        sentinel = capture_record("fleet_child")
    except Exception:
        sentinel = None

    def n(cat):
        return sum(1 for e in ev if e["cat"] == cat)

    report = {
        "host": args.host_id,
        "rank": member.rank,
        "generation": member.generation,
        "resumed_step": resumed,
        "losses": losses,
        "rebuilds": rebuilds,
        "step_wall_t": step_wall_t,
        "sentinel_record": sentinel,
        "first_fired_rel": first_fired_rel,
        "prefetch": prefetch,
        "dispatch_retraces": dispatch_cache_stats()["retraces"],
        "chain_retraces": chain_fusion_stats()["retraces"],
        "step_retraces": step_fusion_stats()["retraces"],
        "steps_promoted": step_fusion_stats()["steps_promoted"],
        "fused_steps": step_fusion_stats()["fused_steps"],
        "aot": aot_cache_stats(),
        "events": {"aot_hit": n("aot.hit"),
                   "aot_store": n("aot.store"),
                   "dispatch_retrace": n("dispatch.retrace"),
                   "chain_compile": n("chain.compile"),
                   "fleet_rebuild": n("fleet.rebuild"),
                   "step_split": n("step.split"),
                   "mesh_mismatch": sum(
                       1 for e in ev
                       if e.get("reason") == "mesh_mismatch")},
    }
    member.close()
    with open(args.out, "w") as f:
        json.dump(report, f)
    return 0


def _spawn_fleet_child(coord, host_id, aot_dir, ckpt_dir, out, steps,
                       kill_at=None, prev_gen=None, pause_at=None,
                       pause_hb=None, step_ms=0):
    cmd = [sys.executable, os.path.abspath(__file__), "--fleet-child",
           "--coord", coord, "--host-id", host_id, "--aot-dir", aot_dir,
           "--ckpt-dir", ckpt_dir, "--out", out, "--steps", str(steps),
           "--step-ms", str(step_ms)]
    if kill_at is not None:
        cmd += ["--kill-at", str(kill_at)]
    if prev_gen:
        cmd += ["--prev-gen", str(prev_gen)]
    if pause_at is not None:
        cmd += ["--pause-at", str(pause_at), "--pause-hb", str(pause_hb)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # every fleet process sees the same virtual device pool, so the mesh
    # topology token (and with it the AOT fingerprint) matches across
    # hosts and phases
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4"
                        ).strip()
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)


def _drain_fleet_children(procs, timeout=600):
    done = {}
    for name, p in procs.items():
        try:
            outs, errs = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            outs, errs = p.communicate()
        done[name] = (p.returncode, errs)
    return done


def scenario_fleet_kill(steps=26, kill_at=8, lease_s=1.5):
    import numpy as np
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.profiler.events import EVENTS
    from paddle_tpu.distributed import fabric

    set_flags({"FLAGS_profiler_events": True})
    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        aot = os.path.join(tmp, "aot")
        ck_fleet = os.path.join(tmp, "ck_fleet")
        outs = {h: os.path.join(tmp, f"{h}.json")
                for h in ("w0", "w1", "w2", "r0", "r1",
                          "j0", "j1", "j2")}

        # phase 1: 3 workers rendezvous, w2 is SIGKILLed mid-accumulation
        seq0 = EVENTS.total
        coord = fabric.Coordinator(lease_s=lease_s, expected=3)
        addr = f"{coord.host}:{coord.port}"
        procs = {h: _spawn_fleet_child(
                     addr, h, aot, ck_fleet, outs[h], steps,
                     kill_at=kill_at if h == "w2" else None, step_ms=150)
                 for h in ("w0", "w1", "w2")}
        rcs = _drain_fleet_children(procs)
        gen_after = coord.generation
        ev = [e for e in EVENTS.snapshot() if e["seq"] > seq0]
        coord.close()
        if rcs["w2"][0] != -signal.SIGKILL:
            failures.append(f"w2 expected SIGKILL death, "
                            f"rc={rcs['w2'][0]}")
        for h in ("w0", "w1"):
            if rcs[h][0] != 0:
                failures.append(
                    f"survivor {h} failed: {rcs[h][1][-800:]}")
        lost = [e for e in ev if e["cat"] == "fleet.leave"
                and e.get("reason") == "host_lost"]
        if len(lost) != 1 or lost[0]["op"] != "w2":
            failures.append(f"expected exactly one host_lost for w2, "
                            f"got {[(e['op'],) for e in lost]}")
        if gen_after != 2:
            failures.append(
                f"coordinator at generation {gen_after} after one "
                "rendezvous + one loss (expected 2)")
        t_kill = None
        if os.path.exists(outs["w2"] + ".kill"):
            with open(outs["w2"] + ".kill") as f:
                t_kill = float(f.read())
        else:
            failures.append("w2 never reached its kill point")
        survivors = {}
        for h in ("w0", "w1"):
            if rcs[h][0] == 0 and os.path.exists(outs[h]):
                with open(outs[h]) as f:
                    survivors[h] = json.load(f)
        for h, rep in survivors.items():
            rb = rep["rebuilds"]
            if len(rb) != 1 or rb[0]["generation"] != 2 \
                    or rb[0]["world"] != 2:
                failures.append(
                    f"{h} rebuilds {rb}: expected exactly one, at "
                    "generation 2 / world 2")
                continue
            if rb[0]["resumed"] < 0:
                failures.append(f"{h} did not resume from the shared "
                                "checkpoint on rebuild")
            # the lose-a-host-in-SECONDS budget: lease expiry + reaper
            # tick + heartbeat propagation + one step boundary
            if t_kill is not None and rb[0]["t"] - t_kill > lease_s * 3:
                failures.append(
                    f"{h} adopted the rebuild {rb[0]['t'] - t_kill:.2f}s "
                    f"after the kill (budget {lease_s * 3:.1f}s)")
            # the promoted ONE-program step must notice the new mesh
            # (split and/or retrace — a world change shrinks the device
            # SET, so it lands in the split/retrace family rather than
            # the same-pool relayout's mesh_mismatch kill) and keep
            # firing fused on the shrunk mesh afterwards
            if rep["events"]["step_split"] < 1 \
                    and rep["step_retraces"] < 1 \
                    and rep["events"]["mesh_mismatch"] < 1:
                failures.append(
                    f"{h}'s promoted step sailed through the mesh "
                    "change without a split or retrace")
            if rep["fused_steps"] < 1:
                failures.append(f"{h} never fired a fused step")
            if len(rep["losses"]) != steps:
                failures.append(f"{h} finished {len(rep['losses'])} of "
                                f"{steps} steps")

        # phase 2: the reference — an UNINTERRUPTED run on the shrunk
        # (dp=2) mesh, fresh checkpoints, same shared store
        if not failures:
            coord2 = fabric.Coordinator(lease_s=lease_s, expected=2)
            addr2 = f"{coord2.host}:{coord2.port}"
            procs2 = {h: _spawn_fleet_child(
                          addr2, h, aot, os.path.join(tmp, "ck_ref"),
                          outs[h], steps)
                      for h in ("r0", "r1")}
            rcs2 = _drain_fleet_children(procs2)
            coord2.close()
            for h in ("r0", "r1"):
                if rcs2[h][0] != 0:
                    failures.append(
                        f"reference {h} failed: {rcs2[h][1][-800:]}")
        if not failures:
            with open(outs["r0"]) as f:
                ref = json.load(f)
            for h, rep in survivors.items():
                rb_step = rep["rebuilds"][0]["resumed"] + 1
                for k, v in rep["losses"].items():
                    if int(k) < rb_step:
                        continue
                    if abs(v - ref["losses"][k]) > 1e-4:
                        failures.append(
                            f"{h} post-rebuild loss diverged from the "
                            f"clean shrunk-mesh run at step {k}: {v} vs "
                            f"{ref['losses'][k]}")
                        break

        # phase 3: the restarted worker REJOINS a full fleet at the
        # current generation and re-promotes with zero fresh compiles —
        # the dp=3 artifacts it stored before dying serve it back
        if not failures:
            seq1 = EVENTS.total
            coord3 = fabric.Coordinator(lease_s=lease_s, expected=3)
            addr3 = f"{coord3.host}:{coord3.port}"
            procs3 = {}
            for h, prev in (("j0", None), ("j1", None), ("j2", 1)):
                procs3[h] = _spawn_fleet_child(
                    addr3, h, aot, ck_fleet, outs[h], steps + 6,
                    prev_gen=prev)
            rcs3 = _drain_fleet_children(procs3)
            ev3 = [e for e in EVENTS.snapshot() if e["seq"] > seq1]
            coord3.close()
            for h in ("j0", "j1", "j2"):
                if rcs3[h][0] != 0:
                    failures.append(
                        f"rejoin-phase {h} failed: {rcs3[h][1][-800:]}")
            if not any(e["cat"] == "fleet.rejoin" and e["op"] == "j2"
                       for e in ev3):
                failures.append("coordinator never attributed j2 as a "
                                "fleet.rejoin")
        if not failures:
            with open(outs["j2"]) as f:
                rej = json.load(f)
            if rej["resumed_step"] < 0:
                failures.append("rejoiner did not pull the shared "
                                "checkpoint")
            if not rej["prefetch"] or rej["prefetch"]["artifacts"] < 1:
                failures.append(
                    f"prefetch warmed {rej.get('prefetch')} — the "
                    "shared store is invisible to the rejoiner")
            # THE acceptance: zero fresh compiles in the rejoined worker
            for k in ("dispatch_retraces", "chain_retraces",
                      "step_retraces"):
                if rej[k] != 0:
                    failures.append(
                        f"rejoiner paid {rej[k]} {k}: the shared store "
                        "did not eliminate the warmup")
            if rej["events"]["dispatch_retrace"] \
                    or rej["events"]["chain_compile"]:
                failures.append(f"rejoiner emitted compile events: "
                                f"{rej['events']}")
            if rej["events"]["aot_hit"] < 3:
                failures.append(
                    f"rejoiner loaded only {rej['events']['aot_hit']} "
                    "artifacts from the shared store")
            if rej["first_fired_rel"] is None \
                    or rej["first_fired_rel"] > 1:
                failures.append(
                    f"rejoiner first fused fire at relative step "
                    f"{rej['first_fired_rel']} (expected <= 1)")
    return {"ok": not failures, "failures": failures}


def scenario_fleet_flap(steps=12, lease_s=2.0, pause_frac=0.6):
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.profiler.events import EVENTS
    from paddle_tpu.distributed import fabric

    set_flags({"FLAGS_profiler_events": True})
    failures = []
    pause = lease_s * pause_frac
    with tempfile.TemporaryDirectory() as tmp:
        aot = os.path.join(tmp, "aot")
        outs = {h: os.path.join(tmp, f"{h}.json") for h in ("f0", "f1")}
        seq0 = EVENTS.total
        coord = fabric.Coordinator(lease_s=lease_s, expected=2)
        addr = f"{coord.host}:{coord.port}"
        procs = {
            "f0": _spawn_fleet_child(addr, "f0", aot,
                                     os.path.join(tmp, "ck"), outs["f0"],
                                     steps, pause_at=4, pause_hb=pause),
            "f1": _spawn_fleet_child(addr, "f1", aot,
                                     os.path.join(tmp, "ck"), outs["f1"],
                                     steps),
        }
        rcs = _drain_fleet_children(procs)
        ev = [e for e in EVENTS.snapshot() if e["seq"] > seq0]
        coord.close()
        for h in ("f0", "f1"):
            if rcs[h][0] != 0:
                failures.append(f"{h} failed: {rcs[h][1][-800:]}")
        if any(e["cat"] == "fleet.leave"
               and e.get("reason") == "host_lost" for e in ev):
            failures.append(
                f"a {pause:.1f}s heartbeat gap inside a {lease_s}s "
                "lease flapped membership")
        reports = {}
        for h in ("f0", "f1"):
            if os.path.exists(outs[h]):
                with open(outs[h]) as f:
                    reports[h] = json.load(f)
        for h, rep in reports.items():
            if rep["rebuilds"]:
                failures.append(f"{h} adopted a rebuild during an "
                                "in-lease slow spell")
            if rep["generation"] != 1:
                failures.append(f"{h} ended at generation "
                                f"{rep['generation']} (expected 1)")
        if len(reports) == 2 and not failures:
            a, b = reports["f0"]["losses"], reports["f1"]["losses"]
            if a != b:
                failures.append("replica trajectories diverged across "
                                "the slow spell")
    return {"ok": not failures, "failures": failures}


# ---------------------------------------------------------------------------
# kill scenario: child training loop + parent orchestration
# ---------------------------------------------------------------------------

def child_main(args):
    """One resumable AMP training run (invoked as `chaos.py --child`).
    Deterministic per (epoch, step): seeded batches, a NaN batch every 7th
    step (exercising skip-step through the crash boundary), Momentum +
    StepDecay so accumulator/step-counter/LR state must all round-trip."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.incubate.checkpoint import train_epoch_range

    set_flags({"FLAGS_check_numerics": True,
               "FLAGS_eager_chain_fusion_min_count": 3,
               "FLAGS_eager_step_fusion_min_count": 5})
    paddle.seed(7)
    rng = np.random.default_rng(11)
    w = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32),
                         stop_gradient=False)
    bias = paddle.to_tensor(rng.standard_normal(8).astype(np.float32),
                            stop_gradient=False)
    sched = paddle.optimizer.lr.StepDecay(learning_rate=0.05, step_size=2,
                                          gamma=0.5)
    opt = paddle.optimizer.Momentum(learning_rate=sched, momentum=0.9,
                                    parameters=[w, bias])
    scaler = paddle.amp.GradScaler(init_loss_scaling=256.0,
                                   decr_every_n_nan_or_inf=1)
    model = {"w": w, "b": bias}
    er = train_epoch_range(args.epochs, save_dir=args.ckpt_dir,
                           run_id="chaos", max_checkpoints=2)
    er.restore(model=model, optimizer=opt, scaler=scaler)
    resumed_from = er.restored_from
    kill_at = None
    if args.kill_at:
        kill_at = tuple(int(v) for v in args.kill_at.split(":"))
    for epoch in er:
        for step in range(args.steps):
            if kill_at == (epoch, step):
                os.kill(os.getpid(), signal.SIGKILL)
            srng = np.random.default_rng(1000 * epoch + step)
            batch = srng.standard_normal((4, 8)).astype(np.float32)
            if (epoch * args.steps + step) % 7 == 5:
                batch[:] = np.nan
            x = paddle.to_tensor(batch)
            loss = F.gelu(paddle.add(paddle.matmul(x, w), bias)).sum()
            scaler.scale(loss).backward()
            scaler.step(opt)
            scaler.update()
            opt.clear_grad()
        sched.step()
        er.save(epoch, model=model, optimizer=opt, scaler=scaler,
                extra={"epoch": epoch})
    paddle.save(
        {"w": w, "b": bias,
         "scale": scaler.get_init_loss_scaling(),
         "step_count": int(getattr(opt, "_step_count", 0)),
         "lr": float(opt.get_lr()),
         "resumed_from": resumed_from},
        args.out)
    return 0


def _spawn_child(ckpt_dir, out, epochs, steps, kill_at=None, timeout=300):
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--ckpt-dir", ckpt_dir, "--out", out,
           "--epochs", str(epochs), "--steps", str(steps)]
    if kill_at:
        cmd += ["--kill-at", kill_at]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)


def scenario_kill(epochs=3, steps=6):
    import numpy as np
    import paddle_tpu as paddle

    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        ck_a = os.path.join(tmp, "interrupted")
        ck_b = os.path.join(tmp, "clean")
        out_resumed = os.path.join(tmp, "resumed.pd")
        out_clean = os.path.join(tmp, "clean.pd")

        # run 1: killed mid-epoch (epoch 1, step 3 — epoch 0's checkpoint
        # exists, epoch 1 is half done)
        r1 = _spawn_child(ck_a, out_resumed, epochs, steps, kill_at="1:3")
        if r1.returncode != -signal.SIGKILL:
            failures.append(
                f"expected the child to die by SIGKILL, got rc={r1.returncode}"
                f" stderr={r1.stderr[-500:]}")
        if os.path.exists(out_resumed):
            failures.append("killed run still produced a final state file")

        # run 2: same ckpt dir — must resume from epoch 0's checkpoint and
        # finish
        r2 = _spawn_child(ck_a, out_resumed, epochs, steps)
        if r2.returncode != 0:
            failures.append(f"resumed run failed: {r2.stderr[-800:]}")

        # reference: uninterrupted run in a fresh dir
        r3 = _spawn_child(ck_b, out_clean, epochs, steps)
        if r3.returncode != 0:
            failures.append(f"reference run failed: {r3.stderr[-800:]}")

        if not failures:
            res = paddle.load(out_resumed)
            ref = paddle.load(out_clean)
            if res["resumed_from"] != 0:
                failures.append(
                    f"rerun resumed from epoch {res['resumed_from']}, "
                    "expected 0 (the last completed before the kill)")
            for k in ("scale", "step_count", "lr"):
                if res[k] != ref[k]:
                    failures.append(
                        f"{k} diverged after resume: {res[k]} != {ref[k]}")
            for k in ("w", "b"):
                a = np.asarray(res[k]._value)
                c = np.asarray(ref[k]._value)
                # whole-step fusion warms up at different step indices in
                # the resumed process, and the ONE-program step differs
                # from per-op dispatch in the last ULP (ROADMAP follow-on
                # (d)) — state equality above is exact, params are
                # float-equal to tight tolerance
                if not np.allclose(a, c, rtol=0, atol=1e-5):
                    failures.append(
                        f"param {k} diverged after resume "
                        f"(max |Δ|={np.max(np.abs(a - c)):.3e})")
    return {"ok": not failures, "failures": failures}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

SCENARIOS = {"nan": scenario_nan, "exception": scenario_exception,
             "kill": scenario_kill, "warm_restart": scenario_warm_restart,
             "serve_hang": scenario_serve_hang,
             "serve_fused_fault": scenario_serve_fused_fault,
             "serve_kill": scenario_serve_kill,
             "tenant_swap": scenario_tenant_swap,
             "telemetry": scenario_telemetry,
             "sentinel": scenario_sentinel,
             "fleet_kill": scenario_fleet_kill,
             "fleet_flap": scenario_fleet_flap}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="all",
                    choices=["all"] + sorted(SCENARIOS))
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    # internal: child training/serving runs for the kill scenarios
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--serve-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--tenant-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--kill-mode", default=None,
                    choices=("staged", "committed"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--aot-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--fleet-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--coord", help=argparse.SUPPRESS)
    ap.add_argument("--host-id", help=argparse.SUPPRESS)
    ap.add_argument("--prev-gen", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--pause-at", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--pause-hb", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--step-ms", default=0, help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", help=argparse.SUPPRESS)
    ap.add_argument("--aot-dir", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--epochs", type=int, default=3, help=argparse.SUPPRESS)
    ap.add_argument("--steps", type=int, default=6, help=argparse.SUPPRESS)
    ap.add_argument("--kill-at", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        return child_main(args)
    if args.serve_child:
        return serve_child_main(args)
    if args.tenant_child:
        return tenant_child_main(args)
    if args.aot_child:
        return aot_child_main(args)
    if args.fleet_child:
        return fleet_child_main(args)

    names = sorted(SCENARIOS) if args.scenario == "all" else [args.scenario]
    report = {}
    ok = True
    for name in names:
        t0 = time.perf_counter()
        res = SCENARIOS[name]()
        res["seconds"] = round(time.perf_counter() - t0, 2)
        report[name] = res
        ok = ok and res["ok"]
        if not args.json:
            status = "OK" if res["ok"] else "FAIL"
            print(f"chaos[{name}]: {status} ({res['seconds']}s)")
            for f in res.get("failures", []):
                print(f"  - {f}", file=sys.stderr)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    elif ok:
        print("chaos: all scenarios OK")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
