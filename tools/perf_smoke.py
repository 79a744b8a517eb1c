#!/usr/bin/env python
"""Fast perf guard for the compiled eager dispatch stack (PR 1 + 2 + 3).

Runs a tiny eager matmul→add→gelu→sum fwd+bwd+SGD loop on CPU and fails
(exit 1) when the dispatch telemetry shows any layer of the optimization
stack silently regressed:

  * post-warmup retraces — the per-op executable cache (ops/dispatch.py)
    must stop tracing after the first few iterations; any later trace means
    cache keying broke (a PR 1 regression);
  * zero chain-fusion replay rate with fusion enabled — the hot sequence
    must be detected and replayed as one fused executable (ops/fusion.py);
    a 0% replay rate means detection or replay broke (a PR 2 regression);
  * zero whole-step fusion replays, a post-warmup step retrace, or a
    fused-step speedup below the guard — the stable fwd+bwd+optimizer
    cycle must be promoted to ONE fused executable (ops/step_fusion.py)
    and beat the chain-fusion path (a PR 3 regression);
  * unexplained splits — with the fusion flight recorder armed
    (FLAGS_profiler_events), every chain.split/step.split event must
    carry a known reason code, and the steady-state loop must report
    ZERO splits (a PR 4 attribution regression);
  * events-off overhead — the recorder's disabled path (one flag check
    per emission site) must cost <3% of a fused step at the observed
    events-per-step rate (a PR 4 hot-path regression);
  * guardian overhead — FLAGS_check_numerics compiles its finite checks
    INTO the fused executables (one scalar per launch, one batched sync
    per step), so the guarded fused loop must stay within 5% of the
    unguarded one AND keep replaying fused (a PR 5 regression);
  * AMP promotion — a dynamic-loss-scaled GradScaler loop under the
    guardian must reach whole-step zero-retrace steady state (scale and
    growth-tracker ride as hoisted scalar args; promotion is no longer
    poisoned by the mid-step grad read — a PR 5 regression);
  * serving decode zero-retrace + occupancy — 64 mixed-length streams
    churning through a 4-slot continuous batch (paddle_tpu/serving) must
    compile the decode executable exactly ONCE, and saturated batch
    occupancy must stay >= 0.75 — the paged KV cache + slot layout keep
    every tenant mix on one program (a PR 6 regression);
  * serving resilience cost + churn — with the hung-step watchdog and
    per-request deadlines ARMED, the serve_8-style loop must stay under
    2x the disarmed engine on best-window-vs-best-window (the monitored
    completion's spin-poll must never sleep or sync on a healthy step —
    that regression class multiplies the window), and the decode executable must
    STILL compile exactly once while requests are cancelled, expired,
    refused, and crash-resumed around it — resilience is value edits to
    the fixed slot layout, never shapes (a PR 7 regression);
  * AOT warm start — a fresh subprocess against a WARM persistent
    executable store (FLAGS_aot_cache, ops/aot_cache.py) must reach a
    promoted fused step with ZERO compile activity (no dispatch
    retraces, no chain compiles, no whole-step retrace — everything
    deserializes) and measurably faster time-to-first-promoted-step
    than the cold subprocess that populated the store (a PR 9
    regression);
  * kernel tier — blockwise paged decode attention (online softmax
    streamed over the KV block table) must beat the dense [S, T, H, D]
    gather at seq >= 1k on the serve-shaped CPU microbench, and a
    serving engine with the int8 KV cache must still compile its decode
    step exactly once under stream churn (a PR 11 regression);
  * telemetry plane — the metrics registry (profiler/metrics.py) must
    record NOTHING with FLAGS_metrics off at one-flag-check cost
    (<3%/step at the observed sites-per-step rate), stay within 5%/step
    armed on BOTH the fused train loop and the serve_8 workload
    (interleaved min-of-ratios), and its histogram hot path must never
    grow memory with observations (a PR 12 regression);
  * telemetry server — the live HTTP observability plane
    (profiler/telemetry_server.py) must cost one module-bool check per
    heartbeat site with no server running (<3%/step, nothing recorded),
    and with the server armed plus a scraper hitting /metrics +
    /healthz every 100 ms, the fused train loop and the serve_8
    workload must stay within 5%/step while every scrape is answered
    (a PR 13 regression);
  * distributed step fusion — a dp=N sharded-batch loop over the
    emulated device mesh must auto-promote into ONE shard_map-wrapped
    executable (ops/spmd_fusion.py; zero retraces after promotion) and
    beat the same loop on unfused eager dispatch (per-op GSPMD
    collectives) by >= 1.3x (a PR 10 regression);
  * multi-tenant serving — 64 streams over 8 tenants (shared system
    prompt through the prefix cache, batched LoRA adapter slots, one
    live weight hot-swap landing mid-run) must keep the decode
    executable at exactly ONE compile — adapter churn and the swap are
    VALUE edits to fixed shapes — and the steady-state prefix-hit
    prefill must beat the cold prefill by >= 3x on interleaved
    min-of-ratios (a PR 17 regression).

Runs in a few seconds; wired into tier-1 as the `perf_smoke`-marked tests
in tests/test_chain_fusion.py and tests/test_step_fusion.py — this CLI is
the same guard for CI scripts and manual bisection:

    JAX_PLATFORMS=cpu python tools/perf_smoke.py
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable from a source checkout without an install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

WARMUP = 14
MEASURE = 40
# promoted DP step vs unfused eager collectives (ops/spmd_fusion.py)
DP_SPEEDUP_GUARD = 1.3
# promoted pp pipeline cycle (ops/spmd_fusion.py pipeline registry) vs the
# unfused eager schedule (forward_backward_pipeline: sequential micro-batch
# accumulation, per-op dispatch). Same bound as the pytest acceptance
# (1.3x) — the whole fill/steady/drain cycle fusing into one executable is
# worth an order of magnitude even on a loaded box, so no CLI loosening
PP_SPEEDUP_GUARD = 1.3
# warm-start guard: a warm store must reach the first PROMOTED FUSED step
# in at most this fraction of the cold process's time-to-first-fire (the
# cold path pays per-op traces + the whole-step trace + XLA compiles; the
# warm path only deserializes) — loose enough for loaded CI boxes, tight
# enough that "the store stopped eliminating the warmup" fails loudly
AOT_WARM_RATIO_GUARD = 0.85
# CLI guard is looser than the pytest acceptance bound (1.3x): the smoke
# must stay green on loaded CI boxes while still catching a real loss of
# whole-step fusion (which is worth ~1.9x on an idle machine)
STEP_SPEEDUP_GUARD = 1.15
# steady-state prefix-hit prefill vs cold prefill on the shared-prefix
# serve workload (serving/tenancy.py): aliasing every full block of the
# shared prompt turns a whole-prompt prefill into a short tail prefill,
# worth far more than 3x even on a loaded box
PREFIX_SPEEDUP_GUARD = 3.0
# sampled decode vs greedy decode per step (serving/sampling.py): the
# sampler head (one shared sort + gumbel) must stay a rounding error next
# to the transformer forward, so the guard runs on a forward-dominated
# model (hidden 640) where the head's fixed cost cannot hide a regression
# behind model FLOPs it doesn't have
SAMPLED_OVERHEAD_GUARD = 0.05
# lag-1 pipelined decode vs unpipelined on the serve_8 workload whose
# per-token commit blocks the host (a stream-write stand-in): the pipeline
# overlaps host WAIT with device compute — on a 1-core CI box CPU-bound
# host work cannot overlap anything, but blocked-host time (client
# sockets, log fsync) can, and on a real accelerator ALL host work can.
# If the launch path ever re-synchronizes (dispatch blocking on the
# in-flight step), both sides degenerate to D+H and the ratio collapses
PIPELINE_SPEEDUP_GUARD = 1.15


def _loop(step_fused, check_numerics=False, use_scaler=False):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops.dispatch import clear_dispatch_cache

    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_chain_fusion": True,
               # fuse within the short warmup (the default thresholds are
               # sized for training loops, not a 54-iteration smoke)
               "FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion": step_fused,
               "FLAGS_eager_step_fusion_min_count": 5,
               "FLAGS_check_numerics": check_numerics})
    clear_dispatch_cache()

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, 32)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((32, 32)).astype(np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(32).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w, b])
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0) \
        if use_scaler else None

    def step():
        y = F.gelu(paddle.add(paddle.matmul(x, w), b))
        loss = y.sum()
        if scaler is None:
            loss.backward()
            opt.step()
        else:
            scaler.scale(loss).backward()
            scaler.step(opt)
            scaler.update()
        opt.clear_grad()

    def sync():
        # drain the async dispatch queue (measurement-boundary hygiene:
        # without it, one leg's enqueued-but-unexecuted work bleeds into
        # the next leg's timed window)
        w._value.block_until_ready()

    step.sync = sync
    return step


def _dp_loop(step_fused):
    """A dp=N data-parallel MLP loop: batch sharded over a mesh spanning
    every device (8 emulated on CPU via tests/conftest-style XLA flags).
    With step fusion on, the cycle must promote through the SPMD lowering
    (ops/spmd_fusion.py) — ONE shard_map executable per step."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.ops.dispatch import clear_dispatch_cache

    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_chain_fusion": True,
               "FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion": step_fused,
               "FLAGS_eager_step_fusion_min_count": 5,
               "FLAGS_check_numerics": False})
    clear_dispatch_cache()

    n = jax.device_count()
    mesh = build_mesh(dp=n, pp=1, sharding=1, sep=1, mp=1)
    set_global_mesh(mesh)
    sharding = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    x = paddle.Tensor(jax.device_put(
        rng.standard_normal((8 * n, 32)).astype(np.float32), sharding),
        stop_gradient=True)
    y = paddle.Tensor(jax.device_put(
        rng.standard_normal((8 * n, 16)).astype(np.float32), sharding),
        stop_gradient=True)
    w1 = paddle.to_tensor(
        (rng.standard_normal((32, 64)) * 0.1).astype(np.float32),
        stop_gradient=False)
    b1 = paddle.to_tensor(np.zeros(64, np.float32), stop_gradient=False)
    w2 = paddle.to_tensor(
        (rng.standard_normal((64, 16)) * 0.1).astype(np.float32),
        stop_gradient=False)
    opt = paddle.optimizer.Momentum(learning_rate=1e-3, momentum=0.9,
                                    parameters=[w1, b1, w2])

    def step():
        h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
        out = paddle.matmul(h, w2)
        diff = paddle.subtract(out, y)
        loss = paddle.mean(paddle.multiply(diff, diff))
        loss.backward()
        opt.step()
        opt.clear_grad()

    def sync():
        w1._value.block_until_ready()

    step.sync = sync
    return step


def aot_child_main(aot_dir, out_path, steps=12) -> int:
    """Warm-start measurement child (`perf_smoke.py --aot-child`): a tiny
    fwd+bwd+SGD loop with the AOT executable store armed. Reports the
    wall time from loop start to the FIRST fused whole-step fire plus the
    compile/AOT counters — the parent runs it once cold (empty store) and
    again warm (populated store) and guards the ratio. Shared with
    tests/test_aot_cache.py so the pytest guard and this CLI can never
    drift."""
    import json
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.profiler import (dispatch_cache_stats,
                                     chain_fusion_stats,
                                     step_fusion_stats, aot_cache_stats)

    set_flags({"FLAGS_aot_cache": True,
               "FLAGS_aot_cache_dir": aot_dir,
               "FLAGS_eager_chain_fusion_min_count": 3,
               "FLAGS_eager_step_fusion_min_count": 5})
    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, 32)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((32, 32)).astype(np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(32).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w, b])
    opt.clear_grad()        # steady-state cycle signature from cycle 1
    t0 = time.perf_counter()
    t_first_fire = None
    for _ in range(steps):
        loss = F.gelu(paddle.add(paddle.matmul(x, w), b)).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        if t_first_fire is None \
                and step_fusion_stats()["fused_steps"] > 0:
            t_first_fire = time.perf_counter() - t0
    report = {
        "t_first_fire_s": t_first_fire,
        "dispatch_retraces": dispatch_cache_stats()["retraces"],
        "chain_retraces": chain_fusion_stats()["retraces"],
        "step_retraces": step_fusion_stats()["retraces"],
        "steps_promoted": step_fusion_stats()["steps_promoted"],
        "fused_steps": step_fusion_stats()["fused_steps"],
        "aot": aot_cache_stats(),
    }
    with open(out_path, "w") as f:
        json.dump(report, f)
    return 0


def _aot_warm_start_leg(failures):
    """Leg (h), PR 9: a fresh subprocess against a WARM store must reach
    a promoted fused step with zero compile activity — no dispatch
    retraces, no chain compiles, no whole-step retrace — and measurably
    faster than the cold subprocess that populated the store (min over
    two warm runs, same best-window hygiene as the guardian leg)."""
    import json
    import subprocess
    import tempfile

    def run(aot_dir, out):
        cmd = [sys.executable, os.path.abspath(__file__), "--aot-child",
               "--aot-dir", aot_dir, "--out", out]
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=300, env=env)
        if r.returncode != 0:
            raise RuntimeError(f"aot child failed: {r.stderr[-800:]}")
        with open(out) as f:
            rep = json.load(f)
        if rep["t_first_fire_s"] is None:
            # a child that never fired must FAIL the guard below, not
            # crash the ratio math / report formatting with a TypeError
            rep["t_first_fire_s"] = float("nan")
        return rep

    with tempfile.TemporaryDirectory() as tmp:
        store = os.path.join(tmp, "store")
        cold = run(store, os.path.join(tmp, "cold.json"))
        warms = [run(store, os.path.join(tmp, f"warm{i}.json"))
                 for i in range(2)]
    warm = min(warms, key=lambda r: r["t_first_fire_s"] or 1e9)
    if cold["fused_steps"] == 0 or cold["aot"]["stores"] == 0:
        failures.append(
            "cold AOT child never promoted/stored — the warm-start leg "
            "has nothing to measure (PR 9 guard bug)")
        return cold, warm
    for r in warms:
        if r["fused_steps"] == 0:
            failures.append("warm AOT child never fired a fused step "
                            "(PR 9 regression)")
        for k in ("dispatch_retraces", "chain_retraces", "step_retraces"):
            if r[k] != 0:
                failures.append(
                    f"warm AOT child paid {r[k]} {k}: the store stopped "
                    "eliminating the warmup (PR 9 regression)")
        if r["aot"]["hits"] == 0:
            failures.append("warm AOT child loaded no artifacts "
                            "(PR 9 regression)")
    ratio = warm["t_first_fire_s"] / cold["t_first_fire_s"] \
        if cold["t_first_fire_s"] else float("inf")
    if ratio >= AOT_WARM_RATIO_GUARD:
        failures.append(
            f"warm-store time-to-first-promoted-step is {ratio:.2f}x the "
            f"cold run ({warm['t_first_fire_s']:.2f}s vs "
            f"{cold['t_first_fire_s']:.2f}s, guard "
            f"{AOT_WARM_RATIO_GUARD}): the AOT store lost its win "
            "(PR 9 regression)")
    return cold, warm


def main() -> int:
    from paddle_tpu.profiler import (chain_fusion_stats,
                                     dispatch_cache_stats,
                                     step_fusion_stats)

    def timed(step):
        """Best-of-3 measurement windows: single-shot wall times on a
        loaded CI box swing 2-3x; the best window is the signal."""
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(MEASURE):
                step()
            best = min(best, (time.perf_counter() - t0) / MEASURE)
        return best

    # ---- chain-fusion leg (step fusion off, PR 1 + PR 2 guards) ----------
    step = _loop(step_fused=False)
    for _ in range(WARMUP):
        step()
    d0, c0 = dispatch_cache_stats(), chain_fusion_stats()
    t_chain = timed(step)
    d1, c1 = dispatch_cache_stats(), chain_fusion_stats()

    failures = []
    retraces = (d1["retraces"] - d0["retraces"]) \
        + (c1["retraces"] - c0["retraces"])
    if retraces:
        failures.append(
            f"{retraces} post-warmup retrace(s): the executable cache is "
            "re-tracing a hot loop (PR 1 regression)")
    chain_replays = c1["fused_replays"] - c0["fused_replays"]
    chain_replays = min(chain_replays, MEASURE)   # 3 timed windows ran
    if chain_replays == 0:
        failures.append(
            "chain-fusion replay rate is zero with fusion enabled "
            f"(detected={c1['chains_detected']}): the hot sequence is not "
            "being fused (PR 2 regression)")

    # ---- whole-step fusion leg (PR 3 guards) -----------------------------
    step = _loop(step_fused=True)
    for _ in range(WARMUP):
        step()
    s0 = step_fusion_stats()
    t_step = timed(step)
    s1 = step_fusion_stats()

    step_replays = min(s1["fused_steps"] - s0["fused_steps"], MEASURE)
    step_retraces = s1["retraces"] - s0["retraces"]
    if step_replays == 0:
        failures.append(
            "whole-step fusion replay rate is zero with the flag enabled "
            f"(promoted={s1['steps_promoted']}, "
            f"splits={s1['fallback_splits']}): the stable cycle is not "
            "being promoted (PR 3 regression)")
    if step_retraces:
        failures.append(
            f"{step_retraces} post-warmup whole-step retrace(s): the step "
            "executable is re-tracing a stable cycle (PR 3 regression)")
    speedup = t_chain / t_step if t_step > 0 else 0.0
    if step_replays and speedup < STEP_SPEEDUP_GUARD:
        failures.append(
            f"whole-step fusion speedup {speedup:.2f}x is below the "
            f"{STEP_SPEEDUP_GUARD}x guard (chain {t_chain*1e6:.0f}us vs "
            f"fused step {t_step*1e6:.0f}us): the fused path lost its win "
            "(PR 3 regression)")

    # ---- flight-recorder legs (PR 4 guards) ------------------------------
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.profiler.events import (EVENTS, REASON_CODES,
                                            clear_fusion_events)

    # (a) no unexplained splits + steady-state zero splits: re-run the
    # fused loop with the recorder armed; warmup splits must all carry a
    # known reason code and the measured window must contain none at all
    step = _loop(step_fused=True)
    clear_fusion_events()
    set_flags({"FLAGS_profiler_events": True})
    for _ in range(WARMUP):
        step()
    steady_seq = EVENTS.total
    for _ in range(MEASURE):
        step()
    set_flags({"FLAGS_profiler_events": False})
    split_events = [e for e in EVENTS.snapshot()
                    if e["cat"] in ("chain.split", "step.split")]
    unexplained = [e for e in split_events
                   if e["reason"] not in REASON_CODES]
    if unexplained:
        failures.append(
            f"{len(unexplained)} split event(s) without a known reason "
            f"code (first: {unexplained[0]}): split attribution broke "
            "(PR 4 regression)")
    steady_splits = [e for e in split_events if e["seq"] > steady_seq]
    if steady_splits:
        failures.append(
            f"{len(steady_splits)} steady-state split(s) in the smoke "
            f"loop (first: {steady_splits[0]['cat']}:"
            f"{steady_splits[0]['reason']}): the stable cycle should "
            "replay without splitting (PR 4 regression)")
    events_per_step = (EVENTS.total - steady_seq) / MEASURE
    clear_fusion_events()

    # (b) events-off overhead: the disabled emit path is one flag check;
    # at the observed events-per-step rate its total cost must stay <3%
    # of a fused step (timing the loop against a never-instrumented
    # binary is impossible in-process, so guard the unit cost directly)
    N_EMIT = 200_000
    t0 = time.perf_counter()
    for _ in range(N_EMIT):
        EVENTS.emit("dispatch.hit", "x")
    emit_off_ns = (time.perf_counter() - t0) / N_EMIT * 1e9
    if len(EVENTS):
        failures.append(
            f"{len(EVENTS)} event(s) recorded with FLAGS_profiler_events "
            "off: the gate is broken (PR 4 regression)")
    overhead_frac = emit_off_ns * events_per_step / max(t_step * 1e9, 1.0)
    if overhead_frac >= 0.03:
        failures.append(
            f"events-off emit cost {emit_off_ns:.0f}ns x "
            f"{events_per_step:.1f} events/step is "
            f"{overhead_frac * 100:.2f}% of a fused step (>=3%): the "
            "disabled path got expensive (PR 4 regression)")

    # ---- guardian legs (PR 5 guards) -------------------------------------
    # (c) FLAGS_check_numerics cost: the checks compile INTO the fused
    # executables, so the guarded loop must stay within 5% of the
    # unguarded fused step (and must still replay fused at all). The
    # the baseline and the guarded loop are measured in INTERLEAVED
    # windows (flag flipped per window — each loop's promoted program
    # re-arms from the per-thread library without retracing) and compared
    # on best-window times: a load spike hits both legs alike instead of
    # faking (or masking) a few-percent regression. The earlier t_step is
    # minutes old by now; process drift dwarfs the effect guarded here.
    base_step = _loop(step_fused=True)
    for _ in range(WARMUP):
        base_step()
    step = _loop(step_fused=True, check_numerics=True)
    for _ in range(WARMUP):
        step()
    # _loop() above cleared the caches, so the base leg's promoted program
    # is gone: re-warm it or window 0's baseline pays full re-record +
    # re-promote + XLA compile, its ratio craters, and min-of-ratios would
    # wave through ANY real guardian regression
    set_flags({"FLAGS_check_numerics": False})
    for _ in range(WARMUP):
        base_step()
    # the guard statistic is the MIN over paired window ratios: a real
    # guardian regression (an added per-step sync costs 2x+) inflates
    # EVERY pair, while a CI-box load spike only inflates the pairs it
    # lands on — so min-of-ratios tracks the true marginal cost even when
    # single-window times swing 2-3x
    ratios = []
    t_base = t_guard = float("inf")
    for _ in range(6):
        set_flags({"FLAGS_check_numerics": False})
        base_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            base_step()
        base_step.sync()
        tb = (time.perf_counter() - t0) / MEASURE
        set_flags({"FLAGS_check_numerics": True})
        step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            step()
        step.sync()
        tg = (time.perf_counter() - t0) / MEASURE
        t_base, t_guard = min(t_base, tb), min(t_guard, tg)
        ratios.append(tg / tb if tb > 0 else float("inf"))
    # (flag is still on) the guarded loop must actually be REPLAYING fused
    g0 = step_fusion_stats()
    for _ in range(8):
        step()
    g1 = step_fusion_stats()
    if g1["fused_steps"] - g0["fused_steps"] == 0:
        failures.append(
            "whole-step fusion stopped replaying under "
            "FLAGS_check_numerics: the guardian un-fused the loop "
            "(PR 5 regression)")
    guard_overhead = min(ratios) - 1.0
    guard_median = sorted(ratios)[len(ratios) // 2] - 1.0
    if guard_overhead >= 0.05:
        failures.append(
            f"FLAGS_check_numerics costs {guard_overhead * 100:.1f}%/step "
            f"(best guarded window {t_guard * 1e6:.0f}us vs base "
            f"{t_base * 1e6:.0f}us, >=5%): the in-graph checks stopped "
            "amortizing (PR 5 regression)")

    # (d) dynamic-loss-scaled AMP promotion: scale/growth-tracker ride as
    # hoisted args, unscale/found-inf/backoff fold into the ONE fused
    # executable — the GradScaler loop must reach zero-retrace steady
    # state instead of splitting on the mid-step grad read
    step = _loop(step_fused=True, check_numerics=True, use_scaler=True)
    for _ in range(WARMUP):
        step()
    a0 = step_fusion_stats()
    for _ in range(MEASURE):
        step()
    a1 = step_fusion_stats()
    amp_replays = min(a1["fused_steps"] - a0["fused_steps"], MEASURE)
    amp_retraces = a1["retraces"] - a0["retraces"]
    if amp_replays == 0:
        failures.append(
            "GradScaler AMP loop did not promote under the guardian "
            f"(promoted={a1['steps_promoted']}, "
            f"splits={a1['fallback_splits']}): scaled training lost "
            "whole-step fusion (PR 5 regression)")
    if amp_retraces:
        failures.append(
            f"{amp_retraces} post-warmup retrace(s) in the guarded AMP "
            "loop: the scaler state is no longer a hoisted arg "
            "(PR 5 regression)")
    # legs (c)/(d) armed the guardian and the eager fusion tiers; the
    # serving legs below measure the ENGINE (its decode/prefill programs
    # are compiled outside the eager tiers) — leaked per-launch
    # finite-check syncs and chain/step-fusion detection bookkeeping on
    # the engine's host-side ops would turn leg (f)'s watchdog ratio
    # into a measurement of guardian + detector jitter instead
    set_flags({"FLAGS_check_numerics": False,
               "FLAGS_eager_chain_fusion": False,
               "FLAGS_eager_step_fusion": False})

    # ---- serving legs (PR 6 guards) --------------------------------------
    # (e) 64 mixed-length streams churn through a 4-slot continuous
    # batch: requests join/leave at token boundaries, yet the decode
    # executable must compile exactly once (slot layout + paged block
    # tables keep shapes fixed), and saturated occupancy must stay
    # >= 0.75 (continuous batching actually packs freed slots)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine

    paddle.seed(0)
    scfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, intermediate_size=64,
                     max_position_embeddings=64, hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0,
                     use_flash_attention=False)
    smodel = GPTForCausalLM(scfg)
    smodel.eval()
    engine = LLMEngine(smodel, max_batch_size=4, block_size=4)
    srng = np.random.default_rng(0)
    sprompts = [srng.integers(0, 128, int(n)).tolist()
                for n in srng.integers(3, 20, 64)]
    engine.generate(sprompts, max_new_tokens=6)
    sstats = engine.stats()
    if sstats["decode_compiles"] != 1:
        failures.append(
            f"serving decode compiled {sstats['decode_compiles']}x across "
            "64 churning streams (must be exactly 1): batch composition "
            "leaked into the decode shapes (PR 6 regression)")
    if sstats["occupancy_saturated"] < 0.75:
        failures.append(
            f"saturated batch occupancy {sstats['occupancy_saturated']:.2f} "
            "< 0.75 with 64 streams over 4 slots: continuous batching is "
            "not refilling freed slots (PR 6 regression)")

    # ---- serving resilience legs (PR 7 guards) ---------------------------
    # (f) watchdog + deadline checks armed must stay cheap on a healthy
    # engine: interleaved disarmed/armed windows over the serve_8-style
    # workload, compared best-window vs best-window (the timed() best-of
    # statistic — each side's min discards the windows a load spike or a
    # GC pause landed on; paired ratios proved bistable on a 3 ms window
    # where the armed poll loop contends with XLA's own compute threads).
    # The bound is a 2x catastrophe guard, not a few-percent one: the
    # armed yield-poll's cost on a ~0.3 ms CPU decode step swings tens
    # of percent with process-wide thread pressure even on healthy code,
    # while the regression class this leg exists to catch — the monitor
    # falling into its millisecond coarse-sleep rung (or an extra device
    # sync) on every healthy step — multiplies the window several-fold
    sprompts8 = [srng.integers(0, 128, int(n)).tolist()
                 for n in srng.integers(3, 20, 8)]
    rengine = LLMEngine(smodel, max_batch_size=4, block_size=4)
    rengine.generate(sprompts8, max_new_tokens=6)          # warm programs

    def serve_window(ttl):
        for p in sprompts8:
            rengine.add_request(p, max_new_tokens=6, ttl_s=ttl)
        rengine.run()

    t_serve_off = t_serve_on = float("inf")
    for _ in range(6):
        set_flags({"FLAGS_serve_step_timeout_ms": 0})
        t0 = time.perf_counter()
        serve_window(None)
        t_serve_off = min(t_serve_off, time.perf_counter() - t0)
        set_flags({"FLAGS_serve_step_timeout_ms": 5000})
        t0 = time.perf_counter()
        serve_window(60.0)
        t_serve_on = min(t_serve_on, time.perf_counter() - t0)
    set_flags({"FLAGS_serve_step_timeout_ms": 0})
    resil_overhead = (t_serve_on / t_serve_off - 1.0) if t_serve_off > 0 \
        else float("inf")
    if resil_overhead >= 1.0:
        failures.append(
            f"armed watchdog + deadlines cost "
            f"{resil_overhead * 100:.1f}%/step on the serve_8 loop "
            f"(best armed window {t_serve_on * 1e3:.1f}ms vs disarmed "
            f"{t_serve_off * 1e3:.1f}ms, >=100%): the monitored "
            "completion is sleeping or syncing on healthy steps "
            "(PR 7 regression)")
    if rengine.stats()["decode_compiles"] != 1:
        failures.append(
            "the resilience timing windows retraced the decode program "
            "(PR 7 regression)")

    # (g) decode compiles exactly once while requests are cancelled,
    # expired, refused, and crash-resumed around the running batch
    from paddle_tpu.serving import ServeRefusal
    churn = LLMEngine(smodel, max_batch_size=4, block_size=4,
                      max_queue_depth=6)
    churn.generate(sprompts8[:4], max_new_tokens=4)        # warm programs
    churn.reset_stats()
    set_flags({"FLAGS_serve_step_timeout_ms": 5000})
    try:
        live = [churn.add_request(p, max_new_tokens=6)
                for p in sprompts8[:4]]
        doomed = churn.add_request(sprompts8[4], max_new_tokens=6,
                                   ttl_s=60.0)
        # deterministic queued-expiry: rewind the deadline instead of
        # racing a tiny TTL against the admission-time feasibility check
        doomed.deadline_ns = 0
        refused = 0
        try:
            for _ in range(16):
                churn.add_request(sprompts8[5], max_new_tokens=6)
        except ServeRefusal:
            refused = 1
        for _ in range(2):
            churn.step()
        churn.cancel(live[0].rid)
        mid = churn.state_payload()                        # live streams
        churn.run()
        # resume: re-admit a mid-flight snapshot (ids are free again)
        resumed = churn.restore_state(mid)
        churn.run()
    finally:
        set_flags({"FLAGS_serve_step_timeout_ms": 0})
    cstats = churn.stats()
    if cstats["decode_compiles"] != 0:
        failures.append(
            f"decode retraced {cstats['decode_compiles']}x under "
            "cancel/expire/refuse/resume churn — resilience edits leaked "
            "into the compiled shapes (PR 7 regression)")
    if not (refused and cstats["cancelled"] >= 1
            and cstats["expired"] >= 1 and len(resumed) >= 1):
        failures.append(
            f"churn leg did not exercise every lifecycle edge "
            f"(refused={refused}, cancelled={cstats['cancelled']}, "
            f"expired={cstats['expired']}, resumed={len(resumed)}) "
            "(PR 7 guard bug)")

    # ---- kernel tier legs (PR 11 guards) ---------------------------------
    # (j) blockwise paged decode attention (online softmax over the block
    # table, kernels/pallas/paged_attention.py) must beat the dense
    # [S, T, H, D] gather at seq >= 1k on the serve-shaped CPU
    # microbench — the whole point of the kernel tier is that the dense
    # context never materializes — and an int8-KV engine must still
    # compile its decode step exactly ONCE under stream churn (the
    # scale side-tables are value edits, never shapes)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.attention import paged_decode_attention

    KS, KH, KD, KBS, KM = 8, 4, 32, 16, 64         # seq = 1024
    knb = KS * KM + 1
    krng = np.random.default_rng(2)
    kmk = lambda sh: jnp.asarray(krng.standard_normal(sh).astype(np.float32))
    kq, kkn, kvn = kmk((KS, 1, KH, KD)), kmk((KS, 1, KH, KD)), \
        kmk((KS, 1, KH, KD))
    # the engine's pool shape: [layers, blocks, block, heads * head_dim]
    kkp, kvp = kmk((1, knb, KBS, KH * KD)), kmk((1, knb, KBS, KH * KD))
    ktables = jnp.asarray(np.stack(
        [1 + i * KM + np.arange(KM) for i in range(KS)]).astype(np.int32))
    klens = jnp.full((KS,), KM * KBS - KBS, jnp.int32)
    kactive = jnp.ones((KS,), bool)

    def _paged_fn(kernel):
        @jax.jit
        def f(q, kn, vn, kp, vp):
            return paged_decode_attention(q, kn, vn, kp, vp, 0, ktables,
                                          klens, kactive, KBS,
                                          kernel=kernel)[0]
        f(kq, kkn, kvn, kkp, kvp).block_until_ready()
        return f

    def _paged_window(f, iters=10):
        t0 = time.perf_counter()
        for _ in range(iters):
            f(kq, kkn, kvn, kkp, kvp).block_until_ready()
        return (time.perf_counter() - t0) / iters

    f_dense, f_block = _paged_fn("reference"), _paged_fn("blockwise")
    # INTERLEAVED paired windows, guard on the MAX ratio: a real loss of
    # the streaming win deflates EVERY pair, while a CI-box load spike
    # only hits the pairs it lands on (the same statistic the guardian/
    # resilience overhead legs use, mirrored for a >= floor)
    kratios, kt_dense, kt_block = [], float("inf"), float("inf")
    for _ in range(6):
        tdw = _paged_window(f_dense)
        tbw = _paged_window(f_block)
        kt_dense, kt_block = min(kt_dense, tdw), min(kt_block, tbw)
        kratios.append(tdw / tbw if tbw > 0 else 0.0)
    paged_speedup = max(kratios)
    if paged_speedup < 1.0:
        failures.append(
            f"blockwise paged attention never beat the dense gather at "
            f"seq 1k across {len(kratios)} paired windows (best ratio "
            f"{paged_speedup:.2f}x; dense {kt_dense * 1e3:.2f}ms vs "
            f"blockwise {kt_block * 1e3:.2f}ms): the kernel tier lost "
            "its win (PR 11 regression)")

    int8_engine = LLMEngine(smodel, max_batch_size=4, block_size=4,
                            kv_dtype="int8")
    int8_engine.generate(sprompts[:16], max_new_tokens=6)
    int8_stats = int8_engine.stats()
    if int8_stats["decode_compiles"] != 1:
        failures.append(
            f"int8-KV decode compiled {int8_stats['decode_compiles']}x "
            "across 16 churning streams (must be exactly 1): the scale "
            "side-tables leaked into the compiled shapes "
            "(PR 11 regression)")

    # ---- telemetry plane legs (PR 12 guards) -----------------------------
    # (k) the metrics registry must honor the flight recorder's cost
    # discipline: with FLAGS_metrics OFF every site is one flag check
    # (<3%/step at the observed sites-per-step rate, and NOTHING is
    # recorded); with it ON, the fused train loop and the serve_8-style
    # workload must stay within 5%/step (interleaved min-of-paired-ratio
    # windows, the guardian leg's statistic); and the histogram hot path
    # must not grow memory with observations (bounded bucket bands)
    from paddle_tpu.profiler import metrics as _pm

    _pm.reset_metrics()
    mh = _pm.TRAIN.step_s
    mc = _pm.SERVE.tokens
    N_OBS = 100_000
    t0 = time.perf_counter()
    for _ in range(N_OBS):
        mh.observe(0.001)
        mc.inc()
    obs_off_ns = (time.perf_counter() - t0) / (2 * N_OBS) * 1e9
    if mh.count != 0 or mc.value != 0:
        failures.append(
            f"metrics recorded with FLAGS_metrics off (hist count="
            f"{mh.count}, counter={mc.value}): the gate is broken "
            "(PR 12 regression)")
    # ~6 instrumented sites fire per fused train step (boundary + step
    # hist + gauges); be generous and budget 10
    m_overhead_off = obs_off_ns * 10 / max(t_step * 1e9, 1.0)
    if m_overhead_off >= 0.03:
        failures.append(
            f"metrics-off site cost {obs_off_ns:.0f}ns x 10 sites/step is "
            f"{m_overhead_off * 100:.2f}% of a fused step (>=3%): the "
            "disabled path got expensive (PR 12 regression)")

    # histogram hot path: zero allocation growth (bounded bucket bands)
    set_flags({"FLAGS_metrics": True})
    gh = _pm.LogHistogram(window=5_000)
    gh.observe(0.001)
    import sys as _sys
    band_len0 = len(gh._cur)
    size0 = _sys.getsizeof(gh._cur)
    for i in range(50_000):
        gh.observe(0.0001 * (1 + (i % 97)))
    if len(gh._cur) != band_len0 or _sys.getsizeof(gh._cur) != size0 \
            or (gh._prev is not None and len(gh._prev) != band_len0):
        failures.append(
            "histogram hot path grew its bucket storage under sustained "
            "observation: the bands are no longer preallocated/bounded "
            "(PR 12 regression)")

    # metrics-on cost, fused train loop: interleaved paired windows
    m_step = _loop(step_fused=True)
    for _ in range(WARMUP):
        m_step()
    set_flags({"FLAGS_metrics": False})
    for _ in range(WARMUP):
        m_step()
    mratios = []
    for _ in range(6):
        set_flags({"FLAGS_metrics": False})
        m_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            m_step()
        m_step.sync()
        t_moff = time.perf_counter() - t0
        set_flags({"FLAGS_metrics": True})
        m_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            m_step()
        m_step.sync()
        t_mon = time.perf_counter() - t0
        mratios.append(t_mon / t_moff if t_moff > 0 else float("inf"))
    set_flags({"FLAGS_metrics": False})
    m_overhead_on = min(mratios) - 1.0
    if m_overhead_on >= 0.05:
        failures.append(
            f"FLAGS_metrics costs {m_overhead_on * 100:.1f}%/step on the "
            "fused train loop (>=5%): the armed telemetry plane stopped "
            "being cheap (PR 12 regression)")

    # metrics-on cost, serve_8-style workload (same engine pattern as
    # the resilience leg; programs warm before the windows)
    mengine = LLMEngine(smodel, max_batch_size=4, block_size=4)
    mengine.generate(sprompts8, max_new_tokens=6)
    msratios = []
    for _ in range(6):
        set_flags({"FLAGS_metrics": False})
        t0 = time.perf_counter()
        for p in sprompts8:
            mengine.add_request(p, max_new_tokens=6)
        mengine.run()
        t_soff = time.perf_counter() - t0
        set_flags({"FLAGS_metrics": True})
        t0 = time.perf_counter()
        for p in sprompts8:
            mengine.add_request(p, max_new_tokens=6)
        mengine.run()
        t_son = time.perf_counter() - t0
        msratios.append(t_son / t_soff if t_soff > 0 else float("inf"))
    set_flags({"FLAGS_metrics": False})
    ms_overhead_on = min(msratios) - 1.0
    if ms_overhead_on >= 0.05:
        failures.append(
            f"FLAGS_metrics costs {ms_overhead_on * 100:.1f}%/step on the "
            "serve_8 loop (>=5%): the serving instrumentation stopped "
            "being cheap (PR 12 regression)")
    _pm.reset_metrics()

    # ---- telemetry server leg (PR 13 guard) ------------------------------
    # (l) the live HTTP observability plane: with NO server running,
    # every heartbeat site must be one module-bool check (<3%/step at a
    # generous 4 sites/step) that records NOTHING; with the server armed
    # AND a scraper hitting /metrics + /healthz every 100 ms, the fused
    # train loop and the serve_8 workload must stay within 5%/step
    # (interleaved scraper-paused vs scraping windows, min-of-ratios —
    # the guardian leg's statistic)
    import threading
    import urllib.error
    import urllib.request
    from paddle_tpu.profiler import telemetry_server as _tsrv

    N_BEAT = 200_000
    t0 = time.perf_counter()
    for _ in range(N_BEAT):
        _tsrv.beat("train")
    beat_off_ns = (time.perf_counter() - t0) / N_BEAT * 1e9
    if _tsrv._HEART:
        failures.append(
            "telemetry heartbeat recorded with no server running: the "
            "module-bool gate is broken (PR 13 regression)")
    tel_overhead_off = beat_off_ns * 4 / max(t_step * 1e9, 1.0)
    if tel_overhead_off >= 0.03:
        failures.append(
            f"server-off heartbeat cost {beat_off_ns:.0f}ns x 4 "
            f"sites/step is {tel_overhead_off * 100:.2f}% of a fused "
            "step (>=3%): the disarmed liveness path got expensive "
            "(PR 13 regression)")

    srv = _tsrv.start(port=0)
    scrape_on = threading.Event()
    scrape_stop = threading.Event()
    scrape_errs = []
    scrape_n = [0]

    def _scraper():
        while not scrape_stop.is_set():
            if not scrape_on.is_set():
                time.sleep(0.005)
                continue
            for ep in ("/metrics", "/healthz"):
                try:
                    with urllib.request.urlopen(srv.url + ep,
                                                timeout=5) as r:
                        r.read()
                    scrape_n[0] += 1
                except urllib.error.HTTPError:
                    scrape_n[0] += 1   # 503 healthz is a served scrape
                except Exception as e:
                    scrape_errs.append(repr(e)[:120])
            time.sleep(0.1)

    _sthr = threading.Thread(target=_scraper, daemon=True)
    _sthr.start()
    set_flags({"FLAGS_metrics": True})
    ts_step = _loop(step_fused=True)
    for _ in range(WARMUP):
        ts_step()
    tratios = []
    for _ in range(6):
        scrape_on.clear()
        ts_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            ts_step()
        ts_step.sync()
        t_plain = time.perf_counter() - t0
        scrape_on.set()
        ts_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            ts_step()
        ts_step.sync()
        t_scraped = time.perf_counter() - t0
        tratios.append(t_scraped / t_plain if t_plain > 0
                       else float("inf"))
    tel_train_overhead = min(tratios) - 1.0
    if tel_train_overhead >= 0.05:
        failures.append(
            f"a 100ms-cadence scraper costs "
            f"{tel_train_overhead * 100:.1f}%/step on the fused train "
            "loop (>=5%): the scrape path is taxing the step it watches "
            "(PR 13 regression)")
    tsratios = []
    for _ in range(6):
        scrape_on.clear()
        t0 = time.perf_counter()
        for p in sprompts8:
            mengine.add_request(p, max_new_tokens=6)
        mengine.run()
        t_plain = time.perf_counter() - t0
        scrape_on.set()
        t0 = time.perf_counter()
        for p in sprompts8:
            mengine.add_request(p, max_new_tokens=6)
        mengine.run()
        t_scraped = time.perf_counter() - t0
        tsratios.append(t_scraped / t_plain if t_plain > 0
                        else float("inf"))
    tel_serve_overhead = min(tsratios) - 1.0
    if tel_serve_overhead >= 0.05:
        failures.append(
            f"a 100ms-cadence scraper costs "
            f"{tel_serve_overhead * 100:.1f}%/step on the serve_8 loop "
            "(>=5%) (PR 13 regression)")
    scrape_stop.set()
    scrape_on.set()
    _sthr.join(timeout=10)
    _tsrv.stop()
    set_flags({"FLAGS_metrics": False})
    if scrape_n[0] == 0:
        failures.append(
            "the telemetry scraper never completed a scrape — the leg "
            "guarded nothing (PR 13 guard bug)")
    if len(scrape_errs) > 5:
        failures.append(
            f"{len(scrape_errs)} scrape failures under churn (first: "
            f"{scrape_errs[0]}): the server stopped answering while the "
            "process works (PR 13 regression)")
    _pm.reset_metrics()

    # ---- AOT warm-start leg (PR 9 guard) ---------------------------------
    # (h) a fresh subprocess with a warm executable store must promote its
    # fused step with zero compile activity and beat the cold subprocess's
    # time-to-first-promoted-step
    aot_cold, aot_warm = _aot_warm_start_leg(failures)

    # ---- distributed step fusion leg (PR 10 guard) -----------------------
    # (i) a dp=N sharded-batch loop must promote into ONE shard_map
    # executable (zero retraces after promotion) and beat the same loop on
    # unfused eager dispatch (per-op GSPMD collectives) by the guard ratio
    import jax as _jax
    dp_speedup = 0.0
    dp_retraces = 0
    dp_mesh = None
    if _jax.device_count() >= 2:
        dp_step = _dp_loop(step_fused=False)
        for _ in range(WARMUP):
            dp_step()
        dp_step.sync()
        t_dp_eager = timed(dp_step)
        dp_step = _dp_loop(step_fused=True)
        for _ in range(WARMUP):
            dp_step()
        dp_step.sync()
        s0 = step_fusion_stats()
        t_dp_fused = timed(dp_step)
        s1 = step_fusion_stats()
        from paddle_tpu.ops.step_fusion import step_cache_info
        dp_mesh = next((p["spmd"] for p in step_cache_info()["programs"]
                        if p["spmd"] and not p["dead"]), None)
        dp_replays = min(s1["fused_steps"] - s0["fused_steps"], MEASURE)
        dp_retraces = s1["retraces"] - s0["retraces"]
        dp_speedup = t_dp_eager / t_dp_fused if t_dp_fused > 0 else 0.0
        if dp_mesh is None:
            failures.append(
                "dp sharded-batch loop did not promote through the SPMD "
                f"lowering (promoted={s1['steps_promoted']}, "
                f"splits={s1['fallback_splits']}): the mesh plan was "
                "refused or demoted (PR 10 regression)")
        if dp_replays == 0:
            failures.append(
                "promoted DP step replay rate is zero "
                "(PR 10 regression)")
        if dp_retraces:
            failures.append(
                f"{dp_retraces} post-warmup retrace(s) in the promoted DP "
                "step: the shard_map executable is re-tracing a stable "
                "sharded cycle (PR 10 regression)")
        if dp_replays and dp_speedup < DP_SPEEDUP_GUARD:
            failures.append(
                f"promoted DP step speedup {dp_speedup:.2f}x over unfused "
                f"eager collectives is below the {DP_SPEEDUP_GUARD}x guard "
                f"(eager {t_dp_eager*1e6:.0f}us vs fused "
                f"{t_dp_fused*1e6:.0f}us) (PR 10 regression)")

    # ---- universal promotion leg (PR 14 guards) --------------------------
    # (m) dropout>0 must promote with ZERO steady-state retraces (the
    # hoisted-key path) and beat the chain tier like any promoted step;
    # a k=4 micro-batch accumulation loop must run as a super-cycle —
    # exactly TWO executables (one sub trace + one update trace), zero
    # retraces at steady state, zero splits
    import numpy as _np
    import paddle_tpu as _pd
    import paddle_tpu.nn.functional as _F
    from paddle_tpu.ops.dispatch import clear_dispatch_cache as _cdc
    from paddle_tpu.profiler import reset_step_fusion_stats as _rsfs

    def _drop_loop(step_fused):
        set_flags({"FLAGS_eager_step_fusion": step_fused,
                   "FLAGS_eager_step_fusion_min_count": 5})
        _cdc()
        _pd.seed(0)
        _rng = _np.random.default_rng(0)
        x = _pd.to_tensor(_rng.standard_normal((16, 32))
                          .astype(_np.float32))
        w = _pd.to_tensor(_rng.standard_normal((32, 32))
                          .astype(_np.float32), stop_gradient=False)
        b = _pd.to_tensor(_rng.standard_normal(32).astype(_np.float32),
                          stop_gradient=False)
        opt = _pd.optimizer.SGD(learning_rate=1e-3, parameters=[w, b])

        def step():
            y = _F.dropout(_F.gelu(_pd.add(_pd.matmul(x, w), b)), 0.2)
            y.sum().backward()
            opt.step()
            opt.clear_grad()

        step.sync = lambda: w._value.block_until_ready()
        return step

    drop_chain = _drop_loop(step_fused=False)
    for _ in range(WARMUP):
        drop_chain()
    t_drop_chain = timed(drop_chain)
    drop_step = _drop_loop(step_fused=True)
    for _ in range(WARMUP):
        drop_step()
    s0 = step_fusion_stats()
    t_drop_step = timed(drop_step)
    s1 = step_fusion_stats()
    drop_replays = min(s1["fused_steps"] - s0["fused_steps"], MEASURE)
    drop_retraces = s1["retraces"] - s0["retraces"]
    drop_speedup = t_drop_chain / t_drop_step if t_drop_step > 0 else 0.0
    if drop_replays == 0:
        failures.append(
            "the dropout>0 loop never promoted (hoisted-key regression: "
            f"promoted={s1['steps_promoted']}, "
            f"splits={s1['fallback_splits']}) (PR 14)")
    if drop_retraces:
        failures.append(
            f"{drop_retraces} post-warmup retrace(s) in the promoted "
            "dropout step: the hoisted key is re-tracing (PR 14)")
    if drop_replays and drop_speedup < STEP_SPEEDUP_GUARD:
        failures.append(
            f"promoted dropout step speedup {drop_speedup:.2f}x below "
            f"the {STEP_SPEEDUP_GUARD}x guard (chain "
            f"{t_drop_chain*1e6:.0f}us vs fused "
            f"{t_drop_step*1e6:.0f}us) (PR 14)")

    set_flags({"FLAGS_eager_step_fusion": True,
               "FLAGS_eager_step_fusion_min_count": 5})
    _cdc()
    _rsfs()
    _pd.seed(0)
    _rng = _np.random.default_rng(0)
    ax = _pd.to_tensor(_rng.standard_normal((16, 32)).astype(_np.float32))
    aw = _pd.to_tensor(_rng.standard_normal((32, 32)).astype(_np.float32),
                       stop_gradient=False)
    ab = _pd.to_tensor(_rng.standard_normal(32).astype(_np.float32),
                       stop_gradient=False)
    aopt = _pd.optimizer.SGD(learning_rate=1e-3, parameters=[aw, ab])

    def _accum_cycle(k=4):
        for _ in range(k):
            y = _F.gelu(_pd.add(_pd.matmul(ax, aw), ab))
            y.sum().backward()
        aopt.step()
        aopt.clear_grad()

    for _ in range(12):
        _accum_cycle()
    sa = step_fusion_stats()
    accum_fused0 = sa["fused_steps"]
    accum_retraces = sa["retraces"]
    for _ in range(8):
        _accum_cycle()
    sb = step_fusion_stats()
    if sa["steps_promoted"] != 1 or sb["fused_steps"] - accum_fused0 < 8:
        failures.append(
            "the k=4 accumulation loop did not promote as a super-cycle "
            f"(promoted={sb['steps_promoted']}, "
            f"fused={sb['fused_steps']}, splits={sb['fallback_splits']}) "
            "(PR 14)")
    if accum_retraces > 2:
        failures.append(
            f"the super-cycle compiled {accum_retraces} executables "
            "(> 2: sub + update) (PR 14)")
    if sb["retraces"] != accum_retraces:
        failures.append(
            f"{sb['retraces'] - accum_retraces} steady-state retrace(s) "
            "in the super-cycle (PR 14)")
    if sb["fallback_splits"]:
        failures.append(
            f"{sb['fallback_splits']} split(s) in the steady accumulation "
            "loop (PR 14)")

    # ---- hybrid pipeline promotion leg (PR 16 guard) ---------------------
    # (n) a pp=2 x virtual=2 interleaved pipeline cycle must promote
    # through the ops/spmd_fusion pipeline registry (ONE ppermute-handoff
    # executable spanning fill/steady/drain + update), replay it on every
    # train_batch with zero steady-state retraces, and beat the same
    # schedule run unfused and eager (forward_backward_pipeline:
    # sequential micro-batch accumulation) by the guard ratio
    pp_speedup = 0.0
    pp_retraces = 0
    pp_promoted = 0
    if _jax.device_count() >= 2:
        import jax.numpy as _jnp
        from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer, PipelineParallel)
        from paddle_tpu.incubate.models import (
            GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
            gpt_pipeline_layers)
        from paddle_tpu.ops.spmd_fusion import clear_pipeline_programs

        # eager tiers off both sides: the registry owns promotion on the
        # fused side, and the eager side is the pure per-op schedule
        set_flags({"FLAGS_eager_op_cache": False,
                   "FLAGS_eager_chain_fusion": False,
                   "FLAGS_eager_step_fusion": False})
        _cdc()
        clear_pipeline_programs()
        _ppcfg = GPTConfig(vocab_size=128, hidden_size=32,
                           num_hidden_layers=8, num_attention_heads=4,
                           intermediate_size=64,
                           max_position_embeddings=32,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           use_flash_attention=False)
        _pprng = _np.random.default_rng(0)
        pids = _jnp.asarray(_pprng.integers(0, 128, (4, 32)), _jnp.int32)
        plab = _jnp.asarray(_pprng.integers(0, 128, (4, 32)), _jnp.int32)

        def _pp_runner():
            _pd.seed(0)
            model = GPTForCausalLM(_ppcfg)
            pl = PipelineLayer(gpt_pipeline_layers(model), num_stages=2,
                               loss_fn=GPTPretrainingCriterion(),
                               num_virtual_pipeline_stages=2)
            runner = PipelineParallel(pl, hcg=None)
            runner.accumulate_steps = 4
            opt = _pd.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=model.parameters())
            return runner, opt

        PP_STEPS = 6
        set_global_mesh(None)                 # unfused eager schedule
        runner, opt = _pp_runner()
        float(runner.train_batch((pids, plab), opt))
        t0 = time.perf_counter()
        for _ in range(2):
            float(runner.train_batch((pids, plab), opt))
        t_pp_eager = (time.perf_counter() - t0) / 2

        set_global_mesh(build_mesh(dp=1, pp=2, sharding=1, sep=1, mp=1,
                                   devices=_jax.devices()[:2]))
        runner, opt = _pp_runner()
        s0 = step_fusion_stats()
        for _ in range(3):                    # warmup: trace + compile
            float(runner.train_batch((pids, plab), opt))
        s1 = step_fusion_stats()
        pp_promoted = s1["steps_promoted"] - s0["steps_promoted"]
        t0 = time.perf_counter()
        for _ in range(PP_STEPS):
            float(runner.train_batch((pids, plab), opt))
        t_pp_fused = (time.perf_counter() - t0) / PP_STEPS
        s2 = step_fusion_stats()
        pp_retraces = s2["retraces"] - s1["retraces"]
        pp_fires = s2["fused_steps"] - s1["fused_steps"]
        pp_speedup = t_pp_eager / t_pp_fused if t_pp_fused > 0 else 0.0
        set_global_mesh(None)
        clear_pipeline_programs()
        if pp_promoted != 1:
            failures.append(
                f"the pp=2 interleaved cycle promoted {pp_promoted} "
                "pipeline program(s) (expected exactly 1) — train_batch "
                "fell off the registry path (PR 16 regression)")
        if pp_fires != PP_STEPS:
            failures.append(
                f"only {pp_fires}/{PP_STEPS} train_batch calls fired the "
                "promoted pipeline executable (PR 16 regression)")
        if pp_retraces:
            failures.append(
                f"{pp_retraces} steady-state retrace(s) in the promoted "
                "pipeline cycle: the handoff program is re-tracing a "
                "stable schedule (PR 16 regression)")
        if pp_promoted and pp_speedup < PP_SPEEDUP_GUARD:
            failures.append(
                f"promoted pipeline cycle speedup {pp_speedup:.2f}x over "
                "the unfused eager schedule is below the "
                f"{PP_SPEEDUP_GUARD}x guard (eager "
                f"{t_pp_eager*1e3:.1f}ms vs fused {t_pp_fused*1e3:.1f}ms) "
                "(PR 16 regression)")

    # ---- multi-tenant serving leg (PR 17 guards) -------------------------
    # (o) 64 streams over 8 tenants (base + 7 LoRA slots) share a system
    # prompt through the prefix cache while a tenant departs, a new one
    # lands in the freed slot, and ONE live weight hot-swap cuts over
    # mid-run: the decode executable must still compile exactly once —
    # the adapter stacks and the swapped params are VALUE edits to fixed
    # shapes, never new programs
    paddle.seed(0)
    tmodel = GPTForCausalLM(scfg)
    tmodel.eval()
    teng = LLMEngine(tmodel, max_batch_size=4, block_size=4,
                     enable_prefix_cache=True, max_adapters=7,
                     adapter_rank=2, hot_swap=True)
    tnames = [None] + [f"t{i}" for i in range(1, 8)]
    for i in range(1, 8):
        teng.register_adapter(f"t{i}", seed=i, scale=4.0)
    trng = np.random.default_rng(17)
    tsys = trng.integers(0, 128, 12).tolist()
    ttails = [trng.integers(0, 128, int(n)).tolist()
              for n in trng.integers(3, 8, 64)]
    for i, tail in enumerate(ttails[:32]):
        teng.add_request(tsys + tail, max_new_tokens=6,
                         adapter=tnames[i % 8])
    teng.run()
    # tenant churn between phases: a drained tenant departs, a new one
    # takes the freed slot
    teng.unregister_adapter("t7")
    teng.register_adapter("t8", seed=11, scale=4.0)
    for i, tail in enumerate(ttails[32:]):
        name = tnames[i % 8]
        teng.add_request(tsys + tail, max_new_tokens=6,
                         adapter="t8" if name == "t7" else name)
    for _ in range(3):                       # streams mid-flight
        teng.step()
    teng.swap_weights([np.asarray(p._value) * np.float32(1.0001)
                       for p in tmodel.parameters()])
    teng.run()
    tstats = teng.stats()
    if tstats["decode_compiles"] != 1:
        failures.append(
            f"tenant decode compiled {tstats['decode_compiles']}x across "
            "64 streams / 8 tenants with adapter churn and a live weight "
            "swap (must be exactly 1): tenancy leaked into the decode "
            "shapes (PR 17 regression)")
    if tstats["weight_swaps"] != 1:
        failures.append(
            f"{tstats['weight_swaps']} weight swap(s) committed "
            "(expected 1): the staged cutover did not land "
            "(PR 17 regression)")
    if tstats["adapter_switches"] < 1:
        failures.append(
            "zero adapter switches across a round-robin 8-tenant mix: "
            "slot routing is not reaching the decode batch "
            "(PR 17 regression)")
    if tstats["prefix_hit_tokens"] <= 0:
        failures.append(
            "zero prefix-hit tokens with a 12-token shared system "
            "prompt across 64 streams: the prefix cache never aliased "
            "(PR 17 regression)")

    # prefix-hit steady state vs cold prefill: interleaved windows over
    # the SAME prompt (min-of-paired-ratios, the guardian-leg statistic —
    # a load spike hits both engines, a real regression inflates every
    # pair). A prefix hit skips prefill ENTIRELY — the stream joins the
    # decode batch at cached_len = hit — so the guarded quantity is a
    # whole prefill vs slot bookkeeping. Measured on a wider model with
    # a long shared prompt so prefill compute dominates the window, and
    # the prompt is 1 past a block boundary (4*64+1) so the hit covers
    # exactly the full blocks and the first KV write lands in a fresh
    # private block — a block-interior hit would COW the tail block
    # every window and measure pool copies instead of aliasing
    paddle.seed(0)
    pcfg = GPTConfig(vocab_size=128, hidden_size=128,
                     num_hidden_layers=2, num_attention_heads=4,
                     intermediate_size=256, max_position_embeddings=272,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0,
                     use_flash_attention=False)
    pmodel = GPTForCausalLM(pcfg)
    pmodel.eval()
    pprompt = srng.integers(0, 128, 257).tolist()
    hot_eng = LLMEngine(pmodel, max_batch_size=4, block_size=4,
                        num_blocks=512, enable_prefix_cache=True)
    cold_eng = LLMEngine(pmodel, max_batch_size=4, block_size=4,
                         num_blocks=512)

    def _prefill_window(eng):
        for _ in range(4):
            eng.add_request(pprompt, max_new_tokens=1)
        eng.run()

    _prefill_window(hot_eng)      # compiles + publishes the prefix
    _prefill_window(cold_eng)
    pratios = []
    for _ in range(6):
        t0 = time.perf_counter()
        _prefill_window(cold_eng)
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        _prefill_window(hot_eng)
        t_hot = time.perf_counter() - t0
        pratios.append(t_cold / t_hot if t_hot > 0 else float("inf"))
    prefix_speedup = min(pratios)
    if prefix_speedup < PREFIX_SPEEDUP_GUARD:
        failures.append(
            f"prefix-hit prefill is only {prefix_speedup:.2f}x the cold "
            f"prefill (>= {PREFIX_SPEEDUP_GUARD}x required): shared-"
            "prefix streams are re-running prefill compute they should "
            "alias (PR 17 regression)")
    if hot_eng.stats()["prefix_hit_rate"] <= 0:
        failures.append(
            "hot engine reports a zero prefix hit rate on a repeated "
            "identical prompt (PR 17 regression)")

    # ---- compiled sampling + pipelined decode legs (PR 18 guards) --------
    # (p1) 64 streams churn through 4 slots with HETEROGENEOUS sampler
    # configs — greedy, temperature-only, top-k, top-p, penalties, per-
    # request seeds, all mixed in the same running batch — and the decode
    # executable must still compile exactly once: sampler params are VALUE
    # buffers of the one program, never structure
    paddle.seed(0)
    samp_eng = LLMEngine(smodel, max_batch_size=4, block_size=4)
    samp_cfgs = [dict(),                                     # greedy slot
                 dict(temperature=0.7),
                 dict(temperature=0.9, top_k=20),
                 dict(temperature=0.8, top_p=0.9),
                 dict(temperature=1.0, top_k=12, top_p=0.95,
                      repetition_penalty=1.2)]
    for i, p in enumerate(sprompts):
        kw = dict(samp_cfgs[i % len(samp_cfgs)])
        if kw:
            kw["seed"] = 1000 + i
        samp_eng.add_request(p, max_new_tokens=6, **kw)
    samp_eng.run()
    samp_stats = samp_eng.stats()
    if samp_stats["decode_compiles"] != 1:
        failures.append(
            f"decode compiled {samp_stats['decode_compiles']}x across 64 "
            "churning streams with mixed sampler configs (must be exactly "
            "1): sampler params leaked into the decode structure "
            "(PR 18 regression)")
    if samp_stats["sampled_tokens"] <= 0:
        failures.append(
            "zero sampled tokens across a mixed greedy/stochastic stream "
            "churn: the stochastic path never ran (PR 18 regression)")

    # (p2) the sampler head must stay cheap: interleaved greedy/sampled
    # windows on a forward-dominated model (hidden 640 — the head's fixed
    # sort+gumbel cost has real FLOPs to amortize against), min-of-paired-
    # ratios (the prefix-leg statistic: a load spike lands on both
    # windows, a real regression inflates every pair)
    paddle.seed(0)
    samp_cfg2 = GPTConfig(vocab_size=128, hidden_size=640,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=1280,
                          max_position_embeddings=128,
                          hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0,
                          use_flash_attention=False)
    samp_model2 = GPTForCausalLM(samp_cfg2)
    samp_model2.eval()
    ov_eng = LLMEngine(samp_model2, max_batch_size=8, block_size=8,
                       max_context=96)
    ov_prompts = [srng.integers(0, 128, 4).tolist() for _ in range(8)]
    ov_eng.generate(ov_prompts, max_new_tokens=3)          # warm greedy

    def _sampler_window(temp, n_new=16):
        for i, p in enumerate(ov_prompts):
            kw = dict(max_new_tokens=n_new)
            if temp > 0:
                kw.update(temperature=temp, top_k=20, top_p=0.9,
                          seed=11 + i)
            ov_eng.add_request(p, **kw)
        t0 = time.perf_counter()
        ov_eng.run()
        return time.perf_counter() - t0

    _sampler_window(0.9, 4)                                # warm sampled
    sratios = []
    for _ in range(5):
        t_greedy = _sampler_window(0.0)
        t_sampled = _sampler_window(0.9)
        sratios.append(t_sampled / t_greedy if t_greedy > 0
                       else float("inf"))
    sampled_overhead = min(sratios) - 1.0
    if sampled_overhead > SAMPLED_OVERHEAD_GUARD:
        failures.append(
            f"sampled decode costs {sampled_overhead * 100:.1f}%/step "
            f"over greedy (> {SAMPLED_OVERHEAD_GUARD * 100:.0f}%): the "
            "sampler head is no longer a rounding error next to the "
            "forward — a sort fell out of the shared pass or the "
            "stochastic branch runs for greedy batches "
            "(PR 18 regression)")
    if ov_eng.stats()["decode_compiles"] != 1:
        failures.append(
            "the sampled-overhead windows retraced the decode program "
            "(PR 18 regression)")

    # (p3) lag-1 pipelined decode vs unpipelined, serve_8 windows whose
    # per-token commit BLOCKS the host (time.sleep — a stream-write /
    # slow-client stand-in that frees the core, which is the only thing a
    # 1-core CI box can genuinely overlap; on an accelerator the same
    # pipeline overlaps ALL host work with off-host device compute).
    # Interleaved min-of-ratios: every round must clear the bar
    paddle.seed(0)
    pipe_cfg = GPTConfig(vocab_size=128, hidden_size=256,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=512,
                         max_position_embeddings=128,
                         hidden_dropout_prob=0.0,
                         attention_probs_dropout_prob=0.0,
                         use_flash_attention=False)
    pipe_model = GPTForCausalLM(pipe_cfg)
    pipe_model.eval()

    def _blocking_sink(req, tok, text):
        time.sleep(0.0006)

    def _mk_pipe_eng(pipelined):
        e = LLMEngine(pipe_model, max_batch_size=8, block_size=8,
                      num_blocks=256, max_context=96,
                      pipeline_decode=pipelined)
        e.generate(ov_prompts, max_new_tokens=3)           # warm programs
        return e

    unpipe_eng = _mk_pipe_eng(False)
    pipe_eng = _mk_pipe_eng(True)

    def _pipe_window(eng, n_new=20):
        for i, p in enumerate(ov_prompts):
            eng.add_request(p, max_new_tokens=n_new, temperature=0.9,
                            top_k=20, top_p=0.9, seed=31 + i,
                            on_token=_blocking_sink)
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0

    _pipe_window(unpipe_eng)
    _pipe_window(pipe_eng)
    pipe_ratios = []
    for _ in range(6):
        t_unpipe = _pipe_window(unpipe_eng)
        t_pipe = _pipe_window(pipe_eng)
        pipe_ratios.append(t_unpipe / t_pipe if t_pipe > 0
                           else float("inf"))
    pipe_speedup = min(pipe_ratios)
    if pipe_speedup < PIPELINE_SPEEDUP_GUARD:
        failures.append(
            f"pipelined decode is only {pipe_speedup:.2f}x the "
            f"unpipelined engine on the blocked-host serve_8 windows "
            f"(>= {PIPELINE_SPEEDUP_GUARD}x required): the launch path "
            "re-synchronized — commit work no longer overlaps the "
            "in-flight step (PR 18 regression)")
    pipe_stats = pipe_eng.stats()
    if pipe_stats["decode_compiles"] != 1:
        failures.append(
            f"pipelined decode compiled {pipe_stats['decode_compiles']}x "
            "(must be exactly 1): the feedback path leaked into the "
            "decode structure (PR 18 regression)")
    if pipe_stats["commit_rollbacks"] != 0:
        failures.append(
            f"{pipe_stats['commit_rollbacks']} commit rollback(s) on a "
            "cancel-free pipelined workload (expected 0): the lag-1 "
            "boundary is discarding healthy streams (PR 18 regression)")

    # ---- regression sentinel leg (PR 19 guards) --------------------------
    # (q) the perf regression sentinel must honor the flight recorder's
    # cost discipline: DISARMED, every tick site is one module-bool check
    # (<3%/step at a generous 4 sites/step, and no windows are opened);
    # ARMED (short evaluation windows, so the probe/classify path really
    # runs inside the measured loops), the fused train loop and the
    # serve_8 workload must each stay within 3%/step — interleaved
    # disarmed-vs-armed min-of-paired-ratio windows with the metrics +
    # events planes ON in both (their cost is budgeted by legs (d)/(k);
    # this measures the sentinel's MARGINAL cost). Finally the leg gates
    # its own whole-run record against the checked-in perf baseline —
    # perf_smoke is itself a baselined leg.
    import json

    from paddle_tpu.profiler import sentinel as _snt

    _snt.disarm()
    N_TICK = 200_000
    t0 = time.perf_counter()
    for _ in range(N_TICK):
        _snt.tick()
    tick_off_ns = (time.perf_counter() - t0) / N_TICK * 1e9
    if _snt.SENTINEL.snapshot()["windows"] != 0:
        failures.append(
            "disarmed sentinel ticks opened evaluation windows: the "
            "module-bool gate is broken (PR 19 regression)")
    snt_overhead_off = tick_off_ns * 4 / max(t_step * 1e9, 1.0)
    if snt_overhead_off >= 0.03:
        failures.append(
            f"disarmed sentinel tick cost {tick_off_ns:.0f}ns x 4 "
            f"sites/step is {snt_overhead_off * 100:.2f}% of a fused "
            "step (>=3%): the disarmed watcher got expensive "
            "(PR 19 regression)")

    set_flags({"FLAGS_metrics": True, "FLAGS_profiler_events": True})
    q_step = _loop(step_fused=True)
    for _ in range(WARMUP):
        q_step()
    qratios = []
    for _ in range(6):
        _snt.disarm()
        q_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            q_step()
        q_step.sync()
        t_qoff = time.perf_counter() - t0
        _snt.arm(window_s=0.2)
        q_step.sync()
        t0 = time.perf_counter()
        for _ in range(MEASURE):
            q_step()
        q_step.sync()
        t_qon = time.perf_counter() - t0
        qratios.append(t_qon / t_qoff if t_qoff > 0 else float("inf"))
    _snt.disarm()
    snt_train_overhead = min(qratios) - 1.0
    if snt_train_overhead >= 0.03:
        failures.append(
            f"the armed sentinel costs {snt_train_overhead * 100:.1f}%"
            "/step on the fused train loop (>=3%): the window "
            "probe/classify path is taxing the step it watches "
            "(PR 19 regression)")

    qsratios = []
    for _ in range(6):
        _snt.disarm()
        t0 = time.perf_counter()
        for p in sprompts8:
            mengine.add_request(p, max_new_tokens=6)
        mengine.run()
        t_qsoff = time.perf_counter() - t0
        _snt.arm(window_s=0.2)
        t0 = time.perf_counter()
        for p in sprompts8:
            mengine.add_request(p, max_new_tokens=6)
        mengine.run()
        t_qson = time.perf_counter() - t0
        qsratios.append(t_qson / t_qsoff if t_qsoff > 0
                        else float("inf"))
    _snt.disarm()
    set_flags({"FLAGS_metrics": False, "FLAGS_profiler_events": False})
    snt_serve_overhead = min(qsratios) - 1.0
    if snt_serve_overhead >= 0.03:
        failures.append(
            f"the armed sentinel costs {snt_serve_overhead * 100:.1f}%"
            "/step on the serve_8 loop (>=3%) (PR 19 regression)")

    # the self-gate: this very run's whole-process record must sit inside
    # the checked-in perf_smoke bands (tools/perf_baselines.json — the
    # same add/match/expire hygiene as the fusion-lint baseline)
    smoke_rec = _snt.capture_record("perf_smoke", kind="mixed")
    print(json.dumps({"event": "sentinel_record", "record": smoke_rec}),
          flush=True)
    from paddle_tpu.profiler.sentinel import (DEFAULT_PERF_BASELINE,
                                              PerfBaseline)
    if not os.path.exists(DEFAULT_PERF_BASELINE):
        failures.append(
            "tools/perf_baselines.json is missing: the perf_smoke leg "
            "has no bands to gate against (PR 19 regression)")
    else:
        _blq = PerfBaseline.load(DEFAULT_PERF_BASELINE)
        _viol, _passed, _unb = _blq.split([smoke_rec])
        for _rec, _fs in _viol:
            failures.append(
                f"perf_smoke's own sentinel record violates its "
                f"checked-in bands: {_fs[0]['reason']} — "
                f"{_fs[0]['message']} (PR 19 regression — or a real "
                "drift; re-seed deliberately with tools/perf_baseline.py "
                "--write-baseline)")
        if _unb:
            failures.append(
                "perf_smoke has no entry in tools/perf_baselines.json: "
                "seed it with tools/perf_baseline.py --write-baseline "
                "(PR 19 regression)")

    print(f"perf_smoke: post-warmup retraces={retraces}, "
          f"chain replays={chain_replays}/{MEASURE}, "
          f"fused steps={step_replays}/{MEASURE} "
          f"(step retraces={step_retraces}), "
          f"step-vs-chain speedup={speedup:.2f}x, "
          f"launches_saved={s1['launches_saved'] - s0['launches_saved']}, "
          f"splits={len(split_events)} (steady={len(steady_splits)}, "
          f"unexplained={len(unexplained)}), "
          f"events-off emit={emit_off_ns:.0f}ns "
          f"({overhead_frac * 100:.3f}%/step), "
          f"guardian overhead={guard_median * 100:.1f}%/step (median; "
          f"min {guard_overhead * 100:.1f}%), "
          f"AMP fused steps={amp_replays}/{MEASURE} "
          f"(retraces={amp_retraces}), "
          f"serve decode compiles={sstats['decode_compiles']} "
          f"occupancy={sstats['occupancy_saturated']:.2f} "
          f"({sstats['completed']} streams), "
          f"resilience overhead={resil_overhead * 100:.1f}%/step "
          f"(churn compiles={cstats['decode_compiles']}, "
          f"cancelled={cstats['cancelled']} expired={cstats['expired']} "
          f"refused={refused} resumed={len(resumed)}), "
          f"paged blockwise-vs-dense={paged_speedup:.2f}x "
          f"(int8 decode compiles={int8_stats['decode_compiles']}), "
          f"metrics off={obs_off_ns:.0f}ns/site "
          f"({m_overhead_off * 100:.2f}%/step) "
          f"on={m_overhead_on * 100:.1f}%/step train "
          f"{ms_overhead_on * 100:.1f}%/step serve, "
          f"telemetry beat-off={beat_off_ns:.0f}ns "
          f"scraped={tel_train_overhead * 100:.1f}%/step train "
          f"{tel_serve_overhead * 100:.1f}%/step serve "
          f"({scrape_n[0]} scrapes), "
          f"aot warm-start={aot_warm['t_first_fire_s']:.2f}s vs "
          f"cold={aot_cold['t_first_fire_s']:.2f}s "
          f"(warm hits={aot_warm['aot']['hits']} "
          f"retraces={aot_warm['dispatch_retraces']}"
          f"+{aot_warm['step_retraces']}), "
          f"dp mesh={dp_mesh} speedup={dp_speedup:.2f}x "
          f"(retraces={dp_retraces}), "
          f"dropout fused={drop_replays}/{MEASURE} "
          f"speedup={drop_speedup:.2f}x (retraces={drop_retraces}), "
          f"accum super-cycle fused={sb['fused_steps']} "
          f"executables={accum_retraces} splits={sb['fallback_splits']}, "
          f"pp pipeline promotes={pp_promoted} "
          f"speedup={pp_speedup:.2f}x (retraces={pp_retraces}), "
          f"tenant decode compiles={tstats['decode_compiles']} "
          f"(swaps={tstats['weight_swaps']} "
          f"switches={tstats['adapter_switches']} "
          f"prefix hit_tokens={tstats['prefix_hit_tokens']}), "
          f"prefix prefill speedup={prefix_speedup:.2f}x, "
          f"mixed-sampler churn compiles={samp_stats['decode_compiles']} "
          f"(sampled_tokens={samp_stats['sampled_tokens']}), "
          f"sampled overhead={sampled_overhead * 100:.1f}%/step, "
          f"pipelined speedup={pipe_speedup:.2f}x "
          f"(rollbacks={pipe_stats['commit_rollbacks']}), "
          f"sentinel tick-off={tick_off_ns:.0f}ns "
          f"armed={snt_train_overhead * 100:.1f}%/step train "
          f"{snt_serve_overhead * 100:.1f}%/step serve "
          f"(record leg={smoke_rec['leg']} kind={smoke_rec['kind']})")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print("perf_smoke: OK")
    return 0


if __name__ == "__main__":
    # the distributed leg needs the emulated multi-device mesh; must land
    # before the first jax import (tests/conftest.py does the same for
    # the pytest-marked legs)
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = \
            (_flags + " --xla_force_host_platform_device_count=8").strip()
    if "--aot-child" in sys.argv:
        import argparse
        ap = argparse.ArgumentParser()
        ap.add_argument("--aot-child", action="store_true")
        ap.add_argument("--aot-dir", required=True)
        ap.add_argument("--out", required=True)
        ap.add_argument("--steps", type=int, default=12)
        a = ap.parse_args()
        sys.exit(aot_child_main(a.aot_dir, a.out, a.steps))
    sys.exit(main())
