"""Benchmark suite: one JSON line per config, headline (GPT-2 train) LAST.

Configs (BASELINE.md):
  2:  GPT-2 124M train   — tokens/s/chip + MFU (target 0.45)
  2b: GPT-2 355M train   — tokens/s/chip + MFU (target 0.45)
  2c: GPT-2 seq-4096 flash-attention train — tokens/s/chip + MFU
  5:  ViT-L/16 train     — images/s, fused vs unfused (fused >= unfused)
  serving: GPT-2 decode  — ms/step, compiled per-token program (<= 0.08 ms)
  serve_1/8/64: continuous-batching engine (paddle_tpu.serving.LLMEngine)
      — tokens/s + p50/p99 step ms at 1/8/64 concurrent mixed-length
      streams through ONE compiled decode executable (paged KV cache;
      decode_compiles in the record must stay 0 in the measured window)

One process per chip: the chip belongs to the process that first touches
JAX, so the parent never imports it and runs ONE child per config.

  parent (no jax import, pure orchestration)
    └─ `bench.py --config NAME`  one subprocess per config, each with a
          hard timeout budgeted against a global wall-clock deadline
          (BENCH_BUDGET_S, default 840 s); on a timeout the parent scrapes
          the child's telemetry server before killing it

A leg measures the chip or fails: a child that finds no TPU exits
non-zero, a failed or hung leg is recorded as an error and is not run
again anywhere else, and the parent exits non-zero when any leg failed.
Every record carries a top-level "platform". Reference analog for the
harness: profiler/timer.py ips + operators/benchmark/op_tester.cc.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "bench_traces")

def peak_flops_per_chip():
    """bf16 peak for the local chip — the goodput accountant's table
    (profiler/goodput.py) is the single source of truth, so the bench's
    MFU and the live registry's MFU divide by the same denominator."""
    from paddle_tpu.profiler.goodput import peak_flops_per_chip as peak
    return peak()


def _trace(config_name, platform, fn):
    """Run fn() under the jax profiler, writing an xplane trace artifact."""
    import jax
    tdir = os.path.join(TRACE_ROOT, platform, config_name)
    os.makedirs(tdir, exist_ok=True)
    with jax.profiler.trace(tdir):
        fn()
    return tdir


# --------------------------------------------------------------------------
# GPT training configs (124M headline, 355M, seq-4096 flash)
# --------------------------------------------------------------------------

def _gpt_train_record(metric, cfg, batch, steps, seq, on_tpu, trace_tag):
    # each config runs in its own subprocess, but reset anyway so the
    # record's dispatch_cache / chain_fusion blocks cover exactly this run
    # (retries incl.)
    from paddle_tpu.profiler import (reset_dispatch_cache_stats,
                                     reset_chain_fusion_stats,
                                     reset_step_fusion_stats,
                                     clear_fusion_events)
    from paddle_tpu.framework.flags import get_flags, set_flags
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    # fusion flight recorder armed for the whole run: the headline embeds
    # the split-reason telemetry (fusion_events block) so every BENCH
    # round records WHY any split/bypass happened, not just how many.
    # try/finally restores the PRIOR value — a raise mid-run must not
    # leave the recorder armed, nor may a finished run disarm a user's
    # globally-enabled recorder
    clear_fusion_events()
    # telemetry plane armed for the run (PR 12): the headline's MFU /
    # tokens-per-second are READ BACK from the goodput accountant +
    # metrics registry — bench numbers and production numbers are the
    # same computation by construction
    from paddle_tpu.profiler.metrics import reset_metrics
    reset_metrics()
    prev = get_flags(["FLAGS_profiler_events", "FLAGS_metrics"])
    set_flags({"FLAGS_profiler_events": True, "FLAGS_metrics": True})
    try:
        return _gpt_train_measured(metric, cfg, batch, steps, seq, on_tpu,
                                   trace_tag)
    finally:
        set_flags(prev)


def _gpt_train_measured(metric, cfg, batch, steps, seq, on_tpu, trace_tag):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import (GPTForCausalLM,
                                            GPTPretrainingCriterion)
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    n_params = model.num_params()
    if on_tpu:
        model.bfloat16()            # bf16 weights; f32 master in AdamW
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 multi_precision=on_tpu)
    criterion = GPTPretrainingCriterion()
    step = TrainStep(model, lambda logits, y: criterion(logits, y), opt,
                     donate="all")

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    x = paddle.Tensor(ids, stop_gradient=True)
    y = paddle.Tensor(labels, stop_gradient=True)

    from paddle_tpu.profiler.goodput import ACCOUNTANT as _acct
    flops_per_token = model.flops_per_token(seq, training=True)

    float(step(x, y))                   # warmup / compile
    # fresh accountant window over exactly the measured steps: the
    # registry's rolling MFU/tokens-per-second below IS the headline
    _acct.reset(warm=True)
    _acct.set_flops_per_step(flops_per_token * batch * seq,
                             tokens=batch * seq,
                             peak=peak_flops_per_chip())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    final = float(loss)                 # blocks on the last step
    _acct.finalize()                    # tail device time joins the window
    elapsed = time.perf_counter() - t0

    goodput = _acct.snapshot()
    tokens_per_sec = goodput["tokens_per_sec"]
    mfu = goodput["mfu"]
    # offline cross-check (the pre-PR 12 computation): the live registry
    # number must stay within a few percent of it — tests assert 2%
    offline_tps = batch * seq * steps / elapsed
    mfu_offline = offline_tps * flops_per_token / peak_flops_per_chip()

    platform = jax.devices()[0].platform
    tdir = _trace(trace_tag, platform, lambda: float(step(x, y)))

    # eager-dispatch cache + chain-fusion + whole-step-fusion telemetry
    # (hits/misses/retraces, fused replays/splits/launches saved): future
    # BENCH rounds diff these blocks to catch retrace and fusion
    # regressions (step_fusion stays zero on the explicit TrainStep path —
    # nonzero values here would mean eager leaked into the compiled loop)
    from paddle_tpu.profiler import (dispatch_cache_stats,
                                     chain_fusion_stats, step_fusion_stats,
                                     aot_cache_stats, events_summary,
                                     fusion_events)
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.ops.guardian import guardian_stats as _guardian_stats
    ev = fusion_events()
    doctor = explain(ev)

    return {
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "platform": platform,
        "extra": {"mfu": round(mfu, 4), "loss": round(final, 3),
                  # offline cross-check of the registry-read MFU (same
                  # formula bench used before the telemetry plane)
                  "mfu_offline": round(mfu_offline, 4),
                  "tokens_per_sec_offline": round(offline_tps, 1),
                  # live accountant view: goodput + wall-time buckets +
                  # step-time percentiles for this exact window
                  "goodput": goodput,
                  "batch": batch, "seq": seq, "params": n_params,
                  "platform": platform, "trace": tdir,
                  "dispatch_cache": dispatch_cache_stats(),
                  "chain_fusion": chain_fusion_stats(),
                  "step_fusion": step_fusion_stats(),
                  # persistent AOT executable store (FLAGS_aot_cache):
                  # all-zero unless the config armed it — nonzero hits
                  # mean this bench process warm-started off disk
                  "aot_cache": aot_cache_stats(),
                  # non-finite step guardian (FLAGS_check_numerics):
                  # all-zero unless the config armed it — nonzero
                  # steps_skipped on a clean bench run means the model
                  # itself is producing non-finite grads
                  "guardian": _guardian_stats(),
                  # split-reason attribution (fusion flight recorder):
                  # per-category event counts + (category, reason, op)
                  # tables, and the doctor's one-line verdict
                  "fusion_events": events_summary(ev),
                  "fusion_doctor": {"verdict": doctor["verdict"],
                                    "headline": doctor["headline"]}},
    }


def bench_gpt2_train(on_tpu):
    from paddle_tpu.incubate.models import gpt2_124m
    seq = 1024
    # batch sweep on v5e with the Pallas flash fwd+bwd path (2026-07):
    # 8 -> 108.7k, 16 -> 111.5k, 24 -> 110.8k, 32 -> 103.8k tok/s
    batch = 16 if on_tpu else 2
    steps = 10 if on_tpu else 2
    cfg = gpt2_124m(hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    max_position_embeddings=seq)
    if not on_tpu:
        from paddle_tpu.incubate.models import GPTConfig
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=256,
                        max_position_embeddings=seq, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
    return _gpt_train_record("gpt2_124m_train_tokens_per_sec_per_chip",
                             cfg, batch, steps, seq, on_tpu, "gpt2_train")


def bench_gpt2_355m(on_tpu):
    """GPT-2 355M: bf16 weights + f32 AdamW masters ≈ 5 GB — fits v5e HBM.
    BASELINE north-star ramp config 2→4."""
    from paddle_tpu.incubate.models import gpt2_355m, GPTConfig
    seq = 1024
    if on_tpu:
        cfg = gpt2_355m(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        max_position_embeddings=seq)
        batch, steps = 8, 8
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_hidden_layers=4,
                        num_attention_heads=4, intermediate_size=256,
                        max_position_embeddings=seq, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        batch, steps = 2, 2
    return _gpt_train_record("gpt2_355m_train_tokens_per_sec_per_chip",
                             cfg, batch, steps, seq, on_tpu, "gpt2_355m")


def bench_accum4(on_tpu):
    """Grad-accumulation train leg (universal promotion): a dropout>0 GPT
    trained EAGERLY with k=4 micro-batches per optimizer step — the exact
    shape that used to fall off the fast path twice over (rng_rekey +
    multi_backward). The loop auto-promotes to the super-cycle executable
    pair (ops/step_fusion.py); tokens/s + MFU are READ BACK from the
    metrics registry like every other train leg, so the accumulation win
    lands in the BENCH trajectory."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.incubate.models import (GPTConfig, GPTForCausalLM,
                                            GPTPretrainingCriterion,
                                            gpt2_124m)
    from paddle_tpu.ops.dispatch import clear_dispatch_cache
    from paddle_tpu.profiler import (reset_dispatch_cache_stats,
                                     reset_chain_fusion_stats,
                                     reset_step_fusion_stats,
                                     step_fusion_stats, clear_fusion_events,
                                     fusion_events, events_summary)
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.profiler.metrics import reset_metrics
    from paddle_tpu.profiler.goodput import ACCOUNTANT as _acct

    k = 4
    if on_tpu:
        seq, batch, warmup, steps = 1024, 4, 8, 10
        cfg = gpt2_124m(hidden_dropout_prob=0.1,
                        attention_probs_dropout_prob=0.0,
                        max_position_embeddings=seq)
    else:
        seq, batch, warmup, steps = 128, 2, 8, 4
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=128,
                        max_position_embeddings=seq,
                        hidden_dropout_prob=0.1,
                        attention_probs_dropout_prob=0.0)
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    clear_fusion_events()
    reset_metrics()
    prev = get_flags(["FLAGS_profiler_events", "FLAGS_metrics"])
    set_flags({"FLAGS_profiler_events": True, "FLAGS_metrics": True,
               "FLAGS_eager_op_cache": True,
               "FLAGS_eager_chain_fusion": True,
               "FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion": True,
               "FLAGS_eager_step_fusion_min_count": 3})
    try:
        clear_dispatch_cache()
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        n_params = model.num_params()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                     parameters=model.parameters())
        criterion = GPTPretrainingCriterion()
        rng = np.random.default_rng(0)
        micro = [
            (paddle.Tensor(jnp.asarray(
                rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
                stop_gradient=True),
             paddle.Tensor(jnp.asarray(
                 rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
                 stop_gradient=True))
            for _ in range(k)]

        def cycle():
            for x, y in micro:
                loss = criterion(model(x), y)
                loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        for _ in range(warmup):
            cycle()
        jax.block_until_ready(
            next(iter(model.parameters()))._value)
        flops_per_token = model.flops_per_token(seq, training=True)
        _acct.reset(warm=True)
        _acct.set_flops_per_step(flops_per_token * batch * seq * k,
                                 tokens=batch * seq * k,
                                 peak=peak_flops_per_chip())
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = cycle()
        final = float(loss.numpy())
        _acct.finalize()
        elapsed = time.perf_counter() - t0

        goodput = _acct.snapshot()
        offline_tps = batch * seq * k * steps / elapsed
        mfu_offline = offline_tps * flops_per_token / peak_flops_per_chip()
        sf = step_fusion_stats()
        ev = fusion_events()
        doctor = explain(ev)
        platform = jax.devices()[0].platform
        return {
            "metric": "gpt2_accum4_train_tokens_per_sec_per_chip",
            "value": round(goodput["tokens_per_sec"], 1),
            "unit": "tokens/s",
            "vs_baseline": 0.0,
            "platform": platform,
            "extra": {"mfu": round(goodput["mfu"], 4),
                      "mfu_offline": round(mfu_offline, 4),
                      "tokens_per_sec_offline": round(offline_tps, 1),
                      "loss": round(final, 3),
                      "k_micro_batches": k,
                      "batch": batch, "seq": seq, "params": n_params,
                      "goodput": goodput,
                      "step_fusion": sf,
                      "fused_steps": sf["fused_steps"],
                      "retraces": sf["retraces"],
                      "fusion_events": events_summary(ev),
                      "fusion_doctor": {"verdict": doctor["verdict"],
                                        "headline": doctor["headline"]},
                      "platform": platform},
        }
    finally:
        set_flags(prev)


def bench_flash4096(on_tpu):
    """Long-context case: GPT-2 124M at seq 4096 through the Pallas flash
    fwd+bwd kernel (attention is ~30% of model FLOPs here, so this is the
    kernel-bound config)."""
    from paddle_tpu.incubate.models import gpt2_124m, GPTConfig
    if on_tpu:
        seq = 4096
        cfg = gpt2_124m(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        max_position_embeddings=seq)
        batch, steps = 4, 6
    else:
        seq = 256
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=256,
                        max_position_embeddings=seq, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        batch, steps = 2, 2
    return _gpt_train_record("gpt2_124m_seq4096_train_tokens_per_sec_per_chip",
                             cfg, batch, steps, seq, on_tpu, "flash4096")


# --------------------------------------------------------------------------
# config 5: ViT-L/16 training, fused vs unfused
# --------------------------------------------------------------------------

def _vit_images_per_sec(fused, on_tpu):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    if on_tpu:
        model = paddle.vision.models.vit_l_16(use_fused_attn=fused)
        batch, steps, img = 32, 8, 224
    else:   # CPU smoke: a small ViT proves the path without minutes of XLA
        model = paddle.vision.models.VisionTransformer(
            img_size=32, patch_size=8, embed_dim=64, depth=2, num_heads=4,
            num_classes=10, use_fused_attn=fused)
        batch, steps, img = 4, 2, 32
    if on_tpu:
        model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=on_tpu)
    step = TrainStep(model, lambda o, y: F.cross_entropy(o, y), opt,
                     donate="all")
    rng = np.random.default_rng(0)
    x = paddle.Tensor(jnp.asarray(rng.normal(size=(batch, 3, img, img)),
                                  jnp.bfloat16 if on_tpu else jnp.float32),
                      stop_gradient=True)
    y = paddle.Tensor(jnp.asarray(
        rng.integers(0, model.num_classes, (batch,)), jnp.int64),
        stop_gradient=True)
    float(step(x, y))                   # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    float(loss)
    elapsed = time.perf_counter() - t0
    ips = batch * steps / elapsed
    mfu = ips * model.flops_per_image(training=True) / peak_flops_per_chip()
    platform = jax.devices()[0].platform
    tag = "vit_fused" if fused else "vit_unfused"
    tdir = _trace(tag, platform, lambda: float(step(x, y)))
    return ips, mfu, tdir, platform


def bench_vit(on_tpu):
    fused_ips, fused_mfu, tdir, platform = _vit_images_per_sec(True, on_tpu)
    unfused_ips, unfused_mfu, _, _ = _vit_images_per_sec(False, on_tpu)
    ratio = fused_ips / unfused_ips
    return {
        "metric": "vit_l16_train_images_per_sec_fused",
        "value": round(fused_ips, 1),
        "unit": "images/s",
        # config-5 criterion: fused path >= unfused path
        "vs_baseline": round(ratio, 4),
        "platform": platform,
        "extra": {"unfused_images_per_sec": round(unfused_ips, 1),
                  "fused_mfu": round(fused_mfu, 4),
                  "unfused_mfu": round(unfused_mfu, 4),
                  "platform": platform,
                  "trace": tdir},
    }


# --------------------------------------------------------------------------
# serving: GPT-2 compiled decode step
# --------------------------------------------------------------------------

def bench_decode(on_tpu):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.incubate.models import (GPTForCausalLM, GPTDecodeStep,
                                            gpt2_124m, GPTConfig)

    paddle.seed(0)
    if on_tpu:
        cfg = gpt2_124m(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        B, T, steps = 8, 160, 50
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=128,
                        max_position_embeddings=64, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
        B, T, steps = 2, 32, 10
    model = GPTForCausalLM(cfg)
    model.eval()
    dstep = GPTDecodeStep(model)
    L = cfg.num_hidden_layers
    H = cfg.num_attention_heads
    D = cfg.hidden_size // H

    def raw(tok, kb, vb, pos):
        lg, nk, nv = dstep(Tensor(tok, stop_gradient=True),
                           Tensor(kb, stop_gradient=True),
                           Tensor(vb, stop_gradient=True),
                           Tensor(pos, stop_gradient=True))
        nxt = jnp.argmax(lg._value[:, -1, :], -1)[:, None].astype(jnp.int64)
        return nxt, nk._value, nv._value

    # one StableHLO program per token, static KV buffers donated step to
    # step (the Predictor replay path proven token-exact by
    # tests/test_gpt.py::test_decode_step_predictor_roundtrip)
    jfn = jax.jit(raw, donate_argnums=(1, 2))

    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int64)
    kb = jnp.zeros((L, B, T, H, D), jnp.float32)
    vb = jnp.zeros((L, B, T, H, D), jnp.float32)
    tok, kb, vb = jfn(tok, kb, vb, jnp.asarray(0, jnp.int32))  # compile
    jax.block_until_ready(tok)

    t0 = time.perf_counter()
    for i in range(steps):
        tok, kb, vb = jfn(tok, kb, vb, jnp.asarray(1 + i, jnp.int32))
    jax.block_until_ready(tok)
    elapsed = time.perf_counter() - t0
    ms_per_step = elapsed / steps * 1e3

    platform = jax.devices()[0].platform
    tdir = _trace("decode", platform, lambda: jax.block_until_ready(
        jfn(tok, kb, vb, jnp.asarray(steps + 1, jnp.int32))[0]))
    return {
        "metric": "gpt2_124m_decode_ms_per_step",
        "value": round(ms_per_step, 4),
        "unit": "ms/step",
        # target from BASELINE.md: <= 0.08 ms/step at batch 8
        "vs_baseline": round(0.08 / ms_per_step, 4) if on_tpu else 0.0,
        "platform": platform,
        "extra": {"batch": B, "buffer_len": T, "steps": steps,
                  "tokens_per_sec": round(B / (ms_per_step / 1e3), 1),
                  "platform": platform,
                  "trace": tdir},
    }


# --------------------------------------------------------------------------
# serve_1 / serve_8 / serve_64: the continuous-batching engine
# --------------------------------------------------------------------------

def _bench_serve(streams, prefix=False, sampled=False, pipeline=False):
    """Serving-engine leg at N concurrent streams; the heavy lifting
    (workload, warmup, zero-retrace window accounting) lives in
    tools/serve_bench.run_serve_bench so the CLI and the bench measure
    the same thing. `prefix=True` runs the multi-tenant shared-prefix
    workload (PR 17) with the prefix cache enabled, so the trajectory
    carries the aliasing economy (hit rate, COW copies) as first-class
    numbers next to the cold-prefill serve legs. `sampled=True` turns
    the streams stochastic (PR 18: per-slot temperature/top-k/top-p
    inside the ONE compiled decode — the record's `sampling` block
    carries the entropy sanity), `pipeline=True` runs the
    software-pipelined decode loop."""
    def run(on_tpu):
        import jax
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import serve_bench
        platform = jax.devices()[0].platform
        leg = f"serve_{streams}"
        if prefix:
            leg += "_prefix"
        if sampled:
            leg += "_sampled"
        if pipeline:
            leg += "_pipelined"
        tdir = os.path.join(TRACE_ROOT, platform, leg)
        rec = serve_bench.run_serve_bench(
            streams, on_tpu, trace_dir=tdir, prefix_cache=prefix,
            temperature=0.8 if sampled else 0.0,
            top_k=40 if sampled else 0,
            top_p=0.95 if sampled else 1.0,
            seed=1234 if sampled else None, pipeline=pipeline)
        if sampled or pipeline:
            rec["metric"] = f"{leg}_tokens_per_sec"
        return rec
    return run


def bench_dp8(on_tpu):
    """Multichip leg: a dp=8 data-parallel training loop that auto-promotes
    into ONE shard_map executable per step (ops/spmd_fusion.py), measured
    against the same loop with step fusion off (per-op eager dispatch with
    GSPMD-inserted collectives). On CPU the 8 devices are emulated
    (xla_force_host_platform_device_count, same harness as the Fleet
    dryruns); on TPU the real chips form the mesh."""
    import jax
    if not on_tpu and jax.device_count() < 8:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from __graft_entry__ import _force_virtual_cpu_mesh
        _force_virtual_cpu_mesh(8)
        import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.ops.dispatch import clear_dispatch_cache
    from paddle_tpu.ops.step_fusion import step_cache_info
    from paddle_tpu.profiler.step_fusion import STEP_STATS

    n = min(jax.device_count(), 8)
    mesh = build_mesh(dp=n, pp=1, sharding=1, sep=1, mp=1,
                      devices=jax.devices()[:n])
    set_global_mesh(mesh)
    sharding = NamedSharding(mesh, P("data"))
    B, D_IN, D_H, D_OUT = 8 * n, 128, 256, 64
    warmup, steps = 12, 40
    rng = np.random.default_rng(0)
    xv = jax.device_put(
        rng.standard_normal((B, D_IN)).astype(np.float32), sharding)
    yv = jax.device_put(
        rng.standard_normal((B, D_OUT)).astype(np.float32), sharding)

    def timed_loop(fused):
        set_flags({"FLAGS_eager_op_cache": True,
                   "FLAGS_eager_chain_fusion": True,
                   "FLAGS_eager_chain_fusion_min_count": 4,
                   "FLAGS_eager_step_fusion": fused,
                   "FLAGS_eager_step_fusion_min_count": 5})
        clear_dispatch_cache()
        paddle.seed(0)
        ri = np.random.default_rng(1)
        w1 = paddle.to_tensor(
            (ri.standard_normal((D_IN, D_H)) * 0.05).astype(np.float32),
            stop_gradient=False)
        b1 = paddle.to_tensor(np.zeros(D_H, np.float32),
                              stop_gradient=False)
        w2 = paddle.to_tensor(
            (ri.standard_normal((D_H, D_OUT)) * 0.05).astype(np.float32),
            stop_gradient=False)
        opt = paddle.optimizer.Momentum(learning_rate=1e-2, momentum=0.9,
                                        parameters=[w1, b1, w2])
        x = paddle.Tensor(xv, stop_gradient=True)
        y = paddle.Tensor(yv, stop_gradient=True)

        def step():
            h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
            out = paddle.matmul(h, w2)
            diff = paddle.subtract(out, y)
            loss = paddle.mean(paddle.multiply(diff, diff))
            loss.backward()
            opt.step()
            opt.clear_grad()

        for _ in range(warmup):
            step()
        jax.block_until_ready(w1._value)
        r0 = STEP_STATS.retraces
        t0 = time.perf_counter()
        for _ in range(steps):
            step()
        jax.block_until_ready(w1._value)
        return (time.perf_counter() - t0) / steps, \
            STEP_STATS.retraces - r0

    eager_s, _ = timed_loop(False)
    fused_s, retraces = timed_loop(True)
    info = step_cache_info()
    spmd = next((p["spmd"] for p in info["programs"]
                 if not p["dead"] and p["spmd"]), None)
    samples_per_sec = B / fused_s
    platform = jax.devices()[0].platform
    return {
        "metric": "dp8_fused_samples_per_sec",
        "value": round(samples_per_sec, 1),
        "unit": "samples/s",
        "vs_baseline": 0.0,
        "platform": platform,
        "extra": {
            "n_devices": n, "mesh": spmd, "batch_global": B,
            "fused_ms_per_step": round(fused_s * 1e3, 3),
            "eager_ms_per_step": round(eager_s * 1e3, 3),
            "speedup_vs_eager_collectives": round(eager_s / fused_s, 3),
            "retraces_post_promotion": retraces,
            "step_fusion": STEP_STATS.snapshot(),
            "platform": platform,
        },
    }


def bench_pp2(on_tpu):
    """Pipeline-parallel train leg (hybrid-parallel promotion): a pp=2 x
    virtual=2 interleaved GPT driven through PipelineParallel.train_batch,
    which routes the whole fill/steady/drain cycle through the
    ops/spmd_fusion pipeline registry as ONE promoted ppermute-handoff
    executable (fwd+bwd+update, all micro-batches rolled in). tokens/s +
    MFU are READ BACK from the metrics registry like every train leg; the
    comparison is the same schedule run unfused and eager
    (forward_backward_pipeline: sequential micro-batch accumulation with
    no cross-stage overlap). On CPU the 2-stage mesh lives on the
    emulated 8-device platform (same harness as dp8)."""
    import jax
    if not on_tpu and jax.device_count() < 2:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from __graft_entry__ import _force_virtual_cpu_mesh
        _force_virtual_cpu_mesh(8)
        import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.distributed.fleet.meta_parallel import (
        PipelineLayer, PipelineParallel)
    from paddle_tpu.incubate.models import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion, gpt2_124m,
        gpt_pipeline_layers)
    from paddle_tpu.ops.dispatch import clear_dispatch_cache
    from paddle_tpu.ops.spmd_fusion import clear_pipeline_programs
    from paddle_tpu.profiler import (reset_step_fusion_stats,
                                     step_fusion_stats, clear_fusion_events,
                                     fusion_events, events_summary)
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.profiler.metrics import reset_metrics
    from paddle_tpu.profiler.goodput import ACCOUNTANT as _acct

    accum = 4                      # micro-batches per optimizer step
    if on_tpu:
        seq, batch, warmup, steps, eager_steps = 1024, 8, 4, 8, 2
        cfg = gpt2_124m(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        max_position_embeddings=seq)
    else:
        seq, batch, warmup, steps, eager_steps = 64, 4, 3, 4, 2
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=8,
                        num_attention_heads=4, intermediate_size=128,
                        max_position_embeddings=seq, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
    reset_step_fusion_stats()
    clear_fusion_events()
    reset_metrics()
    prev = get_flags(["FLAGS_profiler_events", "FLAGS_metrics"])
    # eager tiers OFF: the pipeline registry owns promotion here, and a
    # half-warm chain tier would only add tracer_input noise to the doctor
    set_flags({"FLAGS_profiler_events": True, "FLAGS_metrics": True,
               "FLAGS_eager_op_cache": False,
               "FLAGS_eager_chain_fusion": False,
               "FLAGS_eager_step_fusion": False})
    try:
        clear_dispatch_cache()
        clear_pipeline_programs()
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                          jnp.int32)
        labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                             jnp.int32)

        def make_runner():
            paddle.seed(0)
            model = GPTForCausalLM(cfg)
            pl = PipelineLayer(gpt_pipeline_layers(model), num_stages=2,
                               loss_fn=GPTPretrainingCriterion(),
                               num_virtual_pipeline_stages=2)
            runner = PipelineParallel(pl, hcg=None)
            runner.accumulate_steps = accum
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         weight_decay=0.01,
                                         parameters=model.parameters())
            return model, runner, opt

        # -- unfused eager schedule (single-controller fallback) ----------
        set_global_mesh(None)
        _, runner, opt = make_runner()
        for _ in range(2):
            float(runner.train_batch((ids, labels), opt))
        t0 = time.perf_counter()
        for _ in range(eager_steps):
            float(runner.train_batch((ids, labels), opt))
        eager_s = (time.perf_counter() - t0) / eager_steps

        # -- promoted pipeline cycle --------------------------------------
        mesh = build_mesh(dp=1, pp=2, sharding=1, sep=1, mp=1,
                          devices=jax.devices()[:2])
        set_global_mesh(mesh)
        model, runner, opt = make_runner()
        n_params = model.num_params()
        for _ in range(warmup):
            loss = runner.train_batch((ids, labels), opt)
        jax.block_until_ready(loss._value)
        flops_per_token = model.flops_per_token(seq, training=True)
        _acct.reset(warm=True)
        _acct.set_flops_per_step(flops_per_token * batch * seq,
                                 tokens=batch * seq,
                                 peak=peak_flops_per_chip())
        s0 = dict(step_fusion_stats())
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = runner.train_batch((ids, labels), opt)
        jax.block_until_ready(loss._value)
        final = float(loss.numpy())
        _acct.finalize()
        fused_s = (time.perf_counter() - t0) / steps
        s1 = dict(step_fusion_stats())

        goodput = _acct.snapshot()
        ev = fusion_events()
        promotes = [e for e in ev if e["cat"] == "step.promote"
                    and e["detail"].get("pipe")]
        fires = [e for e in ev if e["cat"] == "step.fire"]
        doctor = explain(ev)
        platform = jax.devices()[0].platform
        return {
            "metric": "pp2_interleaved_train_tokens_per_sec_per_chip",
            "value": round(goodput["tokens_per_sec"], 1),
            "unit": "tokens/s",
            "vs_baseline": 0.0,
            "platform": platform,
            "extra": {"mfu": round(goodput["mfu"], 4),
                      "loss": round(final, 3),
                      "schedule": (promotes[0]["detail"]["schedule"]
                                   if promotes else None),
                      "pipeline_promotes": len(promotes),
                      "pipeline_fires": len(fires),
                      "retraces_in_window": s1["retraces"] - s0["retraces"],
                      "accumulate_steps": accum,
                      "batch": batch, "seq": seq, "params": n_params,
                      "fused_ms_per_step": round(fused_s * 1e3, 3),
                      "eager_ms_per_step": round(eager_s * 1e3, 3),
                      "speedup_vs_eager_schedule": round(eager_s / fused_s,
                                                         3),
                      "goodput": goodput,
                      "fusion_events": events_summary(ev),
                      "fusion_doctor": {"verdict": doctor["verdict"],
                                        "headline": doctor["headline"]},
                      "platform": platform},
        }
    finally:
        set_flags(prev)
        from paddle_tpu.distributed.mesh import set_global_mesh as _sgm
        _sgm(None)


def bench_moe8(on_tpu):
    """MoE train leg (hybrid-parallel promotion): an 8-expert gshard
    MoELayer trained EAGERLY — the stamped gate fn
    (dispatch.mark_collective on the moe_layer dispatch) keys the
    collective so the whole fwd+bwd+update cycle promotes through the
    funnel instead of poisoning every cycle as collective_unkeyed.
    tokens/s + MFU are READ BACK from the metrics registry; the
    comparison is the same loop with the funnel off (per-op eager
    dispatch)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.ops.dispatch import clear_dispatch_cache
    from paddle_tpu.profiler import (reset_step_fusion_stats,
                                     step_fusion_stats, clear_fusion_events,
                                     fusion_events, events_summary)
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.profiler.metrics import reset_metrics
    from paddle_tpu.profiler.goodput import ACCOUNTANT as _acct

    top_k = 2                                    # gshard gate
    if on_tpu:
        d_model, d_hidden, experts = 512, 2048, 8
        batch, seq, warmup, steps, eager_steps = 8, 256, 10, 20, 4
    else:
        d_model, d_hidden, experts = 16, 32, 8
        batch, seq, warmup, steps, eager_steps = 4, 32, 10, 8, 4
    tokens = batch * seq
    # analytic active FLOPs/token: gate matmul + top_k expert FFNs, fwd;
    # training ~= 3x fwd (bwd re-does both matmul operands)
    flops_per_token = 3 * (2 * d_model * experts
                           + top_k * 4 * d_model * d_hidden)
    reset_step_fusion_stats()
    clear_fusion_events()
    reset_metrics()
    prev = get_flags(["FLAGS_profiler_events", "FLAGS_metrics"])
    set_flags({"FLAGS_profiler_events": True, "FLAGS_metrics": True})

    def make_loop(fused, seed=0):
        set_flags({"FLAGS_eager_op_cache": fused,
                   "FLAGS_eager_op_cache_size": 512,
                   "FLAGS_eager_chain_fusion": fused,
                   "FLAGS_eager_chain_fusion_min_count": 3,
                   "FLAGS_eager_step_fusion": fused,
                   "FLAGS_eager_step_fusion_min_count": 4})
        clear_dispatch_cache()
        paddle.seed(seed)
        rng = np.random.default_rng(seed)
        x = paddle.to_tensor(rng.standard_normal(
            (batch, seq, d_model)).astype(np.float32))
        m = MoELayer(d_model, d_hidden, experts, gate="gshard",
                     capacity_factor=2.0, eval_capacity_factor=2.0)
        m.train()
        opt = paddle.optimizer.SGD(learning_rate=1e-3,
                                   parameters=m.parameters())

        def step():
            y = m(x)
            loss = paddle.mean(paddle.multiply(y, y)) + 0.01 * m.l_aux
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        return m, step

    try:
        # -- funnel off: per-op eager dispatch ----------------------------
        m, step = make_loop(False)
        for _ in range(3):
            step()
        jax.block_until_ready(m.w1._value)
        t0 = time.perf_counter()
        for _ in range(eager_steps):
            step()
        jax.block_until_ready(m.w1._value)
        eager_s = (time.perf_counter() - t0) / eager_steps

        # -- funnel on: stamped gate -> promoted cycle --------------------
        m, step = make_loop(True)
        n_params = sum(int(np.prod(p.shape)) for p in m.parameters())
        for _ in range(warmup):
            step()
        jax.block_until_ready(m.w1._value)
        _acct.reset(warm=True)
        _acct.set_flops_per_step(flops_per_token * tokens, tokens=tokens,
                                 peak=peak_flops_per_chip())
        s0 = dict(step_fusion_stats())
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step()
        jax.block_until_ready(m.w1._value)
        final = float(loss.numpy())
        _acct.finalize()
        fused_s = (time.perf_counter() - t0) / steps
        s1 = dict(step_fusion_stats())

        goodput = _acct.snapshot()
        ev = fusion_events()
        doctor = explain(ev)
        platform = jax.devices()[0].platform
        return {
            "metric": "moe8_gshard_train_tokens_per_sec_per_chip",
            "value": round(goodput["tokens_per_sec"], 1),
            "unit": "tokens/s",
            "vs_baseline": 0.0,
            "platform": platform,
            "extra": {"mfu": round(goodput["mfu"], 4),
                      "loss": round(final, 4),
                      "experts": experts, "top_k": top_k,
                      "d_model": d_model, "d_hidden": d_hidden,
                      "batch": batch, "seq": seq, "params": n_params,
                      "steps_promoted": s1["steps_promoted"],
                      "fused_steps_in_window":
                          s1["fused_steps"] - s0["fused_steps"],
                      "retraces_in_window": s1["retraces"] - s0["retraces"],
                      "fallback_splits": s1["fallback_splits"],
                      "fused_ms_per_step": round(fused_s * 1e3, 3),
                      "eager_ms_per_step": round(eager_s * 1e3, 3),
                      "speedup_vs_unfused_eager": round(eager_s / fused_s,
                                                        3),
                      "goodput": goodput,
                      "step_fusion": s1,
                      "fusion_events": events_summary(ev),
                      "fusion_doctor": {"verdict": doctor["verdict"],
                                        "headline": doctor["headline"]},
                      "platform": platform},
        }
    finally:
        set_flags(prev)


# --------------------------------------------------------------------------
# child / parent plumbing
# --------------------------------------------------------------------------

CONFIG_FNS = {
    "vit": bench_vit,
    "decode": bench_decode,
    "serve_1": _bench_serve(1),
    "serve_8": _bench_serve(8),
    "serve_64": _bench_serve(64),
    "serve_8_prefix": _bench_serve(8, prefix=True),
    "serve_8_sampled": _bench_serve(8, sampled=True, pipeline=True),
    "flash4096": bench_flash4096,
    "gpt2_355m": bench_gpt2_355m,
    "gpt2_train": bench_gpt2_train,
    "accum4": bench_accum4,
    "dp8": bench_dp8,
    "pp2": bench_pp2,
    "moe8": bench_moe8,
}

# per-config hard timeouts (seconds)
TPU_CAPS = {"vit": 180, "decode": 150, "serve_1": 120, "serve_8": 120,
            "serve_64": 150, "serve_8_prefix": 120,
            "serve_8_sampled": 120,
            "flash4096": 210, "gpt2_355m": 240,
            "gpt2_train": 280, "accum4": 240, "dp8": 180,
            "pp2": 200, "moe8": 180}
HEADLINE = "gpt2_train"
HEADLINE_RESERVE = 300      # wall-clock held back for the headline config


def _child_config(name):
    import jax
    from paddle_tpu.framework.compile_cache import enable_compile_cache
    enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        # a measurement path takes the device it is given, and a leg that
        # finds no chip fails: there is no CPU version of a device metric
        raise SystemExit(f"bench leg {name!r} needs a TPU; JAX found "
                         f"{platform!r}")
    # live observability for the parent's timeout autopsy: the parent
    # seeded FLAGS_telemetry_port in our environment, so a wedged compile
    # leaves a scrapable /healthz heartbeat + goodput snapshot
    from paddle_tpu.profiler.telemetry_server import maybe_start_from_flags
    maybe_start_from_flags()
    # the goodput accountant feeds the leg's sentinel record below; a
    # config that arms its own flags (serve_bench, the train legs) wins,
    # this just covers the microbench legs that never touch FLAGS_metrics
    # (<0.3%/step, budgeted by perf_smoke leg (d))
    from paddle_tpu.framework.flags import set_flags as _set_flags
    _set_flags({"FLAGS_metrics": True})
    rec = CONFIG_FNS[name](True)
    # sentinel-comparable leg record (profiler/sentinel.py): each config
    # runs in its own child process, so the absolute counters ARE this
    # leg's counters. tools/perf_baseline.py extracts these from the
    # BENCH JSON-lines to seed/check tools/perf_baselines.json.
    from paddle_tpu.profiler.sentinel import capture_record
    extra = rec.setdefault("extra", {})
    if "sentinel_record" in extra:          # serve legs capture
        extra["sentinel_record"]["leg"] = name  # in-engine; restamp
    else:
        extra["sentinel_record"] = capture_record(name)
    print(json.dumps(rec), flush=True)


def _alloc_port():
    """A free loopback port for the child's telemetry server (bind-0
    probe; the tiny race against another allocator is acceptable for a
    diagnostics channel)."""
    import socket
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _probe_child_health(port):
    """Timeout autopsy: ask the (still-alive, about-to-be-killed) child's
    telemetry server what it was doing. A bare rc=124 leaves nothing to
    diagnose a hang with; the /healthz heartbeat age + live goodput
    snapshot say whether the child was stepping, compiling, or wedged —
    and for how long.

    Deliberately NOT telemetry_server.probe_endpoint: the parent
    orchestrator never imports the framework (importing paddle_tpu pulls
    jax, and a wedged backend is exactly what this code runs during), so
    this stays a stdlib-only re-read of the same endpoint contract."""
    import urllib.error
    import urllib.request
    out = {}
    for ep in ("healthz", "goodput"):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/{ep}", timeout=3) as r:
                out[ep] = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:      # 503 = unhealthy, still data
            try:
                out[ep] = json.loads(e.read().decode())
            except Exception:
                out[ep] = {"unreachable": f"http {e.code}"}
        except Exception as e:
            out[ep] = {"unreachable": str(e)[:160]}
    return out


def _run_child(argv, timeout):
    """Run a bench child; return (record_dict | None, rc, note). Forwards
    the child's non-record stdout lines for observability. The child gets
    FLAGS_telemetry_port in its environment (flags seed from env) and
    arms the telemetry server in _child_config — on a hard timeout the
    parent scrapes /healthz + /goodput BEFORE killing, so a hung config
    leaves a heartbeat-age autopsy instead of a bare rc=124."""
    port = _alloc_port()
    cmd = [sys.executable, os.path.abspath(__file__)] + argv
    env = {**os.environ, "FLAGS_telemetry_port": str(port)}
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    try:
        out, err = proc.communicate(timeout=timeout)
        rc, note = proc.returncode, ""
        if rc != 0:
            note = (err or "")[-400:]
    except subprocess.TimeoutExpired:
        autopsy = _probe_child_health(port)      # child is still alive here
        proc.kill()
        try:
            out, err = proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            out = ""
        rc = 124
        hb = (autopsy.get("healthz") or {}).get("last_heartbeat_age_s")
        note = (f"killed after {timeout:.0f}s hard timeout; "
                f"last_heartbeat_age_s={hb}")
        print(json.dumps({"event": "timeout_autopsy", "argv": argv[:2],
                          "last_heartbeat_age_s": hb,
                          "healthz": autopsy.get("healthz"),
                          "goodput": autopsy.get("goodput")},
                         default=str), flush=True)
    record = None
    for line in (out or "").splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if "metric" in obj or "platform" in obj:
            record = obj
        else:
            print(line, flush=True)
    return record, rc, note


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", choices=sorted(CONFIG_FNS))
    args = parser.parse_args()

    if args.config:
        _child_config(args.config)
        return

    # ---------------- parent orchestrator (never imports jax) -------------
    budget = float(os.environ.get("BENCH_BUDGET_S", 840))
    deadline = time.monotonic() + budget

    def remaining():
        return deadline - time.monotonic()

    def run_config(name, timeout):
        t0 = time.monotonic()
        rec, rc, note = _run_child(["--config", name], timeout)
        dur = time.monotonic() - t0
        if rec is not None and rc == 0 and "metric" in rec:
            return rec
        return {"metric": name, "error": note or f"rc={rc}", "rc": rc,
                "elapsed_s": round(dur, 1)}

    results = {}
    for name in ("vit", "decode", "serve_1", "serve_8", "serve_64",
                 "serve_8_prefix", "serve_8_sampled", "flash4096",
                 "gpt2_355m", "dp8"):
        avail = remaining() - HEADLINE_RESERVE
        if avail < 45:
            results[name] = {"metric": name, "skipped": "budget_exhausted"}
        else:
            results[name] = run_config(name, min(TPU_CAPS[name], avail))
        print(json.dumps(results[name]), flush=True)

    # headline LAST: GPT-2 124M train, embedding the other configs'
    # summaries
    head = run_config(HEADLINE, min(TPU_CAPS[HEADLINE],
                                    max(60.0, remaining() - 20)))
    failed = sorted(n for n, r in results.items() if "error" in r)
    if "error" in head:
        print(json.dumps({
            "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
            "error": head["error"][-400:], "rc": head["rc"],
            "failed": failed + [HEADLINE]}), flush=True)
        raise SystemExit(1)

    head.setdefault("extra", {})
    for name, rec in results.items():
        if "error" in rec or "skipped" in rec:
            head["extra"][name] = {k: v for k, v in rec.items()
                                   if k != "metric"}
        else:
            head["extra"][name] = {"metric": rec["metric"],
                                   "value": rec["value"],
                                   "unit": rec["unit"],
                                   "vs_baseline": rec["vs_baseline"],
                                   "platform": rec.get("platform")}
            if name.startswith("serve_"):
                # backpressure/resilience counters ride the trajectory:
                # a regression in refusal/timeout/preempt behavior shows
                # here even when throughput looks healthy
                ex = rec.get("extra") or {}
                head["extra"][name]["resilience"] = {
                    k: ex.get(k, 0)
                    for k in ("evictions", "refused",
                              "refused_queue_full", "refused_deadline",
                              "cancelled", "expired", "hangs",
                              "eager_fallbacks", "resumed")}
                # multi-tenant counters (PR 17): the aliasing economy and
                # tenant churn ride the trajectory next to throughput —
                # a prefix-hit or hot-swap regression shows here even
                # when tokens/s looks healthy
                head["extra"][name]["tenancy"] = {
                    k: ex.get(k, 0)
                    for k in ("prefix_cache", "prefix_hit_tokens",
                              "prefix_hit_rate", "cow_copies",
                              "adapter_switches", "weight_swaps")}
    print(json.dumps(head), flush=True)
    if failed:
        # the records above say which and why; a run with a failed leg
        # is not a clean run
        raise SystemExit(f"bench legs failed: {failed}")


if __name__ == "__main__":
    main()
