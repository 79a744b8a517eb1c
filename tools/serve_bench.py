#!/usr/bin/env python
"""Serving-engine benchmark: tokens/s + p50/p99 decode-step latency at N
concurrent streams through `paddle_tpu.serving.LLMEngine`.

The workload is the continuous-batching steady state the engine is built
for: N requests with MIXED prompt lengths enqueued at once, churning
through a fixed slot layout — requests join and leave at token
boundaries while the ONE compiled decode executable serves every step.
The measured window starts AFTER warmup (decode program + every prefill
bucket the workload uses compiled), so:

  * `decode_compiles` in the record is the number of decode traces INSIDE
    the measured window — the zero-retrace acceptance criterion is this
    field staying 0 while streams churn;
  * p50/p99 step times are steady-state numbers, not compile spikes
    (the serving target: compiled decode step <= 0.08 ms on TPU);
  * batch occupancy under saturation proves continuous batching is
    actually packing the slots (target >= 0.75, guarded by
    tools/perf_smoke.py).

Usage:

    JAX_PLATFORMS=cpu python tools/serve_bench.py --streams 8
    python tools/serve_bench.py --streams 64 --json
    python tools/serve_bench.py --streams 8 --trace /tmp/serve_trace

The tool takes the device it is given: on the chip it runs GPT-2 124M,
and with `JAX_PLATFORMS=cpu` (asked for, never defaulted to) a toy model
whose numbers are CPU timings, not device metrics. bench.py wires
`serve_1` / `serve_8` / `serve_64` legs through run_serve_bench() in its
one-child-per-config harness, where a leg without a TPU fails; the fusion
flight recorder is armed for the run, so the record embeds the serve.*
event summary and the fusion-doctor verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def _build_model(on_tpu):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTForCausalLM, GPTConfig

    paddle.seed(0)
    if on_tpu:
        from paddle_tpu.incubate.models import gpt2_124m
        cfg = gpt2_124m(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        max_position_embeddings=512)
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=128,
                        max_position_embeddings=256,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _workload(streams, vocab, max_prompt, seed=0, shared_prefix=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    prefix = (rng.integers(0, vocab, shared_prefix).tolist()
              if shared_prefix else [])
    lens = rng.integers(4, max_prompt + 1 - shared_prefix, streams)
    return [prefix + rng.integers(0, vocab, int(n)).tolist()
            for n in lens]


def _sampling_block(reqs, vocab, temperature, top_k, top_p, seed, snap):
    """The `sampling` headline: distinct-token fraction and normalized
    entropy over every emitted token. Greedy tiny-model streams loop
    hard (both numbers sit near 0); a working stochastic sampler spreads
    mass — the block is the cheap end-to-end sanity that temperature
    actually reached the compiled program."""
    import collections
    import math
    toks = [t for r in reqs for t in r.generated]
    block = {"temperature": float(temperature), "top_k": int(top_k),
             "top_p": float(top_p), "seed": seed,
             "sampled_tokens": snap["sampled_tokens"],
             "distinct_frac": 0.0, "entropy_norm": 0.0}
    if len(toks) > 1:
        counts = collections.Counter(toks)
        total = len(toks)
        ent = -sum((c / total) * math.log(c / total)
                   for c in counts.values())
        denom = math.log(min(total, vocab))
        block["distinct_frac"] = round(len(counts) / total, 4)
        block["entropy_norm"] = round(ent / denom if denom > 0 else 0.0,
                                      4)
    return block


def run_serve_bench(streams, on_tpu, max_new_tokens=None, trace_dir=None,
                    model=None, kernel=None, kv_dtype=None,
                    prefix_cache=False, temperature=0.0, top_k=0,
                    top_p=1.0, seed=None, pipeline=False):
    """One serving bench leg; returns a bench.py-style record dict.

    `kernel` pins the attention variant (default: the engine resolves
    FLAGS_serve_attention_kernel); `kv_dtype="int8"` runs the quantized
    KV pool. Both land in the record's extra so a bench trajectory always
    says WHICH kernel tier produced its numbers. `prefix_cache` runs the
    multi-tenant shared-prefix workload (PR 17): every stream carries
    the same leading system prompt, so the record's prefix-hit counters
    show the aliasing economy instead of zeros.

    Sampler knobs (PR 18) ride per-request: `temperature > 0` turns the
    legs stochastic (per-stream seeds derive from `seed`), and the
    record grows a `sampling` block — distinct-token fraction +
    normalized entropy over the emitted streams, the sanity check that
    the compiled sampler actually explores (greedy loops collapse both
    toward 0). `pipeline=True` runs the software-pipelined decode loop
    (launch N+1 / commit N) — same contract, overlap measured by the
    tokens/s headline."""
    import jax
    import numpy as np
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.profiler.events import clear_fusion_events
    from paddle_tpu.profiler import events_summary, fusion_events
    from paddle_tpu.profiler.explain import explain
    from paddle_tpu.profiler.metrics import (reset_metrics,
                                             serve_live_summary)
    from paddle_tpu.serving import LLMEngine

    if model is None:
        model = _build_model(on_tpu)
    cfg = model.config
    if max_new_tokens is None:
        max_new_tokens = 32 if on_tpu else 24
    # the serving target is decode latency at batch 8 (BASELINE serving
    # config); more streams than slots is the point — they churn through
    max_batch = min(streams, 8)
    max_prompt = 48 if on_tpu else 24
    clear_fusion_events()
    # telemetry plane armed (PR 12): the p50/p99/TTFT numbers below come
    # off the engine's bounded histograms — the same computation a
    # production scrape of the registry reports
    reset_metrics()
    prev = get_flags(["FLAGS_profiler_events", "FLAGS_metrics"])
    set_flags({"FLAGS_profiler_events": True, "FLAGS_metrics": True})
    try:
        # build the engine with the recorder already armed: construction
        # is where the kernel-tier attribution fires (kernel.fallback on
        # a demoted variant, kernel.quantized for an int8 pool) and the
        # bench's event record must contain it
        engine = LLMEngine(model, max_batch_size=max_batch,
                           block_size=16 if on_tpu else 8,
                           max_context=max_prompt + max_new_tokens + 8,
                           # bounded queue sized generously for the leg:
                           # the backpressure counters below stay 0 in a
                           # healthy run and move in the trajectory when
                           # admission or deadline behavior regresses
                           max_queue_depth=4 * streams,
                           attention_kernel=kernel, kv_dtype=kv_dtype,
                           enable_prefix_cache=prefix_cache,
                           pipeline_decode=pipeline)
        prompts = _workload(streams, cfg.vocab_size, max_prompt,
                            shared_prefix=(max_prompt // 2
                                           if prefix_cache else 0))
        # warmup: compile the decode program and every prefill bucket the
        # workload will hit (one representative prompt per bucket)
        buckets = {}
        for p in prompts:
            buckets.setdefault(engine._bucket_for(len(p)), p)
        for p in buckets.values():
            engine.generate([p], max_new_tokens=2)
        engine.reset_stats()

        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(engine.add_request(
                p, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=(None if seed is None else seed + i)))
        engine.run()
        snap = engine.stats()
        sampling = _sampling_block(reqs, cfg.vocab_size, temperature,
                                   top_k, top_p, seed, snap)

        tdir = None
        if trace_dir:
            # trace a few steady-state decode steps (programs are warm)
            os.makedirs(trace_dir, exist_ok=True)
            try:
                with jax.profiler.trace(trace_dir):
                    engine.generate(prompts[:max_batch], max_new_tokens=4)
                tdir = trace_dir
            except Exception as e:       # tracing must never sink the bench
                print(json.dumps({"event": "trace_failed",
                                  "error": str(e)[:200]}), flush=True)
        ev = fusion_events()
        doctor = explain(ev)
        live = serve_live_summary()
        # sentinel-comparable leg record — captured HERE, while the
        # engine is still registered (its per-engine tallies die with
        # it); bench.py re-stamps the leg name with its config name
        from paddle_tpu.profiler.sentinel import capture_record
        sentinel_rec = capture_record(
            f"serve_{streams}" + ("_prefix" if prefix_cache else ""),
            kind="serve")
    finally:
        set_flags(prev)

    platform = jax.devices()[0].platform
    return {
        "metric": (f"serve_{streams}_prefix_tokens_per_sec" if prefix_cache
                   else f"serve_{streams}_tokens_per_sec"),
        "value": round(snap["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # serving target: compiled decode step <= 0.08 ms (TPU); CPU runs
        # report the same harness's number without claiming the target
        "vs_baseline": (round(0.08 / snap["p50_step_ms"], 4)
                        if on_tpu and snap["p50_step_ms"] else 0.0),
        "platform": platform,
        "extra": {
            "streams": streams,
            "max_batch": max_batch,
            "max_new_tokens": max_new_tokens,
            # kernel tier (PR 11): which attention variant + KV dtype
            # produced these numbers — a perf trajectory without this is
            # uninterpretable once the flag matrix exists
            "attention_kernel": snap["attention_kernel"],
            "kv_dtype": snap["kv_dtype"],
            "p50_step_ms": round(snap["p50_step_ms"], 4),
            "p99_step_ms": round(snap["p99_step_ms"], 4),
            # per-request latency story (PR 12): TTFT / inter-token /
            # queue-wait percentiles from the bounded windowed histograms
            "ttft_p50_ms": round(snap["ttft_p50_ms"], 4),
            "ttft_p99_ms": round(snap["ttft_p99_ms"], 4),
            "inter_token_p50_ms": round(snap["inter_token_p50_ms"], 4),
            "inter_token_p99_ms": round(snap["inter_token_p99_ms"], 4),
            "queue_wait_p99_ms": round(snap["queue_wait_p99_ms"], 4),
            # live registry view — same numbers a production scrape sees
            "metrics_live": live,
            "sentinel_record": sentinel_rec,
            "decode_steps": snap["steps"],
            # decode traces INSIDE the measured window — must stay 0
            "decode_compiles": snap["decode_compiles"],
            "prefill_compiles": snap["prefill_compiles"],
            "occupancy_mean": round(snap["occupancy_mean"], 4),
            "occupancy_saturated": round(snap["occupancy_saturated"], 4),
            "admitted": snap["admitted"],
            "evictions": snap["evictions"],
            "completed": snap["completed"],
            # resilience counters (PR 7): refusal/timeout/cancel/preempt
            # behavior is part of the trajectory, not just throughput —
            # a backpressure regression shows here before it shows in
            # tokens/s
            "refused": snap["refused"],
            "refused_queue_full": snap["refused_queue_full"],
            "refused_deadline": snap["refused_deadline"],
            "cancelled": snap["cancelled"],
            "expired": snap["expired"],
            "hangs": snap["hangs"],
            "eager_fallbacks": snap["eager_fallbacks"],
            "resumed": snap["resumed"],
            # multi-tenant counters (PR 17): zeros on a plain engine;
            # with --prefix-cache the hit-rate line IS the aliasing
            # economy (prefill work the shared system prompt avoided)
            "prefix_cache": prefix_cache,
            "prefix_hit_tokens": snap["prefix_hit_tokens"],
            "prefix_hit_rate": round(snap["prefix_hit_rate"], 4),
            "cow_copies": snap["cow_copies"],
            "adapter_switches": snap["adapter_switches"],
            "weight_swaps": snap["weight_swaps"],
            # compiled sampling + pipelined decode (PR 18): the headline
            # sanity block — a stochastic leg whose streams collapse to
            # repeats (distinct/entropy near 0) is broken sampling even
            # when tokens/s looks fine
            "pipeline": pipeline,
            "sampled_tokens": snap["sampled_tokens"],
            "commit_rollbacks": snap["commit_rollbacks"],
            "sampling": sampling,
            "platform": platform,
            "trace": tdir,
            "fusion_events": events_summary(ev),
            "fusion_doctor": {"verdict": doctor["verdict"],
                              "headline": doctor["headline"]},
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="serve_bench",
        description="continuous-batching serving benchmark "
                    "(paddle_tpu.serving.LLMEngine)")
    ap.add_argument("--streams", type=int, default=8,
                    help="concurrent request streams (default 8)")
    ap.add_argument("--kernel", default=None,
                    choices=("pallas", "blockwise", "reference"),
                    help="attention kernel variant (default: "
                         "FLAGS_serve_attention_kernel)")
    ap.add_argument("--kv-dtype", default=None, choices=("int8",),
                    help="quantized KV cache mode (default: model dtype)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="multi-tenant shared-prefix workload: every "
                         "stream carries the same system prompt and the "
                         "engine aliases its KV blocks (PR 17)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-stream sampling temperature (0 = greedy, "
                         "the compiled program is the SAME either way)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-stream top-k filter (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-stream nucleus mass (1.0 disables)")
    ap.add_argument("--seed", type=int, default=None,
                    help="base sampling seed; stream i uses seed+i "
                         "(default: per-request crc32(rid) seeds)")
    ap.add_argument("--pipeline", action="store_true",
                    help="software-pipelined decode: launch step N+1 "
                         "while step N's host commit overlaps (PR 18)")
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--trace", default=None,
                    help="directory for a jax profiler trace of a few "
                         "steady-state decode steps")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    help="arm the live HTTP observability plane "
                         "(profiler/telemetry_server.py) on this port "
                         "for the run — scrape /metrics /goodput "
                         "/healthz while the bench churns (0 = an "
                         "ephemeral port, printed)")
    ap.add_argument("--json", action="store_true",
                    help="print the raw record as JSON")
    args = ap.parse_args(argv)

    if args.telemetry_port is not None:
        from paddle_tpu.profiler import telemetry_server
        srv = telemetry_server.start(port=args.telemetry_port)
        print(f"serve_bench: telemetry server at {srv.url} "
              "(/metrics /goodput /doctor /healthz /readyz)",
              file=sys.stderr)

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    t0 = time.perf_counter()
    rec = run_serve_bench(args.streams, on_tpu,
                          max_new_tokens=args.max_new_tokens,
                          trace_dir=args.trace, kernel=args.kernel,
                          kv_dtype=args.kv_dtype,
                          prefix_cache=args.prefix_cache,
                          temperature=args.temperature,
                          top_k=args.top_k, top_p=args.top_p,
                          seed=args.seed, pipeline=args.pipeline)
    rec["elapsed_s"] = round(time.perf_counter() - t0, 1)
    if args.json:
        print(json.dumps(rec, indent=2))
    else:
        ex = rec["extra"]
        print(f"serve_bench: {args.streams} stream(s) on {rec['platform']} "
              f"[{ex['attention_kernel']}, kv {ex['kv_dtype']}] "
              f"-> {rec['value']} tok/s, p50 {ex['p50_step_ms']} ms, "
              f"p99 {ex['p99_step_ms']} ms, "
              f"ttft p50 {ex['ttft_p50_ms']} ms, "
              f"inter-token p50 {ex['inter_token_p50_ms']} ms, "
              f"occupancy {ex['occupancy_mean']} "
              f"(saturated {ex['occupancy_saturated']}), "
              f"decode_compiles {ex['decode_compiles']} (window), "
              f"evictions {ex['evictions']}, refused {ex['refused']}, "
              f"expired {ex['expired']}, hangs {ex['hangs']}")
        if ex["prefix_cache"]:
            print(f"prefix: hit_rate {ex['prefix_hit_rate']} "
                  f"({ex['prefix_hit_tokens']} tokens aliased), "
                  f"cow_copies {ex['cow_copies']}")
        sb = ex["sampling"]
        if args.temperature > 0 or args.pipeline:
            print(f"sampling: T={sb['temperature']} top_k={sb['top_k']} "
                  f"top_p={sb['top_p']} "
                  f"-> distinct {sb['distinct_frac']}, "
                  f"entropy {sb['entropy_norm']}, "
                  f"sampled_tokens {sb['sampled_tokens']}, "
                  f"pipelined {ex['pipeline']}, "
                  f"commit_rollbacks {ex['commit_rollbacks']}")
        print(f"doctor: {ex['fusion_doctor']['headline']}")
    return 0 if rec["extra"]["decode_compiles"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
