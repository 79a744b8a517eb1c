#!/usr/bin/env python
"""Fusion doctor: explain WHY a training loop didn't promote (or split).

Runs a training script (or a built-in demo loop) with the fusion flight
recorder armed, then aggregates the event timeline into a root-cause
report: which op poisoned the step cycle, with which reason code, how many
times — e.g.

    verdict : never_promoted
    headline: step never promoted: `dist.all_reduce` collective_unkeyed ×40
    findings:
      - cycle poison collective_unkeyed ×40 ...

Usage:

    # any training script (its own argv after --)
    JAX_PLATFORMS=cpu python tools/fusion_doctor.py train.py -- --epochs 1

    # built-in demos (acceptance fixtures): a tiny GPT-ish loop
    python tools/fusion_doctor.py --demo dropout   # clean promotion: the
                                                   # PRNG key is HOISTED
                                                   # (rng_rekey is gone)
    python tools/fusion_doctor.py --demo accum     # clean promotion of a
                                                   # k=4 grad-accumulation
                                                   # SUPER-cycle
    python tools/fusion_doctor.py --demo masked    # clean promotion
    python tools/fusion_doctor.py --demo dp        # never promotes:
                                                   # collective_unkeyed

    # machine-readable
    python tools/fusion_doctor.py --demo accum --json

    # the persistent AOT executable store (ops/aot_cache.py): list
    # artifacts (kind, digest, size, age, fingerprint match, corruption),
    # and collect it manually
    python tools/fusion_doctor.py --cache [--cache-dir DIR] [--gc]

    # diagnose a RUNNING process without attaching: pull the report from
    # its telemetry server's /doctor endpoint (FLAGS_telemetry_port,
    # profiler/telemetry_server.py) — same JSON schema as --json
    python tools/fusion_doctor.py --url http://host:9100 [--json]

The doctor only ARMS the recorder (FLAGS_profiler_events); it does not
change the fusion configuration of a user script — if the script runs with
caching/fusion off, the report says so instead of inventing activity.
"""
from __future__ import annotations

import argparse
import json
import os
import runpy
import sys

# runnable from a source checkout without an install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def _demo(variant, steps):
    """Tiny single-head GPT-ish loop (embedding → attention → [dropout] →
    projection → cross_entropy → SGD). `dropout` promotes CLEANLY since
    the PRNG key became a hoisted stream position (the universal-promotion
    acceptance fixture — it used to be the rng_rekey fixture); `masked`
    feeds an attention mask — a dispatch input — and promotes cleanly;
    `accum` runs the masked variant as a k=4 micro-batch gradient
    accumulation loop that promotes as a SUPER-cycle (one reusable
    fwd+bwd+accumulate sub-executable + one update executable, zero
    steady-state retraces at any k)."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import manipulation as manip

    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_chain_fusion": True,
               "FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion": True,
               "FLAGS_eager_step_fusion_min_count": 5})
    paddle.seed(0)
    rng = np.random.default_rng(0)
    B, T, D, V = 2, 8, 16, 32
    k_micro = 4 if variant == "accum" else 1
    micro = [(paddle.to_tensor(rng.integers(0, V, (B, T))),
              paddle.to_tensor(rng.integers(0, V, (B * T,))))
             for _ in range(k_micro)]
    emb_w = paddle.to_tensor(
        (rng.standard_normal((V, D)) * 0.1).astype(np.float32),
        stop_gradient=False)
    wq, wk, wv, wo = (
        paddle.to_tensor((rng.standard_normal((D, D)) * 0.1)
                         .astype(np.float32), stop_gradient=False)
        for _ in range(4))
    w_out = paddle.to_tensor(
        (rng.standard_normal((D, V)) * 0.1).astype(np.float32),
        stop_gradient=False)
    mask = None
    if variant == "masked":
        causal = np.tril(np.ones((T, T), bool))
        mask = paddle.to_tensor(causal[None, None])   # [1, 1, T, T]
    params = [emb_w, wq, wk, wv, wo, w_out]
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=params)

    for _ in range(steps):
        for ids, labels in micro:
            h = F.embedding(ids, emb_w)                   # [B, T, D]
            q = manip.reshape(paddle.matmul(h, wq), [B, T, 1, D])
            k = manip.reshape(paddle.matmul(h, wk), [B, T, 1, D])
            v = manip.reshape(paddle.matmul(h, wv), [B, T, 1, D])
            a = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, is_causal=(mask is None))
            h = paddle.matmul(manip.reshape(a, [B, T, D]), wo)
            if variant in ("dropout", "accum"):
                h = F.dropout(h, 0.1)
            logits = manip.reshape(paddle.matmul(h, w_out), [B * T, V])
            loss = F.cross_entropy(logits, labels)
            loss.backward()
        opt.step()
        opt.clear_grad()


def _demo_dp(steps):
    """Data-parallel acceptance fixture: a small sharded-batch loop whose
    gradient sync calls `dist.all_reduce` over a hand-built Group WITHOUT a
    mesh-backed process group — the collective cannot be keyed, every
    cycle is poisoned `collective_unkeyed`, and the report reads "step
    never promoted: `dist.all_reduce` collective_unkeyed ×N". The fix the
    hint prescribes (mesh-backed groups, or dropping eager grad
    collectives so the SPMD promoter fuses the psum) is exactly what
    tests/test_spmd_fusion.py proves out."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.framework.flags import set_flags

    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_chain_fusion": True,
               "FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion": True,
               "FLAGS_eager_step_fusion_min_count": 5})
    paddle.seed(0)
    n = jax.device_count()
    mesh = build_mesh(dp=n, pp=1, sharding=1, sep=1, mp=1)
    set_global_mesh(mesh)
    sharding = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    w = paddle.to_tensor(
        (rng.standard_normal((32, 8)) * 0.1).astype(np.float32),
        stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w])
    group = dist.collective.Group(0, n, id=90, ranks=list(range(n)))
    for _ in range(steps):
        x = paddle.Tensor(jax.device_put(
            rng.standard_normal((2 * n, 32)).astype(np.float32), sharding),
            stop_gradient=True)
        h = paddle.matmul(x, w)
        loss = paddle.mean(paddle.multiply(h, h))
        loss.backward()
        dist.all_reduce(w.grad, group=group)   # unkeyable: pg-less group
        opt.step()
        opt.clear_grad()


def _demo_serve(steps):
    """Tiny continuous-batching serving run (paddle_tpu/serving): a small
    GPT over a deliberately tight KV pool AND a bounded queue, so the
    report shows the full serve.* lifecycle — kv_exhausted evictions plus
    the PR 7 resilience codes (queue_full refusal, client_cancel,
    deadline_expired) — and the PR 11 kernel-tier codes: the engine
    requests the Pallas kernel (demoted to blockwise off-TPU:
    `kernel_fallback`) over an int8 KV pool (`kv_quantized`). `--steps`
    is the number of requests churned through the batch."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine, ServeRefusal

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = LLMEngine(model, max_batch_size=3, block_size=4,
                       num_blocks=10, watermark_blocks=1,
                       max_queue_depth=max(4, steps),
                       attention_kernel="pallas", kv_dtype="int8")
    rng = np.random.default_rng(0)
    base = (11, 12, 10, 5, 7, 9)
    prompts = [rng.integers(0, 128, base[i % len(base)]).tolist()
               for i in range(max(len(base), steps))]
    reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
    # one stream the client abandons, one with a TTL the queue ahead of
    # it will outlast (it expires while QUEUED, at an iteration boundary)
    engine.cancel(reqs[-1].rid)
    engine.add_request(prompts[0], max_new_tokens=8, ttl_s=0.01)
    # fill the bounded queue until admission refuses
    try:
        for _ in range(2 * len(prompts)):
            engine.add_request(prompts[1], max_new_tokens=8)
    except ServeRefusal:
        pass
    engine.run()


def _demo_sample(steps):
    """Compiled-sampling + pipelined-decode fixture (PR 18,
    serving/sampling.py): mixed greedy/stochastic streams on a lag-1
    pipelined engine — per-slot temperature/top-k/top-p/penalty/seed ride
    the ONE decode program as value buffers, so the report must show a
    single decode compile across the whole heterogeneous churn. The
    serve section's `serve.sample` events carry the two PR 18 reason
    codes: a `sampler_mismatch` refusal (an out-of-contract sampler is
    rejected at admission, never silently clamped — a clamp would break
    the (seed, prompt, sampler) reproducibility contract) and the
    `commit_lag_rollback` cost of a client cancel landing at the lag-1
    pipeline boundary (one speculative token, by design)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine, ServeRefusal

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = LLMEngine(model, max_batch_size=3, block_size=4,
                       logprobs_topk=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, int(n)).tolist()
               for n in rng.integers(4, 12, max(6, steps))]
    cfgs = [dict(),                                        # greedy slot
            dict(temperature=0.8, top_k=16, seed=101),
            dict(temperature=0.9, top_p=0.9,
                 repetition_penalty=1.2, seed=102)]
    reqs = [engine.add_request(p, max_new_tokens=8,
                               **cfgs[i % len(cfgs)])
            for i, p in enumerate(prompts)]
    # an out-of-contract sampler: refused at admission (sampler_mismatch)
    try:
        engine.add_request(prompts[0], max_new_tokens=8, temperature=-1.0)
    except (ServeRefusal, ValueError):
        pass
    # a client cancel while a pipelined launch is in flight: the commit
    # discards exactly that stream's speculative token (lag-1 rollback)
    for _ in range(6):
        engine.step()
    engine.cancel(reqs[1].rid)
    engine.run()


def _demo_tenants(steps):
    """Multi-tenant serving fixture (PR 17, serving/tenancy.py): eight
    tenants share one system prompt on a prefix-cache + batched-adapter
    + hot-swap engine, with a live weight swap mid-churn. The report's
    serving section shows the tenant line (prefix hits/misses/evictions/
    swaps) and `prefix_hit` findings with the aliasing hint — a CLEAN
    run: every code here is economy attribution, not a failure."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = LLMEngine(model, max_batch_size=4, block_size=4,
                       num_blocks=96, enable_prefix_cache=True,
                       max_adapters=4, adapter_rank=2, hot_swap=True)
    engine.register_adapter("tenant-a", seed=1, scale=8.0)
    engine.register_adapter("tenant-b", seed=2, scale=8.0)
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(0, 128, 12).tolist()
    n = max(8, steps)
    plan = ("tenant-a", None, "tenant-b", None)
    for i in range(n):
        engine.add_request(system_prompt
                           + rng.integers(0, 128, 3).tolist(),
                           max_new_tokens=6, adapter=plan[i % len(plan)])
    for _ in range(3):
        engine.step()
    # live hot-swap mid-churn: same weights perturbed — the in-flight
    # streams re-prefill under the new epoch, zero recompiles
    engine.swap_weights([np.asarray(p._value) * 1.0001
                         for p in model.parameters()])
    engine.run()


def _demo_metrics(steps):
    """Telemetry-plane acceptance fixture: the masked GPT-ish loop run
    with FLAGS_metrics armed AND a guardian skip-step injected mid-run
    (FLAGS_check_numerics + guardian.inject_fault), so the doctor's
    `--metrics` summary shows a live registry with train_step_seconds
    percentiles, a goodput below 1.0, and the skipped-step wall time
    attributed to the `skipped` bucket."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops import guardian
    from paddle_tpu.profiler.metrics import reset_metrics

    set_flags({"FLAGS_metrics": True, "FLAGS_check_numerics": True,
               # warn-don't-raise: the injected NaN must flow into the
               # gradients so the guardian's skip-step rescue (not the
               # forward raise) is what the goodput report attributes
               "FLAGS_check_numerics_level": 1})
    reset_metrics()
    try:
        # fire while the loop is still eager (pre-promotion) so the NaN
        # poisons one step's grads and the update skips bitwise
        guardian.inject_fault("nan_output", op="matmul", after=8, times=1)
        _demo("masked", steps)
        guardian.flush()
    finally:
        guardian.clear_faults()
        set_flags({"FLAGS_check_numerics": False,
                   "FLAGS_check_numerics_level": 0})


def _demo_pp(steps):
    """Pipeline-parallel acceptance fixture: PipelineParallel.train_batch
    over a pipe=2 × virtual=2 interleaved mesh. The train step routes
    through the ops/spmd_fusion.py pipeline registry: ONE ppermute-handoff
    shard_map program, promoted with a canonical mesh-keyed signature —
    the report reads clean_promotion with step.promote + step.fire from
    the pipeline funnel. Eager per-op fusion stays OFF here: stage compute
    lives inside the compiled program, there is no eager cycle to record
    (runs on the emulated multi-device CPU mesh; --demo pp arms
    xla_force_host_platform_device_count=8 automatically)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.distributed.fleet.meta_parallel import (
        PipelineParallel, PipelineLayer)
    from paddle_tpu.incubate.models import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
        gpt_pipeline_layers)

    from paddle_tpu.framework.flags import set_flags

    if jax.device_count() < 2:
        raise SystemExit(
            "--demo pp needs >=2 devices; run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    # eager fusion OFF: every stage op runs under the pipeline program's
    # jit trace (tracer inputs) — recording those as poisons would be
    # noise about a loop that has no eager cycle at all
    set_flags({"FLAGS_eager_op_cache": False,
               "FLAGS_eager_chain_fusion": False,
               "FLAGS_eager_step_fusion": False})
    mesh = build_mesh(dp=1, pp=2, sharding=1, sep=1, mp=1,
                      devices=jax.devices()[:2])
    set_global_mesh(mesh)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=4,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=32, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    pl = PipelineLayer(gpt_pipeline_layers(model), num_stages=2,
                       loss_fn=GPTPretrainingCriterion(),
                       num_virtual_pipeline_stages=2)
    runner = PipelineParallel(pl, hcg=None)
    runner.accumulate_steps = 4
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 128, (4, 16)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 128, (4, 16)), jnp.int32)
    for _ in range(steps):
        runner.train_batch((ids, labels), opt)


def _demo_moe(steps):
    """Mixture-of-experts acceptance fixture: an MoELayer (gshard top-2
    gate) training loop. The expert dispatch fn closes over the layer —
    formerly an unkeyable closure that poisoned every cycle — but now
    stamps its (kind, gate, d_model, expert-axis, capacity) identity via
    dispatch.mark_collective, so the whole step promotes through the
    funnel: clean_promotion, zero steady-state retraces."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_chain_fusion": True,
               "FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion": True,
               "FLAGS_eager_step_fusion_min_count": 5})
    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        (rng.standard_normal((16, 32)) * 0.5).astype(np.float32))
    moe = MoELayer(d_model=32, d_hidden=64, num_experts=8, gate="gshard")
    moe.train()
    opt = paddle.optimizer.SGD(learning_rate=1e-2,
                               parameters=moe.parameters())
    for _ in range(steps):
        y = moe(x)
        loss = paddle.mean(paddle.multiply(y, y)) + 0.01 * moe.l_aux
        loss.backward()
        opt.step()
        opt.clear_grad()


def _print_goodput(g):
    """One-line goodput rendering shared by --metrics and --url: the
    fraction, the buckets, and WHICH steps each non-productive bucket
    claimed (the PR 13 per-step attribution rings)."""
    print(f"goodput : {g['goodput']} over {g['steps']} step(s) "
          f"(p50 {g['step_ms_p50']} ms, buckets {g['buckets_s']})")
    for b, pretty in sorted((g.get("step_indices_pretty") or {}).items()):
        print(f"          {b} at step(s) {pretty}")


def _url_report(args) -> int:
    """`fusion_doctor --url http://host:port`: fetch the live /doctor
    report from a running process's telemetry server and render it
    exactly like a local run (JSON schema identical to --json, metrics/
    goodput sections present when the process has FLAGS_metrics armed)."""
    import urllib.request

    url = args.url.rstrip("/") + "/doctor"
    try:
        with urllib.request.urlopen(url, timeout=15) as r:
            report = json.loads(r.read().decode())
    except Exception as e:
        print(f"fusion_doctor: could not reach {url}: {e}\n"
              "is the process running with FLAGS_telemetry_port set?",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(report, indent=2))
        return 0
    from paddle_tpu.profiler.explain import format_report
    print(format_report(report))
    if report.get("metrics"):
        from paddle_tpu.profiler.metrics import format_metrics_summary
        print(format_metrics_summary(report["metrics"]))
    if report.get("goodput"):
        _print_goodput(report["goodput"])
    return 0


def _watch_url(args) -> int:
    """`fusion_doctor --watch --url http://host:port`: poll the live
    /sentinel endpoint (--steps polls, ~2 s apart), one status line per
    window plus the full verdict on every latch transition. Exit 1 when
    drift is still latched at the end, so a supervisor can wire this as
    a probe."""
    import time as _time
    import urllib.request

    url = args.url.rstrip("/") + "/sentinel"
    was_degraded = None
    snap = {}
    for i in range(max(1, args.steps)):
        try:
            with urllib.request.urlopen(url, timeout=15) as r:
                snap = json.loads(r.read().decode())
        except Exception as e:
            print(f"fusion_doctor: could not reach {url}: {e}\n"
                  "is the process running with FLAGS_telemetry_port and "
                  "FLAGS_sentinel set?", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(snap))
        else:
            checks = snap.get("checks") or {}
            state = "DRIFT" if snap.get("degraded") else (
                "armed" if snap.get("armed") else "disarmed")
            print(f"[{i:>3}] {state:<8} leg={snap.get('leg') or '-'} "
                  f"windows={snap.get('windows', 0)} "
                  f"checks={json.dumps(checks, sort_keys=True)}")
            if snap.get("degraded") != was_degraded:
                f = snap.get("finding")
                if snap.get("degraded") and f:
                    print(f"      verdict {f.get('reason')}: "
                          f"{f.get('message')}")
                elif was_degraded:
                    print("      recovered: bands clean again")
        was_degraded = bool(snap.get("degraded"))
        if i + 1 < max(1, args.steps):
            _time.sleep(2.0)
    return 1 if snap.get("degraded") else 0


def _print_sentinel(s):
    """Text rendering of the sentinel section (`--watch` local runs)."""
    if not s:
        return
    state = "DRIFT" if s.get("degraded") else "clean"
    print(f"sentinel: {state} | leg {s.get('leg') or '(self-calibrated)'} "
          f"| {s.get('windows', 0)} window(s), "
          f"checks {json.dumps(s.get('checks') or {}, sort_keys=True)}")
    for f in s.get("findings") or []:
        print(f"          {f.get('reason')}: {f.get('message')}")


def _cache_report(args) -> int:
    """`fusion_doctor --cache`: list the AOT executable store (kind,
    digest, size, age, environment-fingerprint match, label), report
    corrupt/quarantined/skewed entries, and with `--gc` run the size/age
    eviction manually."""
    from paddle_tpu.ops import aot_cache

    root = args.cache_dir or aot_cache.cache_dir()
    entries = aot_cache.store_entries(root)
    removed = []
    if args.gc:
        # the listing just CRC-verified every artifact: quarantine the
        # ones that failed so the sweep below removes them too
        for e in entries:
            if e["corrupt"] and not e["quarantined"]:
                p = os.path.join(root, e["file"])
                try:
                    os.replace(p, p + ".corrupt")
                except OSError:
                    pass
        removed = aot_cache.gc_store(root, purge_quarantine=True)
        entries = aot_cache.store_entries(root)
    n_corrupt = sum(1 for e in entries if e["corrupt"] or e["quarantined"])
    n_skew = sum(1 for e in entries
                 if e["fingerprint_match"] is False and not e["corrupt"]
                 and not e["quarantined"])
    total = sum(e["bytes"] for e in entries)
    if args.json:
        print(json.dumps({
            "dir": root, "entries": entries, "total_bytes": total,
            "corrupt": n_corrupt, "version_skew": n_skew,
            "fingerprint": aot_cache.fingerprint_digest(),
            "evicted": removed}, indent=2))
        return 0
    print(f"AOT executable store: {root}")
    print(f"  fingerprint {aot_cache.fingerprint_digest()} | "
          f"{len(entries)} artifact(s), {total / 1024:.1f} KiB | "
          f"{n_corrupt} corrupt/quarantined, {n_skew} version-skewed")
    if removed:
        print(f"  gc removed {len(removed)} file(s): "
              + ", ".join(removed[:8])
              + (" …" if len(removed) > 8 else ""))
    if entries:
        # provenance on a fleet-shared store: `host` names the member
        # that paid the export the rest of the fleet warm-starts from
        print(f"  {'kind':<7} {'digest':<12} {'size':>9} {'age':>8} "
              f"{'fp':>4} {'state':<8} {'host':<12} label")
        for e in entries:
            state = ("QUARANT" if e["quarantined"]
                     else "CORRUPT" if e["corrupt"] else "ok")
            fp = {True: "ok", False: "SKEW", None: "?"}[
                e["fingerprint_match"]]
            age = e["age_s"]
            age_s = f"{age / 3600:.1f}h" if age >= 3600 else f"{age:.0f}s"
            print(f"  {e['kind']:<7} {e.get('digest', '?')[:12]:<12} "
                  f"{e['bytes']:>9} {age_s:>8} {fp:>4} {state:<8} "
                  f"{(e.get('host') or '?')[:12]:<12} "
                  f"{e['label'] or ''}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fusion_doctor",
        description="explain why a training loop didn't promote/split "
                    "(fusion flight-recorder root-cause report)")
    ap.add_argument("script", nargs="?",
                    help="training script to run under the recorder")
    ap.add_argument("script_args", nargs=argparse.REMAINDER,
                    help="arguments passed to the script (after --)")
    ap.add_argument("--demo", choices=("dropout", "masked", "accum",
                                       "serve", "sample", "tenants",
                                       "dp", "pp", "moe", "metrics"),
                    help="run a built-in tiny GPT-ish demo loop instead "
                         "of a script (`dropout`: hoisted-key dropout "
                         "promotes cleanly; `accum`: a k=4 grad-"
                         "accumulation loop promotes as a super-cycle; "
                         "`serve`: a continuous-batching serving run "
                         "over a tight KV pool; `sample`: mixed "
                         "greedy/stochastic streams on a lag-1 "
                         "pipelined engine — sampler_mismatch refusal + "
                         "commit_lag_rollback; `tenants`: eight "
                         "tenants sharing a system prompt on a "
                         "prefix-cache + adapter + hot-swap engine; "
                         "`dp`: a sharded "
                         "data-parallel loop whose unkeyable grad "
                         "collective blocks promotion — "
                         "collective_unkeyed; `pp`: a pipe=2 × virtual=2 "
                         "interleaved pipeline promoting through the "
                         "spmd_fusion pipeline registry; `moe`: a keyed "
                         "gshard MoE layer riding the funnel; `metrics`: "
                         "the telemetry plane armed over a promoting "
                         "loop with an injected guardian skip — live "
                         "goodput/MFU)")
    ap.add_argument("--steps", type=int, default=20,
                    help="demo loop steps (requests, for --demo serve; "
                         "default 20)")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON instead of text")
    ap.add_argument("--metrics", action="store_true",
                    help="arm the telemetry plane (FLAGS_metrics) for "
                         "the run and append the live registry summary "
                         "+ goodput accounting to the report")
    ap.add_argument("--cache", action="store_true",
                    help="inspect the persistent AOT executable store "
                         "(ops/aot_cache.py) instead of running a script: "
                         "list artifacts with fingerprint/corruption "
                         "state; combine with --gc to evict")
    ap.add_argument("--cache-dir", default=None,
                    help="AOT store root (default: the configured "
                         "FLAGS_aot_cache_dir / $PADDLE_TPU_CACHE_DIR/aot)")
    ap.add_argument("--url", default=None, metavar="http://host:port",
                    help="pull the report from a RUNNING process's "
                         "telemetry server /doctor endpoint "
                         "(FLAGS_telemetry_port) instead of running "
                         "anything locally")
    ap.add_argument("--lint", action="store_true",
                    help="run the promotion-safety static analyzer "
                         "(paddle_tpu/analysis, baseline applied) and "
                         "cross-reference runtime split/poison reasons "
                         "with the static findings that predicted them")
    ap.add_argument("--gc", action="store_true",
                    help="with --cache: run the size/age eviction now "
                         "(also removes quarantined *.corrupt files)")
    ap.add_argument("--watch", action="store_true",
                    help="arm the performance regression sentinel "
                         "(profiler/sentinel.py). With --url: poll the "
                         "running process's /sentinel endpoint (--steps "
                         "polls, one line each, exit 1 if drift is "
                         "latched). Locally: watch the --demo/script run "
                         "and append the sentinel verdict to the report")
    args = ap.parse_args(argv)
    if args.demo == "pp" and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the pipe demo needs a multi-device mesh; arm the emulated CPU
        # topology BEFORE the first jax import below
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
    if args.url:
        if args.watch:
            return _watch_url(args)
        return _url_report(args)
    if args.cache:
        return _cache_report(args)
    if not args.demo and not args.script:
        ap.error("either a script, --demo, --cache, or --url is required")

    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.profiler.events import EVENTS, clear_fusion_events
    from paddle_tpu.profiler.explain import explain, format_report

    clear_fusion_events()
    set_flags({"FLAGS_profiler_events": True})
    if args.watch:
        # short windows for a bounded doctor run: a 20-step demo should
        # still see a few evaluation windows (FLAGS_sentinel_window_s
        # governs long-running processes, not this)
        from paddle_tpu.profiler import sentinel as _sentinel
        _sentinel.arm(window_s=0.5)
    want_metrics = args.metrics or args.demo == "metrics"
    if want_metrics:
        from paddle_tpu.profiler.metrics import reset_metrics
        reset_metrics()
        set_flags({"FLAGS_metrics": True})
    try:
        if args.demo == "serve":
            _demo_serve(args.steps)
        elif args.demo == "sample":
            _demo_sample(args.steps)
        elif args.demo == "tenants":
            _demo_tenants(args.steps)
        elif args.demo == "dp":
            _demo_dp(args.steps)
        elif args.demo == "pp":
            _demo_pp(args.steps)
        elif args.demo == "moe":
            _demo_moe(args.steps)
        elif args.demo == "metrics":
            _demo_metrics(args.steps)
        elif args.demo:
            _demo(args.demo, args.steps)
        else:
            sa = args.script_args
            if sa and sa[0] == "--":
                sa = sa[1:]
            old_argv = sys.argv
            sys.argv = [args.script] + sa
            try:
                runpy.run_path(args.script, run_name="__main__")
            except SystemExit as e:
                if e.code not in (0, None):
                    print(f"fusion_doctor: script exited with {e.code} "
                          "(reporting on the events recorded so far)",
                          file=sys.stderr)
            finally:
                sys.argv = old_argv
    finally:
        set_flags({"FLAGS_profiler_events": False})

    report = explain(EVENTS.snapshot())
    if args.watch:
        from paddle_tpu.profiler import sentinel as _sentinel
        report["sentinel"] = _sentinel.sentinel_report()
        _sentinel.disarm()
    if args.lint:
        _attach_lint(report)
    if want_metrics:
        from paddle_tpu.profiler.metrics import (format_metrics_summary,
                                                 metrics_snapshot)
        from paddle_tpu.profiler.goodput import goodput_snapshot
        report["metrics"] = metrics_snapshot()
        report["goodput"] = goodput_snapshot()
        set_flags({"FLAGS_metrics": False})
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(format_report(report))
        if args.watch:
            _print_sentinel(report.get("sentinel") or {})
        if args.lint:
            _print_lint(report.get("lint") or {})
        if want_metrics:
            print(format_metrics_summary(report["metrics"]))
            _print_goodput(report["goodput"])
    return 0


def _attach_lint(report):
    """`fusion_doctor --lint`: run the static analyzer over the repo
    (suppression baseline applied) and cross-reference the RUNTIME
    split/poison/bypass reasons of this report with the STATIC findings
    carrying the same reason code — "this `rng_rekey` split was
    statically predicted at ops/random_ops.py:NN". One taxonomy, two
    observation times."""
    from paddle_tpu.analysis import analyze, Baseline, findings_to_dicts
    from paddle_tpu.analysis.baseline import DEFAULT_BASELINE

    findings = analyze()
    bl = Baseline.load(DEFAULT_BASELINE)
    live, muted = bl.split(findings)
    report["lint"] = {
        "findings": findings_to_dicts(live),
        "suppressed": len(muted),
        "stale_suppressions": len(bl.stale(findings)),
    }
    # runtime reasons observed in THIS window, by source section
    runtime = {}
    step = report.get("step") or {}
    for src in (step.get("split_reasons"), step.get("poisons"),
                (report.get("dispatch") or {}).get("bypass_reasons"),
                (report.get("chain") or {}).get("split_reasons")):
        for r in (src or {}):
            runtime[r] = runtime.get(r, 0) + (src[r].get("count") or 0)
    predicted = []
    for f in live:
        if runtime.get(f.reason_code):
            predicted.append(
                f"runtime `{f.reason_code}` (×{runtime[f.reason_code]}) was "
                f"statically predicted at {f.file}:{f.line} ({f.rule}: "
                f"{f.message})")
    report["lint"]["predicted"] = predicted
    report.setdefault("findings", []).extend(predicted)


def _print_lint(lint):
    n = len(lint.get("findings") or [])
    print(f"lint  : {n} unsuppressed static finding(s), "
          f"{lint.get('suppressed', 0)} suppressed, "
          f"{lint.get('stale_suppressions', 0)} stale suppression(s)")
    for f in (lint.get("findings") or [])[:12]:
        print(f"  - {f['file']}:{f['line']}: {f['rule']} "
              f"[{f['reason_code']}] {f['message']}")


if __name__ == "__main__":
    sys.exit(main())
