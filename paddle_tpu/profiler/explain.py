"""Fusion doctor core: root-cause aggregation of the flight recorder.

`explain()` turns the raw event timeline (profiler/events.py) into a
structured report answering the one question the counter structs cannot:
*why* didn't this training loop promote (or why did it split)? The report
names the op, the reason code, and the multiplicity — "step never
promoted: `dropout` re-keys every call (rng_rekey ×40)" — and
`format_report()` renders it for humans. `tools/fusion_doctor.py` is the
CLI wrapper.

Works on any list of event dicts: the live ring (default), a Profiler
window (`prof._fusion_events`), or a re-loaded chrome trace
(`load_profiler_result(path).fusion_events`).
"""
from __future__ import annotations

from .events import EVENTS, REASON_CODES

__all__ = ["explain", "format_report", "REASON_HINTS"]

# actionable one-liners per reason code: what the attribution means and the
# ROADMAP-backed fix. Keyed on the public REASON_CODES contract.
REASON_HINTS = {
    "rng_rekey": (
        "the op consumes STATEFUL global randomness (a fresh key baked "
        "into its closure per call) — or a hoisted-key replay saw a "
        "shifted stream position (an extra RNG consumer interleaved, a "
        "mid-cycle reseed). The dropout family, sdpa dropout, and "
        "bernoulli already key on structure via hoisted stream positions "
        "(framework/random.rng_key_input) and promote; route custom "
        "random ops through rng_key_input() the same way, or make the "
        "interleaved consumption per-step-deterministic."),
    "unkeyable_closure": (
        "a per-batch array/Tensor is baked into the op's closure instead "
        "of being a dispatch input. Fix: thread it through the op's "
        "inputs as done for embedding/cross_entropy/attention-mask/"
        "nll_loss."),
    "tracer_input": (
        "the op ran under an outer jax trace (jit/grad of a paddle "
        "function); eager fusion stands down there by design."),
    "cache_disabled": (
        "FLAGS_eager_op_cache is off or its size is 0 — nothing above "
        "the per-op tier can engage."),
    "unjittable": (
        "the op failed to jit and is negative-cached; it hard-breaks any "
        "chain or cycle containing it."),
    "key_mismatch": (
        "a different op (or the same op with different fn/AMP/diff "
        "state) arrived where the template expected another — the loop "
        "body is not actually identical across iterations."),
    "shape_mismatch": (
        "same op, different input shapes/dtypes — variable batch or "
        "sequence length re-keys the template. Fix: pad/bucket shapes."),
    "wiring_mismatch": (
        "dataflow between ops diverged from the recorded template "
        "(a value was fed from a different producer)."),
    "registry_bump": (
        "a kernel override was (de)activated mid-loop, re-keying the "
        "op."),
    "mid_chain_escape": (
        "an intermediate tensor was read (value/grad/hook) before its "
        "chain fired; the chain split to materialize it."),
    "mid_step_peek": (
        "a pending whole-step value (loss/grad/intermediate) was read "
        "before optimizer.step(); the replay split to serve it. Fix: "
        "move logging of loss values after step(), or log every N "
        "steps."),
    "event_mismatch": (
        "the backward/clear_grad/step event order diverged from the "
        "recorded cycle (extra backward, different root, out-of-order "
        "optimizer calls)."),
    "param_mismatch": (
        "the parameter set/binding changed: a buffer was swapped, a "
        "param was added/removed, or an outside grad appeared."),
    "optimizer_state_change": (
        "clip/regularizer attributes, hyper-params, or accumulator "
        "structure changed — the baked step executable is stale (the "
        "program is dropped and rebuilt if the loop re-stabilizes)."),
    "hook_present": (
        "tensor/grad/saved-tensor hooks are installed; a fused replay "
        "cannot honor observer semantics, so fusion stands down."),
    "exec_fault": (
        "a transient XLA execution fault during the fused fire; the "
        "replay fell back per-op (bitwise identical)."),
    "trace_fail": (
        "the fused executable failed to trace; the program was "
        "deactivated."),
    "debug_interrupt": (
        "FLAGS_check_nan_inf / FLAGS_benchmark forces materialized "
        "per-op results; fusion is disabled while set."),
    "flag_off": (
        "a fusion flag was flipped off mid-run."),
    "uncached_dispatch": (
        "an op inside the cycle took the uncached path (first-call "
        "compile or a cache fault) — transient during warmup; "
        "persistent occurrences mean cache thrash (check "
        "FLAGS_eager_op_cache_size / evictions)."),
    "multi_backward": (
        "more than one backward() per cycle. Regular gradient "
        "accumulation — k identical (fwd+bwd) micro-batches then one "
        "step() — now promotes automatically as a SUPER-CYCLE (two "
        "executables, any k); this cycle's backwards were irregular "
        "(differing micro-batch structure, dataflow crossing "
        "micro-batches, a backward outside the recorded ops)."),
    "cycle_too_long": (
        "the cycle exceeded the recording cap (_MAX_CYCLE_OPS); a "
        "whole-step compile would not amortize."),
    "unpromotable_cycle": (
        "build-time qualification failed — see the `why` detail "
        "(no_backward_or_params / param_hooks / nonparam_diff_input / "
        "irregular_accum = multi-backward cycle whose micro-batches are "
        "not k identical segments / ...). With RNG hoisting and "
        "super-cycle promotion in place this verdict should be RARE "
        "enough to page on."),
    "fail_streak": (
        "the promoted step was deactivated after repeated failed "
        "replays — look at the step.split reasons right before it."),
    "nonfinite_output": (
        "a forward output was non-finite (FLAGS_check_numerics guardian). "
        "Re-run with FLAGS_check_nan_inf=1 to localize the op "
        "synchronously; check the LR / init / input pipeline."),
    "nonfinite_skip": (
        "gradients or the UPDATED params/optimizer state were non-finite, "
        "so the guardian applied the update as where(finite, new, old) — "
        "the step was a bitwise no-op. Expected under fp16 GradScaler "
        "warmup; persistent skips mean the loss scale (or the LR) is too "
        "high."),
    "scaler_backoff": (
        "GradScaler shrank the loss scale after consecutive non-finite "
        "steps (update_loss_scaling semantics); the scale is a hoisted "
        "scalar arg, so fusion survives the change."),
    "injected_fault": (
        "a chaos-harness fault hook fired (tools/chaos.py): the event is "
        "deliberate; the surrounding splits/poisons validate recovery."),
    "kv_exhausted": (
        "the serving engine's KV block pool ran dry: a running stream "
        "was preempted (resume re-prefills, tokens stay identical) or a "
        "request was refused at admission. Fix: raise num_blocks, lower "
        "max_batch_size, or shorten max_new_tokens."),
    "bucket_retrace": (
        "a prompt landed in a prefill length bucket that had not "
        "compiled yet — expected at most log2(max_context) times per "
        "engine; frequent occurrences mean the bucket cache is being "
        "discarded (rebuild the engine less often)."),
    "client_cancel": (
        "the client cancelled the request (engine.cancel); its slot/KV "
        "blocks were reclaimed at the iteration boundary without "
        "touching the compiled decode program. Deliberate, not an "
        "error."),
    "deadline_expired": (
        "the request's TTL passed while it was queued or running; the "
        "engine cleared it instead of burning decode steps on a stream "
        "nobody is waiting for. Frequent expiries mean the queue is "
        "deeper than the deadline allows — lower max_queue_depth or add "
        "capacity."),
    "queue_full": (
        "the bounded waiting queue was at max_queue_depth, so admission "
        "refused early (ServeRefusal) instead of queueing doomed work. "
        "Persistent refusals mean sustained overload: add engine "
        "replicas or shed load upstream."),
    "deadline_infeasible": (
        "the estimated queue wait plus service time already exceeds the "
        "request's deadline at enqueue; refusing now is strictly better "
        "than expiring it later. Check the deadline against "
        "max_new_tokens x step latency."),
    "step_hang": (
        "a decode/prefill step did not complete within "
        "FLAGS_serve_step_timeout_ms; the watchdog ran its recovery "
        "ladder (retry -> rebuild executable -> fail active requests). "
        "Organic hangs point at the device runtime — "
        "check serve.degrade events for how far the ladder climbed."),
    "decode_fault": (
        "the compiled decode executable faulted or produced poisoned "
        "output; affected requests were finished token-identically via "
        "the eager generate() fallback and the executable was rebuilt. "
        "Repeated faults on real hardware mean a bad device/driver."),
    "crash_resume": (
        "an in-flight request was re-admitted from a serving-state "
        "snapshot after a restart; resume re-prefills prompt + emitted "
        "tokens and continues byte-identically. Expected exactly once "
        "per interrupted request per restart."),
    "prefix_hit": (
        "admission aliased this prompt's leading tokens onto KV blocks "
        "another stream already prefilled (serving/tenancy.py "
        "PrefixCache): the shared prefix's prefill and KV bytes were "
        "paid once. Benign — the win the prefix cache exists for; a "
        "LOW hit rate under shared-prompt traffic is the thing to "
        "investigate (prompts differing before the first block "
        "boundary never alias)."),
    "adapter_mismatch": (
        "a request named a LoRA adapter the engine does not have "
        "registered (or the engine was built with max_adapters=0); it "
        "was refused rather than silently served base weights. Fix the "
        "routing layer or register_adapter() the tenant before "
        "admitting its traffic."),
    "torn_swap": (
        "a crash-resume snapshot was taken under a different base "
        "weight set (weights-CRC mismatch) than the restoring engine "
        "serves — usually a kill mid-hot-swap. restore_state refused "
        "rather than decode half of every stream per weight set; load "
        "the checkpoint matching the snapshot's CRC (or re-stage the "
        "swap) and restore again."),
    "sampler_mismatch": (
        "a request's sampler config is outside the compiled decode "
        "program's contract (temperature negative/non-finite, top_k "
        "negative, top_p outside (0, 1], repetition_penalty "
        "non-positive): it was refused at admission rather than "
        "silently clamped — a clamp would break the (seed, prompt, "
        "sampler) reproducibility contract. Fix the caller's "
        "parameters; every in-contract value is a pure VALUE edit and "
        "never retraces the decode executable."),
    "commit_lag_rollback": (
        "software-pipelined decode commits each step's tokens one "
        "iteration late (launch N+1, then commit N); a stream that "
        "left its slot in that window — client cancel, TTL expiry, "
        "preemption, or finishing on the committed token — has exactly "
        "one speculative token discarded. By design: boundary "
        "decisions land deterministically at the lag-1 boundary. A "
        "high rollback rate relative to completions means churny "
        "cancel traffic, not an engine bug."),
    "collective_unkeyed": (
        "a collective op's group has no canonically-keyable mesh (a "
        "hand-built Group without a mesh-backed process group), so the "
        "dispatch funnel cannot key it and every cycle containing it is "
        "poisoned. Fix: create groups via new_group()/the default group "
        "so the collective keys by (kind, reduce-op, mesh) — or, in the "
        "single-controller sharded world, drop eager grad collectives "
        "entirely and let the SPMD step promoter fuse the psum."),
    "mesh_mismatch": (
        "the cycle's sharded inputs span different meshes, or a promoted "
        "program's inputs moved to another mesh/layout mid-run — the "
        "compiled collectives would run over the wrong axes, so the "
        "program was dropped to re-promote with a fresh mesh plan. "
        "Expected once per deliberate re-mesh; persistent mismatches "
        "mean the loop alternates placements."),
    "spmd_divergence": (
        "the distributed (shard_map) lowering's probation fire did not "
        "match the eager step: the loss is not a per-sample mean over "
        "the sharded batch (sum reduction, batch-coupled normalization), "
        "so the pmean contract does not hold. The step still fused "
        "through the plain jit lowering (GSPMD-exact); to get explicit "
        "collectives, make the loss a mean over the batch."),
    "pipe_schedule_mismatch": (
        "a promoted pipeline train-step's schedule changed (micro-batch "
        "count, virtual-stage interleave, or optimizer binding) over the "
        "SAME mesh and stage structure, forcing a second compiled "
        "program. Expected once at deliberate schedule boundaries "
        "(curriculum batch-size ramps); a mismatch recorded every step "
        "means the loop alternates schedules and pays a retrace each "
        "time — pin accumulate_steps/num_virtual per phase."),
    "artifact_corrupt": (
        "an AOT store artifact failed its CRC/envelope check (torn "
        "write, bit rot, truncation) — it was quarantined as *.corrupt "
        "and the executable recompiled transparently. Frequent "
        "occurrences point at the storage medium; `fusion_doctor "
        "--cache` lists quarantined files, `--gc` removes them."),
    "version_skew": (
        "an AOT store artifact for this key was built under a different "
        "environment fingerprint (jax/jaxlib/numpy version, backend, "
        "device kind, kernel-routing flags) and was not deserialized — "
        "the executable recompiled. Expected once per key after an "
        "upgrade; persistent skew means mixed worker versions share one "
        "store."),
    "kernel_fallback": (
        "the requested paged-attention kernel variant "
        "(FLAGS_serve_attention_kernel) was ineligible here and the call "
        "fell back to the blockwise path — see the event's `why` detail "
        "(no_pallas / not_on_tpu / shape_unknown / quantized_pool / "
        "row_not_whole_lane_tiles / block_not_whole_sublane_tiles / "
        "block_exceeds_vmem). Same math, no silent wrong-kernel "
        "serving; leave the variant unset (the engine then chooses "
        "what can run) or request 'blockwise' explicitly to quiet "
        "the event."),
    "kv_quantized": (
        "the serving engine's KV cache pool runs int8 with "
        "per-block-per-head scales (quantization/kv_cache.py): half the "
        "bytes per cached token, ~2x the streams per pool before "
        "kv_exhausted. Informational — greedy decode is guarded "
        "token-identical (or top-1-equivalent) to fp32 KV; dequant is "
        "fused into the attention kernels' block loads."),
    "contract_drift": (
        "a public observability contract went open under extension "
        "(fusion linter R5, paddle_tpu/analysis/): a REASON_CODES entry "
        "without a REASON_HINTS hint, a METRIC_NAMES entry without a "
        "METRIC_MERGE fleet policy, an event category emitted off "
        "CATEGORIES, or a FLAGS_* name read without a define_flag "
        "registration. Close the pair next to the code that introduced "
        "the new name and update the contract-freeze tests "
        "deliberately."),
    "lock_discipline": (
        "blocking I/O or a user callback runs while a registry/"
        "scheduler lock is held, or two code paths acquire the same "
        "lock pair in opposite orders (fusion linter R6). Snapshot "
        "under the lock and act after release; keep one global lock "
        "order — the chaos harness can only SAMPLE these races, the "
        "linter proves their absence."),
    # -- elastic fleet fabric (distributed/fabric.py) ----------------------
    "host_lost": (
        "a fleet member missed its FULL heartbeat lease and the "
        "coordinator declared it dead: the generation bumped and the "
        "survivors rebuild. Expected exactly once per real host "
        "failure/preemption; host_lost on a machine that is still up "
        "means the lease (fabric lease_s) is tighter than the host's GC/"
        "checkpoint pauses — a slow-but-alive host inside its lease "
        "must never trip this."),
    "mesh_rebuild": (
        "the fleet generation changed (scale-in after host_lost, or "
        "scale-out on a rejoin) and this process adopted the new spec: "
        "the mesh was rebuilt, the promoted program dropped through the "
        "mesh_mismatch split path, state restored from the latest "
        "StepCheckpointer snapshot and executables warm-started from "
        "the shared AOT store. Expected once per membership change; a "
        "rebuild storm means membership is flapping — check the "
        "coordinator's fleet.leave reasons."),
    "stale_member": (
        "a host is heartbeating (alive) but still reports an older "
        "generation than the fleet — it has not run its rebuild hook "
        "for the current spec. Transient during a rebuild window; "
        "persistent staleness means the host's training loop is wedged "
        "between step boundaries (it only polls the fabric at a "
        "boundary) or its member thread died — check that host's "
        "/fleet and /healthz."),
    # -- regression sentinel verdicts (profiler/sentinel.py) ---------------
    "perf_drift": (
        "goodput fraction or tokens/sec fell below the baseline floor "
        "for a full evaluation window. Read /sentinel (or `fusion_doctor "
        "--watch`) for the drifted metric, then /goodput buckets_s: time "
        "leaking into skipped/stalled/other names the thief; if buckets "
        "look clean the denominator grew — check for a batch/seq-length "
        "change against the baseline leg."),
    "split_regression": (
        "a split/bypass/hang reason outside the baseline histogram "
        "appeared in a steady window (or blew its per-reason cap). The "
        "detail names the reason — chase THAT code's own hint; a steady "
        "loop re-splitting is the regression class the bench ladder "
        "died on, never 'expected churn'."),
    "compile_storm": (
        "retraces or decode/prefill rebuilds exceeded the baseline "
        "allowance after warmup. Diff /metrics.json compile counters "
        "against the baseline record; a steady loop recompiling means "
        "a cache key churns — see the retrace reasons in /events."),
    "latency_drift": (
        "step-time or serve p50/p99 left its tolerance band while "
        "goodput/splits stayed clean: the same work got slower. Suspect "
        "host interference, a device sharing another tenant, or an op "
        "routed off its kernel tier (check kernel.fallback events) "
        "before blaming the model."),
    # -- R7 static twin (analysis/rules/r7_perf_contract.py) ---------------
    "perf_contract": (
        "a perf meter would silently lie: a heavy-compute @register_op "
        "estimate_cycle_flops cannot see (declare its FLOPs via "
        "goodput.declare_op_flops or name it into a known family), or "
        "a program-altering FLAGS_* missing from the AOT env "
        "fingerprint (add it there, or list it in "
        "aot_cache.FUSION_NEUTRAL_FLAGS with a justification)."),
}


def _attr(events, pred):
    """{reason: {"count": n, "ops": {op: n}}} over events matching pred."""
    out = {}
    for e in events:
        if not pred(e):
            continue
        r = e.get("reason") or "unattributed"
        rec = out.setdefault(r, {"count": 0, "ops": {}})
        rec["count"] += 1
        op = e.get("op") or ""
        if op:
            rec["ops"][op] = rec["ops"].get(op, 0) + 1
    return out


def _top_op(rec):
    ops = rec.get("ops") or {}
    return max(ops.items(), key=lambda kv: kv[1])[0] if ops else ""


def explain(events=None):
    """Aggregate flight-recorder events into a root-cause report dict.

    `events`: list of event dicts (default: the live ring). Returns a
    JSON-ready report; feed it to `format_report` for text.
    """
    if events is None:
        events = EVENTS.snapshot()
    cats = {}
    for e in events:
        cats[e["cat"]] = cats.get(e["cat"], 0) + 1

    def n(cat):
        return cats.get(cat, 0)

    step_splits = _attr(events, lambda e: e["cat"] == "step.split")
    # guardian decisions ride step.record with detail.kind == "guardian":
    # they are deliberate outcomes, never cycle poisons (a skipped step
    # still fused) — aggregate them into their own section
    guardian_ev = _attr(
        events, lambda e: (e.get("detail") or {}).get("kind") == "guardian")
    # each guardian decision is stamped with the optimizer step index
    # (guardian.note_step step_index) — so the report can say WHICH step
    # skipped / backed off, not just how many did
    for e in events:
        d = e.get("detail") or {}
        if d.get("kind") == "guardian" and d.get("step") is not None \
                and e.get("reason") in guardian_ev:
            rec = guardian_ev[e["reason"]]
            rec.setdefault("steps", []).append(d["step"])
    poisons = _attr(events, lambda e: e["cat"] == "step.record"
                    and e.get("reason") is not None
                    and (e.get("detail") or {}).get("kind") != "guardian")
    chain_splits = _attr(events, lambda e: e["cat"] == "chain.split")
    bypasses = _attr(events, lambda e: e["cat"] == "dispatch.bypass")
    clean_cycles = dirty_cycles = 0
    build_fail_whys = {}
    for e in events:
        if e["cat"] == "step.record":
            d = e.get("detail") or {}
            if d.get("kind") == "cycle":
                if d.get("clean"):
                    clean_cycles += 1
                else:
                    dirty_cycles += 1
            elif d.get("kind") == "build_fail":
                w = d.get("why", "?")
                build_fail_whys[w] = build_fail_whys.get(w, 0) + 1

    report = {
        "events": len(events),
        "step": {
            "promoted": n("step.promote"),
            "fired": n("step.fire"),
            "splits": n("step.split"),
            "deactivated": n("step.deactivate"),
            "split_reasons": step_splits,
            "poisons": poisons,
            "cycles": {"clean": clean_cycles, "dirty": dirty_cycles},
            "build_failures": build_fail_whys,
        },
        "chain": {
            "detected": n("chain.detect"),
            "compiled": n("chain.compile"),
            "fired": n("chain.fire"),
            "splits": n("chain.split"),
            "stitched": n("chain.stitch"),
            "split_reasons": chain_splits,
        },
        "dispatch": {
            "hits": n("dispatch.hit"),
            "misses": n("dispatch.miss"),
            "bypasses": n("dispatch.bypass"),
            "retraces": n("dispatch.retrace"),
            "bypass_reasons": bypasses,
        },
        # non-finite step guardian (FLAGS_check_numerics, ops/guardian.py):
        # why did step N not update? nonfinite_skip = the where() rescue
        # made it a bitwise no-op; scaler_backoff = the loss scale shrank;
        # injected_fault = the chaos harness did it on purpose
        "guardian": guardian_ev,
    }

    # serving engine (serve.* events, paddle_tpu/serving/engine.py):
    # request lifecycle counts, decode-batch occupancy, and the reasons
    # behind evictions / refusals / prefill compiles
    serve_steps = [e for e in events if e["cat"] == "serve.step"]
    if any(e["cat"].startswith("serve.") for e in events):
        occ = [(e.get("detail") or {}).get("occupancy") for e in serve_steps]
        occ = [o for o in occ if o is not None]
        report["serving"] = {
            "enqueued": n("serve.enqueue"),
            "admitted": n("serve.admit"),
            "decode_steps": n("serve.step"),
            "evictions": n("serve.evict"),
            "completed": n("serve.complete"),
            # resilience decisions (PR 7, serving/resilience.py)
            "cancelled": n("serve.cancel"),
            "expired": n("serve.expire"),
            "refused": n("serve.refuse"),
            "hangs": n("serve.hang"),
            "degraded": n("serve.degrade"),
            "resumed": n("serve.resume"),
            # multi-tenant layer (PR 17, serving/tenancy.py)
            "prefix_hits": n("serve.prefix_hit"),
            "prefix_misses": n("serve.prefix_miss"),
            "prefix_evictions": n("serve.prefix_evict"),
            "weight_swaps": n("serve.swap"),
            "occupancy_mean": (round(sum(occ) / len(occ), 4)
                               if occ else None),
            "reasons": _attr(events,
                             lambda e: e["cat"].startswith("serve.")
                             and e.get("reason") is not None),
        }
        # live registry view (profiler/metrics.py): when the telemetry
        # plane is armed, the doctor cites CURRENT p99 latency, TTFT and
        # refusal rates — not just how many events the window held
        try:
            from .metrics import serve_live_summary
            live = serve_live_summary()
        except Exception:
            live = None
        if live is not None:
            report["serving"]["live"] = live
            try:
                # per-step attribution (PR 13): WHICH decode steps the
                # watchdog stalled, straight off the accountant's
                # bounded ring — "stalled at steps 4096-4103", not just
                # a hang count
                from .goodput import ACCOUNTANT, format_step_ranges
                with ACCOUNTANT._ring_lock:     # /doctor HTTP thread
                    stalled = list(
                        ACCOUNTANT.step_indices.get("stalled") or ())
                if stalled:
                    live["stalled_steps"] = format_step_ranges(stalled)
            except Exception:
                pass

    # AOT executable store (aot.* events, ops/aot_cache.py): how much of
    # the warmup came off disk, and whether any artifact was corrupt or
    # version-skewed (each such decision must explain itself)
    aot_reasons = {}
    if any(e["cat"].startswith("aot.") for e in events):
        aot_reasons = _attr(events,
                            lambda e: e["cat"].startswith("aot.")
                            and e.get("reason") is not None)
        # aot.store events carry a `failed` detail when the export could
        # not be serialized — those must not read as populated-store
        # writes (aot_cache_stats() splits them as store_failures)
        store_fails = sum(1 for e in events if e["cat"] == "aot.store"
                          and (e.get("detail") or {}).get("failed"))
        report["aot"] = {
            "hits": n("aot.hit"),
            "misses": n("aot.miss"),
            "stores": n("aot.store") - store_fails,
            "store_failures": store_fails,
            "corrupt": n("aot.corrupt"),
            "version_skew": n("aot.version_skew"),
            "evicted": n("aot.evict"),
            "reasons": aot_reasons,
        }

    # kernel tier (kernel.* events, kernels/pallas/ + attention routing):
    # which variant demotions happened, and whether the KV cache runs
    # quantized — both must explain themselves, never silently
    kernel_reasons = {}
    if any(e["cat"].startswith("kernel.") for e in events):
        kernel_reasons = _attr(events,
                               lambda e: e["cat"].startswith("kernel.")
                               and e.get("reason") is not None)
        report["kernel"] = {
            "fallbacks": n("kernel.fallback"),
            "reasons": kernel_reasons,
        }

    serve_reasons = (report.get("serving") or {}).get("reasons", {})

    findings = []
    unknown = sorted({r for src in (step_splits, poisons, chain_splits,
                                    bypasses, guardian_ev, serve_reasons,
                                    aot_reasons, kernel_reasons)
                      for r in src
                      if r not in REASON_CODES and r != "unattributed"})
    if unknown:
        findings.append(
            f"UNKNOWN reason code(s) {unknown}: the emitting site is off "
            "the public contract — fix the instrumentation")

    promoted, fired, splits = (report["step"][k] for k in
                               ("promoted", "fired", "splits"))
    if not events:
        verdict = "no_data"
        headline = ("no fusion events recorded — enable "
                    "FLAGS_profiler_events (or run inside a Profiler "
                    "window / fusion_doctor)")
    elif fired and not splits and not poisons:
        verdict = "clean_promotion"
        headline = (f"clean promotion: {fired} fused whole-step "
                    f"replay(s), 0 splits, 0 poisoned cycles")
    elif promoted or fired:
        worst_split = max(step_splits.items(),
                          key=lambda kv: kv[1]["count"], default=None)
        worst_poison = max(poisons.items(),
                           key=lambda kv: kv[1]["count"], default=None)
        if worst_split:
            verdict = "unstable_promotion"
            r, rec = worst_split
            via = _top_op(rec)
            headline = (f"promoted but split {splits}× — dominant cause "
                        f"{r}" + (f" at `{via}`" if via else "")
                        + f" ×{rec['count']}")
        elif worst_poison:
            verdict = "promoted_with_noise"
            r, rec = worst_poison
            headline = (f"promoted, {fired} fired, but cycles keep "
                        f"poisoning: {r} ×{rec['count']}"
                        + (f" at `{_top_op(rec)}`" if _top_op(rec) else ""))
        else:
            # promoted on the window's last boundary: no fire, no split,
            # no poison yet — the loop simply ended too early (a window
            # with fires and a clean record took the first branch)
            verdict = "promoted_not_yet_fired"
            headline = (f"promoted ({promoted}), {fired} fired, 0 splits "
                        "— run more steps for a steady-state verdict")
    elif report.get("serving") and not any(
            e["cat"] == "step.record"
            and (e.get("detail") or {}).get("kind") == "eager_step"
            for e in events):
        # a serving-engine process with NO optimizer-step boundaries: the
        # jit-traced model calls leave cycle-poison noise (tracer_input)
        # that would otherwise read as a broken TRAINING loop — the
        # serving verdict is the truthful one here. A combined
        # train+serve process still gets the training diagnosis above.
        sv = report["serving"]
        verdict = "serving"
        headline = (f"serving: {sv['admitted']} admission(s), "
                    f"{sv['decode_steps']} decode step(s), "
                    f"{sv['evictions']} eviction(s), "
                    f"{sv['completed']} completion(s)"
                    + (f", occupancy {sv['occupancy_mean']}"
                       if sv["occupancy_mean"] is not None else ""))
        if sv["hangs"] or sv["degraded"]:
            # a watchdog firing / degraded-mode transition is the lead
            # story of a serving window, not a footnote — and with the
            # telemetry plane armed, the headline cites the LIVE p99 and
            # refusal rate the degradation is costing users right now
            verdict = "serving_degraded"
            headline = (f"serving DEGRADED: {sv['hangs']} hang(s), "
                        f"{sv['degraded']} degrade transition(s) — "
                        + headline)
            live = sv.get("live")
            if live:
                headline += (f" [live: p99 {live['p99_step_ms']} ms/step, "
                             f"refusal rate {live['refusal_rate']}]")
    elif poisons:
        verdict = "never_promoted"
        r, rec = max(poisons.items(), key=lambda kv: kv[1]["count"])
        via = _top_op(rec)
        headline = (f"step never promoted: "
                    + (f"`{via}` " if via else "")
                    + f"{r} ×{rec['count']}")
    elif clean_cycles:
        verdict = "not_yet_promoted"
        headline = (f"{clean_cycles} clean cycle(s) recorded but the "
                    "promotion threshold (FLAGS_eager_step_fusion_"
                    "min_count) was not reached — run more steps")
    else:
        verdict = "no_step_activity"
        headline = ("no step-fusion activity observed (no optimizer-step "
                    "boundaries in the window)")
    report["verdict"] = verdict
    report["headline"] = headline

    for r, rec in sorted(kernel_reasons.items(),
                         key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        findings.append(
            f"kernel tier {r} ×{rec['count']}" + (f" ({ops})" if ops else "")
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(aot_reasons.items(),
                         key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        findings.append(
            f"aot store {r} ×{rec['count']}" + (f" ({ops})" if ops else "")
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(serve_reasons.items(),
                         key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        findings.append(
            f"serving {r} ×{rec['count']}" + (f" ({ops})" if ops else "")
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(guardian_ev.items(), key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        steps = rec.get("steps") or []
        at = ""
        if steps:
            shown = ", ".join(str(s) for s in steps[:8])
            at = (f" at step(s) {shown}"
                  + (f" (+{len(steps) - 8} more)" if len(steps) > 8 else ""))
        findings.append(
            f"guardian {r} ×{rec['count']}" + (f" ({ops})" if ops else "")
            + at
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(poisons.items(), key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        findings.append(
            f"cycle poison {r} ×{rec['count']}" + (f" ({ops})" if ops else "")
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(step_splits.items(),
                         key=lambda kv: -kv[1]["count"]):
        findings.append(
            f"step split {r} ×{rec['count']}"
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(chain_splits.items(),
                         key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        findings.append(
            f"chain split {r} ×{rec['count']}" + (f" ({ops})" if ops else "")
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for r, rec in sorted(bypasses.items(), key=lambda kv: -kv[1]["count"]):
        ops = ", ".join(f"`{o}`×{c}" for o, c in
                        sorted(rec["ops"].items(), key=lambda kv: -kv[1])[:4])
        findings.append(
            f"dispatch bypass {r} ×{rec['count']}"
            + (f" ({ops})" if ops else "")
            + (f" — {REASON_HINTS[r]}" if r in REASON_HINTS else ""))
    for w, c in sorted(build_fail_whys.items(), key=lambda kv: -kv[1]):
        findings.append(f"promotion build failed: {w} ×{c}")
    report["findings"] = findings
    return report


def format_report(report):
    """Human-readable fusion-doctor report."""
    s = report["step"]
    c = report["chain"]
    d = report["dispatch"]
    lines = [
        "================ fusion doctor ================",
        f"verdict : {report['verdict']}",
        f"headline: {report['headline']}",
        "",
        f"step  : promoted={s['promoted']} fired={s['fired']} "
        f"splits={s['splits']} deactivated={s['deactivated']} "
        f"cycles(clean/dirty)={s['cycles']['clean']}/"
        f"{s['cycles']['dirty']}",
        f"chain : detected={c['detected']} fired={c['fired']} "
        f"splits={c['splits']} stitched={c['stitched']}",
        f"disp  : hits={d['hits']} misses={d['misses']} "
        f"bypasses={d['bypasses']} retraces={d['retraces']}",
    ]
    g = report.get("guardian") or {}
    if g:
        lines.append("guard : " + " ".join(
            f"{r}={rec['count']}" for r, rec in sorted(g.items())))
    a = report.get("aot")
    if a:
        lines.append(
            f"aot   : hits={a['hits']} misses={a['misses']} "
            f"stores={a['stores']} corrupt={a['corrupt']} "
            f"skew={a['version_skew']} evicted={a['evicted']}")
    k = report.get("kernel")
    if k:
        lines.append("kernel: fallbacks=" + str(k["fallbacks"]) + " "
                     + " ".join(f"{r}={rec['count']}"
                                for r, rec in sorted(k["reasons"].items())))
    sv = report.get("serving")
    if sv:
        lines.append(
            f"serve : enqueued={sv['enqueued']} admitted={sv['admitted']} "
            f"steps={sv['decode_steps']} evictions={sv['evictions']} "
            f"completed={sv['completed']}"
            + (f" occupancy={sv['occupancy_mean']}"
               if sv["occupancy_mean"] is not None else ""))
        resil = {k: sv[k] for k in ("cancelled", "expired", "refused",
                                    "hangs", "degraded", "resumed")
                 if sv[k]}
        if resil:
            lines.append("resil : " + " ".join(
                f"{k}={v}" for k, v in sorted(resil.items())))
        tenant = {k: sv.get(k, 0)
                  for k in ("prefix_hits", "prefix_misses",
                            "prefix_evictions", "weight_swaps")}
        if any(tenant.values()):
            lines.append(
                f"tenant: prefix_hits={tenant['prefix_hits']} "
                f"misses={tenant['prefix_misses']} "
                f"evictions={tenant['prefix_evictions']} "
                f"swaps={tenant['weight_swaps']}")
        live = sv.get("live")
        if live:
            lines.append("live  : " + " ".join(
                f"{k}={v}" for k, v in sorted(live.items())))
    if report["findings"]:
        lines.append("")
        lines.append("findings:")
        for f in report["findings"]:
            lines.append(f"  - {f}")
    lines.append("===============================================")
    return "\n".join(lines)
