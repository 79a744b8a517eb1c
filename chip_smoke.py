#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one chip: device, `jit.TrainStep`
                                     training, the eager loop promoted to a
                                     whole-step executable, `LLMEngine`
                                     serving with both attention variants
                                     over GPT's per-head pools and over a
                                     latent pool (a small LongCat-Flash),
                                     the grouped expert matmul kernel
    python chip_smoke.py --chips 4   four chips: the hybrid-parallel mesh
                                     (dp=2 x mp=2) and its one-device
                                     control, and no other phase

Every phase drives the entry points a user calls, at the full width of a
GPT-2 the repo supports, with random weights made from `--seed`, and
checks what comes out. One process (the chip belongs to one process), one
JSON record per phase on stdout, and as the LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

A failed check raises: the script then exits non-zero and prints no such
line. Without a TPU it fails at once. The phase functions take their sizes
as arguments so that tests/test_chip_smoke.py can rehearse them on the CPU
at a tiny size; this script itself has no CPU mode.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import re
import sys
import time
import warnings

import numpy as np

# bf16 keeps 8 significand bits: two compiled programs that round at
# different places agree to about this relative error
BF16_EPS = 2.0 ** -8


class SmokeFailure(AssertionError):
    """A phase produced something wrong."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def emit(record):
    print(json.dumps(record), flush=True)


@contextlib.contextmanager
def donation_honoured():
    """Fail if XLA could not use a buffer the program donated."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        yield
    for w in caught:
        check("donated buffers were not usable" not in str(w.message),
              f"donation not honoured: {w.message}")
        warnings.warn_explicit(w.message, w.category, w.filename, w.lineno)


@contextlib.contextmanager
def flight_recorder(capacity=None):
    """Arm the fusion flight recorder, empty, for the block; read the
    events inside it."""
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.profiler import clear_fusion_events
    armed = {"FLAGS_profiler_events": True}
    if capacity is not None:
        armed["FLAGS_profiler_events_capacity"] = capacity
    prev = get_flags(list(armed))
    set_flags(armed)
    clear_fusion_events()
    try:
        yield
    finally:
        set_flags(prev)
        clear_fusion_events()


def memory_record(device):
    """Bytes on `device` now and the process's high-water mark so far;
    {} where the backend does not report them (the CPU)."""
    stats = device.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def timed(fn):
    """(result, seconds) with the device drained inside the window."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def make_batch(cfg, batch, seq, seed):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    rng = np.random.default_rng(seed)
    ids, labels = (jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                               jnp.int32) for _ in range(2))
    return (paddle.Tensor(ids, stop_gradient=True),
            paddle.Tensor(labels, stop_gradient=True))


def make_model(cfg, seed):
    """bf16 weights; the AdamW beside it keeps the float32 masters."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    return model


# A LongCat-Flash small enough to compile in seconds, at the published
# widths of its latent row (512 + 64 values, padded to 640 in the pool)
# and heads (128 + 64 wide queries, values of 128). Every token chooses
# EVERY expert, real and identity: with no discrete choice in it, two bf16
# programs that round at different places part by a rounding and not by
# an expert (PERF.md section 4, "a model that routes").
LATENT_SMOKE = dict(
    vocab_size=4096, hidden_size=512, ffn_hidden_size=1024,
    expert_ffn_hidden_size=256, num_layers=2, num_attention_heads=16,
    q_lora_rank=256, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=4,
    zero_expert_num=2, moe_topk=6, max_position_embeddings=1024)


def make_latent_model(sizes, seed):
    """`LongCatFlashForCausalLM` at `sizes` around bf16 weights from
    `seed` (norm scales 1, the rest N(0, `initializer_range`))."""
    import jax.numpy as jnp
    from paddle_tpu.incubate.models.longcat_flash import (
        LongCatFlashConfig, LongCatFlashForCausalLM, param_shapes)
    cfg = LongCatFlashConfig(**sizes)
    rng = np.random.default_rng(seed)
    weights = {
        name: jnp.ones(shape, jnp.bfloat16) if len(shape) == 1
        else jnp.asarray(rng.normal(0.0, cfg.initializer_range, shape),
                         jnp.bfloat16)
        for name, shape in param_shapes(cfg).items()}
    return LongCatFlashForCausalLM(cfg, weights=weights)


# A Solar-Open2 small enough to compile in seconds whose KDA layers keep
# the cell's two state parts at their published shapes a head (a matrix of
# 128 x 128 float32, convolutions of 4 taps over heads of 128): one gated
# softmax layer and three delta-rule layers, every expert chosen (no
# discrete choice, as above).
STATE_SMOKE = dict(
    vocab_size=4096, hidden_size=512, moe_intermediate_size=256,
    num_hidden_layers=4,
    layer_types=("full_attention",) + ("linear_attention",) * 3,
    num_attention_heads=8, num_key_value_heads=2, head_dim=128,
    linear_num_heads=8, linear_head_dim=128, n_routed_experts=4,
    num_experts_per_tok=4, max_position_embeddings=1024)


def make_state_model(sizes, seed):
    """`SolarOpen2ForCausalLM` at `sizes` around bf16 weights from `seed`:
    the model's own draws (norm scales 1, decays as a trained model's)."""
    import jax.numpy as jnp
    from paddle_tpu.incubate.models.solar_open2 import (
        SolarOpen2Config, SolarOpen2ForCausalLM, _initial, param_shapes)
    cfg = SolarOpen2Config(**sizes)
    rng = np.random.default_rng(seed)
    return SolarOpen2ForCausalLM(cfg, weights={
        name: jnp.asarray(_initial(name, shape, cfg, rng), jnp.bfloat16)
        for name, shape in param_shapes(cfg).items()})


def make_optimizer(model):
    import paddle_tpu as paddle
    return paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                  parameters=model.parameters(),
                                  multi_precision=True)


def make_trainer(model, donate):
    """The TrainStep the train cells run (`benchmark/programs/paddle_gpt.py`)."""
    from paddle_tpu.incubate.models import GPTPretrainingCriterion
    from paddle_tpu.jit import TrainStep
    opt = make_optimizer(model)
    criterion = GPTPretrainingCriterion()
    return TrainStep(model, lambda logits, y: criterion(logits, y), opt,
                     donate=donate), opt


def run_steps(step, x, y, steps):
    """Compile ahead of time (so the program text can be read), then take
    `steps` steps on the fixed batch. Returns (program text, record)."""
    compiled, compile_s = timed(lambda: step.lower(x, y).compile())
    text = compiled.as_text()
    losses, step_ms = [], []
    for _ in range(steps):
        loss, dt = timed(lambda: step(x, y)._value)
        losses.append(float(loss))
        step_ms.append(round(dt * 1e3, 2))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(not re.search(r"\bf64\[", text),
          "the step program holds float64 values (emulated on the chip)")
    # what the compiler reserves for the program on each device; the
    # backend's peak_bytes_in_use counts live arrays, not these temporaries
    analysis = compiled.memory_analysis()
    program_bytes = {k: getattr(analysis, f"{k}_size_in_bytes")
                     for k in ("argument", "output", "alias", "temp")}
    return text, {"compile_s": round(compile_s, 2), "losses": losses,
                  # the first step still loads the compiled program
                  "first_step_ms": step_ms[0], "step_ms": step_ms[1:],
                  "program_bytes": program_bytes}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def device_phase(cache_dir):
    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    d = jax.devices()[0]
    return {"phase": "device", "platform": d.platform,
            "kind": d.device_kind, "count": len(jax.devices()),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version, "compile_cache": cache_dir}


def train_step_phase(cfg, batch, seq, steps, seed, donate="all",
                     device=None, phase="train_step"):
    """`jit.TrainStep` on a fixed batch, on one device (the first unless
    told otherwise: the mesh phase's control runs here too)."""
    import jax
    device = device or jax.devices()[0]
    with jax.default_device(device):
        model = make_model(cfg, seed)
        step, _ = make_trainer(model, donate=donate)
        x, y = make_batch(cfg, batch, seq, seed)
        with donation_honoured():
            text, rec = run_steps(step, x, y, steps)
    return {"phase": phase, "device": device.id, "batch": batch, "seq": seq,
            "params": model.num_params(), **rec,
            # the Pallas kernels of the step (flash fwd, dq, dkv per layer)
            "tpu_custom_calls": text.count("tpu_custom_call"),
            **memory_record(device)}


def train_eager_phase(cfg, batch, seq, cycles, seed):
    """The README quick-start loop, long enough for whole-step promotion
    to fire and replay. An execution fault on the device must surface: the
    fusion stack would otherwise demote to per-op dispatch and carry on."""
    import jax
    from paddle_tpu.incubate.models import GPTPretrainingCriterion
    from paddle_tpu.profiler import (events_summary, fusion_events,
                                     reset_step_fusion_stats,
                                     step_fusion_stats)

    model = make_model(cfg, seed)
    opt = make_optimizer(model)
    criterion = GPTPretrainingCriterion()
    x, y = make_batch(cfg, batch, seq, seed)

    capacity = 1 << 20          # a cycle is hundreds of dispatch events
    reset_step_fusion_stats()
    losses, cycle_ms = [], []
    with flight_recorder(capacity), donation_honoured():
        for _ in range(cycles):
            t0 = time.perf_counter()
            loss = criterion(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            # read after the step boundary: a read between backward and
            # step would split whole-step observation
            losses.append(float(loss))
            cycle_ms.append(round((time.perf_counter() - t0) * 1e3, 2))
        events = fusion_events()
        stats = step_fusion_stats()

    summary = events_summary(events)
    cats = summary["by_category"]
    check(len(events) < capacity, "flight recorder overflowed")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(cats.get("step.promote", 0) >= 1 and stats["steps_promoted"] >= 1,
          f"the loop never promoted: {stats}")
    check(cats.get("step.fire", 0) >= 3 and stats["fused_steps"] >= 3,
          f"the promoted step did not replay: {stats}")
    hidden = {k: n for k, n in summary["reasons"].items()
              if k.split(":")[1] in ("exec_fault", "trace_fail",
                                     "fail_streak", "unjittable")}
    check(not hidden, f"a device fault was demoted, not raised: {hidden}")
    # (chains split and re-form while the loop warms up; a promoted step
    # on a fixed batch has no reason to)
    check(stats["fallback_splits"] == 0 and stats["deactivated"] == 0
          and not cats.get("step.split"),
          f"the promoted step split or died: {stats} {summary['reasons']}")
    return {"phase": "train_eager", "batch": batch, "seq": seq,
            "cycles": cycles, "losses": [losses[0], losses[-1]],
            # slowest cycle: the one that compiled the whole-step program
            "max_cycle_ms": max(cycle_ms),
            # the first cycles compile one program per op; these are past
            # that and before chain fusion starts regrouping them
            "per_op_cycle_ms": round(
                float(np.median(cycle_ms[2:12] or cycle_ms)), 2),
            "fused_cycle_ms": cycle_ms[-1],
            "steps_promoted": stats["steps_promoted"],
            "fused_steps": stats["fused_steps"],
            "events": {c: cats[c] for c in sorted(cats)
                       if not c.startswith("dispatch.")},
            **memory_record(jax.devices()[0])}


def serve_requests(model, prompts, max_new_tokens, attention_kernel):
    """All prompts through one `LLMEngine` by `add_request` / `step()`.
    Returns (token lists, record)."""
    import jax
    from paddle_tpu.profiler import fusion_events
    from paddle_tpu.serving import FINISHED, LLMEngine

    with flight_recorder(), donation_honoured():
        t0 = time.perf_counter()
        engine = LLMEngine(model, max_batch_size=8, block_size=16,
                           attention_kernel=attention_kernel)
        reqs = [engine.add_request(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        first_decode = None     # decode compiles as of the first decode step
        while engine.step():
            if first_decode is None:
                now = engine.stats()
                if now["steps"] >= 1:
                    first_decode = now["decode_compiles"]
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
        hidden = [e for cat in ("kernel.fallback", "serve.degrade",
                                "serve.hang")
                  for e in fusion_events(cat)]

    check(stats["attention_kernel"] == attention_kernel,
          f"asked for {attention_kernel}, "
          f"engine runs {stats['attention_kernel']}")
    check(not hidden, f"the engine degraded or fell back: {hidden[:3]}")
    check(all(r.state == FINISHED and len(r.generated) == max_new_tokens
              for r in reqs),
          f"unfinished requests: {[(r.rid, r.state) for r in reqs]}")
    check(first_decode == 1 and stats["decode_compiles"] == 1,
          f"decode compiled {first_decode} then "
          f"{stats['decode_compiles']} times, want 1 and 1")
    check(stats["eager_fallbacks"] == 0 and stats["hangs"] == 0
          and stats["failed"] == 0, f"engine faults: {stats}")
    return [list(r.generated) for r in reqs], {
        "attention_kernel": stats["attention_kernel"],
        "requests": len(reqs), "tokens": stats["tokens_generated"],
        "decode_steps": stats["steps"],
        "decode_compiles": stats["decode_compiles"],
        "prefill_compiles": stats["prefill_compiles"],
        # compiles included: this is set-up plus service, not a rate
        "elapsed_s": round(elapsed, 2),
        "p50_step_ms": round(stats["p50_step_ms"], 3),
        **memory_record(jax.devices()[0])}


def greedy_gaps(model, prompts, streams, pad_to):
    """Teacher-forced check of greedy streams against the model's own
    full-sequence forward (plain attention, no paged cache, no kernel):
    for every generated token, how far below the reference's largest logit
    at that position its logit lies. 0 means the reference would have
    picked it too. Returns (per-stream worst gap, largest |logit| seen)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    new = len(streams[0])
    ids = np.zeros((len(streams), pad_to), np.int32)
    at = np.zeros((len(streams), new), np.int32)
    for r, (prompt, out) in enumerate(zip(prompts, streams)):
        ids[r, :len(prompt) + new] = list(prompt) + list(out)
        # the logits at position t choose token t+1; causal attention
        # keeps them blind to the padding behind
        at[r] = len(prompt) - 1 + np.arange(new)
    with paddle.no_grad():
        logits = model(paddle.Tensor(jnp.asarray(ids),
                                     stop_gradient=True))._value
    rows = jnp.take_along_axis(logits, jnp.asarray(at)[:, :, None],
                               axis=1).astype(jnp.float32)
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(np.asarray(streams, np.int32))[:, :, None],
        axis=2)[:, :, 0]
    gaps = jnp.max(jnp.max(rows, axis=2) - chosen, axis=1)
    return np.asarray(gaps).tolist(), float(jnp.max(jnp.abs(rows)))


def serve_phase(cfg, prompt_lens, max_new_tokens, seed):
    """GPT's serve legs: per-head K and V pools."""
    return serve_legs(make_model(cfg, seed), "serve", cfg.vocab_size,
                      prompt_lens, max_new_tokens, seed)


def latent_serve_phase(sizes, prompt_lens, max_new_tokens, seed):
    """The same legs over a LATENT pool (`make_latent_model`): the
    blockwise loop and the Pallas kernel over one row every head shares.
    Its `generate` is not run: it attends expanded where the engine
    attends absorbed (no token-exact twin), a token at a time with no
    compiled loop (184 s for one stream on the chip, PR 38); every stream
    is held to the reference's ranking."""
    return serve_legs(make_latent_model(sizes, seed), "serve_latent",
                      sizes["vocab_size"], prompt_lens, max_new_tokens,
                      seed, with_generate=False)


def state_serve_phase(sizes, prompt_lens, max_new_tokens, seed):
    """The same legs beside a PER-SLOT STATE OF TWO PARTS
    (`make_state_model`): every prefill writes the convolutions' inputs
    (bf16) and the delta rule's matrices (float32) whole, every launch
    moves the active slots' one token on where they lie (on a TPU through
    the update's Pallas kernel). Its `generate` is not run (a token at a
    time through the chunked scan, no compiled loop); every stream is
    held to the model's own full forward, where the rule is one scan
    over the whole sequence."""
    model = make_state_model(sizes, seed)
    parts = model.cache_spec().state_parts
    check([str(np.dtype(dtype or "bfloat16")) for _, _, dtype in parts]
          == ["bfloat16", "float32"] and len(parts[1][1]) == 3,
          f"the state's parts are {parts}")
    # twice the attention legs' slack: a served token's logit passes, a
    # KDA layer, through the bf16 roundings of q and k behind their norms
    # and of the gated output besides an attention layer's one, and the
    # launch's recurrence and the forward's chunked scan sum in another
    # order. On the v5e (PR 48) three of 16 streams read 0.0391 where the
    # attention legs' 4 roundings allow 0.0349; a state lost or stale reads
    # the logits' own spread, tens of times that.
    return serve_legs(model, "serve_state", sizes["vocab_size"],
                      prompt_lens, max_new_tokens, seed,
                      with_generate=False, roundings=8)


def grouped_matmul_phase(rows, k, n, experts, seed, interpret=False):
    """The tiled grouped matmul kernel against `jax.lax.ragged_dot` over
    the same sorted rows: `rows` bf16 rows in `experts` uneven groups (one
    empty, the buffer's last eighth past every group and POISONED), each
    against its own ``[k, n]`` matrix. Both accumulate in float32 from the
    same bf16 operands, so they agree to the order of a product's sums;
    nothing of the poisoned rows may reach a live one."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    rng = np.random.default_rng(seed)
    live = rows - rows // 8
    share = rng.dirichlet(np.ones(experts - 1))
    load = np.floor(share * live).astype(np.int32)
    load[0] += live - load.sum()
    load = np.insert(load, experts // 2, 0)             # an idle expert
    a = rng.normal(0, 1, (rows, k)).astype(np.float32)
    a[live:] = np.nan
    a = jnp.asarray(a, jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 0.02, (experts, k, n)), jnp.bfloat16)
    sizes = jnp.asarray(load, jnp.int32)
    got = np.asarray(gm.grouped_matmul(a, w, sizes, interpret=interpret))
    want = np.asarray(jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32))
    check(got.dtype == np.float32 and got.shape == (rows, n),
          f"the kernel's result is {got.dtype}{got.shape}")
    check(np.isfinite(got[:live]).all(),
          "a poisoned row past the groups reached a live row")
    gap = float(np.abs(got[:live] - want[:live]).max())
    scale = float(np.abs(want[:live]).max())
    # float32 sums of k exact bf16 products in another order
    check(gap <= 1e-5 * math.sqrt(k) * scale,
          f"kernel and ragged_dot differ by {gap} of {scale}")
    return {"phase": "grouped_matmul", "rows": rows, "live_rows": live,
            "groups": experts, "k": k, "n": n, "tiles": gm.tiles(rows, k, n),
            "max_abs_gap": gap, "max_abs": scale}


def serve_legs(model, phase, vocab_size, prompt_lens, max_new_tokens, seed,
               with_generate=True, roundings=4):
    """Serve the same requests with the blockwise loop and with the Pallas
    kernel, each asked for by name (unasked, the engine chooses between
    them from the platform and the pool), and hold every stream to the
    model itself: its full forward's ranking, and (`with_generate`) the
    first stream to `model.generate`'s tokens."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab_size, n).tolist()
               for n in prompt_lens]
    records, streams = [], {}
    for kernel in ("blockwise", "pallas"):
        streams[kernel], rec = serve_requests(model, prompts,
                                              max_new_tokens, kernel)
        records.append({"phase": phase, **rec})

    served = streams["blockwise"] + streams["pallas"]
    asked = prompts + prompts
    if with_generate:
        ref, ref_s = timed(lambda: model.generate(
            jnp.asarray([prompts[0]], jnp.int32),
            max_new_tokens=max_new_tokens, do_sample=False)._value)
        reference = np.asarray(ref)[0].tolist()
        served, asked = served + [reference], asked + prompts[:1]

    # Greedy streams of two bf16 programs that round at different places
    # may swap near-tied tokens, after which their contexts differ (on the
    # v5e the Pallas and blockwise variants part on 6 of these 8 streams,
    # each time over two logits one bf16 rounding apart). So what is
    # REQUIRED of every token of every stream is that the reference
    # forward ranks it (near-)first; the first stream, whose tokens
    # `generate` is known to reproduce on the chip, must do so exactly.
    pad_to = -(-(max(prompt_lens) + max_new_tokens) // 128) * 128
    gaps, scale = greedy_gaps(model, asked, served, pad_to)
    slack = roundings * BF16_EPS * scale
    check(max(gaps) <= slack,
          f"a served token is not the reference's choice: worst logit gaps "
          f"{[round(g, 4) for g in gaps]} (bf16 slack {slack:.4f})")
    if with_generate:
        check(streams["blockwise"][0] == reference,
              f"the engine's first stream is not model.generate's: "
              f"{streams['blockwise'][0]} vs {reference}")
        records[-1].update({"reference_generate_s": round(ref_s, 2),
                            "generate_token_identical": True})
    records[-1].update({
        "pallas_streams_identical": sum(
            a == b for a, b in zip(streams["blockwise"],
                                   streams["pallas"])),
        "worst_logit_gap": round(max(gaps), 5),
        "logit_gap_slack": round(slack, 5)})
    return records


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def placement(arrays, devices):
    """Bytes each device holds of `arrays`, from their addressable shards."""
    held = {d.id: 0 for d in devices}
    for a in arrays:
        for shard in a.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[d.id] for d in devices]


def optimizer_arrays(opt):
    return [v for per_param in opt._accumulators.values()
            for v in per_param.values() if hasattr(v, "addressable_shards")]


def mesh_phase(cfg, batch, seq, steps, devices, seed):
    """dp=2 x mp=2 over four devices: `build_mesh` + `set_global_mesh` +
    `shard_gpt` + sharded optimizer state + `TrainStep` — the path
    `__graft_entry__.dryrun_multichip` walks on virtual devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.sharding_opt import \
        shard_optimizer_states
    from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
    from paddle_tpu.incubate.models import shard_gpt

    check(len(devices) == 4, f"the mesh phase takes 4 devices, "
                             f"got {len(devices)}")
    mesh = build_mesh(dp=2, pp=1, sharding=1, sep=1, mp=2, devices=devices)
    set_global_mesh(mesh)
    try:
        model = make_model(cfg, seed)
        shard_gpt(model, mesh)
        step, opt = make_trainer(model, donate=True)
        params = [p for p in model.parameters() if not p.stop_gradient]
        opt._create_accumulators(params)
        shard_optimizer_states(opt)
        x, y = make_batch(cfg, batch, seq, seed)
        data = NamedSharding(mesh, P(("data", "sharding"), None))
        x = paddle.Tensor(jax.device_put(x._value, data), stop_gradient=True)
        y = paddle.Tensor(jax.device_put(y._value, data), stop_gradient=True)

        state = [p._value for p in params] + optimizer_arrays(opt)
        total = sum(a.nbytes for a in state)
        before = placement(state, devices)
        with donation_honoured():
            text, rec = run_steps(step, x, y, steps)
        state = [p._value for p in params] + optimizer_arrays(opt)
        after = placement(state, devices)
        memory = [memory_record(d) for d in devices]
    finally:
        set_global_mesh(None)

    # mp=2 halves every large matrix; the rest is replicated. No device
    # may hold (nearly) all of the state, before the first step or after
    for held in (before, after):
        check(min(held) > 0 and max(held) < 0.75 * total,
              f"state is not spread over the mesh: {held} of {total} bytes")
    collectives = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
                   for op in ("all-reduce", "reduce-scatter", "all-gather",
                              "collective-permute", "all-to-all")}
    check(collectives["all-reduce"] + collectives["reduce-scatter"] > 0,
          f"no gradient reduction in the compiled step: {collectives}")
    in_use = [m.get("bytes_in_use") for m in memory]
    check(all(b is None for b in in_use) or min(in_use) > 0.25 * max(in_use),
          f"device memory is lopsided: {in_use}")
    return {"phase": "mesh", "mesh": {"data": 2, "model": 2},
            "batch": batch, "seq": seq, "params": model.num_params(),
            **rec, "state_bytes": total,
            "state_bytes_per_device_before": before,
            "state_bytes_per_device_after": after,
            "collectives": collectives,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "memory_per_device": memory}


def losses_agree(mesh_rec, control_rec):
    """Same weights, same batch: the two programs differ in where bf16
    rounds and in what order float32 sums, step after step."""
    a, b = np.asarray(mesh_rec["losses"]), np.asarray(control_rec["losses"])
    tol = 2 * BF16_EPS * np.arange(1, len(a) + 1)
    check(np.all(np.abs(a - b) <= tol * np.abs(b)),
          f"mesh and control losses part: {a.tolist()} vs {b.tolist()}")


# ---------------------------------------------------------------------------
# the real sizes
# ---------------------------------------------------------------------------

def run_one_chip(seed, cache_dir):
    from paddle_tpu.framework.flags import get_flags
    from paddle_tpu.incubate.models import gpt2_124m
    emit(device_phase(cache_dir))
    seq = 1024
    cfg = gpt2_124m(hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    max_position_embeddings=seq)

    rec = train_step_phase(cfg, batch=16, seq=seq, steps=6, seed=seed)
    emit(rec)
    # fa.is_eligible falls to the XLA path without an event: only the
    # program text says whether the flash kernels are really in the step
    check(rec["tpu_custom_calls"] >= 3 * cfg.num_hidden_layers,
          f"flash attention is not in the compiled step "
          f"({rec['tpu_custom_calls']} kernel calls)")
    gc.collect()

    promote_after = get_flags(["FLAGS_eager_step_fusion_min_count"])[
        "FLAGS_eager_step_fusion_min_count"]
    emit(train_eager_phase(cfg, batch=4, seq=seq,
                           cycles=promote_after + 8, seed=seed))
    gc.collect()

    # four prefill buckets (32, 64, 256, 512), prompts inside and at the
    # edges of them
    for rec in serve_phase(cfg, [32, 57, 64, 200, 230, 256, 400, 512],
                           max_new_tokens=64, seed=seed):
        emit(rec)
    gc.collect()
    # the latent kernel's group is 512 tokens: contexts inside one group,
    # across its boundary, and in a second
    for rec in latent_serve_phase(LATENT_SMOKE,
                                  [32, 57, 200, 256, 460, 500, 512, 600],
                                  max_new_tokens=64, seed=seed):
        emit(rec)
    gc.collect()
    # a state of two parts beside the pool: prompts inside one chunk of
    # the scan (64), across chunks and across a span (512)
    for rec in state_serve_phase(STATE_SMOKE,
                                 [32, 57, 64, 130, 200, 256, 460, 600],
                                 max_new_tokens=64, seed=seed):
        emit(rec)
    gc.collect()
    # a decode launch of `serve_lfm2_rag_backlog`: 128 tokens' top 4 of 32
    # experts, a gate product
    emit(grouped_matmul_phase(512, 2048, 1792, 32, seed))


def run_four_chips(seed, cache_dir):
    import jax
    from paddle_tpu.incubate.models import gpt2_355m
    emit(device_phase(cache_dir))
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs four chips, JAX found "
                             f"{len(devices)}")
    seq = 1024
    cfg = gpt2_355m(hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    max_position_embeddings=seq)
    # batch 4: the control holds the whole 355M model, its float32 state
    # and the [batch, seq, vocab] logits on ONE 16 GB chip
    sizes = dict(batch=4, seq=seq, steps=3, seed=seed)
    mesh_rec = mesh_phase(cfg, devices=devices, **sizes)
    emit(mesh_rec)
    gc.collect()
    # the control: the same steps, seed and global batch on one device
    control_rec = train_step_phase(cfg, donate=True, device=devices[-1],
                                   phase="mesh_control", **sizes)
    emit(control_rec)
    losses_agree(mesh_rec, control_rec)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    from paddle_tpu.framework.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {d.platform!r}")
    (run_four_chips if args.chips == 4 else run_one_chip)(args.seed,
                                                          cache_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
