"""Solar-Open2 on the CPU at a tiny size that keeps every ratio's KIND
(`tests/benchmark/solar_model/tiny_solar.py`): one gated softmax layer
without positions in four beside three gated delta-rule (KDA) layers, a
share of the experts held beside a shared one. The model is held to the
benchmark's plain reference (`benchmark/reference/solar_open2.py`, written
apart from it: the delta rule there is the token-by-token recurrence), the
engine to the reference's full forward over prompt + served tokens, LOGITS
and not tokens: an engine with `logprobs_topk` = the vocabulary hands back
the whole log-softmax of every served position.

Tolerances, each with its reason:

  TOL = 1e-4 of the largest logit, model and engine against the reference.
  Both sides compute in float32 with products at "highest", so they differ
  only in the ORDER of sums: a chunk's WY form against the recurrence, a
  state carried against a whole convolution, a gathered expert against a
  masked one, blockwise softmax. What a lower precision would do fails it
  (the last section): a matrix state rounded to bfloat16 between tokens is
  off by more than 1e-3 of the largest logit, the reference's own fp8
  control by more than 1e-2.

  SCAN_TOL = 2e-5 of the largest output, the chunked scan against the
  recurrence on the same float32 inputs: the two orders of summing over a
  chunk of 64 (measured 4e-6 with decays from 1e-5 to 0.9999 a token and
  steps up to 2).

The weights are seeded as the benchmark seeds them, but the decays are
SPREAD (the tests carry that: the benchmark's N(0, 0.02) leaves give a
half a token everywhere): `dt_bias` from -6 to 3 over the channels, so a
head's channels forget between 0.25% and 95% a token."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests", "benchmark",
                                   "solar_model")]

import paddle_tpu as paddle  # noqa: E402
from benchmark import seeded  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    held_experts)
from paddle_tpu.incubate.models import solar_open2 as so  # noqa: E402
from paddle_tpu.kernels import kda  # noqa: E402
from paddle_tpu.serving import LLMEngine  # noqa: E402
from paddle_tpu.serving.cache import CacheSpec, PagedKVCache  # noqa: E402
from serving_reference import SAMPLERS, Reference, stream_of  # noqa: E402
from tiny_solar import TINY_SOLAR as FILE  # noqa: E402

from benchmark.programs import paddle_solar  # noqa: E402

TOL = 1e-4
SCAN_TOL = 2e-5
VOCAB = FILE["vocab_size"]
KDA_LAYERS = FILE["layer_types"].count("linear_attention")
HEADS, WIDTH = (FILE["linear_attn_config"][k] for k in ("num_heads",
                                                        "head_dim"))
CONV_ROW = 3 * 3 * HEADS * WIDTH        # three inputs of q, k and v each


def weights_of(file, seed=3, std=0.3):
    """Float32 seeded weights by the reference's names, the decays spread
    (the module's docstring)."""
    w = dict(seeded.make_weights(ref.param_shapes(file), seed, jnp.float32,
                                 std))
    for name in w:
        if name.endswith("dt_bias"):
            w[name] = jnp.linspace(-6.0, 3.0, w[name].size,
                                   dtype=jnp.float32)
    return w


def model_of(file, weights):
    return so.SolarOpen2ForCausalLM(paddle_solar._model_config(file),
                                    weights=weights)


@pytest.fixture(scope="module")
def weights():
    return weights_of(FILE)


@pytest.fixture(scope="module")
def model(weights):
    return model_of(FILE, weights)


def highest(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def gap(got, want):
    return float(jnp.max(jnp.abs(got - want))) \
        / float(jnp.max(jnp.abs(want)))


def close(got, want, tol=TOL):
    assert gap(got, want) <= tol, gap(got, want)


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, VOCAB, n).tolist()


# -- (a) the model against the reference --------------------------------------

def test_parameter_names_and_shapes_are_the_references():
    cfg = paddle_solar._model_config(FILE)
    assert so.param_shapes(cfg) == ref.param_shapes(FILE)
    assert list(so.param_shapes(cfg)) == list(ref.param_shapes(FILE))
    names = set(so.param_shapes(cfg))
    assert {"model.layers.0.self_attn.g_proj.weight",
            "model.layers.1.self_attn.q_conv1d.weight",
            "model.layers.1.self_attn.A_log",
            "model.layers.1.self_attn.dt_bias",
            "model.layers.1.self_attn.f_b_proj.weight",
            "model.layers.3.mlp.shared_experts.up_proj.weight",
            "lm_head.weight"} <= names
    # the router ranks the published experts, the stacks hold the share
    assert so.param_shapes(cfg)["model.layers.0.mlp.gate.weight"] == (64, 64)
    assert so.param_shapes(cfg)[
        "model.layers.0.mlp.experts.up_proj.weight"] == (8, 64, 32)
    spec = so.SolarOpen2ForCausalLM(cfg).cache_spec()
    assert (spec.kind, spec.num_layers, spec.num_heads, spec.query_heads,
            spec.head_dim) == ("kv", 2, 2, 8, 8)
    # TWO parts, of different shapes and types
    assert spec.state_layers == KDA_LAYERS == 6
    assert spec.state_parts == (
        ("conv", (CONV_ROW,), None),
        ("delta", (HEADS, WIDTH, WIDTH), jnp.float32))


def test_the_real_configuration_is_the_issues_arithmetic():
    """The file the cell runs: 3,308 M parameters, the pool's row and the
    state's two parts a slot as ISSUE 48 sized them."""
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "solar_open2_250b_ep8.json")) as f:
        real = json.load(f)
    assert ref.num_params(real) == 3_308_352_064
    cfg = paddle_solar._model_config(real)
    assert cfg.layer_types == ("full_attention",) + ("linear_attention",) * 3
    assert cfg.held == (0, 40) and cfg.n_routed_experts == 320
    (_, conv, _), (_, delta, dtype) = cfg.state_parts
    assert conv == (3 * 24576,) and delta == (64, 128, 128)
    assert dtype == jnp.float32


def test_full_forward_logits_agree_over_a_whole_sequence(model, weights):
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, VOCAB, (2, 19)), jnp.int32)
    got = highest(model, paddle.Tensor(ids))._value
    close(got, ref.forward(weights, ids, FILE))


def test_the_reference_in_blocks_of_queries_is_the_reference(weights,
                                                             monkeypatch):
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, VOCAB, (1, 16)), jnp.int32)
    want = ref.forward(weights, ids, FILE)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)
    # the softmax's sums in another order: float32's last digits
    close(ref.forward(weights, ids, FILE), want, 1e-5)


def test_the_dense_caches_carry_both_parts_token_by_token(model, weights):
    """`generate`'s path: a prompt, then a token at a time through the
    (keys, values) pairs and the KDA layers' two state parts, gives the
    full forward's logits at every position."""
    ids = jnp.asarray(np.random.default_rng(2).integers(0, VOCAB, (1, 11)),
                      jnp.int32)
    want = ref.forward(weights, ids, FILE)[0]
    logits, caches = highest(model, paddle.Tensor(ids[:, :3]),
                             caches=model.gen_caches(1, jnp.float32))
    close(logits._value[0], want[:3])
    for t in range(3, 11):
        logits, caches = highest(model, paddle.Tensor(ids[:, t:t + 1]),
                                 caches=caches)
        close(logits._value[0, 0], want[t])
    assert len(caches) == 2 + 6 and caches[0][0].shape[1] == 11
    conv, delta = caches[-1]
    assert tuple(conv.shape) == (1, CONV_ROW)
    assert tuple(delta.shape) == (1, HEADS, WIDTH, WIDTH)
    assert delta._value.dtype == jnp.float32


def test_a_model_nobody_handed_weights_decays_as_a_trained_one():
    """The model's own initialiser draws `A_log` and `dt_bias` as the
    implementation this configuration follows does: A in [1, 16], dt in
    [0.001, 0.1] through softplus' inverse."""
    fresh = so.SolarOpen2ForCausalLM(paddle_solar._model_config(FILE))
    a = np.exp(np.asarray(fresh._w("model.layers.1.self_attn.A_log")))
    dt = np.asarray(jax.nn.softplus(
        fresh._w("model.layers.1.self_attn.dt_bias")))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 0.9e-3 <= dt.min() and dt.max() <= 0.11


# -- (b) the chunked scan against the recurrence ------------------------------

def scan_case(t, seed=0, b=2, h=3, d=32, decays=(1e-4, 12.0)):
    """Unit keys, steps up to 2 (some above 1: negative eigenvalues), log
    decays from near 0 (a token keeps 0.9999) to -12 (keeps 6e-6), a state
    that is not zeros."""
    rng = np.random.default_rng([seed, t])
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(b, t, h, d))) / np.sqrt(d)
    k = unit(rng.normal(size=(b, t, h, d)))
    v = rng.normal(size=(b, t, h, d))
    g = -np.exp(rng.uniform(*np.log(decays), size=(b, t, h, d)))
    beta = rng.uniform(0.0, 2.0, size=(b, t, h))
    assert (beta > 1).any() and g.max() > -2e-4 and g.min() < -10
    state = rng.normal(size=(b, h, d, d))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, g, beta, state))


@pytest.mark.parametrize("t", [1, 13, 16, 63, 64, 65, 100, 128, 200, 577])
def test_the_chunked_scan_is_the_recurrence(t):
    """At lengths that are and are not whole chunks (64), whole sub-blocks
    (16) and whole spans: outputs and the state after the last token."""
    case = scan_case(t)
    want_o, want_s = highest(ref.delta_rule, *case)
    got_o, got_s = jax.jit(kda.kda_chunk_scan)(*case)
    assert got_s.dtype == jnp.float32 and got_o.shape == want_o.shape
    close(got_o, want_o, SCAN_TOL)
    close(got_s, want_s, SCAN_TOL)


@pytest.mark.parametrize("chunk,span", [(16, 16), (32, 64), (64, 128)])
def test_the_scans_chunk_and_span_move_nothing(chunk, span, monkeypatch):
    monkeypatch.setattr(kda, "CHUNK", chunk)
    monkeypatch.setattr(kda, "_SPAN", span)
    case = scan_case(150, seed=1)
    want_o, want_s = highest(ref.delta_rule, *case)
    got_o, got_s = kda.kda_chunk_scan(*case)
    close(got_o, want_o, SCAN_TOL)
    close(got_s, want_s, SCAN_TOL)


def test_a_decay_that_underflows_stays_finite():
    """A channel that forgets everything in one token (g = -200: exp
    underflows to 0) beside one that forgets nothing: no exponent is ever
    positive, so nothing overflows to inf and no inf meets a 0."""
    q, k, v, g, beta, state = scan_case(130, seed=2)
    g = g.at[..., ::2].set(-200.0).at[..., 1::2].set(0.0)
    want_o, want_s = highest(ref.delta_rule, q, k, v, g, beta, state)
    got_o, got_s = kda.kda_chunk_scan(q, k, v, g, beta, state)
    assert bool(jnp.all(jnp.isfinite(got_o)) & jnp.all(jnp.isfinite(got_s)))
    close(got_o, want_o, SCAN_TOL)
    close(got_s, want_s, SCAN_TOL)


@pytest.mark.parametrize("length", [1, 37, 64, 90])
def test_masked_padding_leaves_the_state_after_the_true_length(length):
    """A bucket of 128 behind a prompt of `length`: with beta = 0 and g = 0
    at the padding the state handed back is the state after `length`, and
    the outputs before it are the unpadded ones."""
    q, k, v, g, beta, state = scan_case(128, seed=3)
    valid = jnp.arange(128) < length
    got_o, got_s = kda.kda_chunk_scan(
        q, k, v, jnp.where(valid[None, :, None, None], g, 0.0),
        jnp.where(valid[None, :, None], beta, 0.0), state)
    want_o, want_s = highest(
        ref.delta_rule, *(x[:, :length] for x in (q, k, v, g, beta)), state)
    close(got_s, want_s, SCAN_TOL)
    close(got_o[:, :length], want_o, SCAN_TOL)


def test_one_decode_step_is_one_step_of_the_recurrence():
    """The one-token update against the reference's recurrence over one
    token, a layer of a stacked buffer; an inactive slot's state and every
    other layer's come back bit for bit."""
    q, k, v, g, beta, state = scan_case(1, seed=4, b=5)
    states = jnp.stack([state + 1.0, state, state - 1.0])
    active = jnp.asarray([True, False, True, True, False])
    o, new = jax.jit(kda.kda_decode_step, static_argnums=6)(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], states, 1, active)
    want_o, want_s = highest(ref.delta_rule, q, k, v, g, beta, state)
    live = np.asarray(active)
    close(o[live], want_o[live, 0], SCAN_TOL)
    close(new[1][live], want_s[live], SCAN_TOL)
    assert bool(jnp.all(new[1][~live] == state[~live]))
    assert bool(jnp.all(new[0] == states[0]) & jnp.all(new[2] == states[2]))


def test_the_short_convolution_behind_its_state():
    """Over a prompt in two calls, and then token by token through the
    stacked per-slot buffer: the reference's whole convolution; the state
    is taken at `length`, and an inactive slot's stays."""
    rng = np.random.default_rng(5)
    widths, taps_n, t = (8, 8, 16), 4, 11
    xs = [jnp.asarray(rng.normal(size=(2, t, c)), jnp.float32)
          for c in widths]
    taps = [jnp.asarray(rng.normal(size=(c, taps_n)), jnp.float32)
            for c in widths]
    want = [ref.short_conv(x, w) for x, w in zip(xs, taps)]
    zeros = jnp.zeros((2, (taps_n - 1) * sum(widths)), jnp.float32)
    # a bucket of 11 whose true lengths are 6 and 9
    length = jnp.asarray([6, 9], jnp.int32)
    first, state = kda.kda_short_conv(xs, taps, zeros, length)
    for got, full in zip(first, want):
        close(got, full, 1e-6)
    for row, n in enumerate((6, 9)):
        # from the state at `length`, the next tokens one at a time
        states = jnp.zeros((2, 2) + state.shape[1:]).at[1].set(state)
        for pos in range(n, t):
            step = [x[:, pos] for x in xs]
            outs, states = kda.kda_short_conv_step(
                step, taps, states, 1,
                jnp.asarray([r == row for r in range(2)]))
            for got, full in zip(outs, want):
                close(got[row], full[row, pos], 1e-6)
            other = 1 - row
            assert bool(jnp.all(states[1, other] == state[other]))
            assert bool(jnp.all(states[0] == 0))


# -- (c) the engine: logits, not tokens ---------------------------------------

def served_logprobs(model, prompts, new_tokens=6, **engine):
    """Every request's served ids and, at each served position, the whole
    log-softmax the engine sampled from (a panel as wide as the
    vocabulary, scattered back into id order)."""
    engine = LLMEngine(model, block_size=4, max_context=48,
                       logprobs_topk=VOCAB, **engine)
    reqs = [engine.add_request(p, max_new_tokens=new_tokens)
            for p in prompts]
    highest(engine.run)
    out = []
    for r in reqs:
        rows = np.zeros((len(r.generated), VOCAB))
        for t, (ids, lps) in enumerate(zip(r.alt_ids, r.alt_logprobs)):
            rows[t, ids] = lps
        out.append((list(r.generated), rows))
    return engine, out


def hold_to_the_reference(weights, prompts, served, tol=TOL):
    """The engine's log-softmax at every served position against the
    reference's full forward over prompt + served tokens. Returns the
    largest difference over the largest logit, asserting nothing, when
    `tol` is None."""
    worst = 0.0
    for prompt, (out, rows) in zip(prompts, served):
        logits = ref.forward(weights, jnp.asarray([prompt + out],
                                                  jnp.int32), FILE)[0]
        at = np.arange(len(prompt) - 1, len(prompt) + len(out) - 1)
        want = np.asarray(jax.nn.log_softmax(logits[at], -1))
        scale = float(jnp.max(jnp.abs(logits)))
        worst = max(worst, float(np.max(np.abs(rows - want))) / scale)
    if tol is not None:
        assert worst <= tol, worst
    return worst


ONE_SLOT_LENGTHS = (13, 1, 2, 3, 9, 5)


def test_prefill_then_decode_gives_the_references_logits(model, weights):
    """Prompts of 1, 2 and 3 tokens (shorter than the convolutions reach
    back: zeros lie before the sequence), every prompt shorter than its
    bucket (8, 16: both parts are taken at `length`), and ONE slot, so
    that every request after the first reuses it, the longest first: a
    shorter request's prefill overwrites the longer one's state whole."""
    prompts = [prompt_of(n) for n in ONE_SLOT_LENGTHS]
    engine, served = served_logprobs(model, prompts, max_batch_size=1)
    hold_to_the_reference(weights, prompts, served)
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 2
    assert s["prefill_tokens"] == 33 and s["prefill_bucket_tokens"] == 64
    by_part = {"conv": KDA_LAYERS * CONV_ROW * 4,
               "delta": KDA_LAYERS * HEADS * WIDTH * WIDTH * 4}
    assert s["slot_state_bytes_by_part"] == by_part
    assert s["slot_state_bytes"] == sum(by_part.values())
    # what the state's layers did: a scan over every bucket's tokens, the
    # padding masked; an update a decoded token a KDA layer
    assert s["prefill_scan_tokens"] == KDA_LAYERS * 64
    assert s["prefill_scan_padding_tokens"] == KDA_LAYERS * (64 - 33)
    assert s["decode_state_updates"] == KDA_LAYERS * s["decode_tokens"] > 0
    assert s["decode_routed_computed"] == s["decode_routed_held"] > 0
    assert s["prefill_routed_computed"] == s["prefill_routed_held"]
    layers, topk = FILE["num_hidden_layers"], FILE["num_experts_per_tok"]
    assert s["prefill_routed_held"] + s["prefill_routed_elsewhere"] \
        == layers * topk * 33
    assert s["prefill_routed_elsewhere"] > 0          # a share, not all
    assert s["kv_bytes_held"] == 0                    # everything finished


def test_a_full_batch_of_slots_gives_the_references_logits(model, weights):
    prompts = [prompt_of(n, 1) for n in (5, 9, 13, 7, 11, 6, 2)]
    _, served = served_logprobs(model, prompts, max_batch_size=3)
    hold_to_the_reference(weights, prompts, served)


# temperature, top-k, top-p and a repetition penalty at once
SEEDED = SAMPLERS[4]


@pytest.mark.parametrize("sampler", [SAMPLERS[0], SEEDED],
                         ids=["greedy", "penalty"])
def test_streams_are_the_dense_forwards_under_an_eviction_schedule(model,
                                                                   sampler):
    """A pool too tight for its batch evicts; the evicted request's resume
    is a re-prefill of prompt + generated tokens, which restores BOTH
    parts of the state by computing them: every stream is what one
    request at a time gives through the model's dense forward, which
    never preempts; greedy streams are `generate`'s."""
    prompts = [prompt_of(n, 5) for n in (11, 12, 10, 5)]
    engine = LLMEngine(model, max_batch_size=3, block_size=4, num_blocks=10,
                       watermark_blocks=1)
    reqs = [engine.add_request(p, max_new_tokens=10, **stream_of(sampler, i))
            for i, p in enumerate(prompts)]
    highest(engine.run)
    s = engine.stats()
    assert s["evictions"] >= 1 and s["decode_compiles"] == 1
    highest(Reference(model).assert_served, reqs)
    if not sampler:
        assert [r.generated for r in reqs] == [
            np.asarray(highest(model.generate, np.asarray([p]),
                               max_new_tokens=10)._value)[0].tolist()
            for p in prompts]


# -- (d) the rule a slot's state is kept by -----------------------------------

def state_after(model, context, before=(), new_tokens=1, slots=1):
    """The (conv, delta) buffers of an engine that served `before` (junk
    that dirties the slot) and then `context` for `new_tokens` tokens, and
    the tokens it served for `context`."""
    engine = LLMEngine(model, max_batch_size=slots, block_size=4,
                       max_context=48)
    for junk in before:
        engine.add_request(junk, max_new_tokens=3)
    highest(engine.run)
    req = engine.add_request(context, max_new_tokens=new_tokens)
    highest(engine.run)
    conv, delta = engine._bufs[2:]
    assert engine.stats()["commit_rollbacks"] == 0
    return (np.asarray(conv), np.asarray(delta)), list(req.generated)


def test_a_prefill_writes_the_state_whole_at_the_true_length(model, weights):
    """One token served (the prefill's own: no launch follows), so the
    slot holds what the prefill wrote. In a CLEAN slot it is the
    reference's state after the prompt's true length, not its bucket's
    end (13 of 16); in a slot a longer request DIRTIED (another bucket,
    another length) it is the same BIT FOR BIT: a reused slot needs no
    clearing."""
    context = prompt_of(13, 7)
    (conv, delta), _ = state_after(model, context)
    (conv2, delta2), _ = state_after(
        model, context, before=[prompt_of(29, 8), prompt_of(3, 9)])
    assert (conv == conv2).all() and (delta == delta2).all()
    assert delta.dtype == np.float32
    # the reference's own state of the first KDA layer (layer 1)
    ids = jnp.asarray([context], jnp.int32)
    x = weights["model.embed_tokens.weight"][ids]
    w = lambda leaf: weights["model.layers.1.self_attn." + leaf]
    mm = lambda a, b: jnp.matmul(a, b)
    with jax.default_matmul_precision("highest"):
        # layer 0, whole, then layer 1's input
        u = ref._rms(x, weights["model.layers.0.input_layernorm.weight"],
                     1e-5)
        x = x + ref.gated_attention(
            u, lambda leaf: weights["model.layers.0.self_attn." + leaf],
            FILE, mm, lambda a: a)
        u = ref._rms(
            x, weights["model.layers.0.post_attention_layernorm.weight"],
            1e-5)
        x = x + ref.routed_sum(u, weights, 0, FILE, mm) \
            + ref.shared_expert(u, weights, 0, mm)
        u = ref._rms(x, weights["model.layers.1.input_layernorm.weight"],
                     1e-5)
        streams = [mm(u, w(f"{s}_proj.weight")) for s in "qkv"]
        q, k, v = (ref.short_conv(x_, w(f"{s}_conv1d.weight"))
                   .reshape(1, 13, HEADS, WIDTH)
                   for s, x_ in zip("qkv", streams))
        g, beta = ref.kda_gates(u, w, FILE, mm)
        _, want = ref.delta_rule(ref._l2norm(q) / np.sqrt(WIDTH),
                                 ref._l2norm(k), v, g, beta)
    close(delta[0, 0], want[0])
    # the convolutions' part: the last three inputs, q's, k's, v's side by
    # side at a position, oldest first
    kept = jnp.concatenate([s[0, 10:13] for s in streams], -1).reshape(-1)
    close(conv[0, 0], kept, 1e-6)


def test_a_resume_computes_the_state_an_uninterrupted_run_holds(model):
    """A resume is a re-prefill of prompt + generated tokens. (i) Through
    a dirty slot it leaves, BIT FOR BIT, the state the same context
    leaves in a fresh engine (the test above: no clearing, no leftover of
    the evicted run). (ii) That state is the one an UNINTERRUPTED run
    holds after the same tokens, prefill of the prompt and then one
    launch a token: the chunked scan and the token-by-token update differ
    by the order of their sums only (SCAN_TOL of the largest entry)."""
    prompt = prompt_of(11, 11)
    # 4 tokens served = the prefill's and three launches', which fed the
    # first three: the state stands after prompt + served[:3]
    (conv_run, delta_run), served = state_after(model, prompt, new_tokens=4)
    (conv_new, delta_new), _ = state_after(
        model, prompt + served[:3], before=[prompt_of(21, 12)])
    # inputs are kept, not summed: a token's projection alone against the
    # same row of a prompt's, behind layers that summed in another order
    close(jnp.asarray(conv_run), jnp.asarray(conv_new), SCAN_TOL)
    close(jnp.asarray(delta_run), jnp.asarray(delta_new), SCAN_TOL)


def test_a_launch_leaves_inactive_slots_states_as_they_were(model):
    """Three slots, one request: the two slots no request ever held stay
    zeros through every launch (an inactive slot's forward computes
    garbage by design; none of it may be written), in both parts."""
    (conv, delta), served = state_after(model, prompt_of(9, 13),
                                        new_tokens=6, slots=3)
    assert len(served) == 6
    held = [s for s in range(3) if np.abs(delta[:, s]).max() > 0]
    assert len(held) == 1
    for s in set(range(3)) - set(held):
        assert not conv[:, s].any() and not delta[:, s].any()


# -- (e) what the engine refuses, and how it picks a long bucket ----------------

@pytest.mark.parametrize("option,named", [
    ({"enable_prefix_cache": True}, "enable_prefix_cache"),
    ({"max_adapters": 2}, "max_adapters"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'")])
def test_an_option_the_state_lacks_is_refused_by_name(model, option, named):
    """By the option's name and by the state's parts, kind by kind."""
    parts = rf"parts: conv \[{CONV_ROW}\], delta \[{HEADS}, {WIDTH}, " \
        rf"{WIDTH}\]"
    with pytest.raises(ValueError, match=named + ".*per-slot state.*"
                       + parts):
        LLMEngine(model, max_batch_size=2, block_size=4, max_context=32,
                  **option)


def test_the_cache_builds_both_parts_in_their_own_types(model):
    spec = model.cache_spec()
    cache = PagedKVCache(spec, 9, 4, jnp.bfloat16, num_slots=3)
    conv, delta = cache.slot_state
    assert (conv.shape, conv.dtype) == ((6, 3, CONV_ROW), jnp.bfloat16)
    assert (delta.shape, delta.dtype) == (
        (6, 3, HEADS, WIDTH, WIDTH), jnp.float32)
    assert [b.shape for b in cache.buffers()] == [
        cache.k_pools.shape, cache.v_pools.shape, conv.shape, delta.shape]
    assert isinstance(spec, CacheSpec)


@pytest.mark.parametrize("tokens,bucket", [
    (5, 8), (2049, 4096), (4096, 4096), (4097, 8192), (8192, 8192),
    (8193, 12288), (12288, 12288), (12289, 16384), (16384, 16384),
    (16385, 20480)])
def test_long_buckets_grow_by_steps_and_short_ones_double(tokens, bucket):
    assert LLMEngine._bucket_for(tokens) == bucket


# -- (f) the shares of one layer add up ----------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(weights):
    """Eight chips' shares of one layer's 64 experts (8 each, each told
    which it holds, each routing over all 64) plus the shared expert,
    which every chip computes alike, COUNTED ONCE, add up to what the
    UNCUT reference gives for the whole layer."""
    layer, d = 1, FILE["hidden_size"]
    u = jnp.asarray(np.random.default_rng(6).normal(size=(24, d)),
                    jnp.float32)
    whole = dict(FILE, n_routed_experts=64, experts_held_from=0)
    whole.pop("published")
    prefix = f"model.layers.{layer}.mlp."
    every = seeded.make_weights(
        {k: s for k, s in ref.param_shapes(whole).items()
         if k.startswith(prefix + "experts.")}, 9, jnp.float32, 0.3)
    params = {**weights, **every}
    mm = lambda a, w: jnp.matmul(a, w)
    want = highest(lambda: ref.routed_sum(u[None], params, layer, whole, mm)
                   + ref.shared_expert(u[None], params, layer, mm))[0]
    total, held_sum = 0.0, 0
    for first in range(0, 64, 8):
        share = [every[prefix + f"experts.{leaf}.weight"][first:first + 8]
                 for leaf in ("gate_proj", "up_proj", "down_proj")]
        out, counters = highest(
            held_experts.held_expert_block, u, params[prefix + "gate.weight"],
            None, *share, topk=4, real_experts=64, scaling=1.0,
            first_held=first, scoring="sigmoid", normalise=True)
        total = total + out
        held_sum += int(counters[0])
        assert int(counters[0]) == int(counters[5])   # computed == held
    assert held_sum == 24 * 4                         # every choice, once
    shared = highest(so._swiglu, u, *(
        weights[prefix + f"shared_experts.{leaf}.weight"]
        for leaf in ("gate_proj", "up_proj", "down_proj")))
    close(total + shared, want)
    # counted eight times, the shared expert is off by seven of itself
    assert gap(total + 8 * shared, want) > 0.1


# -- (g) what a lower precision would do fails the tolerance --------------------

def test_a_bfloat16_state_fails_the_comparison(model, weights, monkeypatch):
    """The matrix state rounded to bfloat16 wherever it is handed on (a
    prefill's result, every launch's) is a different result: the engine's
    logits leave the reference's by more than ten times TOL."""
    round_ = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)
    scan, step = kda.kda_chunk_scan, kda.kda_decode_step

    def rounded_scan(*args, **kw):
        o, state = scan(*args, **kw)
        return o, round_(state)

    def rounded_step(*args):
        o, states = step(*args)
        return o, round_(states)

    monkeypatch.setattr(kda, "kda_chunk_scan", rounded_scan)
    monkeypatch.setattr(kda, "kda_decode_step", rounded_step)
    prompts = [prompt_of(n, 2) for n in (13, 9)]
    _, served = served_logprobs(model, prompts, max_batch_size=2)
    assert hold_to_the_reference(weights, prompts, served, tol=None) \
        > 10 * TOL


def test_the_fp8_control_fails_the_comparison(weights):
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, VOCAB, (1, 19)), jnp.int32)
    want = ref.forward(weights, ids, FILE)
    assert gap(ref.forward(weights, ids, FILE, "fp8"), want) > 100 * TOL
    assert gap(ref.forward(weights, ids, FILE, "bfloat16"), want) > 10 * TOL
