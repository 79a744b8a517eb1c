"""MiMo-V2-Flash on the CPU at a tiny size that keeps every ratio's KIND
(`tests/benchmark/mimo_model/tiny_mimo.py`): window and full layers in one
stack, each kind with its own key/value heads and its own cache, a key
wider than its value, a rotary part of a head, a sink a head, a share of
the experts held. The model is held to the benchmark's plain reference
(`benchmark/reference/mimo_v2_flash.py`, written apart from it), the
engine to the reference's full forward over prompt + served tokens, LOGITS
and not tokens: an engine with `logprobs_topk` = the vocabulary hands back
the whole log-softmax of every served position.

Tolerances: both sides compute in float32 with products at "highest", so
they differ only in the ORDER of sums (a ring's pages against a whole
band, blockwise softmax, a gathered expert against a masked one): TOL =
1e-4 of the largest logit, as the other served models' tests. A term left
out (the sink, the value scale, the rotary part, a kind's base, the
window's edge) is off by 30 x TOL or more (`LEFT_OUT`)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests", "benchmark",
                                   "mimo_model")]

import paddle_tpu as paddle  # noqa: E402
from benchmark import seeded  # noqa: E402
from benchmark.reference import mimo_v2_flash as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    held_experts)
from paddle_tpu.incubate.models import mimo_v2_flash as mimo  # noqa: E402
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402
from paddle_tpu.kernels.pallas import paged_attention as pa  # noqa: E402
from paddle_tpu.nn.functional import attention as fattn  # noqa: E402
from paddle_tpu.serving import LLMEngine  # noqa: E402
from paddle_tpu.serving.cache import (  # noqa: E402
    CacheSpec, PagedKVCache, ring_block, scatter_window_prefill)
from serving_reference import SAMPLERS, Reference, stream_of  # noqa: E402
from tiny_mimo import TINY_MIMO as FILE  # noqa: E402

from benchmark.programs import paddle_mimo  # noqa: E402

TOL = 1e-4
VOCAB = FILE["vocab_size"]
WINDOW, BLOCK = FILE["sliding_window"], 4            # a ring of 3 blocks
EXPERT_LAYERS = range(FILE["first_k_dense_replace"],
                      FILE["num_hidden_layers"])


def weights_of(file, seed=3, std=0.3):
    """Float32 seeded weights by the reference's names (the sinks N(0,
    std) too), and (the tests carry it: the benchmark holds it at zeros)
    a non-zero router bias."""
    w = dict(seeded.make_weights(ref.param_shapes(file), seed, jnp.float32,
                                 std))
    rng = np.random.default_rng(seed)
    for i in EXPERT_LAYERS:
        w[ref.bias_name(i)] = jnp.asarray(rng.normal(
            0, 0.05, file["published"]["n_routed_experts"]), jnp.float32)
    return w


def model_of(file, weights):
    model = mimo.MiMoV2FlashForCausalLM(
        paddle_mimo._model_config(file),
        weights={k: v for k, v in weights.items()
                 if "correction_bias" not in k})
    for i in EXPERT_LAYERS:
        model.expert_bias(i)._value = weights[ref.bias_name(i)]
    return model


@pytest.fixture(scope="module")
def weights():
    return weights_of(FILE)


@pytest.fixture(scope="module")
def model(weights):
    return model_of(FILE, weights)


def highest(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def close(got, want, tol=TOL):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, VOCAB, n).tolist()


# -- (a) the model against the reference --------------------------------------

def test_parameter_names_and_shapes_are_the_references():
    cfg = paddle_mimo._model_config(FILE)
    assert mimo.param_shapes(cfg) == ref.param_shapes(FILE)
    assert list(mimo.param_shapes(cfg)) == list(ref.param_shapes(FILE))
    names = set(mimo.param_shapes(cfg))
    assert {"model.layers.0.mlp.gate_proj.weight",
            "model.layers.1.self_attn.attention_sink_bias",
            "model.layers.2.mlp.experts.down_proj.weight",
            "lm_head.weight"} <= names
    # a full layer has no sink, and the bias is no parameter
    assert "model.layers.0.self_attn.attention_sink_bias" not in names
    assert not any("correction_bias" in n for n in names)
    assert cfg.rotary_dim == ref.rotary_dim(FILE) == 4
    spec = mimo.MiMoV2FlashForCausalLM(cfg).cache_spec()
    assert (spec.kind, spec.num_layers, spec.num_heads, spec.query_heads,
            spec.head_dim) == ("kv", 2, 1, 8, 12)
    assert spec.parts == ((1, 12), (1, 8)) and spec.widths == (12, 8)
    assert (spec.window_layers, spec.window, spec.window_parts) == \
        (5, 6, ((2, 12), (2, 8)))
    assert spec.ring_blocks(BLOCK) == 3 and spec.state_layers == 0


def test_the_published_pattern_is_the_sources():
    cfg = mimo.MiMoV2FlashConfig()
    kinds = cfg.layer_types
    assert len(kinds) == 48 and kinds.count(mimo.FULL) == 9
    assert [i for i, k in enumerate(kinds) if k == mimo.FULL] == \
        [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert cfg.rotary_dim == 64 and cfg.held == (0, 256)


def test_full_forward_logits_agree_over_a_whole_sequence(model, weights):
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, VOCAB, (2, 40)), jnp.int32)
    got = highest(model, paddle.Tensor(ids))._value
    close(got, ref.forward(weights, ids, FILE))


def test_the_reference_in_blocks_of_queries_is_the_reference(weights,
                                                             monkeypatch):
    """`QUERY_BLOCK` only bounds what is held at once: 40 positions in
    blocks of 8 (a window layer's block against its band's keys) are the
    40 positions at once."""
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, VOCAB, (1, 40)), jnp.int32)
    whole = ref.forward(weights, ids, FILE)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    close(ref.forward(weights, ids, FILE), whole, 1e-6)


def test_the_dense_caches_carry_keys_token_by_token(model, weights):
    """`generate`'s path: a prompt, then a token at a time through the
    (keys, values) pairs of both kinds, gives the full forward's logits at
    every position (a window layer masks its own pairs to the band)."""
    ids = jnp.asarray(np.random.default_rng(2).integers(0, VOCAB, (1, 15)),
                      jnp.int32)
    want = ref.forward(weights, ids, FILE)[0]
    logits, caches = highest(model, paddle.Tensor(ids[:, :3]),
                             caches=model.gen_caches(1, jnp.float32))
    close(logits._value[0], want[:3])
    for t in range(3, 15):
        logits, caches = highest(model, paddle.Tensor(ids[:, t:t + 1]),
                                 caches=caches)
        close(logits._value[0, 0], want[t])
    assert len(caches) == 2 + 5
    assert tuple(caches[0][0].shape) == (1, 15, 1, 12)        # full K
    assert tuple(caches[-1][1].shape) == (1, 15, 2, 8)        # window V


# -- (b) the engine: logits, not tokens ---------------------------------------

def served_logprobs(model, prompts, new_tokens=14, **engine):
    """Every request's served ids and, at each served position, the whole
    log-softmax the engine sampled from."""
    engine = LLMEngine(model, block_size=BLOCK, max_context=64,
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


def gap_to_the_reference(weights, prompt, out, rows, file=FILE):
    """The largest difference between the engine's log-softmax and the
    reference's full forward over prompt + served tokens, at the served
    positions, over the largest logit."""
    logits = ref.forward(weights, jnp.asarray([prompt + out], jnp.int32),
                         file)[0]
    at = np.arange(len(prompt) - 1, len(prompt) + len(out) - 1)
    want = np.asarray(jax.nn.log_softmax(logits[at], -1))
    return float(np.max(np.abs(rows - want))) \
        / float(jnp.max(jnp.abs(logits)))


# contexts shorter than the window (6), equal to it, a whole block (4, 8),
# a whole ring (12), several windows; 14 served tokens carry each across a
# block boundary and every one from 3 up across the ring's wrap
LENGTHS = (1, 3, 4, 5, 6, 7, 8, 12, 13, 17, 29, 40)


@pytest.fixture(scope="module")
def one_slot(model):
    """ONE slot, so that every request after the first reuses it, the
    longest first: a shorter request's prefill must leave nothing of the
    longer one's ring that its decode can read."""
    prompts = [prompt_of(n) for n in reversed(LENGTHS)]
    engine, served = served_logprobs(model, prompts, max_batch_size=1)
    return engine, dict(zip(reversed(LENGTHS), zip(prompts, served)))


# temperature, top-k, top-p and a repetition penalty at once
SEEDED = SAMPLERS[4]


@pytest.fixture(scope="module")
def one_slot_seeded(model):
    """The same schedule with every stream SEEDED: by its length, the
    request the one slot served and the reference over the model's own
    dense forward, which keeps no cache of either kind."""
    engine = LLMEngine(model, max_batch_size=1, block_size=BLOCK,
                       max_context=64)
    reqs = {n: engine.add_request(prompt_of(n), max_new_tokens=14,
                                  **stream_of(SEEDED, n))
            for n in reversed(LENGTHS)}
    highest(engine.run)
    assert engine.stats()["sampled_tokens"] == 14 * len(LENGTHS)
    return reqs, Reference(model)


@pytest.mark.parametrize("length", LENGTHS)
def test_prefill_then_decode_gives_the_references_logits(one_slot, weights,
                                                         length):
    prompt, (out, rows) = one_slot[1][length]
    assert len(out) == 14
    assert gap_to_the_reference(weights, prompt, out, rows) <= TOL


@pytest.mark.parametrize("length", LENGTHS)
def test_a_seeded_stream_is_the_dense_forwards(one_slot_seeded, length):
    """What is DRAWN, and not only the logits drawn from: the key at
    `fold_in(seed, position)`, the penalty's history through the ring's
    wrap and a reused slot, the packed call's sampler row."""
    reqs, reference = one_slot_seeded
    highest(reference.assert_served, [reqs[length]])


def test_the_engine_counts_what_the_two_caches_hold(one_slot):
    s = one_slot[0].stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 4
    assert s["prefill_tokens"] == sum(LENGTHS)
    # 5 window layers x (1 null + 1 slot x 3 blocks) x 4 rows x (2 x 12 +
    # 2 x 8) values x 4 bytes
    assert s["window_ring_bytes"] == 5 * 4 * 4 * 40 * 4
    # the loop reads whole chunks of the ring's table, the windows lie in
    # two or three pages of it: never under what is held
    assert s["window_pages_streamed"] >= s["window_pages_held"] > 0
    assert s["window_tokens_held"] <= WINDOW * s["decode_tokens"]
    assert s["attn_tokens_held"] > s["window_tokens_held"]
    held, first, per = 4, FILE["first_k_dense_replace"], 4
    assert s["decode_routed_computed"] == s["decode_routed_held"] > 0
    assert s["prefill_routed_computed"] == s["prefill_routed_held"]
    total = s["prefill_routed_held"] + s["prefill_routed_elsewhere"]
    assert total == (7 - first) * per * sum(LENGTHS)
    assert s["prefill_routed_elsewhere"] > s["prefill_routed_held"]


def test_a_full_batch_of_slots_gives_the_references_logits(model, weights):
    prompts = [prompt_of(n, 1) for n in (5, 9, 13, 7, 21, 6, 2)]
    _, served = served_logprobs(model, prompts, max_batch_size=3)
    for prompt, (out, rows) in zip(prompts, served):
        assert gap_to_the_reference(weights, prompt, out, rows) <= TOL


@pytest.mark.parametrize("sampler", [SAMPLERS[0], SEEDED],
                         ids=["greedy", "penalty"])
def test_streams_are_generates_under_an_eviction_schedule(model, sampler):
    """A pool too tight for its batch evicts; the evicted request's resume
    is a re-prefill of prompt + generated tokens, which writes its slot's
    ring anew (`CacheSpec`'s rule): every stream is token-identical to
    `generate`, which never preempts; a seeded stream to one request at
    a time through the model's dense forward."""
    prompts = [prompt_of(n, 5) for n in (11, 12, 10, 5)]
    engine = LLMEngine(model, max_batch_size=3, block_size=BLOCK,
                       num_blocks=10, watermark_blocks=1)
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


@pytest.mark.parametrize("option,kwargs", [
    ("enable_prefix_cache", {"enable_prefix_cache": True}),
    ("max_adapters", {"max_adapters": 2}),
    ("kv_dtype='int8'", {"kv_dtype": "int8"})])
def test_what_a_ring_cannot_do_is_refused_by_name(model, option, kwargs):
    with pytest.raises(ValueError, match=option.split("=")[0]) as refusal:
        LLMEngine(model, max_batch_size=2, block_size=BLOCK, max_context=64,
                  **kwargs)
    assert "window layers' rings" in str(refusal.value)
    assert "MiMoV2FlashForCausalLM" in str(refusal.value)


# -- (c) every term, left out, fails the comparison ---------------------------

LEFT_OUT = {
    "sink": {"add_swa_attention_sink_bias": False},
    "value_scale": {"attention_value_scale": 1.0},
    "rotary_part": {"partial_rotary_factor": 1.0},
    "no_rotary": {"partial_rotary_factor": 0.0},
    "window_base": {"swa_rope_theta": FILE["rope_theta"]},
    "full_base": {"rope_theta": FILE["swa_rope_theta"]},
    "window_edge": {"sliding_window": WINDOW + 1},
    "no_window": {"sliding_window": 64},
}


@pytest.mark.parametrize("term", sorted(LEFT_OUT))
def test_a_term_left_out_fails_the_comparison(one_slot, weights, term):
    """The engine's logits held to a reference WITHOUT the term (the same
    weights; a sink's leaf is then not read): 30 x TOL or more away, at a
    context of several windows."""
    prompt, (out, rows) = one_slot[1][29]
    without = dict(FILE, **LEFT_OUT[term])
    assert gap_to_the_reference(weights, prompt, out, rows, without) \
        >= 30 * TOL


# -- (d) the rings ------------------------------------------------------------

def test_a_ring_holds_the_window_and_a_block_and_turns():
    spec = CacheSpec.per_head(1, 1, 12, value_dim=8, window_layers=2,
                              window=6, window_parts=((2, 12), (2, 8)))
    assert spec.ring_blocks(4) == 3 and spec.ring_blocks(6) == 2 \
        and spec.ring_blocks(16) == 2
    cache = PagedKVCache(spec, 9, 4, jnp.float32, num_slots=3)
    assert [tuple(p.shape) for p in cache.window_pools] == \
        [(2, 1 + 3 * 3, 4, 24), (2, 10, 4, 16)]
    assert len(cache.buffers()) == 4
    assert cache.k_pools.shape[-1] == 12 and cache.v_pools.shape[-1] == 8
    # slot 2's positions 0..13: blocks 7, 8, 9, then 7 again
    blocks = [int(ring_block(2, p, 4, 3)) for p in range(14)]
    assert blocks == [7] * 4 + [8] * 4 + [9] * 4 + [7] * 2
    with pytest.raises(ValueError, match="window"):
        CacheSpec.per_head(1, 1, 12, window_layers=2)


@pytest.mark.parametrize("length", [2, 6, 9, 16])
def test_a_prefill_writes_the_last_window_before_the_true_length(length):
    """Bucket 16, true length 2 / 6 / 9 / 16: the rows written are the last
    6 positions before `length` (all of a shorter prompt), at their ring
    places; nothing of the bucket's padding, nothing of another slot."""
    layers, bucket, heads = 2, 16, 2
    k = jnp.arange(layers * bucket * heads * 3, dtype=jnp.float32).reshape(
        layers, bucket, heads, 3) + 1.0
    pools = [jnp.zeros((layers, 1 + 3 * 3, 4, heads * 3), jnp.float32)] * 2
    wk, wv = scatter_window_prefill(*pools, k, 2 * k, 1, length, 4, 6)
    want = np.zeros_like(np.asarray(wk))
    for p in range(max(0, length - 6), length):
        want[:, 1 + 1 * 3 + (p // 4) % 3, p % 4] = np.asarray(
            k[:, p]).reshape(layers, -1)
    np.testing.assert_array_equal(np.asarray(wk)[:, 1:], want[:, 1:])
    np.testing.assert_array_equal(np.asarray(wv)[:, 1:], 2 * want[:, 1:])


# -- (e) the Pallas paths in the interpreter against the loop -----------------

def banded_case(window, dtype, seed=0):
    """5 slots (one inactive) over K rows 2 x 24 and V rows 2 x 16, eight
    query heads; a full layer's pool with tables, or a window layer's
    rings holding each slot's last `window` tokens."""
    rng = np.random.default_rng(seed)
    s, hq, h, dk, dv, bs = 5, 8, 2, 24, 16, 4
    lens = np.array([0, 3, 9, 17, 30], np.int32)
    active = np.array([1, 1, 1, 1, 0], bool)
    token = lambda n, w: jnp.asarray(rng.normal(size=(s, 1, n, w)), dtype)
    case = dict(q=token(hq, dk), k=token(h, dk), v=token(h, dv),
                lens=jnp.asarray(lens), active=jnp.asarray(active), bs=bs,
                window=window, sink=None, tables=None)
    if window:
        ring = -(-window // bs) + 1
        blocks = 1 + s * ring
        case["sink"] = jnp.asarray(rng.normal(size=(hq,)), jnp.float32)
    else:
        blocks = 1 + s * 10
        case["tables"] = jnp.asarray(
            1 + np.arange(s * 10).reshape(s, 10), jnp.int32)
    # every row random: what lies outside the window, or past a length,
    # is there to be masked
    case["k_pools"] = jnp.asarray(
        rng.normal(size=(2, blocks, bs, h * dk)), dtype)
    case["v_pools"] = jnp.asarray(
        rng.normal(size=(2, blocks, bs, h * dv)), dtype)
    return case


def banded_out(case, kernel):
    out, k_pools, v_pools = fattn.paged_banded_decode_attention(
        case["q"], case["k"], case["v"], case["k_pools"], case["v_pools"],
        1, case["tables"], case["lens"], case["active"], case["bs"],
        window=case["window"] or None, sink=case["sink"], kernel=kernel,
        interpret=True)
    # the other layer of the pools is what came in
    np.testing.assert_array_equal(np.asarray(k_pools[0], np.float32),
                                  np.asarray(case["k_pools"][0], np.float32))
    return np.asarray(out, np.float32)[:4, 0]         # the active slots


def plain_banded(case):
    """An oracle written apart from every variant: a slot at a time, a
    query head at a time, over the slot's own tokens where they lie."""
    q = np.asarray(case["q"], np.float64)
    s, _, hq, dk = q.shape
    bs, window = case["bs"], case["window"]
    h = case["k"].shape[2]
    out = np.zeros((s, hq, case["v"].shape[3]))
    for slot in range(4):
        n = int(case["lens"][slot])
        oldest = max(0, n - window + 1) if window else 0
        rows = {}
        for name, new in (("k_pools", "k"), ("v_pools", "v")):
            pool = np.asarray(case[name][1], np.float64)
            ctx = []
            for p in range(oldest, n):
                block = int(ring_block(slot, p, bs, -(-window // bs) + 1)) \
                    if window else int(case["tables"][slot, p // bs])
                ctx.append(pool[block, p % bs])
            ctx.append(np.asarray(case[new][slot, 0], np.float64).reshape(-1))
            rows[name] = np.stack(ctx).reshape(n - oldest + 1, h, -1)
        for head in range(hq):
            kh = head // (hq // h)
            score = rows["k_pools"][:, kh] @ q[slot, 0, head] / np.sqrt(dk)
            sink = -np.inf if case["sink"] is None \
                else float(case["sink"][head])
            top = max(score.max(), sink)
            p = np.exp(score - top)
            out[slot, head] = (p / (p.sum() + np.exp(sink - top))) \
                @ rows["v_pools"][:, kh]
    return out[:4]


@pytest.mark.parametrize("window", [0, 10, 6], ids=["full", "w10", "w6"])
@pytest.mark.parametrize("kernel", ["pallas", "blockwise", "reference"])
def test_the_decode_variants_agree_over_unequal_rows(kernel, window):
    """The Pallas kernel (in the interpreter), the blockwise loop and the
    dense gather against the plain oracle: a key wider than its value,
    four queries a key/value head, a window's oldest position, the sink.
    Float32 everywhere: 1e-5 of the largest output covers the order of
    the sums."""
    case = banded_case(window, jnp.float32)
    want = plain_banded(case)
    got = banded_out(case, kernel)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    if window:
        # broken on purpose: the sink left out of the denominator
        assert np.abs(banded_out(dict(case, sink=None), kernel)
                      - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("window", [0, 10], ids=["full", "window"])
def test_a_bf16_pool_keeps_every_bit_of_the_kernel(window):
    """bf16 queries and pools through the kernel's 0/1 products at two
    widths: what the loop gives from the same rounded operands, to bf16's
    last bit of the output."""
    case = banded_case(window, jnp.bfloat16)
    np.testing.assert_allclose(banded_out(case, "pallas"),
                               banded_out(case, "blockwise"),
                               rtol=2 ** -7, atol=2 ** -7)


def test_the_kernel_copies_a_windows_pages_and_no_more():
    """The ring's table starts at the block of the window's oldest
    position, so `_slot_pages` of the effective lengths are the pages the
    window lies in: the host's count (`_count_attention`) reads 1.0."""
    window, bs, ring = 128, 16, 9
    for pos in (0, 15, 127, 128, 143, 144, 4300, 8191):
        first = max(pos - (window - 1), 0) // bs
        eff = np.asarray([pos - first * bs])
        copied, held = pa.pallas_copied_pages(eff, np.asarray([True]), ring,
                                              bs)
        assert copied == held == pos // bs - first + 1 <= ring


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("window", [128, 100, 129])
def test_the_band_kernel_is_the_band(window, dtype, tol):
    """`flash_band_attention_bnhd` in the interpreter against the model's
    own dense path (`_causal`): two blocks of keys a block of queries,
    grouped heads through the index map, the sink."""
    rng = np.random.default_rng(0)
    n, h, kh, dk, dv = 384, 4, 2, 24, 16
    q, k, v = (jnp.asarray(rng.normal(size=(1, n, heads, width)), dtype)
               for heads, width in ((h, dk), (kh, dk), (kh, dv)))
    sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    got = fa.flash_band_attention_bnhd(q, k, v, window, sink,
                                       interpret=True)
    want = mimo.MiMoV2FlashForCausalLM._causal(None, q, k, v, 0, window,
                                               sink)
    assert got.shape == (1, n, h, dv)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_the_engine_serves_both_kinds_through_the_kernel(monkeypatch):
    """On a TPU (here: told so, the kernel in the interpreter) an engine
    over rows on the lane tiles (K 256 / V 128 a key/value head, pages of
    8) chooses `pallas` unasked for BOTH kinds of layer: it serves the
    blockwise engine's tokens from one decode program, and over the rings
    the kernel copies the pages the windows lie in and one for every
    inactive slot of a launch: `window_streamed_share` is the window and
    no more."""
    file = dict(FILE, head_dim=256, v_head_dim=128, num_attention_heads=4,
                partial_rotary_factor=0.25, sliding_window=12)
    model = model_of(file, weights_of(file))

    def serve_through(kernel, block_size=8):
        engine = LLMEngine(model, max_batch_size=4, block_size=block_size,
                           max_context=64, attention_kernel=kernel)
        prompts = [prompt_of(n, 3) for n in (5, 19, 13, 7, 30, 6)]
        return engine, highest(engine.generate, prompts, max_new_tokens=16)

    _, expect = serve_through("blockwise")
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    kernel, names = pa.pallas_banded_attention, []
    monkeypatch.setattr(
        pa, "pallas_banded_attention",
        lambda *args, interpret=False, **kw: names.append(kw["name"])
        or kernel(*args, interpret=True, **kw))
    engine, served = serve_through(None)
    st, raw = engine.stats(), engine._stats
    assert st["attention_kernel"] == "pallas" and st["decode_compiles"] == 1
    assert served == expect
    # traced once a layer, each kind under its own name
    assert sorted(names) == ["full_decode_attention"] * 2 \
        + ["window_decode_attention"] * 5
    idle = raw.launches * 4 - raw.decode_tokens
    assert idle > 0
    assert raw.window_pages_streamed == raw.window_pages_held + idle
    assert raw.attn_entries_streamed == raw.attn_entries_held + idle
    # a ring of 3 pages of 8 for a window of 12: two or three in use
    assert raw.window_pages_held <= 3 * raw.decode_tokens
    # a page off the sublane tiles: the loop, unasked
    assert serve_through(None, block_size=4)[0].stats()[
        "attention_kernel"] == "blockwise"


# -- (f) the expert block with a share of the experts held --------------------

def block_inputs(weights, n=24, seed=11):
    p = "model.layers.2.mlp."
    u = jnp.asarray(np.random.default_rng(seed).normal(
        0, 1, (n, FILE["hidden_size"])), jnp.float32)
    return u, weights[p + "gate.weight"], weights[ref.bias_name(2)], [
        weights[p + f"experts.{leaf}.weight"]
        for leaf in ("gate_proj", "up_proj", "down_proj")]


@pytest.mark.parametrize("form", ["masked", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer(weights, monkeypatch, form):
    """Four chips' shares of one layer's 16 experts (4 each, each told
    which it holds, each routing over all 16) add up to what the UNCUT
    reference gives for the whole layer; nothing is computed alike on
    every chip (no shared expert), so nothing is counted once."""
    monkeypatch.setattr(held_experts, "products_form",
                        lambda tokens, topk, held: form)
    u, router, bias, _ = block_inputs(weights)
    whole = dict(FILE, n_routed_experts=16, experts_held_from=0)
    whole.pop("published")
    every = seeded.make_weights(
        {k: s for k, s in ref.param_shapes(whole).items()
         if k.startswith("model.layers.2.mlp.experts.")}, 9, jnp.float32, 0.3)
    params = {"model.layers.2.mlp.gate.weight": router,
              ref.bias_name(2): bias, **every}
    want = ref.expert_block(u[None], params, 2, whole,
                            lambda a, w: jnp.matmul(a, w))[0]
    total, held_sum = 0.0, 0
    for first in (0, 4, 8, 12):
        share = [every[f"model.layers.2.mlp.experts.{leaf}.weight"]
                 [first:first + 4]
                 for leaf in ("gate_proj", "up_proj", "down_proj")]
        out, counters = highest(
            held_experts.held_expert_block, u, router, bias, *share, topk=4,
            real_experts=16, scaling=1.0, first_held=first,
            scoring="sigmoid", normalise=True)
        total = total + out
        held_sum += int(counters[0])
        assert int(counters[0]) == int(counters[5])   # computed == held
    assert held_sum == 24 * 4                         # every choice, once
    close(total, highest(lambda: want))


def test_a_buffer_at_a_time_is_the_whole_sorted_order(weights, monkeypatch):
    """A call whose routing could fill more than the buffer runs the
    sorted order a buffer at a time: the same sum as the masked form, no
    token dropped, `computed` every held assignment, with a buffer (16
    rows) that the routing here (4 held of top 4 of 16 over 24 tokens)
    fills more than once."""
    u, router, bias, share = block_inputs(weights)
    kw = dict(topk=4, real_experts=16, scaling=1.0, first_held=4,
              scoring="sigmoid", normalise=True)
    monkeypatch.setattr(held_experts, "products_form", lambda *s: "masked")
    want, counted = highest(held_experts.held_expert_block, u, router, bias,
                            *share, **kw)
    monkeypatch.setattr(held_experts, "products_form", lambda *s: "grouped")
    monkeypatch.setattr(held_experts, "_ROWS_MAX", 16)
    assert held_experts.buffer_rows(24, 4, 4) == 16 < 24 * 4
    got, counters = highest(held_experts.held_expert_block, u, router, bias,
                            *share, **kw)
    assert int(counters[0]) > 16                      # more than a buffer
    np.testing.assert_array_equal(np.asarray(counters), np.asarray(counted))
    close(got, want, 1e-5)


@pytest.mark.parametrize("tokens,topk,held,form", [
    (128, 4, 32, "grouped"), (2048, 4, 32, "grouped"),      # every expert
    (128, 12, 16, "masked"), (512, 12, 16, "masked"),       # 16 of 512
    (128, 8, 8, "masked"), (1024, 8, 8, "masked"),          # 8 of 256
    (2048, 8, 8, "grouped"), (4096, 8, 8, "grouped"),
    (8192, 8, 8, "grouped")])
def test_the_products_form_follows_the_static_shape(tokens, topk, held,
                                                    form):
    assert held_experts.products_form(tokens, topk, held) == form
    rows = held_experts.buffer_rows(tokens, topk, held)
    assert rows == min(tokens * min(topk, held), 8192)
    grouped, _ = held_experts.products_run(tokens, topk, held, 64, 32,
                                           jnp.float32)
    assert grouped == (3 if form == "grouped" else 0)
