"""LFM2-MoE on the CPU at a tiny size that keeps every ratio's KIND
(`tests/benchmark/lfm2_model/tiny_lfm2.py`): convolution and attention
layers in one stack, four query heads a key/value head, every expert held.
The model is held to the benchmark's plain reference
(`benchmark/reference/lfm2_moe.py`, written apart from it), the engine to
the reference's full forward over prompt + served tokens, LOGITS and not
tokens: an engine with `logprobs_topk` = the vocabulary hands back the
whole log-softmax of every served position.

Tolerances: both sides compute in float32 with products at "highest", so
they differ only in the ORDER of sums (a state carried against a whole
convolution, a gathered expert against a masked one, blockwise softmax):
TOL = 1e-4 of the largest logit, as the other served model's tests. A
program that leaves the state at zeros, takes it at the bucket's end or
reads the wrong key/value head is off by 1e-2 or more of it (the last
section)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests", "benchmark",
                                   "lfm2_model")]

import paddle_tpu as paddle  # noqa: E402
from benchmark import seeded  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    held_experts)
from paddle_tpu.incubate.models import lfm2_moe as lfm  # noqa: E402
from paddle_tpu.kernels.pallas import grouped_matmul as gm  # noqa: E402
from paddle_tpu.kernels.pallas import paged_attention as pa  # noqa: E402
from paddle_tpu.nn.functional import attention as fattn  # noqa: E402
from paddle_tpu.serving import LLMEngine  # noqa: E402
from serving_reference import SAMPLERS, Reference, stream_of  # noqa: E402
from tiny_lfm2 import TINY_LFM2 as FILE  # noqa: E402

from benchmark.programs import paddle_lfm2  # noqa: E402

TOL = 1e-4
VOCAB = FILE["vocab_size"]


def weights_of(file, seed=3, std=0.3):
    """Float32 seeded weights by the reference's names, and (the tests
    carry it: the benchmark holds it at zeros) a non-zero expert bias."""
    w = dict(seeded.make_weights(ref.param_shapes(file), seed, jnp.float32,
                                 std))
    rng = np.random.default_rng(seed)
    for i in range(file["num_dense_layers"], file["num_hidden_layers"]):
        w[ref.bias_name(i)] = jnp.asarray(
            rng.normal(0, 0.05, file["num_experts"]), jnp.float32)
    return w


def model_of(file, weights):
    model = lfm.Lfm2MoeForCausalLM(
        paddle_lfm2._model_config(file),
        weights={k: v for k, v in weights.items() if "bias" not in k})
    for i in range(file["num_dense_layers"], file["num_hidden_layers"]):
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
    cfg = paddle_lfm2._model_config(FILE)
    assert lfm.param_shapes(cfg) == ref.param_shapes(FILE)
    assert list(lfm.param_shapes(cfg)) == list(ref.param_shapes(FILE))
    names = set(lfm.param_shapes(cfg))
    assert {"model.layers.0.conv.in_proj.weight",
            "model.layers.2.self_attn.q_layernorm.weight",
            "model.layers.2.feed_forward.experts.w1.weight",
            "model.embedding_norm.weight"} <= names
    assert not any("expert_bias" in n or "lm_head" in n for n in names)
    spec = lfm.Lfm2MoeForCausalLM(cfg).cache_spec()
    assert (spec.kind, spec.num_layers, spec.num_heads, spec.query_heads,
            spec.head_dim) == ("kv", 2, 2, 8, 8)
    # ONE part, in the model's dtype: the one-entry case of the parts
    assert (spec.state_layers, spec.state_parts) == (
        5, (("conv", (2 * 64,), None),))


def test_full_forward_logits_agree_over_a_whole_sequence(model, weights):
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, VOCAB, (2, 19)), jnp.int32)
    got = highest(model, paddle.Tensor(ids))._value
    close(got, ref.forward(weights, ids, FILE))


def test_the_dense_caches_carry_state_and_keys_token_by_token(model,
                                                              weights):
    """`generate`'s path: a prompt, then a token at a time through the
    (keys, values) pairs and the convolutions' states, gives the full
    forward's logits at every position."""
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
    assert len(caches) == 2 + 5 and caches[0][0].shape[1] == 11
    (state,) = caches[-1]
    assert tuple(state.shape) == (1, 2 * 64)


# -- (b) the engine: logits, not tokens ---------------------------------------

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


def test_prefill_then_decode_gives_the_references_logits(model, weights):
    """Prompts of 1, 2 and 3 tokens (shorter than the state reaches back:
    zeros lie before the sequence), every prompt shorter than its bucket
    (8, 16: the state is taken at `length`), and ONE slot, so that every
    request after the first reuses it, the longest first: a shorter
    request's prefill overwrites the longer one's state whole."""
    prompts = [prompt_of(n) for n in (13, 1, 2, 3, 9, 5)]
    engine, served = served_logprobs(model, prompts, max_batch_size=1)
    hold_to_the_reference(weights, prompts, served)
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 2
    assert s["prefill_tokens"] == 33 and s["prefill_bucket_tokens"] == 64
    assert s["slot_state_bytes"] == 5 * 1 * 2 * 64 * 4
    assert s["slot_state_bytes_by_part"] == {"conv": s["slot_state_bytes"]}
    assert s["decode_routed_computed"] == s["decode_routed_held"] > 0
    assert s["prefill_routed_computed"] == s["prefill_routed_held"] \
        == 5 * 4 * 33
    assert s["decode_routed_elsewhere"] == s["prefill_routed_elsewhere"] == 0


# temperature, top-k, top-p and a repetition penalty at once
SEEDED = SAMPLERS[4]
ONE_SLOT_LENGTHS = (13, 1, 2, 3, 9, 5)


@pytest.fixture(scope="module")
def one_slot_seeded(model):
    """The schedule above (ONE slot, the longest first, prompts shorter
    than the state reaches back) with every stream SEEDED: by its length,
    the request the slot served, and the reference over the model's own
    dense forward, which carries no state from token to token."""
    engine = LLMEngine(model, max_batch_size=1, block_size=4, max_context=48)
    reqs = {n: engine.add_request(prompt_of(n), max_new_tokens=6,
                                  **stream_of(SEEDED, n))
            for n in ONE_SLOT_LENGTHS}
    highest(engine.run)
    assert engine.stats()["sampled_tokens"] == 6 * len(reqs)
    return reqs, Reference(model, width=48)


@pytest.mark.parametrize("length", ONE_SLOT_LENGTHS)
def test_a_seeded_stream_is_the_dense_forwards(one_slot_seeded, length):
    """What is DRAWN, and not only the logits drawn from: the key at
    `fold_in(seed, position)`, the penalty's history in a reused slot,
    the packed call's sampler row."""
    reqs, reference = one_slot_seeded
    highest(reference.assert_served, [reqs[length]])


def test_a_full_batch_of_slots_gives_the_references_logits(model, weights):
    prompts = [prompt_of(n, 1) for n in (5, 9, 13, 7, 11, 6, 2)]
    _, served = served_logprobs(model, prompts, max_batch_size=3)
    hold_to_the_reference(weights, prompts, served)


@pytest.mark.parametrize("sampler", [SAMPLERS[0], SAMPLERS[4]],
                         ids=["greedy", "penalty"])
def test_streams_are_generates_under_an_eviction_schedule(model, sampler):
    """A pool too tight for its batch evicts; the evicted request's resume
    is a re-prefill of prompt + generated tokens, which restores the
    convolutions' state by computing it: every stream is token-identical
    to `generate`, which never preempts; a seeded stream (temperature,
    top-k, top-p and a repetition penalty) to one request at a time
    through the model's dense forward, which keeps no state at all."""
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


# -- (c) decode attention with four queries a key/value head ------------------

def paged_case(heads, kv_heads, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    s, d, bs, m, layers = 4, 64, 8, 6, 2
    blocks = 1 + s * m
    pool = lambda: jnp.asarray(
        rng.normal(size=(layers, blocks, bs, kv_heads * d)), dtype)
    token = lambda h: jnp.asarray(rng.normal(size=(s, 1, h, d)), dtype)
    return dict(q=token(heads), k=token(kv_heads), v=token(kv_heads),
                k_pools=pool(), v_pools=pool(),
                tables=jnp.asarray(1 + np.arange(s * m).reshape(s, m),
                                   jnp.int32),
                lens=jnp.asarray([0, 5, 17, 40], jnp.int32),
                active=jnp.asarray([False, True, True, True]), bs=bs)


def paged_out(case, kernel, q=None):
    out, _, _ = fattn.paged_decode_attention(
        case["q"] if q is None else q, case["k"], case["v"],
        case["k_pools"], case["v_pools"], 1, case["tables"], case["lens"],
        case["active"], case["bs"], kernel=kernel, interpret=True)
    return np.asarray(out, np.float32)[1:, 0]        # the active slots


def plain_attention(case, kv_head_of):
    """An oracle written apart from every variant: a slot at a time, a
    query head at a time, over the slot's own tokens in table order."""
    q, bs = np.asarray(case["q"], np.float64), case["bs"]
    heads, d = q.shape[2], q.shape[3]
    out = np.zeros((q.shape[0], heads, d))
    for s in range(1, q.shape[0]):
        n = int(case["lens"][s])
        rows = {}
        for name, new in (("k_pools", "k"), ("v_pools", "v")):
            pool = np.asarray(case[name][1], np.float64)
            ctx = pool[np.asarray(case["tables"][s])].reshape(-1, pool.shape[-1])
            ctx = np.concatenate([ctx[:n], np.asarray(
                case[new][s], np.float64).reshape(1, -1)])
            rows[name] = ctx.reshape(n + 1, -1, d)
        for h in range(heads):
            kh = kv_head_of(h)
            score = rows["k_pools"][:, kh] @ q[s, 0, h] / np.sqrt(d)
            p = np.exp(score - score.max())
            out[s, h] = (p / p.sum()) @ rows["v_pools"][:, kh]
    return out[1:]


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (32, 8), (4, 4)],
                         ids=["G4", "G4_cell_heads", "G1"])
@pytest.mark.parametrize("kernel", ["pallas", "blockwise", "reference"])
def test_the_decode_variants_agree_at_grouped_queries(kernel, heads,
                                                      kv_heads):
    """The Pallas kernel (in the interpreter), the blockwise loop and the
    dense gather against the plain oracle: query head i reads key/value
    head i // G. Float32 everywhere: 1e-5 of the largest output covers
    the order of the sums."""
    case = paged_case(heads, kv_heads)
    group = heads // kv_heads
    want = plain_attention(case, lambda h: h // group)
    got = paged_out(case, kernel)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    if group > 1:
        # broken on purpose: head i reading key/value head i % KH
        wrong = plain_attention(case, lambda h: h % kv_heads)
        assert np.abs(got - wrong).max() > 0.1 * np.abs(want).max()


def test_grouped_queries_over_a_bf16_pool_keep_every_bit_of_the_kernel():
    """bf16 queries and pools through the kernel's 0/1 products (the
    query's placement in the lanes, the output's assembly): what the loop
    gives from the same rounded operands, to bf16's last bit of the
    output."""
    case = paged_case(8, 2, jnp.bfloat16)
    np.testing.assert_allclose(paged_out(case, "pallas"),
                               paged_out(case, "blockwise"),
                               rtol=2 ** -7, atol=2 ** -7)


def test_the_attention_counters_count_pages_whatever_the_heads():
    """`pallas_copied_pages` and `_count_attention` count pages from the
    lengths alone: the query heads do not enter."""
    lens, active = np.asarray([0, 5, 17, 40]), np.asarray([0, 1, 1, 1], bool)
    assert pa.pallas_copied_pages(lens, active, 6, 8) == (1 + 1 + 3 + 6,
                                                         1 + 3 + 6)


# -- (d) the expert block with every expert held ------------------------------

def block_inputs(weights, n=24, seed=11):
    p = "model.layers.2.feed_forward."
    u = jnp.asarray(np.random.default_rng(seed).normal(
        0, 1, (n, FILE["hidden_size"])), jnp.float32)
    return u, weights[p + "gate.weight"], weights[ref.bias_name(2)], [
        weights[p + f"experts.{leaf}.weight"] for leaf in ("w1", "w3", "w2")]


KERNEL = gm.grouped_matmul


def through_the_kernel(monkeypatch):
    """Every grouped product of whatever shape through the tiled kernel,
    in the interpreter: `grouped_matmul.is_eligible` is what the block's
    choice AND the model's count both ask."""
    calls = []
    monkeypatch.setattr(gm, "is_eligible", lambda *shape: (True, None))
    monkeypatch.setattr(
        gm, "grouped_matmul", lambda a, w, load: calls.append(a.shape)
        or KERNEL(a, w, load, interpret=True))
    return calls


def block(u, router, bias, experts, form, first=0, valid=None, monkeypatch=None):
    """`form`: "masked", "grouped" (the library's product, as the CPU
    resolves) or "kernel" (grouped, by the tiled kernel interpreted)."""
    if form == "kernel":
        form = "grouped"
        through_the_kernel(monkeypatch)
    monkeypatch.setattr(held_experts, "products_form",
                        lambda tokens, topk, held: form)
    return highest(
        held_experts.held_expert_block, u, router, bias, *experts,
        topk=FILE["num_experts_per_tok"], real_experts=FILE["num_experts"],
        scaling=FILE["routed_scaling_factor"], first_held=first,
        valid=valid, scoring="sigmoid", normalise=True, epsilon=1e-6)


@pytest.mark.parametrize("product", ["grouped", "kernel"],
                         ids=["ragged_dot", "tiled_kernel"])
def test_the_grouped_and_the_masked_products_agree(weights, monkeypatch,
                                                   product):
    u, router, bias, experts = block_inputs(weights)
    valid = jnp.arange(24) < 19             # a bucket's padding
    masked, c_m = block(u, router, bias, experts, "masked", valid=valid,
                        monkeypatch=monkeypatch)
    grouped, c_g = block(u, router, bias, experts, product, valid=valid,
                         monkeypatch=monkeypatch)
    close(grouped[:19], masked[:19])
    assert float(jnp.max(jnp.abs(grouped[19:]))) == 0.0
    assert np.asarray(c_m).tolist() == np.asarray(c_g).tolist()
    held, computed = int(c_g[0]), int(c_g[5])
    assert held == computed == 19 * 4       # no token dropped
    # and both are the reference's block, which chooses for itself
    params = {"model.layers.2.feed_forward.gate.weight": router,
              ref.bias_name(2): bias}
    params.update({f"model.layers.2.feed_forward.experts.{leaf}.weight": w
                   for leaf, w in zip(("w1", "w3", "w2"), experts)})
    want = highest(ref.expert_block, u[None], params, 2, FILE, jnp.matmul)
    close(grouped[:19], want[0, :19])


def test_the_form_follows_the_calls_shape():
    """All 32 of top 4 held: the grouped form, at a decode launch and at
    every prefill (the chip's readings and the compile times behind it:
    PERF.md section 4); 16 held of top 12: masked at every size its cell
    runs, as `serve_longcat_decode` had it."""
    assert {held_experts.products_form(t, 4, 32)
            for t in (128, 256, 512, 1024, 2048)} == {"grouped"}
    assert {held_experts.products_form(t, 12, 16)
            for t in (128, 256, 512)} == {"masked"}


@pytest.mark.parametrize("form", ["grouped", "masked", "kernel"])
def test_four_shares_of_the_experts_add_up_to_the_block_that_holds_all(
        weights, form, monkeypatch):
    """The test that ties a share to the model: shares of 4 experts
    (`first_held` 0, 4, 8, 12 of this size's 16; the cell's 0, 8, 16, 24
    of 32) each add nothing for an expert held elsewhere, and together
    they are the block that holds every expert."""
    u, router, bias, experts = block_inputs(weights)
    whole, counters = block(u, router, bias, experts, form,
                            monkeypatch=monkeypatch)
    total, held = 0.0, 0
    for first in range(0, 16, 4):
        part, c = block(u, router, bias,
                        [w[first:first + 4] for w in experts], form,
                        first=first, monkeypatch=monkeypatch)
        total = total + part
        held += int(c[0])
        assert int(c[0]) == int(c[5]) and int(c[0]) + int(c[2]) == 24 * 4
    close(total, whole)
    assert held == int(counters[0]) == 24 * 4


def test_a_kernel_product_skips_what_the_padding_leaves_past_the_groups(
        weights, monkeypatch):
    """The kernel leaves in the rows past the last group what the memory
    held; the block cuts them off behind every product: a POISONED
    padding token (its rows sort past the groups) changes no valid
    token's result, and its own is zero."""
    u, router, bias, experts = block_inputs(weights)
    valid = jnp.arange(24) < 7
    clean, _ = block(u, router, bias, experts, "kernel", valid=valid,
                     monkeypatch=monkeypatch)
    dirty, _ = block(u.at[7:].set(jnp.nan), router, bias, experts, "kernel",
                     valid=valid, monkeypatch=monkeypatch)
    assert np.array_equal(np.asarray(clean[:7]), np.asarray(dirty[:7]))
    assert float(jnp.max(jnp.abs(clean[7:]))) == 0.0


def test_the_products_and_the_kernels_are_counted_over_layers_and_calls(
        model, monkeypatch):
    """`products`: three a block call, summed over the expert layers and,
    in `stats()`, over the calls; `kernel_products`: those of them the
    tiled kernel ran: none on the CPU, all of them where the kernel takes
    the shapes (here: told so, the kernel interpreted). The count and the
    block's choice ask the same function."""
    names = lfm.Lfm2MoeForCausalLM.serve_counter_names
    assert names[:len(held_experts.COUNTERS)] == held_experts.COUNTERS
    assert names[-2:] == ("products", "kernel_products")
    layers = FILE["num_hidden_layers"] - FILE["num_dense_layers"]
    ids = jnp.asarray([prompt_of(9)], jnp.int32)

    def counted():
        highest(model, ids)
        return dict(zip(names, np.asarray(model.pop_serve_counters())))
    got = counted()
    assert (got["products"], got["kernel_products"]) == (3 * layers, 0)
    assert got["routed_held"] == 9 * FILE["num_experts_per_tok"] * layers

    def served(engine):
        reqs = [engine.add_request(prompt_of(n), max_new_tokens=4)
                for n in (13, 5)]
        highest(engine.run)
        st = engine.stats()
        assert all(len(r.generated) == 4 for r in reqs)
        for phase in ("decode", "prefill"):
            assert st[phase + "_products"] \
                == 3 * layers * st[phase + "_counted"] > 0
        return st, [list(r.generated) for r in reqs]
    st, tokens = served(LLMEngine(model, max_batch_size=2, block_size=4,
                                  max_context=48))
    assert st["decode_kernel_products"] == st["prefill_kernel_products"] == 0
    calls = through_the_kernel(monkeypatch)
    got = counted()
    assert got["kernel_products"] == got["products"] == 3 * layers
    assert len(calls) == 3 * layers
    st, through = served(LLMEngine(model, max_batch_size=2, block_size=4,
                                   max_context=48))
    assert st["decode_kernel_products"] == st["decode_products"]
    assert st["prefill_kernel_products"] == st["prefill_products"]
    assert through == tokens


# -- (e) what the engine refuses ----------------------------------------------

@pytest.mark.parametrize("option,named", [
    ({"enable_prefix_cache": True}, "enable_prefix_cache"),
    ({"max_adapters": 2}, "max_adapters"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'")])
def test_an_option_a_per_slot_state_lacks_is_refused_by_name(model, option,
                                                             named):
    # the refusal names the option and the state's parts, kind by kind
    with pytest.raises(ValueError, match=named + r".*per-slot state "
                       r"\(parts: conv \[128\]\)"):
        LLMEngine(model, max_batch_size=2, block_size=4, max_context=32,
                  **option)


def test_the_cache_builds_and_threads_the_state_the_spec_describes(model):
    """`PagedKVCache` allocates what `CacheSpec` describes, `buffers()` is
    what the programs donate, and a view hands the state on with the index
    of the next layer that owns one, apart from the pools' own index."""
    from paddle_tpu.serving.cache import (CacheSpec, PagedCacheView,
                                          PagedKVCache)
    spec = model.cache_spec()
    cache = PagedKVCache(spec, 9, 4, jnp.float32, num_slots=3)
    assert cache.k_pools.shape == (2, 9, 4, 2 * 8)      # key/value heads' row
    (state,) = cache.slot_state
    assert state.shape == (5, 3, 2 * 64) and state.dtype == jnp.float32
    assert [b.shape for b in cache.buffers()] == [
        cache.k_pools.shape, cache.v_pools.shape, state.shape]
    assert cache.slot_state_bytes() == {"conv": state.nbytes}
    plain = PagedKVCache(CacheSpec.per_head(2, 4, 8), 9, 4, jnp.float32)
    assert plain.slot_state is None and len(plain.buffers()) == 2
    assert plain.slot_state_bytes() == {}
    view = PagedCacheView(cache.k_pools, cache.v_pools, 0, None, None, None,
                          4, slot_state=cache.slot_state)
    after_conv = view.updated(slot_state=(state + 1,))
    assert (after_conv.layer, after_conv.state_layer) == (0, 1)
    after_attn = after_conv.updated(cache.k_pools, cache.v_pools)
    assert (after_attn.layer, after_attn.state_layer) == (1, 1)
    assert after_attn.slot_state is after_conv.slot_state
    with pytest.raises(ValueError, match="not whole groups"):
        CacheSpec.per_head(2, 3, 8, query_heads=8)


def test_a_state_of_several_parts_each_of_its_own_shape_and_type():
    """The one-part state above is the one-entry case of this: an array
    a part, ``[layers, slots] + shape``, a part that names a dtype keeps
    it whatever the model's, `buffers()` holds them in the parts' order
    and a spec names parts exactly where it has layers that keep them."""
    from paddle_tpu.serving.cache import CacheSpec, PagedKVCache
    parts = (("conv", (24,), None), ("delta", (2, 4, 4), jnp.float32))
    spec = CacheSpec.per_head(1, 2, 8, state_layers=3, state_parts=parts)
    cache = PagedKVCache(spec, 5, 4, jnp.bfloat16, num_slots=2)
    conv, delta = cache.slot_state
    assert (conv.shape, conv.dtype) == ((3, 2, 24), jnp.bfloat16)
    assert (delta.shape, delta.dtype) == ((3, 2, 2, 4, 4), jnp.float32)
    assert [b.shape for b in cache.buffers()[2:]] == [conv.shape,
                                                      delta.shape]
    assert cache.slot_state_bytes() == {"conv": 3 * 2 * 24 * 2,
                                        "delta": 3 * 2 * 32 * 4}
    (first, second), *_ = spec.empty_prefill(jnp.bfloat16)[1:]
    assert (tuple(first.shape), tuple(second.shape)) == ((1, 24),
                                                         (1, 2, 4, 4))
    assert second._value.dtype == jnp.float32
    for layers, named in ((3, ()), (0, parts)):
        with pytest.raises(ValueError, match="names its parts"):
            CacheSpec.per_head(1, 2, 8, state_layers=layers,
                               state_parts=named)


# -- (f) broken on purpose: each must fail the comparison ---------------------

def broken_state(fault):
    """`_short_conv` with the named fault in what a PREFILL hands back
    (a call of more than one position)."""
    sound = lfm.Lfm2MoeForCausalLM._short_conv

    def short_conv(self, u, p, state, length):
        out, new = sound(self, u, p, state, length)
        if u.shape[1] == 1:
            return out, new
        if fault == "left_at_zeros":
            return out, jnp.zeros_like(new)
        # taken at the bucket's end, not at the prompt's
        return out, sound(self, u, p, state, jnp.full_like(
            length, u.shape[1]))[1]
    return short_conv


@pytest.mark.parametrize("fault", ["left_at_zeros", "taken_at_bucket_end"])
def test_a_state_the_prefill_gets_wrong_fails_the_comparison(
        model, weights, fault, monkeypatch):
    prompts = [prompt_of(n) for n in (13, 3, 9)]
    monkeypatch.setattr(lfm.Lfm2MoeForCausalLM, "_short_conv",
                        broken_state(fault))
    _, served = served_logprobs(model, prompts, max_batch_size=1)
    assert hold_to_the_reference(weights, prompts, served,
                                 tol=None) > 100 * TOL


def test_a_query_head_that_reads_the_wrong_key_head_fails_the_comparison(
        model, weights, monkeypatch):
    """Query head i reading key/value head i % KH (the other way to lay
    groups out) through the engine's decode launches."""
    heads, kv = FILE["num_attention_heads"], FILE["num_key_value_heads"]
    perm = np.asarray([(i % kv) * (heads // kv) + i // kv
                       for i in range(heads)])
    sound = fattn.paged_decode_attention

    def wrong(q, *args, **kw):
        out, *pools = sound(q[:, :, np.argsort(perm)], *args, **kw)
        return (out[:, :, perm], *pools)
    monkeypatch.setattr(fattn, "paged_decode_attention", wrong)
    prompts = [prompt_of(n) for n in (13, 9)]
    _, served = served_logprobs(model, prompts, max_batch_size=2)
    assert hold_to_the_reference(weights, prompts, served,
                                 tol=None) > 100 * TOL
