"""LongCat-Flash on the CPU at a tiny size that keeps every ratio's KIND:
two attention sublayers and two dense FFNs a layer around one expert
block, a router over real + identity experts wider than what is held,
more choices a token than experts held, a latent row of a compressed and
a rotary part. The model is held to the benchmark's plain reference
(`benchmark/reference/longcat_flash.py`, written apart from it), the
engine to the reference's full forward over prompt + served tokens.

Tolerances: both sides compute in float32 with products at "highest", so
they differ only in the ORDER of sums (an absorbed product against an
expanded one, a gathered expert against a masked one, blockwise softmax):
1e-4 of the largest logit. A program that drops the identity term, skips
the bias, renormalises the weights, scales the rotary key or adds the
expert block's result before the second sublayer is off by 1e-2 or more
of it."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests", "benchmark",
                                   "longcat_model")]

import paddle_tpu as paddle  # noqa: E402
from benchmark import seeded  # noqa: E402
from benchmark.reference import longcat_flash as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    held_experts)
from paddle_tpu.incubate.models import longcat_flash as lc  # noqa: E402
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.serving import LLMEngine  # noqa: E402
from paddle_tpu.serving.cache import (PagedCacheView,  # noqa: E402
                                      PagedKVCache, scatter_prefill)
from serving_reference import SAMPLERS, Reference, stream_of  # noqa: E402
from tiny_longcat import TINY_LONGCAT  # noqa: E402

from benchmark.programs import paddle_longcat  # noqa: E402

EXPERTS, HELD = 16, 4            # real experts the router ranks; held
FILE = dict(TINY_LONGCAT, experts_held_from=4)     # a share in the middle
TOL = 1e-4


def weights_of(file, seed=3, std=0.3, bias=True):
    """Float32 seeded weights by the reference's names, and (the tests
    carry it: the benchmark holds it at zeros) a non-zero router bias."""
    w = dict(seeded.make_weights(ref.param_shapes(file), seed, jnp.float32,
                                 std))
    if bias:
        rng = np.random.default_rng(seed)
        ranked = file["published"]["n_routed_experts"] \
            + file["zero_expert_num"]
        for i in range(file["num_layers"]):
            w[ref.bias_name(i)] = jnp.asarray(
                rng.normal(0, 0.05, ranked), jnp.float32)
    return w


def model_of(file, weights):
    """The program's model around the same weights, the bias in its
    buffers."""
    model = lc.LongCatFlashForCausalLM(
        paddle_longcat._model_config(file),
        weights={k: v for k, v in weights.items() if "bias" not in k})
    for i in range(file["num_layers"]):
        if ref.bias_name(i) in weights:
            model.router_bias(i)._value = weights[ref.bias_name(i)]
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


# -- (a) the model against the reference --------------------------------------

def test_parameter_names_and_shapes_are_the_references():
    cfg = paddle_longcat._model_config(FILE)
    assert lc.param_shapes(cfg) == ref.param_shapes(FILE)
    assert list(lc.param_shapes(cfg)) == list(ref.param_shapes(FILE))
    assert cfg.held == (4, HELD) and cfg.n_routed_experts == EXPERTS
    assert ref.num_params(FILE) == sum(
        int(np.prod(s)) for s in ref.param_shapes(FILE).values())


def test_full_forward_logits_agree_with_a_non_zero_bias(model, weights):
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, FILE["vocab_size"], (2, 12)), jnp.int32)
    got = highest(model, paddle.Tensor(ids))._value
    close(got, ref.forward(weights, ids, FILE))


@pytest.mark.parametrize("fault", ["identity_dropped", "bias_skipped",
                                   "weights_renormalised",
                                   "rotary_key_scaled", "m_added_early"])
def test_a_fault_in_the_layers_mathematics_fails_the_comparison(
        weights, fault, monkeypatch):
    """Each departure that the issue names, made in the PROGRAM, moves the
    logits by far more than the tolerance."""
    model = model_of(FILE, weights)
    block, real = held_experts.held_expert_block, held_experts.route
    if fault == "identity_dropped":
        def faulty(u, *a, **kw):
            m, c = block(u, *a, **kw)
            none = dict(kw, real_experts=10 ** 6)   # no id is an identity's
            return m - (block(u, *a, **kw)[0] - block(u, *a, **none)[0]), c
        monkeypatch.setattr(lc, "held_expert_block", faulty)
    elif fault == "bias_skipped":
        monkeypatch.setattr(held_experts, "route",
                            lambda u, w, b, k, s, *form:
                            real(u, w, None, k, s, *form))
    elif fault == "weights_renormalised":
        def renormalised(u, w, b, k, s, *form):
            chosen, weight = real(u, w, b, k, s, *form)
            return chosen, s * weight / jnp.sum(weight, -1, keepdims=True)
        monkeypatch.setattr(held_experts, "route", renormalised)
    elif fault == "rotary_key_scaled":
        keep = lc.mla.queries_and_row

        def scaled(x, pos, w, cfg):
            qn, qr, c, kr = keep(x, pos, w, cfg)
            return qn, qr, c, kr * 2.0
        monkeypatch.setattr(lc.mla, "queries_and_row", scaled)
    else:
        # the block's result joins right after it is computed
        def early(u, *a, **kw):
            m, c = block(u, *a, **kw)
            early.m = m
            return jnp.zeros_like(m), c
        monkeypatch.setattr(lc, "held_expert_block", early)
        mlp = lc._swiglu
        monkeypatch.setattr(
            lc, "_swiglu", lambda x, *w: mlp(x, *w) + (
                early.__dict__.pop("m").reshape(x.shape).astype(x.dtype)
                if "m" in early.__dict__ else 0))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, FILE["vocab_size"], (2, 12)), jnp.int32)
    got = highest(model, paddle.Tensor(ids))._value
    want = ref.forward(weights, ids, FILE)
    off = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert off > 100 * TOL, (fault, off)


# -- (b) the engine through the latent paged cache ----------------------------

def serve(model, kernel=None, new_tokens=10):
    engine = LLMEngine(model, max_batch_size=4, block_size=4,
                       max_context=48, attention_kernel=kernel)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, FILE["vocab_size"], n).tolist()
               for n in (5, 9, 13, 7, 11, 6)]
    reqs = [engine.add_request(p, max_new_tokens=new_tokens)
            for p in prompts]
    highest(engine.run)
    return engine, prompts, [list(r.generated) for r in reqs]


def gaps_of(weights, prompts, served):
    """At every served position, how far the served token's logit lies
    below the reference's best over prompt + served tokens."""
    gaps = []
    for prompt, out in zip(prompts, served):
        logits = ref.forward(weights, jnp.asarray([prompt + out],
                                                  jnp.int32), FILE)[0]
        at = np.arange(len(prompt) - 1, len(prompt) + len(out) - 1)
        gaps.append(np.asarray(jnp.max(logits[at], -1)
                               - logits[at, np.asarray(out)]))
    return np.concatenate(gaps), float(jnp.max(jnp.abs(logits)))


@pytest.mark.parametrize("kernel", ["blockwise", "reference"])
def test_prefill_then_decode_serves_the_references_tokens(model, weights,
                                                          kernel):
    engine, prompts, served = serve(model, kernel)
    assert all(len(s) == 10 for s in served)
    gaps, scale = gaps_of(weights, prompts, served)
    assert gaps.max() <= TOL * scale
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["kv_dtype"] == "float32"
    assert engine.cache.spec.kind == "latent"
    assert engine.cache.v_pools.size == 0          # ONE pool, one row a token


# temperature, top-k, top-p and a repetition penalty at once
SEEDED = SAMPLERS[4]


@pytest.mark.parametrize("schedule", ["batch", "one_slot", "eviction"])
def test_a_seeded_stream_is_the_dense_forwards(model, schedule):
    """What is DRAWN through the latent pool, and not only the logits
    drawn from: every stream is the one a request at a time through the
    model's own dense forward is owed, in a full batch, through ONE slot
    that every request after the first reuses (the longest first), and
    under a pool so tight that requests are evicted and resume by a
    second prefill."""
    lengths = {"batch": (5, 9, 13, 7, 11, 6), "one_slot": (29, 1, 2, 13, 4),
               "eviction": (11, 12, 10, 5)}[schedule]
    engine = LLMEngine(
        model, block_size=4, max_context=48,
        **{"batch": dict(max_batch_size=4), "one_slot": dict(max_batch_size=1),
           "eviction": dict(max_batch_size=3, num_blocks=10,
                            watermark_blocks=1)}[schedule])
    rng = np.random.default_rng(2)
    reqs = [engine.add_request(rng.integers(0, FILE["vocab_size"], n).tolist(),
                               max_new_tokens=10, **stream_of(SEEDED, i))
            for i, n in enumerate(lengths)]
    highest(engine.run)
    s = engine.stats()
    assert s["decode_compiles"] == 1
    assert s["sampled_tokens"] == 10 * len(lengths)
    assert (s["evictions"] >= 1) == (schedule == "eviction")
    highest(Reference(model, width=48).assert_served, reqs)


def test_an_altered_served_token_is_seen_in_the_gap(model, weights):
    _, prompts, served = serve(model)
    served[2][4] = (served[2][4] + 1) % FILE["vocab_size"]
    gaps, scale = gaps_of(weights, prompts, served)
    assert gaps.max() > 100 * TOL * scale


@pytest.mark.parametrize("lens", [[9, 17, 4], [1, 23, 16]])
@pytest.mark.parametrize("kernel", ["blockwise", "reference"])
def test_absorbed_decode_equals_expanded_attention(model, weights, kernel,
                                                   lens):
    """One token a slot through the paged latent pool, absorbed, gives the
    logits the full forward gives at that position: no key or value of a
    head is made for a cached token, and the result does not notice."""
    spec = model.cache_spec()
    bs, slots, table = 4, 3, 6
    cache = PagedKVCache(spec, 1 + slots * table, bs, jnp.float32)
    rng = np.random.default_rng(5)
    ids = [rng.integers(0, FILE["vocab_size"], n + 1) for n in lens]
    k_pools, v_pools = cache.k_pools, cache.v_pools
    tables = np.zeros((slots, table), np.int32)
    for slot, n in enumerate(lens):
        row = np.zeros(table, np.int32)
        row[:-(-(n + 1) // bs)] = cache.allocator.allocate(-(-(n + 1) // bs))
        tables[slot] = row
        prompt = jnp.asarray(ids[slot][None, :n], jnp.int32)
        _, rows = highest(model, paddle.Tensor(prompt),
                          caches=spec.empty_prefill(jnp.float32))
        k_pools, v_pools = scatter_prefill(
            k_pools, v_pools, jnp.stack([c[0]._value[0] for c in rows]),
            jnp.stack([c[1]._value[0] for c in rows]), jnp.asarray(row),
            jnp.int32(n), bs)
    view = PagedCacheView(k_pools, v_pools, 0, jnp.asarray(tables),
                          jnp.asarray(lens, jnp.int32),
                          jnp.ones(slots, bool), bs, kernel=kernel)
    last = jnp.asarray([[int(i[-1])] for i in ids], jnp.int32)
    got, (after,) = highest(model, paddle.Tensor(last), caches=[view])
    assert after.layer == spec.num_layers
    for slot, n in enumerate(lens):
        want = ref.forward(weights, jnp.asarray(ids[slot][None], jnp.int32),
                           FILE)[0, n]
        close(got._value[slot, 0], want)


# -- (c) the partition test ---------------------------------------------------

def test_the_shares_parts_add_up_to_the_uncut_layers_expert_block(weights):
    """Over all shares of the experts: each share's m, with what every
    chip computes alike (the identity experts) counted once, adds up to
    the uncut reference's m for the whole layer."""
    uncut = dict(FILE, n_routed_experts=EXPERTS, experts_held_from=0)
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.normal(0, 1, (1, 24, FILE["hidden_size"])),
                    jnp.float32)
    p = "model.layers.0.mlp."
    whole = dict(weights)
    for leaf, shape in (("gate_proj", (EXPERTS, 64, 32)),
                        ("up_proj", (EXPERTS, 64, 32)),
                        ("down_proj", (EXPERTS, 32, 64))):
        whole[p + f"experts.{leaf}.weight"] = jnp.asarray(
            rng.normal(0, 0.3, shape), jnp.float32)
    mm = jnp.matmul
    want = highest(ref.expert_block, u, whole, 0, uncut, mm)
    # the identity experts alone: a share that holds no expert
    alike = highest(ref.expert_block, u, whole, 0,
                    dict(uncut, n_routed_experts=0), mm)
    assert float(jnp.max(jnp.abs(alike))) > 0
    total = alike
    for first in range(0, EXPERTS, HELD):
        held = slice(first, first + HELD)
        m, counters = highest(
            held_experts.held_expert_block, u[0],
            whole[p + "router.classifier.weight"],
            whole[ref.bias_name(0)],
            *(whole[p + f"experts.{leaf}.weight"][held]
              for leaf in ("gate_proj", "up_proj", "down_proj")),
            topk=FILE["moe_topk"], real_experts=EXPERTS,
            scaling=FILE["routed_scaling_factor"], first_held=first)
        share = dict(uncut, n_routed_experts=HELD, experts_held_from=first,
                     published={"n_routed_experts": EXPERTS})
        cut = dict(whole, **{
            p + f"experts.{leaf}.weight":
                whole[p + f"experts.{leaf}.weight"][held]
            for leaf in ("gate_proj", "up_proj", "down_proj")})
        close(m[None], highest(ref.expert_block, u, cut, 0, share, mm))
        total = total + (m[None] - alike)
        assert int(counters[0]) == int(counters[5])
    close(total, want)


# -- (d) routing --------------------------------------------------------------

def block_of(u, weights, bias, first=4, **kw):
    p = "model.layers.0.mlp."
    return highest(
        held_experts.held_expert_block, u,
        weights[p + "router.classifier.weight"], bias,
        *(weights[p + f"experts.{leaf}.weight"]
          for leaf in ("gate_proj", "up_proj", "down_proj")),
        topk=FILE["moe_topk"], real_experts=EXPERTS,
        scaling=FILE["routed_scaling_factor"], first_held=first, **kw)


def tokens(n, seed=11):
    return jnp.asarray(np.random.default_rng(seed).normal(
        0, 1, (n, FILE["hidden_size"])), jnp.float32)


def test_the_bias_moves_the_choice_and_never_the_weight(weights):
    u = tokens(8)
    router = weights["model.layers.0.mlp.router.classifier.weight"]
    scores = jax.nn.softmax(highest(jnp.matmul, u, router), -1)
    plain, _ = highest(held_experts.route, u, router, None, 6, 6.0)
    bias = jnp.zeros(EXPERTS + 8).at[9].set(10.0)
    chosen, weight = highest(held_experts.route, u, router, bias, 6, 6.0)
    assert bool(jnp.all(jnp.any(chosen == 9, -1)))
    assert not bool(jnp.all(jnp.any(plain == 9, -1)))
    np.testing.assert_allclose(
        weight, 6.0 * jnp.take_along_axis(scores, chosen, -1), rtol=1e-6)


def test_a_token_that_chose_only_identity_experts_is_a_scale_of_itself(
        weights):
    u = tokens(8)
    bias = jnp.zeros(EXPERTS + 8).at[EXPERTS:].set(10.0)
    m, counters = block_of(u, weights, bias)
    router = weights["model.layers.0.mlp.router.classifier.weight"]
    scores = jax.nn.softmax(highest(jnp.matmul, u, router), -1)
    top = jax.lax.top_k(scores[:, EXPERTS:], 6)[0]
    close(m, 6.0 * jnp.sum(top, -1, keepdims=True) * u)
    assert counters.tolist() == [0, 48, 0, 0, HELD, 0]


def test_a_token_with_no_held_expert_gets_only_its_identity_term(weights):
    u = tokens(8)
    bias = jnp.zeros(EXPERTS + 8).at[4:4 + HELD].set(-10.0)
    m, counters = block_of(u, weights, bias)
    chosen, weight = highest(
        held_experts.route, u,
        weights["model.layers.0.mlp.router.classifier.weight"], bias, 6, 6.0)
    scale = jnp.sum(jnp.where(chosen >= EXPERTS, weight, 0.0), -1)
    close(m, scale[:, None] * u)
    assert int(counters[0]) == 0 and int(counters[5]) == 0


@pytest.mark.parametrize("n,padding,idle_held", [
    (24, 3, ()), (256, 226, ()), (64, 3, (0, 2))])
def test_no_token_is_dropped_and_the_counters_add_up(weights, n, padding,
                                                     idle_held):
    """Counters sum to topk x T over the valid tokens; assignments
    computed = assignments to held experts, at any load (253 tokens give
    an expert some 80) and where held experts sit idle (a bias keeps
    every token off two of them: they are counted idle and not run)."""
    u = tokens(n)
    bias = jnp.zeros(EXPERTS + 8)
    for e in idle_held:
        bias = bias.at[4 + e].set(-10.0)
    valid = jnp.arange(n) < n - padding
    m, counters = block_of(u, weights, bias, valid=valid)
    held, identity, elsewhere, load_max, idle, computed = counters.tolist()
    assert held + identity + elsewhere == 6 * (n - padding)
    assert computed == held > 0 and load_max <= n - padding
    assert idle == len(idle_held)
    want = highest(ref.expert_block, u[None], dict(weights, **{
        ref.bias_name(0): bias}), 0, FILE, jnp.matmul)[0]
    close(m[:n - padding], want[:n - padding])


def test_an_idle_expert_is_not_run(weights, monkeypatch):
    """The products of an expert no token chose are never computed: the
    program's `cond` takes the branch that adds nothing."""
    ran = []
    real = held_experts._swiglu
    monkeypatch.setattr(
        held_experts, "_swiglu",
        lambda x, *w: jax.debug.callback(lambda: ran.append(1)) or real(
            x, *w))
    bias = jnp.zeros(EXPERTS + 8).at[4:4 + HELD].set(-10.0).at[5].set(10.0)
    _, counters = block_of(tokens(8), weights, bias)
    jax.effects_barrier()
    assert int(counters[4]) == HELD - 1 and len(ran) == 1


def test_the_models_counters_sum_over_its_layers(model):
    ids = jnp.zeros((2, 5), jnp.int32)
    highest(model, paddle.Tensor(ids))
    counters = model.pop_serve_counters()
    assert int(sum(counters[:3])) == FILE["num_layers"] * 10 * 6
    assert model.pop_serve_counters() is None


def test_the_engines_stats_carry_the_counters_by_phase(model):
    engine, prompts, served = serve(model)
    s = engine.stats()
    kinds = ("routed_held", "routed_identity", "routed_elsewhere")
    assert s["prefill_counted"] == len(prompts)
    assert sum(s[f"prefill_{k}"] for k in kinds) == \
        FILE["num_layers"] * 6 * s["prefill_tokens"]
    assert s["prefill_tokens"] == sum(map(len, prompts))
    assert s["decode_counted"] == s["decode_launches"]
    assert sum(s[f"decode_{k}"] for k in kinds) == \
        FILE["num_layers"] * 6 * s["decode_tokens"]
    for phase in ("prefill", "decode"):
        assert s[f"{phase}_routed_computed"] == s[f"{phase}_routed_held"]
    engine.reset_stats()
    assert "decode_counted" not in engine.stats()


def test_a_launched_prefills_counters_come_with_its_first_token(model):
    """The engine launches a prefill and reads its counters at the
    commit, from the row that brings the first token: the window's
    `prefill_*` counts are what the model's own dense forward counts
    over each prompt, though four of the six prefills (two were their
    bucket's first call) were never awaited."""
    engine, prompts, _ = serve(model)
    names = type(model).serve_counter_names
    want = np.zeros(len(names), np.int64)
    for prompt in prompts:
        highest(model, paddle.Tensor(jnp.asarray([prompt], jnp.int32)))
        want += np.asarray(model.pop_serve_counters())
    s = engine.stats()
    assert "routed_held" in names and want.sum() > 0
    assert [s[f"prefill_{name}"] for name in names] == want.tolist()
    assert s["prefill_counted"] == len(prompts)
    assert s["prefill_unawaited_share"] == pytest.approx(4 / 6)


# -- (e) the seams in the engine ----------------------------------------------

@pytest.mark.parametrize("option,named", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"enable_prefix_cache": True}, "enable_prefix_cache"),
    ({"max_adapters": 2}, "max_adapters")])
def test_an_option_the_latent_cache_lacks_is_refused_by_name(model, option,
                                                             named):
    with pytest.raises(ValueError, match=named):
        LLMEngine(model, max_batch_size=2, block_size=4, max_context=32,
                  **option)


def test_the_engine_serves_through_the_latent_kernel(model, weights,
                                                     monkeypatch):
    """On a TPU (here: told so, the kernel in the interpreter) an engine
    over a latent cache takes `attention_kernel='pallas'`, and chooses
    it unasked for a row and a page on the tiles: it serves the blockwise
    engine's tokens, compiles one decode program, and counts the pages
    the kernel copies: those that hold tokens, and one for every
    inactive slot of a launch."""
    from paddle_tpu.kernels.pallas import paged_attention as pa

    def serve_through(kernel, block_size=8):
        engine = LLMEngine(model, max_batch_size=4, block_size=block_size,
                           max_context=48, attention_kernel=kernel)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, FILE["vocab_size"], n).tolist()
                   for n in (5, 9, 13, 7, 11, 6)]
        reqs = [engine.add_request(p, max_new_tokens=10) for p in prompts]
        highest(engine.run)
        return engine, prompts, [list(r.generated) for r in reqs]

    _, prompts, expect = serve_through("blockwise")
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    kernel = pa.pallas_latent_attention
    calls = []
    monkeypatch.setattr(
        pa, "pallas_latent_attention",
        lambda *args, interpret=False, **kw: calls.append(1) or kernel(
            *args, interpret=True, **kw))
    for asked in ("pallas", None):
        engine, _, served = serve_through(asked)
        st, raw = engine.stats(), engine._stats
        assert st["attention_kernel"] == "pallas"
        assert served == expect
        assert st["decode_compiles"] == 1
        idle = raw.launches * 4 - raw.decode_tokens
        assert idle > 0
        assert raw.attn_entries_streamed == raw.attn_entries_held + idle
        assert 0.0 < st["attn_held_share"] < st["attn_streamed_share"] < 1.0
    # traced once a cached sublayer, in each engine's ONE decode program
    assert len(calls) == 2 * model.cache_spec().num_layers
    gaps, scale = gaps_of(weights, prompts, served)
    assert gaps.max() <= TOL * scale
    # a page off the sublane tiles: the loop, unasked and with no event
    assert serve_through(None, block_size=4)[0].stats()[
        "attention_kernel"] == "blockwise"


def test_this_models_weights_are_program_arguments_and_never_read(model):
    engine = LLMEngine(model, max_batch_size=2, block_size=4,
                       max_context=32)
    assert engine._weights_as_args and not engine._hot_swap
    assert engine._weights_crc is None        # no host read at construction
    aux = engine._decode_aux()
    assert [v.shape for v in aux["params"]] == [
        tuple(s) for s in ref.param_shapes(FILE).values()]


def test_hot_swap_cuts_over_to_new_weights(weights):
    model = model_of(FILE, weights)
    engine = LLMEngine(model, max_batch_size=2, block_size=4,
                       max_context=32, hot_swap=True)
    prompt = [3, 1, 4, 1, 5]
    before = highest(engine.generate, [prompt], max_new_tokens=4)
    other = weights_of(FILE, seed=4)
    engine.swap_weights([other[n] for n, _ in model.named_parameters()])
    after = highest(engine.generate, [prompt], max_new_tokens=4)
    assert engine.weight_epoch == 1 and before != after
    gaps, scale = gaps_of(other, [prompt], after)
    assert gaps.max() <= TOL * scale


def gpt():
    paddle.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))


def test_gpts_description_gives_the_pools_it_always_had():
    model = gpt()
    spec = model.cache_spec()
    assert (spec.kind, spec.num_layers, spec.num_heads, spec.head_dim) == \
        ("kv", 2, 4, 8)
    assert spec.widths == (32, 32)
    engine = LLMEngine(model, max_batch_size=2, block_size=4,
                       max_context=32)
    assert engine.cache.k_pools.shape == engine.cache.v_pools.shape == \
        (2, 1 + 2 * 8, 4, 32)
    assert not engine._tenant and not engine._counter_names
    empty = spec.empty_prefill(jnp.float32)
    assert [tuple(c.shape) for c in empty[0]] == [(1, 0, 4, 8)] * 2
    assert len(empty) == 2


def test_gpts_streams_are_its_own_generates():
    model = gpt()
    engine = LLMEngine(model, max_batch_size=2, block_size=4,
                       max_context=32)
    prompts = [[5, 9, 2, 7], [11, 3, 8, 1, 6, 2]]
    served = engine.generate(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, served):
        want = model.generate(np.asarray([prompt], np.int64),
                              max_new_tokens=6, do_sample=False)
        assert out == np.asarray(want._value)[0].tolist()
    assert "decode_counted" not in engine.stats()


def test_three_widths_of_the_latent_loop_give_what_the_whole_table_gives():
    """At the cell's 128 slots the latent loop runs chunks at 128, 64 or
    32 slots (`CacheSpec.loop_plan`); every slot's output is what a dense
    gather of its whole table gives, and the engine's counter counts
    exactly the entries those widths read."""
    from paddle_tpu.kernels.pallas import paged_attention as pa
    from paddle_tpu.nn.functional.attention import \
        paged_latent_decode_attention
    slots, table, bs, row, value, heads = 128, 12, 4, 24, 16, 2
    plan = dict(chunk_blocks=2, min_width=32)
    widths, chunk, n_chunks = pa._blockwise_plan(slots, table, bs, 1, 128,
                                                 **plan)
    assert widths == (128, 64, 32) and (chunk, n_chunks) == (2, 6)
    rng = np.random.default_rng(2)
    lens = rng.integers(0, 12, slots)
    lens[:5] = [47, 40, 33, 30, 20]               # a few long contexts
    pool = jnp.asarray(rng.normal(0, 1, (1, 1 + slots * table, bs, 128)),
                       jnp.float32)
    tables = jnp.asarray(1 + np.arange(slots * table).reshape(slots, table),
                         jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (slots, heads, row)), jnp.float32)
    new = (jnp.asarray(rng.normal(0, 1, (slots, value)), jnp.float32),
           jnp.asarray(rng.normal(0, 1, (slots, row - value)), jnp.float32))
    args = (q, new, pool, 0, tables, jnp.asarray(lens, jnp.int32),
            jnp.ones(slots, bool), bs)
    got, _ = highest(paged_latent_decode_attention, *args,
                     value_width=value, scale=0.3, kernel="blockwise",
                     **plan)
    want, _ = highest(paged_latent_decode_attention, *args,
                      value_width=value, scale=0.3, kernel="reference")
    close(got, want)
    streamed, held = pa.blockwise_streamed_entries(
        lens, np.ones(slots, bool), table, bs, 1, 128, **plan)
    order = -np.sort(-lens)
    by_hand = 0
    for c in range(order[0] // (chunk * bs) + 1):
        need = slots if c == 0 else int((order // (chunk * bs) >= c).sum())
        by_hand += min(w for w in widths if w >= need) * chunk
    assert streamed == by_hand < slots * table
    assert held == int((lens // bs + 1).sum())


def test_the_programs_lower_the_same_with_the_grouped_kernel_unimportable(
        model, monkeypatch):
    """16 held of top 12 resolve to the masked products, so nothing of the
    grouped form, its tiled kernel (`kernels/pallas/grouped_matmul.py`)
    or the kernel's eligibility reaches this model's programs: every
    prefill and decode program lowers to the same StableHLO with all three
    out of reach (any use raises). Against the parent's bytes:
    `benchmark/proof/pr41_stablehlo.txt`."""

    def lowered():
        engine = LLMEngine(model, max_batch_size=4, block_size=4,
                           max_context=48)
        seen, real = [], engine._call_program

        def spy(name, fn, args, first):
            if first:
                seen.append((name, fn.lower(*args).as_text()))
            return real(name, fn, args, first)
        monkeypatch.setattr(engine, "_call_program", spy)
        rng = np.random.default_rng(0)
        engine.generate([rng.integers(0, FILE["vocab_size"], n).tolist()
                         for n in (5, 9, 13)], max_new_tokens=4)
        return seen

    class Unimportable:
        def __getattr__(self, name):
            raise ImportError(f"grouped_matmul.{name} reached")

    def unreachable(*args, **kw):
        raise AssertionError("the grouped products reached")

    with_kernel = lowered()
    monkeypatch.setattr(held_experts, "_gm", Unimportable())
    monkeypatch.setattr(held_experts, "grouped_products", unreachable)
    monkeypatch.setattr(held_experts, "product_kernel", unreachable)
    without = lowered()
    names = [name for name, _ in with_kernel]
    assert names.count("engine.decode.dispatch") == 1
    assert names.count("engine.prefill.dispatch") >= 2   # two buckets
    assert with_kernel == without
    assert not any("ragged" in text for _, text in with_kernel)
