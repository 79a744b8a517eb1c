"""Multi-tenant serving suite (PR 17, paddle_tpu/serving/tenancy.py).

The contracts pinned here are the ISSUE 17 acceptance criteria:

  * N streams sharing a prompt prefix pay its prefill ONCE and its KV
    bytes once (refcounted aliasing), and every stream's greedy output
    stays token-identical to `model.generate` — including the stream
    that diverges mid-block and triggers copy-on-write;
  * admission accounting (`can_ever_fit`, the watermark check) counts a
    refcounted block once, before AND after aliasing — the PR 17 bugfix;
  * per-tenant LoRA-style adapters are VALUE inputs to the ONE compiled
    decode executable: base tenants are bit-identical to the
    adapter-free engine, tenant churn never recompiles, unknown
    adapters are refused (`adapter_mismatch`), and a live tenant's slot
    cannot be unregistered out from under it;
  * live weight hot-swap is a byte-exact cutover at an iteration
    boundary (zero recompiles), a crash snapshot taken under one weight
    set refuses to restore under another (`torn_swap`), and staging the
    byte-identical set is a no-op.

Prefix-cache and allocator unit tests are pure host-side (no jax work).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events
from paddle_tpu.serving import (BlockAllocator, LLMEngine, Request,
                                Scheduler, ServeRefusal, NULL_BLOCK,
                                PrefixCache, AdapterSet, FINISHED)

from serving_reference import (SAMPLERS, Reference, each_sampler,
                               stream_of)

VOCAB = 128


def _make_model(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _make_model(seed=0)


@pytest.fixture(scope="module")
def reference(model):
    """What the engine owes a request, worked out without an engine."""
    return Reference(model)


def _assert_tenants_served(reference, engine, requests):
    """Every request holds the reference's stream: a base tenant the
    model's own, an adapter's tenant the model's with the adapter folded
    into the weights (`AdapterSet.merged`: W + A @ B x scale, where the
    programs add the low-rank product to the activations)."""
    for req in requests:
        if req.adapter is None:
            reference.assert_served([req])
        else:
            with engine._adapters.merged(req.adapter):
                reference.assert_served([req])


def _prompt(length, seed=0):
    rng = np.random.default_rng(seed * 1000 + length)
    return rng.integers(0, VOCAB, length).tolist()


def _gen(model, prompt, n):
    out = model.generate(paddle.Tensor(np.asarray([prompt], np.int64)),
                         max_new_tokens=n, do_sample=False)
    arr = out._value if hasattr(out, "_value") else out
    return np.asarray(arr)[0].tolist()


_REF_CACHE = {}


def _ref(model, prompt, n):
    key = (id(model), tuple(prompt), n)
    if key not in _REF_CACHE:
        _REF_CACHE[key] = _gen(model, prompt, n)
    return _REF_CACHE[key]


def _shared_prompts(n_prompts, prefix_len=12, suffix_len=3, seed=7):
    """n prompts sharing a `prefix_len`-token prefix, distinct tails."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, VOCAB, prefix_len).tolist()
    return [prefix + rng.integers(0, VOCAB, suffix_len).tolist()
            for _ in range(n_prompts)]


# ---------------------------------------------------------------------------
# refcounted block allocator (pure host-side)
# ---------------------------------------------------------------------------

class TestRefcountedAllocator:
    def test_incref_free_lifecycle(self):
        alloc = BlockAllocator(4)                 # capacity 3
        a, b = alloc.allocate(2)
        assert alloc.num_free == 1
        assert alloc.refcount(a) == 1
        alloc.incref(a)
        # a shared block counts ONCE in the free-block math
        assert alloc.refcount(a) == 2
        assert alloc.num_free == 1
        assert alloc.num_shared == 1
        alloc.free([a])                           # decref: still resident
        assert alloc.refcount(a) == 1
        assert alloc.num_free == 1
        assert alloc.num_shared == 0
        alloc.free([a, b])                        # last refs: back to pool
        assert alloc.num_free == 3
        assert alloc.refcount(a) == 0

    def test_incref_and_free_guard_null_and_unallocated(self):
        alloc = BlockAllocator(4)
        with pytest.raises(ValueError):
            alloc.incref(NULL_BLOCK)
        with pytest.raises(ValueError):
            alloc.incref(2)                       # never allocated
        with pytest.raises(ValueError):
            alloc.free([2])

    def test_all_or_nothing_allocation_unchanged(self):
        alloc = BlockAllocator(4)
        got = alloc.allocate(2)
        alloc.incref(got[0])
        assert alloc.allocate(2) is None          # only 1 truly free
        assert alloc.num_free == 1                # probe did not leak


# ---------------------------------------------------------------------------
# prefix cache index (pure host-side)
# ---------------------------------------------------------------------------

class TestPrefixCacheUnit:
    def _setup(self, num_blocks=16, block_size=4):
        alloc = BlockAllocator(num_blocks)
        return PrefixCache(alloc, block_size), alloc

    def test_publish_acquire_roundtrip_and_len_minus_one_cap(self):
        pc, alloc = self._setup()
        toks = list(range(10))                    # 2 full blocks + tail 2
        blocks = alloc.allocate(3)
        assert pc.publish(toks, blocks) == 3
        # the index holds its own reference on every published block
        assert all(alloc.refcount(b) == 2 for b in blocks)
        # identical prompt: the hit caps at len-1 (one input token must
        # remain so the DECODE step emits the first token)
        shared, hit = pc.probe(toks)
        assert (shared, hit) == (3, 9)
        got, hit = pc.acquire(toks)
        assert got == list(blocks) and hit == 9
        assert all(alloc.refcount(b) == 3 for b in blocks)
        assert pc.hits == 1
        alloc.free(got)                           # caller undo

    def test_partial_match_inside_full_block(self):
        pc, alloc = self._setup()
        toks = list(range(12))                    # 3 full blocks
        blocks = alloc.allocate(3)
        pc.publish(toks, blocks)
        # shares 1 full block + 2 tokens of the second block
        other = toks[:6] + [99, 98, 97, 96]
        got, hit = pc.acquire(other)
        assert hit == 6 and got == list(blocks[:2])
        alloc.free(got)

    def test_sub_block_hit_unusable_unless_whole_prompt(self):
        pc, alloc = self._setup()
        toks = list(range(12))
        pc.publish(toks, alloc.allocate(3))
        # 2 shared tokens < block_size and < len-1: not worth the chew
        assert pc.acquire(toks[:2] + [99] * 8) == ([], 0)
        assert pc.misses == 1
        # ...but a 2-token hit covering the whole cacheable prompt is
        assert pc.acquire(toks[:3])[1] == 2

    def test_reclaim_is_leaf_first_lru(self):
        pc, alloc = self._setup(num_blocks=8)     # capacity 7
        a = list(range(8))                        # chain of 2
        b = [50, 51, 52, 53]                      # chain of 1
        for toks, n in ((a, 2), (b, 1)):
            blocks = alloc.allocate(n)
            pc.publish(toks, blocks)
            alloc.free(blocks)                    # publisher finished:
        assert alloc.num_free == 4                # the index is sole owner
        # a's leaf is older than b's, but touch a so b becomes coldest
        got, _ = pc.acquire(a + [99])
        alloc.free(got)
        dropped = pc.reclaim(5)
        assert dropped == 1 and alloc.num_free == 5
        assert pc.acquire(b + [99]) == ([], 0)    # b was evicted
        assert pc.acquire(a + [99])[1] == 8       # a's chain survives
        # a's ROOT block is never dropped while its child entry lives
        pc.reclaim(6)
        shared, _ = pc.probe(a + [99])
        assert shared in (0, 1)

    def test_reclaim_popularity_beats_recency(self):
        """PR 18 aging eviction: a plain LRU would evict the OLDEST
        entry; the aged-hit-count policy evicts the LEAST POPULAR one,
        so a cold tenant's recent burst cannot rotate out the hot
        shared system prompt."""
        pc, alloc = self._setup(num_blocks=8)
        hot = [10, 11, 12, 13]
        cold = [20, 21, 22, 23]
        for toks in (hot, cold):
            blocks = alloc.allocate(1)
            pc.publish(toks, blocks)
            alloc.free(blocks)
        for _ in range(3):                        # hot: 3 hits, old ticks
            got, _ = pc.acquire(hot + [99])
            alloc.free(got)
        got, _ = pc.acquire(cold + [99])          # cold: 1 hit, NEWEST tick
        alloc.free(got)
        assert pc.reclaim(alloc.num_free + 1) == 1
        assert pc.acquire(cold + [99]) == ([], 0)  # recency didn't save it
        got, hit = pc.acquire(hot + [99])
        assert hit == 4                            # popularity did
        alloc.free(got)

    def test_reclaim_hit_tie_breaks_on_recency(self):
        pc, alloc = self._setup(num_blocks=8)
        first = [10, 11, 12, 13]
        second = [20, 21, 22, 23]
        for toks in (first, second):
            blocks = alloc.allocate(1)
            pc.publish(toks, blocks)
            alloc.free(blocks)
        for toks in (first, second):              # one hit each, in order
            got, _ = pc.acquire(toks + [99])
            alloc.free(got)
        assert pc.reclaim(alloc.num_free + 1) == 1
        assert pc.acquire(first + [99]) == ([], 0)  # older tick loses
        assert pc.acquire(second + [99])[1] == 4

    def test_aging_decays_stale_popularity(self):
        """Hit counts halve every _AGE_PERIOD lookups: an entry hot last
        epoch but cold now loses its eviction immunity to a recently
        used neighbor."""
        from paddle_tpu.serving.tenancy import _AGE_PERIOD
        pc, alloc = self._setup(num_blocks=8)
        stale = [10, 11, 12, 13]
        blocks = alloc.allocate(1)
        pc.publish(stale, blocks)
        alloc.free(blocks)
        for _ in range(4):                        # hot... for now
            got, _ = pc.acquire(stale + [99])
            alloc.free(got)
        for i in range(2 * _AGE_PERIOD):          # two epochs of misses:
            pc.acquire([70 + (i % 8), 1, 2, 3, 4])  # 4 hits decay to 1
        fresh = [20, 21, 22, 23]
        blocks = alloc.allocate(1)
        pc.publish(fresh, blocks)
        alloc.free(blocks)
        got, _ = pc.acquire(fresh + [99])         # 1 hit, newest tick
        alloc.free(got)
        # decayed tie (1 == 1): the stale entry's OLD tick evicts it —
        # without decay its 4 early hits would have been immunity forever
        assert pc.reclaim(alloc.num_free + 1) == 1
        assert pc.acquire(stale + [99]) == ([], 0)
        assert pc.acquire(fresh + [99])[1] == 4

    def test_reclaim_never_drops_pinned_interior(self):
        """A popular leaf cannot force eviction of its own chain's
        interior blocks: victims are leaves only, however cold the
        interior entry's own counters look."""
        pc, alloc = self._setup(num_blocks=8)
        chain = list(range(12))                   # 3 full blocks
        blocks = alloc.allocate(3)
        pc.publish(chain, blocks)
        alloc.free(blocks)
        assert pc.reclaim(alloc.num_free + 1) == 1  # only the leaf goes
        got, hit = pc.acquire(chain + [99])
        assert hit == 8                           # interior chain intact
        alloc.free(got)

    def test_invalidate_frees_reset_forgets(self):
        pc, alloc = self._setup()
        blocks = alloc.allocate(2)
        pc.publish(list(range(8)), blocks)
        alloc.free(blocks)                        # publisher finished
        assert pc.invalidate() == 2
        assert alloc.num_free == alloc.capacity   # index refs released
        blocks = alloc.allocate(2)
        pc.publish(list(range(8)), blocks)
        new_alloc = BlockAllocator(16)
        pc.reset(new_alloc)                       # forget, do NOT free
        assert pc.entries == 0
        assert all(alloc.refcount(b) == 2 for b in blocks)
        assert pc.allocator is new_alloc


# ---------------------------------------------------------------------------
# refcount-aware admission accounting (the PR 17 bugfix satellite)
# ---------------------------------------------------------------------------

class TestAliasedAdmission:
    def _sched(self, num_blocks=9, block_size=4, watermark=1,
               num_slots=2):
        alloc = BlockAllocator(num_blocks)
        return Scheduler(num_slots, alloc, block_size,
                         watermark_blocks=watermark), alloc

    def test_can_ever_fit_counts_shared_blocks_once(self):
        sched, _ = self._sched(num_blocks=9, watermark=1)  # budget 7
        req = Request("r", list(range(30)), 4)    # peak 9 blocks
        assert not sched.can_ever_fit(req)        # pre-aliasing: refused
        # post-aliasing: 2 blocks ride the shared prefix -> 7 <= 7
        assert sched.can_ever_fit(req, shared_blocks=2)

    def test_try_admit_watermark_counts_aliased_blocks_once(self):
        sched, alloc = self._sched(num_blocks=9, watermark=2)
        cached = alloc.allocate(3)                # the "published prefix"
        req = Request("r", list(range(20)), 2)    # ctx 20 -> 6 blocks
        sched.enqueue(req)

        def hook(r):
            for b in cached:
                alloc.incref(b)
            return list(cached), 12

        got = sched.try_admit(prefix_hook=hook)
        # pre-fix math would want 6 fresh of 5 free and refuse; aliasing
        # needs only 3 fresh, leaving exactly the watermark
        assert got is req
        assert req.blocks[:3] == list(cached) and len(req.blocks) == 6
        assert req.prefix_hit == 12
        assert alloc.num_shared == 3
        assert alloc.num_free == sched.watermark_blocks

    def test_failed_admission_releases_the_hooks_claim(self):
        sched, alloc = self._sched(num_blocks=9, watermark=5)
        cached = alloc.allocate(3)
        sched2 = None  # silence lint about unused
        req = Request("r", list(range(20)), 2)
        sched.enqueue(req)

        def hook(r):
            for b in cached:
                alloc.incref(b)
            return list(cached), 12

        # needs 3 fresh of 5 free, watermark 5: refused -> undo increfs
        assert sched.try_admit(prefix_hook=hook) is None
        assert all(alloc.refcount(b) == 1 for b in cached)
        assert alloc.num_shared == 0
        assert req.blocks == [] and sched.waiting == [req]

    def _enqueue(self, sched, req):
        sched.enqueue(req)
        return req

    def test_try_admit_watermark_hook_path_enqueued(self):
        # same as above but through the normal enqueue/admit flow
        sched, alloc = self._sched(num_blocks=9, watermark=2)
        cached = alloc.allocate(3)
        req = self._enqueue(sched, Request("r", list(range(20)), 2))

        def hook(r):
            for b in cached:
                alloc.incref(b)
            return list(cached), 12

        assert sched.try_admit(prefix_hook=hook) is req
        # eviction decrefs: shared blocks stay resident for the cache
        sched.preempt(req)
        assert all(alloc.refcount(b) == 1 for b in cached)
        assert alloc.num_free == 5                # only the 3 fresh ones


# ---------------------------------------------------------------------------
# shared-prefix serving (compiled path)
# ---------------------------------------------------------------------------

class TestPrefixServing:
    @each_sampler
    def test_shared_prefix_one_prefill_token_identical(self, model,
                                                       reference, sampler):
        """Four streams share a 12-token prefix: ONE prefill total, and
        every stream's output is the reference's (greedy: per-stream
        generate's) — including through the copy-on-write divergence,
        and a seeded stream's draws at the positions it chewed up to."""
        prompts = _shared_prompts(4, prefix_len=12, suffix_len=3)
        engine = LLMEngine(model, max_batch_size=4, block_size=4,
                           num_blocks=64, enable_prefix_cache=True)
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            reqs = [engine.add_request(p, max_new_tokens=8,
                                       **stream_of(sampler, i))
                    for i, p in enumerate(prompts)]
            engine.run()
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        reference.assert_served(reqs)
        if not sampler:
            for p, r in zip(prompts, reqs):
                assert r.generated == _ref(model, p, 8)
        st = engine.stats()
        assert st["prefills"] == 1                # N sharers, one prefill
        assert st["decode_compiles"] == 1
        assert st["prefix_hit_tokens"] > 0
        assert 0.0 < st["prefix_hit_rate"] <= 1.0
        assert st["cow_copies"] >= 1              # tails diverge in-block
        cats = [e["cat"] for e in ev]
        assert "serve.prefix_miss" in cats        # the first, cold stream
        hits = [e for e in ev if e["cat"] == "serve.prefix_hit"]
        assert len(hits) == 3
        assert all(e["reason"] == "prefix_hit" for e in hits)

    @each_sampler
    def test_identical_prompts_full_alias_and_cow(self, model, reference,
                                                  sampler):
        """Bit-identical prompts alias every block (hit = len-1); the
        divergence then happens inside a SHARED block, so parity proves
        copy-on-write actually copies (seeded, the two streams draw
        under seeds of their own and part at once)."""
        p = _prompt(12, seed=11)
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, enable_prefix_cache=True)
        reqs = [engine.add_request(list(p), max_new_tokens=8,
                                   **stream_of(sampler, i))
                for i in range(2)]
        engine.run()
        reference.assert_served(reqs)
        if not sampler:
            ref = _ref(model, p, 8)
            assert reqs[0].generated == ref and reqs[1].generated == ref
        st = engine.stats()
        assert st["prefills"] == 1
        assert st["prefix_hit_tokens"] == len(p) - 1
        assert st["cow_copies"] >= 1

    def test_prefix_survives_across_generate_calls(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, enable_prefix_cache=True)
        p = _prompt(12, seed=12)
        engine.generate([p], max_new_tokens=4)
        assert engine.stats()["prefix_entries"] > 0
        out = engine.generate([list(p)], max_new_tokens=4)[0]
        assert out == _ref(model, p, 4)
        st = engine.stats()
        assert st["prefills"] == 1                # second call aliased
        assert st["decode_compiles"] == 1

    def test_unrelated_prompts_all_miss_and_stay_correct(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, enable_prefix_cache=True)
        prompts = [_prompt(9, seed=13), _prompt(10, seed=14)]
        outs = engine.generate(prompts, max_new_tokens=6)
        for p, o in zip(prompts, outs):
            assert o == _ref(model, p, 6)
        st = engine.stats()
        assert st["prefix_hit_tokens"] == 0
        assert st["prefills"] == 2

    def test_pool_pressure_reclaims_index_leaf_first(self, model):
        """A dry pool evicts cold index entries instead of wedging
        admission; the evictions are attributed."""
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=14, enable_prefix_cache=True)
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            for seed in (21, 22, 23, 24, 25):
                p = _prompt(12, seed=seed)        # 3+ blocks each
                out = engine.generate([p], max_new_tokens=6)[0]
                assert out == _ref(model, p, 6)
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        st = engine.stats()
        assert st["prefix_evictions"] > 0
        assert any(e["cat"] == "serve.prefix_evict" for e in ev)


# ---------------------------------------------------------------------------
# batched adapters (compiled path)
# ---------------------------------------------------------------------------

class TestAdapters:
    @each_sampler
    def test_base_tenant_bit_identical_to_adapter_free(self, model,
                                                       reference, sampler):
        """Slot 0's delta is an exact 0.0 — base tenants on an
        adapter-enabled engine, beside an adapter's tenant, are served
        the adapter-free reference's streams (greedy: generate's), and
        the adapter's tenant the stream of the model with the adapter
        folded in."""
        prompts = [_prompt(9, seed=31), _prompt(7, seed=32),
                   _prompt(8, seed=33)]
        engine = LLMEngine(model, max_batch_size=3, block_size=4,
                           num_blocks=64, max_adapters=2, adapter_rank=2)
        engine.register_adapter("tenant-a", seed=3, scale=25.0)
        reqs = [engine.add_request(p, max_new_tokens=8, adapter=ad,
                                   **stream_of(sampler, i))
                for i, (p, ad) in enumerate(zip(
                    prompts, (None, None, "tenant-a")))]
        engine.run()
        _assert_tenants_served(reference, engine, reqs)
        if not sampler:
            for p, r in zip(prompts[:2], reqs):
                assert r.generated == _ref(model, p, 8)
        base = reference.logits(prompts[2])
        with engine._adapters.merged("tenant-a"):     # the delta bites
            assert np.abs(reference.logits(prompts[2]) - base).max() > 1e-2
        assert engine.stats()["decode_compiles"] == 1

    def test_adapter_changes_output_deterministically(self, model):
        p = _prompt(9, seed=33)
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, max_adapters=2, adapter_rank=2)
        engine.register_adapter("tenant-a", seed=3, scale=25.0)
        runs = []
        for i in range(2):
            engine.add_request(p, max_new_tokens=6, request_id=f"a{i}",
                               adapter="tenant-a")
            engine.run()
            runs.append(engine.pop_finished()[f"a{i}"].generated)
        assert runs[0] != _ref(model, p, 6)       # the delta bites
        assert runs[0] == runs[1]                 # and is deterministic

    def test_tenant_churn_zero_recompiles(self, model, reference):
        """Tenants joining/leaving only edit stack VALUES and slot
        indices: the decode executable compiles exactly once."""
        prompts = _shared_prompts(6, prefix_len=8, suffix_len=2, seed=40)
        engine = LLMEngine(model, max_batch_size=3, block_size=4,
                           num_blocks=64, max_adapters=3, adapter_rank=2)
        engine.register_adapter("t1", seed=1, scale=25.0)
        engine.register_adapter("t2", seed=2, scale=25.0)
        plan = ["t1", None, "t2", "t1", "t2", None]
        for i, (p, ad) in enumerate(zip(prompts, plan)):
            engine.add_request(p, max_new_tokens=5, request_id=f"c{i}",
                               adapter=ad)
        engine.run()
        done = engine.pop_finished()
        base2 = _ref(model, prompts[1], 5)
        base5 = _ref(model, prompts[5], 5)
        assert done["c1"].generated == base2
        assert done["c5"].generated == base5
        _assert_tenants_served(reference, engine, done.values())
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["adapter_switches"] >= 2
        assert sorted(st["adapters"]) == ["t1", "t2"]
        # churn: t2 leaves, t3 joins — still zero recompiles
        engine.unregister_adapter("t2")
        engine.register_adapter("t3", seed=9, scale=25.0)
        engine.add_request(prompts[0], max_new_tokens=5,
                           request_id="c9", adapter="t3")
        engine.run()
        assert engine.pop_finished()["c9"].state == FINISHED
        assert engine.stats()["decode_compiles"] == 1

    def test_unknown_adapter_refused_as_adapter_mismatch(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, max_adapters=2)
        with pytest.raises(ServeRefusal) as ei:
            engine.add_request(_prompt(5, seed=34), max_new_tokens=4,
                               adapter="nobody")
        assert ei.value.reason == "adapter_mismatch"
        # an adapter-free engine refuses EVERY adapter request
        plain = LLMEngine(model, max_batch_size=2, block_size=4,
                          num_blocks=64)
        with pytest.raises(ServeRefusal) as ei:
            plain.add_request(_prompt(5, seed=34), max_new_tokens=4,
                              adapter="anyone")
        assert ei.value.reason == "adapter_mismatch"

    def test_unregister_refuses_while_streams_live(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, max_adapters=2)
        engine.register_adapter("busy", seed=5)
        engine.add_request(_prompt(6, seed=35), max_new_tokens=4,
                           request_id="live", adapter="busy")
        with pytest.raises(ValueError, match="live"):
            engine.unregister_adapter("busy")
        engine.run()                              # drain
        assert engine.unregister_adapter("busy") >= 1

    def test_registry_validation(self, model):
        ad = AdapterSet(model, max_adapters=2, rank=2)
        ad.register("x", seed=1)
        with pytest.raises(ValueError, match="already registered"):
            ad.register("x", seed=2)
        ad.register("y", seed=2)
        with pytest.raises(ValueError, match="slots"):
            ad.register("z", seed=3)
        ad.unregister("y")
        with pytest.raises(KeyError):
            ad.slot_of("y")
        assert ad.slot_of(None) == 0              # base is always slot 0
        L = model.config.num_hidden_layers
        bad = {t: (np.zeros((L, 1, 1)), np.zeros((L, 1, 1)))
               for t in ("qkv", "out")}
        with pytest.raises(ValueError, match="want A"):
            ad.register("bad", weights=bad)
        with pytest.raises(ValueError):
            AdapterSet(model, max_adapters=0, rank=2)

    def test_merged_fallback_context_restores_weights(self, model):
        """The eager-fallback merge (W + A@B*scale) changes generate
        under the context and restores the base weights bit-for-bit on
        exit — the degraded-mode contract."""
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, max_adapters=2, adapter_rank=2)
        engine.register_adapter("m", seed=6, scale=25.0)
        p = _prompt(8, seed=36)
        base = _gen(model, p, 6)
        with engine._adapters.merged("m"):
            merged = _gen(model, p, 6)
        assert merged != base
        assert _gen(model, p, 6) == base          # restored exactly


# ---------------------------------------------------------------------------
# live weight hot-swap (compiled path; fresh models — swap mutates them)
# ---------------------------------------------------------------------------

class TestTenantPrefillLaunchedNotAwaited:
    @pytest.mark.parametrize("sampler", [SAMPLERS[0], SAMPLERS[4]],
                             ids=["greedy", "penalty"])
    def test_joined_tenants_first_tokens_ride_the_device(self, model,
                                                         reference,
                                                         sampler):
        """The tenant prefill program hands its token on as the plain one
        does: with the buckets warm, tenants joining a running batch two
        at a boundary are launched over unawaited, and are served the
        reference's streams (a base tenant greedy: what `generate`
        does)."""
        prompts = [_prompt(n, seed=60 + n) for n in (6, 11, 7, 10, 5, 12)]
        plan = [None, "t1", "t2", None, "t1", None]
        engine = LLMEngine(model, max_batch_size=3, block_size=4,
                           num_blocks=64, max_adapters=3,
                           adapter_rank=2, hot_swap=True)
        engine.register_adapter("t1", seed=1, scale=25.0)
        engine.register_adapter("t2", seed=2, scale=25.0)
        engine.generate([_prompt(5, seed=59), _prompt(9, seed=59)],
                        max_new_tokens=2)        # buckets 8, 16
        engine.reset_stats()
        reqs = []
        for i in range(0, len(prompts), 2):
            for p, ad in zip(prompts[i:i + 2], plan[i:i + 2]):
                reqs.append(engine.add_request(
                    p, max_new_tokens=4 + i, adapter=ad,
                    **stream_of(sampler, len(reqs))))
            engine.step()
            engine.step()
        engine.run()
        st = engine.stats()
        assert st["decode_compiles"] == 0 == st["prefill_compiles"]
        assert st["commit_rollbacks"] == 0
        assert st["prefill_unawaited_share"] == 1.0
        _assert_tenants_served(reference, engine, reqs)
        if not sampler:
            for i in (0, 3, 5):
                assert reqs[i].generated == _ref(model, prompts[i],
                                                 4 + 2 * (i // 2))


class TestHotSwap:
    def test_swap_between_steps_byte_exact_zero_recompiles(self):
        m1 = _make_model(seed=0)
        m2 = _make_model(seed=1)
        w2 = [np.asarray(p._value) for p in m2.parameters()]
        p = _prompt(9, seed=51)
        ref1 = _gen(m1, p, 6)
        engine = LLMEngine(m1, max_batch_size=2, block_size=4,
                           num_blocks=64, hot_swap=True)
        assert engine.generate([p], max_new_tokens=6)[0] == ref1
        assert engine.weight_epoch == 0
        epoch = engine.swap_weights(w2)
        assert epoch == 1
        out2 = engine.generate([list(p)], max_new_tokens=6)[0]
        assert out2 == _gen(m2, p, 6)             # serving m2's function
        st = engine.stats()
        assert st["decode_compiles"] == 1         # across the swap
        assert st["weight_swaps"] == 1
        assert st["weight_epoch"] == 1

    @each_sampler
    def test_mid_run_swap_cutover_boundary_is_exact(self, sampler):
        """Streams in flight at the cutover finish as: every token
        emitted before the swap is exactly the OLD weights' token,
        every token after is exactly the NEW weights' continuation of
        (prompt + old tokens) — never a half-epoch token. A seeded
        stream draws on at the positions it had reached."""
        m1 = _make_model(seed=0)
        m2 = _make_model(seed=1)
        w2 = [np.asarray(p._value) for p in m2.parameters()]
        prompts = [_prompt(8, seed=52), _prompt(10, seed=53)]
        cfgs = [stream_of(sampler, i) for i in range(2)]
        refs1 = [Reference(m1).serve(p, 10, **c)[0]
                 for p, c in zip(prompts, cfgs)]
        if not sampler:
            assert refs1 == [_gen(m1, p, 10) for p in prompts]
        engine = LLMEngine(m1, max_batch_size=2, block_size=4,
                           num_blocks=64, hot_swap=True)
        reqs = [engine.add_request(p, max_new_tokens=10,
                                   request_id=f"w{i}", **c)
                for i, (p, c) in enumerate(zip(prompts, cfgs))]
        for _ in range(4):
            engine.step()
        engine.swap_weights(w2)                   # boundary: commits now
        marks = [len(r.generated) for r in reqs]
        assert any(0 < k < 10 for k in marks)     # genuinely mid-flight
        engine.run()
        new = Reference(m2)
        for r, p, c, ref1, k in zip(reqs, prompts, cfgs, refs1, marks):
            assert r.generated[:k] == ref1[:k]
            cont, _ = new.serve(p + ref1[:k], 10 - k, **c)
            assert r.generated[k:] == cont
            if not sampler:
                assert cont == _gen(m2, p + ref1[:k], 10 - k)
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["weight_swaps"] == 1
        # the cutover is a PLANNED preemption, not kv pressure: the
        # in-flight streams re-prefilled, yet nothing was "evicted"
        assert any(r.preemptions >= 1 for r in reqs)
        assert st["evictions"] == 0

    def test_stage_identical_weights_is_a_skipped_noop(self):
        m1 = _make_model(seed=0)
        engine = LLMEngine(m1, max_batch_size=2, block_size=4,
                           num_blocks=64, hot_swap=True)
        same = [np.asarray(p._value) for p in m1.parameters()]
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            assert engine.stage_weights(same) is False
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        assert engine.weight_epoch == 0
        assert engine.stats()["weight_swaps"] == 0
        skip = [e for e in ev if e["cat"] == "serve.swap"]
        assert skip and skip[0]["detail"]["skipped"]

    def test_swap_requires_hot_swap_engine(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64)
        with pytest.raises(ValueError, match="hot_swap"):
            engine.stage_weights([])

    def test_swap_invalidates_prefix_index(self):
        """Cached KV is a function of the base weights: the index is
        emptied at the cutover and the post-swap stream re-prefills
        (and is correct) under the new weights."""
        m1 = _make_model(seed=0)
        m2 = _make_model(seed=1)
        w2 = [np.asarray(p._value) for p in m2.parameters()]
        p = _prompt(12, seed=54)
        engine = LLMEngine(m1, max_batch_size=2, block_size=4,
                           num_blocks=64, hot_swap=True,
                           enable_prefix_cache=True)
        engine.generate([p], max_new_tokens=4)
        assert engine.stats()["prefix_entries"] > 0
        engine.swap_weights(w2)
        assert engine.stats()["prefix_entries"] == 0
        out = engine.generate([list(p)], max_new_tokens=4)[0]
        assert out == _gen(m2, p, 4)
        assert engine.stats()["prefills"] == 2    # no stale-KV alias


# ---------------------------------------------------------------------------
# crash-resume under tenancy
# ---------------------------------------------------------------------------

class TestTenantCrashResume:
    def test_snapshot_roundtrips_adapter_assignment(self, model,
                                                    reference):
        """A mid-flight snapshot carries each stream's adapter; the
        restored engine finishes them under the SAME adapter,
        token-identically to an uninterrupted run (the reference's,
        with the adapter folded into the weights)."""
        p1, p2 = _prompt(8, seed=61), _prompt(7, seed=62)

        def build():
            e = LLMEngine(model, max_batch_size=2, block_size=4,
                          num_blocks=64, max_adapters=2, adapter_rank=2)
            e.register_adapter("tt", seed=8, scale=25.0)
            return e

        half = build()
        half.add_request(p1, max_new_tokens=8, request_id="u1",
                         adapter="tt")
        half.add_request(p2, max_new_tokens=8, request_id="u2")
        for _ in range(4):
            half.step()
        payload = half.state_payload()
        assert any(rp["adapter"] == "tt"
                   for rp in payload["requests"])
        fresh = build()
        restored = fresh.restore_state(payload)
        fresh.run()
        by_rid = {r.rid: r for r in restored}
        assert sorted(by_rid) == ["u1", "u2"]
        assert all(r.state == FINISHED for r in restored)
        _assert_tenants_served(reference, fresh, restored)
        assert by_rid["u1"].generated != _ref(model, p1, 8)  # adapter live
        assert by_rid["u2"].generated == _ref(model, p2, 8)

    def test_restore_refuses_unregistered_adapter(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=64, max_adapters=2)
        engine.register_adapter("gone", seed=9)
        engine.add_request(_prompt(6, seed=63), max_new_tokens=4,
                           request_id="g", adapter="gone")
        payload = engine.state_payload()
        bare = LLMEngine(model, max_batch_size=2, block_size=4,
                         num_blocks=64, max_adapters=2)
        with pytest.raises(ServeRefusal) as ei:
            bare.restore_state(payload)
        assert ei.value.reason == "adapter_mismatch"

    def test_restore_refuses_torn_swap(self):
        """A snapshot taken under one weight set refuses to restore in
        an engine serving another — the supervisor must load the
        matching weights first (tools/chaos.py tenant_swap drills the
        full kill/restart path)."""
        m1 = _make_model(seed=0)
        m_other = _make_model(seed=1)
        engine = LLMEngine(m1, max_batch_size=2, block_size=4,
                           num_blocks=64, hot_swap=True)
        engine.add_request(_prompt(6, seed=64), max_new_tokens=4,
                           request_id="t")
        payload = engine.state_payload()
        assert payload["weights_crc"] is not None
        torn = LLMEngine(m_other, max_batch_size=2, block_size=4,
                         num_blocks=64, hot_swap=True)
        with pytest.raises(ServeRefusal) as ei:
            torn.restore_state(payload)
        assert ei.value.reason == "torn_swap"
        # loading the matching weight set unblocks the restore
        w1 = [np.asarray(p._value) for p in m1.parameters()]
        torn.swap_weights(w1)
        [req] = torn.restore_state(payload)
        torn.run()
        assert req.state == FINISHED
        assert req.generated == _gen(m1, _prompt(6, seed=64), 4)


# ---------------------------------------------------------------------------
# everything at once (the acceptance shape, scaled down)
# ---------------------------------------------------------------------------

class TestCombined:
    def test_prefix_adapters_swap_one_executable(self):
        """Scaled-down ISSUE 17 acceptance: streams over mixed tenants
        with a shared prefix, a mid-run weight swap — ONE decode
        compile through all of it."""
        m1 = _make_model(seed=0)
        m2 = _make_model(seed=1)
        w2 = [np.asarray(p._value) for p in m2.parameters()]
        engine = LLMEngine(m1, max_batch_size=4, block_size=4,
                           num_blocks=96, enable_prefix_cache=True,
                           max_adapters=3, adapter_rank=2, hot_swap=True)
        engine.register_adapter("a1", seed=1, scale=25.0)
        engine.register_adapter("a2", seed=2, scale=25.0)
        prompts = _shared_prompts(8, prefix_len=12, suffix_len=2,
                                  seed=70)
        plan = ["a1", None, "a2", "a1", None, "a2", "a1", None]
        for i, (p, ad) in enumerate(zip(prompts, plan)):
            engine.add_request(p, max_new_tokens=6, request_id=f"x{i}",
                               adapter=ad)
        for _ in range(3):
            engine.step()
        engine.swap_weights(w2)                   # mid-run cutover
        engine.run()
        done = engine.pop_finished()
        assert len(done) == 8
        assert all(r.state == FINISHED for r in done.values())
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["prefix_hit_tokens"] > 0
        assert st["adapter_switches"] >= 1
        assert st["weight_swaps"] == 1
