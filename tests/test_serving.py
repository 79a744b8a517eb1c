"""Serving engine suite (paddle_tpu/serving): continuous batching, paged
KV cache, ONE compiled decode step.

The contracts pinned here are the ISSUE 6 acceptance criteria:

  * decode output is token-identical to `model.generate(do_sample=False)`
    for every stream, whatever the batch composition;
  * a stream already running keeps producing ITS tokens bit-for-bit when
    other requests join or leave mid-flight (iteration-level batching
    must not perturb neighbors);
  * preemption (KV pool dry -> evict -> re-prefill -> resume) is
    token-equivalent to never having been preempted;
  * a request whose peak KV footprint can never fit is refused at
    admission (attributed `kv_exhausted`), not deadlocked;
  * the decode executable compiles exactly ONCE while 64 mixed-length
    streams churn through the slots (zero retraces).

The scheduler tests are pure host-side policy checks (no jax work).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import time

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops import guardian
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events
from paddle_tpu.profiler.explain import explain
from paddle_tpu.serving import (BlockAllocator, LLMEngine, Request,
                                Scheduler, ServeRefusal, NULL_BLOCK,
                                QUEUED, RUNNING, FINISHED, FAILED,
                                CANCELLED, EXPIRED)

import serving_backlog as backlog
from serving_reference import Reference, each_sampler, stream_of

VOCAB = 128


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def reference(model):
    """What the engine owes a request, worked out without an engine."""
    return Reference(model)


def _prompt(length, seed=0):
    rng = np.random.default_rng(seed * 1000 + length)
    return rng.integers(0, VOCAB, length).tolist()


_REF_CACHE = {}


def _ref(model, prompt, n):
    """Greedy reference through model.generate (ONE XLA scan program per
    prompt length — memoized so the module compiles each length once)."""
    key = (tuple(prompt), n)
    if key not in _REF_CACHE:
        out = model.generate(paddle.Tensor(np.asarray([prompt], np.int64)),
                             max_new_tokens=n, do_sample=False)
        arr = out._value if hasattr(out, "_value") else out
        _REF_CACHE[key] = np.asarray(arr)[0].tolist()
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# scheduler policy (pure host-side, no jax)
# ---------------------------------------------------------------------------

class TestSchedulerPolicy:
    def _sched(self, num_slots=2, num_blocks=9, block_size=4,
               watermark=1):
        alloc = BlockAllocator(num_blocks)
        return Scheduler(num_slots, alloc, block_size,
                         watermark_blocks=watermark), alloc

    def test_allocator_all_or_nothing_and_null_guard(self):
        alloc = BlockAllocator(4)
        assert alloc.capacity == 3
        assert alloc.allocate(4) is None          # more than free: nothing
        got = alloc.allocate(3)
        assert len(got) == 3 and NULL_BLOCK not in got
        with pytest.raises(ValueError):
            alloc.free([NULL_BLOCK])
        alloc.free(got)
        assert alloc.num_free == 3

    def test_fcfs_head_only_no_skipping(self):
        sched, _ = self._sched(num_slots=2, num_blocks=9, watermark=0)
        big = Request("big", list(range(20)), 4)     # needs 6 blocks
        small = Request("small", [1], 2)             # needs 1 block
        sched.enqueue(big)
        sched.enqueue(small)
        # head needs 6 of 8 free; admit it, then the pool can't take the
        # NEXT head... admit everything that fits in arrival order only
        first = sched.try_admit()
        assert first is big                           # strict FCFS
        second = sched.try_admit()
        assert second is small

    def test_watermark_blocks_admission(self):
        sched, alloc = self._sched(num_slots=2, num_blocks=9, watermark=2)
        # 8 allocatable; a 20-token context needs 6 blocks -> 2 left ==
        # watermark: OK. A second 4-token request (2 blocks) would leave
        # 0 < watermark: refused for now (stays QUEUED, not failed)
        a = Request("a", list(range(20)), 2)
        b = Request("b", [1, 2, 3, 4], 2)
        sched.enqueue(a)
        sched.enqueue(b)
        assert sched.try_admit() is a
        assert sched.try_admit() is None
        assert b.state == QUEUED
        assert sched.waiting == [b]

    def test_growth_dips_into_watermark(self):
        sched, alloc = self._sched(num_slots=1, num_blocks=4, watermark=2)
        r = Request("r", [1, 2, 3], 8)
        sched.enqueue(r)
        assert sched.try_admit() is r                 # 1 block, 2 free left
        assert sched.grow(r) and sched.grow(r)        # growth ignores mark
        assert alloc.num_free == 0

    def test_preempt_victim_is_lifo_and_requeue_keeps_arrival_order(self):
        sched, _ = self._sched(num_slots=3, num_blocks=20, watermark=0)
        reqs = [Request(f"r{i}", [1, 2], 4) for i in range(3)]
        for r in reqs:
            sched.enqueue(r)
        for _ in range(3):
            assert sched.try_admit() is not None
        victim = sched.preempt_victim()
        assert victim is reqs[2]                      # newest admission
        sched.preempt(victim)
        assert victim.state == QUEUED and victim.blocks == []
        assert victim.preemptions == 1
        late = Request("late", [1], 2)
        sched.enqueue(late)
        # the preempted request resumes BEFORE later arrivals
        assert sched.waiting.index(victim) < sched.waiting.index(late)

    def test_release_returns_blocks_and_slot(self):
        sched, alloc = self._sched(num_slots=1, num_blocks=9, watermark=0)
        r = Request("r", list(range(6)), 2)
        sched.enqueue(r)
        sched.try_admit()
        held = list(r.blocks)
        assert held
        sched.release(r)
        assert alloc.num_free == 8 and sched.slots == [None]

    def test_can_ever_fit_respects_watermark(self):
        sched, _ = self._sched(num_slots=1, num_blocks=4, block_size=4,
                               watermark=0)
        assert sched.block_budget() == 3
        assert sched.can_ever_fit(Request("ok", [1] * 8, 4))      # 3 blocks
        assert not sched.can_ever_fit(Request("big", [1] * 8, 20))
        # the watermark reserve is never granted: a request needing the
        # WHOLE pool can never be admitted once a reserve exists
        sched2, _ = self._sched(num_slots=1, num_blocks=4, block_size=4,
                                watermark=1)
        assert not sched2.can_ever_fit(Request("ok", [1] * 8, 4))


# ---------------------------------------------------------------------------
# engine: parity / continuity / preemption / refusal / zero-retrace
# ---------------------------------------------------------------------------

class TestDecodeParity:
    @each_sampler
    def test_mixed_length_batch_serves_the_reference(self, model, reference,
                                                     sampler):
        """Every stream of a mixed-length batch is what one request at a
        time through the dense forward is owed (greedy: `generate`'s)."""
        prompts = [_prompt(n) for n in (11, 5, 17, 3)]
        engine = LLMEngine(model, max_batch_size=4, block_size=4)
        reqs = [engine.add_request(p, max_new_tokens=10,
                                   **stream_of(sampler, i))
                for i, p in enumerate(prompts)]
        engine.run()
        reference.assert_served(reqs)
        if not sampler:
            assert [r.generated for r in reqs] \
                == [_ref(model, p, 10) for p in prompts]
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["completed"] == 4
        assert st["sampled_tokens"] == (40 if sampler else 0)

    @each_sampler
    def test_eos_stops_a_stream_early(self, model, reference, sampler):
        p = _prompt(7)
        ref, _ = reference.serve(p, 12, **sampler)
        eos = ref[4]                       # force a stop mid-stream
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        req = engine.add_request(p, max_new_tokens=12, eos_token_id=eos,
                                 **sampler)
        engine.run()
        assert req.state == FINISHED
        # stop at the FIRST occurrence (a tiny model may repeat tokens)
        stop = ref.index(eos)
        assert req.generated == ref[:stop + 1]
        assert len(req.generated) < 12

    def test_streaming_callbacks_fire_per_token(self, model):
        p = _prompt(9)
        ref = _ref(model, p, 8)
        seen = []
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.add_request(p, max_new_tokens=8,
                           on_token=lambda r, tok, text: seen.append(tok))
        engine.run()
        assert seen == ref                 # streamed in generation order


    def test_the_second_loops_option_is_gone(self, model):
        """The option that chose the serial loop went with it (PR 46): an
        unknown keyword, like any other; no shim, no warning path. (The
        name is spelt in two halves so that a search of the tree for it
        finds the records alone.)"""
        option = "pipeline" + "_decode"
        with pytest.raises(TypeError, match=option):
            LLMEngine(model, **{option: False})
        assert not hasattr(LLMEngine, "_decode_step")


class TestContinuousBatching:
    @each_sampler
    def test_join_mid_flight_keeps_running_stream_bitwise(
            self, model, reference, sampler):
        """A request joining the batch must not perturb a stream that is
        already decoding: same tokens as a solo run, bit for bit."""
        pa, pb = _prompt(13, seed=1), _prompt(6, seed=2)
        ref_a, _ = reference.serve(pa, 12, **stream_of(sampler, 0))
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        ra = engine.add_request(pa, max_new_tokens=12,
                                **stream_of(sampler, 0))
        for _ in range(5):                 # a is mid-flight...
            engine.step()
        tokens_before = list(ra.generated)
        assert 0 < len(tokens_before) < 12
        assert tokens_before == ref_a[:len(tokens_before)]
        rb = engine.add_request(pb, max_new_tokens=8,   # ...b joins
                                **stream_of(sampler, 1))
        engine.run()
        reference.assert_served([ra, rb])  # a never noticed
        if not sampler:
            assert ra.generated == _ref(model, pa, 12)
            assert rb.generated == _ref(model, pb, 8)
        assert engine.stats()["decode_compiles"] == 1

    @each_sampler
    def test_departure_mid_flight_keeps_survivors_bitwise(
            self, model, reference, sampler):
        """Short streams finishing and leaving slots must not perturb the
        longer streams still running."""
        long_p, short_p = _prompt(10, seed=3), _prompt(4, seed=4)
        engine = LLMEngine(model, max_batch_size=3, block_size=4)
        rl = engine.add_request(long_p, max_new_tokens=14,
                                **stream_of(sampler, 0))
        rs = engine.add_request(short_p, max_new_tokens=2,
                                **stream_of(sampler, 1))
        engine.run()
        assert rs.state == FINISHED and len(rs.generated) == 2
        reference.assert_served([rl, rs])
        if not sampler:
            assert rl.generated == _ref(model, long_p, 14)

    @each_sampler
    def test_preempt_resume_token_equivalence(self, model, reference,
                                              sampler):
        """A deliberately tight pool forces eviction; the evicted stream
        re-prefills from its block-table-less state and must still match
        the never-preempted reference: a seeded stream's draws replay
        from the restored positions, they are not rolled again."""
        prompts = [_prompt(n, seed=5) for n in (11, 12, 10, 5)]
        engine = LLMEngine(model, max_batch_size=3, block_size=4,
                           num_blocks=10, watermark_blocks=1)
        reqs = [engine.add_request(p, max_new_tokens=10,
                                   **stream_of(sampler, i))
                for i, p in enumerate(prompts)]
        engine.run()
        st = engine.stats()
        assert st["evictions"] >= 1        # the tight pool actually bit
        reference.assert_served(reqs)
        if not sampler:
            assert [r.generated for r in reqs] \
                == [_ref(model, p, 10) for p in prompts]
        assert st["decode_compiles"] == 1  # eviction is a table edit
        assert any(r.preemptions for r in reqs)

    def test_kv_exhaustion_admission_refusal(self, model):
        """A request whose PEAK footprint exceeds the pool budget can
        never be served: refuse at admission instead of deadlocking the
        queue."""
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=6, watermark_blocks=1)
        with pytest.raises(ValueError, match="KV blocks at peak"):
            engine.add_request(_prompt(20), max_new_tokens=20)
        assert engine.stats()["refused"] == 1
        # a request that merely can't fit RIGHT NOW queues instead
        ok = engine.add_request(_prompt(4), max_new_tokens=4)
        assert ok.state == QUEUED

    def test_context_overflow_refused(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        with pytest.raises(ValueError, match="exceeds max_context"):
            engine.add_request(_prompt(40), max_new_tokens=60)
        with pytest.raises(ValueError, match="empty prompt"):
            engine.add_request([], max_new_tokens=4)

    def test_duplicate_active_request_id_refused(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.add_request(_prompt(5), max_new_tokens=4, request_id="x")
        with pytest.raises(ValueError, match="already queued/running"):
            engine.add_request(_prompt(6), max_new_tokens=4,
                               request_id="x")
        engine.run()
        # finished ids may be reused (the old handle is replaced)
        again = engine.add_request(_prompt(6), max_new_tokens=4,
                                   request_id="x")
        engine.run()
        assert again.state == FINISHED


class TestZeroRetrace:
    def test_64_mixed_streams_one_decode_compile(self, model):
        """The acceptance criterion: 64 concurrent mixed-length requests
        churning through 8 slots, ONE decode trace, every stream
        token-identical to generate()."""
        lengths = (3, 5, 8, 11, 16, 21)
        uniques = {n: _prompt(n, seed=7) for n in lengths}
        refs = {n: _ref(model, p, 6) for n, p in uniques.items()}
        prompts = [uniques[lengths[i % len(lengths)]] for i in range(64)]
        engine = LLMEngine(model, max_batch_size=8, block_size=4)
        outs = engine.generate(prompts, max_new_tokens=6)
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["completed"] == 64
        # prefill buckets are the pow-2 cover of the lengths, compiled
        # once each — admission never touches the decode program
        assert st["prefill_compiles"] <= 5
        for i, out in enumerate(outs):
            assert out == refs[lengths[i % len(lengths)]], f"stream {i}"

    def test_churn_occupancy_saturated(self, model):
        """Under
        saturation (demand >= slots) continuous batching must keep the
        slots >= 75% full, and the decode program must not retrace."""
        prompts = [_prompt(3 + (i % 9), seed=8) for i in range(24)]
        engine = LLMEngine(model, max_batch_size=4, block_size=4)
        engine.generate(prompts, max_new_tokens=5)
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["occupancy_saturated"] >= 0.75

    def test_reset_stats_opens_a_clean_window(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.generate([_prompt(5)], max_new_tokens=3)   # warmup
        engine.reset_stats()
        engine.generate([_prompt(5)], max_new_tokens=3)
        st = engine.stats()
        assert st["decode_compiles"] == 0       # no retrace in the window
        assert st["completed"] == 1


class TestBacklog:
    """The benchmark's backlog cell in small (`serving_backlog.py`): every
    slot full, requests of mixed prompt buckets finishing and joining at
    every boundary."""

    STEPS = 48

    def test_every_stream_matches_generate(self, model):
        requests, boundaries, engine = backlog.drive(
            model, VOCAB, self.STEPS)
        backlog.assert_steady(boundaries)
        for i, r in enumerate(requests):
            assert r.generated == _ref(model, r.prompt, r.max_new_tokens), \
                f"stream {i}"
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["commit_rollbacks"] == 0
        assert st["prefill_compiles"] == 3         # buckets 8, 16, 32

    def test_a_joined_slot_decodes_from_its_prefill_token(self, model):
        """The pipelined launch feeds a slot from the device unless the
        host wrote the slot's token. A slot that changed tenants is fed
        the NEW tenant's prefill token, which the host has not seen when
        the launch is dispatched (a bucket's first call excepted: that
        one was committed where it stood, and is the host's): never what
        the launch before sampled there for the tenant that left."""
        tenants, joined, fed, awaited = {}, [], [], []

        def on_launch(engine, args):
            slots, feedback = args[:2]
            tokens, override, _, active = slots[
                :, engine.max_blocks_per_seq:].T
            for req in engine.scheduler.running:
                slot = req.slot
                if not active[slot]:
                    continue
                if tenants.get(slot) is req:
                    # a stream in flight: its last token is on the device
                    assert not override[slot]
                    assert not isinstance(feedback, np.ndarray)
                    fed.append(slot)
                    continue
                tenants[slot] = req
                if override[slot]:
                    assert req.generated == [int(tokens[slot])]
                    awaited.append(slot)
                else:
                    assert req.generated == []
                    joined.append((req, slot,
                                   int(np.asarray(feedback)[slot])))

        requests, _, engine = backlog.drive(
            model, VOCAB, self.STEPS, on_launch=on_launch)
        assert len(awaited) == 3                   # buckets 8, 16, 32
        assert len(joined) + len(awaited) == len(requests) >= 80
        assert {slot for _, slot, _ in joined} == set(range(backlog.SLOTS))
        assert all(r.generated[0] == tok for r, _, tok in joined)
        assert len(fed) >= len(joined)
        for r in requests[:24]:
            assert r.generated == _ref(model, r.prompt, r.max_new_tokens)
        st = engine.stats()
        assert st["prefill_unawaited_share"] == len(joined) / len(requests)
        assert st["commit_rollbacks"] == 0

    def test_launches_overlap_the_commit_before_them(self, model):
        """`pipelined_launch_share`: launches issued while the launch
        before them was uncommitted, over all launches of the window."""
        engine = LLMEngine(model, max_batch_size=backlog.SLOTS,
                           block_size=4)
        requests = []

        def steps(n):
            for _ in range(n):
                backlog.top_up(engine, requests, VOCAB)
                engine.step()

        steps(4)                           # the first launch has none before
        assert engine.stats()["pipelined_launch_share"] == 0.75
        engine.reset_stats()
        assert engine.stats()["pipelined_launch_share"] == 0.0
        assert engine._stats.launches == 0
        steps(12)
        st = engine.stats()
        assert st["pipelined_launch_share"] == 1.0
        assert engine._stats.launches_overlapped == st["steps"] == 12
        engine.run()
        assert engine.stats()["commit_rollbacks"] == 0


class _NumpySpy:
    """numpy, as the engine's module sees it, telling `on_fetch` of every
    `asarray`: the one call by which the engine brings a device array to
    the host."""

    def __init__(self, on_fetch):
        self._on_fetch = on_fetch

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        self._on_fetch(a)
        return np.asarray(a, *args, **kw)


class TestPrefillLaunchedNotAwaited:
    """A prefill is dispatched and not awaited: its token feeds the
    decode launch of the admitting step on the device and is committed a
    step later, from one fetch for all of a boundary's prefills."""

    PENALISED = (dict(),
                 dict(temperature=0.8, top_k=6, repetition_penalty=4.0,
                      seed=900),
                 dict(repetition_penalty=1.5),
                 dict(temperature=1.1, top_p=0.9, repetition_penalty=2.5,
                      seed=901))

    @pytest.mark.parametrize("mix", ["greedy", "seeded"])
    def test_staggered_admission_serves_the_same_streams(self, model,
                                                         reference, mix):
        """Two to four prefills at every boundary: the engine, the
        reference and (greedy) `model.generate` agree token for token,
        under a repetition penalty too, whose history must hold the
        first token by the second launch over the slot."""
        samplers = (dict(),) if mix == "greedy" else self.PENALISED
        requests, boundaries, eng = backlog.drive(
            model, VOCAB, 40, samplers=samplers)
        backlog.assert_steady(boundaries, at_least=32)
        assert all(2 <= joined <= 4 for joined, _, _ in boundaries[4:])
        st = eng.stats()
        assert st["commit_rollbacks"] == 0
        assert st["decode_compiles"] == 1
        assert st["prefill_compiles"] == 3
        assert st["prefill_unawaited_share"] > 0.9
        assert len(requests) >= 80
        reference.assert_served(requests)
        for r in requests:
            if r.temperature == 0:
                assert r.generated == _ref(model, r.prompt,
                                           r.max_new_tokens)
        if mix == "seeded":
            assert sum(r.temperature > 0 for r in requests) >= 40

    @staticmethod
    def _warm(model, slots):
        """An engine whose prefill buckets 8 and 16 and decode program
        have run, and a neighbour stream that keeps its launches going."""
        engine = LLMEngine(model, max_batch_size=slots, block_size=4)
        engine.generate([_prompt(5, seed=80), _prompt(9, seed=80)],
                        max_new_tokens=2)
        engine.reset_stats()
        return engine

    @pytest.mark.parametrize("case", [
        "first_token_eos", "max_new_tokens_1", "max_new_tokens_1_alone",
        "cancel", "expiry", "preempt_resume", "refilled_slot"])
    def test_a_stream_that_leaves_before_its_first_commit(self, model, case):
        """Between a prefill's dispatch and the commit of its token lies
        a decode launch that fed on it. Whatever takes the stream away in
        between costs it that one speculative token (and, where the first
        token was never delivered, that one too); nobody else's stream
        moves."""
        slots = 1 if case in ("refilled_slot",
                              "max_new_tokens_1_alone") else 3
        engine = self._warm(model, slots)
        near = _prompt(10, seed=81)
        neighbour = None
        if slots > 1:
            neighbour = engine.add_request(near, max_new_tokens=9)
            engine.step()
        prompt = _prompt(7, seed=82)
        ref = _ref(model, prompt, 6)
        kw = {"first_token_eos": dict(max_new_tokens=6,
                                      eos_token_id=ref[0]),
              "max_new_tokens_1": dict(max_new_tokens=1),
              "max_new_tokens_1_alone": dict(max_new_tokens=1),
              "expiry": dict(max_new_tokens=6, ttl_s=600.0)}.get(
                  case, dict(max_new_tokens=6))
        req = engine.add_request(prompt, **kw)
        engine.step()                 # admitted, launched over, uncommitted
        if case == "max_new_tokens_1_alone":
            # nothing could be launched behind it: committed on the spot
            assert req.state == FINISHED
        else:
            assert req.state == RUNNING and req.generated == []
        want, rollbacks, state = [], 2, None
        if case == "first_token_eos":
            want, rollbacks, state = ref[:1], 1, FINISHED
        elif case.startswith("max_new_tokens_1"):
            # its slot was held and not launched: no token to lose
            want, rollbacks, state = ref[:1], 0, FINISHED
        elif case == "cancel":
            assert engine.cancel(req.rid)
            state = CANCELLED
        elif case == "expiry":
            req.deadline_ns = 0
            state = EXPIRED
        elif case == "preempt_resume":
            engine._evict(req)        # what KV growth does to the newest
            want, state = ref, FINISHED
        elif case == "refilled_slot":
            assert engine.cancel(req.rid)
            other = _prompt(6, seed=83)
            heir = engine.add_request(other, max_new_tokens=5)
        engine.run()
        st = engine.stats()
        if case == "refilled_slot":
            assert heir.generated == _ref(model, other, 5)
            state = CANCELLED
        assert req.state == state
        assert req.generated == want
        assert st["commit_rollbacks"] == rollbacks
        assert st["decode_compiles"] == 0 and st["prefill_compiles"] == 0
        if neighbour is not None:
            assert neighbour.generated == _ref(model, near, 9)

    def test_no_result_of_a_prefill_is_touched_before_the_launch(
            self, model, monkeypatch):
        """Between a boundary's prefill dispatches and the decode launch
        behind them the host neither waits for nor fetches anything those
        prefills return (`prefill_unawaited_share` 1.0). The share is
        windowed by `reset_stats()`."""
        import paddle_tpu.serving.engine as engine_mod
        engine = LLMEngine(model, max_batch_size=4, block_size=4)
        engine.generate([_prompt(5, seed=84), _prompt(9, seed=84)],
                        max_new_tokens=2)           # buckets 8, 16 + decode
        assert engine.stats()["prefill_unawaited_share"] == 0.0
        engine.reset_stats()
        events, fresh = [], []
        call, wait = engine._call_program, engine._monitor.wait

        def spy_call(name, fn, args, first):
            res = call(name, fn, args, first)
            if name == "engine.prefill.dispatch":
                fresh.extend(res)
            events.append((name, None))
            return res

        def touched(kind, arrays):
            events.append((kind, any(a is f for a in arrays
                                     for f in fresh)))

        def spy_wait(arrays, phase, attempt=1, programs=1):
            touched("wait", arrays)
            return wait(arrays, phase, attempt, programs)

        engine._call_program = spy_call
        engine._monitor.wait = spy_wait
        monkeypatch.setattr(engine_mod, "np", _NumpySpy(
            lambda a: touched("fetch", (a,))))
        prompts = [_prompt(n, seed=85) for n in (6, 11, 7)]
        reqs = [engine.add_request(p, max_new_tokens=5) for p in prompts]
        del events[:]
        engine.step()                   # three prefills, then the launch
        names = [n for n, _ in events]
        last = max(i for i, n in enumerate(names)
                   if n == "engine.prefill.dispatch")
        launch = names.index("engine.decode.dispatch")
        assert names[:last + 1] == ["engine.prefill.dispatch"] * 3
        assert [e for e in events[last + 1:launch] if e[1]] == []
        assert all(r.generated == [] for r in reqs)
        engine.run()
        monkeypatch.undo()
        for r, p in zip(reqs, prompts):
            assert r.generated == _ref(model, p, 5)
        st = engine.stats()
        assert st["prefills"] == 3
        assert st["prefill_unawaited_share"] == 1.0
        assert engine._stats.prefills_unawaited == 3
        engine.reset_stats()
        assert engine.stats()["prefill_unawaited_share"] == 0.0
        assert engine._stats.prefills_unawaited == 0

    def test_a_boundarys_first_tokens_come_in_one_fetch(self, model,
                                                        reference,
                                                        monkeypatch):
        """Three prefills at one boundary cost the host ONE fetch (token,
        logprob, panel in one row a slot), a step later; their tokens,
        logprobs and panels are the reference's."""
        import paddle_tpu.serving.engine as engine_mod
        prompts = [_prompt(n, seed=86) for n in (6, 11, 7)]
        engine = LLMEngine(model, max_batch_size=4, block_size=4,
                           logprobs_topk=3)
        engine.generate([_prompt(5, seed=84), _prompt(9, seed=84)],
                        max_new_tokens=2)
        reqs = [engine.add_request(p, max_new_tokens=4,
                                   temperature=0.7, seed=40 + i)
                for i, p in enumerate(prompts)]
        engine.step()
        fetched, commit = [], engine._commit_joined

        def counted(inf, programs=None):
            # what the commit of the first tokens brings to the host:
            # device arrays (rows of a fetched one are the host's already)
            with monkeypatch.context() as patch:
                patch.setattr(engine_mod, "np", _NumpySpy(
                    lambda a: isinstance(a, np.ndarray)
                    or fetched.append(a)))
                return commit(inf, programs)

        engine._commit_joined = counted
        engine.step()
        assert len(fetched) == 1
        assert all(len(r.generated) == 2 for r in reqs)
        engine.run()
        reference.assert_served(reqs)
        for r in reqs:
            for i, (ids, lps) in enumerate(zip(r.alt_ids, r.alt_logprobs)):
                logits = reference.logits(r.prompt + r.generated[:i])
                logp = logits - np.log(np.exp(logits).sum())
                assert ids == np.argsort(-logp)[:3].tolist()
                np.testing.assert_allclose(lps, logp[ids], atol=2e-4)


# ---------------------------------------------------------------------------
# telemetry: serve.* events through the flight recorder + doctor
# ---------------------------------------------------------------------------

class TestServeTelemetry:
    def test_lifecycle_events_and_doctor_verdict(self, model):
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            prompts = [_prompt(n, seed=9) for n in (11, 12, 10, 5, 7)]
            engine = LLMEngine(model, max_batch_size=3, block_size=4,
                               num_blocks=10, watermark_blocks=1)
            engine.generate(prompts, max_new_tokens=6)
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
            clear_fusion_events()
        cats = {e["cat"] for e in ev}
        assert {"serve.enqueue", "serve.admit", "serve.step",
                "serve.complete"} <= cats
        evicts = [e for e in ev if e["cat"] == "serve.evict"]
        assert evicts and all(e["reason"] == "kv_exhausted" for e in evicts)
        resumed = [e for e in ev if e["cat"] == "serve.admit"
                   and (e.get("detail") or {}).get("resumed")]
        assert resumed                       # the evicted stream came back
        rep = explain(ev)
        assert rep["verdict"] == "serving"
        sv = rep["serving"]
        assert sv["completed"] == len(prompts)
        assert sv["evictions"] == len(evicts)
        assert sv["occupancy_mean"] is not None
        assert "kv_exhausted" in sv["reasons"]
        assert any("kv_exhausted" in f for f in rep["findings"])

    def test_refusal_attributed_kv_exhausted(self, model):
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            engine = LLMEngine(model, max_batch_size=2, block_size=4,
                               num_blocks=6, watermark_blocks=1)
            with pytest.raises(ValueError):
                engine.add_request(_prompt(20), max_new_tokens=20)
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
            clear_fusion_events()
        # refusals emit serve.refuse (PR 7): one category for every
        # admission bounce, whatever the reason code
        refusals = [e for e in ev if e["cat"] == "serve.refuse"
                    and e["reason"] == "kv_exhausted"]
        assert len(refusals) == 1
        d = refusals[0]["detail"]
        assert d["blocks_needed"] > d["blocks_budget"]


# ---------------------------------------------------------------------------
# resilience: deadlines, cancellation, backpressure, watchdog, fallback,
# crash-resume (PR 7, serving/resilience.py)
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _no_stale_resilience_state():
    guardian.clear_faults()
    set_flags({"FLAGS_serve_step_timeout_ms": 0})
    yield
    guardian.clear_faults()
    set_flags({"FLAGS_serve_step_timeout_ms": 0})


class TestBackpressure:
    def test_queue_full_refusal_is_structured_and_ordered(self, model):
        """The bounded queue refuses the overflow request with a
        structured ServeRefusal (a ValueError, reason `queue_full`),
        WITHOUT perturbing the queued work — the survivors are then
        served strictly FCFS."""
        engine = LLMEngine(model, max_batch_size=1, block_size=4,
                           max_queue_depth=2)
        first = engine.add_request(_prompt(5, seed=11), max_new_tokens=3,
                                   request_id="a")
        engine.step()                                 # "a" is running
        engine.add_request(_prompt(6, seed=12), max_new_tokens=3,
                           request_id="b")
        engine.add_request(_prompt(7, seed=13), max_new_tokens=3,
                           request_id="c")
        with pytest.raises(ServeRefusal) as ei:
            engine.add_request(_prompt(8, seed=14), max_new_tokens=3,
                               request_id="d")
        assert ei.value.reason == "queue_full"
        assert isinstance(ei.value, ValueError)       # PR 6 compat
        assert ei.value.detail["max_queue_depth"] == 2
        assert engine.stats()["refused_queue_full"] == 1
        # queue untouched by the refusal, still FCFS behind the head
        assert [r.rid for r in engine.scheduler.waiting] == ["b", "c"]
        engine.run()
        done = [engine.requests[rid] for rid in ("a", "b", "c")]
        assert all(r.state == FINISHED for r in done)
        assert (done[0].finish_ns < done[1].finish_ns
                < done[2].finish_ns)                  # strict FCFS
        assert first.state == FINISHED

    def test_deadline_infeasible_refused_at_enqueue(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        # TTL already spent at enqueue
        with pytest.raises(ServeRefusal) as ei:
            engine.add_request(_prompt(5, seed=15), max_new_tokens=4,
                               ttl_s=0.0)
        assert ei.value.reason == "deadline_infeasible"
        # with latency samples, an impossible wait+service estimate is
        # refused even though the TTL has not yet expired
        engine.generate([_prompt(5, seed=16)], max_new_tokens=4)
        assert engine._stats.step_times_s
        with pytest.raises(ServeRefusal) as ei:
            engine.add_request(_prompt(4, seed=17), max_new_tokens=40,
                               ttl_s=1e-5)
        assert ei.value.reason == "deadline_infeasible"
        assert engine.stats()["refused_deadline"] == 2


class TestDeadlines:
    def test_expiry_while_queued_does_not_block_admission(self, model):
        """An expired QUEUED request is cleared at the boundary before
        FCFS admission looks at the head — it must never shadow live
        work behind it, and the running stream never notices."""
        ref = _ref(model, _prompt(10, seed=18), 10)
        engine = LLMEngine(model, max_batch_size=1, block_size=4)
        live = engine.add_request(_prompt(10, seed=18), max_new_tokens=10)
        engine.step()                                   # live is running
        # a generous TTL passes admission; the deterministic seam pulls
        # the deadline into the past once it is safely queued (wall-clock
        # racing against CPU step times would flake)
        doomed = engine.add_request(_prompt(5, seed=19),
                                    max_new_tokens=4, ttl_s=60.0)
        behind = engine.add_request(_prompt(6, seed=20), max_new_tokens=2)
        doomed.deadline_ns = time.perf_counter_ns() - 1
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            engine.run()
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        assert doomed.state == EXPIRED
        assert doomed.error == "deadline_expired"
        assert behind.state == FINISHED                 # not shadowed
        assert live.generated == ref                    # undisturbed
        exp = [e for e in ev if e["cat"] == "serve.expire"]
        assert len(exp) == 1
        assert exp[0]["reason"] == "deadline_expired"
        assert exp[0]["detail"]["where"] == "queued"
        assert engine.stats()["decode_compiles"] == 1

    def test_expiry_while_running_frees_the_slot(self, model):
        """A RUNNING stream whose deadline passes is cleared at the next
        iteration boundary (a value-only slot edit): the slot is reused,
        the survivor stream is bitwise-unaffected, and the decode
        program never retraces."""
        ref_b = _ref(model, _prompt(7, seed=21), 12)
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        doomed = engine.add_request(_prompt(9, seed=22),
                                    max_new_tokens=12, ttl_s=60.0)
        keeper = engine.add_request(_prompt(7, seed=21), max_new_tokens=12)
        for _ in range(4):
            engine.step()
        assert doomed.state == RUNNING
        # deterministic expiry: pull the deadline into the past instead
        # of racing wall-clock against CPU step times
        doomed.deadline_ns = time.perf_counter_ns() - 1
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            engine.step()
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        assert doomed.state == EXPIRED
        exp = [e for e in ev if e["cat"] == "serve.expire"]
        assert exp and exp[0]["detail"]["where"] == "running"
        waiter = engine.add_request(_prompt(5, seed=23), max_new_tokens=3)
        engine.run()
        assert keeper.generated == ref_b                # bitwise
        assert waiter.state == FINISHED                 # slot was reused
        assert engine.stats()["decode_compiles"] == 1


class TestCancellation:
    def test_cancel_queued_and_running(self, model):
        ref = _ref(model, _prompt(8, seed=24), 10)
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        keeper = engine.add_request(_prompt(8, seed=24), max_new_tokens=10)
        victim = engine.add_request(_prompt(6, seed=25), max_new_tokens=10)
        queued = engine.add_request(_prompt(5, seed=26), max_new_tokens=4)
        for _ in range(3):
            engine.step()
        assert victim.state == RUNNING and queued.state == QUEUED
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            assert engine.cancel(victim.rid) is True
            assert engine.cancel(queued.rid) is True
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        assert victim.state == CANCELLED
        assert queued.state == CANCELLED
        cancels = [e for e in ev if e["cat"] == "serve.cancel"]
        assert {e["reason"] for e in cancels} == {"client_cancel"}
        assert {e["detail"]["was_running"] for e in cancels} == \
            {True, False}
        engine.run()
        assert keeper.generated == ref                  # bitwise
        assert engine.stats()["decode_compiles"] == 1
        assert engine.stats()["cancelled"] == 2

    def test_cancel_from_streaming_callback_defers_to_boundary(
            self, model):
        """A cancel issued from inside an on_token callback — the
        natural place to notice a client disconnect — must not edit the
        slot arrays under step()'s feet: it defers to the next boundary
        sweep, the neighbor stream stays bitwise, and the decode program
        never retraces."""
        ref = _ref(model, _prompt(8, seed=42), 10)
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        victim = engine.add_request(_prompt(6, seed=43), max_new_tokens=10)

        def on_tok(req, tok, text):
            if len(req.generated) == 3:
                # cross-request cancel from a live stream's callback
                assert engine.cancel(victim.rid) is True
        keeper = engine.add_request(_prompt(8, seed=42), max_new_tokens=10,
                                    on_token=on_tok)
        engine.run()
        assert victim.state == CANCELLED
        assert len(victim.generated) <= 4      # stopped at the boundary
        assert keeper.generated == ref         # bitwise undisturbed
        assert engine.stats()["decode_compiles"] == 1
        # self-cancel from the victim's own callback is equally safe
        engine2 = LLMEngine(model, max_batch_size=2, block_size=4)
        selfc = engine2.add_request(
            _prompt(7, seed=44), max_new_tokens=10,
            on_token=lambda r, t, txt: (len(r.generated) == 2
                                        and engine2.cancel(r.rid)))
        other = engine2.add_request(_prompt(8, seed=42), max_new_tokens=10)
        engine2.run()
        assert selfc.state == CANCELLED
        assert other.generated == ref

    def test_pop_finished_drains_terminal_handles(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        done = engine.add_request(_prompt(5, seed=45), max_new_tokens=3,
                                  request_id="d")
        live = engine.add_request(_prompt(6, seed=46), max_new_tokens=40,
                                  request_id="l")
        while done.state != FINISHED:
            engine.step()
        drained = engine.pop_finished()
        assert set(drained) == {"d"} and drained["d"] is done
        assert set(engine.requests) == {"l"}   # live handles stay
        engine.cancel(live.rid)

    def test_cancel_racing_completion_is_noop(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        req = engine.add_request(_prompt(5, seed=27), max_new_tokens=3)
        engine.run()
        assert req.state == FINISHED
        assert engine.cancel(req.rid) is False          # too late: no-op
        assert req.state == FINISHED
        assert engine.cancel("never-existed") is False
        assert engine.stats()["cancelled"] == 0


class TestAgingGuard:
    def _sched(self, **kw):
        alloc = BlockAllocator(20)
        return Scheduler(3, alloc, 4, watermark_blocks=0, **kw)

    def test_protected_request_never_chosen_as_victim(self):
        sched = self._sched(aging_max_preemptions=2)
        reqs = [Request(f"r{i}", [1, 2], 4) for i in range(3)]
        for r in reqs:
            sched.enqueue(r)
            sched.try_admit()
        reqs[2].preemptions = 2                    # paid its dues
        assert sched.protected(reqs[2])
        # LIFO would pick r2 (newest); the guard redirects to r1
        assert sched.preempt_victim() is reqs[1]
        reqs[1].preemptions = 2
        reqs[0].preemptions = 2
        assert sched.preempt_victim() is None      # everyone protected

    def test_sustained_preemption_cannot_starve(self, model):
        """A request bounced by LIFO preemption becomes protected after
        aging_max_preemptions evictions: under a sustained stream of
        competing work over a deliberately tight pool, every stream
        still completes, nobody's preemption count passes the cap + 1,
        and the outputs stay token-identical."""
        prompts = [_prompt(n, seed=28) for n in (11, 12, 10, 5, 9, 7)]
        refs = [_ref(model, p, 10) for p in prompts]
        engine = LLMEngine(model, max_batch_size=3, block_size=4,
                           num_blocks=10, watermark_blocks=1,
                           aging_max_preemptions=2)
        outs = engine.generate(prompts, max_new_tokens=10)
        assert outs == refs
        assert engine.stats()["evictions"] >= 1    # churn actually bit
        cap = engine.scheduler.aging_max_preemptions
        assert all(r.preemptions <= cap + 1
                   for r in engine.requests.values())

    def test_grower_steps_aside_when_victims_protected(self, model):
        """When every other tenant is protected, the grower self-preempts
        (requeued at its arrival slot) instead of being terminally
        failed — bounded fairness, not collateral damage."""
        engine = LLMEngine(model, max_batch_size=2, block_size=4,
                           num_blocks=8, watermark_blocks=1,
                           aging_max_preemptions=3)
        a = engine.add_request(_prompt(10, seed=29), max_new_tokens=10)
        b = engine.add_request(_prompt(9, seed=30), max_new_tokens=10)
        for _ in range(2):
            engine.step()
        assert a.state == RUNNING and b.state == RUNNING
        a.preemptions = 3                          # a is protected
        engine.run()
        assert a.state == FINISHED and b.state == FINISHED
        assert b.preemptions >= 1                  # b stepped aside
        assert a.generated == _ref(model, _prompt(10, seed=29), 10)
        assert b.generated == _ref(model, _prompt(9, seed=30), 10)


class TestWatchdog:
    def test_injected_hang_recovers_within_budget(self, model):
        """Rung 1: one hung decode step is detected by the watchdog and
        retried — every stream finishes token-identically, the decode
        program does NOT retrace, and the hang is attributed."""
        prompts = [_prompt(n, seed=31) for n in (9, 6)]
        refs = [_ref(model, p, 8) for p in prompts]
        set_flags({"FLAGS_serve_step_timeout_ms": 2000})
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            engine.step()
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        guardian.inject_fault("hang", op="serve.decode", times=1)
        try:
            engine.run()
            ev = fusion_events()
        finally:
            guardian.clear_faults()
            set_flags({"FLAGS_profiler_events": False})
        st = engine.stats()
        assert st["hangs"] == 1
        assert st["decode_compiles"] == 1
        assert not engine.degraded                  # recovered
        for r, ref in zip(reqs, refs):
            assert r.state == FINISHED and r.generated == ref
        hangs = [e for e in ev if e["cat"] == "serve.hang"]
        assert hangs and hangs[0]["reason"] == "step_hang"
        # the degraded window is visible: entry + recovery transitions
        degr = [e for e in ev if e["cat"] == "serve.degrade"]
        assert any((e.get("detail") or {}).get("rung") == "retry"
                   for e in degr)
        assert any((e.get("detail") or {}).get("recovered")
                   for e in degr)
        rep = explain(ev)
        assert rep["verdict"] == "serving_degraded"

    def test_a_step_that_never_returns_fails_active_without_wedging(
            self, model):
        """The last rung: a step that will not come back fails the ACTIVE
        requests with an attributed reason; queued and new requests are
        then served normally — the process never wedges. A launch whose
        successor already consumed its pools cannot be replayed, so the
        wait is retried once and then given up (2 hangs)."""
        set_flags({"FLAGS_serve_step_timeout_ms": 2000})
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        doomed = engine.add_request(_prompt(6, seed=32), max_new_tokens=8)
        engine.step()
        guardian.inject_fault("hang", op="serve.decode", times=2)
        try:
            engine.run()
        finally:
            guardian.clear_faults()
        assert doomed.state == FAILED
        assert doomed.error == "step_hang"
        assert engine.stats()["hangs"] == 2
        fresh = engine.add_request(_prompt(5, seed=33), max_new_tokens=4)
        engine.run()
        assert fresh.state == FINISHED

    SEEDED = dict(temperature=0.9, top_k=20, repetition_penalty=1.2,
                  seed=3000)

    def _hung(self, model, phase, hangs, consumed=False):
        """A warm engine whose commit of `phase` hangs `hangs` times in a
        row while two seeded streams are in flight and a third request
        waits for a slot: ``(engine, the three requests, events)``."""
        set_flags({"FLAGS_serve_step_timeout_ms": 2000})
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.generate([_prompt(6, seed=90), _prompt(9, seed=90)],
                        max_new_tokens=2)             # buckets 8, 16
        engine.reset_stats()
        reqs = [engine.add_request(_prompt(n, seed=91), max_new_tokens=7,
                                   **stream_of(self.SEEDED, i))
                for i, n in enumerate((7, 10, 6))]
        engine.step()         # two prefills and a launch, none committed
        assert [r.generated for r in reqs] == [[], [], []]
        if consumed:
            # what donation does on a chip to the buffers a later program
            # was handed: the launch in flight cannot be run again
            engine._bufs[0].delete()
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        guardian.inject_fault("hang", op=f"serve.{phase}", times=hangs)
        try:
            engine.run()
            events = fusion_events()
        finally:
            guardian.clear_faults()
            set_flags({"FLAGS_profiler_events": False})
            clear_fusion_events()
        return engine, reqs, events

    @staticmethod
    def _rungs(events):
        return [(e["detail"]["rung"], e["detail"]["phase"])
                for e in events if e["cat"] == "serve.degrade"
                and "rung" in (e.get("detail") or {})]

    @pytest.mark.parametrize("phase, where", [("decode", "commit"),
                                              ("prefill", "prefill")])
    def test_one_hang_at_a_commit_is_retried(self, model, reference, phase,
                                             where):
        """The ladder's first rung, at a launch's commit and at a
        boundary's prefills': the WAIT is retried, nothing is rebuilt,
        and every stream, seeded, is the reference's."""
        engine, reqs, events = self._hung(model, phase, 1)
        st = engine.stats()
        assert st["hangs"] == 1 and st["failed"] == 0
        assert st["decode_compiles"] == 0 and st["prefill_compiles"] == 0
        assert not engine.degraded
        assert self._rungs(events) == [("retry", where)]
        (hang,) = [e for e in events if e["cat"] == "serve.hang"]
        assert hang["detail"]["phase"] == where
        assert hang["detail"]["attempt"] == 1
        assert all(r.state == FINISHED for r in reqs)
        reference.assert_served(reqs)

    @pytest.mark.parametrize("phase, where", [("decode", "commit"),
                                              ("prefill", "prefill")])
    def test_two_hangs_in_a_row_fail_the_active_alone(
            self, model, reference, phase, where):
        """The last rung: the active requests fail with `step_hang`, the
        decode program is traced anew, and the request that was waiting
        for a slot is admitted afterwards and served the reference's
        stream."""
        engine, reqs, events = self._hung(model, phase, 2)
        st = engine.stats()
        assert st["hangs"] == 2 and st["failed"] == 2
        assert st["decode_compiles"] == 1            # the rebuild
        assert self._rungs(events) == [("retry", where),
                                       ("fail_active", where)]
        assert [(r.state, r.error) for r in reqs[:2]] \
            == [(FAILED, "step_hang")] * 2
        assert reqs[2].state == FINISHED
        reference.assert_served(reqs[2:])
        assert not engine.degraded

    def test_consumed_pools_are_not_waited_for_twice(self, model,
                                                     reference):
        """A hang over pools a later program has consumed cannot be
        retried: the active requests fail at once, the KV state is built
        anew, and the engine serves the waiting request and new work to
        the reference."""
        engine, reqs, events = self._hung(model, "prefill", 1,
                                          consumed=True)
        assert self._rungs(events) == [("fail_active", "prefill")]
        (degrade,) = [e for e in events if e["cat"] == "serve.degrade"
                      and e["detail"].get("rung")]
        assert degrade["detail"]["pools_consumed"] is True
        assert engine.stats()["hangs"] == 1
        assert [r.state for r in reqs] == [FAILED, FAILED, FINISHED]
        assert not any(b.is_deleted() for b in engine._bufs)
        fresh = engine.add_request(_prompt(12, seed=92), max_new_tokens=6,
                                   **self.SEEDED)
        engine.run()
        reference.assert_served([reqs[2], fresh])


    def test_a_prefill_behind_a_launch_is_given_both_budgets(self, model):
        """The armed watchdog's wait for a prefill is the commit's, and
        is given one budget for every program the device's queue holds
        up to it. A bucket's first call is committed where it stands:
        one budget with nothing ahead, two behind an uncommitted launch.
        Warm, a boundary's prefills are awaited a step later, together:
        as many budgets as there were prefills, and the launch they were
        queued before is then given its own one."""
        set_flags({"FLAGS_serve_step_timeout_ms": 2000})
        engine = LLMEngine(model, max_batch_size=4, block_size=4)
        waits, wait = [], engine._monitor.wait

        def spy(arrays, phase, attempt=1, programs=1):
            waits.append((phase, programs, engine._inflight is not None))
            return wait(arrays, phase, attempt, programs)

        engine._monitor.wait = spy
        engine.add_request(_prompt(6, seed=71), max_new_tokens=6)
        engine.step()
        engine.add_request(_prompt(9, seed=72), max_new_tokens=4)
        engine.run()
        assert engine.stats()["hangs"] == 0
        prefills = [w for w in waits if w[0] == "prefill"]
        assert prefills == [("prefill", 1, False), ("prefill", 2, True)]
        # both buckets warm: one request, then two at one boundary
        del waits[:]
        engine.add_request(_prompt(7, seed=73), max_new_tokens=6)
        engine.step()
        assert waits == []                  # dispatched, launched over
        engine.add_request(_prompt(5, seed=74), max_new_tokens=3)
        engine.add_request(_prompt(10, seed=75), max_new_tokens=3)
        engine.step()
        assert waits == [("prefill", 1, True), ("decode", 1, False)]
        del waits[:]
        engine.step()
        assert waits == [("prefill", 2, True), ("decode", 1, False)]
        engine.run()
        assert engine.stats()["hangs"] == 0

    def test_a_hung_prefill_found_at_the_commit_fails_the_active(self, model):
        """A prefill that never comes back is found at its commit, a
        step later, with later programs queued over its pools: the wait
        is retried once, then the active requests fail with an
        attributed reason and the engine serves new work."""
        set_flags({"FLAGS_serve_step_timeout_ms": 2000})
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.generate([_prompt(6, seed=76)], max_new_tokens=2)   # warm
        doomed = engine.add_request(_prompt(7, seed=77), max_new_tokens=8)
        engine.step()
        assert doomed.generated == []
        guardian.inject_fault("hang", op="serve.prefill", times=2)
        try:
            engine.run()
        finally:
            guardian.clear_faults()
        assert doomed.state == FAILED and doomed.error == "step_hang"
        st = engine.stats()
        assert st["hangs"] == 2
        assert st["commit_rollbacks"] == 2     # first token + decoded one
        fresh = engine.add_request(_prompt(5, seed=78), max_new_tokens=4)
        engine.run()
        assert fresh.state == FINISHED
        assert fresh.generated == _ref(model, _prompt(5, seed=78), 4)

    def test_a_wait_over_two_programs_burns_two_budgets(self):
        from paddle_tpu.serving.resilience import MonitoredWait, StepHang

        class Never:
            def is_ready(self):
                return False

        with pytest.raises(StepHang) as hang:
            MonitoredWait(budget_s=0.01).wait([Never()], "prefill",
                                              programs=2)
        assert hang.value.budget_ms == pytest.approx(20.0)
        with pytest.raises(StepHang) as hang:
            MonitoredWait(budget_s=0.01).wait([Never()], "prefill")
        assert hang.value.budget_ms == pytest.approx(10.0)


class TestDegradedFallback:
    def test_poisoned_decode_falls_back_eager_token_identically(
            self, model):
        """A poisoned compiled-decode launch is discarded; every
        in-flight stream finishes through the model's own generate()
        path with IDENTICAL tokens, streaming callbacks included, and
        the engine keeps serving new work on the (unrebuilt) compiled
        program."""
        prompts = [_prompt(n, seed=34) for n in (10, 7)]
        refs = [_ref(model, p, 9) for p in prompts]
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        streamed = {p[0]: [] for p in ("a", "b")}
        reqs = [engine.add_request(
                    p, max_new_tokens=9, request_id=rid,
                    on_token=lambda r, tok, text: streamed[r.rid]
                    .append(tok))
                for rid, p in zip(("a", "b"), prompts)]
        for _ in range(4):
            engine.step()
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        guardian.inject_fault("nan_output", op="serve.decode", times=1)
        try:
            engine.run()
            ev = fusion_events()
        finally:
            guardian.clear_faults()
            set_flags({"FLAGS_profiler_events": False})
        st = engine.stats()
        assert st["eager_fallbacks"] == 2
        assert st["decode_compiles"] == 1           # no rebuild
        for r, ref in zip(reqs, refs):
            assert r.state == FINISHED and r.generated == ref
            assert streamed[r.rid] == ref           # stream continuity
        degr = [e for e in ev if e["cat"] == "serve.degrade"
                and e["reason"] == "decode_fault"]
        assert degr
        # and the compiled path still serves new requests, zero retrace
        again = engine.add_request(prompts[0], max_new_tokens=9)
        engine.run()
        assert again.generated == refs[0]
        assert engine.stats()["decode_compiles"] == 1


class TestCrashResume:
    def test_state_payload_restores_byte_identically(self, model):
        """A mid-flight snapshot restored into a FRESH engine finishes
        every stream with the same final tokens as the uninterrupted
        run (re-prefill of prompt + emitted tokens is the PR 6
        token-identical resume path)."""
        prompts = [_prompt(n, seed=35) for n in (11, 6, 9)]
        refs = [_ref(model, p, 10) for p in prompts]
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        for i, p in enumerate(prompts):
            engine.add_request(p, max_new_tokens=10, request_id=f"s{i}")
        for _ in range(5):
            engine.step()                           # mid-flight
        payload = engine.state_payload()
        assert payload["requests"]                  # live streams inside
        engine2 = LLMEngine(model, max_batch_size=2, block_size=4)
        clear_fusion_events()
        set_flags({"FLAGS_profiler_events": True})
        try:
            restored = engine2.restore_state(payload)
            ev = fusion_events()
        finally:
            set_flags({"FLAGS_profiler_events": False})
        assert [e["reason"] for e in ev
                if e["cat"] == "serve.resume"] \
            == ["crash_resume"] * len(restored)
        engine2.run()
        by_rid = {r.rid: r for r in restored}
        for i, ref in enumerate(refs):
            rid = f"s{i}"
            if rid in by_rid:                       # was still in flight
                assert by_rid[rid].generated == ref
                assert by_rid[rid].state == FINISHED
        assert engine2.stats()["resumed"] == len(restored)

    def test_restore_rejects_live_duplicate(self, model):
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.add_request(_prompt(5, seed=36), max_new_tokens=6,
                           request_id="dup")
        payload = engine.state_payload()
        with pytest.raises(ValueError, match="already live"):
            engine.restore_state(payload)

    def test_serve_checkpointer_roundtrip_and_corruption_refusal(
            self, model, tmp_path):
        from paddle_tpu.framework.io import CheckpointCorruptError
        from paddle_tpu.incubate.checkpoint import ServeCheckpointer
        ref = _ref(model, _prompt(8, seed=37), 8)
        ck = ServeCheckpointer(str(tmp_path), save_every_n_steps=1,
                               max_checkpoints=2)
        engine = LLMEngine(model, max_batch_size=2, block_size=4)
        engine.add_request(_prompt(8, seed=37), max_new_tokens=8,
                           request_id="k")
        for n in range(1, 4):
            engine.step()
            ck.tick(n, engine.state_payload())
        assert len(ck._retained()) == 2             # rolling retention
        engine2 = LLMEngine(model, max_batch_size=2, block_size=4)
        [req] = engine2.restore_state(ck.restore())
        engine2.run()
        assert req.generated == ref                 # byte-identical
        # torn writes on every retained snapshot -> REFUSE, never start
        # empty while silently dropping in-flight user streams
        for s in ck._retained():
            p = os.path.join(ck.checkpoint_path(s), ck.CKPT_FILE)
            with open(p, "r+b") as fh:
                fh.seek(8)
                fh.write(b"XXXX")
        with pytest.raises(CheckpointCorruptError, match="refusing"):
            ck.restore()

    def test_decode_compiles_once_under_lifecycle_churn(self, model):
        """The acceptance criterion: cancel/expire/refuse/resume are
        VALUE edits to the fixed slot layout — the decode executable
        compiles exactly once through all of it."""
        set_flags({"FLAGS_serve_step_timeout_ms": 2000})
        engine = LLMEngine(model, max_batch_size=4, block_size=4,
                           max_queue_depth=6)
        engine.generate([_prompt(5, seed=38)], max_new_tokens=3)  # warm
        engine.reset_stats()
        live = [engine.add_request(_prompt(4 + i, seed=39),
                                   max_new_tokens=6) for i in range(4)]
        doomed = engine.add_request(_prompt(5, seed=40), max_new_tokens=6,
                                    ttl_s=60.0)
        doomed.deadline_ns = 0        # deterministic queued expiry
        with pytest.raises(ServeRefusal):
            for _ in range(16):
                engine.add_request(_prompt(6, seed=41), max_new_tokens=6)
        for _ in range(2):
            engine.step()
        engine.cancel(live[0].rid)
        mid = engine.state_payload()
        engine.run()
        resumed = engine.restore_state(mid)
        engine.run()
        st = engine.stats()
        assert st["decode_compiles"] == 0           # post-warmup window
        assert st["cancelled"] >= 1 and st["expired"] >= 1
        assert st["refused_queue_full"] >= 1 and len(resumed) >= 1
