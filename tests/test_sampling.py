"""Compiled stochastic sampling suite (paddle_tpu/serving/sampling.py +
the engine's sampler head, logprobs, and pipelined decode).

The contracts pinned here are the PR 18 acceptance criteria:

  * every sampler knob is a per-slot VALUE in the one compiled decode
    step: heterogeneous sampler churn across 64 streams compiles decode
    exactly once;
  * ``temperature=0`` is greedy under the SAME program — token-identical
    to ``model.generate(do_sample=False)`` whatever the other knobs say;
  * a given (seed, prompt, sampler config) reproduces its token stream
    byte-identically across join-order permutations, preemption and
    crash-checkpoint resume (the per-slot keys are
    ``fold_in(PRNGKey(seed), position)``, so a replay is a replay, not a
    re-roll): the stream one request at a time through the dense forward
    is owed (tests/serving_reference.py);
  * per-token logprobs and static-K alternative panels ride the same
    executable with zero extra compiles;
  * software-pipelined decode (launch N+1 before committing N) serves
    that reference's streams, and the commit-lag-1 transaction rolls a
    launched-but-uncommitted token back instead of leaking it into a
    cancelled stream.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import LLMEngine, FINISHED, CANCELLED
from paddle_tpu.serving.sampling import (SAMPLER_VERSION, default_seed,
                                         validate_sampler,
                                         apply_repetition_penalty,
                                         apply_temperature, apply_top_k,
                                         apply_top_p, sample_tokens)

import serving_backlog as backlog
from serving_reference import (SAMPLERS, Reference, each_sampler,
                               stream_of)

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
    return Reference(model)


def _prompt(length, seed=0):
    rng = np.random.default_rng(seed * 1000 + length)
    return rng.integers(0, VOCAB, length).tolist()


_REF_CACHE = {}


def _ref(model, prompt, n):
    """Greedy reference through model.generate (memoized per length)."""
    key = (tuple(prompt), n)
    if key not in _REF_CACHE:
        out = model.generate(paddle.Tensor(np.asarray([prompt], np.int64)),
                             max_new_tokens=n, do_sample=False)
        arr = out._value if hasattr(out, "_value") else out
        _REF_CACHE[key] = np.asarray(arr)[0].tolist()
    return _REF_CACHE[key]


def _run_streams(model, prompts, cfgs, n_new=8, **eng_kw):
    """One engine, one request per (prompt, sampler cfg); returns the
    generated token lists in request order plus the engine."""
    eng = LLMEngine(model, max_batch_size=4, block_size=4, **eng_kw)
    reqs = [eng.add_request(p, max_new_tokens=n_new, **cfg)
            for p, cfg in zip(prompts, cfgs)]
    eng.run()
    return [list(r.generated) for r in reqs], eng


# ---------------------------------------------------------------------------
# pure sampler math (no engine, no model)
# ---------------------------------------------------------------------------

class TestSamplerHelpers:
    def test_validate_sampler_contract(self):
        validate_sampler(0.0, 0, 1.0, 1.0)            # greedy defaults
        validate_sampler(1.5, 40, 0.9, 1.2)           # the full stack
        for bad in (-0.5, float("nan"), float("inf")):
            with pytest.raises(ValueError, match="temperature"):
                validate_sampler(bad, 0, 1.0, 1.0)
        with pytest.raises(ValueError, match="top_k"):
            validate_sampler(1.0, -1, 1.0, 1.0)
        for bad in (0.0, -0.1, 1.5):
            with pytest.raises(ValueError, match="top_p"):
                validate_sampler(1.0, 0, bad, 1.0)
        for bad in (0.0, -1.0, float("inf")):
            with pytest.raises(ValueError, match="repetition_penalty"):
                validate_sampler(1.0, 0, 1.0, bad)

    def test_default_seed_is_stable_and_rid_keyed(self):
        # crc32 of the rid: process-stable (serializes through crash
        # checkpoints), distinct per request id
        assert default_seed("r1") == default_seed("r1")
        assert default_seed("r1") != default_seed("r2")
        s = default_seed("anything")
        assert isinstance(s, int) and 0 <= s < 2**32

    def test_temperature_zero_is_divide_safe(self):
        lg = jnp.asarray(np.random.default_rng(0)
                         .normal(size=(3, 16)).astype(np.float32))
        out = np.asarray(apply_temperature(
            lg, jnp.asarray([0.0, 1.0, 2.0], jnp.float32)))
        assert np.all(np.isfinite(out))
        # T=1 row is untouched, T=2 row is halved
        np.testing.assert_allclose(out[1], np.asarray(lg)[1], rtol=1e-6)
        np.testing.assert_allclose(out[2], np.asarray(lg)[2] / 2,
                                   rtol=1e-6)

    def test_top_k_zero_disables_and_one_is_argmax(self):
        lg = jnp.asarray(np.random.default_rng(1)
                         .normal(size=(2, 32)).astype(np.float32))
        off = np.asarray(apply_top_k(lg, jnp.asarray([0, 0], jnp.int32)))
        np.testing.assert_array_equal(off, np.asarray(lg))
        one = np.asarray(apply_top_k(lg, jnp.asarray([1, 1], jnp.int32)))
        for row, raw in zip(one, np.asarray(lg)):
            kept = np.flatnonzero(row > -1e29)
            assert kept.tolist() == [int(np.argmax(raw))]

    def test_top_p_one_is_exact_noop_and_top1_survives(self):
        lg = jnp.asarray(np.random.default_rng(2)
                         .normal(size=(2, 32)).astype(np.float32))
        noop = np.asarray(apply_top_p(lg, jnp.asarray([1.0, 1.0],
                                                      jnp.float32)))
        np.testing.assert_array_equal(noop, np.asarray(lg))
        # p small enough to keep only the nucleus head: the argmax token
        # must ALWAYS survive (exclusive-mass test)
        tight = np.asarray(apply_top_p(lg, jnp.asarray([1e-6, 1e-6],
                                                       jnp.float32)))
        for row, raw in zip(tight, np.asarray(lg)):
            assert row[int(np.argmax(raw))] > -1e29

    def test_repetition_penalty_noop_and_ctrl_rule(self):
        lg = jnp.asarray([[2.0, -1.0, 0.5, 3.0]], jnp.float32)
        hist = jnp.asarray([[0, 1, 1]], jnp.int32)
        valid = jnp.asarray([[True, True, False]])
        noop = np.asarray(apply_repetition_penalty(
            lg, hist, valid, jnp.asarray([1.0], jnp.float32)))
        np.testing.assert_array_equal(noop, np.asarray(lg))
        out = np.asarray(apply_repetition_penalty(
            lg, hist, valid, jnp.asarray([2.0], jnp.float32)))[0]
        assert out[0] == pytest.approx(1.0)    # seen positive: divided
        assert out[1] == pytest.approx(-2.0)   # seen negative: multiplied
        assert out[2] == pytest.approx(0.5)    # unseen: untouched
        assert out[3] == pytest.approx(3.0)    # invalid history entry

    def test_all_greedy_head_is_raw_argmax_with_logprob_panels(self):
        rng = np.random.default_rng(3)
        lg = jnp.asarray(rng.normal(size=(4, VOCAB)).astype(np.float32))
        zeros = jnp.zeros(4, jnp.float32)
        args = (lg, zeros, jnp.zeros(4, jnp.int32),
                jnp.ones(4, jnp.float32), jnp.ones(4, jnp.float32),
                jnp.zeros(4, jnp.uint32), jnp.zeros(4, jnp.int32),
                jnp.zeros((4, 8), jnp.int32), jnp.zeros((4, 8), bool))
        nxt, chosen, alt_ids, alt_lps = sample_tokens(*args,
                                                      logprobs_topk=3)
        raw = np.asarray(lg)
        np.testing.assert_array_equal(np.asarray(nxt),
                                      np.argmax(raw, axis=-1))
        # chosen logprob comes from the raw log-softmax; the greedy token
        # is also the top-1 panel entry with the identical value
        ref_lp = raw - np.log(np.exp(raw).sum(-1, keepdims=True))
        np.testing.assert_allclose(
            np.asarray(chosen), ref_lp[np.arange(4), np.asarray(nxt)],
            rtol=1e-5)
        assert np.asarray(alt_ids).shape == (4, 3)
        np.testing.assert_array_equal(np.asarray(alt_ids)[:, 0],
                                      np.asarray(nxt))
        np.testing.assert_allclose(np.asarray(alt_lps)[:, 0],
                                   np.asarray(chosen), rtol=1e-5)
        # panels are sorted descending
        lps = np.asarray(alt_lps)
        assert np.all(np.diff(lps, axis=-1) <= 1e-7)

    def test_top_k_one_forces_argmax_even_when_stochastic(self):
        rng = np.random.default_rng(4)
        lg = jnp.asarray(rng.normal(size=(4, VOCAB)).astype(np.float32))
        nxt, _, _, _ = sample_tokens(
            lg, jnp.full(4, 1.0, jnp.float32), jnp.ones(4, jnp.int32),
            jnp.ones(4, jnp.float32), jnp.ones(4, jnp.float32),
            jnp.asarray([5, 6, 7, 8], jnp.uint32),
            jnp.asarray([3, 4, 5, 6], jnp.int32),
            jnp.zeros((4, 8), jnp.int32), jnp.zeros((4, 8), bool))
        np.testing.assert_array_equal(np.asarray(nxt),
                                      np.argmax(np.asarray(lg), -1))

    def test_same_key_same_draw_new_position_new_draw(self):
        rng = np.random.default_rng(5)
        lg = jnp.asarray(rng.normal(size=(8, VOCAB)).astype(np.float32))
        base = (lg, jnp.full(8, 1.2, jnp.float32),
                jnp.zeros(8, jnp.int32), jnp.ones(8, jnp.float32),
                jnp.ones(8, jnp.float32), jnp.arange(8, dtype=jnp.uint32))
        hist = (jnp.zeros((8, 8), jnp.int32), jnp.zeros((8, 8), bool))
        pos = jnp.full(8, 9, jnp.int32)
        a = np.asarray(sample_tokens(*base, pos, *hist)[0])
        b = np.asarray(sample_tokens(*base, pos, *hist)[0])
        np.testing.assert_array_equal(a, b)          # replay == replay
        c = np.asarray(sample_tokens(*base, pos + 1, *hist)[0])
        assert not np.array_equal(a, c)              # stream advanced

    def test_sampler_version_is_pinned(self):
        # bumping the math without bumping the version would let stale
        # AOT exports replay silently — freeze the current value
        assert SAMPLER_VERSION == 2


# ---------------------------------------------------------------------------
# greedy identity: temperature=0 under the sampling program
# ---------------------------------------------------------------------------

class TestGreedyIdentity:
    def test_temperature_zero_matches_generate_in_mixed_batch(self, model):
        """A greedy stream sharing slots with stochastic neighbors stays
        token-identical to model.generate(do_sample=False) — sampling is
        per-slot, never batch-global."""
        prompts = [_prompt(n, seed=50) for n in (9, 7, 11, 6)]
        cfgs = [dict(),
                dict(temperature=1.0, top_k=20, seed=51),
                dict(),
                dict(temperature=0.8, top_p=0.9, seed=52)]
        outs, eng = _run_streams(model, prompts, cfgs)
        assert outs[0] == _ref(model, prompts[0], 8)
        assert outs[2] == _ref(model, prompts[2], 8)
        st = eng.stats()
        assert st["decode_compiles"] == 1
        assert st["sampled_tokens"] == 16            # the two hot streams

    def test_other_knobs_inert_at_temperature_zero(self, model):
        """top_k/top_p/repetition_penalty/seed do nothing at T=0: the
        greedy select reads the RAW logits."""
        p = _prompt(10, seed=53)
        outs, _ = _run_streams(
            model, [p], [dict(temperature=0.0, top_k=3, top_p=0.5,
                              repetition_penalty=1.8, seed=99)])
        assert outs[0] == _ref(model, p, 8)


# ---------------------------------------------------------------------------
# zero-retrace churn
# ---------------------------------------------------------------------------

class TestZeroRetraceSampling:
    def test_heterogeneous_sampler_churn_one_compile(self, model):
        """The acceptance criterion: 32 streams cycling five different
        sampler configs (greedy included) through 4 slots — the decode
        executable compiles exactly once."""
        prompts = [_prompt(3 + (i % 7), seed=54) for i in range(32)]
        cfgs = []
        for i in range(32):
            cfg = dict(SAMPLERS[i % len(SAMPLERS)])
            if "seed" in cfg:
                cfg["seed"] = 1000 + i               # every stream unique
            cfgs.append(cfg)
        outs, eng = _run_streams(model, prompts, cfgs, n_new=5)
        st = eng.stats()
        assert st["decode_compiles"] == 1
        assert st["completed"] == 32
        assert st["sampled_tokens"] > 0
        assert all(len(o) == 5 for o in outs)

    def test_invalid_sampler_refused_not_compiled(self, model):
        eng = LLMEngine(model, max_batch_size=2, block_size=4)
        for bad in (dict(temperature=-1.0), dict(top_k=-2),
                    dict(top_p=0.0), dict(repetition_penalty=0.0)):
            with pytest.raises(ValueError):
                eng.add_request(_prompt(5, seed=55), **bad)
        assert eng.stats()["decode_compiles"] == 0   # nothing traced


# ---------------------------------------------------------------------------
# (seed, prompt, sampler) byte-identical reproduction
# ---------------------------------------------------------------------------

class TestSampledDeterminism:
    def test_streams_invariant_under_join_order(self, model, reference):
        """Each stream's tokens depend only on ITS (seed, prompt,
        sampler) — not on which neighbors shared the batch or the
        admission order: both orders serve the reference's streams."""
        prompts = [_prompt(n, seed=56) for n in (8, 11, 6, 9, 7)]
        cfgs = [dict(SAMPLERS[i % len(SAMPLERS)]) for i in range(5)]
        want = [reference.serve(p, 8, **c)[0]
                for p, c in zip(prompts, cfgs)]
        fwd, e1 = _run_streams(model, prompts, cfgs)
        rev, e2 = _run_streams(model, list(reversed(prompts)),
                               list(reversed(cfgs)))
        assert fwd == want == list(reversed(rev))
        assert e1.stats()["decode_compiles"] == 1
        assert e2.stats()["decode_compiles"] == 1

    def test_preempt_resume_replays_not_rerolls(self, model, reference):
        """A deliberately tight pool forces eviction of sampled streams;
        the re-prefilled stream continues from restored positions, so the
        draws replay byte-identically: the streams of requests that were
        never preempted (the reference's)."""
        prompts = [_prompt(n, seed=57) for n in (11, 12, 10, 5)]
        cfgs = [dict(temperature=0.9, top_k=16, top_p=0.9,
                     seed=2000 + i) for i in range(4)]
        tight = LLMEngine(model, max_batch_size=3, block_size=4,
                          num_blocks=10, watermark_blocks=1)
        got = [tight.add_request(p, max_new_tokens=10, **c)
               for p, c in zip(prompts, cfgs)]
        tight.run()
        st = tight.stats()
        assert st["evictions"] >= 1                  # the pool actually bit
        assert st["decode_compiles"] == 1
        reference.assert_served(got)

    @each_sampler
    def test_crash_resume_replays_sampled_streams(self, model, reference,
                                                  sampler):
        """state_payload() serializes the sampler identity; a FRESH
        engine restoring mid-flight sampled streams finishes them with
        the final tokens of an uninterrupted run (the reference's)."""
        prompts = [_prompt(n, seed=59) for n in (11, 6, 9)]
        cfgs = [stream_of(sampler, i) for i in range(3)]
        clean = [reference.serve(p, 10, **c)[0]
                 for p, c in zip(prompts, cfgs)]
        eng = LLMEngine(model, max_batch_size=2, block_size=4)
        for i, (p, c) in enumerate(zip(prompts, cfgs)):
            eng.add_request(p, max_new_tokens=10, request_id=f"s{i}", **c)
        for _ in range(5):
            eng.step()                               # mid-flight
        payload = eng.state_payload()
        assert payload["requests"]
        eng2 = LLMEngine(model, max_batch_size=2, block_size=4)
        restored = eng2.restore_state(payload)
        assert len(restored) >= 2
        assert any(r.generated for r in restored)    # resumed mid-stream
        eng2.run()
        by_rid = {r.rid: r for r in restored}
        for i, ref in enumerate(clean):
            rid = f"s{i}"
            if rid in by_rid:
                assert by_rid[rid].state == FINISHED
                assert list(by_rid[rid].generated) == ref


# ---------------------------------------------------------------------------
# per-token logprobs
# ---------------------------------------------------------------------------

class TestLogprobs:
    def test_logprob_panels_ride_the_one_compile(self, model):
        prompts = [_prompt(n, seed=60) for n in (8, 10)]
        eng = LLMEngine(model, max_batch_size=2, block_size=4,
                        logprobs_topk=2)
        greedy = eng.add_request(prompts[0], max_new_tokens=6)
        hot = eng.add_request(prompts[1], max_new_tokens=6,
                              temperature=0.9, top_k=16, seed=61)
        eng.run()
        assert eng.stats()["decode_compiles"] == 1
        for r in (greedy, hot):
            lp = r.logprobs()
            assert set(lp) == {"token_logprobs", "topk_ids",
                               "topk_logprobs"}
            assert len(lp["token_logprobs"]) == len(r.generated) == 6
            for v in lp["token_logprobs"]:
                assert v is not None and np.isfinite(v) and v <= 1e-6
            for ids, lps in zip(lp["topk_ids"], lp["topk_logprobs"]):
                assert len(ids) == 2 and len(lps) == 2
                assert lps[0] >= lps[1] - 1e-7       # sorted panel
        # the greedy stream's chosen token IS the top-1 alternative, and
        # the two logprob views agree bit-for-bit
        glp = greedy.logprobs()
        for tok, chosen, ids, lps in zip(greedy.generated,
                                         glp["token_logprobs"],
                                         glp["topk_ids"],
                                         glp["topk_logprobs"]):
            assert ids[0] == tok
            assert lps[0] == pytest.approx(chosen, abs=1e-6)

    def test_default_engine_keeps_alt_panels_off(self, model):
        eng = LLMEngine(model, max_batch_size=2, block_size=4)
        req = eng.add_request(_prompt(7, seed=62), max_new_tokens=4,
                              temperature=0.8, seed=63)
        eng.run()
        lp = req.logprobs()
        assert len(lp["token_logprobs"]) == 4
        assert all(a is None for a in lp["topk_ids"])
        assert all(a is None for a in lp["topk_logprobs"])


# ---------------------------------------------------------------------------
# software-pipelined decode
# ---------------------------------------------------------------------------

class TestPipelined:
    def test_launch_hands_the_program_copies_of_its_host_buffers(
            self, model):
        """A launch is asynchronous and the engine edits `_lens`,
        `_tables` and the sampler buffers in place right after it: an
        argument that shares their memory lets a slow dispatch read the
        NEXT step's values (under load the sampled streams then differ
        from run to run — what failed the mixed-batch test below on a
        busy box). Every host array the program is given is a copy."""
        eng = LLMEngine(model, max_batch_size=4, block_size=4)
        eng.add_request(_prompt(9, seed=64), max_new_tokens=8,
                        temperature=0.7, seed=11)
        mine = [a for a in vars(eng).values() if isinstance(a, np.ndarray)]
        seen, call = [], eng._call_decode

        def spy(args):
            seen.extend(a for a in args if isinstance(a, np.ndarray))
            return call(args)

        eng._call_decode = spy
        eng.run()
        assert seen, "no host array reached the decode program"
        for a in seen:
            assert not any(np.shares_memory(a, m) for m in mine)

    def test_a_mixed_batch_serves_the_reference(self, model, reference):
        """Launching N+1 before N is committed changes WHEN tokens are
        committed, never WHICH tokens: mixed greedy+sampled streams are
        the reference's, with one compile, and the clean drain needs
        zero rollbacks."""
        prompts = [_prompt(n, seed=64) for n in (9, 6, 11, 7, 8)]
        cfgs = [dict(SAMPLERS[i % len(SAMPLERS)]) for i in range(5)]
        piped, eng = _run_streams(model, prompts, cfgs)
        assert piped == [reference.serve(p, 8, **c)[0]
                         for p, c in zip(prompts, cfgs)]
        assert eng.stats()["decode_compiles"] == 1
        assert eng.stats()["commit_rollbacks"] == 0

    @pytest.mark.parametrize("mix", ["greedy", "seeded", "mixed"])
    def test_backlog_streams_are_the_references(self, model, reference,
                                                mix):
        """The benchmark's backlog cell in small: every slot full,
        requests of mixed prompt buckets finishing AND joining at every
        boundary, so every launch mixes device-fed slots with
        host-authored ones. Greedy, seeded and both in one batch: the
        engine serves the reference's streams token for token, with no
        rollback and one decode program."""
        samplers = {"greedy": (dict(),), "seeded": SAMPLERS[1:],
                    "mixed": SAMPLERS}[mix]
        requests, boundaries, eng = backlog.drive(
            model, VOCAB, 48, samplers=samplers)
        backlog.assert_steady(boundaries)
        st = eng.stats()
        assert st["decode_compiles"] == 1
        assert st["commit_rollbacks"] == 0
        hot = sum(r.max_new_tokens for r in requests if r.temperature > 0)
        assert st["sampled_tokens"] == hot
        assert (hot > 0) == (mix != "greedy")
        assert len(requests) >= 100
        reference.assert_served(requests)

    @each_sampler
    def test_commit_lag_cancel_rolls_back_not_leaks(self, model, reference,
                                                    sampler):
        """Cancel lands between launch N+1 and its commit: the launched
        token for the cancelled slot is rolled back (never appended),
        the rollback is attributed, and the surviving streams finish as
        the reference's; what the cancelled stream was served before is
        the head of its own."""
        prompts = [_prompt(n, seed=65) for n in (10, 8, 9)]
        eng = LLMEngine(model, max_batch_size=4, block_size=4)
        reqs = [eng.add_request(p, max_new_tokens=10,
                                **stream_of(sampler, i))
                for i, p in enumerate(prompts)]
        for _ in range(4):
            eng.step()                   # an uncommitted launch in flight
        victim = reqs[1]
        n_before = len(victim.generated)
        assert 0 < n_before < 10
        eng.cancel(victim.rid)
        eng.run()
        st = eng.stats()
        assert victim.state == CANCELLED
        assert len(victim.generated) == n_before     # nothing leaked
        assert victim.generated == reference.owed(victim)[0][:n_before]
        assert st["commit_rollbacks"] >= 1
        assert st["decode_compiles"] == 1
        reference.assert_served([reqs[0], reqs[2]])
