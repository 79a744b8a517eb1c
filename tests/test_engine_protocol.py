"""What crosses between host and device in one program call (PR 43): one
packed int32 array in, one int32 array out; the sampler's table and the
history live on the device, the host's arrays are the record.

  * the counters that say the protocol engaged (`decode_host_arrays`,
    `prefill_host_arrays`, `decode_fetched_arrays`, `state_uploads`);
  * the streams are the ones the engine served BEFORE the protocol
    changed, token for token and logprob bit for bit: a batch in which
    every slot has sampler settings of its own, with a logprob panel,
    under a pool so tight that requests are evicted and resumed, and
    across `state_payload()` -> `restore_state()` into a new engine.
    `fixtures/serving/pr43_parent_streams.json` holds what commit 877c7c8
    served from both loops it had (`serial-*`: launch, wait, commit in
    one step; that loop went in PR 46, and the one loop is held to its
    record too: the same tokens, logprobs to the last bits). The streams
    are also the ones `serving_reference.Reference` works out with no
    engine at all;
  * a slot cleared after a sampled request reads the row that samples
    nothing, though the device's table still holds the request's.
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as paddle
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import LLMEngine, FINISHED

from serving_reference import Reference

VOCAB = 128
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "serving", "pr43_parent_streams.json")

# every slot its own settings: greedy, temperature alone, top k, top p,
# the whole stack with a repetition penalty, and greedy with the other
# knobs set (inert at temperature 0)
SAMPLERS = (
    dict(),
    dict(temperature=0.7, seed=11),
    dict(temperature=1.0, top_k=12, seed=12),
    dict(temperature=0.9, top_p=0.85, seed=13),
    dict(temperature=1.1, top_k=24, top_p=0.9, repetition_penalty=1.3,
         seed=14),
    dict(temperature=0.0, top_k=5, top_p=0.5, repetition_penalty=1.7,
         seed=15),
    dict(temperature=1.3, repetition_penalty=0.8, seed=4000000000),
)
LENGTHS = (11, 12, 10, 5, 9, 7, 13)
NEW_TOKENS = 10
PANEL = 3


def new_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def model():
    return new_model()


def prompt(length, seed=57):
    rng = np.random.default_rng(seed * 1000 + length)
    return rng.integers(0, VOCAB, length).tolist()


def bits(values):
    """float32 values by their bits (None stays None)."""
    return [None if v is None else
            np.asarray(v, np.float32).view(np.int32).tolist()
            for v in values]


def stream_of(req):
    return {"generated": list(req.generated),
            "logprobs": bits(req.token_logprobs),
            "alt_ids": [None if a is None else list(a)
                        for a in req.alt_ids],
            "alt_logprobs": bits(req.alt_logprobs)}


def canary(model):
    """The model's logits for one prompt, by their bits: what the engine
    is not in. Where they differ from the recorded ones the CPU rounds
    differently from the one that recorded, and no stream can be held to
    the record."""
    ids = np.asarray([prompt(9, seed=3)], np.int64)
    logits = model(paddle.Tensor(ids, stop_gradient=True))
    return np.asarray(logits._value, np.float32).view(np.int32) \
        .reshape(-1).tolist()


def tight_engine(model):
    """Three slots over a pool that cannot hold three grown contexts:
    requests are evicted and resume by a second prefill."""
    return LLMEngine(model, max_batch_size=3, block_size=4, num_blocks=12,
                     watermark_blocks=1, logprobs_topk=PANEL)


def serve(model, crash_after=None):
    """The scenario: seven requests, each with settings of its own, through
    a tight pool; with `crash_after`, that many steps, then the snapshot
    into a NEW engine that finishes them. Returns ({rid: stream}, stats of
    the engine that finished, {rid: request})."""
    engine = tight_engine(model)
    for i, (n, sampler) in enumerate(zip(LENGTHS, SAMPLERS)):
        engine.add_request(prompt(n), max_new_tokens=NEW_TOKENS,
                           request_id=f"q{i}", **sampler)
    done = {}
    if crash_after is not None:
        for _ in range(crash_after):
            engine.step()
        payload = json.loads(json.dumps(engine.state_payload()))
        done = {rid: r for rid, r in engine.requests.items() if r.finished}
        evictions = engine.stats()["evictions"]
        engine = tight_engine(model)
        engine.restore_state(payload)
    else:
        evictions = 0
    engine.run()
    done.update(engine.requests)
    assert all(r.state == FINISHED for r in done.values())
    stats = engine.stats()
    stats["evictions"] += evictions
    return ({rid: stream_of(r) for rid, r in sorted(done.items())}, stats,
            done)


SCENARIOS = {"evicted": None, "restored": 6}


@pytest.fixture(scope="module")
def recorded(model):
    with open(RECORDED) as f:
        kept = json.load(f)
    if kept["canary"] != canary(model):
        pytest.skip("this CPU rounds the model's own logits differently "
                    "from the one that recorded the parent's streams")
    return kept["streams"]


def ulps_apart(got, want):
    """The largest distance, in float32 steps, between two streams'
    logprobs (and panels) where both hold one."""
    far = 0
    for key in ("logprobs", "alt_logprobs"):
        for a, b in zip(got[key], want[key]):
            if a is not None and b is not None:
                far = max(far, int(np.abs(np.asarray(a, np.int64)
                                          - np.asarray(b, np.int64)).max()))
    return far


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("recorded_by", ["serial", "pipelined"])
def test_streams_are_the_parents_token_for_token_and_bit_for_bit(
        model, recorded, recorded_by, scenario):
    """The parent's pipelined loop is this engine's: its streams bit
    for bit. The parent's serial loop committed a token a step earlier
    (so a snapshot after six steps holds one logprob more a stream) and
    batched other slots together: its tokens and panels' ids exactly,
    its logprobs to the last bits."""
    streams, stats, _ = serve(model, SCENARIOS[scenario])
    assert stats["evictions"] >= 1              # the pool actually bit
    assert stats["decode_compiles"] == 1
    want = recorded[f"{scenario}-{recorded_by}"]
    assert sorted(streams) == sorted(want)
    for rid in want:
        if recorded_by == "pipelined":
            assert streams[rid] == want[rid], rid
            continue
        assert streams[rid]["generated"] == want[rid]["generated"], rid
        for got, kept in zip(streams[rid]["alt_ids"],
                             want[rid]["alt_ids"]):
            assert got is None or kept is None or got == kept, rid
        assert ulps_apart(streams[rid], want[rid]) <= 2, rid


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_the_scenarios_streams_are_the_references(model, scenario):
    """The same seven streams against no engine at all: every sampler
    of the scenario (a seed over 2^31, a penalty under 1, greedy with
    every other knob set), through evictions and a restore."""
    _, stats, done = serve(model, SCENARIOS[scenario])
    assert stats["evictions"] >= 1
    Reference(model).assert_served(done.values())


def test_both_tails_and_both_scenarios_recorded_the_same_tokens(recorded):
    """The record itself: WHICH tokens are served depends on neither the
    tail nor on a crash (logprobs of tokens committed before a crash are
    not in a snapshot, so only the ids compare)."""
    ids = {name: {rid: s["generated"] for rid, s in streams.items()}
           for name, streams in recorded.items()}
    first = ids["evicted-serial"]
    assert all(other == first for other in ids.values())
    assert len({tuple(s) for s in first.values()}) == len(first)


def test_greedy_slots_of_the_mixed_batch_match_generate(model):
    """The two temperature-0 requests of the scenario, one with every
    other knob set: `model.generate`'s tokens, through evictions."""
    streams, _, _ = serve(model)
    for i in (0, 5):
        ids = np.asarray([prompt(LENGTHS[i])], np.int64)
        out = model.generate(paddle.Tensor(ids), max_new_tokens=NEW_TOKENS,
                             do_sample=False)
        ref = np.asarray(out._value if hasattr(out, "_value") else out)[0]
        assert streams[f"q{i}"]["generated"] == ref.tolist()


# ---------------------------------------------------------------------------
# the counters of the protocol
# ---------------------------------------------------------------------------

def warm(engine):
    """Every program the test will call, called once; the window reset."""
    engine.generate([prompt(5), prompt(9)], max_new_tokens=3)
    engine.reset_stats()


def crossing(engine):
    s = engine.stats()
    return {k: s[k] for k in (
        "decode_launches", "prefills", "decode_host_arrays",
        "prefill_host_arrays", "decode_fetched_arrays", "state_uploads")}


def test_a_call_hands_over_one_host_array_and_fetches_one(model):
    engine = LLMEngine(model, max_batch_size=4, block_size=4,
                       logprobs_topk=PANEL)
    warm(engine)
    reqs = [engine.add_request(prompt(n), max_new_tokens=6, **sampler)
            for n, sampler in zip((6, 11, 7, 5, 9), SAMPLERS)]
    engine.run()
    assert all(r.state == FINISHED for r in reqs)
    c = crossing(engine)
    assert c["prefills"] == 5 and c["decode_launches"] >= 5
    assert c["decode_host_arrays"] == c["decode_launches"]
    assert c["prefill_host_arrays"] == c["prefills"]
    assert c["decode_fetched_arrays"] == c["decode_launches"]
    assert c["state_uploads"] == 0
    # what the programs were handed, by type: one numpy array each
    seen, call = [], engine._call_program

    def spy(name, fn, args, first):
        seen.append((name, [type(a).__module__ for a in args
                            if isinstance(a, (np.ndarray, np.generic))]))
        return call(name, fn, args, first)
    engine._call_program = spy
    engine.generate([prompt(6)], max_new_tokens=3)
    assert {name for name, _ in seen} == {"engine.prefill.dispatch",
                                          "engine.decode.dispatch"}
    assert all(host == ["numpy"] for _, host in seen), seen


def test_a_restore_uploads_the_record_once(model):
    engine = LLMEngine(model, max_batch_size=4, block_size=4)
    warm(engine)
    engine.add_request(prompt(6), max_new_tokens=8, temperature=0.8, seed=5)
    for _ in range(3):
        engine.step()
    before = crossing(engine)
    assert before["state_uploads"] == 0
    payload = engine.state_payload()
    # the same process restores (a fresh request beside the live one)
    payload["requests"] = [dict(payload["requests"][0], rid="again",
                                arrival_seq=99)]
    engine.restore_state(payload)
    engine.run()
    after = crossing(engine)
    assert after["state_uploads"] == 1
    calls = (after["decode_launches"] - before["decode_launches"]
             + after["prefills"] - before["prefills"])
    handed = (after["decode_host_arrays"] - before["decode_host_arrays"]
              + after["prefill_host_arrays"] - before["prefill_host_arrays"])
    assert handed == calls + 2      # the table and the history, once
    assert after["decode_fetched_arrays"] == after["decode_launches"]


def test_a_prefix_hit_uploads_the_record_once(model):
    """A prefix-hit admission edits the record with no prefill behind it:
    the next call is handed the record, and the stream is the one the
    reference works out with no cache at all."""
    shared = prompt(12, seed=9)
    sampler = dict(temperature=0.9, top_k=20, repetition_penalty=1.4,
                   seed=77)
    engine = LLMEngine(model, max_batch_size=4, block_size=4,
                       enable_prefix_cache=True)
    engine.generate([shared + [1, 2]], max_new_tokens=3)
    engine.generate([prompt(5)], max_new_tokens=2)
    engine.reset_stats()
    hit = engine.add_request(shared + [3, 4], max_new_tokens=6, **sampler)
    engine.run()
    st = engine.stats()
    assert st["prefix_hit_tokens"] > 0 and st["prefills"] == 0
    assert st["state_uploads"] == 1
    assert st["decode_host_arrays"] == st["decode_launches"] + 2
    Reference(model).assert_served([hit])


def test_the_device_holds_what_the_record_holds(model):
    """At rest, the device's table and history agree with the host's
    record in every active slot's row up to its length."""
    engine = LLMEngine(model, max_batch_size=4, block_size=4)
    reqs = [engine.add_request(prompt(n), max_new_tokens=12, **sampler)
            for n, sampler in zip((6, 11, 7), SAMPLERS[2:5])]
    for _ in range(5):
        engine.step()
    engine._flush_inflight()
    table, history = map(np.asarray, engine._sampler_dev)
    for req in reqs:
        slot = req.slot
        assert table[slot].tolist() == engine._sampler_row(
            req.temperature, req.top_k, req.top_p, req.repetition_penalty,
            req.seed).tolist()
        n = int(engine._lens[slot])
        assert n == len(req.prompt) + len(req.generated) - 1
        np.testing.assert_array_equal(history[slot, :n],
                                      engine._history[slot, :n])
        assert history[slot, :n].tolist() == (req.prompt + req.generated)[:n]
    engine.run()


@pytest.mark.parametrize("tenant", [False, True], ids=["baked", "aux"])
def test_the_programs_donate_the_caches_buffers_and_nothing_else(model,
                                                                 tenant):
    """Donation is real only on a TPU, so no CPU run would notice a
    program that donated the history where a pool stands: the argument
    numbers each builder asks `_donated` for are where `_decode_args` /
    `_prefill_args` put the cache's buffers, in both program families."""
    engine = LLMEngine(model, max_batch_size=4, block_size=4,
                       hot_swap=tenant)
    asked, donated = [], engine._donated

    def spy(first):
        asked.append(first)
        return donated(first)
    engine._donated = spy
    req = engine.add_request(prompt(6), max_new_tokens=3)
    seen, call = {}, engine._call_program

    def spy_call(name, fn, args, first):
        seen.setdefault(name, args)
        return call(name, fn, args, first)
    engine._call_program = spy_call
    engine.run()
    assert req.state == FINISHED

    def where(args):
        first = [i for i, a in enumerate(args) if a is args[-1]][0] \
            - len(engine._bufs) + 1
        assert len(args) == first + len(engine._bufs)
        return first
    assert asked == [where(seen["engine.prefill.dispatch"]),
                     where(seen["engine.decode.dispatch"])]
    assert asked == ([6, 5] if tenant else [5, 4])


# ---------------------------------------------------------------------------
# a cleared slot
# ---------------------------------------------------------------------------

def test_a_cleared_slot_reads_the_row_that_samples_nothing(model,
                                                           monkeypatch):
    """A sampled request leaves; a greedy one goes on. Nothing is uploaded
    for the clear, the device's table still holds the departed request's
    row, and every launch behind it hands `sample_tokens` temperature 0
    in every slot: the batch is back on the greedy branch."""
    import jax
    import paddle_tpu.serving.engine as engine_mod
    seen = []
    real = engine_mod.sample_tokens

    def watched(logits, temperature, *rest, **kw):
        if logits.shape[0] > 1:               # a launch, not a prefill
            jax.debug.callback(
                lambda t: seen.append(np.asarray(t).copy()), temperature,
                ordered=True)
        return real(logits, temperature, *rest, **kw)
    monkeypatch.setattr(engine_mod, "sample_tokens", watched)
    engine = LLMEngine(model, max_batch_size=4, block_size=4)
    sampled = engine.add_request(prompt(6), max_new_tokens=3,
                                 temperature=0.9, top_k=7, seed=21)
    greedy = engine.add_request(prompt(9), max_new_tokens=12)
    engine.run()
    jax.effects_barrier()
    assert sampled.state == FINISHED and greedy.state == FINISHED
    hot = [bool((t > 0).any()) for t in seen]
    assert hot[0] and not hot[-1]
    # once the sampled request has left, no launch sees its temperature
    assert hot == sorted(hot, reverse=True)
    assert hot.count(False) >= 8
    table = np.asarray(engine._sampler_dev[0]).view(np.float32)
    assert (table[:, 0] > 0).any()            # its row is still there
    assert not engine._temps.any()            # the record says cleared
    assert engine.stats()["state_uploads"] == 1     # the first call's
    out = model.generate(paddle.Tensor(np.asarray([prompt(9)], np.int64)),
                         max_new_tokens=12, do_sample=False)
    ref = np.asarray(out._value if hasattr(out, "_value") else out)[0]
    assert greedy.generated == ref.tolist()
