"""The benchmark's backlog cell in small, for the serving tests: every
slot of one engine kept full by a queue that is topped up before every
`step()`, with requests finishing and joining at every boundary. Request
i is the same prompt, length and sampler in every run."""
from __future__ import annotations

import numpy as np

from paddle_tpu.serving import FINISHED, LLMEngine

from serving_reference import stream_of

SLOTS = 8
QUEUE_DEPTH = 4
# prompt lengths over the prefill buckets 8, 16 and 32
PROMPT_LENGTHS = (5, 12, 27, 9, 19, 7, 30, 14)
# the first tenants leave at different boundaries, so the slots never
# turn over in step with one another
FIRST_OUTPUTS = (2, 3, 4, 5)
OUTPUTS = 4


def request_of(i, vocab, samplers):
    """(prompt, max_new_tokens, sampler keywords) of request i."""
    rng = np.random.default_rng(7000 + i % 16)
    prompt = rng.integers(
        0, vocab, PROMPT_LENGTHS[i % len(PROMPT_LENGTHS)]).tolist()
    want = FIRST_OUTPUTS[i % len(FIRST_OUTPUTS)] if i < SLOTS else OUTPUTS
    return prompt, want, stream_of(samplers[i % len(samplers)], i)


def top_up(engine, requests, vocab, samplers=({},)):
    """Keep the queue `QUEUE_DEPTH` deep with the next requests."""
    while len(engine.scheduler.waiting) < QUEUE_DEPTH:
        prompt, want, sampler = request_of(len(requests), vocab, samplers)
        requests.append(engine.add_request(prompt, max_new_tokens=want,
                                           **sampler))


def drive(model, vocab, steps, samplers=({},), on_launch=None, **engine_kw):
    """`steps` boundaries of a standing backlog, then the drain. Returns
    (requests in arrival order, one record a boundary, engine). A record
    is (joined, finished, slots held when the step's decode launched)."""
    engine = LLMEngine(model, max_batch_size=SLOTS, block_size=4,
                       **engine_kw)
    if on_launch is not None:
        call = engine._call_decode

        def spy(args):
            on_launch(engine, args)
            return call(args)
        engine._call_decode = spy
    requests, boundaries = [], []
    for _ in range(steps):
        top_up(engine, requests, vocab, samplers)
        counters = engine._stats
        admitted, completed = counters.admitted, counters.completed
        engine.step()
        finished = counters.completed - completed
        boundaries.append((counters.admitted - admitted, finished,
                           len(engine.scheduler.running) + finished))
    engine.run()
    assert all(r.state == FINISHED and len(r.generated) == r.max_new_tokens
               for r in requests)
    return requests, boundaries, engine


def assert_steady(boundaries, warm=4, at_least=40):
    """Past the first `warm` boundaries, at every one of at least
    `at_least`: two or more requests joined, two or more finished, and
    the step's decode launched with every slot held."""
    steady = boundaries[warm:]
    assert len(steady) >= at_least
    for joined, finished, held in steady:
        assert joined >= 2 and finished >= 2 and held == SLOTS
