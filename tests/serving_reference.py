"""What an `LLMEngine` must serve, worked out without one: a request at a
time through the model's own dense forward over the growing context. No
engine, no paged cache, no compiled decode program, no batch: a greedy
request takes the argmax of the raw logits (`model.generate(do_sample=
False)`), a seeded one `serving.sampling.sample_tokens` on those logits,
with the context so far as the repetition penalty's history, at the key
the engine derives, ``fold_in(seed, position)``, position being the count
of tokens in the context. The sampler has unit tests of its own
(tests/test_sampling.py); what this is independent of is everything
between a request and its logits: admission, paging, the packed call,
feedback on the device, the lag-1 commit, eviction, resume, restore."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.autograd import set_grad_enabled
from paddle_tpu.framework.core import Tensor
from paddle_tpu.serving.sampling import sample_tokens

# the sampler configurations the stream tests run under: greedy,
# temperature alone, with top-k, with top-p, and the whole stack
SAMPLERS = (
    dict(),
    dict(temperature=0.7, seed=11),
    dict(temperature=1.0, top_k=12, seed=12),
    dict(temperature=0.9, top_p=0.85, seed=13),
    dict(temperature=1.1, top_k=24, top_p=0.9, repetition_penalty=1.3,
         seed=14),
)
each_sampler = pytest.mark.parametrize(
    "sampler", SAMPLERS,
    ids=("greedy", "temperature", "top_k", "top_p", "penalty"))

_sample = jax.jit(sample_tokens)


def stream_of(sampler, i):
    """`sampler` for stream `i` of a batch: where it has a seed, one of
    the stream's own."""
    return dict(sampler, seed=sampler["seed"] + i) if "seed" in sampler \
        else dict(sampler)


class Reference:
    """`serve(prompt, n, ...)` -> ``(tokens, logprobs)`` of one request.

    The forward runs at ONE width (contexts are padded on the right, and
    the logits read at the last real token: attention is causal), so it
    compiles once a model; the weights are its arguments and nothing is
    remembered between calls, so a model whose values changed (a hot
    swap, an adapter folded in) needs no new reference."""

    def __init__(self, model, width=64):
        self.model, self.width = model, width
        params = model.parameters()

        def last_logits(values, ids, n):
            saved = [p._value for p in params]
            for p, v in zip(params, values):
                p._value = v
            try:
                with set_grad_enabled(False):
                    logits = model(Tensor(ids, stop_gradient=True))
            finally:
                for p, v in zip(params, saved):
                    p._value = v
            return logits._value[0, n - 1].astype(jnp.float32)

        self._last_logits = jax.jit(last_logits)

    def logits(self, context):
        """The logits after `context`, ``[vocabulary]`` float32."""
        assert 0 < len(context) <= self.width, len(context)
        ids = np.zeros((1, self.width), np.int32)
        ids[0, :len(context)] = context
        return np.asarray(self._last_logits(
            [p._value for p in self.model.parameters()], ids,
            np.int32(len(context))))

    def serve(self, prompt, max_new_tokens, temperature=0.0, top_k=0,
              top_p=1.0, repetition_penalty=1.0, seed=0,
              eos_token_id=None):
        """The tokens and their logprobs (of the raw distribution) that
        the request is owed."""
        context, logprobs = list(prompt), []
        while len(context) - len(prompt) < max_new_tokens:
            logits = self.logits(context)
            if temperature > 0:
                history = np.zeros((1, self.width), np.int32)
                history[0, :len(context)] = context
                token = int(_sample(
                    logits[None], np.float32([temperature]),
                    np.int32([top_k]), np.float32([top_p]),
                    np.float32([repetition_penalty]),
                    np.uint32([seed & 0xFFFFFFFF]),
                    np.int32([len(context)]), history,
                    np.arange(self.width)[None] < len(context))[0][0])
            else:
                token = int(np.argmax(logits))
            shifted = logits.astype(np.float64) - logits.max()
            logprobs.append(float(
                shifted[token] - np.log(np.exp(shifted).sum())))
            context.append(token)
            if token == eos_token_id:
                break
        return context[len(prompt):], logprobs

    def owed(self, req):
        """`serve` for a `Request` handle, from what it was asked."""
        return self.serve(
            req.prompt, req.max_new_tokens, req.temperature, req.top_k,
            req.top_p, req.repetition_penalty, req.seed or 0,
            req.eos_token_id)

    def assert_served(self, requests):
        """Every finished request of `requests` holds the tokens it is
        owed, and (where the engine kept one: a resumed or chewed token
        has none) each token's logprob."""
        for req in requests:
            tokens, lps = self.owed(req)
            assert list(req.generated) == tokens, (req.rid, req.generated,
                                                   tokens)
            for got, want in zip(req.token_logprobs, lps):
                assert got is None or abs(got - want) < 2e-4, \
                    (req.rid, got, want)
