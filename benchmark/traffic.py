"""The one general traffic generator. A mix is a data file of parameters
(`traffic/<name>.json`); nothing here knows a cell by name.

Serving mixes draw prompt and output lengths from a clipped log-normal.
The lengths are a FIXED multiset: quantiles of the distribution at evenly
spaced levels (stratified), so every seed offers the same work and only
shuffles which request gets which pair, and draws the token ids."""
from __future__ import annotations

import math
import statistics

from . import seeded

_NORMAL = statistics.NormalDist()


def stratified_lengths(spec, count):
    """`count` lengths: the quantiles of a log-normal with the given
    median and sigma at levels (i + 0.5) / count, clipped to [lo, hi]."""
    mu = math.log(spec["median"])
    out = []
    for i in range(count):
        z = _NORMAL.inv_cdf((i + 0.5) / count)
        out.append(int(min(spec["hi"], max(spec["lo"], round(
            math.exp(mu + spec["sigma"] * z))))))
    return out


def length_pairs(mix, count, rng):
    """`count` (prompt, output) pairs: both multisets fixed by `count`,
    paired by a seeded shuffle of the outputs."""
    prompts = stratified_lengths(mix["prompt_tokens"], count)
    outputs = stratified_lengths(mix["output_tokens"], count)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(prompts, outputs))


def filler_requests(mix, seed, vocab, stream):
    """An endless supply of requests of the mix, for warm traffic and a
    backlog's queue: blocks of `block` stratified pairs, each block
    shuffled."""
    rng = seeded.host_rng(seed, stream)
    block = mix.get("block", 128)
    while True:
        for p, o in length_pairs(mix, block, rng):
            yield seeded.token_ids(rng, p, vocab), o
